"""The table of peaks, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, not a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s. "TPU v5 lite" is what
    # jax.devices()[0].device_kind reads on that chip (chip run, PR 22).
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no peaks recorded for device kind %r: add it to "
                       "benchmarks/peaks.py with its source" % device_kind)
