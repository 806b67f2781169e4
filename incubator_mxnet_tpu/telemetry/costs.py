"""Per-executable FLOPs/bytes accounting and MFU (hardware truth).

XLA's compiled executables report their static cost via
``jitted.lower(...).compile().cost_analysis()`` — total FLOPs and bytes
accessed for one execution.  ``capture()`` records that once per
executable name; ``observe()`` then turns each timed execution into
achieved-TFLOP/s, tokens/sec, and ``model_flops_utilization`` (MFU =
achieved FLOP/s over the ``MXTPU_PEAK_TFLOPS`` roofline) gauges.

Capture sites (ShardedTrainer.step/step_scan, the serving forward) are
gated behind ``MXTPU_COSTS=1`` because capture lowers and compiles a
second, non-donating executable purely for accounting.  ``observe()``
is one predicate check when telemetry is off and a dict miss when
nothing was captured, so it rides inside the existing hot-path
telemetry blocks.

Roofline defaults are TPU v5e bf16: 197 TFLOP/s, 819 GB/s — override
with ``MXTPU_PEAK_TFLOPS`` / ``MXTPU_PEAK_GBS`` per accelerator.
"""

import os
import threading

from . import metrics as _m
from . import catalog as _cat

__all__ = ["capture_enabled", "normalize", "cost_of", "capture",
           "captured", "observe", "mfu", "peak_flops", "peak_bytes",
           "reset"]

_lock = threading.Lock()
_captured = {}


def capture_enabled():
    """True when cost capture (an extra lower+compile) is opted in."""
    return os.environ.get("MXTPU_COSTS", "0") == "1"


def peak_flops():
    """Roofline peak in FLOP/s (MXTPU_PEAK_TFLOPS, default v5e bf16)."""
    try:
        return float(os.environ.get("MXTPU_PEAK_TFLOPS", "197")) * 1e12
    except ValueError:
        return 197e12


def peak_bytes():
    """Roofline HBM bandwidth in bytes/s (MXTPU_PEAK_GBS)."""
    try:
        return float(os.environ.get("MXTPU_PEAK_GBS", "819")) * 1e9
    except ValueError:
        return 819e9


def normalize(cost_analysis):
    """Flatten a ``Compiled.cost_analysis()`` result (dict, or a
    one-element list of dicts on some jax versions) to
    ``{"flops": float, "bytes": float}``."""
    ca = cost_analysis
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if ca is None:
        ca = {}
    return {"flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes": float(ca.get("bytes accessed", 0.0) or 0.0)}


def cost_of(compiled):
    """Static cost of a ``jax.stages.Compiled`` executable."""
    return normalize(compiled.cost_analysis())


def capture(name, compiled=None, cost=None, samples_per_exec=None):
    """Record the static cost of one executable run under ``name``.

    Pass either a compiled executable or a pre-normalized ``cost``
    dict.  Returns the stored entry.
    """
    c = dict(cost) if cost is not None else cost_of(compiled)
    entry = {"flops": c.get("flops", 0.0), "bytes": c.get("bytes", 0.0),
             "samples": samples_per_exec}
    with _lock:
        _captured[name] = entry
    if _m._state["enabled"]:
        _cat.model_flops_per_exec.set(entry["flops"], name=name)
        _cat.model_bytes_per_exec.set(entry["bytes"], name=name)
    return entry


def captured(name=None):
    with _lock:
        if name is not None:
            ent = _captured.get(name)
            return dict(ent) if ent else None
        return {k: dict(v) for k, v in _captured.items()}


def reset():
    with _lock:
        _captured.clear()


def observe(name, seconds, execs=1):
    """Fold one timed execution window into the achieved/MFU gauges.
    One predicate check when telemetry is off; a dict miss when
    ``name`` was never captured."""
    if not _m._state["enabled"]:
        return
    with _lock:
        ent = _captured.get(name)
    if ent is None or seconds <= 0:
        return
    achieved = ent["flops"] * execs / seconds
    _cat.model_achieved_tflops.set(achieved / 1e12, name=name)
    _cat.model_flops_utilization.set(achieved / peak_flops(), name=name)
    if ent["samples"]:
        _cat.model_tokens_per_sec.set(ent["samples"] * execs / seconds,
                                      name=name)


def mfu(flops, seconds, execs=1):
    """Model FLOPs utilization: fraction of roofline peak achieved by
    running ``execs`` executions of ``flops`` FLOPs in ``seconds``."""
    if seconds <= 0:
        return 0.0
    return flops * execs / seconds / peak_flops()
