"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest device."""

from benchmarks.metrics_common import peak_hbm_bytes


def read(facts):
    return peak_hbm_bytes(facts)
