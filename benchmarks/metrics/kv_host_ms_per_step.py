"""Host milliseconds a decode step spends in the paged cache: ``kv.gather``
(lengths, block tables, the pools handed to the forward) plus ``kv.commit``
(the appends of the new keys and values). Median over the window's decode
steps."""

from benchmarks import span_metrics


def read(facts):
    return span_metrics.median_ms_per_step(facts, ("kv.gather", "kv.commit"))
