"""Bytes a decode step ships from the host to the device, counted where
they cross: the ``h2d_bytes`` attribute of ``lm.dispatch``, the ``nbytes``
of every argument of the jitted call that is a host array at the call (the
K and V pools today, beside tokens, lengths and tables; a pool that lives on
the device counts 0). Median over the window's decode steps."""

from benchmarks import span_metrics


def read(facts):
    return span_metrics.median_per_step(facts, ("lm.dispatch",), "h2d_bytes")
