"""Host milliseconds a decode step spends inside the model's jitted call
(``lm.dispatch`` in ``GPTPagedLM.forward``): arguments that are host arrays
are shipped to the device there. Median over the window's decode steps."""

from benchmarks import span_metrics


def read(facts):
    return span_metrics.median_ms_per_step(facts, ("lm.dispatch",))
