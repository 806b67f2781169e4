"""Host milliseconds a decode step waits for the device and copies the
logits and the new keys and values back (``lm.fetch``: the ``np.asarray``
reads in ``GPTPagedLM.forward``). Median over the window's decode steps."""

from benchmarks import span_metrics


def read(facts):
    return span_metrics.median_ms_per_step(facts, ("lm.fetch",))
