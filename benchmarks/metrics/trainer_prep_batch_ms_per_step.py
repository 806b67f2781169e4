"""Host milliseconds a step spends placing its batch: mean duration of the
trainer's ``trainer.prep_batch`` spans (``ShardedTrainer._prep_batch``: the
arrays made device arrays and laid out on the mesh), over the traced steps.
Read under the profiler, which slows the host; ``train_host_ms_per_step``
times the whole call from outside without it."""

from benchmarks import span_metrics


def read(facts):
    return span_metrics.mean_ms(span_metrics.named(
        span_metrics.records(facts), "trainer.prep_batch"))
