"""Host milliseconds a step spends handing the program to the device: mean
duration of the trainer's ``trainer.dispatch`` spans (the key and step
scalars made eagerly, the jitted call, the retrace retry), over the traced
steps. Read under the profiler, which slows the host."""

from benchmarks import span_metrics


def read(facts):
    return span_metrics.mean_ms(span_metrics.named(
        span_metrics.records(facts), "trainer.dispatch"))
