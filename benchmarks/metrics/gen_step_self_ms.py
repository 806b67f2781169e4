"""What a decode step spends outside its forward's phases: median over the
window's ``gen.decode_step`` spans of the step's duration minus what its
children (``kv.gather``, ``lm.dispatch``, ``lm.fetch``, ``kv.commit``)
cover: building the token row, sampling, bookkeeping."""

import statistics

from benchmarks import span_metrics


def read(facts):
    steps, children = span_metrics.decode_steps(facts)
    if not steps:
        return None
    return statistics.median(
        span_metrics.self_us(s, children[s["span_id"]]) for s in steps) / 1e3
