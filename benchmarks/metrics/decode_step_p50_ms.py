"""Median duration of the engine's ``gen.decode_step`` spans over the
window's decode steps."""

import statistics


def read(facts):
    steps = facts.get("decode_step_seconds")
    if not steps:
        return None
    return 1e3 * statistics.median(steps)
