"""Median duration of the engine's ``gen.denoise_step`` spans over the
window's calls: one denoising forward of the block loop (gather, the jitted
call, the copy of ``x0`` and its confidence back, the schedule's choice),
nothing stored. The spans are real only in a traced run, whose runner puts
a parent span around every call and keeps each call's records."""

import statistics


def read(facts):
    seconds = (facts.get("block_span_seconds") or {}).get("gen.denoise_step")
    if not seconds:
        return None
    return 1e3 * statistics.median(seconds)
