"""Host milliseconds a window's closing takes: the median
``gen.window_close`` span of the traced call (its launch that pools the
window's rows into summaries, their commit to the summary group, the
window restarted), under ``gen.prefill`` and ``gen.decode_step`` alike.
The records are the call's kept journey (``benchmarks/call_spans.py``); a
program without the span reads nothing."""

import statistics

from benchmarks import call_spans


def read(facts):
    closings = [r["dur_us"] for r in call_spans.traced_call(facts) or ()
                if r["name"] == "gen.window_close"]
    if not closings:
        return None
    return statistics.median(closings) / 1e3
