"""The part of ``gen_idle_decode_ms_per_forward`` whose innermost span is
``lm.fetch``: the device is done, its results are on their way to the
host, and nothing is queued behind them. What choosing on the device, or
one packed output, would take off a block forward."""

from benchmarks import call_spans


def read(facts):
    return call_spans.decode_idle_ms_per_forward(facts, innermost="lm.fetch")
