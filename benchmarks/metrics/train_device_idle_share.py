"""Share of the traced window in which no operation ran, mean over the
cell's devices."""

from benchmarks.metrics_common import idle_share


def read(facts):
    return idle_share(facts)
