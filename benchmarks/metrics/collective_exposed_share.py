"""Share of the traced window in which a collective ran on a device and
nothing else did; the device where that share is largest."""

from benchmarks import trace_reduce


def read(facts):
    trace = facts.get("trace")
    if not trace or not trace["ops"]:
        return None
    lo, hi = trace["window"]
    exposed = [trace_reduce.collective_exposed_seconds(ops, (lo, hi))
               for ops in trace["ops"].values()]
    if not any(trace_reduce.is_collective(ev.name)
               for ops in trace["ops"].values() for ev in ops):
        return None
    return 100.0 * max(exposed) / ((hi - lo) / 1e9)
