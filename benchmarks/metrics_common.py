"""What several per-layer readers share."""

from . import trace_reduce


def idle_share(facts):
    trace = facts.get("trace")
    if not trace or not trace["ops"]:
        return None
    lo, hi = trace["window"]
    busy = trace_reduce.mean_busy_seconds(trace["ops"], (lo, hi))
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))


def peak_hbm_bytes(facts):
    return facts.get("memory_peak_bytes") or None


def kernel_events(facts, matches):
    """The traced window's operations whose name `matches`, on the first
    device that has any; [] where there is no trace or no such event."""
    trace = facts.get("trace")
    if not trace:
        return []
    lo, hi = trace["window"]
    for plane in sorted(trace["ops"]):
        picked = [ev for ev in trace["ops"][plane]
                  if lo <= ev.start_ns < hi and matches(ev.name)]
        if picked:
            return picked
    return []
