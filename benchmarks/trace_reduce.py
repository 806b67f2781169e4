"""The reduction from a profiler trace to numbers.

Works on plain ``Event`` tuples, so that the arithmetic is tested on a
hand-made set of events (``tests/benchmark/test_benchmark_trace_reduce.py``);
``read_xplane`` is the only part that touches a trace file. All times on
an event are nanoseconds on the trace's own clock, which host threads and
devices share.
"""

import collections
import glob
import os
import re

Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def is_collective(name):
    return bool(COLLECTIVE.match(short_name(name)))


def read_xplane(trace_dir):
    """Every event of the newest ``*.xplane.pb`` under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no xplane.pb under %s" % trace_dir)
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


def device_ops(events):
    """{device plane: its operation events}, sorted by start."""
    out = collections.defaultdict(list)
    for ev in events:
        if DEVICE_PLANE.match(ev.plane) and ev.line == OPS_LINE:
            out[ev.plane].append(ev)
    return {p: sorted(v, key=lambda e: e.start_ns) for p, v in out.items()}


def host_spans(events, names):
    """Host events whose name is one of `names` (the benchmark's own
    ``TraceAnnotation``s), sorted by start."""
    return sorted((ev for ev in events if ev.name in names
                   and not DEVICE_PLANE.match(ev.plane)),
                  key=lambda e: e.start_ns)


def window_of(events, name):
    """(start, end) of the first host event called `name`."""
    spans = host_spans(events, {name})
    if not spans:
        return None
    return spans[0].start_ns, spans[0].start_ns + spans[0].dur_ns


def _clip(events, window):
    lo, hi = window
    for ev in events:
        a, b = max(ev.start_ns, lo), min(ev.start_ns + ev.dur_ns, hi)
        if b > a:
            yield a, b


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of the disjoint, sorted `intervals` that no `holes` cover;
    one sweep over both."""
    out = []
    holes = merge(holes)
    first = 0
    for a, b in intervals:
        while first < len(holes) and holes[first][1] <= a:
            first += 1
        cur, i = a, first
        while i < len(holes) and holes[i][0] < b:
            if holes[i][0] > cur:
                out.append((cur, holes[i][0]))
            cur = max(cur, holes[i][1])
            i += 1
        if cur < b:
            out.append((cur, b))
    return out


def busy_seconds(ops, window):
    """Seconds of `window` in which an operation ran on the device."""
    return length(merge(_clip(ops, window))) / 1e9


def mean_busy_seconds(ops_by_plane, window):
    """Busy seconds of `window`, mean over the devices."""
    busy = [busy_seconds(ops, window) for ops in ops_by_plane.values()]
    return sum(busy) / len(busy)


def idle_gaps(ops, spans, window, top=10):
    """The idle time of one device inside `window`, by what the host was
    doing: every stretch of a gap goes to the innermost (shortest) host
    span that covers it, "no_span" where none does; stretches of one span
    name are summed, the `top` largest sums are returned as
    [[name, seconds], ...]."""
    busy = merge(_clip(ops, window))
    by_name = collections.Counter()
    for a, b in subtract([window], busy):
        inside = [sp for sp in spans
                  if sp.start_ns < b and sp.start_ns + sp.dur_ns > a]
        cuts = sorted({a, b} | {t for sp in inside
                                for t in (sp.start_ns, sp.start_ns + sp.dur_ns)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            covering = [sp for sp in inside if sp.start_ns <= lo
                        and sp.start_ns + sp.dur_ns >= hi]
            name = min(covering, key=lambda sp: sp.dur_ns).name \
                if covering else "no_span"
            by_name[name] += (hi - lo) / 1e9
    return [[n, s] for n, s in by_name.most_common(top)]


def short_name(name):
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12': this JAX names
    a device event by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(ops, window, top=10):
    """[[name, seconds], ...] of the operations that took most time."""
    total = collections.Counter()
    lo, hi = window
    for ev in ops:
        if lo <= ev.start_ns < hi:
            total[short_name(ev.name)] += ev.dur_ns / 1e9
    return [[n, s] for n, s in total.most_common(top)]


def seconds_matching(ops, window, matches):
    """(summed seconds, count) of the events for which matches(name)."""
    lo, hi = window
    picked = [ev for ev in ops
              if lo <= ev.start_ns < hi and matches(ev.name)]
    return sum(ev.dur_ns for ev in picked) / 1e9, len(picked)


def collective_exposed_seconds(ops, window):
    """Seconds of `window` in which a collective ran on the device and
    nothing else did."""
    coll = [ev for ev in ops if is_collective(ev.name)]
    rest = [ev for ev in ops if not is_collective(ev.name)]
    exposed = subtract(merge(_clip(coll, window)), list(_clip(rest, window)))
    return length(exposed) / 1e9
