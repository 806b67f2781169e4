"""The traced ``generate`` call's own spans, whole and on the trace's clock.

The program keeps every record that finished under a root span together
with that root (``telemetry/tracing.py``: a journey), whatever the ring of
finished spans has lost since. The runners put a root span called
``bench.generate_call`` around every call of a traced run, so the newest
journey of that name is the traced call: ``traced_call``.

The records carry the host's wall clock, the device operations the
profiler's. Both saw the call begin and end: the runner enters its
``TraceAnnotation`` and then the root span, which takes its wall time and
then enters an annotation of its own, and they leave in the opposite
order. So the trace holds TWO events called ``bench.generate_call``, the
runner's around the span's, and the record's wall start lies between their
starts, its wall end between their ends: ``clock_pair`` puts the record's
start midway between the events' starts and says how wide that bracket is
and how far the record's end then lies from the middle of the ends'. No
``wall_at_window_start``.

``idle_by_span`` puts every stretch in which the busiest device ran
nothing, over the traced window, down to the INNERMOST record of the
journey that covers it (``trace_reduce.idle_gaps``'s rule: the shortest
covering span), in one sweep over sorted gaps and spans. The
``gen_idle_*`` readers under ``metrics/`` sum its stretches by the regions
of ``GenerateEngine.generate``.

A run that was not traced, a program that keeps no journeys, a journey
that passed its cap and a run with no device plane all read nothing.
"""

import bisect
import collections
import heapq
import statistics
import sys

from . import trace_reduce

CALL_SPAN = "bench.generate_call"
DECODE_REGIONS = ("gen.decode_step", "gen.block")
REGIONS = ("gen.prefill",) + DECODE_REGIONS
NO_SPAN = "no_span"


def traced_call(facts):
    """The records of the traced call, its root ``bench.generate_call``
    last, or None: the run was not traced, the program kept no such
    journey, or the journey's root says it left records out."""
    if not facts.get("trace"):
        return None
    from incubator_mxnet_tpu.telemetry import tracing
    recent = getattr(tracing, "recent_journeys", None)
    journeys = recent(CALL_SPAN) if recent else []    # a program without
    if not journeys or journeys[-1][-1].get("journey_dropped"):
        return None
    return journeys[-1]


def clock_pair(facts, root):
    """How `root` (the journey's root record, on the wall clock) lies
    among the trace's own events of the call: ``{"offset_ns": what a
    record's wall nanoseconds take to be on the trace's clock,
    "start_bracket_ns", "end_bracket_ns": how far apart the events' starts
    and ends are (the root's lie between them), "end_off_ns": the root's
    end, moved over, less the middle of the events' ends}``; None where the
    trace holds no such event."""
    events = [ev for ev in facts["trace"]["spans"]
              if ev.name == CALL_SPAN and ev.plane != "program"]
    if not events:
        return None
    outer = max(events, key=lambda ev: ev.dur_ns)
    inner = min(events, key=lambda ev: ev.dur_ns)
    start_ns, dur_ns = root["ts_us"] * 1e3, root["dur_us"] * 1e3
    offset = (outer.start_ns + inner.start_ns) / 2 - start_ns
    ends = (inner.start_ns + inner.dur_ns, outer.start_ns + outer.dur_ns)
    return {"offset_ns": offset,
            "start_bracket_ns": inner.start_ns - outer.start_ns,
            "end_bracket_ns": ends[1] - ends[0],
            "end_off_ns": start_ns + dur_ns + offset - sum(ends) / 2}


def sweep(gaps, spans):
    """{key: nanoseconds} of the sorted, disjoint `gaps` ((start, end))
    by the innermost of `spans` ((start, end, key)) over each stretch, None
    where no span covers it. Innermost as ``trace_reduce.idle_gaps`` has
    it: of the spans that cover the stretch the shortest, the first given
    among equals. One pass: the spans that have begun wait in a heap by
    length, and one that has ended is dropped when it comes to the top."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    cuts = sorted({t for span in spans for t in span[:2]})
    total = collections.Counter()
    begun, upcoming = [], 0         # heap of (length, index given, end)
    for a, b in gaps:
        at = bisect.bisect_right(cuts, a)
        lo = a
        while lo < b:
            hi = cuts[at] if at < len(cuts) and cuts[at] < b else b
            at += 1
            while upcoming < len(order) and spans[order[upcoming]][0] <= lo:
                start, end, _key = spans[order[upcoming]]
                heapq.heappush(begun, (end - start, order[upcoming], end))
                upcoming += 1
            while begun and begun[0][2] <= lo:
                heapq.heappop(begun)
            total[spans[begun[0][1]][2] if begun else None] += hi - lo
            lo = hi
    return total


def idle_by_span(facts):
    """The busiest device's idle time over the traced window, by the
    innermost record of the traced call: ``{"records": the journey,
    "by_id": {span id: record}, "idle_s": {index into records, or None
    under no span: seconds}, "pair": clock_pair's, "call_s": the root's
    seconds}``, or None where there is nothing to read. Computed once a
    run and kept in `facts`."""
    if "call_idle" not in facts:
        facts["call_idle"] = _idle_by_span(facts)
    return facts["call_idle"]


def _idle_by_span(facts):
    records = traced_call(facts)
    if records is None or not facts["trace"]["ops"]:   # a CPU has no plane
        return None
    pair = clock_pair(facts, records[-1])
    if pair is None:
        return None
    window = facts["trace"]["window"]
    ops = max(facts["trace"]["ops"].values(),
              key=lambda ops: trace_reduce.busy_seconds(ops, window))
    gaps = trace_reduce.subtract(
        [window], [(ev.start_ns, ev.start_ns + ev.dur_ns) for ev in ops])
    # the records' wall microseconds, less the root's before they are made
    # nanoseconds: a double holds today's date to a quarter microsecond
    zero_us = records[-1]["ts_us"]
    zero_ns = zero_us * 1e3 + pair["offset_ns"]
    spans = [(zero_ns + (r["ts_us"] - zero_us) * 1e3,
              zero_ns + (r["ts_us"] - zero_us + r["dur_us"]) * 1e3, i)
             for i, r in enumerate(records)]
    idle = sweep(gaps, spans)
    return {"records": records, "pair": pair,
            "by_id": {r["span_id"]: r for r in records},
            "idle_s": {key: ns / 1e9 for key, ns in idle.items()},
            "call_s": records[-1]["dur_us"] / 1e6}


def region_of(rec, by_id, names):
    """The nearest of `rec` and its ancestors called one of `names`, or
    None (`rec` None: a stretch under no span)."""
    while rec is not None and rec["name"] not in names:
        rec = by_id.get(rec.get("parent_id"))
    return rec


def idle_under(idle, names, innermost=None):
    """Idle seconds whose innermost record is, or lies under, a span
    called one of `names`; with `innermost` only those whose innermost
    record is so called."""
    records = idle["records"]
    return sum(seconds for i, seconds in idle["idle_s"].items()
               if i is not None
               and (innermost is None or records[i]["name"] == innermost)
               and region_of(records[i], idle["by_id"], names) is not None)


def count_under(idle, name, names):
    """How many records called `name` lie under a span called one of
    `names` (or are one)."""
    return sum(1 for r in idle["records"] if r["name"] == name
               and region_of(r, idle["by_id"], names) is not None)


def decode_idle_ms_per_forward(facts, innermost=None):
    """Idle milliseconds under the traced call's decode regions (with
    `innermost`, the part whose innermost span is so called) over the
    ``lm.dispatch`` records under them: a step's one forward, a block's
    denoising and store forwards. None where there is nothing to read."""
    idle = idle_by_span(facts)
    forwards = idle and count_under(idle, "lm.dispatch", DECODE_REGIONS)
    if not forwards:
        return None
    return 1e3 * idle_under(idle, DECODE_REGIONS, innermost) / forwards


def idle_per_region(idle, names):
    """Idle seconds of each record called one of `names`, in the journey's
    order: what lies under it, its children's included."""
    records = idle["records"]
    total = {r["span_id"]: 0.0 for r in records if r["name"] in names}
    for i, seconds in idle["idle_s"].items():
        region = None if i is None else region_of(records[i], idle["by_id"],
                                                  names)
        if region is not None:
            total[region["span_id"]] += seconds
    return list(total.values())


def by_name(idle):
    """[[span name, idle seconds], ...], the largest first."""
    total = collections.Counter()
    for i, seconds in idle["idle_s"].items():
        total[NO_SPAN if i is None else idle["records"][i]["name"]] += seconds
    return [[name, seconds] for name, seconds in total.most_common()]


def note_table(idle):
    """The whole table on standard error, as the runners write their
    notes: the call's idle seconds by innermost span, and how well the
    two clocks agree at the call's two ends."""
    print("benchmark: traced call of %.3f s, %d records; device idle %.4f s "
          "of the traced window by innermost span: %s"
          % (idle["call_s"], len(idle["records"]),
             sum(idle["idle_s"].values()),
             " ".join("%s=%.4f" % (n, s) for n, s in by_name(idle))),
          file=sys.stderr)
    for names in (("gen.prefill",), DECODE_REGIONS):
        each = sorted(idle_per_region(idle, names))
        if each:
            print("benchmark: %d %s of the traced call, idle ms each: "
                  "median %.3f, the largest %s, %d over 1 ms"
                  % (len(each), "/".join(names),
                     1e3 * statistics.median(each),
                     " ".join("%.3f" % (1e3 * x) for x in each[-3:]),
                     sum(x > 1e-3 for x in each)), file=sys.stderr)
    print("benchmark: clock pair (us): the call's two events start %.1f "
          "apart and end %.1f apart; the root record's end lies %+.1f from "
          "the middle of their ends"
          % tuple(idle["pair"][k] / 1e3 for k in (
              "start_bracket_ns", "end_bracket_ns", "end_off_ns")),
          file=sys.stderr, flush=True)
