"""What the readers of the program's own spans share.

A record is one dict of ``tracing.recent_spans()``: ``name``, ``ts_us``,
``dur_us``, ``span_id``, ``parent_id`` (none on a root) and the span's
attributes (``h2d_bytes`` on ``lm.dispatch``, ...). Everything but
``records`` and ``decode_steps`` takes plain records, so the arithmetic is
tested on hand-made ones (``tests/benchmark/test_benchmark_span_metrics.py``).

The program opens these spans only while something listens: the generation
runner's traced run puts a parent span around each call of its window, and
inside a profiler session (the traced steps of a training run) every
``span()`` is real. An untraced run records nothing, and a reader looks at
the ring only when the run was traced: the ring belongs to the process, and
a test's worker may hold another test's records.
"""

import collections
import statistics

from . import trace_reduce

DECODE_STEP = "gen.decode_step"


def records(facts):
    """The ring's records, oldest first; [] for a run that was not traced."""
    if not facts.get("trace"):
        return []
    from incubator_mxnet_tpu.telemetry import tracing
    return tracing.recent_spans()


def named(recs, name):
    return [r for r in recs if r["name"] == name]


def children_by_parent(recs):
    """{span id: its direct children, in the ring's order}"""
    out = collections.defaultdict(list)
    for r in recs:
        if r.get("parent_id") is not None:
            out[r["parent_id"]].append(r)
    return out


def self_us(rec, children):
    """A span's duration minus what its direct `children` cover of it
    (their union, cut to the span: two children that overlap count once)."""
    lo, hi = rec["ts_us"], rec["ts_us"] + rec["dur_us"]
    covered = trace_reduce.merge(
        (max(c["ts_us"], lo), min(c["ts_us"] + c["dur_us"], hi))
        for c in children if c["ts_us"] < hi and c["ts_us"] + c["dur_us"] > lo)
    return rec["dur_us"] - trace_reduce.length(covered)


def sums_per_parent(parents, children, names, key="dur_us"):
    """For each of `parents`, the sum of `key` over its direct children
    called one of `names`; a parent with no such child gives 0."""
    return [sum(c.get(key, 0) for c in children.get(p["span_id"], ())
                if c["name"] in names) for p in parents]


def mean_ms(recs):
    """Mean duration of `recs` in milliseconds, None for none."""
    if not recs:
        return None
    return sum(r["dur_us"] for r in recs) / len(recs) / 1e3


def decode_steps(facts):
    """-> (the window's decode steps, {span id: children}), or (None, None)
    where the program has no spans below a decode step (a program from
    before they were added: its steps would read as all self time).

    The window's steps are the ones ``decode_step_p50_ms`` counts: the
    runner clears the ring when the window opens, so they are the first
    ``len(facts["decode_step_seconds"])`` step records; the traced call's
    steps come after them and run under the profiler. A ring that
    overflowed (a call leaves 197 records, the ring holds 4,096) has lost
    its oldest records, a step's children before the step: the runner's
    count is then of the surviving steps too, and at most the first of
    them has lost children and reads them as self time, which a median
    does not follow."""
    recs = records(facts)
    steps = named(recs, DECODE_STEP)
    counted = len(facts.get("decode_step_seconds") or ())
    if counted:
        steps = steps[:counted]
    children = children_by_parent(recs)
    if not any(children.get(s["span_id"]) for s in steps):
        return None, None
    return steps, children


def median_per_step(facts, names, key="dur_us"):
    """Median over the window's decode steps of the summed `key` of each
    step's children called one of `names`; None where nothing was read."""
    steps, children = decode_steps(facts)
    if not steps or not any(c["name"] in names
                            for s in steps for c in children[s["span_id"]]):
        return None
    return statistics.median(sums_per_parent(steps, children, names, key))


def median_ms_per_step(facts, names):
    """`median_per_step` of the spans' durations, in milliseconds."""
    us = median_per_step(facts, names)
    return None if us is None else us / 1e3
