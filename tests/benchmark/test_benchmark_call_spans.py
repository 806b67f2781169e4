"""``benchmarks/call_spans.py`` and the readers over it, on the CPU: the
clock pair on hand-made events, the sweep against ``trace_reduce.idle_gaps``
(its oracle: the same rule, every span tried against every gap), each
``gen_idle_*`` reader and ``moe_decode_experts_hit_per_layer`` on hand-made
facts, a journey that is missing or passed its cap, and the three
generation runners' toy cells through ``drive`` with the ring of finished
spans far smaller than a call (``BENCHMARK_call_spans_tiny.json``): the
traced call is still read whole. A CPU has no device plane, so those runs
are handed three made-up device operations inside the traced window.
"""

import os
import random

import pytest

from benchmarks import call_spans, run, spec, trace_reduce
from benchmarks.trace_reduce import Event
from incubator_mxnet_tpu.telemetry import tracing

ROOT = spec.ROOT
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 37         # the driver's seeds pass 32 signed bits
DEVICE = "/device:TPU:0"
NEW = ("gen_idle_prefill_ms_per_prompt", "gen_idle_decode_ms_per_forward",
       "gen_idle_fetch_tail_ms_per_forward",
       "gen_idle_outside_regions_share")


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def op(start_s, end_s, name="fusion.1"):
    return Event(DEVICE, "XLA Ops", name, start_s * 1e9,
                 (end_s - start_s) * 1e9)


# ------------------------------------------------------- hand-made facts
WALL = 1.79e9       # the records' wall seconds; the trace's clock starts at 0


def rec(name, start_s, end_s, span_id, parent_id=None, **attrs):
    out = {"name": name, "ts_us": (WALL + start_s) * 1e6,
           "dur_us": (end_s - start_s) * 1e6, "trace_id": "t",
           "span_id": span_id}
    if parent_id is not None:
        out["parent_id"] = parent_id
    out.update(attrs)
    return out


def hand_made_call():
    """A call of 10 s on the trace's clock from 1 s on: two prompts, two
    decode steps, the engine's own spans around them; root last."""
    return [
        rec("gen.admit", 1.001, 1.002, "admit", "call"),
        rec("lm.dispatch", 1.10, 1.20, "p0d", "p0"),
        rec("lm.fetch", 1.50, 2.00, "p0f", "p0"),
        rec("gen.prefill", 1.05, 2.00, "p0", "call"),
        rec("lm.dispatch", 2.10, 2.20, "p1d", "p1"),
        rec("lm.fetch", 2.50, 3.00, "p1f", "p1"),
        rec("kv.sync", 3.00, 4.00, "p1s", "p1"),
        rec("gen.prefill", 2.05, 4.00, "p1", "call"),
        rec("lm.dispatch", 4.10, 4.20, "s0d", "s0"),
        rec("lm.fetch", 4.20, 6.00, "s0f", "s0"),
        rec("gen.decode_step", 4.05, 6.00, "s0", "call"),
        rec("lm.dispatch", 6.10, 6.20, "s1d", "s1"),
        rec("lm.fetch", 6.20, 9.00, "s1f", "s1"),
        rec("gen.decode_step", 6.00, 9.00, "s1", "call"),
        rec("gen.release", 10.0, 10.5, "release", "call"),
        rec("gen.call", 1.0005, 10.9, "call", "root"),
        rec("bench.generate_call", 1.0, 11.0, "root"),
    ]


def hand_made_facts(ops, events=None):
    """The window 0.5–11.5 s; the call's two events in the trace bracket
    the root record's start and end by 20 us."""
    events = events or [
        Event("/host:CPU", "python", "bench.generate_call",
              0.99999e9, 10.00002e9),
        Event("/host:CPU", "python", "bench.generate_call",
              1.00001e9, 9.99998e9),
        Event("program", "telemetry", "gen.prefill", 1.05e9, 0.95e9)]
    return {"trace": {"ops": {DEVICE: ops}, "window": (0.5e9, 11.5e9),
                      "spans": events}}


@pytest.fixture
def kept(monkeypatch):
    """Hands ``traced_call`` a journey without running anything."""
    def keep(records):
        monkeypatch.setattr(
            tracing, "recent_journeys",
            lambda root_name=None: [records] if records else [])
    return keep


# a device that idles 0.5–1.5 (the window's start, admission, the first
# prompt's launch), 1.8–2.6 (the first prompt's drain, the second's
# launch and the start of its drain), 3.5–4.15 (the wait for the last
# commit, the first step's launch), 5–5.5 (inside a step's fetch) and
# from 8.5 on (a fetch's tail, the call's own time, the release, the
# window's end): 5.95 s
BUSY = [op(1.5, 1.8), op(2.6, 3.5), op(4.15, 5.0), op(5.5, 8.5)]


def test_the_pair_puts_the_root_between_the_calls_two_events():
    facts = hand_made_facts(BUSY)
    root = hand_made_call()[-1]
    pair = call_spans.clock_pair(facts, root)
    # the root's wall start maps to the middle of the two events' starts
    assert root["ts_us"] * 1e3 + pair["offset_ns"] == pytest.approx(1e9)
    assert pair["start_bracket_ns"] == pytest.approx(20e3)
    assert pair["end_bracket_ns"] == pytest.approx(20e3)
    assert abs(pair["end_off_ns"]) < 1e3        # a double at today's date
    # a root that ran 30 us longer than the events say: its end is off
    late = dict(root, dur_us=root["dur_us"] + 30)
    assert call_spans.clock_pair(facts, late)["end_off_ns"] \
        == pytest.approx(30e3, abs=1e3)
    # one event of the call (no annotation of the span's own): no bracket
    alone = hand_made_facts(BUSY, [facts["trace"]["spans"][0]])
    pair = call_spans.clock_pair(alone, root)
    assert pair["start_bracket_ns"] == 0 == pair["end_bracket_ns"]
    assert root["ts_us"] * 1e3 + pair["offset_ns"] \
        == pytest.approx(0.99999e9)
    # the program's own records in the trace's list are not the pair
    assert call_spans.clock_pair(
        hand_made_facts(BUSY, [facts["trace"]["spans"][2]]), root) is None


def _as_events(spans):
    return [Event("program", "telemetry", key, a, b - a)
            for a, b, key in spans]


def _both_ways(ops, spans, window):
    busy = trace_reduce.merge(
        (max(ev.start_ns, window[0]),
         min(ev.start_ns + ev.dur_ns, window[1])) for ev in ops)
    swept = call_spans.sweep(trace_reduce.subtract([window], busy), spans)
    oracle = trace_reduce.idle_gaps(ops, _as_events(spans), window,
                                    top=10 ** 6)
    return ({("no_span" if k is None else k): v / 1e9
             for k, v in swept.items() if v}, dict(oracle))


def test_the_sweep_on_nested_spans_by_hand():
    spans = [(0, 100, "root"), (10, 60, "a"), (20, 30, "a.x"),
             (40, 70, "late"),        # overlaps `a` without nesting
             (80, 90, "b"), (80, 90, "b.twin")]    # equals: the first given
    ops = [Event(DEVICE, "XLA Ops", "op", 25, 20), Event(
        DEVICE, "XLA Ops", "op", 85, 100)]
    swept, oracle = _both_ways(ops, spans, (-10, 120))
    assert swept == pytest.approx(oracle)
    # idle: -10–25 and 45–85
    assert swept == pytest.approx({
        "no_span": 10e-9, "root": (10 + 10) * 1e-9, "a": 10e-9,
        "a.x": 5e-9, "late": 25e-9, "b": 5e-9})


@pytest.mark.parametrize("seed", range(6))
def test_the_sweep_agrees_with_idle_gaps_on_random_events(seed):
    rng = random.Random(seed)
    spans = []
    for i in range(60):         # on a grid, so that edges and lengths tie
        a = rng.randrange(0, 900, 10)
        spans.append((float(a), float(a + rng.randrange(0, 300, 10)),
                      "s%d" % (i % 7)))
    ops = []
    for _ in range(40):
        a = rng.randrange(-50, 1000, 5)
        ops.append(Event(DEVICE, "XLA Ops", "op", float(a),
                         float(rng.randrange(5, 60, 5))))
    swept, oracle = _both_ways(ops, spans, (-20.0, 1100.0))
    assert swept == pytest.approx(oracle)
    assert sum(swept.values()) == pytest.approx(
        (1120.0 - trace_reduce.busy_seconds(ops, (-20.0, 1100.0)) * 1e9)
        / 1e9)


def test_each_reader_on_a_hand_made_call(kept, capsys):
    kept(hand_made_call())
    facts = hand_made_facts(BUSY)
    idle = call_spans.idle_by_span(facts)
    assert facts["call_idle"] is idle           # once a run
    table = dict(call_spans.by_name(idle))
    assert table == pytest.approx({
        "no_span": 0.5 + 0.5, "bench.generate_call": 0.0005 + 0.1,
        "gen.call": 0.0005 + 0.048 + 0.05 + 0.05 + 1.0 + 0.4,
        "gen.admit": 0.001, "gen.release": 0.5,
        "gen.prefill": 0.05 + 0.3 + 0.05 + 0.3, "gen.decode_step": 0.05,
        "lm.dispatch": 0.1 + 0.1 + 0.05,
        "lm.fetch": 0.2 + 0.1 + 0.5 + 0.5, "kv.sync": 0.5}, abs=1e-6)
    # what the table sums to is the device's idle time over the window
    assert sum(table.values()) == pytest.approx(
        11.0 - trace_reduce.busy_seconds(BUSY, facts["trace"]["window"]))
    # under the two prefills: 1.05–1.5, 1.8–2.0; 2.05–2.6, 3.5–4.0
    assert reader(NEW[0])(facts) == pytest.approx(
        1e3 * (0.45 + 0.2 + 0.55 + 0.5) / 2)
    # under the two steps: 4.05–4.15, 5–5.5; 8.5–9
    assert reader(NEW[1])(facts) == pytest.approx(1e3 * 1.1 / 2)
    # of that inside a fetch: 5–5.5 and 8.5–9
    assert reader(NEW[2])(facts) == pytest.approx(1e3 * 1.0 / 2)
    # outside the regions: the rest of the window's 5.95 s, of a 10 s call
    assert reader(NEW[3])(facts) == pytest.approx(
        100 * (5.95 - 1.7 - 1.1) / 10)
    err = capsys.readouterr().err
    assert "by innermost span: gen.call=1.5485 lm.fetch=1.3000" in err
    assert "start 20.0 apart and end 20.0 apart" in err


def test_a_block_loops_forwards_are_counted_under_their_block(kept):
    records = [
        rec("lm.dispatch", 1.1, 1.2, "d0d", "d0"),
        rec("lm.fetch", 1.2, 1.9, "d0f", "d0"),
        rec("gen.denoise_step", 1.1, 2.0, "d0", "b0"),
        rec("lm.dispatch", 2.1, 2.2, "k0d", "k0"),
        rec("lm.fetch", 2.3, 2.9, "k0f", "k0"),
        rec("gen.block_store", 2.0, 3.0, "k0", "b0"),
        rec("gen.block", 1.0, 3.0, "b0", "root"),
        rec("bench.generate_call", 1.0, 11.0, "root")]
    kept(records)
    facts = hand_made_facts([op(0.5, 1.5), op(1.7, 2.6), op(3.0, 11.5)])
    # idle 1.5–1.7 and 2.6–3.0, both inside a fetch but 2.9–3.0
    assert reader(NEW[1])(facts) == pytest.approx(1e3 * 0.6 / 2)
    assert reader(NEW[2])(facts) == pytest.approx(1e3 * 0.5 / 2)
    assert reader(NEW[0])(facts) is None        # no prompt was prefilled
    assert reader(NEW[3])(facts) == pytest.approx(0.0)


def test_a_call_that_is_not_whole_reads_nothing(kept, monkeypatch):
    facts = hand_made_facts(BUSY)
    kept(None)                                  # no journey kept
    assert call_spans.traced_call(facts) is None
    assert [reader(n)(dict(facts)) for n in NEW] == [None] * 4
    torso = hand_made_call()
    torso[-1]["journey_dropped"] = 3            # the cap left records out
    kept(torso)
    assert [reader(n)(dict(facts)) for n in NEW] == [None] * 4
    kept(hand_made_call())
    assert call_spans.traced_call({"trace": None}) is None   # not traced
    no_plane = {"trace": dict(facts["trace"], ops={})}       # a CPU
    assert [reader(n)(dict(no_plane)) for n in NEW] == [None] * 4
    assert reader(NEW[0])(dict(facts)) is not None
    # a program from before journeys were kept
    monkeypatch.delattr(tracing, "recent_journeys")
    assert [reader(n)(dict(facts)) for n in NEW] == [None] * 4


def test_the_decode_experts_reader_on_hand_made_facts():
    read = reader("moe_decode_experts_hit_per_layer")
    moe = {"forwards": 12, "experts_hit": 500, "by_phase": {
        "prefill": {"forwards": 4, "experts_hit": 380},
        "decode": {"forwards": 8, "experts_hit": 120}}}
    cfg = {"num_hidden_layers": 6, "first_k_dense_replace": 1}
    assert read({"traced_moe": moe, "config": cfg}) == 120 / 8 / 5
    # the block runner says how many layers hold experts
    assert read({"traced_moe": dict(moe, layers=3), "config": cfg}) \
        == 120 / 8 / 3
    assert read({"config": cfg}) is None
    assert read({"traced_moe": {"forwards": 12, "experts_hit": 500},
                 "config": cfg}) is None       # a program without phases


# ------------------------------------------------- toy cells through drive
@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(
        ROOT, "tests", "benchmark", "BENCHMARK_call_spans_tiny.json"))


@pytest.fixture
def small_ring():
    size = tracing._finished.maxlen
    tracing._resize(32)
    yield 32
    tracing._resize(size)


@pytest.fixture
def a_device_plane(monkeypatch):
    """Three made-up device operations inside the traced window, so that
    the ``device_trace`` readers have something to read on a CPU; the
    facts of the run are kept for the test."""
    seen = {}
    reduce = run.Tracer.reduce

    def with_ops(self, program_spans=()):
        trace = reduce(self, program_spans)
        lo, hi = trace["window"]
        at = lambda share: lo + share * (hi - lo)      # noqa: E731
        trace["ops"] = {DEVICE: [
            Event(DEVICE, "XLA Ops", "fusion.%d" % i, at(a), at(b) - at(a))
            for i, (a, b) in enumerate([(0.05, 0.3), (0.35, 0.6),
                                        (0.7, 0.95)])]}
        seen["trace"] = trace
        return trace
    monkeypatch.setattr(run.Tracer, "reduce", with_ops)
    return seen


@pytest.mark.parametrize("cell,expert_layers", [
    ("gpt2_tiny.generate_tiny", 0),
    ("sdar_tiny.generate_blocks_tiny", 1),
    ("kimi_tiny.generate_wide_tiny", 1)], ids=["plain", "blocks", "share"])
def test_a_traced_toy_cell_finds_its_call_whole_in_a_ring_too_small(
        bench, cell, expert_layers, small_ring, a_device_plane, tmp_path):
    import jax
    for name in ("benchmarks", "tests"):    # a trace directory of its own
        os.symlink(os.path.join(ROOT, name), str(tmp_path / name))
    result = run.drive(bench, cell, SEED, 0.3, True, jax.devices(),
                       root=str(tmp_path), peaks=PEAKS)
    assert result["correct"] is True
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(values)
    assert ("moe_decode_experts_hit_per_layer" in values) \
        == bool(expert_layers)
    if expert_layers:
        assert values["moe_decode_experts_hit_per_layer"] >= 1
    # the ring lost most of the call; the journey none of it
    journey = call_spans.traced_call({"trace": a_device_plane["trace"]})
    assert len(tracing.recent_spans()) <= small_ring < len(journey)
    names = [r["name"] for r in journey]
    assert names[-1] == "bench.generate_call" and "journey_dropped" \
        not in journey[-1]
    assert names.count("gen.call") == names.count("gen.admit") \
        == names.count("gen.release") == 1
    traffic = spec.load_cell(bench, cell, ROOT)[2]
    assert names.count("gen.prefill") == len(traffic["prompt_lens"])
    steps = names.count("gen.decode_step") + names.count("gen.block")
    assert steps and names.count("lm.dispatch") > steps
    by_id = {r["span_id"]: r for r in journey}
    assert all(r["parent_id"] in by_id for r in journey[:-1])
    # the table sums to the made-up device's idle time over the window
    idle = call_spans.idle_by_span({"trace": a_device_plane["trace"]})
    lo, hi = a_device_plane["trace"]["window"]
    assert sum(idle["idle_s"].values()) == pytest.approx(
        0.25 * (hi - lo) / 1e9, rel=1e-6)
    # the clocks: the root record lies inside the call's own two events,
    # which the span's annotation and the runner's leave microseconds apart
    pair = idle["pair"]
    assert 0 < pair["start_bracket_ns"] < 5e6
    assert 0 < pair["end_bracket_ns"] < 5e6
    assert abs(pair["end_off_ns"]) < 5e6
    assert 0 <= values["gen_idle_outside_regions_share"] <= 100
    assert values["gen_idle_fetch_tail_ms_per_forward"] \
        <= values["gen_idle_decode_ms_per_forward"]


def test_a_cpu_run_reports_none_of_the_device_readers(bench, tmp_path):
    import jax
    for name in ("benchmarks", "tests"):
        os.symlink(os.path.join(ROOT, name), str(tmp_path / name))
    result = run.drive(bench, "gpt2_tiny.generate_tiny", SEED, 0.3, True,
                       jax.devices(), root=str(tmp_path), peaks=PEAKS)
    assert set(result["metrics"]) == {"prefill_share"}


def test_the_real_benchmark_lists_the_new_readers_for_the_generation_cells():
    real = spec.load_benchmark()
    generation = [w["name"] for w in real["workloads"]
                  if "generate" in w["traffic"]]
    assert len(generation) >= 4
    for name in NEW:
        entry = spec.find(real["per_layer"], name, "metric")
        assert entry["workloads"] == generation[:len(entry["workloads"])]
        assert (entry["source"], entry["layer"], entry["better"],
                entry["moves"]) == ("device_trace", "generate", "lower",
                                    "gen_tokens_per_s_per_chip")
        assert callable(reader(name))
    entry = spec.find(real["per_layer"], "moe_decode_experts_hit_per_layer",
                      "metric")
    assert entry["workloads"] == [
        "sdar_30b_a3b.generate_blocks",
        "xing4_29b_a4b.generate_long_prompts",
        "kimi_k2_7_code.generate_wide_batch"]
    assert entry["source"] == "program_counter"
