"""The readers of the program's own spans, on hand-made records and events;
then the harness on the CPU with the new entries, which has to print none
of the new metrics as a number it did not read.

One call with two decode steps, times in us:
  step A  1_000..1_100   kv.gather 1_000..1_010, lm.dispatch 1_010..1_040
                         (h2d 500), lm.fetch 1_040..1_090, kv.commit
                         1_090..1_096   -> self 4
  step B  2_000..2_200   lm.dispatch 2_000..2_060 (h2d 700), lm.fetch
                         2_050..2_150 (overlaps dispatch by 10)  -> self 50
"""

import os

import pytest

from benchmarks import run, span_metrics, spec
from benchmarks.trace_reduce import Event, OPS_LINE

ROOT = spec.ROOT
DEV = "/device:TPU:0"
NEW = ["trainer_prep_batch_ms_per_step", "trainer_dispatch_ms_per_step",
       "optim_path_device_ms_per_step", "gen_step_self_ms",
       "lm_dispatch_ms_per_step", "lm_fetch_wait_ms_per_step",
       "kv_host_ms_per_step", "kv_h2d_bytes_per_step"]


def rec(name, ts, dur, span_id, parent_id=None, **attrs):
    out = {"name": name, "ts_us": float(ts), "dur_us": float(dur),
           "trace_id": "t", "span_id": span_id}
    if parent_id is not None:
        out["parent_id"] = parent_id
    out.update(attrs)
    return out


CALL = [
    rec("kv.gather", 1_000, 10, "a1", "A"),
    rec("lm.dispatch", 1_010, 30, "a2", "A", h2d_bytes=500),
    rec("lm.fetch", 1_040, 50, "a3", "A"),
    rec("kv.commit", 1_090, 6, "a4", "A"),
    rec("gen.decode_step", 1_000, 100, "A", "call"),
    rec("lm.dispatch", 2_000, 60, "b1", "B", h2d_bytes=700),
    rec("lm.fetch", 2_050, 100, "b2", "B"),
    rec("gen.decode_step", 2_000, 200, "B", "call"),
    rec("bench.generate_call", 900, 1_400, "call"),
]


@pytest.fixture
def ring(monkeypatch):
    """Put hand-made records where the readers look."""
    from incubator_mxnet_tpu.telemetry import tracing

    def fill(records):
        monkeypatch.setattr(tracing, "recent_spans", lambda n=None: records)
    return fill


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def traced(**facts):
    return dict({"trace": {"ops": {}, "window": (0.0, 1.0), "spans": []}},
                **facts)


# ------------------------------------------------------------------ helper
def test_self_time_is_the_span_less_the_union_of_its_children():
    children = span_metrics.children_by_parent(CALL)
    a, b = span_metrics.named(CALL, "gen.decode_step")
    assert span_metrics.self_us(a, children["A"]) == pytest.approx(4)
    # dispatch 2_000..2_060 and fetch 2_050..2_150 cover 150, not 160
    assert span_metrics.self_us(b, children["B"]) == pytest.approx(50)


def test_a_step_with_no_children_is_all_self_time():
    lone = rec("gen.decode_step", 0, 70, "L")
    assert span_metrics.self_us(lone, []) == 70
    assert span_metrics.sums_per_parent([lone], {}, ("lm.fetch",)) == [0]


def test_a_child_that_runs_past_its_parent_is_cut_to_it():
    parent = rec("p", 100, 50, "P")
    late = rec("c", 140, 40, "c1", "P")        # 140..180 of 100..150
    assert span_metrics.self_us(parent, [late]) == pytest.approx(40)


def test_sums_go_to_each_parent_by_name_and_by_attribute():
    children = span_metrics.children_by_parent(CALL)
    steps = span_metrics.named(CALL, "gen.decode_step")
    assert span_metrics.sums_per_parent(
        steps, children, ("kv.gather", "kv.commit")) == [16, 0]
    assert span_metrics.sums_per_parent(
        steps, children, ("lm.dispatch",), "h2d_bytes") == [500, 700]


def test_mean_of_no_records_is_nothing():
    assert span_metrics.mean_ms([]) is None
    assert span_metrics.mean_ms([rec("x", 0, 1_000, "1"),
                                 rec("x", 9, 3_000, "2")]) == 2.0


# ----------------------------------------------------------------- readers
def test_the_generation_readers_take_the_median_over_the_windows_steps(ring):
    ring(CALL)
    facts = traced()
    assert reader("gen_step_self_ms")(facts) == pytest.approx(0.027)
    assert reader("lm_dispatch_ms_per_step")(facts) == pytest.approx(0.045)
    assert reader("lm_fetch_wait_ms_per_step")(facts) == pytest.approx(0.075)
    assert reader("kv_host_ms_per_step")(facts) == pytest.approx(0.008)
    assert reader("kv_h2d_bytes_per_step")(facts) == 600
    # the runner counted one step in its window: the traced call's step B,
    # which comes after it in the ring, is left out
    facts = traced(decode_step_seconds=[1e-4])
    assert reader("gen_step_self_ms")(facts) == pytest.approx(0.004)
    assert reader("kv_h2d_bytes_per_step")(facts) == 500


def test_a_program_without_the_child_spans_reads_nothing(ring):
    # the parent commit: decode steps under the call's span, nothing below
    ring([r for r in CALL if r["name"] in ("gen.decode_step",
                                           "bench.generate_call")])
    for name in NEW:
        assert reader(name)(traced()) is None, name
    # a model adapter of another kind: cache spans, no lm.* below the step
    ring([r for r in CALL if not r["name"].startswith("lm.")])
    assert reader("lm_dispatch_ms_per_step")(traced()) is None
    assert reader("kv_h2d_bytes_per_step")(traced()) is None
    assert reader("kv_host_ms_per_step")(traced()) == pytest.approx(0.008)


def test_an_empty_ring_or_an_untraced_run_reads_nothing(ring):
    ring([])
    for name in NEW:
        assert reader(name)(traced()) is None, name
    ring(CALL + [rec("trainer.prep_batch", 0, 5, "p"),
                 rec("trainer.dispatch", 5, 9, "d")])
    for name in NEW:                    # records there, the run not traced
        assert reader(name)({"trace": None}) is None, name


def test_the_trainer_readers_take_the_mean_of_their_span(ring):
    ring([rec("trainer.prep_batch", 0, 1_000, "p1", "s1"),
          rec("trainer.dispatch", 1_000, 5_000, "d1", "s1"),
          rec("trainer.step", 0, 6_100, "s1", rows=128),
          rec("trainer.prep_batch", 9_000, 2_000, "p2", "s2"),
          rec("trainer.dispatch", 11_000, 6_000, "d2", "s2"),
          rec("trainer.step", 9_000, 8_100, "s2", rows=128)])
    assert reader("trainer_prep_batch_ms_per_step")(traced()) == 1.5
    assert reader("trainer_dispatch_ms_per_step")(traced()) == 5.5


# ------------------------------------------------- the optimizer's path
def op(name, start_us, dur_us, plane=DEV):
    return Event(plane, OPS_LINE, name, start_us * 1e3, dur_us * 1e3)


PACKS = ("%concatenate.8 = f32[2048]{0:T(1024)} concatenate(f32[768]{0} "
         "%param_vals__w.1, f32[1280]{0} %reshape.13), dimensions={0}")
LAUNCH = ("%fused_adamw.1 = (f32[16,128]{1,0:T(8,128)}, f32[16,128]{1,0}, "
          "f32[16,128]{1,0}) custom-call(f32[1,8]{1,0:T(1,128)} %bitcast.9, "
          "f32[16,128]{1,0:T(8,128)} %bitcast.3, f32[16,128]{1,0} %bitcast.4,"
          " f32[16,128]{1,0} %bitcast.5, f32[16,128]{1,0} %bitcast.6), "
          "custom_call_target=\"tpu_custom_call\"")
UNPACK = ("%convert.7 = bf16[16,128]{1,0:T(8,128)(2,1)} convert("
          "f32[16,128]{1,0:T(8,128)} %pallas_call.83)")
OTHER = ("%fusion.12 = bf16[128,768]{1,0:T(8,128)(2,1)} fusion(bf16[128,768]"
         "{1,0} %x), kind=kLoop, calls=%fused_computation.12")
OLD_NAME = LAUNCH.replace("%fused_adamw.1", "%step_fn.1")


def ops_facts(names):
    """Two steps of 100 us each: every operation 10 us, once a step."""
    events = [op(n, 100 * step + 10 * i, 10)
              for step in range(2) for i, n in enumerate(names)]
    return {"trace": {"ops": {DEV: events}, "window": (0.0, 200_000.0),
                      "spans": []}}


def test_the_optimizer_path_is_the_launch_and_what_moves_its_buffers():
    read = reader("optim_path_device_ms_per_step")
    facts = ops_facts([PACKS, OTHER, LAUNCH, UNPACK, OTHER])
    # pack + launch + unpack = 30 us a step; the two other fusions are not
    # on the path though they run between its operations
    assert read(facts) == pytest.approx(0.030)
    # packs removed: the reading follows
    assert read(ops_facts([OTHER, LAUNCH, UNPACK])) == pytest.approx(0.020)


def test_two_groups_are_two_launches_a_step_and_both_sizes_are_followed():
    # a trainer with float32 and bfloat16 leaves: a launch of its own for
    # each group, each once a step, each with packs of its own size
    second = LAUNCH.replace("%fused_adamw.1", "%fused_adamw.2").replace(
        "f32[16,128]", "bf16[24,128]")
    packs2 = PACKS.replace("f32[2048]", "bf16[3072]")
    read = reader("optim_path_device_ms_per_step")
    facts = ops_facts([PACKS, packs2, OTHER, LAUNCH, second, UNPACK])
    # five operations of 10 us over TWO steps, not over four launches
    assert read(facts) == pytest.approx(0.050)


def test_a_launch_that_is_not_called_after_its_kernel_reads_nothing():
    read = reader("optim_path_device_ms_per_step")
    assert read(ops_facts([PACKS, OLD_NAME, UNPACK])) is None
    assert read(ops_facts([OTHER])) is None
    assert read({"trace": {"ops": {}, "window": (0.0, 1.0),
                           "spans": []}}) is None


# --------------------------------------------------- the harness on the CPU
@pytest.fixture(scope="module")
def tiny_with_the_new_entries():
    """The tests' toy benchmark plus this PR's entries of BENCHMARK.json,
    each pointed at the toy cell of its kind; built in memory."""
    bench = spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                        "BENCHMARK_tiny.json"))
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in NEW:
        cell = ("bert_tiny.pretrain_tiny" if "bert_base.pretrain_t128"
                in entries[name]["workloads"] else "gpt2_tiny.generate_tiny")
        bench["per_layer"].append(dict(entries[name], workloads=[cell]))
    return bench


def drive(bench, workload):
    import jax
    from incubator_mxnet_tpu.telemetry import tracing
    tracing.clear_spans()       # this worker's earlier tests
    return run.drive(bench, workload, 2 ** 31 + 5, 0.5, True, jax.devices(),
                     peaks={"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})


def test_a_traced_training_run_reads_the_trainers_spans_and_no_device_time(
        tiny_with_the_new_entries):
    metrics = drive(tiny_with_the_new_entries,
                    "bert_tiny.pretrain_tiny")["metrics"]
    # a CPU has no device plane: the optimizer path's device time is left
    # out, never printed as 0; the spans were real inside the session
    assert "optim_path_device_ms_per_step" not in metrics
    prep = metrics["trainer_prep_batch_ms_per_step"]["value"]
    dispatch = metrics["trainer_dispatch_ms_per_step"]["value"]
    assert prep > 0 and dispatch > 0


def test_a_traced_generation_run_reads_every_phase_of_a_decode_step(
        tiny_with_the_new_entries):
    metrics = drive(tiny_with_the_new_entries,
                    "gpt2_tiny.generate_tiny")["metrics"]
    parts = [metrics[n]["value"] for n in (
        "gen_step_self_ms", "lm_dispatch_ms_per_step",
        "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step")]
    assert all(p > 0 for p in parts)
    # medians of the parts against the median of the whole
    assert sum(parts) == pytest.approx(
        metrics["decode_step_p50_ms"]["value"], rel=0.5)
    pools = metrics["kv_host_bytes_per_step"]["value"]
    h2d = metrics["kv_h2d_bytes_per_step"]["value"]
    assert pools < h2d < pools + 4096    # tokens, lengths and tables beside


def test_an_untraced_run_leaves_the_ring_alone(tiny_with_the_new_entries):
    import jax
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import tracing
    telemetry.disable()         # whatever this worker's earlier tests left
    tracing.clear_spans()
    result = run.drive(tiny_with_the_new_entries, "gpt2_tiny.generate_tiny",
                       7, 0.3, False, jax.devices(),
                       peaks={"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})
    assert set(result["metrics"]) == {"gen_tokens_per_s_per_chip", "setup_s"}
    assert tracing.recent_spans() == []
