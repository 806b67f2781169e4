"""The reduction from trace to numbers, on a small hand-made set of events.

One device, a window of 100 us (0..100_000 ns):
  fusion.1        0..30 us
  all-reduce.1   20..50 us   (overlaps fusion.1 for 10 us)
  custom-call.7  60..80 us   (a kernel)
  all-reduce.2   90..120 us  (runs past the window's end)
so busy = 0..50, 60..80, 90..100 = 80 us, idle = 50..60 and 80..90.
"""

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.trace_reduce import Event

DEV = "/device:TPU:0"
WINDOW = (0.0, 100_000.0)


def op(name, start_us, dur_us, plane=DEV, line=tr.OPS_LINE):
    return Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


EVENTS = [
    op("fusion.1", 0, 30), op("all-reduce.1", 20, 30),
    op("custom-call.7", 60, 20), op("all-reduce.2", 90, 30),
    op("fusion.9", 0, 100, line="XLA Modules"),          # not an operation
    op("bench.trace_window", 0, 100, plane="/host:CPU", line="python"),
    op("bench.step_call", 0, 55, plane="/host:CPU", line="python"),
    op("bench.step_call", 55, 10, plane="/host:CPU", line="python"),
    op("other", 80, 10, plane="/host:CPU", line="python"),
]


def test_device_ops_keeps_only_the_operations_line():
    ops = tr.device_ops(EVENTS)
    assert list(ops) == [DEV]
    assert [e.name for e in ops[DEV]] == ["fusion.1", "all-reduce.1",
                                          "custom-call.7", "all-reduce.2"]


def test_window_of_reads_the_named_host_event():
    assert tr.window_of(EVENTS, "bench.trace_window") == WINDOW
    assert tr.window_of(EVENTS, "absent") is None


def test_busy_is_the_union_clipped_to_the_window():
    ops = tr.device_ops(EVENTS)[DEV]
    assert tr.busy_seconds(ops, WINDOW) == pytest.approx(80e-6)
    assert tr.busy_seconds(ops, (25_000.0, 65_000.0)) == pytest.approx(30e-6)


@pytest.mark.parametrize("intervals,holes,left", [
    ([(0, 10)], [(2, 4), (3, 6)], [(0, 2), (6, 10)]),
    ([(0, 10)], [(-5, 20)], []),
    ([(0, 10), (20, 30)], [(8, 22)], [(0, 8), (22, 30)]),
    ([(0, 10)], [], [(0, 10)]),
])
def test_subtract(intervals, holes, left):
    assert tr.subtract(intervals, holes) == left


def test_merge_joins_touching_and_overlapping_intervals():
    assert tr.merge([(5, 7), (0, 2), (2, 3), (6, 9)]) == [(0, 3), (5, 9)]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    ops = tr.device_ops(EVENTS)[DEV]
    spans = tr.host_spans(EVENTS, {"bench.step_call"})
    gaps = dict(tr.idle_gaps(ops, spans, WINDOW))
    # 50..60 lies half under each step_call; 80..90 under no named span
    assert gaps == {"bench.step_call": pytest.approx(10e-6),
                    "no_span": pytest.approx(10e-6)}


def test_a_gap_is_split_between_the_innermost_spans_that_cover_it():
    ops = tr.device_ops(EVENTS)[DEV]
    spans = [op("outer", 0, 100, plane="program"),
             op("inner.a", 40, 15, plane="program"),     # 40..55
             op("inner.b", 57, 30, plane="program")]     # 57..87
    gaps = dict(tr.idle_gaps(ops, spans, WINDOW))
    # gap 50..60: inner.a 50..55, outer 55..57, inner.b 57..60;
    # gap 80..90: inner.b 80..87, outer 87..90
    assert gaps == {"inner.a": pytest.approx(5e-6),
                    "inner.b": pytest.approx(10e-6),
                    "outer": pytest.approx(5e-6)}


def test_event_names_are_cut_to_the_instruction():
    long = "%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8] %p), kind=kLoop"
    assert tr.short_name(long) == "fusion.12"
    assert tr.short_name("copy.3") == "copy.3"
    assert tr.is_collective("%all-reduce.7 = f32[4] all-reduce(f32[4] %x)")
    assert not tr.is_collective("%fusion.1 = f32[4] fusion(%all-reduce.7)")


def test_top_ops_sums_by_name_and_orders_by_time():
    ops = tr.device_ops(EVENTS)[DEV] + [
        op("%fusion.1 = f32[8]{0} fusion(f32[8] %p), kind=kLoop", 40, 5)]
    top = tr.top_ops(ops, WINDOW, top=2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(35e-6)
    assert len(top) == 2


def test_events_by_kernel():
    ops = tr.device_ops(EVENTS)[DEV]
    seconds, count = tr.seconds_matching(
        ops, WINDOW, lambda name: name.startswith("custom-call"))
    assert (seconds, count) == (pytest.approx(20e-6), 1)
    assert tr.seconds_matching(ops, WINDOW, lambda n: False) == (0.0, 0)


def test_collectives_exposed_leave_out_what_compute_hides():
    ops = tr.device_ops(EVENTS)[DEV]
    # all-reduce.1: 30..50 alone (20..30 hidden by fusion.1);
    # all-reduce.2: 90..100 inside the window
    assert tr.collective_exposed_seconds(ops, WINDOW) == pytest.approx(30e-6)


@pytest.mark.parametrize("name,is_collective", [
    ("all-reduce.12", True), ("all-gather-start.3", True),
    ("reduce-scatter.1", True), ("collective-permute-done", True),
    ("all-to-all", True), ("fusion.4", False), ("copy.1", False)])
def test_collective_names(name, is_collective):
    assert tr.is_collective(name) is is_collective


# ---------------------------------------------------------------- readers
# the fused AdamW launch as the first chip trace of this PR named it
ADAMW = ('%step_fn.1 = (f32[860208,128]{1,0:T(8,128)}, f32[860208,128]'
         '{1,0:T(8,128)}, f32[860208,128]{1,0:T(8,128)}) custom-call('
         'f32[1,8]{1,0:T(1,128)} %bitcast.2250, f32[860208,128]{1,0:T(8,128)}'
         ' %bitcast.3, f32[860208,128]{1,0:T(8,128)} %bitcast.4), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def facts_with(ops):
    return {"peaks": PEAKS, "chips": 1,
            "trace": {"ops": {DEV: ops}, "window": WINDOW, "spans": []}}


def reader(name):
    from benchmarks import spec
    return spec.load_reader(spec.load_benchmark(), name)


def test_fused_adamw_roofline_is_bytes_over_bandwidth_over_kernel_time():
    least = 7 * 860208 * 128 * 4 / 819e9            # 3.76 ms
    ops = [op(ADAMW, 10, 40), op("%fusion.1 = f32[8] fusion()", 50, 10)]
    share = reader("fused_adamw_roofline")(facts_with(ops))
    assert share == pytest.approx(100 * least / 40e-6)
    assert reader("fused_adamw_roofline")(facts_with(ops[1:])) is None


def test_idle_share_is_the_mean_over_devices():
    facts = facts_with(tr.device_ops(EVENTS)[DEV])
    assert reader("train_device_idle_share")(facts) == pytest.approx(20.0)
    facts["trace"]["ops"]["/device:TPU:1"] = []
    assert reader("train_device_idle_share")(facts) == pytest.approx(60.0)


def test_collective_exposed_share_reads_the_worst_device():
    facts = facts_with(tr.device_ops(EVENTS)[DEV])
    assert reader("collective_exposed_share")(facts) == pytest.approx(30.0)
    quiet = facts_with([op("fusion.1", 0, 30)])
    assert reader("collective_exposed_share")(quiet) is None


# the flash-attention calls as the first T=512 trace of this PR named them
FLASH_FWD = ('%jvp__.51 = (bf16[384,512,64]{2,1,0:T(8,128)(2,1)S(1)}, '
             'f32[384,8,512]{2,1,0:T(8,128)}) custom-call(bf16[384,512,64]'
             '{2,1,0:T(8,128)(2,1)S(1)} %bitcast.1999, bf16[384,512,64]{2,1,0}'
             ' %bitcast.2000, bf16[384,512,64]{2,1,0} %bitcast.2003), '
             'custom_call_target="tpu_custom_call", operand_layout={}')
FLASH_BWD = ('%transpose_jvp___.47 = (bf16[384,512,64]{2,1,0:T(8,128)(2,1)}, '
             'bf16[384,512,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call('
             'bf16[384,512,64]{2,1,0:T(8,128)(2,1)} %bitcast.1962, '
             'bf16[384,512,64]{2,1,0} %bitcast.1966), '
             'custom_call_target="tpu_custom_call", operand_layout={}')


def test_flash_attention_roofline_counts_one_backward_pass_a_forward_call():
    product = 2 * 384 * 512 * 512 * 64
    fwd, bwd = 2 * product / 197e12, 4 * product / 197e12   # compute-bound
    ops = [op(FLASH_FWD, 0, 10), op(FLASH_BWD, 10, 8), op(FLASH_BWD, 20, 7),
           op(ADAMW, 30, 40)]
    share = reader("flash_attention_roofline")(facts_with(ops))
    assert share == pytest.approx(100 * (fwd + bwd) / 25e-6)
    forward_only = reader("flash_attention_roofline")(facts_with(ops[:1]))
    assert forward_only == pytest.approx(100 * fwd / 10e-6)
    assert reader("flash_attention_roofline")(facts_with(ops[3:])) is None
