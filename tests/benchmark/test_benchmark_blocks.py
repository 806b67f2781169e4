"""The block-generation cell's harness on the CPU, at a toy size that only
these tests reach (``BENCHMARK_blocks_tiny.json``: the ``sdar_moe`` family
at 2 layers, hidden 64, 8 experts top-2, float32 so that a sound run reads
next to nothing on any CPU; bfloat16 serving is in ``tests/test_sdar_moe.py``):
a sound run, the control and two planted faults, each new reader on
hand-made facts, and ``costs_moe.py`` against counts by hand.
"""

import os
import types

import numpy as np
import pytest

from benchmarks import compare, costs_moe, run, spec
from benchmarks.peaks import peaks_for
from benchmarks.runners import block_calls

ROOT = spec.ROOT
CELL = "sdar_tiny.generate_blocks_tiny"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 77         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_blocks_tiny.json"))


def drive(bench, trace=False, seed=SEED, seconds=0.5):
    import jax
    return run.drive(bench, CELL, seed, seconds, trace, jax.devices(),
                     peaks=PEAKS)


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


# --------------------------------------------------------------- sound runs
def test_a_sound_run_is_correct_and_reports_both_end_to_end_metrics(bench):
    result = drive(bench)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4
    assert set(result["metrics"]) == {"gen_tokens_per_s_per_chip", "setup_s"}
    assert result["metrics"]["gen_tokens_per_s_per_chip"]["value"] > 0
    assert set(result["compared"]) == {"served_gap_per_close_call",
                                       "chosen_confidence_gap"}
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]


def test_a_traced_run_reports_every_layer_a_cpu_can_read(bench):
    result = drive(bench, trace=True)
    # a CPU has no device plane: idle share, peak memory and the expert
    # products' roofline are left out, never reported as 0
    assert set(result["metrics"]) == {
        "prefill_share", "kv_host_bytes_per_step", "gen_mfu", "compile_s",
        "block_forward_p50_ms", "block_tokens_per_forward",
        "block_store_share", "moe_load_max_over_mean"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    # two denoising forwards and a store pass a block of four
    assert values["block_tokens_per_forward"] == pytest.approx(4 / 3)
    assert 0 < values["block_store_share"] < 100
    assert values["block_forward_p50_ms"] > 0
    assert values["moe_load_max_over_mean"] >= 1
    assert result["correct"] is True
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", CELL))


# ------------------------------------------------------------------ faults
def _least_confident_first(monkeypatch):
    """A forward that fixes the wrong positions: the least confident of
    the masked, not the most."""
    from incubator_mxnet_tpu.generate import GenerateEngine
    right = GenerateEngine._fix_most_confident

    def wrong(masked, confidence, steps_left):
        return right(masked, -confidence, steps_left)
    monkeypatch.setattr(GenerateEngine, "_fix_most_confident",
                        staticmethod(wrong))


def _store_pass_left_out(monkeypatch):
    """No forward over the final tokens: the K and V committed for a block
    are those of a denoising forward's input, two positions still MASK."""
    from incubator_mxnet_tpu.generate import SDARPagedLM
    forward_kv = SDARPagedLM.forward_kv

    def faulty(self, tokens, *rest):
        if tokens.shape[1] == self.block_length:    # a block, not a chunk
            tokens = np.array(tokens)
            tokens[:, -2:] = self.mask_id
        return forward_kv(self, tokens, *rest)
    monkeypatch.setattr(SDARPagedLM, "forward_kv", faulty)


@pytest.mark.parametrize("fault,number", [
    (_least_confident_first, "chosen_confidence_gap"),
    (_store_pass_left_out, "served_gap_per_close_call")],
    ids=["wrong_positions", "store_pass_left_out"])
def test_a_fault_in_the_block_loop_turns_correct_false(bench, monkeypatch,
                                                       fault, number):
    fault(monkeypatch)
    result = drive(bench, seconds=1.0)
    assert result["correct"] is False
    assert result["compared"][number]["value"] \
        > result["compared"][number]["limit"]


def test_a_served_mask_token_counts_as_failed(bench, monkeypatch):
    from incubator_mxnet_tpu.generate import GenerateEngine
    generate = GenerateEngine.generate

    def faulty(self, prompts, max_new_tokens, eos_id=None):
        served = generate(self, prompts, max_new_tokens, eos_id)
        served[0][-1] = self.model.mask_id
        return served
    monkeypatch.setattr(GenerateEngine, "generate", faulty)
    result = drive(bench)
    assert result["failed"] >= 1 and result["correct"] is False


# ----------------------------------------------------------------- control
def test_the_8_bit_control_fails_the_block_comparison(bench):
    import jax
    cell, config, traffic, limits = spec.load_cell(bench, CELL)
    assert config["control_precision"] == "float8_e4m3"
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "limits": limits, "devices": jax.devices(), "seed": 4,
           "annotate": jax.profiler.TraceAnnotation}
    (_i, _seed, row), = block_calls.calibrate(ctx, [4], 1)
    assert compare.judge(row["program"], limits)[0] is True
    assert compare.judge(row["control_float8_e4m3"], limits)[0] is False
    assert row["counted"]["with_a_choice"] > 0


# ------------------------------------------------------- the two numbers
class _Reference:
    """A reference whose logits are handed to it."""

    def __init__(self, logits):
        self._logits = logits

    def logits(self, weights, cfg, tokens, at, precision, block_rows):
        return self._logits[precision][:len(tokens)]

    @staticmethod
    def fix_most_confident(masked, confidence, steps_left):
        from benchmarks.reference import sdar_moe
        return sdar_moe.fix_most_confident(masked, confidence, steps_left)


def _family(logits):
    return types.SimpleNamespace(
        reference=_Reference(logits),
        assumed=lambda cfg, key: {"mask_token_id": 6}[key])


def test_the_two_numbers_on_hand_made_logits():
    # one forward over a block of 4, all masked, 2 steps left: 2 to fix.
    # The reference is surest of positions 0 and 1 (confidence 0.9, 0.8
    # against 0.5, 0.4, from logits log(share) over 6 tokens).
    # Token 6 is MASK, with next to no share.
    shares = np.array([[.9, .02, .02, .02, .02, .02, 1e-30],
                       [.02, .8, .05, .05, .04, .04, 1e-30],
                       [.5, .42, .02, .02, .02, .02, 1e-30],
                       [.3, .4, .1, .1, .05, .05, 1e-30]])
    ref = np.log(shares)[None].astype(np.float32)
    forward = {"prefix": [1, 2, 3, 4], "tokens": np.full(4, 6),
               "masked": np.ones(4, bool), "steps_left": 2,
               "fixed": np.array([True, True, False, False]),
               "x0": np.array([0, 1, 0, 1])}
    numbers, counted = block_calls.block_numbers(
        _family({"float32": ref}), {}, None, [forward], 8)
    assert numbers == {"served_gap_per_close_call": 0.0,
                       "chosen_confidence_gap": pytest.approx(0.0)}
    assert counted == {"forwards": 1, "with_a_choice": 1, "positions": 2,
                       "close_calls": 0}
    # the program fixes positions 2 and 3 instead, and a token at 2 that
    # the reference puts second: 1 - (0.5 + 0.4) / (0.9 + 0.8), and the
    # logit gap log(0.5 / 0.42) over no close call at all
    wrong = dict(forward, fixed=np.array([False, False, True, True]),
                 x0=np.array([0, 1, 1, 1]))
    numbers, _ = block_calls.block_numbers(
        _family({"float32": ref}), {}, None, [wrong], 8)
    assert numbers["chosen_confidence_gap"] == pytest.approx(1 - 0.9 / 1.7,
                                                             rel=1e-5)
    assert numbers["served_gap_per_close_call"] == pytest.approx(
        np.log(0.5 / 0.42), rel=1e-5)
    # the control chooses from its OWN logits of the same input: handed the
    # block reversed it fixes positions 3 and 2
    low = ref[:, ::-1]
    numbers, _ = block_calls.block_numbers(
        _family({"float32": ref, "float8_e4m3": low}), {}, None, [forward],
        8, control="float8_e4m3")
    assert numbers["chosen_confidence_gap"] == pytest.approx(1 - 0.9 / 1.7,
                                                             rel=1e-5)
    # MASK is never a choice: a reference that puts it first is read
    # without it
    masked_first = np.log(np.array([[.2, .1, .1, .05, .03, .02, .5]] * 4)
                          )[None]
    numbers, _ = block_calls.block_numbers(
        _family({"float32": masked_first.astype(np.float32)}), {}, None,
        [dict(forward, x0=np.zeros(4, int))], 8)
    assert numbers["served_gap_per_close_call"] == 0.0


def test_sampled_forwards_hand_the_reference_the_programs_own_input():
    step = {"tokens": np.array([[7, 9, 9, 9], [9, 9, 9, 9]]),
            "masked": np.array([[False, True, True, True], [True] * 4]),
            "fixed": np.array([[False, True, True, False],
                               [True, True, False, False]]),
            "x0": np.array([[0, 1, 2, 3], [4, 5, 6, 7]]),
            "confidence": np.zeros((2, 4))}
    idle = dict(step, masked=np.zeros((2, 4), bool))
    call = {"prompts": [[1, 2, 3, 4, 7], [5, 6, 7, 8]],
            "served": [[1, 2, 3], [4, 5, 6, 7]],
            "stats": {"blocks": [{"rows": [0, 1], "starts": [4, 4],
                                  "steps": [step, idle]}]}}
    got = block_calls.sampled_forwards([call], 5, 2,
                                       np.random.default_rng(0))
    assert len(got) == 2        # a forward with nothing masked is left out
    by_row = {tuple(f["prefix"]): f for f in got}
    assert set(by_row) == {(1, 2, 3, 4), (5, 6, 7, 8)}
    assert by_row[(1, 2, 3, 4)]["tokens"].tolist() == [7, 9, 9, 9]
    assert by_row[(1, 2, 3, 4)]["steps_left"] == 2
    assert block_calls.reference_length(
        {"prompt_lens": [32, 95], "new_tokens": 128}, 4) == 224


def test_the_real_benchmark_as_it_stands_with_the_block_cell():
    """What ``test_benchmark_spec.py`` asserts of the real benchmark's
    command, paths and first cells, with the ``reduced`` lists as they now
    stand: the two configurations of PR 26 whole, this one cut in depth and
    in nothing else (``conftest.py`` says why the older test cannot say it)."""
    import json
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    cells = [c["name"] for c in bench["workloads"]]
    assert cells[:2] == ["bert_base.pretrain_t128", "gpt2_xl.generate_short"]
    assert "sdar_30b_a3b.generate_blocks" in cells
    assert {c["name"]: c["reduced"] for c in bench["configs"]} == {
        "bert_base": [], "gpt2_xl": [],
        "sdar_30b_a3b": ["num_hidden_layers"]}
    config = spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                         "sdar_30b_a3b.json"))
    # every width as published; the depth is the one key changed
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "num_experts": 128, "num_experts_per_tok": 8,
                 "moe_intermediate_size": 768, "vocab_size": 151936,
                 "rope_theta": 1000000, "rms_norm_eps": 1e-6,
                 "intermediate_size": 6144, "max_position_embeddings": 32768}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 6
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["dtype"] == "bfloat16"
    assert {"block_length", "denoise_steps", "schedule", "mask_token_id",
            "qk_norm", "no_shift", "mask_never_chosen"} <= set(
                config["assumed"])
    assert all("assumed" in a["why"] for a in config["assumed"].values())


# ----------------------------------------------------------------- readers
def test_the_block_span_readers_on_hand_made_facts():
    spans = {"gen.denoise_step": [0.010, 0.030, 0.020, 0.040, 0.050],
             "gen.block_store": [0.020, 0.040],
             "gen.block": [0.060, 0.140]}
    facts = {"block_span_seconds": spans}
    assert reader("block_forward_p50_ms")(facts) == pytest.approx(30.0)
    assert reader("block_store_share")(facts) == pytest.approx(30.0)
    for name in ("block_forward_p50_ms", "block_store_share"):
        assert reader(name)({}) is None        # an untraced run, a parent
        assert reader(name)({"block_span_seconds": {}}) is None


def test_the_counter_readers_on_hand_made_facts():
    facts = {"block_row_forwards": 99 * 16 - 4 * 3,
             "block_positions_committed": 4 * (33 * 16 - 4),
             "moe_load_max_over_mean": 2.75}
    assert reader("block_tokens_per_forward")(facts) == pytest.approx(4 / 3)
    assert reader("moe_load_max_over_mean")(facts) == 2.75
    assert reader("block_tokens_per_forward")({}) is None
    assert reader("moe_load_max_over_mean")({}) is None


def test_the_roofline_reader_finds_the_products_by_name():
    from benchmarks.trace_reduce import Event
    cfg = {"hidden_size": 2048, "moe_intermediate_size": 768}
    launch = ('%moe_grouped_matmul.3 = f32[2432,768]{1,0} custom-call(...), '
              'custom_call_target="tpu_custom_call"')
    ops = [Event("/device:TPU:0", "ops", launch, 1000, 500_000),
           Event("/device:TPU:0", "ops", launch, 600_000, 500_000),
           Event("/device:TPU:0", "ops", "%fusion.7 = bf16[64,2048] fusion()",
                 1_200_000, 9_000_000)]
    facts = {"config": cfg, "peaks": PEAKS,
             "traced_moe": {"routes": 1024, "experts_hit": 200},
             "trace": {"window": (0, 20_000_000),
                       "ops": {"/device:TPU:0": ops}}}
    nbytes = costs_moe.expert_product_bytes(cfg, 1024, 200)
    flops = costs_moe.expert_product_flops(cfg, 1024)
    least = max(nbytes / PEAKS["hbm_bytes_per_s"],
                flops / PEAKS["bf16_flops_per_s"])
    assert reader("moe_experts_roofline")(facts) == pytest.approx(
        100 * least / 1e-3)
    # XLA's own launch of ragged_dot counts, its metadata companion not
    ops[1] = Event("/device:TPU:0", "ops",
                   "%ragged-dot-none = f32[512,768] custom-call(...)",
                   600_000, 250_000)
    ops.append(Event("/device:TPU:0", "ops",
                     "%ragged-dot-metadata = (s32[129]) custom-call(...)",
                     11_000_000, 4_000))
    assert reader("moe_experts_roofline")(facts) == pytest.approx(
        100 * least / 0.75e-3)
    # no product in the trace (a parent without the layer), or no trace
    facts["trace"]["ops"]["/device:TPU:0"] = ops[2:3]
    assert reader("moe_experts_roofline")(facts) is None
    assert reader("moe_experts_roofline")({"traced_moe": None}) is None


# ------------------------------------------------------------------- costs
def test_the_costs_agree_with_counts_by_hand():
    cfg = spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                      "sdar_30b_a3b.json"))
    assert costs_moe.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    # q and o 2048 x 4096, k and v 2048 x 512, router 2048 x 128, norms
    assert costs_moe.layer_params_outside_experts(cfg) == (
        18_874_368 + 262_144 + 4_352)
    # the issue's arithmetic: a layer 623,120,640; both tables 622,329,856
    assert costs_moe.param_count(cfg) == (
        6 * 623_120_640 + 622_329_856 + 2048) == 4_361_055_744
    assert costs_moe.kv_bytes_per_position(cfg) == 12_288
    # 512 routes: a multiply-add is two operations, three products a route
    assert costs_moe.expert_product_flops(cfg, 512) == 2 * 512 * 4_718_592
    # 126 experts' weights in bfloat16, and a route's rows: x in twice and
    # the hidden row once in bfloat16, two hidden rows and the result out
    # in float32
    assert costs_moe.expert_product_bytes(cfg, 512, 126) == (
        126 * 4_718_592 * 2
        + 512 * ((2 * 2048 + 768) * 2 + (2 * 768 + 2048) * 4))
    # a forward of 64 tokens that hits every expert and runs the head reads
    # every parameter once (8.72 GB, less the embedding's other rows and
    # the final gain): on the v5e bound by memory, 10.6 ms
    v5e = peaks_for("TPU v5 lite")
    floor = costs_moe.block_forward_floor_seconds(cfg, 64, 128, 1.0, 0, v5e)
    assert floor == pytest.approx(
        (4_361_055_744 - 2048 - 151_936 * 2048 + 64 * 2048) * 2 / 819e9)
    assert 0.0098 < floor < 0.0107
    # the store pass skips the head: less to read
    assert costs_moe.block_forward_floor_seconds(
        cfg, 64, 128, 0.0, 0, v5e) == pytest.approx(
            floor - 151_936 * 2048 * 2 / 819e9)
    # live keys and values are read too
    assert costs_moe.block_forward_floor_seconds(
        cfg, 64, 128, 1.0, 1000, v5e) == pytest.approx(
            floor + 1000 * 12_288 / 819e9)
    # where the operations take longer than the bytes, they are the floor:
    # 2 x 64 tokens x (six layers of attention, router and 8 experts, and
    # the head)
    slow = dict(v5e, bf16_flops_per_s=1e12)
    assert costs_moe.block_forward_floor_seconds(
        cfg, 64, 128, 1.0, 0, slow) == pytest.approx(
            2 * 64 * (6 * (19_140_864 + 8 * 4_718_592)
                      + 151_936 * 2048) / 1e12)
