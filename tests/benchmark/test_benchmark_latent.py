"""The long-prompt cell's harness on the CPU, at a toy size that only these
tests reach (``BENCHMARK_latent_tiny.json``: the ``xing4_0`` family at 3
layers, hidden 32, a latent of 16 + 8, 8 experts top-2 with a shared one,
float32 so that a sound run reads next to nothing on any CPU): a sound run,
a traced run, the control and four planted faults, the new readers on
hand-made facts, ``costs_xing4.py`` against counts by hand, and the real
benchmark as it stands with five cells.
"""

import os

import numpy as np
import pytest

from benchmarks import compare, costs_xing4, run, spec
from benchmarks.peaks import peaks_for
from benchmarks.runners import generate_long_calls

ROOT = spec.ROOT
CELL = "xing4_tiny.generate_long_tiny"
REAL = "xing4_29b_a4b.generate_long_prompts"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 77         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_latent_tiny.json"))


def drive(bench, trace=False, seed=SEED, seconds=0.3):
    import jax
    return run.drive(bench, CELL, seed, seconds, trace, jax.devices(),
                     peaks=PEAKS)


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def real_config():
    return spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "xing4_29b_a4b.json"))


# --------------------------------------------------------------- sound runs
def test_a_sound_run_is_correct_and_reports_both_end_to_end_metrics(bench):
    result = drive(bench)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4
    assert set(result["metrics"]) == {"gen_tokens_per_s_per_chip", "setup_s"}
    assert result["metrics"]["gen_tokens_per_s_per_chip"]["value"] > 0
    assert set(result["compared"]) == {"served_gap_per_close_call"}
    row = result["compared"]["served_gap_per_close_call"]
    assert row["value"] <= row["limit"]


def test_a_traced_run_reports_every_layer_a_cpu_can_read(bench):
    result = drive(bench, trace=True)
    # a CPU has no device plane: idle share, peak memory and the expert
    # products' roofline are left out, never reported as 0
    assert set(result["metrics"]) == {
        "prefill_share", "gen_mfu", "compile_s", "decode_step_p50_ms",
        "gen_step_self_ms", "lm_dispatch_ms_per_step",
        "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step",
        "kv_h2d_bytes_per_step", "moe_load_max_over_mean", "prefill_mfu",
        "mla_expanded_rows_per_prompt_token"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    # prompts of 9, 12, 17 and 23 commit 8, 11, 16 and 22 tokens in chunks
    # of 8: the chunks after a prompt's first find 8, 8 and 8 + 16 rows
    assert values["mla_expanded_rows_per_prompt_token"] == pytest.approx(
        40 / 57)
    assert 0 < values["prefill_mfu"] < 100 and 0 < values["gen_mfu"] < 100
    # a token, a length and a table of 2 blocks of 16 a row, 4 B each
    assert values["kv_h2d_bytes_per_step"] == 4 * (4 + 4 + 2 * 4)
    assert values["decode_step_p50_ms"] > values["gen_step_self_ms"] > 0
    assert values["moe_load_max_over_mean"] >= 1
    assert result["correct"] is True
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", CELL))


# ------------------------------------------------------------------ faults
def _rotary_part_dropped(monkeypatch):
    """The absorbed score without its rotary part: the decode steps forget
    where a key lies."""
    from incubator_mxnet_tpu.ops.pallas import paged_latent
    import jax.numpy as jnp
    right = paged_latent.paged_latent_attention

    def faulty(q_nope, q_rope, *rest, **kw):
        if q_nope.shape[1] == 1:
            q_rope = jnp.zeros_like(q_rope)
        return right(q_nope, q_rope, *rest, **kw)
    _patch_the_model(monkeypatch, "paged_latent_attention", faulty)


def _bias_in_the_weights(monkeypatch):
    """The router's choice bias used for the weights too."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    right = moe._routes

    def faulty(logits, k, scoring, choice_bias, route_scale):
        top_e, _weights = right(logits, k, scoring, choice_bias, route_scale)
        biased = jnp.take_along_axis(
            jax.nn.sigmoid(logits) + choice_bias.astype(jnp.float32), top_e,
            axis=-1)
        return top_e, route_scale * biased / biased.sum(-1, keepdims=True)
    monkeypatch.setattr(moe, "_routes", faulty)


def _residual_map_left_unnormalised(monkeypatch):
    """``H_res`` without its Sinkhorn iterations."""
    _patch_the_model(monkeypatch, "sinkhorn", lambda a, iters, eps: a)


def _shared_expert_left_out(monkeypatch):
    from incubator_mxnet_tpu.parallel import moe
    right = moe.moe_dropless

    def faulty(*args, shared=None, **kw):
        return right(*args, shared=None, **kw)
    _patch_the_model(monkeypatch, "moe_dropless", faulty)


def _patch_the_model(monkeypatch, name, value):
    from incubator_mxnet_tpu.models import mla_moe
    monkeypatch.setattr(mla_moe, name, value)


@pytest.mark.parametrize("fault", [
    _rotary_part_dropped, _bias_in_the_weights,
    _residual_map_left_unnormalised, _shared_expert_left_out],
    ids=["rotary_part_dropped", "bias_in_the_weights",
         "h_res_unnormalised", "shared_expert_left_out"])
def test_a_planted_fault_turns_correct_false(bench, monkeypatch, fault):
    fault(monkeypatch)
    result = drive(bench)
    row = result["compared"]["served_gap_per_close_call"]
    assert result["correct"] is False and row["value"] > row["limit"]


def test_a_token_outside_the_vocabulary_counts_as_failed(bench, monkeypatch):
    from incubator_mxnet_tpu.generate import GenerateEngine
    generate = GenerateEngine.generate

    def faulty(self, prompts, max_new_tokens, eos_id=None):
        served = generate(self, prompts, max_new_tokens, eos_id)
        if max_new_tokens > 1:          # not the warm-up
            served[0].pop()
        return served
    monkeypatch.setattr(GenerateEngine, "generate", faulty)
    result = drive(bench)
    assert result["failed"] >= 1 and result["correct"] is False


# ----------------------------------------------------------------- control
def test_the_8_bit_control_fails_the_comparison(bench):
    import jax
    cell, config, traffic, limits = spec.load_cell(bench, CELL)
    assert config["control_precision"] == "float8_e4m3"
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "limits": limits, "devices": jax.devices(), "seed": 4,
           "annotate": jax.profiler.TraceAnnotation}
    (_i, _seed, row), = generate_long_calls.calibrate(ctx, [4], 1)
    assert compare.judge(row["program"], limits)[0] is True
    assert compare.judge(row["control_float8_e4m3"], limits)[0] is False
    assert row["positions"] == 4 * 6 and row["stats"]["mla"][
        "absorbed_forwards"] == 6


def test_the_check_draws_rows_and_asks_for_served_positions_alone():
    calls = [{"prompts": [[1, 2, 3], [4, 5]], "served": [[6, 7], [8, 9]]},
             {"prompts": [[3, 2, 1], [5, 4]], "served": [[7, 6], [9, 8]]}]
    rows = generate_long_calls.sampled_rows(calls, 3,
                                            np.random.default_rng(0))
    assert len(rows) == len({tuple(p) for p, _s in rows}) == 3
    assert len(generate_long_calls.sampled_rows(
        calls, 9, np.random.default_rng(0))) == 4
    asked = []

    class Reference:
        BLOCK_ROWS = 512

        @staticmethod
        def logits(weights, cfg, tokens, at, precision="float32",
                   key_rows=None):
            asked.append((np.asarray(tokens).tolist(),
                          np.asarray(at).tolist(), key_rows))
            out = np.zeros((1, np.asarray(at).shape[1], 10), np.float32)
            out[0, 0, 6], out[0, 1, 5] = 1.0, 2.0   # serves 6, then 5
            out[0, 1, 7] = 1.95                     # a close call, lost
            return out
    family = type("Family", (), {"reference": Reference})
    gaps, margins = generate_long_calls.served_gaps(
        family, {}, None, [([1, 2, 3], [6, 7])],
        generate_long_calls.key_rows_of(family, {"prompt_lens": [2048, 16384],
                                                 "new_tokens": 256}))
    # prompt + served, logits from the last prompt position on
    assert asked == [([[1, 2, 3, 6, 7]], [[2, 3, 4]], 16896)]
    np.testing.assert_allclose(gaps, [0.0, 0.05], atol=1e-6)
    np.testing.assert_allclose(margins, [1.0, 0.05], atol=1e-6)
    assert compare.served_numbers(gaps, margins) == {
        "served_gap_per_close_call": pytest.approx(0.05, abs=1e-6)}


# ----------------------------------------------------------------- readers
def test_the_new_readers_on_hand_made_facts():
    facts = {"prefill_flops": 3e12, "prefill_seconds": 6.0, "peaks": PEAKS,
             "prefill_tokens": 400, "mla": {"expanded_rows": 1000}}
    assert reader("prefill_mfu")(facts) == pytest.approx(50.0)
    assert reader("mla_expanded_rows_per_prompt_token")(facts) == 2.5
    for name in ("prefill_mfu", "mla_expanded_rows_per_prompt_token"):
        assert reader(name)({}) is None        # a parent without the tally
        assert reader(name)({"peaks": PEAKS, "prefill_seconds": 0.0,
                             "prefill_tokens": 0}) is None


# ------------------------------------------------------------------- costs
def test_the_costs_agree_with_counts_by_hand():
    cfg = real_config()
    # the issue's section "The cut"
    assert costs_xing4.attention_params(cfg) == (
        2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064
        + 768 + 512) == 28_411_136
    assert costs_xing4.kv_b_params(cfg) == 4_194_304
    assert costs_xing4.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert 2 * costs_xing4.hyper_params(cfg) == 2 * (14_336 * 24 + 27) \
        == 688_182
    assert costs_xing4.router_params(cfg) == 229_376 + 64
    assert costs_xing4.dense_mlp_params(cfg) == 99_090_432
    outside = 28_411_136 + 688_182 + 2 * 3584
    assert costs_xing4.layer_params_outside_mlp(cfg) == outside
    expert_layer = outside + 229_440 + 65 * 11_010_048
    assert costs_xing4.param_count(cfg) == (
        outside + 99_090_432 + 4 * expert_layer + 2 * 469_762_048 + 3584
    ) == 4_047_680_782 == cfg["param_count"]
    assert "4,047,680,782 parameters, 8.10 GB" in cfg["deployment"]
    assert costs_xing4.cache_bytes_per_position(cfg) == 5 * 1_152
    # a decode step of 16 rows that hits 41 experts a layer reads the
    # layers outside their feed-forward, the dense one, 42 experts and the
    # router of four layers, the head, the final gain and 16 embedding
    # rows; 108,544 live positions add 1,152 B a layer each
    v5e = peaks_for("TPU v5 lite")
    read = (5 * outside + 99_090_432 + 4 * (229_440 + 42 * 11_010_048)
            + 469_762_048 + 3584 + 16 * 3584)
    floor = costs_xing4.decode_step_floor_seconds(cfg, 16, 41, 108_544, v5e)
    assert floor == pytest.approx((read * 2 + 108_544 * 5_760) / 819e9)
    assert 0.0068 < floor < 0.0072
    # where the operations take longer they are the floor: a token's
    # products (5 experts of an expert layer) and 32 heads against every
    # live row, 576 wide for the score and 512 for the value
    slow = dict(v5e, bf16_flops_per_s=1e12)
    token = (5 * outside + 99_090_432 + 4 * (229_440 + 5 * 11_010_048)
             + 469_762_048)
    assert costs_xing4.decode_step_floor_seconds(
        cfg, 16, 41, 108_544, slow) == pytest.approx(
            2 * (16 * token + 5 * 108_544 * 32 * 1_088) / 1e12)
    # prefill: four layers whole and the last one's maps and kv_a; the
    # causal half of 32 heads x (192 + 128); the re-expansion through W_kvb
    whole = 4 * outside + 99_090_432 + 3 * (229_440 + 5 * 11_010_048)
    last = 14_336 * 24 + 27 + 3584 * 576
    assert costs_xing4.prefill_flops(cfg, [10, 4], 7) == 2.0 * (
        14 * (whole + last) + 4 * ((55 + 10) * 32 * 320 + 7 * 4_194_304))


# ------------------------------------------------------- the real benchmark
def test_the_real_benchmark_as_it_stands_with_the_long_prompt_cell():
    """What ``test_benchmark_blocks.py::test_the_real_benchmark_as_it_
    stands_with_the_block_cell`` asserts (``tests/conftest.py`` says why it
    cannot say it any longer), with the ``reduced`` lists as they now
    stand, and this cell's configuration as published."""
    import json
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    cells = {c["name"]: c for c in bench["workloads"]}
    assert list(cells)[:2] == ["bert_base.pretrain_t128",
                               "gpt2_xl.generate_short"]
    assert "sdar_30b_a3b.generate_blocks" in cells and REAL in cells
    assert all(c["chips"] == 1 for c in cells.values()) and len(cells) == 5
    assert {c["name"]: c["reduced"] for c in bench["configs"]} == {
        "bert_base": [], "gpt2_xl": [],
        "sdar_30b_a3b": ["num_hidden_layers"],
        "xing4_29b_a4b": ["num_hidden_layers", "first_k_dense_replace",
                          "num_nextn_predict_layers"]}
    sdar = spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "sdar_30b_a3b.json"))
    assert (sdar["num_hidden_layers"], sdar["published"]) == (
        6, {"num_hidden_layers": 48})
    assert (sdar["hidden_size"], sdar["num_experts"],
            sdar["moe_intermediate_size"], sdar["head_dim"]) == (
                2048, 128, 768, 128)
    # every reader the cell lists has a file, and the cell reports the
    # generation metric and set-up
    assert [m["name"] for m in spec.metrics_of(bench, "end_to_end", REAL)] \
        == ["gen_tokens_per_s_per_chip", "setup_s"]
    listed = [m["name"] for m in spec.metrics_of(bench, "per_layer", REAL)]
    assert {"prefill_mfu", "mla_expanded_rows_per_prompt_token", "gen_mfu",
            "moe_experts_roofline", "decode_step_p50_ms"} <= set(listed)
    assert "kv_host_bytes_per_step" not in listed
    for name in listed:
        assert callable(spec.load_reader(bench, name))


def test_the_configuration_is_the_published_one_but_for_three_keys():
    config = real_config()
    row = {"attention_bias": False, "ep_size": 1, "hidden_act": "silu",
           "hidden_size": 3584, "intermediate_size": 9216,
           "kv_lora_rank": 512, "max_position_embeddings": 262144,
           "model_type": "xing4_0", "moe_intermediate_size": 1024,
           "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
           "n_shared_experts": 1, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_experts_per_tok": 4,
           "num_key_value_heads": 32, "hc_mult": 4, "hc_sinkhorn_iters": 20,
           "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "rms_norm_eps": 1e-06, "rope_theta": 10000,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                            "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 4096,
                            "type": "yarn"},
           "routed_scaling_factor": 2, "scoring_func": "sigmoid",
           "tie_word_embeddings": False, "topk_group": 1,
           "topk_method": "noaux_tc", "v_head_dim": 128,
           "vocab_size": 131072}
    assert {k: config[k] for k in row} == row
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_nextn_predict_layers"]) == (5, 1, 0)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "first_k_dense_replace": 2,
                                   "num_nextn_predict_layers": 1}
    assert config["dtype"] == "bfloat16"
    assert config["control_precision"] == "float8_e4m3"
    assert {"stream_repeat_in_sum_out", "stream_norm_gain", "rotary_pairs",
            "hc_phi_range", "hc_alpha", "hc_bias_range", "router_bias_range",
            "decoding", "prefill_chunk", "no_prediction_layer"} <= set(
                config["assumed"])
    assert all(len(a["why"]) > 20 for a in config["assumed"].values())
    assert config["assumed"]["prefill_chunk"]["value"] in (1024, 2048, 4096)
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_long_prompts.json"))
    assert sum(traffic["prompt_lens"]) == 104_448
    assert len(traffic["prompt_lens"]) == 16
    assert max(traffic["prompt_lens"]) + traffic["new_tokens"] \
        <= traffic["cache_max_len"] == 16_896
