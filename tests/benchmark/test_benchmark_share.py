"""The wide-batch cell's harness on the CPU, at a toy size that only these
tests reach (``BENCHMARK_share_tiny.json``: the ``kimi_k2`` family at 3
layers, hidden 32, a latent of 16 + 8, ONE residual stream, a router over
24 experts top-8 of which this chip holds the 3 of share 2, float32 so
that a sound run reads next to nothing on any CPU): a sound run, a traced
run in which every listed reader returns a number, the control and the
faults of a share (the weights renormalised over the held routes, the
neighbour's experts computed, the shared expert left out), the new reader
on hand-made facts, ``costs_kimi_k2.py`` against counts by hand, the
configuration as published, and the real benchmark as it stands.
"""

import os

import pytest

from benchmarks import compare, costs_kimi_k2, run, spec
from benchmarks.peaks import peaks_for
from benchmarks.runners import generate_long_calls

ROOT = spec.ROOT
CELL = "kimi_tiny.generate_wide_tiny"
REAL = "kimi_k2_7_code.generate_wide_batch"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 77         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_share_tiny.json"))


def drive(bench, trace=False, seed=SEED, seconds=0.3, root=ROOT):
    import jax
    return run.drive(bench, CELL, seed, seconds, trace, jax.devices(),
                     root=root, peaks=PEAKS)


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def real_config():
    return spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "kimi_k2_7_code.json"))


# --------------------------------------------------------------- sound runs
def test_a_sound_run_is_correct_and_reports_both_end_to_end_metrics(bench):
    result = drive(bench)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert set(result["metrics"]) == {"gen_tokens_per_s_per_chip", "setup_s"}
    assert result["metrics"]["gen_tokens_per_s_per_chip"]["value"] > 0
    row = result["compared"]["served_gap_per_close_call"]
    assert row["value"] <= row["limit"]


def test_a_traced_run_reports_every_reader_a_cpu_can_read(bench, tmp_path):
    """Every reader the toy cell lists returns a number, but for the three
    that need a device plane (idle share, peak memory, the grouped
    products' roofline: left out, never reported as 0). The run keeps its
    trace under a root of its own."""
    for name in ("benchmarks", "tests"):
        os.symlink(os.path.join(ROOT, name), str(tmp_path / name))
    result = drive(bench, trace=True, root=str(tmp_path))
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", CELL)}
    assert set(result["metrics"]) == listed - {
        "gen_device_idle_share", "gen_peak_hbm_bytes",
        "moe_experts_roofline"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    # 1 is a layer that touches its own routes alone, experts / held one
    # that moves every route: a pass of this toy lays 32 routes out (the
    # bound's floor) for the one to six that fall here
    assert 1 <= values["moe_moved_rows_per_held_route"]
    assert 0 < values["prefill_mfu"] < 100 and 0 < values["gen_mfu"] < 100
    # a length and a table of 2 blocks of 16 a row, 6 rows, 4 B each
    assert values["kv_h2d_bytes_per_step"] == 6 * (4 + 2 * 4)
    assert values["decode_step_p50_ms"] > values["gen_step_self_ms"] > 0
    assert values["lm_fetch_wait_ms_per_step"] > 0
    assert values["moe_load_max_over_mean"] >= 1
    assert result["correct"] is True
    assert not os.path.exists(str(tmp_path / ".bench_trace" / CELL))


# ------------------------------------------------------------------ faults
def _patch_the_model(monkeypatch, value):
    from incubator_mxnet_tpu.models import mla_moe
    monkeypatch.setattr(mla_moe, "moe_dropless", value)


def _weights_renormalised_over_the_held_routes(monkeypatch):
    """The weights of a token's held routes made to sum to the scaling
    factor by themselves: a share that forgets the routes it does not
    compute."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    right = moe._held_routes

    def faulty(x, top_e, weights, experts, gate_w, up_w, down_w, held,
               *rest):
        first, count = held
        here = (top_e >= first) & (top_e < first + count)
        total = jnp.sum(jnp.where(here, weights, 0.0), -1, keepdims=True)
        weights = weights * (jnp.sum(weights, -1, keepdims=True)
                             / jnp.maximum(total, 1e-20))
        return right(x, top_e, weights, experts, gate_w, up_w, down_w, held,
                     *rest)
    monkeypatch.setattr(moe, "_held_routes", faulty)


def _the_neighbours_routes_computed(monkeypatch):
    """The layer told it holds the share before its own: the routes of
    the experts 3-5 through the weights of 6-8."""
    from incubator_mxnet_tpu.parallel import moe
    right = moe.moe_dropless

    def faulty(*args, held=None, **kw):
        return right(*args, held=(held[0] - held[1], held[1]), **kw)
    _patch_the_model(monkeypatch, faulty)


def _shared_expert_left_out(monkeypatch):
    from incubator_mxnet_tpu.parallel import moe
    right = moe.moe_dropless

    def faulty(*args, shared=None, **kw):
        return right(*args, shared=None, **kw)
    _patch_the_model(monkeypatch, faulty)


@pytest.mark.parametrize("fault", [
    _weights_renormalised_over_the_held_routes,
    _the_neighbours_routes_computed, _shared_expert_left_out],
    ids=["renormalised_over_held", "neighbours_routes",
         "shared_expert_left_out"])
def test_a_planted_fault_turns_correct_false(bench, monkeypatch, fault):
    fault(monkeypatch)
    result = drive(bench)
    row = result["compared"]["served_gap_per_close_call"]
    assert result["correct"] is False and row["value"] > row["limit"]


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
    assert row["positions"] == 6 * 6 and row["stats"]["mla"][
        "absorbed_forwards"] == 6


def test_a_program_without_the_share_is_refused_before_any_weight(
        monkeypatch):
    """What the parent commit does with the cell: ``require_program``
    probes ``moe_dropless``'s ``held``, not the adapter (which the parent
    has), and raises at once."""
    from benchmarks.families import kimi_k2
    from incubator_mxnet_tpu.parallel import moe
    kimi_k2.require_program()

    def parents(x, router_w, gate_w, up_w, down_w, top_k, shared=None):
        raise AssertionError("never called")
    monkeypatch.setattr(moe, "moe_dropless", parents)
    with pytest.raises(RuntimeError, match="has no `held`"):
        kimi_k2.require_program()


# ----------------------------------------------------------------- readers
def test_the_new_reader_on_hand_made_facts():
    read = reader("moe_moved_rows_per_held_route")
    assert read({"traced_moe": {"rows_moved": 4800, "routes": 640}}) == 7.5
    # a program whose layer holds every expert counts no such rows: the
    # parent's line leaves the metric out
    assert read({"traced_moe": {"routes": 640, "experts_hit": 9}}) is None
    assert read({"traced_moe": {"rows_moved": 0, "routes": 0}}) is None
    assert read({}) is None


# ------------------------------------------------------------------- costs
def test_the_costs_agree_with_counts_by_hand():
    cfg = real_config()
    # the issue's section 3, "Sizes"
    assert costs_kimi_k2.attention_params(cfg) == (
        11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 58_720_256
        + 1536 + 512) == 101_124_096
    assert costs_kimi_k2.layer_params_outside_mlp(cfg) == 101_138_432
    assert costs_kimi_k2.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert costs_kimi_k2.router_params(cfg) == 7169 * 384
    assert costs_kimi_k2.dense_mlp_params(cfg) == 396_361_728
    dense_layer = 101_138_432 + 396_361_728
    assert dense_layer == 497_500_160
    outside = costs_kimi_k2.expert_layer_params_outside_routed(cfg)
    assert outside == 101_138_432 + 2_752_896 + 44_040_192 == 147_931_520
    expert_layer = outside + 12 * 44_040_192
    assert expert_layer == 676_413_824
    assert costs_kimi_k2.param_count(cfg) == (
        dense_layer + 5 * expert_layer + 2 * 20_480 * 7168 + 7168
    ) == 4_173_177_728 == cfg["param_count"]
    assert "4,173,177,728 parameters, 8.35 GB" in cfg["deployment"]
    assert costs_kimi_k2.cache_bytes_per_position(cfg) == 6 * 1_152
    assert costs_kimi_k2.routes_here_a_token(cfg) == 0.25
    # a decode step of 128 rows that hits 11.3 held experts a layer reads
    # the dense layer, what every chip holds of five expert layers and 11.3
    # experts of each, the head's slice, the final gain and 128 embedding
    # rows; 84,672 live positions add 1,152 B a layer each: 7.7 GB, 10 ms
    v5e = peaks_for("TPU v5 lite")
    read = (dense_layer + 5 * (outside + 11.3 * 44_040_192)
            + 20_480 * 7168 + 7168 + 128 * 7168)
    floor = costs_kimi_k2.decode_step_floor_seconds(cfg, 128, 11.3, 84_672,
                                                    v5e)
    assert floor == pytest.approx((read * 2 + 84_672 * 6_912) / 819e9)
    assert 7.6e9 < read * 2 < 7.8e9 and 0.0098 < floor < 0.0104
    # a floor that counted all 384 experts or the whole vocabulary would
    # read several times the step the chip can run
    assert floor < costs_kimi_k2.decode_step_floor_seconds(
        dict(cfg, vocab_size=163_840), 128, 11.3, 84_672, v5e)
    # where the operations take longer they are the floor: a token's
    # products here (a quarter of a routed expert, the shared one, the
    # router over 384) and 64 heads against every live row
    slow = dict(v5e, bf16_flops_per_s=1e12)
    token = (6 * 101_138_432 + 396_361_728
             + 5 * (2_752_896 + 1.25 * 44_040_192) + 20_480 * 7168)
    assert costs_kimi_k2.decode_step_floor_seconds(
        cfg, 128, 11.3, 84_672, slow) == pytest.approx(
            2 * (128 * token + 6 * 84_672 * 64 * 1_088) / 1e12)
    # prefill: five layers whole and the last one's kv_a; the causal half
    # of 64 heads x (192 + 128); the re-expansion through W_kvb
    whole = (5 * 101_138_432 + 396_361_728
             + 4 * (2_752_896 + 1.25 * 44_040_192))
    assert costs_kimi_k2.prefill_flops(cfg, [10, 4], 7) == 2.0 * (
        14 * (whole + 7168 * 576) + 5 * ((55 + 10) * 64 * 320
                                         + 7 * 8_388_608))


# ------------------------------------------------------- the real benchmark
def test_the_real_benchmark_as_it_stands_with_the_wide_batch_cell():
    """What ``test_benchmark_latent.py::test_the_real_benchmark_as_it_
    stands_with_the_long_prompt_cell`` asserts (``tests/conftest.py`` says
    why it cannot say it any longer), asked only of the configurations and
    cells it names, so that the next configuration breaks nothing."""
    import json
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    cells = {c["name"]: c for c in bench["workloads"]}
    assert list(cells)[:2] == ["bert_base.pretrain_t128",
                               "gpt2_xl.generate_short"]
    for name in ("sdar_30b_a3b.generate_blocks",
                 "xing4_29b_a4b.generate_long_prompts", REAL):
        assert name in cells and cells[name]["chips"] == 1
    assert len(cells) >= 6
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(
        1, len(cells) // 4)
    assert all(len(c["why"]) <= 200 for c in cells.values())
    reduced = {c["name"]: c["reduced"] for c in bench["configs"]}
    for name, keys in {
            "bert_base": [], "gpt2_xl": [],
            "sdar_30b_a3b": ["num_hidden_layers"],
            "xing4_29b_a4b": ["num_hidden_layers", "first_k_dense_replace",
                              "num_nextn_predict_layers"],
            "kimi_k2_7_code": ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]}.items():
        assert reduced[name] == keys
    sdar = spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "sdar_30b_a3b.json"))
    assert (sdar["num_hidden_layers"], sdar["published"]) == (
        6, {"num_hidden_layers": 48})
    assert (sdar["hidden_size"], sdar["num_experts"],
            sdar["moe_intermediate_size"], sdar["head_dim"]) == (
                2048, 128, 768, 128)
    # every reader a generation cell lists has a file, and the cell
    # reports the generation metric and set-up
    for cell in ("xing4_29b_a4b.generate_long_prompts", REAL):
        assert [m["name"] for m in spec.metrics_of(bench, "end_to_end",
                                                   cell)] \
            == ["gen_tokens_per_s_per_chip", "setup_s"]
        listed = [m["name"] for m in spec.metrics_of(bench, "per_layer",
                                                     cell)]
        assert {"prefill_mfu", "gen_mfu", "moe_experts_roofline",
                "decode_step_p50_ms"} <= set(listed)
        assert "kv_host_bytes_per_step" not in listed
        for name in listed:
            assert callable(spec.load_reader(bench, name))
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", REAL)}
    assert {"moe_moved_rows_per_held_route", "moe_load_max_over_mean",
            "gen_device_idle_share", "gen_peak_hbm_bytes", "prefill_share",
            "gen_step_self_ms", "lm_dispatch_ms_per_step",
            "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step",
            "kv_h2d_bytes_per_step", "compile_s"} <= listed
    assert "mla_expanded_rows_per_prompt_token" not in listed
    new = spec.find(bench["per_layer"], "moe_moved_rows_per_held_route",
                    "metric")
    assert (new["workloads"], new["moves"], new["layer"], new["better"]) == (
        [REAL], "gen_tokens_per_s_per_chip", "model step", "lower")


def test_the_configuration_is_the_published_one_but_for_three_keys():
    config = real_config()
    row = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
           "hidden_act": "silu", "hidden_size": 7168,
           "intermediate_size": 18432, "kv_lora_rank": 512,
           "max_position_embeddings": 262144, "model_type": "kimi_k2",
           "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
           "n_shared_experts": 1, "norm_topk_prob": True,
           "num_attention_heads": 64, "num_experts_per_tok": 8,
           "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
           "q_lora_rank": 1536, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                            "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 4096,
                            "type": "yarn"},
           "rope_theta": 50000, "routed_scaling_factor": 2.827,
           "scoring_func": "sigmoid", "seq_aux": True,
           "tf_legacy_loss": False, "tie_word_embeddings": False,
           "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: config[k] for k in row} == row
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 12, 20480)
    assert config["router_width"] == 384        # the router keeps them all
    assert config["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 384,
                                   "vocab_size": 163840}
    assert config["dtype"] == "bfloat16"
    assert config["control_precision"] == "float8_e4m3"
    for said in ("32 chips", "12 of 384", "data-parallel attention",
                 "split 8 ways", "6 of 61 layers"):
        assert said in config["deployment"]
    assert {"initializer_range", "router_bias_range", "rotary_pairs",
            "decoding", "prefill_chunk", "expert_share_index",
            "no_tower"} <= set(config["assumed"])
    assert all(len(a["why"]) > 20 for a in config["assumed"].values())
    assert config["assumed"]["expert_share_index"]["value"] == 0
    from benchmarks.families import kimi_k2
    program = kimi_k2.program_config(config)
    assert program["experts_held"] == (0, 12)
    assert program["num_experts"] == 384 and "streams" not in program
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_wide_batch.json"))
    assert traffic["prompt_lens"] == [16 * round(8 * 4 ** (i / 127))
                                      for i in range(128)]
    assert (min(traffic["prompt_lens"]), max(traffic["prompt_lens"]),
            sum(traffic["prompt_lens"])) == (128, 512, 35_520)
    assert (traffic["new_tokens"], traffic["cache_max_len"],
            traffic["ring_calls"], traffic["checked_rows"],
            traffic["warm_new_tokens"], traffic["first_token_id"]) == (
                384, 896, 4, 8, 2, 1000)
    assert max(traffic["prompt_lens"]) + traffic["new_tokens"] \
        <= traffic["cache_max_len"]
    # the family's prefill chunk is a measured choice among these
    assert config["assumed"]["prefill_chunk"]["value"] in (128, 256, 512,
                                                          1024)
