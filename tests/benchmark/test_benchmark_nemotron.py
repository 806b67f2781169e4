"""The chat-batch cell's harness on the CPU, at a toy size that only these
tests reach (``BENCHMARK_nemotron_tiny.json``: the ``nemotron_h`` family
at the pattern ``MEM*``, hidden 64, 8 SSM heads of 8, state 16, 4 of 8
relu² experts held, 320 tokens, float32 so that a sound run reads next to
nothing on any CPU): a sound run, a traced run in which every listed reader
a CPU can read returns a number, a planted fault of a state-space layer,
a state held in bfloat16, the 8-bit control, the new readers on hand-made
facts, ``costs_nemotron_h.py`` against counts by hand, the configuration as
published but for its cut, and the real benchmark as it stands.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import compare, costs_nemotron_h as costs, run, spec
from benchmarks.peaks import peaks_for
from benchmarks.runners import generate_state_calls

ROOT = spec.ROOT
CELL = "nemotron_tiny.generate_state_tiny"
REAL = "nemotron3_nano_30b_a3b.generate_chat_batch"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 93         # a run's seed may pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_nemotron_tiny.json"))


def drive(bench, trace=False, seed=SEED, seconds=0.3, root=ROOT):
    import jax
    return run.drive(bench, CELL, seed, seconds, trace, jax.devices(),
                     root=root, peaks=PEAKS)


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def real_config():
    return spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "nemotron3_nano_30b_a3b.json"))


# --------------------------------------------------------------- sound runs
def test_a_sound_run_is_correct_and_reports_both_end_to_end_metrics(bench):
    result = drive(bench)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert set(result["metrics"]) == {"gen_tokens_per_s_per_chip", "setup_s"}
    assert result["metrics"]["gen_tokens_per_s_per_chip"]["value"] > 0
    row = result["compared"]["served_gap_per_close_call"]
    assert row["value"] <= row["limit"]
    # a float32 state: 1 value in 256 ends in 8 zero bits, by chance
    row = result["compared"]["ssm_state_short_mantissa_share"]
    assert 0 < row["value"] < 4 / 256 < row["limit"]


def test_a_traced_run_reports_every_reader_a_cpu_can_read(bench, tmp_path):
    """Every reader the toy cell lists returns a number, but for those
    that need a device plane (left out, never reported as 0). The toy
    cell lists what the real cell lists; no state byte crosses from the
    host."""
    for name in ("benchmarks", "tests"):
        os.symlink(os.path.join(ROOT, name), str(tmp_path / name))
    result = drive(bench, trace=True, root=str(tmp_path))
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", CELL)}
    assert listed | {"compile_s"} == {m["name"] for m in spec.metrics_of(
        spec.load_benchmark(), "per_layer", REAL)}
    assert set(result["metrics"]) == listed - {
        "gen_device_idle_share", "gen_peak_hbm_bytes",
        "ssm_state_step_roofline",
        "gen_idle_prefill_ms_per_prompt", "gen_idle_decode_ms_per_forward",
        "gen_idle_fetch_tail_ms_per_forward",
        "gen_idle_outside_regions_share"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert 20 < values["ssm_state_share_of_decode_bytes"] < 80
    assert 0 < values["prefill_mfu"] < 100 and 0 < values["gen_mfu"] < 100
    # a length, a table of four blocks of 16, a slot and a fed count a
    # row, 6 rows, 4 B each: no token and no state crosses
    assert values["kv_h2d_bytes_per_step"] == 6 * 4 * (1 + 4 + 1 + 1)
    assert 0 < values["prefill_share"] < 100
    assert result["correct"] is True


# ------------------------------------------------------------------ faults
def test_padding_that_moves_the_state_turns_correct_false(bench,
                                                          monkeypatch):
    """Every row of a prefill chunk counted whole: the pads after a
    prompt's last chunk feed the recurrence and the conv window, and the
    replies drift from the reference's."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import nemotron_h
    right = nemotron_h.nemotron_forward_paged

    def faulty(params, cfg, tokens, lengths, tables, kps, vps, slots, fed,
               *pools, **kw):
        whole = jnp.where(jnp.asarray(fed) > 0, tokens.shape[1], 0)
        return right(params, cfg, tokens, lengths, tables, kps, vps, slots,
                     whole, *pools, **kw)
    monkeypatch.setattr(nemotron_h, "nemotron_forward_paged", faulty)
    result = drive(bench)
    row = result["compared"]["served_gap_per_close_call"]
    assert result["correct"] is False and row["value"] > row["limit"]


def test_a_state_held_in_bfloat16_turns_correct_false(bench, monkeypatch):
    """The program with its SSM state pools in bfloat16, below the float32
    the configuration states: every value of the state it leaves fits 16
    bits, and that alone fails the run."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.generate import NemotronHPagedLM
    right = NemotronHPagedLM.cache_spec

    def bfloat16_state(self):
        return {name: entry[:2] + (jnp.dtype(jnp.bfloat16),) + entry[3:]
                if name.startswith("ssm") else entry
                for name, entry in right(self).items()}
    monkeypatch.setattr(NemotronHPagedLM, "cache_spec", bfloat16_state)
    result = drive(bench)
    row = result["compared"]["ssm_state_short_mantissa_share"]
    assert result["correct"] is False and row["value"] == 1.0 > row["limit"]


# ----------------------------------------------------------------- control
def test_the_8_bit_control_fails_the_comparison(bench):
    import jax
    cell, config, traffic, limits = spec.load_cell(bench, CELL)
    assert config["control_precision"] == "float8_e4m3"
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "limits": limits, "devices": jax.devices(), "seed": 4,
           "annotate": jax.profiler.TraceAnnotation}
    (_i, _seed, row), = generate_state_calls.calibrate(ctx, [4], 1)
    assert compare.judge(row["program"], limits)[0] is True
    assert compare.judge(row["control_float8_e4m3"], limits)[0] is False
    assert row["positions"] == 6 * 12
    assert row["stats"]["ssm"]["decode"]["forwards"] == 12
    assert row["distinct_served"] > 12      # the rows do not lock on a token


def test_a_program_without_the_adapter_is_refused_before_any_weight(
        monkeypatch):
    """What the parent commit does with the cell: ``require_program``
    imports ``NemotronHPagedLM`` and raises at once."""
    from benchmarks.families import nemotron_h
    from incubator_mxnet_tpu import generate
    nemotron_h.require_program()
    monkeypatch.delattr(generate, "NemotronHPagedLM")
    with pytest.raises(ImportError, match="NemotronHPagedLM"):
        nemotron_h.require_program()


# ----------------------------------------------------------------- readers
def test_the_new_readers_on_hand_made_facts():
    cfg = real_config()
    read = reader("ssm_state_share_of_decode_bytes")
    # 256 rows a step, every held expert hit, 768 cached rows a row: the
    # state's 4.37 GB beside 5.98 GB of weights and 0.2 GB of rows, 41 %
    facts = {"config": cfg, "decode_experts_hit_per_layer": 64,
             "ssm": {"decode": {"forwards": 2, "rows": 512,
                                "cache_rows_read": 2 * 256 * 768}}}
    weights, state, cached = costs.decode_step_bytes(cfg, 256, 64, 256 * 768)
    assert read(facts) == pytest.approx(100 * state / (weights + state
                                                       + cached))
    assert 40 < read(facts) < 43
    assert read({"ssm": {"decode": {"forwards": 0}}}) is None
    assert read({}) is None     # a program without the tallies
    # the launch's share: 256 rows x 4 layers at 4.2 MB each at the HBM
    # peak, 5.3 ms, over the launches' 8 ms
    from benchmarks.trace_reduce import Event
    read = reader("ssm_state_step_roofline")
    ops = [Event("/device:TPU:0", "XLA Ops", "ssm_state_step.%d" % i,
                 1000.0 + i * 1e6, 2e6) for i in range(4)]
    ops.append(Event("/device:TPU:0", "XLA Ops", "fusion.3", 50.0, 7e5))
    facts = {"trace": {"ops": {"/device:TPU:0": ops}, "window": (0, 1e7)},
             "config": cfg, "peaks": peaks_for("TPU v5 lite"),
             "traced_ssm": {"decode": {"rows": 256}}}
    assert read(facts) == pytest.approx(
        100 * costs.ssm_step_bytes(cfg, 256) / 819e9 / 8e-3)
    assert 60 < read(facts) < 70
    assert read(dict(facts, traced_ssm=None)) is None
    facts["trace"]["ops"] = {"/device:TPU:0": ops[-1:]}     # the lax path
    assert read(facts) is None


# ------------------------------------------------------------------- costs
def test_the_costs_agree_with_counts_by_hand():
    cfg = real_config()
    # the cut's counts: a Mamba layer, the attention layer, an expert
    # layer of 64 held experts with the shared one and the router
    assert costs.mamba_params(cfg) == 38_744_896
    assert costs.attention_params(cfg) == 23_399_040
    assert costs.expert_params(cfg) == 9_977_856
    assert costs.shared_params(cfg) == 19_955_712
    assert costs.router_params(cfg) == 344_064 + 128
    assert costs.param_count(cfg) == (
        4 * 38_744_896 + 4 * (64 * 9_977_856 + 19_955_712 + 344_192 + 2688)
        + 23_399_040 + 2 * 65536 * 2688 + 2688) == 3_166_244_352 \
        == cfg["param_count"]
    assert "3,166,244,352 parameters, 6.33 GB" in cfg["deployment"]
    from benchmarks.families import nemotron_h as family
    from incubator_mxnet_tpu.models import nemotron_h
    shapes = nemotron_h.nemotron_param_shapes(nemotron_h.nemotron_config(
        family.program_config(cfg)))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 3_166_244_352
    # a slot's state: 4 x (64 x 64 x 128 float32 + 3 x 6,144 bfloat16)
    assert costs.state_bytes_per_slot(cfg) == 4 * (2_097_152 + 36_864)
    assert 256 * costs.state_bytes_per_slot(cfg) == 2_185_232_384
    assert costs.cache_bytes_per_row(cfg) == 1024
    # a decode step of 256 rows, all 64 held experts hit, 768 rows a row
    v5e = peaks_for("TPU v5 lite")
    weights, state, cached = costs.decode_step_bytes(cfg, 256, 64, 256 * 768)
    assert 5.98e9 < weights < 5.99e9 and state == 2 * 2_185_232_384
    floor = costs.decode_step_floor_seconds(cfg, 256, 64, 256 * 768, v5e)
    assert floor == pytest.approx((weights + state + cached) / 819e9)
    assert 0.0128 < floor < 0.0130
    # prefill: a token's products with 3 of its 6 routes here, the causal
    # half of its scores, the scan's share
    assert costs.routes_here_a_token(cfg) == 3
    one = costs.prefill_flops(cfg, [1])
    assert one == pytest.approx(2 * costs.token_products(cfg, 3)
                                + 4 * 32 * 128
                                + 4 * costs.scan_flops_per_token(cfg))
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_chat_batch.json"))
    call = costs.prefill_flops(cfg, [n - 1 for n in traffic["prompt_lens"]])
    assert 1.2e14 < call < 1.3e14


# ------------------------------------------------------- the real benchmark
def test_the_real_benchmark_as_it_stands_with_the_chat_batch_cell():
    """Asked only of the configurations and cells this file names (`in`,
    `>=`), so that the next configuration breaks nothing."""
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert len(json.dumps(bench)) < 64 * 1024
    cells = {c["name"]: c for c in bench["workloads"]}
    assert REAL in cells and cells[REAL]["chips"] == 1
    assert (cells[REAL]["config"], cells[REAL]["traffic"]) == (
        "nemotron3_nano_30b_a3b", "generate_chat_batch")
    assert len(cells) >= 9
    assert all(len(c["why"]) <= 200 for c in cells.values())
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs["nemotron3_nano_30b_a3b"]["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert configs["nemotron3_nano_30b_a3b"]["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
        "/blob/main/config.json")
    assert len(configs["nemotron3_nano_30b_a3b"]["why"]) <= 200
    assert [m["name"] for m in spec.metrics_of(bench, "end_to_end", REAL)] \
        == ["gen_tokens_per_s_per_chip", "setup_s"]
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", REAL)}
    assert {"ssm_state_step_roofline", "ssm_state_share_of_decode_bytes",
            "gen_mfu", "prefill_mfu", "decode_step_p50_ms", "prefill_share",
            "gen_device_idle_share", "gen_peak_hbm_bytes",
            "gen_step_self_ms", "lm_dispatch_ms_per_step",
            "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step",
            "kv_h2d_bytes_per_step", "gen_idle_prefill_ms_per_prompt",
            "gen_idle_decode_ms_per_forward",
            "gen_idle_fetch_tail_ms_per_forward",
            "gen_idle_outside_regions_share", "moe_load_max_over_mean",
            "compile_s"} <= listed
    # its reader counts three products an expert: not this layer's two;
    # the other two are pinned to their cells by the tests of cells 4-6
    assert not {"moe_experts_roofline", "moe_decode_experts_hit_per_layer",
                "moe_moved_rows_per_held_route"} & listed
    for name in listed:
        assert callable(spec.load_reader(bench, name))
    for name, unit, source, layer, better in (
            ("ssm_state_step_roofline", "%", "device_trace", "kernels",
             "higher"),
            ("ssm_state_share_of_decode_bytes", "%", "program_counter",
             "paged KV", "lower")):
        new = spec.find(bench["per_layer"], name, "metric")
        assert (new["workloads"], new["moves"], new["unit"], new["source"],
                new["layer"], new["better"]) == (
            [REAL], "gen_tokens_per_s_per_chip", unit, source, layer,
            better)
    _cell, _config, _traffic, limits = spec.load_cell(bench, REAL)
    assert list(limits) == ["served_gap_per_close_call",
                            "ssm_state_short_mantissa_share"]


def test_the_configuration_is_the_published_one_but_for_its_cut():
    config = real_config()
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "EMEMEMEM*",
           "n_routed_experts": 64, "vocab_size": 65536}
    assert {k: config[k] for k in cut} == cut
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    # one period of the pattern: the published layers 34-42
    assert config["published"]["hybrid_override_pattern"][34:43] == \
        "EMEMEMEM*"
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32,
              "num_key_value_heads": 2, "head_dim": 128,
              "num_experts_per_tok": 6, "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2"}
    assert {k: config[k] for k in widths} == widths
    published = {"chunk_size": 128, "conv_kernel": 4, "expand": 2,
                 "layer_norm_epsilon": 1e-05, "mamba_hidden_act": "silu",
                 "mamba_proj_bias": False, "max_position_embeddings": 262144,
                 "model_type": "nemotron_h", "n_group": 1,
                 "n_shared_experts": 1, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "rope_theta": 10000,
                 "partial_rotary_factor": 1, "tie_word_embeddings": False,
                 "time_step_floor": 0.0001, "time_step_max": 0.1,
                 "time_step_min": 0.001, "topk_group": 1,
                 "use_conv_bias": True, "intermediate_size": 1856}
    assert {k: config[k] for k in published} == published
    assert config["router_width"] == 128
    assert config["dtype"] == "bfloat16"
    assert config["control_precision"] == "float8_e4m3"
    for said in ("one of 2 chips", "experts 0-63", "rows 0-65,535",
                 "layers 34-42"):
        assert said in config["deployment"]
    assert {"initializer_range", "mamba_init", "router_bias_range",
            "expert_share_index", "ssm_state_dtype", "no_positions",
            "decoding", "prefill_chunk"} <= set(config["assumed"])
    assert config["assumed"]["prefill_chunk"]["value"] % 128 == 0
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_chat_batch.json"))
    lens = traffic["prompt_lens"]
    assert lens == [round(96 * (2048 / 96) ** (i / 255)) for i in range(256)]
    assert sum(lens) == 163_730 and sum(n % 128 == 0 for n in lens) == 2
    assert (traffic["new_tokens"], traffic["cache_max_len"],
            traffic["ring_calls"], traffic["checked_rows"],
            traffic["warm_new_tokens"], traffic["first_token_id"]) == (
                256, 2304, 4, 8, 2, 1000)
    assert max(lens) + traffic["new_tokens"] == traffic["cache_max_len"]
    assert traffic["runner"] == "benchmarks.runners.generate_state_calls"
