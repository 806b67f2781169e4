"""The reasoning-answers cell's harness on the CPU, at a toy size that only
these tests reach (``BENCHMARK_ouro_tiny.json``: the ``ouro`` family at 3
layers run 3 times a token, hidden 64, 4 heads of 16, 320 tokens, float32
so that a sound run reads next to nothing on any CPU): a sound run, a
traced run in which every listed reader a CPU can read returns a number,
the control and four planted faults of a looped model, the new readers on
hand-made facts, ``costs_ouro.py`` against counts by hand, the
configuration as published, and the real benchmark as it stands.
"""

import os

import numpy as np
import pytest

from benchmarks import compare, costs_ouro, run, spec
from benchmarks.peaks import peaks_for
from benchmarks.runners import generate_loop_calls

ROOT = spec.ROOT
CELL = "ouro_tiny.generate_loop_tiny"
REAL = "ouro_2_6b.generate_reasoning_answers"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 91         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_ouro_tiny.json"))


def drive(bench, trace=False, seed=SEED, seconds=0.3, root=ROOT):
    import jax
    return run.drive(bench, CELL, seed, seconds, trace, jax.devices(),
                     root=root, peaks=PEAKS)


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def real_config():
    return spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "ouro_2_6b.json"))


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
    """Every reader the toy cell lists returns a number, but for those
    that need a device plane (idle share, peak memory, the four idle
    readings, the walk's roofline: left out, never reported as 0). The
    toy cell lists what the real cell lists. The run keeps its trace under
    a root of its own."""
    for name in ("benchmarks", "tests"):
        os.symlink(os.path.join(ROOT, name), str(tmp_path / name))
    result = drive(bench, trace=True, root=str(tmp_path))
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", CELL)}
    assert listed | {"compile_s"} == {m["name"] for m in spec.metrics_of(
        spec.load_benchmark(), "per_layer", REAL)}
    assert set(result["metrics"]) == listed - {
        "gen_device_idle_share", "gen_peak_hbm_bytes",
        "loop_decode_walk_roofline",
        "gen_idle_prefill_ms_per_prompt", "gen_idle_decode_ms_per_forward",
        "gen_idle_fetch_tail_ms_per_forward",
        "gen_idle_outside_regions_share"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < values["loop_cache_share_of_decode_bytes"] < 100
    assert 0 < values["prefill_mfu"] < 100 and 0 < values["gen_mfu"] < 100
    # a length and a table of two blocks of 32 a row, 6 rows, 4 B each:
    # no token crosses
    assert values["kv_h2d_bytes_per_step"] == 6 * 4 * (1 + 2)
    assert values["decode_step_p50_ms"] > values["gen_step_self_ms"] > 0
    assert values["lm_fetch_wait_ms_per_step"] > 0
    assert 0 < values["prefill_share"] < 100
    assert result["correct"] is True
    assert not os.path.exists(str(tmp_path / ".bench_trace" / CELL))


# ------------------------------------------------------------------ faults
def _final_norm_after_the_last_step_alone(monkeypatch):
    """The stream goes from one loop step to the next un-normed: the
    program of T x L layers run once (layer u L + l carrying layer l's
    weights, its keys and values step u's), the final norm at its end."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import ouro
    right = ouro.ouro_forward_paged

    def faulty(params, cfg, tokens, lengths, tables, kps, vps, **kw):
        T, L, d = cfg["loop_steps"], cfg["num_layers"], cfg["units"]
        flat = dict(params)
        for n in range(T * L):
            for name, leaf in params.items():
                if name.startswith("l%d_" % (n % L)):
                    flat["l%d_" % n + name.split("_", 1)[1]] = leaf

        def lanes(pools):
            return [pools[n % L][..., n // L * d:(n // L + 1) * d]
                    for n in range(T * L)]
        out, nk, nv = right(flat, dict(cfg, num_layers=T * L, loop_steps=1),
                            tokens, lengths, tables, lanes(kps), lanes(vps),
                            **kw)
        S, C = tokens.shape

        def steps_side_by_side(new):
            return jnp.moveaxis(new.reshape(T, L, S, C, d), 0, 3).reshape(
                L, S, C, T * d)
        return out, steps_side_by_side(nk), steps_side_by_side(nv)
    monkeypatch.setattr(ouro, "ouro_forward_paged", faulty)


def _step_0s_rows_read_at_every_step(monkeypatch):
    """Every loop step's attention reads the keys and values the FIRST
    step cached: the steps share one cache entry a layer."""
    from incubator_mxnet_tpu.models import ouro
    right = ouro._step_rows

    def faulty(pool, tables, step, heads, head_dim):
        return right(pool, tables, 0, heads, head_dim)
    monkeypatch.setattr(ouro, "_step_rows", faulty)


def _one_loop_step_too_few(monkeypatch):
    """T - 1 loop steps run; the last step's cache rows stay zero."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import ouro
    right = ouro.ouro_forward_paged

    def faulty(params, cfg, *args, **kw):
        out, nk, nv = right(params, dict(cfg, loop_steps=cfg["loop_steps"]
                                         - 1), *args, **kw)
        pad = [(0, 0)] * 3 + [(0, cfg["units"])]
        return out, jnp.pad(nk, pad), jnp.pad(nv, pad)
    monkeypatch.setattr(ouro, "ouro_forward_paged", faulty)


def _attention_post_norm_left_out(monkeypatch):
    """A layer adds its attention's output to the stream as it is: the
    second of its four norms (g2) is left out."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import ouro
    right_layer, right_rms = ouro._layer, ouro._rms
    calls = []

    def layer(*args, **kw):
        calls.clear()
        return right_layer(*args, **kw)

    def rms(x, g, eps):
        calls.append(g)     # a layer's norms in order: g1, g2, g3, g4
        if len(calls) == 2:
            return x.astype(jnp.float32)
        return right_rms(x, g, eps)
    monkeypatch.setattr(ouro, "_layer", layer)
    monkeypatch.setattr(ouro, "_rms", rms)


@pytest.mark.parametrize("fault", [
    _final_norm_after_the_last_step_alone, _step_0s_rows_read_at_every_step,
    _one_loop_step_too_few, _attention_post_norm_left_out],
    ids=["final_norm_once", "step_0_rows_shared", "t_minus_1_steps",
         "attention_post_norm_left_out"])
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
    (_i, _seed, row), = generate_loop_calls.calibrate(ctx, [4], 1)
    assert compare.judge(row["program"], limits)[0] is True
    assert compare.judge(row["control_float8_e4m3"], limits)[0] is False
    assert row["positions"] == 6 * 12
    assert row["stats"]["loop"]["decode"]["forwards"] == 12
    assert row["stats"]["loop"]["decode"]["loop_steps"] == 12 * 3
    assert row["distinct_served"] > 12      # the rows do not lock on a token


def test_a_program_without_the_adapter_is_refused_before_any_weight(
        monkeypatch):
    """What the parent commit does with the cell: ``require_program``
    imports ``OuroPagedLM`` and raises at once."""
    from benchmarks.families import ouro
    from incubator_mxnet_tpu import generate
    ouro.require_program()
    monkeypatch.delattr(generate, "OuroPagedLM")
    with pytest.raises(ImportError, match="OuroPagedLM"):
        ouro.require_program()


# ----------------------------------------------------------------- readers
def test_the_new_readers_on_hand_made_facts():
    cfg = real_config()
    read = reader("loop_cache_share_of_decode_bytes")
    # 8 rows of 270 positions a step: 8 x 270 x 192 rows of 8,192 B
    # beside 4 x the layers, the head and 8 embeddings: some 15 %
    facts = {"config": cfg, "traffic": {"prompt_lens": [0] * 8},
             "loop": {"decode": {"forwards": 2,
                                 "cache_rows_read": 2 * 8 * 270 * 192}}}
    cached = 8 * 270 * 192 * 8192
    weights = (4 * (48 * 51_388_416 + 2048) + 2048 * 49152 + 8 * 2048) * 2
    assert read(facts) == pytest.approx(100 * cached / (weights + cached))
    assert 14 < read(facts) < 16
    assert read({"loop": {"decode": {"forwards": 0}}}) is None
    # a program without the tallies: the parent's line leaves it out
    assert read({"eva": {"decode": {"positions": 3}}}) is None
    assert read({}) is None
    # the walk's share: 1,000 rows over the entries are 1,000 x 8,192 B
    # at the HBM peak, 0.01 ms, over the launches' 0.04 ms
    from benchmarks.trace_reduce import Event
    read = reader("loop_decode_walk_roofline")
    ops = [Event("/device:TPU:0", "XLA Ops", "paged_heads_decode.%d" % i,
                 1000.0 + i * 1e6, 1e4) for i in range(4)]
    ops.append(Event("/device:TPU:0", "XLA Ops", "fusion.3", 50.0, 7e5))
    facts = {"trace": {"ops": {"/device:TPU:0": ops}, "window": (0, 1e7)},
             "config": cfg, "peaks": peaks_for("TPU v5 lite"),
             "traced_loop": {"decode": {"cache_rows_read": 1000}}}
    assert read(facts) == pytest.approx(100 * (1000 * 8192 / 819e9) / 4e-5)
    assert 20 < read(facts) < 30
    assert read(dict(facts, traced_loop=None)) is None
    facts["trace"]["ops"] = {"/device:TPU:0": ops[-1:]}     # the lax gather
    assert read(facts) is None


# ------------------------------------------------------------------- costs
def test_the_costs_agree_with_counts_by_hand():
    cfg = real_config()
    # ISSUE 41, "Sizing"
    assert costs_ouro.layer_products(cfg) == (
        4 * 2048 ** 2 + 3 * 2048 * 5632) == 51_380_224
    assert costs_ouro.layer_params(cfg) == 51_388_416
    assert costs_ouro.param_count(cfg) == (
        48 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2048 + 1
    ) == 2_667_974_657 == cfg["param_count"]
    assert "2,667,974,657 parameters, 5.34 GB" in cfg["deployment"]
    from benchmarks.families import ouro as family
    from incubator_mxnet_tpu.models import ouro
    shapes = ouro.ouro_param_shapes(ouro.ouro_config(
        family.program_config(cfg)))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 2_667_974_657
    assert costs_ouro.applications(cfg) == 192
    assert costs_ouro.cache_bytes_per_row(cfg) == 8192
    # the pools the deployment states: 1,572,864 B a position
    assert 192 * 8192 == 1_572_864 and 8 * 512 * 1_572_864 == 6_442_450_944
    assert "= 6.44 GB" in cfg["deployment"]
    # a decode step of 8 rows at 270 positions each: 4 x the layers' 4.93
    # GB, the head, the cache's 3.4 GB: 23.3 GB, 28.5 ms at 819 GB/s
    v5e = peaks_for("TPU v5 lite")
    rows = 8 * 270 * 192
    weights, cached = costs_ouro.decode_step_bytes(cfg, 8, rows)
    assert cached == rows * 8192 and 3.3e9 < cached < 3.5e9
    assert 19.9e9 < weights < 20.0e9
    floor = costs_ouro.decode_step_floor_seconds(cfg, 8, rows, v5e)
    assert floor == pytest.approx((weights + cached) / 819e9)
    assert 0.0284 < floor < 0.0286
    # where the operations take longer they are the floor: a token's
    # products in 192 layer applications and the head, a score and a
    # value a cache row and head dimension
    slow = dict(v5e, bf16_flops_per_s=1e12)
    assert costs_ouro.decode_step_floor_seconds(
        cfg, 8, rows, slow) == pytest.approx(2 * (
            8 * (192 * 51_380_224 + 2048 * 49152) + rows * 2 * 2048) / 1e12)
    assert costs_ouro.walk_floor_seconds(cfg, rows, v5e) == pytest.approx(
        rows * 8192 / 819e9)
    # prefill from an empty slot: every application's products and the
    # causal half of its own scores and values
    assert costs_ouro.prefill_flops(cfg, [100, 3]) == 2.0 * 192 * (
        103 * 51_380_224 + (100 * 101 // 2 + 6) * 2 * 2048)
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_reasoning_answers.json"))
    call = costs_ouro.prefill_flops(
        cfg, [n - 1 for n in traffic["prompt_lens"]])
    assert 2.0 * 192 * 51_380_224 * 1124 < call < 2.3e13


# ------------------------------------------------------- the real benchmark
def test_the_real_benchmark_as_it_stands_with_the_looped_cell():
    """Asked only of the configurations and cells this file names (`in`,
    `>=`), so that the next configuration breaks nothing."""
    import json
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert len(json.dumps(bench)) < 64 * 1024
    cells = {c["name"]: c for c in bench["workloads"]}
    assert REAL in cells and cells[REAL]["chips"] == 1
    assert (cells[REAL]["config"], cells[REAL]["traffic"]) == (
        "ouro_2_6b", "generate_reasoning_answers")
    assert len(cells) >= 8
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(
        1, len(cells) // 4)
    assert all(len(c["why"]) <= 200 for c in cells.values())
    configs = {c["name"]: c for c in bench["configs"]}
    assert "ouro_2_6b" in configs
    assert configs["ouro_2_6b"]["reduced"] == []
    assert configs["ouro_2_6b"]["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert len(configs["ouro_2_6b"]["why"]) <= 200
    assert [m["name"] for m in spec.metrics_of(bench, "end_to_end", REAL)] \
        == ["gen_tokens_per_s_per_chip", "setup_s"]
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", REAL)}
    assert {"loop_decode_walk_roofline", "loop_cache_share_of_decode_bytes",
            "gen_mfu", "prefill_mfu", "decode_step_p50_ms", "prefill_share",
            "gen_device_idle_share", "gen_peak_hbm_bytes",
            "gen_step_self_ms", "lm_dispatch_ms_per_step",
            "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step",
            "kv_h2d_bytes_per_step", "gen_idle_prefill_ms_per_prompt",
            "gen_idle_decode_ms_per_forward",
            "gen_idle_fetch_tail_ms_per_forward",
            "gen_idle_outside_regions_share", "compile_s"} <= listed
    assert not any(name.startswith(("moe_", "mla_", "block_", "eva_",
                                    "paged_heads_")) for name in listed)
    for name in listed:
        assert callable(spec.load_reader(bench, name))
    for name, unit, source, layer, better in (
            ("loop_decode_walk_roofline", "%", "device_trace", "kernels",
             "higher"),
            ("loop_cache_share_of_decode_bytes", "%", "program_counter",
             "paged KV", "lower")):
        new = spec.find(bench["per_layer"], name, "metric")
        assert (new["workloads"], new["moves"], new["unit"], new["source"],
                new["layer"], new["better"]) == (
            [REAL], "gen_tokens_per_s_per_chip", unit, source, layer,
            better)
    _cell, _config, _traffic, limits = spec.load_cell(bench, REAL)
    assert list(limits) == ["served_gap_per_close_call"]


def test_the_configuration_is_the_published_one_whole():
    import json
    config = real_config()
    row, = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Ouro-2.6B"' in line] or [None]
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    if row is not None:         # the catalog, where this machine has it
        assert {k: config[k] for k in row["config"]} == row["config"]
    assert config["dtype"] == "bfloat16"
    assert config["control_precision"] == "float8_e4m3"
    for said in ("one chip serves the whole model", "no layer shared",
                 "nothing cut"):
        assert said in config["deployment"]
    assert {"initializer_range", "no_attention_bias", "no_qk_norm",
            "sandwich_norm_order", "final_norm_fed_back", "exit_gate_form",
            "rotary_pairs", "prompt_ids", "decoding",
            "prefill_chunk"} <= set(config["assumed"])
    assert all(len(a["why"]) > 20 for a in config["assumed"].values())
    assert config["assumed"]["prefill_chunk"]["value"] == 256
    from benchmarks.families import ouro as family
    program = family.program_config(config)
    assert (program["units"], program["num_heads"], program["hidden"],
            program["loop_steps"], program["vocab_size"],
            program["num_layers"], program["exit_threshold"]) == (
                2048, 16, 5632, 4, 49152, 48, 1.0)
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_reasoning_answers.json"))
    lens = traffic["prompt_lens"]
    assert lens == [round(64 * 4 ** (i / 7)) for i in range(8)] == [
        64, 78, 95, 116, 141, 172, 210, 256]
    assert sum(lens) == 1132
    # most lengths are no multiple of a block of 32
    assert sum(n % 32 != 0 for n in lens) >= 5
    assert (traffic["new_tokens"], traffic["cache_max_len"],
            traffic["ring_calls"], traffic["checked_rows"],
            traffic["warm_new_tokens"], traffic["first_token_id"]) == (
                256, 512, 4, 8, 2, 1000)
    assert max(lens) + traffic["new_tokens"] == traffic["cache_max_len"]
    assert max(lens) - 1 <= config["assumed"]["prefill_chunk"]["value"]
    assert traffic["runner"] == "benchmarks.runners.generate_loop_calls"
