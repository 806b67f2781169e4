"""The file-completion cell's harness on the CPU, at a toy size that only
these tests reach (``BENCHMARK_eva_tiny.json``: the ``evabyte`` family at 3
layers, hidden 64, 4 heads of 16, a window of 32 positions in chunks of 4,
320 bytes, 8 prediction heads, float32 so that a sound run reads next to
nothing on any CPU): a sound run, a traced run in which every listed
reader a CPU can read returns a number, the control and four planted
faults of a cache of windows and summaries, the new readers on hand-made
facts, ``costs_evabyte.py`` against counts by hand, the configuration as
published, and the real benchmark as it stands.
"""

import os

import numpy as np
import pytest

from benchmarks import compare, costs_evabyte, run, spec
from benchmarks.peaks import peaks_for
from benchmarks.runners import generate_byte_calls

ROOT = spec.ROOT
CELL = "eva_tiny.generate_bytes_tiny"
REAL = "evabyte_6_5b.generate_file_completions"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 77         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_eva_tiny.json"))


def drive(bench, trace=False, seed=SEED, seconds=0.3, root=ROOT):
    import jax
    return run.drive(bench, CELL, seed, seconds, trace, jax.devices(),
                     root=root, peaks=PEAKS)


def reader(name):
    return spec.load_reader(spec.load_benchmark(), name)


def real_config():
    return spec.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       "evabyte_6_5b.json"))


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
    readings, the decode launch's roofline: left out, never reported as
    0). The toy cell lists what the
    real cell lists. The run keeps its trace under a root of its own."""
    for name in ("benchmarks", "tests"):
        os.symlink(os.path.join(ROOT, name), str(tmp_path / name))
    result = drive(bench, trace=True, root=str(tmp_path))
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", CELL)}
    assert listed | {"compile_s"} == {m["name"] for m in spec.metrics_of(
        spec.load_benchmark(), "per_layer", REAL)}
    assert set(result["metrics"]) == listed - {
        "gen_device_idle_share", "gen_peak_hbm_bytes",
        "paged_heads_decode_roofline",
        "gen_idle_prefill_ms_per_prompt", "gen_idle_decode_ms_per_forward",
        "gen_idle_fetch_tail_ms_per_forward",
        "gen_idle_outside_regions_share"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    # contexts of 37-107 positions over windows of 32 and 8 summaries a
    # closed window: between the live window alone and every row
    assert 0.2 < values["eva_rows_read_per_position"] < 0.7
    assert values["eva_window_close_ms"] > 0
    assert 0 < values["prefill_mfu"] < 100 and 0 < values["gen_mfu"] < 100
    # two lengths, a window table of one block and a summary table of
    # four a row, 6 rows, 4 B each: no token crosses
    assert values["kv_h2d_bytes_per_step"] == 6 * 4 * (2 + 1 + 4)
    assert values["decode_step_p50_ms"] > values["gen_step_self_ms"] > 0
    assert values["lm_fetch_wait_ms_per_step"] > 0
    assert 0 < values["prefill_share"] < 100
    assert result["correct"] is True
    assert not os.path.exists(str(tmp_path / ".bench_trace" / CELL))


# ------------------------------------------------------------------ faults
def _summaries_left_out_of_the_softmax(monkeypatch):
    """A query scores its window and itself alone."""
    from incubator_mxnet_tpu.models import eva_byte
    right = eva_byte.attend

    def faulty(q, parts):
        return right(q, parts[1:])      # the summaries are the first part
    monkeypatch.setattr(eva_byte, "attend", faulty)


def _summaries_seen_one_window_early(monkeypatch):
    """A query sees the whole chunks of its OWN window through their
    summaries too, beside the exact rows: the forward is handed summary
    pools that hold, after the closed windows' rows, the pooled chunks of
    the live window."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import eva_byte
    right = eva_byte.eva_forward_paged

    def faulty(params, cfg, tokens, wlen, wtables, slen, stables, wk, wv,
               sk, sv, **kw):
        chunk, H = cfg["chunk"], cfg["num_heads"]
        wlen, slen = jnp.asarray(wlen), jnp.asarray(slen)
        early = wlen // chunk
        new_sk, new_sv = [], []
        for i in range(cfg["num_layers"]):
            p = "l%d_" % i
            ek, ev = eva_byte.summarize_chunks(
                eva_byte._paged_rows(wk[i], wtables, H),
                eva_byte._paged_rows(wv[i], wtables, H),
                params[p + "mu"], params[p + "phi"], chunk)
            both = []
            for closed, added in (
                    (eva_byte._paged_rows(sk[i], stables, H), ek),
                    (eva_byte._paged_rows(sv[i], stables, H), ev)):
                rows = jnp.concatenate([closed, added], axis=1)
                # row j: the closed windows' first, then the early ones
                j = jnp.arange(rows.shape[1])[None]
                take = jnp.where(j < slen[:, None], j,
                                 closed.shape[1] + j - slen[:, None])
                take = jnp.clip(take, 0, rows.shape[1] - 1)
                rows = jnp.take_along_axis(rows, take[:, :, None, None],
                                           axis=1)
                both.append(rows.reshape(rows.shape[:2] + (-1,)))
            new_sk.append(both[0])
            new_sv.append(both[1])
        # a sequence's rows are its own "block" of the handed pools
        tables = jnp.arange(len(new_sk[0]), dtype=jnp.int32)[:, None]
        kw["fresh"] = False
        return right(params, cfg, tokens, wlen, wtables, slen + early,
                     tables, wk, wv, new_sk, new_sv, **kw)
    monkeypatch.setattr(eva_byte, "eva_forward_paged", faulty)


def _mu_used_where_phi_belongs(monkeypatch):
    """A window's values pooled with the keys' weights."""
    from incubator_mxnet_tpu.models import eva_byte
    right = eva_byte.summarize_chunks

    def faulty(k, v, mu, phi, chunk):
        return right(k, v, mu, mu, chunk)
    monkeypatch.setattr(eva_byte, "summarize_chunks", faulty)


def _window_length_not_reset_at_a_closing(monkeypatch):
    """A forward reads a window that has closed as still full: the rows
    of the closed window beside their summaries, the new rows written over
    them from row 0. (The cache itself stores nothing past a full window,
    so the stale length is planted where it would go unseen: in what the
    forward is told.)"""
    from incubator_mxnet_tpu.generate import engine
    right = engine.EvaPagedLM._call

    def faulty(self, head, tokens, wlen, wtables, slen, *rest):
        stale = np.where(slen > 0, self.window, wlen).astype(wlen.dtype)
        return right(self, head, tokens, stale, wtables, slen, *rest)
    monkeypatch.setattr(engine.EvaPagedLM, "_call", faulty)


@pytest.mark.parametrize("fault", [
    _summaries_left_out_of_the_softmax, _summaries_seen_one_window_early,
    _mu_used_where_phi_belongs, _window_length_not_reset_at_a_closing],
    ids=["summaries_left_out", "summaries_one_window_early",
         "mu_for_phi", "window_length_not_reset"])
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
    (_i, _seed, row), = generate_byte_calls.calibrate(ctx, [4], 1)
    assert compare.judge(row["program"], limits)[0] is True
    assert compare.judge(row["control_float8_e4m3"], limits)[0] is False
    assert row["positions"] == 6 * 12
    assert row["stats"]["eva"]["decode"]["forwards"] == 12
    assert row["distinct_served"] > 12      # the rows do not lock on a byte


def test_a_program_without_the_adapter_is_refused_before_any_weight(
        monkeypatch):
    """What the parent commit does with the cell: ``require_program``
    imports ``EvaPagedLM`` and raises at once."""
    from benchmarks.families import evabyte
    from incubator_mxnet_tpu import generate
    evabyte.require_program()
    monkeypatch.delattr(generate, "EvaPagedLM")
    with pytest.raises(ImportError, match="EvaPagedLM"):
        evabyte.require_program()


# ----------------------------------------------------------------- readers
def test_the_new_readers_on_hand_made_facts():
    read = reader("eva_rows_read_per_position")
    assert read({"eva": {"decode": {"window_rows_read": 600,
                                    "summary_rows_read": 400,
                                    "positions": 5000}}}) == 0.2
    assert read({"eva": {"decode": {"window_rows_read": 0,
                                    "summary_rows_read": 0,
                                    "positions": 0}}}) is None
    # a program without the tallies: the parent's line leaves it out
    assert read({"mla": {"absorbed_forwards": 3}}) is None
    assert read({}) is None
    read = reader("eva_window_close_ms")
    assert read({}) is None and read({"trace": None}) is None
    # the launch's share: 1,000 cache rows a layer are 8 x 1,000 x 16,384 B
    # at the HBM peak, 0.16 ms, over the launches' 0.4 ms
    from benchmarks.trace_reduce import Event
    read = reader("paged_heads_decode_roofline")
    ops = [Event("/device:TPU:0", "XLA Ops", "paged_heads_decode.%d" % i,
                 1000.0 + i * 1e6, 1e5) for i in range(4)]
    ops.append(Event("/device:TPU:0", "XLA Ops", "fusion.3", 50.0, 7e5))
    facts = {"trace": {"ops": {"/device:TPU:0": ops}, "window": (0, 1e7)},
             "config": real_config(), "peaks": peaks_for("TPU v5 lite"),
             "traffic": {"prompt_lens": [0] * 24},
             "traced_eva": {"decode": {
                 "forwards": 2, "windows_closed": 1,
                 "window_rows_read": 700 + 2048 + 48,
                 "summary_rows_read": 300}}}
    assert read(facts) == pytest.approx(
        100 * (8 * 1000 * 16_384 / 819e9) / 4e-4)
    assert 30 < read(facts) < 50
    assert read(dict(facts, traced_eva=None)) is None
    facts["trace"]["ops"] = {"/device:TPU:0": ops[-1:]}     # the lax gather
    assert read(facts) is None


# ------------------------------------------------------------------- costs
def test_the_costs_agree_with_counts_by_hand():
    cfg = real_config()
    # ISSUE 39, "Sizing"
    assert costs_evabyte.layer_products(cfg) == (
        4 * 4096 ** 2 + 3 * 4096 * 11008) == 202_375_168
    assert costs_evabyte.layer_params(cfg) == 202_391_552
    assert costs_evabyte.head_params(cfg) == 4096 * 2560
    assert costs_evabyte.param_count(cfg) == (
        8 * 202_391_552 + 320 * 4096 + 4096 * 2560 + 4096
    ) == 1_630_932_992 == cfg["param_count"]
    assert "1,630,932,992 parameters, 3.26 GB" in cfg["deployment"]
    from benchmarks.families import evabyte
    from incubator_mxnet_tpu.models import eva_byte
    shapes = eva_byte.eva_param_shapes(eva_byte.eva_config(
        evabyte.program_config(cfg)))
    assert sum(int(np.prod(s)) for s in shapes.values()) == 1_630_932_992
    assert costs_evabyte.cache_bytes_per_row(cfg) == 16_384
    assert costs_evabyte.summaries_per_window(cfg) == 128
    # the pools the deployment states
    assert 24 * 8 * (2048 + 768) * 16_384 == 8_858_370_048
    assert "= 8.86 GB" in cfg["deployment"]
    assert costs_evabyte.rows_read(cfg, 1) == (1, 0)
    assert costs_evabyte.rows_read(cfg, 2048) == (2048, 0)
    assert costs_evabyte.rows_read(cfg, 2049) == (1, 128)
    assert costs_evabyte.rows_read(cfg, 7400) == (7399 % 2048 + 1, 384)
    # a decode step of 24 rows that reads 24 x 1,024 exact rows and 24 x
    # 450 summaries a layer: every layer's weights, the 8 heads, the final
    # offset, 24 embeddings, and 16,384 B a cache row and layer: 7.9 GB
    v5e = peaks_for("TPU v5 lite")
    read = 8 * 202_391_552 + 4096 * 2560 + 4096 + 24 * 4096
    floor = costs_evabyte.decode_step_floor_seconds(
        cfg, 24, 24 * 1024, 24 * 450, v5e)
    cached = 8 * 24 * (1024 + 450) * 16_384
    assert floor == pytest.approx((read * 2 + cached) / 819e9)
    assert 4.6e9 < cached < 4.7e9 and 7.8e9 < read * 2 + cached < 8.0e9
    # where the operations take longer they are the floor: a token's
    # products in all 8 layers and the head, a score and a value a cache
    # row over the 4,096 head dimensions
    slow = dict(v5e, bf16_flops_per_s=1e12)
    assert costs_evabyte.decode_step_floor_seconds(
        cfg, 24, 24 * 1024, 24 * 450, slow) == pytest.approx(2 * (
            24 * (8 * 202_375_168 + 4096 * 2560)
            + 8 * 24 * (1024 + 450) * 2 * 4096) / 1e12)
    # prefill, every layer whole: a prompt of 5,000 positions is two
    # windows and 904; the causal half of each, 128 summaries for the
    # second window's queries and 256 for the rest's, two closings
    pairs = (2 * 2048 * 2049 // 2 + 904 * 905 // 2
             + 2048 * 128 + 904 * 256)
    assert costs_evabyte.prefill_flops(cfg, [5000, 10]) == 2.0 * 8 * (
        5010 * 202_375_168 + (pairs + 55) * 2 * 4096
        + 2 * 2048 * 4 * 4096)
    # a call of the cell: the issue's 0.56 PFLOP of products
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_file_completions.json"))
    call = costs_evabyte.prefill_flops(
        cfg, [n - 1 for n in traffic["prompt_lens"]])
    assert 0.55e15 < 2.0 * 8 * 202_375_168 * 172_008 < 0.56e15 < call \
        < 0.60e15


# ------------------------------------------------------- the real benchmark
def test_the_real_benchmark_as_it_stands_with_the_file_completion_cell():
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
        "evabyte_6_5b", "generate_file_completions")
    assert len(cells) >= 7
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(
        1, len(cells) // 4)
    assert all(len(c["why"]) <= 200 for c in cells.values())
    configs = {c["name"]: c for c in bench["configs"]}
    assert "evabyte_6_5b" in configs
    assert configs["evabyte_6_5b"]["reduced"] == ["num_hidden_layers"]
    assert configs["evabyte_6_5b"]["source"] == \
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    assert len(configs["evabyte_6_5b"]["why"]) <= 200
    assert [m["name"] for m in spec.metrics_of(bench, "end_to_end", REAL)] \
        == ["gen_tokens_per_s_per_chip", "setup_s"]
    listed = {m["name"] for m in spec.metrics_of(bench, "per_layer", REAL)}
    assert {"eva_rows_read_per_position", "eva_window_close_ms", "gen_mfu",
            "prefill_mfu", "decode_step_p50_ms", "prefill_share",
            "gen_device_idle_share", "gen_peak_hbm_bytes",
            "gen_step_self_ms", "lm_dispatch_ms_per_step",
            "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step",
            "kv_h2d_bytes_per_step", "gen_idle_prefill_ms_per_prompt",
            "gen_idle_decode_ms_per_forward",
            "gen_idle_fetch_tail_ms_per_forward",
            "gen_idle_outside_regions_share", "compile_s",
            "paged_heads_decode_roofline"} <= listed
    assert not any(name.startswith(("moe_", "mla_", "block_"))
                   for name in listed)
    for name in listed:
        assert callable(spec.load_reader(bench, name))
    for name, unit, source, layer, better in (
            ("eva_rows_read_per_position", "rows/position",
             "program_counter", "paged KV", "lower"),
            ("eva_window_close_ms", "ms", "program_span", "generate",
             "lower"),
            ("paged_heads_decode_roofline", "%", "device_trace", "kernels",
             "higher")):
        new = spec.find(bench["per_layer"], name, "metric")
        assert (new["workloads"], new["moves"], new["unit"], new["source"],
                new["layer"], new["better"]) == (
            [REAL], "gen_tokens_per_s_per_chip", unit, source, layer,
            better)
    _cell, _config, _traffic, limits = spec.load_cell(bench, REAL)
    assert list(limits) == ["served_gap_per_close_call"]


def test_the_configuration_is_the_published_one_but_for_its_depth():
    config = real_config()
    row = {"attention_bias": False, "attention_class": "eva",
           "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
           "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
           "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
           "intermediate_size": 11008, "lazy_init": True,
           "max_position_embeddings": 32768, "max_seq_length": 32768,
           "mixedp_attn": True, "model_type": "evabyte",
           "norm_add_unit_offset": True, "num_attention_heads": 32,
           "num_chunks": None, "num_key_value_heads": 32,
           "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
           "rope_theta": 100000, "tie_word_embeddings": False,
           "vocab_size": 320, "window_size": 2048}
    assert {k: config[k] for k in row} == row
    assert config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["dtype"] == "bfloat16"
    assert config["control_precision"] == "float8_e4m3"
    for said in ("first of four pipeline stages", "no layer shared",
                 "all 8 prediction heads"):
        assert said in config["deployment"]
    assert {"pool_scores_scaled", "pool_vector_range",
            "summaries_pool_rotated_keys", "rotary_pairs",
            "head_column_order", "prompt_ids", "decoding",
            "no_image_input", "prefill_chunk"} <= set(config["assumed"])
    assert all(len(a["why"]) > 20 for a in config["assumed"].values())
    assert config["assumed"]["prefill_chunk"]["value"] == 2048
    from benchmarks.families import evabyte
    program = evabyte.program_config(config)
    assert (program["units"], program["num_heads"], program["hidden"],
            program["window"], program["chunk"], program["pred_heads"],
            program["vocab_size"], program["num_layers"]) == (
                4096, 32, 11008, 2048, 16, 8, 320, 8)
    traffic = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "generate_file_completions.json"))
    lens = traffic["prompt_lens"]
    assert lens == [round(2048 + i * 10240 / 23) for i in range(24)]
    assert (min(lens), max(lens), len(lens), sum(lens)) == (
        2048, 12288, 24, 172_032)
    # not multiples of a chunk save by chance (the range's two ends)
    assert [n for n in lens if n % 16 == 0] == [2048, 12288]
    # every call closes windows while it decodes: a prompt that ends
    # within 256 bytes under a multiple of 2,048 fills its window then
    assert sum(-n % 2048 < 256 for n in lens) >= 3
    assert (traffic["new_tokens"], traffic["cache_max_len"],
            traffic["ring_calls"], traffic["checked_rows"],
            traffic["first_token_id"]) == (256, 12544, 4, 8, 64)
    assert max(lens) + traffic["new_tokens"] == traffic["cache_max_len"]
    assert traffic["runner"] == "benchmarks.runners.generate_byte_calls"
