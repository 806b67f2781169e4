"""EvaByte on the generation path, at a toy size on the CPU (hidden 64, 4
heads of 16, a window of 32 positions in chunks of 4, 3 layers, 320 bytes,
8 prediction heads, float32): prefill through the grouped cache in window
chunks and then decoding across two closings against the plain reference's
full forward on every head's logits; EVA's two identities against an
independent plain causal attention; prompts of awkward lengths; two slots
of different lengths in one decode step, one closing its window and one
not; the engine's call with its ``eva`` tallies and ``gen.window_close``
spans.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import evabyte as family
from benchmarks.reference import evabyte as reference
from incubator_mxnet_tpu.generate import EvaPagedLM, GenerateEngine
from incubator_mxnet_tpu.generate.engine import prefill_slot, step_slots
from incubator_mxnet_tpu.models import eva_byte
from incubator_mxnet_tpu.telemetry import tracing

# float32 on both sides, every product at `highest`: the two differ in the
# order of their sums alone (a cache's two-part softmax against one dense
# softmax; chunks of a window against a whole sequence), which moves a
# logit of size 10 by some 1e-5; a summary or a row wrongly seen moves it
# by 1e-2 and more
TOLERANCE = 1e-4


def toy(**over):
    cfg = {"vocab_size": 320, "hidden_size": 64, "num_hidden_layers": 3,
           "num_attention_heads": 4, "intermediate_size": 96,
           "num_pred_heads": 8, "window_size": 32, "chunk_size": 4,
           "rms_norm_eps": 1e-5, "rope_theta": 1e5,
           "max_position_embeddings": 512, "dtype": "float32",
           "init_std": 0.2,
           "assumed": {"pool_vector_range": {"value": 4.0},
                       "prefill_chunk": {"value": 32}}}
    cfg.update(over)
    return cfg


def build(cfg, seed=5, slots=2, max_len=256):
    weights = reference.init_weights(cfg, seed)
    model = EvaPagedLM(weights, family.program_config(cfg), dtype="float32")
    return weights, model, model.make_cache(slots, max_len=max_len)


def through_the_cache(model, cache, slot, tokens, prompt, chunk):
    """Prefill `tokens[:prompt - 1]` in chunks, then feed the rest a step
    at a time -> every head's logits at the positions prompt - 1 ...
    len(tokens) - 2, (n, heads * V)."""
    got = []
    with jax.default_matmul_precision("highest"):
        prefill_slot(model, cache, slot, tokens[:prompt - 1], chunk)
        for t in range(prompt - 1, len(tokens) - 1):
            step_slots(model, cache, [slot],
                       np.asarray([[tokens[t]]], np.int32))
            got.append(model.last_pred_logits[0, -1].reshape(-1))
    return np.stack(got)


def reference_logits(weights, cfg, tokens, first):
    at = np.arange(first, len(tokens) - 1)[None]
    return reference.logits(weights, cfg, np.asarray(tokens)[None], at,
                            all_heads=True)[0]


@pytest.mark.parametrize("prompt,new,chunk", [
    (45, 70, 32),       # the issue's case: two closings while decoding
    (32, 9, 32),        # a prompt of one whole window: its first step closes
    (33, 40, 32),       # the prefill's last chunk closes, decoding opens one
    (51, 20, 8),        # chunks of a quarter window find live window rows
    (7, 30, 32),        # shorter than a chunk, than a window
], ids=["two_closings", "whole_window", "window_and_one", "quarter_chunks",
        "short"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        prompt, new, chunk):
    cfg = toy()
    weights, model, cache = build(cfg)
    tokens = np.random.default_rng(prompt).integers(64, 320, prompt + new)
    slot = cache.alloc()
    got = through_the_cache(model, cache, slot, tokens, prompt, chunk)
    want = reference_logits(weights, cfg, tokens, prompt - 1)
    assert got.shape == want.shape == (new, 8 * 320)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOLERANCE
    # the cache holds what the equations say: the live window's rows and
    # a summary a chunk of every closed window
    held = len(tokens) - 1
    assert cache.lengths[slot] == held
    assert cache.group_lengths("window")[slot] == held % 32
    assert cache.group_lengths("summary")[slot] == held // 32 * 8
    assert len(cache.table(slot, "window")) == 1    # reused in place


# ------------------------------------------------------- the two identities
def plain_causal_logits(weights, cfg, tokens):
    """An independent plain decoder: ordinary causal softmax attention
    over the whole sequence, every position's logits, float32."""
    H = cfg["num_attention_heads"]
    T, d = len(tokens), cfg["hidden_size"] // H
    w = {n: np.asarray(a, np.float64) for n, a in weights.items()}

    def norm(x, g):
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True)
                           + cfg["rms_norm_eps"]) * (1 + g)

    def rope(x):
        half = d // 2
        ang = np.arange(T)[:, None, None] * cfg["rope_theta"] ** (
            -np.arange(half) / half)
        a, b = x[..., :half], x[..., half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1)
    x = w["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        a = norm(x, w[p + "attn_norm"])
        q, k, v = [(a @ w[p + n]).reshape(T, H, d)
                   for n in ("q_w", "k_w", "v_w")]
        s = np.einsum("qhd,khd->hqk", rope(q), rope(k)) * d ** -0.5
        s = np.where(np.tril(np.ones((T, T), bool))[None], s, -np.inf)
        prob = np.exp(s - s.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        x = x + np.einsum("hqk,khd->qhd", prob, v).reshape(T, -1) @ w[
            p + "o_w"]
        m = norm(x, w[p + "ffn_norm"])
        gate = m @ w[p + "gate_w"]
        x = x + (gate / (1 + np.exp(-gate)) * (m @ w[p + "up_w"])) @ w[
            p + "down_w"]
    return norm(x, w["final_norm"]) @ w["head"]


@pytest.mark.parametrize("over,length", [
    ({}, 32),                                   # n <= w: one window
    ({"chunk_size": 1}, 75),                    # c = 1: a summary is a token
    ({"chunk_size": 1, "window_size": 8}, 41),
], ids=["one_window", "chunk_of_one", "chunk_of_one_short_windows"])
def test_evas_identities_with_plain_causal_attention(over, length):
    """With every position in one window EVA is causal softmax attention;
    with chunks of one position a summary IS its token, whatever mu and
    phi: both the reference and the program through its cache agree with
    an independent plain decoder."""
    cfg = toy(**over)
    weights, model, cache = build(cfg)
    tokens = np.random.default_rng(length).integers(64, 320, length)
    want = plain_causal_logits(weights, cfg, tokens)
    assert np.abs(want).max() > 1.0
    ref = reference.logits(weights, cfg, tokens[None],
                           np.arange(length)[None], all_heads=True)[0]
    assert np.abs(ref - want).max() < TOLERANCE
    prompt = length // 2
    got = through_the_cache(model, cache, cache.alloc(), tokens, prompt,
                            cfg["window_size"])
    assert np.abs(got - want[prompt - 1:-1]).max() < TOLERANCE


def test_summaries_are_seen_once_their_window_has_closed_never_before():
    """The reference's logits at a position of window 1 move when a byte
    of window 0 changes, and those of window 0's later positions do not
    see window 0's own summaries: with mu and phi replaced, only the
    positions past the first window move."""
    cfg = toy()
    weights = reference.init_weights(cfg, 3)
    tokens = np.random.default_rng(0).integers(64, 320, 60)[None]
    at = np.arange(60)[None]
    base = reference.logits(weights, cfg, tokens, at)
    other = dict(weights)
    for i in range(cfg["num_hidden_layers"]):
        other["l%d_mu" % i] = -weights["l%d_mu" % i]
        other["l%d_phi" % i] = -weights["l%d_phi" % i]
    moved = np.abs(reference.logits(other, cfg, tokens, at) - base).max(-1)[0]
    assert moved[:32].max() == 0.0 and moved[32:].min() > 1e-3


# ------------------------------------------------ two slots in one step
def test_two_slots_of_different_lengths_one_closing_its_window_one_not():
    cfg = toy()
    weights, model, cache = build(cfg)
    rng = np.random.default_rng(11)
    rows = [rng.integers(64, 320, n) for n in (30 + 6, 41 + 6)]
    prompts = (30, 41)      # the first row's third step fills its window
    slots = [cache.alloc(), cache.alloc()]
    got = [[], []]
    closed = []
    with jax.default_matmul_precision("highest"):
        for r in (0, 1):
            prefill_slot(model, cache, slots[r], rows[r][:prompts[r] - 1],
                         32)
        for step in range(6):
            tokens = np.asarray([[rows[r][prompts[r] - 1 + step]]
                                 for r in (0, 1)], np.int32)
            before = cache.group_lengths("summary")[slots].copy()
            step_slots(model, cache, slots, tokens)
            closed.append((cache.group_lengths("summary")[slots]
                           - before).tolist())
            for r in (0, 1):
                got[r].append(model.last_pred_logits[r, -1].reshape(-1))
    # one closing, of the first row alone, at its third step
    assert closed == [[0, 0], [0, 0], [8, 0], [0, 0], [0, 0], [0, 0]]
    assert cache.group_lengths("window")[slots].tolist() == [3, 46 - 32]
    for r in (0, 1):
        want = reference_logits(weights, cfg, rows[r], prompts[r] - 1)
        assert np.abs(np.stack(got[r]) - want).max() < TOLERANCE


# ------------------------------------------------------- the decode launch
@pytest.mark.parametrize("S,H,D,block,blocks,tile,lengths", [
    (3, 4, 128, 16, 5, 32, [37, 0, 80]),    # a sequence with no row
    (2, 2, 128, 8, 4, 8, [32, 1]),          # a tile a block, a full table
    (4, 8, 128, 16, 3, 64, [0, 0, 48, 5]),  # a tile wider than the table
], ids=["ragged", "full_and_one", "wide_tile"])
def test_the_decode_launch_walks_each_sequences_own_rows(S, H, D, block,
                                                         blocks, tile,
                                                         lengths):
    """``paged_heads_decode`` in interpret mode against a dense softmax's
    state over each sequence's first `lengths` rows: head h's query meets
    the lanes of head h alone; a table's padding is never read."""
    from incubator_mxnet_tpu.ops.pallas import paged_heads
    rng = np.random.default_rng(S * H)
    pools = [jnp.asarray(rng.normal(size=(S * blocks, block, H * D)),
                         jnp.float32) for _ in "kv"]
    tables = rng.permutation(S * blocks).reshape(S, blocks).astype(np.int32)
    lengths = np.asarray(lengths, np.int32)
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    m, l, acc = paged_heads.paged_heads_decode(
        q, *pools, tables, lengths, scale=D ** -0.5, key_tile=tile,
        interpret=True)
    assert m.shape == l.shape == (S, H) and acc.shape == (S, H, D)
    for s, n in enumerate(lengths):
        k, v = [np.asarray(p)[tables[s]].reshape(-1, H, D)[:n]
                for p in pools]
        if n == 0:
            assert (np.asarray(m[s]) == -1e30).all()
            assert not np.asarray(l[s]).any() and not np.asarray(acc[s]).any()
            continue
        scores = np.einsum("hd,thd->ht", np.asarray(q[s]), k) * D ** -0.5
        p = np.exp(scores - scores.max(-1, keepdims=True))
        assert np.allclose(m[s], scores.max(-1), atol=1e-5)
        assert np.allclose(l[s], p.sum(-1), atol=1e-4)
        assert np.allclose(acc[s], np.einsum("ht,thd->hd", p, v), atol=1e-4)
    blocked = paged_heads.block_diagonal(q)
    assert blocked.shape == (S, H, H * D)
    assert np.array_equal(paged_heads.own_blocks(blocked.astype(jnp.float32)),
                          q)
    assert float(jnp.abs(blocked).sum()) == pytest.approx(
        float(jnp.abs(q).sum()), rel=1e-6)
    pool = jax.ShapeDtypeStruct((4, 16, H * D), jnp.bfloat16)
    assert paged_heads.paged_heads_decode_available(pool, H) is False  # a CPU


def test_prefill_and_decoding_on_the_launches_are_the_references_forward():
    """The adapter with ``interpret=True`` takes a chunk that opens its
    window through the tiled flash forward and walks both groups by the
    decode launches (heads of 128 lanes, as they need): two prefill
    chunks, the second over the first's summaries, then decoding, against
    the reference on every head's logits, and against the ``lax`` path."""
    cfg = toy(hidden_size=256, num_attention_heads=2, window_size=128,
              chunk_size=16)
    cfg["assumed"]["prefill_chunk"]["value"] = 128
    weights = reference.init_weights(cfg, 7)
    tokens = np.random.default_rng(1).integers(64, 320, 151 + 6)
    got = []
    for interpret in (True, False):
        model = EvaPagedLM(weights, family.program_config(cfg),
                           dtype="float32", interpret=interpret)
        cache = model.make_cache(1, max_len=256)
        got.append(through_the_cache(model, cache, cache.alloc(), tokens,
                                     151, 128))
        assert cache.group_lengths("summary")[0] == 8
    want = reference_logits(weights, cfg, tokens, 150)
    assert np.abs(got[0] - want).max() < TOLERANCE
    assert np.abs(got[0] - got[1]).max() < TOLERANCE


# ------------------------------------------------------------ the engine
def test_a_generate_call_closes_windows_and_tallies_them():
    """``GenerateEngine.generate`` over the adapter: the plain loop with
    the token head, greedy ids that are the reference's argmax of head 0,
    ``last_stats["eva"]`` by phase and a ``gen.window_close`` span a
    closing under the region that filled the window."""
    cfg = toy()
    weights, model, cache = build(cfg, slots=3, max_len=128)
    engine = GenerateEngine(model, cache, prefill_chunk=32, name="eva_toy")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(64, 320, n).tolist() for n in (61, 20, 70)]
    tracing.clear_spans()
    with jax.default_matmul_precision("highest"):
        with tracing.Span("test.eva_call"):
            served = engine.generate(prompts, max_new_tokens=8)
    stats = engine.last_stats
    assert stats["decode_steps"] == 8
    assert stats["decode_steps_fed_on_device"] == 7
    eva = stats["eva"]
    # prefill commits 60, 19, 69 positions in 2 + 1 + 3 chunks of 32:
    # three rounds of three rows; windows close at 32 (row 0) and at 32,
    # 64 (row 2)
    assert stats["prefill_forwards"] == eva["prefill"]["forwards"] == 3
    assert eva["prefill"]["windows_closed"] == 3
    assert eva["prefill"]["summary_rows_written"] == 24
    # decode: row 0 closes when it commits position 63 (its 4th step)
    assert eva["decode"]["forwards"] == 8
    assert eva["decode"]["windows_closed"] == 1
    assert eva["decode"]["window_rows_read"] == sum(
        (n - 1 + s) % 32 + 1 for n in (61, 20, 70) for s in range(8)) + 32
    assert eva["decode"]["summary_rows_read"] == sum(
        (n - 1 + s) // 32 * 8 for n in (61, 20, 70) for s in range(8))
    assert eva["decode"]["positions"] == sum(
        n + s for n in (61, 20, 70) for s in range(8))
    closings = [s for s in tracing.recent_spans()
                if s["name"] == "gen.window_close"]
    assert [(s["phase"], s["slots"], s["rows_written"]) for s in closings] \
        == [("prefill", 1, 8)] * 3 + [("decode", 1, 8)]
    by_id = {s["span_id"]: s["name"] for s in tracing.recent_spans()}
    assert [by_id[s["parent_id"]] for s in closings] \
        == ["gen.prefill_round"] * 3 + ["gen.decode_step"]
    for prompt, out in zip(prompts, served):
        sequence = np.asarray(prompt + out)
        ref = reference.logits(weights, cfg, sequence[None], np.arange(
            len(prompt) - 1, len(sequence) - 1)[None])[0]
        assert ref.shape == (8, 320)
        assert out == ref.argmax(-1).tolist()
    assert cache.in_use == 0 and cache.blocks_in_use == 0


def test_the_engine_refuses_a_chunk_that_would_straddle_a_closing():
    cfg = toy()
    _weights, model, cache = build(cfg)
    with pytest.raises(ValueError, match="must divide the model's window"):
        GenerateEngine(model, cache, prefill_chunk=24)
    with pytest.raises(ValueError, match="takes no draft model"):
        GenerateEngine(model, cache, draft=model, draft_cache=cache,
                       prefill_chunk=32)
    GenerateEngine(model, cache, prefill_chunk=16)


def test_the_parameter_shapes_and_the_pooling():
    cfg = eva_byte.eva_config(family.program_config(toy()))
    shapes = eva_byte.eva_param_shapes(cfg)
    assert shapes["head"] == (64, 8 * 320) and shapes["l2_mu"] == (4, 16)
    assert {n: tuple(a.shape) for n, a in reference.init_weights(
        toy(), 1).items()} == shapes
    # one row a chunk pools to itself; equal scores pool to the mean
    k = jnp.asarray(np.random.default_rng(0).normal(size=(8, 2, 4)),
                    jnp.float32)
    zero = jnp.zeros((2, 4), jnp.float32)
    sk, sv = eva_byte.summarize_chunks(k, 2 * k, zero, zero, 4)
    assert np.allclose(sk, np.asarray(k).reshape(2, 4, 2, 4).mean(1),
                       atol=1e-6)
    assert np.allclose(sv, 2 * np.asarray(sk), atol=1e-6)
    one_k, one_v = eva_byte.summarize_chunks(k, 2 * k, zero + 3.0, zero, 1)
    assert np.allclose(one_k, k) and np.allclose(one_v, 2 * k)
