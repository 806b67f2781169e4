"""Nemotron-H, a hybrid state-space decoder, on the generation path, at a
toy size on the CPU (``tests/benchmark/configs/nemotron_tiny.json``: the
pattern ``MEM*``, hidden 64, 8 SSM heads of 8 in 2 groups, state 16,
scan chunks of 8, 4 query heads over 2 key/value heads, 4 of 8 relu²
experts held, 320 tokens): prefill through the cache and then decoding
against the plain reference's full forward (float32 and bfloat16); the
chunked scan against the sequential recurrence; several padded prompts in
one prefill round; a reused slot; the decode launch in interpret mode
against its ``lax`` path; the held shares of a relu² expert layer against
the uncut layer, and the gated layer as it was; the state entries of the
cache and their guards; the engine's call with its ``ssm`` tallies, spans
and counters.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import nemotron_h as family
from benchmarks.reference import nemotron_h as reference
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.generate import (GenerateEngine, NemotronHPagedLM,
                                          PagedKVCache)
from incubator_mxnet_tpu.generate.engine import (position_bytes,
                                                 prefill_slot, prefill_slots,
                                                 step_slots)
from incubator_mxnet_tpu.models import nemotron_h
from incubator_mxnet_tpu.ops.pallas.ssm_state import (from_heads,
                                                      ssm_state_step,
                                                      to_heads)
from incubator_mxnet_tpu.parallel.moe import moe_dropless
from incubator_mxnet_tpu.telemetry import catalog, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# float32 on both sides, every product at `highest`: the two differ in the
# order of their sums alone (the chunked scan against one position at a
# time, a cache's two-part softmax against one dense softmax), which moves
# a logit of size 6 by some 1e-5; a state not carried across a chunk, or
# a pad let into it, moves it by 1e-1 and more
TOLERANCE = 1e-4
# bfloat16 weights and activations against the float32 reference: four
# layers of rounding to 8 bits of mantissa move a logit of size 6 by some
# 0.05; a fault moves it by 1 and more
BF16_TOLERANCE = 0.15


def toy(**over):
    with open(os.path.join(ROOT, "tests", "benchmark", "configs",
                           "nemotron_tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


def build(cfg, seed=3, interpret=False):
    weights = reference.init_weights(cfg, seed)
    return weights, NemotronHPagedLM(weights, family.program_config(cfg),
                                     dtype=cfg["dtype"], interpret=interpret)


# one model a dtype for the file: its programs compile once a shape
@pytest.fixture(scope="module")
def f32():
    return build(toy())


@pytest.fixture(scope="module")
def bf16(f32):
    """The float32 draws rounded to bfloat16, as ``init_weights`` makes
    them for a bfloat16 configuration."""
    weights = {n: a.astype(jnp.bfloat16) for n, a in f32[0].items()}
    return weights, NemotronHPagedLM(weights, family.program_config(toy(
        dtype="bfloat16")), dtype="bfloat16")


def through_the_cache(model, cache, slot, tokens, prompt, chunk):
    """Prefill `tokens[:prompt - 1]` in chunks, then feed the rest a step
    at a time -> the logits at the positions prompt - 1 ...
    len(tokens) - 2, (n, V)."""
    got = []
    with jax.default_matmul_precision("highest"):
        prefill_slot(model, cache, slot, tokens[:prompt - 1], chunk)
        for t in range(prompt - 1, len(tokens) - 1):
            got.append(step_slots(model, cache, [slot],
                                  np.asarray([[tokens[t]]], np.int32))[0])
    return np.stack(got)


def reference_logits(weights, cfg, tokens, first):
    at = np.arange(first, len(tokens) - 1)[None]
    return reference.logits(weights, cfg, np.asarray(tokens)[None], at)[0]


@pytest.mark.parametrize("dtype,prompt,tolerance", [
    ("float32", 6, TOLERANCE),      # 5 positions: under one scan chunk of 8
    ("float32", 17, TOLERANCE),     # 16: one chunk, two whole scan chunks
    ("float32", 30, TOLERANCE),     # 29: across chunks and scan chunks
    ("bfloat16", 30, BF16_TOLERANCE),
], ids=["under_a_scan_chunk", "one_chunk", "across_chunks", "bfloat16"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        request, dtype, prompt, tolerance):
    weights, model = request.getfixturevalue(
        "f32" if dtype == "float32" else "bf16")
    cfg = toy(dtype=dtype)
    cache = model.make_cache(1, max_len=64)
    tokens = np.random.default_rng(0).integers(0, 320, prompt + 16).tolist()
    got = through_the_cache(model, cache, cache.alloc(), tokens, prompt, 16)
    want = reference_logits(weights, cfg, tokens, prompt - 1)
    assert got.shape == want.shape == (16, 320)
    np.testing.assert_allclose(got, want, atol=tolerance, rtol=0)
    assert np.abs(want).max() > 3       # the logits are not all near 0


def _sequential(x, dt, a, b, c, state):
    """The recurrence one position at a time, numpy float64."""
    S, T, H, _P = x.shape
    per = H // b.shape[2]
    ys = np.zeros(x.shape)
    state = np.array(state, np.float64)
    for t in range(T):
        for h in range(H):
            g = h // per
            state[:, h] = (state[:, h] * np.exp(dt[:, t, h] * a[h])[:, None,
                                                                   None]
                           + (dt[:, t, h][:, None] * x[:, t, h])[:, :, None]
                           * b[:, t, g][:, None, :])
            ys[:, t, h] = np.einsum("spn,sn->sp", state[:, h], c[:, t, g])
    return ys, state


@pytest.mark.parametrize("length", [5, 8, 21], ids=["under", "at", "across"])
def test_the_chunked_scan_is_the_sequential_recurrence(length):
    """The SSD form over sub-chunks of 8, padded positions (dt 0) at the
    end of some rows, from a state that is not zero."""
    rng = np.random.default_rng(length)
    S, H, P, G, N = 3, 4, 8, 2, 16
    x = rng.normal(size=(S, length, H, P))
    dt = rng.uniform(0.001, 0.3, (S, length, H))
    dt[1, length // 2:] = 0.0               # a row padded after its half
    a = -rng.uniform(1, 16, H)
    b, c = (rng.normal(size=(S, length, G, N)) for _ in range(2))
    state = rng.normal(size=(S, H, P, N))
    with jax.default_matmul_precision("highest"):
        y, end = nemotron_h.ssm_scan(*(jnp.asarray(v, jnp.float32) for v in (
            x, dt, a, b, c, state)), 8)
    want_y, want_end = _sequential(x, dt, a, b, c, state)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(end), want_end, atol=1e-4,
                               rtol=1e-4)
    # the padded row's state is the one its last real position left
    _, half = _sequential(x[1:2, :length // 2], dt[1:2, :length // 2], a,
                          b[1:2, :length // 2], c[1:2, :length // 2],
                          state[1:2])
    np.testing.assert_allclose(np.asarray(end)[1:2], half, atol=1e-4,
                               rtol=1e-4)


def _states(cache, slot):
    return [cache.state(n, slot) for n in sorted(cache.spec)
            if cache.spec[n][0] == "state"]


def test_padded_prompts_in_one_prefill_round_move_no_state(f32):
    """Four prompts of different lengths prefilled in rounds of three rows
    (the tail rounds padded with rows of count 0 on a fed slot, and every
    row's last chunk padded after its tokens) leave each slot the state,
    the conv window and the cache rows of that prompt prefilled alone."""
    _weights, model = f32
    cache = model.make_cache(4, max_len=64)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 320, n).tolist() for n in (5, 21, 40, 16)]
    slots = [cache.alloc() for _ in prompts]
    with jax.default_matmul_precision("highest"):
        fed = list(prefill_slots(model, cache, slots, prompts, 16, 3))
    # 7 chunks in rounds of 3: the last round is one prompt and two pads
    assert sum(fed) == sum(map(len, prompts)) and len(fed) == 3
    alone = model.make_cache(1, max_len=64)
    for prompt, slot in zip(prompts, slots):
        one = alone.alloc()
        with jax.default_matmul_precision("highest"):
            prefill_slot(model, alone, one, prompt, 16)
        assert int(cache.lengths[slot]) == len(prompt)
        for got, want in zip(_states(cache, slot), _states(alone, one)):
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(cache.prefix("k3", slot),
                                   alone.prefix("k3", one), atol=1e-5)
        alone.free(one)


def test_a_reused_slot_gives_a_fresh_sequences_logits(f32):
    weights, model = f32
    cache = model.make_cache(1, max_len=64)
    rng = np.random.default_rng(2)
    first, second = (rng.integers(0, 320, 24).tolist() for _ in range(2))
    slot = cache.alloc()
    through_the_cache(model, cache, slot, first, 12, 16)
    cache.free(slot)
    again = cache.alloc()
    got = through_the_cache(model, cache, again, second, 12, 16)
    assert again == slot and cache.state_resets == 2
    np.testing.assert_allclose(got, reference_logits(weights, toy(), second,
                                                     11),
                               atol=TOLERANCE, rtol=0)


def test_the_state_launch_in_interpret_mode_is_the_lax_update():
    """``ssm_state_step`` as one Pallas launch (interpret mode) and on its
    ``lax`` path: the same y, the same rows written, the other rows of the
    pool as they were, a row given dt 0 unchanged."""
    rng = np.random.default_rng(3)
    S, H, P, N, G, slots = 3, 8, 8, 128, 2, 6
    heads = rng.normal(size=(slots, H, P, N))
    pool = from_heads(jnp.asarray(heads, jnp.float32), G)
    assert pool.shape == (slots, 2, N, 32)     # 4 heads of 8 lanes a row
    np.testing.assert_allclose(np.asarray(to_heads(pool, P)), heads,
                               atol=1e-6)
    rows = jnp.asarray([4, 1, 2], jnp.int32)
    x = rng.normal(size=(S, H, P))
    dt = rng.uniform(0, 0.1, (S, H))
    dt[1] = 0.0
    a = -rng.uniform(1, 16, H)
    b, c = (rng.normal(size=(S, G, N)) for _ in range(2))
    y, lax_pool = ssm_state_step(pool, rows, x, dt, a, b, c,
                                 use_kernel=False)
    y_k, kernel_pool = ssm_state_step(pool, rows, x, dt, a, b, c,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel_pool),
                               np.asarray(lax_pool), atol=1e-5)
    kept = np.asarray(pool)
    np.testing.assert_array_equal(np.asarray(kernel_pool)[[0, 3, 5]],
                                  kept[[0, 3, 5]])
    # the row fed dt 0 (slot 1): exp(0) S + 0
    np.testing.assert_allclose(np.asarray(kernel_pool)[1], kept[1], atol=0)
    # and every row as the recurrence one position at a time has it
    want_y, want = _sequential(x[:, None], dt[:, None], a, b[:, None],
                               c[:, None], heads[[4, 1, 2]])
    np.testing.assert_allclose(np.asarray(y_k), want_y[:, 0], atol=1e-4)
    np.testing.assert_allclose(np.asarray(to_heads(kernel_pool, P))[
        [4, 1, 2]], want, atol=1e-4)


# -------------------------------------------------------- the expert layer
def _expert_layer(rng, d=16, experts=8, f=12, fs=20):
    return {"x": jnp.asarray(rng.normal(size=(10, d)), jnp.float32),
            "router": jnp.asarray(rng.normal(size=(d, experts)), jnp.float32),
            "bias": jnp.asarray(0.01 * rng.normal(size=experts), jnp.float32),
            "gate": jnp.asarray(0.3 * rng.normal(size=(experts, d, f)),
                                jnp.float32),
            "up": jnp.asarray(0.3 * rng.normal(size=(experts, d, f)),
                              jnp.float32),
            "down": jnp.asarray(0.3 * rng.normal(size=(experts, f, d)),
                                jnp.float32),
            "s_up": jnp.asarray(0.3 * rng.normal(size=(d, fs)), jnp.float32),
            "s_down": jnp.asarray(0.3 * rng.normal(size=(fs, d)),
                                  jnp.float32)}


def _dense(w, gated, k=3, scale=2.5):
    """Every token through its top-k experts by plain products."""
    x = np.asarray(w["x"], np.float64)
    s = 1 / (1 + np.exp(-x @ np.asarray(w["router"], np.float64)))
    chosen = np.argsort(-(s + np.asarray(w["bias"])), axis=1)[:, :k]
    out = np.zeros_like(x)
    for t in range(len(x)):
        picked = s[t, chosen[t]]
        for e, p in zip(chosen[t], scale * picked / (picked.sum() + 1e-20)):
            up = x[t] @ np.asarray(w["up"][e], np.float64)
            if gated:
                g = x[t] @ np.asarray(w["gate"][e], np.float64)
                hidden = g / (1 + np.exp(-g)) * up
            else:
                hidden = np.maximum(up, 0) ** 2
            out[t] += p * hidden @ np.asarray(w["down"][e], np.float64)
    return out


@pytest.mark.parametrize("out_in", [False, True], ids=["d_f", "f_d"])
def test_the_held_shares_and_the_shared_expert_once_are_the_uncut_layer(
        out_in):
    """A relu² layer of 8 experts held as two shares of 4: the two parts,
    the shared expert counted once, sum to the layer that holds all 8,
    which is the plain products' layer; with the up weights given (d, f)
    or out by in (f, d), on the launches in interpret mode for the
    latter."""
    w = _expert_layer(np.random.default_rng(5))
    shared = (None, w["s_up"], w["s_down"])
    kw = dict(scoring="sigmoid", choice_bias=w["bias"], route_scale=2.5,
              up_out_in=out_in, interpret=out_in)
    if out_in:
        w["up"] = jnp.swapaxes(w["up"], 1, 2)
    layer = jax.jit(lambda up, down, shared, held: moe_dropless(
        w["x"], w["router"], None, up, down, 3, shared=shared, held=held,
        **kw), static_argnums=3)
    with jax.default_matmul_precision("highest"):
        whole = layer(w["up"], w["down"], shared, None)
        parts = [layer(w["up"][lo:lo + 4], w["down"][lo:lo + 4], shared,
                       (lo, 4)) for lo in (0, 4)]
        alone = layer(w["up"], w["down"], None, None)
    shared_out = np.asarray(whole) - np.asarray(alone)
    np.testing.assert_allclose(
        np.asarray(parts[0]) + np.asarray(parts[1]) - shared_out,
        np.asarray(whole), atol=1e-4)
    if out_in:
        w["up"] = jnp.swapaxes(w["up"], 1, 2)
    np.testing.assert_allclose(np.asarray(alone), _dense(w, gated=False),
                               atol=1e-4)


@pytest.mark.parametrize("held", [None, (0, 8)], ids=["every", "held"])
def test_the_gated_layer_is_the_plain_products_as_before(held):
    """The gated SiLU experts (cells 4, 5 and 6): no new argument changes
    them, and the relu² layer's `up_out_in` left off leaves their program
    alone."""
    w = _expert_layer(np.random.default_rng(6))
    args = (w["x"], w["router"], w["gate"], w["up"], w["down"], 3)
    kw = dict(scoring="sigmoid", choice_bias=w["bias"], route_scale=2.5,
              held=held)
    with jax.default_matmul_precision("highest"):
        as_before = jax.jit(lambda *a: moe_dropless(*a, 3, **kw)).lower(
            *args[:5])
        said = jax.jit(lambda *a: moe_dropless(*a, 3, up_out_in=False, **kw)
                       ).lower(*args[:5])
    assert as_before.as_text() == said.as_text()
    np.testing.assert_allclose(np.asarray(as_before.compile()(*args[:5])),
                               _dense(w, gated=True), atol=1e-4)


# ---------------------------------------------------------------- the cache
def test_the_state_entries_live_on_the_device_on_their_layers_alone(f32):
    _w, model = f32
    cache = model.make_cache(3, max_len=64)
    assert model.attention_layers == [3]
    assert model.mamba_layers == [0, 2]
    assert sorted(n for n, e in cache.spec.items() if e[0] == "kv") == [
        "k3", "v3"]
    states = {n: e for n, e in cache.spec.items() if e[0] == "state"}
    assert sorted(states) == ["conv0", "conv2", "ssm0", "ssm2"]
    # two rows of four heads side by side (4 x 8 lanes), each head (N, P)
    assert cache.data["ssm2"].shape == (3, 2, 16, 4 * 8)
    assert cache.data["ssm2"].dtype == jnp.float32
    assert cache.data["conv2"].shape == (3, 3, 8 * 8 + 2 * 2 * 16)
    assert all(isinstance(cache.data[n], jax.Array) for n in states)
    # a forward is handed the rows' slots and fed counts, then the pools
    a, b = cache.alloc(), cache.alloc()
    lengths, tables, kp, vp, slots, fed, ssm, conv = cache.forward_inputs(
        [b, a], [5, 0])
    assert slots.tolist() == [b, a] and fed.tolist() == [5, 0]
    assert len(kp) == len(vp) == 1 and len(ssm) == len(conv) == 2
    # the chunk writes one attention layer's rows a position
    assert position_bytes(model) == 2 * 2 * 16 * 4
    # a state entry takes set_state and gives it back, zeroed at alloc
    cache.set_state("ssm2", a, np.ones((2, 16, 32)))
    assert cache.state("ssm2", a).sum() == 8 * 8 * 16
    cache.free(a)
    assert cache.state("ssm2", cache.alloc()).sum() == 0


def test_a_recurrent_state_refuses_a_rollback_and_a_draft(f32):
    """A recurrence cannot be rolled back without snapshots: the cache
    refuses ``truncate`` and the engine a draft model."""
    _w, model = f32
    cache = model.make_cache(2, max_len=64)
    slot = cache.alloc()
    with pytest.raises(ValueError, match="state entries.*cannot be truncated"):
        cache.truncate(slot, 0)
    with pytest.raises(ValueError, match="takes no draft model"):
        GenerateEngine(model, cache, draft=model,
                       draft_cache=model.make_cache(2, max_len=64))


def test_a_generate_call_tallies_the_state_and_its_spans(f32):
    _w, model = f32
    cache = model.make_cache(3, max_len=64)
    engine = GenerateEngine(model, cache, prefill_chunk=16, name="nemo")
    prompts = [list(range(20, 20 + n)) for n in (9, 20, 3)]
    telemetry.enable()
    tracing.clear_spans()
    before = catalog.gen_ssm_state_bytes.value(model="nemo", phase="decode")
    chunks = catalog.gen_ssm_scan_chunks.value(model="nemo")
    resets = catalog.gen_ssm_state_resets.value(model="default")
    try:
        with tracing.Span("test.root"):
            out = engine.generate(prompts, max_new_tokens=5)
    finally:
        telemetry.disable()
    assert [len(o) for o in out] == [5, 5, 5]
    ssm = engine.last_stats["ssm"]
    row = 2 * 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)    # read + written
    assert model.row_state_bytes == row
    # prefill: rounds of 3 rows of 16 (the 20-token prompt's 19 are two
    # chunks); decode: 5 steps of 3 rows
    assert ssm["prefill"]["forwards"] == 2
    assert ssm["prefill"]["positions"] == 8 + 19 + 2
    assert ssm["prefill"]["scan_chunks"] == 2 * 3 * 2 * 2
    # a step reads each row's committed positions in the attention layer
    assert ssm["decode"] == {"forwards": 5, "rows": 15, "positions": 15,
                             "scan_chunks": 0, "state_bytes": 15 * row,
                             "cache_rows_read": sum(
                                 len(p) - 1 + i for p in prompts
                                 for i in range(5))}
    assert catalog.gen_ssm_state_bytes.value(
        model="nemo", phase="decode") - before == 15 * row
    assert catalog.gen_ssm_scan_chunks.value(model="nemo") - chunks == 24
    assert catalog.gen_ssm_state_resets.value(model="default") - resets == 3
    spans = tracing.recent_spans()
    dispatch = [s for s in spans if s["name"] == "lm.dispatch"]
    assert {s["ssm_state_bytes"] for s in dispatch} == {3 * row}
    prefill = [s for s in spans if s["name"] == "gen.prefill"]
    assert sorted(s["ssm_chunks"] for s in prefill) == [2, 2, 4]
    assert engine.last_stats["moe"]["forwards"] == 7


def test_the_references_bfloat16_state_rounds_the_recurrence_alone(f32):
    """The reading PERF.md reports beside the 8-bit control: the state
    rounded to bfloat16 after every position moves the logits, by less
    than the program's own bfloat16 rounding is allowed to."""
    weights, _model = f32
    cfg = toy()
    tokens = np.random.default_rng(7).integers(0, 320, 40)[None]
    at = np.arange(40)[None]
    exact = reference.logits(weights, cfg, tokens, at)
    rounded = reference.logits(weights, cfg, tokens, at,
                               precision="bfloat16_state")
    gap = np.abs(exact - rounded).max()
    assert 0 < gap < BF16_TOLERANCE
    with pytest.raises(ValueError, match="no such precision"):
        reference.logits(weights, cfg, tokens, at, precision="bfloat16")
