"""The ``sdar_moe`` family at a small size on the CPU: 2 layers, hidden 64,
4 query and 2 key/value heads of 16, 8 experts top-2 of width 32,
vocabulary 256, block length 4, seeded weights.

- the dropless expert layer against a per-token loop (also every route on
  one expert, and an expert that gets none), on the lax path and through
  the Pallas launch in interpret mode;
- the grouped product's launch against ``jax.lax.ragged_dot``;
- ``paged_causal_attention`` with 2 key/value heads under both in-chunk
  masks against a dense mask, and bit-identical to the function as it was
  for GPT-2's call;
- prefill then block decoding through ``PagedKVCache`` against the plain
  reference's full forward;
- the block loop's invariants, and the plain loop left as it was;
- the denoising program's schedule against the host's, bit for bit, and
  the block loop reading every forward one forward behind.
"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import sdar_moe as reference
from incubator_mxnet_tpu.generate import (GenerateEngine, GPTPagedLM,
                                          SDARPagedLM)
from incubator_mxnet_tpu.generate.engine import (commit_slots, forward_slots,
                                                 prefill_slot, step_slots)
from incubator_mxnet_tpu.models.gpt import gpt_config, gpt_param_shapes
from incubator_mxnet_tpu.models.sdar_moe import sdar_logits
from incubator_mxnet_tpu.ops.pallas import flash_decode
from incubator_mxnet_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                           tile_plan)
from incubator_mxnet_tpu.parallel.moe import moe_dropless
from incubator_mxnet_tpu.telemetry import catalog as cat
from incubator_mxnet_tpu.telemetry import metrics as _met
from incubator_mxnet_tpu import telemetry

MASK = 255
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
       "moe_intermediate_size": 32, "vocab_size": 256, "rope_theta": 1000000,
       "rms_norm_eps": 1e-6, "num_hidden_layers": 2, "dtype": "float32",
       "seed_weight_range": 0.3,
       "assumed": {"block_length": {"value": 4},
                   "mask_token_id": {"value": MASK}}}
PROGRAM = {"vocab_size": 256, "units": 64, "num_layers": 2, "num_heads": 4,
           "num_kv_heads": 2, "head_dim": 16, "num_experts": 8,
           "experts_per_token": 2, "expert_hidden": 32, "block_length": 4,
           "mask_id": MASK, "max_len": 64}


@pytest.fixture(scope="module")
def weights():
    return reference.init_weights(CFG, 3)


@pytest.fixture(scope="module")
def model(weights):
    return SDARPagedLM(weights, PROGRAM, dtype="float32")


def _engine(model, slots=4, max_len=64, **kw):
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("denoise_steps", 2)
    return GenerateEngine(model, model.make_cache(slots, max_len=max_len,
                                                  block_size=8), **kw)


# ------------------------------------------------------------ expert layer
def _expert_weights(rng, experts=8, d=16, f=12):
    return [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for s in ((d, experts), (experts, d, f), (experts, d, f),
                      (experts, f, d))]


def _per_token_loop(x, router_w, gate_w, up_w, down_w, k):
    """Every token's experts one at a time, in float64."""
    x, router_w, gate_w, up_w, down_w = [
        np.asarray(a, np.float64) for a in (x, router_w, gate_w, up_w,
                                            down_w)]
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        z = row @ router_w
        p = np.exp(z - z.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:k]
        for e in chosen:
            gate = row @ gate_w[e]
            hidden = gate / (1 + np.exp(-gate)) * (row @ up_w[e])
            out[t] += p[e] / p[chosen].sum() * (hidden @ down_w[e])
    return out


@pytest.mark.parametrize("kernel", [False, True], ids=["lax", "interpret"])
@pytest.mark.parametrize("routing", ["even", "one_expert", "one_unused"])
def test_the_dropless_layer_agrees_with_a_per_token_loop(routing, kernel):
    rng = np.random.default_rng(7)
    router_w, gate_w, up_w, down_w = _expert_weights(rng)
    x = jnp.asarray(rng.normal(size=(21, 16)), jnp.float32)
    if routing == "one_expert":
        # expert 5's column so large that it is every token's first choice
        # (the second still varies): 21 of the 42 routes on one expert
        router_w = router_w.at[:, 5].set(0.0)
        x = x.at[:, 0].set(4.0)
        router_w = router_w.at[0, 5].set(25.0)
    if routing == "one_unused":
        router_w = router_w.at[:, 2].set(0.0).at[0, 2].set(-25.0)
        x = x.at[:, 0].set(4.0)
    out, stats = moe_dropless(x, router_w, gate_w, up_w, down_w, 2,
                              use_kernel=kernel, interpret=kernel,
                              return_stats=True)
    load = np.asarray(stats["expert_load"])
    assert load.sum() == 21 * 2             # no token dropped
    if routing == "one_expert":
        assert load[5] == 21
    if routing == "one_unused":
        assert load[2] == 0
    # float32 products against a float64 loop: sums of 16 and 12 terms
    np.testing.assert_allclose(
        np.asarray(out), _per_token_loop(x, router_w, gate_w, up_w, down_w,
                                         2), atol=2e-5)


def test_all_routes_on_one_expert_top_1():
    """k = 1 with a router that sends everything to expert 3: the grouped
    product gets one group of all rows and seven empty ones."""
    rng = np.random.default_rng(8)
    router_w, gate_w, up_w, down_w = _expert_weights(rng)
    router_w = jnp.zeros_like(router_w).at[0, 3].set(9.0)
    x = jnp.asarray(rng.normal(size=(33, 16)), jnp.float32).at[:, 0].set(1.0)
    for kernel in (False, True):
        out, stats = moe_dropless(x, router_w, gate_w, up_w, down_w, 1,
                                  use_kernel=kernel, interpret=kernel,
                                  return_stats=True)
        assert np.asarray(stats["expert_load"]).tolist() == [
            0, 0, 0, 33, 0, 0, 0, 0]
        np.testing.assert_allclose(
            np.asarray(out),
            _per_token_loop(x, router_w, gate_w, up_w, down_w, 1), atol=2e-5)


@pytest.mark.parametrize("sizes", [[3, 0, 17, 1, 0, 11], [32, 0, 0, 0, 0, 0],
                                   [0, 0, 0, 0, 0, 5], [16, 16, 16, 0, 1, 0]])
def test_the_grouped_launch_is_ragged_dot(sizes):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(sum(sizes), 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 64, 48)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    launch = grouped_matmul(x, w, group_sizes, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(launch),
        np.asarray(jax.lax.ragged_dot(x, w, group_sizes)))
    dest, tile_group, used = tile_plan(group_sizes, sum(sizes))
    tiles = [-(-n // 16) for n in sizes]
    assert int(used[0]) == sum(tiles)
    # a tile belongs to one group; the tiles past the used ones repeat the
    # last used group, so that they fetch nothing
    owners = [g for g, n in enumerate(tiles) for _ in range(n)]
    assert np.asarray(tile_group)[:len(owners)].tolist() == owners
    assert set(np.asarray(tile_group)[len(owners):].tolist()) <= {owners[-1]}
    assert len(set(np.asarray(dest).tolist())) == sum(sizes)


# --------------------------------------------------------------- attention
def _pool(rng, lengths, bs, mb, H, D):
    S = len(lengths)
    kp = rng.normal(size=(S * mb, bs, H, D)).astype(np.float32)
    vp = rng.normal(size=(S * mb, bs, H, D)).astype(np.float32)
    tables = rng.permutation(S * mb).reshape(S, mb).astype(np.int32)
    return kp, vp, tables


@pytest.mark.parametrize("mask_block,C,past", [
    (None, 4, 8), (None, 8, 4), (None, 4, 0), (None, 1, 5),
    (4, 4, 8), (4, 8, 4), (4, 4, 0), (4, 12, 16)])
def test_grouped_query_attention_matches_a_dense_mask(mask_block, C, past):
    """A chunk under the block mask holds whole blocks."""
    S, H, Hkv, D, bs, mb = 2, 4, 2, 8, 4, 4
    rng = np.random.default_rng(C * 31 + past)
    lengths = np.asarray([past, max(past - 4, 0)], np.int32)
    q = rng.normal(size=(S, C, H, D)).astype(np.float32)
    k_new = rng.normal(size=(S, C, Hkv, D)).astype(np.float32)
    v_new = rng.normal(size=(S, C, Hkv, D)).astype(np.float32)
    kp, vp, tables = _pool(rng, lengths, bs, mb, Hkv, D)
    out = np.asarray(flash_decode.paged_causal_attention(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kp), jnp.asarray(vp), tables, lengths,
        use_kernel=False, mask_block=mask_block))
    for s in range(S):
        n = int(lengths[s])
        k_all = np.concatenate([kp[tables[s]].reshape(-1, Hkv, D)[:n],
                                k_new[s]])
        v_all = np.concatenate([vp[tables[s]].reshape(-1, Hkv, D)[:n],
                                v_new[s]])
        pos = np.arange(n + C)
        sees = (pos[None, :] // mask_block <= pos[:, None] // mask_block
                if mask_block else pos[None, :] <= pos[:, None])
        for h in range(H):
            sc = q[s, :, h] @ k_all[:, h // 2].T / math.sqrt(D)
            sc = np.where(sees[n:], sc, -np.inf)
            w = np.exp(sc - sc.max(-1, keepdims=True))
            ref = (w / w.sum(-1, keepdims=True)) @ v_all[:, h // 2]
            np.testing.assert_allclose(out[s, :, h], ref, atol=1e-5)


def _attention_as_it_was(q, k_new, v_new, k_pool, v_pool, block_tables,
                         lengths):
    """``paged_causal_attention`` of the commit before grouped-query heads
    and the block mask, lax path, copied whole."""
    S, C, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    o_p, m_p, l_p = flash_decode.paged_flash_decode(
        q, k_pool, v_pool, block_tables, lengths, scale=scale,
        use_kernel=False)
    s_new = jnp.einsum("schd,sthd->shct", q.astype(jnp.float32),
                       k_new.astype(jnp.float32)) * scale
    causal = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :])
    s_new = jnp.where(causal[None, None], s_new, -1e30)
    m_s = jnp.max(s_new, axis=-1)
    p = jnp.exp(s_new - m_s[..., None])
    p = jnp.where(causal[None, None], p, 0.0)
    l_s = jnp.sum(p, axis=-1)
    o_s = jnp.einsum("shct,sthd->schd", p, v_new.astype(jnp.float32))
    m_s = m_s.transpose(0, 2, 1)
    l_s = l_s.transpose(0, 2, 1)
    m = jnp.maximum(m_p, m_s)
    w_p = l_p * jnp.exp(m_p - m)
    w_s = jnp.exp(m_s - m)
    num = o_p.astype(jnp.float32) * w_p[..., None] + o_s * w_s[..., None]
    den = w_p + l_s * w_s
    return (num / den[..., None]).astype(q.dtype)


@pytest.mark.parametrize("C,past", [(1, 9), (5, 0), (8, 3)])
def test_gpt2s_attention_call_is_bit_identical_to_what_it_was(C, past):
    S, H, D, bs, mb = 3, 2, 8, 4, 4
    rng = np.random.default_rng(C)
    lengths = np.full(S, past, np.int32)
    q, k_new, v_new = (jnp.asarray(rng.normal(size=(S, C, H, D)),
                                   jnp.float32) for _ in range(3))
    kp, vp, tables = _pool(rng, lengths, bs, mb, H, D)
    args = (q, k_new, v_new, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lengths))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda *a: flash_decode.paged_causal_attention(
            *a, use_kernel=False))(*args)),
        np.asarray(jax.jit(_attention_as_it_was)(*args)))


# ------------------------------------------------- model against reference
def test_the_full_forward_agrees_with_the_plain_reference(weights):
    tokens = np.random.default_rng(0).integers(0, 250, (3, 24)).astype(
        np.int32)
    ours = np.asarray(sdar_logits(weights, PROGRAM, jnp.asarray(tokens)))
    theirs = reference.logits(weights, CFG, tokens)
    assert np.abs(theirs).max() > 5        # the logits are not all alike
    # float32 both, the reference at `highest`; on the CPU both are exact
    # float32 products and differ by the order of summation alone
    np.testing.assert_allclose(ours, theirs, atol=2e-4)


@pytest.mark.parametrize("prompt_len", [8, 9, 10, 11])
def test_prefill_then_a_block_through_the_cache_is_the_full_forward(
        model, weights, prompt_len):
    """The prompt's whole blocks are prefilled into a PagedKVCache, the tail
    opens the block; the block's logits through the cache, with every
    masked position holding MASK, are the reference's over the whole
    sequence under the dense block mask."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, 250, prompt_len).tolist()
    eng = _engine(model)
    slot = eng.cache.alloc()
    whole = prompt_len // 4 * 4
    prefill_slot(model, eng.cache, slot, prompt[:whole], eng.prefill_chunk)
    assert eng.cache.lengths[slot] == whole
    block = (prompt[whole:] + [MASK] * 4)[:4]
    tokens = np.asarray([block], np.int32)
    logits, nk, nv = forward_slots(model, eng.cache, [slot], tokens)
    theirs = reference.logits(weights, CFG, np.asarray([prompt[:whole]
                                                        + block], np.int32),
                              at=np.arange(whole, whole + 4)[None])
    # float32 on both sides; the paged path sums past and chunk apart
    np.testing.assert_allclose(logits[0], theirs[0], atol=2e-4)
    # the device's choice is the logits' argmax and its softmax share;
    # with one step left every masked position takes it
    read, final, _left = forward_slots(
        model, eng.cache, [slot], tokens,
        functools.partial(model.forward_denoise, masked=tokens == MASK,
                          steps_left=1))
    x0, confidence = jax.device_get((read["x0"], read["confidence"]))
    logits = np.array(logits)
    logits[..., MASK] = -np.inf             # a position never takes MASK
    assert x0[0].tolist() == logits[0].argmax(-1).tolist()
    z = logits[0] - logits[0].max(-1, keepdims=True)
    np.testing.assert_allclose(confidence[0], 1 / np.exp(z).sum(-1),
                               rtol=1e-5)
    # a second block after the first is stored: the cache now holds the
    # block's final tokens' K and V
    assert np.array_equal(final, np.where(tokens == MASK, x0, tokens))
    _none, nk, nv = forward_slots(model, eng.cache, [slot], final,
                                  model.forward_kv)
    commit_slots(eng.cache, [slot], nk, nv, 4)
    nxt = np.full((1, 4), MASK, np.int32)
    logits2, _, _ = forward_slots(model, eng.cache, [slot], nxt)
    theirs2 = reference.logits(
        weights, CFG, np.asarray([prompt[:whole] + final[0].tolist()
                                  + [MASK] * 4], np.int32),
        at=np.arange(whole + 4, whole + 8)[None])
    np.testing.assert_allclose(logits2[0], theirs2[0], atol=2e-4)
    eng.cache.free(slot)


def test_bfloat16_serving_stays_near_the_reference(weights):
    """Served as the cell serves it (bfloat16 weights, activations and
    pools): bfloat16 keeps 8 bits, a relative error of 2**-9 a rounding;
    through 2 layers of some 20 roundings each the logits stay within 5 %
    of their largest magnitude, and an 8-bit reference does not."""
    low = reference.init_weights(dict(CFG, dtype="bfloat16"), 3)
    tokens = np.random.default_rng(1).integers(0, 250, (2, 16)).astype(
        np.int32)
    theirs = reference.logits(low, CFG, tokens)
    ours = np.asarray(sdar_logits(low, PROGRAM, jnp.asarray(tokens)))
    bound = 0.05 * np.abs(theirs).max()
    assert np.abs(ours - theirs).max() < bound
    eight = reference.logits(low, CFG, tokens, precision="float8_e4m3")
    assert np.abs(eight - theirs).max() > bound


# --------------------------------------------------------------- block loop
@pytest.fixture()
def _metrics():
    telemetry.enable()
    _met.reset()
    yield
    _met.reset()
    telemetry.disable()


def test_the_block_loop_keeps_its_invariants(model, _metrics, monkeypatch):
    """Prompts with tails of 0, 1, 2 and 3 tokens, 10 new tokens each (a
    last block cut by max_new_tokens): exactly 10 a row, none MASK, the
    cache holding prompt + committed whole blocks when the row is freed,
    every forward counted."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 250, n).tolist() for n in (8, 9, 10, 11)]
    eng = _engine(model)
    at_free = {}
    free = eng.cache.free
    monkeypatch.setattr(eng.cache, "free", lambda slot: (
        at_free.update({slot: int(eng.cache.lengths[slot])}), free(slot)))
    out = eng.generate(prompts, max_new_tokens=10)
    assert [len(o) for o in out] == [10] * 4
    assert all(MASK not in o and all(0 <= t < 256 for t in o) for o in out)
    # 8: blocks at 8, 12, 16 (4 + 4 + 2 of 4); 9: 3 + 4 + 3; 10: 2 + 4 + 4;
    # 11: 1 + 4 + 4 + 1 of a fourth block that the row takes alone
    assert sorted(at_free.values()) == [20, 20, 20, 24]
    assert eng.cache.in_use == 0
    st = eng.last_stats
    assert st["block_forwards"] == {"denoise": 8, "store": 4}
    assert st["block_row_forwards"] == 3 * (4 + 4 + 4 + 1)
    assert st["block_positions_committed"] == 4 * (4 + 4 + 4 + 1)
    assert st["decode_tokens"] == 40 and st["prefill_tokens"] == 32
    assert [b["rows"] for b in st["blocks"]] == [[0, 1, 2, 3]] * 3 + [[3]]
    assert st["blocks"][0]["starts"] == [8, 8, 8, 8]
    first = st["blocks"][0]["steps"]
    # the static schedule: ceil(masked / steps left) a forward
    assert [s["masked"].sum(1).tolist() for s in first] == [[4, 3, 2, 1],
                                                            [2, 1, 1, 0]]
    assert [s["fixed"].sum(1).tolist() for s in first] == [[2, 2, 1, 1],
                                                           [2, 1, 1, 0]]
    for step in first:      # the most confident of the masked are fixed
        for r in range(4):
            conf = np.where(step["masked"][r], step["confidence"][r], -1)
            worst_fixed = conf[step["fixed"][r]].min(initial=np.inf)
            assert (conf[step["masked"][r] & ~step["fixed"][r]]
                    <= worst_fixed).all()
    # the four prompts' chunks are ONE prefill round of four rows
    assert (st["prefill_forwards"], st["prefill_rows"]) == (1, 4)
    # no token is dropped: every forward routes tokens x 2 in every layer
    moe = st["moe"]
    fed = 4 * 8 + 3 * (4 + 4 + 4 + 1) * 4          # prefill chunks; blocks
    assert moe["forwards"] == 1 + 12 and moe["routes"] == fed * 2 * 2
    assert len(moe["load_max_over_mean"]) == 13
    # by phase: the prefill round; the denoising and store forwards are
    # all "decode", and the two phases sum to the totals
    by_phase = moe["by_phase"]
    assert by_phase["prefill"]["forwards"] == 1
    assert by_phase["prefill"]["routes"] == 4 * 8 * 2 * 2
    assert by_phase["decode"]["forwards"] == 8 + 4
    for key in ("forwards", "routes", "experts_hit"):
        assert by_phase["prefill"][key] + by_phase["decode"][key] \
            == moe[key]
        assert by_phase["prefill"][key] > 0 < by_phase["decode"][key]
    for phase, tally in by_phase.items():
        assert cat.moe_routes.value(model="gpt", phase=phase) \
            == tally["routes"]
        assert cat.moe_experts_hit.value(model="gpt", phase=phase) \
            == tally["experts_hit"]
    assert cat.gen_block_forwards.value(model="gpt", phase="denoise") == 8
    assert cat.gen_block_forwards.value(model="gpt", phase="store") == 4
    assert cat.gen_block_positions_committed.value(model="gpt") == 52
    # every forward but the call's first is launched ahead of its reads
    assert st["block_forwards_launched_ahead"] == 11
    assert cat.gen_block_forwards.value(model="gpt", phase="denoise",
                                        ahead="false") == 1
    # the same call again gives the same tokens: nothing is left behind
    assert eng.generate(prompts, max_new_tokens=10) == out


def test_the_block_loop_follows_the_reference_forward_by_forward(model,
                                                                 weights):
    """Every denoising forward's choice, from the recorded schedule: the
    reference over prompt + served tokens up to the block + the block as
    the forward saw it picks the same tokens with the same confidence."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 250, n).tolist() for n in (9, 12)]
    eng = _engine(model)
    out = eng.generate(prompts, max_new_tokens=7)
    # the tokens of the tree before ISSUE 30 (host pools, appends)
    assert out == [[141, 224, 224, 115, 94, 15, 224],
                   [23, 23, 216, 216, 67, 162, 56]]
    checked = 0
    for block in eng.last_stats["blocks"]:
        for step in block["steps"]:
            for r, (row, start) in enumerate(zip(block["rows"],
                                                 block["starts"])):
                masked = step["masked"][r]
                if not masked.any():
                    continue
                sequence = (prompts[row] + out[row])[:start] \
                    + step["tokens"][r].tolist()
                logits = reference.logits(
                    weights, CFG, np.asarray([sequence], np.int32),
                    at=np.arange(start, start + 4)[None], block_rows=1)[0]
                logits[:, MASK] = -np.inf   # a position never takes MASK
                assert (logits.argmax(-1) == step["x0"][r])[masked].all()
                z = logits - logits.max(-1, keepdims=True)
                np.testing.assert_allclose(
                    step["confidence"][r][masked],
                    (1 / np.exp(z).sum(-1))[masked], rtol=1e-4)
                checked += 1
    assert checked >= 6


def _argsort_schedule(masked, confidence, steps_left):
    """The schedule as PR 29 wrote it on the host: a stable argsort's
    rank of ``where(masked, -confidence, inf)``."""
    count = -(-masked.sum(axis=1) // steps_left)
    order = np.argsort(np.where(masked, -confidence, np.inf), axis=1,
                       kind="stable")
    rank = np.argsort(order, axis=1, kind="stable")
    return masked & (rank < count[:, None])


@pytest.mark.parametrize("steps_left", range(1, 9))
def test_the_programs_schedule_is_the_hosts_bit_for_bit(steps_left):
    """``_fix_most_confident`` traced (one program, `steps_left` an int32
    operand), on numpy arrays, and the argsort it replaced fix the same
    positions over seeded (12, 8) rows: every position masked, none
    masked, confidences drawn from three values (ties, the leftmost
    first), one row all one value, the rest drawn freely."""
    rng = np.random.default_rng(steps_left)
    masked = rng.random((12, 8)) < 0.6
    confidence = rng.random((12, 8)).astype(np.float32)
    masked[0] = masked[6] = True
    masked[1] = False
    confidence[2:6] = rng.choice(np.float32([0.125, 0.25, 0.5]), (4, 8))
    confidence[6] = 0.5
    want = _argsort_schedule(masked, confidence, steps_left)
    program = jax.jit(GenerateEngine._fix_most_confident)
    got = np.asarray(program(jnp.asarray(masked), jnp.asarray(confidence),
                             jnp.int32(steps_left)))
    assert got.dtype == bool and np.array_equal(got, want)
    assert np.array_equal(GenerateEngine._fix_most_confident(
        masked, confidence, steps_left), want)
    # exactly ceil(m / steps_left) of a row's m masked: the host counts
    assert np.array_equal(want.sum(1), -(-masked.sum(1) // steps_left))
    assert not want[1].any() and want[6].tolist() \
        == [i < -(-8 // steps_left) for i in range(8)]


def test_the_denoising_program_fixes_what_the_schedule_fixes(model):
    """``forward_denoise`` fetches nothing; what it leaves on the device is
    the logits' choice (argmax and softmax share, MASK left out) with the
    schedule applied to it: the next mask is the mask less
    ``_fix_most_confident``'s positions, the next tokens take ``x0``
    there."""
    rng = np.random.default_rng(4)
    eng = _engine(model)
    slots = [eng.cache.alloc() for _ in range(2)]
    for slot in slots:
        prefill_slot(model, eng.cache, slot, rng.integers(0, 250, 8).tolist(),
                     eng.prefill_chunk)
    tokens = np.asarray([[17, MASK, MASK, MASK], [MASK] * 4], np.int32)
    masked = tokens == MASK
    logits, _, _ = forward_slots(model, eng.cache, slots, tokens)
    logits = np.array(logits)
    logits[..., MASK] = -np.inf
    z = logits - logits.max(-1, keepdims=True)
    for steps_left in (1, 2, 3):
        read, nxt, left = forward_slots(
            model, eng.cache, slots, tokens,
            functools.partial(model.forward_denoise, masked=masked,
                              steps_left=steps_left))
        assert model.last_expert_loads is None
        assert all(isinstance(a, jax.Array) for a in read.values())
        host = jax.device_get(read)
        assert np.array_equal(host["x0"], logits.argmax(-1))
        np.testing.assert_allclose(host["confidence"],
                                   1 / np.exp(z).sum(-1), rtol=1e-5)
        fixed = GenerateEngine._fix_most_confident(
            masked, host["confidence"], steps_left)
        assert np.array_equal(host["masked"], masked & ~fixed)
        assert np.array_equal(np.asarray(left), host["masked"])
        assert np.array_equal(np.asarray(nxt),
                              np.where(fixed, host["x0"], tokens))
        assert host["expert_loads"].shape == (2, 8)
    for slot in slots:
        eng.cache.free(slot)


def test_the_block_loop_reads_every_forward_one_forward_behind(
        model, _metrics, monkeypatch):
    """The order of launches and reads over a call of 12 block forwards
    (``_dispatch`` and ``_fetch`` logged): every block forward is read
    once, after the next one is launched, the call's last after nothing;
    the prefill is read before the first block forward, which is the one
    forward not launched ahead (``last_stats``, the ``ahead`` attribute of
    the spans and the counter's label)."""
    from incubator_mxnet_tpu.generate import engine as engine_mod
    from incubator_mxnet_tpu.telemetry import tracing
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 250, n).tolist() for n in (8, 9, 10, 11)]
    eng = _engine(model)
    want = eng.generate(prompts, max_new_tokens=10)
    log, owner = [], {}
    dispatch, fetch = engine_mod._dispatch, engine_mod._fetch

    def logged_dispatch(fn, params, args, *rest, **kw):
        out = dispatch(fn, params, args, *rest, **kw)
        n = sum(1 for e in log if e[0] == "dispatch")
        log.append(("dispatch", n, args[0].shape))
        for leaf in jax.tree_util.tree_leaves(out):
            owner[id(leaf)] = (n, leaf)     # the leaf kept: ids stay apart
        return out

    def logged_fetch(arrays):
        log.append(("fetch", {owner[id(leaf)][0] for leaf in
                              jax.tree_util.tree_leaves(arrays)}))
        return fetch(arrays)
    monkeypatch.setattr(engine_mod, "_dispatch", logged_dispatch)
    monkeypatch.setattr(engine_mod, "_fetch", logged_fetch)
    _met.reset()
    tracing.clear_spans()
    assert eng.generate(prompts, max_new_tokens=10) == want
    blocks = [n for kind, n, *shape in (e for e in log if e[0] == "dispatch")
              if shape[0][1] == 4]
    # after ONE prefill round of the 4 prompts' chunks
    assert len(blocks) == 12 and blocks[0] == 1
    assert log[0] == ("dispatch", 0, (4, 8))
    at = {("dispatch", e[1]): i for i, e in enumerate(log)
          if e[0] == "dispatch"}
    reads = {}
    for i, e in enumerate(log):
        if e[0] == "fetch":
            (n,) = e[1]                     # a read is one forward's
            assert n not in reads           # and made once
            reads[n] = i
    assert set(reads) == set(range(13))     # every forward read
    assert reads[0] < at["dispatch", blocks[0]]
    for k, n in enumerate(blocks[:-1]):
        assert at["dispatch", blocks[k + 1]] < reads[n]
    assert reads[blocks[-1]] == len(log) - 1
    assert eng.last_stats["block_forwards_launched_ahead"] == 11
    recs = tracing.recent_spans()
    ahead = [r["ahead"] for r in recs
             if r["name"] in ("gen.denoise_step", "gen.block_store")]
    assert ahead == [False] + [True] * 11
    for phase, count in (("denoise", 7), ("store", 4)):
        assert cat.gen_block_forwards.value(model="gpt", phase=phase,
                                            ahead="true") == count
    assert cat.gen_block_forwards.value(model="gpt", phase="store",
                                        ahead="false") == 0
    assert "ahead" in cat.gen_block_forwards.help


def test_eos_ends_one_row_mid_call_as_the_parents_loop_did(model):
    """A stop token that row 2 emits as its sixth token, the second of
    its second block: the row ends there, the other two rows are served
    whole, no block forward more than before runs (rows 0-2 for two
    blocks, 0-1 for two more), and the tokens are those of the loop that
    read every forward before the next (pinned from the tree before ISSUE
    40)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 250, n).tolist() for n in (9, 10, 12)]
    eng = _engine(model)
    out = eng.generate(prompts, max_new_tokens=12, eos_id=100)
    assert out == [[165, 165, 227, 252, 207, 207, 252, 165, 165, 227, 227,
                    213],
                   [0, 37, 216, 56, 0, 56, 56, 56, 81, 81, 56, 56],
                   [47, 126, 126, 90, 88, 100]]
    st = eng.last_stats
    assert [b["rows"] for b in st["blocks"]] == [[0, 1, 2]] * 2 + [[0, 1]] * 2
    assert st["block_forwards"] == {"denoise": 8, "store": 4}
    assert st["block_forwards_launched_ahead"] == 11
    assert st["decode_tokens"] == 30 and eng.cache.in_use == 0
    for block in st["blocks"]:      # the records are the host's, whole
        assert all(isinstance(step[key], np.ndarray)
                   for step in block["steps"] for key in step)
        assert not block["steps"][-1]["masked"][
            ~block["steps"][-1]["fixed"]].any()
        assert isinstance(block["final"], np.ndarray) \
            and MASK not in block["final"]


def test_block_decoding_takes_no_draft_no_temperature_no_split_chunk(model):
    cache = model.make_cache(2, max_len=32)
    with pytest.raises(ValueError, match="greedy"):
        GenerateEngine(model, cache, temperature=0.7, prefill_chunk=8)
    with pytest.raises(ValueError, match="multiple of the model's"):
        GenerateEngine(model, cache, prefill_chunk=6)
    eng = GenerateEngine(model, cache, prefill_chunk=8)
    assert eng.denoise_steps == 4           # default: a position a step


def test_the_last_block_must_fit_the_cache_whole(model):
    eng = GenerateEngine(model, model.make_cache(1, max_len=30),
                         prefill_chunk=8)
    with pytest.raises(ValueError, match="exceeds cache"):
        eng.generate([[1] * 26], max_new_tokens=3)   # 29 -> stored to 32


def test_eos_ends_a_row_inside_a_block(model):
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 250, 8).tolist()]
    eng = _engine(model)
    out = eng.generate(prompts, max_new_tokens=12)[0]
    eos = out[5]
    cut = eng.generate(prompts, max_new_tokens=12, eos_id=eos)[0]
    assert cut == out[:out.index(eos) + 1]


def test_a_gpt_model_still_takes_the_plain_loop_token_for_token():
    """A model that declares no block length is decoded as before: the
    engine's tokens are those of a hand-rolled loop of one-token steps."""
    cfg = gpt_config({"vocab_size": 29, "units": 24, "num_layers": 2,
                      "num_heads": 2, "max_len": 64})
    rng = np.random.RandomState(0)
    lm = GPTPagedLM({n: (rng.randn(*s) * 0.05).astype(np.float32)
                     for n, s in gpt_param_shapes(cfg).items()}, cfg)
    prompts = [[3, 5, 7, 2, 11, 1, 4], [9, 8]]
    eng = GenerateEngine(lm, lm.make_cache(2, max_len=32))
    assert eng.block_length == 0
    out = eng.generate(prompts, max_new_tokens=6)
    assert "block_forwards" not in eng.last_stats
    assert "moe" not in eng.last_stats
    for prompt, served in zip(prompts, out):
        cache = lm.make_cache(1, max_len=32)
        slot = cache.alloc()
        prefill_slot(lm, cache, slot, prompt[:-1], eng.prefill_chunk)
        token, tokens = prompt[-1], []
        for _ in range(6):
            token = int(np.argmax(step_slots(
                lm, cache, [slot], np.asarray([[token]], np.int32))[0]))
            tokens.append(token)
        assert tokens == served
