"""The ``xing4_0`` family at a small size on the CPU, float32: 3 layers (one
dense, two expert layers), hidden 32, 4 heads over a latent of 16 + 8, 8
experts top-2 of width 16 with a shared one, 4 streams, vocabulary 128,
seeded weights.

- prefill in chunks then decoding through ``PagedKVCache`` against the
  plain reference's full forward;
- the absorbed and the expanded attention path on the same rows, and the
  same rows committed whatever the chunk width;
- the cache: one latent row a position and layer, no per-head K or V;
- the hyper-connections' invariants, YaRN's numbers;
- the sigmoid router (choice by score + bias, weight by score), the shared
  expert counted once, ``moe_dropless``'s defaults left as they were;
- which path ran, in ``last_stats["mla"]``, the counters and the span.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import kimi_k2 as share_family
from benchmarks.families import xing4 as family
from benchmarks.reference import kimi_k2 as share_reference
from benchmarks.reference import xing4 as reference
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.generate import GenerateEngine, MLAPagedLM
from incubator_mxnet_tpu.generate.engine import (forward_slots, prefill_slot,
                                                 step_slots)
from incubator_mxnet_tpu.generate.paged_kv import PagedKVCache
from incubator_mxnet_tpu.models import mla_moe
from incubator_mxnet_tpu.ops.pallas.paged_latent import (
    cache_row_width, latent_block_size, latent_path, paged_latent_attention,
    paged_latent_decode, rows_walked)
from incubator_mxnet_tpu.parallel.moe import moe_dropless
from incubator_mxnet_tpu.telemetry import catalog as cat
from incubator_mxnet_tpu.telemetry import tracing

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
CFG = {"hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
       "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
       "v_head_dim": 8, "intermediate_size": 64, "n_routed_experts": 8,
       "num_experts_per_tok": 2, "moe_intermediate_size": 16,
       "n_shared_experts": 1, "first_k_dense_replace": 1,
       "num_hidden_layers": 3, "vocab_size": 128, "hc_mult": 4,
       "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
       "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
       "routed_scaling_factor": 2, "max_position_embeddings": 64,
       "rope_scaling": YARN, "dtype": "float32", "seed_weight_range": 0.2,
       "assumed": {"initializer_range": {"value": 0.02},
                   "hc_phi_range": {"value": 0.1},
                   "hc_bias_range": {"value": 1.0},
                   "router_bias_range": {"value": 0.1},
                   "prefill_chunk": {"value": 8}}}
PUBLISHED_YARN = dict(CFG, qk_rope_head_dim=64, qk_nope_head_dim=128)


@pytest.fixture(scope="module")
def weights():
    return reference.init_weights(CFG, 3)


@pytest.fixture(scope="module")
def model(weights):
    return MLAPagedLM(weights, family.program_config(CFG), dtype="float32")


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).tolist()


def _reference_at(weights, tokens, positions):
    return reference.logits(weights, CFG, [tokens], [positions],
                            block_rows=8)[0]


# ---------------------------------------------- through the cache, end to end
@pytest.mark.parametrize("length,chunk", [(21, 8), (9, 8), (17, 4), (6, 8)],
                         ids=["chunks_and_tail", "one_over", "whole_chunks",
                              "under_a_chunk"])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        weights, model, length, chunk):
    """Chunks run the expanded path, the steps after them the absorbed
    one; the reference expands everywhere and has no cache."""
    prompt = _prompt(length + 3, seed=length)
    cache = model.make_cache(2, max_len=32, block_size=4)
    slot = cache.alloc()
    prefill_slot(model, cache, slot, prompt[:length], chunk)
    theirs = _reference_at(weights, prompt, np.arange(length, length + 3))
    for i in range(3):
        logits = step_slots(model, cache, [slot],
                            np.asarray([[prompt[length + i]]], np.int32))
        np.testing.assert_allclose(logits[0], theirs[i], atol=1e-4)
    assert int(cache.lengths[slot]) == length + 3


def test_a_chunks_logits_are_the_full_forwards(weights, model):
    """A (1, k + 1) verify forward of speculative decoding is a chunk too:
    the expanded path with the head."""
    prompt = _prompt(14)
    cache = model.make_cache(1, max_len=32, block_size=4)
    slot = cache.alloc()
    prefill_slot(model, cache, slot, prompt[:9], 4)
    logits, _rows = forward_slots(model, cache, [slot],
                                  np.asarray([prompt[9:]], np.int32))
    np.testing.assert_allclose(
        logits[0], _reference_at(weights, prompt, np.arange(9, 14)),
        atol=1e-4)


def test_generate_serves_the_references_choice(weights, model):
    engine = GenerateEngine(model, model.make_cache(2, max_len=32,
                                                    block_size=4),
                            prefill_chunk=8, name="mla_test")
    prompts = [_prompt(11, 1), _prompt(5, 2)]
    served = engine.generate(prompts, max_new_tokens=4)
    for prompt, out in zip(prompts, served):
        tokens = prompt + out
        theirs = _reference_at(weights, tokens,
                               np.arange(len(prompt) - 1, len(tokens) - 1))
        top2 = np.sort(theirs, axis=1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 1e-3      # no close call
        assert np.array_equal(theirs.argmax(1)[clear], np.asarray(out)[clear])
    assert engine.last_stats["decode_tokens"] == 8


@pytest.mark.parametrize("chunk", [1, 3, 8])
def test_any_chunk_width_commits_the_same_rows(weights, model, chunk):
    """Width 1 prefills by the absorbed path, 3 and 8 by the expanded one
    (8 in two chunks, the last padded)."""
    prompt = _prompt(13, seed=5)

    def committed(width):
        cache = model.make_cache(1, max_len=16, block_size=4)
        slot = cache.alloc()
        prefill_slot(model, cache, slot, prompt, width)
        return [cache.prefix(name, slot) for name in cache.spec]
    for ours, whole in zip(committed(chunk), committed(13)):
        assert ours.shape == (13, 128)      # 16 + 8 values, then zeros
        assert np.abs(ours[:, :24]).min() > 0 and not ours[:, 24:].any()
        np.testing.assert_allclose(ours, whole, atol=1e-5)


# ------------------------------------------------------------- the two paths
def test_absorbed_and_expanded_attention_agree_on_the_same_rows():
    rng = np.random.default_rng(0)
    S, C, H, r, d_n, d_r, d_v, bs = 2, 3, 4, 16, 8, 8, 8, 4
    f = jnp.float32
    q_nope = jnp.asarray(rng.normal(size=(S, C, H, d_n)), f)
    q_rope = jnp.asarray(rng.normal(size=(S, C, H, d_r)), f)
    # rows of 24 values in 32 lanes: what lies past them is never read by
    # the expanded path and meets the query's zeros in the absorbed one
    new_rows = jnp.asarray(rng.normal(size=(S, C, 32)), f)
    kv_b = jnp.asarray(rng.normal(size=(r, H, d_n + d_v)) * 0.3, f)
    pool = np.asarray(rng.normal(size=(6, bs, 32)), np.float32)
    tables = np.asarray([[4, 1, 0], [2, 5, 3]], np.int32)
    lengths = np.asarray([6, 3], np.int32)
    assert latent_path(C) == "expanded" and latent_path(1) == "absorbed"
    expanded = paged_latent_attention(q_nope, q_rope, new_rows, kv_b,
                                      jnp.asarray(pool), tables, lengths,
                                      0.25, key_tile=8)
    for c in range(C):
        # the chunk's earlier rows stored where the cache would put them
        stored, at = pool.copy(), lengths.copy()
        for s in range(S):
            for j in range(c):
                block, off = divmod(int(lengths[s]) + j, bs)
                stored[tables[s, block], off] = np.asarray(new_rows[s, j])
            at[s] += c
        absorbed = paged_latent_attention(
            q_nope[:, c:c + 1], q_rope[:, c:c + 1], new_rows[:, c:c + 1],
            kv_b, jnp.asarray(stored), tables, at, 0.25, key_tile=4)
        np.testing.assert_allclose(absorbed[:, 0], expanded[:, c], atol=1e-5)


def test_the_tiles_walked_follow_the_longest_sequence():
    """A sequence with no past, beside one with a past of three tiles: the
    rows past a length are masked, whatever block the table names."""
    rng = np.random.default_rng(1)
    f = jnp.float32
    q_nope = jnp.asarray(rng.normal(size=(2, 1, 2, 4)), f)
    q_rope = jnp.asarray(rng.normal(size=(2, 1, 2, 2)), f)
    new_rows = jnp.asarray(rng.normal(size=(2, 1, 6)), f)
    kv_b = jnp.asarray(rng.normal(size=(4, 2, 7)), f)
    pool = jnp.asarray(rng.normal(size=(8, 2, 6)), f)
    tables = np.asarray([[0, 0, 0, 0, 0], [7, 6, 5, 4, 3]], np.int32)
    out = paged_latent_attention(q_nope, q_rope, new_rows, kv_b, pool,
                                 tables, np.asarray([0, 9], np.int32), 0.5,
                                 key_tile=4)
    # no past: the softmax is over the row itself, so the output is its value
    alone = np.einsum("r,rhv->hv", np.asarray(new_rows[0, 0, :4]),
                      np.asarray(kv_b[..., 4:]))
    np.testing.assert_allclose(out[0, 0], alone, atol=1e-5)
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------ the absorbed path's kernel
def _decode_case(rng, heads, dtype, lengths, blocks=8, bs=4, rank=16,
                 width=32):
    """A step's operands over a pool whose block tables are a permutation
    of the blocks, padded past a length with any valid id."""
    S = len(lengths)
    lengths = np.asarray(lengths, np.int32)
    tables = rng.permutation(S * blocks).reshape(S, blocks).astype(np.int32)
    for s, n in enumerate(lengths):
        used = -(-int(n) // bs)
        tables[s, used:] = rng.integers(0, S * blocks, size=blocks - used)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return {"q_nope": draw(S, 1, heads, 8), "q_rope": draw(S, 1, heads, 8),
            "new_rows": draw(S, 1, width),
            "kv_b": draw(rank, heads, 16) * jnp.asarray(0.3, dtype),
            "pool": draw(S * blocks, bs, width), "block_tables": tables,
            "lengths": lengths, "scale": 0.25}


# a tile is 8 positions (two blocks of 4); tables hold 32
_DECODE_LENGTHS = {
    "none_and_around_a_tile": [0, 7, 8, 9],
    "around_the_second_tile": [15, 16, 17],
    "longest_beside_shortest": [32, 1, 0, 31, 3],
    "one_sequence": [21],
}


@pytest.mark.parametrize("lengths", list(_DECODE_LENGTHS.values()),
                         ids=list(_DECODE_LENGTHS))
@pytest.mark.parametrize("dtype,atol", [("float32", 2e-6), ("bfloat16", 0.0)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [2, 8])
def test_the_decode_kernel_is_the_lax_walk(heads, dtype, atol, lengths):
    """``paged_latent_decode`` (interpreted) under the absorbed path
    against the ``lax`` walk on the same tiles: the same products in the
    same precisions, so bfloat16 agrees to the bit and float32 to an
    accumulation order."""
    case = _decode_case(np.random.default_rng(len(lengths) * heads), heads,
                        jnp.dtype(dtype), lengths)
    want = paged_latent_attention(**case, key_tile=8)
    got = paged_latent_attention(**case, key_tile=8, interpret=True)
    assert got.dtype == want.dtype and got.shape == (len(lengths), 1, heads, 8)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_the_decode_kernel_returns_the_pasts_running_state():
    """(m, l, acc) of each sequence's past alone: a sequence with none
    keeps the empty state, and acc / l is the softmax-weighted mean of its
    rows' first `rank` columns."""
    rng = np.random.default_rng(5)
    case = _decode_case(rng, 2, jnp.float32, [0, 11])
    query = jnp.asarray(rng.normal(size=(2, 2, 32)), jnp.float32)
    m, l, acc = paged_latent_decode(query, case["pool"], case["block_tables"],
                                    case["lengths"], 0.25, 16, key_tile=8,
                                    interpret=True)
    assert (m.shape, l.shape, acc.shape) == ((2, 2, 1), (2, 2, 1), (2, 2, 16))
    assert (np.asarray(m[0]) == -1e30).all()
    assert not np.asarray(l[0]).any() and not np.asarray(acc[0]).any()
    rows = np.asarray(case["pool"])[case["block_tables"][1, :3]]
    rows = rows.reshape(12, 32)[:11]
    scores = np.asarray(query[1]) @ rows.T * 0.25               # (H, 11)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(m[1, :, 0]), scores.max(-1),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(acc[1] / l[1]),
                               p @ rows[:, :16] / p.sum(-1, keepdims=True),
                               atol=1e-5)


def test_the_decode_kernel_reads_no_row_past_a_length():
    """The kernel twin of the test above: a sequence with no past beside
    one of three tiles. Whatever lies past a length changes nothing: the
    rest of a last block is masked, and a block past it (its table entry
    any valid id) is never fetched, so it may hold what would poison a
    product."""
    rng = np.random.default_rng(1)
    case = _decode_case(rng, 2, jnp.float32, [0, 21, 6], blocks=8)
    out = paged_latent_attention(**case, key_tile=8, interpret=True)
    # no past: the softmax is over the row itself, so the output is its value
    alone = np.einsum("r,rhv->hv", np.asarray(case["new_rows"][0, 0, :16]),
                      np.asarray(case["kv_b"][..., 8:]))
    np.testing.assert_allclose(out[0, 0], alone, atol=1e-5)
    pool = np.asarray(case["pool"]).copy()
    tables, live = case["block_tables"], np.zeros(len(pool), bool)
    for s, n in enumerate(case["lengths"]):
        used = -(-int(n) // 4)
        live[tables[s, :used]] = True
        if n % 4:
            pool[tables[s, used - 1], n % 4:] = 1e4     # masked, finite
    pool[~live] = np.nan                                # never fetched
    tables = tables.copy()
    tables[0, :] = np.flatnonzero(~live)[0]
    again = paged_latent_attention(**dict(
        case, pool=jnp.asarray(pool), block_tables=tables), key_tile=8,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


@pytest.mark.parametrize("pool,dtype,runs", [
    ((8, 128, 640), "bfloat16", True),      # a latent cache's block
    ((8, 16, 640), "bfloat16", True),
    ((8, 8, 640), "bfloat16", False),       # half a bfloat16 tile
    ((8, 8, 128), "float32", True),
    ((8, 4, 128), "float32", False),        # the toys of this file
    ((8, 16, 576), "bfloat16", False),      # rows that are no whole lanes
], ids=["latent_block", "16", "half_tile", "float32", "toy", "576_wide"])
def test_the_launch_is_chosen_where_a_block_is_whole_tiles(monkeypatch, pool,
                                                           dtype, runs):
    """On a TPU, by what the pool shows; never off it."""
    from incubator_mxnet_tpu.ops.pallas import paged_latent
    pool = jax.ShapeDtypeStruct(pool, jnp.dtype(dtype))
    assert not paged_latent.paged_latent_decode_available(pool)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_latent.paged_latent_decode_available()
    assert paged_latent.paged_latent_decode_available(pool) is runs


def test_the_kernels_walk_reads_each_sequences_own_rows():
    """``rows_walked`` at the long-prompt cell's lengths (16 prompts of
    2,048 to 16,384 tokens, 16-position blocks, tables of 1,056): the
    ``lax`` walk fetches the longest sequence's tiles for every row, 2.5
    times the live rows; the kernel's each sequence's own blocks."""
    from benchmarks import spec
    traffic = spec.load_json(spec.ROOT
                             + "/benchmarks/traffic/generate_long_prompts.json")
    lengths = [n - 1 + 128 for n in traffic["prompt_lens"]]     # mid-call
    live = sum(lengths)
    blocks = traffic["cache_max_len"] // 16
    assert rows_walked(lengths, 16, blocks, kernel=False) \
        == 16 * 33 * 512 > 2.4 * live
    kernel = rows_walked(lengths, 16, blocks, kernel=True)
    assert live <= kernel < 1.01 * live
    assert rows_walked([0, 0], 16, blocks, kernel=True) == 0
    assert rows_walked([0, 1], 16, blocks, kernel=False) == 2 * 512
    # tables narrower than a tile: the tile is the table
    assert rows_walked([5, 9], 4, 8, kernel=False) == 2 * 32
    assert rows_walked([5, 9], 4, 8, kernel=True) == 8 + 12


# ------------------------------------------------------------------ the cache
@pytest.mark.parametrize("row_bytes,max_len,block", [
    (640 * 2, 16896, 128),      # the long-prompt cell: 160 KB a block
    (640 * 2, 896, 128),        # the wide-batch cell
    (640 * 2, 200, 96),         # no more than half of max_len, in 16s
    (128 * 4, 32, 16),          # the toy: two blocks of 16 a slot
    (128 * 4, 4096, 320),
    (1 << 20, 4096, 16),        # 16 at least
], ids=["long_prompts", "wide_batch", "short_cache", "toy", "toy_long",
        "wide_row"])
def test_a_latent_caches_block_follows_its_rows_bytes(row_bytes, max_len,
                                                      block):
    assert latent_block_size(row_bytes, max_len) == block


def test_the_adapter_sizes_its_caches_blocks_and_a_given_size_wins(model):
    """``MLAPagedLM.make_cache`` takes the block size from the row (the
    toy's float32 rows of 128 lanes: 320 positions, held to half of
    `max_len`), whatever ``MXTPU_GEN_BLOCK_SIZE`` says."""
    assert model.make_cache(2, max_len=32).block_size == 16
    assert model.make_cache(2, max_len=2048).block_size == 320
    assert model.make_cache(2, max_len=2048, block_size=4).block_size == 4
    served = MLAPagedLM({}, dict(model.config), dtype="bfloat16")
    served.kv_entries = {"c": ((640,), jnp.dtype("bfloat16"))}
    cache = served.make_cache(1, max_len=896)
    assert (cache.block_size, cache.max_blocks_per_slot) == (128, 7)
    assert cache.pool("c0").shape == (7, 128, 640)


def test_the_cache_holds_one_latent_row_a_position_and_no_k_or_v(model):
    # 16 + 8 values in a row of whole lanes (576 in 640 as published)
    assert cache_row_width(16, 8) == 128 and cache_row_width(512, 64) == 640
    assert model.kv_entries == {"c": ((128,), jnp.float32)}
    cache = model.make_cache(3, max_len=32, block_size=4)
    assert list(cache.spec) == ["c0", "c1", "c2"]
    assert all(cache.pool(n).shape == (24, 4, 128) for n in cache.spec)
    lengths, tables, pools = cache.forward_inputs([0, 1])
    assert len(pools) == 3 and tables.shape == (2, 8)
    # the commit program takes one entry: (pools, new rows, positions)
    lowered = cache.lower_commit(jax.ShapeDtypeStruct((3, 2, 1, 128),
                                                      jnp.float32))
    (entry,), rows = lowered.in_avals[0][:1], lowered.in_avals[0][-1]
    assert [a.shape for a in entry] == [(24, 4, 128)] * 3
    assert rows.shape == (2, 1)


def test_layer_spec_names_the_entries_a_layer_declares():
    spec = PagedKVCache.layer_spec(2, {"k": ((2, 4), np.float32),
                                       "v": ((2, 4), np.float32)})
    assert list(spec) == ["k0", "v0", "k1", "v1"]
    cache = PagedKVCache(1, spec, max_len=8, block_size=4)
    _lengths, _tables, k_pools, v_pools = cache.forward_inputs([0])
    assert len(k_pools) == len(v_pools) == 2
    mixed = PagedKVCache(1, {"c0": ("kv", (6,)), "c2": ("kv", (6,))},
                         max_len=8)
    with pytest.raises(ValueError, match="<entry><i>"):
        mixed.forward_inputs([0])


# ---------------------------------------------------------- hyper-connections
def test_sinkhorn_leaves_rows_and_columns_that_sum_to_one(weights):
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.normal(size=(4, 7, 32)), jnp.float32)
    cfg = mla_moe.mla_config(family.program_config(CFG))
    pre, post, res = mla_moe.hyper_connection_maps(weights, "l1_ffn_", cfg, X)
    assert pre.shape == post.shape == (4, 7) and res.shape == (4, 4, 7)
    np.testing.assert_allclose(res.sum(axis=0), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-3)
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and float(post.max()) < 2
    # far from the identity and from uniform, and not one map for all tokens
    assert float(jnp.std(res, axis=2).mean()) > 0.02
    # the reference's maps, written token-major, are the same numbers
    theirs = reference.mappings(
        X.transpose(1, 0, 2), weights["l1_ffn_hc_phi"],
        weights["l1_ffn_hc_alpha"], weights["l1_ffn_hc_b"], CFG)
    np.testing.assert_allclose(pre.T, theirs[0], atol=1e-5)
    np.testing.assert_allclose(post.T, theirs[1], atol=1e-5)
    np.testing.assert_allclose(res.transpose(2, 0, 1), theirs[2], atol=1e-5)


def test_the_streams_sum_moves_by_the_post_weights_times_the_output(weights):
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(4, 5, 32)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    cfg = mla_moe.mla_config(family.program_config(CFG))
    _pre, post, _res = mla_moe.hyper_connection_maps(weights, "l0_attn_",
                                                     cfg, X)
    after = mla_moe._hyper(weights, "l0_attn_", cfg, X,
                           weights["l0_attn_norm"], lambda h: y)
    np.testing.assert_allclose(
        after.sum(axis=0), X.sum(axis=0) + post.sum(axis=0)[:, None] * y,
        atol=2e-3)


def test_yarn_low_high_and_scale_are_the_published_numbers():
    freq, low, high = reference.yarn_frequencies(PUBLISHED_YARN)
    assert (low, high) == (10, 23)
    assert reference.softmax_scale(PUBLISHED_YARN) == pytest.approx(
        0.144679, abs=1e-6)
    ours = mla_moe.yarn_inv_freq(64, 10000.0, YARN)
    np.testing.assert_allclose(ours, freq, rtol=1e-12)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(ours[:11], plain[:11])       # kept
    np.testing.assert_allclose(ours[23:], plain[23:] / 64)  # divided
    assert mla_moe.attention_scale(
        {"nope_dim": 128, "rope_dim": 64, "yarn": YARN}) == pytest.approx(
            0.144679, abs=1e-6)
    assert mla_moe.attention_scale(
        {"nope_dim": 128, "rope_dim": 64, "yarn": None}) == 192 ** -0.5


# ------------------------------------------------------------ the expert layer
def _experts(rng, experts=8, d=16, f=12):
    return [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for s in ((d, experts), (experts, d, f), (experts, d, f),
                      (experts, f, d))]


def _sigmoid_loop(x, router_w, gate_w, up_w, down_w, k, bias, scale,
                  shared=None):
    """Every token's experts one at a time, in float64: chosen by score +
    bias, weighed by score."""
    x, router_w, gate_w, up_w, down_w, bias = [
        np.asarray(a, np.float64) for a in (x, router_w, gate_w, up_w,
                                            down_w, bias)]

    def expert(row, gate, up, down):
        g = row @ gate
        return (g / (1 + np.exp(-g)) * (row @ up)) @ down
    out, chosen_all = np.zeros_like(x), []
    for t, row in enumerate(x):
        s = 1 / (1 + np.exp(-(row @ router_w)))
        chosen = np.argsort(-(s + bias), kind="stable")[:k]
        chosen_all.append(sorted(chosen))
        for e in chosen:
            out[t] += scale * s[e] / (s[chosen].sum() + 1e-20) * expert(
                row, gate_w[e], up_w[e], down_w[e])
        if shared is not None:
            out[t] += expert(row, *[np.asarray(a, np.float64)
                                    for a in shared])
    return out, chosen_all


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(7)
    router_w, gate_w, up_w, down_w = _experts(rng)
    x = jnp.asarray(rng.normal(size=(21, 16)), jnp.float32)
    none = jnp.zeros((8,), jnp.float32)
    planted = none.at[3].set(5.0)           # expert 3: every token's choice

    def layer(bias):
        return moe_dropless(x, router_w, gate_w, up_w, down_w, 2,
                            return_stats=True, scoring="sigmoid",
                            choice_bias=bias, route_scale=2.0)
    for bias in (none, planted):
        out, stats = layer(bias)
        want, chosen = _sigmoid_loop(x, router_w, gate_w, up_w, down_w, 2,
                                     bias, 2.0)
        np.testing.assert_allclose(out, want, atol=2e-5)
        assert np.asarray(stats["expert_load"]).tolist() == [
            sum(e in c for c in chosen) for e in range(8)]
    assert int(layer(planted)[1]["expert_load"][3]) == 21
    assert int(layer(none)[1]["expert_load"][3]) < 21       # a choice flipped
    # ... and no weight: a bias that leaves every choice as it was (the
    # same for all experts) leaves the output as it was
    np.testing.assert_array_equal(layer(none + 0.25)[0], layer(none)[0])


def test_the_shared_expert_is_added_once_and_is_not_a_route():
    rng = np.random.default_rng(8)
    router_w, gate_w, up_w, down_w = _experts(rng)
    shared = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
              for s in ((16, 12), (16, 12), (12, 16))]
    x = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.1, jnp.float32)
    kw = dict(scoring="sigmoid", choice_bias=bias, route_scale=2.0,
              return_stats=True)
    with_shared, stats = moe_dropless(x, router_w, gate_w, up_w, down_w, 2,
                                      shared=shared, **kw)
    routed, routed_stats = moe_dropless(x, router_w, gate_w, up_w, down_w, 2,
                                        **kw)
    want, _ = _sigmoid_loop(x, router_w, gate_w, up_w, down_w, 2, bias, 2.0,
                            shared)
    np.testing.assert_allclose(with_shared, want, atol=2e-5)
    gate = x @ shared[0]
    np.testing.assert_allclose(
        with_shared - routed,
        (jax.nn.silu(gate) * (x @ shared[1])) @ shared[2], atol=2e-5)
    assert np.array_equal(stats["expert_load"], routed_stats["expert_load"])
    assert int(stats["expert_load"].sum()) == 9 * 2


def test_the_dropless_layers_defaults_are_the_softmax_layer_as_it_was():
    rng = np.random.default_rng(9)
    router_w, gate_w, up_w, down_w = _experts(rng)
    x = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    plain = moe_dropless(x, router_w, gate_w, up_w, down_w, 2)
    spelled = moe_dropless(x, router_w, gate_w, up_w, down_w, 2,
                           scoring="softmax", choice_bias=None,
                           route_scale=1.0, shared=None)
    np.testing.assert_array_equal(plain, spelled)
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    want = np.zeros((11, 16), np.float32)
    for t in range(11):
        for p, e in zip(top_p[t] / top_p[t].sum(), top_e[t]):
            want[t] += p * ((jax.nn.silu(x[t] @ gate_w[e])
                             * (x[t] @ up_w[e])) @ down_w[e])
    np.testing.assert_allclose(plain, want, atol=2e-5)
    with pytest.raises(ValueError, match="no such scoring"):
        moe_dropless(x, router_w, gate_w, up_w, down_w, 2, scoring="tanh")


# -------------------------------------------------------------------- tallies
@pytest.fixture()
def _metrics():
    """Metrics on for one test: left on, every later test of the worker
    records spans with nothing listening."""
    telemetry.enable()
    yield
    telemetry.disable()


def test_a_traced_call_says_which_path_every_forward_ran(model, _metrics):
    """Prefill runs the expanded path and counts the cached rows it
    expands again; a decode step runs the absorbed one and counts the rows
    its walk had to read and did."""
    engine = GenerateEngine(model, model.make_cache(2, max_len=32,
                                                    block_size=4),
                            prefill_chunk=4, name="mla_tally")
    before = {c: c.value(model="mla_tally") for c in (
        cat.mla_absorbed_forwards, cat.mla_expanded_forwards,
        cat.mla_expanded_kernel_forwards, cat.mla_expanded_rows,
        cat.mla_absorbed_rows_live, cat.mla_absorbed_rows_read)}
    tracing.clear_spans()
    with tracing.Span("test.call"):
        engine.generate([_prompt(10), _prompt(6)], max_new_tokens=3)
    mla = engine.last_stats["mla"]
    # 9 tokens in chunks of 4 at lengths 0, 4, 8; 5 tokens at 0, 4
    # the steps' lengths (9, 5), (10, 6), (11, 7); off the TPU the walk is
    # the lax one: both rows' tiles up to the longest, a tile the whole
    # table of 8 blocks of 4
    # table of 8 blocks of 4; off the TPU no chunk's attention is the launch
    assert mla == {"absorbed_forwards": 3, "expanded_forwards": 5,
                   "expanded_kernel_forwards": 0,
                   "expanded_rows": 4 + 8 + 4, "absorbed_rows_live": 48,
                   "absorbed_rows_read": 3 * 2 * 32}
    moe = engine.last_stats["moe"]
    assert moe["forwards"] == 8
    # two expert layers: a step of 2 rows makes 2 x 2 routes in each
    assert moe["routes"] == 2 * (5 * 4 * 2 + 3 * 2 * 2)
    assert cat.mla_absorbed_forwards.value(model="mla_tally") \
        - before[cat.mla_absorbed_forwards] == 3
    assert cat.mla_expanded_forwards.value(model="mla_tally") \
        - before[cat.mla_expanded_forwards] == 5
    assert cat.mla_expanded_kernel_forwards.value(model="mla_tally") \
        == before[cat.mla_expanded_kernel_forwards]
    assert cat.mla_expanded_rows.value(model="mla_tally") \
        - before[cat.mla_expanded_rows] == 16
    assert cat.mla_absorbed_rows_live.value(model="mla_tally") \
        - before[cat.mla_absorbed_rows_live] == 48
    assert cat.mla_absorbed_rows_read.value(model="mla_tally") \
        - before[cat.mla_absorbed_rows_read] == 192
    paths = [s["mla_path"] for s in tracing.recent_spans()
             if s["name"] == "lm.dispatch"]
    assert paths == ["expanded"] * 5 + ["absorbed"] * 3


def test_where_the_launch_is_available_every_chunk_runs_it(weights, model,
                                                           monkeypatch,
                                                           _metrics):
    """``paged_latent_prefill_available`` answering yes (as on a TPU over
    a cache of whole tiles) and the launch interpreted: every expanded
    forward of a call is counted as the kernel's,
    ``expanded_kernel_forwards`` beside ``expanded_forwards`` in
    ``last_stats["mla"]`` and on its counter, and the call serves the
    tokens of the ``lax`` path."""
    from incubator_mxnet_tpu.generate import engine as engine_module
    from incubator_mxnet_tpu.ops.pallas import paged_latent
    prompts = [_prompt(10), _prompt(6, seed=1)]

    def generate(lm, name):
        engine = GenerateEngine(lm, lm.make_cache(2, max_len=32,
                                                  block_size=4),
                                prefill_chunk=4, name=name)
        return engine.generate(prompts, max_new_tokens=3), engine.last_stats
    want, stats = generate(model, "mla_lax")
    assert stats["mla"]["expanded_kernel_forwards"] == 0
    launches, prefill = [], paged_latent.paged_latent_prefill

    def launch(*args, **kw):
        launches.append(args[0].shape)
        return prefill(*args, **dict(kw, interpret=True))
    for module in (paged_latent, engine_module):
        monkeypatch.setattr(module, "paged_latent_prefill_available",
                            lambda *shapes: True)
    monkeypatch.setattr(paged_latent, "paged_latent_prefill", launch)
    before = cat.mla_expanded_kernel_forwards.value(model="mla_kernel")
    # a model of its own: the fixture's programs are traced already
    fresh = MLAPagedLM(weights, family.program_config(CFG), dtype="float32")
    got, stats = generate(fresh, "mla_kernel")
    mla = stats["mla"]
    assert mla["expanded_kernel_forwards"] == mla["expanded_forwards"] == 5
    assert cat.mla_expanded_kernel_forwards.value(model="mla_kernel") \
        - before == 5
    # one trace of the prefill program: a launch a layer, a chunk of (1, 4)
    assert launches == [(1, 4, 4, 8)] * 3
    assert got == want


def test_the_prefill_launch_is_chosen_by_backend_and_shapes(monkeypatch):
    """On a TPU, by what the pool, the chunk and the widths show; never
    off it: a block of whole tiles, a chunk of whole sublane tiles, the
    latent, a head's keys and its values whole lanes wide."""
    from incubator_mxnet_tpu.ops.pallas import paged_latent
    available = paged_latent.paged_latent_prefill_available
    pool = jax.ShapeDtypeStruct((8, 128, 640), jnp.bfloat16)
    assert not available(pool, 1024, 512, 128, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert available(pool, 1024, 512, 128, 128)         # the long prompts
    assert available(pool, 512, 512, 128, 128)          # the wide batch
    assert available(pool, 16, 512, 128, 128)
    assert not available(pool, 8, 512, 128, 128)        # half a tile of rows
    assert not available(pool, 1024, 512, 128, 64)      # values of half lanes
    assert not available(pool, 1024, 512, 192, 128)
    assert not available(pool, 1024, 576, 128, 128)
    assert not available(jax.ShapeDtypeStruct((8, 8, 640), jnp.bfloat16),
                         1024, 512, 128, 128)
    # the toys of this file stay on the lax path
    assert not available(jax.ShapeDtypeStruct((8, 4, 128), jnp.float32),
                         4, 16, 8, 8)
    assert paged_latent._head_group(32, 1024) == 4
    assert paged_latent._head_group(64, 512) == 8
    assert paged_latent._head_group(4, 8) == 4
    assert paged_latent._head_group(7, 8192) == 1


def test_the_configuration_maps_every_published_width(model):
    cfg = model.config
    assert (cfg["q_rank"], cfg["kv_rank"], cfg["nope_dim"], cfg["rope_dim"],
            cfg["v_dim"]) == (16, 16, 8, 8, 8)
    shapes = mla_moe.mla_param_shapes(cfg)
    assert set(shapes) == set(model.params)
    assert all(tuple(model.params[n].shape) == s for n, s in shapes.items())
    assert shapes["l0_gate_w"] == (32, 64)              # the dense layer
    assert shapes["l1_gate_w"] == (8, 32, 16)           # an expert layer
    assert shapes["l1_attn_hc_phi"] == (4 * 32, 24)
    with pytest.raises(ValueError, match="missing 'kv_rank'"):
        mla_moe.mla_config({k: v for k, v in cfg.items() if k != "kv_rank"})


@pytest.mark.parametrize("rows,tile", [(40, 16), (256, 128), (300, 128)],
                         ids=["decode_sized", "half_full", "ragged"])
def test_the_grouped_product_takes_tall_tiles_where_the_groups_are_full(
        rows, tile):
    """A prefill chunk's routes fill their experts: tiles of 128 rows, the
    MXU's height; a decode step's keep the 16-row tiles. Either way the
    launch (interpret mode) is ``ragged_dot``."""
    from incubator_mxnet_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul, tile_rows_for)
    groups = 4
    assert tile_rows_for(rows, groups) == tile
    assert tile_rows_for(768, 128) == tile_rows_for(512, 128) == 16   # SDAR's
    assert tile_rows_for(8192, 64) == 128 and tile_rows_for(64, 64) == 16
    rng = np.random.default_rng(rows)
    sizes = rng.multinomial(rows, [0.5, 0.0, 0.3, 0.2]).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(rows, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(groups, 24, 128)), jnp.float32)
    got = grouped_matmul(x, w, jnp.asarray(sizes), interpret=True)
    want = jax.lax.ragged_dot(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(got, want, atol=1e-4)


# ===================================================== one chip's share
# The ``kimi_k2`` family on the same layer body: ONE residual stream (no
# ``hc_*`` leaves) and an expert layer that holds a SHARE of its experts:
# 24 experts top-8 over 8 shares of 3, this chip share 2 (the experts 6-8).
KIMI = {"hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8, "intermediate_size": 64, "n_routed_experts": 3,
        "router_width": 24, "num_experts_per_tok": 8,
        "moe_intermediate_size": 16, "n_shared_experts": 1,
        "first_k_dense_replace": 1, "num_hidden_layers": 3,
        "vocab_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 50000,
        "routed_scaling_factor": 2.827, "max_position_embeddings": 64,
        "rope_scaling": YARN, "dtype": "float32", "seed_weight_range": 0.2,
        "assumed": {"initializer_range": {"value": 0.02},
                    "router_bias_range": {"value": 0.1},
                    "prefill_chunk": {"value": 8},
                    "expert_share_index": {"value": 2}}}


@pytest.fixture(scope="module")
def share_weights():
    return share_reference.init_weights(KIMI, 5)


@pytest.fixture(scope="module")
def share_model(share_weights):
    return MLAPagedLM(share_weights, share_family.program_config(KIMI),
                      dtype="float32")


def _share_reference_at(weights, tokens, positions):
    return share_reference.logits(weights, KIMI, [tokens], [positions],
                                  block_rows=8)[0]


def _share_logits_through_the_cache(model, prompt, length, chunk):
    cache = model.make_cache(2, max_len=32, block_size=4)
    slot = cache.alloc()
    prefill_slot(model, cache, slot, prompt[:length], chunk)
    return np.stack([step_slots(
        model, cache, [slot],
        np.asarray([[prompt[length + i]]], np.int32))[0] for i in range(3)])


@pytest.mark.parametrize("length,chunk", [(21, 8), (9, 8), (6, 8)],
                         ids=["chunks_and_tail", "one_over",
                              "under_a_chunk"])
def test_one_stream_and_a_held_share_through_the_cache_is_the_full_forward(
        share_weights, share_model, length, chunk):
    """Prefill (the expanded path) then decode (the absorbed one) with one
    residual stream and the experts 6-8 of 24 held, against the reference's
    full forward, which routes over all 24 and adds only the held experts'
    part. Tolerance 1e-4 on logits of spread ~1: float32 sums in another
    order (the cache's tiles, the absorbed products, the layer's sorted
    routes) read under 1e-5 here; the same model served in bfloat16 reads
    over 1e-2 (the next test), so a lower precision fails it a hundredfold."""
    prompt = _prompt(length + 3, seed=length)
    ours = _share_logits_through_the_cache(share_model, prompt, length,
                                           chunk)
    theirs = _share_reference_at(share_weights, prompt,
                                 np.arange(length, length + 3))
    np.testing.assert_allclose(ours, theirs, atol=1e-4)


def test_a_bfloat16_run_of_the_float32_toy_fails_that_tolerance(
        share_weights):
    prompt = _prompt(24, seed=21)
    low = MLAPagedLM(share_weights, share_family.program_config(KIMI),
                     dtype="bfloat16")
    ours = _share_logits_through_the_cache(low, prompt, 21, 8)
    theirs = _share_reference_at(share_weights, prompt, np.arange(21, 24))
    assert np.abs(ours - theirs).max() > 1e-2


def test_one_stream_has_no_hyper_connection_leaves_and_holds_its_share(
        share_model):
    cfg = share_model.config
    assert cfg["streams"] is None and cfg["experts_held"] == (6, 3)
    shapes = mla_moe.mla_param_shapes(cfg)
    assert set(shapes) == set(share_model.params)
    assert not [n for n in shapes if "hc_" in n]
    assert shapes["l1_router_w"] == (32, 24)        # the router: all 24
    assert shapes["l1_router_bias"] == (24,)
    assert shapes["l1_gate_w"] == shapes["l1_up_w"] == (3, 32, 16)
    assert shapes["l1_down_w"] == (3, 16, 32)       # the experts: its 3
    # ``streams`` is not required: a configuration without it is one stream
    assert "streams" not in share_family.program_config(KIMI)


def _share_layer(rng, experts=24, d=16, f=12, tokens=40):
    router_w, gate_w, up_w, down_w = _experts(rng, experts, d, f)
    shared = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
              for s in ((d, f), (d, f), (f, d))]
    x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=experts) * 0.1, jnp.float32)
    return x, router_w, gate_w, up_w, down_w, shared, bias


def _held(x, router_w, gate_w, up_w, down_w, bias, first, count, **kw):
    return moe_dropless(
        x, router_w, gate_w[first:first + count], up_w[first:first + count],
        down_w[first:first + count], 8, return_stats=True,
        scoring="sigmoid", choice_bias=bias, route_scale=2.827,
        held=(first, count), **kw)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "tiles_interpreted"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(interpret):
    """THE SHARE TEST: 24 experts in 8 shares of 3. The eight ``out_here``
    less the shared expert's part, summed, plus the shared part once, are
    the uncut layer: ``moe_dropless(held=None)``'s, a float64 loop's, and
    the plain reference's (its share loop at all 24 held). Off the TPU a
    pass's product is ``ragged_dot`` on the order itself; `interpret` runs
    the launch's tile layout."""
    rng = np.random.default_rng(11)
    x, router_w, gate_w, up_w, down_w, shared, bias = _share_layer(rng)
    whole, stats = moe_dropless(
        x, router_w, gate_w, up_w, down_w, 8, return_stats=True,
        scoring="sigmoid", choice_bias=bias, route_scale=2.827,
        shared=shared)
    gate = x @ shared[0]
    shared_part = (jax.nn.silu(gate) * (x @ shared[1])) @ shared[2]
    total, loads, elsewhere = shared_part, [], 0
    for share in range(8):
        out_here, here = _held(x, router_w, gate_w, up_w, down_w, bias,
                               3 * share, 3, shared=shared,
                               interpret=interpret)
        total = total + (out_here - shared_part)
        loads.append(np.asarray(here["expert_load"]))
        elsewhere += int(here["routes_elsewhere"])
        assert loads[-1].shape == (3,)
        assert int(here["routes_elsewhere"]) == 40 * 8 - loads[-1].sum()
    np.testing.assert_allclose(total, whole, atol=3e-5)
    want, _chosen = _sigmoid_loop(x, router_w, gate_w, up_w, down_w, 8,
                                  bias, 2.827, shared)
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert np.concatenate(loads).tolist() == np.asarray(
        stats["expert_load"]).tolist()
    assert elsewhere == 7 * 40 * 8      # every route is someone's, once
    # the plain reference, told it holds all 24, and told it holds 3
    cfg = dict(KIMI, hidden_size=16, moe_intermediate_size=12)
    w = {"router_w": router_w, "router_bias": bias, "gate_w": gate_w,
         "up_w": up_w, "down_w": down_w, "shared_gate_w": shared[0],
         "shared_up_w": shared[1], "shared_down_w": shared[2]}
    uncut = share_reference._Frozen(dict(cfg, n_routed_experts=24,
                                         assumed={"expert_share_index":
                                                  {"value": 0}}))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            share_reference._experts_here(w, "", uncut, "float32", x, 40),
            total, atol=3e-5)
        for share in (0, 5):
            part = dict(w, **{n: w[n][3 * share:3 * share + 3]
                              for n in ("gate_w", "up_w", "down_w")})
            theirs = share_reference._experts_here(
                part, "", share_reference._Frozen(dict(
                    cfg, assumed={"expert_share_index": {"value": share}})),
                "float32", x, 40)
            ours, _ = _held(x, router_w, gate_w, up_w, down_w, bias,
                            3 * share, 3, shared=shared)
            np.testing.assert_allclose(ours, theirs, atol=3e-5)


def test_a_skewed_router_drops_nothing_and_an_idle_share_adds_nothing():
    """Every token's 8 routes on the 8 held experts: 8 times the even
    load, computed in several passes of the bound, none dropped. And a
    share that no token chooses returns the shared part alone and moves
    no row."""
    from incubator_mxnet_tpu.parallel.moe import share_bound
    rng = np.random.default_rng(12)
    x, router_w, gate_w, up_w, down_w, shared, bias = _share_layer(rng)
    planted = bias.at[8:16].add(5.0)         # the held range: all chosen
    out, stats = _held(x, router_w, gate_w, up_w, down_w, planted, 8, 8)
    want, chosen = _sigmoid_loop(x, router_w, gate_w, up_w, down_w, 8,
                                 planted, 2.827)
    assert all(c == list(range(8, 16)) for c in chosen)
    np.testing.assert_allclose(out, want, atol=3e-5)
    assert np.asarray(stats["expert_load"]).tolist() == [40] * 8
    assert int(stats["routes_elsewhere"]) == 0
    bound = share_bound(40, 8, 24, 8)
    assert bound == 224 < 320       # twice the even load, under the skew's
    assert int(stats["rows_moved"]) == 2 * bound    # two passes, no tiles
    # the same through the launch's tiles: 16-row tiles, 8 groups
    tiled, tiled_stats = _held(x, router_w, gate_w, up_w, down_w, planted,
                               8, 8, interpret=True)
    np.testing.assert_allclose(tiled, want, atol=3e-5)
    assert int(tiled_stats["rows_moved"]) == 2 * ((bound + 8 * 15) // 16
                                                  * 16)
    shunned = bias.at[8:16].add(-5.0)       # the held range: never chosen
    gate = x @ shared[0]
    for interpret in (False, True):
        out, stats = _held(x, router_w, gate_w, up_w, down_w, shunned, 8, 8,
                           shared=shared, interpret=interpret)
        np.testing.assert_allclose(
            out, (jax.nn.silu(gate) * (x @ shared[1])) @ shared[2],
            atol=2e-5)
        assert not np.asarray(stats["expert_load"]).any()
        assert int(stats["routes_elsewhere"]) == 320
        assert int(stats["rows_moved"]) == 0


def test_a_layer_that_holds_every_expert_is_the_layer_with_no_share():
    rng = np.random.default_rng(13)
    x, router_w, gate_w, up_w, down_w, shared, bias = _share_layer(rng)
    whole, stats = moe_dropless(
        x, router_w, gate_w, up_w, down_w, 8, return_stats=True,
        scoring="sigmoid", choice_bias=bias, route_scale=2.827,
        shared=shared)
    held, held_stats = _held(x, router_w, gate_w, up_w, down_w, bias, 0, 24,
                             shared=shared)
    np.testing.assert_allclose(held, whole, atol=2e-5)
    assert np.array_equal(held_stats["expert_load"], stats["expert_load"])
    assert int(held_stats["routes_elsewhere"]) == 0
    assert set(stats) == {"expert_load"}        # no share, no share's tally
    with pytest.raises(ValueError, match=r"held=\(20, 8\) must lie inside"):
        _held(x, router_w, gate_w, up_w, down_w, bias, 20, 8)
    with pytest.raises(ValueError, match="3 experts' weights"):
        moe_dropless(x, router_w, gate_w, up_w, down_w, 8, held=(0, 3))
