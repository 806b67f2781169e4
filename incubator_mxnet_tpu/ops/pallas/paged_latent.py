"""Paged attention over a LATENT cache (multi-head latent attention).

A layer caches ONE row a position, shared by all heads: ``[c | k_r | 0]``,
the normalised low-rank latent (``r`` wide), the rotated key part (``d_r``)
and zeros up to a whole number of the device's 128 lanes, in a pool of
``(num_blocks, block_size, W)`` (``generate/paged_kv``). (A TPU holds a pool
whose rows are 576 wide with its BLOCKS minor, and every forward would copy
each pool into row order to gather from it; at 640 the rows are whole tiles,
which a 576-wide row's tiles pad to anyway: ``cache_row_width``.)
Per head the keys and values are the latent's up-projection, ``[k_n,h |
v_h] = c W_kvb,h``, and the score of query ``[q_n,h | q_r,h]`` against
position j is ``scale * (q_n,h . k_n,h(j) + q_r,h . k_r(j))``.

Two paths compute the same numbers, and the chunk width chooses:

- **expanded** (a chunk of C > 1 positions: prefill): every tile of cached
  rows is up-projected to per-head keys and values again, the chunk's own
  rows too, and the chunk's queries attend past + chunk causally. The work
  is the products'; the price of chunking is the re-expansion of the rows
  that earlier chunks cached.
- **absorbed** (C = 1: a decode step): ``W_kvb``'s key half is folded into
  the query, ``q'_h = [q_n,h W_uk,h^T | q_r,h]``, its value half into the
  output, ``o_h = (sum_j p_h(j) c(j)) W_uv,h``, so that a step reads the
  cached rows alone: H query heads against one shared row, keys the whole
  row, values its first ``r``.

Both walk the block table in tiles of ``key_tile`` positions under a running
softmax (no (C, L, H) score array exists), as many tiles as the longest
sequence of the call has: a cache sized for long sequences costs a short
one nothing. Plain ``lax``: the TPU's compiler refuses the Mosaic kernel of
``flash_decode.py``, and an absorbed-path kernel is not written yet.
Products take their operands in the cache's dtype and accumulate in
float32; scores, the softmax and its running statistics are float32.
"""

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

#: cached positions a tile of the running softmax
KEY_TILE = 512

#: a cache row is whole lanes wide
LANES = 128

__all__ = ["paged_latent_attention", "latent_path", "cache_row_width"]


def cache_row_width(kv_rank, rope_dim):
    """The width of a cache row: ``[c | k_r]`` and zeros up to whole lanes."""
    return -(-(kv_rank + rope_dim) // LANES) * LANES


def latent_path(chunk):
    """Which path a chunk `chunk` positions wide runs."""
    return "absorbed" if chunk == 1 else "expanded"


def _fold(state, scores, mask, values, spec):
    """One tile of keys into a running softmax. `state`: (m, l, acc), the
    row maximum and denominator (S, H, C) and the unnormalised output
    (S, H, C, W), float32; `scores` (S, H, C, T) float32; `mask`
    broadcastable to them; the tile's values enter by ``einsum(spec, p,
    values)``."""
    m, l, acc = state
    scores = jnp.where(mask, scores, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # a row with no live key yet has scores - m_new = 0 everywhere
    p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
    alpha = jnp.exp(m - m_new)
    acc = acc * alpha[..., None] + jnp.einsum(
        spec, p.astype(values.dtype), values,
        preferred_element_type=jnp.float32)
    return m_new, l * alpha + jnp.sum(p, axis=-1), acc


def _over_past(state, fold_rows, pool, block_tables, lengths, key_tile):
    """`fold_rows(state, rows (S, T, W), live (S, 1, 1, T))` over the
    cached rows of every sequence, tile by tile of its block table, up to
    the longest sequence's length."""
    S, blocks = block_tables.shape
    block_size = pool.shape[1]
    tile_blocks = max(1, min(key_tile // block_size, blocks))
    T = tile_blocks * block_size
    block_tables = jnp.pad(block_tables,
                           ((0, 0), (0, -blocks % tile_blocks)))

    def one_tile(j, state):
        ids = jax.lax.dynamic_slice_in_dim(block_tables, j * tile_blocks,
                                           tile_blocks, axis=1)
        rows = pool[ids].reshape(S, T, pool.shape[2])
        live = j * T + jnp.arange(T)[None, :] < lengths[:, None]
        return fold_rows(state, rows, live[:, None, None, :])
    return jax.lax.fori_loop(0, (jnp.max(lengths) + T - 1) // T, one_tile,
                             state)


def paged_latent_attention(q_nope, q_rope, new_rows, kv_b, pool,
                           block_tables, lengths, scale, key_tile=KEY_TILE):
    """Attention of a chunk over its sequence's cached rows and itself.

    q_nope (S, C, H, d_n), q_rope (S, C, H, d_r) rotated: the chunk's
    queries; new_rows (S, C, W): the chunk's own rows ``[c | k_r | 0]``,
    NOT yet in the pool; kv_b (r, H, d_n + d_v): ``W_kvb``, a head's
    columns its ``[k_n | v]``; pool (num_blocks, block_size, W);
    block_tables (S, MB) int32 (pad with any valid block id); lengths (S,)
    int32 committed past positions. Position c of the chunk attends every
    past position and the chunk's positions <= c.

    -> (S, C, H, d_v), in q_nope's dtype. C = 1 runs the absorbed path,
    any other width the expanded one (``latent_path``)."""
    S, C, H, d_n = q_nope.shape
    r, d_r = kv_b.shape[0], q_rope.shape[-1]
    dtype = q_nope.dtype
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    w_k, w_v = kv_b[..., :d_n], kv_b[..., d_n:]

    if latent_path(C) == "absorbed":
        # the key half of W_kvb folded into the query, as wide as a row:
        # (S, C, H, W), zeros against a row's zeros
        query = jnp.concatenate(
            [jnp.einsum("schn,rhn->schr", q_nope, w_k,
                        preferred_element_type=jnp.float32).astype(dtype),
             q_rope, jnp.zeros((S, C, H, pool.shape[2] - r - d_r), dtype)],
            axis=-1)
        width = r

        def fold_rows(state, rows, mask):
            scores = jnp.einsum("schw,stw->shct", query, rows,
                                preferred_element_type=jnp.float32) * scale
            return _fold(state, scores, mask, rows[..., :r],
                         "shct,str->shcr")
    else:
        width = w_v.shape[-1]

        def fold_rows(state, rows, mask):
            latent, k_rope = rows[..., :r], rows[..., r:r + d_r]
            k_nope = jnp.einsum("str,rhn->sthn", latent, w_k,
                                preferred_element_type=jnp.float32)
            values = jnp.einsum("str,rhv->sthv", latent, w_v,
                                preferred_element_type=jnp.float32)
            scores = (jnp.einsum("schn,sthn->shct", q_nope,
                                 k_nope.astype(dtype),
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("schd,std->shct", q_rope, k_rope,
                                   preferred_element_type=jnp.float32))
            return _fold(state, scores * scale, mask, values.astype(dtype),
                         "shct,sthv->shcv")

    state = (jnp.full((S, H, C), _NEG_INF, jnp.float32),
             jnp.zeros((S, H, C), jnp.float32),
             jnp.zeros((S, H, C, width), jnp.float32))
    state = _over_past(state, fold_rows, pool, block_tables, lengths,
                       key_tile)
    # the chunk itself, causal; its diagonal gives every row a live key
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    for lo in range(0, C, key_tile):
        state = fold_rows(state, new_rows[:, lo:lo + key_tile],
                          causal[None, None, :, lo:lo + key_tile])
    _m, l, acc = state
    out = acc / l[..., None]                                # (S, H, C, W)
    if latent_path(C) == "absorbed":    # the value half of W_kvb, last
        return jnp.einsum("shcr,rhv->schv", out.astype(dtype), w_v,
                          preferred_element_type=jnp.float32).astype(dtype)
    return out.transpose(0, 2, 1, 3).astype(dtype)
