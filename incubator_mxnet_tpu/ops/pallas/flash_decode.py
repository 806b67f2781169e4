"""Paged causal flash-decode — single-query attention over a block table.

The decode-side counterpart of ``flash_attention.py``: at decode time
each sequence attends ONE new query (or a short prefill chunk) against
its whole KV history, which lives in a paged pool (``generate/paged_kv``)
rather than a contiguous strip. The kernel walks the sequence's block
table with the scalar-prefetch grid — block ids and lengths are scalar
operands, so the index_map fetches exactly the pool rows the sequence
owns — and runs the usual online-softmax accumulation per block.

Two layers:

- ``paged_flash_decode(q, k_pool, v_pool, tables, lengths)`` — attention
  over the PAST only (positions ``< lengths``), returning the normalized
  output plus the online-softmax ``(m, l)`` statistics so a caller can
  merge further terms.
- ``paged_causal_attention(q, k_new, v_new, ...)`` — the full decode
  step: past term via the kernel/reference, in-chunk causal self term
  in plain lax, merged by the standard two-way softmax combine. This is
  what the GPT decoder calls for both chunked prefill (C>1) and
  single-token decode (C=1).

A ``lax`` reference path (`_lax_paged_mhl`) is the numerics oracle and
the CPU fallback; the Pallas kernel covers the hot C==1 case and runs
under ``interpret=True`` in tier-1. Dead rows (zero past) come back as
exact zeros with ``m = -inf, l = 0`` in both paths.
"""

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

__all__ = ["paged_flash_decode", "paged_causal_attention",
           "flash_decode_available"]


def flash_decode_available():
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------- lax ref
def _lax_paged_mhl(q, k_pool, v_pool, block_tables, lengths, scale):
    """Reference past-attention: gather the table, mask by length.

    q (S, C, H, D); pools (NB, bs, H, D); block_tables (S, MB) int32;
    lengths (S,) int32 counting PAST positions. Returns normalized
    ``o (S, C, H, D)`` plus ``m, l (S, C, H)``.
    """
    S, C, H, D = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    k = k_pool[block_tables].reshape(S, mb * bs, H, D)
    v = v_pool[block_tables].reshape(S, mb * bs, H, D)
    s = jnp.einsum("schd,sphd->shcp", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale       # (S, H, C, P)
    live = (jnp.arange(mb * bs)[None, :]
            < lengths[:, None])                          # (S, P)
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)                              # (S, H, C)
    p = jnp.exp(s - m[..., None])
    # all-masked rows have s - m = 0 everywhere: re-mask so p sums to 0,
    # not P
    p = jnp.where(live[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                              # (S, H, C)
    o = jnp.einsum("shcp,sphd->schd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    dead = m <= _NEG_INF * 0.5
    o = jnp.where(dead.transpose(0, 2, 1)[..., None], 0.0, o)
    l = jnp.where(dead, 0.0, l)
    return (o.astype(q.dtype), m.transpose(0, 2, 1),
            l.transpose(0, 2, 1))


# ---------------------------------------------------------------- kernel
def _decode_kernel(bt_ref, ln_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                   l_ref, acc_ref, ms_ref, ls_ref, *, block_size, scale):
    s_idx = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, _NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    length = ln_ref[s_idx]
    base = j * block_size

    @pl.when(base < length)
    def _compute():
        q = q_ref[0]                                      # (H, D)
        k = k_ref[0]                                      # (bs, H, D)
        v = v_ref[0]
        # single-query scores: elementwise multiply + reduce on the VPU
        # (a (1, D) x (D, bs) MXU matmul per head would waste 127/128
        # lanes)
        s_blk = jnp.sum(q[None].astype(jnp.float32)
                        * k.astype(jnp.float32), axis=-1) * scale  # (bs, H)
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 0)
        liv = pos < length
        s_blk = jnp.where(liv, s_blk, _NEG_INF)
        m_prev = ms_ref[0]                                # (H,)
        l_prev = ls_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=0))
        p = jnp.exp(s_blk - m_new[None])
        p = jnp.where(liv, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        ls_ref[...] = jnp.broadcast_to(
            (l_prev * alpha + jnp.sum(p, axis=0))[None], ls_ref.shape)
        ms_ref[...] = jnp.broadcast_to(m_new[None], ms_ref.shape)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.sum(p[..., None] * v.astype(jnp.float32),
                                  axis=0))

    @pl.when(j == nb - 1)
    def _finalize():
        l_safe = jnp.maximum(ls_ref[0], 1e-30)
        dead = ms_ref[0] <= _NEG_INF * 0.5
        o = acc_ref[...] / l_safe[:, None]
        o_ref[0] = jnp.where(dead[:, None], 0.0, o).astype(o_ref.dtype)
        # (1, 8, H) sublane-replicated blocks, same trick as the
        # flash-attention lse output
        m_ref[0] = ms_ref[...]
        l_ref[0] = jnp.where(dead[None], 0.0, ls_ref[...])


def _kernel_call(q, k_pool, v_pool, block_tables, lengths, scale,
                 interpret):
    """q (S, H, D) — the C==1 fast path."""
    S, H, D = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, mb),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda s, j, bt, ln: (s, 0, 0)),
            pl.BlockSpec((1, bs, H, D),
                         lambda s, j, bt, ln: (bt[s, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, H, D),
                         lambda s, j, bt, ln: (bt[s, j], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda s, j, bt, ln: (s, 0, 0)),
            pl.BlockSpec((1, 8, H), lambda s, j, bt, ln: (s, 0, 0)),
            pl.BlockSpec((1, 8, H), lambda s, j, bt, ln: (s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((8, H), jnp.float32),
            pltpu.VMEM((8, H), jnp.float32),
        ],
    )
    kern = functools.partial(_decode_kernel, block_size=bs, scale=scale)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, H, D), q.dtype),
            jax.ShapeDtypeStruct((S, 8, H), jnp.float32),
            jax.ShapeDtypeStruct((S, 8, H), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(block_tables, lengths, q, k_pool, v_pool)
    return o, m[:, 0, :], l[:, 0, :]


# ------------------------------------------------------------------ api
def paged_flash_decode(q, k_pool, v_pool, block_tables, lengths,
                       scale=None, use_kernel=None, interpret=False):
    """Attention of ``q`` over the paged PAST of each sequence.

    q (S, C, H, D); k_pool/v_pool (num_blocks, block_size, H, D);
    block_tables (S, MB) int32 (pad with any valid block id); lengths
    (S,) int32 — committed past positions per sequence.

    Returns ``(out, m, l)``: normalized output (S, C, H, D) and the
    online-softmax row max / denominator, both (S, C, H), for merging
    with in-chunk terms. Sequences with zero past yield exact-zero
    output with ``m = -1e30, l = 0``.
    """
    S, C, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if use_kernel is None:
        use_kernel = flash_decode_available()
    if use_kernel and C == 1:
        o, m, l = _kernel_call(q[:, 0], k_pool, v_pool,
                               jnp.asarray(block_tables, jnp.int32),
                               jnp.asarray(lengths, jnp.int32),
                               scale, interpret)
        return o[:, None], m[:, None], l[:, None]
    return _lax_paged_mhl(q, k_pool, v_pool,
                          jnp.asarray(block_tables, jnp.int32),
                          jnp.asarray(lengths, jnp.int32), scale)


def paged_causal_attention(q, k_new, v_new, k_pool, v_pool, block_tables,
                           lengths, scale=None, use_kernel=None,
                           interpret=False, mask_block=None):
    """Full decode-step attention: paged past + in-chunk self.

    q/k_new/v_new (S, C, H, D) — the chunk being fed this step, whose
    k/v are NOT yet in the pool; position ``c`` attends every past
    position plus in-chunk positions ``<= c``. Returns (S, C, H, D).

    Fewer key/value heads than query heads (grouped-query attention):
    ``k_new``/``v_new`` and the pools may carry ``Hkv = H // G`` heads;
    query head ``h`` reads key/value head ``h // G``. The G query heads of
    a key/value head are folded into the chunk axis, so that both terms
    run the one-``H`` code below on ``(S, C * G, Hkv, D)`` queries.

    ``mask_block`` = B replaces the causal in-chunk term by the block
    mask of block-diffusion decoders: with absolute positions
    ``lengths + c``, position i sees j iff ``j // B <= i // B`` — causal
    between blocks of B, bidirectional inside one. (Every past position
    is in an earlier or the same block, so the past term is unchanged.)

    The past term comes from :func:`paged_flash_decode` (kernel when
    available); the in-chunk term is a small C x C masked softmax in
    lax; the two are merged with the standard two-way online-softmax
    combine. The diagonal guarantees every row has at least one live
    score, so the merge never divides by zero even with empty past.
    """
    S, C, H, D = q.shape
    if k_new.shape[2] != H:
        return _grouped_query(q, k_new, v_new, k_pool, v_pool, block_tables,
                              lengths, scale, use_kernel, interpret,
                              mask_block)
    return _paged_attention(q, k_new, v_new, k_pool, v_pool, block_tables,
                            lengths, scale, use_kernel, interpret,
                            _in_chunk_mask(C, lengths, mask_block))


def _in_chunk_mask(C, lengths, mask_block, fold=1):
    """Which in-chunk key t a query row sees, broadcastable to
    (S, H, C * fold, T): causal, or the block mask at absolute
    positions. `fold` query rows share a chunk position (grouped-query
    heads folded into the chunk axis, position-major)."""
    row = jnp.arange(C * fold) // fold
    if mask_block is None:
        return (row[:, None] >= jnp.arange(C)[None, :])[None, None]
    pos = jnp.asarray(lengths, jnp.int32)[:, None] + jnp.arange(C)[None]
    blk = pos // mask_block                                  # (S, C)
    return (blk[:, :, None] >= blk[:, None, :])[:, None, row, :]


def _grouped_query(q, k_new, v_new, k_pool, v_pool, block_tables, lengths,
                   scale, use_kernel, interpret, mask_block):
    S, C, H, D = q.shape
    Hkv = k_new.shape[2]
    if H % Hkv:
        raise ValueError("query heads (%d) must be a multiple of key/value "
                         "heads (%d)" % (H, Hkv))
    G = H // Hkv
    # (S, C, Hkv, G, D) -> rows (c, g) of key/value head kv
    folded = q.reshape(S, C, Hkv, G, D).transpose(0, 1, 3, 2, 4).reshape(
        S, C * G, Hkv, D)
    out = _paged_attention(folded, k_new, v_new, k_pool, v_pool,
                           block_tables, lengths, scale, use_kernel,
                           interpret,
                           _in_chunk_mask(C, lengths, mask_block, fold=G))
    return out.reshape(S, C, G, Hkv, D).transpose(0, 1, 3, 2, 4).reshape(
        S, C, H, D)


def _paged_attention(q, k_new, v_new, k_pool, v_pool, block_tables, lengths,
                     scale, use_kernel, interpret, causal):
    """`causal`: the in-chunk mask, broadcastable to (S, H, Cq, T) where
    q holds Cq rows a sequence and k_new/v_new T in-chunk positions."""
    S, C, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    o_p, m_p, l_p = paged_flash_decode(
        q, k_pool, v_pool, block_tables, lengths, scale=scale,
        use_kernel=use_kernel, interpret=interpret)

    s_new = jnp.einsum("schd,sthd->shct", q.astype(jnp.float32),
                       k_new.astype(jnp.float32)) * scale  # (S, H, C, T)
    s_new = jnp.where(causal, s_new, _NEG_INF)
    m_s = jnp.max(s_new, axis=-1)                          # (S, H, C)
    p = jnp.exp(s_new - m_s[..., None])
    p = jnp.where(causal, p, 0.0)
    l_s = jnp.sum(p, axis=-1)                              # (S, H, C)
    o_s = jnp.einsum("shct,sthd->schd", p,
                     v_new.astype(jnp.float32))            # unnormalized
    m_s = m_s.transpose(0, 2, 1)                           # (S, C, H)
    l_s = l_s.transpose(0, 2, 1)

    m = jnp.maximum(m_p, m_s)
    w_p = l_p * jnp.exp(m_p - m)            # (S, C, H): past weight
    w_s = jnp.exp(m_s - m)                  # self-term rescale
    num = (o_p.astype(jnp.float32) * w_p[..., None]
           + o_s * w_s[..., None])
    den = w_p + l_s * w_s                   # >= exp(0) via the diagonal
    return (num / den[..., None]).astype(q.dtype)
