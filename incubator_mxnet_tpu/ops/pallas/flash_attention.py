"""Flash attention — Pallas TPU kernels with online softmax, forward AND
backward.

The O(T)-memory attention kernel (net-new vs the reference, which predates
flash attention; justified by the BERT/long-context BASELINE configs).

Forward: grid (batch*heads, q_blocks, kv_blocks); K/V stream through VMEM
one block at a time (constant VMEM footprint at any sequence length), with
the online-softmax accumulator held in VMEM scratch across the innermost
grid dimension; also emits the per-row LSE for the backward. QK^T and PV
ride the MXU; the rescale runs on the VPU.

Backward: the standard flash recomputation split into two kernels so every
output has its own accumulation order — dQ over KV blocks, dK/dV over Q
blocks — each streaming one tile pair at a time (O(T) memory, no T x T
materialization). delta = rowsum(dO * O) is a cheap fused jnp elementwise.

A sequence that is ONE tile (``flash_attention_bthd``, T <= _TILE_MAX_T):
the same algorithm with a tile count of 1, on the projections' own
(B, T, H*D) arrays. One program instance a batch row and 128-lane group of
heads (grid (B, H*D/128), blocks (1, T, 128)); the forward has no running
statistics (one max, one exp, one sum), the backward is ONE launch that
writes dQ, dK and dV from scores computed once. Launches
``flash_attention_tile_fwd`` / ``flash_attention_tile_bwd``.

Falls back transparently on CPU (no Mosaic) — callers check
``flash_attention_available()``; tests run the same kernels with
``interpret=True``.
"""

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def flash_attention_available():
    return jax.default_backend() == "tpu"


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, block_q, block_k, scale, causal,
                has_bias):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal
    if causal:
        run = qi * block_q + block_q - 1 >= kj * block_k
    else:
        run = True

    @pl.when(run)
    def _compute():
        # feed the MXU in the INPUT dtype (bf16 at full rate, f32 accum via
        # preferred_element_type); scale applied to the f32 scores
        q = q_ref[0]                                      # (BQ, D)
        k = k_ref[0]                                      # (BK, D)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            # additive kv bias (0 for live, -inf for padding): broadcast
            # over the query rows of this tile
            s = s + bias_ref[0, 0][None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        # a query row whose keys are ALL masked leaves m at (about) the bias
        # floor: the online softmax would renormalize it into near-uniform
        # attention over padding. Emit EXACT zeros instead, and set lse=0 so
        # the backward's p = exp(s - lse) = exp(-1e30) underflows to 0 —
        # zero grads for dead rows in both directions.
        dead = m_ref[:, 0] <= _NEG_INF * 0.5
        o = acc_ref[...] / l_safe[:, None]
        o_ref[0] = jnp.where(dead[:, None], 0.0, o).astype(o_ref.dtype)
        # lse is materialized 8-sublane-replicated: Mosaic requires block
        # sublane dims divisible by 8, and (1, BQ) blocks of a (bh, T) array
        # are not; (1, 8, BQ) blocks of (bh, 8, T) are.
        lse = jnp.where(dead, 0.0, m_ref[:, 0] + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse[None], lse_ref.shape[1:])


def _fwd_call(q, k, v, bias, scale, causal, block_q, block_k,
              interpret=False):
    bh, T, d = q.shape
    grid = (bh, T // block_q, T // block_k)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 8, block_k),
                                     lambda b, i, j: (b, 0, j)))
        args.append(bias)
    kern = functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                             scale=scale, causal=causal, has_bias=has_bias)
    if not has_bias:
        def kern(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
            return _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                               acc_ref, m_ref, l_ref, block_q=block_q,
                               block_k=block_k, scale=scale, causal=causal,
                               has_bias=False)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)


def _recompute_p_ds(q, k, v, do, lse, delta, qi, kj, block_q, block_k,
                    scale, causal, bias=None):
    """Shared tile math of the backward kernels: p, ds, and the UNscaled
    score cotangent (= the additive-bias cotangent) for one (Q, KV) tile
    pair (MXU in input dtype, fp32 accumulation)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias[None, :]
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    p = jnp.exp(s - lse[:, None])                         # (BQ, BK)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds_bias = p * (dp - delta[:, None])                   # dL/ds (f32)
    ds = ds_bias * scale                                  # dL/d(qk)
    return p.astype(v.dtype), ds.astype(v.dtype), ds_bias


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
               dq_ref, acc_ref, *, block_q, block_k, scale, causal,
               has_bias):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True if not causal else qi * block_q + block_q - 1 >= kj * block_k

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        bias = bias_ref[0, 0] if has_bias else None
        _, ds, _ = _recompute_p_ds(q, k, v, do, lse_ref[0, 0],
                                   delta_ref[0, 0], qi, kj, block_q,
                                   block_k, scale, causal, bias)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
                dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, db_acc, *,
                block_q, block_k, scale, causal, has_bias, has_dbias):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if has_dbias:
            db_acc[...] = jnp.zeros_like(db_acc)

    run = True if not causal else qi * block_q + block_q - 1 >= kj * block_k

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        bias = bias_ref[0, 0] if has_bias else None
        p, ds, ds_bias = _recompute_p_ds(q, k, v, do, lse_ref[0, 0],
                                         delta_ref[0, 0], qi, kj, block_q,
                                         block_k, scale, causal, bias)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if has_dbias:
            # per-key bias cotangent: sum dL/ds over this tile's query rows
            db_acc[...] += jnp.broadcast_to(
                jnp.sum(ds_bias, axis=0)[None, :], db_acc.shape)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        if has_dbias:
            # the kernel only READS sublane 0 of the replicated (8, T) bias
            # layout, so only sublane 0 carries a true cotangent
            sub = jax.lax.broadcasted_iota(jnp.int32, db_acc.shape, 0)
            dbias_ref[0] = jnp.where(sub == 0, db_acc[...], 0.0) \
                .astype(dbias_ref.dtype)


def _bwd_call(q, k, v, out, lse, g, bias, scale, causal, block_q, block_k,
              interpret=False, needs_dbias=False):
    bh, T, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, T))
    has_bias = bias is not None

    qkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # do
        pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),   # lse
        pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),   # delta
    ]
    args = [q, k, v, g, lse, delta]
    if has_bias:
        qkv_specs.append(pl.BlockSpec((1, 8, block_k),
                                      lambda b, i, j: (b, 0, j)))
        args.append(bias)
    dq_kern = functools.partial(_dq_kernel, block_q=block_q,
                                block_k=block_k, scale=scale, causal=causal,
                                has_bias=has_bias)
    if not has_bias:
        base_dq = dq_kern

        def dq_kern(q_r, k_r, v_r, do_r, lse_r, dl_r, dq_r, acc_r):
            return base_dq(q_r, k_r, v_r, do_r, lse_r, dl_r, None, dq_r,
                           acc_r)
    dq = pl.pallas_call(
        dq_kern,
        grid=(bh, T // block_q, T // block_k),
        in_specs=qkv_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*args)

    kv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),   # do
        pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),   # lse
        pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),   # delta
    ]
    if has_bias:
        kv_specs.append(pl.BlockSpec((1, 8, block_k),
                                     lambda b, j, i: (b, 0, j)))
    has_dbias = has_bias and needs_dbias
    dkv_kern = functools.partial(_dkv_kernel, block_q=block_q,
                                 block_k=block_k, scale=scale, causal=causal,
                                 has_bias=has_bias, has_dbias=has_dbias)
    out_specs = [
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bh, T, d), k.dtype),
        jax.ShapeDtypeStruct((bh, T, d), v.dtype),
    ]
    scratch = [pltpu.VMEM((block_k, d), jnp.float32),
               pltpu.VMEM((block_k, d), jnp.float32)]
    if has_dbias:
        out_specs.append(pl.BlockSpec((1, 8, block_k),
                                      lambda b, j, i: (b, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 8, T), jnp.float32))
        scratch.append(pltpu.VMEM((8, block_k), jnp.float32))
    base_dkv = dkv_kern
    if has_bias and not has_dbias:
        def dkv_kern(q_r, k_r, v_r, do_r, lse_r, dl_r, b_r, dk_r, dv_r,
                     dk_a, dv_a):
            return base_dkv(q_r, k_r, v_r, do_r, lse_r, dl_r, b_r, dk_r,
                            dv_r, None, dk_a, dv_a, None)
    elif not has_bias:
        def dkv_kern(q_r, k_r, v_r, do_r, lse_r, dl_r, dk_r, dv_r,
                     dk_a, dv_a):
            return base_dkv(q_r, k_r, v_r, do_r, lse_r, dl_r, None, dk_r,
                            dv_r, None, dk_a, dv_a, None)
    outs = pl.pallas_call(
        dkv_kern,
        grid=(bh, T // block_k, T // block_q),
        in_specs=kv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*args)
    if has_dbias:
        dk, dv, dbias = outs
    else:
        (dk, dv), dbias = outs, None
    return dq, dk, dv, dbias


import os as _os


def _default_blocks(T):
    """Block sizes of the TILED kernels: MXTPU_FLASH_BLOCK_Q/K, else the
    whole sequence up to 1024 (the one-tile path below reads neither; what
    the tiled launches cost at T=512 is in PERF.md section 5, cell 3)."""
    bq = int(_os.environ.get("MXTPU_FLASH_BLOCK_Q", "0")) or min(T, 1024)
    bk = int(_os.environ.get("MXTPU_FLASH_BLOCK_K", "0")) or min(T, 1024)
    while T % bq:
        bq //= 2
    while T % bk:
        bk //= 2
    return max(bq, 8), max(bk, 8)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, bias, scale, causal, block_q, block_k, interpret,
                needs_dbias):
    out, _ = _fwd_call(q, k, v, bias, scale, causal, block_q, block_k,
                       interpret)
    return out


def _flash_fwd(q, k, v, bias, scale, causal, block_q, block_k, interpret,
               needs_dbias):
    out, lse = _fwd_call(q, k, v, bias, scale, causal, block_q, block_k,
                         interpret)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, needs_dbias,
               res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv, dbias = _bwd_call(q, k, v, out, lse, g, bias, scale, causal,
                                  block_q, block_k, interpret,
                                  needs_dbias=needs_dbias)
    if bias is not None:
        # mask-only biases are non-differentiable constants: skip the
        # in-kernel accumulation and return a zeros cotangent (XLA folds
        # the dead upstream ops away under jit)
        dbias = (jnp.zeros_like(bias) if dbias is None
                 else dbias.astype(bias.dtype))
    return dq, dk, dv, dbias


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, scale=None, causal=False, kv_mask=None,
                    kv_bias=None, block_q=None, block_k=None,
                    interpret=False):
    """q/k/v: (B, H, T, D). Returns (B, H, T, D).

    kv_mask: optional (B, T) array, nonzero = live key/value position,
    0 = padding (the reference BERT valid-length mask). Padded positions
    receive zero attention in forward AND backward. Query rows whose keys
    are ALL masked return exact zeros (and zero grads), not renormalized
    garbage.

    kv_bias: optional LEARNED additive per-key bias, (B, H, T) or (B, T),
    added to the attention scores. Differentiable — the backward kernel
    accumulates the true bias cotangent (no silent zero gradient).

    Requires T % 128 == 0, or T <= 128 with T % 8 == 0 (Mosaic sublane
    tiling); callers fall back to the einsum path otherwise."""
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if T > 128:
        if T % 128 != 0:
            raise ValueError("flash_attention requires seq_len % 128 == 0")
    elif T % 8 != 0:
        raise ValueError("flash_attention requires seq_len % 8 == 0")
    bq0, bk0 = _default_blocks(T)
    bq = block_q or bq0
    bk = block_k or bk0
    if T % bq or T % bk:
        raise ValueError("flash_attention: block sizes (%d, %d) must divide "
                         "seq_len %d" % (bq, bk, T))
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    bias = None
    if kv_mask is not None or kv_bias is not None:
        b1 = jnp.zeros((B, H, T), jnp.float32)
        if kv_bias is not None:
            kb = jnp.asarray(kv_bias, jnp.float32)
            if kb.ndim == 2:
                kb = kb[:, None, :]
            b1 = b1 + jnp.broadcast_to(kb, (B, H, T))
        if kv_mask is not None:
            live = jnp.asarray(kv_mask).reshape(B, T) != 0
            b1 = b1 + jnp.where(live, 0.0, _NEG_INF)[:, None, :]
        # (B,H,8,T) -> (B*H,8,T): replicated-sublane layout like lse/delta.
        # Only sublane 0 is read in-kernel, and only sublane 0 carries a
        # backward cotangent, so AD through this broadcast stays exact.
        bias = jnp.broadcast_to(b1[:, :, None, :], (B, H, 8, T)) \
            .reshape(B * H, 8, T)
    out = _flash_core(qf, kf, vf, bias, float(scale), bool(causal),
                      int(bq), int(bk), bool(interpret),
                      kv_bias is not None)
    return out.reshape(B, H, T, D)


def flash_attention_lse(q, k, v, scale=None, causal=False, block_q=None,
                        block_k=None, interpret=False):
    """The tiled forward alone, with its softmax's log-sum: q/k/v (B, H, T,
    D) -> (out (B, H, T, D) in q's dtype, lse (B, H, T) float32), so that
    a caller merges further keys under the same softmax (a window's own
    causal part beside its summaries, ``models/eva_byte.py``). No
    gradient. T as ``flash_attention`` requires it."""
    B, H, T, D = q.shape
    if T % 128 and (T > 128 or T % 8):
        raise ValueError("flash_attention_lse requires seq_len % 128 == 0")
    bq0, bk0 = _default_blocks(T)
    out, lse = _fwd_call(
        q.reshape(B * H, T, D), k.reshape(B * H, T, D),
        v.reshape(B * H, T, D), None,
        float(D ** -0.5 if scale is None else scale), bool(causal),
        int(block_q or bq0), int(block_k or bk0), bool(interpret))
    return out.reshape(B, H, T, D), lse[:, 0].reshape(B, H, T)


# ----------------------------------------------------------------------
# one tile: the whole sequence of a batch row in one program instance
# ----------------------------------------------------------------------
# T up to which the (T, T) float32 temporaries of a head fit the scoped
# VMEM: 1024 compiles for v5e with a mask, in the backward, at either head
# width (tests/test_tpu_compile.py); 2048 is 16 MB a temporary. Measured on
# the chip at 128 to 1024 against the tiled launches (PERF.md section 6,
# PR 32).
_TILE_MAX_T = 1024
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _one_tile(T, num_heads, d, causal, kv_bias):
    """What the call itself says: the sequence is one tile, the heads fill
    whole 128-lane groups, and nothing needs a position or a bias
    gradient. (num_heads <= 128: a head's row statistic has a lane.)"""
    return (not causal and kv_bias is None and T % 128 == 0
            and T <= _TILE_MAX_T and d in (64, 128)
            and (num_heads * d) % 128 == 0 and num_heads <= 128)


def _head_lanes(d):
    """Lane masks (1, 128) of the heads of one 128-lane group; a head is
    taken by zeroing the other's lanes of ONE operand and contracting over
    all 128 (what a 64-deep product costs on a 128-deep MXU anyway), and
    its 64 output lanes are selected from an N=128 product."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    if d == 128:
        return lane, [None]
    return lane, [(lane >= h * d) & (lane < (h + 1) * d)
                  for h in range(128 // d)]


def _only(sel, x):
    return x if sel is None else jnp.where(sel, x, jnp.zeros_like(x))


def _folds(scale):
    """A power of two scales q exactly (1/8 at d=64), so it is applied to
    q's (T, 128) elements; any other scale stays on the float32 scores."""
    return math.frexp(scale)[0] == 0.5


def _tile_fwd_kernel(q_ref, k_ref, v_ref, *rest, d, scale, has_bias):
    bias_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    j = pl.program_id(1)
    q, k, v = q_ref[0], k_ref[0], v_ref[0]              # (T, 128)
    fold = _folds(scale)
    if fold:
        q = q * jnp.asarray(scale, q.dtype)
    lane, heads = _head_lanes(d)
    o = lse_all = None
    for h, sel in enumerate(heads):
        s = _dot(_only(sel, q), k, _NT)                 # (T, T) f32
        if not fold:
            s = s * scale
        if has_bias:
            s = s + bias_ref[0]                         # (1, T) over rows
        # every statistic stays a (T, 1) column: no column becomes a row
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)          # >= 1
        oh = _dot(p.astype(v.dtype), v, _NN) / l        # (T, 128)
        lse = m + jnp.log(l)
        if has_bias:
            # all keys masked: exact zeros, and lse = 0 so that the
            # backward's exp(s - lse) underflows to 0 (as the tiled kernel)
            dead = m <= _NEG_INF * 0.5
            oh = jnp.where(dead, 0.0, oh)
            lse = jnp.where(dead, 0.0, lse)
        o = oh if o is None else jnp.where(sel, oh, o)
        # head g's lse lives in lane g of one (T, 128) block a batch row
        mine = jnp.where(lane == j * len(heads) + h, lse, 0.0)
        lse_all = mine if lse_all is None else lse_all + mine
    o_ref[0] = o.astype(o_ref.dtype)

    # the block is resident over the head-group axis (its index is b alone)
    @pl.when(j == 0)
    def _first():
        lse_ref[0] = lse_all

    @pl.when(j > 0)
    def _more():
        lse_ref[0] += lse_all


def _tile_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest, d,
                     scale, has_bias):
    bias_ref = rest[0] if has_bias else None
    dq_ref, dk_ref, dv_ref = rest[-3:]
    j = pl.program_id(1)
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    fold = _folds(scale)
    if fold:
        q = q * jnp.asarray(scale, q.dtype)
    lane, heads = _head_lanes(d)
    lse_all = lse_ref[0]
    do_o = do.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    dq = dk = dv = None
    for h, sel in enumerate(heads):
        doh = _only(sel, do)
        delta = jnp.sum(_only(sel, do_o), axis=-1, keepdims=True)
        lse = jnp.sum(jnp.where(lane == j * len(heads) + h, lse_all, 0.0),
                      axis=-1, keepdims=True)
        s = _dot(_only(sel, q), k, _NT)
        if not fold:
            s = s * scale
        if has_bias:
            s = s + bias_ref[0]
        p = jnp.exp(s - lse)
        ds = p * (_dot(doh, v, _NT) - delta)            # dL/ds, f32
        if not fold:
            ds = ds * scale
        p = p.astype(v.dtype)
        ds = ds.astype(v.dtype)
        dqh = _dot(ds, k, _NN)
        if fold:
            dqh = dqh * scale
        dkh = _dot(ds, q, _TN)          # q carries the folded scale
        dvh = _dot(p, do, _TN)
        if dq is None:
            dq, dk, dv = dqh, dkh, dvh
        else:
            dq = jnp.where(sel, dqh, dq)
            dk = jnp.where(sel, dkh, dk)
            dv = jnp.where(sel, dvh, dv)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _tile_call(kernel, name, per_head, per_row, bias, num_heads, scale,
               out_shape, interpret):
    """One launch over grid (B, H*D/128). `per_head`: (B, T, H*D) operands,
    q first, in (1, T, 128) blocks a group of heads; `per_row`: the packed
    lse (B, T, 128), one block a batch row, as is the key bias (B, 1, T).
    Results take the same two forms, told by their width (where H*D is
    128 the two are one)."""
    B, T, HD = per_head[0].shape
    heads = pl.BlockSpec((1, T, 128), lambda b, j: (b, 0, j))
    row = pl.BlockSpec((1, T, 128), lambda b, j: (b, 0, 0))
    args = per_head + per_row
    in_specs = [heads] * len(per_head) + [row] * len(per_row)
    if bias is not None:
        args.append(bias)
        in_specs.append(pl.BlockSpec((1, 1, T), lambda b, j: (b, 0, 0)))
    return pl.pallas_call(
        functools.partial(kernel, d=HD // num_heads, scale=scale,
                          has_bias=bias is not None),
        grid=(B, HD // 128),
        in_specs=in_specs,
        out_specs=[heads if o.shape[-1] == HD else row for o in out_shape],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*args)


def _tile_fwd(q, k, v, bias, num_heads, scale, interpret):
    B, T, _ = q.shape
    return _tile_call(
        _tile_fwd_kernel, "flash_attention_tile_fwd", [q, k, v], [], bias,
        num_heads, scale,
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((B, T, 128), jnp.float32)], interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _tile_core(q, k, v, bias, num_heads, scale, interpret):
    return _tile_fwd(q, k, v, bias, num_heads, scale, interpret)[0]


def _tile_core_fwd(q, k, v, bias, num_heads, scale, interpret):
    out, lse = _tile_fwd(q, k, v, bias, num_heads, scale, interpret)
    return out, (q, k, v, bias, out, lse)


def _tile_core_bwd(num_heads, scale, interpret, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _tile_call(
        _tile_bwd_kernel, "flash_attention_tile_bwd",
        [q, k, v, g, out], [lse], bias, num_heads, scale,
        [jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3, interpret)
    # the bias is a mask here (a learned kv_bias stays on the tiled path)
    return dq, dk, dv, None if bias is None else jnp.zeros_like(bias)


_tile_core.defvjp(_tile_core_fwd, _tile_core_bwd)


def flash_attention_bthd(q, k, v, num_heads, scale=None, causal=False,
                         kv_mask=None, kv_bias=None, interpret=False):
    """q/k/v: (B, T, H*D) as three dense layers leave them. Returns
    (B, T, H*D), ready for the output projection.

    A sequence that is one tile (see ``_one_tile``: read off this call's
    shapes and arguments, nothing else) runs the one-tile launches on these
    arrays as they are. Anything else — longer, causal, a learned
    ``kv_bias`` — is carried into (B, H, T, D) and runs ``flash_attention``
    with its contract (seq_len % 128 == 0, ...)."""
    B, T, HD = q.shape
    D = HD // num_heads
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if _one_tile(T, num_heads, D, causal, kv_bias):
        bias = None
        if kv_mask is not None:
            live = jnp.asarray(kv_mask).reshape(B, 1, T) != 0
            bias = jnp.where(live, 0.0, _NEG_INF).astype(jnp.float32)
        return _tile_core(q, k, v, bias, int(num_heads), float(scale),
                          bool(interpret))

    def split(x):
        return x.reshape(B, T, num_heads, D).transpose(0, 2, 1, 3)

    out = flash_attention(split(q), split(k), split(v), scale=scale,
                          causal=causal, kv_mask=kv_mask, kv_bias=kv_bias,
                          interpret=interpret)
    return out.transpose(0, 2, 1, 3).reshape(B, T, HD)
