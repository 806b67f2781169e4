"""Ring attention — sequence/context parallelism over the mesh.

Net-new capability vs the reference (SURVEY §5: long-context/SP absent
there); required for long sequences on TPU. Implements blockwise ring
attention: Q stays local per sequence shard, K/V blocks rotate around the
ring via ppermute while running log-sum-exp-stable partial softmax
accumulation. Use inside shard_map with the sequence axis sharded.

Two per-hop engines (SURVEY §5's "GSPMD sequence sharding + Pallas
ring/flash kernel" as ONE composed path):

- ``ring_attention``: dense einsum per KV shard — O(T_local^2) score
  tensors per hop; the reference arm for A/B and the CPU fallback.
- ``ring_flash_attention``: the Pallas flash kernel per KV shard — the
  online-softmax (m, l) stats stream across ppermute hops exactly as they
  stream across KV tiles inside one kernel call, so per-device memory is
  O(T_local) at ANY total sequence length. The custom VJP re-rotates KV
  blocks and lets each block's dK/dV accumulators travel the ring with it,
  arriving home after the full rotation (the standard ring-flash backward
  dataflow).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.pallas.flash_attention import (_fwd_call, _bwd_call,
                                          _default_blocks, _NEG_INF)

__all__ = ["ring_attention", "ring_flash_attention", "local_attention",
           "make_ring_attention"]


def local_attention(q, k, v, scale=None, causal=False, q_offset=0, kv_offset=0):
    """Plain attention on local blocks. q: (B, H, Tq, D), k/v: (B, H, Tk, D).
    Returns (out, logsumexp-stats) pieces: (num, denom, max)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qi = q_offset + jnp.arange(q.shape[2])[:, None]
        ki = kv_offset + jnp.arange(k.shape[2])[None, :]
        scores = jnp.where(qi >= ki, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)           # (B,H,Tq,1)
    p = jnp.exp(scores - m)
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v)             # (B,H,Tq,D)
    denom = jnp.sum(p, axis=-1, keepdims=True)            # (B,H,Tq,1)
    return num, denom, m


def _merge(acc_num, acc_den, acc_max, num, den, m):
    new_max = jnp.maximum(acc_max, m)
    a = jnp.exp(acc_max - new_max)
    b = jnp.exp(m - new_max)
    return acc_num * a + num * b, acc_den * a + den * b, new_max


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Blockwise ring attention inside shard_map; sequence axis sharded on
    ``axis_name``. q/k/v: (B, H, T_local, D) per shard."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t_local = q.shape[2]
    q_offset = idx * t_local

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, _):
        k_blk, v_blk, blk_idx, acc_num, acc_den, acc_max = carry
        kv_offset = blk_idx * t_local
        num, den, m = local_attention(q, k_blk, v_blk, scale=scale,
                                      causal=causal, q_offset=q_offset,
                                      kv_offset=kv_offset)
        acc_num, acc_den, acc_max = _merge(acc_num, acc_den, acc_max,
                                           num, den, m)
        # rotate K/V to the next ring position (overlaps with next compute
        # in XLA's async collective scheduling)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        idx_next = lax.ppermute(blk_idx, axis_name, perm)
        return (k_next, v_next, idx_next, acc_num, acc_den, acc_max), None

    acc_num = jnp.zeros_like(q)
    acc_den = jnp.zeros(q.shape[:-1] + (1,), q.dtype)
    acc_max = jnp.full(q.shape[:-1] + (1,), -1e30, q.dtype)
    carry = (k, v, idx, acc_num, acc_den, acc_max)
    carry, _ = lax.scan(body, carry, None, length=n)
    _, _, _, acc_num, acc_den, acc_max = carry
    return acc_num / jnp.maximum(acc_den, 1e-30)


# ---------------------------------------------------------------------------
# ring + flash composition: Pallas flash kernel on each KV shard, online
# softmax stats merged across ppermute hops
# ---------------------------------------------------------------------------

def _merge_lse(o_acc, lse_acc, o_blk, lse_blk):
    """Merge two normalized partial-attention results by their LSE stats
    (exact: o = sum_i o_i * exp(lse_i - lse_new)). All f32; the _NEG_INF
    floor marks 'no contribution yet' and weighs in at exactly zero."""
    lse_new = jnp.logaddexp(lse_acc, lse_blk)
    dead1 = lse_acc <= _NEG_INF * 0.5
    dead2 = lse_blk <= _NEG_INF * 0.5
    w1 = jnp.where(dead1, 0.0, jnp.exp(lse_acc - lse_new))
    w2 = jnp.where(dead2, 0.0, jnp.exp(lse_blk - lse_new))
    o = o_acc * w1[:, 0, :, None] + o_blk * w2[:, 0, :, None]
    return o, jnp.where(dead1 & dead2, _NEG_INF, lse_new)


def _hop_kind(blk_idx, idx):
    """0 = skip (KV strictly after Q under causal), 1 = diagonal (local
    causal), 2 = full (KV strictly before Q)."""
    return jnp.where(blk_idx > idx, 0, jnp.where(blk_idx == idx, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention(q, k, v, axis_name, scale=None, causal=False,
                         interpret=False):
    """Ring attention with the Pallas flash kernel as the per-hop engine.

    Use inside shard_map with the sequence axis sharded on ``axis_name``;
    q/k/v are the LOCAL shards, (B, H, T_local, D) with T_local a multiple
    of 128 (or <=128, multiple of 8 — the flash kernel's tiling contract).
    Numerics match ``ring_attention`` (dense einsum ring) and single-device
    attention; per-device memory stays O(T_local) in forward AND backward.
    """
    out, _ = _ring_flash_fwd(q, k, v, axis_name, scale, causal, interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, scale, causal, interpret):
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    scale = float(scale)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bq, bk = _default_blocks(T)
    qf = q.reshape(B * H, T, D)

    def run_hop(k_blk, v_blk, hop_causal):
        o_blk, lse_blk = _fwd_call(qf, k_blk.reshape(B * H, T, D),
                                   v_blk.reshape(B * H, T, D), None, scale,
                                   hop_causal, bq, bk, interpret)
        return o_blk.astype(jnp.float32), lse_blk

    def skip_hop(k_blk, v_blk):
        return (jnp.zeros((B * H, T, D), jnp.float32),
                jnp.full((B * H, 8, T), _NEG_INF, jnp.float32))

    def body(carry, _):
        k_blk, v_blk, blk_idx, o_acc, lse_acc = carry
        if causal:
            o_blk, lse_blk = lax.switch(
                _hop_kind(blk_idx, idx),
                [skip_hop,
                 functools.partial(run_hop, hop_causal=True),
                 functools.partial(run_hop, hop_causal=False)],
                k_blk, v_blk)
        else:
            o_blk, lse_blk = run_hop(k_blk, v_blk, hop_causal=False)
        o_acc, lse_acc = _merge_lse(o_acc, lse_acc, o_blk, lse_blk)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        idx_next = lax.ppermute(blk_idx, axis_name, perm)
        return (k_next, v_next, idx_next, o_acc, lse_acc), None

    o0 = jnp.zeros((B * H, T, D), jnp.float32)
    lse0 = jnp.full((B * H, 8, T), _NEG_INF, jnp.float32)
    (k_home, v_home, _, o_acc, lse), _ = lax.scan(
        body, (k, v, idx, o0, lse0), None, length=n)
    out = o_acc.astype(q.dtype).reshape(B, H, T, D)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, scale, causal, interpret, res, g):
    q, k, v, out, lse = res
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    scale = float(scale)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bq, bk = _default_blocks(T)
    qf = q.reshape(B * H, T, D)
    of = out.reshape(B * H, T, D)
    gf = g.reshape(B * H, T, D).astype(q.dtype)

    def run_hop(k_blk, v_blk, hop_causal):
        dq_b, dk_b, dv_b, _ = _bwd_call(
            qf, k_blk.reshape(B * H, T, D), v_blk.reshape(B * H, T, D),
            of, lse, gf, None, scale, hop_causal, bq, bk, interpret)
        return (dq_b.astype(jnp.float32), dk_b.astype(jnp.float32),
                dv_b.astype(jnp.float32))

    def skip_hop(k_blk, v_blk):
        z = jnp.zeros((B * H, T, D), jnp.float32)
        return z, z, z

    def body(carry, _):
        k_blk, v_blk, dk_acc, dv_acc, blk_idx, dq_acc = carry
        if causal:
            dq_b, dk_b, dv_b = lax.switch(
                _hop_kind(blk_idx, idx),
                [skip_hop,
                 functools.partial(run_hop, hop_causal=True),
                 functools.partial(run_hop, hop_causal=False)],
                k_blk, v_blk)
        else:
            dq_b, dk_b, dv_b = run_hop(k_blk, v_blk, hop_causal=False)
        dq_acc = dq_acc + dq_b
        # dK/dV accumulators TRAVEL with their KV block — after the full
        # rotation each block (and its gradient) is back on its home shard
        dk_acc = dk_acc + dk_b
        dv_acc = dv_acc + dv_b
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        dk_next = lax.ppermute(dk_acc, axis_name, perm)
        dv_next = lax.ppermute(dv_acc, axis_name, perm)
        idx_next = lax.ppermute(blk_idx, axis_name, perm)
        return (k_next, v_next, dk_next, dv_next, idx_next, dq_acc), None

    z = jnp.zeros((B * H, T, D), jnp.float32)
    (k_home, v_home, dk, dv, _, dq), _ = lax.scan(
        body, (k, v, z, z, idx, z), None, length=n)
    return (dq.astype(q.dtype).reshape(B, H, T, D),
            dk.astype(k.dtype).reshape(B, H, T, D),
            dv.astype(v.dtype).reshape(B, H, T, D))


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def make_ring_attention(mesh, seq_axis="sp", causal=False, impl="auto",
                        interpret=False):
    """Return a jit-able attention fn over globally-sharded (B,H,T,D) arrays:
    shard_map'ing ring attention over the sequence axis.

    impl: 'flash' (Pallas per-hop kernel), 'dense' (einsum per hop), or
    'auto' — flash on TPU when the local shard length satisfies the
    kernel's tiling contract, dense otherwise."""
    from ..ops.pallas import flash_attention_available

    spec = P(None, None, seq_axis, None)

    def _flash_ok(t_local):
        if t_local > 128:
            return t_local % 128 == 0
        return t_local % 8 == 0

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def fn(q, k, v):
        t_local = q.shape[2]
        use_flash = impl == "flash" or (
            impl == "auto" and (flash_attention_available() or interpret)
            and _flash_ok(t_local))
        if use_flash:
            return ring_flash_attention(q, k, v, seq_axis, causal=causal,
                                        interpret=interpret)
        return ring_attention(q, k, v, seq_axis, causal=causal)

    return fn
