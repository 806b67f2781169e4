"""Pallas TPU kernels: fused LayerNorm and Softmax.

Reference parity: the reference's LayerNorm/softmax CPU+CUDA kernels
(src/operator/nn/layer_norm.cc, src/operator/nn/softmax-inl.h) are
hand-written reductions; on TPU the win is a SINGLE HBM read+write per row
(XLA's fused lowering reads the input twice: once for the statistics pass,
once for the normalize pass). Each program normalizes a block of rows held
in VMEM; statistics ride the VPU.

Backward passes are jnp (XLA fuses them into the surrounding graph); the
forward kernels carry a custom VJP so autograd works transparently.

All kernels require the row length (last axis) to fit a VMEM block and the
row count to tile evenly; callers fall back to the jnp path otherwise via
``fused_norm_available()`` + ``_supported()`` checks inside the wrappers.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def fused_norm_available():
    return jax.default_backend() == "tpu"


_VMEM_BUDGET = 8 * 1024 * 1024   # block + fp32 working copy must fit


def _row_block(n_rows, n_cols):
    """Largest row-block that tiles n_rows AND fits the VMEM budget
    (block + its fp32 working copy)."""
    for cand in (256, 128, 64, 32, 16, 8):
        if n_rows % cand == 0 and cand * n_cols * 4 * 2 <= _VMEM_BUDGET:
            return cand
    return None


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                    # (BR, C)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)                    # (1, C)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (xc * inv * g + b).astype(o_ref.dtype)


def _ln_call(x2d, gamma, beta, eps, block_r, interpret=False):
    R, C = x2d.shape
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(R // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x2d.dtype),
        interpret=interpret,
        name="fused_layernorm",
    )(x2d, gamma.reshape(1, C), beta.reshape(1, C))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln_core(x2d, gamma, beta, eps, interpret):
    block_r = _row_block(x2d.shape[0], x2d.shape[1])
    return _ln_call(x2d, gamma, beta, eps, block_r, interpret)


def _ln_fwd(x2d, gamma, beta, eps, interpret):
    return _ln_core(x2d, gamma, beta, eps, interpret), (x2d, gamma)


def _ln_bwd(eps, interpret, res, g):
    x, gamma = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    dgamma = jnp.sum(gf * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(gf, axis=0).astype(gamma.dtype)
    dy = gf * gamma.astype(jnp.float32)
    C = x.shape[-1]
    dx = inv / C * (C * dy - jnp.sum(dy, axis=-1, keepdims=True)
                    - xhat * jnp.sum(dy * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dgamma, dbeta


_ln_core.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(data, gamma, beta, eps=1e-5, interpret=False):
    """LayerNorm over the last axis. Returns None if shapes don't tile —
    caller falls back to the jnp path."""
    C = data.shape[-1]
    rows = 1
    for d in data.shape[:-1]:
        rows *= d
    if rows == 0 or _row_block(rows, C) is None:
        return None
    x2d = data.reshape(rows, C)
    out = _ln_core(x2d, gamma, beta, float(eps), interpret)
    return out.reshape(data.shape)


# ---------------------------------------------------------------------------
# Softmax (row-wise, last axis)
# ---------------------------------------------------------------------------

def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _softmax_core(x2d, interpret):
    R, C = x2d.shape
    block_r = _row_block(R, C)
    return pl.pallas_call(
        _softmax_kernel,
        grid=(R // block_r,),
        in_specs=[pl.BlockSpec((block_r, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_r, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x2d.dtype),
        interpret=interpret,
        name="fused_softmax",
    )(x2d)


def _softmax_fwd(x2d, interpret):
    y = _softmax_core(x2d, interpret)
    return y, (y,)


def _softmax_bwd(interpret, res, g):
    (y,) = res
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dx = yf * (gf - jnp.sum(gf * yf, axis=-1, keepdims=True))
    return (dx.astype(y.dtype),)


_softmax_core.defvjp(_softmax_fwd, _softmax_bwd)


def fused_softmax(data, axis=-1, interpret=False):
    """Softmax along ``axis``; returns None when the kernel can't tile."""
    nd = data.ndim
    axis = axis % nd
    if axis != nd - 1:
        return None
    C = data.shape[-1]
    rows = 1
    for d in data.shape[:-1]:
        rows *= d
    if rows == 0 or _row_block(rows, C) is None:
        return None
    out = _softmax_core(data.reshape(rows, C), interpret)
    return out.reshape(data.shape)
