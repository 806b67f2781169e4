"""Pallas TPU kernels: fused multi-tensor optimizer updates for the EAGER
``gluon.Trainer`` / ``Updater``.

Reference parity: the fused update kernels of src/operator/optimizer_op.cc
apply one parameter per launch; an eager ResNet-50 step therefore pays ~160
tiny kernel dispatches just to apply SGD. Here the caller flattens every
(weight, grad, state...) tree of one dtype into a single 1-D buffer and the
whole update runs as ONE Pallas launch: each program owns a (block_r, 128)
tile held in VMEM, the hyper-parameters ride SMEM, and weight/state inputs
are aliased to the outputs so the update is in-place in HBM.

Three flavors are fused — SGD-momentum, Adam, and AdamW — matching the
``_sgd_mom_update`` / ``_adam_update`` / ``_adamw_update`` kernels in
``ops/_optim_kernels.py`` bit-for-bit (the scalar arithmetic stays in
float32 and is cast to the buffer dtype exactly where jax weak-type
promotion would cast it in the per-parameter kernels). The Adam bias
corrections ``1 - b**t`` are taken in the caller and ride SMEM with the
other scalars: Mosaic has no lowering for ``math.powf``, so a power in a
kernel body passes interpret mode and is refused by the chip's compiler.
The lazy/sparse update kernels stay on the per-parameter path.

Dispatch lives behind the ``_optim_kernels`` seam (``_multi_*`` wrappers):
real Pallas on TPU, interpret mode where a test asks for it, and a lax
fallback (the per-parameter kernel applied once to the packed flat buffer)
anywhere else. ``MXTPU_FUSED_OPTIM=0`` disables the fold entirely.

The compiled ``parallel.ShardedTrainer`` step does NOT come through here:
its update is already inside one XLA program, where the per-leaf form fuses
and runs in place and the packed form only adds copies of every buffer
(measured on the chip, PERF.md section 6, PR 28).
"""

import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fused_optim_available():
    return jax.default_backend() == "tpu"


def fused_optim_enabled():
    """One env lookup: the whole fused path costs one predicate when off."""
    return os.environ.get("MXTPU_FUSED_OPTIM", "1") != "0"


#: optimizer names (optimizer/optimizer.py registry) with a fused path.
FUSED_OPTIMIZERS = ("sgd", "adam", "adamw")

_LANE = 128
# Pad the packed buffer to a multiple of 16 sublanes so the (block_r, 128)
# tiles satisfy the minimum tile for BOTH f32 (8, 128) and bf16 (16, 128).
_PAD_TO = 16 * _LANE


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def flatten_group(arrs):
    """Concat ravelled same-dtype ``arrs`` -> (flat_1d, metas) where metas
    reverses the packing via :func:`split_group`."""
    metas = [(a.shape, int(a.size)) for a in arrs]
    with jax.named_scope("pack"):
        if len(arrs) == 1:
            return arrs[0].reshape(-1), metas
        return jnp.concatenate([a.reshape(-1) for a in arrs]), metas


def split_group(flat, metas):
    """Inverse of :func:`flatten_group`."""
    out, off = [], 0
    with jax.named_scope("unpack"):
        for shape, size in metas:
            out.append(
                jax.lax.slice(flat, (off,), (off + size,)).reshape(shape))
            off += size
    return out


def _to_tiles(flat):
    """Zero-pad the 1-D buffer and reshape to (R, 128) Pallas tiles."""
    n = flat.shape[0]
    pad = (-n) % _PAD_TO
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, _LANE)


def _row_block(n_rows):
    """Largest row-block from the ladder that tiles n_rows (n_rows is a
    multiple of 16 by construction; 512 rows x 128 lanes x 4 B = 256 KiB per
    buffer keeps the worst case — Adam's 7 buffers — well inside VMEM)."""
    for cand in (512, 256, 128, 64, 32, 16):
        if n_rows % cand == 0:
            return cand
    return 16


# ---------------------------------------------------------------------------
# kernels — scalar math in f32, cast to the buffer dtype exactly where the
# per-parameter kernels' weak-type promotion would (bit-parity contract).
# ---------------------------------------------------------------------------

def _sgd_mom_kernel(s_ref, w_ref, m_ref, g_ref, ow_ref, om_ref):
    dt = w_ref.dtype
    lr, wd, momentum = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2]
    rescale, clip = s_ref[0, 5], s_ref[0, 6]
    w, g, mom = w_ref[...], g_ref[...], m_ref[...]
    g = g * rescale.astype(dt)
    g = jnp.where(clip > 0, jnp.clip(g, -clip.astype(dt), clip.astype(dt)), g)
    mom = momentum.astype(dt) * mom - lr.astype(dt) * (g + wd.astype(dt) * w)
    ow_ref[...] = w + mom
    om_ref[...] = mom


def _adam_kernel(s_ref, w_ref, m_ref, v_ref, g_ref,
                 ow_ref, om_ref, ov_ref):
    dt = w_ref.dtype
    wd, b1, b2 = s_ref[0, 1], s_ref[0, 2], s_ref[0, 3]
    eps, rescale, clip, coef = (s_ref[0, 4], s_ref[0, 5], s_ref[0, 6],
                                s_ref[0, 7])
    one = jnp.float32(1)
    w, g, m, v = w_ref[...], g_ref[...], m_ref[...], v_ref[...]
    g = g * rescale.astype(dt)
    g = jnp.where(clip > 0, jnp.clip(g, -clip.astype(dt), clip.astype(dt)), g)
    g = g + wd.astype(dt) * w
    m = b1.astype(dt) * m + (one - b1).astype(dt) * g
    v = b2.astype(dt) * v + (one - b2).astype(dt) * g * g
    ow_ref[...] = w - coef.astype(dt) * m / (jnp.sqrt(v) + eps.astype(dt))
    om_ref[...] = m
    ov_ref[...] = v


def _adamw_kernel(s_ref, w_ref, m_ref, v_ref, g_ref,
                  ow_ref, om_ref, ov_ref):
    dt = w_ref.dtype
    lr, wd, b1, b2 = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2], s_ref[0, 3]
    eps, rescale, clip, eta = (s_ref[0, 4], s_ref[0, 5], s_ref[0, 6],
                               s_ref[0, 7])
    c1, c2 = s_ref[0, 8], s_ref[0, 9]
    one = jnp.float32(1)
    w, g, m, v = w_ref[...], g_ref[...], m_ref[...], v_ref[...]
    g = g * rescale.astype(dt)
    g = jnp.where(clip > 0, jnp.clip(g, -clip.astype(dt), clip.astype(dt)), g)
    m = b1.astype(dt) * m + (one - b1).astype(dt) * g
    v = b2.astype(dt) * v + (one - b2).astype(dt) * g * g
    mhat = m / c1.astype(dt)
    vhat = v / c2.astype(dt)
    ow_ref[...] = w - eta.astype(dt) * (
        lr.astype(dt) * mhat / (jnp.sqrt(vhat) + eps.astype(dt))
        + wd.astype(dt) * w)
    om_ref[...] = m
    ov_ref[...] = v


# ---------------------------------------------------------------------------
# launch plumbing
# ---------------------------------------------------------------------------

def _launch(kernel, scalars, bufs, n_out, interpret, name):
    """One pallas_call over the packed (R, 128) buffers. ``bufs[:n_out]``
    are aliased to the outputs (in-place update in HBM) on the real-TPU
    path; weight/state buffers must therefore come first. `name` is the
    launch's name on the device: the compiled instruction and its events
    in a trace are called after it."""
    tiles = [_to_tiles(b) for b in bufs]
    R = tiles[0].shape[0]
    block_r = _row_block(R)
    tile_spec = pl.BlockSpec((block_r, _LANE), lambda i: (i, 0))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    dt = bufs[0].dtype
    aliases = {}
    if not interpret:
        # w/m(/v) inputs sit right after the scalar operand and map 1:1
        # onto the outputs; g (never aliased) is passed last.
        aliases = {1 + j: j for j in range(n_out)}
    outs = pl.pallas_call(
        kernel,
        grid=(R // block_r,),
        in_specs=[smem_spec] + [tile_spec] * len(tiles),
        out_specs=tuple([tile_spec] * n_out),
        out_shape=tuple(jax.ShapeDtypeStruct((R, _LANE), dt)
                        for _ in range(n_out)),
        input_output_aliases=aliases,
        interpret=interpret,
        name=name,
    )(scalars, *tiles)
    n = bufs[0].shape[0]
    return tuple(o.reshape(-1)[:n] for o in outs)


def _scalars(*vals):
    return jnp.asarray([vals], jnp.float32)


def _bias_corrections(b1, b2, t):
    """``(1 - b1**t, 1 - b2**t)`` in f32, taken outside the kernel (no
    ``powf`` in Mosaic): the same f32 ops in the same order as the
    per-parameter kernels evaluate them."""
    one = jnp.float32(1)
    tf = jnp.asarray(t, jnp.float32)
    return (one - jnp.asarray(b1, jnp.float32) ** tf,
            one - jnp.asarray(b2, jnp.float32) ** tf)


def fused_sgd_mom_flat(w, g, mom, lr, wd, momentum, rescale, clip,
                       interpret=False):
    """One-launch SGD-momentum over packed 1-D buffers -> (w, mom)."""
    s = _scalars(lr, wd, momentum, 0.0, 0.0, rescale, clip, 0.0)
    return _launch(_sgd_mom_kernel, s, [w, mom, g], 2, interpret,
                   "fused_sgd_mom")


def fused_adam_flat(w, g, m, v, lr, wd, b1, b2, eps, t, rescale, clip,
                    interpret=False):
    """One-launch Adam over packed 1-D buffers -> (w, m, v)."""
    c1, c2 = _bias_corrections(b1, b2, t)
    coef = jnp.asarray(lr, jnp.float32) * jnp.sqrt(c2) / c1
    s = _scalars(lr, wd, b1, b2, eps, rescale, clip, coef)
    return _launch(_adam_kernel, s, [w, m, v, g], 3, interpret,
                   "fused_adam")


def fused_adamw_flat(w, g, m, v, lr, wd, eta, b1, b2, eps, t, rescale, clip,
                     interpret=False):
    """One-launch AdamW over packed 1-D buffers -> (w, m, v)."""
    c1, c2 = _bias_corrections(b1, b2, t)
    s = _scalars(lr, wd, b1, b2, eps, rescale, clip, eta, c1, c2)
    return _launch(_adamw_kernel, s, [w, m, v, g], 3, interpret,
                   "fused_adamw")
