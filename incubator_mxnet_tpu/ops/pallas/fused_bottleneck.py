"""Pallas experiment: ONE fully-fused ResNet bottleneck block in VMEM.

VERDICT r4 #1b asked for a measured answer to "would a Pallas fused
conv+BN+ReLU(+residual) stage-1 bottleneck beat XLA's conv stack?"
(BENCHMARKS.md had dismissed it without numbers). This kernel computes
the ENTIRE stage-1 bottleneck — 1x1 conv -> BN -> ReLU -> 3x3 conv ->
BN -> ReLU -> 1x1 conv -> BN -> +residual -> ReLU — as one Pallas
program per image, with every intermediate resident in VMEM: the
inter-conv activations (the HBM traffic XLA cannot elide, ~2x51 MB per
block at bs 128) never touch HBM.

Scope: inference-mode BN (folded per-channel scale/bias — the only form
expressible without a batch-global reduction inside a per-image grid).
That is exactly what the experiment needs: if the fused FORWARD cannot
beat XLA's convs, the training-mode version (which adds batch-stat
plumbing and a custom VJP) cannot either, and the negative is decisive.

Layout: NHWC (channels-last minor axis = the MXU lane axis). The convs
run as matmuls: the 1x1s directly over the flattened spatial axis, the
3x3 as 9 shifted (HW, M) @ (M, M) accumulations over a zero-padded
VMEM copy.

Reference counterpart: src/operator/fusion/fused_op.cu (the reference
fuses elementwise chains into generated CUDA; conv fusion is what its
cuDNN backend provides). No caller outside the tests (ROADMAP C3).
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_bottleneck", "fused_bottleneck_available",
           "bottleneck_reference"]


def fused_bottleneck_available():
    return jax.default_backend() == "tpu"


def _kernel(x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref, b2_ref,
            w3_ref, s3_ref, b3_ref, o_ref, *, H, W, C, M):
    x = x_ref[0]                                     # (H, W, C) bf16
    # ---- 1x1 conv + BN + ReLU: (H*W, C) @ (C, M)
    xf = x.reshape(H * W, C)
    h1 = jnp.dot(xf, w1_ref[...], preferred_element_type=jnp.float32)
    h1 = jnp.maximum(h1 * s1_ref[...] + b1_ref[...], 0.0)
    h1 = h1.astype(x.dtype).reshape(H, W, M)
    # ---- 3x3 conv (pad 1) as 9 shifted matmuls over a padded VMEM copy
    hp = jnp.pad(h1, ((1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((H * W, M), jnp.float32)
    for ky in range(3):
        for kx in range(3):
            tap = hp[ky:ky + H, kx:kx + W].reshape(H * W, M)
            acc += jnp.dot(tap, w2_ref[ky * 3 + kx],
                           preferred_element_type=jnp.float32)
    h2 = jnp.maximum(acc * s2_ref[...] + b2_ref[...], 0.0).astype(x.dtype)
    # ---- 1x1 conv + BN + residual + ReLU: (H*W, M) @ (M, C)
    h3 = jnp.dot(h2, w3_ref[...], preferred_element_type=jnp.float32)
    h3 = h3 * s3_ref[...] + b3_ref[...]
    out = jnp.maximum(h3 + xf.astype(jnp.float32), 0.0)
    o_ref[0] = out.astype(o_ref.dtype).reshape(H, W, C)


def fused_bottleneck(x, w1, s1, b1, w2, s2, b2, w3, s3, b3,
                     interpret=False):
    """x: (B, H, W, C) NHWC; w1 (C, M); w2 (9, M, M) [ky*3+kx taps];
    w3 (M, C); s*/b* folded BN scale/bias per channel (fp32).
    Returns relu(bn3(conv3(relu(bn2(conv2(relu(bn1(conv1(x)))))))) + x).
    One grid step per image; all intermediates VMEM-resident."""
    B, H, W, C = x.shape
    M = w1.shape[1]
    spec_w = lambda shape: pl.BlockSpec(shape, lambda b: (0,) * len(shape))
    try:        # one image's working set is ~17 MB; the default scoped
        #         limit is 16 MB but v5e has 128 MB physical VMEM
        params = dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024))
    except Exception:       # pragma: no cover — mxlint: disable=broad-except (pallas/TPU availability probe: any import or lowering failure means fall back to the XLA path) - older pallas APIs
        params = {}
    return pl.pallas_call(
        functools.partial(_kernel, H=H, W=W, C=C, M=M),
        grid=(B,),
        **params,
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
            spec_w((C, M)), spec_w((1, M)), spec_w((1, M)),
            spec_w((9, M, M)), spec_w((1, M)), spec_w((1, M)),
            spec_w((M, C)), spec_w((1, C)), spec_w((1, C)),
        ],
        out_specs=pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, C), x.dtype),
        interpret=interpret,
        name="fused_bottleneck",
    )(x, w1, s1.reshape(1, M), b1.reshape(1, M),
      w2, s2.reshape(1, M), b2.reshape(1, M),
      w3, s3.reshape(1, C), b3.reshape(1, C))


def bottleneck_reference(x, w1, s1, b1, w2, s2, b2, w3, s3, b3):
    """The identical math through XLA's conv stack (the A/B arm):
    lax.conv_general_dilated in NHWC with the same folded BN."""
    dn = jax.lax.conv_dimension_numbers(x.shape, (1, 1, 1, 1),
                                        ("NHWC", "HWIO", "NHWC"))
    C, M = w1.shape

    def conv(h, w, pad):
        return jax.lax.conv_general_dilated(
            h, w, window_strides=(1, 1), padding=pad,
            dimension_numbers=dn,
            preferred_element_type=jnp.float32)

    h = conv(x, w1.reshape(1, 1, C, M), "VALID")
    h = jnp.maximum(h * s1 + b1, 0.0).astype(x.dtype)
    h = conv(h, w2.reshape(3, 3, M, M), "SAME")
    h = jnp.maximum(h * s2 + b2, 0.0).astype(x.dtype)
    h = conv(h, w3.reshape(1, 1, M, C), "VALID")
    h = h * s3 + b3
    return jnp.maximum(h + x.astype(jnp.float32), 0.0).astype(x.dtype)
