"""Neural-network operators.

Reference parity: src/operator/nn/* (FullyConnected, Convolution/Deconvolution,
BatchNorm, LayerNorm, InstanceNorm, L2Normalization, LRN, Pooling, Activation,
LeakyReLU zoo, Dropout, softmax family, SoftmaxOutput, UpSampling, Concat) per
SURVEY §2.3. Layout is NC(D)HW like the reference; XLA's layout assignment
re-tiles for the MXU so no manual NHWC conversion is needed.

All functions are pure and jit-traceable; stateful bits (BatchNorm moving
stats, Dropout RNG) are explicit inputs/outputs — the Gluon layer threads them.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

# ---------------------------------------------------------------------------
# FullyConnected (reference: src/operator/nn/fully_connected.cc:40-80)
# ---------------------------------------------------------------------------

@register("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """y = x @ W^T + b.  weight: (num_hidden, in_units) as in the reference."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.matmul(data, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution (reference: src/operator/nn/convolution.cc; NCHW/NCDHW layouts)
# ---------------------------------------------------------------------------

def _conv_dim_numbers(ndim):
    if ndim == 3:   # NCW
        return ("NCH", "OIH", "NCH")
    if ndim == 4:   # NCHW
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


def _use_channels_last():
    """Optional channels-last conv execution (API stays NCHW), toggled by
    MXTPU_CONV_LAYOUT=NHWC. Measured on v5e: isolated conv grads are ~15x
    faster feature-minor, but in full training programs XLA's layout
    assignment already normalizes, so the default stays NCHW."""
    import os
    return os.environ.get("MXTPU_CONV_LAYOUT", "").upper() in (
        "NHWC", "CHANNELS_LAST")


def _tup(v, n):
    if v is None:
        return (1,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _conv_stem_s2d(data, weight, stride, pad):
    """Space-to-depth lowering of the classic 7x7/2 pad-3 RGB stem conv
    (MLPerf TPU recipe): zero-pad the kernel to 8x8 and fold a 2x2 block of
    the input into channels, turning the conv into a 4x4/1 conv with 4*C_in
    input channels — the C_in=3 form pads badly onto the MXU's 8-sublane
    tiling. Exact same math (the extra kernel row/col multiplies zeros).
    Disable with MXTPU_CONV1_S2D=0."""
    B, C, H, W = data.shape
    O = weight.shape[0]
    x2 = data.reshape(B, C, H // 2, 2, W // 2, 2)
    x2 = x2.transpose(0, 3, 5, 1, 2, 4).reshape(B, 4 * C, H // 2, W // 2)
    wp = jnp.pad(weight, ((0, 0), (0, 0), (1, 0), (1, 0)))        # O,C,8,8
    w2 = wp.reshape(O, C, 4, 2, 4, 2).transpose(0, 3, 5, 1, 2, 4)
    w2 = w2.reshape(O, 4 * C, 4, 4)
    dn = lax.conv_dimension_numbers(x2.shape, w2.shape, ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(x2, w2, (1, 1), [(2, 1), (2, 1)],
                                    dimension_numbers=dn)


def _s2d_enabled():
    import os
    return os.environ.get("MXTPU_CONV1_S2D", "1") != "0"


@register("Convolution")
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None, **_ignored):
    """Grouped N-D convolution, NC(D)HW. weight: (num_filter, C/g, *kernel)."""
    sd = data.ndim - 2
    stride, dilate = _tup(stride, sd), _tup(dilate, sd)
    pad = _tup(pad, sd) if pad is not None else (0,) * sd
    if (sd == 2 and weight.shape[2:] == (7, 7) and stride == (2, 2)
            and pad == (3, 3) and dilate == (1, 1) and num_group == 1
            and data.shape[1] <= 4 and data.shape[2] % 2 == 0
            and data.shape[3] % 2 == 0 and not _use_channels_last()
            and _s2d_enabled()):
        out = _conv_stem_s2d(data, weight, stride, pad)
        if bias is not None and not no_bias:
            out = out + bias.reshape((1, -1) + (1,) * sd)
        return out
    # bf16 inputs: XLA's TPU lowering accumulates in fp32 on the MXU already;
    # forcing preferred_element_type=f32 here breaks the conv transpose rule
    # (cotangent dtype mismatch in grad-of-weight).
    if _use_channels_last():
        # TPU: run the conv feature-minor (NHWC/HWIO). The API stays NCHW;
        # the transposes are free — XLA folds them into the conv's layout
        # assignment — and the grad-of-weight conv avoids the pathological
        # channel-major path (measured ~15x slower on v5e).
        perm_in = (0,) + tuple(range(2, data.ndim)) + (1,)      # NC... -> N...C
        perm_w = tuple(range(2, data.ndim)) + (1, 0)            # OI... -> ...IO
        spatial = "DHW"[3 - sd:] if sd > 1 else "H"
        dn_cl = ("N" + spatial + "C", spatial + "IO", "N" + spatial + "C")
        dn = lax.conv_dimension_numbers(
            tuple(data.shape[p] for p in perm_in),
            tuple(weight.shape[p] for p in perm_w), dn_cl)
        out = lax.conv_general_dilated(
            jnp.transpose(data, perm_in), jnp.transpose(weight, perm_w),
            window_strides=stride, padding=[(p, p) for p in pad],
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)
        inv = (0, data.ndim - 1) + tuple(range(1, data.ndim - 1))
        out = jnp.transpose(out, inv)                           # N...C -> NC...
    else:
        dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                        _conv_dim_numbers(data.ndim))
        out = lax.conv_general_dilated(
            data, weight, window_strides=stride, padding=[(p, p) for p in pad],
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * sd)
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1, no_bias=False,
                  target_shape=None, **_ignored):
    """Transposed convolution. weight: (C_in, num_filter/g, *kernel)."""
    sd = data.ndim - 2
    stride, dilate = _tup(stride, sd), _tup(dilate, sd)
    pad = _tup(pad, sd) if pad is not None else (0,) * sd
    adj = _tup(adj, sd) if adj is not None else (0,) * sd
    kernel = weight.shape[2:]
    # conv_transpose of XLA: use lhs_dilation (fractional stride) formulation.
    pads = []
    for i in range(sd):
        k = (kernel[i] - 1) * dilate[i] + 1
        lo = k - 1 - pad[i]
        hi = k - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    if num_group > 1:
        cin = data.shape[1]
        xg = data.reshape((data.shape[0], num_group, cin // num_group) + data.shape[2:])
        wg = weight.reshape((num_group, cin // num_group) + weight.shape[1:])
        outs = [ _deconv_one(xg[:, g], wg[g], stride, dilate, pads) for g in range(num_group) ]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = _deconv_one(data, weight, stride, dilate, pads)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * sd)
    return out


def _deconv_one(data, weight, stride, dilate, pads):
    sd = data.ndim - 2
    # weight (C_in, C_out, *k) -> flip spatial, swap io -> (C_out, C_in, *k)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + sd)))
    w = jnp.swapaxes(w, 0, 1)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dim_numbers(data.ndim))
    return lax.conv_general_dilated(
        data, w, window_strides=(1,) * sd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)


# ---------------------------------------------------------------------------
# Pooling (reference: src/operator/nn/pooling.cc; pool_type max/avg/sum/lp)
# ---------------------------------------------------------------------------

@register("Pooling")
def pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True,
            p_value=2, **_ignored):
    """Max/avg/sum/lp pooling, N-D NCHW (reference: pooling.cc)."""
    sd = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride, pad = (1,) * sd, (0,) * sd
    else:
        kernel = _tup(kernel, sd)
        stride = _tup(stride, sd) if stride is not None else (1,) * sd
        pad = _tup(pad, sd) if pad is not None else (0,) * sd

    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full" and not global_pool:
        # ceil-mode: pad high edge so the last partial window is included
        pads = [(0, 0), (0, 0)]
        for i in range(sd):
            size = data.shape[2 + i]
            out = -(-(size + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            needed = (out - 1) * stride[i] + kernel[i] - size
            pads.append((pad[i], max(needed - pad[i], pad[i])))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]

    # NOTE: python-scalar init values are required — they make lax dispatch to
    # the differentiable monoid primitives (reduce_window_sum/max); array
    # inits fall back to the generic primitive which has no transpose rule.
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating)
                              else 0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        powed = jnp.abs(data) ** p_value
        s = lax.reduce_window(powed, 0.0, lax.add, window, strides, pads)
        return s ** (1.0 / p_value)
    raise ValueError("unknown pool_type %r" % pool_type)


@register("UpSampling")
def upsampling(data, weight=None, scale=2, sample_type="nearest",
               num_filter=0, **_ignored):
    """NCHW upsampling. 'nearest' replicates pixels; 'bilinear' is the
    reference's Deconvolution formulation (src/operator/nn/upsampling.cc:
    kernel 2*scale - scale%2, stride scale, pad ceil((scale-1)/2),
    per-channel groups) — `weight` (C, 1, k, k) is the learnable kernel;
    omitted, a fixed bilinear-interpolation kernel is used (the
    reference's standard initializer for it)."""
    b, c, h, w = data.shape
    if sample_type == "nearest":
        return jax.image.resize(data, (b, c, h * scale, w * scale),
                                method="nearest")
    if sample_type != "bilinear":
        raise ValueError("sample_type must be nearest or bilinear")
    k = 2 * scale - scale % 2
    pad = -(-(scale - 1) // 2)   # ceil((scale-1)/2)
    if weight is None:
        # bilinear interpolation kernel (reference init.Bilinear)
        center = (2 * scale - 1 - scale % 2) / (2.0 * scale)
        og = jnp.arange(k, dtype=jnp.float32)
        f1d = 1.0 - jnp.abs(og / scale - center)
        kern = f1d[:, None] * f1d[None, :]
        weight = jnp.broadcast_to(kern, (c, 1, k, k)).astype(data.dtype)
    # per-channel transposed conv: lhs_dilation=scale with OIHW (C,1,k,k)
    # weights and feature_group_count=C. The reference is a TRUE
    # Deconvolution (flipped kernel), and conv_general_dilated computes
    # cross-correlation — flip the taps so reference-trained asymmetric
    # weights transfer exactly (no-op for the symmetric bilinear init).
    return lax.conv_general_dilated(
        data, weight[..., ::-1, ::-1], window_strides=(1, 1),
        padding=[(k - 1 - pad, k - 1 - pad)] * 2,
        lhs_dilation=(scale, scale),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=c)


# ---------------------------------------------------------------------------
# Normalization (reference: batch_norm.cc, layer_norm.cc, instance_norm.cc,
# l2_normalization.cc, lrn.cc)
# ---------------------------------------------------------------------------

@register("BatchNorm", num_outputs=3)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               axis=1, training=False, **_ignored):
    """Returns (out, new_moving_mean, new_moving_var).

    Training mode uses a hand-written one-pass VJP (`_make_bn_train`): the
    batch stats are E[x]/E[x^2] accumulated in fp32 in a single read of the
    activation, and backward re-reads (x, dy) exactly once — HBM traffic is
    the binding constraint for BN on TPU, not FLOPs (reference semantics:
    src/operator/nn/batch_norm.cc, biased variance for both the normalizer
    and the moving average)."""
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    if training and not use_global_stats:
        out, mean, var = _make_bn_train(int(axis) % data.ndim, float(eps))(
            data, gamma, beta)
        mean = mean.astype(moving_mean.dtype)
        var = var.astype(moving_var.dtype)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
        return out, new_mean, new_var
    bshape = tuple(data.shape[axis] if i == axis else 1 for i in range(data.ndim))
    mean, var = moving_mean, moving_var
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape).astype(data.dtype)) \
        * (gamma * inv).reshape(bshape) + beta.reshape(bshape)
    return out, moving_mean, moving_var


import functools as _functools


@_functools.lru_cache(maxsize=None)
def _make_bn_train(axis, eps):
    """One-pass batch-norm training kernel as a custom VJP.

    Forward: s1=Σx, s2=Σx² fuse into ONE read of x (convert-to-f32 folded
    into the reduction), then out = x*scale + shift is one more read+write.
    Backward: Σdy and Σ(dy·x̂) fuse into one read of (x, dy); dx is one more.
    The naive jnp.mean/jnp.var formulation costs an extra full pass over x
    (mean first, then (x-mean)²) plus an un-fused normalize — ~40% more HBM
    traffic per BN layer.

    The mean/var outputs feed the moving-average update only; they are
    treated as non-differentiable (their cotangents are ignored), matching
    the reference where moving stats are aux state outside the graph.
    """

    def _fwd_impl(data, gamma, beta):
        red = tuple(i for i in range(data.ndim) if i != axis)
        bshape = tuple(-1 if i == axis else 1 for i in range(data.ndim))
        n = 1.0
        for i in red:
            n *= data.shape[i]
        f32 = jnp.float32
        s1 = jnp.sum(data, axis=red, dtype=f32)
        s2 = jnp.sum(jnp.square(data.astype(f32)), axis=red)
        mean = s1 / n
        var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
        inv = lax.rsqrt(var + eps)
        scale = gamma.astype(f32) * inv
        shift = beta.astype(f32) - mean * scale
        out = data * scale.astype(data.dtype).reshape(bshape) \
            + shift.astype(data.dtype).reshape(bshape)
        return out, mean, var, inv

    @jax.custom_vjp
    def core(data, gamma, beta):
        out, mean, var, _ = _fwd_impl(data, gamma, beta)
        return out, mean, var

    def fwd(data, gamma, beta):
        out, mean, var, inv = _fwd_impl(data, gamma, beta)
        return (out, mean, var), (data, gamma, beta, mean, inv)

    def bwd(res, cts):
        dy = cts[0]   # mean/var cotangents ignored (aux moving-stat outputs)
        data, gamma, beta, mean, inv = res
        red = tuple(i for i in range(data.ndim) if i != axis)
        bshape = tuple(-1 if i == axis else 1 for i in range(data.ndim))
        n = 1.0
        for i in red:
            n *= data.shape[i]
        f32 = jnp.float32
        dyf = dy.astype(f32)
        xhat = (data.astype(f32) - mean.reshape(bshape)) * inv.reshape(bshape)
        dbeta = jnp.sum(dyf, axis=red)
        dgamma = jnp.sum(dyf * xhat, axis=red)
        k = (gamma.astype(f32) * inv).astype(data.dtype).reshape(bshape)
        dx = k * (dy
                  - (dbeta / n).astype(data.dtype).reshape(bshape)
                  - xhat.astype(data.dtype)
                  * (dgamma / n).astype(data.dtype).reshape(bshape))
        return dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype)

    core.defvjp(fwd, bwd)
    return core


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, **_ignored):
    """Layer normalization over `axis` (reference: layer_norm.cc)."""
    if axis in (-1, data.ndim - 1):
        from ..gluon.block import trace_on_one_device
        from .pallas import fused_layer_norm, fused_norm_available
        if fused_norm_available() and trace_on_one_device():
            out = fused_layer_norm(data, gamma, beta, eps)
            if out is not None:
                return out
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + jnp.asarray(eps, var.dtype))
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    return (data - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3, **_ignored):
    """Instance normalization over spatial dims (reference: instance_norm.cc)."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance"):
    """L2-normalize per instance/channel/spatial (reference: l2_normalization.cc)."""
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / n


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response normalization across channels (reference: lrn.cc)."""
    sq = jnp.square(data)
    c = data.shape[1]
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (data.ndim - 2))
    acc = sum(padded[:, i:i + c] for i in range(nsize))
    return data / ((knorm + alpha * acc) ** beta)


# ---------------------------------------------------------------------------
# Activations (reference: activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------

@register("Activation")
def activation(data, act_type="relu"):
    """relu/sigmoid/tanh/softrelu/softsign by act_type (reference: activation.cc)."""
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %r" % act_type)


register("relu")(jax.nn.relu)
register("sigmoid")(jax.nn.sigmoid)
register("softsign")(jax.nn.soft_sign)
register("hard_sigmoid")(lambda data, alpha=0.2, beta=0.5:
                         jnp.clip(alpha * data + beta, 0.0, 1.0))
register("gelu")(lambda data: jax.nn.gelu(data, approximate=False))


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, key=None):
    """leaky/prelu/elu/selu/gelu/rrelu family (reference: leaky_relu.cc)."""
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 and data.ndim > 2 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        return 1.0507009873554805 * jax.nn.elu(data, alpha=1.6732632423543772)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        if key is None:  # inference: use mean slope
            return jnp.where(data >= 0, data, (lower_bound + upper_bound) / 2 * data)
        s = jax.random.uniform(key, data.shape, data.dtype, lower_bound, upper_bound)
        return jnp.where(data >= 0, data, s * data)
    raise ValueError("unknown act_type %r" % act_type)


register("swish")(lambda data, beta=1.0: data * jax.nn.sigmoid(beta * data))


# ---------------------------------------------------------------------------
# Softmax family (reference: softmax.cc, softmax-inl.h, softmax_output.cc)
# ---------------------------------------------------------------------------

@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None, use_length=False):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    if use_length and length is not None:
        steps = jnp.arange(data.shape[axis])
        bshape = [1] * data.ndim
        bshape[axis] = data.shape[axis]
        mask = steps.reshape(bshape) < length.reshape(
            [length.shape[0]] + [1] * (data.ndim - 1))
        data = jnp.where(mask, data, -jnp.inf)
    if axis in (-1, data.ndim - 1):
        from ..gluon.block import trace_on_one_device
        from .pallas import fused_softmax, fused_norm_available
        if fused_norm_available() and trace_on_one_device():
            out = fused_softmax(data, axis=axis)
            if out is not None:
                return out
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@register("softmin")
def softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label=None, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Forward = softmax; the loss-layer gradient semantics live in its
    custom VJP (reference: softmax_output.cc backward)."""
    axis = 1 if multi_output else -1
    if label is None:
        return jax.nn.softmax(data, axis=axis)
    core = _make_softmax_output(float(grad_scale), float(ignore_label),
                                bool(use_ignore), axis, normalization,
                                float(smooth_alpha))
    return core(data, label.astype(jnp.float32))


import functools as _functools


@_functools.lru_cache(maxsize=None)
def _make_softmax_output(grad_scale, ignore_label, use_ignore, axis,
                         normalization, smooth_alpha):
    @jax.custom_vjp
    def core(data, label):
        return jax.nn.softmax(data, axis=axis)

    def fwd(data, label):
        out = jax.nn.softmax(data, axis=axis)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        k = out.shape[axis]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), k, axis=axis, dtype=out.dtype)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (k - 1) * (1 - onehot)
        grad = out - onehot
        if use_ignore:
            keep = (label != ignore_label).astype(out.dtype)
            grad = grad * jnp.expand_dims(keep, axis)
        if normalization == "valid" and use_ignore:
            n = jnp.maximum(jnp.sum(label != ignore_label), 1).astype(out.dtype)
            grad = grad / n * out.shape[0]
        return (grad * grad_scale, jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked)


# ---------------------------------------------------------------------------
# Dropout (reference: dropout.cc — mode 'training'/'always')
# ---------------------------------------------------------------------------

@register("Dropout")
def dropout(data, p=0.5, mode="training", axes=(), training=False, key=None):
    """Inverted dropout; identity at inference (reference: dropout.cc)."""
    if (not training and mode != "always") or p <= 0:
        return data
    if key is None:
        from . import random as _rnd
        key = _rnd.next_key()
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    return jnp.where(keep, data / (1.0 - p), jnp.zeros((), data.dtype))


# ---------------------------------------------------------------------------
# Losses as ops (reference: regression_output.cc, make_loss)
# ---------------------------------------------------------------------------

@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    return _make_regression(float(grad_scale), "linear")(data, label.astype(data.dtype))


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    return _make_regression(float(grad_scale), "logistic")(data, label.astype(data.dtype))


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    return _make_regression(float(grad_scale), "mae")(data, label.astype(data.dtype))


@_functools.lru_cache(maxsize=None)
def _make_regression(grad_scale, kind):
    @jax.custom_vjp
    def core(data, label):
        return jax.nn.sigmoid(data) if kind == "logistic" else data

    def fwd(data, label):
        out = jax.nn.sigmoid(data) if kind == "logistic" else data
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        label = label.reshape(out.shape)
        grad = jnp.sign(out - label) if kind == "mae" else (out - label)
        return (grad * grad_scale, jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core
