"""Pallas kernel tests. On the CPU test mesh only availability/fallback is
checked; numerical checks run when a TPU is attached (they are also
exercised by bench/driver runs on device)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.pallas import (flash_attention,
                                            flash_attention_available)
from incubator_mxnet_tpu.parallel.ring_attention import local_attention


def test_available_flag_consistent():
    avail = flash_attention_available()
    assert avail == (jax.default_backend() == "tpu")


def test_seq_len_validation():
    if not flash_attention_available():
        pytest.skip("needs TPU")
    q = jnp.zeros((1, 1, 100, 64))
    with pytest.raises(ValueError):
        flash_attention(q, q, q)


@pytest.mark.skipif(not flash_attention_available(), reason="needs TPU")
def test_flash_matches_reference():
    np.random.seed(0)
    B, H, T, D = 2, 4, 256, 64
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    out = flash_attention(q, k, v)
    num, den, _ = local_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(num / den),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.skipif(not flash_attention_available(), reason="needs TPU")
def test_flash_causal_and_grads():
    np.random.seed(1)
    B, H, T, D = 1, 2, 128, 64
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    outc = flash_attention(q, k, v, causal=True)
    num, den, _ = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(outc), np.asarray(num / den),
                               rtol=2e-3, atol=2e-3)
    gf = jax.grad(lambda a, b, c: flash_attention(a, b, c).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: (lambda n, d, m: (n / d).sum())(
        *local_attention(a, b, c)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2,
                                   atol=1e-2)


# ---------------------------------------------------------------------------
# fused LayerNorm / Softmax (interpret mode runs on CPU, so these check
# numerics everywhere; on TPU the same code path compiles via Mosaic)
# ---------------------------------------------------------------------------

def test_fused_layer_norm_matches_jnp():
    from incubator_mxnet_tpu.ops.pallas import fused_layer_norm
    np.random.seed(1)
    x = jnp.asarray(np.random.randn(32, 256).astype(np.float32))
    g = jnp.asarray(np.random.rand(256).astype(np.float32) + 0.5)
    b = jnp.asarray(np.random.randn(256).astype(np.float32))
    got = fused_layer_norm(x, g, b, eps=1e-5, interpret=True)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    want = (x - mean) / np.sqrt(var + 1e-5) * g + b
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_layer_norm_grad():
    from incubator_mxnet_tpu.ops.pallas.fused_norm import _ln_core
    np.random.seed(2)
    x = jnp.asarray(np.random.randn(16, 128).astype(np.float32))
    g = jnp.asarray(np.random.rand(128).astype(np.float32) + 0.5)
    b = jnp.asarray(np.random.randn(128).astype(np.float32))

    def f_pallas(x, g, b):
        return jnp.sum(_ln_core(x, g, b, 1e-5, True) ** 2)

    def f_ref(x, g, b):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return jnp.sum(((x - mean) / jnp.sqrt(var + 1e-5) * g + b) ** 2)

    got = jax.grad(f_pallas, argnums=(0, 1, 2))(x, g, b)
    want = jax.grad(f_ref, argnums=(0, 1, 2))(x, g, b)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-3, atol=1e-3)


def test_fused_softmax_matches_jnp():
    from incubator_mxnet_tpu.ops.pallas import fused_softmax
    np.random.seed(3)
    x = jnp.asarray(np.random.randn(8, 4, 128).astype(np.float32) * 3)
    got = fused_softmax(x, interpret=True)
    want = jax.nn.softmax(x, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_softmax_grad():
    from incubator_mxnet_tpu.ops.pallas.fused_norm import _softmax_core
    np.random.seed(4)
    x = jnp.asarray(np.random.randn(8, 128).astype(np.float32))
    got = jax.grad(lambda v: jnp.sum(_softmax_core(v, True) ** 2))(x)
    want = jax.grad(lambda v: jnp.sum(jax.nn.softmax(v, -1) ** 2))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fused_fallback_on_bad_shapes():
    from incubator_mxnet_tpu.ops.pallas import fused_layer_norm, fused_softmax
    # 7 rows doesn't tile -> None (caller falls back)
    x = jnp.zeros((7, 64))
    assert fused_layer_norm(x, jnp.ones(64), jnp.zeros(64)) is None
    assert fused_softmax(jnp.zeros((5, 3, 7, 64))[..., 0]) is None


# ---------------------------------------------------------------------------
# flash attention backward (Pallas kernels, interpret mode on CPU)
# ---------------------------------------------------------------------------

def _dense_ref(q, k, v, causal):
    T = q.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / jnp.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    return jax.nn.softmax(s, -1) @ v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    np.random.seed(0)
    B, H, T, D = 1, 2, 256, 64
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True) ** 2).sum()

    def fr(q, k, v):
        return (_dense_ref(q, k, v, causal) ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        rel = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert rel < 1e-4, rel


def test_flash_forward_interpret_matches_dense():
    np.random.seed(1)
    q = jnp.asarray(np.random.randn(1, 2, 256, 64).astype(np.float32))
    out = flash_attention(q, q, q, interpret=True)
    want = _dense_ref(q, q, q, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_kv_mask_interpret():
    """Padding mask: padded kv positions get zero attention fwd+bwd."""
    np.random.seed(0)
    B, H, T, D = 2, 2, 128, 32
    valid = 96
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    mask = jnp.asarray(
        (np.arange(T) < valid).astype(np.int32)[None].repeat(B, 0))

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, kv_mask=mask, interpret=True)
        return (out[:, :, :valid] ** 2).sum(), out

    def dense_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(mask[:, None, None, :] != 0, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        return (out[:, :, :valid] ** 2).sum(), out

    (lf, of), gf = jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    (ld, od), gd = jax.value_and_grad(dense_loss, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(of[:, :, :valid]),
                               np.asarray(od[:, :, :valid]),
                               rtol=2e-3, atol=2e-3)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)
    # no attention mass on padded keys: dk/dv vanish there
    np.testing.assert_allclose(np.asarray(gf[1][:, :, valid:]), 0,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gf[2][:, :, valid:]), 0,
                               atol=1e-6)


def test_flash_fully_masked_rows_are_zero():
    """A sample with valid_length == 0 must produce EXACT zero outputs and
    zero grads, not renormalized attention over padding (ADVICE r2)."""
    np.random.seed(5)
    B, H, T, D = 2, 2, 128, 32
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    # sample 0 fully masked, sample 1 fully live
    mask = jnp.asarray(np.stack([np.zeros(T), np.ones(T)]).astype(np.int32))

    def loss(q, k, v):
        out = flash_attention(q, k, v, kv_mask=mask, interpret=True)
        return (out ** 2).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    for g in grads:
        np.testing.assert_array_equal(np.asarray(g[0]), 0.0)
    # the live sample still matches the dense reference
    want = _dense_ref(q[1:], k[1:], v[1:], False)
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_kv_bias_gradient_matches_dense():
    """Learned per-key additive bias: forward AND the bias cotangent match
    einsum attention (the r2 kernel silently returned dbias = 0)."""
    np.random.seed(6)
    B, H, T, D = 2, 2, 128, 32
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    bias = jnp.asarray(np.random.randn(B, H, T).astype(np.float32))

    def flash_loss(q, k, v, bias):
        out = flash_attention(q, k, v, kv_bias=bias, interpret=True)
        return (out ** 2).sum()

    def dense_loss(q, k, v, bias):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = s + bias[:, :, None, :]
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return (out ** 2).sum()

    gf = jax.grad(flash_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def test_flash_kv_bias_causal_gradient():
    np.random.seed(7)
    B, H, T, D = 1, 2, 128, 32
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    bias = jnp.asarray(np.random.randn(B, T).astype(np.float32))   # 2-D form

    def flash_loss(bias):
        out = flash_attention(q, k, v, causal=True, kv_bias=bias,
                              interpret=True)
        return (out ** 2).sum()

    def dense_loss(bias):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = s + bias[:, None, None, :]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return (out ** 2).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(flash_loss)(bias)),
                               np.asarray(jax.grad(dense_loss)(bias)),
                               rtol=2e-2, atol=2e-3)


def test_fused_bottleneck_matches_xla_reference():
    """Pallas fully-fused stage-1 bottleneck (interpret mode) == the XLA
    conv-stack arm, fp32 (VERDICT r5 #1b experiment's numerics gate)."""
    from incubator_mxnet_tpu.ops.pallas.fused_bottleneck import (
        fused_bottleneck, bottleneck_reference)
    rng = np.random.RandomState(0)
    B, H, W, C, M = 2, 8, 8, 32, 8
    x = jnp.asarray(rng.randn(B, H, W, C).astype(np.float32) * 0.5)
    w1 = jnp.asarray(rng.randn(C, M).astype(np.float32) * 0.2)
    w2 = jnp.asarray(rng.randn(9, M, M).astype(np.float32) * 0.2)
    w3 = jnp.asarray(rng.randn(M, C).astype(np.float32) * 0.2)
    mkv = lambda n: (jnp.asarray(rng.rand(n).astype(np.float32) + 0.5),
                     jnp.asarray(rng.randn(n).astype(np.float32) * 0.1))
    s1, b1 = mkv(M); s2, b2 = mkv(M); s3, b3 = mkv(C)
    out_p = fused_bottleneck(x, w1, s1, b1, w2, s2, b2, w3, s3, b3,
                             interpret=True)
    out_r = bottleneck_reference(x, w1, s1, b1, w2, s2, b2, w3, s3, b3)
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_flash_parity_at_default_min_t():
    """Fwd+bwd parity at T=512 — the env-tunable gate's new DEFAULT
    threshold (MXTPU_FLASH_MIN_T). Lowering the crossover from 2048 is
    only sound if the kernel keeps numerics at the shorter length too."""
    np.random.seed(8)
    B, H, T, D = 1, 1, 512, 32
    q = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, H, T, D).astype(np.float32))
    out = flash_attention(q, k, v, interpret=True)
    want = _dense_ref(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def f(q, k, v):
        return (flash_attention(q, k, v, interpret=True) ** 2).sum()

    def fr(q, k, v):
        return (_dense_ref(q, k, v, False) ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        rel = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        assert rel < 1e-4, rel


# ---------------------------------------------------------------------------
# flash attention for a sequence that is one tile (interpret mode on CPU)
# ---------------------------------------------------------------------------

def _dense_bthd(q, k, v, H, mask):
    """Dense float32 attention on (B, T, H*D); rows whose keys are all
    masked give exact zeros."""
    B, T, HD = q.shape
    D = HD // H

    def split(x):
        return x.astype(jnp.float32).reshape(B, T, H, D).transpose(0, 2, 1, 3)

    s = jnp.einsum("bhqd,bhkd->bhqk", split(q), split(k)) / np.sqrt(D)
    if mask is not None:
        s = s + jnp.where(mask != 0, 0.0, -1e30)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        p = jnp.where(jnp.max(s, -1, keepdims=True) <= -5e29, 0.0, p)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, split(v))
    return out.transpose(0, 2, 1, 3).reshape(B, T, HD)


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kv_mask"])
@pytest.mark.parametrize("H,D", [(2, 64), (12, 64), (2, 128)])
@pytest.mark.parametrize("T", [128, 256, 512, 1024])
def test_flash_one_tile_matches_dense(T, H, D, masked):
    """The one-tile launches against dense float32 attention: forward and
    all three gradients, with a valid-length mask, and with a row whose
    keys are ALL masked (exact zeros, zero gradients)."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention_bthd
    from incubator_mxnet_tpu.ops.pallas.flash_attention import _one_tile
    B = 3 if masked else 1
    assert _one_tile(T, H, D, False, None)
    rng = np.random.RandomState(T + H + D)
    q, k, v, g = (jnp.asarray(rng.randn(B, T, H * D).astype(np.float32))
                  .astype(jnp.bfloat16) for _ in range(4))
    mask = None
    if masked:
        lens = np.array([T // 2, 0, T])          # row 1: no live key
        mask = jnp.asarray((np.arange(T)[None] < lens[:, None])
                           .astype(np.int32))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention_bthd(
        q, k, v, H, kv_mask=mask, interpret=True), q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: _dense_bthd(q, k, v, H, mask),
                             q, k, v)
    assert out.shape == (B, T, H * D) and out.dtype == jnp.bfloat16
    # bfloat16 results of float32 mathematics: half an ulp of the largest
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=8e-3)
    grads, want_grads = vjp(g), want_vjp(g.astype(jnp.float32))
    for got, ref in zip(grads, want_grads):
        ref = np.asarray(ref, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   atol=2e-2 * max(1.0, np.abs(ref).max()))
    if masked:
        np.testing.assert_array_equal(np.asarray(out[1], np.float32), 0.0)
        for got in grads:
            np.testing.assert_array_equal(np.asarray(got[1], np.float32),
                                          0.0)
        # no attention mass on padded keys: dK and dV vanish there
        for got in grads[1:]:
            np.testing.assert_array_equal(
                np.asarray(got[0, T // 2:], np.float32), 0.0)


def _launches(fn, *args):
    """The Pallas launches of fn's forward and backward, by name, as the
    program lowered for the TPU holds them (nothing compiles or runs)."""
    import re
    loss = lambda *a: fn(*a).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return sorted(re.findall(r"kernel_name = \"(\w+)\"", text))


def test_flash_one_tile_is_chosen_from_the_call_alone(monkeypatch):
    """No knob: the shape and arguments of the call pick the launches, and
    the block-size variables of the tiled kernels mean nothing to the
    one-tile path."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention_bthd
    monkeypatch.setenv("MXTPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MXTPU_FLASH_BLOCK_K", "128")
    tile = ["flash_attention_tile_bwd", "flash_attention_tile_fwd"]
    tiled = ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
             "flash_attention_fwd"]

    def x(T):
        return jax.ShapeDtypeStruct((2, T, 768), jnp.bfloat16)

    plain = lambda q, k, v: flash_attention_bthd(q, k, v, 12)
    assert _launches(plain, x(512), x(512), x(512)) == tile
    mask = jnp.ones((2, 512), jnp.int32)
    assert _launches(lambda q, k, v: flash_attention_bthd(
        q, k, v, 12, kv_mask=mask), x(512), x(512), x(512)) == tile
    assert _launches(plain, x(2048), x(2048), x(2048)) == tiled
    assert _launches(lambda q, k, v: flash_attention_bthd(
        q, k, v, 12, causal=True), x(512), x(512), x(512)) == tiled
    bias = jnp.zeros((2, 512), jnp.float32)
    assert _launches(lambda q, k, v: flash_attention_bthd(
        q, k, v, 12, kv_bias=bias), x(512), x(512), x(512)) == tiled
    # heads that do not fill 128-lane groups: 6 heads of 32
    assert _launches(lambda q, k, v: flash_attention_bthd(q, k, v, 6),
                     jax.ShapeDtypeStruct((2, 512, 192), jnp.bfloat16),
                     jax.ShapeDtypeStruct((2, 512, 192), jnp.bfloat16),
                     jax.ShapeDtypeStruct((2, 512, 192), jnp.bfloat16)
                     ) == tiled


def test_flash_bthd_tiled_fallback_matches_dense():
    """Beyond one tile the (B, T, H*D) entry carries heads outermost and
    runs the tiled kernels: same results as the dense reference."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention_bthd
    rng = np.random.RandomState(3)
    B, T, H, D = 1, 256, 2, 32           # D=32: not a one-tile shape
    q, k, v = (jnp.asarray(rng.randn(B, T, H * D).astype(np.float32))
               for _ in range(3))
    out = flash_attention_bthd(q, k, v, H, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_bthd(q, k, v, H, None)),
                               rtol=2e-5, atol=2e-5)


_GATE_N = [0]


def _flash_gate_fired(T, monkeypatch, min_t=None):
    """Drive MultiHeadAttention._attend at seq len T inside a fake trace
    with flash availability forced on; report whether the gate dispatched
    to the (sentinel) kernel. The negative case falls through to the
    dense einsum path, so the output shape is exercised either way."""
    import incubator_mxnet_tpu.ops.pallas as pallas_mod
    from incubator_mxnet_tpu.gluon.block import _TraceCtx, _trace_state
    from incubator_mxnet_tpu.models.bert import MultiHeadAttention

    called = []

    def _sentinel(q, k, v, num_heads, scale=None, kv_mask=None, **kw):
        called.append(T)
        return q

    # bert.py resolves both names from the module at call time, so
    # module-attr patching reaches the gate without a TPU attached
    monkeypatch.setattr(pallas_mod, "flash_attention_available",
                        lambda: True)
    monkeypatch.setattr(pallas_mod, "flash_attention_bthd", _sentinel)
    if min_t is None:
        monkeypatch.delenv("MXTPU_FLASH_MIN_T", raising=False)
    else:
        monkeypatch.setenv("MXTPU_FLASH_MIN_T", min_t)
    B, H, D = 1, 1, 8
    # (B, T, H*D), as the projections leave it: the gate sits before any
    # reshape into heads
    q = jnp.asarray(np.random.RandomState(0)
                    .randn(B, T, H * D).astype(np.float32))
    mha = MultiHeadAttention(H * D, H, prefix="flashgate%d_" % _GATE_N[0])
    _GATE_N[0] += 1
    prev = getattr(_trace_state, "ctx", None)
    _trace_state.ctx = _TraceCtx({}, None, training=False)
    try:
        out = mha._attend(_trace_state.ctx.F, q, q, q, None, B, T, D)
    finally:
        _trace_state.ctx = prev
    assert out.shape == (B, T, H * D)
    return bool(called)


def test_flash_gate_default_min_t(monkeypatch):
    assert _flash_gate_fired(512, monkeypatch)       # at default: fires
    assert not _flash_gate_fired(384, monkeypatch)   # %128==0 but < 512


def test_flash_gate_env_override(monkeypatch):
    assert not _flash_gate_fired(512, monkeypatch, min_t="2048")
    assert _flash_gate_fired(2048, monkeypatch, min_t="2048")
    assert _flash_gate_fired(128, monkeypatch, min_t="128")
    # the T % 128 tiling contract is NOT tunable below the threshold
    assert not _flash_gate_fired(192, monkeypatch, min_t="128")
    # garbage value falls back to the 512 default
    assert _flash_gate_fired(512, monkeypatch, min_t="not-a-number")
    assert not _flash_gate_fired(384, monkeypatch, min_t="not-a-number")


def test_int8_matmul_kernel_numerics():
    """Mosaic int8 x int8 -> s32 kernel (interpret mode) == numpy int32
    matmul exactly (VERDICT r5 #8 probe's numerics gate)."""
    from incubator_mxnet_tpu.ops.pallas.int8_matmul import int8_matmul
    rng = np.random.RandomState(0)
    a = rng.randint(-127, 128, (64, 96)).astype(np.int8)
    b = rng.randint(-127, 128, (96, 32)).astype(np.int8)
    out = int8_matmul(jnp.asarray(a), jnp.asarray(b), block_m=32,
                      block_n=32, interpret=True)
    want = a.astype(np.int32) @ b.astype(np.int32)
    np.testing.assert_array_equal(np.asarray(out), want)


# ------------------------------------------------------- names on the device
_KERNEL_FILES = ["flash_attention.py", "flash_decode.py",
                 "fused_bottleneck.py", "fused_norm.py", "fused_optim.py",
                 "grouped_matmul.py", "int8_matmul.py", "paged_heads.py",
                 "paged_latent.py", "ssm_state.py"]


def _pallas_calls(tree):
    import ast
    return [node for node in ast.walk(tree) if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call"]


@pytest.mark.parametrize("filename", _KERNEL_FILES)
def test_every_pallas_call_names_its_kernel(filename):
    """A launch's instruction in the compiled program, and its events in a
    device trace, are called after ``name=``; without it they take the
    name of whatever scope the call sits in (``step_fn.1``)."""
    import ast
    import os

    from incubator_mxnet_tpu.ops import pallas
    path = os.path.join(os.path.dirname(pallas.__file__), filename)
    with open(path) as f:
        calls = _pallas_calls(ast.parse(f.read()))
    assert calls, "no pallas_call in %s: drop it from the list" % filename
    for call in calls:
        assert "name" in {kw.arg for kw in call.keywords}, \
            "%s:%d pallas_call without name=" % (filename, call.lineno)


def test_the_list_of_kernel_files_is_whole():
    import ast
    import glob
    import os

    from incubator_mxnet_tpu.ops import pallas
    having = []
    for path in glob.glob(os.path.join(os.path.dirname(pallas.__file__),
                                       "*.py")):
        with open(path) as f:
            if _pallas_calls(ast.parse(f.read())):
                having.append(os.path.basename(path))
    assert sorted(having) == _KERNEL_FILES


# ------------------------------------- the expanded path of latent attention
def _prefill_case(rng, dtype, lengths, chunk, heads, poison=False, bs=4,
                  blocks=8, rank=16, nope=8, rope=8, v=8):
    """A chunk's operands over a pool whose block tables are a permutation
    of the blocks, padded past a length with any valid id, or (`poison`)
    with the id of a block full of NaN."""
    from incubator_mxnet_tpu.ops.pallas.paged_latent import cache_row_width
    S, width = len(lengths), cache_row_width(rank, rope)
    lengths = np.asarray(lengths, np.int32)
    pool = rng.normal(size=(S * blocks + 1, bs, width))
    tables = rng.permutation(S * blocks).reshape(S, blocks).astype(np.int32)
    for s, n in enumerate(lengths):
        used = -(-int(n) // bs)
        tables[s, used:] = rng.integers(0, S * blocks, size=blocks - used)
        if poison:
            tables[s, used:] = S * blocks
    if poison:
        live = np.zeros(len(pool), bool)
        for s, n in enumerate(lengths):
            live[tables[s, :-(-int(n) // bs)]] = True
        pool[~live] = np.nan
    # keys, values and scores of deviation 1 whatever the widths

    def draw(*shape, over=1):
        return jnp.asarray(rng.normal(size=shape) * over ** -0.5, dtype)
    return {"q_nope": draw(S, chunk, heads, nope, over=nope + rope),
            "q_rope": draw(S, chunk, heads, rope, over=nope + rope),
            "new_rows": draw(S, chunk, width),
            "kv_b": draw(rank, heads, nope + v, over=rank),
            "pool": jnp.asarray(pool, dtype), "block_tables": tables,
            "lengths": lengths, "scale": 1.0}


# toys: blocks of 4 positions, a tile of 8, tables of 32; the two latent
# cells' chunks and heads at a quarter of the rank over a cache's own
# blocks of 128 and tiles of 512
_PREFILL_CASES = {
    "no_past": dict(lengths=[0], chunk=8, heads=4),
    "past_of_no_whole_block": dict(lengths=[5], chunk=8, heads=4),
    "past_of_no_whole_tile": dict(lengths=[13], chunk=16, heads=2),
    "two_unequal_sequences": dict(lengths=[21, 3], chunk=12, heads=4),
    "padding_names_a_nan_block": dict(lengths=[6, 0, 17], chunk=8, heads=2,
                                      poison=True),
    "a_chunk_of_three_tiles": dict(lengths=[9], chunk=20, heads=3),
    "long_prompts_c1024_h32": dict(
        lengths=[700], chunk=1024, heads=32, bs=128, rank=128, nope=128,
        rope=64, v=128, key_tile=512),
    "wide_batch_c512_h64": dict(
        lengths=[0], chunk=512, heads=64, bs=128, rank=128, nope=128,
        rope=64, v=128, key_tile=512),
}


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5), ("bfloat16", 0.0)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_PREFILL_CASES))
def test_the_prefill_kernel_is_the_lax_path(case, dtype, atol):
    """``paged_latent_prefill`` (interpreted) against the ``lax`` expanded
    path on the same tiles: each sequence's own live rows and no other
    (a block past a length may hold NaN), then the chunk itself under
    the diagonal. The same products in the same precisions: float32
    agrees to an accumulation order, bfloat16 to the bit at the toys'
    widths, as the decode kernel does; at the cells' widths the launch
    sums a score's 192 terms in one product where the ``lax`` path adds
    two, and an exponent rounded the other way moves an output by one
    bfloat16 step (of values of deviation 1)."""
    from incubator_mxnet_tpu.ops.pallas.paged_latent import (
        paged_latent_attention)
    case = dict(_PREFILL_CASES[case])
    key_tile = case.pop("key_tile", 8)
    rtol = 0.0
    if key_tile == 512 and dtype == "bfloat16":
        atol = rtol = 2 ** -7
    case = _prefill_case(np.random.default_rng(case["chunk"]),
                         jnp.dtype(dtype), **case)
    got = paged_latent_attention(**case, key_tile=key_tile, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    # the oracle reads a table's padding too (masked): give it clean blocks
    case["pool"] = jnp.nan_to_num(case["pool"])
    want = paged_latent_attention(**case, key_tile=key_tile)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=rtol)


# ------------------------------------------- the decode walk of a loop step
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("step", [None, 0, 2], ids=["no_step", "step_0",
                                                    "step_2"])
def test_the_walk_of_a_loop_step_is_the_walk_over_that_steps_lanes(step,
                                                                   dtype):
    """``paged_heads_decode`` given a loop step over pools whose rows hold
    three steps' H D lanes side by side is, to the bit, the walk without
    the operand over that step's lanes alone (what the ``lax`` path
    gathers, ``models/ouro.py``), and both are the dense softmax over each
    sequence's own rows; the step may be traced. Without the operand the
    walk is the one cell 7 runs."""
    from incubator_mxnet_tpu.ops.pallas import paged_heads
    S, H, D, steps, block, blocks = 3, 2, 128, 3, 8, 4
    rng = np.random.default_rng(7)
    width = H * D
    lanes = width * (steps if step is not None else 1)
    pools = [jnp.asarray(rng.normal(size=(S * blocks, block, lanes)), dtype)
             for _ in "kv"]
    tables = rng.permutation(S * blocks).reshape(S, blocks).astype(np.int32)
    lengths = np.asarray([19, 0, 32], np.int32)
    q = jnp.asarray(rng.normal(size=(S, H, D)), dtype)
    walk = jax.jit(lambda u, *pools: paged_heads.paged_heads_decode(
        q, *pools, tables, lengths, scale=D ** -0.5, key_tile=16,
        interpret=True, step=u))
    got = walk(step, *pools)
    first = 0 if step is None else step * width
    ours = [p[..., first:first + width] for p in pools]
    alone = paged_heads.paged_heads_decode(
        q, *ours, tables, lengths, scale=D ** -0.5, key_tile=16,
        interpret=True)
    for a, b in zip(got, alone):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    m, l, acc = [np.asarray(a, np.float64) for a in got]
    for s, n in enumerate(lengths):
        if n == 0:
            assert (m[s] <= -1e29).all() and not l[s].any()
            continue
        k, v = [np.asarray(p, np.float64)[tables[s]].reshape(-1, H, D)[:n]
                for p in ours]
        scores = np.einsum("hd,thd->ht", np.asarray(q[s], np.float64), k) \
            * D ** -0.5
        p = np.exp(scores - scores.max(-1, keepdims=True))
        tol = 1e-4 if dtype == "float32" else 0.05
        assert np.allclose(m[s], scores.max(-1), atol=tol, rtol=tol)
        assert np.allclose(acc[s] / l[s][:, None],
                           np.einsum("ht,thd->hd", p, v) / p.sum(-1)[:, None],
                           atol=tol, rtol=tol)
    pool = jax.ShapeDtypeStruct((4, 16, steps * width), jnp.bfloat16)
    assert paged_heads.paged_heads_decode_available(pool, H, steps) is False
