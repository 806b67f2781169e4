"""Fused multi-tensor optimizer (ops/pallas/fused_optim.py) — bit-parity
pins against the per-param kernels at the _optim_kernels seam, the
ShardedTrainer / gluon.Trainer integration, and the stay-per-param
carve-outs (sparse grads, momentum=0).

Parity tiers (FMA contraction moves once shapes/fusion change):
- seam level (_multi_* vs per-param _*_update, same jit boundary):
  BITWISE, f32 and bf16;
- whole trainer on-vs-off: allclose rtol=1e-5/atol=1e-8 (different
  program partitioning around the update);
- interpret-vs-fallback arms of the same seam call: rtol=1e-4/atol=1e-6.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.ops import _optim_kernels as K
from incubator_mxnet_tpu.parallel import make_mesh, ShardedTrainer

_SHAPES = [(3, 5), (7,), (2, 2, 4), (1,)]
# t=1 and t=1000 pin the bias corrections hoisted out of the kernels
# (1 - b**t taken in the caller, passed through SMEM): the first step,
# where 1 - b2**t is smallest, and a late one, where b**t underflows
# towards 0, must still round exactly as the per-parameter kernels do.
_INTERP_T = [(False, 3), (True, 3), (True, 1), (True, 1000)]
_INTERP_T_IDS = ["compiled", "interpret", "interpret-t1", "interpret-t1000"]


def _tensors(dt, seed=0):
    rng = np.random.RandomState(seed)
    ws = [jnp.asarray(rng.randn(*s), dt) for s in _SHAPES]
    gs = [jnp.asarray(rng.randn(*s), dt) for s in _SHAPES]
    ms = [jnp.asarray(rng.randn(*s), dt) for s in _SHAPES]
    vs = [jnp.asarray(np.abs(rng.randn(*s)), dt) for s in _SHAPES]
    return ws, gs, ms, vs


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("interp", [False, True],
                         ids=["compiled", "interpret"])
def test_seam_sgd_mom_bitwise(dt, interp):
    ws, gs, ms, _ = _tensors(dt)
    lr, wd, mom, rescale, clip = 0.1, 1e-4, 0.9, 1.0 / 32, 2.0
    ref = [K._sgd_mom_update(w, g, m, lr, wd, mom, rescale, clip)
           for w, g, m in zip(ws, gs, ms)]
    nw, nm = K._multi_sgd_mom_update(ws, gs, ms, lr, wd, mom, rescale,
                                     clip, interpret=interp)
    for (rw, rm), fw, fm in zip(ref, nw, nm):
        assert rw.dtype == fw.dtype
        np.testing.assert_array_equal(np.asarray(rw), np.asarray(fw))
        np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("interp,t", _INTERP_T, ids=_INTERP_T_IDS)
def test_seam_adam_bitwise(dt, interp, t):
    ws, gs, ms, vs = _tensors(dt)
    lr, wd, rescale, clip = 0.1, 1e-4, 1.0 / 32, 2.0
    b1, b2, eps = 0.9, 0.999, 1e-8
    ref = [K._adam_update(w, g, m, v, lr, wd, b1, b2, eps, t, rescale,
                          clip)
           for w, g, m, v in zip(ws, gs, ms, vs)]
    nw, nm, nv = K._multi_adam_update(ws, gs, ms, vs, lr, wd, b1, b2,
                                      eps, t, rescale, clip,
                                      interpret=interp)
    for (rw, rm, rv), fw, fm, fv in zip(ref, nw, nm, nv):
        np.testing.assert_array_equal(np.asarray(rw), np.asarray(fw))
        np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
        np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("interp,t", _INTERP_T, ids=_INTERP_T_IDS)
def test_seam_adamw_bitwise(dt, interp, t):
    ws, gs, ms, vs = _tensors(dt)
    lr, wd, eta, rescale, clip = 0.1, 1e-4, 1.0, 1.0 / 32, 2.0
    b1, b2, eps = 0.9, 0.999, 1e-8
    ref = [K._adamw_update(w, g, m, v, lr, wd, eta, b1, b2, eps, t,
                           rescale, clip)
           for w, g, m, v in zip(ws, gs, ms, vs)]
    nw, nm, nv = K._multi_adamw_update(ws, gs, ms, vs, lr, wd, eta, b1,
                                       b2, eps, t, rescale, clip,
                                       interpret=interp)
    for (rw, rm, rv), fw, fm, fv in zip(ref, nw, nm, nv):
        np.testing.assert_array_equal(np.asarray(rw), np.asarray(fw))
        np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
        np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))


def test_sparse_and_momentumless_stay_per_param():
    """update_multi must route sparse grads and momentum=0 through the
    per-param path (0 fused launches), never densify, never crash."""
    from incubator_mxnet_tpu import optimizer as opt
    from incubator_mxnet_tpu.ndarray import sparse as sp

    o = opt.create("sgd", learning_rate=0.1)       # momentum=0
    w = nd.array(np.ones((4, 3), np.float32))
    g = nd.array(np.full((4, 3), 0.5, np.float32))
    st = o.create_state(0, w)
    assert o.update_multi([0], [w], [g], [st]) == 0

    o2 = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    w2 = nd.array(np.ones((4, 3), np.float32))
    gs = sp.row_sparse_array(
        (np.full((1, 3), 0.5, np.float32), np.array([2], np.int64)),
        shape=(4, 3))
    st2 = o2.create_state(0, w2)
    assert o2.update_multi([0], [w2], [gs], [st2]) == 0
    out = np.asarray(w2._data)
    assert (out[2] != 1.0).all() and (out[0] == 1.0).all()


def _make_mlp(prefix):
    np.random.seed(0)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _loss_fn(out, label):
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None],
                                axis=-1).mean()


_OPTS = [("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
         ("adam", {"learning_rate": 0.01}),
         ("adamw", {"learning_rate": 0.01, "wd": 0.01})]

_COUNTER = [0]


def _sharded_run(opt, params, env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    net = _make_mlp("fo%d_" % _COUNTER[0])
    _COUNTER[0] += 1
    np.random.seed(1)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, _loss_fn, mesh, optimizer=opt,
                        optimizer_params=params)
    losses = [float(jax.device_get(tr.step(nd.array(X), nd.array(y))))
              for _ in range(3)]
    pv = {k.split("_", 1)[1]: np.asarray(jax.device_get(v))
          for k, v in tr.param_values.items()}
    for k in env:
        monkeypatch.delenv(k, raising=False)
    return losses, pv, getattr(tr, "_fused_launches", None)


@pytest.mark.parametrize("opt,params", _OPTS,
                         ids=[o for o, _ in _OPTS])
def test_sharded_trainer_fused_on_off_interpret(opt, params, monkeypatch):
    l_off, p_off, fl_off = _sharded_run(
        opt, params, {"MXTPU_FUSED_OPTIM": "0"}, monkeypatch)
    l_on, p_on, fl_on = _sharded_run(opt, params, {}, monkeypatch)
    l_in, p_in, fl_in = _sharded_run(
        opt, params, {"MXTPU_FUSED_OPTIM_INTERPRET": "1"}, monkeypatch)
    # the traced trainer only engages the fused launch where it really is
    # one launch (TPU) or when interpret is forced; on CPU the default-on
    # arm stays per-param by design (lax-packed form would only add
    # pack/unpack copies to the already-fused step program)
    expect_on = 1 if jax.default_backend() == "tpu" else 0
    assert fl_off == 0 and fl_on == expect_on and fl_in == 1, (
        fl_off, fl_on, fl_in)
    for k in p_off:
        np.testing.assert_allclose(p_off[k], p_on[k], rtol=1e-5,
                                   atol=1e-8, err_msg="%s %s" % (opt, k))
        np.testing.assert_allclose(p_on[k], p_in[k], rtol=1e-4,
                                   atol=1e-6, err_msg="%s %s" % (opt, k))
    np.testing.assert_allclose(l_off, l_on, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("opt,params", _OPTS,
                         ids=[o for o, _ in _OPTS])
def test_gluon_trainer_fused_bitwise(opt, params, monkeypatch):
    """The EAGER gluon.Trainer path calls the seam directly, so fused
    vs per-param is bitwise there — same losses, identical params."""
    np.random.seed(1)
    X = nd.array(np.random.rand(16, 8).astype(np.float32))
    y = nd.array(np.random.randint(0, 4, (16,)).astype(np.int32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(fused):
        monkeypatch.setenv("MXTPU_FUSED_OPTIM", "1" if fused else "0")
        net = _make_mlp("gf%d_" % _COUNTER[0])
        _COUNTER[0] += 1
        tr = gluon.Trainer(net.collect_params(), opt, dict(params))
        losses = []
        for _ in range(3):
            with autograd.record():
                loss = loss_fn(net(X), y).mean()
            loss.backward()
            tr.step(16)
            losses.append(float(np.asarray(loss._data)))
        pv = {p.name.split("_", 1)[1]: np.asarray(p.data()._data)
              for p in net.collect_params().values()}
        return losses, pv

    l0, p0 = run(fused=False)
    l1, p1 = run(fused=True)
    assert l0 == l1, (opt, l0, l1)
    for k in p0:
        np.testing.assert_array_equal(p0[k], p1[k],
                                      err_msg="%s %s" % (opt, k))
