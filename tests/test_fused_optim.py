"""Fused multi-tensor optimizer (ops/pallas/fused_optim.py) — bit-parity
pins against the per-param kernels at the _optim_kernels seam, the eager
gluon.Trainer integration, the stay-per-param carve-outs (sparse grads,
momentum=0), and the ShardedTrainer's own per-leaf update, which takes no
packed launch.

Parity tiers (FMA contraction moves once shapes/fusion change):
- seam level (_multi_* vs per-param _*_update, same jit boundary):
  BITWISE, f32 and bf16;
- compiled ShardedTrainer step vs the per-param kernels on a gradient
  written out by hand: allclose rtol=1e-5/atol=1e-6; vs its own
  _apply_opt_fp leaf by leaf, and under MXTPU_FUSED_OPTIM* : BITWISE;
- interpret-vs-fallback arms of the same seam call: rtol=1e-4/atol=1e-6.
"""

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


def _batch():
    np.random.seed(1)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    return X, y


def _mlp_loss(pv, X, y):
    """_make_mlp's forward and _loss_fn written out, so that a reference
    shares no code with the trainer's gradient stage."""
    h = jax.nn.relu(X @ pv["dense0_weight"].T + pv["dense0_bias"])
    return _loss_fn(h @ pv["dense1_weight"].T + pv["dense1_bias"], y)


def _bare(tree):
    """Host copies under the names without the net's prefix."""
    return {k.split("_", 1)[1]: jax.tree_util.tree_map(np.array, v)
            for k, v in tree.items()}


def _sharded_run(opt, params, env, monkeypatch, **trainer_kw):
    """Three steps of a one-device ShardedTrainer under `env` -> (trainer,
    its first parameters, its last parameters)."""
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    net = _make_mlp("fo%d_" % _COUNTER[0])
    _COUNTER[0] += 1
    X, y = _batch()
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, _loss_fn, mesh, optimizer=opt,
                        optimizer_params=params, **trainer_kw)
    first = _bare(tr.param_values)      # copies: the step donates them
    for _ in range(3):
        tr.step(nd.array(X), nd.array(y))
    for k in env:
        monkeypatch.delenv(k, raising=False)
    return tr, first, _bare(tr.param_values)


def _per_param_kernel(opt, params, w, g, st, t):
    """One leaf through the per-parameter kernel of ops/_optim_kernels.py
    (rescale 1, clip off) -> (new weight, new state)."""
    lr, wd = params["learning_rate"], params.get("wd", 0.0)
    if opt == "sgd":
        nw, nm = K._sgd_mom_update(w, g, st[0], lr, wd, params["momentum"],
                                   1.0, -1.0)
        return nw, (nm,)
    if opt == "adam":
        # the kernel adds epsilon to sqrt(v) before the bias correction,
        # the trainer to sqrt(vhat) after it
        nw, nm, nv = K._adam_update(w, g, *st, lr, wd, 0.9, 0.999,
                                    1e-8 * (1 - 0.999 ** t) ** 0.5, t,
                                    1.0, -1.0)
    else:
        # the kernel decays by eta * wd * w, the trainer by lr * wd * w
        nw, nm, nv = K._adamw_update(w, g, *st, lr, lr * wd, 1.0, 0.9,
                                     0.999, 1e-8, t, 1.0, -1.0)
    return nw, (nm, nv)


def _reference_steps(first, state, update):
    """Three steps from the parameters `first` on _mlp_loss's gradient,
    `update(w, g, leaf state, t)` leaf by leaf -> (parameters, state)."""
    X, y = _batch()
    pv = {k: jnp.asarray(v) for k, v in first.items()}
    for t in (1, 2, 3):
        grads = jax.grad(_mlp_loss)(pv, X, y)
        for k in pv:
            pv[k], state[k] = update(pv[k], grads[k], state[k], t)
    return pv, state


@pytest.mark.parametrize("opt,params", _OPTS,
                         ids=[o for o, _ in _OPTS])
def test_sharded_trainer_matches_per_param_kernels(opt, params, monkeypatch):
    """The compiled step applies the optimizer leaf by leaf: three steps
    land on the per-parameter kernels' parameters, and the variables that
    once chose a packed launch change no bit of them."""
    _, first, last = _sharded_run(opt, params, {}, monkeypatch)
    for env in ({"MXTPU_FUSED_OPTIM": "0"},
                {"MXTPU_FUSED_OPTIM_INTERPRET": "1"}):
        _, first_env, last_env = _sharded_run(opt, params, env, monkeypatch)
        for k in last:
            np.testing.assert_array_equal(first[k], first_env[k])
            np.testing.assert_array_equal(last[k], last_env[k],
                                          err_msg="%s %s %s" % (opt, k, env))
    n_slots = 1 if opt == "sgd" else 2
    pv, _ = _reference_steps(
        first, {k: (jnp.zeros_like(v),) * n_slots for k, v in first.items()},
        lambda w, g, st, t: _per_param_kernel(opt, params, w, g, st, t))
    for k in pv:
        assert np.abs(last[k] - first[k]).max() > 0, k
        # atol: a few float32 roundings of weights of size 1, which is what
        # an element near zero is held to after three +-lr steps of Adam
        np.testing.assert_allclose(last[k], np.asarray(pv[k]), rtol=1e-5,
                                   atol=1e-6, err_msg="%s %s" % (opt, k))


@pytest.mark.parametrize("opt,params", _OPTS[1:],
                         ids=[o for o, _ in _OPTS[1:]])
def test_sharded_trainer_bf16_moments_per_leaf(opt, params, monkeypatch):
    """opt_state_dtype="bfloat16": the moments are STORED bfloat16 beside
    float32 weights, lifted to float32 for the arithmetic and rounded on
    the way out — _apply_opt_fp, leaf by leaf, as a mesh runs it."""
    tr, first, last = _sharded_run(opt, params, {}, monkeypatch,
                                   opt_state_dtype="bfloat16")
    moments = _bare(tr._opt_state)
    assert sorted(moments) == sorted(last)
    for k in last:
        assert last[k].dtype == np.float32, k
        assert [s.dtype for s in moments[k]] == [jnp.bfloat16] * 2, k
    leaf_update = jax.jit(tr._apply_opt_fp)
    pv, state = _reference_steps(
        first, {k: (jnp.zeros(v.shape, jnp.bfloat16),) * 2
                for k, v in first.items()},
        lambda w, g, st, t: leaf_update(w, g, st, jnp.float32(t)))
    for k in pv:
        np.testing.assert_array_equal(last[k], np.asarray(pv[k]), err_msg=k)
        for mine, theirs in zip(moments[k], state[k]):
            np.testing.assert_array_equal(mine, np.asarray(theirs),
                                          err_msg=k)


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
