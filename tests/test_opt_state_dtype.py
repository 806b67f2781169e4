"""Low-precision optimizer state in ShardedTrainer (opt_state_dtype):
bf16-stored Adam moments, fp32 update math — the standard TPU trick for
halving the optimizer's HBM traffic (BENCHMARKS.md BERT roofline names
the AdamW state traffic as the step's dominant non-activation term)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh


def _loss(out, lab):
    lp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, lab[:, None], axis=-1).mean()


def _fresh_net(X):
    net = mx.models.lenet5()
    net.initialize(mx.init.Xavier(), force_reinit=True)
    net(nd.array(X[:2]))                     # resolve deferred shapes
    return net


def _clone_params(src, dst):
    # fresh blocks differ only in the auto prefix counter; align by order
    sps = sorted(src.collect_params().values(), key=lambda p: p.name)
    dps = sorted(dst.collect_params().values(), key=lambda p: p.name)
    for s, d in zip(sps, dps):
        d.set_data(nd.array(s.data().asnumpy()))


def _run(net, X, y, osd, steps=15, optimizer="adamw"):
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, _loss, mesh, optimizer=optimizer,
                        optimizer_params={"learning_rate": 1e-3,
                                          "momentum": 0.9},
                        data_specs=[P()], label_spec=P(),
                        opt_state_dtype=osd)
    losses = [float(tr.step([nd.array(X)], nd.array(y)))
              for _ in range(steps)]
    return losses, tr


def test_bf16_state_tracks_fp32_trajectory():
    rng = np.random.RandomState(0)
    X = rng.rand(64, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.int32)
    net_a = _fresh_net(X)
    net_b = _fresh_net(X)
    _clone_params(net_a, net_b)

    l32, tr32 = _run(net_a, X, y, None)
    lb16, trb = _run(net_b, X, y, "bfloat16")
    # identical starting point; state storage is the only difference
    assert abs(l32[0] - lb16[0]) < 1e-5, (l32[0], lb16[0])
    assert lb16[-1] < lb16[0]                       # still converges
    drift = max(abs(a - b) for a, b in zip(l32, lb16))
    assert drift < 0.05, drift                      # tracks closely

    m, v = next(iter(trb._opt_state.values()))
    assert m.dtype == jnp.bfloat16 and v.dtype == jnp.bfloat16
    m32, v32 = next(iter(tr32._opt_state.values()))
    assert m32.dtype == jnp.float32


def test_bf16_state_sgd_momentum():
    rng = np.random.RandomState(1)
    X = rng.rand(32, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.int32)
    net = _fresh_net(X)
    losses, tr = _run(net, X, y, "bfloat16", optimizer="sgd")
    (mom,) = next(iter(tr._opt_state.values()))
    assert mom.dtype == jnp.bfloat16
    assert losses[-1] < losses[0]


def _remap(flat, src_tr, dst_tr):
    """Translate state-dict keys between two structurally-identical nets
    that differ only in the auto prefix counter."""
    mapping = dict(zip(sorted(src_tr._diff_names + src_tr._aux_names),
                       sorted(dst_tr._diff_names + dst_tr._aux_names)))
    out = {}
    for k, v in flat.items():
        for tag in ("param/", "opt0/", "opt1/"):
            if k.startswith(tag) and k[len(tag):] in mapping:
                k = tag + mapping[k[len(tag):]]
                break
        out[k] = v
    return out


def test_bf16_state_checkpoint_round_trip(tmp_path):
    """nd.save/load must round-trip bfloat16 (npz bit-casts via uint16),
    and a restored trainer keeps its CONFIGURED state precision."""
    rng = np.random.RandomState(2)
    X = rng.rand(32, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.int32)
    net = _fresh_net(X)
    net2 = _fresh_net(X)
    net3 = _fresh_net(X)
    _clone_params(net, net2)        # clone BEFORE training: the jitted
    _clone_params(net, net3)        # step donates the captured buffers
    _, tr = _run(net, X, y, "bfloat16", steps=3)

    # raw nd bf16 round-trip
    arr = nd.array(np.array([1.5, -2.25], np.float32)).astype("bfloat16")
    path = str(tmp_path / "bf16.npz")
    mx.nd.save(path, {"a": arr})
    back = mx.nd.load(path)["a"]
    assert str(back.dtype) == "bfloat16"
    np.testing.assert_allclose(back.asnumpy().astype(np.float32),
                               [1.5, -2.25])

    # full trainer state dict through save/load
    sd = tr.state_dict()
    ck = str(tmp_path / "trainer.npz")
    mx.nd.save(ck, {k: nd.array(np.asarray(v)) if not hasattr(v, "_data")
                    else v for k, v in sd.items()})
    flat = mx.nd.load(ck)
    _, tr2 = _run(net2, X, y, "bfloat16", steps=0)
    flat = _remap(flat, tr, tr2)
    tr2.load_state_dict(flat)
    m, v = next(iter(tr2._opt_state.values()))
    assert m.dtype == jnp.bfloat16
    m1, v1 = next(iter(tr._opt_state.values()))
    np.testing.assert_array_equal(np.asarray(m).view(np.uint16),
                                  np.asarray(m1).view(np.uint16))

    # fp32 checkpoint into a bf16-configured trainer follows the config
    _, tr32 = _run(net3, X, y, None, steps=3)
    sd32 = tr32.state_dict()
    ck32 = str(tmp_path / "trainer32.npz")
    mx.nd.save(ck32, {k: v if hasattr(v, "_data")
                      else nd.array(np.asarray(v))
                      for k, v in sd32.items()})
    tr2.load_state_dict(_remap(mx.nd.load(ck32), tr32, tr2))
    m, v = next(iter(tr2._opt_state.values()))
    assert m.dtype == jnp.bfloat16          # configured precision wins


def _run_pd(net, X, y, pd, steps=15, optimizer="adamw"):
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, _loss, mesh, optimizer=optimizer,
                        optimizer_params={"learning_rate": 1e-3,
                                          "momentum": 0.9},
                        data_specs=[P()], label_spec=P(),
                        param_dtype=pd)
    losses = [float(tr.step([nd.array(X)], nd.array(y)))
              for _ in range(steps)]
    return losses, tr


def test_stochastic_round_is_unbiased():
    """E[SR(x)] == x: averaging many independent roundings of a value that
    is NOT bf16-representable must recover it far more closely than one
    bf16 ulp (nearest-rounding is off by up to half an ulp EVERY time)."""
    from incubator_mxnet_tpu.parallel.trainer import _stochastic_round
    x = jnp.full((4096,), 1.0 + 1.0 / 512.0, jnp.float32)  # between ulps
    acc = np.zeros(x.shape, np.float64)
    n = 64
    for i in range(n):
        r = _stochastic_round(x, jnp.bfloat16, jax.random.PRNGKey(i))
        acc += np.asarray(r.astype(jnp.float32), np.float64)
    mean_err = abs(acc.mean() / n - float(x[0]))
    ulp = 2.0 / 256.0                      # bf16 ulp at 1.x
    assert mean_err < 0.05 * ulp, (mean_err, ulp)
    # single roundings land on representable values only
    one = _stochastic_round(x, jnp.bfloat16, jax.random.PRNGKey(99))
    vals = set(np.asarray(one.astype(np.float32)).tolist())
    assert vals <= {1.0, 1.0 + 1.0 / 128.0}, vals


def test_bf16_params_track_fp32_trajectory():
    """bf16-STORED params with SR write-back (no fp32 master at all) must
    still track the fp32 trajectory and converge."""
    rng = np.random.RandomState(3)
    X = rng.rand(64, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.int32)
    net_a = _fresh_net(X)
    net_b = _fresh_net(X)
    _clone_params(net_a, net_b)

    l32, _ = _run(net_a, X, y, None)
    lb16, trb = _run_pd(net_b, X, y, "bfloat16")
    assert abs(l32[0] - lb16[0]) < 2e-2, (l32[0], lb16[0])  # bf16 init fwd
    assert lb16[-1] < lb16[0]
    drift = max(abs(a - b) for a, b in zip(l32, lb16))
    assert drift < 0.1, drift

    for n in trb._diff_names:
        assert trb._param_vals[n].dtype == jnp.bfloat16


def test_bf16_params_checkpoint_configured_precision(tmp_path):
    rng = np.random.RandomState(4)
    X = rng.rand(32, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.int32)
    net = _fresh_net(X)
    net2 = _fresh_net(X)
    _clone_params(net, net2)
    _, tr = _run_pd(net, X, y, "bfloat16", steps=3)
    sd = tr.state_dict()
    ck = str(tmp_path / "trainer_pd.npz")
    mx.nd.save(ck, {k: v if hasattr(v, "_data")
                    else nd.array(np.asarray(v)) for k, v in sd.items()})
    _, tr2 = _run_pd(net2, X, y, "bfloat16", steps=0)
    tr2.load_state_dict(_remap(mx.nd.load(ck), tr, tr2))
    for n in tr2._diff_names:
        assert tr2._param_vals[n].dtype == jnp.bfloat16


def test_bf16_params_zero1_manual_step_scan():
    """zero1(manual) x param_dtype: bf16-SR params compose with the
    dp shard_map region (SR keys derive from the PRE-rank-fold key so
    replicated params round identically on every rank), and opt state
    defaults to fp32 — bf16 params alone must NOT silently downgrade
    the Adam moments."""
    rng = np.random.RandomState(5)
    X = rng.rand(32, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.int32)
    net = _fresh_net(X)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr = ShardedTrainer(net, _loss, mesh, optimizer="adamw",
                        optimizer_params={"learning_rate": 1e-3},
                        zero1="manual", param_dtype="bfloat16")
    losses = tr.step_scan([nd.array(X)], nd.array(y), n_steps=4)
    arr = np.asarray(jax.device_get(losses), np.float32)
    assert np.isfinite(arr).all(), arr
    for n in tr._diff_names:
        assert tr._param_vals[n].dtype == jnp.bfloat16
    # opt state stayed fp32 (no opt_state_dtype given)
    m, v = next(iter(tr._opt_state.values()))
    assert m.dtype == jnp.float32 and v.dtype == jnp.float32


def test_bf16_params_grad_accum_fp32_buffer():
    """grad_accum x param_dtype: microbatch grads accumulate in fp32
    even though the stored params (and therefore per-micro grads) are
    bf16 — accumulation must not lose sub-ulp contributions."""
    rng = np.random.RandomState(6)
    X = rng.rand(32, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.int32)
    net_a = _fresh_net(X)
    net_b = _fresh_net(X)
    _clone_params(net_a, net_b)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])

    def build(net, accum):
        return ShardedTrainer(net, _loss, mesh, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.05},
                              grad_accum=accum, param_dtype="bfloat16")

    tr1 = build(net_a, 1)
    tr4 = build(net_b, 4)
    for _ in range(3):
        l1 = tr1.step([nd.array(X)], nd.array(y))
        l4 = tr4.step([nd.array(X)], nd.array(y))
    # same data, same math up to bf16 fwd + fp32-mean-of-4 vs full mean:
    # trajectories track closely (SR noise differs -> loose bound)
    assert abs(float(l1) - float(l4)) < 0.05, (float(l1), float(l4))
