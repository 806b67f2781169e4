"""Checkpoint/resume formats (SURVEY §5): gluon save/load_parameters,
HybridBlock.export + SymbolBlock.imports, Module save/load_checkpoint."""

import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx


def test_gluon_params_roundtrip(tmp_path):
    net = mx.models.lenet5()
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.rand(2, 1, 28, 28).astype(np.float32))
    out1 = net(x).asnumpy()
    p = str(tmp_path / "p.params")
    net.save_parameters(p)
    net2 = mx.models.lenet5()
    net2.load_parameters(p)
    np.testing.assert_allclose(net2(x).asnumpy(), out1, rtol=1e-6)


def test_export_symbolblock_roundtrip(tmp_path):
    net = mx.models.lenet5()
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.rand(2, 1, 28, 28).astype(np.float32))
    out1 = net(x).asnumpy()
    net.hybridize()
    net(x)
    base = str(tmp_path / "m")
    net.export(base)
    sb = mx.gluon.SymbolBlock.imports(base + "-symbol.json", ["data"],
                                      base + "-0000.params")
    np.testing.assert_allclose(sb(x).asnumpy(), out1, rtol=1e-4, atol=1e-4)


def test_export_with_batchnorm_aux(tmp_path):
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(8), mx.gluon.nn.BatchNorm(),
            mx.gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.rand(4, 5).astype(np.float32))
    out1 = net(x).asnumpy()          # inference stats path
    net.hybridize()
    net(x)
    base = str(tmp_path / "bn")
    net.export(base)
    loaded = mx.nd.load(base + "-0000.params")
    assert any(k.startswith("aux:") for k in loaded), sorted(loaded)
    sb = mx.gluon.SymbolBlock.imports(base + "-symbol.json", ["data"],
                                      base + "-0000.params")
    np.testing.assert_allclose(sb(x).asnumpy(), out1, rtol=1e-4, atol=1e-4)


def test_module_checkpoint_roundtrip(tmp_path):
    sym = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4, name="fc")
    mod = mx.module.Module(sym, data_names=["data"], label_names=[])
    mod.bind(data_shapes=[("data", (2, 8))])
    mod.init_params(mx.init.Xavier())
    base = str(tmp_path / "ck")
    mod.save_checkpoint(base, 3)
    sym2, arg2, aux2 = mx.model.load_checkpoint(base, 3)
    assert sorted(arg2) == ["fc_bias", "fc_weight"]
    x = np.random.rand(2, 8).astype(np.float32)
    out = sym2.eval(data=mx.nd.array(x), **{k: v for k, v in arg2.items()})
    want = x @ arg2["fc_weight"].asnumpy().T + arg2["fc_bias"].asnumpy()
    np.testing.assert_allclose(out[0].asnumpy(), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# CheckpointManager: preemption-aware checkpointing (SURVEY §5 "modern
# equivalent: preemption-aware checkpointing + coordinator restart")
# ---------------------------------------------------------------------------

import json
import os
import signal
import subprocess
import sys
import textwrap

from incubator_mxnet_tpu.utils import CheckpointManager


def _params(seed, n=3):
    rng = np.random.RandomState(seed)
    return {"w%d" % i: mx.nd.array(rng.rand(4, 4).astype(np.float32))
            for i in range(n)}


def test_ckpt_manager_roundtrip_and_keep(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for step in (10, 20, 30, 40):
        mgr.save(step, _params(step))
    assert mgr.steps() == [30, 40]            # keep=2 pruned the rest
    step, params, trainer, meta = mgr.restore()
    assert step == 40 and meta["step"] == 40
    want = _params(40)
    for k in want:
        np.testing.assert_array_equal(params[k].asnumpy(),
                                      want[k].asnumpy())
    # explicit older step still restorable
    s30, p30, _, _ = mgr.restore(step=30)
    np.testing.assert_array_equal(p30["w0"].asnumpy(),
                                  _params(30)["w0"].asnumpy())


def test_ckpt_manager_async_consistent_cut(tmp_path):
    """The device->host snapshot happens inside save(): mutating the
    params right after save() returns must not affect the checkpoint."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    params = _params(1)
    before = {k: v.asnumpy().copy() for k, v in params.items()}
    mgr.save(100, params)
    for k in params:                           # racing mutation
        params[k] += 1000.0
    mgr.wait()
    _, restored, _, _ = mgr.restore(100)
    for k in before:
        np.testing.assert_array_equal(restored[k].asnumpy(), before[k])


def test_ckpt_manager_ignores_partial(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(5, _params(5))
    # a crashed writer leaves a temp dir and a renamed-but-empty dir
    os.makedirs(str(tmp_path / "ckpt-00000009.tmp.1234"))
    os.makedirs(str(tmp_path / "ckpt-00000007"))   # no meta.json
    assert mgr.steps() == [5]
    assert mgr.latest_step() == 5
    step, _, _, _ = mgr.restore()
    assert step == 5


def test_ckpt_manager_resave_step_replaces_without_window(tmp_path):
    """Re-saving an existing step publishes the new content via
    rename-aside (old dir moved out of the way, new dir renamed in, old
    deleted) — never a delete-then-rename window with no checkpoint, and
    no stale aside dirs left behind."""
    from incubator_mxnet_tpu.utils import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(5, {"w": mx.nd.array(np.full((2,), 1.0, np.float32))})
    mgr.save(5, {"w": mx.nd.array(np.full((2,), 2.0, np.float32))})
    assert mgr.steps() == [5]
    _, params, _, _ = mgr.restore(5)
    np.testing.assert_array_equal(params["w"].asnumpy(),
                                  np.full((2,), 2.0, np.float32))
    leftovers = [e for e in os.listdir(str(tmp_path)) if ".old" in e]
    assert leftovers == []


def test_ckpt_manager_trainer_states_roundtrip(tmp_path):
    net = mx.gluon.nn.Dense(4, in_units=8, prefix="ck_")
    net.initialize(mx.init.Xavier())
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.array(np.random.rand(2, 8).astype(np.float32))
    from incubator_mxnet_tpu import autograd
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    tr.step(2)

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    params = {p.name: p.data() for p in net.collect_params().values()}
    mgr.save(1, params, trainer=tr, extra={"epoch": 3})
    step, restored, payload, meta = mgr.restore()
    assert meta["epoch"] == 3 and payload is not None

    # resume into a FRESH net+trainer: load the checkpointed params and
    # optimizer states, then take one identical step on both — equal
    # post-step params proves the momentum state actually round-tripped
    # (a fresh trainer without restore diverges, checked last)
    net2 = mx.gluon.nn.Dense(4, in_units=8, prefix="ck_")
    net2.initialize(mx.init.Xavier())
    for p in net2.collect_params().values():
        p.set_data(restored[p.name])
    tr2 = mx.gluon.Trainer(net2.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
    mgr.restore_trainer(tr2, payload)

    def one_step(n, t):
        with autograd.record():
            loss = (n(x) ** 2).mean()
        loss.backward()
        t.step(2)
        return {p.name: p.data().asnumpy()
                for p in n.collect_params().values()}

    after1 = one_step(net, tr)
    after2 = one_step(net2, tr2)
    for k in after1:
        np.testing.assert_allclose(after2[k], after1[k], rtol=1e-6)

    # control: WITHOUT restore the same step diverges (momentum at zero)
    net3 = mx.gluon.nn.Dense(4, in_units=8, prefix="ck_")
    net3.initialize(mx.init.Xavier())
    for p in net3.collect_params().values():
        p.set_data(restored[p.name])
    tr3 = mx.gluon.Trainer(net3.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
    after3 = one_step(net3, tr3)
    assert any(np.abs(after3[k] - after1[k]).max() > 1e-7 for k in after1)


def test_ckpt_manager_keep_zero_rejected(tmp_path):
    with pytest.raises(ValueError):
        CheckpointManager(str(tmp_path), keep=0)


def test_ckpt_manager_sigterm_final_save(tmp_path):
    """Preemption drill in a subprocess: SIGTERM triggers one final
    synchronous save (marked preempted) before the default handler kills
    the process; the parent then resumes from it."""
    script = textwrap.dedent("""
        import os, signal, sys, time
        import numpy as np
        import jax; jax.config.update("jax_platforms", "cpu")
        import incubator_mxnet_tpu as mx
        from incubator_mxnet_tpu.utils import CheckpointManager

        mgr = CheckpointManager(sys.argv[1], async_save=True)
        params = {"w": mx.nd.array(np.full((2, 2), 7.0, np.float32))}
        state = {"step": 0}
        mgr.install_preemption_handler(
            lambda: (state["step"], params, None, {"note": "drill"}))
        mgr.save(1, params)
        mgr.wait()
        state["step"] = 2
        params["w"] += 1.0
        print("READY", flush=True)
        time.sleep(30)
    """)
    proc = subprocess.Popen(
        [sys.executable, "-c", script, str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORM_NAME": "cpu",
             "PYTHONPATH": os.getcwd()})
    assert proc.stdout.readline().strip() == "READY", proc.stderr.read()
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=60)
    assert proc.returncode != 0                # died by signal, not exit 0

    mgr = CheckpointManager(str(tmp_path))
    step, params, _, meta = mgr.restore()
    assert step == 2 and meta["preempted"] is True and meta["note"] == "drill"
    np.testing.assert_array_equal(params["w"].asnumpy(),
                                  np.full((2, 2), 8.0, np.float32))


def test_sharded_trainer_checkpoint_resume(tmp_path):
    """Distributed checkpoint/resume: a zero1 ShardedTrainer's full state
    (params + dp-sharded adam slots + step) round-trips through
    CheckpointManager; the resumed trainer's loss trajectory continues
    EXACTLY as the uninterrupted run."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_mesh, ShardedTrainer

    def build():
        np.random.seed(21)
        net = gluon.nn.HybridSequential(prefix="sc_")
        with net.name_scope():
            net.add(gluon.nn.Dense(16, activation="relu", in_units=8,
                                   prefix="a_"))
            net.add(gluon.nn.Dense(4, in_units=16, prefix="b_"))
        net.initialize(mx.init.Xavier())
        return net

    def xent(out, label):
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.take_along_axis(
            logp, label.astype(jnp.int32)[:, None], axis=-1).mean()

    rng = np.random.RandomState(22)
    X = rng.rand(16, 8).astype(np.float32)
    Y = rng.randint(0, 4, (16,)).astype(np.float32)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])

    def mk():
        return ShardedTrainer(build(), xent, mesh, optimizer="adam",
                              optimizer_params={"learning_rate": 1e-2},
                              data_specs=P("dp"), label_spec=P("dp"),
                              zero1=True)

    # uninterrupted run: 6 steps
    ref = mk()
    ref_losses = [float(ref.step(X, Y)) for _ in range(6)]

    # interrupted run: 3 steps, checkpoint, fresh trainer, resume 3 more
    tr = mk()
    for _ in range(3):
        tr.step(X, Y)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, tr.state_dict())
    _, flat, _, _ = mgr.restore()

    tr2 = mk()
    tr2.load_state_dict(flat)
    resumed = [float(tr2.step(X, Y)) for _ in range(3)]
    np.testing.assert_allclose(resumed, ref_losses[3:], rtol=1e-5,
                               atol=1e-6)
    # optimizer slots really are dp-sharded after restore
    n_sh = 0
    for n, st in tr2._opt_state.items():
        if tr2._zero_axes.get(n) is None:
            continue
        n_sh += 1
        for s in st:
            assert "dp" in str(s.sharding.spec), (n, s.sharding)
    assert n_sh > 0


def test_ckpt_manager_restore_falls_back_past_corruption(tmp_path):
    """restore() with no explicit step skips an unreadable latest
    checkpoint (post-publish disk damage) and loads the previous
    retained step; an explicit step= never falls back."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(10, _params(10))
    mgr.save(20, _params(20))
    with open(os.path.join(str(tmp_path), "ckpt-%08d" % 20, "params"),
              "wb") as f:
        f.write(b"this is not an ndarray file")
    with pytest.warns(UserWarning, match="step 20 is unreadable"):
        step, params, _, _ = mgr.restore()
    assert step == 10
    np.testing.assert_array_equal(params["w0"].asnumpy(),
                                  _params(10)["w0"].asnumpy())
    # the damaged checkpoint stays damaged for direct addressing
    with pytest.raises(Exception):
        mgr.restore(step=20)


def test_ckpt_manager_restore_all_corrupt_raises_newest_error(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    for step in (1, 2):
        mgr.save(step, _params(step))
        with open(os.path.join(str(tmp_path), "ckpt-%08d" % step,
                               "params"), "wb") as f:
            f.write(b"garbage")
    with pytest.warns(UserWarning):
        with pytest.raises(Exception) as ei:
            mgr.restore()
    assert not isinstance(ei.value, FileNotFoundError)
