"""Sharding/parallelism tests on the 8-device virtual CPU mesh
(reference analogue: multi-device tests without a cluster, SURVEY §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon
from incubator_mxnet_tpu.parallel import (make_mesh, ShardedTrainer,
                                          ring_attention, local_attention,
                                          sharding_rules)
from incubator_mxnet_tpu.parallel.ring_attention import make_ring_attention


def test_make_mesh_infer():
    mesh = make_mesh({"dp": 2, "tp": -1})
    assert mesh.shape == {"dp": 2, "tp": 4}
    # smaller meshes take the leading devices; oversubscription errors
    assert make_mesh({"dp": 3}).shape == {"dp": 3}
    with pytest.raises(ValueError):
        make_mesh({"dp": 16})


def test_ring_attention_matches_local():
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, H, T, D = 2, 2, 16, 8
    np.random.seed(0)
    q = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))

    num, den, m = local_attention(q, k, v)
    ref = num / den

    fn = make_ring_attention(mesh, seq_axis="sp", causal=False)
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal():
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, H, T, D = 1, 1, 8, 4
    np.random.seed(1)
    q = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(np.random.rand(B, H, T, D).astype(np.float32))
    num, den, m = local_attention(q, k, v, causal=True)
    ref = num / den
    fn = make_ring_attention(mesh, seq_axis="sp", causal=True)
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def _make_mlp(seed=0):
    np.random.seed(seed)
    net = gluon.nn.HybridSequential(prefix="mlp_")
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _loss_fn(out, label):
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None],
                                axis=-1).mean()


def test_sharded_trainer_dp_matches_single_device():
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)

    # single device
    net1 = _make_mlp(0)
    mesh1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh1, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    # 4-way data parallel with identical init
    net2 = _make_mlp(0)
    mesh2 = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr2 = ShardedTrainer(net2, _loss_fn, mesh2, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})

    for _ in range(3):
        l1 = tr1.step(nd.array(X), nd.array(y))
        l2 = tr2.step(nd.array(X), nd.array(y))
    np.testing.assert_allclose(float(jax.device_get(l1)),
                               float(jax.device_get(l2)), rtol=1e-4)
    p1 = tr1.param_values
    p2 = tr2.param_values
    for k in p1:
        np.testing.assert_allclose(np.asarray(jax.device_get(p1[k])),
                                   np.asarray(jax.device_get(p2[k])),
                                   rtol=2e-4, atol=1e-5)


def test_sharded_trainer_tp_matches_replicated():
    np.random.seed(0)
    X = np.random.rand(8, 8).astype(np.float32)
    y = np.random.randint(0, 4, (8,)).astype(np.int32)
    net1 = _make_mlp(0)
    mesh1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh1, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    net2 = _make_mlp(0)
    mesh2 = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    rules = [(r"mlp_dense0_weight$", P("tp", None)),
             (r"mlp_dense0_bias$", P("tp")),
             (r"mlp_dense1_weight$", P(None, "tp"))]
    tr2 = ShardedTrainer(net2, _loss_fn, mesh2, rules=rules, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    for _ in range(2):
        l1 = tr2.step(nd.array(X), nd.array(y))
        l0 = tr1.step(nd.array(X), nd.array(y))
    np.testing.assert_allclose(float(jax.device_get(l0)),
                               float(jax.device_get(l1)), rtol=1e-4)


def test_sharded_trainer_sync_to_block():
    net = _make_mlp(0)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tr = ShardedTrainer(net, _loss_fn, mesh, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.5})
    before = net.collect_params()["mlp_dense0_weight"] \
        .data().asnumpy().copy()
    X = np.random.rand(4, 8).astype(np.float32)
    y = np.zeros(4, np.int32)
    tr.step(nd.array(X), nd.array(y))
    tr.sync_to_block()
    after = net.collect_params()["mlp_dense0_weight"] \
        .data().asnumpy()
    assert not np.allclose(before, after)


def test_collectives_in_shard_map():
    from incubator_mxnet_tpu.parallel import collectives as C
    import functools
    mesh = make_mesh({"x": 8})

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                       check_vma=False)
    def f(v):
        s = C.all_reduce(v, "x")
        return v * 0 + s

    x = jnp.arange(8.0)
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_sharding_rules_matcher():
    match = sharding_rules([(r"weight$", P("tp", None))])
    assert match("layer0_weight") == P("tp", None)
    assert match("layer0_bias") == P()


def test_ring_attention_differentiable_on_mesh():
    """Gradients flow through the ring (scan + ppermute) — the long-context
    training path, on a 4-device slice of the virtual CPU mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from incubator_mxnet_tpu.parallel.ring_attention import make_ring_attention

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("sp",))
    B, H, T, D = 1, 2, 64 * 4, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    fn = make_ring_attention(mesh, seq_axis="sp", causal=True)

    def loss(q):
        return (fn(q, q, q) ** 2).sum()

    g = jax.jit(jax.grad(loss))(q)
    assert g.shape == q.shape

    def ref_loss(q):
        s = jnp.einsum("bhtd,bhsd->bhts", q, q) / (D ** 0.5)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
        return ((jax.nn.softmax(s, -1) @ q) ** 2).sum()

    gr = jax.grad(ref_loss)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=2e-3,
                               atol=2e-4)


def test_step_scan_matches_step():
    """K scanned steps == K individual steps (same math, one program)."""
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    net1, net2 = _make_mlp(0), _make_mlp(0)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9})
    tr2 = ShardedTrainer(net2, _loss_fn, mesh, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9})
    for _ in range(4):
        l1 = tr1.step(nd.array(X), nd.array(y),
                      key=jax.random.PRNGKey(7))
    losses = tr2.step_scan(nd.array(X), nd.array(y), 4,
                           key=jax.random.PRNGKey(7),
                           per_step_batches=False)
    assert losses.shape == (4,)
    p1, p2 = tr1.param_values, tr2.param_values
    for k in p1:
        np.testing.assert_allclose(np.asarray(jax.device_get(p1[k])),
                                   np.asarray(jax.device_get(p2[k])),
                                   rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("call", ["step", "step_guarded", "step_scan"])
def test_a_step_under_a_parent_span_leaves_its_phases(call):
    """trainer.step with the batch placement and the dispatch below it,
    each with its parent's id; with nothing listening, no record at all."""
    from incubator_mxnet_tpu.telemetry import tracing
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    tr = ShardedTrainer(_make_mlp(0), _loss_fn,
                        make_mesh({"dp": 1}, devices=jax.devices()[:1]),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1})

    def run():
        if call == "step_scan":
            return tr.step_scan(nd.array(X), nd.array(y), 2,
                                per_step_batches=False)
        return getattr(tr, call)(nd.array(X), nd.array(y))

    run()                               # compiles; nothing listens
    tracing.clear_spans()
    run()
    assert tracing.recent_spans() == []
    with tracing.Span("test.parent") as parent:
        run()
    recs = {r["name"]: r for r in tracing.recent_spans()}
    assert list(recs) == ["trainer.prep_batch", "trainer.dispatch",
                          "trainer.step", "test.parent"]
    step = recs["trainer.step"]
    assert step["parent_id"] == parent.span_id and step["rows"] == 16
    for name in ("trainer.prep_batch", "trainer.dispatch"):
        assert recs[name]["parent_id"] == step["span_id"]
    assert recs["trainer.prep_batch"]["dur_us"] \
        + recs["trainer.dispatch"]["dur_us"] <= step["dur_us"]


def test_step_scan_per_step_batches():
    """A leading steps-axis on data/label feeds a fresh batch per step."""
    np.random.seed(0)
    K = 3
    Xs = np.random.rand(K, 16, 8).astype(np.float32)
    ys = np.random.randint(0, 4, (K, 16)).astype(np.int32)
    net1, net2 = _make_mlp(0), _make_mlp(0)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    tr2 = ShardedTrainer(net2, _loss_fn, mesh, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    for i in range(K):
        tr1.step(nd.array(Xs[i]), nd.array(ys[i]),
                 key=jax.random.PRNGKey(3))
    tr2.step_scan(nd.array(Xs), nd.array(ys), K, key=jax.random.PRNGKey(3),
                  per_step_batches=True)
    p1, p2 = tr1.param_values, tr2.param_values
    for k in p1:
        np.testing.assert_allclose(np.asarray(jax.device_get(p1[k])),
                                   np.asarray(jax.device_get(p2[k])),
                                   rtol=2e-4, atol=1e-5)


def test_step_scan_per_step_batches_dp_mesh():
    """Per-step batches + dp sharding: the steps axis must stay unsharded
    while the batch axis shards over dp."""
    np.random.seed(0)
    K = 2
    Xs = np.random.rand(K, 16, 8).astype(np.float32)
    ys = np.random.randint(0, 4, (K, 16)).astype(np.int32)
    net1, net2 = _make_mlp(0), _make_mlp(0)
    mesh1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh1, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    mesh4 = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr2 = ShardedTrainer(net2, _loss_fn, mesh4, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1})
    for i in range(K):
        tr1.step(nd.array(Xs[i]), nd.array(ys[i]))
    tr2.step_scan(nd.array(Xs), nd.array(ys), K, per_step_batches=True)
    p1, p2 = tr1.param_values, tr2.param_values
    for k in p1:
        np.testing.assert_allclose(np.asarray(jax.device_get(p1[k])),
                                   np.asarray(jax.device_get(p2[k])),
                                   rtol=2e-4, atol=1e-5)


from incubator_mxnet_tpu.parallel.collectives import \
    collective_counts as _collective_counts


def test_dp_step_inserts_grad_allreduce():
    """HLO audit: a pure-dp step must contain gradient all-reduce(s) over
    the dp axis — and a single-device step must contain none."""
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)

    net1 = _make_mlp(0)
    mesh1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh1)
    hlo1 = tr1.lowered(nd.array(X), nd.array(y)).compile().as_text()
    c1 = _collective_counts(hlo1)
    assert c1["all-reduce"] == 0, c1

    net2 = _make_mlp(0)
    mesh4 = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr2 = ShardedTrainer(net2, _loss_fn, mesh4)
    hlo4 = tr2.lowered(nd.array(X), nd.array(y)).compile().as_text()
    c4 = _collective_counts(hlo4)
    # GSPMD combines per-parameter psums; expect >=1 and a small combined
    # count (4 diff params + loss -> must not explode into per-op chatter)
    assert 1 <= c4["all-reduce"] <= 6, c4
    assert c4["all-to-all"] == 0 and c4["collective-permute"] == 0, c4


def test_tp_forward_single_allreduce():
    """Megatron placement: column-parallel then row-parallel Dense needs
    exactly ONE all-reduce in the forward pass."""
    np.random.seed(0)
    net = gluon.nn.HybridSequential(prefix="tpmlp_")
    with net.name_scope():
        net.add(gluon.nn.Dense(32, activation="relu", in_units=16,
                               prefix="col_"),
                gluon.nn.Dense(16, in_units=32, prefix="row_"))
    net.initialize(mx.init.Xavier())
    mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
    from incubator_mxnet_tpu.gluon.block import _TraceCtx, _trace_state
    from jax.sharding import NamedSharding

    rules = sharding_rules([
        (r"col_weight$", P("tp", None)),     # (out, in): shard out
        (r"col_bias$", P("tp")),
        (r"row_weight$", P(None, "tp")),     # contract over sharded in
    ])
    params = {p.name: p for p in net.collect_params().values()}
    pv = {n: jax.device_put(p._data._data, NamedSharding(mesh, rules(n)))
          for n, p in params.items()}

    def fwd(pv, x):
        ctx = _TraceCtx(pv, jax.random.PRNGKey(0), training=False)
        prev = getattr(_trace_state, "ctx", None)
        _trace_state.ctx = ctx
        try:
            return net.forward(x)
        finally:
            _trace_state.ctx = prev

    x = jax.device_put(jnp.asarray(np.random.rand(8, 16), jnp.float32),
                       NamedSharding(mesh, P()))
    hlo = jax.jit(fwd).lower(pv, x).compile().as_text()
    c = _collective_counts(hlo)
    assert c["all-reduce"] == 1, c
    assert c["all-gather"] == 0, c


# ---------------------------------------------------------------------------
# ZeRO-1 (reduce-scatter sharded optimizer) + gradient accumulation
# ---------------------------------------------------------------------------

def test_zero1_emits_reduce_scatter():
    """HLO audit: zero1=True must lower the dp gradient reduction to
    reduce-scatter (+ param all-gather), replacing plain all-reduce."""
    from incubator_mxnet_tpu.parallel.collectives import \
        collective_counts as cc
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    net = _make_mlp(0)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr = ShardedTrainer(net, _loss_fn, mesh, optimizer="adam",
                        optimizer_params={"learning_rate": 0.01},
                        zero1=True)
    hlo = tr.lowered(nd.array(X), nd.array(y)).compile().as_text()
    c = cc(hlo)
    assert c["reduce-scatter"] >= 1, c
    assert c["all-gather"] >= 1, c


def test_zero1_matches_unsharded_adam():
    """ZeRO-1 is a memory layout, not an algorithm change: training with
    dp-sharded optimizer state must produce the same weights."""
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    tr_ref = ShardedTrainer(_make_mlp(0), _loss_fn, mesh, optimizer="adam",
                            optimizer_params={"learning_rate": 0.01})
    tr_z = ShardedTrainer(_make_mlp(0), _loss_fn, mesh, optimizer="adam",
                          optimizer_params={"learning_rate": 0.01},
                          zero1=True)
    for _ in range(5):
        l_ref = tr_ref.step(nd.array(X), nd.array(y))
        l_z = tr_z.step(nd.array(X), nd.array(y))
    np.testing.assert_allclose(float(jax.device_get(l_ref)),
                               float(jax.device_get(l_z)), rtol=1e-5)
    p_ref, p_z = tr_ref.param_values, tr_z.param_values
    for k in p_ref:
        np.testing.assert_allclose(np.asarray(jax.device_get(p_ref[k])),
                                   np.asarray(jax.device_get(p_z[k])),
                                   rtol=2e-5, atol=1e-6)
    # optimizer state really is dp-sharded
    for n, st in tr_z._opt_state.items():
        for s in st:
            spec = s.sharding.spec
            assert "dp" in tuple(spec), (n, spec)


def test_grad_accum_matches_full_batch():
    """grad_accum=4 over a 16-batch == one step on the full 16-batch
    (mean-of-micro-means equals the full-batch mean for equal slices)."""
    np.random.seed(0)
    X = np.random.rand(16, 8).astype(np.float32)
    y = np.random.randint(0, 4, (16,)).astype(np.int32)
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tr_full = ShardedTrainer(_make_mlp(0), _loss_fn, mesh, optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1})
    tr_acc = ShardedTrainer(_make_mlp(0), _loss_fn, mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1},
                            grad_accum=4)
    for _ in range(3):
        l_full = tr_full.step(nd.array(X), nd.array(y))
        l_acc = tr_acc.step(nd.array(X), nd.array(y))
    np.testing.assert_allclose(float(jax.device_get(l_full)),
                               float(jax.device_get(l_acc)), rtol=1e-5)
    p_full, p_acc = tr_full.param_values, tr_acc.param_values
    for k in p_full:
        np.testing.assert_allclose(np.asarray(jax.device_get(p_full[k])),
                                   np.asarray(jax.device_get(p_acc[k])),
                                   rtol=2e-5, atol=1e-6)


def test_multidevice_convergence_lenet():
    """VERDICT r2 #2: train LeNet 50 steps on the 8-device mesh (with
    zero1 + grad accumulation) vs 1 device — same final weights."""
    def make_lenet(seed):
        np.random.seed(seed)
        return mx.models.lenet5()

    np.random.seed(0)
    X = np.random.rand(32, 1, 28, 28).astype(np.float32)
    y = np.random.randint(0, 10, (32,)).astype(np.int32)

    net1 = make_lenet(1)
    net1.initialize(mx.init.Xavier())
    net1(nd.array(X[:2]))   # materialize deferred shapes
    mesh1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr1 = ShardedTrainer(net1, _loss_fn, mesh1, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05,
                                           "momentum": 0.9})
    net8 = make_lenet(1)
    net8.initialize(mx.init.Xavier())
    net8(nd.array(X[:2]))
    mesh8 = make_mesh({"dp": 8}, devices=jax.devices()[:8])
    tr8 = ShardedTrainer(net8, _loss_fn, mesh8, optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05,
                                           "momentum": 0.9},
                         zero1=True, grad_accum=2)
    losses1, losses8 = [], []
    for _ in range(50):
        losses1.append(float(jax.device_get(tr1.step(nd.array(X),
                                                     nd.array(y)))))
        losses8.append(float(jax.device_get(tr8.step(nd.array(X),
                                                     nd.array(y)))))
    # training converged and both meshes took the same trajectory
    assert losses1[-1] < losses1[0] * 0.5, losses1[::10]
    np.testing.assert_allclose(losses1[-1], losses8[-1], rtol=5e-3)
    p1, p8 = tr1.param_values, tr8.param_values
    # prefixes auto-number per-net (hybridsequential0_ vs 1_): match by the
    # suffix after the net prefix
    def suffix(k):
        return k.split("_", 1)[1]
    m8 = {suffix(k): v for k, v in p8.items()}
    for k in p1:
        np.testing.assert_allclose(np.asarray(jax.device_get(p1[k])),
                                   np.asarray(jax.device_get(m8[suffix(k)])),
                                   rtol=5e-3, atol=5e-4)


# ---------------------------------------------------------------------------
# ring + flash composition (VERDICT r3 #4: flash inner loop, ring outer loop)
# ---------------------------------------------------------------------------

def _rand_qkv(B, H, T, D, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.rand(B, H, T, D).astype(np.float32))
                 for _ in range(3))


def test_ring_flash_matches_dense_ring():
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, H, T, D = 2, 2, 64, 8          # T_local = 16: flash tiling contract
    q, k, v = _rand_qkv(B, H, T, D, seed=3)
    dense = make_ring_attention(mesh, seq_axis="sp", impl="dense")(q, k, v)
    flash = make_ring_attention(mesh, seq_axis="sp", impl="flash",
                                interpret=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)
    # and both match single-device attention
    num, den, _ = local_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(num / den),
                               rtol=2e-4, atol=2e-5)


def test_ring_flash_causal_matches_dense_ring():
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, H, T, D = 1, 2, 64, 8
    q, k, v = _rand_qkv(B, H, T, D, seed=4)
    flash = make_ring_attention(mesh, seq_axis="sp", causal=True,
                                impl="flash", interpret=True)(q, k, v)
    num, den, _ = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(num / den),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gradients_match_dense(causal):
    """The ring-flash custom VJP (dK/dV accumulators riding the ring) must
    produce the same gradients as differentiating the einsum ring."""
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, H, T, D = 1, 2, 64, 8
    q, k, v = _rand_qkv(B, H, T, D, seed=5)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    dense_fn = make_ring_attention(mesh, seq_axis="sp", causal=causal,
                                   impl="dense")
    flash_fn = make_ring_attention(mesh, seq_axis="sp", causal=causal,
                                   impl="flash", interpret=True)
    gd = jax.grad(loss(dense_fn), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(flash_fn), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s mismatch" % name)


def test_sp_axis_routes_through_ring_attention(monkeypatch):
    """VERDICT r4 #3: with sp>1 in the trainer mesh, BERT attention runs
    RING attention (ppermute KV rotation inside shard_map) instead of a
    GSPMD all-gather — asserted on the compiled HLO — and the one-step
    loss matches the all-gather formulation."""
    import os
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.bert import BERTModel
    from incubator_mxnet_tpu.parallel import make_mesh, ShardedTrainer
    from incubator_mxnet_tpu.parallel.collectives import collective_counts

    vocab, units, heads = 97, 32, 4
    np.random.seed(0)
    model = BERTModel(vocab_size=vocab, units=units, hidden_size=2 * units,
                      num_layers=2, num_heads=heads, max_length=64,
                      dropout=0.0, prefix="spbert_")
    model.initialize(mx.init.Normal(0.02))
    tokens = mx.nd.array(np.random.randint(0, vocab, (4, 32)), dtype="int32")
    labels = mx.nd.array(np.random.randint(0, vocab, (4, 32)), dtype="int32")
    model(tokens)

    def loss_fn(outs, labels):
        seq, pooled = outs
        logits = seq @ jnp.ones((units, vocab), seq.dtype) * 0.0 + seq.sum()
        # scalar objective through the encoder is enough for parity
        return logits.mean() * 0 + (seq * seq).mean()

    mesh = make_mesh({"dp": 2, "sp": 2}, devices=jax.devices()[:4])

    def build():
        return ShardedTrainer(model, loss_fn, mesh,
                              optimizer="sgd",
                              optimizer_params={"learning_rate": 0.0},
                              data_specs=P("dp", "sp"),
                              label_spec=P("dp", "sp"))

    monkeypatch.delenv("MXTPU_DISABLE_RING", raising=False)
    counts_ring, loss_ring = build().audit_step(tokens, labels)
    assert counts_ring["collective-permute"] >= 1, counts_ring
    monkeypatch.setenv("MXTPU_DISABLE_RING", "1")
    counts_ag, loss_ag = build().audit_step(tokens, labels)
    assert counts_ag["collective-permute"] == 0, counts_ag
    assert abs(loss_ring - loss_ag) < 1e-5 * max(1.0, abs(loss_ag)), \
        (loss_ring, loss_ag)
