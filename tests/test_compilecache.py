"""compilecache/ tests: content keying, corruption fallback, LRU capping,
concurrent writers, the cached-compile zero-event warm path, the
MXTPU_COSTS single-compile pin, the checkpoint ``executables`` section,
and the two-process warm drills (trainer and serving) that pin the PR's
invariant: a warm replica reaches its first step/reply with ZERO
backend_compile events."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, telemetry
from incubator_mxnet_tpu.compilecache import aot
from incubator_mxnet_tpu.compilecache import store as ccstore
from incubator_mxnet_tpu.compilecache import warmup as ccwarmup
from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh
from incubator_mxnet_tpu.telemetry import catalog as cat
from incubator_mxnet_tpu.telemetry import costs
from incubator_mxnet_tpu.utils.checkpoint import CheckpointManager


@pytest.fixture
def tele():
    telemetry.enable()
    cat.install_jax_compile_hook()
    yield cat
    telemetry.disable()


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "ccache")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", d)
    return d


# ------------------------------------------------------------------ keying
def test_compile_key_is_deterministic_and_sensitive():
    l1 = jax.jit(lambda x: x * 2).lower(jnp.ones((4,)))
    l2 = jax.jit(lambda x: x * 3).lower(jnp.ones((4,)))
    k1 = aot.compile_key(l1)
    assert k1 == aot.compile_key(l1)                   # deterministic
    assert k1 != aot.compile_key(l2)                   # program text
    assert k1 != aot.compile_key(l1, donation=(0,))    # donation signature
    assert k1 != aot.compile_key(l1, extra=("ns2",))   # caller namespace


def test_compile_key_folds_in_jax_version(monkeypatch):
    lowered = jax.jit(lambda x: x + 1).lower(jnp.ones((2,)))
    k = aot.compile_key(lowered)
    monkeypatch.setattr(jax, "__version__", "0.0.0-somethingelse")
    assert aot.compile_key(lowered) != k


# ------------------------------------------------------------------- store
def test_store_roundtrip_and_hit_miss_counters(cache_dir, tele):
    st = ccstore.default_store()
    assert st is not None and st.directory == cache_dir
    h0 = cat.compile_cache_hits.value(where="t")
    m0 = cat.compile_cache_misses.value(where="t")
    s0 = cat.compile_cache_seconds_saved.value()
    assert st.get("deadbeef", where="t") is None       # cold miss
    st.put("deadbeef", b"PAYLOAD" * 10, compile_seconds=2.5, name="p")
    got = st.get("deadbeef", where="t")
    assert got is not None
    payload, header = got
    assert payload == b"PAYLOAD" * 10
    assert header["name"] == "p"
    assert cat.compile_cache_hits.value(where="t") == h0 + 1
    assert cat.compile_cache_misses.value(where="t") == m0 + 1
    assert cat.compile_cache_seconds_saved.value() == pytest.approx(
        s0 + 2.5)


def test_statusz_entry_reports_stats(cache_dir):
    st = ccstore.default_store()
    st.put("aa", b"x" * 100, name="a")
    ent = ccstore.statusz_entry()
    assert ent["enabled"] is True
    assert ent["entries"] == 1 and ent["bytes"] > 100


@pytest.mark.parametrize("damage", ["truncate", "bitflip", "version",
                                    "garbage"])
def test_corrupt_entry_falls_back_with_warning(cache_dir, tele, caplog,
                                               damage):
    """Any damaged entry — truncated payload, flipped bit, wrong entry
    version, unparsable header — is logged, quarantined (removed), and
    reported as a miss so the caller recompiles. Never an exception."""
    st = ccstore.default_store()
    st.put("k1", b"A" * 64, name="victim")
    path = st._path("k1")
    raw = open(path, "rb").read()
    if damage == "truncate":
        blob = raw[:-10]
    elif damage == "bitflip":
        blob = raw[:-5] + bytes([raw[-5] ^ 0x40]) + raw[-4:]
    elif damage == "version":
        hdr, _, payload = raw.partition(b"\n")
        h = json.loads(hdr)
        h["v"] = 999
        blob = json.dumps(h).encode() + b"\n" + payload
    else:
        blob = b"not json at all\njunk"
    with open(path, "wb") as f:
        f.write(blob)
    e0 = cat.compile_cache_errors.value(kind="corrupt")
    with caplog.at_level("WARNING",
                         logger="incubator_mxnet_tpu.compilecache.store"):
        assert st.get("k1", where="t") is None
    assert cat.compile_cache_errors.value(kind="corrupt") == e0 + 1
    assert not os.path.exists(path)                    # quarantined
    assert any("dropping" in r.getMessage() for r in caplog.records)


def test_lru_eviction_under_cap(tmp_path, tele):
    # cap = 2500 bytes; each entry is 1000b payload + ~110b header, so
    # two entries fit and the third forces one oldest-mtime eviction
    st = ccstore.CompileCacheStore(str(tmp_path / "c"), cap_mb=0.0025)
    ev0 = cat.compile_cache_evictions.value()
    st.put("old", b"x" * 1000, name="old")
    os.utime(st._path("old"), (1_000, 1_000))          # oldest mtime
    st.put("mid", b"y" * 1000, name="mid")
    os.utime(st._path("mid"), (2_000, 2_000))
    st.put("new", b"z" * 1000, name="new")             # cap enforcement
    assert not os.path.exists(st._path("old"))         # LRU victim
    assert os.path.exists(st._path("mid"))
    assert os.path.exists(st._path("new"))
    assert cat.compile_cache_evictions.value() == ev0 + 1
    assert cat.compile_cache_entries.value() == 2


def test_hit_refreshes_lru_recency(tmp_path):
    st = ccstore.CompileCacheStore(str(tmp_path / "c"), cap_mb=0.0025)
    st.put("a", b"x" * 1000)
    os.utime(st._path("a"), (1_000, 1_000))
    st.put("b", b"y" * 1000)
    os.utime(st._path("b"), (2_000, 2_000))
    assert st.get("a") is not None                     # bumps a's mtime
    st.put("c", b"z" * 1000)                           # evicts b, not a
    assert os.path.exists(st._path("a"))
    assert not os.path.exists(st._path("b"))


def test_concurrent_writers_never_corrupt(tmp_path):
    """Racing writers (same and different keys) always leave every
    published entry complete and readable — the atomic rename-aside
    publish discipline."""
    st = ccstore.CompileCacheStore(str(tmp_path / "c"))
    errors = []

    def writer(seed):
        rng = np.random.RandomState(seed)
        for i in range(25):
            key = "shared" if i % 3 == 0 else "k%d_%d" % (seed, i)
            payload = bytes(rng.randint(0, 256, 300, dtype=np.uint8))
            try:
                st.put(key, payload, name=key)
                got = st.get(key)
                # a racing writer may have replaced "shared" — but the
                # entry must ALWAYS be complete and self-consistent
                assert got is not None
            except Exception as e:  # noqa: BLE001 — collecting for assert
                errors.append(e)

    threads = [threading.Thread(target=writer, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    for path, _sz, _mt in st._entries():
        key = os.path.basename(path)[:-len(".mxc")]
        assert st.get(key) is not None


def test_cache_off_is_none_store(monkeypatch):
    monkeypatch.delenv("MXTPU_COMPILE_CACHE_DIR", raising=False)
    assert ccstore.enabled() is False
    assert ccstore.default_store() is None
    assert ccstore.statusz_entry() == {"enabled": False}


# --------------------------------------------------------- cached_compile
def test_cached_compile_hit_is_zero_compile_events(cache_dir, tele):
    def f(x):
        return (x * 2 + 1).sum()

    x = jnp.arange(8.0)                 # input creation compiles: outside
    c1 = aot.cached_compile(jax.jit(f).lower(jnp.ones((8,))), name="t.f")
    want = float(c1(x))
    base = cat.compile_events()
    c2 = aot.cached_compile(jax.jit(f).lower(jnp.ones((8,))), name="t.f")
    assert cat.compile_events() == base     # hit: deserialized, 0 compiles
    assert float(c2(x)) == want
    h = cat.compile_cache_hits.value(where="other")
    assert h >= 1


@pytest.mark.parametrize("over", ["one_device", "mesh"])
def test_blob_loads_onto_the_devices_it_was_compiled_for(over):
    """The blob names its devices: a one-device program is not spread over
    all eight local devices, and a mesh program comes back on ITS four
    (here not the first four, and not in id order)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    d = jax.devices()
    if over == "mesh":
        devs = [d[7], d[5], d[6], d[4]]
        mesh = jax.sharding.Mesh(np.array(devs).reshape(2, 2), ("a", "b"))
        x = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                           NamedSharding(mesh, P("a", "b")))
    else:
        devs = [d[3]]
        x = jax.device_put(jnp.arange(16.0).reshape(4, 4), d[3])
    compiled = jax.jit(lambda a: (a + 1).sum(0)).lower(x).compile()
    loaded = aot.deserialize_compiled(aot.serialize_compiled(compiled))
    assert loaded.runtime_executable().local_devices() == devs
    np.testing.assert_array_equal(np.asarray(loaded(x)),
                                  np.asarray(compiled(x)))


def test_cached_compile_deserialize_failure_recompiles(cache_dir, tele):
    lowered = jax.jit(lambda x: x - 5).lower(jnp.ones((4,)))
    aot.cached_compile(lowered, name="t.g")
    st = ccstore.default_store()
    [(path, _s, _m)] = st._entries()
    # poison the PAYLOAD with valid framing: header says this pickle is
    # fine, but deserialize_and_load cannot load it
    bad = b"\x80\x04N."                      # pickle of None
    import hashlib
    hdr = {"v": ccstore.ENTRY_VERSION,
           "sha256": hashlib.sha256(bad).hexdigest(), "size": len(bad),
           "compile_seconds": 0.0, "name": "t.g"}
    with open(path, "wb") as f:
        f.write(json.dumps(hdr).encode() + b"\n" + bad)
    e0 = cat.compile_cache_errors.value(kind="deserialize")
    compiled = aot.cached_compile(
        jax.jit(lambda x: x - 5).lower(jnp.ones((4,))), name="t.g")
    assert float(compiled(jnp.full((4,), 7.0)).sum()) == pytest.approx(8.0)
    assert cat.compile_cache_errors.value(kind="deserialize") == e0 + 1


def test_compiling_context_labels_events(tele):
    x = jnp.ones((3,)) * 2.0            # input creation outside the region
    base = cat.compile_events(where="warmup")
    with cat.compiling("warmup"):
        jax.jit(lambda v: v * 17.3 + 0.21)(x)
    assert cat.compile_events(where="warmup") == base + 1


def test_an_event_outside_any_region_counts_under_other(tele):
    # (the deprecated trainer_jit_* aliases this test read are gone)
    x = jnp.ones((3,)) * 3.0
    old = cat.compile_events(where="other")
    new = cat.compile_events()
    jax.jit(lambda v: v * 31.7 - 0.77)(x)
    assert cat.compile_events(where="other") == old + 1
    assert cat.compile_events() == new + 1
    assert not hasattr(cat, "trainer_jit_compiles")


# ------------------------------------------------------- warmup env knobs
def test_warmup_env_parsing(monkeypatch):
    monkeypatch.delenv("MXTPU_WARMUP_ROWS", raising=False)
    assert ccwarmup.warmup_rows() == [1, 8]
    monkeypatch.setenv("MXTPU_WARMUP_ROWS", "4, 2;4")
    assert ccwarmup.warmup_rows() == [2, 4]
    monkeypatch.setenv("MXTPU_WARMUP_BUCKETS", "64,32")
    assert ccwarmup.warmup_buckets() == [32, 64]


# -------------------------------------------------- checkpoint executables
def test_checkpoint_executables_roundtrip_and_corrupt_skip(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=2, async_save=False)
    params = {"w": np.ones((2, 2), np.float32)}
    mgr.save(1, params, executables={"step": b"AAAA", "scan/1": b"BBBBBB"})
    assert mgr.load_executables() == {"step": b"AAAA", "scan/1": b"BBBBBB"}
    # corrupt one blob: skipped with a warning, the other survives
    meta = json.load(open(os.path.join(mgr._path(1), "meta.json")))
    fname = meta["executables"]["step"]["file"]
    with open(os.path.join(mgr._path(1), "executables", fname), "wb") as f:
        f.write(b"AAXA")
    with pytest.warns(UserWarning, match="corrupt"):
        exes = mgr.load_executables(1)
    assert exes == {"scan/1": b"BBBBBB"}
    # checkpoints without the section read as empty
    mgr.save(2, params)
    assert mgr.load_executables(2) == {}


# ------------------------------------------------------------- trainer AOT
def _mlp(seed=0):
    np.random.seed(seed)
    net = gluon.nn.HybridSequential(prefix="cc_mlp_")
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def _loss_fn(out, label):
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None],
                                axis=-1).mean()


def _trainer(seed=0):
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    return ShardedTrainer(_mlp(seed), _loss_fn, mesh, optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1})


def test_trainer_aot_step_matches_plain(cache_dir, tele, monkeypatch):
    X = np.random.RandomState(0).rand(8, 8).astype(np.float32)
    y = (np.arange(8) % 4).astype(np.int32)
    key = jax.random.PRNGKey(3)
    tr_aot = _trainer(0)
    l_aot = float(jax.device_get(tr_aot.step(nd.array(X), nd.array(y),
                                             key=key)))
    monkeypatch.delenv("MXTPU_COMPILE_CACHE_DIR")
    tr_plain = _trainer(0)
    l_plain = float(jax.device_get(tr_plain.step(nd.array(X), nd.array(y),
                                                 key=key)))
    assert l_aot == pytest.approx(l_plain, rel=1e-6)


def test_trainer_costs_capture_single_compile(tele, monkeypatch):
    """Satellite pin: MXTPU_COSTS=1 captures the cost model off the SAME
    executable the step runs — exactly ONE where=trainer compile for the
    first step, not the historical double compile."""
    monkeypatch.delenv("MXTPU_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXTPU_COSTS", "1")
    costs.reset()
    try:
        X = np.random.RandomState(0).rand(8, 8).astype(np.float32)
        y = (np.arange(8) % 4).astype(np.int32)
        key = jax.random.PRNGKey(0)
        tr = _trainer(0)
        data, label = nd.array(X), nd.array(y)
        base = cat.compile_events(where="trainer")
        tr.step(data, label, key=key)
        assert cat.compile_events(where="trainer") == base + 1
        assert costs.captured("trainer.step") is not None
    finally:
        costs.reset()


def test_trainer_export_import_blob_roundtrips(cache_dir, tele):
    """export_executables must ship a blob that a THIRD consumer can
    still deserialize — including when this trainer's own executable
    came from a cache hit (a deserialized executable cannot be
    re-serialized; the original blob must be reused)."""
    X = np.random.RandomState(0).rand(8, 8).astype(np.float32)
    y = (np.arange(8) % 4).astype(np.int32)
    key = jax.random.PRNGKey(1)
    tr1 = _trainer(0)
    tr1.step(nd.array(X), nd.array(y), key=key)        # miss: publishes
    tr2 = _trainer(0)
    tr2.step(nd.array(X), nd.array(y), key=key)        # hit: deserialized
    blobs = tr2.export_executables()
    assert "step" in blobs
    aot.deserialize_compiled(blobs["step"])            # still loadable


_WARM_TRAINER_CHILD = r"""
import json, os, sys
import numpy as np
import jax
sys.path.insert(0, sys.argv[3])
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, telemetry
from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh
from incubator_mxnet_tpu.telemetry import catalog as cat
from incubator_mxnet_tpu.utils.checkpoint import CheckpointManager
import jax.numpy as jnp

telemetry.enable()
cat.install_jax_compile_hook()
np.random.seed(0)
net = gluon.nn.HybridSequential(prefix="cc_mlp_")
with net.name_scope():
    net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
            gluon.nn.Dense(4, in_units=16))
net.initialize(mx.init.Xavier())

def loss_fn(out, label):
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None],
                                axis=-1).mean()

mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
tr = ShardedTrainer(net, loss_fn, mesh, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1})
rng = np.random.RandomState(0)
X = rng.rand(8, 8).astype(np.float32)
y = (np.arange(8) % 4).astype(np.int32)
key = jax.random.PRNGKey(7)            # key creation compiles: outside
data, label = nd.array(X), nd.array(y)
mgr = CheckpointManager(sys.argv[1], keep=2, async_save=False)
blobs = mgr.load_executables()
assert blobs, "warm child found no executables in the checkpoint"
base = cat.compile_events()
tr.load_executables(blobs)
loss = float(jax.device_get(tr.step(data, label, key=key)))
events = cat.compile_events() - base
print(json.dumps({"tag": "warm_child", "events": events, "loss": loss}))
"""


def test_warm_trainer_two_process_drill(tmp_path, tele, monkeypatch):
    """THE invariant: a restarted trainer replica that imports its step
    executable from a checkpoint reaches its first step with ZERO
    backend_compile events, and computes the identical loss."""
    ckpt = str(tmp_path / "ck")
    # phase 1 ("previous life"): compile, step, checkpoint executables.
    # No compile cache — the executables section alone must carry it.
    monkeypatch.delenv("MXTPU_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXTPU_COSTS", "1")   # engages the trainer AOT path
    tr = _trainer(0)
    rng = np.random.RandomState(0)
    X = rng.rand(8, 8).astype(np.float32)
    y = (np.arange(8) % 4).astype(np.int32)
    key = jax.random.PRNGKey(7)
    loss1 = float(jax.device_get(tr.step(nd.array(X), nd.array(y),
                                         key=key)))
    blobs = tr.export_executables()
    assert "step" in blobs
    CheckpointManager(ckpt, keep=2, async_save=False).save(
        0, tr.param_values, executables=blobs)
    # phase 2 ("restarted replica"): fresh process, no compile cache
    env = dict(os.environ)
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)
    env.pop("MXTPU_COSTS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _WARM_TRAINER_CHILD, ckpt, "-", repo],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = next(json.loads(l) for l in proc.stdout.splitlines()
               if l.strip().startswith("{") and "warm_child" in l)
    assert rec["events"] == 0, \
        "warm replica compiled %d time(s)" % rec["events"]
    assert rec["loss"] == pytest.approx(loss1, rel=1e-6)


_WARM_SERVING_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, sys.argv[3])
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.serving import loader as L
from incubator_mxnet_tpu.telemetry import catalog as cat

telemetry.enable()
cat.install_jax_compile_hook()
served = L.load_served_model(sys.argv[1], quantize=False)
assert served.programs, "warm child bound no executables"
ids = (np.arange(16, dtype=np.int32).reshape(2, 8) % 29)
base = cat.compile_events()
out = served.encode_fn({"token_ids": ids}, 8)
pooled = np.asarray(out["pooled"])
events = cat.compile_events() - base
print(json.dumps({"tag": "warm_child", "events": events,
                  "pooled0": float(pooled[0, 0])}))
"""


def test_warm_serving_two_process_drill(tmp_path, tele, cache_dir):
    """A restarted serving replica that binds its encode executables
    from the checkpoint answers its first request with ZERO
    backend_compile events and the identical reply."""
    from incubator_mxnet_tpu import init as _init
    from incubator_mxnet_tpu.models.bert import BERTModel
    from incubator_mxnet_tpu.serving import loader as L
    cfg = dict(vocab_size=29, units=16, hidden_size=32, num_layers=1,
               num_heads=2, max_length=32)
    m = BERTModel(prefix="ccs_", dropout=0.0, **cfg)
    m.initialize(_init.Normal(0.02))
    m(nd.array(np.zeros((1, 8), np.int32)))
    ckpt = str(tmp_path / "serve")
    L.export_for_serving(ckpt, "bert_encoder", cfg, m)
    served = L.load_served_model(ckpt, quantize=False)
    ids = (np.arange(16, dtype=np.int32).reshape(2, 8) % 29)
    ref = np.asarray(served.encode_fn({"token_ids": ids}, 8)["pooled"])
    L.attach_executables(ckpt, served.export_executables())
    # restarted replica: NO compile cache — checkpoint executables only
    env = dict(os.environ)
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _WARM_SERVING_CHILD, ckpt, "-", repo],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = next(json.loads(l) for l in proc.stdout.splitlines()
               if l.strip().startswith("{") and "warm_child" in l)
    assert rec["events"] == 0, \
        "warm replica compiled %d time(s)" % rec["events"]
    assert rec["pooled0"] == pytest.approx(float(ref[0, 0]), rel=1e-5)


# ------------------------------------------------------------ serving AOT
def test_serving_program_aval_drift_falls_back(cache_dir, tele, tmp_path):
    """A bound program whose avals no longer match serves the request
    through the eager path instead of crashing."""
    from incubator_mxnet_tpu import init as _init
    from incubator_mxnet_tpu.models.bert import BERTModel
    from incubator_mxnet_tpu.serving import loader as L
    cfg = dict(vocab_size=29, units=16, hidden_size=32, num_layers=1,
               num_heads=2, max_length=32)
    m = BERTModel(prefix="ccd_", dropout=0.0, **cfg)
    m.initialize(_init.Normal(0.02))
    m(nd.array(np.zeros((1, 8), np.int32)))
    ckpt = str(tmp_path / "serve2")
    L.export_for_serving(ckpt, "bert_encoder", cfg, m)
    served = L.load_served_model(ckpt, quantize=False)
    ids = (np.arange(8, dtype=np.int32).reshape(1, 8) % 29)
    ref = np.asarray(served.encode_fn({"token_ids": ids}, 8)["pooled"])
    key = (1, 8, ("token_ids",))
    good = served.programs[key]
    # sabotage: rebind the (2, 16) program under the (1, 8) key
    served.programs[key] = served.program_for(2, 16, ("token_ids",))
    out = np.asarray(served.encode_fn({"token_ids": ids}, 8)["pooled"])
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    assert served.programs[key] is None                # retired
    served.programs[key] = good
