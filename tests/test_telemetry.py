"""Telemetry subsystem: metrics registry, exporters, tracing, and the
instrumented framework layers (RPC, trainer, dataloader, checkpoint),
plus the profiler.dumps()/Counter satellite fixes."""

import json
import os
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, profiler, telemetry
from incubator_mxnet_tpu.telemetry import catalog, export, metrics, tracing


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()
    export.stop_flusher()


# ------------------------------------------------------------- registry

def test_counter_labels_and_values():
    c = telemetry.counter("t_requests_total", "test counter")
    c.inc()
    c.inc(2, op="push")
    c.inc(op="push")
    assert c.value() == 1
    assert c.value(op="push") == 3
    assert c.value(op="pull") == 0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_a_counter_read_by_fewer_labels_sums_the_series_it_matches():
    c = telemetry.counter("t_partial_total", "test counter")
    c.inc(2, phase="a", ahead="true")
    c.inc(phase="a", ahead="false")
    c.inc(5, phase="b", ahead="true")
    assert c.value(phase="a") == 3          # no series of exactly these
    assert c.value(ahead="true") == 7
    assert c.value() == 8
    assert c.value(phase="c") == 0
    c.inc(10, phase="a")                    # a series of exactly these
    assert c.value(phase="a") == 10         # wins over the sum


def test_gauge_set_inc_dec():
    g = telemetry.gauge("t_gauge")
    g.set(10, shard="a")
    g.inc(5, shard="a")
    g.dec(2, shard="a")
    assert g.value(shard="a") == 13


def test_histogram_buckets_cumulative():
    h = telemetry.histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 4
    assert abs(h.sum() - 55.55) < 1e-9
    snap = h.snapshot()[()]
    assert snap[2] == [1, 2, 3]     # cumulative per-bucket counts


def test_registry_type_collision_raises():
    telemetry.counter("t_collide")
    with pytest.raises(ValueError):
        telemetry.gauge("t_collide")


def test_registry_same_name_returns_same_instrument():
    assert telemetry.counter("t_same") is telemetry.counter("t_same")


def test_disabled_mutators_are_noops():
    c = telemetry.counter("t_disabled_total")
    h = telemetry.histogram("t_disabled_seconds")
    telemetry.disable()
    c.inc(5)
    h.observe(1.0)
    telemetry.enable()
    assert c.value() == 0
    assert h.count() == 0


def test_counter_thread_safety():
    c = telemetry.counter("t_mt_total")

    def worker():
        for _ in range(1000):
            c.inc()
    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000


def test_reset_clears_series_not_registrations():
    c = telemetry.counter("t_reset_total")
    c.inc(3)
    telemetry.reset()
    assert c.value() == 0
    assert telemetry.counter("t_reset_total") is c


# ------------------------------------------------------------ exporters

def test_render_prometheus_format():
    c = telemetry.counter("t_prom_total", "help text")
    c.inc(2, op="push", peer="10.0.0.1")
    h = telemetry.histogram("t_prom_seconds", buckets=(0.5, 2.0))
    h.observe(1.0)
    out = telemetry.render_prometheus()
    assert "# HELP t_prom_total help text" in out
    assert "# TYPE t_prom_total counter" in out
    assert 't_prom_total{op="push",peer="10.0.0.1"} 2' in out
    assert "# TYPE t_prom_seconds histogram" in out
    assert 't_prom_seconds_bucket{le="0.5"} 0' in out
    assert 't_prom_seconds_bucket{le="2.0"} 1' in out
    assert 't_prom_seconds_bucket{le="+Inf"} 1' in out
    assert "t_prom_seconds_sum 1.0" in out
    assert "t_prom_seconds_count 1" in out


def test_render_prometheus_escapes_labels():
    c = telemetry.counter("t_escape_total")
    c.inc(key='has"quote\nand\\slash')
    out = telemetry.render_prometheus()
    assert 'key="has\\"quote\\nand\\\\slash"' in out


def test_render_json_roundtrip():
    telemetry.counter("t_json_total").inc(4, op="x")
    data = json.loads(telemetry.render_json())
    assert data["t_json_total"]["kind"] == "counter"
    assert data["t_json_total"]["series"]["op=x"] == 4


def test_flush_writes_file_atomically(tmp_path):
    telemetry.counter("t_flush_total").inc()
    p = str(tmp_path / "metrics.prom")
    telemetry.flush(p)
    with open(p) as f:
        assert "t_flush_total 1" in f.read()
    jp = str(tmp_path / "metrics.json")
    telemetry.flush(jp, fmt="json")
    with open(jp) as f:
        assert json.load(f)["t_flush_total"]["series"][""] == 1


def test_periodic_flusher(tmp_path):
    telemetry.counter("t_periodic_total").inc(7)
    p = str(tmp_path / "out.prom")
    telemetry.start_flusher(p, interval=0.05)
    deadline = time.time() + 5
    while not os.path.exists(p) and time.time() < deadline:
        time.sleep(0.02)
    telemetry.stop_flusher()
    assert os.path.exists(p), "flusher never wrote"
    with open(p) as f:
        assert "t_periodic_total 7" in f.read()


def test_flusher_env_init(tmp_path, monkeypatch):
    p = str(tmp_path / "env.json")
    monkeypatch.setenv("MXTPU_METRICS_EXPORT", p)
    monkeypatch.setenv("MXTPU_METRICS_INTERVAL", "0.05")
    monkeypatch.setenv("MXTPU_METRICS_FORMAT", "json")
    export._init_from_env()
    try:
        telemetry.counter("t_env_total").inc()
        deadline = time.time() + 5
        while not os.path.exists(p) and time.time() < deadline:
            time.sleep(0.02)
        assert os.path.exists(p)
        with open(p) as f:
            json.load(f)    # valid JSON export
    finally:
        telemetry.stop_flusher()


def test_flusher_rejects_bad_format():
    with pytest.raises(ValueError):
        telemetry.start_flusher("/tmp/x", fmt="xml")


# -------------------------------------------------------------- tracing

def test_span_nesting_and_ids():
    profiler.set_config(filename="/tmp/_tm_span.json")
    profiler.start()
    try:
        with telemetry.span("outer") as outer:
            with telemetry.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                assert telemetry.current() is inner
            assert telemetry.current() is outer
        assert telemetry.current() is None
    finally:
        profiler.stop()
    spans = [e for e in profiler._events if e.get("cat") == "span"]
    names = {e["name"] for e in spans}
    assert {"outer", "inner"} <= names
    for e in spans:
        assert e["ph"] == "X"
        assert "trace_id" in e["args"] and "span_id" in e["args"]


def test_span_is_noop_when_idle():
    telemetry.disable()
    assert not profiler._state["running"]
    assert telemetry.span("x") is tracing.NULL_SPAN
    with telemetry.span("x") as sp:
        assert sp.trace_id is None


def test_inject_extract_roundtrip():
    with telemetry.span("rpc") as sp:
        meta = {"op": "push"}
        telemetry.inject(meta)
        assert meta[tracing.TRACE_KEY] == sp.trace_id
        assert meta[tracing.PARENT_KEY] == sp.span_id
        tid, pid = telemetry.extract(meta)
        assert (tid, pid) == (sp.trace_id, sp.span_id)
        # an already-stamped meta is not overwritten
        with telemetry.span("deeper"):
            telemetry.inject(meta)
        assert meta[tracing.PARENT_KEY] == sp.span_id


def test_from_meta_links_server_span():
    with telemetry.span("client") as sp:
        meta = telemetry.inject({"op": "push"})
    server = telemetry.from_meta("rpc.push", meta)
    assert server.trace_id == sp.trace_id
    assert server.parent_id == sp.span_id
    assert telemetry.from_meta("rpc.x", {"op": "x"}) is tracing.NULL_SPAN


# ------------------------------------------------------------- journeys

@pytest.fixture
def ring_of_16():
    size = tracing._finished.maxlen
    tracing._resize(16)
    tracing.clear_spans()
    yield 16
    tracing._resize(size)


def test_a_root_keeps_its_whole_tree_whatever_the_ring_lost(ring_of_16):
    with tracing.Span("journey.root") as root:
        for i in range(100):
            with telemetry.span("journey.child", i=i):
                pass
    (journey,) = [j for j in tracing.recent_journeys("journey.root")
                  if j[-1]["span_id"] == root.span_id]
    assert len(journey) == 101 and journey[-1]["name"] == "journey.root"
    assert "journey_dropped" not in journey[-1]
    assert [r["i"] for r in journey[:-1]] == list(range(100))
    assert all(r["parent_id"] == root.span_id for r in journey[:-1])
    # the ring holds the last 16, the same records
    ring = tracing.recent_spans()
    assert len(ring) == ring_of_16 and ring == journey[-16:]
    assert all(a is b for a, b in zip(ring, journey[-16:]))
    # the timeline of that trace id is whole too: the journey, not the ring
    assert tracing.spans_for_trace(root.trace_id) \
        == sorted(journey, key=lambda r: r["ts_us"])
    timeline = tracing.build_timeline(tracing.spans_for_trace(root.trace_id))
    assert len(timeline["roots"]) == 1
    assert len(timeline["roots"][0]["children"]) == 100
    # clearing the ring leaves the journeys alone
    tracing.clear_spans()
    assert tracing.recent_spans() == []
    assert tracing.recent_journeys("journey.root")[-1] is journey
    assert len(tracing.spans_for_trace(root.trace_id)) == 101


def test_many_threads_roots_do_not_mix(monkeypatch):
    """More threads than cores, switching every 10 us, each under a root
    of its own: every journey holds its own thread's records, all of
    them, and a lost update would leave one short."""
    import collections
    import sys
    workers = 16
    monkeypatch.setattr(tracing, "_journeys",
                        collections.deque(maxlen=workers))
    start = threading.Barrier(workers)

    def call(tag):
        start.wait(10.0)
        with tracing.Span("journey.thread", attrs={"tag": tag}):
            for i in range(200):
                with telemetry.span("journey.step", tag=tag):
                    pass
    threads = [threading.Thread(target=call, args=(tag,))
               for tag in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    journeys = tracing.recent_journeys("journey.thread")
    assert sorted(j[-1]["tag"] for j in journeys) == list(range(workers))
    for journey in journeys:
        assert len(journey) == 201
        assert {r["tag"] for r in journey} == {journey[-1]["tag"]}
        assert {r["trace_id"] for r in journey} \
            == {journey[-1]["trace_id"]}
    assert tracing._open_journeys == {}


def test_a_journey_stops_at_its_cap_and_its_root_says_so(monkeypatch):
    monkeypatch.setattr(tracing, "JOURNEY_MAX_SPANS", 10)
    with tracing.Span("journey.capped"):
        for i in range(25):
            with telemetry.span("journey.child", i=i):
                pass
    journey = tracing.recent_journeys("journey.capped")[-1]
    assert len(journey) == 10           # nine children and the root
    assert [r["i"] for r in journey[:-1]] == list(range(9))
    assert journey[-1]["name"] == "journey.capped"
    assert journey[-1]["journey_dropped"] == 16
    # one under the cap drops nothing
    with tracing.Span("journey.capped"):
        for i in range(9):
            with telemetry.span("journey.child", i=i):
                pass
    whole = tracing.recent_journeys("journey.capped")[-1]
    assert len(whole) == 10 and "journey_dropped" not in whole[-1]


def test_the_kept_journeys_are_bounded_and_oldest_first():
    for i in range(tracing.JOURNEYS_KEPT + 3):
        with tracing.Span("journey.many", attrs={"i": i}):
            pass
    kept = tracing.recent_journeys()
    assert len(kept) == tracing.JOURNEYS_KEPT
    assert [j[-1]["i"] for j in kept] == list(range(
        3, tracing.JOURNEYS_KEPT + 3))
    assert tracing.recent_journeys("journey.none") == []
    # nothing stays open once its root has closed
    assert tracing._open_journeys == {}


def test_record_span_joins_an_open_journey():
    with tracing.Span("journey.root") as root:
        rec = tracing.record_span("queue.wait", root.trace_id,
                                  parent_id=root.span_id,
                                  t0=time.time() - 0.5, t1=time.time())
        tracing.record_span("elsewhere", "another-trace")
    journey = tracing.recent_journeys("journey.root")[-1]
    assert [r["name"] for r in journey] == ["queue.wait", "journey.root"]
    assert journey[0] is rec
    # a span that continues a remote parent's trace is no root
    with tracing.from_meta("rpc.push", {tracing.TRACE_KEY: "remote",
                                        tracing.PARENT_KEY: "p"}):
        pass
    assert all(j[-1]["name"] != "rpc.push"
               for j in tracing.recent_journeys())


def test_nothing_is_kept_while_telemetry_is_idle():
    telemetry.disable()
    before = [j[-1]["span_id"] for j in tracing.recent_journeys()]
    assert telemetry.span("journey.idle") is tracing.NULL_SPAN
    with telemetry.span("journey.idle"):
        with telemetry.span("journey.idle.child"):
            pass
    assert [j[-1]["span_id"] for j in tracing.recent_journeys()] == before
    assert tracing._open_journeys == {}


def test_tracez_builds_a_large_calls_timeline_from_its_journey(ring_of_16):
    from incubator_mxnet_tpu.telemetry import debugz
    import urllib.request
    with tracing.Span("journey.served") as root:
        for i in range(40):
            with telemetry.span("journey.child", i=i):
                pass
    server = debugz.start(0)
    try:
        body = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/tracez?trace_id=%s"
            % (server.server_address[1], root.trace_id),
            timeout=10).read())
    finally:
        debugz.stop()
    assert len(body["timeline"]["spans"]) == 41
    assert len(body["timeline"]["roots"][0]["children"]) == 40


def test_merge_traces(tmp_path):
    a = {"traceEvents": [{"name": "w", "ph": "X", "pid": 0, "tid": 1,
                          "ts": 0, "dur": 5}]}
    b = {"traceEvents": [{"name": "s", "ph": "X", "pid": 0, "tid": 1,
                          "ts": 1, "dur": 2}]}
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    for p, d in ((pa, a), (pb, b)):
        with open(p, "w") as f:
            json.dump(d, f)
    out = str(tmp_path / "merged.json")
    merged = telemetry.merge_traces([pa, pb], out)
    assert {(e["name"], e["pid"]) for e in merged} == {("w", 0), ("s", 1)}
    with open(out) as f:
        assert len(json.load(f)["traceEvents"]) == 2


# ------------------------------------------------- RPC instrumentation

def _echo_handler(meta, payload):
    return {"ok": True}, payload


def test_rpc_client_server_metrics():
    from incubator_mxnet_tpu.kvstore import rpc
    srv = rpc.Server(_echo_handler).start()
    try:
        conn = rpc.Connection(srv.addr)
        conn.call({"op": "ping"}, b"abc")
        conn.call({"op": "ping"}, b"abc")
        assert catalog.rpc_client_requests.value(op="ping", status="ok") == 2
        assert catalog.rpc_client_seconds.count(op="ping") == 2
        assert catalog.rpc_bytes_sent.value() > 0
        assert catalog.rpc_bytes_received.value() > 0
        deadline = time.time() + 5
        while (catalog.rpc_server_requests.value(op="ping", status="ok") < 2
               and time.time() < deadline):
            time.sleep(0.01)
        assert catalog.rpc_server_requests.value(op="ping", status="ok") == 2
        assert catalog.rpc_server_seconds.count(op="ping") == 2
        # reconnect counter: drop the socket, next call re-establishes
        conn.close()
        conn.call({"op": "ping"})
        assert catalog.rpc_reconnects.value() == 1
        conn.close()
    finally:
        srv.stop()


def test_rpc_retry_counter():
    from incubator_mxnet_tpu.kvstore import rpc
    # grab a port with nothing listening
    import socket as _socket
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    conn = rpc.Connection(("127.0.0.1", port))
    with pytest.raises(OSError):
        conn.call_idempotent({"op": "push"}, window=0.3)
    assert catalog.rpc_retries.value(op="push") >= 1


def test_rpc_dedup_hit_counter():
    from incubator_mxnet_tpu.kvstore import rpc
    cache = rpc.DedupCache()
    calls = []

    def handler(meta, payload):
        calls.append(meta["_seq"])
        return {"ok": True}, b""
    wrapped = cache.wrap(handler)
    meta = {"op": "push", "_client": "tok", "_seq": 1}
    wrapped(dict(meta), b"")
    wrapped(dict(meta), b"")      # resend: served from cache
    assert calls == [1]
    assert catalog.rpc_dedup_hits.value() == 1


def test_rpc_trace_propagation_single_process():
    """Worker span context rides the meta dict into the server handler
    thread and comes back as a linked chrome-trace span."""
    from incubator_mxnet_tpu.kvstore import rpc
    srv = rpc.Server(_echo_handler).start()
    profiler.set_config(filename="/tmp/_tm_rpc_span.json")
    profiler.start()
    try:
        conn = rpc.Connection(srv.addr)
        with telemetry.span("client.op") as sp:
            conn.call({"op": "ping"})
            trace_id, client_span = sp.trace_id, sp.span_id
        conn.close()
    finally:
        profiler.stop()
        srv.stop()
    spans = [e for e in profiler._events if e.get("cat") == "span"]
    server_spans = [e for e in spans if e["name"] == "rpc.ping"]
    assert server_spans, [e["name"] for e in spans]
    assert server_spans[0]["args"]["trace_id"] == trace_id
    assert server_spans[0]["args"]["parent_id"] == client_span


def test_failpoint_trigger_counter():
    from incubator_mxnet_tpu.utils import failpoints
    failpoints.activate("telemetry.test")
    try:
        assert failpoints.failpoint("telemetry.test")
        assert failpoints.failpoint("telemetry.test")
        assert catalog.failpoints_triggered.value(name="telemetry.test") == 2
    finally:
        failpoints.deactivate("telemetry.test")


# -------------------------------------------- trainer instrumentation

def _xent(out, lab):
    import jax
    import jax.numpy as jnp
    lp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, lab[:, None], axis=-1).mean()


def _tiny_trainer():
    import jax
    from jax.sharding import PartitionSpec as P
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    X = np.random.RandomState(0).rand(16, 8).astype(np.float32)
    net(nd.array(X))
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, _xent, mesh, optimizer="sgd",
                        data_specs=[P()], label_spec=P())
    y = np.random.RandomState(1).randint(0, 4, 16).astype(np.int32)
    return tr, X, y


def test_trainer_step_metrics():
    tr, X, y = _tiny_trainer()
    steps0 = catalog.trainer_steps.value(zero="off", pipeline="off")
    samples0 = catalog.trainer_samples.value()
    tr.step([nd.array(X)], nd.array(y))
    tr.step([nd.array(X)], nd.array(y))
    assert catalog.trainer_steps.value(zero="off", pipeline="off") == steps0 + 2
    assert catalog.trainer_step_seconds.count(zero="off", pipeline="off") >= 2
    assert catalog.trainer_samples.value() == samples0 + 32
    out = telemetry.render_prometheus()
    assert "mxtpu_trainer_step_seconds_count" in out
    assert "mxtpu_trainer_steps_total" in out


def test_trainer_jit_compile_hook():
    # the hook is installed by ShardedTrainer.__init__; the first step
    # triggers a backend compile which jax.monitoring reports
    tr, X, y = _tiny_trainer()
    compiles0 = catalog.compile_events()
    tr.step([nd.array(X)], nd.array(y))
    assert catalog.compile_events() > compiles0
    assert sum(catalog.jit_compile_seconds.snapshot().values()) > 0


def test_trainer_step_scan_counts_all_steps():
    tr, X, y = _tiny_trainer()
    steps0 = catalog.trainer_steps.value(zero="off", pipeline="off")
    samples0 = catalog.trainer_samples.value()
    tr.step_scan([nd.array(X)], nd.array(y), n_steps=3,
                 per_step_batches=False)
    assert catalog.trainer_steps.value(zero="off", pipeline="off") == steps0 + 3
    assert catalog.trainer_samples.value() == samples0 + 48


def test_jax_event_listener_folds_compile_events():
    catalog.install_jax_compile_hook()
    before = catalog.compile_events()
    catalog._on_jax_event_duration(catalog._COMPILE_EVENT, 0.25)
    catalog._on_jax_event_duration("/jax/unrelated", 9.0)
    assert catalog.compile_events() == before + 1


# ----------------------------------------- dataloader instrumentation

def test_dataloader_metrics_sync_path():
    from incubator_mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    rng = np.random.RandomState(0)
    ds = ArrayDataset(rng.rand(64, 4).astype(np.float32),
                      np.arange(64).astype(np.float32))
    before = catalog.dataloader_batches.value()
    n = len(list(DataLoader(ds, batch_size=16)))
    assert n == 4
    assert catalog.dataloader_batches.value() == before + 4


def test_dataloader_metrics_worker_path():
    from incubator_mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    rng = np.random.RandomState(0)
    ds = ArrayDataset(rng.rand(64, 4).astype(np.float32),
                      np.arange(64).astype(np.float32))
    before = catalog.dataloader_batches.value()
    wait0 = catalog.dataloader_wait_seconds.count()
    n = len(list(DataLoader(ds, batch_size=16, num_workers=2)))
    assert n == 4
    assert catalog.dataloader_batches.value() == before + 4
    assert catalog.dataloader_wait_seconds.count() >= wait0 + 4
    out = telemetry.render_prometheus()
    assert "mxtpu_dataloader_batch_wait_seconds_count" in out


# ----------------------------------------- checkpoint instrumentation

def test_checkpoint_metrics(tmp_path):
    from incubator_mxnet_tpu.utils.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    mgr.save(1, {"w": nd.array(np.ones((4,), np.float32))})
    mgr.restore()
    assert catalog.checkpoint_saves.value(status="ok") == 1
    assert catalog.checkpoint_save_seconds.count() == 1
    assert catalog.checkpoint_restores.value(status="ok") == 1
    assert catalog.checkpoint_restore_seconds.count() == 1


# ------------------------------------------------- profiler satellites

def _seed_profiler_events():
    profiler.set_config(filename="/tmp/_tm_dumps.json")
    profiler.start()
    profiler._record("event", "aaa", ts=0, dur=100.0)
    profiler._record("event", "bbb", ts=0, dur=40.0)
    profiler._record("event", "bbb", ts=0, dur=20.0)
    profiler.stop()


def _table_names(table):
    return [line.split()[0] for line in table.splitlines()[1:] if line]


def test_profiler_dumps_sort_by_total_desc_default():
    _seed_profiler_events()
    assert _table_names(profiler.dumps()) == ["aaa", "bbb"]


def test_profiler_dumps_sort_and_ascending():
    _seed_profiler_events()
    assert _table_names(profiler.dumps(sort_by="total",
                                       ascending=True)) == ["bbb", "aaa"]
    assert _table_names(profiler.dumps(sort_by="count")) == ["bbb", "aaa"]
    assert _table_names(profiler.dumps(sort_by="name",
                                       ascending=True)) == ["aaa", "bbb"]
    assert _table_names(profiler.dumps(sort_by="avg")) == ["aaa", "bbb"]
    assert _table_names(profiler.dumps(sort_by="min",
                                       ascending=True)) == ["bbb", "aaa"]
    assert _table_names(profiler.dumps(sort_by="max")) == ["aaa", "bbb"]


def test_profiler_dumps_json_format():
    _seed_profiler_events()
    data = json.loads(profiler.dumps(format="json"))
    assert data["aaa"]["count"] == 1
    assert data["bbb"]["count"] == 2
    assert data["bbb"]["total"] == 60.0
    assert data["bbb"]["avg"] == 30.0
    assert data["bbb"]["min"] == 20.0 and data["bbb"]["max"] == 40.0


def test_profiler_dumps_rejects_unknown_args():
    _seed_profiler_events()
    with pytest.raises(ValueError):
        profiler.dumps(sort_by="bogus")
    with pytest.raises(ValueError):
        profiler.dumps(format="xml")


def test_profiler_dumps_reset():
    _seed_profiler_events()
    profiler.dumps(reset=True)
    assert _table_names(profiler.dumps()) == []


def test_profiler_counter_thread_safe():
    c = profiler.Counter("t_prof_counter")

    def worker():
        for _ in range(1000):
            c.increment()
    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    c.decrement(4000)
    assert c.value == 4000
