"""Tier-1 overhead gate: the disabled-telemetry path must reduce to one
predicate check per instrumented call — no allocation, no locking, no
recording. Verified by a generous wall-clock bound (CI boxes are noisy;
the real disabled cost is ~100ns/call, the bound allows 50x that)."""

import time

from incubator_mxnet_tpu import profiler, telemetry
from incubator_mxnet_tpu.telemetry import costs, debugz, flight, tracing

N = 100_000
MAX_SECONDS_PER_CALL = 5e-6     # 50x headroom over the measured cost


def _per_call(fn):
    t0 = time.perf_counter()
    for _ in range(N):
        fn()
    return (time.perf_counter() - t0) / N


def test_disabled_counter_is_cheap_and_records_nothing():
    telemetry.disable()
    c = telemetry.counter("overhead_counter_total")
    assert _per_call(c.inc) < MAX_SECONDS_PER_CALL
    assert c.value() == 0


def test_disabled_histogram_is_cheap_and_records_nothing():
    telemetry.disable()
    h = telemetry.histogram("overhead_seconds")
    assert _per_call(lambda: h.observe(0.5)) < MAX_SECONDS_PER_CALL
    assert h.count() == 0


def test_disabled_gauge_is_cheap():
    telemetry.disable()
    g = telemetry.gauge("overhead_gauge")
    assert _per_call(lambda: g.set(1)) < MAX_SECONDS_PER_CALL
    assert g.value() == 0


def test_idle_span_is_shared_noop():
    telemetry.disable()
    assert not profiler._state["running"]
    # no span object churn: every idle span() is the same null object
    assert telemetry.span("x") is tracing.NULL_SPAN
    assert _per_call(lambda: telemetry.span("x")) < MAX_SECONDS_PER_CALL


def test_span_is_real_inside_a_jax_profiler_session(tmp_path):
    """No switch of ours: with metrics off, the package's profiler off and
    no parent span, span() is the shared null object until a jax.profiler
    session starts; inside one it is real, its name is a host event of the
    session's trace with the attributes it was opened with, and its record
    still reaches the ring. After the session it is null again."""
    import glob

    import jax
    from jax.profiler import ProfileData

    telemetry.disable()
    assert not profiler._state["running"]
    assert tracing.current() is None
    assert tracing.span("x") is tracing.NULL_SPAN
    tracing.clear_spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        outer = tracing.span("overhead.outer", rows=3)
        assert outer is not tracing.NULL_SPAN
        with outer:
            with tracing.span("overhead.inner") as inner:
                inner.set_attr("late", 1)       # the record's alone
    finally:
        jax.profiler.stop_trace()
    assert tracing.span("x") is tracing.NULL_SPAN
    recs = {r["name"]: r for r in tracing.recent_spans()}
    assert recs["overhead.inner"]["parent_id"] == \
        recs["overhead.outer"]["span_id"]
    assert recs["overhead.outer"]["rows"] == 3
    assert recs["overhead.inner"]["late"] == 1
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = {ev.name: ev for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("overhead.")}
    assert set(host) == {"overhead.outer", "overhead.inner"}
    assert dict(host["overhead.outer"].stats)["rows"] == 3
    # the child lies inside its parent on the profiler's clock too
    assert host["overhead.outer"].start_ns <= host["overhead.inner"].start_ns
    assert host["overhead.inner"].end_ns <= host["overhead.outer"].end_ns


def test_sampling_off_request_span_is_cheap_shared_noop():
    """Head sampling off (MXTPU_TRACE_SAMPLE=0): request_span() is one
    rate lookup + compare returning the shared null span — no id
    generation, no allocation, nothing retained. This is the cost every
    serving request pays when tracing is disabled."""
    telemetry.disable()
    prev = tracing.sample_rate()
    tracing.set_sample_rate(0.0)
    try:
        tracing.clear_spans()
        sp = tracing.request_span("client.infer")
        assert sp is tracing.NULL_SPAN
        with sp:
            pass                       # the null span context is free too
        assert _per_call(lambda: tracing.request_span("client.infer")) \
            < MAX_SECONDS_PER_CALL
        assert tracing.recent_spans() == []
    finally:
        tracing.set_sample_rate(prev)


def test_enabled_flag_is_single_predicate():
    """The gate the hot paths check is one dict lookup."""
    telemetry.disable()
    assert telemetry.enabled() is False
    assert _per_call(telemetry.enabled) < MAX_SECONDS_PER_CALL
    telemetry.enable()
    try:
        assert telemetry.enabled() is True
    finally:
        telemetry.disable()


def test_disabled_flight_record_is_cheap_and_records_nothing():
    was = flight.enabled()
    flight.disable()
    try:
        flight.clear()
        assert _per_call(lambda: flight.record("ev", a=1)) \
            < MAX_SECONDS_PER_CALL
        assert flight.events() == []
    finally:
        if was:
            flight.enable()


def test_disabled_cost_observe_is_cheap_and_records_nothing():
    telemetry.disable()
    costs.capture("overhead_exec", cost={"flops": 1e9, "bytes": 1e6})
    try:
        assert _per_call(lambda: costs.observe("overhead_exec", 0.1)) \
            < MAX_SECONDS_PER_CALL
        from incubator_mxnet_tpu.telemetry import catalog
        assert catalog.model_flops_utilization.value(
            name="overhead_exec") == 0
    finally:
        costs.reset()


def test_inactive_debugz_status_is_cheap():
    assert not debugz.active()
    assert _per_call(lambda: debugz.set_status("k", 1)) \
        < MAX_SECONDS_PER_CALL


def test_disabled_history_is_one_flag_check():
    """History plane off (the default): sample_local() is one predicate
    check, default() resolves to None, and nothing is retained."""
    from incubator_mxnet_tpu.telemetry import history
    was = history.enabled()
    history.disable()
    try:
        assert history.enabled() is False
        assert history.default() is None
        assert history.sample_local() is None
        assert _per_call(history.sample_local) < MAX_SECONDS_PER_CALL
    finally:
        if was:
            history.enable()


def test_disabled_health_is_one_flag_check():
    """Health plane off (the default): tick() is one predicate check,
    statusz_entry() is a constant stub, and the verdict is a benign OK."""
    from incubator_mxnet_tpu.telemetry import health
    assert health.enabled() is False
    assert health.evaluator() is None
    assert health.tick() is None
    assert health.statusz_entry() == {"enabled": False}
    v = health.verdict()
    assert v["ok"] is True and v["level"] == health.OK
    assert _per_call(health.tick) < MAX_SECONDS_PER_CALL


def test_disabled_compile_cache_is_one_env_check(monkeypatch):
    """Cache off (no MXTPU_COMPILE_CACHE_DIR): enabled() is one env-dict
    lookup, default_store() resolves to None, and the statusz entry is a
    constant — no filesystem access anywhere on the off path."""
    from incubator_mxnet_tpu.compilecache import store as ccstore
    monkeypatch.delenv("MXTPU_COMPILE_CACHE_DIR", raising=False)
    assert ccstore.enabled() is False
    assert ccstore.default_store() is None
    assert ccstore.statusz_entry() == {"enabled": False}
    assert _per_call(ccstore.enabled) < MAX_SECONDS_PER_CALL
    calls = []
    monkeypatch.setattr(ccstore.os, "listdir",
                        lambda *a, **k: calls.append(a) or [])
    monkeypatch.setattr(ccstore.os, "makedirs",
                        lambda *a, **k: calls.append(a))
    assert ccstore.default_store() is None
    assert ccstore.statusz_entry() == {"enabled": False}
    assert calls == []


def test_disabled_fused_optim_is_one_env_check(monkeypatch):
    """Fused optimizer off (MXTPU_FUSED_OPTIM=0): the eligibility gate
    reduces to one env-dict lookup, and update_multi reports zero fused
    launches while still applying the per-param updates."""
    import numpy as np
    from incubator_mxnet_tpu import nd, optimizer as opt
    from incubator_mxnet_tpu.ops.pallas.fused_optim import (
        fused_optim_enabled)
    monkeypatch.setenv("MXTPU_FUSED_OPTIM", "0")
    assert fused_optim_enabled() is False
    assert _per_call(fused_optim_enabled) < MAX_SECONDS_PER_CALL
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    w = nd.array(np.ones((4, 3), np.float32))
    g = nd.array(np.full((4, 3), 0.5, np.float32))
    st = o.create_state(0, w)
    assert o.update_multi([0], [w], [g], [st]) == 0
    assert (np.asarray(w._data) != 1.0).all()   # update still applied


def test_disabled_ps_overlap_is_one_flag_check():
    """Overlap pipeline off (MXTPU_PS_BUCKET_MB=0): the gate the Trainer
    reads at kv init is two attribute checks — the cap is parsed ONCE at
    store construction, never per step, and the off path allocates
    nothing."""
    from incubator_mxnet_tpu.kvstore.dist import KVStoreDist
    kv = KVStoreDist.__new__(KVStoreDist)   # predicate needs no connection
    kv._bucket_bytes = 0
    kv._io = None
    assert kv.overlap_enabled() is False
    assert _per_call(kv.overlap_enabled) < MAX_SECONDS_PER_CALL


def test_disabled_deploy_instruments_are_cheap_and_record_nothing():
    """The deploy plane's instruments (generation gauge, swap counter,
    in-flight gauge) sit on the serving hot path's neighbors — disabled
    they must reduce to the same one-predicate check as every other
    instrument, and record nothing."""
    telemetry.disable()
    from incubator_mxnet_tpu.telemetry import catalog
    assert _per_call(
        lambda: catalog.serving_generation.set(3, model="m")) \
        < MAX_SECONDS_PER_CALL
    assert _per_call(lambda: catalog.deploy_inflight.set(1)) \
        < MAX_SECONDS_PER_CALL
    assert _per_call(
        lambda: catalog.deploy_swaps.inc(model="m", outcome="ok")) \
        < MAX_SECONDS_PER_CALL
    assert catalog.serving_generation.value(model="m") == 0
    assert catalog.deploy_swaps.value(model="m", outcome="ok") == 0


def test_disabled_lockdep_is_one_env_check():
    """Lockdep witness off (the default): check_blocking — which sits on
    the rpc send/recv hot path — is one dict lookup, lock construction
    is untouched, and the statusz entry is a constant stub."""
    import threading
    from incubator_mxnet_tpu.telemetry import lockdep
    assert lockdep.installed() is False
    assert _per_call(lambda: lockdep.check_blocking("rpc.send")) \
        < MAX_SECONDS_PER_CALL
    assert lockdep.statusz_entry() == {"enabled": False}
    assert lockdep.report() == {"enabled": False}
    assert threading.Lock is lockdep._ORIG_LOCK
    assert threading.RLock is lockdep._ORIG_RLOCK
    assert lockdep.violations() == []


def test_disabled_memz_is_one_predicate(monkeypatch):
    """Memz plane off (MXTPU_MEMZ unset): sample(), note_kv() and
    capture_memory() — the three hooks on the history-daemon / decode /
    compile hot paths — each reduce to one predicate check: no device
    queries, no jax import, no filesystem, nothing captured."""
    import builtins
    from incubator_mxnet_tpu.telemetry import memz
    was = memz.enabled()
    memz.disable()
    try:
        assert memz.enabled() is False
        assert memz.statusz_entry() == {"enabled": False}
        assert _per_call(memz.sample) < MAX_SECONDS_PER_CALL
        assert _per_call(lambda: memz.note_kv(None)) \
            < MAX_SECONDS_PER_CALL
        assert _per_call(lambda: memz.capture_memory("p", compiled=None)) \
            < MAX_SECONDS_PER_CALL
        # the off path must touch neither the backend nor the disk
        real_import = builtins.__import__

        def _no_jax(name, *a, **k):
            assert name != "jax", "disabled memz imported jax"
            return real_import(name, *a, **k)
        monkeypatch.setattr(builtins, "__import__", _no_jax)
        monkeypatch.setattr(memz.os.path, "exists",
                            lambda *a, **k: (_ for _ in ()).throw(
                                AssertionError("disabled memz hit the "
                                               "filesystem")))
        monkeypatch.setattr(builtins, "open",
                            lambda *a, **k: (_ for _ in ()).throw(
                                AssertionError("disabled memz opened a "
                                               "file")))
        memz.sample()
        memz.note_kv(None)
        memz.capture_memory("p", compiled=object())
        assert memz.programs() == {}
    finally:
        monkeypatch.undo()
        if was:
            memz.enable()
