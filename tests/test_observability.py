"""Fleet observability plane acceptance.

- costs.py FLOPs pinned against the hand-computed 2*M*N*K for a matmul
- bounded trace-span retention (MXTPU_TRACE_MAX_SPANS semantics)
- flight recorder ring + JSONL dump + the atexit trace/flight dump fix
- debugz endpoints all answer 200 with parseable payloads
- two-process drill: aggregate.scrape() over a live scheduler+server+
  worker fleet returns ONE merged registry with role labels, and a
  SIGTERM-killed worker leaves a flight JSONL holding its final events
"""

import json
import multiprocessing as mp
import os
import signal
import time
import urllib.error
import urllib.request

import pytest

import incubator_mxnet_tpu as mx  # noqa: F401 — forces the cpu mesh env
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.telemetry import (aggregate, costs, debugz,
                                           flight, tracing)


# --------------------------------------------------------------- costs

def test_costs_matmul_flops_pin():
    import jax
    import jax.numpy as jnp
    M, N, K = 64, 128, 32
    f = jax.jit(lambda a, b: a @ b)
    compiled = f.lower(jnp.zeros((M, K), jnp.float32),
                       jnp.zeros((K, N), jnp.float32)).compile()
    c = costs.cost_of(compiled)
    assert c["flops"] == 2.0 * M * N * K
    assert c["bytes"] > 0


def test_costs_capture_observe_mfu(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "1")   # 1 TFLOP/s roofline
    telemetry.enable()
    try:
        costs.capture("obs_exec", cost={"flops": 5e11, "bytes": 1.0},
                      samples_per_exec=100)
        costs.observe("obs_exec", seconds=1.0)
        from incubator_mxnet_tpu.telemetry import catalog
        assert catalog.model_flops_utilization.value(
            name="obs_exec") == pytest.approx(0.5)
        assert catalog.model_tokens_per_sec.value(
            name="obs_exec") == pytest.approx(100.0)
        assert costs.mfu(5e11, 1.0) == pytest.approx(0.5)
    finally:
        costs.reset()
        telemetry.disable()


# ------------------------------------------------- span retention ring

def test_trace_span_retention_is_bounded():
    telemetry.enable()
    old_len = tracing._finished.maxlen
    try:
        tracing._resize(8)
        tracing.clear_spans()
        from incubator_mxnet_tpu.telemetry import catalog
        dropped0 = catalog.telemetry_spans_dropped.value()
        for i in range(20):
            with telemetry.span("ring_span", i=i):
                pass
        spans = tracing.recent_spans()
        assert len(spans) == 8
        # newest-last: the ring kept the final 8 spans
        assert [s["i"] for s in spans] == list(range(12, 20))
        assert catalog.telemetry_spans_dropped.value() - dropped0 == 12
        assert tracing.recent_spans(3) == spans[-3:]
    finally:
        tracing._resize(old_len)
        tracing.clear_spans()
        telemetry.disable()


# ------------------------------------------------------ flight recorder

def test_flight_ring_and_dump(tmp_path):
    was = flight.enabled()
    flight.enable()
    try:
        flight.clear()
        flight.set_identity("tester", 7)
        flight.record("rpc.retry", op="push", addr="127.0.0.1:1")
        flight.record("membership.epoch", epoch=3, quorum=2)
        evs = flight.events()
        assert [e["event"] for e in evs] == ["rpc.retry",
                                            "membership.epoch"]
        assert evs[0]["role"] == "tester" and evs[0]["rank"] == 7
        out = tmp_path / "flight.jsonl"
        assert flight.dump(str(out), reason="test") == str(out)
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        assert [l["event"] for l in lines] == \
            ["rpc.retry", "membership.epoch", "flight.dump"]
        assert lines[-1]["attrs"]["reason"] == "test"
    finally:
        flight.clear()
        flight.set_identity(role=None, rank=None)
        if not was:
            flight.disable()


def test_atexit_flush_emits_trace_and_flight_dumps(tmp_path, monkeypatch):
    """S6 fix: the atexit flusher must also dump the trace/flight rings
    when their env knobs are set, so a clean exit keeps its final
    seconds."""
    from incubator_mxnet_tpu.telemetry import export
    trace_out = tmp_path / "spans.jsonl"
    flight_out = tmp_path / "flight.jsonl"
    monkeypatch.setenv("MXTPU_TRACE_EXPORT", str(trace_out))
    monkeypatch.setenv("MXTPU_FLIGHT_EXPORT", str(flight_out))
    telemetry.enable()
    was = flight.enabled()
    flight.enable()
    try:
        tracing.clear_spans()
        flight.clear()
        with telemetry.span("final_span"):
            pass
        flight.record("final_event")
        export._atexit_flush()
        spans = [json.loads(l) for l in
                 trace_out.read_text().splitlines()]
        assert any(s["name"] == "final_span" for s in spans)
        evs = [json.loads(l) for l in
               flight_out.read_text().splitlines()]
        assert any(e["event"] == "final_event" for e in evs)
    finally:
        tracing.clear_spans()
        flight.clear()
        if not was:
            flight.disable()
        telemetry.disable()


# ------------------------------------------- the decode steps' counter

def test_decode_steps_are_counted_by_where_their_tokens_came_from():
    """``mxtpu_gen_decode_steps_total``, labels `model` and `fed`: a
    greedy call's first step is fed from the host and every other the
    device array the forward before chose its tokens into; a sampled
    step is always fed from the host. The series render with both
    labels."""
    import numpy as np
    from incubator_mxnet_tpu.generate import GenerateEngine, GPTPagedLM
    from incubator_mxnet_tpu.models.gpt import gpt_config, gpt_param_shapes
    from incubator_mxnet_tpu.telemetry import catalog, export, metrics
    cfg = gpt_config({"vocab_size": 29, "units": 24, "num_layers": 1,
                      "num_heads": 2, "max_len": 32})
    rng = np.random.RandomState(0)
    lm = GPTPagedLM({n: (rng.randn(*s) * 0.05).astype(np.float32)
                     for n, s in gpt_param_shapes(cfg).items()}, cfg)
    telemetry.enable()
    try:
        metrics.reset()
        steps = catalog.gen_decode_steps
        assert steps.name == "mxtpu_gen_decode_steps_total"
        assert "fed=device" in steps.help
        GenerateEngine(lm, lm.make_cache(2, max_len=32), name="obs"
                       ).generate([[3, 5, 7], [9, 8]], max_new_tokens=5)
        assert steps.value(model="obs", fed="host") == 1
        assert steps.value(model="obs", fed="device") == 4
        GenerateEngine(lm, lm.make_cache(2, max_len=32), name="obs",
                       temperature=0.7).generate([[3, 5, 7]],
                                                 max_new_tokens=3)
        assert steps.value(model="obs", fed="host") == 1 + 3
        assert steps.value(model="obs", fed="device") == 4
        assert sorted(steps.labels()) == [
            (("fed", "device"), ("model", "obs")),
            (("fed", "host"), ("model", "obs"))]
        text = export.render_prometheus()
        assert 'mxtpu_gen_decode_steps_total{fed="device",model="obs"} 4' \
            in text
    finally:
        metrics.reset()
        telemetry.disable()


# --------------------------------------------------------------- debugz

def test_debugz_endpoints_in_process():
    telemetry.enable()
    was = flight.enabled()
    flight.enable()
    try:
        with telemetry.span("dbz_span"):
            pass
        flight.record("dbz_event")
        debugz.set_identity("tester", 3)
        srv = debugz.start(0)
        assert srv is debugz.start(0)        # idempotent
        debugz.set_status("models", lambda: ["m1"])
        port = debugz.port()

        def get(path):
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d%s" % (port, path),
                    timeout=10) as r:
                return r.status, r.read().decode("utf-8")

        st, body = get("/statusz")
        assert st == 200
        status = json.loads(body)
        assert status["role"] == "tester" and status["rank"] == 3
        assert status["models"] == ["m1"]
        st, body = get("/metrics")
        assert st == 200 and "# TYPE" in body
        st, body = get("/metrics.json")
        assert st == 200
        assert "mxtpu_rpc_retries_total" in json.loads(body)
        st, body = get("/tracez")
        assert st == 200
        assert any(s["name"] == "dbz_span"
                   for s in json.loads(body)["spans"])
        st, body = get("/threadz")
        assert st == 200 and "MainThread" in body
        st, body = get("/flightz")
        assert st == 200
        payload = json.loads(body)
        assert payload["enabled"] is True
        assert any(e["event"] == "dbz_event" for e in payload["events"])
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            get("/nonesuch")
        assert exc_info.value.code == 404
    finally:
        debugz.stop()
        flight.clear()
        if not was:
            flight.disable()
        telemetry.disable()
    assert not debugz.active()
    debugz.set_status("after_stop", 1)       # cheap no-op once stopped


# -------------------------------------------- two-process fleet drill

def _fleet_worker():
    """Runs inside the spawned worker: full drill against the live
    scheduler+server, returning everything the parent asserts on."""
    import tempfile
    os.environ["MXTPU_DEBUGZ_PORT"] = "0"
    tmpd = tempfile.mkdtemp(prefix="obsfleet_")
    flight_path = os.path.join(tmpd, "flight.jsonl")
    os.environ["MXTPU_FLIGHT_EXPORT"] = flight_path
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.kvstore.dist import KVStoreDist
    telemetry.enable()
    flight.enable()
    flight.install_crash_hooks()

    kv = KVStoreDist("dist_sync")
    kv.init("w", nd.ones((8,)))
    kv.push("w", nd.ones((8,)) * 2)
    out = nd.zeros((8,))
    kv.pull("w", out=out)

    scrape = aggregate.scrape()

    pages = {}
    port = debugz.port()
    for path in ("/metrics", "/metrics.json", "/statusz", "/tracez",
                 "/threadz", "/flightz"):
        with urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
            body = r.read().decode("utf-8")
        if path in ("/metrics.json", "/statusz", "/tracez", "/flightz"):
            parseable = isinstance(json.loads(body), dict)
        elif path == "/metrics":
            parseable = "# TYPE" in body
        else:
            parseable = "MainThread" in body
        pages[path] = {"status": r.status, "parseable": parseable}

    kv.close()      # records worker.bye into the flight ring
    reg = scrape["registry"]
    role_keys = set()
    for inst in reg.values():
        for skey in inst["series"]:
            role_keys.add(skey.split(",rank=", 1)[0])
    return {
        "pull": out.asnumpy().tolist(),
        "members": scrape["members"],
        "epoch": scrape["epoch"],
        "roles_seen": sorted(role_keys),
        "worker_pushes": (reg.get("mxtpu_kvstore_pushes_total") or
                          {}).get("series", {}),
        "server_requests": (reg.get("mxtpu_rpc_server_requests_total") or
                            {}).get("series", {}),
        "pages": pages,
        "flight_path": flight_path,
    }


def _fleet_worker_proc(queue):
    import jax
    jax.config.update("jax_platforms", "cpu")
    try:
        res = _fleet_worker()
    except Exception as e:  # surface failures to the test
        import traceback
        queue.put("ERROR: %s\n%s" % (e, traceback.format_exc()))
        return
    queue.put(res)
    queue.close()
    queue.join_thread()     # result delivered before the kill below
    # the drill's last act: die by SIGTERM so the crash hook dumps the
    # flight ring (worker.bye + sigterm) to MXTPU_FLIGHT_EXPORT
    os.kill(os.getpid(), signal.SIGTERM)


def test_aggregate_scrapes_fleet_and_killed_worker_leaves_flight_dump():
    from incubator_mxnet_tpu.kvstore.dist_server import (run_scheduler,
                                                         run_server,
                                                         SchedulerClient)
    import socket as _socket
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    os.environ.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "JAX_PLATFORM_NAME": "cpu", "JAX_PLATFORMS": "cpu",
        "MXTPU_METRICS": "1",   # scheduler/server enable at import
    })
    ctx = mp.get_context("spawn")
    procs = []
    try:
        sched = ctx.Process(target=run_scheduler, args=(port, 1, 1),
                            daemon=True)
        sched.start()
        procs.append(sched)
        time.sleep(0.3)
        srv = ctx.Process(target=run_server,
                          args=(("127.0.0.1", port), 1), daemon=True)
        srv.start()
        procs.append(srv)
        queue = ctx.Queue()
        w = ctx.Process(target=_fleet_worker_proc, args=(queue,),
                        daemon=True)
        w.start()
        res = queue.get(timeout=120)
        w.join(timeout=30)
    finally:
        os.environ.pop("MXTPU_METRICS", None)
        try:
            SchedulerClient(("127.0.0.1", port)).shutdown()
        except OSError:
            pass
        for p in procs:
            p.terminate()

    assert not (isinstance(res, str) and res.startswith("ERROR")), res
    assert res["pull"] == [2.0] * 8

    # one merged registry, every role answered and carries its label
    roles = {m["role"]: m["ok"] for m in res["members"]}
    assert roles == {"scheduler": True, "server": True, "worker": True}
    assert res["epoch"] >= 1
    assert "role=worker" in res["roles_seen"]
    assert "role=server" in res["roles_seen"]
    assert any("role=worker" in k for k in res["worker_pushes"])
    assert any("role=server" in k for k in res["server_requests"])

    # every debugz endpoint: 200 + parseable
    for path, page in res["pages"].items():
        assert page["status"] == 200, (path, page)
        assert page["parseable"], (path, page)

    # the SIGTERM'd worker left its flight recorder dump behind
    assert w.exitcode == -signal.SIGTERM
    deadline = time.time() + 10
    while not os.path.exists(res["flight_path"]) and \
            time.time() < deadline:
        time.sleep(0.1)
    lines = [json.loads(l) for l in
             open(res["flight_path"]).read().splitlines()]
    events = [l["event"] for l in lines]
    assert "worker.bye" in events        # membership departure
    assert "sigterm" in events           # the kill itself
    assert lines[-1]["attrs"]["reason"] == "sigterm"
    assert all(l["role"] == "worker" for l in lines
               if l["event"] == "worker.bye")
