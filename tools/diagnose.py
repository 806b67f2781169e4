#!/usr/bin/env python
"""Environment diagnostics for bug reports and support.

Reference parity: tools/diagnose.py (platform/python/deps/build-flags
dump). TPU-native additions: the JAX backend and device inventory, the
XLA virtual-device flags, whether the native C++ runtime library is
built, and the framework's runtime feature flags (runtime.Features).

Usage: python tools/diagnose.py
"""

import os
import platform
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def section(title):
    print("----------" + title + "----------")


def main():
    section("Platform Info")
    print("Platform     :", platform.platform())
    print("machine      :", platform.machine())
    print("processor    :", platform.processor() or "n/a")

    section("Python Info")
    print("version      :", sys.version.replace("\n", " "))
    print("executable   :", sys.executable)

    section("Dependency Versions")
    for mod in ("numpy", "jax", "jaxlib"):
        try:
            m = __import__(mod)
            print("%-12s : %s" % (mod, getattr(m, "__version__", "?")))
        except ImportError:
            print("%-12s : NOT INSTALLED" % mod)

    section("JAX Backend")
    try:
        import jax
        print("backend      :", jax.default_backend())
        devs = jax.devices()
        print("devices      : %d x %s" % (len(devs), devs[0].platform))
        for d in devs[:8]:
            print("  -", d)
        print("XLA_FLAGS    :", os.environ.get("XLA_FLAGS", "(unset)"))
        print("JAX_PLATFORMS:", os.environ.get("JAX_PLATFORMS", "(unset)"))
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("jax unavailable:", e)

    section("Framework")
    try:
        import incubator_mxnet_tpu as mx
        print("version      :", getattr(mx, "__version__", "?"))
        from incubator_mxnet_tpu import native
        print("native lib   :", "built" if native.available() else "NOT built"
              " (run `make -C native`)")
        from incubator_mxnet_tpu import runtime
        feats = runtime.Features()
        on = [f for f in feats.keys() if feats.is_enabled(f)]
        print("features on  :", ", ".join(sorted(on)) or "(none)")
    except Exception as e:  # noqa: BLE001
        print("framework import failed:", e)

    section("Lint (graphlint)")
    # a dirty tree is exactly the kind of context a bug report needs:
    # embed the same findings `python -m tools.mxlint` would print
    try:
        from tools.mxlint import lint_paths
        pkg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "incubator_mxnet_tpu")
        findings = lint_paths([pkg])
        print("mxlint       :", "clean" if not findings
              else "%d finding(s)" % len(findings))
        for f in findings[:20]:
            print("  -", f.format())
        if len(findings) > 20:
            print("  ... %d more (run python -m tools.mxlint)" %
                  (len(findings) - 20))
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("mxlint failed:", e)

    section("Concurrency")
    # the two-pronged lock story: the interprocedural static pass over
    # the package (lock-order cycles, locks held across blocking ops,
    # orphan daemon threads) plus the live lockdep witness state when
    # embedded in a running job with MXTPU_LOCKDEP=1
    try:
        from incubator_mxnet_tpu.analysis import analyze_package
        from incubator_mxnet_tpu.analysis.concurrency import (
            CONCURRENCY_RULES, build_program)
        pkg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "incubator_mxnet_tpu")
        sources = []
        for root_, dirs, files in os.walk(pkg):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for fn in sorted(files):
                if fn.endswith(".py"):
                    p = os.path.join(root_, fn)
                    with open(p, encoding="utf-8") as fh:
                        sources.append((p, fh.read()))
        prog = build_program(sources,
                             root=os.path.dirname(os.path.abspath(pkg)))
        n_locks = sum(len(c.locks) for m in prog.modules.values()
                      for c in m.classes.values())
        n_threads = sum(len(c.threads) for m in prog.modules.values()
                        for c in m.classes.values())
        print("rules        :", ", ".join(sorted(CONCURRENCY_RULES)))
        print("inventory    : %d lock-owning attrs, %d thread attrs, "
              "%d order edges" % (n_locks, n_threads,
                                  len(prog.lock_order_edges())))
        findings = analyze_package(pkg)
        print("static pass  :", "clean" if not findings
              else "%d finding(s)" % len(findings))
        for f in findings[:20]:
            print("  -", f.format())
        from incubator_mxnet_tpu.telemetry import lockdep
        print("lockdep      :", lockdep.statusz_entry())
        for v in lockdep.violations()[:3]:
            print(lockdep.format_violation(v))
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("concurrency analysis failed:", e)

    section("Telemetry")
    # live metrics snapshot: in-process state when diagnose runs embedded
    # (post-mortem in a failing job), plus the exporter configuration
    try:
        from incubator_mxnet_tpu import telemetry
        print("enabled      :", telemetry.enabled())
        print("export       :",
              os.environ.get("MXTPU_METRICS_EXPORT", "(unset)"))
        snap = telemetry.snapshot()
        nonzero = {k: v["series"] for k, v in snap.items() if v["series"]}
        print("instruments  : %d registered, %d with data"
              % (len(snap), len(nonzero)))
        for name, series in sorted(nonzero.items())[:20]:
            for labels, val in sorted(series.items())[:4]:
                if isinstance(val, dict):   # histogram: skip bucket noise
                    val = "count=%s sum=%.6g" % (val["count"], val["sum"])
                print("  - %s{%s} = %s" % (name, labels, val))
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("telemetry unavailable:", e)

    section("Memory")
    # memz plane: live device HBM + host RSS read on demand (works even
    # with the plane off — only the sampled watermarks/programs need
    # MXTPU_MEMZ=1 in the examined process), per-program static
    # footprints from the compile seam, and the paged-KV block census
    try:
        from incubator_mxnet_tpu.telemetry import memz as _memz
        print("enabled      :", _memz.enabled(),
              "(export: %s)" % (_memz.export_path() or "unset"))
        for d in _memz.device_stats()[:8]:
            lim = d.get("bytes_limit")
            print("  - %s: in_use=%.1f MB%s peak=%.1f MB [%s]"
                  % (d["device"], d["bytes_in_use"] / 1e6,
                     " limit=%.1f MB" % (lim / 1e6) if lim else "",
                     (d.get("peak_bytes_in_use") or 0) / 1e6,
                     d["source"]))
        host = _memz.host_memory()
        print("host rss     : %.1f MB (peak %.1f MB)"
              % (host["rss_bytes"] / 1e6, host["peak_rss_bytes"] / 1e6))
        marks = _memz.memz_dict().get("watermarks") or {}
        if marks:
            print("watermarks   :", ", ".join(
                "%s=%.0f" % (k, v) for k, v in sorted(marks.items())))
        progs = _memz.programs()
        if progs:
            print("programs     : %d captured" % len(progs))
            for name, row in sorted(
                    progs.items(),
                    key=lambda kv: -(kv[1].get("total_bytes") or 0))[:10]:
                print("  - %-32s total=%.2f MB (args=%.2f out=%.2f "
                      "temp=%.2f)"
                      % (name, (row.get("total_bytes") or 0) / 1e6,
                         (row.get("argument_bytes") or 0) / 1e6,
                         (row.get("output_bytes") or 0) / 1e6,
                         (row.get("temp_bytes") or 0) / 1e6))
        for pool in _memz.kv_census():
            print("  kv pool %-12s: %d/%d blocks used (peak %d, "
                  "free %.0f%%, frag %.2f), %d/%d slots"
                  % (pool["name"], pool["blocks_in_use"],
                     pool["num_blocks"], pool["blocks_in_use_peak"],
                     100.0 * pool["free_fraction"],
                     pool["fragmentation"], pool["slots_in_use"],
                     pool["slots"]))
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("memz unavailable:", e)

    section("Health")
    # health plane: in-process evaluator state when embedded in a live
    # job; with a reachable scheduler, a one-shot fleet verdict via
    # tools/healthcheck.py semantics
    try:
        from incubator_mxnet_tpu.telemetry import health as _health
        print("enabled      :", _health.enabled())
        if _health.enabled():
            v = _health.verdict()
            print("level        :", v["level"])
            for e in v.get("firing", []):
                print("  [%s] %s value=%s" % (e["level"], e["rule"],
                                              e.get("value")))
        elif os.environ.get("DMLC_PS_ROOT_URI"):
            from tools import healthcheck as _hc
            v, _ = _hc.run(samples=2, interval=1.0, timeout=3.0)
            print("fleet verdict:", v["level"],
                  "(%d firing / %d rules)" % (len(v["firing"]),
                                              len(v["rules"])))
            for e in v.get("firing", [])[:10]:
                print("  [%s] %s value=%s" % (e["level"], e["rule"],
                                              e.get("value")))
        else:
            print("(disabled — set MXTPU_HEALTH=1 for the in-process "
                  "loop, or DMLC_PS_ROOT_URI/PORT for a fleet verdict)")
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("health unavailable:", e)

    section("Serving")
    # live serving-plane probe: point MXTPU_SERVE_ADDR at a ModelServer
    # ("host:port") and diagnose reports its models and SLO quantiles
    addr = os.environ.get("MXTPU_SERVE_ADDR", "")
    if not addr:
        print("(no server configured — set MXTPU_SERVE_ADDR=host:port)")
    else:
        try:
            host, port = addr.rsplit(":", 1)
            from incubator_mxnet_tpu.serving import ServingClient
            c = ServingClient((host, int(port)), timeout=3.0)
            try:
                ping = c.ping()
                print("server       :", addr, "up,",
                      "%d model(s)" % len(ping["models"]))
                for name, ent in sorted(c.stats().items()):
                    reqs = ent.get("requests", {})
                    print("  - %s (%s): ok=%s shed=%s error=%s p50=%ss "
                          "p99=%ss occupancy=%s"
                          % (name, ent.get("family", "?"),
                             reqs.get("ok"), reqs.get("shed"),
                             reqs.get("error"), ent.get("p50_s", "n/a"),
                             ent.get("p99_s", "n/a"),
                             ent.get("mean_batch_occupancy", "n/a")))
            finally:
                c.close()
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            print("server       : %s unreachable (%s)" % (addr, e))

    section("Deployment")
    # live weight-push view: per-replica serving generation and drain
    # state (MXTPU_SERVE_ADDR takes a comma-separated replica list), and
    # whether the fleet agrees — skew here means a rollout stalled or a
    # replica was left behind
    addrs = [a.strip() for a in
             os.environ.get("MXTPU_SERVE_ADDR", "").split(",") if a.strip()]
    if not addrs:
        print("(no server configured — set MXTPU_SERVE_ADDR=host:port"
              "[,host:port...])")
    else:
        by_model = {}
        for a in addrs:
            try:
                host, port = a.rsplit(":", 1)
                from incubator_mxnet_tpu.serving import ServingClient
                c = ServingClient((host, int(port)), timeout=3.0)
                try:
                    for name, ent in sorted(c.generation().items()):
                        print("  - %s %s: generation=%s%s"
                              % (a, name, ent.get("generation"),
                                 " DRAINING" if ent.get("draining")
                                 else ""))
                        by_model.setdefault(name, set()).add(
                            ent.get("generation"))
                finally:
                    c.close()
            except Exception as e:  # noqa: BLE001
                print("  - %s unreachable (%s)" % (a, e))
        for name, gens in sorted(by_model.items()):
            if len(gens) > 1:
                print("  !! generation skew on %r: %s — rollout stalled?"
                      % (name, sorted(gens)))

    section("Compile Cache")
    # persistent compile cache: config + entry inventory of the
    # MXTPU_COMPILE_CACHE_DIR this process would use
    try:
        from incubator_mxnet_tpu.compilecache import store as ccstore
        if not ccstore.enabled():
            print("(disabled — set MXTPU_COMPILE_CACHE_DIR to enable)")
        else:
            st = ccstore.default_store()
            stats = st.stats()
            print("dir          :", stats["dir"])
            print("entries      : %d (%.1f MB of %.0f MB cap)"
                  % (stats["entries"], stats["bytes"] / 1e6,
                     stats["cap_bytes"] / 1e6))
            import json as _json
            shown = 0
            for path, size, _mtime in sorted(
                    st._entries(), key=lambda e: -e[2]):
                if shown >= 10:
                    print("  ... (%d more)" % (stats["entries"] - shown))
                    break
                with open(path, "rb") as f:
                    hdr = _json.loads(f.readline().decode("utf-8"))
                print("  - %-32s %8.2f MB  saved %.1fs"
                      % (hdr.get("name") or os.path.basename(path),
                         size / 1e6, hdr.get("compile_seconds") or 0))
                shown += 1
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("compile cache unavailable:", e)

    section("Donation / Layout")
    # compiled-step audit on a tiny probe model: does XLA alias every
    # donated buffer (params/aux/opt state updated in place), and does
    # the step loop stay free of hidden device->host syncs?
    if os.environ.get("MXTPU_DIAG_DONATION", "1") == "0":
        print("(skipped — MXTPU_DIAG_DONATION=0)")
    else:
        try:
            import numpy as _dl_np
            import jax as _dl_jax
            import jax.numpy as _dl_jnp
            import incubator_mxnet_tpu as _dl_mx
            from incubator_mxnet_tpu import gluon as _dl_gluon, nd as _dl_nd
            from incubator_mxnet_tpu.parallel import (make_mesh as _dl_mesh,
                                                      ShardedTrainer
                                                      as _DLTrainer)
            from incubator_mxnet_tpu.parallel.audits import \
                donation_layout_audit

            _dl_np.random.seed(0)
            net = _dl_gluon.nn.HybridSequential(prefix="diag_")
            with net.name_scope():
                net.add(_dl_gluon.nn.Dense(16, activation="relu",
                                           in_units=8),
                        _dl_gluon.nn.Dense(4, in_units=16))
            net.initialize(_dl_mx.init.Xavier())

            def _dl_loss(out, label):
                logp = _dl_jax.nn.log_softmax(out, axis=-1)
                return -_dl_jnp.take_along_axis(
                    logp, label.astype(_dl_jnp.int32)[:, None],
                    axis=-1).mean()

            tr = _DLTrainer(net, _dl_loss,
                            _dl_mesh({"dp": 1},
                                     devices=_dl_jax.devices()[:1]),
                            optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3})
            X = _dl_nd.array(_dl_np.random.rand(8, 8).astype("float32"))
            y = _dl_nd.array(_dl_np.random.randint(
                0, 4, (8,)).astype("int32"))
            tr.step(X, y)   # warm: states + first compile
            rep = donation_layout_audit(tr, X, y)
            print("donated      : %d leaves, %.1f KB"
                  % (rep["donated_leaves"], rep["donated_bytes"] / 1e3))
            print("aliased      : %d in-place, %d copied (%.1f KB lost)"
                  % (rep["aliased"], rep["unaliased"],
                     rep["unaliased_bytes"] / 1e3))
            for n in rep["unaliased_names"][:8]:
                print("  copy NOT elided:", n)
            print("host syncs   : %d per step (contract: 0)"
                  % rep["host_syncs_per_step"])
            coll = {k: v for k, v in rep["collectives"].items() if v}
            print("collectives  :",
                  ", ".join("%s=%d" % kv for kv in sorted(coll.items()))
                  or "(none)")
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            print("donation audit failed:", e)

    section("Stream")
    # live data-plane probe: point MXTPU_STREAM_ADDR at a
    # StreamCoordinator ("host:port") and diagnose reports its shard
    # assignment, worker roster, and quarantine state
    saddr = os.environ.get("MXTPU_STREAM_ADDR", "")
    if not saddr:
        print("(no coordinator configured — set "
              "MXTPU_STREAM_ADDR=host:port)")
    else:
        try:
            host, port = saddr.rsplit(":", 1)
            from incubator_mxnet_tpu.kvstore.rpc import request
            meta, _ = request((host, int(port)), {"op": "stream.stats"},
                              timeout=3.0)
            if meta.get("error"):
                raise RuntimeError(meta["error"])
            stats = meta.get("stats") or {}
            cfg = meta.get("config") or {}
            print("coordinator  :", saddr, "up")
            print("  - seed=%s batch_size=%s window=%s version=%s"
                  % (cfg.get("seed"), cfg.get("batch_size"),
                     cfg.get("window"), stats.get("version")))
            quar = stats.get("quarantined") or []
            print("  - shards: %s (%d quarantined)"
                  % (stats.get("shards", "?"), len(quar)))
            for uri in quar[:5]:
                print("    quarantined: %s" % uri)
            print("  - workers: %s, reassignments: %s"
                  % (stats.get("workers", "?"),
                     stats.get("reassigned_total", "?")))
            mmeta, _ = request((host, int(port)), {"op": "stream.members"},
                               timeout=3.0)
            for wid, waddr in sorted(
                    (mmeta.get("workers") or {}).items()):
                print("  worker %-6s: %s:%s" % (wid, waddr[0], waddr[1]))
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            print("coordinator  : %s unreachable (%s)" % (saddr, e))

    section("Debugz")
    # live-process probe: point MXTPU_DEBUGZ_PORT at a process that
    # started its debug server and diagnose reports its /statusz
    dport = os.environ.get("MXTPU_DEBUGZ_PORT", "")
    if not dport or dport == "0":
        print("(no port configured — set MXTPU_DEBUGZ_PORT to a live "
              "process's debugz port; 0 means auto-bind, see that "
              "process's stderr for the chosen port)")
    else:
        url = "http://127.0.0.1:%s/statusz" % dport
        try:
            import json as _json
            from urllib.request import urlopen
            with urlopen(url, timeout=3) as resp:
                status = _json.loads(resp.read().decode("utf-8"))
            print("statusz      :", url, "up")
            for key in ("role", "rank", "pid", "uptime_s", "epoch",
                        "models", "jax_devices"):
                if key in status:
                    print("  - %s: %s" % (key, status[key]))
            print("  endpoints: /metrics /metrics.json /statusz /tracez "
                  "/threadz /flightz /alertz")
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            print("statusz      : %s unreachable (%s)" % (url, e))

    section("Membership")
    # elastic-fabric probe: when a parameter-server scheduler is
    # reachable (DMLC_PS_ROOT_URI/PORT), report its epoch-numbered
    # membership view — who is in the quorum right now
    uri = os.environ.get("DMLC_PS_ROOT_URI", "")
    sport = os.environ.get("DMLC_PS_ROOT_PORT", "")
    if not uri or not sport:
        print("(no scheduler configured — set DMLC_PS_ROOT_URI and "
              "DMLC_PS_ROOT_PORT)")
    else:
        try:
            from incubator_mxnet_tpu.kvstore.dist_server import \
                SchedulerClient
            sc = SchedulerClient((uri, int(sport)))
            try:
                mem = sc.membership(timeout=3)
                print("scheduler    : %s:%s up" % (uri, sport))
                print("epoch        :", mem["epoch"])
                print("quorum       :", mem["quorum"], "worker(s)")
                print("elastic      :",
                      "on" if os.environ.get("MXTPU_ELASTIC") == "1"
                      else "off (fixed launch-time membership)")
                for r, a in sorted(mem["workers"].items()):
                    print("  worker %-4d: %s:%s" % (r, a[0], a[1]))
                for r, a in sorted(mem["servers"].items()):
                    print("  server %-4d: %s:%s" % (r, a[0], a[1]))
            finally:
                sc._conn.close()
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            print("scheduler    : %s:%s unreachable (%s)"
                  % (uri, sport, e))

    section("Threads")
    # hang post-mortem: every live thread's stack plus watchdog state —
    # the same rendering the resilience watchdog dumps on a deadline
    try:
        from incubator_mxnet_tpu.resilience import watchdog as wd
        w = wd.current()
        print("watchdog     :", "installed" if w is not None else "(none)")
        if w is not None and w.fired:
            for phase, tname, overdue in w.fired:
                print("  fired      : phase %r on %r (+%.1fs)"
                      % (phase, tname, overdue))
        print(wd.format_thread_stacks())
    except Exception as e:  # noqa: BLE001 — diagnostics must not crash
        print("thread dump failed:", e)

    section("Environment Variables (MXTPU_*/MXNET_*)")
    hits = {k: v for k, v in sorted(os.environ.items())
            if k.startswith(("MXTPU_", "MXNET_"))}
    for k, v in hits.items():
        print("%-28s = %s" % (k, v))
    if not hits:
        print("(none set)")


if __name__ == "__main__":
    main()
