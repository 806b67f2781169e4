#!/usr/bin/env python
"""loadstorm — trace-driven load-storm harness for the serving fleet.

Replays a deterministic traffic spec against live model servers and
emits the SLO report the ROADMAP names as the acceptance instrument for
the serving north-stars: per-stage latency percentiles (p50/p99/p999
for queue, end-to-end, and — for generative models — TTFT and
per-token TPOT straight from the new histograms), shed%, goodput, and
the N slowest head-sampled request timelines stitched from the fleet's
/tracez rings.

The traffic spec models the production shapes the batcher has to
survive, all reproducible from one seed:

  * heavy-tailed request sizes — lognormal prompt lengths, so most
    requests are small and the tail pins a decode slot for seconds;
  * a diurnal rate curve — sinusoidal multiplier over the run, the
    slow breathing load-balancers see across a day;
  * flash-crowd bursts — bounded windows where the arrival rate
    multiplies, the shed path's reason to exist;
  * mixed tenants — prefill-heavy (long prompt, few tokens),
    decode-heavy (short prompt, many tokens), and encode (classifier
    forward) traffic sharing one fleet.

Clients are CLOSED-LOOP: a fixed pool of workers walks the precomputed
arrival schedule; a worker sleeps until its request's arrival time and
fires, so when the fleet falls behind the backlog shows up as queue
wait and sheds, never as a silently stretched schedule.

    python tools/loadstorm.py --serving host:port [--serving host:port]
        --model gpt --duration 20 --rps 30 --seed 7 --sample 0.2
"""

import argparse
import json
import math
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.serving import (  # noqa: E402
    DeadlineExceeded, ServingClient, ServingError)
from incubator_mxnet_tpu.telemetry import tracing  # noqa: E402
from incubator_mxnet_tpu.telemetry.aggregate import hist_quantile  # noqa: E402

__all__ = ["default_spec", "build_schedule", "rate_at", "run_storm",
           "render_report", "main"]


# --------------------------------------------------------------- spec
def default_spec(**overrides):
    """The reference storm: one generative fleet, three tenants.

    Every knob is plain data so specs can live in JSON files; overrides
    merge shallowly (pass ``tenants=[...]`` to replace the mix)."""
    spec = {
        "seed": 7,
        "duration_s": 20.0,
        "clients": 8,
        "base_rps": 20.0,
        # diurnal curve: rate multiplier 1 + amplitude*sin(2*pi*t/period)
        "diurnal": {"amplitude": 0.5, "period_s": 20.0},
        # flash crowds: rate multiplied by `mult` inside the window
        "bursts": [{"at_frac": 0.55, "duration_frac": 0.15, "mult": 3.0}],
        "slo_ms": 2000.0,
        "tenants": [
            {"name": "chat", "model": "gpt", "kind": "decode_heavy",
             "weight": 0.5, "prompt_len": {"median": 8, "sigma": 0.6,
                                           "max": 48},
             "max_new": 12, "vocab": 64},
            {"name": "summarize", "model": "gpt", "kind": "prefill_heavy",
             "weight": 0.3, "prompt_len": {"median": 24, "sigma": 0.8,
                                           "max": 56},
             "max_new": 4, "vocab": 64},
            {"name": "classify", "model": "bert", "kind": "encode",
             "weight": 0.2, "seqlen": 16, "vocab": 64},
        ],
    }
    spec.update(overrides)
    return spec


def rate_at(spec, t):
    """Arrival rate (req/s) at offset ``t`` seconds into the storm:
    base * diurnal multiplier * any active flash-crowd multiplier."""
    rate = float(spec["base_rps"])
    di = spec.get("diurnal") or {}
    amp = float(di.get("amplitude", 0.0))
    period = float(di.get("period_s", 0.0) or 0.0)
    if amp and period > 0:
        rate *= 1.0 + amp * math.sin(2.0 * math.pi * t / period)
    dur = float(spec["duration_s"])
    for b in spec.get("bursts") or []:
        start = float(b["at_frac"]) * dur
        if start <= t < start + float(b["duration_frac"]) * dur:
            rate *= float(b["mult"])
    return max(rate, 0.0)


def _draw_len(rng, dist):
    """Heavy-tailed length draw: lognormal around ``median`` with shape
    ``sigma``, clipped to [1, max]."""
    v = rng.lognormal(math.log(float(dist["median"])),
                      float(dist["sigma"]))
    return int(min(max(v, 1), dist.get("max", 1 << 30)))


def build_schedule(spec):
    """Deterministic request list, ordered by arrival offset.

    Arrivals are a non-homogeneous Poisson process, thinned against the
    peak rate; each entry is ``{"t", "tenant", "model", "kind"}`` plus
    the drawn sizes. Same spec + seed => identical schedule."""
    rng = np.random.RandomState(int(spec["seed"]))
    dur = float(spec["duration_s"])
    di = spec.get("diurnal") or {}
    peak = float(spec["base_rps"]) * (1.0 + abs(float(
        di.get("amplitude", 0.0))))
    for b in spec.get("bursts") or []:
        peak = max(peak, peak * float(b["mult"]))
    peak = max(peak, 1e-9)
    tenants = spec["tenants"]
    weights = np.asarray([float(t.get("weight", 1.0)) for t in tenants])
    weights = weights / weights.sum()
    sched, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / peak)
        if t >= dur:
            break
        if rng.uniform() * peak > rate_at(spec, t):   # thinning
            continue
        tenant = tenants[int(rng.choice(len(tenants), p=weights))]
        ent = {"t": round(t, 6), "tenant": tenant["name"],
               "model": tenant["model"], "kind": tenant["kind"],
               "vocab": int(tenant.get("vocab", 64))}
        if tenant["kind"] == "encode":
            ent["seqlen"] = int(tenant.get("seqlen", 16))
        else:
            ent["prompt_len"] = _draw_len(rng, tenant["prompt_len"])
            ent["max_new"] = int(tenant.get("max_new", 8))
        sched.append(ent)
    return sched


# ---------------------------------------------------------- execution
def _tokens(ent, n):
    """Deterministic prompt content — content is irrelevant to load,
    so cheap and reproducible beats random."""
    return (np.arange(n, dtype=np.int32) % max(ent["vocab"] - 2, 1)) + 1


def _fire(client, ent, slo_ms):
    if ent["kind"] == "encode":
        ids = _tokens(ent, ent["seqlen"]).reshape(1, -1)
        client.infer(ent["model"], {"token_ids": ids}, deadline_ms=slo_ms)
        return 0
    out = client.decode(ent["model"], _tokens(ent, ent["prompt_len"]),
                        max_new_tokens=ent["max_new"],
                        deadline_ms=slo_ms)
    return int(np.asarray(out).size)


def run_storm(addrs, spec, timeout=120.0):
    """Replay ``spec`` against the replicas at ``addrs`` and return the
    SLO report dict (see render_report for the human form)."""
    sched = build_schedule(spec)
    slo_ms = float(spec.get("slo_ms") or 0) or None
    n_clients = int(spec["clients"])
    addrs = list(addrs)
    clients = [ServingClient(addrs[i % len(addrs):] + addrs[:i % len(addrs)],
                             timeout=timeout)
               for i in range(n_clients)]
    lock = threading.Lock()
    cursor = [0]
    results = []            # (ent, status, latency_s, tokens, trace_id)

    def worker(client):
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(sched):
                return
            ent = sched[i]
            delay = t_start + ent["t"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t0 = time.perf_counter()
            try:
                toks = _fire(client, ent, slo_ms)
                status = "ok"
            except DeadlineExceeded:
                toks, status = 0, "shed"
            except (ServingError, OSError) as exc:
                toks, status = 0, "error:%s" % type(exc).__name__
            lat = time.perf_counter() - t0
            with lock:
                results.append((ent, status, lat, toks,
                                client.last_trace_id))

    t_start = time.perf_counter() + 0.05
    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in clients]
    wall0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - wall0

    # fleet-side registries: one JSON metrics snapshot per replica
    registries = []
    for i, _addr in enumerate(addrs):
        try:
            registries.append(json.loads(
                clients[i % n_clients].metrics(fmt="json")))
        except (ServingError, OSError, ValueError):
            registries.append({})
    report = _build_report(spec, sched, results, wall, registries,
                           clients, addrs)
    for c in clients:
        c.close()
    return report


def _merged_series(registries, name):
    """Sum one histogram instrument's series across replicas, keyed by
    the series labels (count/sum/buckets added bucket-wise)."""
    out = {}
    for reg in registries:
        inst = reg.get(name) or {}
        for key, val in (inst.get("series") or {}).items():
            if not isinstance(val, dict):
                continue
            ent = out.setdefault(key, {"count": 0, "sum": 0.0,
                                       "buckets": {}})
            ent["count"] += val.get("count", 0)
            ent["sum"] += val.get("sum", 0.0)
            for edge, c in (val.get("buckets") or {}).items():
                ent["buckets"][edge] = ent["buckets"].get(edge, 0) + c
    return out


def _stage_quantiles(registries, name):
    """{series-labels: {p50_ms, p99_ms, p999_ms, count}} for one
    latency histogram, merged across the fleet."""
    out = {}
    for key, val in _merged_series(registries, name).items():
        ent = {"count": val["count"]}
        for q, label in ((0.5, "p50_ms"), (0.99, "p99_ms"),
                         (0.999, "p999_ms")):
            v = hist_quantile(val, q)
            ent[label] = round(v * 1e3, 3) if v is not None else None
        out[key] = ent
    return out


_STAGE_METRICS = {
    "queue": "mxtpu_serving_queue_seconds",
    "request": "mxtpu_serving_request_seconds",
    "ttft": "mxtpu_serving_ttft_seconds",
    "tpot": "mxtpu_serving_tpot_seconds",
    "prefill": "mxtpu_gen_prefill_seconds",
}


def _build_report(spec, sched, results, wall, registries, clients, addrs):
    ok = [r for r in results if r[1] == "ok"]
    shed = [r for r in results if r[1] == "shed"]
    errors = [r for r in results if r[1].startswith("error")]
    lat_ms = sorted(1e3 * r[2] for r in ok)

    def pct(p):
        if not lat_ms:
            return None
        return round(lat_ms[min(int(p * len(lat_ms)), len(lat_ms) - 1)], 3)

    tenants = {}
    for ent, status, lat, toks, _tid in results:
        t = tenants.setdefault(ent["tenant"], {"ok": 0, "shed": 0,
                                               "error": 0, "lat_ms": [],
                                               "tokens": 0})
        t["ok" if status == "ok" else
          "shed" if status == "shed" else "error"] += 1
        if status == "ok":
            t["lat_ms"].append(1e3 * lat)
            t["tokens"] += toks
    for t in tenants.values():
        ls = sorted(t.pop("lat_ms"))
        t["p50_ms"] = round(ls[len(ls) // 2], 3) if ls else None
        t["p99_ms"] = round(ls[min(int(0.99 * len(ls)),
                                   len(ls) - 1)], 3) if ls else None

    stages = {stage: _stage_quantiles(registries, metric)
              for stage, metric in _STAGE_METRICS.items()}
    stages = {k: v for k, v in stages.items() if v}

    # N slowest head-sampled journeys, stitched across every replica's
    # /tracez ring (a retried request can leave spans on two servers)
    sampled = sorted(((r[2], r[4]) for r in results if r[4]),
                     reverse=True)
    slow = []
    for lat, tid in sampled[:int(spec.get("slow_traces", 3))]:
        spans = []
        for i in range(len(addrs)):
            try:
                tl = clients[i % len(clients)].tracez(trace_id=tid)
                spans.extend(tl.get("spans") or [])
            except (ServingError, OSError):
                pass
        spans.extend(tracing.spans_for_trace(tid))   # client-side spans
        timeline = tracing.build_timeline(spans, trace_id=tid)
        slow.append({"trace_id": tid, "latency_ms": round(1e3 * lat, 3),
                     "spans": len(timeline["spans"]),
                     "text": tracing.render_timeline(timeline, width=100)})

    total = len(results)
    return {
        "spec": {k: spec[k] for k in ("seed", "duration_s", "clients",
                                      "base_rps", "slo_ms")},
        "requests": {"total": total, "ok": len(ok), "shed": len(shed),
                     "error": len(errors), "scheduled": len(sched)},
        "wall_s": round(wall, 3),
        "goodput_rps": round(len(ok) / wall, 3) if wall > 0 else None,
        "shed_pct": round(100.0 * len(shed) / max(total, 1), 2),
        "tokens_generated": sum(r[3] for r in ok),
        "client_latency_ms": {"p50": pct(0.5), "p99": pct(0.99),
                              "p999": pct(0.999)},
        "stages": stages,
        "tenants": tenants,
        "slow_traces": slow,
    }


# ----------------------------------------------------------- reporting
def render_report(report):
    lines = ["== loadstorm SLO report =="]
    req = report["requests"]
    lines.append("requests: %d total  %d ok  %d shed (%.2f%%)  %d error"
                 % (req["total"], req["ok"], req["shed"],
                    report["shed_pct"], req["error"]))
    lines.append("goodput: %s req/s over %.1fs   tokens: %d"
                 % (report["goodput_rps"], report["wall_s"],
                    report["tokens_generated"]))
    cl = report["client_latency_ms"]
    lines.append("client e2e ms: p50=%s p99=%s p999=%s"
                 % (cl["p50"], cl["p99"], cl["p999"]))
    lines.append("-- per-stage (fleet histograms, ms) --")
    for stage, series in sorted(report["stages"].items()):
        for key, ent in sorted(series.items()):
            lines.append("  %-8s %-28s p50=%-10s p99=%-10s p999=%-10s n=%d"
                         % (stage, key or "-", ent["p50_ms"],
                            ent["p99_ms"], ent["p999_ms"], ent["count"]))
    lines.append("-- per-tenant --")
    for name, t in sorted(report["tenants"].items()):
        lines.append("  %-12s ok=%-5d shed=%-5d err=%-4d p50=%s p99=%s "
                     "tokens=%d" % (name, t["ok"], t["shed"], t["error"],
                                    t["p50_ms"], t["p99_ms"], t["tokens"]))
    if report["slow_traces"]:
        lines.append("-- slowest sampled journeys --")
        for s in report["slow_traces"]:
            lines.append("  [%.1f ms] %s" % (s["latency_ms"],
                                             s["trace_id"]))
            for ln in s["text"].splitlines():
                lines.append("    " + ln)
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--serving", action="append", required=True,
                    help="model-server host:port (repeat per replica)")
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--rps", type=float, default=20.0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slo-ms", type=float, default=2000.0)
    ap.add_argument("--spec", help="JSON spec file (overrides the flags)")
    ap.add_argument("--gpt-model", default="gpt",
                    help="served name of the generative model")
    ap.add_argument("--bert-model", default=None,
                    help="served name of the encode model (omit to send "
                         "generative traffic only)")
    ap.add_argument("--slow", type=int, default=3,
                    help="slowest sampled timelines to include")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON instead of text")
    args = ap.parse_args(argv)

    if args.spec:
        with open(args.spec) as f:
            spec = default_spec(**json.load(f))
    else:
        spec = default_spec(seed=args.seed, duration_s=args.duration,
                            base_rps=args.rps, clients=args.clients,
                            slo_ms=args.slo_ms)
        for t in spec["tenants"]:
            t["model"] = (args.gpt_model if t["kind"] != "encode"
                          else args.bert_model)
        if args.bert_model is None:
            spec["tenants"] = [t for t in spec["tenants"]
                               if t["kind"] != "encode"]
    spec["slow_traces"] = args.slow
    report = run_storm(args.serving, spec)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(render_report(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
