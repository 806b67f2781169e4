#!/usr/bin/env python
"""mxtop — live terminal dashboard over the fleet telemetry scrape.

Walks the scheduler's membership view via telemetry.aggregate.scrape()
once per interval and renders per-member rates: kvstore push bytes/s,
rpc retries, compile seconds, guardian skips, membership epoch, the
memz MEM column set (HBM% = worst device fill from
mxtpu_mem_hbm_used_fraction, KVFREE = tightest paged-KV pool free
fraction, FRAG = worst pool fragmentation; "-" while the memz plane is
off), and —
for model servers passed with --serving — QPS, p99 latency, batch
occupancy, shed counts, the generative LATENCY column set (TTFT
p50/p99 and per-token TPOT p99 in ms, from the fleet-merged
mxtpu_serving_ttft_seconds / mxtpu_serving_tpot_seconds histograms),
and (for generative families) committed tokens/sec plus the
speculative-decode accept-rate. Counters are turned into rates by
diffing consecutive scrapes.

With --stream (or MXTPU_STREAM_ADDR) the frame adds an input-plane
rollup — records/s, shard reassignments, quarantined shards, fetch-wait
p99 — plus a corrupt-shard attribution table built from the uri-labeled
recordio resync/quarantine counters.

    python tools/mxtop.py                      # scheduler from DMLC env
    python tools/mxtop.py --scheduler host:port --serving host:port
    python tools/mxtop.py --stream host:port   # + data-plane rollup
    python tools/mxtop.py --once               # one frame, no clearing
    python tools/mxtop.py --once --json        # raw scrape, see below

--once --json prints the raw scrape dict instead of the rendered frame,
the stable machine interface scripts should parse:

    {"epoch": int | null,            # PS membership epoch
     "quorum": bool | null,
     "members": [{"role": str, "rank": int|str, "addr": "host:port",
                  "ok": bool, "error": str (only when not ok)}],
     "registry": {metric_name: {"kind": "counter"|"gauge"|"histogram",
                                "help": str,
                                "series": {labels: value}}}}

Every series key is prefixed "role=...,rank=..." (the member it came
from) followed by the instrument's own labels. Counter/gauge values are
numbers; histogram values are {"count", "sum", "buckets": {edge:
cumulative_count}} and, when a head-sampled request landed in a bucket,
"exemplars": {edge: {"trace_id", "value", "ts"}} — that trace_id keys
straight into the member's /tracez?trace_id= journey lookup.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from incubator_mxnet_tpu.telemetry import aggregate  # noqa: E402
from incubator_mxnet_tpu.telemetry import catalog, health, history  # noqa: E402


def _series_sum(registry, name, where=None):
    """Sum of a (counter) instrument's series values, optionally
    filtered by a label-substring predicate on the series key."""
    inst = registry.get(name) or {}
    total = 0.0
    for key, val in (inst.get("series") or {}).items():
        if where and where not in key:
            continue
        if isinstance(val, dict):      # histogram: use the count
            total += val.get("count", 0)
        else:
            total += val
    return total


def _member_key(role, rank):
    return "role=%s,rank=%s" % (role, rank)


def _series_agg(registry, name, where, agg):
    """min/max over a gauge instrument's series values matching the
    label-substring filter; None when the member exports no series
    (memz plane off, or no paged pools live)."""
    vals = [v for k, v in ((registry.get(name) or {}).get("series")
                           or {}).items()
            if (not where or where in k) and not isinstance(v, dict)]
    return agg(vals) if vals else None


def _merged_quantile(registry, name, where, q):
    """Quantile over ONE logical histogram merged across every member's
    matching series (bucket-wise sum — replicas of a model each carry
    their own series in the role/rank-prefixed registry)."""
    merged = {"count": 0, "sum": 0.0, "buckets": {}}
    for skey, sval in ((registry.get(name) or {}).get("series")
                       or {}).items():
        if where not in skey or not isinstance(sval, dict):
            continue
        merged["count"] += sval.get("count", 0)
        merged["sum"] += sval.get("sum", 0.0)
        for edge, c in (sval.get("buckets") or {}).items():
            merged["buckets"][edge] = merged["buckets"].get(edge, 0) + c
    if not merged["count"]:
        return None
    return aggregate.hist_quantile(merged, q)


def _rates(prev, cur, elapsed):
    if prev is None or elapsed <= 0:
        return {k: 0.0 for k in cur}
    return {k: max(0.0, (cur[k] - prev.get(k, 0.0)) / elapsed)
            for k in cur}


def frame(scheduler, serving, prev_totals, prev_ts, stream=None,
          health_state=None):
    scrape = aggregate.scrape(scheduler=scheduler, serving=serving,
                              stream=stream)
    reg = scrape["registry"]
    now = time.monotonic()
    elapsed = (now - prev_ts) if prev_ts else 0.0

    lines = []
    lines.append("mxtop  %s  epoch=%s quorum=%s  members=%d (%d up)"
                 % (time.strftime("%H:%M:%S"), scrape["epoch"],
                    scrape["quorum"], len(scrape["members"]),
                    sum(1 for m in scrape["members"] if m["ok"])))
    lines.append("-" * 78)
    lines.append("%-10s %-5s %-21s %12s %8s %9s %7s %6s %7s %6s"
                 % ("ROLE", "RANK", "ADDR", "PUSH B/s", "RETRY/s",
                    "COMPILE s", "SKIPS", "HBM%", "KVFREE", "FRAG"))

    totals = {}
    for m in scrape["members"]:
        key = _member_key(m["role"], m["rank"])
        if not m["ok"]:
            lines.append("%-10s %-5s %-21s  DOWN: %s"
                         % (m["role"], m["rank"], m["addr"],
                            m.get("error", "?")[:40]))
            continue
        totals[key + "/push_bytes"] = _series_sum(
            reg, "mxtpu_kvstore_push_bytes_total", where=key)
        totals[key + "/retries"] = _series_sum(
            reg, "mxtpu_rpc_retries_total", where=key)
        compile_s = _series_sum(
            reg, "mxtpu_jit_compile_seconds_total", where=key)
        skips = _series_sum(
            reg, "mxtpu_guard_skipped_steps_total", where=key)
        r = _rates({k: prev_totals.get(k, 0.0) for k in totals},
                   totals, elapsed)
        # MEM column set (memz plane): worst device HBM fill, tightest
        # paged-KV pool, worst pool fragmentation — "-" when the member
        # runs with MXTPU_MEMZ off or owns no paged pools
        hbm = _series_agg(reg, "mxtpu_mem_hbm_used_fraction", key, max)
        kvfree = _series_agg(reg, "mxtpu_gen_kv_free_fraction", key, min)
        frag = _series_agg(reg, "mxtpu_gen_kv_fragmentation", key, max)
        lines.append("%-10s %-5s %-21s %12.0f %8.2f %9.1f %7.0f %6s %7s %6s"
                     % (m["role"], m["rank"], m["addr"],
                        r.get(key + "/push_bytes", 0.0),
                        r.get(key + "/retries", 0.0), compile_s, skips,
                        "%.0f" % (100.0 * hbm) if hbm is not None else "-",
                        "%.2f" % kvfree if kvfree is not None else "-",
                        "%.2f" % frag if frag is not None else "-"))

    # serving rollup (per model): QPS / p99 / occupancy / shed, plus
    # the generative-engine columns — TOK/s (rate of committed decode+
    # prefill tokens) and ACC% (speculation accept-rate) — which stay
    # "-" for encoder-only models that never bump the gen_* counters
    req = reg.get("mxtpu_serving_requests_total") or {}
    models = sorted({seg.split("model=", 1)[1].split(",")[0]
                     for seg in (req.get("series") or {})
                     if "model=" in seg})
    if models:
        lines.append("")
        lines.append("%-16s %8s %9s %8s %8s %8s %10s %7s %9s %6s"
                     % ("MODEL", "QPS", "p99 ms", "TTFT50", "TTFT99",
                        "TPOT99", "OCCUPANCY", "SHED", "TOK/s", "ACC%"))
        occ = reg.get("mxtpu_serving_batch_occupancy") or {}
        for model in models:
            sel = "model=%s" % model
            ok = _series_sum(reg, "mxtpu_serving_requests_total",
                             where=sel + ",status=ok")
            totals["serve/%s/ok" % model] = ok
            qps = _rates({("serve/%s/ok" % model):
                          prev_totals.get("serve/%s/ok" % model, 0.0)},
                         {("serve/%s/ok" % model): ok},
                         elapsed)["serve/%s/ok" % model]
            p99 = _merged_quantile(reg, "mxtpu_serving_request_seconds",
                                   sel, 0.99)
            # generative LATENCY set: time-to-first-token and per-token
            # gap, merged across replicas; "-" for encoder-only models
            ttft50 = _merged_quantile(reg, "mxtpu_serving_ttft_seconds",
                                      sel, 0.5)
            ttft99 = _merged_quantile(reg, "mxtpu_serving_ttft_seconds",
                                      sel, 0.99)
            tpot99 = _merged_quantile(reg, "mxtpu_serving_tpot_seconds",
                                      sel, 0.99)
            occ_mean = None
            for skey, sval in (occ.get("series") or {}).items():
                if sel in skey and isinstance(sval, dict) \
                        and sval.get("count"):
                    occ_mean = sval["sum"] / sval["count"]
            shed = _series_sum(reg, "mxtpu_serving_shed_total", where=sel)
            toks = _series_sum(reg, "mxtpu_gen_tokens_committed_total",
                               where=sel)
            tok_key = "serve/%s/tokens" % model
            tok_rate = None
            if toks:
                totals[tok_key] = toks
                tok_rate = _rates({tok_key: prev_totals.get(tok_key,
                                                            0.0)},
                                  {tok_key: toks}, elapsed)[tok_key]
            proposed = _series_sum(reg, "mxtpu_gen_spec_proposed_total",
                                   where=sel)
            accepted = _series_sum(reg, "mxtpu_gen_spec_accepted_total",
                                   where=sel)
            acc = 100.0 * accepted / proposed if proposed else None

            def _ms(v):
                return "%.1f" % (v * 1e3) if v is not None else "-"
            lines.append("%-16s %8.1f %9s %8s %8s %8s %10s %7.0f %9s %6s"
                         % (model, qps, _ms(p99),
                            _ms(ttft50), _ms(ttft99), _ms(tpot99),
                            "%.1f" % occ_mean if occ_mean is not None
                            else "-", shed,
                            "%.0f" % tok_rate if tok_rate is not None
                            else "-",
                            "%.1f" % acc if acc is not None else "-"))

    # stream rollup: input-plane throughput + failure accounting
    served = _series_sum(reg, "mxtpu_stream_batches_served_total")
    recs = _series_sum(reg, "mxtpu_stream_records_served_total")
    if served or recs:
        totals["stream/records"] = recs
        rps = _rates({"stream/records": prev_totals.get("stream/records",
                                                        0.0)},
                     {"stream/records": recs}, elapsed)["stream/records"]
        reassigned = _series_sum(
            reg, "mxtpu_stream_shard_reassignments_total")
        quarantined = _series_sum(
            reg, "mxtpu_stream_quarantined_shards_total")
        wait = None
        for sval in ((reg.get("mxtpu_stream_client_wait_seconds") or {})
                     .get("series") or {}).values():
            wait = aggregate.hist_quantile(sval, 0.99)
        lines.append("")
        lines.append("STREAM  records/s=%.0f batches=%.0f reassigned=%.0f "
                     "quarantined=%.0f fetch-wait p99=%s"
                     % (rps, served, reassigned, quarantined,
                        "%.1f ms" % (wait * 1e3) if wait is not None
                        else "-"))

    # corrupt-shard attribution: the uri-labeled recordio counters name
    # the shard(s) producing resyncs/quarantined bytes
    resync = reg.get("mxtpu_recordio_resyncs_total") or {}
    bad = {}
    for skey, sval in (resync.get("series") or {}).items():
        if "uri=" in skey:
            uri = skey.split("uri=", 1)[1].split(",")[0]
            bad[uri] = bad.get(uri, 0.0) + sval
    if bad:
        qbytes = reg.get("mxtpu_recordio_quarantined_bytes_total") or {}
        lines.append("")
        lines.append("%-52s %8s %12s" % ("CORRUPT SHARD", "RESYNCS",
                                         "QUAR BYTES"))
        for uri in sorted(bad, key=bad.get, reverse=True)[:10]:
            b = sum(v for k, v in (qbytes.get("series") or {}).items()
                    if "uri=%s" % uri in k)
            lines.append("%-52s %8.0f %12.0f" % (uri[-52:], bad[uri], b))

    # alerts panel: the persistent history+evaluator in health_state
    # accumulate across frames, so burn windows fill as mxtop watches
    if health_state is not None:
        health_state["history"].record_scrape(scrape)
        verdict = health_state["evaluator"].evaluate()
        lines.append("")
        lines.append("ALERTS  overall=%s  (%d firing / %d rules)"
                     % (verdict["level"], len(verdict["firing"]),
                        len(verdict["rules"])))
        for e in verdict["firing"][:10]:
            val = e.get("value")
            lines.append("  [%s] %-28s %-10s %s"
                         % (e["level"], e["rule"], e["type"],
                            "%.4g" % val
                            if isinstance(val, (int, float)) else "-"))
    return "\n".join(lines), totals, now, scrape


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scheduler", default=None,
                    help="host:port (default: DMLC_PS_ROOT_URI/PORT)")
    ap.add_argument("--serving", action="append", default=None,
                    help="model-server host:port (repeatable)")
    ap.add_argument("--stream",
                    default=os.environ.get("MXTPU_STREAM_ADDR") or None,
                    help="stream coordinator host:port "
                         "(default: MXTPU_STREAM_ADDR)")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit")
    ap.add_argument("--json", action="store_true",
                    help="with --once: print the raw scrape as JSON "
                         "(stable schema — see the module docstring)")
    args = ap.parse_args(argv)

    prev_totals, prev_ts = {}, None
    health_state = {"history": history.MetricHistory(),
                    "evaluator": None}
    health_state["evaluator"] = health.HealthEvaluator(
        health_state["history"], catalog.default_health_rules())
    if args.once:
        # burn/rate rules need two samples: prime the history with one
        # scrape so the single rendered frame still evaluates them
        try:
            health_state["history"].record_scrape(aggregate.scrape(
                scheduler=args.scheduler, serving=args.serving,
                stream=args.stream))
            time.sleep(min(args.interval, 2.0))
        except (OSError, RuntimeError):
            pass      # the framed scrape will report the failure
    while True:
        try:
            text, prev_totals, prev_ts, scrape = frame(
                args.scheduler, args.serving, prev_totals, prev_ts,
                stream=args.stream, health_state=health_state)
        except (OSError, RuntimeError) as exc:
            text, scrape = "mxtop: scrape failed: %s" % exc, None
        if args.once:
            if args.json and scrape is not None:
                print(json.dumps(scrape, indent=2, default=str))
            else:
                print(text)
            return 0 if scrape is not None else 1
        sys.stdout.write("\x1b[2J\x1b[H" + text + "\n")
        sys.stdout.flush()
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
