#!/usr/bin/env python
"""bench_diff — automated reader for the BENCH_r*.json trajectory.

Compares the newest round against the previous one: every throughput
metric the two rounds share (unit contains "/sec" — higher is better),
every row the emitter flagged `lower_is_better` (latency/startup rows
like the BENCH_MODEL=cold_start time-to-first-step numbers — gated in
the INVERTED direction), plus any `mfu` fields. Exits nonzero when a
shared metric regressed by more than --threshold (default 10%), so CI
or a human can gate on "did this round get slower" without reading
JSON by hand.

Preflight health rows (preflight_*) are diagnostics, not
benchmarks — dispatch RTT is lower-is-better and host-condition
dependent — so they are reported but never gated on.

Every metric line since round 6 carries a `platform`/`device_kind`
stamp. The regression gate only arms when BOTH rounds carry the SAME
platform; a cross-platform pair (or one predating the stamp) prints
its rows for reference and warn-skips with exit 0 — a CPU round vs a
TPU round is not a regression signal in either direction.

    python tools/bench_diff.py                 # newest vs previous, repo root
    python tools/bench_diff.py --dir . --threshold 0.05
    python tools/bench_diff.py --old BENCH_r06.json --new BENCH_r07.json
"""

import argparse
import glob
import json
import os
import sys


def load_round(path):
    """{metric: {"value", "unit", "mfu"?}} from one BENCH_r*.json (its
    `tail` field holds the bench stdout with one JSON line per metric)."""
    with open(path) as f:
        doc = json.load(f)
    out = {}
    for line in (doc.get("tail") or "").splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if "metric" not in rec or "value" not in rec:
            continue
        out[rec["metric"]] = rec
    return out


def round_platform(recs):
    """The round's recorded platform stamp ('cpu', 'tpu', ...) or None
    for rounds predating the stamp. Rounds are single-process runs so a
    mixed stamp is never expected; if it happens, the joined set makes
    the mismatch visible instead of hiding behind one element."""
    plats = {str(r["platform"]) for r in recs.values()
             if r.get("platform")}
    if not plats:
        return None
    return plats.pop() if len(plats) == 1 else "+".join(sorted(plats))


def comparable(rec):
    """Gate-worthy throughput row: higher-is-better per-second units,
    excluding the preflight health probes. Non-rate capacity rows
    (e.g. llm_capacity's concurrent_sessions_per_chip, unit
    "sessions/chip") opt in with an explicit ``higher_is_better``
    flag on the record."""
    if rec["metric"].startswith("preflight"):
        return False
    return ("/sec" in str(rec.get("unit", ""))
            or bool(rec.get("higher_is_better")))


def lower_is_better(rec):
    """Gate-worthy latency row: the emitter flagged it
    ``lower_is_better`` (e.g. the cold_start time-to-first-step rows),
    so the regression direction is INVERTED — growing is bad."""
    if rec["metric"].startswith("preflight"):
        return False
    return bool(rec.get("lower_is_better"))


def baselines(old, new):
    """Gate-worthy metrics appearing for the FIRST time in the newer
    round (e.g. llm_decode's debut). They can't be diffed yet, but they
    must not vanish silently either: name them so the reader knows the
    round established a baseline that gates from the next round on."""
    return [m for m in sorted(set(new) - set(old))
            if comparable(new[m]) or lower_is_better(new[m])]


def diff(old, new, threshold):
    """[(metric, kind, old, new, ratio, regressed)] over shared rows."""
    rows = []
    for metric in sorted(set(old) & set(new)):
        o, n = old[metric], new[metric]
        if comparable(o) and comparable(n):
            ratio = n["value"] / o["value"] if o["value"] else float("inf")
            rows.append((metric, "throughput", o["value"], n["value"],
                         ratio, ratio < 1.0 - threshold))
        elif lower_is_better(o) and lower_is_better(n):
            ratio = n["value"] / o["value"] if o["value"] else float("inf")
            rows.append((metric, "latency", o["value"], n["value"],
                         ratio, ratio > 1.0 + threshold))
        if "mfu" in o and "mfu" in n:
            ratio = n["mfu"] / o["mfu"] if o["mfu"] else float("inf")
            rows.append((metric, "mfu", o["mfu"], n["mfu"], ratio,
                         ratio < 1.0 - threshold))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--old", default=None, help="explicit older round file")
    ap.add_argument("--new", default=None, help="explicit newer round file")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="regression gate as a fraction (default 0.10)")
    args = ap.parse_args(argv)

    if (args.old is None) != (args.new is None):
        ap.error("pass both --old and --new, or neither")
    if args.old:
        old_path, new_path = args.old, args.new
    else:
        rounds = sorted(glob.glob(os.path.join(args.dir, "BENCH_r*.json")))
        if len(rounds) < 2:
            print("bench_diff: need at least two BENCH_r*.json rounds "
                  "under %s, found %d" % (args.dir, len(rounds)))
            return 2
        old_path, new_path = rounds[-2], rounds[-1]

    old = load_round(old_path)
    new = load_round(new_path)
    rows = diff(old, new, args.threshold)
    fresh = baselines(old, new)

    print("bench_diff: %s -> %s (gate: -%.0f%%)"
          % (os.path.basename(old_path), os.path.basename(new_path),
             args.threshold * 100))
    # cross-platform guard: a CPU round vs a TPU round is not a
    # regression signal in either direction, so the gate only arms when
    # BOTH rounds carry the same platform stamp. Mismatched (or
    # pre-stamp unstamped) pairs still print their rows for the reader,
    # but warn-skip with exit 0 instead of failing.
    po, pn = round_platform(old), round_platform(new)
    gate_armed = po is not None and po == pn
    if not gate_armed:
        print("  WARNING: platform stamps %r -> %r differ or are "
              "missing — rows shown for reference, regression gate "
              "SKIPPED (cross-platform rates are not comparable)"
              % (po, pn))
    for metric in fresh:
        print("  %-9s %-52s %27.2f  baseline established — gated "
              "from next round" % ("new", metric, new[metric]["value"]))
    if not rows:
        if fresh:
            print("bench_diff: ok (no shared metrics yet — %d new "
                  "baseline%s)" % (len(fresh), "" if len(fresh) == 1
                                   else "s"))
            return 0
        print("no shared throughput metrics between the two rounds")
        return 2
    failed = False
    for metric, kind, o, n, ratio, regressed in rows:
        regressed = regressed and gate_armed
        flag = "REGRESSED" if regressed else "ok"
        print("  %-9s %-52s %12.2f -> %12.2f  %+6.1f%%  %s"
              % (kind, metric, o, n, (ratio - 1.0) * 100, flag))
        failed = failed or regressed
    skipped = [m for m in sorted(set(old) & set(new))
               if not comparable(old[m]) and not lower_is_better(old[m])
               and "mfu" not in old[m]]
    if skipped:
        print("  (not gated: %s)" % ", ".join(skipped))
    if failed:
        print("bench_diff: FAIL — regression beyond %.0f%%"
              % (args.threshold * 100))
        return 1
    print("bench_diff: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
