"""Readings that the limits of ``correct`` are set from (PERF.md, section 2).

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 3] [--out chiprun_out/calibrate_<name>.json]

One process. For every seed: the program's numbers against the plain
reference. For the first ``--control-seeds`` seeds also the control (the
reference one precision below what the configuration states, put in the
program's place) and, for training, the planted faults "half of the batch
left out" and "state unchanged". Every row goes through ``compare.judge``
with the cell's own limits, and what it says is recorded under "correct".
Not part of a benchmark run; needs the chip like one.
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, spec   # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    from incubator_mxnet_tpu import compilecache
    compilecache.use_jax_cache()
    bench = spec.load_benchmark()
    cell, config, traffic, limits = spec.load_cell(bench, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "limits": limits, "devices": jax.devices(), "seed": seeds[0],
           "annotate": jax.profiler.TraceAnnotation,
           "phase": lambda name: None}
    runner = importlib.import_module(traffic["runner"])
    readings = []
    for i, seed, row in runner.calibrate(ctx, seeds, args.control_seeds):
        # every program, control and fault row goes through the comparison
        # of a run, with the cell's own limits: what `correct` would say
        row["correct"] = {name: compare.judge(numbers, limits)[0]
                          for name, numbers in row.items()
                          if name == "program"
                          or name.startswith(("control_", "fault_"))}
        row = {"seed": seed, "t": round(time.time(), 1), **row}
        readings.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "raw"}),
              flush=True)
    out = args.out or os.path.join(
        ROOT, "chiprun_out", "calibrate_%s.json" % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"workload": args.workload,
                   "device": jax.devices()[0].device_kind,
                   "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
