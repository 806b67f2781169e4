"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Refuses to run (exit 2, no result line) unless
JAX finds a TPU and as many chips as the cell asks for. The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; then
``compared``, each number of the output check beside its limit, which is
also repeated as the last lines of standard error.
"""

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import importlib            # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:    # run as a script: the checkout is the package root
    sys.path.insert(0, ROOT)

from benchmarks import spec, trace_reduce   # noqa: E402
from benchmarks.peaks import peaks_for      # noqa: E402

TRACE_WINDOW = "bench.trace_window"
HOST_SPANS = ("bench.step_call", "bench.generate_call")


class Tracer:
    """One short profiler trace, written inside the checkout and removed
    once it is reduced. Use as a context manager around the traced part."""

    def __init__(self, directory):
        self.directory = directory
        self.wall_at_window_start = None
        self._annotation = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans are ours, by name
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(TRACE_WINDOW)
        self.wall_at_window_start = time.time()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._annotation.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def reduce(self, program_spans=()):
        """-> the traced part as plain data: device operations by plane,
        the window on the trace's clock, the host spans inside it.
        `program_spans` are (name, wall start s, wall end s) from the
        program's own telemetry, moved onto the trace's clock by the
        window's start, which both clocks saw."""
        events = trace_reduce.read_xplane(self.directory)
        shutil.rmtree(self.directory, ignore_errors=True)
        window = trace_reduce.window_of(events, TRACE_WINDOW)
        if window is None:
            raise RuntimeError("the trace holds no %s event" % TRACE_WINDOW)
        spans = trace_reduce.host_spans(events, set(HOST_SPANS))
        for name, t0, t1 in program_spans:
            start = window[0] + (t0 - self.wall_at_window_start) * 1e9
            spans.append(trace_reduce.Event("program", "telemetry", name,
                                            start, (t1 - t0) * 1e9))
        return {"ops": trace_reduce.device_ops(events), "window": window,
                "spans": sorted(spans, key=lambda e: e.start_ns)}


class CompileClock:
    """Seconds JAX spends compiling or loading compiled programs: its
    ``backend_compile_duration`` events, which wrap the look into the
    persistent cache too. (The cache's own events are left out: one of them
    is the compile time a hit SAVED.)"""

    def __init__(self):
        self.seconds = 0.0

    def install(self):
        import jax.monitoring

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += float(duration)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self


def device_record(devices, chips, facts, trace):
    first = devices[0]
    record = {"platform": first.platform, "kind": first.device_kind,
              "count": chips,
              "memory_peak_bytes": int(facts["memory_peak_bytes"])}
    if trace is not None and trace["ops"]:
        lo, hi = trace["window"]
        record["busy_s"] = trace_reduce.mean_busy_seconds(trace["ops"],
                                                          (lo, hi))
        record["window_s"] = (hi - lo) / 1e9
    return record


def breakdown(trace):
    """The device that was busiest speaks for the cell."""
    window = trace["window"]
    plane = max(trace["ops"], key=lambda p: trace_reduce.busy_seconds(
        trace["ops"][p], window))
    ops = trace["ops"][plane]
    return {"device_ops": trace_reduce.top_ops(ops, window),
            "idle_gaps": trace_reduce.idle_gaps(ops, trace["spans"], window)}


def note(t_start, text):
    """A line on standard error with the seconds since the process began:
    where set-up's time went is read from these."""
    print("benchmark: +%.2f s %s" % (time.time() - t_start, text),
          file=sys.stderr, flush=True)


def drive(bench, workload, seed, seconds, trace, devices, root=spec.ROOT,
          peaks=None, t_start=None):
    """Everything of a run but the look for a chip: build the cell, warm
    it, measure, check. -> the result object. The command reaches this
    only with TPU devices; the tests call it with the CPU's."""
    import jax
    cell, config, traffic, limits = spec.load_cell(bench, workload, root)
    compile_clock = CompileClock().install()
    t_start = t_start or time.time()
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "phase": lambda text: note(t_start, text),
           "limits": limits, "seed": int(seed), "seconds": float(seconds),
           "devices": devices, "t_start": t_start,
           "peaks": peaks or peaks_for(devices[0].device_kind),
           "annotate": jax.profiler.TraceAnnotation,
           "tracer": Tracer(os.path.join(root, ".bench_trace", workload))
           if trace else None}
    out = importlib.import_module(traffic["runner"]).run(ctx)
    facts = out["facts"]
    facts.update(cell=cell, config=config, traffic=traffic,
                 peaks=ctx["peaks"], chips=cell["chips"],
                 compile_s=compile_clock.seconds, trace=None)
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        facts["trace"] = ctx["tracer"].reduce(facts.get("program_spans", ()))
        metrics = {}
        for m in spec.metrics_of(bench, "per_layer", workload):
            value = spec.load_reader(bench, m["name"], root)(facts)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in spec.metrics_of(bench, "end_to_end", workload)}
    result["metrics"] = metrics
    result["device"] = device_record(devices, cell["chips"], facts,
                                     facts["trace"])
    if trace and facts["trace"]["ops"]:    # a CPU has no device plane
        result["breakdown"] = breakdown(facts["trace"])
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in out["compared"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    chips = spec.find(bench["workloads"], args.workload, "workload")["chips"]
    import jax
    note(T_START, "jax imported")
    devices = jax.devices()
    note(T_START, "devices found")
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("benchmark: %s needs %d TPU chip(s); jax found %d %r device(s)"
              % (args.workload, chips, len(devices), devices[0].platform),
              file=sys.stderr)
        return 2
    from incubator_mxnet_tpu import compilecache
    cache_dir = compilecache.use_jax_cache()
    note(T_START, "package imported; %s seed %d, compile cache %s"
         % (args.workload, args.seed, cache_dir))
    result = drive(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, t_start=T_START)
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print("compared %s value=%r limit=%r" % (name, row["value"],
                                                 row["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
