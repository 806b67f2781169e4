"""Loaders: everything a run needs is found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix. The configuration's file
is the one its entry gives; a traffic mix is ``<path>/traffic/<name>.json``,
a cell's limits ``<path>/limits/<cell>.json`` and a per-layer metric's
reader ``<path>/metrics/<name>.py``, looked for under each directory of
``paths`` in turn. So a later PR adds a cell, a mix or a metric with new
files and list entries, and edits nothing that is here.
"""

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError("no %s named %r in BENCHMARK.json (have: %s)"
                   % (what, name, ", ".join(e["name"] for e in entries)))


def find_file(bench, root, *parts):
    for path in bench["paths"]:
        candidate = os.path.join(root, path, *parts)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError("%s under none of %s"
                            % (os.path.join(*parts), bench["paths"]))


def load_cell(bench, name, root=ROOT):
    """-> (cell, configuration, traffic, limits), the last three as the
    dicts their files hold."""
    cell = find(bench["workloads"], name, "workload")
    config = find(bench["configs"], cell["config"], "config")
    return (cell,
            load_json(os.path.join(root, config["file"])),
            load_json(find_file(bench, root, "traffic",
                                cell["traffic"] + ".json")),
            load_json(find_file(bench, root, "limits", name + ".json")))


def metrics_of(bench, kind, cell_name):
    """The `kind` ("end_to_end" or "per_layer") metrics this cell reports:
    those that list it, and those that list no cells at all."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def load_reader(bench, name, root=ROOT):
    """The reader of one per-layer metric: ``read(facts) -> number or
    None`` in a file of its own."""
    path = find_file(bench, root, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
