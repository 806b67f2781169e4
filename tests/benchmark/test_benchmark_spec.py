"""BENCHMARK.json and every file it names, through the harness's own
loaders; and the tests' toy benchmark through the same loaders, which is
how a later PR adds a cell, a configuration, a mesh or a metric: new files
and list entries, nothing edited."""

import importlib
import json
import os
import re

import pytest

from benchmarks import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOY = os.path.join(ROOT, "tests", "benchmark", "BENCHMARK_tiny.json")


def benches():
    return {"BENCHMARK.json": spec.load_benchmark(),
            "BENCHMARK_tiny.json": spec.load_json(TOY)}


@pytest.fixture(params=sorted(benches()))
def bench(request):
    return benches()[request.param]


def metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_names_and_units_hold_only_the_allowed_characters(bench):
    for entry in bench["configs"] + bench["workloads"] + metrics(bench):
        assert NAME.match(entry["name"]), entry["name"]
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    for m in metrics(bench):
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    names = [e["name"] for e in metrics(bench)]
    assert len(names) == len(set(names))


def test_entries_have_just_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])
    for cell in bench["workloads"]:
        e2e = [m["name"] for m in spec.metrics_of(bench, "end_to_end",
                                                  cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert spec.metrics_of(bench, "per_layer", cell["name"])


def test_a_metric_moves_what_each_of_its_cells_reports(bench):
    cells = [c["name"] for c in bench["workloads"]]
    for m in bench["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells, (m["name"], cell)
            reported = [e["name"] for e in spec.metrics_of(
                bench, "end_to_end", cell)]
            assert m["moves"] in reported, (m["name"], cell)


def test_at_most_one_cell_asks_for_four_chips(bench):
    four = [c["name"] for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= 1
    assert all(name.endswith(".dp2tp2") for name in four)


def test_every_configuration_has_a_cell_and_a_file_under_paths(bench):
    used = {c["config"] for c in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for config in bench["configs"]:
        assert config["name"] in used
        assert any(config["file"].startswith(p + "/") for p in bench["paths"])
        assert "family" in spec.load_json(os.path.join(ROOT, config["file"]))


def test_every_cell_loads_with_traffic_limits_runner_and_readers(bench):
    for cell in bench["workloads"]:
        _cell, config, traffic, limits = spec.load_cell(bench, cell["name"])
        assert callable(importlib.import_module(traffic["runner"]).run)
        assert importlib.import_module(config["family"]).reference
        assert limits and all(v > 0 for v in limits.values())
        for m in spec.metrics_of(bench, "per_layer", cell["name"]):
            assert callable(spec.load_reader(bench, m["name"]))


def test_a_reader_that_finds_nothing_returns_nothing(bench):
    for m in bench["per_layer"]:
        assert spec.load_reader(bench, m["name"])({"trace": None}) is None


def test_the_command_and_paths_of_the_real_benchmark():
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    cells = [c["name"] for c in bench["workloads"]]
    assert cells[:2] == ["bert_base.pretrain_t128", "gpt2_xl.generate_short"]
    for config in bench["configs"]:
        assert config["reduced"] == []      # published sizes, nothing cut


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell(spec.load_benchmark(), "no_such.cell")
