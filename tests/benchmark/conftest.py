"""Two repairs to tests whose files are closed to the PR that needed them
(ISSUE 29): one expected failure, and one lock.

**The expected failure.**

``test_benchmark_spec.py::test_the_command_and_paths_of_the_real_benchmark``
ends with ``assert config["reduced"] == []`` for EVERY configuration of
``BENCHMARK.json``: PR 26's statement about its own two configurations
("published sizes, nothing cut"). ISSUE 29 adds ``sdar_30b_a3b``, whose depth
is cut to 6 of 48 layers and whose ``reduced`` must say so
(``["num_hidden_layers"]``), and forbids any edit to a file that is under
``tests/benchmark/`` already. Everything else that test asserts, and the
``reduced`` lists as they now stand, are asserted by
``test_benchmark_blocks.py::test_the_real_benchmark_as_it_stands_with_the_
block_cell``. The mark is strict: once a ``benchmark`` PR narrows the old
assertion to the configurations that are whole, the test passes, the mark
fails the run, and the mark goes.

**The lock.** ``test_benchmark_harness.py`` and ``test_benchmark_span_metrics.py``
both trace the toy cell ``bert_tiny.pretrain_tiny``, and ``benchmarks/run.py``
keeps a run's trace under ``.bench_trace/<workload>``, removing the directory
before and after. Under ``--dist loadfile`` the two files run in two
workers, and when their traced runs overlap one removes the other's trace
("no xplane.pb under ..."; seen once in two whole runs of this PR, whose new
test file shifts which worker gets what). Their tests take one lock file in
turn; a ``benchmark`` PR that gives a run a trace directory of its own can
drop it.
"""

import fcntl
import os

import pytest

SHARE_A_TRACE_DIRECTORY = ("test_benchmark_harness",
                           "test_benchmark_span_metrics")

OUTDATED = ("test_benchmark_spec.py::"
            "test_the_command_and_paths_of_the_real_benchmark")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(OUTDATED):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts reduced == [] for every configuration; "
                       "sdar_30b_a3b states its cut in depth (ISSUE 29)"))


@pytest.fixture(autouse=True)
def _one_toy_trace_at_a_time(request):
    if request.module.__name__.rsplit(".", 1)[-1] \
            not in SHARE_A_TRACE_DIRECTORY:
        yield
        return
    directory = os.path.join(str(request.config.rootpath), ".bench_trace")
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, ".lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
