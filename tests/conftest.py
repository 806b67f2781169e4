"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax loads.

Mirrors the reference's test strategy (SURVEY §4): deterministic seeds, CPU
as the reference backend, multi-device tests without real hardware (the
reference tests model parallelism on cpu contexts the same way).
"""

import os

# XLA_FLAGS and JAX_PLATFORMS are read at jax's first backend init, so both
# are set before the import.
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as _np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: exceeds the tier-1 wall-clock budget or is a "
        "known-flaky long drill (deselected by -m 'not slow'; see the "
        "verify command in ROADMAP.md)")


# One test under tests/benchmark/ (closed to a perf_opt PR) asserts the
# bottleneck ISSUE 30 removed; all else it asserted is asserted by
# tests/test_generate.py::test_the_span_readers_split_a_decode_step_with_
# the_pools_on_the_device. Strict: once a benchmark PR loosens its last
# line to `h2d < pools + 4096` it passes, this mark fails the run, and
# the mark goes.
POOLS_CROSS_IN_EVERY_STEP = (
    "tests/benchmark/test_benchmark_span_metrics.py::"
    "test_a_traced_generation_run_reads_every_phase_of_a_decode_step")


# One more (closed to a model_config PR) asserts the exact {name: reduced}
# of THREE configurations; ISSUE 33 lists a fourth. All else it asserted,
# with the lists as they now stand, is asserted by tests/benchmark/
# test_benchmark_latent.py::test_the_real_benchmark_as_it_stands_with_the_
# long_prompt_cell. Strict: once a benchmark PR makes it ask only for the
# configurations it names, it passes, this mark fails the run, and the
# mark goes.
THREE_CONFIGURATIONS = (
    "tests/benchmark/test_benchmark_blocks.py::"
    "test_the_real_benchmark_as_it_stands_with_the_block_cell")


# Two more (closed to a perf_opt PR) assert what ISSUE 34 ended: a greedy
# token chosen on the host. One plants its fault by patching
# ``GenerateEngine._sample``, which a greedy call no longer goes through
# (the forward's program chooses the token); the other counts a token a
# row among the bytes a decode step ships, and a step is now fed the
# device array the forward before left. All else they asserted is
# asserted by tests/test_generate.py::test_an_altered_token_at_the_token_
# heads_output_fails_the_toy_cell and ::test_a_traced_toy_latent_run_
# reports_every_layer_a_cpu_can_read. Strict: once a benchmark PR plants
# the fault at the program's output and drops the token's 4 B from the
# expected bytes they pass, these marks fail the run, and the marks go.
TOKEN_CHOSEN_ON_THE_HOST = (
    "tests/benchmark/test_benchmark_harness.py::"
    "test_a_fault_under_the_timed_path_turns_correct_false[token_altered]",
    "tests/benchmark/test_benchmark_latent.py::"
    "test_a_traced_run_reports_every_layer_a_cpu_can_read")


# One more (closed to a model_config PR) asserts ``len(cells) == 5`` and the
# exact {name: reduced} of FOUR configurations; ISSUE 35 lists a fifth
# configuration and a sixth cell. All else it asserted is asserted by
# tests/benchmark/test_benchmark_share.py::test_the_real_benchmark_as_it_
# stands_with_the_wide_batch_cell, which names only the configurations and
# cells it needs (``in``, ``>=``), so that the next configuration breaks
# nothing. Strict: once a benchmark PR makes the old test ask the same
# way, it passes, this mark fails the run, and the mark goes.
FOUR_CONFIGURATIONS = (
    "tests/benchmark/test_benchmark_latent.py::"
    "test_the_real_benchmark_as_it_stands_with_the_long_prompt_cell")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(FOUR_CONFIGURATIONS):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts five cells and the reduced lists of exactly "
                       "four configurations (ISSUE 35 lists a fifth); "
                       "PERF.md section 7 item 17"))
        if item.nodeid.endswith(TOKEN_CHOSEN_ON_THE_HOST):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts that a greedy token is chosen on the host "
                       "and shipped to the next step (ISSUE 34); PERF.md "
                       "section 7 items 15, 16"))
        if item.nodeid.endswith(POOLS_CROSS_IN_EVERY_STEP):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts that the pools cross to the device in every "
                       "step (ISSUE 30); PERF.md section 7 item 8"))
        if item.nodeid.endswith(THREE_CONFIGURATIONS):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts the reduced lists of exactly three "
                       "configurations (ISSUE 33 lists a fourth); PERF.md "
                       "section 7"))


@pytest.fixture(autouse=True)
def _seed_everything():
    """Deterministic per-test seeding (reference: with_seed decorator;
    MXNET_TEST_SEED overrides, logged seed for repro)."""
    seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
    _np.random.seed(seed)
    import incubator_mxnet_tpu as mx
    mx.random.seed(seed)
    yield
