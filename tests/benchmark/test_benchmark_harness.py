"""The harness end to end on the CPU, at toy sizes that only these tests
reach: ``run.drive`` is everything of a run but the look for a chip. The
command itself has no CPU carry-on (last test).

Then the faults a cell can have, planted under the timed path, each of
which has to turn ``correct`` false; and the controls (the reference one
precision below the configuration's, put in the program's place), which
have to fail the comparison too.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import compare, run, spec

ROOT = spec.ROOT
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2 ** 31 + 77         # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(ROOT, "tests", "benchmark",
                                       "BENCHMARK_tiny.json"))


def drive(bench, workload, trace=False, seed=SEED, seconds=0.5):
    import jax
    return run.drive(bench, workload, seed, seconds, trace, jax.devices(),
                     peaks=PEAKS)


@pytest.mark.parametrize("workload,rate", [
    ("bert_tiny.pretrain_tiny", "train_tokens_per_s_per_chip"),
    ("bert_tiny.pretrain_tiny.dp2tp2", "train_tokens_per_s_per_chip"),
    ("gpt2_tiny.generate_tiny", "gen_tokens_per_s_per_chip")])
def test_a_sound_run_is_correct_and_reports_its_end_to_end_metrics(
        bench, workload, rate):
    result = drive(bench, workload)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {rate, "setup_s"}
    assert result["metrics"][rate]["value"] > 0
    chips = spec.find(bench["workloads"], workload, "workload")["chips"]
    assert result["device"]["count"] == chips
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]


@pytest.mark.parametrize("workload,expected", [
    ("bert_tiny.pretrain_tiny", {"train_host_ms_per_step", "train_mfu",
                                 "compile_s"}),
    ("gpt2_tiny.generate_tiny", {"decode_step_p50_ms", "prefill_share",
                                 "kv_host_bytes_per_step", "gen_mfu",
                                 "compile_s"})])
def test_a_traced_run_reports_the_layers_that_found_something_to_read(
        bench, workload, expected):
    result = drive(bench, workload, trace=True)
    # a CPU has no device plane: the device-trace readers return nothing
    # and are left out, never reported as 0
    assert set(result["metrics"]) == expected
    assert result["correct"] is True
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace", workload))


# ------------------------------------------------------------------ faults
def _unchanged_state(monkeypatch):
    from incubator_mxnet_tpu.parallel import ShardedTrainer
    step = ShardedTrainer.step

    def faulty(self, data, label, key=None):
        before = self.device_snapshot()
        loss = step(self, data, label, key)
        self.restore_device_snapshot(before)
        return loss
    monkeypatch.setattr(ShardedTrainer, "step", faulty)


def _half_batch(monkeypatch):
    """Half of the rows left out, the mean taken over the rest. On the mesh
    this is also the exchange over `dp` left out: one replica's half is all
    that reaches the update."""
    from incubator_mxnet_tpu.parallel import ShardedTrainer
    step = ShardedTrainer.step

    def first_half_twice(a):
        half = np.asarray(a)[:len(a) // 2]
        return np.concatenate([half, half])

    def faulty(self, data, label, key=None):
        return step(self, [first_half_twice(a) for a in data],
                    [first_half_twice(a) for a in label], key)
    monkeypatch.setattr(ShardedTrainer, "step", faulty)


def _altered_token(monkeypatch):
    from incubator_mxnet_tpu.generate import GenerateEngine
    sample = GenerateEngine._sample
    calls = {"n": 0}

    def faulty(self, logits_row):
        calls["n"] += 1
        token = sample(self, logits_row)
        # one token in seven, where it is produced: every call has some
        return (token + 1) % len(logits_row) if calls["n"] % 7 == 0 else token
    monkeypatch.setattr(GenerateEngine, "_sample", faulty)


@pytest.mark.parametrize("workload,fault", [
    ("bert_tiny.pretrain_tiny", _unchanged_state),
    ("bert_tiny.pretrain_tiny", _half_batch),
    ("bert_tiny.pretrain_tiny.dp2tp2", _half_batch),
    ("gpt2_tiny.generate_tiny", _altered_token)],
    ids=["state_unchanged", "half_batch", "dp_exchange_left_out",
         "token_altered"])
def test_a_fault_under_the_timed_path_turns_correct_false(
        bench, monkeypatch, workload, fault):
    fault(monkeypatch)
    result = drive(bench, workload, seconds=1.0)
    assert result["correct"] is False
    assert any(row["value"] > row["limit"]
               for row in result["compared"].values())


# ---------------------------------------------------------------- controls
def test_the_fp8_control_fails_the_training_comparison(bench):
    import jax
    from benchmarks.runners import train_steps
    cell, config, traffic, limits = spec.load_cell(
        bench, "bert_small.pretrain_small")
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "limits": limits, "devices": jax.devices(), "seed": 4}
    (_i, _seed, row), = train_steps.calibrate(ctx, [4], 1)
    sound, _ = compare.judge(row["program"], limits)
    control, _ = compare.judge(row["control_fp8"], limits)
    fault, _ = compare.judge(row["fault_half_batch"], limits)
    assert sound is True
    assert control is False
    assert fault is False


def test_the_bfloat16_control_fails_the_generation_comparison(bench):
    from benchmarks.reference import gpt2
    _cell, config, _traffic, limits = spec.load_cell(
        bench, "gpt2_tiny.generate_tiny")
    assert config["control_precision"] == "bfloat16"
    weights = gpt2.init_weights(config, 5)
    tokens = np.random.default_rng(5).integers(1000, 1100, (8, 48))
    ref = gpt2.logits(weights, config, tokens)
    low = gpt2.logits(weights, config, tokens, precision="bfloat16")
    picks = compare.first_choices(low, tokens, prompt_len=1)
    top2 = np.sort(np.asarray(ref)[:, :-1], axis=2)[:, :, -2:]
    ok, _ = compare.judge(compare.served_numbers(
        compare.served_gaps(ref, picks, prompt_len=1),
        top2[:, :, 1] - top2[:, :, 0]), limits)
    assert ok is False
    # and the reference's own first choices have no gap at all
    own = compare.first_choices(ref, tokens, prompt_len=1)
    assert compare.served_gaps(ref, own, prompt_len=1).max() == 0.0


# -------------------------------------------------------------- comparison
def test_worst_leaf_gap_measures_against_the_median_leaf_at_least():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "tiny": 3e-9}
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)


def test_leaves_with_no_gradient_are_left_out_of_the_change():
    first = {"a": np.ones(4), "b": np.ones(4), "dead": np.zeros(4)}
    ref = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 1.0, "dead": 1e-7},
           "delta_norms": {"a": 1.0, "b": 1.0, "dead": 1e-3},
           "first_gradient": first}
    prog = {"losses": [1.0], "grad_norms": dict(ref["grad_norms"]),
            "delta_norms": {"a": 1.0, "b": 1.0, "dead": 5.0},
            "first_gradient": first}
    numbers, where = compare.training_numbers(prog, ref)
    assert numbers["delta_norm_gap"] == 0.0
    assert numbers["grad_diff_median"] == 0.0


def test_the_median_leaf_difference_sees_what_the_norms_cannot():
    ref = {"a": np.array([1.0, 0.0]), "b": np.array([0.0, 2.0]),
           "c": np.array([3.0, 0.0])}
    # each leaf turned a little: the norms stay, the difference does not
    turned = {"a": np.array([1.0, 0.1]), "b": np.array([0.2, 2.0]),
              "c": np.array([3.0, 0.6])}
    assert compare.median_leaf_difference(ref, ref) == 0.0
    # differences 0.1, 0.2, 0.6 against max(own norm, median norm 2)
    assert compare.median_leaf_difference(turned, ref) == pytest.approx(0.1)


def test_a_number_that_is_not_finite_fails():
    assert compare.judge({"x": float("nan")}, {"x": 1.0})[0] is False
    assert compare.judge({"x": 0.5}, {"x": 1.0})[0] is True
    assert compare.judge({"x": 2.0}, {"x": 1.0})[0] is False


@pytest.mark.parametrize("numbers,limits", [
    ({"x": 2.0}, {"x": None}),              # a null limit holds nothing
    ({"y": 0.0}, {"x": 1.0}),               # a number without a limit
    ({"x": 0.0}, {"x": 1.0, "y": 1.0}),     # a limit without its number
    ({"x": 0.0}, {})], ids=["null", "unnamed", "missing", "empty"])
def test_a_limits_file_that_does_not_hold_every_number_is_an_error(
        numbers, limits):
    with pytest.raises((KeyError, TypeError)):
        compare.judge(numbers, limits)


def test_served_gaps_are_zero_for_the_references_own_tokens():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 6, 11))
    tokens = np.zeros((2, 6), np.int64)
    tokens[:, 3:] = logits[:, 2:-1].argmax(axis=2)
    assert compare.served_gaps(logits, tokens, prompt_len=3).max() == 0.0
    tokens[1, 4] = (tokens[1, 4] + 1) % 11
    gaps = compare.served_gaps(logits, tokens, prompt_len=3)
    assert gaps.shape == (2, 3) and gaps[1, 1] > 0.0
    assert np.count_nonzero(gaps) == 1
    # three of the six positions are close calls: the summed gap over three
    margins = np.array([[0.01, 0.5, 0.09], [2.0, 0.0999, 0.1]])
    assert compare.served_numbers(gaps, margins) == {
        "served_gap_per_close_call": pytest.approx(gaps[1, 1] / 3)}
    # no close call at all: the summed gap itself, never a division by 0
    assert compare.served_numbers(gaps, margins + 1.0) == {
        "served_gap_per_close_call": pytest.approx(gaps[1, 1])}


# ----------------------------------------------------------------- command
def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "bert_base.pretrain_t128", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr
