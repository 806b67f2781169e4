"""Multi-process SPMD mesh (parallel/multihost.py + launch.py --launcher
mesh): two OS processes x two virtual CPU devices form ONE global dp=4
mesh via jax.distributed (Gloo standing in for DCN); ShardedTrainer runs
its unchanged jitted step on every process, and the trajectories must
(a) agree across ranks and (b) fall. The reference bar is its
multi-machine NCCL/ps-lite path (tools/launch.py ssh/mpi); here the
same launcher contract drives a single global XLA program instead."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest


def test_two_process_mesh_training():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root)
    # the workers pin their own XLA device counts; scrub this process's
    # conftest settings so they don't leak
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--launcher", "mesh",
         sys.executable, os.path.join(root, "tests",
                                      "_multihost_worker.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])

    found = dict(re.findall(r"LOSSES rank=(\d) ([\d.,-]+)", r.stdout))
    assert set(found) == {"0", "1"}, r.stdout
    tr0 = [float(v) for v in found["0"].split(",")]
    tr1 = [float(v) for v in found["1"].split(",")]
    # SPMD: both ranks computed the SAME global program
    np.testing.assert_allclose(tr0, tr1, rtol=1e-6)
    assert tr0[-1] < tr0[0], tr0


def test_mesh_launcher_failure_propagation():
    """One dead rank must not hang the job: the launcher kills the
    stragglers (which would otherwise block in collectives forever) and
    forwards the failing rank's exit code."""
    import time
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import os, sys, time\n"
            "if os.environ['MXTPU_PROC_ID'] == '1':\n"
            "    sys.exit(3)\n"
            "time.sleep(120)\n")
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--launcher", "mesh", sys.executable, "-c", code],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 3, (r.returncode, r.stderr[-500:])
    assert time.time() - t0 < 30
