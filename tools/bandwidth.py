#!/usr/bin/env python
"""Measure collective (all-reduce) bandwidth across the device mesh.

Reference parity: tools/bandwidth/measure.py (KVStore push/pull bandwidth
benchmark). TPU-first: the equivalent transport is an XLA ``psum`` over ICI
inside a pjit-ed program, which is exactly what ShardedTrainer's gradient
sync compiles to — so this measures the number that matters for DP scaling.

Usage: python tools/bandwidth.py [--size-mb 64] [--iters 20]
(on a CPU host, set XLA_FLAGS=--xla_force_host_platform_device_count=8 to
exercise the virtual mesh; numbers are then only wiring checks.)
"""

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    n_elem = int(args.size_mb * 1e6 / 4)
    x = jnp.ones((n * n_elem,), jnp.float32)

    @jax.jit
    def allreduce(v):
        def f(s):
            return jax.lax.psum(s, "dp")
        return jax.shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(v)

    jax.block_until_ready(allreduce(x))  # compile
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = allreduce(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / args.iters
    # ring all-reduce moves 2*(n-1)/n of the payload per device
    payload = n_elem * 4
    algo_bw = payload / dt / 1e9
    bus_bw = algo_bw * 2 * (n - 1) / n
    print("devices=%d shard=%.1fMB time=%.3fms algo_bw=%.2fGB/s "
          "bus_bw=%.2fGB/s" % (n, payload / 1e6, dt * 1e3, algo_bw, bus_bw))


if __name__ == "__main__":
    main()
