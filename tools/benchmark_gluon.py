"""Model-zoo throughput benchmark (reference:
benchmark/python/gluon/benchmark_gluon.py — per-model fwd / fwd+bwd
imgs/sec across the vision zoo).

Usage:
  python tools/benchmark_gluon.py [--models resnet50_v1,mobilenet1_0]
                                  [--batch 64] [--steps 20] [--train]
                                  [--dtype bfloat16|float32]

Timing closes each measured window with a host transfer, so async dispatch
on the TPU is charged honestly.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_MODELS = ["resnet18_v1", "resnet50_v1", "mobilenet1_0",
                  "squeezenet1_0", "densenet121", "vgg16", "alexnet",
                  "inception_v3"]


def bench_model(name, batch, steps, train, dtype):
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import make_mesh, ShardedTrainer
    from jax.sharding import PartitionSpec as P

    size = 299 if "inception" in name else 224
    net = mx.gluon.model_zoo.vision.get_model(name)
    net.initialize(mx.init.Xavier())
    data = mx.nd.array(np.random.rand(batch, 3, size, size).astype(np.float32))
    net(data[0:1])

    if train:
        label = mx.nd.array(np.random.randint(0, 1000, (batch,)).astype(np.float32))

        def loss_fn(out, lab):
            logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(
                logp, lab.astype(jnp.int32)[:, None], axis=-1).mean()

        mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
        tr = ShardedTrainer(net, loss_fn, mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1},
                            data_specs=P(), label_spec=P(),
                            compute_dtype=None if dtype == "float32" else dtype)
        run = lambda: tr.step(data, label)
        sync = lambda r: float(r)
    else:
        net.hybridize()
        if dtype != "float32":
            # cast params too, or bf16 @ fp32 promotes back to fp32
            for p in net.collect_params().values():
                if p._data is not None and p._data._data.dtype == jnp.float32:
                    p._data._data = p._data._data.astype(jnp.bfloat16)
            data = mx.nd.array(data._data.astype(jnp.bfloat16))
        run = lambda: net(data)
        sync = lambda r: float(r.asnumpy().ravel()[0])

    for _ in range(5):
        r = run()
    sync(r)
    t0 = time.perf_counter()
    for _ in range(steps):
        r = run()
    sync(r)
    dt = time.perf_counter() - t0
    return batch * steps / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default=",".join(DEFAULT_MODELS))
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--train", action="store_true")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    results = {}
    for name in args.models.split(","):
        try:
            ips = bench_model(name.strip(), args.batch, args.steps,
                              args.train, args.dtype)
            results[name] = round(ips, 1)
            print(json.dumps({"model": name,
                              "mode": "train" if args.train else "inference",
                              "imgs_per_sec": round(ips, 1)}))
        except Exception as e:   # keep benching the rest
            print(json.dumps({"model": name, "error": str(e)[:120]}))
    return results


if __name__ == "__main__":
    main()
