"""Runner for traffic of the kind "train_steps".

The window drives ``ShardedTrainer.step`` with a new seed-made batch each
step: a ring of host batches made in set-up, cycled. The host runs at most
``steps_in_flight`` steps ahead of the device (it waits for the loss of
step n - k before it dispatches step n), so the device never waits on it
and the window ends when ``--seconds`` have passed, not when a queue of
unknown length has drained. The rate is all tokens of all steps dispatched,
over the wall time from the first dispatch to the ``block_until_ready``
after the last.

``correct``: set-up builds ONE trainer, loads the seed-made weights into it
and drives it through its first steps with the window's own call and feed;
the window goes on with that same object. After the window the plain
reference follows the same first steps from the same seed and
``benchmarks.compare`` holds the losses, the first gradient's norms (from
Adam's first moment after one step) and the norms of the parameters' change
against it, and the median leaf's difference between the two first
gradients, which is what separates 8-bit operands from bfloat16.

A traffic file gives: ``batch_per_chip``, ``seq_len``, ``mlm_positions``,
``mesh`` (axis -> size), ``data_axis``, ``sharding_rules``, ``ring_batches``,
``checked_steps``, ``steps_in_flight``, ``reference_block_rows``,
``trace_seconds``.
"""

import collections
import gc
import importlib
import time

import numpy as np

from .. import compare


def drive_steps(trainer, ring, start, seconds, in_flight, annotate):
    """Dispatch steps for `seconds`, batch `start + i` of the ring at step
    i. -> (steps, wall seconds, device losses, seconds inside each call)"""
    import jax
    losses, call_s = [], []
    pending = collections.deque()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        data, label = ring[(start + len(losses)) % len(ring)]
        if len(pending) >= in_flight:
            jax.block_until_ready(pending.popleft())
        t_call = time.perf_counter()
        with annotate("bench.step_call"):
            loss = trainer.step(list(data), list(label))
        call_s.append(time.perf_counter() - t_call)
        losses.append(loss)
        pending.append(loss)
    jax.block_until_ready(losses[-1])
    return len(losses), time.perf_counter() - t0, losses, call_s


def load_seed_weights(trainer, family, weights):
    """Give the trainer the seed-made `weights` themselves (no copy on the
    device: the first step donates them) with zero moments, and keep the
    parameters' start on the HOST, so that set-up holds nothing on the
    device that a plain training run would not, and the run's memory peak
    is the step's own. -> {parameter name: host array}"""
    import jax.numpy as jnp
    p0 = {n: np.asarray(weights[family.leaf_name(n)])
          for n in trainer.param_values}
    state = {k: jnp.zeros_like(v) for k, v in trainer.state_dict().items()
             if not k.startswith("param/")}
    state.update({"param/" + n: weights[family.leaf_name(n)] for n in p0})
    trainer.load_state_dict(state)
    return p0


def program_first_steps(trainer, family, p0, ring, n_steps, beta1):
    """Drive the trainer through `n_steps` from `p0` with the window's own
    call, and read what the comparison needs, on the host. The first
    gradient is read as the optimizer got it: after one step from zero
    moments, Adam's first moment is (1 - beta1) g.
    -> {"losses", "grad_norms", "delta_norms", "first_gradient"}"""
    import jax
    scale = np.float32(1.0 / (1.0 - beta1))
    losses, first = [], None
    for i in range(n_steps):
        data, label = ring[i % len(ring)]
        losses.append(trainer.step(list(data), list(label)))
        if i == 0:
            sd = trainer.state_dict()
            first = {family.leaf_name(n): scale * np.asarray(
                sd["opt0/" + n]).astype(np.float32) for n in p0}
            del sd
    sd = trainer.state_dict()
    delta = {family.leaf_name(n): float(np.linalg.norm(
        (np.asarray(sd["param/" + n], np.float32) - p0[n]).ravel()))
        for n in p0}
    del sd
    return {"losses": [float(x) for x in jax.device_get(losses)],
            "first_gradient": first,
            "grad_norms": {n: float(np.linalg.norm(a.ravel()))
                           for n, a in first.items()},
            "delta_norms": delta}


def memory_peak_bytes(devices):
    """The fullest device's high-water mark since the process began. The
    CPU of the tests keeps no memory statistics; a TPU does."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def make_ring(family, cfg, traffic, seed, rows, n_batches):
    rng = np.random.default_rng(seed)
    return [family.reference.make_batch(cfg, rng, rows, traffic["seq_len"],
                                        traffic["mlm_positions"])
            for _ in range(n_batches)]


def build(ctx):
    """The trainer on the cell's mesh, and the ring of host batches."""
    from incubator_mxnet_tpu.parallel import make_mesh
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    devices = ctx["devices"][:ctx["cell"]["chips"]]
    mesh = make_mesh(dict(traffic["mesh"]), devices=devices)
    trainer = family.build_trainer(
        cfg, mesh, rules=family.sharding_rules(traffic["sharding_rules"]),
        data_spec=traffic["data_axis"])
    ring = make_ring(family, cfg, traffic, ctx["seed"],
                     traffic["batch_per_chip"] * ctx["cell"]["chips"],
                     traffic["ring_batches"])
    return family, trainer, ring, devices


def reference_first_steps(ctx, family, ring, precision="float32",
                          keep_rows=None, frozen=False):
    cfg, traffic = ctx["config"], ctx["traffic"]
    return family.reference.follow(
        cfg, ctx["seed"], ring[:traffic["checked_steps"]], cfg["optimizer"],
        traffic["reference_block_rows"], precision=precision,
        keep_rows=keep_rows, frozen=frozen)


def run(ctx):
    import jax
    cfg, traffic, chips = ctx["config"], ctx["traffic"], ctx["cell"]["chips"]
    ctx["phase"]("runner entered")
    family, trainer, ring, devices = build(ctx)
    ctx["phase"]("trainer built")
    weights = family.reference.init_weights(cfg, ctx["seed"])
    p0 = load_seed_weights(trainer, family, weights)
    del weights
    ctx["phase"]("seed weights loaded, memory peak %d B"
                 % memory_peak_bytes(devices))
    n_checked = traffic["checked_steps"]
    prog = program_first_steps(trainer, family, p0, ring, n_checked,
                               cfg["optimizer"]["beta1"])
    del p0
    ctx["phase"]("first steps done, memory peak %d B"
                 % memory_peak_bytes(devices))
    in_flight = traffic["steps_in_flight"]

    setup_s = time.time() - ctx["t_start"]
    steps, wall, losses, call_s = drive_steps(
        trainer, ring, n_checked, ctx["seconds"], in_flight, ctx["annotate"])
    tokens = steps * traffic["batch_per_chip"] * chips * traffic["seq_len"]
    facts = {"setup_s": setup_s, "window_s": wall, "steps": steps,
             "tokens": tokens, "tokens_per_s": tokens / wall,
             "step_call_seconds": call_s,
             "flops_per_token": family.train_flops_per_token(cfg, traffic)}

    if ctx["tracer"] is not None:
        with ctx["tracer"]:
            drive_steps(trainer, ring, n_checked + steps,
                        traffic["trace_seconds"], in_flight, ctx["annotate"])

    facts["memory_peak_bytes"] = memory_peak_bytes(devices)
    ctx["phase"]("window closed: %d steps in %.3f s, longest step call "
                 "%.1f ms, memory peak %d B" % (
                     steps, wall, 1e3 * max(call_s),
                     facts["memory_peak_bytes"]))
    window_losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(window_losses)))
    facts["window_losses"] = [float(window_losses[0]),
                              float(window_losses[-1])]
    del trainer, losses
    gc.collect()

    ref = reference_first_steps(ctx, family, ring)
    numbers, where = compare.training_numbers(prog, ref)
    correct, rows = compare.judge(numbers, ctx["limits"])
    facts["compared_where"] = where
    return {"end_to_end": {"train_tokens_per_s_per_chip":
                           tokens / wall / chips, "setup_s": setup_s},
            "attempted": steps, "failed": failed,
            "correct": correct and failed == 0, "compared": rows,
            "facts": facts}


def calibrate(ctx, seeds, control_seeds):
    """Yields (index, seed, readings) for ``benchmarks/calibrate.py``: one
    trainer, reloaded from each seed's weights."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    family, trainer, _ring, _devices = build(ctx)
    rows_n = traffic["batch_per_chip"] * ctx["cell"]["chips"]
    for i, seed in enumerate(seeds):
        one = dict(ctx, seed=seed)
        ring = make_ring(family, cfg, traffic, seed, rows_n,
                         traffic["checked_steps"])
        weights = family.reference.init_weights(cfg, seed)
        p0 = load_seed_weights(trainer, family, weights)
        del weights
        prog = program_first_steps(trainer, family, p0, ring,
                                   traffic["checked_steps"],
                                   cfg["optimizer"]["beta1"])
        del p0
        ref = reference_first_steps(one, family, ring)
        rows = {"program": prog}
        if i < control_seeds:
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("fault_half_batch", {"keep_rows": rows_n // 2}),
                             ("fault_state_unchanged", {"frozen": True})):
                rows[name] = reference_first_steps(one, family, ring, **kw)
        out = {"where": {}, "first_loss_gap": {},
               "losses": {"program": prog["losses"],
                          "reference": ref["losses"]}}
        for name, other in rows.items():
            out[name], out["where"][name] = compare.training_numbers(
                other, ref)
            out["first_loss_gap"][name] = abs(
                other["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0])
        yield i, seed, out
