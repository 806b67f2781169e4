"""Runner for traffic of the kind "generate_calls": a closed loop, one
client, ``GenerateEngine.generate`` called back to back.

A call is a batch of seed-made prompts, greedy, a fixed number of new
tokens each; the calls cycle through a ring of distinct prompt sets made in
set-up. Every call of every seed holds the same set of prompt lengths, in
an order drawn from the seed. Calls are issued until ``--seconds`` have
passed and the call in flight is finished: the window is those whole
calls, and the rate is all new tokens they returned over the wall time from
the first call's start to the last call's return.

``correct``: once the window has closed, ``checked_calls`` of its calls are
drawn from the seed; the plain reference runs once over each prompt with
its served tokens, and the gaps by which the served tokens' logits lie
below the reference's best, summed and taken per close call of the
reference, are held to the cell's limit.

A traffic file gives: ``prompt_lens`` (one call's), ``new_tokens``,
``cache_max_len``, ``ring_calls``, ``checked_calls``, ``warm_new_tokens``,
``first_token_id`` (prompts draw their ids from there up).
"""

import contextlib
import gc
import importlib
import time

import numpy as np

from .. import compare

CALL_SPAN = "bench.generate_call"
PROGRAM_SPANS = ("gen.prefill", "gen.decode_step")


def make_ring(cfg, traffic, seed):
    rng = np.random.default_rng(seed)
    ring = []
    for _ in range(traffic["ring_calls"]):
        lens = rng.permutation(traffic["prompt_lens"])
        ring.append([rng.integers(traffic["first_token_id"],
                                  cfg["vocab_size"], int(n)).tolist()
                     for n in lens])
    return ring


def drive_calls(engine, ring, start, seconds, new_tokens, annotate,
                program_span=None):
    """Whole calls until `seconds` have passed, one at the least.
    -> (calls, wall seconds);
    a call is {"prompts", "served", "stats", "t0", "t1"}."""
    calls = []
    t0 = time.perf_counter()
    while not calls or time.perf_counter() - t0 < seconds:
        prompts = ring[(start + len(calls)) % len(ring)]
        wall0 = time.time()
        # a parent span of the program's makes the engine's own spans record
        parent = (program_span(CALL_SPAN) if program_span
                  else contextlib.nullcontext())
        with annotate(CALL_SPAN), parent:
            served = engine.generate(prompts, max_new_tokens=new_tokens)
        calls.append({"prompts": prompts, "served": served,
                      "stats": dict(engine.last_stats),
                      "t0": wall0, "t1": time.time()})
    return calls, time.perf_counter() - t0


def served_gaps(family, cfg, weights, calls, precision=None):
    """Every served gap of `calls`, requests of one prompt length batched
    into one reference forward. With `precision` the control's reading:
    at each position of the same prompts and served tokens, the gap of the
    token that the lower precision puts first.
    -> (gaps, margins): one number a served token; a margin is the
    reference's best logit over its second best at that position."""
    by_len = {}
    for call in calls:
        for prompt, served in zip(call["prompts"], call["served"]):
            by_len.setdefault((len(prompt), len(served)), []).append(
                list(prompt) + list(served))
    gaps, margins = [], []
    for (prompt_len, _n), rows in sorted(by_len.items()):
        tokens = np.asarray(rows, np.int32)
        ref = np.asarray(family.reference.logits(weights, cfg, tokens))
        if precision is not None:
            tokens = compare.first_choices(family.reference.logits(
                weights, cfg, tokens, precision=precision), tokens,
                prompt_len)
        gaps.append(compare.served_gaps(ref, tokens, prompt_len).ravel())
        top2 = np.partition(ref[:, prompt_len - 1:-1], -2, axis=2)[:, :, -2:]
        margins.append((top2[:, :, 1] - top2[:, :, 0]).ravel())
    return np.concatenate(gaps), np.concatenate(margins)


def count_failed(calls, new_tokens, vocab):
    return sum(1 for call in calls for served in call["served"]
               if len(served) != new_tokens
               or not all(0 <= t < vocab for t in served))


def run(ctx):
    import jax
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    new_tokens = traffic["new_tokens"]
    ctx["phase"]("runner entered")
    weights = family.reference.init_weights(cfg, ctx["seed"])
    jax.block_until_ready(weights)
    ctx["phase"]("weights made")
    engine, cache = family.build_engine(
        cfg, weights, len(traffic["prompt_lens"]), traffic["cache_max_len"])
    ring = make_ring(cfg, traffic, ctx["seed"])
    ctx["phase"]("engine built")
    # warms both shapes the window uses: the prefill chunk and the decode row
    engine.generate(ring[-1], max_new_tokens=traffic["warm_new_tokens"])
    ctx["phase"]("warmed")
    program_span = None
    if ctx["tracer"] is not None:
        from incubator_mxnet_tpu.telemetry import tracing
        tracing.clear_spans()
        program_span = tracing.Span

    setup_s = time.time() - ctx["t_start"]
    calls, wall = drive_calls(engine, ring, 0, ctx["seconds"], new_tokens,
                              ctx["annotate"], program_span)
    ctx["phase"]("window closed: %d calls in %.3f s; seconds a call: %s"
                 % (len(calls), wall, " ".join(
                     "%.3f" % (c["t1"] - c["t0"]) for c in calls)))
    tokens = sum(len(s) for call in calls for s in call["served"])
    decode_steps = new_tokens * len(calls)
    rows = len(traffic["prompt_lens"])
    facts = {"setup_s": setup_s, "window_s": wall, "calls": len(calls),
             "tokens": tokens, "tokens_per_s": tokens / wall,
             "prefill_seconds": sum(c["stats"]["prefill_seconds"]
                                    for c in calls),
             "decode_seconds": sum(c["stats"]["decode_seconds"]
                                   for c in calls),
             "decode_steps": decode_steps,
             "kv_host_bytes_per_step": family.kv_host_bytes(cfg, cache),
             "decode_step_floor_s": family.decode_step_floor_seconds(
                 cfg, rows, sum(traffic["prompt_lens"])
                 + rows * new_tokens // 2, ctx["peaks"])}

    if ctx["tracer"] is not None:
        with ctx["tracer"]:
            drive_calls(engine, ring, len(calls), 0.0, new_tokens,
                        ctx["annotate"], program_span)
        from incubator_mxnet_tpu.telemetry import tracing
        spans = [s for s in tracing.recent_spans()
                 if s["name"] in PROGRAM_SPANS]
        facts["program_spans"] = [
            (s["name"], s["ts_us"] / 1e6, (s["ts_us"] + s["dur_us"]) / 1e6)
            for s in spans]
        facts["decode_step_seconds"] = [
            s["dur_us"] / 1e6 for s in spans
            if s["name"] == "gen.decode_step"
            and s["ts_us"] / 1e6 <= calls[-1]["t1"]]    # the window's own

    facts["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in ctx["devices"][:ctx["cell"]["chips"]])
    failed = count_failed(calls, new_tokens, cfg["vocab_size"])
    del engine, cache
    gc.collect()

    rng = np.random.default_rng(ctx["seed"])
    picked = rng.choice(len(calls), min(traffic["checked_calls"],
                                        len(calls)), replace=False)
    correct, compared = compare.judge(compare.served_numbers(*served_gaps(
        family, cfg, weights, [calls[i] for i in picked])), ctx["limits"])
    jax.block_until_ready(weights)
    return {"end_to_end": {"gen_tokens_per_s_per_chip":
                           tokens / wall / ctx["cell"]["chips"],
                           "setup_s": setup_s},
            "attempted": len(calls) * rows, "failed": failed,
            "correct": correct and failed == 0, "compared": compared,
            "facts": facts}


def calibrate(ctx, seeds, control_seeds):
    """Yields (index, seed, readings) for ``benchmarks/calibrate.py``: per
    seed new weights, a warm call, ``checked_calls`` calls at the cell's
    own load, the reference over them and, for the first `control_seeds`
    seeds, the control (the configuration's ``control_precision``)."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    for i, seed in enumerate(seeds):
        weights = family.reference.init_weights(cfg, seed)
        engine, cache = family.build_engine(
            cfg, weights, len(traffic["prompt_lens"]),
            traffic["cache_max_len"])
        ring = make_ring(cfg, traffic, seed)
        engine.generate(ring[-1], max_new_tokens=traffic["warm_new_tokens"])
        calls = []
        while len(calls) < traffic["checked_calls"]:
            more, _wall = drive_calls(engine, ring, len(calls), 0.0,
                                      traffic["new_tokens"], ctx["annotate"])
            calls += more
        del engine, cache
        gaps, margins = served_gaps(family, cfg, weights, calls)
        out = {"program": compare.served_numbers(gaps, margins),
               "call_seconds": [c["t1"] - c["t0"] for c in calls],
               "first_tokens": [s[:4] for s in calls[0]["served"]],
               "raw": {"program": gaps.tolist(), "margins": margins.tolist()}}
        if i < control_seeds:
            name = "control_" + cfg["control_precision"]
            gaps, _ = served_gaps(family, cfg, weights, calls,
                                  precision=cfg["control_precision"])
            out[name] = compare.served_numbers(gaps, margins)
            out["raw"][name] = gaps.tolist()
        del weights
        gc.collect()
        yield i, seed, out
