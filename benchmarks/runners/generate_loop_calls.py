"""Runner for traffic of the kind "generate_loop_calls":
``generate_long_calls``'s closed loop and its check (one client,
``GenerateEngine.generate`` back to back over a ring of seed-made prompt
sets, whole calls until ``--seconds`` have passed; ``correct`` from
``checked_rows`` (call, row) pairs of the timed window, the plain reference
asked for logits from the last prompt position on,
``served_gap_per_close_call``) for a LOOPED decoder whose layers run
several times a token over one set of weights (``OuroPagedLM``): no
expert layer, no latent cache and no windows, so the call's
``last_stats["loop"]`` stands where ``generate_long_calls`` reads ``"moe"``
and ``"mla"`` and ``generate_byte_calls`` reads ``"eva"``.

``facts`` carry the loop's tallies by phase (``loop``: the window's
``forwards``, ``loop_steps``, ``layer_passes``, ``cache_rows_read``,
``cache_rows_written``, ``positions``; ``traced_loop`` the traced call's),
``prefill_tokens`` and ``prefill_flops``; a decode step's floor takes the
cache rows a step read, a mean over the window's steps from those
tallies, and every layer's weights once a loop step.

A traffic file gives ``generate_long_calls``'s keys.
"""

import gc
import importlib
import time

import numpy as np

from .. import compare
from .block_calls import one_call
from .generate_calls import count_failed, make_ring
from .generate_long_calls import (DECODE_STEP, PROGRAM_SPANS, drive_window,
                                  key_rows_of, recorded_spans, sampled_rows,
                                  served_gaps)

LOOP_KEYS = ("forwards", "loop_steps", "layer_passes", "cache_rows_read",
             "cache_rows_written", "positions")


def loop_tallies(stats):
    """The calls' ``last_stats["loop"]`` summed, phase by phase."""
    return {phase: {key: sum(s["loop"][phase][key] for s in stats)
                    for key in LOOP_KEYS} for phase in ("prefill", "decode")}


def window_facts(cfg, traffic, family, calls, wall, peaks):
    stats = [c["stats"] for c in calls]
    tokens = sum(len(s) for call in calls for s in call["served"])
    loop = loop_tallies(stats)
    decode = loop["decode"]
    return {"window_s": wall, "calls": len(calls), "tokens": tokens,
            "tokens_per_s": tokens / wall,
            "prefill_seconds": sum(s["prefill_seconds"] for s in stats),
            "decode_seconds": sum(s["decode_seconds"] for s in stats),
            "decode_steps": traffic["new_tokens"] * len(calls),
            "prefill_tokens": sum(s["prefill_tokens"] for s in stats),
            "prefill_flops": len(calls) * family.costs.prefill_flops(
                cfg, [n - 1 for n in traffic["prompt_lens"]]),
            "loop": loop,
            "decode_step_floor_s": family.costs.decode_step_floor_seconds(
                cfg, len(traffic["prompt_lens"]),
                decode["cache_rows_read"] / max(1, decode["forwards"]),
                peaks)}


def run(ctx):
    import jax
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    new_tokens = traffic["new_tokens"]
    ctx["phase"]("runner entered")
    family.require_program()
    weights = family.reference.init_weights(cfg, ctx["seed"])
    jax.block_until_ready(weights)
    ctx["phase"]("weights made")
    engine, cache = family.build_engine(cfg, weights, traffic)
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
    calls, wall = drive_window(engine, ring, ctx["seconds"], traffic,
                               ctx["annotate"], program_span)
    ctx["phase"]("window closed: %d calls in %.3f s; seconds a call: %s"
                 % (len(calls), wall, " ".join(
                     "%.3f" % (c["t1"] - c["t0"]) for c in calls)))
    facts = window_facts(cfg, traffic, family, calls, wall, ctx["peaks"])
    facts["setup_s"] = setup_s
    ctx["phase"]("prefill %.3f s, decode %.3f s; loop: %s"
                 % (facts["prefill_seconds"], facts["decode_seconds"],
                    facts["loop"]))

    if ctx["tracer"] is not None:
        facts["decode_step_seconds"] = [
            s["dur_us"] / 1e6 for s in calls[-1]["spans"]
            if s["name"] == DECODE_STEP]
        with ctx["tracer"]:
            traced = one_call(engine, ring, len(calls), traffic,
                              ctx["annotate"], program_span)
        at = len(calls[-1]["spans"])    # the traced call's come after
        facts["program_spans"] = [
            (s["name"], s["ts_us"] / 1e6, (s["ts_us"] + s["dur_us"]) / 1e6)
            for s in recorded_spans()[at:] if s["name"] in PROGRAM_SPANS]
        facts["traced_loop"] = loop_tallies([traced["stats"]])

    facts["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in ctx["devices"][:ctx["cell"]["chips"]])
    failed = count_failed(calls, new_tokens, cfg["vocab_size"])
    del engine, cache
    gc.collect()

    t0 = time.time()
    rows = sampled_rows(calls, traffic["checked_rows"],
                        np.random.default_rng(ctx["seed"]))
    correct, compared = compare.judge(compare.served_numbers(*served_gaps(
        family, cfg, weights, rows, key_rows_of(family, traffic))),
        ctx["limits"])
    jax.block_until_ready(weights)
    ctx["phase"]("checked %d rows in %.1f s" % (len(rows), time.time() - t0))
    tokens = facts["tokens"]
    return {"end_to_end": {"gen_tokens_per_s_per_chip":
                           tokens / wall / ctx["cell"]["chips"],
                           "setup_s": setup_s},
            "attempted": len(calls) * len(traffic["prompt_lens"]),
            "failed": failed, "correct": correct and failed == 0,
            "compared": compared, "facts": facts}


def calibrate(ctx, seeds, control_seeds):
    """Yields (index, seed, readings) for ``benchmarks/calibrate.py``, in
    ``generate_long_calls.calibrate``'s shape: per seed new weights, a
    warm call, one call at the cell's own load, the reference over
    ``checked_rows`` of its rows and, for the first `control_seeds` seeds,
    the control (the configuration's ``control_precision``)."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    key_rows = key_rows_of(family, traffic)
    for i, seed in enumerate(seeds):
        weights = family.reference.init_weights(cfg, seed)
        engine, cache = family.build_engine(cfg, weights, traffic)
        ring = make_ring(cfg, traffic, seed)
        engine.generate(ring[-1], max_new_tokens=traffic["warm_new_tokens"])
        call = one_call(engine, ring, 0, traffic, ctx["annotate"], None)
        del engine, cache
        gc.collect()
        rows = sampled_rows([call], traffic["checked_rows"],
                            np.random.default_rng(seed))
        t0 = time.time()
        gaps, margins = served_gaps(family, cfg, weights, rows, key_rows)
        out = {"program": compare.served_numbers(gaps, margins),
               "reference_seconds": time.time() - t0,
               "close_calls": int((margins < compare.CLOSE_CALL_LOGITS).sum()),
               "positions": len(gaps),
               "distinct_served": len({t for s in call["served"] for t in s}),
               "call_seconds": [call["t1"] - call["t0"]],
               "stats": {k: call["stats"][k] for k in
                         ("prefill_seconds", "decode_seconds", "loop")},
               "first_tokens": [s[:4] for s in call["served"]]}
        if i < control_seeds:
            name = "control_" + cfg["control_precision"]
            gaps, _ = served_gaps(family, cfg, weights, rows, key_rows,
                                  precision=cfg["control_precision"])
            out[name] = compare.served_numbers(gaps, margins)
        del weights
        gc.collect()
        yield i, seed, out
