"""Runner for traffic of the kind "generate_state_calls":
``generate_long_calls``'s closed loop and its check (one client,
``GenerateEngine.generate`` back to back over a ring of seed-made prompt
sets, whole calls until ``--seconds`` have passed; ``correct`` from
``checked_rows`` (call, row) pairs of the timed window, the plain reference
asked for logits from the last prompt position on,
``served_gap_per_close_call``; and ``ssm_state_short_mantissa_share``,
which holds the program to the precision the configuration states for
its SSM state: ``state_short_mantissa_share``) for a HYBRID state-space
decoder
(``NemotronHPagedLM``): Mamba layers whose recurrent state lives in the
cache's state entries, an attention layer, expert layers that hold one
chip's share. It has no latent cache, so the call's ``last_stats["ssm"]``
stands where ``generate_long_calls`` reads ``"mla"``.

``facts`` carry the state layers' tallies by phase (``ssm``: the window's
``forwards``, ``rows``, ``positions``, ``scan_chunks``, ``state_bytes``,
``cache_rows_read``; ``traced_ssm`` the traced call's), the expert layer's
as ``generate_long_calls`` has them (``traced_moe``, with the number of
expert ``layers``; ``moe_load_max_over_mean``), the decode steps' mean of
experts hit an expert layer (``decode_experts_hit_per_layer``),
``prefill_tokens`` and ``prefill_flops``; a decode step's floor takes the
weights with the experts hit, each row's state read and written, and the
cached rows a step read (``benchmarks/costs_nemotron_h.py``).

A traffic file gives ``generate_long_calls``'s keys.
"""

import gc
import importlib
import statistics
import time

import numpy as np

from .. import compare
from .block_calls import one_call
from .generate_calls import count_failed, make_ring
from .generate_long_calls import (DECODE_STEP, PROGRAM_SPANS, drive_window,
                                  key_rows_of, recorded_spans, sampled_rows,
                                  served_gaps)

SSM_KEYS = ("forwards", "rows", "positions", "scan_chunks", "state_bytes",
            "cache_rows_read")


#: a float32 value whose lowest so many mantissa bits are all zero is
#: one that a float of 16 bits (bfloat16's 7, float16's 10 bits of
#: mantissa) could hold: 1 in 256 of a float32 state's, every one of a
#: state rounded to 16 bits anywhere on its way
SHORT_MANTISSA_BITS = 8


def state_short_mantissa_share(cache, rows):
    """The share of the nonzero values of every ``ssm`` state entry, in
    the first `rows` slots of its pool, whose float32 mantissa ends in
    ``SHORT_MANTISSA_BITS`` zero bits: about 1/256 where the program
    holds the state in float32 (as the configuration's
    ``assumed.ssm_state_dtype`` states), 1 where it holds or rounds it in
    16 bits. The served tokens cannot tell those apart on seed-made
    weights (PERF.md section 6); this reads the state the timed path
    left."""
    mask = (1 << SHORT_MANTISSA_BITS) - 1
    short = nonzero = 0
    for name, entry in cache.spec.items():
        if entry[0] == "state" and name.startswith("ssm"):
            bits = np.asarray(cache.data[name][:rows], np.float32).view(
                np.uint32)
            live = (bits & 0x7FFFFFFF) != 0
            short += int(np.count_nonzero(live & ((bits & mask) == 0)))
            nonzero += int(np.count_nonzero(live))
    return short / max(1, nonzero)


def ssm_tallies(stats):
    """The calls' ``last_stats["ssm"]`` summed, phase by phase."""
    return {phase: {key: sum(s["ssm"][phase][key] for s in stats)
                    for key in SSM_KEYS} for phase in ("prefill", "decode")}


def decode_experts_hit_per_layer(cfg, stats):
    """The experts hit by a decode forward's expert layer, a mean over the
    calls' decode forwards."""
    decode = [s["moe"]["by_phase"]["decode"] for s in stats]
    forwards = sum(d["forwards"] for d in decode)
    return (sum(d["experts_hit"] for d in decode)
            / max(1, forwards) / cfg["hybrid_override_pattern"].count("E"))


def window_facts(cfg, traffic, family, calls, wall, peaks):
    stats = [c["stats"] for c in calls]
    tokens = sum(len(s) for call in calls for s in call["served"])
    ssm = ssm_tallies(stats)
    decode = ssm["decode"]
    steps = max(1, decode["forwards"])
    hit = decode_experts_hit_per_layer(cfg, stats)
    return {"window_s": wall, "calls": len(calls), "tokens": tokens,
            "tokens_per_s": tokens / wall,
            "prefill_seconds": sum(s["prefill_seconds"] for s in stats),
            "decode_seconds": sum(s["decode_seconds"] for s in stats),
            "decode_steps": traffic["new_tokens"] * len(calls),
            "prefill_tokens": sum(s["prefill_tokens"] for s in stats),
            "prefill_flops": len(calls) * family.costs.prefill_flops(
                cfg, [n - 1 for n in traffic["prompt_lens"]]),
            "ssm": ssm,
            "decode_experts_hit_per_layer": hit,
            "moe_load_max_over_mean": statistics.median(
                x for s in stats for x in s["moe"]["load_max_over_mean"]),
            "decode_step_floor_s": family.costs.decode_step_floor_seconds(
                cfg, decode["rows"] / steps, hit,
                decode["cache_rows_read"] / steps, peaks)}


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
    ctx["phase"]("prefill %.3f s, decode %.3f s; ssm: %s"
                 % (facts["prefill_seconds"], facts["decode_seconds"],
                    facts["ssm"]))

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
        facts["traced_moe"] = dict(
            traced["stats"]["moe"],
            layers=cfg["hybrid_override_pattern"].count("E"))
        facts["traced_ssm"] = ssm_tallies([traced["stats"]])

    facts["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in ctx["devices"][:ctx["cell"]["chips"]])
    failed = count_failed(calls, new_tokens, cfg["vocab_size"])
    # the rows of the last call's first slots, as its last step left them
    short = state_short_mantissa_share(cache, traffic["checked_rows"])
    del engine, cache
    gc.collect()

    t0 = time.time()
    rows = sampled_rows(calls, traffic["checked_rows"],
                        np.random.default_rng(ctx["seed"]))
    correct, compared = compare.judge(dict(
        compare.served_numbers(*served_gaps(
            family, cfg, weights, rows, key_rows_of(family, traffic))),
        ssm_state_short_mantissa_share=short), ctx["limits"])
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
    the control (the configuration's ``control_precision``, on the
    reference's side: the program's state is read as it is, beside
    it)."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    key_rows = key_rows_of(family, traffic)
    for i, seed in enumerate(seeds):
        weights = family.reference.init_weights(cfg, seed)
        engine, cache = family.build_engine(cfg, weights, traffic)
        ring = make_ring(cfg, traffic, seed)
        engine.generate(ring[-1], max_new_tokens=traffic["warm_new_tokens"])
        call = one_call(engine, ring, 0, traffic, ctx["annotate"], None)
        state = {"ssm_state_short_mantissa_share": state_short_mantissa_share(
            cache, traffic["checked_rows"])}
        del engine, cache
        gc.collect()
        rows = sampled_rows([call], traffic["checked_rows"],
                            np.random.default_rng(seed))
        t0 = time.time()
        gaps, margins = served_gaps(family, cfg, weights, rows, key_rows)
        out = {"program": dict(compare.served_numbers(gaps, margins),
                               **state),
               "reference_seconds": time.time() - t0,
               "close_calls": int((margins < compare.CLOSE_CALL_LOGITS).sum()),
               "positions": len(gaps),
               "distinct_served": len({t for s in call["served"] for t in s}),
               "call_seconds": [call["t1"] - call["t0"]],
               "stats": {k: call["stats"][k] for k in
                         ("prefill_seconds", "decode_seconds", "ssm")},
               "first_tokens": [s[:4] for s in call["served"][:8]]}
        if i < control_seeds:
            name = "control_" + cfg["control_precision"]
            gaps, _ = served_gaps(family, cfg, weights, rows, key_rows,
                                  precision=cfg["control_precision"])
            out[name] = dict(compare.served_numbers(gaps, margins), **state)
        del weights
        gc.collect()
        yield i, seed, out
