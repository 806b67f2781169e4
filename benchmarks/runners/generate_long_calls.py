"""Runner for traffic of the kind "generate_long_calls": ``generate_calls``'s
closed loop (one client, ``GenerateEngine.generate`` back to back over a
ring of seed-made prompt sets, whole calls until ``--seconds`` have passed)
for calls of LONG prompts of different lengths over a model with an expert
layer and a latent cache.

What differs from ``generate_calls``:

- ``correct`` checks ``checked_rows`` (call, row) pairs drawn from the seed
  out of the window's calls, not whole calls, and asks the plain reference
  for logits at the served positions alone (from the last prompt position
  on: a row of 16,640 positions times the vocabulary would be 8.7 GB). The
  number is ``generate_calls``'s: ``served_gap_per_close_call``, how far
  the reference's logit of each served token lies below its best, summed,
  per position at which the reference's two best lie within 0.1.
- The ring of spans is drained after every call of a traced run (a call
  leaves some 1,500 records against a ring of 4,096) and kept with the
  call. The window's LAST call is left in the ring as well, where the
  readers of a decode step's phases look (``benchmarks/span_metrics.py``):
  ``decode_step_seconds`` are that call's steps, so that those readers
  and ``decode_step_p50_ms`` read the same steps, none of them run under
  the profiler.
- ``facts`` carry the expert layer's tallies as ``block_calls`` has them
  (``traced_moe``, ``moe_load_max_over_mean``), the latent cache's
  (``mla``: the window's ``absorbed_forwards``, ``expanded_forwards``,
  ``expanded_rows``), ``prefill_tokens`` and ``prefill_flops``.

A traffic file gives ``generate_calls``'s keys with ``checked_rows`` in
the place of ``checked_calls``.
"""

import gc
import importlib
import statistics
import time

import numpy as np

from .. import compare
from .block_calls import one_call
from .generate_calls import count_failed, make_ring

PROGRAM_SPANS = ("gen.prefill", "gen.decode_step")
DECODE_STEP = "gen.decode_step"


def recorded_spans():
    from incubator_mxnet_tpu.telemetry import tracing
    return tracing.recent_spans()


def drive_window(engine, ring, seconds, traffic, annotate, program_span):
    """Whole calls until `seconds` have passed. -> (calls, wall seconds).
    Traced (`program_span`), a call keeps the program's span records under
    "spans", and the ring is cleared before every call but the first: what
    is in it after the window is the last call's."""
    calls = []
    t0 = time.perf_counter()
    while not calls or time.perf_counter() - t0 < seconds:
        if program_span is not None and calls:
            from incubator_mxnet_tpu.telemetry import tracing
            tracing.clear_spans()
        call = one_call(engine, ring, len(calls), traffic, annotate,
                        program_span)
        if program_span is not None:
            call["spans"] = recorded_spans()
        calls.append(call)
    return calls, time.perf_counter() - t0


# ------------------------------------------------------------ the check
def sampled_rows(calls, count, rng):
    """`count` (call, row) pairs drawn from `calls`: -> [(prompt, served)]."""
    pairs = [(c, r) for c, call in enumerate(calls)
             for r in range(len(call["prompts"]))]
    picked = rng.choice(len(pairs), min(count, len(pairs)), replace=False)
    return [(calls[c]["prompts"][r], calls[c]["served"][r])
            for c, r in (pairs[i] for i in picked)]


def served_gaps(family, cfg, weights, rows, key_rows, precision=None):
    """Every served gap of `rows`, a reference forward a row, its logits
    asked for from the last prompt position on. With `precision` the
    control's reading: at each position of the same prompt and served
    tokens, the gap of the token that the lower precision puts first.
    -> (gaps, margins) as ``generate_calls.served_gaps`` gives them."""
    gaps, margins = [], []
    for prompt, served in rows:
        sequence = np.asarray([list(prompt) + list(served)], np.int32)
        at = np.arange(len(prompt) - 1, sequence.shape[1])[None]
        ref = family.reference.logits(weights, cfg, sequence, at,
                                      key_rows=key_rows)
        tokens = sequence[:, len(prompt) - 1:]      # cut as the logits are
        if precision is not None:
            tokens = compare.first_choices(family.reference.logits(
                weights, cfg, sequence, at, precision=precision,
                key_rows=key_rows), tokens, 1)
        gaps.append(compare.served_gaps(ref, tokens, 1).ravel())
        top2 = np.partition(ref[:, :-1], -2, axis=2)[:, :, -2:]
        margins.append((top2[:, :, 1] - top2[:, :, 0]).ravel())
    return np.concatenate(gaps), np.concatenate(margins)


def key_rows_of(family, traffic):
    """Every checked sequence's keys are padded to the longest a call can
    reach, in whole blocks of the reference: one set of programs."""
    block = family.reference.BLOCK_ROWS
    longest = max(traffic["prompt_lens"]) + traffic["new_tokens"]
    return -(-longest // block) * block


# ------------------------------------------------------------------ run
def window_facts(cfg, traffic, family, calls, wall, peaks):
    stats = [c["stats"] for c in calls]
    rows = len(traffic["prompt_lens"])
    new_tokens = traffic["new_tokens"]
    tokens = sum(len(s) for call in calls for s in call["served"])
    moe_forwards = sum(s["moe"]["forwards"] for s in stats)
    expert_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    mla = {key: sum(s["mla"][key] for s in stats)
           for key in ("absorbed_forwards", "expanded_forwards",
                       "expanded_rows")}
    return {"window_s": wall, "calls": len(calls), "tokens": tokens,
            "tokens_per_s": tokens / wall,
            "prefill_seconds": sum(s["prefill_seconds"] for s in stats),
            "decode_seconds": sum(s["decode_seconds"] for s in stats),
            "decode_steps": new_tokens * len(calls),
            "prefill_tokens": sum(s["prefill_tokens"] for s in stats),
            "prefill_flops": len(calls) * family.costs.prefill_flops(
                cfg, [n - 1 for n in traffic["prompt_lens"]],
                mla["expanded_rows"] / len(calls)),
            "mla": mla,
            "moe_load_max_over_mean": statistics.median(
                x for s in stats for x in s["moe"]["load_max_over_mean"]),
            "decode_step_floor_s": family.costs.decode_step_floor_seconds(
                cfg, rows, sum(s["moe"]["experts_hit"] for s in stats)
                / moe_forwards / expert_layers,
                sum(traffic["prompt_lens"]) + rows * new_tokens // 2, peaks)}


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
    ctx["phase"]("prefill %.3f s, decode %.3f s; latent cache: %s"
                 % (facts["prefill_seconds"], facts["decode_seconds"],
                    facts["mla"]))

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
        facts["traced_moe"] = dict(traced["stats"]["moe"])
        facts["traced_mla"] = dict(traced["stats"]["mla"])

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
    """Yields (index, seed, readings) for ``benchmarks/calibrate.py``: per
    seed new weights, a warm call, one call at the cell's own load, the
    reference over ``checked_rows`` of its rows and, for the first
    `control_seeds` seeds, the control (the configuration's
    ``control_precision``)."""
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
               "call_seconds": [call["t1"] - call["t0"]],
               "stats": {k: call["stats"][k] for k in
                         ("prefill_seconds", "decode_seconds", "mla")},
               "first_tokens": [s[:4] for s in call["served"]]}
        if i < control_seeds:
            name = "control_" + cfg["control_precision"]
            gaps, _ = served_gaps(family, cfg, weights, rows, key_rows,
                                  precision=cfg["control_precision"])
            out[name] = compare.served_numbers(gaps, margins)
        del weights
        gc.collect()
        yield i, seed, out
