"""Runner for traffic of the kind "block_calls": ``generate_calls``'s closed
loop (one client, ``GenerateEngine.generate`` back to back over a ring of
seed-made prompt sets, whole calls until ``--seconds`` have passed) for a
block-diffusion decoder, whose step commits a block and not a token.

What differs from ``generate_calls``: a decode step is a block forward
(``decode_steps`` = the denoising and store forwards of the window, from
the engine's ``last_stats``); the ring of spans is drained after every
call of a traced run, so that a window of a dozen calls at some 540 records
each loses none; and ``correct`` checks the denoising forwards themselves.

``correct``: the engine records what every denoising forward was given
(the block's tokens, MASK where still masked), what it chose (``x0``, its
confidence) and which positions it fixed. Once the window has closed,
``checked_blocks`` (call, block, row) triples are drawn from the seed out
of ``checked_calls`` of its calls; for each denoising forward of each the
plain reference is given the program's own input (the row's prompt and
served tokens up to the block, then the block as the forward saw it) and
yields the block's logits. Two numbers (``block_numbers``):

- ``served_gap_per_close_call`` as ``generate_calls`` defines it, over the
  positions that forward fixed: how far the reference's logit of the fixed
  token lies below its best, summed, per position at which the
  reference's two best lie within 0.1;
- ``chosen_confidence_gap``: over the forwards that had a choice (fewer
  positions to fix than masked), the mean share by which the reference's
  confidence summed over the positions the program fixed lies under that
  summed over the reference's own choice of positions.

A traffic file gives ``generate_calls``'s keys and ``denoise_steps``,
``prefill_chunk``, ``checked_blocks``, ``token_id_end`` (prompts draw their
ids below it: the tokenizer's special ids and MASK lie above).
"""

import gc
import importlib
import statistics
import time

import numpy as np

from .. import compare
from .generate_calls import count_failed, drive_calls, make_ring

PROGRAM_SPANS = ("gen.prefill", "gen.block")
BLOCK_SPANS = ("gen.block", "gen.denoise_step", "gen.block_store")
REFERENCE_ROWS = 16         # sequences a reference forward


def one_call(engine, ring, index, traffic, annotate, program_span):
    calls, _wall = drive_calls(engine, ring, index, 0.0,
                               traffic["new_tokens"], annotate, program_span)
    return calls[0]


def drive_window(engine, ring, seconds, traffic, annotate, program_span,
                 drain):
    """Whole calls until `seconds` have passed. -> (calls, wall seconds);
    `drain` (or None) is called after each call and keeps what it
    returns under the call's "spans"."""
    calls = []
    t0 = time.perf_counter()
    while not calls or time.perf_counter() - t0 < seconds:
        call = one_call(engine, ring, len(calls), traffic, annotate,
                        program_span)
        if drain is not None:
            call["spans"] = drain()
        calls.append(call)
    return calls, time.perf_counter() - t0


def drain_ring():
    from incubator_mxnet_tpu.telemetry import tracing
    spans = tracing.recent_spans()
    tracing.clear_spans()
    return spans


# ------------------------------------------------------------ the check
def sampled_forwards(calls, count, steps, rng):
    """`count` (call, block, row) triples drawn from `calls`; -> one
    entry a denoising forward of each in which the row had a masked
    position: {"prefix", "tokens", "masked", "fixed", "x0", "steps_left"}."""
    triples = [(c, b, r) for c, call in enumerate(calls)
               for b, block in enumerate(call["stats"]["blocks"])
               for r in range(len(block["rows"]))]
    out = []
    for t in rng.choice(len(triples), min(count, len(triples)),
                        replace=False):
        c, b, r = triples[t]
        block = calls[c]["stats"]["blocks"][b]
        row = block["rows"][r]
        sequence = list(calls[c]["prompts"][row]) + list(
            calls[c]["served"][row])
        for i, step in enumerate(block["steps"]):
            if step["masked"][r].any():
                out.append({"prefix": sequence[:block["starts"][r]],
                            "tokens": step["tokens"][r],
                            "masked": step["masked"][r],
                            "fixed": step["fixed"][r], "x0": step["x0"][r],
                            "steps_left": steps - i})
    return out


def reference_logits(family, cfg, weights, forwards, length, precision):
    """The reference over each forward's own input, REFERENCE_ROWS
    sequences a time, every sequence padded to `length` (the pad lies in
    later blocks, which the block cannot see). Yields (forward, logits
    (B, V)) in order."""
    B = len(forwards[0]["tokens"])
    for lo in range(0, len(forwards), REFERENCE_ROWS):
        batch = forwards[lo:lo + REFERENCE_ROWS]
        tokens = np.zeros((len(batch), length), np.int32)
        at = np.zeros((len(batch), B), np.int32)
        for n, fwd in enumerate(batch):
            start = len(fwd["prefix"])
            tokens[n, :start] = fwd["prefix"]
            tokens[n, start:start + B] = fwd["tokens"]
            at[n] = start + np.arange(B)
        logits = family.reference.logits(weights, cfg, tokens, at=at,
                                         precision=precision,
                                         block_rows=REFERENCE_ROWS)
        yield from zip(batch, logits)


def without_mask(logits, mask_id):
    """The family's rule that a position is never fixed to MASK: its
    logit is left out of the choice and of the confidence."""
    logits = np.array(logits)
    logits[:, mask_id] = -np.inf
    return logits


def confidence_of(logits):
    """(B, V) -> the softmax probability of each position's best token."""
    z = logits.astype(np.float64)
    z -= z.max(axis=1, keepdims=True)
    return 1.0 / np.exp(z).sum(axis=1)


def block_numbers(family, cfg, weights, forwards, length, control=None):
    """The two numbers of `forwards` against the plain reference. With
    `control` (a precision) the control's reading: the reference at that
    precision put in the program's place, choosing tokens and positions
    from its own logits of the same inputs."""
    fix = family.reference.fix_most_confident
    mask_id = family.assumed(cfg, "mask_token_id")
    acted = (reference_logits(family, cfg, weights, forwards, length,
                              control) if control else None)
    gaps, margins, shares = [], [], []
    for fwd, ref in reference_logits(family, cfg, weights, forwards, length,
                                     "float32"):
        masked, fixed, x0 = fwd["masked"], fwd["fixed"], fwd["x0"]
        ref = without_mask(ref, mask_id)
        if acted is not None:
            low = without_mask(next(acted)[1], mask_id)
            x0 = low.argmax(axis=1)
            fixed = fix(masked, confidence_of(low), fwd["steps_left"])
        where = np.flatnonzero(fixed)
        top2 = np.partition(ref[where], -2, axis=1)[:, -2:]
        gaps.append(top2[:, 1] - ref[where, x0[where]])
        margins.append(top2[:, 1] - top2[:, 0])
        if fixed.sum() < masked.sum():              # there was a choice
            confidence = confidence_of(ref)
            own = fix(masked, confidence, fwd["steps_left"])
            shares.append(1.0 - confidence[fixed].sum()
                          / confidence[own].sum())
    numbers = compare.served_numbers(np.concatenate(gaps),
                                     np.concatenate(margins))
    numbers["chosen_confidence_gap"] = (float(np.mean(shares)) if shares
                                        else 0.0)
    return numbers, {"forwards": len(forwards), "with_a_choice": len(shares),
                     "positions": int(sum(len(g) for g in gaps)),
                     "close_calls": int(sum(
                         (m < compare.CLOSE_CALL_LOGITS).sum()
                         for m in margins))}


def reference_length(traffic, block_length):
    """Every checked sequence is padded to the longest a call can reach."""
    longest = max(traffic["prompt_lens"]) + traffic["new_tokens"]
    return -(-longest // block_length) * block_length


def masked_served(calls, mask_id):
    return sum(1 for call in calls for served in call["served"]
               if mask_id in served)


# ------------------------------------------------------------------ run
def span_seconds(calls, name):
    return [s["dur_us"] / 1e6 for call in calls
            for s in call.get("spans", ()) if s["name"] == name]


def forward_split(calls, parent):
    """Median milliseconds of each child span under the window's `parent`
    spans (a denoising forward or a store pass), and the median bytes its
    ``lm.dispatch`` shipped: the split the ``lm_*`` / ``kv_*`` readers give
    a ``gen.decode_step``, for the note on standard error."""
    spans = [s for call in calls for s in call.get("spans", ())]
    ids = {s["span_id"] for s in spans if s["name"] == parent}
    by_name = {}
    for s in spans:
        if s.get("parent_id") in ids:
            by_name.setdefault(s["name"], []).append(s)
    out = {name: round(statistics.median(r["dur_us"] for r in recs) / 1e3, 3)
           for name, recs in sorted(by_name.items())}
    if "lm.dispatch" in by_name:
        out["h2d_bytes"] = statistics.median(
            r.get("h2d_bytes", 0) for r in by_name["lm.dispatch"])
    return out


def run(ctx):
    import jax
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    new_tokens = traffic["new_tokens"]
    rows = len(traffic["prompt_lens"])
    block_length = family.assumed(cfg, "block_length")
    ctx["phase"]("runner entered")
    family.require_program()
    weights = family.reference.init_weights(cfg, ctx["seed"])
    jax.block_until_ready(weights)
    ctx["phase"]("weights made")
    engine, cache = family.build_engine(cfg, weights, traffic)
    ring = make_ring({"vocab_size": traffic["token_id_end"]}, traffic,
                     ctx["seed"])
    ctx["phase"]("engine built")
    # warms every shape of the window: the prefill chunk, and the block
    # forwards of all rows and of the rows that a prompt's tail leaves
    # one block behind (the last block of a call)
    engine.generate(ring[-1], max_new_tokens=traffic["warm_new_tokens"])
    ctx["phase"]("warmed")
    program_span = drain = None
    if ctx["tracer"] is not None:
        from incubator_mxnet_tpu.telemetry import tracing
        tracing.clear_spans()
        program_span, drain = tracing.Span, drain_ring

    setup_s = time.time() - ctx["t_start"]
    calls, wall = drive_window(engine, ring, ctx["seconds"], traffic,
                               ctx["annotate"], program_span, drain)
    ctx["phase"]("window closed: %d calls in %.3f s; seconds a call: %s"
                 % (len(calls), wall, " ".join(
                     "%.3f" % (c["t1"] - c["t0"]) for c in calls)))
    tokens = sum(len(s) for call in calls for s in call["served"])
    stats = [c["stats"] for c in calls]
    forwards = sum(sum(s["block_forwards"].values()) for s in stats)
    denoise = sum(s["block_forwards"]["denoise"] for s in stats)
    moe_forwards = sum(s["moe"]["forwards"] for s in stats)
    layers = cfg["num_hidden_layers"]
    facts = {"setup_s": setup_s, "window_s": wall, "calls": len(calls),
             "tokens": tokens, "tokens_per_s": tokens / wall,
             "prefill_seconds": sum(s["prefill_seconds"] for s in stats),
             "decode_seconds": sum(s["decode_seconds"] for s in stats),
             "decode_steps": forwards,
             "block_row_forwards": sum(s["block_row_forwards"]
                                       for s in stats),
             "block_positions_committed": sum(
                 s["block_positions_committed"] for s in stats),
             "moe_load_max_over_mean": statistics.median(
                 x for s in stats for x in s["moe"]["load_max_over_mean"]),
             "kv_host_bytes_per_step": family.kv_host_bytes(cfg, cache),
             "decode_step_floor_s": family.block_forward_floor_seconds(
                 cfg, rows * block_length,
                 sum(s["moe"]["experts_hit"] for s in stats)
                 / moe_forwards / layers, denoise / forwards,
                 sum(traffic["prompt_lens"]) + rows * new_tokens // 2,
                 ctx["peaks"])}

    if ctx["tracer"] is not None:
        with ctx["tracer"]:
            traced = one_call(engine, ring, len(calls), traffic,
                              ctx["annotate"], program_span)
        traced["spans"] = drain()
        facts["program_spans"] = [
            (s["name"], s["ts_us"] / 1e6, (s["ts_us"] + s["dur_us"]) / 1e6)
            for s in traced["spans"] if s["name"] in PROGRAM_SPANS]
        facts["block_span_seconds"] = {name: span_seconds(calls, name)
                                       for name in BLOCK_SPANS}
        facts["traced_moe"] = dict(traced["stats"]["moe"], layers=layers)
        for parent in ("gen.denoise_step", "gen.block_store", "gen.prefill"):
            ctx["phase"]("median ms under %s: %s"
                         % (parent, forward_split(calls, parent)))

    facts["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in ctx["devices"][:ctx["cell"]["chips"]])
    failed = (count_failed(calls, new_tokens, cfg["vocab_size"])
              + masked_served(calls, family.assumed(cfg, "mask_token_id")))
    del engine, cache
    gc.collect()

    rng = np.random.default_rng(ctx["seed"])
    picked = rng.choice(len(calls), min(traffic["checked_calls"],
                                        len(calls)), replace=False)
    numbers, counted = block_numbers(
        family, cfg, weights,
        sampled_forwards([calls[i] for i in picked],
                         traffic["checked_blocks"],
                         traffic["denoise_steps"], rng),
        reference_length(traffic, block_length))
    ctx["phase"]("checked: %s" % counted)
    correct, compared = compare.judge(numbers, ctx["limits"])
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
    own load, the reference over ``checked_blocks`` of their blocks and,
    for the first `control_seeds` seeds, the control (the configuration's
    ``control_precision``)."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    family = importlib.import_module(cfg["family"])
    length = reference_length(traffic, family.assumed(cfg, "block_length"))
    for i, seed in enumerate(seeds):
        weights = family.reference.init_weights(cfg, seed)
        engine, cache = family.build_engine(cfg, weights, traffic)
        ring = make_ring({"vocab_size": traffic["token_id_end"]}, traffic,
                         seed)
        engine.generate(ring[-1], max_new_tokens=traffic["warm_new_tokens"])
        calls = [one_call(engine, ring, n, traffic, ctx["annotate"], None)
                 for n in range(traffic["checked_calls"])]
        del engine, cache
        gc.collect()
        forwards = sampled_forwards(calls, traffic["checked_blocks"],
                                    traffic["denoise_steps"],
                                    np.random.default_rng(seed))
        t0 = time.time()
        numbers, counted = block_numbers(family, cfg, weights, forwards,
                                         length)
        out = {"program": numbers, "counted": counted,
               "reference_seconds": time.time() - t0,
               "call_seconds": [c["t1"] - c["t0"] for c in calls],
               "first_tokens": [s[:8] for s in calls[0]["served"]]}
        if i < control_seeds:
            out["control_" + cfg["control_precision"]], _ = block_numbers(
                family, cfg, weights, forwards, length,
                control=cfg["control_precision"])
        del weights
        gc.collect()
        yield i, seed, out
