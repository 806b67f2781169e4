"""The comparison that decides ``correct``.

Each number compared has a limit of its own, read from the cell's file
under ``benchmarks/limits/``; how each limit was set is in ``PERF.md``.
"""

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone (a key's bias under softmax): it is
# left out of the change, by this rule and not by name
ZERO_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(prog, ref, leaves=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. -> (gap, leaf)"""
    leaves = sorted(ref if leaves is None else leaves)
    floor = float(np.median([ref[n] for n in leaves]))
    gaps = [abs(prog[n] - ref[n]) / max(ref[n], floor) for n in leaves]
    i = int(np.argmax(gaps))
    return float(gaps[i]), leaves[i]


def median_leaf_difference(prog, ref):
    """The median leaf's norm of the DIFFERENCE between the program's
    first gradient and the reference's, against the reference's norm of
    that leaf or of the median leaf, whichever is larger. The worst leaf's
    is noise (a bfloat16 pipeline reads 0.08 on one small leaf where the
    median reads 0.01); the median is what 8-bit operands move and the
    norms cannot see (PERF.md section 2). `prog`, `ref`: {leaf: host array}"""
    norms = {n: float(np.linalg.norm(np.asarray(a, np.float32).ravel()))
             for n, a in ref.items()}
    floor = float(np.median(list(norms.values())))
    return float(np.median([
        np.linalg.norm((np.asarray(prog[n], np.float32)
                        - np.asarray(ref[n], np.float32)).ravel())
        / max(norms[n], floor) for n in sorted(ref)]))


def training_numbers(prog, ref):
    """`prog` and `ref`: {"losses", "grad_norms", "delta_norms",
    "first_gradient"} of the same first steps.
    -> ({number: value}, {number: where it was worst})"""
    loss_gaps = [abs(p - r) / abs(r)
                 for p, r in zip(prog["losses"], ref["losses"])]
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"],
                                         ref["grad_norms"])
    median = float(np.median(list(ref["grad_norms"].values())))
    moved = [n for n, g in ref["grad_norms"].items()
             if g >= ZERO_GRADIENT_SHARE * median]
    delta_gap, delta_leaf = worst_leaf_gap(prog["delta_norms"],
                                           ref["delta_norms"], moved)
    return ({"loss_gap": float(max(loss_gaps)),
             "grad_norm_gap": grad_gap, "delta_norm_gap": delta_gap,
             "grad_diff_median": median_leaf_difference(
                 prog["first_gradient"], ref["first_gradient"])},
            {"loss_gap": "step %d" % (1 + int(np.argmax(loss_gaps))),
             "grad_norm_gap": grad_leaf, "delta_norm_gap": delta_leaf,
             "grad_diff_median": "median leaf"})


def served_gaps(ref_logits, tokens, prompt_len):
    """How far each served token's logit lies below the reference's best.
    ref_logits (N, T, V) over tokens (N, T) = prompt + served; position
    p's logits choose token p + 1. -> (N, served) gaps, each >= 0"""
    rows = np.asarray(ref_logits)[:, prompt_len - 1:-1]      # (N, new, V)
    served = np.asarray(tokens)[:, prompt_len:]
    chosen = np.take_along_axis(rows, served[:, :, None], axis=2)[:, :, 0]
    return rows.max(axis=2) - chosen


def first_choices(other_logits, tokens, prompt_len):
    """The control's side: `tokens` with every served token replaced by
    the one that `other_logits` puts first at its position, so that
    ``served_gaps`` reads the control as it reads the program."""
    tokens = np.array(tokens)
    tokens[:, prompt_len:] = np.asarray(other_logits)[
        :, prompt_len - 1:-1].argmax(axis=2)
    return tokens


# the reference's best logit within this of its second best: a close call,
# which the rounding of sound float32 arithmetic may decide either way
CLOSE_CALL_LOGITS = 0.1


def served_numbers(gaps, margins):
    """`gaps`: every served gap of the checked requests; `margins`: at
    the same positions, the reference's best logit over its second best.
    The summed gap is taken per close call: how many positions a seed's
    weights leave close varies by half from seed to seed and moves the
    program's gaps and a lower precision's alike, so an absolute mean
    cannot part them and this does (PERF.md section 2)."""
    close = max(1, int(np.sum(np.asarray(margins) < CLOSE_CALL_LOGITS)))
    return {"served_gap_per_close_call": float(np.sum(gaps)) / close}


def judge(numbers, limits):
    """-> (correct, [[name, value, limit], ...]). The cell's file gives
    every number computed a limit, a number and not null, and names no
    other: anything else is an error, not a pass. A number that is not
    finite fails."""
    if set(numbers) != set(limits):
        raise KeyError("numbers %s, limits %s"
                       % (sorted(numbers), sorted(limits)))
    rows, ok = [], True
    for name, value in numbers.items():
        limit = float(limits[name])     # null or a word: an error
        rows.append([name, value, limit])
        if not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, rows
