"""Plain reference: the ``evabyte`` family (EvaByte's ``config.json``:
``model_type: evabyte``, ``attention_class: eva``), full-sequence forward.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision. No cache, no paging, no kernels: every position's rotated key
and value are computed once, every whole chunk's summary once from all
keys, and attention is a dense softmax with the queries taken a WINDOW at
a time (one compiled shape whatever the sequence's length); logits only at
the positions asked for. It imports nothing of the program and is given
nothing the program made but the tokens it is asked about.

The equations (ISSUE 39; sizes H hidden, heads of d, ``s = d ** -0.5``, F,
V, P prediction heads, window w, chunk c)::

    n_g(x) = x / sqrt(mean(x^2) + eps) * (1 + g)        (norm_add_unit_offset)
    a = n_1(h);  q, k, v = a W_q, a W_k, a W_v  (no bias), split into heads
    q, k rotated at the token's position (theta, all d dimensions)
    h <- h + EVA(q, k, v) W_o;  m = n_2(h)
    h <- h + (silu(m W_g) * (m W_u)) W_d
    logits = n_f(h_L) W_head,  W_head of H x (P V): head j at position t
    scores byte t + 1 + j (columns [j V, (j + 1) V)); served: head 0.

    EVA, a head, with mu, phi in R^d. Position t lies in window t // w and
    chunk t // c; a window is w / c whole chunks.
    summary of chunk j:  ktilde_j = sum_i softmax_i(s k_i . mu) k_i,
                         vtilde_j = sum_i softmax_i(s k_i . phi) v_i
    over the chunk's c rotated keys k_i and values v_i;
    query t sees T_t = {i: i // w = t // w, i <= t} exactly and
    S_t = {j: j's window < t // w} through their summaries, ONE softmax:
    out_t = (sum_{T_t} e^{s q_t.k_i} v_i + sum_{S_t} e^{s q_t.ktilde_j} vtilde_j)
            / (the same sums without v)

Departures from the published model, the program's own too (the
configuration's file lists them under ``assumed``):

- seed-made weights: matrices N(0, init_std); norm offsets g = 0; mu, phi
  N(0, 1) clipped to +-1, times init_std;
- the pooling scores ``k . mu`` and ``k . phi`` carry the factor s;
- summaries pool ROTATED keys, so a query's score with a summary keeps
  each pooled token's relative position;
- the rotary pairs are (i, i + d / 2), not interleaved;
- the head's columns are ordered (P, V);
- no image input; greedy decoding of head 0 (the heads' self-drafting,
  ``multi_byte_generate``, is not modelled).

Leaf names are the program's (``models/eva_byte.py`` ``eva_param_shapes``),
so that one seed-made dict serves both; leaves are made in the dtype the
configuration states and lifted to float32 here.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from .bert import round_trip_8bit, seed_key

PRECISIONS = ("float32", "float8_e4m3")
#: the real configuration's window: the runner pads every checked
#: sequence's keys to whole blocks of this (``key_rows_of``)
BLOCK_ROWS = 2048


def assumed(cfg, key):
    return cfg["assumed"][key]["value"]


def layer_shapes(cfg):
    C, H, F = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["intermediate_size"])
    return {"attn_norm": (C,), "q_w": (C, C), "k_w": (C, C), "v_w": (C, C),
            "o_w": (C, C), "mu": (H, C // H), "phi": (H, C // H),
            "ffn_norm": (C,), "gate_w": (C, F), "up_w": (C, F),
            "down_w": (F, C)}


def init_weights(cfg, seed):
    """All leaves in the configuration's dtype on the default device: the
    matrices N(0, init_std) (``seed_weight_range`` where the file gives
    one), the norms' offsets 0, ``mu`` and ``phi`` N(0, 1) clipped to +-1
    times ``assumed.pool_vector_range`` (init_std as published). One
    compiled program makes a layer, another the two tables."""
    C, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["dtype"])
    std = cfg.get("seed_weight_range", cfg["init_std"])
    pool = assumed(cfg, "pool_vector_range")

    def draw(key, shape, scale):
        return (scale * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def leaf(key, name, shape):
        if name.endswith("_norm"):
            return jnp.zeros(shape, dtype)
        if name in ("mu", "phi"):
            return (pool * jnp.clip(jax.random.normal(
                key, shape, jnp.float32), -1.0, 1.0)).astype(dtype)
        return draw(key, shape, std)

    @jax.jit
    def make_layer(key):
        shapes = layer_shapes(cfg)
        return {n: leaf(jax.random.fold_in(key, i), n, shapes[n])
                for i, n in enumerate(sorted(shapes))}

    @jax.jit
    def make_rest(key):
        k1, k2 = jax.random.split(key)
        width = cfg["num_pred_heads"] * cfg["vocab_size"]
        return {"embed": draw(k1, (cfg["vocab_size"], C), std),
                "head": draw(k2, (C, width), std),
                "final_norm": jnp.zeros((C,), dtype)}

    key = seed_key(seed)
    weights = make_rest(jax.random.fold_in(key, layers))
    for i in range(layers):
        layer = make_layer(jax.random.fold_in(key, i))
        weights.update({"l%d_%s" % (i, n): a for n, a in layer.items()})
    return weights


# ------------------------------------------------------------- the pieces
def _dot(precision):
    if precision == "float32":
        return lambda x, w: jnp.matmul(x, w, precision="highest")

    def eight_bit(x, w):
        return jnp.matmul(round_trip_8bit(x, jnp.float8_e4m3fn),
                          round_trip_8bit(w, jnp.float8_e4m3fn),
                          precision="highest")
    return eight_bit


def _f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * (1.0 + _f32(g)))


def _rope(x, positions, theta):
    """x (B, H, d) at `positions` (B,): pairs (i, i + d / 2)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


class _Frozen(dict):
    """A configuration as a static argument of a jitted piece."""
    def __init__(self, cfg):
        super().__init__(cfg)
        self._text = json.dumps(cfg, sort_keys=True)

    def __hash__(self):
        return hash(self._text)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._text == other._text


@functools.partial(jax.jit, static_argnums=(0, 1))
def _project(cfg, precision, x, positions, gain, q_w, k_w, v_w):
    """A window's stream x (w, C) -> rotated q, rotated k, v, each
    (w, H, d)."""
    dot, H = _dot(precision), cfg["num_attention_heads"]
    a = _norm(x, gain, cfg["rms_norm_eps"])
    q, k, v = [dot(a, _f32(m)).reshape(len(x), H, -1)
               for m in (q_w, k_w, v_w)]
    theta = float(cfg["rope_theta"])
    return _rope(q, positions, theta), _rope(k, positions, theta), v


@functools.partial(jax.jit, static_argnums=(0,))
def _summaries(cfg, k, v, mu, phi):
    """Every chunk's summary of rotated keys k and values v (R, H, d), R
    whole chunks -> ktilde, vtilde (R / c, H, d)."""
    c = cfg["chunk_size"]
    R, H, d = k.shape
    kc, vc = k.reshape(R // c, c, H, d), v.reshape(R // c, c, H, d)
    scale = d ** -0.5

    def pooled(rows, by):
        weights = jax.nn.softmax(jnp.einsum(
            "jihd,hd->jih", kc, _f32(by), precision="highest") * scale,
            axis=1)
        return jnp.sum(weights[..., None] * rows, axis=1)
    return pooled(kc, mu), pooled(vc, phi)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attend(cfg, precision, x, q, k, v, sk, sv, seen, o_w):
    """A window of queries q (w, H, d) against its own keys causally and
    the first `seen` summaries of (sk, sv) (the chunks of earlier
    windows), one softmax, added to the window's stream: -> (w, C)."""
    scale = q.shape[-1] ** -0.5
    own = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
    past = jnp.einsum("qhd,jhd->hqj", q, sk, precision="highest")
    causal = jnp.arange(len(k))[None, :] <= jnp.arange(len(q))[:, None]
    s = jnp.concatenate([
        jnp.where((jnp.arange(len(sk)) < seen)[None, None], past, -jnp.inf),
        jnp.where(causal[None], own, -jnp.inf)], axis=-1) * scale
    p = jax.nn.softmax(s, axis=-1)
    o = (jnp.einsum("hqj,jhd->qhd", p[..., :len(sk)], sv,
                    precision="highest")
         + jnp.einsum("hqk,khd->qhd", p[..., len(sk):], v,
                      precision="highest"))
    return x + _dot(precision)(o.reshape(len(q), -1), _f32(o_w))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _mlp(cfg, precision, x, gain, gate, up, down):
    dot = _dot(precision)
    m = _norm(x, gain, cfg["rms_norm_eps"])
    return x + dot(jax.nn.silu(dot(m, _f32(gate))) * dot(m, _f32(up)),
                   _f32(down))


@jax.jit
def _embed(table, tokens):
    return _f32(table[tokens])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(cfg, precision, x, gain, head):
    return _dot(precision)(_norm(x, gain, cfg["rms_norm_eps"]), _f32(head))


def _sequence_logits(w, cfg, tokens, at, precision, key_rows):
    T, W = len(tokens), cfg["window_size"]
    windows = -(-max(T, key_rows or 0) // W)    # one shape of summaries
    live = -(-T // W)                           # windows that hold a token
    padded = np.zeros(live * W, np.int32)
    padded[:T] = tokens
    spans = [np.arange(i * W, (i + 1) * W, dtype=np.int32)
             for i in range(live)]
    per = W // cfg["chunk_size"]
    X = [_embed(w["embed"], padded[s]) for s in spans]
    for layer in range(cfg["num_hidden_layers"]):
        p = "l%d_" % layer
        parts = [_project(cfg, precision, x, s, w[p + "attn_norm"],
                          w[p + "q_w"], w[p + "k_w"], w[p + "v_w"])
                 for x, s in zip(X, spans)]
        # every chunk's summary, once, from all keys; a window's pads lie
        # after the sequence and in no window that a query sees closed
        pooled = [_summaries(cfg, k, v, w[p + "mu"], w[p + "phi"])
                  for _q, k, v in parts]
        room = [(0, (windows - live) * per), (0, 0), (0, 0)]
        sk, sv = [jnp.pad(jnp.concatenate([pair[i] for pair in pooled]),
                          room) for i in (0, 1)]
        X = [_attend(cfg, precision, x, q, k, v, sk, sv, i * per,
                     w[p + "o_w"])
             for i, (x, (q, k, v)) in enumerate(zip(X, parts))]
        X = [_mlp(cfg, precision, x, w[p + "ffn_norm"], w[p + "gate_w"],
                  w[p + "up_w"], w[p + "down_w"]) for x in X]
    return np.asarray(_head(cfg, precision,
                            jnp.concatenate(X)[np.asarray(at)],
                            w["final_norm"], w["head"]))


def logits(w, cfg, tokens, at, precision="float32", key_rows=None,
           all_heads=False):
    """(N, T) int32 tokens -> (N, P, V) float32 logits of the SERVED head
    (head 0) at the positions `at` (N, P); position t's logits choose
    byte t + 1. `all_heads`: every prediction head's, (N, P, heads * V).
    "float8_e4m3", the control: the operands of every linear layer's
    matrix product (q, k, v, o, gate, up, down, head) through a
    per-tensor scaled e4m3 round trip, the nearest precision below the
    bfloat16 the configuration states; all else as in float32.
    `key_rows`: every sequence's summaries are padded as for so many
    positions (sequences of different lengths then share their compiled
    programs)."""
    if precision not in PRECISIONS:
        raise ValueError("no such precision: %r" % precision)
    cfg = _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        out = np.stack([
            _sequence_logits(w, cfg, np.asarray(row, np.int32), positions,
                             precision, key_rows)
            for row, positions in zip(tokens, at)])
    return out if all_heads else out[..., :cfg["vocab_size"]]
