"""Plain reference: the ``kimi_k2`` family (moonshotai Kimi-K2.7-Code's
``config.json``) as ONE chip's share of a deployment, full-sequence forward.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision. No cache, no paging, no kernels, no sorting, no absorbed
products: every position's latent is up-projected to per-head keys and
values (the EXPANDED form everywhere), attention is a dense causal softmax
with the queries taken a block of rows at a time, and the expert layer is a
plain loop over the experts HELD, each over its own tokens. A sequence is
computed a block of ``BLOCK_ROWS`` positions at a time (one compiled shape
whatever its length) and its logits only at the positions asked for. It
imports nothing of the program and is given nothing the program made but
the tokens it is asked about.

With ``x`` a token's one residual stream and ``RMS_g(z) = g z /
sqrt(mean(z^2) + eps)`` (the issue's equations: DeepSeek-V3's layer)::

    x <- x + Attn(RMS_g1(x));   x <- x + FFN(RMS_g2(x))
    logits = RMS_g(x_L) W_head                              (untied head)

    attention, h at position t:
    c_q = RMS(h W_qa);  [q_n | q_r]_h = c_q W_qb;  [c | k_r] = h W_kva
    c = RMS(c);  q_r, k_r = rope(., t)  (YaRN frequencies, pairs (i, i + 32))
    [k_n | v]_h = c W_kvb
    s_h(t, j) = scale (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)),  j <= t
    out = concat_h(softmax_j(s_h) v_h) W_o
    scale = (d_n + d_r)^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1

    feed-forward: down(silu(gate h) * up h) in the leading dense layers;
    then s = sigmoid(h W_r) over ALL ``router_width`` experts,
    chosen = top-k of s + bias,
    w_e = scaling s_e / (sum_chosen s + 1e-20)      (over all k chosen)
    out_here = sum_{e in chosen, e held here} w_e Expert_e(h)
               + Expert_shared(h)

Departures from the published model, the program's own too (the
configuration's file lists them under ``assumed`` and ``deployment``):

- seed-made weights;
- the rotary pairs are (i, i + 32), not interleaved;
- THE PARTIAL EXPERT SUM: this chip holds the ``n_routed_experts``
  experts from ``expert_share_index * n_routed_experts`` on, of the
  ``router_width`` the router scores. A chosen expert held elsewhere adds
  nothing here (its chip would, and an exchange would sum the parts;
  neither is modelled), and ``out_here`` is what goes on to the next layer;
- the vocabulary slice: embedding and head hold ``vocab_size`` rows, the
  first of the published table, and logits are over them;
- no vision tower (the catalog row's ``config`` is the language model).

Leaf names are the program's (``models/mla_moe.py`` ``mla_param_shapes``),
so that one seed-made dict serves both; leaves are made in the dtype the
configuration states and lifted to float32 here.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .bert import round_trip_8bit, seed_key

PRECISIONS = ("float32", "float8_e4m3")
BLOCK_ROWS = 512            # positions a block of the forward


def assumed(cfg, key):
    return cfg["assumed"][key]["value"]


def held_experts(cfg):
    """(first, count): the routed experts this chip holds."""
    count = cfg["n_routed_experts"]
    return assumed(cfg, "expert_share_index") * count, count


def layer_shapes(cfg, dense):
    """The leaves of one layer (a leading dense one, or an expert layer:
    the router over all ``router_width`` experts, the experts held)."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    d_n, d_r, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    shapes = {"attn_norm": (C,), "q_a": (C, r_q), "q_a_norm": (r_q,),
              "q_b": (r_q, H * (d_n + d_r)), "kv_a": (C, r_kv + d_r),
              "kv_a_norm": (r_kv,), "kv_b": (r_kv, H * (d_n + d_v)),
              "o_w": (H * d_v, C), "ffn_norm": (C,)}
    if dense:
        F = cfg["intermediate_size"]
        shapes.update(gate_w=(C, F), up_w=(C, F), down_w=(F, C))
    else:
        E, held, f = (cfg["router_width"], cfg["n_routed_experts"],
                      cfg["moe_intermediate_size"])
        fs = cfg["n_shared_experts"] * f
        shapes.update(router_w=(C, E), router_bias=(E,),
                      gate_w=(held, C, f), up_w=(held, C, f),
                      down_w=(held, f, C), shared_gate_w=(C, fs),
                      shared_up_w=(C, fs), shared_down_w=(fs, C))
    return shapes


def init_weights(cfg, seed):
    """All leaves in the configuration's dtype on the default device: the
    matrices N(0, initializer_range); gains 1; the router's choice bias
    N(0, router_bias_range) (the ranges: the file's ``assumed``). One
    compiled program makes a layer of each kind, so that no more than one
    leaf's float32 random bits are live beside the weights; another makes
    the two tables."""
    C, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["dtype"])
    std = cfg.get("seed_weight_range", assumed(cfg, "initializer_range"))
    bias_range = assumed(cfg, "router_bias_range")

    def draw(key, shape, scale):
        return (scale * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def leaf(key, name, shape):
        if name.endswith("_norm"):
            return jnp.ones(shape, dtype)
        return draw(key, shape,
                    bias_range if name == "router_bias" else std)

    @functools.partial(jax.jit, static_argnums=1)
    def make_layer(key, dense):
        shapes = layer_shapes(cfg, dense)
        return {n: leaf(jax.random.fold_in(key, i), n, shapes[n])
                for i, n in enumerate(sorted(shapes))}

    @jax.jit
    def make_rest(key):
        k1, k2 = jax.random.split(key)
        return {"embed": draw(k1, (cfg["vocab_size"], C), std),
                "head": draw(k2, (C, cfg["vocab_size"]), std),
                "final_norm": jnp.ones((C,), dtype)}

    key = seed_key(seed)
    weights = make_rest(jax.random.fold_in(key, layers))
    for i in range(layers):
        layer = make_layer(jax.random.fold_in(key, i),
                           i < cfg["first_k_dense_replace"])
        weights.update({"l%d_%s" % (i, n): a for n, a in layer.items()})
    return weights


# ------------------------------------------------------------- the pieces
def yarn_frequencies(cfg):
    """The 32 rotary frequencies under YaRN, and the integers ``low`` and
    ``high`` between which the ramp runs."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    ys = cfg["rope_scaling"]
    i = np.arange(dim // 2, dtype=np.float64)
    fe = base ** (-2 * i / dim)
    fi = fe / ys["factor"]

    def corr(b):
        return (dim * math.log(ys["original_max_position_embeddings"]
                               / (2 * math.pi * b)) / (2 * math.log(base)))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return fi * ramp + fe * (1 - ramp), low, high


def softmax_scale(cfg):
    ys = cfg["rope_scaling"]
    m = 0.1 * ys["mscale_all_dim"] * math.log(ys["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


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


def _rms(z, g, eps):
    return g * z * jax.lax.rsqrt(jnp.mean(jnp.square(z), -1, keepdims=True)
                                 + eps)


def _rope(x, positions, inv_freq):
    """x (B, ..., 64) at `positions` (B,): pairs (i, i + 32)."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq     # (B, 32)
    ang = ang.reshape((len(positions),) + (1,) * (x.ndim - 2) + (-1,))
    half = x.shape[-1] // 2
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


@functools.partial(jax.jit, static_argnums=(0,))
def _normed(cfg, x, gain):
    return _rms(x, _f32(gain), cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _project(cfg, precision, h, positions, q_a, q_a_norm, q_b, kv_a,
             kv_a_norm, kv_b):
    """h (B, C) -> q_n (B, H, d_n), q_r (B, H, d_r), k_n (B, H, d_n),
    k_r (B, d_r), v (B, H, d_v): the expanded form."""
    dot, eps = _dot(precision), cfg["rms_norm_eps"]
    B, H = h.shape[0], cfg["num_attention_heads"]
    r, d_n = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    inv_freq = jnp.asarray(yarn_frequencies(cfg)[0], jnp.float32)
    q = dot(_rms(dot(h, _f32(q_a)), _f32(q_a_norm), eps),
            _f32(q_b)).reshape(B, H, -1)
    kv = dot(h, _f32(kv_a))
    c = _rms(kv[:, :r], _f32(kv_a_norm), eps)
    expanded = dot(c, _f32(kv_b)).reshape(B, H, -1)
    return (q[..., :d_n], _rope(q[..., d_n:], positions, inv_freq),
            expanded[..., :d_n], _rope(kv[:, r:], positions, inv_freq),
            expanded[..., d_n:])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attend(cfg, precision, x, q_n, q_r, positions, k_n, k_r, v, o_w):
    """A block of queries against every key (K, ...) at positions 0..K-1,
    causal, added to the block's stream: -> (B, C)."""
    s = (jnp.einsum("qhd,khd->hqk", q_n, k_n, precision="highest")
         + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision="highest"))
    sees = jnp.arange(k_n.shape[0])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(sees[None], s * softmax_scale(cfg),
                                 -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
    return x + _dot(precision)(o.reshape(o.shape[0], -1), _f32(o_w))


@functools.partial(jax.jit, static_argnums=(0,))
def _gated(precision, h, gate, up, down):
    dot = _dot(precision)
    return dot(jax.nn.silu(dot(h, _f32(gate))) * dot(h, _f32(up)),
               _f32(down))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _route(cfg, precision, h, router_w, bias):
    """Over ALL the router's experts -> (the chosen (T, k), their weights
    (T, k), normalised over all k chosen)."""
    s = jax.nn.sigmoid(_dot(precision)(h, _f32(router_w)))
    _, chosen = jax.lax.top_k(s + _f32(bias), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, (cfg["routed_scaling_factor"] * picked
                    / (jnp.sum(picked, -1, keepdims=True) + 1e-20))


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _one_expert(precision, out, h, index, weight, e, gate, up, down):
    """`out` plus held expert `e`'s weighted output for its own tokens: the
    rows `index` of h (padded with an index past h: dropped)."""
    dot = _dot(precision)
    rows = h.at[index].get(mode="fill", fill_value=0.0)
    hidden = (jax.nn.silu(dot(rows, _f32(gate[e])))
              * dot(rows, _f32(up[e])))
    return out.at[index].add(weight[:, None] * dot(hidden, _f32(down[e])),
                             mode="drop")


def _experts_here(w, p, cfg, precision, h, live):
    """h (T, C), of which the first `live` rows are the sequence: the
    shared expert over all, then every HELD expert over the tokens that
    chose it; a chosen expert held elsewhere adds nothing."""
    chosen, weights = jax.device_get(_route(
        cfg, precision, h, w[p + "router_w"], w[p + "router_bias"]))
    out = _gated(precision, h, w[p + "shared_gate_w"], w[p + "shared_up_w"],
                 w[p + "shared_down_w"])
    first, count = held_experts(cfg)
    for e in range(count):
        tokens, slot = np.nonzero(chosen[:live] == first + e)
        if not len(tokens):
            continue
        room = 1 << max(6, int(len(tokens) - 1).bit_length())  # few shapes
        index = np.full(room, len(h), np.int32)
        index[:len(tokens)] = tokens
        weight = np.zeros(room, np.float32)
        weight[:len(tokens)] = weights[tokens, slot]
        out = _one_expert(precision, out, h, index, weight, e,
                          w[p + "gate_w"], w[p + "up_w"], w[p + "down_w"])
    return out


@jax.jit
def _embed(table, tokens):
    return _f32(table[tokens])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(cfg, precision, x, gain, head):
    return _dot(precision)(_rms(x, _f32(gain), cfg["rms_norm_eps"]),
                           _f32(head))


def _pad_rows(blocks, rows):
    """The blocks end to end, zero rows up to `rows`."""
    a = jnp.concatenate(blocks)
    return jnp.pad(a, [(0, rows - len(a))] + [(0, 0)] * (a.ndim - 1))


def _sequence_logits(w, cfg, tokens, at, precision, block_rows, key_rows):
    T, B = len(tokens), block_rows
    blocks = -(-T // B)
    key_rows = max(key_rows or 0, blocks * B)
    padded = np.zeros(blocks * B, np.int32)
    padded[:T] = tokens
    spans = [np.arange(i * B, (i + 1) * B, dtype=np.int32)
             for i in range(blocks)]
    X = [_embed(w["embed"], padded[s]) for s in spans]
    for layer in range(cfg["num_hidden_layers"]):
        p = "l%d_" % layer
        parts = [_project(cfg, precision, _normed(cfg, x, w[p + "attn_norm"]),
                          s, w[p + "q_a"], w[p + "q_a_norm"], w[p + "q_b"],
                          w[p + "kv_a"], w[p + "kv_a_norm"], w[p + "kv_b"])
                 for x, s in zip(X, spans)]
        k_n, k_r, v = [_pad_rows([part[i] for part in parts], key_rows)
                       for i in (2, 3, 4)]
        X = [_attend(cfg, precision, x, part[0], part[1], s, k_n, k_r, v,
                     w[p + "o_w"]) for x, part, s in zip(X, parts, spans)]
        hs = [_normed(cfg, x, w[p + "ffn_norm"]) for x in X]
        if layer < cfg["first_k_dense_replace"]:
            ys = [_gated(precision, h, w[p + "gate_w"], w[p + "up_w"],
                         w[p + "down_w"]) for h in hs]
        else:
            out = _experts_here(w, p, cfg, precision,
                                _pad_rows(hs, key_rows), T)
            ys = [out[s] for s in spans]
        X = [x + y for x, y in zip(X, ys)]
    return np.asarray(_head(cfg, precision,
                            jnp.concatenate(X)[np.asarray(at)],
                            w["final_norm"], w["head"]))


def logits(w, cfg, tokens, at, precision="float32", block_rows=BLOCK_ROWS,
           key_rows=None):
    """(N, T) int32 tokens -> (N, P, V) float32 logits at the positions
    `at` (N, P); position t's logits choose token t + 1. "float8_e4m3",
    the control: the operands of every linear layer's matrix product (the
    attention projections with the latent's up-projection, the dense
    layer, the router, the experts held, the shared expert, the head)
    through a per-tensor scaled e4m3 round trip, the nearest precision
    below the bfloat16 the configuration states; all else as in float32.
    `key_rows`: every sequence's keys are padded to so many rows
    (sequences of different lengths then share their compiled programs)."""
    if precision not in PRECISIONS:
        raise ValueError("no such precision: %r" % precision)
    cfg = _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        return np.stack([
            _sequence_logits(w, cfg, np.asarray(row, np.int32), positions,
                             precision, block_rows, key_rows)
            for row, positions in zip(tokens, at)])
