"""Plain reference: the ``sdar_moe`` family (JetLM SDAR-30B-A3B-Chat's
``config.json``), full-sequence forward under the block mask.

Straight ``jax.numpy`` in float32 with every matrix product at
``highest`` precision. No cache, no paging, no kernels, no sorting: the
whole sequence under a dense T x T block mask, and every token's experts by
a plain loop over ALL experts in which an expert the token did not choose
has weight zero. Sequences are computed in blocks of rows, so that the
float32 temporaries fit beside the bfloat16 weights. It imports nothing of
the program and is given nothing the program made but the tokens it is
asked about.

The layer, with ``x`` its input (from the issue's equations; Qwen3-MoE's
layer with the SDAR family's mask)::

    h  = rmsnorm(x; attn_norm)
    q  = rmsnorm_per_head(h q_w; q_norm)   k likewise   v = h v_w
    q, k = rope(q, pos), rope(k, pos)            rotate-half, absolute pos
    a  = softmax(q_h k_{h // G}^T / sqrt(D) + M) v_{h // G}
    x  = x + a o_w
    h2 = rmsnorm(x; ffn_norm)
    p  = softmax(h2 router_w) over all experts;  T = top-k of p
    w_e = p_e / sum_{e in T} p_e
    x  = x + sum_{e in T} w_e (silu(h2 gate_w[e]) * (h2 up_w[e])) down_w[e]
    logits = rmsnorm(x; final_norm) head

``M``: position i sees j iff ``j // B <= i // B``. A masked position holds
the MASK token and predicts its own token (no shift).

Departures from the published model, the program's own too: seed-made
weights N(0, initializer_range), gains 1; the per-head q/k norms, the mask
token, the block length and the no-shift rule are the family's convention
and not in ``config.json`` (the configuration's file lists them under
``assumed``). Leaf names are the program's (``models/sdar_moe.py``
``sdar_param_shapes``), so that one seed-made dict serves both; leaves are
made in the dtype the configuration states and lifted to float32 here.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from .bert import round_trip_8bit, seed_key

PRECISIONS = ("float32", "float8_e4m3")


def layer_shapes(cfg):
    d, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {"attn_norm": (d,), "q_w": (d, H * D), "k_w": (d, Hkv * D),
            "v_w": (d, Hkv * D), "o_w": (H * D, d), "q_norm": (D,),
            "k_norm": (D,), "ffn_norm": (d,), "router_w": (d, E),
            "gate_w": (E, d, f), "up_w": (E, d, f), "down_w": (E, f, d)}


def init_weights(cfg, seed):
    """All leaves in the configuration's dtype on the default device.
    One compiled program makes a layer and is called once a layer, so
    that no more than one leaf's float32 random bits are live beside the
    weights; a second makes the two tables."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["dtype"])
    std = cfg.get("seed_weight_range", cfg.get("initializer_range", 0.02))
    shapes = layer_shapes(cfg)
    names = sorted(shapes)

    def draw(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    @jax.jit
    def make_layer(key):
        return {n: jnp.ones(shapes[n], dtype) if n.endswith("_norm")
                else draw(jax.random.fold_in(key, i), shapes[n])
                for i, n in enumerate(names)}

    @jax.jit
    def make_rest(key):
        k1, k2 = jax.random.split(key)
        return {"embed": draw(k1, (cfg["vocab_size"], d)),
                "head": draw(k2, (d, cfg["vocab_size"])),
                "final_norm": jnp.ones((d,), dtype)}

    key = seed_key(seed)
    weights = make_rest(jax.random.fold_in(key, layers))
    for i in range(layers):
        layer = make_layer(jax.random.fold_in(key, i))
        weights.update({"l%d_%s" % (i, n): a for n, a in layer.items()})
    return weights


def fix_most_confident(masked, confidence, steps_left):
    """The family's static low-confidence schedule for one row: of the
    still-masked positions the ``ceil(masked / steps_left)`` with the
    highest confidence are fixed by this forward, the leftmost first among
    equals. masked (B,) bool, confidence (B,) -> fixed (B,) bool."""
    count = -(-int(masked.sum()) // steps_left)
    fixed = np.zeros(len(masked), bool)
    for _ in range(count):
        fixed[int(np.argmax(np.where(masked & ~fixed, confidence,
                                     -np.inf)))] = True
    return fixed


def _dot(precision):
    if precision == "float32":
        return lambda x, w: jnp.matmul(x, w, precision="highest")

    def eight_bit(x, w):
        return jnp.matmul(round_trip_8bit(x, jnp.float8_e4m3fn),
                          round_trip_8bit(w, jnp.float8_e4m3fn),
                          precision="highest")
    return eight_bit


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x (N, T, H, D), position t of the sequence at index t."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _experts(w, p, h2, top_k, dot):
    """Every token's experts by a loop over all of them: the weight of an
    expert outside a token's top-k is exactly zero."""
    rows = h2.shape[0]
    probs = jax.nn.softmax(dot(h2, w[p + "router_w"].astype(jnp.float32)),
                           axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    share = jnp.zeros_like(probs).at[jnp.arange(rows)[:, None], top_e].set(
        top_p / jnp.sum(top_p, axis=-1, keepdims=True))

    def one_expert(acc, xs):
        gate, up, down, share_e = xs
        hidden = (jax.nn.silu(dot(h2, gate.astype(jnp.float32)))
                  * dot(h2, up.astype(jnp.float32)))
        return acc + share_e[:, None] * dot(hidden,
                                            down.astype(jnp.float32)), None
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h2),
                          (w[p + "gate_w"], w[p + "up_w"], w[p + "down_w"],
                           share.T))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_json", "precision"))
def _logits_at(w, tokens, at, cfg_json, precision):
    cfg = json.loads(cfg_json)
    dot = _dot(precision)
    N, T = tokens.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, B = cfg["head_dim"], cfg["assumed"]["block_length"]["value"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    G = H // Hkv

    def get(name):
        return w[name].astype(jnp.float32)
    x = w["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(T)
    sees = pos[None, :] // B <= pos[:, None] // B           # dense (T, T)
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        h = _rmsnorm(x, get(p + "attn_norm"), eps)
        q = dot(h, get(p + "q_w")).reshape(N, T, H, D)
        k = dot(h, get(p + "k_w")).reshape(N, T, Hkv, D)
        v = dot(h, get(p + "v_w")).reshape(N, T, Hkv, D)
        q = _rope(_rmsnorm(q, get(p + "q_norm"), eps), theta)
        k = _rope(_rmsnorm(k, get(p + "k_norm"), eps), theta)
        k = jnp.repeat(k, G, axis=2)        # query head h reads h // G
        v = jnp.repeat(v, G, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                       precision="highest") / np.sqrt(D)
        a = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("nhqk,nkhd->nqhd", a, v, precision="highest")
        x = x + dot(o.reshape(N, T, H * D), get(p + "o_w"))
        h2 = _rmsnorm(x, get(p + "ffn_norm"), eps)
        x = x + _experts(w, p, h2.reshape(N * T, -1),
                         cfg["num_experts_per_tok"], dot).reshape(x.shape)
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)      # (N, P, d)
    return dot(_rmsnorm(x, get("final_norm"), eps), get("head"))


def logits(w, cfg, tokens, masked=None, at=None, precision="float32",
           block_rows=16):
    """(N, T) int32 tokens -> (N, P, V) float32 logits at the positions
    `at` (N, P) (default: all T). `masked` (N, T) bool: positions that
    hold the MASK token whatever `tokens` says there. "float8_e4m3", the
    control: the operands of every linear layer's matrix product (the
    projections, the router, the experts, the head) through a per-tensor
    scaled e4m3 round trip, the nearest precision below the bfloat16 the
    configuration states; all else as in float32."""
    if precision not in PRECISIONS:
        raise ValueError("no such precision: %r" % precision)
    tokens = np.asarray(tokens, np.int32)
    if masked is not None:
        tokens = np.where(np.asarray(masked, bool),
                          cfg["assumed"]["mask_token_id"]["value"], tokens)
    N, T = tokens.shape
    at = (np.broadcast_to(np.arange(T, dtype=np.int32), (N, T))
          if at is None else np.asarray(at, np.int32))
    cfg_json = json.dumps(cfg, sort_keys=True)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, N, block_rows):
            rows = slice(lo, lo + block_rows)
            n = len(tokens[rows])
            pad = [(0, block_rows - n), (0, 0)]     # one shape, one program
            got = _logits_at(w, np.pad(tokens[rows], pad),
                             np.pad(at[rows], pad), cfg_json, precision)
            out.append(np.asarray(got)[:n])
    return np.concatenate(out)
