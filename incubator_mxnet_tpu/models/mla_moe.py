"""A latent-attention decoder with a sigmoid-scored expert layer behind
leading dense layers, over one residual stream or several hyper-connected
ones.

DeepSeek-V3's layer (multi-head latent attention with a low-rank query,
YaRN rotary frequencies; sigmoid router scores with a choice bias, a shared
expert, leading dense layers), for two families that the configuration
tells apart:

- ``streams`` absent or None (``kimi_k2``: moonshotai Kimi-K2's
  ``config.json``): ONE residual stream, plain pre-norm, ``x <- x +
  Fn(rmsnorm(x))`` around both sublayers, the final norm on the stream; no
  ``hc_*`` leaves;
- ``streams`` n (``xing4_0``: XingChen-AGI Xing4.0-29B-A4B's
  ``config.json``): the one stream replaced by ``n`` streams that every
  sublayer reads through, and writes back through, per-token mappings
  (manifold-constrained hyper-connections, arXiv:2512.24880).

With ``X`` (n, d) a token's streams and ``Fn`` a sublayer (attention, or
the feed-forward), each with maps of its own::

    x      = vec(X) / sqrt(mean(vec(X)^2) + eps)                  (n d,)
    H_pre  = sigmoid(a_pre (x phi_pre) + b_pre)                   (n,)
    H_post = 2 sigmoid(a_post (x phi_post) + b_post)              (n,)
    H_res  = sinkhorn(exp(clip(a_res mat(x phi_res) + b_res)))    (n, n)
    y      = Fn(rmsnorm(H_pre X))
    X      = H_res X + H_post^T y

``sinkhorn`` divides rows, then columns, by their sums, ``sinkhorn_iters``
times: ``H_res`` is (nearly) doubly stochastic, so the streams' sum moves
by ``(sum H_post) y`` alone. The embedding is repeated into the n streams;
their sum is normed and meets the untied head.

Attention caches ONE row a position and layer, ``[rmsnorm(c) | rope(k_r)]``
from ``h W_kva`` (``kv_rank + rope_dim`` values, and zeros up to whole
lanes), shared by all heads (``ops/pallas/paged_latent.py``: the expanded
path for a chunk, the absorbed path for a decode step; on a TPU each is
one launch a layer, ``paged_latent_prefill`` and ``paged_latent_decode``,
plain ``lax`` elsewhere). The feed-forward is
a gated-SiLU MLP in the first ``dense_layers`` layers and
``parallel.moe.moe_dropless`` after them (sigmoid scores, the top-k of
score + bias, weights from the scores renormalised and scaled, a shared
expert every token visits).

``experts_held`` ``(first, count)``: this chip's share of every expert
layer under expert parallelism. The router's leaves keep all
``num_experts`` outputs, ``gate_w / up_w / down_w`` hold the `count` experts
``[first, first + count)``, and a layer adds the routes that fall on those
and the shared expert: its PART of the layer, which is what goes on to the
next layer (``moe_dropless``'s ``held``; no exchange, no stand-in for the
other chips). Embedding and head are as tall as ``vocab_size`` says: a
slice of a vocabulary is a smaller vocabulary.

One layer body, one forward (:func:`mla_forward_paged`, the contract of
``gpt_forward_paged`` over a latent cache). The full-sequence float32
forward is the plain reference's (``benchmarks/reference/xing4.py``).
Weights and activations are in the parameters' dtype (bfloat16 as served);
norms, rotary angles, softmaxes, the router, the hyper-connection maps and
the logits are float32.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.paged_latent import (cache_row_width,
                                       paged_latent_attention)
from ..parallel.moe import moe_dropless

__all__ = ["mla_config", "mla_param_shapes", "mla_forward_paged",
           "yarn_inv_freq", "attention_scale", "sinkhorn",
           "hyper_connection_maps"]

_REQUIRED = ("vocab_size", "units", "num_layers", "num_heads", "q_rank",
             "kv_rank", "nope_dim", "rope_dim", "v_dim", "dense_layers",
             "dense_hidden", "num_experts", "experts_per_token",
             "expert_hidden")


def mla_config(config):
    """Normalize a config dict (the program's names; the published
    ``config.json`` keys map onto them in the caller)."""
    cfg = dict(config)
    for key in _REQUIRED:
        if key not in cfg:
            raise ValueError("mla_moe config missing %r" % key)
    cfg.setdefault("streams", None)
    cfg.setdefault("experts_held", None)
    if cfg["experts_held"] is not None:
        cfg["experts_held"] = tuple(int(v) for v in cfg["experts_held"])
    cfg.setdefault("shared_experts", 1)
    cfg.setdefault("route_scale", 1.0)
    cfg.setdefault("sinkhorn_iters", 20)
    cfg.setdefault("hc_eps", 1e-6)
    cfg.setdefault("res_clamp", (-30.0, 30.0))
    cfg.setdefault("rms_eps", 1e-6)
    cfg.setdefault("rope_theta", 10000.0)
    cfg.setdefault("yarn", None)
    cfg.setdefault("max_len", 4096)
    return cfg


def mla_param_shapes(cfg):
    """Flat ``name -> shape`` map of every parameter: the ``hc_*`` maps
    where there are streams, the expert leaves of the experts held."""
    d, H, n = cfg["units"], cfg["num_heads"], cfg.get("streams")
    r_q, r_kv = cfg["q_rank"], cfg["kv_rank"]
    d_n, d_r, d_v = cfg["nope_dim"], cfg["rope_dim"], cfg["v_dim"]
    E, f = cfg["num_experts"], cfg["expert_hidden"]
    held = cfg["experts_held"][1] if cfg.get("experts_held") else E
    shapes = {"embed": (cfg["vocab_size"], d), "final_norm": (d,),
              "head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "attn_norm": (d,), p + "q_a": (d, r_q),
            p + "q_a_norm": (r_q,), p + "q_b": (r_q, H * (d_n + d_r)),
            p + "kv_a": (d, r_kv + d_r), p + "kv_a_norm": (r_kv,),
            p + "kv_b": (r_kv, H * (d_n + d_v)), p + "o_w": (H * d_v, d),
            p + "ffn_norm": (d,)})
        for sub in ("attn", "ffn") if n else ():
            # phi's columns: pre (n), post (n), res (n x n, row-major);
            # alpha: one a map; b: the biases in phi's order
            shapes[p + sub + "_hc_phi"] = (n * d, 2 * n + n * n)
            shapes[p + sub + "_hc_alpha"] = (3,)
            shapes[p + sub + "_hc_b"] = (2 * n + n * n,)
        if i < cfg["dense_layers"]:
            F = cfg["dense_hidden"]
            shapes.update({p + "gate_w": (d, F), p + "up_w": (d, F),
                           p + "down_w": (F, d)})
        else:
            fs = cfg["shared_experts"] * f
            shapes.update({
                p + "router_w": (d, E), p + "router_bias": (E,),
                p + "gate_w": (held, d, f), p + "up_w": (held, d, f),
                p + "down_w": (held, f, d), p + "shared_gate_w": (d, fs),
                p + "shared_up_w": (d, fs), p + "shared_down_w": (fs, d)})
    return shapes


# ------------------------------------------------------------------ rotary
def yarn_inv_freq(dim, theta, yarn=None):
    """The ``dim // 2`` rotary frequencies, float64 on the host. With
    `yarn` (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``) the YaRN blend: a frequency that turns
    more than ``beta_fast`` times in the original window is kept, one that
    turns fewer than ``beta_slow`` times is divided by ``factor``, a
    linear ramp between them."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return extra

    def correction(turns):
        return (dim * math.log(yarn["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / yarn["factor"] * ramp + extra * (1 - ramp)


def attention_scale(cfg):
    """``(d_n + d_r)^-0.5``, times YaRN's ``m^2`` with ``m = 0.1
    mscale_all_dim ln(factor) + 1``."""
    scale = (cfg["nope_dim"] + cfg["rope_dim"]) ** -0.5
    yarn = cfg.get("yarn")
    if yarn and yarn.get("mscale_all_dim") and yarn["factor"] > 1:
        m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
        scale *= m * m
    return scale


def _rope(x, positions, inv_freq):
    """Rotate-half: pairs (i, i + half). x (S, C, ..., D); positions
    (S, C)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)                              # (S, C, half)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _rmsnorm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


# -------------------------------------------------------- hyper-connections
def sinkhorn(a, iters, eps):
    """`a` (n, n, ...) positive: rows (axis 1 summed), then columns (axis
    0 summed), divided by their sums + eps, `iters` times."""
    for _ in range(iters):
        a = a / (jnp.sum(a, axis=1, keepdims=True) + eps)
        a = a / (jnp.sum(a, axis=0, keepdims=True) + eps)
    return a


def hyper_connection_maps(params, p, cfg, X):
    """The three mappings of one sublayer from the streams X (n, T, d), in
    float32 with the tokens minor (a TPU's lanes):
    -> H_pre (n, T), H_post (n, T), H_res (n, n, T)."""
    n, T, d = X.shape
    X32 = X.astype(jnp.float32)
    phi = params[p + "hc_phi"].astype(jnp.float32).reshape(n, d, -1)
    alpha = params[p + "hc_alpha"].astype(jnp.float32)
    b = params[p + "hc_b"].astype(jnp.float32)
    # x' phi = (x phi) / rms(x): the stream norm has no gain
    rms = jax.lax.rsqrt(jnp.mean(jnp.square(X32), axis=(0, 2))
                        + cfg["hc_eps"])                        # (T,)
    a = jnp.einsum("ntd,ndk->kt", X32, phi, precision="highest") * rms
    scale = jnp.repeat(alpha, np.asarray([n, n, n * n]),
                       total_repeat_length=2 * n + n * n)
    a = a * scale[:, None] + b[:, None]                         # (k, T)
    lo, hi = cfg["res_clamp"]
    res = jnp.exp(jnp.clip(a[2 * n:], lo, hi)).reshape(n, n, T)
    return (jax.nn.sigmoid(a[:n]), 2.0 * jax.nn.sigmoid(a[n:2 * n]),
            sinkhorn(res, cfg["sinkhorn_iters"], cfg["hc_eps"]))


def _hyper(params, p, cfg, X, norm, fn):
    """One sublayer `fn` ((T, d) -> (T, d)) under its hyper-connections
    (the parameters ``<p>hc_*``); X (n, T, d) -> X (n, T, d)."""
    n = X.shape[0]
    pre, post, res = hyper_connection_maps(params, p, cfg, X)
    X32 = X.astype(jnp.float32)
    u = sum(pre[i][:, None] * X32[i] for i in range(n)).astype(X.dtype)
    y = fn(_rmsnorm(u, norm, cfg["rms_eps"])).astype(jnp.float32)
    return jnp.stack([
        sum(res[i, j][:, None] * X32[j] for j in range(n))
        + post[i][:, None] * y for i in range(n)]).astype(X.dtype)


# ------------------------------------------------------------------ layers
def _attention(params, p, cfg, h, positions, inv_freq, pool, block_tables,
               lengths):
    """h (S, C, d) normed -> (the sublayer's output (S, C, d), the chunk's
    cache rows ``[rmsnorm(c) | rope(k_r) | 0]`` (S, C, cache_row_width))."""
    S, C, _ = h.shape
    H, r = cfg["num_heads"], cfg["kv_rank"]
    d_n, d_r, d_v = cfg["nope_dim"], cfg["rope_dim"], cfg["v_dim"]
    eps = cfg["rms_eps"]
    q = (_rmsnorm(h @ params[p + "q_a"], params[p + "q_a_norm"], eps)
         @ params[p + "q_b"]).reshape(S, C, H, d_n + d_r)
    kv = h @ params[p + "kv_a"]                         # (S, C, r + d_r)
    rows = jnp.concatenate(
        [_rmsnorm(kv[..., :r], params[p + "kv_a_norm"], eps),
         _rope(kv[..., r:], positions, inv_freq),
         jnp.zeros((S, C, cache_row_width(r, d_r) - r - d_r), kv.dtype)],
        axis=-1)
    out = paged_latent_attention(
        q[..., :d_n], _rope(q[..., d_n:], positions, inv_freq), rows,
        params[p + "kv_b"].reshape(r, H, d_n + d_v), pool, block_tables,
        lengths, attention_scale(cfg))
    return out.reshape(S, C, H * d_v) @ params[p + "o_w"], rows


def _residual(x, norm, cfg, fn):
    """One sublayer `fn` on the one stream x (T, d): ``x + fn(rmsnorm(x))``,
    summed in float32."""
    y = fn(_rmsnorm(x, norm, cfg["rms_eps"]))
    return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)


def _feed_forward(params, p, cfg, h, dense, loads):
    """h (T, d) normed -> (T, d); an expert layer appends its loads (a
    held share's with its ``routes_elsewhere`` and ``rows_moved``)."""
    if dense:
        gate = jnp.dot(h, params[p + "gate_w"],
                       preferred_element_type=jnp.float32)
        up = jnp.dot(h, params[p + "up_w"],
                     preferred_element_type=jnp.float32)
        return (jax.nn.silu(gate) * up).astype(h.dtype) @ params[p + "down_w"]
    out, stats = moe_dropless(
        h, params[p + "router_w"], params[p + "gate_w"], params[p + "up_w"],
        params[p + "down_w"], cfg["experts_per_token"], return_stats=True,
        scoring="sigmoid", choice_bias=params[p + "router_bias"],
        route_scale=cfg["route_scale"],
        shared=(params[p + "shared_gate_w"], params[p + "shared_up_w"],
                params[p + "shared_down_w"]), held=cfg["experts_held"])
    loads.append(stats["expert_load"] if cfg["experts_held"] is None
                 else jnp.concatenate([stats["expert_load"], jnp.stack(
                     [stats["routes_elsewhere"], stats["rows_moved"]])]))
    return out


def mla_forward_paged(params, cfg, tokens, lengths, block_tables, pools,
                      head="logits"):
    """A chunk of C new positions a sequence over the paged latent cache.

    tokens (S, C) int32; lengths (S,) int32 committed past positions;
    block_tables (S, MB) int32; pools: a layer's ``(num_blocks,
    block_size, cache_row_width)`` pool, by layer.

    Returns ``(out, new_rows, loads)``: new_rows by layer (S, C,
    cache_row_width), the chunk's cache rows for the caller to commit; ``loads``
    (expert layers, E) int32, the routes each routed expert got in this
    forward. Under ``experts_held`` it is (expert layers, count + 2): the
    routes each HELD expert got, then the layer's ``routes_elsewhere`` and
    ``rows_moved`` (one array: an output buffer costs a launch more than
    two numbers do). `head`: ``"logits"`` -> out (S, C, V) float32, position c's
    logits choose token c + 1; ``"token"`` -> (S,) int32, the argmax of
    the last chunk position's logits (the first index on a tie, as
    ``np.argmax``), so the (S, C, V) array is no output of the program;
    ``"none"`` -> None: a forward run for its rows alone (prefill) skips
    the final norm and the head.

    A chunk of one position runs the absorbed attention path (one
    ``paged_latent_decode`` launch a layer on a TPU, each sequence's own
    blocks read once), any wider chunk the expanded one (one
    ``paged_latent_prefill`` launch a layer on a TPU: the chunk against
    its sequence's own cached rows and itself, rows up-projected and
    scores kept in VMEM; ``ops/pallas/paged_latent.py``)."""
    cfg = mla_config(cfg)
    S, C = tokens.shape
    n, d = cfg["streams"], cfg["units"]
    held = cfg["experts_held"]
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    inv_freq = yarn_inv_freq(cfg["rope_dim"], cfg["rope_theta"], cfg["yarn"])
    X = params["embed"][tokens].reshape(S * C, d)
    if n:   # the streams, major: (n, S C, d) keeps a TPU's tiles on (tokens, d)
        X = jnp.broadcast_to(X[None], (n, S * C, d))
    new_rows, loads = [], []
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i

        def attention(h, p=p, i=i):
            out, rows = _attention(params, p, cfg, h.reshape(S, C, d),
                                   positions, inv_freq, pools[i],
                                   block_tables, lengths)
            new_rows.append(rows)
            return out.reshape(S * C, d)

        def feed_forward(h, p=p, i=i):
            return _feed_forward(params, p, cfg, h, i < cfg["dense_layers"],
                                 loads)
        if n:
            X = _hyper(params, p + "attn_", cfg, X, params[p + "attn_norm"],
                       attention)
            X = _hyper(params, p + "ffn_", cfg, X, params[p + "ffn_norm"],
                       feed_forward)
        else:
            X = _residual(X, params[p + "attn_norm"], cfg, attention)
            X = _residual(X, params[p + "ffn_norm"], cfg, feed_forward)
    loads = (jnp.stack(loads) if loads else jnp.zeros(
        (0, cfg["num_experts"] if held is None else held[1] + 2), jnp.int32))
    if head == "none":
        return None, new_rows, loads
    if head not in ("logits", "token"):
        raise ValueError("no such head: %r" % (head,))
    x = jnp.sum(X.astype(jnp.float32), axis=0).astype(X.dtype) if n else X
    logits = jnp.dot(_rmsnorm(x, params["final_norm"], cfg["rms_eps"]),
                     params["head"], preferred_element_type=jnp.float32)
    logits = logits.reshape(S, C, -1)
    if head == "token":
        logits = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return logits, new_rows, loads
