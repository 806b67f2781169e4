"""SDAR-MoE — a block-diffusion decoder with a dropless expert layer.

The ``sdar_moe`` family (JetLM SDAR-30B-A3B-Chat's ``config.json``): a
pre-norm decoder with RMSNorm, rotary positions (rotate-half), separate
q/k/v/o projections without biases, RMSNorm over each head of q and k,
grouped-query attention (fewer key/value heads than query heads), a
mixture of gated-SiLU experts in every layer (softmax over all experts,
top-k, renormalised: ``parallel.moe.moe_dropless``) and an untied head.
With ``x`` the layer's input::

    h  = rmsnorm(x; attn_norm)
    q  = rmsnorm_per_head(h q_w; q_norm)    k likewise    v = h v_w
    q, k = rope(q, pos), rope(k, pos)
    a  = softmax(q_h k_{h // G}^T / sqrt(D) + M) v_{h // G}
    x  = x + a o_w
    x  = x + moe(rmsnorm(x; ffn_norm))
    logits = rmsnorm(x; final_norm) head            (float32)

``M`` is the family's block mask: with block length B and absolute
positions, position i sees j iff ``j // B <= i // B`` — causal between
blocks, bidirectional inside one, over prompt and answer alike. A masked
position predicts its own token (no shift).

Two pure forwards over one flat param dict (``sdar_param_shapes``):

- :func:`sdar_logits` — the full sequence under a dense T x T block mask.
- :func:`sdar_forward_paged` — the contract of ``gpt_forward_paged``: a
  chunk of C new positions a sequence attends its paged history and the
  chunk itself under the block mask, and returns the chunk's K and V for
  the caller to commit — or not to: a denoising forward of the block loop
  (``generate/engine.py``) stores nothing.

Weights and activations are in the parameters' dtype (bfloat16 as served);
norms, rotary angles, softmaxes, the router and the logits are float32.
"""

import math

import jax
import jax.numpy as jnp

from ..ops.pallas.flash_decode import paged_causal_attention
from ..parallel.moe import moe_dropless

__all__ = ["sdar_config", "sdar_param_shapes", "sdar_logits",
           "sdar_forward_paged", "block_mask"]


def sdar_config(config):
    """Normalize a config dict (the program's names; the published
    ``config.json`` keys map onto them in the caller)."""
    cfg = dict(config)
    for key in ("vocab_size", "units", "num_layers", "num_heads",
                "num_kv_heads", "head_dim", "num_experts",
                "experts_per_token", "expert_hidden", "block_length"):
        if key not in cfg:
            raise ValueError("sdar_moe config missing %r" % key)
    cfg.setdefault("rope_theta", 1e6)
    cfg.setdefault("rms_eps", 1e-6)
    cfg.setdefault("max_len", 32768)
    if cfg["num_heads"] % cfg["num_kv_heads"]:
        raise ValueError("num_heads (%d) must divide by num_kv_heads (%d)"
                         % (cfg["num_heads"], cfg["num_kv_heads"]))
    return cfg


def sdar_param_shapes(cfg):
    """Flat ``name -> shape`` map of every parameter."""
    d, D = cfg["units"], cfg["head_dim"]
    H, Hkv = cfg["num_heads"], cfg["num_kv_heads"]
    E, f = cfg["num_experts"], cfg["expert_hidden"]
    shapes = {"embed": (cfg["vocab_size"], d), "final_norm": (d,),
              "head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        shapes[p + "attn_norm"] = (d,)
        shapes[p + "q_w"] = (d, H * D)
        shapes[p + "k_w"] = (d, Hkv * D)
        shapes[p + "v_w"] = (d, Hkv * D)
        shapes[p + "o_w"] = (H * D, d)
        shapes[p + "q_norm"] = (D,)
        shapes[p + "k_norm"] = (D,)
        shapes[p + "ffn_norm"] = (d,)
        shapes[p + "router_w"] = (d, E)
        shapes[p + "gate_w"] = (E, d, f)
        shapes[p + "up_w"] = (E, d, f)
        shapes[p + "down_w"] = (E, f, d)
    return shapes


def block_mask(positions_q, positions_k, block_length):
    """True where query position i sees key position j:
    ``j // B <= i // B``. (..., Tq) x (..., Tk) -> (..., Tq, Tk)"""
    return (positions_k[..., None, :] // block_length
            <= positions_q[..., :, None] // block_length)


def _rmsnorm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotate-half rotary embedding. x (S, C, H, D); positions (S, C)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv        # (S, C, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _qkv(params, p, cfg, x, positions):
    """The layer's normed, rotated q (S, C, H, D), k and raw v
    (S, C, Hkv, D)."""
    S, C, _ = x.shape
    D, H, Hkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    eps, theta = cfg["rms_eps"], cfg["rope_theta"]
    h = _rmsnorm(x, params[p + "attn_norm"], eps)
    q = (h @ params[p + "q_w"]).reshape(S, C, H, D)
    k = (h @ params[p + "k_w"]).reshape(S, C, Hkv, D)
    v = (h @ params[p + "v_w"]).reshape(S, C, Hkv, D)
    q = _rope(_rmsnorm(q, params[p + "q_norm"], eps), positions, theta)
    k = _rope(_rmsnorm(k, params[p + "k_norm"], eps), positions, theta)
    return q, k, v


def _experts(params, p, cfg, x, loads):
    S, C, d = x.shape
    h2 = _rmsnorm(x, params[p + "ffn_norm"], cfg["rms_eps"])
    out, stats = moe_dropless(
        h2.reshape(S * C, d), params[p + "router_w"], params[p + "gate_w"],
        params[p + "up_w"], params[p + "down_w"], cfg["experts_per_token"],
        return_stats=True)
    loads.append(stats["expert_load"])
    return out.reshape(S, C, d)


def _head(params, cfg, x):
    return jnp.dot(_rmsnorm(x, params["final_norm"], cfg["rms_eps"]),
                   params["head"], preferred_element_type=jnp.float32)


def sdar_logits(params, cfg, tokens):
    """Full-sequence forward under the block mask: (B, T) int32 ->
    (B, T, V) float32 logits; position t's logits are of token t."""
    cfg = sdar_config(cfg)
    B, T = tokens.shape
    H, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G = H // Hkv
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = block_mask(jnp.arange(T), jnp.arange(T), cfg["block_length"])
    x = params["embed"][tokens]
    loads = []
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        q, k, v = _qkv(params, p, cfg, x, positions)
        q = q.reshape(B, T, Hkv, G, D)
        s = jnp.einsum("bqkgd,btkd->bkgqt", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", a, v.astype(jnp.float32))
        x = x + o.reshape(B, T, H * D).astype(x.dtype) @ params[p + "o_w"]
        x = x + _experts(params, p, cfg, x, loads)
    return _head(params, cfg, x)


def sdar_forward_paged(params, cfg, tokens, lengths, block_tables,
                       k_pools, v_pools, head="logits"):
    """A chunk of C new positions a sequence over the paged KV cache.

    tokens (S, C) int32; lengths (S,) int32 committed past positions;
    block_tables (S, MB) int32; k_pools/v_pools — per-layer lists of
    ``(num_blocks, block_size, Hkv, D)`` pool arrays.

    Returns ``(out, new_k, new_v, loads)``: new_k/new_v per-layer
    (S, C, Hkv, D) for the caller to commit (k normed and rotated, as
    attention reads it); ``loads`` (layers, E) int32, the routes each
    expert got in this forward. `head` chooses ``out``:

    - ``"logits"``: (S, C, V) float32;
    - ``"choice"``: ``(x0 (S, C) int32, confidence (S, C) float32)``,
      the argmax token of every position and its softmax probability,
      taken on the device (a block step's logits are never shipped);
      the config's ``mask_id``, if any, is left out of both: a position
      is never fixed to MASK;
    - ``"none"``: None — a forward run for its K and V alone (prefill, a
      block's store pass) skips the final norm and the head.

    The chunk must not split a block: the in-chunk mask is the block mask
    at absolute positions, and the half of a block that came earlier
    could not see the half that comes later.
    """
    cfg = sdar_config(cfg)
    S, C = tokens.shape
    H, D = cfg["num_heads"], cfg["head_dim"]
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    x = params["embed"][tokens]
    new_k, new_v, loads = [], [], []
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        q, k, v = _qkv(params, p, cfg, x, positions)
        new_k.append(k)
        new_v.append(v)
        att = paged_causal_attention(
            q, k, v, k_pools[i], v_pools[i], block_tables, lengths,
            use_kernel=False, mask_block=cfg["block_length"])
        x = x + att.reshape(S, C, H * D) @ params[p + "o_w"]
        x = x + _experts(params, p, cfg, x, loads)
    loads = jnp.stack(loads)
    if head == "none":
        return None, new_k, new_v, loads
    logits = _head(params, cfg, x)
    if head == "logits":
        return logits, new_k, new_v, loads
    if head != "choice":
        raise ValueError("no such head: %r" % (head,))
    if cfg.get("mask_id") is not None:      # a position never takes MASK
        logits = logits.at[..., cfg["mask_id"]].set(-jnp.inf)
    top = jnp.max(logits, axis=-1)
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    confidence = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    return (x0, confidence), new_k, new_v, loads
