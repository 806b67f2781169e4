"""A looped decoder: Ouro (ByteDance's LoopLM, ``config.json``
``model_type: ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741).

A stack of ``num_layers`` decoder layers applied ``loop_steps`` (T) times to
every token with ONE set of weights: ``h_0 = embed(x)``, and for u = 1..T
``h_u = rms(Layers(h_{u-1}), g_f)``, the final norm at the end of every
loop step; the logits are ``h_T W_head`` (untied). A layer is a "sandwich":
an RMS norm before and after each sublayer::

    a = rms(h, g1);  q, k, v = a Wq, a Wk, a Wv   (no bias), heads of d
    q, k rotated at the token's position (rotate-half over all d)
    h = h + rms(Attn_{u,l}(q, k, v) Wo, g2)
    m = rms(h, g3);  h = h + rms((silu(m Wg) * (m Wu)) Wd, g4)

``Attn_{u,l}`` is causal softmax attention over the keys and values OF
LAYER l AT LOOP STEP u, no other step's: the cache keeps a position's T
rows of a layer side by side, one entry a layer whose row is ``T x H D``
lanes (step u in lanes ``[u H D, (u + 1) H D)``), behind ONE block table
and length (``generate/paged_kv.py``), so a forward takes 2 x L pools and
one commit stores its T steps.

The loop is a loop in the program (``lax.fori_loop``): its body holds the
L layers once, so neither the program nor its compile grows with T. The
forward's new keys and values of every step come out of the loop as one
(L, S, C, T H D) array each. A decode step's query meets its layer's past
at its step by ``ops/pallas/paged_heads.py``'s walk given the step as an
operand (a TPU; ``interpret`` anywhere), its own row merged under one
softmax; a chunk that opens its sequences (a prefill chunk over empty
slots) takes its causal part from the tiled flash forward
(``flash_attention_lse``); any other chunk gathers its step's lanes of
``pool[tables]`` in ``lax``.

The exit gate (``lambda_u = sigmoid(h_u . w + b)``, a token leaving at the
first step whose cumulative exit probability reaches
``early_exit_threshold``) is computed by the plain reference alone: at the
published threshold 1.0 every token runs all T steps, and a threshold
below 1.0 is refused here (per-token exit in a batch: ROADMAP B12).

Weights, activations and the cache are in the parameters' dtype (bfloat16
as served); the residual stream, the norms' statistics, the rotary angles,
the scores and softmaxes and the logits are float32. The full-sequence
float32 forward is the plain reference's (``benchmarks/reference/ouro.py``).
"""

import jax
import jax.numpy as jnp

from ..ops.pallas.flash_attention import (flash_attention_available,
                                          flash_attention_lse)
from ..ops.pallas.paged_heads import (paged_heads_decode,
                                      paged_heads_decode_available)
from .eva_byte import _rope, attend

__all__ = ["ouro_config", "ouro_param_shapes", "ouro_forward_paged",
           "EXIT_ENTRY"]

_REQUIRED = ("vocab_size", "units", "num_layers", "num_heads", "hidden",
             "loop_steps")
#: where per-token early exit is queued
EXIT_ENTRY = "ROADMAP.md B12"


def ouro_config(config):
    """Normalize a config dict (the program's names; the published
    ``config.json`` keys map onto them in the caller)."""
    cfg = dict(config)
    for key in _REQUIRED:
        if key not in cfg:
            raise ValueError("ouro config missing %r" % key)
    cfg.setdefault("rms_eps", 1e-6)
    cfg.setdefault("rope_theta", 1e6)
    cfg.setdefault("max_len", 65536)
    cfg.setdefault("exit_threshold", 1.0)
    if cfg["units"] % cfg["num_heads"] or cfg["loop_steps"] < 1:
        raise ValueError("units must be whole heads and loop_steps >= 1: "
                         "%r" % (config,))
    if cfg["exit_threshold"] < 1.0:
        raise ValueError(
            "early_exit_threshold %r < 1.0: a token would leave the loop "
            "early, and per-token exit in a batch is not run (%s); every "
            "token runs all %d loop steps at 1.0"
            % (cfg["exit_threshold"], EXIT_ENTRY, cfg["loop_steps"]))
    return cfg


def ouro_param_shapes(cfg):
    """Flat ``name -> shape`` map of every parameter, the exit gate's
    (``exit_w``, ``exit_b``: the reference's alone) among them."""
    d, F = cfg["units"], cfg["hidden"]
    shapes = {"embed": (cfg["vocab_size"], d), "final_norm": (d,),
              "head": (d, cfg["vocab_size"]), "exit_w": (d,),
              "exit_b": (1,)}
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "attn_norm": (d,), p + "q_w": (d, d), p + "k_w": (d, d),
            p + "v_w": (d, d), p + "o_w": (d, d), p + "attn_post_norm": (d,),
            p + "ffn_norm": (d,), p + "gate_w": (d, F), p + "up_w": (d, F),
            p + "down_w": (F, d), p + "ffn_post_norm": (d,)})
    return shapes


def _rms(x, g, eps):
    """``x / sqrt(mean(x^2) + eps) * g``, float32."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * g.astype(jnp.float32))


def _step_rows(pool, tables, step, heads, head_dim):
    """Loop step `step`'s rows of a layer's pool behind `tables` (S, MB),
    the ``lax`` gather: (S, MB * block, H, D)."""
    width = heads * head_dim
    rows = pool[tables]
    rows = rows.reshape(rows.shape[0], -1, rows.shape[-1])
    rows = jax.lax.dynamic_slice_in_dim(rows, step * width, width, axis=2)
    return rows.reshape(rows.shape[:2] + (heads, head_dim))


def _walked(q, k, v, lengths, tables, k_pool, v_pool, step, interpret):
    """One query a sequence, q / k / v (S, 1, H, D), on the launch: the
    layer's past at loop step `step` walked by one ``paged_heads_decode``,
    its state and the step's own row merged under one softmax. -> (S, 1,
    H, D) float32."""
    scale = q.shape[-1] ** -0.5
    q = q[:, 0]
    own = jnp.sum(q.astype(jnp.float32) * k[:, 0].astype(jnp.float32),
                  axis=-1) * scale                              # (S, H)
    m, l, acc = paged_heads_decode(q, k_pool, v_pool, tables, lengths,
                                   scale=scale, interpret=interpret,
                                   step=step)
    top = jnp.maximum(own, m)           # a walk over no row: m = -1e30
    mine, past = jnp.exp(own - top), jnp.exp(m - top)
    num = (mine[..., None] * v[:, 0].astype(jnp.float32)
           + acc * past[..., None])
    return (num / (mine + l * past)[..., None])[:, None]


def _attention(q, k, v, lengths, tables, k_pool, v_pool, step, steps,
               fresh, interpret):
    """The chunk's queries (S, C, H, D) against the layer's cached rows of
    loop step `step` (lanes ``[step H D, (step + 1) H D)`` of every row of
    the pools) and the chunk itself, causally."""
    _S, C, H, D = q.shape
    if C == 1 and (interpret or paged_heads_decode_available(
            k_pool, H, steps)):
        return _walked(q, k, v, lengths, tables, k_pool, v_pool, step,
                       interpret)
    if fresh and (interpret or flash_attention_available()) \
            and C % 128 == 0 and D % 128 == 0:
        def heads_first(a):
            return a.transpose(0, 2, 1, 3)
        own, _lse = flash_attention_lse(heads_first(q), heads_first(k),
                                        heads_first(v), causal=True,
                                        interpret=interpret)
        return heads_first(own).astype(jnp.float32)
    parts = []
    if not fresh:
        past_k, past_v = [_step_rows(pool, tables, step, H, D)
                          for pool in (k_pool, v_pool)]
        parts.append((past_k, past_v, (jnp.arange(past_k.shape[1])[None]
                                       < lengths[:, None])[:, None, None]))
    causal = (jnp.arange(C)[:, None] >= jnp.arange(C)[None])[None, None]
    return attend(q, parts + [(k, v, causal)])


def _layer(params, p, cfg, x, positions, attention):
    """One sandwich-norm layer (leaves ``p`` + name) on the stream x (S C,
    d) float32 at `positions` (S, C); `attention(q, k, v)` -> (S, C, H, D)
    over the chunk's rotated q, k and its v. -> (x, k, v), k and v (S, C,
    H, D) in the weights' dtype for the cache."""
    S, C = positions.shape
    d, H, eps = cfg["units"], cfg["num_heads"], cfg["rms_eps"]
    dtype = params[p + "q_w"].dtype
    a = _rms(x, params[p + "attn_norm"], eps).astype(dtype)
    q, k, v = [(a @ params[p + n]).reshape(S, C, H, d // H)
               for n in ("q_w", "k_w", "v_w")]
    q, k = (_rope(q, positions, cfg["rope_theta"]),
            _rope(k, positions, cfg["rope_theta"]))
    att = attention(q, k, v).astype(dtype)
    x = x + _rms(jnp.dot(att.reshape(S * C, d), params[p + "o_w"],
                         preferred_element_type=jnp.float32),
                 params[p + "attn_post_norm"], eps)
    m = _rms(x, params[p + "ffn_norm"], eps).astype(dtype)
    gate = jnp.dot(m, params[p + "gate_w"],
                   preferred_element_type=jnp.float32)
    up = jnp.dot(m, params[p + "up_w"], preferred_element_type=jnp.float32)
    x = x + _rms(jnp.dot((jax.nn.silu(gate) * up).astype(dtype),
                         params[p + "down_w"],
                         preferred_element_type=jnp.float32),
                 params[p + "ffn_post_norm"], eps)
    return x, k, v


def ouro_forward_paged(params, cfg, tokens, lengths, tables, k_pools,
                       v_pools, head="logits", fresh=False, interpret=False):
    """A chunk of C new positions a sequence through the whole loop, over
    the paged cache of every (loop step, layer).

    tokens (S, C) int32; lengths (S,) int32 the committed positions of
    each sequence, tables (S, MB) its blocks; the pools by layer,
    ``(num_blocks, block_size, T H D)``: a row holds the position's keys
    (values) of all T loop steps, each step's heads side by side. `fresh`:
    every sequence is empty (a prefill chunk that opens its slots), and
    the pools are not read. `interpret`: a chunk of one position walks the
    cache by the launch in interpret mode, wherever it runs (on a TPU it
    takes it by itself), and a fresh chunk of whole tiles takes the flash
    forward likewise.

    Returns ``(out, new_k, new_v)``: new_k / new_v (L, S, C, T H D), the
    chunk's rotated keys and its values at every loop step, for the caller
    to commit. `head`: ``"logits"`` -> out (S, C, V) float32;
    ``"token"`` -> (S,) int32, the argmax at the last chunk position;
    ``"none"`` -> (S,) int32, the positions each sequence holds after the
    chunk (the program's smallest output: what a caller fetches to keep
    one chunk ahead of the device)."""
    cfg = ouro_config(cfg)
    if head not in ("logits", "token", "none"):
        raise ValueError("no such head: %r" % (head,))
    S, C = tokens.shape
    d, T, L = cfg["units"], cfg["loop_steps"], cfg["num_layers"]
    eps, dtype = cfg["rms_eps"], params["embed"].dtype
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    x = params["embed"][tokens].astype(jnp.float32).reshape(S * C, d)
    new = jnp.zeros((L, S, C, T * d), dtype)

    def loop_step(u, carry):
        x, new_k, new_v = carry
        for i in range(L):
            def attention(q, k, v, i=i):
                return _attention(q, k, v, lengths, tables, k_pools[i],
                                  v_pools[i], u, T, fresh, interpret)
            x, k, v = _layer(params, "l%d_" % i, cfg, x, positions,
                             attention)
            at = (i, 0, 0, u * d)
            new_k = jax.lax.dynamic_update_slice(
                new_k, k.reshape(1, S, C, d), at)
            new_v = jax.lax.dynamic_update_slice(
                new_v, v.reshape(1, S, C, d), at)
        # the final norm ends EVERY loop step: the next starts from it
        return _rms(x, params["final_norm"], eps), new_k, new_v

    x, new_k, new_v = jax.lax.fori_loop(0, T, loop_step, (x, new, new))
    if head == "none":
        return lengths + C, new_k, new_v
    x = x.reshape(S, C, d)
    if head == "token":
        x = x[:, -1:]
    logits = jnp.dot(x.astype(dtype), params["head"],
                     preferred_element_type=jnp.float32)
    if head == "token":
        logits = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return logits, new_k, new_v
