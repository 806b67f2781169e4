"""A byte-level decoder whose attention is EVA in its windowed, chunk-pooled
causal form (EvaByte's ``config.json``: ``model_type: evabyte``,
``attention_class: eva``; Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542).

A pre-norm decoder (RMS norm with a unit offset, rotary positions over the
whole head, a gated-SiLU MLP, ``pred_heads`` untied prediction heads side by
side in one matrix) whose attention keeps, a head and layer, TWO kinds of
row:

- the exact rotated keys and values of the query's own WINDOW of ``window``
  positions (``t // window``), seen causally;
- one SUMMARY a ``chunk`` of positions of every window that has closed:
  ``ktilde = sum_i softmax_i(s k_i . mu) k_i`` and ``vtilde = sum_i
  softmax_i(s k_i . phi) v_i`` over the chunk's rotated keys and its values,
  ``mu`` and ``phi`` learned, one pair a head and layer.

Query t scores its window's rows up to itself and every summary of an
earlier window under ONE softmax (float32). A chunk's summary is seen once
its window has closed, never before; a closed window's exact rows are never
read again. With every position in one window this is causal softmax
attention; with ``chunk`` 1 a summary is its token, and it is full causal
attention whatever ``mu`` and ``phi``.

The cache (``generate/paged_kv.py``) holds two GROUPS of entries a layer:
``wk`` / ``wv``, the window (at most ``window`` rows a slot, row ``t %
window``, restarted at a closing) and ``sk`` / ``sv``, the summaries
(``window // chunk`` rows a closing). A row holds ALL heads side by side
(``H x D`` lanes), so that a decode step's walk over a group is one launch
a layer on a TPU (``ops/pallas/paged_heads.py``: each sequence's own live
blocks copied once; two walks and the step's own row merged under the one
softmax); a chunk that opens its window (a prefill chunk of one window)
takes its own causal part from the tiled flash forward
(``ops/pallas/flash_attention.py`` ``flash_attention_lse``) and merges the
summaries by its log-sum; the ``lax`` path gathers ``pool[tables]``, on
every other backend and for every other chunk. :func:`eva_forward_paged` is the
contract of ``gpt_forward_paged`` over that cache; :func:`eva_close_window`
reads a full window's rows and returns its summaries. A chunk wider than
one position must not straddle a closing (its window rows and itself fit
the window): the caller's to keep.

Weights, activations and the cache are in the parameters' dtype (bfloat16
as served); the residual stream and its additions (``fp32_skip_add``), the
norms, the rotary angles, the scores and softmaxes (``mixedp_attn``), the
pooling weights and the logits (``fp32_logits``: bfloat16 operands, float32
sums) are float32. The full-sequence float32 forward is the plain
reference's (``benchmarks/reference/evabyte.py``).
"""

import jax
import jax.numpy as jnp

from ..ops.pallas.flash_attention import (flash_attention_available,
                                          flash_attention_lse)
from ..ops.pallas.paged_heads import (paged_heads_decode,
                                      paged_heads_decode_available)

__all__ = ["eva_config", "eva_param_shapes", "eva_forward_paged",
           "eva_close_window", "summarize_chunks"]

_REQUIRED = ("vocab_size", "units", "num_layers", "num_heads", "hidden",
             "window", "chunk")
_NEG = -1e30
QUERY_TILE = 512        # a chunk's queries meet its own keys a tile at a time


def eva_config(config):
    """Normalize a config dict (the program's names; the published
    ``config.json`` keys map onto them in the caller)."""
    cfg = dict(config)
    for key in _REQUIRED:
        if key not in cfg:
            raise ValueError("eva_byte config missing %r" % key)
    cfg.setdefault("pred_heads", 1)
    cfg.setdefault("rms_eps", 1e-5)
    cfg.setdefault("rope_theta", 1e5)
    cfg.setdefault("max_len", 32768)
    if cfg["units"] % cfg["num_heads"] or cfg["window"] % cfg["chunk"]:
        raise ValueError("units must be whole heads and a window whole "
                         "chunks: %r" % (config,))
    return cfg


def eva_param_shapes(cfg):
    """Flat ``name -> shape`` map of every parameter. ``head`` holds the
    ``pred_heads`` prediction heads side by side: head j's logits are the
    columns ``[j V, (j + 1) V)``."""
    d, H, F = cfg["units"], cfg["num_heads"], cfg["hidden"]
    shapes = {"embed": (cfg["vocab_size"], d), "final_norm": (d,),
              "head": (d, cfg["pred_heads"] * cfg["vocab_size"])}
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        shapes.update({
            p + "attn_norm": (d,), p + "q_w": (d, d), p + "k_w": (d, d),
            p + "v_w": (d, d), p + "o_w": (d, d), p + "mu": (H, d // H),
            p + "phi": (H, d // H), p + "ffn_norm": (d,),
            p + "gate_w": (d, F), p + "up_w": (d, F), p + "down_w": (F, d)})
    return shapes


def _norm(x, g, eps):
    """``x / sqrt(mean(x^2) + eps) * (1 + g)`` (``norm_add_unit_offset``),
    float32."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * (1.0 + g.astype(jnp.float32)))


def _rope(x, positions, theta):
    """Rotate-half over the whole head: pairs (i, i + D / 2). x (S, C, H,
    D); positions (S, C)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def summarize_chunks(k, v, mu, phi, chunk):
    """The summaries of whole chunks: k (rotated), v (..., R, H, D), R
    whole chunks; mu, phi (H, D) -> ktilde, vtilde (..., R // chunk, H, D)
    in k's dtype, the pooling in float32. The pooling scores carry the
    attention's factor ``D ** -0.5``."""
    *lead, R, H, D = k.shape
    scale = D ** -0.5

    def chunks(rows):
        return rows.astype(jnp.float32).reshape(*lead, R // chunk, chunk, H,
                                                D)
    kc = chunks(k)

    def pooled(rows, by):
        weights = jax.nn.softmax(
            jnp.einsum("...jihd,hd->...jih", kc, by.astype(jnp.float32),
                       precision="highest") * scale, axis=-2)
        return jnp.sum(weights[..., None] * rows, axis=-3).astype(k.dtype)
    return pooled(kc, mu), pooled(chunks(v), phi)


def _scores(q, k):
    """q (S, C, H, D), k (S, T, H, D) -> (S, H, C, T) float32. One query a
    sequence is a product a key row on the VPU: no operand is transposed
    and the keys are read once."""
    if q.shape[1] == 1:
        return jnp.sum(q.astype(jnp.float32) * k.astype(jnp.float32),
                       axis=-1).transpose(0, 2, 1)[:, :, None]
    return jnp.einsum("schd,sthd->shct", q, k,
                      preferred_element_type=jnp.float32)


def _weighted(p, v):
    """p (S, H, C, T) float32, v (S, T, H, D) -> (S, C, H, D) float32."""
    if p.shape[2] == 1:
        return jnp.sum(p[:, :, 0].transpose(0, 2, 1)[..., None]
                       * v.astype(jnp.float32), axis=1)[:, None]
    return jnp.einsum("shct,sthd->schd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def attend(q, parts):
    """ONE softmax of q's (S, C, H, D) scores with the keys of every part,
    ``(keys (S, T, H, D), values (S, T, H, D), sees)``, `sees` which key a
    query sees, broadcastable to (S, H, C, T). Every query sees a key of
    some part (itself). -> (S, C, H, D) float32."""
    scale = q.shape[-1] ** -0.5
    scores = [jnp.where(sees, _scores(q, k) * scale, _NEG)
              for k, _v, sees in parts]
    top = scores[0].max(-1)
    for s in scores[1:]:
        top = jnp.maximum(top, s.max(-1))
    num = den = 0.0
    for s, (_k, v, _sees) in zip(scores, parts):
        p = jnp.exp(s - top[..., None])         # a key not seen: exp(-1e30)
        den = den + p.sum(-1)
        num = num + _weighted(p, v)
    return num / den.transpose(0, 2, 1)[..., None]


def _paged_rows(pool, tables, heads):
    """A pool's rows behind `tables` (S, MB): (S, MB * block, H, D)."""
    rows = pool[tables]
    return rows.reshape(rows.shape[0], -1, heads, rows.shape[-1] // heads)


def _walked(q, k, v, cache, interpret):
    """One query a sequence, q / k / v (S, 1, H, D), on the launches: the
    live window rows and the summaries walked each by one
    ``paged_heads_decode``, their states and the step's own row merged
    under one softmax. -> (S, 1, H, D) float32."""
    wlen, wtables, wk, wv, slen, stables, sk, sv = cache
    scale = q.shape[-1] ** -0.5
    q = q[:, 0]
    own = jnp.sum(q.astype(jnp.float32) * k[:, 0].astype(jnp.float32),
                  axis=-1) * scale                              # (S, H)
    walks = [paged_heads_decode(q, kp, vp, tables, lengths, scale=scale,
                                interpret=interpret)
             for kp, vp, tables, lengths in ((wk, wv, wtables, wlen),
                                             (sk, sv, stables, slen))]
    top = own
    for m, _l, _acc in walks:
        top = jnp.maximum(top, m)
    den = jnp.exp(own - top)
    num = den[..., None] * v[:, 0].astype(jnp.float32)
    for m, l, acc in walks:      # a walk over no row: m = -1e30, l = 0
        weight = jnp.exp(m - top)
        den = den + l * weight
        num = num + acc * weight[..., None]
    return (num / den[..., None])[:, None]


def _opened(q, k, v, sk, sv, sees, interpret):
    """A chunk that opens its window, on the launch: its own causal part
    by the tiled flash forward (scores kept in VMEM), the summaries (S, T,
    H, D), of which a query `sees` some, in ``lax``, merged under one
    softmax by the launch's log-sum. -> (S, C, H, D) float32."""
    def heads_first(a):
        return a.transpose(0, 2, 1, 3)
    own, lse = flash_attention_lse(heads_first(q), heads_first(k),
                                   heads_first(v), causal=True,
                                   interpret=interpret)
    scores = jnp.where(sees, _scores(q, sk) * q.shape[-1] ** -0.5, _NEG)
    top = jnp.maximum(lse, scores.max(-1))                  # (S, H, C)
    p = jnp.where(sees, jnp.exp(scores - top[..., None]), 0.0)
    weight = jnp.exp(lse - top)         # of the normalised own part
    num = (heads_first(own).astype(jnp.float32)
           * heads_first(weight[..., None]) + _weighted(p, sv))
    return num / heads_first((weight + p.sum(-1))[..., None])


def _attention(q, k, v, cache, fresh, interpret=False):
    """The chunk's queries (S, C, H, D) against the live window rows, the
    summaries and the chunk itself. `cache`: (wlen, wtables, window key
    pool, window value pool, slen, stables, summary key pool, summary
    value pool) of the layer."""
    S, C, H = q.shape[:3]
    wlen, wtables, wk, wv, slen, stables, sk, sv = cache
    if C == 1 and (interpret or paged_heads_decode_available(wk, H)):
        return _walked(q, k, v, cache, interpret)
    sk, sv = _paged_rows(sk, stables, H), _paged_rows(sv, stables, H)
    parts = [(sk, sv, (jnp.arange(sk.shape[1])[None] < slen[:, None]
                       )[:, None, None])]
    if fresh and (interpret or flash_attention_available()) \
            and C % 128 == 0 and q.shape[-1] % 128 == 0:
        return _opened(q, k, v, *parts[0], interpret)
    if not fresh:
        wk, wv = _paged_rows(wk, wtables, H), _paged_rows(wv, wtables, H)
        parts.append((wk, wv, (jnp.arange(wk.shape[1])[None]
                               < wlen[:, None])[:, None, None]))
    tile = QUERY_TILE if C > QUERY_TILE and C % QUERY_TILE == 0 else C
    out = []
    for lo in range(0, C, tile):
        hi = lo + tile          # the tile's rows see the chunk's first hi
        causal = (jnp.arange(lo, hi)[:, None]
                  >= jnp.arange(hi)[None])[None, None]
        out.append(attend(q[:, lo:hi],
                          parts + [(k[:, :hi], v[:, :hi], causal)]))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def eva_forward_paged(params, cfg, tokens, wlen, wtables, slen, stables,
                      wk_pools, wv_pools, sk_pools, sv_pools, head="logits",
                      fresh=False, interpret=False):
    """A chunk of C new positions a sequence over the grouped paged cache.

    tokens (S, C) int32; wlen (S,) int32 the live rows of each sequence's
    window, wtables (S, MBw) its blocks; slen (S,), stables (S, MBs) the
    summaries likewise; the pools by layer, ``(num_blocks, block_size, H
    D)``: a row holds all heads side by side. A sequence's position is ``slen // (window // chunk) * window +
    wlen``: every closed window left its summaries. `fresh`: every window
    is empty (a prefill chunk that opens its window), and the window pools
    are not read. `interpret`: a chunk of one position walks the cache by
    the launches in interpret mode, wherever it runs (on a TPU it takes
    them by itself).

    Returns ``(out, new_k, new_v)``: new_k / new_v by layer (S, C, H D),
    the chunk's rotated keys and its values for the caller to commit to
    the window group. `head`: ``"logits"`` -> out (S, C, pred_heads * V)
    float32, every prediction head's (head j at position t scores byte t +
    1 + j, columns ``[j V, (j + 1) V)``); ``"token"`` -> (S,) int32, the
    argmax of head 0's logits at the last chunk position, all heads'
    logits computed; ``"none"`` -> the last layer's stream (S, C, d)
    float32, no final norm, no head: what a pipeline stage hands on (and
    what keeps the last layer of a prefill forward in the program)."""
    cfg = eva_config(cfg)
    if head not in ("logits", "token", "none"):
        raise ValueError("no such head: %r" % (head,))
    S, C = tokens.shape
    d, H = cfg["units"], cfg["num_heads"]
    eps, dtype = cfg["rms_eps"], params["embed"].dtype
    wlen, slen = jnp.asarray(wlen, jnp.int32), jnp.asarray(slen, jnp.int32)
    closed = slen // (cfg["window"] // cfg["chunk"])
    positions = (closed * cfg["window"] + wlen)[:, None] + jnp.arange(
        C, dtype=jnp.int32)[None]
    x = params["embed"][tokens].astype(jnp.float32).reshape(S * C, d)
    new_k, new_v = [], []
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        a = _norm(x, params[p + "attn_norm"], eps).astype(dtype)
        q, k, v = [(a @ params[p + n]).reshape(S, C, H, d // H)
                   for n in ("q_w", "k_w", "v_w")]
        q, k = (_rope(q, positions, cfg["rope_theta"]),
                _rope(k, positions, cfg["rope_theta"]))
        new_k.append(k.reshape(S, C, d))
        new_v.append(v.reshape(S, C, d))
        att = _attention(q, k, v, (wlen, wtables, wk_pools[i], wv_pools[i],
                                   slen, stables, sk_pools[i], sv_pools[i]),
                         fresh, interpret).astype(dtype)
        x = x + jnp.dot(att.reshape(S * C, d), params[p + "o_w"],
                        preferred_element_type=jnp.float32)
        m = _norm(x, params[p + "ffn_norm"], eps).astype(dtype)
        gate = jnp.dot(m, params[p + "gate_w"],
                       preferred_element_type=jnp.float32)
        up = jnp.dot(m, params[p + "up_w"],
                     preferred_element_type=jnp.float32)
        x = x + jnp.dot((jax.nn.silu(gate) * up).astype(dtype),
                        params[p + "down_w"],
                        preferred_element_type=jnp.float32)
    x = x.reshape(S, C, d)
    if head == "none":
        return x, new_k, new_v
    if head == "token":
        x = x[:, -1:]
    logits = jnp.dot(_norm(x, params["final_norm"], eps).astype(dtype),
                     params["head"], preferred_element_type=jnp.float32)
    if head == "token":
        logits = jnp.argmax(logits[:, -1, :cfg["vocab_size"]],
                            axis=-1).astype(jnp.int32)
    return logits, new_k, new_v


def eva_close_window(params, cfg, wtables, wk_pools, wv_pools):
    """The summaries of full windows: wtables (S, MBw), the blocks of S
    sequences' windows, every one holding ``window`` rows. -> (new_sk,
    new_sv) by layer, (S, window // chunk, H D): a layer's chunks pooled
    with its ``mu`` and ``phi``."""
    cfg = eva_config(cfg)
    H = cfg["num_heads"]
    new_sk, new_sv = [], []
    for i in range(cfg["num_layers"]):
        p = "l%d_" % i
        sk, sv = summarize_chunks(
            _paged_rows(wk_pools[i], wtables, H)[:, :cfg["window"]],
            _paged_rows(wv_pools[i], wtables, H)[:, :cfg["window"]],
            params[p + "mu"], params[p + "phi"], cfg["chunk"])
        new_sk.append(sk.reshape(sk.shape[:2] + (-1,)))
        new_sv.append(sv.reshape(sv.shape[:2] + (-1,)))
    return new_sk, new_sv
