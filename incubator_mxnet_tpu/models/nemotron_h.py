"""A hybrid state-space decoder: Nemotron-H (NVIDIA Nemotron 3 Nano's
``config.json``, ``model_type: nemotron_h``).

A stack of layers named by a pattern string, one character a layer: ``M``
a Mamba-2 mixer, ``*`` an attention layer, ``E`` an expert layer. Each
layer is ONE mixer or feed-forward part with a residual around it, the
sum held in the weights' dtype::

    h = h + mixer(rms(h, g))                    (eps 1e-5; float32 statistic)
    logits = rms(h, g_f) W_head                 (untied)

The Mamba-2 mixer (H heads of P, G groups, state N, Di = H P)::

    [z | xBC | dt] = x W_in                     widths Di | Di + 2 G N | H
    xBC = silu(causal_depthwise_conv(xBC, w_conv (Di + 2 G N, K)) + b_conv)
    [x | B | C] = xBC;  head h reads group h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (P, N) a head
    y_t = S_t C_t + D x_t
    y = rms_per_group(y * silu(z), g_y) W_out         (G groups of Di / G)

A chunk of C > 1 positions (prefill) takes the chunked (SSD) form, a
``lax.scan`` over sub-chunks of ``ssm_chunk`` positions that carries the
state, every product in float32 at ``highest``: with s_t the cumulative
sum of ``dt A`` inside a sub-chunk, ``y_t = sum_{u<=t} exp(s_t - s_u)
(C_t . B_u) dt_u x_u + exp(s_t) S_prev C_t + D x_t`` and the sub-chunk
hands on ``S_end = exp(s_Q) S_prev + sum_u exp(s_Q - s_u) dt_u x_u
B_u^T``. A chunk of one position (decode) takes the one-step recurrence,
``ops/pallas/ssm_state.py``'s ``ssm_state_step``: one Pallas launch a
layer on a TPU that reads each sequence's state row of the pool and
writes it back in place.

The attention layer: ``q = x Wq`` (heads of D), ``k, v = x Wk, x Wv``
(fewer key/value heads: grouped-query), no biases and NO positions (the
family applies no rotary embedding), causal softmax over the paged cache
(``ops/pallas/flash_decode.py``'s ``paged_causal_attention``, cell 4's
path). The expert layer: sigmoid scores over every expert the router
holds, the top-k of score + choice bias, the chosen scores renormalised
times ``route_scale``, experts NOT gated, ``relu(x W_up)^2 W_down``, and a
shared expert of the same form (``parallel.moe.moe_dropless`` with no
gate weights, under ``experts_held`` one chip's share).

Where the state lives: ``generate/paged_kv.py`` ``"state"`` entries, a
device pool a Mamba layer and kind, ``(slots,) + shape``: ``ssm`` float32,
H heads of (P, N) as the decode launch reads them (``(H / k, N, k P)``,
k heads side by side in a row's lanes: ``state_shapes``), and ``conv``
(K - 1, Di + 2 G N) in the weights' dtype, the window of the last K - 1
conv inputs. A forward is handed the rows' slot ids and ``fed`` (S,), how
many chunk positions of each row are real: a prefill round pads its rows
after their tokens, and a padded position takes dt = 0 (the state as it
was) and is not in the window, so a prompt's next chunk resumes from what
its last real position left. The forward returns the pools with the rows
of ``fed > 0`` written (the caller donates them, so in place), and the
cache installs them (``commit``).

Weights, activations and the cache are in the parameters' dtype (bfloat16
as served); the norms' statistics, the conv, the scan and its state, the
router and the logits are float32. The full-sequence float32 forward with
the sequential recurrence is the plain reference's
(``benchmarks/reference/nemotron_h.py``).
"""

import jax
import jax.numpy as jnp

from ..ops.pallas.flash_decode import paged_causal_attention
from ..ops.pallas.ssm_state import (from_heads, pool_row_shape,
                                    ssm_state_step, to_heads)
from ..parallel.moe import moe_dropless

__all__ = ["nemotron_config", "nemotron_param_shapes",
           "nemotron_forward_paged", "layer_kinds", "ssm_scan",
           "state_shapes"]

_REQUIRED = ("vocab_size", "units", "pattern", "num_heads", "num_kv_heads",
             "head_dim", "ssm_heads", "ssm_head_dim", "ssm_groups",
             "ssm_state", "conv_kernel", "num_experts", "experts_per_token",
             "expert_hidden", "shared_hidden")
_HIGHEST = jax.lax.Precision.HIGHEST


def nemotron_config(config):
    """Normalize a config dict (the program's names; the published
    ``config.json`` keys map onto them in the caller)."""
    cfg = dict(config)
    for key in _REQUIRED:
        if key not in cfg:
            raise ValueError("nemotron_h config missing %r" % key)
    if set(cfg["pattern"]) - set("ME*"):
        raise ValueError("pattern %r: one of M, E, * a layer"
                         % (cfg["pattern"],))
    cfg["num_layers"] = len(cfg["pattern"])
    cfg.setdefault("ssm_chunk", 128)
    cfg.setdefault("route_scale", 1.0)
    cfg.setdefault("rms_eps", 1e-5)
    cfg.setdefault("max_len", 262144)
    cfg.setdefault("experts_held", None)
    if cfg["experts_held"] is not None:
        cfg["experts_held"] = tuple(int(v) for v in cfg["experts_held"])
    if (cfg["num_heads"] % cfg["num_kv_heads"]
            or cfg["ssm_heads"] % cfg["ssm_groups"]):
        raise ValueError("heads must split evenly into key/value heads and "
                         "SSM heads into groups: %r" % (config,))
    return cfg


def layer_kinds(cfg):
    """The layers of each kind, in order: {"M": [...], "*": [...], "E":
    [...]}."""
    return {kind: [i for i, c in enumerate(cfg["pattern"]) if c == kind]
            for kind in "M*E"}


def _widths(cfg):
    inner = cfg["ssm_heads"] * cfg["ssm_head_dim"]
    conv = inner + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
    return inner, conv


def state_shapes(cfg):
    """A sequence's state a Mamba layer: the ssm state, H heads of (P, N)
    as the decode launch takes them (``ssm_state.pool_row_shape``: (H / k,
    N, k P), k heads side by side in a row's lanes), and the conv window
    (K - 1, Di + 2 G N)."""
    _inner, conv = _widths(cfg)
    return {"ssm": pool_row_shape(cfg["ssm_heads"], cfg["ssm_head_dim"],
                                  cfg["ssm_groups"], cfg["ssm_state"]),
            "conv": (cfg["conv_kernel"] - 1, conv)}


def nemotron_param_shapes(cfg):
    """Flat ``name -> shape`` map of every parameter; the expert leaves of
    the experts held, their ``up_w`` out by in (f, d) as the checkpoint's
    ``up_proj.weight``: a TPU keeps an (E, d, 1856) array with d minor,
    which the grouped product would copy into the other order every
    forward (``moe_dropless(up_out_in=True)``)."""
    d, V = cfg["units"], cfg["vocab_size"]
    inner, conv = _widths(cfg)
    H, D = cfg["num_heads"], cfg["head_dim"]
    Hkv, Hs = cfg["num_kv_heads"], cfg["ssm_heads"]
    E, f, fs = cfg["num_experts"], cfg["expert_hidden"], cfg["shared_hidden"]
    held = cfg["experts_held"][1] if cfg.get("experts_held") else E
    shapes = {"embed": (V, d), "final_norm": (d,), "head": (d, V)}
    for i, kind in enumerate(cfg["pattern"]):
        p = "l%d_" % i
        shapes[p + "norm"] = (d,)
        if kind == "M":
            shapes.update({
                p + "in_w": (d, inner + conv + Hs),
                p + "conv_w": (conv, cfg["conv_kernel"]),
                p + "conv_b": (conv,), p + "dt_bias": (Hs,),
                p + "A_log": (Hs,), p + "D": (Hs,),
                p + "gate_norm": (inner,), p + "out_w": (inner, d)})
        elif kind == "*":
            shapes.update({p + "q_w": (d, H * D), p + "k_w": (d, Hkv * D),
                           p + "v_w": (d, Hkv * D), p + "o_w": (H * D, d)})
        else:
            shapes.update({
                p + "router_w": (d, E), p + "router_bias": (E,),
                p + "up_w": (held, f, d), p + "down_w": (held, f, d),
                p + "shared_up_w": (d, fs), p + "shared_down_w": (fs, d)})
    return shapes


def _rms(x, g, eps):
    """``x / sqrt(mean(x^2) + eps) * g`` in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- the scan
def ssm_scan(x, dt, a, b, c, state, chunk):
    """The chunked (SSD) form over a chunk of T positions of S sequences.

    x (S, T, H, P), dt (S, T, H) (0 at a padded position), a (H,),
    b / c (S, T, G, N), state (S, H, P, N): float32, the state the chunk
    starts from. -> (y (S, T, H, P) without the skip term, the state after
    the chunk). T is cut into sub-chunks of `chunk` (the last padded with
    dt = 0) and a ``lax.scan`` carries the state across them."""
    S, T, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    per = H // G
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        def padded(v):
            return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = padded(x), padded(dt), padded(b), padded(c)
    n = (T + pad) // Q

    def pieces(v):      # (S, n Q, ...) -> (n, S, Q, ...)
        return jnp.moveaxis(v.reshape((S, n, Q) + v.shape[2:]), 1, 0)
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    def one(state, piece):
        xq, dtq, bq, cq = piece
        s = jnp.cumsum(dtq * a[None, None], axis=1)          # (S, Q, H)
        gap = s[:, :, None, :] - s[:, None, :, :]           # (S, t, u, H)
        decay = jnp.where(causal[None, :, :, None],
                          jnp.exp(jnp.where(causal[None, :, :, None], gap,
                                            0.0)), 0.0)
        cb = jnp.einsum("stgn,sugn->stug", cq, bq, precision=_HIGHEST)
        weight = (decay.reshape(S, Q, Q, G, per) * cb[..., None]
                  * dtq.reshape(S, 1, Q, G, per))           # (S,t,u,G,per)
        xg = xq.reshape(S, Q, G, per, P)
        y = jnp.einsum("stugk,sugkp->stgkp", weight, xg, precision=_HIGHEST)
        sg = state.reshape(S, G, per, P, N)
        y = y + (jnp.einsum("stgn,sgkpn->stgkp", cq, sg, precision=_HIGHEST)
                 * jnp.exp(s).reshape(S, Q, G, per, 1))
        last = s[:, -1]                                     # (S, H)
        w = (jnp.exp(last[:, None] - s) * dtq).reshape(S, Q, G, per)
        new = (sg * jnp.exp(last).reshape(S, G, per, 1, 1)
               + jnp.einsum("sugk,sugkp,sugn->sgkpn", w, xg, bq,
                            precision=_HIGHEST))
        return new.reshape(S, H, P, N), y.reshape(S, Q, H, P)
    state, ys = jax.lax.scan(one, state, tuple(map(pieces, (x, dt, b, c))))
    y = jnp.moveaxis(ys, 0, 1).reshape(S, n * Q, H, P)[:, :T]
    return y, state


def _mamba(params, p, cfg, h, slots, fed, ssm_pool, conv_pool, interpret):
    """h (S, C, d) normed -> (the mixer's output (S, C, d), the ssm pool
    and the conv pool with the rows of ``fed > 0`` written)."""
    S, C, _ = h.shape
    Hs, P, G, N = (cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_groups"],
                   cfg["ssm_state"])
    inner, conv = _widths(cfg)
    K = cfg["conv_kernel"]
    f32 = jnp.float32
    zxbcdt = jnp.dot(h, params[p + "in_w"],
                     preferred_element_type=f32).astype(h.dtype)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv],
                  zxbcdt[..., inner + conv:])
    # the conv over the window the slot kept and the chunk
    window = conv_pool[slots]                               # (S, K-1, conv)
    seq = jnp.concatenate([window, xbc], axis=1)            # (S, K-1+C, .)
    w = params[p + "conv_w"].astype(f32)
    acc = sum(seq[:, j:j + C].astype(f32) * w[:, j] for j in range(K))
    xbc = jax.nn.silu(acc + params[p + "conv_b"].astype(f32)).astype(h.dtype)
    kept = jnp.take_along_axis(
        seq, (fed[:, None] + jnp.arange(K - 1))[:, :, None], axis=1)
    x = xbc[..., :inner].astype(f32).reshape(S, C, Hs, P)
    b = xbc[..., inner:inner + G * N].astype(f32).reshape(S, C, G, N)
    c = xbc[..., inner + G * N:].astype(f32).reshape(S, C, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + params[p + "dt_bias"].astype(f32))
    dt = jnp.where(jnp.arange(C)[None, :, None] < fed[:, None, None], dt, 0.0)
    a = -jnp.exp(params[p + "A_log"].astype(f32))
    written = jnp.where(fed > 0, slots, ssm_pool.shape[0])  # past: dropped
    if C == 1:
        y, ssm_pool = ssm_state_step(ssm_pool, slots, x[:, 0], dt[:, 0], a,
                                     b[:, 0], c[:, 0], interpret=interpret)
        y = y[:, None]
    else:
        y, state = ssm_scan(x, dt, a, b, c,
                            to_heads(ssm_pool[slots].astype(f32), P),
                            cfg["ssm_chunk"])
        ssm_pool = ssm_pool.at[written].set(
            from_heads(state, G).astype(ssm_pool.dtype), mode="drop")
    conv_pool = conv_pool.at[written].set(kept, mode="drop")
    y = y + params[p + "D"].astype(f32)[:, None] * x
    y = y.reshape(S, C, inner) * jax.nn.silu(z.astype(f32))
    y = y.reshape(S, C, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg["rms_eps"])
    y = (y.reshape(S, C, inner) * params[p + "gate_norm"].astype(f32)
         ).astype(h.dtype)
    return y @ params[p + "out_w"], ssm_pool, conv_pool


def _attention(params, p, cfg, h, lengths, tables, k_pool, v_pool):
    """h (S, C, d) normed -> (the layer's output, the chunk's k, v)."""
    S, C, _ = h.shape
    H, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = (h @ params[p + "q_w"]).reshape(S, C, H, D)
    k = (h @ params[p + "k_w"]).reshape(S, C, Hkv, D)
    v = (h @ params[p + "v_w"]).reshape(S, C, Hkv, D)
    att = paged_causal_attention(q, k, v, k_pool, v_pool, tables, lengths,
                                 use_kernel=False)
    return att.reshape(S, C, H * D) @ params[p + "o_w"], k, v


def _experts(params, p, cfg, h, loads, interpret):
    """h (S, C, d) normed -> (S, C, d); appends the layer's loads (under
    ``experts_held``: the held experts', then ``routes_elsewhere`` and
    ``rows_moved``)."""
    S, C, d = h.shape
    held = cfg["experts_held"]
    out, stats = moe_dropless(
        h.reshape(S * C, d), params[p + "router_w"], None,
        params[p + "up_w"], params[p + "down_w"], cfg["experts_per_token"],
        interpret=interpret, return_stats=True, scoring="sigmoid",
        choice_bias=params[p + "router_bias"],
        route_scale=cfg["route_scale"],
        shared=(None, params[p + "shared_up_w"],
                params[p + "shared_down_w"]),
        held=held, up_out_in=True)
    loads.append(stats["expert_load"] if held is None
                 else jnp.concatenate([stats["expert_load"], jnp.stack(
                     [stats["routes_elsewhere"], stats["rows_moved"]])]))
    return out.reshape(S, C, d)


def nemotron_forward_paged(params, cfg, tokens, lengths, tables, k_pools,
                           v_pools, slots, fed, ssm_pools, conv_pools,
                           head="logits", interpret=False):
    """A chunk of C new positions a sequence over the paged cache and the
    state pools.

    tokens (S, C) int32; lengths (S,) int32 committed positions, tables
    (S, MB) the blocks of the attention layers' cache; k_pools / v_pools
    by attention layer ``(num_blocks, block_size, Hkv, D)``; slots (S,)
    int32 each row's slot, fed (S,) int32 its real positions of the chunk
    (a prefill round's pads after them; 0: a row that stores nothing);
    ssm_pools / conv_pools by Mamba layer, ``(slots,) + state_shapes``.
    `interpret`: the expert layer's grouped products and the decode step's
    state launch in interpret mode, wherever it runs.

    Returns ``(out, loads, new_k, new_v, ssm_pools, conv_pools)``: new_k /
    new_v by attention layer (S, C, Hkv, D), for the caller to commit; the
    state pools with each fed row's state after its last real position
    (the caller donates the pools it passed); ``loads`` (expert layers,
    E, or held + 2) int32. `head`: ``"logits"`` -> (S, C, V) float32,
    position c's logits choose token c + 1; ``"token"`` -> (S,) int32,
    the argmax at the last chunk position; ``"none"`` -> None."""
    cfg = nemotron_config(cfg)
    if head not in ("logits", "token", "none"):
        raise ValueError("no such head: %r" % (head,))
    S, C = tokens.shape
    eps = cfg["rms_eps"]
    lengths = jnp.asarray(lengths, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    fed = jnp.asarray(fed, jnp.int32)
    x = params["embed"][tokens]
    new_k, new_v, loads = [], [], []
    ssm_pools, conv_pools = list(ssm_pools), list(conv_pools)
    m = a = 0
    for i, kind in enumerate(cfg["pattern"]):
        p = "l%d_" % i
        h = _rms(x, params[p + "norm"], eps)
        if kind == "M":
            y, ssm_pools[m], conv_pools[m] = _mamba(
                params, p, cfg, h, slots, fed, ssm_pools[m], conv_pools[m],
                interpret)
            m += 1
        elif kind == "*":
            y, k, v = _attention(params, p, cfg, h, lengths, tables,
                                 k_pools[a], v_pools[a])
            new_k.append(k)
            new_v.append(v)
            a += 1
        else:
            y = _experts(params, p, cfg, h, loads, interpret)
        x = (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)
    held = cfg["experts_held"]
    loads = (jnp.stack(loads) if loads else jnp.zeros(
        (0, cfg["num_experts"] if held is None else held[1] + 2), jnp.int32))
    out = None
    if head != "none":
        if head == "token":
            x = x[:, -1:]
        out = jnp.dot(_rms(x, params["final_norm"], eps), params["head"],
                      preferred_element_type=jnp.float32)
        if head == "token":
            out = jnp.argmax(out[:, -1], axis=-1).astype(jnp.int32)
    return out, loads, new_k, new_v, ssm_pools, conv_pools
