"""GPT — causal decoder LLM for the generative inference engine.

Pre-LN transformer decoder (GPT-2 layout): learned token + position
embeddings, per-layer ``x += proj(attn(ln1(x)))`` then
``x += ffn(ln2(x))``, final LayerNorm, logits through the tied embedding.
The FFN is either a dense GELU MLP or — with ``moe_experts > 0`` — the
`parallel/` top-k MoE routing (`moe_ffn`), giving the decode path
expert-parallel capacity without new routing code.

Two pure forwards over the same flat param dict (names below match the
HybridBlock registration, so ``_collect_params_with_prefix`` keys align
with the serving checkpoint):

- :func:`gpt_logits` — full-sequence training/eval forward (B, T).
- :func:`gpt_forward_paged` — incremental decode forward: a chunk of C
  new tokens per sequence attends its paged KV history
  (``generate/paged_kv``) through `ops.pallas.flash_decode`, and returns
  the chunk's K/V for the engine to commit. C>1 is chunked prefill,
  C=1 is decode; one program per (S, C) shape.

``GPTDecoder`` wraps the same math as a HybridBlock so the serving
export/import machinery (initialize, checkpoints, ``_set_params``)
treats it like any other model.
"""

import math

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock, current_trace
from ..gluon.nn.basic_layers import _init_of
from ..ops.pallas.flash_decode import paged_causal_attention
from ..ops.pallas.paged_latent import LANES
from ..parallel.moe import moe_ffn

__all__ = ["GPTDecoder", "gpt_config", "gpt_param_shapes", "gpt_logits",
           "gpt_lookup_rows", "gpt_forward_paged", "gpt_sharding_rules"]


def gpt_config(config):
    """Normalize a config dict, filling derived defaults."""
    cfg = dict(config)
    cfg.setdefault("max_len", 512)
    cfg.setdefault("ffn_hidden", 4 * cfg["units"])
    cfg.setdefault("moe_experts", 0)
    cfg.setdefault("moe_top_k", 2)
    cfg.setdefault("moe_capacity_factor", 1.25)
    for key in ("vocab_size", "units", "num_layers", "num_heads"):
        if key not in cfg:
            raise ValueError("gpt config missing %r" % key)
    if cfg["units"] % cfg["num_heads"]:
        raise ValueError("units (%d) must divide by num_heads (%d)"
                         % (cfg["units"], cfg["num_heads"]))
    return cfg


def gpt_param_shapes(cfg):
    """Flat ``name -> shape`` map of every decoder parameter."""
    d, f = cfg["units"], cfg["ffn_hidden"]
    E = cfg["moe_experts"]
    shapes = {"wte": (cfg["vocab_size"], d), "wpe": (cfg["max_len"], d)}
    for i in range(cfg["num_layers"]):
        p = "h%d_" % i
        shapes[p + "ln1_g"] = (d,)
        shapes[p + "ln1_b"] = (d,)
        shapes[p + "qkv_w"] = (d, 3 * d)
        shapes[p + "qkv_b"] = (3 * d,)
        shapes[p + "proj_w"] = (d, d)
        shapes[p + "proj_b"] = (d,)
        shapes[p + "ln2_g"] = (d,)
        shapes[p + "ln2_b"] = (d,)
        if E:
            shapes[p + "gate_weight"] = (d, E)
            shapes[p + "expert_w1"] = (E, d, f)
            shapes[p + "expert_b1"] = (E, f)
            shapes[p + "expert_w2"] = (E, f, d)
            shapes[p + "expert_b2"] = (E, d)
        else:
            shapes[p + "fc_w"] = (d, f)
            shapes[p + "fc_b"] = (f,)
            shapes[p + "out_w"] = (f, d)
            shapes[p + "out_b"] = (d,)
    shapes["lnf_g"] = (d,)
    shapes["lnf_b"] = (d,)
    return shapes


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _ffn(x_flat, params, prefix, cfg):
    """Position-wise FFN on (N, d) tokens: dense GELU MLP, or the MoE
    routing when the config carries experts."""
    if cfg["moe_experts"]:
        return moe_ffn(x_flat, params, prefix,
                       top_k=cfg["moe_top_k"],
                       capacity_factor=cfg["moe_capacity_factor"])
    h = jax.nn.gelu(x_flat @ params[prefix + "fc_w"]
                    + params[prefix + "fc_b"])
    return h @ params[prefix + "out_w"] + params[prefix + "out_b"]


def gpt_logits(params, cfg, tokens):
    """Full-sequence causal forward: (B, T) int32 -> (B, T, V) logits."""
    cfg = gpt_config(cfg)
    B, T = tokens.shape
    H = cfg["num_heads"]
    d = cfg["units"]
    D = d // H
    scale = 1.0 / math.sqrt(D)
    x = params["wte"][tokens] + params["wpe"][jnp.arange(T)][None]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    for i in range(cfg["num_layers"]):
        p = "h%d_" % i
        h = _ln(x, params[p + "ln1_g"], params[p + "ln1_b"])
        qkv = h @ params[p + "qkv_w"] + params[p + "qkv_b"]
        q, k, v = [a.reshape(B, T, H, D)
                   for a in jnp.split(qkv, 3, axis=-1)]
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = jnp.where(causal[None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a,
                       v.astype(jnp.float32)).astype(x.dtype)
        x = x + (o.reshape(B, T, d) @ params[p + "proj_w"]
                 + params[p + "proj_b"])
        h2 = _ln(x, params[p + "ln2_g"], params[p + "ln2_b"])
        x = x + _ffn(h2.reshape(B * T, d), params, p, cfg).reshape(B, T, d)
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    return x @ params["wte"].T


def gpt_lookup_rows(wte):
    """The tied embedding as the paged forward's lookup table: ``wte`` (V,
    units) with k rows side by side a row, k the fewest that fill whole
    lanes of 128 (GPT-2-XL: 2 x 1,600 = 25 lanes; 1 where the units do),
    (ceil(V / k), k * units). The chip holds a float32 (V, units) array
    whose rows fill no whole lanes with the VOCABULARY minor, where a
    gather of rows wants the units minor, so a forward that gathers from
    ``wte`` first copies all of it (322 MB at GPT-2-XL); this table is
    held with its rows minor, costs no padding, and is gathered from as it
    is (``_lookup``)."""
    V, d = wte.shape
    k = LANES // math.gcd(d, LANES)
    if k == 1:
        return wte
    return jnp.pad(wte, ((0, -V % k), (0, 0))).reshape(-1, k * d)


def _lookup(rows, tokens, d):
    """The (…, d) embeddings of `tokens` from a ``gpt_lookup_rows`` table:
    the row that holds each token's, then its k-th part, chosen element by
    element (a select the consumer fuses, where a second gather is a
    launch of its own)."""
    k = rows.shape[1] // d
    held = rows[tokens // k]
    which = jnp.broadcast_to((tokens % k)[..., None], tokens.shape + (d,))
    return jax.lax.select_n(which, *(held[..., j * d:(j + 1) * d]
                                     for j in range(k)))


def gpt_forward_paged(params, cfg, tokens, lengths, block_tables,
                      k_pools, v_pools, lookup_rows, use_kernel=False,
                      interpret=False, head="logits"):
    """Incremental decode forward over the paged KV cache.

    tokens (S, C) int32 — C new tokens per slot (C=1 decode, C>1
    chunked prefill); lengths (S,) int32 committed past positions;
    block_tables (S, MB) int32; k_pools/v_pools — per-layer lists of
    ``(num_blocks, block_size, H, D)`` pool arrays; lookup_rows — the
    table the tokens' embeddings are gathered from,
    ``gpt_lookup_rows(params["wte"])`` held by the caller (the head
    reads ``params["wte"]``).

    Returns ``(logits (S, C, V), new_k, new_v)`` where new_k/new_v are
    per-layer (S, C, H, D) chunk projections for the caller (the
    engine/decode loop) to commit into the cache. Positions are clipped
    at ``max_len - 1`` so an over-length feed cannot index out of the
    position table (the cache's own max_len guard fires first in
    practice).

    ``head="token"`` returns in the logits' place the greedy choice,
    (S,) int32: the argmax of the last chunk position's logits (the
    first index on a tie, as ``np.argmax``), so the (S, C, V) array is
    no output of the program.
    """
    if head not in ("logits", "token"):
        raise ValueError("no such head: %r" % (head,))
    cfg = gpt_config(cfg)
    S, C = tokens.shape
    H = cfg["num_heads"]
    d = cfg["units"]
    D = d // H
    positions = jnp.clip(lengths[:, None] + jnp.arange(C)[None],
                         0, cfg["max_len"] - 1)
    x = _lookup(lookup_rows, tokens, d) + params["wpe"][positions]
    new_k, new_v = [], []
    for i in range(cfg["num_layers"]):
        p = "h%d_" % i
        h = _ln(x, params[p + "ln1_g"], params[p + "ln1_b"])
        qkv = h @ params[p + "qkv_w"] + params[p + "qkv_b"]
        q, k, v = [a.reshape(S, C, H, D)
                   for a in jnp.split(qkv, 3, axis=-1)]
        new_k.append(k)
        new_v.append(v)
        att = paged_causal_attention(
            q, k, v, k_pools[i], v_pools[i], block_tables, lengths,
            use_kernel=use_kernel, interpret=interpret)
        x = x + (att.reshape(S, C, d) @ params[p + "proj_w"]
                 + params[p + "proj_b"])
        h2 = _ln(x, params[p + "ln2_g"], params[p + "ln2_b"])
        x = x + _ffn(h2.reshape(S * C, d), params, p, cfg).reshape(S, C, d)
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    logits = x @ params["wte"].T
    if head == "token":
        logits = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return logits, new_k, new_v


class GPTDecoder(HybridBlock):
    """gluon face of the decoder: flat param registration (local names
    ARE the checkpoint keys), full-sequence forward through
    :func:`gpt_logits` on both the eager tape and traces."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 max_len=512, ffn_hidden=None, moe_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, **kwargs):
        super().__init__(**kwargs)
        self._cfg = gpt_config(dict(
            vocab_size=vocab_size, units=units, num_layers=num_layers,
            num_heads=num_heads, max_len=max_len,
            ffn_hidden=ffn_hidden or 4 * units, moe_experts=moe_experts,
            moe_top_k=moe_top_k, moe_capacity_factor=moe_capacity_factor))
        with self.name_scope():
            for name, shape in gpt_param_shapes(self._cfg).items():
                if name.endswith(("_b", "_b1", "_b2")):
                    init = _init_of("zeros")
                elif name.endswith("_g"):
                    init = _init_of("ones")
                else:
                    init = None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    @property
    def config(self):
        return dict(self._cfg)

    def hybrid_forward(self, F, tokens, **params):
        if hasattr(tokens, "_data"):        # eager NDArray path (tape)
            from ..ndarray.ndarray import _invoke_simple
            names = sorted(params)

            def fn(toks, *vals):
                return gpt_logits(dict(zip(names, vals)), self._cfg, toks)
            return _invoke_simple(fn, tokens, *[params[n] for n in names],
                                  op_name="GPTDecoder")
        return gpt_logits(params, self._cfg, tokens)


def gpt_sharding_rules(tp_axis="tp", ep_axis="ep"):
    """Megatron-style tensor-parallel PartitionSpecs for ShardedTrainer:
    QKV/fc column-parallel (shard output dim), proj/out row-parallel
    (shard input dim), embeddings on vocab, stacked experts over ep."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"qkv_w$", P(None, tp_axis)),
        (r"qkv_b$", P(tp_axis)),
        (r"proj_w$", P(tp_axis, None)),
        (r"fc_w$", P(None, tp_axis)),
        (r"fc_b$", P(tp_axis)),
        (r"out_w$", P(tp_axis, None)),
        (r"wte$", P(tp_axis, None)),
        (r"expert_w[12]$", P(ep_axis, None, None)),
        (r"expert_b[12]$", P(ep_axis, None)),
    ]
