"""Plain reference: the ``nemotron_h`` family (NVIDIA Nemotron 3 Nano's
``config.json``, ``model_type: nemotron_h``) as ONE chip's share of a
deployment, full-sequence forward.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision. No cache, no paging, no kernels, no batching, no chunked scan:
each Mamba layer runs the SEQUENTIAL recurrence, a ``lax.scan`` over the
positions one at a time; attention is a dense causal softmax, its queries
a block of ``BLOCK_ROWS`` at a time; the expert layer is a plain loop over
the experts HELD, each over the tokens that chose it. The logits are
computed at the positions asked for alone. It imports nothing of the
program and is given nothing the program made but the tokens it is asked
about.

The equations (the pattern's characters, one layer each: M a Mamba-2
mixer, * attention, E experts; ``rms(z, g) = g z / sqrt(mean(z^2) +
eps)``, eps 1e-5)::

    every layer: h = h + mixer(rms(h, g))
    M:  [z | xBC | dt] = x W_in;  xBC = silu(conv4(xBC) + b_conv)
        [x (H, P) | B (G, N) | C (G, N)] = xBC;  head h reads group h // (H/G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        out = rms_per_group(y * silu(z), g_y) W_out   (G groups of H P / G)
    *:  q, k, v = x Wq, x Wk, x Wv (no biases, NO positions);
        out = concat_h softmax_causal(q_h k_{h // (Hq/Hkv)}^T / sqrt(D)) v W_o
    E:  s = sigmoid(x W_r) over all ``router_width`` experts;
        chosen = top-k of s + bias;  w_e = scale s_e / (sum_chosen s + 1e-20)
        out_here = sum_{e chosen, held here} w_e relu(x W_up,e)^2 W_down,e
                   + relu(x W_up,shared)^2 W_down,shared
    logits = rms(h_L, g_f) W_head   (untied)

Departures from the published model, the program's own too (the
configuration's file lists them under ``assumed`` and ``deployment``):

- seed-made weights: matrices N(0, ``initializer_range``); the conv's
  weight and bias U(-0.5, 0.5) (PyTorch's default at a fan-in of 4);
  ``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of dt drawn
  log-uniformly in [``time_step_min``, ``time_step_max``], floored at
  ``time_step_floor`` (the Mamba-2 initialisation, so that the state
  decays as the model's does); ``D`` 1; gains 1; the router's choice bias
  N(0, ``router_bias_range``);
- THE PARTIAL EXPERT SUM: this chip holds ``n_routed_experts`` experts of
  the ``router_width`` the router scores; a chosen expert held elsewhere
  adds nothing here, and ``out_here`` goes on to the next layer;
- the vocabulary slice: embedding and head hold ``vocab_size`` rows, the
  first of the published table;
- the layers: one period of the pattern (``hybrid_override_pattern``), the
  published layers 34-42;
- no rotary embedding: the family's modelling code applies none
  (``rope_theta`` and ``partial_rotary_factor`` are keys it does not read);
- the SSM state in float32, the program's too (the configuration states
  it; ``"bfloat16_state"`` rounds it to bfloat16 after every position,
  a reading the check cannot tell from float32 on seed-made weights:
  PERF.md section 6).

Leaf names are the program's (``models/nemotron_h.py``
``nemotron_param_shapes``), so that one seed-made dict serves both; leaves
are made in the dtype the configuration states and lifted to float32 here.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .bert import round_trip_8bit, seed_key
from .evabyte import _f32, _Frozen

PRECISIONS = ("float32", "float8_e4m3", "bfloat16_state")
#: positions a block of queries; the runner pads every checked sequence
#: to whole blocks of this (``key_rows_of``): one compiled shape a run
BLOCK_ROWS = 256


def assumed(cfg, key):
    return cfg["assumed"][key]["value"]


def held_experts(cfg):
    """(first, count): the routed experts this chip holds."""
    count = cfg["n_routed_experts"]
    return assumed(cfg, "expert_share_index") * count, count


def widths(cfg):
    """(Di = H P, the conv's channels Di + 2 G N)."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return inner, inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def layer_shapes(cfg, kind):
    """The leaves of one layer of `kind` (M, * or E)."""
    C = cfg["hidden_size"]
    shapes = {"norm": (C,)}
    if kind == "M":
        inner, conv = widths(cfg)
        H = cfg["mamba_num_heads"]
        shapes.update(in_w=(C, inner + conv + H),
                      conv_w=(conv, cfg["conv_kernel"]), conv_b=(conv,),
                      dt_bias=(H,), A_log=(H,), D=(H,), gate_norm=(inner,),
                      out_w=(inner, C))
    elif kind == "*":
        D, Hq, Hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"])
        shapes.update(q_w=(C, Hq * D), k_w=(C, Hkv * D), v_w=(C, Hkv * D),
                      o_w=(Hq * D, C))
    else:
        E, held = cfg["router_width"], cfg["n_routed_experts"]
        f = cfg["moe_intermediate_size"]
        fs = cfg["moe_shared_expert_intermediate_size"]
        # a routed expert's up_w out by in, (f, C), as the program holds it
        shapes.update(router_w=(C, E), router_bias=(E,), up_w=(held, f, C),
                      down_w=(held, f, C), shared_up_w=(C, fs),
                      shared_down_w=(fs, C))
    return shapes


def _leaf(cfg, key, name, shape):
    """One leaf, drawn as the module's docstring says."""
    f32, dtype = jnp.float32, jnp.dtype(cfg["dtype"])
    if name in ("norm", "gate_norm", "D", "final_norm"):
        return jnp.ones(shape, dtype)
    if name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(cfg["conv_kernel"])
        return jax.random.uniform(key, shape, f32, -bound, bound
                                  ).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, f32, lo, hi)),
                         cfg["time_step_floor"])
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    scale = assumed(cfg, "router_bias_range" if name == "router_bias"
                    else "initializer_range")
    return (scale * jax.random.normal(key, shape, f32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make_layer(cfg, key, kind):
    shapes = layer_shapes(cfg, kind)
    return {n: _leaf(cfg, jax.random.fold_in(key, i), n, shapes[n])
            for i, n in enumerate(sorted(shapes))}


@functools.partial(jax.jit, static_argnums=0)
def _make_rest(cfg, key):
    k1, k2 = jax.random.split(key)
    V, C = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": _leaf(cfg, k1, "embed", (V, C)),
            "head": _leaf(cfg, k2, "head", (C, V)),
            "final_norm": _leaf(cfg, k1, "final_norm", (C,))}


def init_weights(cfg, seed):
    """All leaves in the configuration's dtype on the default device (the
    draws: the module's docstring). One compiled program makes a layer of
    each kind, another the two tables; a configuration's programs are
    compiled once a process."""
    cfg = _Frozen(cfg)
    pattern = cfg["hybrid_override_pattern"]
    key = seed_key(seed)
    weights = _make_rest(cfg, jax.random.fold_in(key, len(pattern)))
    for i, kind in enumerate(pattern):
        layer = _make_layer(cfg, jax.random.fold_in(key, i), kind)
        weights.update({"l%d_%s" % (i, n): a for n, a in layer.items()})
    return weights


# ------------------------------------------------------------- the pieces
def _dot(x, w, precision="float32"):
    if precision == "float8_e4m3":
        x, w = (round_trip_8bit(_f32(v), jnp.float8_e4m3fn) for v in (x, w))
    return jnp.matmul(x, _f32(w), precision="highest")


def _rms(z, g, eps):
    return _f32(g) * z * jax.lax.rsqrt(jnp.mean(jnp.square(z), -1,
                                                keepdims=True) + eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _normed(cfg, x, gain):
    return _rms(x, gain, cfg["norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _mamba(cfg, precision, h, in_w, conv_w, conv_b, dt_bias, A_log, D,
           gate_norm, out_w):
    """The mixer over the whole sequence h (R, C), the recurrence a
    position at a time."""
    R = h.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner, conv = widths(cfg)
    zxbcdt = _dot(h, in_w, precision)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    padded = jnp.concatenate([jnp.zeros((K - 1, conv)), xbc])
    xbc = jax.nn.silu(sum(padded[j:j + R] * _f32(conv_w)[:, j]
                          for j in range(K)) + _f32(conv_b))
    x = xbc[:, :inner].reshape(R, H, P)
    b = jnp.repeat(xbc[:, inner:inner + G * N].reshape(R, G, N), H // G, 1)
    c = jnp.repeat(xbc[:, inner + G * N:].reshape(R, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + _f32(dt_bias))                # (R, H)
    a = -jnp.exp(_f32(A_log))

    def step(state, t):
        xt, dtt, bt, ct = t
        state = (state * jnp.exp(dtt * a)[:, None, None]
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        if precision == "bfloat16_state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hpn,hn->hp", state, ct,
                                 precision="highest")
    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, dt, b, c))
    y = (y + _f32(D)[:, None] * x).reshape(R, inner) * jax.nn.silu(z)
    y = y.reshape(R, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg["norm_eps"])
    return _dot(y.reshape(R, inner) * _f32(gate_norm), out_w, precision)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(cfg, precision, h, q_w, k_w, v_w, o_w):
    """Dense causal attention over the whole sequence, a block of
    ``BLOCK_ROWS`` queries at a time."""
    R, D = h.shape[0], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _dot(h, q_w, precision).reshape(R // BLOCK_ROWS, BLOCK_ROWS, Hkv,
                                        Hq // Hkv, D)
    k = _dot(h, k_w, precision).reshape(R, Hkv, D)
    v = _dot(h, v_w, precision).reshape(R, Hkv, D)

    def block(args):
        qb, first = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k,
                       precision="highest") / math.sqrt(D)
        sees = (jnp.arange(R)[None, :]
                <= first + jnp.arange(BLOCK_ROWS)[:, None])
        p = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision="highest")
    o = jax.lax.map(block, (q, jnp.arange(R // BLOCK_ROWS) * BLOCK_ROWS))
    return _dot(o.reshape(R, Hq * D), o_w, precision)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _route(cfg, precision, h, router_w, bias):
    """Over ALL the router's experts -> (the chosen (T, k), their weights
    (T, k), normalised over all k chosen)."""
    s = jax.nn.sigmoid(_dot(h, router_w, precision))
    _, chosen = jax.lax.top_k(s + _f32(bias), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, (cfg["routed_scaling_factor"] * picked
                    / (jnp.sum(picked, -1, keepdims=True) + 1e-20))


def _relu2(h, up, down, precision):
    return _dot(jnp.square(jax.nn.relu(_dot(h, up, precision))), down,
                precision)


@functools.partial(jax.jit, static_argnums=(0,))
def _shared(precision, h, up, down):
    return _relu2(h, up, down, precision)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _one_expert(precision, out, h, index, weight, e, up, down):
    """`out` plus held expert `e`'s weighted output for its own tokens: the
    rows `index` of h (padded with an index past h: dropped)."""
    rows = h.at[index].get(mode="fill", fill_value=0.0)
    return out.at[index].add(
        weight[:, None] * _relu2(rows, up[e].T, down[e], precision),
        mode="drop")


def _experts_here(w, p, cfg, precision, h, live):
    """h (R, C), of which the first `live` rows are the sequence: the
    shared expert over all, then every HELD expert over the tokens that
    chose it; a chosen expert held elsewhere adds nothing."""
    chosen, weights = jax.device_get(_route(
        cfg, precision, h, w[p + "router_w"], w[p + "router_bias"]))
    out = _shared(precision, h, w[p + "shared_up_w"], w[p + "shared_down_w"])
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
                          w[p + "up_w"], w[p + "down_w"])
    return out


@jax.jit
def _embed(table, tokens):
    return _f32(table[tokens])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(cfg, precision, x, gain, head):
    return _dot(_rms(x, gain, cfg["norm_eps"]), head, precision)


def _sequence_logits(w, cfg, tokens, at, precision, key_rows):
    T = len(tokens)
    R = -(-max(T, key_rows or 0) // BLOCK_ROWS) * BLOCK_ROWS
    padded = np.zeros(R, np.int32)
    padded[:T] = tokens     # pads after the sequence: causality keeps them
    x = _embed(w["embed"], padded)      # out of its rows
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = "l%d_" % i
        h = _normed(cfg, x, w[p + "norm"])
        if kind == "M":
            y = _mamba(cfg, precision, h, *[w[p + n] for n in (
                "in_w", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "gate_norm", "out_w")])
        elif kind == "*":
            y = _attention(cfg, precision, h, w[p + "q_w"], w[p + "k_w"],
                           w[p + "v_w"], w[p + "o_w"])
        else:
            y = _experts_here(w, p, cfg, precision, h, T)
        x = x + y
    return np.asarray(_head(cfg, precision, x[np.asarray(at)],
                            w["final_norm"], w["head"]))


def logits(w, cfg, tokens, at, precision="float32", key_rows=None):
    """(N, T) int32 tokens -> (N, P, V) float32 logits at the positions
    `at` (N, P); position t's logits choose token t + 1. "float8_e4m3",
    the control: the operands of every linear layer's matrix product (the
    Mamba layers' in and out projections, q, k, v, o, the router, the
    experts held, the shared expert, the head) through a per-tensor scaled
    e4m3 round trip, the nearest precision below the bfloat16 the
    configuration states; all else as in float32. "bfloat16_state": the
    SSM state rounded to bfloat16 after every position, all else as in
    float32 (a reading, not the control). `key_rows`: every
    sequence is padded to so many positions (sequences of different
    lengths then share their compiled programs)."""
    if precision not in PRECISIONS:
        raise ValueError("no such precision: %r" % precision)
    cfg = _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        return np.stack([
            _sequence_logits(w, cfg, np.asarray(row, np.int32), positions,
                             precision, key_rows)
            for row, positions in zip(tokens, at)])
