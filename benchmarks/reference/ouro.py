"""Plain reference: the ``ouro`` family (ByteDance Ouro's ``config.json``:
``model_type: ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741), full-sequence forward.

Straight ``jax.numpy`` in float32 with every matrix product at ``highest``
precision. No cache, no paging, no kernels, no loop in a program: the
stack is applied T times in Python, a layer a jitted call whose bfloat16
weights are lifted to float32 inside it (one layer's lift at a time beside
the engine's pools), attention a dense causal softmax over the whole
sequence, logits only at the positions asked for. It imports nothing of
the program and is given nothing the program made but the tokens it is
asked about.

The equations (ISSUE 41; sizes H hidden, n heads of d, ``s = d ** -0.5``,
F, V, L layers, T = ``total_ut_steps``, eps)::

    rms(x, g) = x / sqrt(mean(x^2) + eps) * g       (statistic in float32)
    layer l on a stream h (layer l's weights at every loop step u):
      a = rms(h, g1);  q, k, v = a Wq, a Wk, a Wv  (no bias), n heads
      q, k rotated at the token's position (theta, rotate-half over all d;
      the position is the same at every loop step)
      h = h + rms(Attn_{u,l}(q, k, v) Wo, g2)
      m = rms(h, g3);  h = h + rms((silu(m Wg) * (m Wu)) Wd, g4)
    Attn_{u,l}: causal softmax attention (float32) over the sequence's
      keys and values of layer l at loop step u, no other step's
    h_0 = embed(x);  h_u = rms(Layers_1..L(h_{u-1}), g_f)  for u = 1..T
      (the final norm ends every loop step; the next starts from it)
    exit gate: lambda_u = sigmoid(h_u . w_g + b_g);
      p_u = lambda_u prod_{j<u} (1 - lambda_j) for u < T,
      p_T = prod_{j<T} (1 - lambda_j);
      a token exits at the first u with sum_{j<=u} p_j >= threshold
    logits = h_exit W_head  (untied); at the published threshold 1.0 the
      exit is u = T for every token

Departures from the published model, the program's own too (the
configuration's file lists them under ``assumed``, none fetched):

- seed-made weights: matrices N(0, ``initializer_range``), norm gains 1,
  the gate's vector N(0, ``initializer_range``) and its bias 0;
- no biases in q, k, v, o; no q/k norms;
- the sandwich norms' order as written; the final norm fed back between
  loop steps; the gate's form as written;
- greedy decoding.

Leaf names are the program's (``models/ouro.py`` ``ouro_param_shapes``),
so that one seed-made dict serves both; leaves are made in the dtype the
configuration states and lifted to float32 here.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bert import round_trip_8bit, seed_key
from .evabyte import _f32, _Frozen, _rope

PRECISIONS = ("float32", "float8_e4m3")
#: the runner pads every checked sequence to whole blocks of this
#: (``key_rows_of``): one compiled shape a run
BLOCK_ROWS = 128


def assumed(cfg, key):
    return cfg["assumed"][key]["value"]


def layer_shapes(cfg):
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    return {"attn_norm": (C,), "q_w": (C, C), "k_w": (C, C), "v_w": (C, C),
            "o_w": (C, C), "attn_post_norm": (C,), "ffn_norm": (C,),
            "gate_w": (C, F), "up_w": (C, F), "down_w": (F, C),
            "ffn_post_norm": (C,)}


def init_weights(cfg, seed):
    """All leaves in the configuration's dtype on the default device: the
    matrices and the gate's vector N(0, ``seed_weight_range`` where the
    file gives one, else ``assumed.initializer_range``), the norms' gains
    1, the gate's bias 0. One compiled program makes a layer, another the
    rest."""
    C, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(cfg["dtype"])
    std = cfg.get("seed_weight_range", assumed(cfg, "initializer_range"))

    def draw(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    @jax.jit
    def make_layer(key):
        shapes = layer_shapes(cfg)
        return {n: (jnp.ones(shapes[n], dtype) if n.endswith("_norm")
                    else draw(jax.random.fold_in(key, i), shapes[n]))
                for i, n in enumerate(sorted(shapes))}

    @jax.jit
    def make_rest(key):
        k1, k2, k3 = jax.random.split(key, 3)
        V = cfg["vocab_size"]
        return {"embed": draw(k1, (V, C)), "head": draw(k2, (C, V)),
                "final_norm": jnp.ones((C,), dtype),
                "exit_w": draw(k3, (C,)), "exit_b": jnp.zeros((1,), dtype)}

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


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * _f32(g))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg, precision, x, positions, attn_norm, q_w, k_w, v_w, o_w,
           attn_post_norm, ffn_norm, gate_w, up_w, down_w, ffn_post_norm):
    """One layer over the whole sequence's stream x (R, C): causal
    attention over this application's own keys and values, then the
    MLP, each sublayer's output normed before it is added."""
    dot, eps = _dot(precision), cfg["rms_norm_eps"]
    R, H = len(x), cfg["num_attention_heads"]
    theta = float(cfg["rope_theta"])
    a = _rms(x, attn_norm, eps)
    q, k, v = [dot(a, _f32(m)).reshape(R, H, -1) for m in (q_w, k_w, v_w)]
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") \
        * q.shape[-1] ** -0.5
    causal = jnp.arange(R)[None, :] <= jnp.arange(R)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest").reshape(R, -1)
    x = x + _rms(dot(o, _f32(o_w)), attn_post_norm, eps)
    m = _rms(x, ffn_norm, eps)
    y = dot(jax.nn.silu(dot(m, _f32(gate_w))) * dot(m, _f32(up_w)),
            _f32(down_w))
    return x + _rms(y, ffn_post_norm, eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _step_end(cfg, x, final_norm, at, exit_w, exit_b):
    """The final norm that ends a loop step -> (h_u (R, C), at the asked
    rows the stream and the gate's lambda_u)."""
    h = _rms(x, final_norm, cfg["rms_norm_eps"])
    lam = jax.nn.sigmoid(jnp.dot(h[at], _f32(exit_w), precision="highest")
                         + _f32(exit_b)[0])
    return h, h[at], lam


@jax.jit
def _embed(table, tokens):
    return _f32(table[tokens])


@functools.partial(jax.jit, static_argnums=(0,))
def _head(precision, h, head):
    return _dot(precision)(h, _f32(head))


def exit_steps(lambdas, threshold):
    """lambdas (T, P), the gate at every loop step of P positions -> the
    loop step (1..T) each exits at: the first u whose cumulative exit
    probability ``sum_{j<=u} p_j`` reaches `threshold`, the last step's
    probability being what is left, so that the sum is 1 there exactly."""
    lambdas = np.asarray(lambdas, np.float64)
    T = len(lambdas)
    left = np.ones(lambdas.shape[1])
    done = np.zeros(lambdas.shape[1], np.int64)
    for u in range(T):
        if u < T - 1:
            cdf = 1.0 - left * (1.0 - lambdas[u])
            left = left * (1.0 - lambdas[u])
        else:
            cdf = np.ones_like(left)
        done = np.where((done == 0) & (cdf >= threshold), u + 1, done)
    return done


def _sequence_logits(w, cfg, tokens, at, precision, key_rows, threshold):
    n = len(tokens)
    R = max(n, key_rows or 0)
    padded = np.zeros(R, np.int32)
    padded[:n] = tokens     # pads lie after the sequence: causality keeps
    positions = np.arange(R, dtype=np.int32)        # them out of its rows
    at = np.asarray(at)
    x = _embed(w["embed"], padded)
    names = ["attn_norm", "q_w", "k_w", "v_w", "o_w", "attn_post_norm",
             "ffn_norm", "gate_w", "up_w", "down_w", "ffn_post_norm"]
    streams, lambdas = [], []
    for _u in range(cfg["total_ut_steps"]):
        for layer in range(cfg["num_hidden_layers"]):
            x = _layer(cfg, precision, x, positions,
                       *[w["l%d_%s" % (layer, n)] for n in names])
        x, h, lam = _step_end(cfg, x, w["final_norm"], at, w["exit_w"],
                              w["exit_b"])
        streams.append(h)
        lambdas.append(np.asarray(lam))
    step = exit_steps(lambdas, threshold)
    h = jnp.stack(streams)[step - 1, np.arange(len(at))]
    return np.asarray(_head(precision, h, w["head"]))


def logits(w, cfg, tokens, at, precision="float32", key_rows=None,
           exit_threshold=None):
    """(N, T) int32 tokens -> (N, P, V) float32 logits at the positions
    `at` (N, P); position t's logits choose token t + 1, taken from the
    loop step each position exits at (``exit_steps`` under
    `exit_threshold`, the configuration's ``early_exit_threshold`` where
    None: 1.0, every position the last step). "float8_e4m3", the control:
    the operands of every linear layer's matrix product (q, k, v, o,
    gate, up, down, head) through a per-tensor scaled e4m3 round trip,
    the nearest precision below the bfloat16 the configuration states;
    all else as in float32. `key_rows`: every sequence is padded to so
    many positions (sequences of different lengths then share their
    compiled programs)."""
    if precision not in PRECISIONS:
        raise ValueError("no such precision: %r" % precision)
    cfg = _Frozen(cfg)
    threshold = (cfg["early_exit_threshold"] if exit_threshold is None
                 else exit_threshold)
    with jax.default_matmul_precision("highest"):
        return np.stack([
            _sequence_logits(w, cfg, np.asarray(row, np.int32), positions,
                             precision, key_rows, threshold)
            for row, positions in zip(tokens, at)])
