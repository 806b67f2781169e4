"""Plain reference: BERT pretraining (Devlin et al. 2018, arXiv:1810.04805).

Straight ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernel, no mixed precision, no sharding. It
imports nothing of the program and is given nothing the program made;
weights and batches come from the seed through the functions below, which
the harness also uses to hand the program its inputs.

Post-LN encoder (section 3 and appendix A.2 of the paper): token + position +
segment embeddings, LayerNorm; per layer ``h = LN(x + proj(attention(x)))``,
``x = LN(h + ffn2(gelu(ffn1(h))))``; masked-LM head on the masked
positions only, decoder tied to the token embedding, plus a bias over the
vocabulary; next-sentence head on the pooled first position. Loss is the
mean masked-LM cross-entropy plus the mean next-sentence cross-entropy.

Departures from the paper, all three the program's own (``models/bert.py``,
``parallel/trainer.py``), followed here so that the two compute the same
function: the masked-LM transform uses tanh where the paper uses gelu;
LayerNorm's epsilon is 1e-5; AdamW decays every leaf, biases and LayerNorm
included. Moments are float32 here; the program keeps them in bfloat16.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

MASK_ID = 103          # [MASK] in the published vocabulary
LN_EPS = 1e-5
INIT_STD = 0.02        # paper: truncated normal 0.02; plain normal here


def leaf_shapes(cfg):
    """{leaf name: shape}. A name is the program's parameter name without
    its block prefix, so that the harness can pair them by suffix."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {"word_weight": (cfg["vocab_size"], d),
              "word_bias": (cfg["vocab_size"],),
              "pos_weight": (cfg["max_position_embeddings"], d),
              "type_weight": (cfg["type_vocab_size"], d),
              "embln_gamma": (d,), "embln_beta": (d,),
              "pooler_weight": (d, d), "pooler_bias": (d,),
              "mlmd_weight": (d, d), "mlmd_bias": (d,),
              "mlmln_gamma": (d,), "mlmln_beta": (d,),
              "nsp_weight": (2, d), "nsp_bias": (2,)}
    for i in range(cfg["num_hidden_layers"]):
        p = "enc_layer%d_" % i
        for name in ("query", "key", "value", "proj"):
            shapes[p + "attn_%s_weight" % name] = (d, d)
            shapes[p + "attn_%s_bias" % name] = (d,)
        shapes[p + "ffn_ffn1_weight"] = (f, d)
        shapes[p + "ffn_ffn1_bias"] = (f,)
        shapes[p + "ffn_ffn2_weight"] = (d, f)
        shapes[p + "ffn_ffn2_bias"] = (d,)
        for ln in ("ln1", "ln2"):
            shapes[p + ln + "_gamma"] = (d,)
            shapes[p + ln + "_beta"] = (d,)
    return shapes


def seed_key(seed):
    """A PRNG key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_weights(cfg, seed):
    """All leaves in float32, on the default device, in one jitted call:
    weights N(0, 0.02), gammas 1, biases and betas 0."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for i, n in enumerate(names):
            if n.endswith("_weight"):
                out[n] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shapes[n], jnp.float32)
            elif n.endswith("_gamma"):
                out[n] = jnp.ones(shapes[n], jnp.float32)
            else:
                out[n] = jnp.zeros(shapes[n], jnp.float32)
        return out
    return make(seed_key(seed))


def make_batch(cfg, rng, rows, seq_len, mlm_positions):
    """One synthetic pretraining batch of host arrays from a numpy
    Generator: rows that all differ, `mlm_positions` distinct masked
    positions a row. -> (ids, types, positions), (mlm labels, nsp labels)"""
    ids = rng.integers(1000, cfg["vocab_size"], (rows, seq_len),
                       dtype=np.int32)
    split = rng.integers(seq_len // 4, 3 * seq_len // 4, (rows, 1))
    types = (np.arange(seq_len)[None, :] >= split).astype(np.int32)
    pos = np.argsort(rng.random((rows, seq_len)), axis=1)[:, :mlm_positions]
    pos = np.sort(pos, axis=1).astype(np.int32)
    mlm_lab = np.take_along_axis(ids, pos, axis=1)
    masked = ids.copy()
    np.put_along_axis(masked, pos, MASK_ID, axis=1)
    nsp_lab = rng.integers(0, 2, (rows,), dtype=np.int32)
    return (masked, types, pos), (mlm_lab, nsp_lab)


# ------------------------------------------------------------- precisions
def exact_dot(x, w):
    return jnp.matmul(x, w, precision="highest")


def round_trip_8bit(x, dtype):
    """Per-tensor scaled cast to an 8-bit float and back."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_dot(x, w):
    """The control's linear layer, one precision below the bfloat16 the
    configuration states: e4m3 operands forward, e5m2 cotangents
    backward, per-tensor scales, float32 accumulation."""
    return exact_dot(round_trip_8bit(x, jnp.float8_e4m3fn),
                     round_trip_8bit(w, jnp.float8_e4m3fn))


def _fp8_fwd(x, w):
    qx = round_trip_8bit(x, jnp.float8_e4m3fn)
    qw = round_trip_8bit(w, jnp.float8_e4m3fn)
    return exact_dot(qx, qw), (qx, qw)


def _fp8_bwd(res, g):
    qx, qw = res
    qg = round_trip_8bit(g, jnp.float8_e5m2)
    dx = exact_dot(qg, qw.T)
    dw = exact_dot(qx.reshape(-1, qx.shape[-1]).T,
                   qg.reshape(-1, qg.shape[-1]))
    return dx, dw


fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)

DOTS = {"float32": exact_dot, "fp8": fp8_dot}


# ---------------------------------------------------------------- forward
def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _dense(w, prefix, x, dot):
    return dot(x, w[prefix + "_weight"].T) + w[prefix + "_bias"]


def forward(w, cfg, ids, types, positions, dot=exact_dot):
    """-> (masked-LM logits (B, M, V), next-sentence logits (B, 2))"""
    B, T = ids.shape
    H = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    D = d // H
    x = (w["word_weight"][ids] + w["pos_weight"][jnp.arange(T)][None]
         + w["type_weight"][types])
    x = _ln(x, w["embln_gamma"], w["embln_beta"])
    for i in range(cfg["num_hidden_layers"]):
        p = "enc_layer%d_" % i
        q, k, v = [_dense(w, p + "attn_" + n, x, dot)
                   .reshape(B, T, H, D).transpose(0, 2, 1, 3)
                   for n in ("query", "key", "value")]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision="highest") / np.sqrt(D)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                       precision="highest")
        a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
        h = _ln(x + _dense(w, p + "attn_proj", a, dot),
                w[p + "ln1_gamma"], w[p + "ln1_beta"])
        f = jax.nn.gelu(_dense(w, p + "ffn_ffn1", h, dot), approximate=False)
        x = _ln(h + _dense(w, p + "ffn_ffn2", f, dot),
                w[p + "ln2_gamma"], w[p + "ln2_beta"])
    pooled = jnp.tanh(_dense(w, "pooler", x[:, 0], dot))
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    t = _ln(jnp.tanh(_dense(w, "mlmd", picked, dot)),
            w["mlmln_gamma"], w["mlmln_beta"])
    mlm = dot(t, w["word_weight"].T) + w["word_bias"]
    return mlm, _dense(w, "nsp", pooled, dot)


def loss_fn(w, cfg, data, label, dot=exact_dot):
    mlm, nsp = forward(w, cfg, *data, dot=dot)
    mlm_lab, nsp_lab = label
    mlm_loss = -jnp.take_along_axis(jax.nn.log_softmax(mlm, axis=-1),
                                    mlm_lab[:, :, None], axis=-1).mean()
    nsp_loss = -jnp.take_along_axis(jax.nn.log_softmax(nsp, axis=-1),
                                    nsp_lab[:, None], axis=-1).mean()
    return mlm_loss + nsp_loss


# ------------------------------------------------------------------- step
def _blocked(arrays, block_rows):
    return tuple(jnp.asarray(a).reshape((-1, block_rows) + a.shape[1:])
                 for a in arrays)


def loss_and_grads(w, cfg, data, label, block_rows, dot=exact_dot):
    """Mean loss and its gradient over the batch, `block_rows` rows at a
    time so that the activations of one block are all that is live. Every
    block has as many rows and masked positions, so the mean of the
    blocks' means is the batch's."""
    grad = jax.value_and_grad(
        lambda w_, d_, l_: loss_fn(w_, cfg, d_, l_, dot))

    def body(acc, xs):
        d_, l_ = xs
        loss, g = grad(w, d_, l_)
        return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    data, label = _blocked(data, block_rows), _blocked(label, block_rows)
    zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, w))
    (loss, g), _ = jax.lax.scan(body, zero, (data, label))
    n = data[0].shape[0]
    return loss / n, jax.tree_util.tree_map(lambda a: a / n, g)


def adamw(w, g, m, v, t, hp):
    """Decoupled weight decay (Loshchilov & Hutter), bias-corrected."""
    b1, b2 = hp["beta1"], hp["beta2"]
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                               v, g)

    def upd(w_, m_, v_):
        mhat = m_ / (1 - b1 ** t)
        vhat = v_ / (1 - b2 ** t)
        return w_ - hp["learning_rate"] * (
            mhat / (jnp.sqrt(vhat) + hp["epsilon"]) + hp["weight_decay"] * w_)
    return jax.tree_util.tree_map(upd, w, m, v), m, v


@functools.lru_cache(maxsize=8)
def _step_program(cfg_json, hp_json, block_rows, precision, frozen):
    """One jitted reference step per variant, kept so that a second seed
    of a calibration compiles nothing."""
    cfg, hp, dot = json.loads(cfg_json), json.loads(hp_json), DOTS[precision]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, t, data, label):
        loss, g = loss_and_grads(w, cfg, data, label, block_rows, dot)
        if frozen:
            return w, m, v, loss, g
        new_w, m, v = adamw(w, g, m, v, t, hp)
        return new_w, m, v, loss, g
    return step


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for n, a in tree.items()}


def follow(cfg, seed, batches, hp, block_rows, precision="float32",
           keep_rows=None, frozen=False):
    """The first len(batches) steps from the seed's weights.

    -> {"losses": [...], "grad_norms": {leaf: norm of step 1's gradient},
        "delta_norms": {leaf: norm of the change after the last step},
        "first_gradient": {leaf: step 1's gradient, a host array}}

    `precision` "fp8" is the control. Two faults can be planted:
    `keep_rows`, "part of the batch left out, the mean taken over the
    rest" (only the first `keep_rows` rows of every batch are used), and
    `frozen`, "a step that returns its state unchanged".
    """
    step = _step_program(json.dumps(cfg, sort_keys=True),
                         json.dumps(hp, sort_keys=True), block_rows,
                         precision, frozen)
    w0 = init_weights(cfg, seed)
    w = jax.tree_util.tree_map(jnp.copy, w0)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first = [], None
    for t, (data, label) in enumerate(batches, start=1):
        if keep_rows is not None:
            data = tuple(a[:keep_rows] for a in data)
            label = tuple(a[:keep_rows] for a in label)
        w, m, v, loss, g = step(w, m, v, jnp.float32(t), data, label)
        losses.append(loss)
        if t == 1:
            first = {n: np.asarray(a) for n, a in g.items()}
        del g
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(w, w0)
    return {"losses": [float(x) for x in losses],
            "grad_norms": {n: float(np.linalg.norm(a.ravel()))
                           for n, a in first.items()},
            "delta_norms": {n: float(x) for n, x in delta.items()},
            "first_gradient": first}
