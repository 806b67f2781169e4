"""Plain reference: GPT-2 (Radford et al. 2019), full-sequence forward.

Straight ``jax.numpy`` in the precision the configuration states:
float32 arrays through ``jnp.matmul`` and ``jnp.einsum`` as JAX ships them
("float32 as shipped", ISSUE 26). On the TPU that is one bfloat16 pass on
the MXU with float32 accumulation for a matrix product and float32 for all
else; on a CPU it is float32 throughout. No cache, no paging, no chunks, no
batching tricks. Pre-LN
decoder: learned token + position embeddings; per layer
``x += proj(attention(ln1(x)))``, ``x += out(gelu_new(fc(ln2(x))))``;
final LayerNorm; logits through the tied token embedding. It imports
nothing of the program and is given nothing the program made.

Leaf names are the program's (``models/gpt.py`` ``gpt_param_shapes``), so
that one seed-made dict serves both. Initialisation is GPT-2's scheme:
weights N(0, r), the two residual projections of a layer scaled by
1/sqrt(2 x layers), positions at half that range, gains 1, biases 0; r is
the configuration's ``seed_weight_range`` where it gives one (PERF.md says
why the cells do) and the published ``initializer_range`` otherwise.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bert import seed_key

LN_EPS = 1e-5


def layer_shapes(cfg):
    d = cfg["n_embd"]
    f = 4 * d
    return {"ln1_g": (d,), "ln1_b": (d,), "qkv_w": (d, 3 * d),
            "qkv_b": (3 * d,), "proj_w": (d, d), "proj_b": (d,),
            "ln2_g": (d,), "ln2_b": (d,), "fc_w": (d, f), "fc_b": (f,),
            "out_w": (f, d), "out_b": (d,)}


def init_weights(cfg, seed):
    """All leaves in float32 on the default device. One compiled program
    makes a layer and is called once a layer, so that no more than a
    layer of random bits is live beside the weights; a second makes the
    embeddings."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    shapes = layer_shapes(cfg)
    names = sorted(shapes)
    resid = 1.0 / np.sqrt(2.0 * layers)
    std = cfg.get("seed_weight_range", cfg.get("initializer_range", 0.02))

    @jax.jit
    def make_layer(key):
        out = {}
        for i, n in enumerate(names):
            if n.endswith("_w"):
                scale = std * (resid if n in ("proj_w", "out_w") else 1.0)
                out[n] = scale * jax.random.normal(
                    jax.random.fold_in(key, i), shapes[n], jnp.float32)
            elif n.endswith("_g"):
                out[n] = jnp.ones(shapes[n], jnp.float32)
            else:
                out[n] = jnp.zeros(shapes[n], jnp.float32)
        return out

    @jax.jit
    def make_rest(key):
        k1, k2 = jax.random.split(key)
        return {"wte": std * jax.random.normal(
                    k1, (cfg["vocab_size"], d), jnp.float32),
                "wpe": 0.5 * std * jax.random.normal(
                    k2, (cfg["n_positions"], d), jnp.float32),
                "lnf_g": jnp.ones((d,), jnp.float32),
                "lnf_b": jnp.zeros((d,), jnp.float32)}

    key = seed_key(seed)
    weights = make_rest(jax.random.fold_in(key, layers))
    for i in range(layers):
        layer = make_layer(jax.random.fold_in(key, i))
        weights.update({"h%d_%s" % (i, n): a for n, a in layer.items()})
    return weights


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


@functools.partial(jax.jit, static_argnames=("layers", "heads", "dtype"))
def _logits(w, tokens, layers, heads, dtype):
    def get(name):
        return w[name].astype(dtype)
    B, T = tokens.shape
    d = w["wte"].shape[1]
    D = d // heads
    x = get("wte")[tokens] + get("wpe")[jnp.arange(T)][None]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    for i in range(layers):
        p = "h%d_" % i
        h = _ln(x, get(p + "ln1_g"), get(p + "ln1_b"))
        qkv = jnp.matmul(h, get(p + "qkv_w")) + get(p + "qkv_b")
        q, k, v = [a.reshape(B, T, heads, D) for a in jnp.split(qkv, 3, -1)]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D).astype(dtype)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                       v).reshape(B, T, d)
        x = x + jnp.matmul(a, get(p + "proj_w")) + get(p + "proj_b")
        h = _ln(x, get(p + "ln2_g"), get(p + "ln2_b"))
        f = jax.nn.gelu(jnp.matmul(h, get(p + "fc_w")) + get(p + "fc_b"),
                        approximate=True)
        x = x + jnp.matmul(f, get(p + "out_w")) + get(p + "out_b")
    x = _ln(x, get("lnf_g"), get("lnf_b"))
    return jnp.matmul(x, get("wte").T).astype(jnp.float32)


def logits(w, cfg, tokens, precision="float32"):
    """(B, T) int32 -> (B, T, V) float32 logits. "bfloat16", the control:
    weights, activations and every elementwise step in bfloat16, the
    nearest precision below the float32 that the configuration states."""
    if precision not in ("float32", "bfloat16"):
        raise ValueError("no such precision: %r" % precision)
    return _logits(w, jnp.asarray(tokens, jnp.int32), cfg["n_layer"],
                   cfg["n_head"], jnp.dtype(precision))
