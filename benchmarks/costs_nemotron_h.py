"""Operations and bytes of the ``nemotron_h`` family as ONE chip's share of
a deployment, from the shapes alone.

Counted by hand from the layer equations (``benchmarks/reference/
nemotron_h.py``), as ``costs_kimi_k2.py`` counts its family: matrix
products, attention and the state-space scan, a multiply-add is two
operations. Configuration dicts are the files under
``benchmarks/configs/``: ``n_routed_experts`` is the number of experts
HELD here, ``router_width`` what the router scores, ``vocab_size`` the
rows of the vocabulary held, ``hybrid_override_pattern`` the layers. An
expert is NOT gated: two products (up, down), not three. The recurrent
state is bytes a decode step must move like the weights: each live
sequence's float32 state of every Mamba layer is read and written once a
step, whatever its length.
"""

from . import costs


def _count(cfg, kind):
    return cfg["hybrid_override_pattern"].count(kind)


def widths(cfg):
    """(Di = H P, the conv's channels Di + 2 G N)."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return inner, inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mamba_products(cfg):
    """What one token multiplies in a Mamba layer's two matrices."""
    C, H = cfg["hidden_size"], cfg["mamba_num_heads"]
    inner, conv = widths(cfg)
    return C * (inner + conv + H) + inner * C


def mamba_params(cfg):
    """in_w, out_w, the conv's weight and bias, A_log, D, dt_bias, the
    gated norm's gain and the layer's norm."""
    C, H, K = cfg["hidden_size"], cfg["mamba_num_heads"], cfg["conv_kernel"]
    inner, conv = widths(cfg)
    return mamba_products(cfg) + conv * (K + 1) + 3 * H + inner + C


def attention_products(cfg):
    C, D = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return C * (Hq + 2 * Hkv) * D + Hq * D * C


def attention_params(cfg):
    return attention_products(cfg) + cfg["hidden_size"]


def expert_params(cfg):
    """up and down: relu^2 experts have no gate."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def router_params(cfg):
    """Over every expert of the layer, held here or not; and the bias."""
    return (cfg["hidden_size"] + 1) * cfg["router_width"]


def expert_layer_params_outside_routed(cfg):
    """What every chip holds of an expert layer: the router, the shared
    expert and the layer's norm."""
    return router_params(cfg) + shared_params(cfg) + cfg["hidden_size"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def param_count(cfg):
    """Every parameter held HERE: the layers with the experts held, the
    vocabulary's slice of the embedding and of the untied head, the final
    norm."""
    expert_layer = (expert_layer_params_outside_routed(cfg)
                    + cfg["n_routed_experts"] * expert_params(cfg))
    return (_count(cfg, "M") * mamba_params(cfg)
            + _count(cfg, "*") * attention_params(cfg)
            + _count(cfg, "E") * expert_layer
            + 2 * head_params(cfg) + cfg["hidden_size"])


def routes_here_a_token(cfg):
    """The routes of a token that fall on this chip's experts under even
    routing: ``k held / router_width``."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def state_bytes_per_slot(cfg):
    """A sequence's state of every Mamba layer: the float32 ssm state
    (H, P, N) and the conv window (K - 1, conv) in the weights' dtype."""
    inner, conv = widths(cfg)
    ssm = inner * cfg["ssm_state_size"] * 4
    window = (cfg["conv_kernel"] - 1) * conv * 2
    return _count(cfg, "M") * (ssm + window)


def cache_bytes_per_row(cfg, itemsize=2):
    """A key and a value of every key/value head: one attention layer's
    row of a position."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def token_products(cfg, routes_here):
    """What one token multiplies in every layer's matrices (the router,
    the shared expert and `routes_here` routed experts an expert layer),
    the head not counted."""
    C = cfg["hidden_size"]
    expert = (C * cfg["router_width"] + shared_params(cfg)
              + routes_here * expert_params(cfg))
    return (_count(cfg, "M") * mamba_products(cfg)
            + _count(cfg, "*") * attention_products(cfg)
            + _count(cfg, "E") * expert)


def decode_step_bytes(cfg, rows, experts_hit_per_layer, cache_rows,
                      itemsize=2):
    """What one decode step of `rows` sequences has to move -> (the
    weights: every Mamba and attention layer's, an expert layer's router,
    shared expert and the experts hit, the head, the rows' embeddings;
    the state: each row's read and written once; the cache: `cache_rows`
    live rows summed over the attention layers)."""
    experts = _count(cfg, "E") * (
        expert_layer_params_outside_routed(cfg)
        + experts_hit_per_layer * expert_params(cfg))
    weights = (_count(cfg, "M") * mamba_params(cfg)
               + _count(cfg, "*") * attention_params(cfg) + experts
               + head_params(cfg) + rows * cfg["hidden_size"]) * itemsize
    state = 2 * rows * state_bytes_per_slot(cfg)
    return weights, state, cache_rows * cache_bytes_per_row(cfg, itemsize)


def decode_step_floor_seconds(cfg, rows, experts_hit_per_layer, cache_rows,
                              peaks, itemsize=2):
    """The least time for one decode step: ``decode_step_bytes`` once at
    the HBM peak, or the step's operations at the bf16 peak (a token's
    products with the routes that fall here, the head, a score and a value
    a cached row and query head, the recurrence's ~4 operations a state
    element), whichever is longer."""
    weights, state, cached = decode_step_bytes(
        cfg, rows, experts_hit_per_layer, cache_rows, itemsize)
    inner, _conv = widths(cfg)
    flops = (2.0 * rows * (token_products(cfg, routes_here_a_token(cfg))
                           + head_params(cfg))
             + 4.0 * cache_rows * cfg["num_attention_heads"]
             * cfg["head_dim"]
             + 4.0 * rows * _count(cfg, "M") * inner
             * cfg["ssm_state_size"])
    return costs.roofline_seconds(flops, weights + state + cached, peaks)


def ssm_step_bytes(cfg, rows):
    """What the decode launches (``ssm_state_step``, one a Mamba layer) of
    `rows` row-steps must move: each row's float32 state read and written
    once a layer, its dt x, decay, B and C in and y out (float32)."""
    inner, _conv = widths(cfg)
    H, G, N = (cfg["mamba_num_heads"], cfg["n_groups"],
               cfg["ssm_state_size"])
    per_layer = 2 * inner * N * 4 + (2 * inner + H + 2 * G * N) * 4
    return rows * _count(cfg, "M") * per_layer


def ssm_step_floor_seconds(cfg, rows, peaks):
    """``ssm_step_bytes`` at the HBM peak (the launch's ~4 operations a
    state element are far under the bf16 peak's share)."""
    inner, _conv = widths(cfg)
    flops = 4.0 * rows * _count(cfg, "M") * inner * cfg["ssm_state_size"]
    return costs.roofline_seconds(flops, ssm_step_bytes(cfg, rows), peaks)


def scan_flops_per_token(cfg):
    """The chunked scan's operations a token and Mamba layer: the causal
    half of a chunk's C.B and weighted-x products, its state's read into
    y and its share of the state handed on."""
    inner, _conv = widths(cfg)
    Q, G, N = cfg["chunk_size"], cfg["n_groups"], cfg["ssm_state_size"]
    half = (Q + 1) / 2.0
    return 2.0 * (half * (G * N + inner) + 2 * inner * N)


def prefill_flops(cfg, prompt_tokens):
    """The operations of prefilling prompts of `prompt_tokens` (a list:
    the positions committed of each), each from an empty cache: a token's
    products with the routes that fall here under even routing, the
    causal half of its scores and values in every attention layer, the
    scan in every Mamba layer. A chunk's padding is work the chip does
    and no prompt needs: not counted; nor is the head, which a prefill
    forward does not run."""
    tokens = sum(prompt_tokens)
    pairs = sum(n * (n + 1) // 2 for n in prompt_tokens)
    return (2.0 * tokens * token_products(cfg, routes_here_a_token(cfg))
            + 4.0 * pairs * _count(cfg, "*") * cfg["num_attention_heads"]
            * cfg["head_dim"]
            + tokens * _count(cfg, "M") * scan_flops_per_token(cfg))
