"""Operations and bytes of the ``xing4_0`` family, from the shapes alone.

Counted by hand from the layer equations (``benchmarks/reference/
xing4.py``), as ``costs.py`` counts BERT and GPT-2: matrix products and
attention only, a multiply-add is two operations. Configuration dicts are
the files under ``benchmarks/configs/`` (the published ``config.json``
keys).
"""

from . import costs


def attention_params(cfg):
    """q_a, q_b, kv_a, kv_b, o and the two latent norms."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    d_n, d_r, d_v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (C * r_q + r_q * H * (d_n + d_r) + C * (r_kv + d_r)
            + kv_b_params(cfg) + H * d_v * C + r_q + r_kv)


def kv_b_params(cfg):
    """The latent's up-projection to every head's keys and values."""
    return (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))


def hyper_params(cfg):
    """One sublayer's hyper-connection maps: phi, three scalars, biases."""
    n = cfg["hc_mult"]
    maps = 2 * n + n * n
    return n * cfg["hidden_size"] * maps + 3 + maps


def expert_params(cfg):
    """Parameters of ONE expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts"]    # + bias


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params_outside_mlp(cfg):
    """Attention, both sublayers' maps and both sublayer norms."""
    return (attention_params(cfg) + 2 * hyper_params(cfg)
            + 2 * cfg["hidden_size"])


def dense_layers(cfg):
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def param_count(cfg):
    """Every parameter held: the layers with all their experts, the
    embedding, the untied head and the final norm."""
    dense = dense_layers(cfg)
    expert_layer = (router_params(cfg) + expert_params(cfg)
                    * (cfg["n_routed_experts"] + cfg["n_shared_experts"]))
    return (cfg["num_hidden_layers"] * layer_params_outside_mlp(cfg)
            + dense * dense_mlp_params(cfg)
            + (cfg["num_hidden_layers"] - dense) * expert_layer
            + 2 * cfg["vocab_size"] * cfg["hidden_size"]
            + cfg["hidden_size"])


def mlp_params_a_token(cfg, layer):
    """What one token multiplies in layer `layer`'s feed-forward."""
    if layer < dense_layers(cfg):
        return dense_mlp_params(cfg)
    return router_params(cfg) + expert_params(cfg) * (
        cfg["num_experts_per_tok"] + cfg["n_shared_experts"])


def cache_bytes_per_position(cfg, itemsize=2):
    """One latent row a layer: ``kv_lora_rank + qk_rope_head_dim``."""
    return ((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * cfg["num_hidden_layers"] * itemsize)


def decode_step_floor_seconds(cfg, rows, experts_hit_per_layer,
                              live_positions, peaks, itemsize=2):
    """The least time for one decode step of `rows` sequences: every
    weight the step touches (of an expert layer the `experts_hit_per_layer`
    routed experts that got a route, a measured mean, and the shared one),
    the head, the rows' embeddings and the live latent rows read once at
    the HBM peak; or its operations at the bf16 peak (the products a token
    and the absorbed attention: H heads against every live row, 576 wide
    for the score, 512 for the value), whichever is longer."""
    C, V, layers = (cfg["hidden_size"], cfg["vocab_size"],
                    cfg["num_hidden_layers"])
    dense = dense_layers(cfg)
    read = (layers * layer_params_outside_mlp(cfg)
            + dense * dense_mlp_params(cfg)
            + (layers - dense) * (router_params(cfg) + expert_params(cfg) * (
                experts_hit_per_layer + cfg["n_shared_experts"]))
            + C * V + C + rows * C)
    nbytes = read * itemsize + live_positions * cache_bytes_per_position(
        cfg, itemsize)
    token = (layers * layer_params_outside_mlp(cfg)
             + sum(mlp_params_a_token(cfg, i) for i in range(layers))
             + C * V)
    attention = (layers * live_positions * cfg["num_attention_heads"]
                 * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))
    return costs.roofline_seconds(2.0 * (rows * token + attention), nbytes,
                                  peaks)


def prefill_flops(cfg, prompt_tokens, expanded_rows):
    """The operations of prefilling prompts of `prompt_tokens` (a list:
    the positions committed of each) in chunks that up-projected
    `expanded_rows` cached rows again (the engine's tally): a token's
    products, the expanded attention over the causal half, the
    re-expansion. The LAST layer's rows need its ``kv_a`` projection and
    its attention maps alone: what follows them feeds nothing a prefill
    keeps, and is not counted."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    tokens = sum(prompt_tokens)
    whole = ((layers - 1) * layer_params_outside_mlp(cfg)
             + sum(mlp_params_a_token(cfg, i) for i in range(layers - 1)))
    last = (hyper_params(cfg) + C
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))
    head_width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                  + cfg["v_head_dim"])
    pairs = sum(n * (n + 1) // 2 for n in prompt_tokens)
    return 2.0 * (tokens * (whole + last)
                  + (layers - 1) * (pairs * H * head_width
                                    + expanded_rows * kv_b_params(cfg)))
