"""Operations and bytes of the ``kimi_k2`` family as ONE chip's share of a
deployment, from the shapes alone.

Counted by hand from the layer equations (``benchmarks/reference/
kimi_k2.py``), as ``costs_xing4.py`` counts its family: matrix products and
attention only, a multiply-add is two operations. Configuration dicts are
the files under ``benchmarks/configs/``: ``n_routed_experts`` is the number
of experts HELD here, ``router_width`` what the router scores,
``vocab_size`` the rows of the vocabulary held. Everything counted is what
this chip holds, reads or computes: a token's routes that fall on other
chips' experts cost this chip nothing and are not counted.
"""

from . import costs
# the same layer family, the same counts: latent attention's projections, a
# gated MLP's three matrices, the leading dense layers, a latent cache row
from .costs_xing4 import (attention_params, cache_bytes_per_position,
                          dense_layers, dense_mlp_params, expert_params,
                          kv_b_params)


def router_params(cfg):
    """Over every expert of the layer, held here or not; and the bias."""
    return (cfg["hidden_size"] + 1) * cfg["router_width"]


def layer_params_outside_mlp(cfg):
    """Attention and both sublayer norms."""
    return attention_params(cfg) + 2 * cfg["hidden_size"]


def expert_layer_params_outside_routed(cfg):
    """What every chip holds of an expert layer: attention, the norms, the
    router and the shared expert."""
    return (layer_params_outside_mlp(cfg) + router_params(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg))


def param_count(cfg):
    """Every parameter held HERE: the layers with the experts held, the
    vocabulary's slice of the embedding and of the untied head, the final
    norm."""
    dense = dense_layers(cfg)
    expert_layer = (expert_layer_params_outside_routed(cfg)
                    + cfg["n_routed_experts"] * expert_params(cfg))
    return (dense * (layer_params_outside_mlp(cfg) + dense_mlp_params(cfg))
            + (cfg["num_hidden_layers"] - dense) * expert_layer
            + 2 * cfg["vocab_size"] * cfg["hidden_size"]
            + cfg["hidden_size"])


def routes_here_a_token(cfg):
    """The routes of a token that fall on this chip's experts under even
    routing: ``k held / router_width`` (0.25 as published over 32 chips)."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def mlp_params_a_token(cfg, layer):
    """What one token multiplies HERE in layer `layer`'s feed-forward."""
    if layer < dense_layers(cfg):
        return dense_mlp_params(cfg)
    return router_params(cfg) + expert_params(cfg) * (
        routes_here_a_token(cfg) + cfg["n_shared_experts"])


def decode_step_floor_seconds(cfg, rows, experts_hit_per_layer,
                              live_positions, peaks, itemsize=2):
    """The least time for one decode step of `rows` sequences: every
    weight the step touches (of an expert layer the `experts_hit_per_layer`
    HELD experts that got a route, a measured mean, and what every chip
    holds), the head's slice, the rows' embeddings and the live latent
    rows read once at the HBM peak; or its operations at the bf16 peak
    (the products a token makes here and the absorbed attention: H heads
    against every live row, 576 wide for the score, 512 for the value),
    whichever is longer."""
    C, V, layers = (cfg["hidden_size"], cfg["vocab_size"],
                    cfg["num_hidden_layers"])
    dense = dense_layers(cfg)
    read = (dense * (layer_params_outside_mlp(cfg) + dense_mlp_params(cfg))
            + (layers - dense) * (expert_layer_params_outside_routed(cfg)
                                  + experts_hit_per_layer
                                  * expert_params(cfg))
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
    products here, the expanded attention over the causal half, the
    re-expansion. The LAST layer's rows need its ``kv_a`` projection
    alone: what follows them feeds nothing a prefill keeps, and is not
    counted. A chunk's padding is work the chip does and no prompt needs:
    not counted either."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    tokens = sum(prompt_tokens)
    whole = ((layers - 1) * layer_params_outside_mlp(cfg)
             + sum(mlp_params_a_token(cfg, i) for i in range(layers - 1)))
    last = C * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    head_width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                  + cfg["v_head_dim"])
    pairs = sum(n * (n + 1) // 2 for n in prompt_tokens)
    return 2.0 * (tokens * (whole + last)
                  + (layers - 1) * (pairs * H * head_width
                                    + expanded_rows * kv_b_params(cfg)))
