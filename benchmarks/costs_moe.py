"""Operations and bytes of the ``sdar_moe`` family, from the shapes alone.

Counted by hand from the layer equations (``benchmarks/reference/
sdar_moe.py``), as ``costs.py`` counts BERT and GPT-2: matrix products and
attention only, a multiply-add is two operations. Configuration dicts are
the files under ``benchmarks/configs/`` (the published ``config.json``
keys).
"""

from . import costs


def expert_params(cfg):
    """Parameters of ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_experts(cfg):
    d, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attention = 2 * d * H * D + 2 * d * Hkv * D      # q, o; k, v
    norms = 2 * d + 2 * D
    return attention + d * cfg["num_experts"] + norms


def param_count(cfg):
    """Every parameter held: the layers with all their experts, the
    embedding, the untied head and the final norm."""
    layer = (layer_params_outside_experts(cfg)
             + cfg["num_experts"] * expert_params(cfg))
    tables = 2 * cfg["vocab_size"] * cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer + tables + cfg["hidden_size"]


def expert_product_flops(cfg, routes):
    """The three grouped products of an expert layer over `routes`
    token-expert routes: each route is one row through gate, up, down."""
    return 2.0 * routes * expert_params(cfg)


def expert_product_bytes(cfg, routes, experts_hit, itemsize=2):
    """What the three grouped products must move: the weights of the
    experts that got a route, once; and a row a route in and out of each
    product (the hidden row in `itemsize`, product outputs in float32)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = experts_hit * expert_params(cfg) * itemsize
    rows = routes * ((2 * d + f) * itemsize + (2 * f + d) * 4)
    return weights + rows


def kv_bytes_per_position(cfg, itemsize=2):
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"] * itemsize)


def block_forward_floor_seconds(cfg, tokens, experts_hit_per_layer,
                                head_share, live_positions, peaks,
                                itemsize=2):
    """The least time for one forward of `tokens` positions (all rows):
    every weight it touches and the live keys and values read once at the
    HBM peak, or its operations at the bf16 peak, whichever is longer.
    `experts_hit_per_layer`: the experts whose weights a layer reads (a
    measured mean, at most ``num_experts``); `head_share`: the share of
    forwards that run the head (a store pass does not)."""
    d, v, layers = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    k = cfg["num_experts_per_tok"]
    outside = layer_params_outside_experts(cfg)
    read = (layers * (outside + experts_hit_per_layer * expert_params(cfg))
            + head_share * d * v + tokens * d)          # embedding rows
    nbytes = read * itemsize + live_positions * kv_bytes_per_position(
        cfg, itemsize)
    flops = 2.0 * tokens * (layers * (outside + k * expert_params(cfg))
                            + head_share * d * v)
    return costs.roofline_seconds(flops, nbytes, peaks)
