"""The ``sdar_moe`` family's batch generation through the program's normal
path: ``generate.GenerateEngine``'s block loop over ``SDARPagedLM`` and a
``PagedKVCache``, in the dtype the configuration states (bfloat16: weights,
activations and pools).
"""

from .. import costs_moe
from ..reference import sdar_moe as reference  # noqa: F401  (the runner's)


def assumed(cfg, key):
    return cfg["assumed"][key]["value"]


def program_config(cfg):
    """The published ``config.json`` keys in the program's names."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["hidden_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "num_experts": cfg["num_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_hidden": cfg["moe_intermediate_size"],
            "rope_theta": float(cfg["rope_theta"]),
            "rms_eps": cfg["rms_norm_eps"],
            "max_len": cfg["max_position_embeddings"],
            "block_length": assumed(cfg, "block_length"),
            "mask_id": assumed(cfg, "mask_token_id")}


def require_program():
    """Fails at once, before any weight is made, in a program that has no
    block-diffusion decoder (a commit from before it)."""
    from incubator_mxnet_tpu.generate import SDARPagedLM  # noqa: F401


def build_engine(cfg, weights, traffic):
    """`weights`: the seed-made leaves, already on the device in the
    configuration's dtype; the adapter takes them as they are."""
    from incubator_mxnet_tpu.generate import GenerateEngine, SDARPagedLM
    model = SDARPagedLM(weights, program_config(cfg), dtype=cfg["dtype"])
    cache = model.make_cache(len(traffic["prompt_lens"]),
                             max_len=traffic["cache_max_len"])
    engine = GenerateEngine(model, cache, name="sdar_moe",
                            prefill_chunk=traffic["prefill_chunk"],
                            denoise_steps=traffic["denoise_steps"])
    return engine, cache


def kv_host_bytes(cfg, cache):
    """Bytes of the K and V pools handed to one forward, from the cache's
    own arrays."""
    return sum(cache.pool("%s%d" % (kind, i)).nbytes
               for i in range(cfg["num_hidden_layers"]) for kind in "kv")


def block_forward_floor_seconds(cfg, tokens, experts_hit_per_layer,
                                head_share, live_positions, peaks):
    return costs_moe.block_forward_floor_seconds(
        cfg, tokens, experts_hit_per_layer, head_share, live_positions,
        peaks)
