"""The ``xing4_0`` family's batch generation through the program's normal
path: ``generate.GenerateEngine``'s plain loop (a token a step, greedy)
over ``MLAPagedLM`` and a ``PagedKVCache`` of one latent row a position and
layer, in the dtype the configuration states (bfloat16: weights,
activations and the cache). The prefill chunk is the family's
(``assumed.prefill_chunk``), not the traffic's.
"""

from .. import costs_xing4 as costs  # noqa: F401  (the runner's)
from ..reference import xing4 as reference  # noqa: F401  (the runner's)
from ..reference.xing4 import assumed


def program_config(cfg):
    """The published ``config.json`` keys in the program's names."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["hidden_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "nope_dim": cfg["qk_nope_head_dim"],
            "rope_dim": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
            "dense_layers": cfg["first_k_dense_replace"],
            "dense_hidden": cfg["intermediate_size"],
            "num_experts": cfg["n_routed_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_hidden": cfg["moe_intermediate_size"],
            "shared_experts": cfg["n_shared_experts"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "streams": cfg["hc_mult"],
            "sinkhorn_iters": cfg["hc_sinkhorn_iters"],
            "hc_eps": cfg["hc_eps"],
            "res_clamp": (float(cfg["mhc_h_res_clamp_min"]),
                          float(cfg["mhc_h_res_clamp_max"])),
            "rms_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]),
            "yarn": cfg["rope_scaling"],
            "max_len": cfg["max_position_embeddings"]}


def require_program():
    """Fails at once, before any weight is made, in a program that has no
    latent-attention decoder (a commit from before it)."""
    from incubator_mxnet_tpu.generate import MLAPagedLM  # noqa: F401


def build_engine(cfg, weights, traffic):
    """`weights`: the seed-made leaves, already on the device in the
    configuration's dtype; the adapter takes them as they are."""
    from incubator_mxnet_tpu.generate import GenerateEngine, MLAPagedLM
    model = MLAPagedLM(weights, program_config(cfg), dtype=cfg["dtype"])
    cache = model.make_cache(len(traffic["prompt_lens"]),
                             max_len=traffic["cache_max_len"])
    engine = GenerateEngine(model, cache, name="xing4",
                            prefill_chunk=assumed(cfg, "prefill_chunk"))
    return engine, cache
