"""The ``kimi_k2`` family's batch generation through the program's normal
path: ``generate.GenerateEngine``'s plain loop (a token a step, greedy)
over ``MLAPagedLM`` and a ``PagedKVCache`` of one latent row a position and
layer, in the dtype the configuration states (bfloat16: weights,
activations and the cache). One residual stream; the configuration is ONE
chip's share of a deployment: ``n_routed_experts`` counts the experts held
here of the ``router_width`` the router scores, ``vocab_size`` the rows of
the vocabulary held. The prefill chunk is the family's
(``assumed.prefill_chunk``), not the traffic's.
"""

from .. import costs_kimi_k2 as costs  # noqa: F401  (the runner's)
from ..reference import kimi_k2 as reference  # noqa: F401  (the runner's)
from ..reference.kimi_k2 import assumed, held_experts


def program_config(cfg):
    """The published ``config.json`` keys in the program's names; no
    ``streams``: one residual stream."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["hidden_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
            "nope_dim": cfg["qk_nope_head_dim"],
            "rope_dim": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
            "dense_layers": cfg["first_k_dense_replace"],
            "dense_hidden": cfg["intermediate_size"],
            "num_experts": cfg["router_width"],
            "experts_held": held_experts(cfg),
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_hidden": cfg["moe_intermediate_size"],
            "shared_experts": cfg["n_shared_experts"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "rms_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]),
            "yarn": cfg["rope_scaling"],
            "max_len": cfg["max_position_embeddings"]}


def require_program():
    """Fails at once, before any weight is made, in a program whose expert
    layer cannot be told which experts it holds (a commit from before
    ``moe_dropless``'s ``held``)."""
    import inspect
    from incubator_mxnet_tpu.parallel.moe import moe_dropless
    if "held" not in inspect.signature(moe_dropless).parameters:
        raise RuntimeError(
            "this program's moe_dropless has no `held`: its expert layer "
            "holds every expert or none, and cannot run one chip's share")


def build_engine(cfg, weights, traffic):
    """`weights`: the seed-made leaves, already on the device in the
    configuration's dtype; the adapter takes them as they are."""
    from incubator_mxnet_tpu.generate import GenerateEngine, MLAPagedLM
    model = MLAPagedLM(weights, program_config(cfg), dtype=cfg["dtype"])
    cache = model.make_cache(len(traffic["prompt_lens"]),
                             max_len=traffic["cache_max_len"])
    engine = GenerateEngine(model, cache, name="kimi_k2",
                            prefill_chunk=assumed(cfg, "prefill_chunk"))
    return engine, cache
