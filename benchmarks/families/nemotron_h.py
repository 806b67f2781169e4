"""The ``nemotron_h`` family's batch generation through the program's normal
path: ``generate.GenerateEngine``'s plain loop (a token a step, greedy)
over ``NemotronHPagedLM`` and a ``PagedKVCache`` whose attention layer
keeps a key and a value row a position and whose Mamba layers keep each
slot's state on the device (``ssm`` float32, ``conv`` in the weights'
dtype), in the dtype the configuration states (bfloat16: weights,
activations and the cache). The configuration is ONE chip's share of a
deployment: ``n_routed_experts`` counts the experts held here of the
``router_width`` the router scores, ``vocab_size`` the rows of the
vocabulary held. The prefill chunk is the family's
(``assumed.prefill_chunk``), not the traffic's.
"""

from .. import costs_nemotron_h as costs  # noqa: F401  (the runner's)
from ..reference import nemotron_h as reference  # noqa: F401  (the runner's)
from ..reference.nemotron_h import assumed, held_experts


def program_config(cfg):
    """The published ``config.json`` keys in the program's names."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["hidden_size"],
            "pattern": cfg["hybrid_override_pattern"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "ssm_heads": cfg["mamba_num_heads"],
            "ssm_head_dim": cfg["mamba_head_dim"],
            "ssm_groups": cfg["n_groups"],
            "ssm_state": cfg["ssm_state_size"],
            "conv_kernel": cfg["conv_kernel"],
            "ssm_chunk": cfg["chunk_size"],
            "num_experts": cfg["router_width"],
            "experts_held": held_experts(cfg),
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_hidden": cfg["moe_intermediate_size"],
            "shared_hidden": cfg["moe_shared_expert_intermediate_size"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "rms_eps": cfg["norm_eps"],
            "max_len": cfg["max_position_embeddings"]}


def require_program():
    """Fails at once, before any weight is made, in a program that has no
    adapter for a hybrid state-space model (a commit from before
    ``NemotronHPagedLM``)."""
    from incubator_mxnet_tpu.generate import NemotronHPagedLM  # noqa: F401


def build_engine(cfg, weights, traffic):
    """`weights`: the seed-made leaves, already on the device in the
    configuration's dtype; the adapter takes them as they are."""
    from incubator_mxnet_tpu.generate import GenerateEngine, NemotronHPagedLM
    model = NemotronHPagedLM(weights, program_config(cfg), dtype=cfg["dtype"])
    cache = model.make_cache(len(traffic["prompt_lens"]),
                             max_len=traffic["cache_max_len"],
                             name="nemotron_h")
    engine = GenerateEngine(model, cache, name="nemotron_h",
                            prefill_chunk=assumed(cfg, "prefill_chunk"))
    return engine, cache
