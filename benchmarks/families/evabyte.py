"""The ``evabyte`` family's batch generation through the program's normal
path: ``generate.GenerateEngine``'s plain loop (a byte a step, greedy, head
0 of the prediction heads) over ``EvaPagedLM`` and a grouped
``PagedKVCache`` (a window of exact keys and values beside the summaries of
closed windows), in the dtype the configuration states (bfloat16: weights,
activations and the cache). The prefill chunk is the family's
(``assumed.prefill_chunk``: one window, so that a chunk never straddles a
closing), not the traffic's.
"""

from .. import costs_evabyte as costs  # noqa: F401  (the runner's)
from ..reference import evabyte as reference  # noqa: F401  (the runner's)
from ..reference.evabyte import assumed


def program_config(cfg):
    """The published ``config.json`` keys in the program's names."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["hidden_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "hidden": cfg["intermediate_size"],
            "pred_heads": cfg["num_pred_heads"],
            "window": cfg["window_size"], "chunk": cfg["chunk_size"],
            "rms_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]),
            "max_len": cfg["max_position_embeddings"]}


def require_program():
    """Fails at once, before any weight is made, in a program that has no
    adapter for a cache of windows and summaries (a commit from before
    ``EvaPagedLM``)."""
    from incubator_mxnet_tpu.generate import EvaPagedLM  # noqa: F401


def build_engine(cfg, weights, traffic):
    """`weights`: the seed-made leaves, already on the device in the
    configuration's dtype; the adapter takes them as they are."""
    from incubator_mxnet_tpu.generate import EvaPagedLM, GenerateEngine
    model = EvaPagedLM(weights, program_config(cfg), dtype=cfg["dtype"])
    cache = model.make_cache(len(traffic["prompt_lens"]),
                             max_len=traffic["cache_max_len"])
    engine = GenerateEngine(model, cache, name="evabyte",
                            prefill_chunk=assumed(cfg, "prefill_chunk"))
    return engine, cache
