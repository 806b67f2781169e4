"""The ``ouro`` family's batch generation through the program's normal
path: ``generate.GenerateEngine``'s plain loop (a token a step, greedy)
over ``OuroPagedLM`` and a ``PagedKVCache`` whose entries hold a
position's rows of every loop step side by side, in the dtype the
configuration states (bfloat16: weights, activations and the cache). The
prefill chunk is the family's (``assumed.prefill_chunk``: one chunk a
prompt), not the traffic's.
"""

from .. import costs_ouro as costs  # noqa: F401  (the runner's)
from ..reference import ouro as reference  # noqa: F401  (the runner's)
from ..reference.ouro import assumed


def program_config(cfg):
    """The published ``config.json`` keys in the program's names."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["hidden_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "hidden": cfg["intermediate_size"],
            "loop_steps": cfg["total_ut_steps"],
            "exit_threshold": float(cfg["early_exit_threshold"]),
            "rms_eps": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]),
            "max_len": cfg["max_position_embeddings"]}


def require_program():
    """Fails at once, before any weight is made, in a program that has no
    adapter for a looped model (a commit from before ``OuroPagedLM``)."""
    from incubator_mxnet_tpu.generate import OuroPagedLM  # noqa: F401


def build_engine(cfg, weights, traffic):
    """`weights`: the seed-made leaves, already on the device in the
    configuration's dtype; the adapter takes them as they are."""
    from incubator_mxnet_tpu.generate import GenerateEngine, OuroPagedLM
    model = OuroPagedLM(weights, program_config(cfg), dtype=cfg["dtype"])
    cache = model.make_cache(len(traffic["prompt_lens"]),
                             max_len=traffic["cache_max_len"])
    engine = GenerateEngine(model, cache, name="ouro",
                            prefill_chunk=assumed(cfg, "prefill_chunk"))
    return engine, cache
