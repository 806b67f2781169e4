"""GPT-2 batch generation through the program's normal path:
``generate.GenerateEngine`` over ``GPTPagedLM`` and a ``PagedKVCache``,
float32 and ``use_kernel=False`` as shipped (``chip_smoke.phase_generate``,
proven on the chip in PR 22, at the sizes of the configuration's file).
"""

from .. import costs
from ..reference import gpt2 as reference  # noqa: F401  (the runner's)


def program_config(cfg):
    """The published ``config.json`` keys in the program's names."""
    return {"vocab_size": cfg["vocab_size"], "units": cfg["n_embd"],
            "num_layers": cfg["n_layer"], "num_heads": cfg["n_head"],
            "max_len": cfg["n_positions"]}


def build_engine(cfg, weights, slots, cache_max_len):
    """`weights`: the seed-made leaves, already on the device; the model
    takes them as they are (``jnp.asarray`` of a device array is itself)."""
    from incubator_mxnet_tpu.generate import GenerateEngine, GPTPagedLM
    model = GPTPagedLM(weights, program_config(cfg))
    cache = model.make_cache(slots, max_len=cache_max_len)
    return GenerateEngine(model, cache), cache


def kv_host_bytes(cfg, cache):
    """Bytes of the K and V pools handed to one forward, from the cache's
    own arrays."""
    return sum(cache.pool("%s%d" % (kind, i)).nbytes
               for i in range(cfg["n_layer"]) for kind in "kv")


def decode_step_floor_seconds(cfg, rows, live_positions, peaks):
    return costs.gpt_decode_step_floor_seconds(cfg, rows, live_positions,
                                               peaks)
