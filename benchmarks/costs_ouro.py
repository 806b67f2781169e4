"""Operations and bytes of the ``ouro`` family, from the shapes alone.

Counted by hand from the layer equations (``benchmarks/reference/
ouro.py``), as ``costs_evabyte.py`` counts its family: matrix products and
attention only, a multiply-add is two operations. Configuration dicts are
the files under ``benchmarks/configs/``. EVERY layer is counted at EVERY
loop step: a token passes the stack ``total_ut_steps`` (T) times, so its
products, its reads of the layer weights and its cache rows are T times a
plain decoder's of the same depth. A floor that forgot the loop would read
``gen_mfu`` T times too low.
"""

from . import costs


def layer_params(cfg):
    """q, k, v, o; gate, up, down; the four norms of the sandwich."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * C * C + 3 * C * F + 4 * C


def layer_products(cfg):
    """What one token multiplies in a layer's seven matrices, once."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * C * C + 3 * C * F


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def param_count(cfg):
    """The layers, the embedding and the untied head, the final norm and
    the exit gate (a vector and a bias)."""
    C = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["vocab_size"] * C + head_params(cfg) + C + C + 1)


def applications(cfg):
    """Layer applications a token: T x L."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def cache_bytes_per_row(cfg, itemsize=2):
    """A key and a value of every head: one (loop step, layer) entry's row
    of a position."""
    return 2 * cfg["hidden_size"] * itemsize


def decode_step_bytes(cfg, rows, cache_rows, itemsize=2):
    """What one decode step of `rows` sequences has to read -> (the
    weights: every layer's T times, the final norm T times, the head once,
    the rows' embeddings; the cache: `cache_rows` rows summed over every
    (loop step, layer) entry, a key and a value of every head each)."""
    C, T = cfg["hidden_size"], cfg["total_ut_steps"]
    weights = (T * (cfg["num_hidden_layers"] * layer_params(cfg) + C)
               + head_params(cfg) + rows * C) * itemsize
    return weights, cache_rows * cache_bytes_per_row(cfg, itemsize)


def decode_step_floor_seconds(cfg, rows, cache_rows, peaks, itemsize=2):
    """The least time for one decode step of `rows` sequences that read
    `cache_rows` live cache rows summed over the T x L entries (a step's
    mean, from the engine's tallies): ``decode_step_bytes`` once at the
    HBM peak, or the step's operations at the bf16 peak (a token's
    products in T x L layer applications and the head, a score and a
    value a cache row and head dimension), whichever is longer."""
    weights, cached = decode_step_bytes(cfg, rows, cache_rows, itemsize)
    flops = 2.0 * (rows * (applications(cfg) * layer_products(cfg)
                           + head_params(cfg))
                   + cache_rows * 2 * cfg["hidden_size"])
    return costs.roofline_seconds(flops, weights + cached, peaks)


def walk_floor_seconds(cfg, cache_rows, peaks, itemsize=2):
    """The least time for the decode walks over `cache_rows` cache rows
    summed over every (loop step, layer) entry walked: a key and a value
    of every head read once at the HBM peak, or a score and a value a row
    and head dimension at the bf16 peak, whichever is longer."""
    return costs.roofline_seconds(
        2.0 * cache_rows * 2 * cfg["hidden_size"],
        cache_rows * cache_bytes_per_row(cfg, itemsize), peaks)


def prefill_flops(cfg, prompt_tokens):
    """The operations of prefilling prompts of `prompt_tokens` (a list:
    the positions committed of each), each from an empty cache: a token's
    products in every layer at every loop step, and the causal half of
    its own scores and values at every (step, layer). A chunk's padding is
    work the chip does and no prompt needs: not counted; nor is the head,
    which a prefill forward does not run."""
    C = cfg["hidden_size"]
    pairs = sum(n * (n + 1) // 2 for n in prompt_tokens)
    return 2.0 * applications(cfg) * (sum(prompt_tokens) * layer_products(cfg)
                                      + pairs * 2 * C)
