"""Operations and bytes of the ``evabyte`` family, from the shapes alone.

Counted by hand from the layer equations (``benchmarks/reference/
evabyte.py``), as ``costs_xing4.py`` counts its family: matrix products and
attention only, a multiply-add is two operations. Configuration dicts are
the files under ``benchmarks/configs/``. EVERY layer is counted whole, in a
prefill too: the chip is a pipeline stage, and a prefill forward hands the
last layer's stream on (``eva_forward_paged(head="none")`` returns it, so
the program runs what is counted here).
"""

from . import costs


def layer_params(cfg):
    """q, k, v, o; gate, up, down; the two norms; mu and phi."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * C * C + 3 * C * F + 2 * C + 2 * C


def layer_products(cfg):
    """What one token multiplies in a layer's seven matrices."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * C * C + 3 * C * F


def head_params(cfg):
    return cfg["hidden_size"] * cfg["num_pred_heads"] * cfg["vocab_size"]


def param_count(cfg):
    C = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["vocab_size"] * C + head_params(cfg) + C)


def summaries_per_window(cfg):
    return cfg["window_size"] // cfg["chunk_size"]


def cache_bytes_per_row(cfg, itemsize=2):
    """A key and a value of every head: a window row and a summary row
    alike, a layer."""
    return 2 * cfg["hidden_size"] * itemsize


def rows_read(cfg, context):
    """(exact window rows, summary rows) a query at position `context` - 1
    reads, itself among the first."""
    w = cfg["window_size"]
    last = context - 1
    return last % w + 1, last // w * summaries_per_window(cfg)


def decode_step_floor_seconds(cfg, rows, window_rows, summary_rows, peaks,
                              itemsize=2):
    """The least time for one decode step of `rows` sequences that read
    `window_rows` exact rows and `summary_rows` summaries a layer between
    them (a step's means, from the engine's tallies): every layer's
    weights, all prediction heads, the rows' embeddings and those cache
    rows read once at the HBM peak; or the step's operations at the bf16
    peak (a token's products, a score and a value a cache row and head
    dimension), whichever is longer."""
    C, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    cached = layers * (window_rows + summary_rows)
    nbytes = ((layers * layer_params(cfg) + head_params(cfg) + C + rows * C)
              * itemsize + cached * cache_bytes_per_row(cfg, itemsize))
    flops = 2.0 * (rows * (layers * layer_products(cfg) + head_params(cfg))
                   + cached * 2 * C)
    return costs.roofline_seconds(flops, nbytes, peaks)


def walk_floor_seconds(cfg, cached_rows, peaks, itemsize=2):
    """The least time for the decode walks over `cached_rows` cache rows a
    layer (window rows and summaries alike, every layer's): a key and a
    value of every head read once at the HBM peak, or a score and a value
    a row and head dimension at the bf16 peak, whichever is longer."""
    layers, C = cfg["num_hidden_layers"], cfg["hidden_size"]
    return costs.roofline_seconds(
        2.0 * layers * cached_rows * 2 * C,
        layers * cached_rows * cache_bytes_per_row(cfg, itemsize), peaks)


def prefill_flops(cfg, prompt_tokens):
    """The operations of prefilling prompts of `prompt_tokens` (a list:
    the positions committed of each): a token's products in every layer,
    the causal half of each window's own scores and values, the summaries
    of earlier windows a query reads, and the pooling of each window that
    closes (two pooling scores and two weighted sums a row). A chunk's
    padding is work the chip does and no prompt needs: not counted."""
    C, layers, w = (cfg["hidden_size"], cfg["num_hidden_layers"],
                    cfg["window_size"])
    per = summaries_per_window(cfg)
    pairs = closings = 0
    for n in prompt_tokens:
        full, rest = divmod(n, w)
        pairs += full * w * (w + 1) // 2 + rest * (rest + 1) // 2
        # window i's queries each read the i * per summaries before it
        pairs += per * (w * full * (full - 1) // 2 + rest * full)
        closings += full
    return 2.0 * layers * (sum(prompt_tokens) * layer_products(cfg)
                           + pairs * 2 * C + closings * w * 4 * C)
