"""Grouped matrix product — one launch over the experts a batch really hit.

``grouped_matmul(x, w, group_sizes)`` has ``jax.lax.ragged_dot``'s
contract (and is that function off the TPU): the rows of ``x (M, K)`` are
sorted by group, ``group_sizes (G,)`` says how many consecutive rows belong
to each group, and rows of group ``g`` are multiplied by ``w[g] (K, N)``. It is the expert product of
a dropless mixture-of-experts layer (``parallel/moe.py``): its cost follows
the rows and the groups that have any, never ``M x G``.

How. Every group is padded to whole tiles of ``tile_rows`` rows, so a tile
belongs to exactly one group. The grid walks the tiles in order; the
tile -> group table is a scalar-prefetch operand, so the weight block's
index_map fetches ``w[group of this tile]`` and the pipeline skips the
fetch when the next tile has the same group. A group with no rows has no
tile and its weights never leave HBM. The number of tiles is static (the
worst case, every group ending in a nearly empty tile); the tiles past the
last used one point at the last used group (no fetch) and write zeros.

A layer that holds a SHARE of its experts (``parallel/moe.py``
``moe_dropless(held=...)``) lays a pass's routes out in tiles itself, once
for its three products, and calls ``grouped_matmul_tiles`` on that layout
(12 groups of 7,168 x 2,048, a few rows each, in the benchmark's
``kimi_k2_7_code`` cell: 87 % of the HBM roofline of the experts hit,
PERF.md, PR 35).

Decode-sized batches (a few rows an expert, 3 MB of weights a group) are
bound by the weight reads; the short tiles keep the MXU work under them. A
batch that fills its groups (a prefill chunk of 2,048 tokens x 4 routes over
64 experts: 128 rows a group) takes tiles of 128 rows (``tile_rows_for``).
Measured on a v5e at 512 routes over 128 experts of 2048 x 768 bfloat16
(PERF.md, PR 29): 0.62 ms a product, 77 % of the HBM roofline of the experts
hit, where the launch XLA makes of ``ragged_dot`` takes 1.56-1.82 ms.
"""

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "tile_plan", "grouped_matmul_tiles",
           "grouped_matmul_available"]

#: rows a tile; 16 is one packed bfloat16 sublane group
TILE_ROWS = 16
#: rows a tile where the groups are full: a prefill chunk's routes
FULL_TILE_ROWS = 128


def tile_rows_for(rows, groups):
    """Rows a tile for `rows` rows over `groups` groups: short tiles where
    a group gets a few rows (a decode step: the weight reads bound it and
    the padding of 128-row tiles would be most of the work), tiles as tall
    as the MXU where the mean group would fill half of one (a prefill
    chunk: a 16-row tile leaves the MXU loading weights seven cycles of
    eight)."""
    return (FULL_TILE_ROWS if 2 * rows >= groups * FULL_TILE_ROWS
            else TILE_ROWS)


def grouped_matmul_available():
    return jax.default_backend() == "tpu"


def tile_plan(group_sizes, rows, tile_rows=TILE_ROWS):
    """Where sorted row ``r`` sits once every group is padded to whole
    tiles. -> ``(dest (rows,), tile_group (T,), used (1,))``: the padded
    position of each row, the group of each tile (tiles past the used ones
    repeat the last used group) and the number of tiles used. ``T`` is the
    static worst case, ``(rows + G * (tile_rows - 1)) // tile_rows``."""
    groups = group_sizes.shape[0]
    tiles = (rows + groups * (tile_rows - 1)) // tile_rows
    sizes = group_sizes.astype(jnp.int32)
    per_group = (sizes + tile_rows - 1) // tile_rows
    tile_end = jnp.cumsum(per_group)                  # inclusive, by group
    used = tile_end[-1]
    row_end = jnp.cumsum(sizes)
    group_of_row = jnp.searchsorted(row_end, jnp.arange(rows, dtype=jnp.int32),
                                    side="right").astype(jnp.int32)
    rank = jnp.arange(rows, dtype=jnp.int32) - (row_end - sizes)[group_of_row]
    dest = (tile_end - per_group)[group_of_row] * tile_rows + rank
    tile_group = jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                              jnp.maximum(used - 1, 0)),
        side="right").astype(jnp.int32)
    return dest, jnp.minimum(tile_group, groups - 1), used.reshape(1)


def _kernel(tile_group_ref, used_ref, x_ref, w_ref, o_ref):
    del tile_group_ref      # read by the index_maps
    live = pl.program_id(1) < used_ref[0]

    @pl.when(live)
    def _product():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _zeros():
        o_ref[...] = jnp.zeros_like(o_ref)


def grouped_matmul_tiles(x_tiles, w, tile_group, used, interpret=False):
    """The launch itself, on rows already laid out in tiles.
    x_tiles (T * tile_rows, K); w (G, K, N) -> (T * tile_rows, N) float32."""
    rows, k = x_tiles.shape
    tile_rows = rows // tile_group.shape[0]
    n = w.shape[2]
    # one weight block is (K, block_n): whole when it is 4 MB at most, so
    # that two of them (the pipeline's) stay far under the scoped VMEM
    block_n = n
    while k * block_n * w.dtype.itemsize > (4 << 20) and block_n % 256 == 0:
        block_n //= 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block_n, rows // tile_rows),
        in_specs=[
            pl.BlockSpec((tile_rows, k), lambda j, t, tg, used: (t, 0)),
            pl.BlockSpec((1, k, block_n),
                         lambda j, t, tg, used: (tg[t], 0, j)),
        ],
        out_specs=pl.BlockSpec((tile_rows, block_n),
                               lambda j, t, tg, used: (t, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_group, used, x_tiles, w)


def grouped_matmul(x, w, group_sizes, use_kernel=None, interpret=False):
    """x (M, K) sorted by group; w (G, K, N); group_sizes (G,) summing to
    M. -> (M, N) float32: ``jax.lax.ragged_dot``'s contract, as one
    Pallas launch on a TPU (`use_kernel` None: where the backend is one;
    `interpret` runs the launch anywhere) and as ``ragged_dot`` itself
    elsewhere, the numerics oracle and the CPU path."""
    if use_kernel is None:
        use_kernel = grouped_matmul_available()
    if not (use_kernel or interpret):
        return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                                  preferred_element_type=jnp.float32)
    rows = x.shape[0]
    tile_rows = tile_rows_for(rows, w.shape[0])
    dest, tile_group, used = tile_plan(group_sizes, rows, tile_rows)
    # padding rows read row 0: what they produce is never gathered back
    src = jnp.zeros((tile_group.shape[0] * tile_rows,), jnp.int32
                    ).at[dest].set(jnp.arange(rows, dtype=jnp.int32))
    return grouped_matmul_tiles(x[src], w, tile_group, used, interpret)[dest]
