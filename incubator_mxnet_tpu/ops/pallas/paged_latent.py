"""Paged attention over a LATENT cache (multi-head latent attention).

A layer caches ONE row a position, shared by all heads: ``[c | k_r | 0]``,
the normalised low-rank latent (``r`` wide), the rotated key part (``d_r``)
and zeros up to a whole number of the device's 128 lanes, in a pool of
``(num_blocks, block_size, W)`` (``generate/paged_kv``). (A TPU holds a pool
whose rows are 576 wide with its BLOCKS minor, and every forward would copy
each pool into row order to gather from it; at 640 the rows are whole tiles,
which a 576-wide row's tiles pad to anyway: ``cache_row_width``.)
Per head the keys and values are the latent's up-projection, ``[k_n,h |
v_h] = c W_kvb,h``, and the score of query ``[q_n,h | q_r,h]`` against
position j is ``scale * (q_n,h . k_n,h(j) + q_r,h . k_r(j))``.

Two paths compute the same numbers, and the chunk width chooses:

- **expanded** (a chunk of C > 1 positions: prefill): every tile of cached
  rows is up-projected to per-head keys and values again, the chunk's own
  rows too, and the chunk's queries attend past + chunk causally. The work
  is the products'; the price of chunking is the re-expansion of the rows
  that earlier chunks cached.
- **absorbed** (C = 1: a decode step): ``W_kvb``'s key half is folded into
  the query, ``q'_h = [q_n,h W_uk,h^T | q_r,h]``, its value half into the
  output, ``o_h = (sum_j p_h(j) c(j)) W_uv,h``, so that a step reads the
  cached rows alone: H query heads against one shared row, keys the whole
  row, values its first ``r``.

Both walk the block table in tiles of ``key_tile`` positions under a running
softmax (no (C, L, H) score array exists), so a cache sized for long
sequences costs a short one nothing. Products take their operands in the
cache's dtype and accumulate in float32; scores, the softmax and its running
statistics are float32.

On a TPU each path's walk is ONE Pallas launch a layer: block tables and
lengths are scalar-prefetch operands, the pool stays in HBM, and the
kernel copies a sequence's own live blocks (no block past its length,
whatever the table names there) into a tile in VMEM, the next tile's
copies in flight under the tile's products. Off the TPU, and as the
numerics oracle, both paths are plain ``lax`` (``_over_past``: a step
gathers a tile of EVERY sequence's blocks into a copy and walks as many
tiles as the longest sequence of the call has; same tiles, same
precisions).

- ``paged_latent_prefill`` (the expanded path whole): a grid step is a
  sequence and a group of heads. A tile's rows are up-projected to each
  head's keys and values IN the kernel (rounded to the cache's dtype, as
  the ``lax`` path rounds them) and folded into the running softmax of
  every query tile of the chunk; the chunk's own rows, not yet in the
  pool, come in as an ordinary operand and follow causally, the key tiles
  that lie wholly after a query tile skipped. Scores, exponents and
  statistics never leave VMEM (the ``lax`` path writes a (H, C, 512)
  float32 score array to HBM and reads it back three times a tile), and
  the result leaves normalised, a head in its own lanes of (S, C, H v).
- ``paged_latent_decode`` (the absorbed path's walk over the past): a grid
  step is a sequence, the next sequence's first tile in flight under the
  last products of the one at hand. Every live row crosses HBM once,
  rounded up to a block; ``rows_walked`` is that count, on the host, for
  the engine's ``last_stats["mla"]``. The launch returns the running state
  of the past; the step's own row (not yet in the pool) and ``W_uv`` stay
  in ``lax``; bfloat16 agrees with the ``lax`` walk to the bit.

``flash_decode.py``'s Mosaic kernel (per-head K/V) is refused by the TPU's
compiler; these two compile and run on a v5e (PERF.md, PRs 36 and 38).
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

#: cached positions a tile of the running softmax
KEY_TILE = 512

#: a cache row is whole lanes wide
LANES = 128

#: bytes a block of a latent cache, and so a copy of the decode kernel's:
#: 128 positions of 640 bfloat16 lanes, what a 16-position block of a
#: per-head cache holds. At 20 KB a copy (16 positions) the kernel's walk
#: is bound by issuing its copies, not by HBM (PERF.md, PR 36)
BLOCK_BYTES = 160 << 10

__all__ = ["paged_latent_attention", "paged_latent_decode",
           "paged_latent_decode_available", "paged_latent_prefill",
           "paged_latent_prefill_available", "latent_path", "cache_row_width",
           "latent_block_size", "rows_walked"]


def cache_row_width(kv_rank, rope_dim):
    """The width of a cache row: ``[c | k_r]`` and zeros up to whole lanes."""
    return -(-(kv_rank + rope_dim) // LANES) * LANES


def latent_block_size(row_bytes, max_len):
    """Positions a block of a latent cache whose row is `row_bytes` wide,
    for sequences of `max_len` positions at most: whole groups of 16 worth
    ``BLOCK_BYTES``, and no more than half of `max_len` (a sequence wastes
    half a block on average: that stays under a quarter of what it may
    hold, and a slot's table keeps two entries), 16 at least."""
    return max(16, min(BLOCK_BYTES // row_bytes, max_len // 2) // 16 * 16)


def latent_path(chunk):
    """Which path a chunk `chunk` positions wide runs."""
    return "absorbed" if chunk == 1 else "expanded"


def _fold(state, scores, mask, values, spec):
    """One tile of keys into a running softmax. `state`: (m, l, acc), the
    row maximum and denominator (S, H, C) and the unnormalised output
    (S, H, C, W), float32; `scores` (S, H, C, T) float32; `mask`
    broadcastable to them; the tile's values enter by ``einsum(spec, p,
    values)``."""
    m, l, acc = state
    scores = jnp.where(mask, scores, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # a row with no live key yet has scores - m_new = 0 everywhere
    p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
    alpha = jnp.exp(m - m_new)
    acc = acc * alpha[..., None] + jnp.einsum(
        spec, p.astype(values.dtype), values,
        preferred_element_type=jnp.float32)
    return m_new, l * alpha + jnp.sum(p, axis=-1), acc


def _tile_blocks(key_tile, block_size, blocks):
    """Blocks a tile of the walk, over tables `blocks` wide."""
    return max(1, min(key_tile // block_size, blocks))


def _over_past(state, fold_rows, pool, block_tables, lengths, key_tile):
    """`fold_rows(state, rows (S, T, W), live (S, 1, 1, T))` over the
    cached rows of every sequence, tile by tile of its block table, up to
    the longest sequence's length."""
    S, blocks = block_tables.shape
    block_size = pool.shape[1]
    tile_blocks = _tile_blocks(key_tile, block_size, blocks)
    T = tile_blocks * block_size
    block_tables = jnp.pad(block_tables,
                           ((0, 0), (0, -blocks % tile_blocks)))

    def one_tile(j, state):
        ids = jax.lax.dynamic_slice_in_dim(block_tables, j * tile_blocks,
                                           tile_blocks, axis=1)
        rows = pool[ids].reshape(S, T, pool.shape[2])
        live = j * T + jnp.arange(T)[None, :] < lengths[:, None]
        return fold_rows(state, rows, live[:, None, None, :])
    return jax.lax.fori_loop(0, (jnp.max(lengths) + T - 1) // T, one_tile,
                             state)


def paged_latent_decode_available(pool=None):
    """Whether the absorbed walk over `pool` is the launch: the backend is
    a TPU, and a block is whole tiles of the device (rows of whole lanes,
    16 positions of a 2-byte dtype, 8 of a 4-byte one), which a copy into
    VMEM needs; any latent cache of ``latent_block_size`` is."""
    return jax.default_backend() == "tpu" and (pool is None or (
        pool.shape[2] % LANES == 0
        and pool.shape[1] % (32 // pool.dtype.itemsize) == 0))


def rows_walked(lengths, block_size, blocks, kernel, key_tile=KEY_TILE):
    """The cache rows a layer's absorbed walk fetches for sequences of
    `lengths` committed positions (host arithmetic): with the kernel each
    sequence's own, rounded up to whole blocks; on the ``lax`` path every
    sequence's tiles up to the longest sequence's last."""
    lengths = [int(n) for n in lengths]
    if kernel:
        return sum(-(-n // block_size) * block_size for n in lengths)
    tile = _tile_blocks(key_tile, block_size, blocks) * block_size
    return len(lengths) * (-(-max(lengths, default=0) // tile) * tile)


def _each_block(tables_ref, lengths_ref, pool_ref, buf, sems, seq, j, slot,
                act):
    """`act` on the copy of every live block of tile j of sequence `seq`
    from the pool in HBM into half `slot` of `buf` (2, tile_blocks,
    block_size, W): no block past the length, whatever the table names."""
    _two, tile_blocks, block_size, _width = buf.shape
    live = pl.cdiv(lengths_ref[seq], block_size) - j * tile_blocks

    def one(b, _):
        act(pltpu.make_async_copy(
            pool_ref.at[tables_ref[seq, j * tile_blocks + b]],
            buf.at[slot, b], sems.at[slot]))
        return 0
    jax.lax.fori_loop(0, jnp.minimum(live, tile_blocks), one, 0)


def _decode_kernel(tables_ref, lengths_ref, q_ref, pool_ref, m_ref, l_ref,
                   acc_ref, buf, sems, state, *, scale, rank):
    """One sequence a grid step: its live blocks fetched tile by tile into
    the two halves of `buf`, the next tile's (this sequence's, or the next
    sequence's first) under the products of the one at hand. `state`
    carries from step to step the half the step's first tile goes to and
    whether the step before has already asked for it."""
    s, last = pl.program_id(0), pl.num_programs(0) - 1
    _two, tile_blocks, block_size, width = buf.shape
    T = tile_blocks * block_size
    length = lengths_ref[s]
    tiles = pl.cdiv(length, T)

    def each_block(seq, j, slot, act):
        _each_block(tables_ref, lengths_ref, pool_ref, buf, sems, seq, j,
                    slot, act)

    def start(seq, j, slot):
        each_block(seq, j, slot, lambda copy: copy.start())

    @pl.when(s == 0)
    def _first():
        # a row never fetched is multiplied by p = 0: it has to be finite
        buf[...] = jnp.zeros_like(buf)
        state[0] = 0
        state[1] = 0
    slot0 = state[0]

    @pl.when((tiles > 0) & (state[1] == 0))
    def _own():
        start(s, 0, slot0)
    m_ref[0] = jnp.full(m_ref.shape[1:], _NEG_INF, jnp.float32)
    l_ref[0] = jnp.zeros(l_ref.shape[1:], jnp.float32)
    acc_ref[0] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
    after = jnp.minimum(s + 1, last)
    hands_on = (tiles > 0) & (s < last) & (lengths_ref[after] > 0)

    def one_tile(j, _):
        slot = (slot0 + j) & 1

        @pl.when(j + 1 < tiles)
        def _next():
            start(s, j + 1, 1 - slot)

        @pl.when((j + 1 == tiles) & hands_on)
        def _next_sequence():
            start(after, 0, 1 - slot)
        each_block(s, j, slot, lambda copy: copy.wait())
        rows = buf[slot].reshape(T, width)
        scores = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # (H, T)
        mask = (j * T + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                < length)
        scores = jnp.where(mask, scores, _NEG_INF)
        m = m_ref[0]
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        acc_ref[0] = acc_ref[0] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[0] = m_new
        return 0
    jax.lax.fori_loop(0, tiles, one_tile, 0)
    state[0] = (slot0 + tiles) & 1
    state[1] = hands_on.astype(jnp.int32)


def paged_latent_decode(query, pool, block_tables, lengths, scale, rank,
                        key_tile=KEY_TILE, interpret=False):
    """The absorbed path's walk over the past, one launch: query (S, H, W)
    in the pool's dtype against each sequence's own live rows, scores over
    the whole row, values its first `rank` columns.

    -> the running softmax's state over the past, float32: the row maximum
    m (S, H, 1), the denominator l (S, H, 1) and the unnormalised output
    acc (S, H, rank); a sequence with no past keeps ``(-1e30, 0, 0)``.

    `block_tables` and `lengths` are scalar-prefetch operands, the pool
    stays in HBM: a grid step is a sequence, and it copies its live blocks
    (and no other: a table's padding is never read) into a tile of
    `key_tile` positions in VMEM, two tiles in flight."""
    S, H, width = query.shape
    block_size = pool.shape[1]
    tile_blocks = _tile_blocks(key_tile, block_size, block_tables.shape[1])
    state = [(S, H, 1), (S, H, 1), (S, H, rank)]           # m, l, acc

    def of_sequence(shape):
        return pl.BlockSpec((1,) + shape[1:],
                            lambda s, tables, lengths: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[of_sequence(query.shape),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[of_sequence(shape) for shape in state],
        scratch_shapes=[
            pltpu.VMEM((2, tile_blocks, block_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, rank=rank),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32)
                   for shape in state],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_decode",
    )(block_tables, lengths, query, pool)


def paged_latent_prefill_available(pool, chunk, rank, nope_dim, v_dim):
    """Whether the expanded path of a chunk `chunk` positions wide over
    `pool` is the launch: the backend is a TPU, a block is whole tiles of
    the device (``paged_latent_decode_available``), the chunk is whole
    sublane tiles of the pool's dtype, and the latent, a head's keys and
    its values are whole lanes wide (the kernel slices rows and weights
    there, and writes a head's output into its own lanes)."""
    return (paged_latent_decode_available(pool)
            and chunk % (32 // pool.dtype.itemsize) == 0
            and rank % LANES == 0 and nope_dim % LANES == 0
            and v_dim % LANES == 0)


def _head_group(heads, chunk):
    """Heads a grid step of the prefill launch: the most that divide
    `heads` and keep the group's running state (a float32 (chunk, v) and
    two lane-padded statistics a head) at 4,096 query rows."""
    return max(g for g in range(1, heads + 1)
               if heads % g == 0 and (g == 1 or g * chunk <= 4096))


def _prefill_kernel(tables_ref, lengths_ref, q_ref, new_ref, w_ref, pool_ref,
                    out_ref, buf, sems, m_ref, l_ref, acc_ref, *, scale,
                    rank, nope_dim, rope_dim, key_tile):
    """A sequence and a group of heads a grid step: the chunk's queries of
    those heads against the sequence's live cached rows, fetched tile by
    tile into the two halves of `buf` (the next tile's copies under the
    products of the one at hand), then against the chunk's own rows,
    causally. A tile's rows are up-projected to a head's keys and values
    here, once a head, and every query tile of the chunk folds them into
    the head's running softmax: scores, exponents and statistics never
    leave VMEM."""
    s = pl.program_id(0)
    group, chunk = q_ref.shape[1], q_ref.shape[2]
    _two, tile_blocks, block_size, _width = buf.shape
    T = tile_blocks * block_size
    v_dim = acc_ref.shape[-1]
    dtype = q_ref.dtype
    length = lengths_ref[s]
    tiles = pl.cdiv(length, T)

    def tiles_of(rows):
        return [(lo, min(rows, chunk - lo)) for lo in range(0, chunk, rows)]
    # the chunk's own keys, and the queries they meet, in tiles of
    # key_tile; against a tile of the past, query tiles of twice that (on
    # a v5e 1,024 rows a tile beat 512 by 7 %: PERF.md, PR 38)
    spans, past_spans = tiles_of(key_tile), tiles_of(2 * key_tile)

    def each_block(j, slot, act):
        _each_block(tables_ref, lengths_ref, pool_ref, buf, sems, s, j, slot,
                    act)

    @pl.when((s == 0) & (pl.program_id(1) == 0))
    def _first():
        # a row never fetched is multiplied by p = 0: it has to be finite
        buf[...] = jnp.zeros_like(buf)

    @pl.when(tiles > 0)
    def _own():
        each_block(0, 0, lambda copy: copy.start())
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(h, latent, k_rope, folds):
        """A tile of rows, `latent` (n, r) and `k_rope` (n, d_r), into head
        h's state: for each of `folds` (first query row, query rows, what
        masks the (rows, n) scores)."""
        kv = jnp.dot(latent, w_ref[h],
                     preferred_element_type=jnp.float32).astype(dtype)
        keys = jnp.concatenate([kv[:, :nope_dim], k_rope], axis=1)
        values = kv[:, nope_dim:]
        for lo, n, mask in folds:
            rows = pl.ds(lo, n)
            scores = mask(jax.lax.dot_general(
                q_ref[0, h, rows, :], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale)
            m = m_ref[h, rows]
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            # a masked score is -1e30 under a live one: its p is 0
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            acc_ref[h, rows] = acc_ref[h, rows] * alpha + jnp.dot(
                p.astype(dtype), values, preferred_element_type=jnp.float32)
            l_ref[h, rows] = l_ref[h, rows] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            m_ref[h, rows] = m_new

    def each_head(latent, k_rope, folds):
        def one(h, _):
            fold(h, latent(), k_rope(), folds)
            return 0
        jax.lax.fori_loop(0, group, one, 0)

    def one_tile(j, _):
        slot = j & 1

        @pl.when(j + 1 < tiles)
        def _next():
            each_block(j + 1, 1 - slot, lambda copy: copy.start())
        each_block(j, slot, lambda copy: copy.wait())
        # every tile walked holds a live key: no query row is left empty
        dead = jnp.where(
            j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) < length,
            0.0, _NEG_INF)
        each_head(
            lambda: buf[slot, :, :, :rank].reshape(T, rank),
            lambda: buf[slot, :, :, rank:rank + rope_dim].reshape(
                T, rope_dim),
            [(lo, n, lambda scores: scores + dead) for lo, n in past_spans])
        return 0
    jax.lax.fori_loop(0, tiles, one_tile, 0)

    def causal(scores):
        return jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            >= jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1),
            scores, _NEG_INF)
    # the chunk itself: a key tile meets its own query tile under the
    # diagonal, the later ones whole, the earlier ones not at all
    for lo, n in spans:
        each_head(
            lambda lo=lo, n=n: new_ref[0, pl.ds(lo, n), :rank],
            lambda lo=lo, n=n: new_ref[0, pl.ds(lo, n),
                                       rank:rank + rope_dim],
            [(q_lo, q_n, causal if q_lo == lo else lambda scores: scores)
             for q_lo, q_n in spans if q_lo >= lo])
    for h in range(group):
        out_ref[0, :, h * v_dim:(h + 1) * v_dim] = (
            acc_ref[h] / l_ref[h]).astype(out_ref.dtype)


# jitted, so that a program traces and lowers the kernel once, not once a layer
@functools.partial(jax.jit, static_argnames=("scale", "key_tile", "interpret"))
def paged_latent_prefill(q_nope, q_rope, new_rows, kv_b, pool, block_tables,
                         lengths, scale, key_tile=KEY_TILE, interpret=False):
    """The expanded path, one launch: the arguments and the result of
    ``paged_latent_attention`` for a chunk wider than one position.

    `block_tables` and `lengths` are scalar-prefetch operands, the pool
    stays in HBM: a grid step is a sequence and a group of heads
    (``_head_group``), and it copies the sequence's live blocks (and no
    other) into a tile of `key_tile` positions in VMEM, two tiles in
    flight, up-projects a tile's rows to each head's keys and values
    there (rounded to the cache's dtype) and folds them into the running
    softmax of every query tile of the chunk; the chunk's own rows, an
    ordinary operand, follow causally, the key tiles that lie wholly
    after a query tile skipped. The result is normalised in the kernel
    and written a head to its own lanes of (S, C, H v)."""
    S, C, H, d_n = q_nope.shape
    r, d_r, d_v = kv_b.shape[0], q_rope.shape[-1], kv_b.shape[-1] - d_n
    width = pool.shape[2]
    dtype = q_nope.dtype
    block_size = pool.shape[1]
    tile_blocks = _tile_blocks(key_tile, block_size, block_tables.shape[1])
    T = tile_blocks * block_size
    group = _head_group(H, C)
    # a head's queries [q_n | q_r] and its slice of W_kvb, heads major
    query = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    weights = kv_b.transpose(1, 0, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, H // group),
        in_specs=[
            pl.BlockSpec((1, group, C, d_n + d_r),
                         lambda s, g, tables, lengths: (s, g, 0, 0)),
            pl.BlockSpec((1, C, width),
                         lambda s, g, tables, lengths: (s, 0, 0)),
            pl.BlockSpec((group, r, d_n + d_v),
                         lambda s, g, tables, lengths: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, C, group * d_v),
                               lambda s, g, tables, lengths: (s, 0, g)),
        scratch_shapes=[
            pltpu.VMEM((2, tile_blocks, block_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((group, C, 1), jnp.float32),             # m
            pltpu.VMEM((group, C, 1), jnp.float32),             # l
            pltpu.VMEM((group, C, d_v), jnp.float32),           # acc
        ],
    )
    # what a grid step holds, minor dimensions in whole lanes: the blocks
    # the pipeline keeps twice and the tile buffer; m, l and acc; one
    # head's keys and values of a tile and its scores three times over
    item, lanes = dtype.itemsize, lambda n: -(-n // LANES) * LANES
    held = (2 * item * (group * C * lanes(d_n + d_r) + C * width
                        + group * r * (d_n + d_v) + C * group * d_v
                        + T * width)
            + 4 * group * C * (2 * LANES + d_v)
            + T * (d_n + d_v) * (4 + item)
            + 3 * 4 * min(2 * key_tile, C) * T)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, rank=r,
                          nope_dim=d_n, rope_dim=d_r, key_tile=key_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, H * d_v), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(100 << 20, max(32 << 20, 2 * held))),
        interpret=interpret,
        name="paged_latent_prefill",
    )(block_tables, lengths, query, new_rows, weights, pool)
    return out.reshape(S, C, H, d_v)


def paged_latent_attention(q_nope, q_rope, new_rows, kv_b, pool,
                           block_tables, lengths, scale, key_tile=KEY_TILE,
                           interpret=False):
    """Attention of a chunk over its sequence's cached rows and itself.

    q_nope (S, C, H, d_n), q_rope (S, C, H, d_r) rotated: the chunk's
    queries; new_rows (S, C, W): the chunk's own rows ``[c | k_r | 0]``,
    NOT yet in the pool; kv_b (r, H, d_n + d_v): ``W_kvb``, a head's
    columns its ``[k_n | v]``; pool (num_blocks, block_size, W);
    block_tables (S, MB) int32 (pad with any valid block id); lengths (S,)
    int32 committed past positions. Position c of the chunk attends every
    past position and the chunk's positions <= c.

    -> (S, C, H, d_v), in q_nope's dtype. C = 1 runs the absorbed path,
    any other width the expanded one (``latent_path``). Where the backend
    is a TPU and the shapes allow (``paged_latent_*_available``) the
    absorbed path's walk over the past is the launch
    ``paged_latent_decode`` and the expanded path the launch
    ``paged_latent_prefill`` (`interpret` runs the launches anywhere)."""
    S, C, H, d_n = q_nope.shape
    r, d_r = kv_b.shape[0], q_rope.shape[-1]
    dtype = q_nope.dtype
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    w_k, w_v = kv_b[..., :d_n], kv_b[..., d_n:]

    if latent_path(C) == "absorbed":
        # the key half of W_kvb folded into the query, as wide as a row:
        # (S, C, H, W), zeros against a row's zeros
        query = jnp.concatenate(
            [jnp.einsum("schn,rhn->schr", q_nope, w_k,
                        preferred_element_type=jnp.float32).astype(dtype),
             q_rope, jnp.zeros((S, C, H, pool.shape[2] - r - d_r), dtype)],
            axis=-1)
        width = r

        def fold_rows(state, rows, mask):
            scores = jnp.einsum("schw,stw->shct", query, rows,
                                preferred_element_type=jnp.float32) * scale
            return _fold(state, scores, mask, rows[..., :r],
                         "shct,str->shcr")
    else:
        width = w_v.shape[-1]
        if interpret or paged_latent_prefill_available(pool, C, r, d_n,
                                                       width):
            return paged_latent_prefill(
                q_nope, q_rope, new_rows, kv_b, pool, block_tables, lengths,
                scale, key_tile, interpret=interpret)

        def fold_rows(state, rows, mask):
            latent, k_rope = rows[..., :r], rows[..., r:r + d_r]
            k_nope = jnp.einsum("str,rhn->sthn", latent, w_k,
                                preferred_element_type=jnp.float32)
            values = jnp.einsum("str,rhv->sthv", latent, w_v,
                                preferred_element_type=jnp.float32)
            scores = (jnp.einsum("schn,sthn->shct", q_nope,
                                 k_nope.astype(dtype),
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("schd,std->shct", q_rope, k_rope,
                                   preferred_element_type=jnp.float32))
            return _fold(state, scores * scale, mask, values.astype(dtype),
                         "shct,sthv->shcv")

    if latent_path(C) == "absorbed" and (
            interpret or paged_latent_decode_available(pool)):
        m, l, acc = paged_latent_decode(
            query[:, 0], pool, block_tables, lengths, scale, r, key_tile,
            interpret)
        state = (m, l, acc[:, :, None])
    else:
        state = (jnp.full((S, H, C), _NEG_INF, jnp.float32),
                 jnp.zeros((S, H, C), jnp.float32),
                 jnp.zeros((S, H, C, width), jnp.float32))
        state = _over_past(state, fold_rows, pool, block_tables, lengths,
                           key_tile)
    # the chunk itself, causal; its diagonal gives every row a live key
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    for lo in range(0, C, key_tile):
        state = fold_rows(state, new_rows[:, lo:lo + key_tile],
                          causal[None, None, :, lo:lo + key_tile])
    _m, l, acc = state
    out = acc / l[..., None]                                # (S, H, C, W)
    if latent_path(C) == "absorbed":    # the value half of W_kvb, last
        return jnp.einsum("shcr,rhv->schv", out.astype(dtype), w_v,
                          preferred_element_type=jnp.float32).astype(dtype)
    return out.transpose(0, 2, 1, 3).astype(dtype)
