"""One query a head against a paged cache of per-head keys and values whose
rows hold ALL heads side by side: the decode walk of a cache that keeps
``H x D`` lanes a position (``models/eva_byte.py``: the window group and
the summary group, each walked by one launch a layer).

``paged_heads_decode`` is ``paged_latent.paged_latent_decode``'s pattern
(block tables and lengths as scalar-prefetch operands, the pools left in
HBM, a grid step a sequence that copies ITS live blocks tile by tile into
a double-buffered tile in VMEM, the next tile's copies, or the next
sequence's first, under the products of the tile at hand) over TWO pools,
keys and values. A head's query meets a row's ``D`` lanes of that head
alone, so the step's (H, D) queries enter as a block-diagonal (H, H D)
matrix: one product a tile gives every head's scores (H, T), and one more
``p (H, T) @ values (T, H D)`` gives (H, H D) of which head h's output is
the h-th block of row h (taken outside the launch). The MXU computes the
off-diagonal blocks for nothing: a decode walk is bound by the rows'
bytes, and the products hide under the copies.

It returns the running softmax's state over the rows walked, so that a
caller merges several walks and the step's own row under ONE softmax. The
``lax`` path (``pool[tables]`` gathered, a product a key row) stays for
every CPU run and as the oracle; ``interpret=True`` runs the launch
anywhere.

A looped model (``models/ouro.py``: every layer applied ``T`` times a
token, a cache entry a layer and loop step) keeps a position's ``T`` rows
of a layer side by side, ``T x H D`` lanes; the walk of loop step u is
given u as a third scalar-prefetch operand (``step``) and copies the H D
lanes ``[u H D, (u + 1) H D)`` of each live block and no others. Without
``step`` the launch is the one above, lowered as before.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_latent import LANES, _each_block, _tile_blocks

_NEG_INF = -1e30

#: cached rows a tile of the walk: 256 rows of 4,096 bfloat16 lanes are
#: 2 MB a pool and half, 8 MB of VMEM in all
KEY_TILE = 256

__all__ = ["paged_heads_decode", "paged_heads_decode_available",
           "block_diagonal", "own_blocks"]


def paged_heads_decode_available(pool, heads, steps=1):
    """Whether the walk over `pool` (num_blocks, block_size, steps x H D)
    is the launch: the backend is a TPU, a head is whole lanes, and a
    block is whole tiles of the device (16 rows of a 2-byte dtype, 8 of a
    4-byte one), which a copy into VMEM needs."""
    width = pool.shape[-1] // steps
    return (jax.default_backend() == "tpu" and pool.ndim == 3
            and pool.shape[2] % steps == 0 and width % heads == 0
            and (width // heads) % LANES == 0
            and pool.shape[1] % (32 // pool.dtype.itemsize) == 0)


def block_diagonal(q):
    """q (S, H, D) -> (S, H, H D): head h's query in the lanes of head h,
    zeros elsewhere."""
    S, H, D = q.shape
    eye = jnp.eye(H, dtype=q.dtype)
    return (q[:, :, None, :] * eye[None, :, :, None]).reshape(S, H, H * D)


def own_blocks(acc):
    """acc (S, H, H D) -> (S, H, D): of row h the h-th block."""
    S, H, width = acc.shape
    return jnp.einsum("shhd->shd", acc.reshape(S, H, H, width // H))


def _decode_kernel(tables_ref, lengths_ref, *refs, scale, stepped):
    """One sequence a grid step, as ``paged_latent._decode_kernel``: its
    live blocks of both pools fetched tile by tile into the two halves of
    `kbuf` / `vbuf`; `state` carries from step to step the half the
    step's first tile goes to and whether the step before has already
    asked for it. `stepped`: the first of `refs` is the loop step, and a
    copy takes that step's lanes of a row."""
    first_lane = None
    if stepped:
        step_ref, *refs = refs
    (q_ref, k_pool, v_pool, m_ref, l_ref, acc_ref, kbuf, vbuf, ksems, vsems,
     state) = refs
    s, last = pl.program_id(0), pl.num_programs(0) - 1
    _two, tile_blocks, block_size, width = kbuf.shape
    if stepped:
        first_lane = pl.multiple_of(step_ref[0] * width, LANES)
    T = tile_blocks * block_size
    length = lengths_ref[s]
    tiles = pl.cdiv(length, T)

    def each_block(seq, j, slot, act):
        for pool, buf, sems in ((k_pool, kbuf, ksems), (v_pool, vbuf, vsems)):
            _each_block(tables_ref, lengths_ref, pool, buf, sems, seq, j,
                        slot, act, first_lane)

    def start(seq, j, slot):
        each_block(seq, j, slot, lambda copy: copy.start())

    @pl.when(s == 0)
    def _first():
        # a row never fetched is multiplied by p = 0: it has to be finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
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
        keys = kbuf[slot].reshape(T, width)
        values = vbuf[slot].reshape(T, width)
        scores = jax.lax.dot_general(
            q_ref[0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # (H, T)
        mask = (j * T + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                < length)
        scores = jnp.where(mask, scores, _NEG_INF)
        m = m_ref[0]
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        acc_ref[0] = acc_ref[0] * alpha + jnp.dot(
            p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[0] = m_new
        return 0
    jax.lax.fori_loop(0, tiles, one_tile, 0)
    state[0] = (slot0 + tiles) & 1
    state[1] = hands_on.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("scale", "key_tile",
                                             "interpret"))
def paged_heads_decode(q, k_pool, v_pool, block_tables, lengths, scale,
                       key_tile=KEY_TILE, interpret=False, step=None):
    """One query a head and sequence, q (S, H, D) in the pools' dtype,
    against each sequence's own first ``lengths[s]`` rows of `k_pool` /
    `v_pool` (num_blocks, block_size, H D) behind `block_tables` (S, MB):
    head h's query scores, and its output sums, the lanes of head h.
    `step` (an int32 scalar, traced or not): the pools' rows hold several
    loop steps' rows side by side, (num_blocks, block_size, steps x H D),
    and the walk reads step `step`'s lanes alone.

    -> the running softmax's state over those rows, float32: the row
    maximum m (S, H), the denominator l (S, H) and the unnormalised
    output acc (S, H, D); a sequence with no row keeps ``(-1e30, 0, 0)``.

    One launch: a grid step is a sequence, and it copies its live blocks
    (and no other: a table's padding is never read) into tiles of
    `key_tile` rows in VMEM, two tiles of each pool in flight."""
    S, H, D = q.shape
    block_size = k_pool.shape[1]
    width = H * D
    tile_blocks = _tile_blocks(key_tile, block_size, block_tables.shape[1])
    state = [(S, H, 1), (S, H, 1), (S, H, width)]          # m, l, acc

    def of_sequence(shape):
        return pl.BlockSpec((1,) + shape[1:], lambda s, *_: (s, 0, 0))
    tile = pltpu.VMEM((2, tile_blocks, block_size, width), k_pool.dtype)
    scalars = [jnp.asarray(block_tables, jnp.int32),
               jnp.asarray(lengths, jnp.int32)]
    if step is not None:
        scalars.append(jnp.asarray(step, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S,),
        in_specs=[of_sequence((S, H, width)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[of_sequence(shape) for shape in state],
        scratch_shapes=[tile, tile, pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale,
                          stepped=step is not None),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32)
                   for shape in state],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_heads_decode",
    )(*scalars, block_diagonal(q.astype(k_pool.dtype)), k_pool, v_pool)
    return m[..., 0], l[..., 0], own_blocks(acc)
