"""The decode update of a state-space layer: one token for every live
sequence of a Mamba-2 mixer, its state read and written in place.

A sequence's state is H heads of (P, N) float32 (head dim, state size),
kept in a ``(slots, H / k, N, k P)`` device pool that
``generate/paged_kv.py`` holds (a ``"state"`` entry): a row of the pool
holds k heads of one group side by side in its lanes, each head's (P, N)
transposed to (N, P), so that at Nemotron-H's P = 64 a row of two heads
is a (128, 128) float32 tile, whole vregs (``heads_per_row``; ``to_heads``
and ``from_heads`` convert to and from (H, P, N)). For one token of
sequence s, head h of group g = h // (H / G)::

    S_new = exp(dt_h A_h) S + (dt_h x_h) B_g^T        (P, N)
    y_h   = S_new C_g                                  (P,)

``ssm_state_step`` takes the pool and the rows' slot ids and returns y
with the pool, row ``slots[s]`` set to S_new. On a TPU it is ONE Pallas
launch (``name="ssm_state_step"``): a grid step a sequence, its slot id a
scalar-prefetch operand whose block index reads the slot's row of the
pool; the pool is an aliased operand, so the row is written back where it
was read and nothing else of the pool moves (the caller's program donates
the pool: without that XLA copies it first). In a (N, k P) tile the decay
and ``dt x`` are rows (one a lane, a sublane broadcast), B and C columns
(one a sublane, picked out of the group's (N, G) tile once a group), and
y a sum over the sublanes: no lane is reduced.

A row whose ``dt`` is 0 (the caller's pad, or a row fed nothing) keeps
its state: ``exp(0) S + 0``. The ``lax`` path (the gather, the same
arithmetic, the scatter) is every CPU run's and the oracle;
``interpret=True`` runs the launch anywhere.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_state_step", "ssm_state_step_available", "heads_per_row",
           "pool_row_shape", "to_heads", "from_heads"]

LANES = 128


def heads_per_row(heads, head_dim, groups):
    """k, the heads a pool row holds side by side: the most heads of one
    group whose lanes fit in 128 (2 at H 64, P 64, G 8)."""
    per_group = heads // groups
    return max(k for k in range(1, per_group + 1)
               if per_group % k == 0 and k * head_dim <= max(LANES,
                                                             head_dim))


def pool_row_shape(heads, head_dim, groups, state):
    """A sequence's state as the pool holds it: (H / k, N, k P)."""
    k = heads_per_row(heads, head_dim, groups)
    return heads // k, state, k * head_dim


def to_heads(rows, head_dim):
    """(..., H / k, N, k P) -> (..., H, P, N)."""
    *lead, R, N, L = rows.shape
    k = L // head_dim
    rows = rows.reshape(tuple(lead) + (R, N, k, head_dim))
    rows = jnp.moveaxis(rows, -3, -1)                  # (..., R, k, P, N)
    return rows.reshape(tuple(lead) + (R * k, head_dim, N))


def from_heads(states, groups):
    """(..., H, P, N) -> (..., H / k, N, k P)."""
    *lead, H, P, N = states.shape
    R, _N, L = pool_row_shape(H, P, groups, N)
    states = states.reshape(tuple(lead) + (R, L // P, P, N))
    states = jnp.moveaxis(states, -1, -3)              # (..., R, N, k, P)
    return states.reshape(tuple(lead) + (R, N, L))


def ssm_state_step_available(pool):
    """Whether `pool` (slots, H / k, N, k P) takes the launch: the backend
    is a TPU, the state is float32 and a row is whole tiles."""
    return (jax.default_backend() == "tpu" and pool.ndim == 4
            and pool.dtype == jnp.float32 and pool.shape[3] % LANES == 0
            and pool.shape[2] % 8 == 0)


def _lax_step(pool, slots, x, dt, a, b, c):
    heads, P = x.shape[1], x.shape[2]
    groups = b.shape[1]
    per = heads // groups
    state = to_heads(pool[slots].astype(jnp.float32), P)   # (S, H, P, N)
    decay = jnp.exp(dt * a[None])                           # (S, H)
    bh = jnp.repeat(b, per, axis=1)                         # (S, H, N)
    ch = jnp.repeat(c, per, axis=1)
    new = (state * decay[:, :, None, None]
           + (dt[:, :, None] * x)[..., None] * bh[:, :, None, :])
    y = jnp.einsum("shpn,shn->shp", new, ch,
                   precision=jax.lax.Precision.HIGHEST)
    return y, pool.at[slots].set(from_heads(new, groups).astype(pool.dtype))


def _step_kernel(slots_ref, u_ref, decay_ref, b_ref, c_ref, state_ref,
                 y_ref, out_ref, *, rows_per_group):
    """One sequence: u, decay (1, R, L) (dt x, the heads' decays, a lane
    each), b / c (1, N, G) (B and C transposed), its state (1, R, N, L) ->
    y (1, R, L), the new state."""
    del slots_ref       # read by the index maps alone
    groups = b_ref.shape[2]
    b, c = b_ref[0], c_ref[0]                               # (N, G)
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    for g in range(groups):
        bcol = jnp.sum(jnp.where(lane == g, b, 0.0), axis=1, keepdims=True)
        ccol = jnp.sum(jnp.where(lane == g, c, 0.0), axis=1, keepdims=True)
        for r in range(g * rows_per_group, (g + 1) * rows_per_group):
            new = (state_ref[0, r] * decay_ref[0, r:r + 1, :]
                   + bcol * u_ref[0, r:r + 1, :])           # (N, L)
            out_ref[0, r] = new
            y_ref[0, r:r + 1, :] = jnp.sum(new * ccol, axis=0, keepdims=True)


def _kernel_step(pool, slots, x, dt, a, b, c, interpret):
    S, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    R, _N, L = pool.shape[1:]
    u = (dt[:, :, None] * x).reshape(S, R, L)
    decay = jnp.repeat(jnp.exp(dt * a[None]), P, axis=1).reshape(S, R, L)
    row_bytes = R * N * L * 4

    def of_row(shape):
        return pl.BlockSpec((1,) + shape[1:],
                            lambda s, _slots: (s,) + (0,) * (len(shape) - 1))
    state = pl.BlockSpec((1, R, N, L),
                         lambda s, slots_ref: (slots_ref[s], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[of_row((S, R, L)), of_row((S, R, L)), of_row((S, N, G)),
                  of_row((S, N, G)), state],
        out_specs=[of_row((S, R, L)), state],
    )
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, rows_per_group=R // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, R, L), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row in and out, each double-buffered, and the small tiles
            vmem_limit_bytes=max(32 << 20, 4 * row_bytes + (8 << 20))),
        interpret=interpret,
        name="ssm_state_step",
    )(slots, u, decay, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), pool)
    return y.reshape(S, H, P), pool


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def ssm_state_step(pool, slots, x, dt, a, b, c, use_kernel=None,
                   interpret=False):
    """One token of S sequences through a state-space layer's recurrence.

    pool (slots, H / k, N, k P) float32 (``pool_row_shape``), each
    sequence's state a row; slots (S,) int32, the rows' distinct slot ids;
    x (S, H, P), dt (S, H) (after the softplus; 0 leaves a row's state as
    it was), a (H,) (``-exp(A_log)``), b and c (S, G, N), all float32.

    -> (y (S, H, P) float32, ``S_new C`` without the skip term, the pool
    with row ``slots[s]`` = S_new). `use_kernel` None: the launch where
    ``ssm_state_step_available``; `interpret` runs it anywhere. The ``lax``
    path also takes a pool of another float dtype: read in float32,
    written back in its own."""
    f32 = jnp.float32
    slots = jnp.asarray(slots, jnp.int32)
    x, dt, a, b, c = (jnp.asarray(v, f32) for v in (x, dt, a, b, c))
    if use_kernel is None:
        use_kernel = ssm_state_step_available(pool)
    if use_kernel or interpret:
        return _kernel_step(pool, slots, x, dt, a, b, c, interpret)
    return _lax_step(pool, slots, x, dt, a, b, c)
