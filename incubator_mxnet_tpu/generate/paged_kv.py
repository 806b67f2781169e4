"""Paged KV cache — block-granular allocation behind the KVCache surface.

PagedAttention (Kwon et al., SOSP '23): instead of reserving a dense
``(slots, max_len)`` strip per slot, kv entries live in a shared pool of
``num_blocks`` fixed-size blocks and each slot holds a *block table* —
the ordered list of pool blocks its sequence occupies. A slot consumes
``ceil(length / block_size)`` blocks, so short sequences in a grid sized
for long ones stop wasting ``max_len - length`` rows, and the freed
blocks are immediately reusable by other slots.

The public surface is a strict superset of ``serving.kv_cache.KVCache``
(alloc/free/append/advance/prefix/set_state/state, same error messages),
so the continuous-batching ``DecodeLoop`` runs unchanged on top.

**Where the data lives.** The book-keeping (lengths, block tables, the
free list, the gauges) is host state. The pool of every kv entry is a
DEVICE array in the entry's dtype: a forward reads it where it runs, so a
jitted call ships the block tables and lengths, never a pool, and what
the forward produced is stored by ``commit``: one donated program over
all pools that scatters the new rows and hands the pools back in place.
``append`` / ``prefix`` are the ``KVCache`` contract's slow surface, for
tests and third-party ``step_fn``s: a launch a row, a gathered copy
fetched to the host. ``cache.data[name]`` of a kv entry is that device
array (immutable: write through ``commit`` or ``append``).

The paged extras feed the flash-decode kernel:

- ``pool(name)`` — the ``(num_blocks, block_size) + per_step_shape``
  backing device array of a kv entry,
- ``layer_spec(num_layers, entries)`` — the spec of a decoder's cache:
  every layer holds the named `entries` (per-head keys and values ``k``
  and ``v``; one latent row ``c`` shared by all heads), ``<entry><i>``
  for layer i; ``layers=`` puts an entry on some layers alone (a hybrid
  model's keys and values on its attention layers), ``states=`` adds
  state entries likewise,
- ``forward_inputs(slots, fed)`` — what a forward over `slots` reads of
  the cache: lengths, block tables, then every layer's pool entry by
  entry (K pools, V pools); a cache with state entries adds the rows'
  slot ids, `fed` (the real positions of each row's chunk) and the state
  pools entry by entry,
- ``commit(slots, *new, count)`` — store what a forward produced, entry
  by entry in the same order (``commit(slots, new_k, new_v, count)``;
  then the state pools the forward wrote, which are installed as they
  are; ``lower_commit``: the kv program lowered, for an owner that ships
  executables; ``sync()`` waits for the commits launched so far),
- ``tables_array(slots)`` — an ``(S, max_blocks_per_slot)`` int32 block
  table, padded with block 0 (padded fetches are masked by ``lengths``
  so any valid pool row is safe),
- ``truncate(slot, new_len)`` — roll a sequence back (speculative
  decode rejects draft tokens by truncating the drafted suffix; refused
  where there are state entries: a recurrence has no rows to give back),
- ``fragmentation()`` — unused fraction of mapped block capacity.

**State entries.** A ``"state"`` entry is one value a slot that a model
REPLACES as it goes (a state-space layer's recurrent state, its conv
window), not rows appended: a dense ``(slots,) + shape`` DEVICE pool in
the entry's dtype, paging buys nothing. A slot's rows are zeroed when it
is allocated (one donated launch over every state pool, before the next
forward reads them: ``state_resets`` counts the slots), written in place
by the forward that reads them (the forward's program is given the pools
donated and hands them back), and installed by ``commit``; they never
cross from the host. ``set_state`` / ``state`` are the ``KVCache``
contract's slow surface: a launch, a fetched copy.

The kv entries of a spec that names no groups share one block table per
slot: they advance in lockstep (the KVCache contract), so their block
layouts are identical by construction.

**Groups.** A spec may put its kv entries into named GROUPS
(``layer_spec(..., groups=...)``, the geometry of each under
``PagedKVCache(groups=...)``): the entries of a group advance in lockstep
behind a length, a block table, a block size and a free list of the
group's own, and the groups advance at different rates. The FIRST group
is the leading one: a forward's additions are committed there
(``commit(slots, *new, count)``) and each row stored is a position of the
sequence (``cache.lengths``, held to ``max_len``); any other group gains
rows when its owner says so (``commit(..., group=name)``). ``restart(slot,
group)`` sets a group's length back to 0 and keeps its blocks, which the
next rows then reuse in place: a window of exact keys and values (rows
``t % w``) beside a growing list of summaries is two groups, the window
restarted at each closing. ``forward_inputs`` hands a forward each
group's lengths and tables, then the pools group by group; ``free``
returns every group's blocks; ``truncate`` rolls back inside the leading
group's live rows and raises across a restart.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import catalog as _cat
from ..telemetry import flight as _flight
from ..telemetry import memz as _memz
from ..telemetry import metrics as _metrics

__all__ = ["PagedKVCache", "KVPoolExhausted"]


class KVPoolExhausted(ValueError):
    """An append found no free block in the paged pool.

    Typed (rather than the bare ValueError it subclasses for backward
    compatibility) so shed-on-pressure is distinguishable from a bug:
    the serving loop catches this to shed the session as a capacity
    event, anything else stays an error.  Carries the pool geometry the
    handler needs to report without re-deriving it."""

    def __init__(self, message, name=None, slot=None, block=None,
                 num_blocks=None, block_size=None):
        super().__init__(message)
        self.name = name
        self.slot = slot
        self.block = block
        self.num_blocks = num_blocks
        self.block_size = block_size

_KINDS = ("state", "kv")

#: default block size (positions per block); MXTPU_GEN_BLOCK_SIZE
DEFAULT_BLOCK_SIZE = 16


def _env_int(name, default):
    import os
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


# a fresh device buffer a call, one trace a (shape, dtype): `jnp.zeros`
# itself costs the host 1.5 ms a pool, and a cache has 2 a layer
_device_zeros = jax.jit(jnp.zeros, static_argnums=(0, 1))


def device_order(pool):
    """A pool's dims major to minor as its device holds them. A TPU keeps
    a (blocks, 16, 25, 64) float32 pool as (blocks, 25, 16, 64): its tiles
    cover the two minor dims, and 25 heads would pad to 32."""
    return tuple(pool.format.layout.major_to_minor)


def _cells_at(shape, rows, order):
    """For the positions `rows` (n,), flat over (block, offset), of a
    `shape` pool: the rows of its (size, shape[minor]) cells, the pool as
    the device holds it (`order`) with all but its minor dim flattened,
    that hold them, position by position; `size`, past the cells, for a
    position past the pool."""
    minor = order[-1]
    stride, size = {}, 1
    for d in reversed(order[:-1]):
        stride[d], size = size, size * shape[d]
    at = (rows // shape[1]) * stride[0] + (rows % shape[1]) * stride[1]
    for d in range(2, len(shape)):
        if d != minor:
            at = at[..., None] + jnp.arange(shape[d],
                                            dtype=jnp.int32) * stride[d]
    stored = rows.reshape((-1,) + (1,) * (at.ndim - 1)) < shape[0] * shape[1]
    return jnp.where(stored, at, size).reshape(-1)


def _put(pool, rows, new, order, memo):
    """`pool` with its positions `rows` (n,), flat over (block, offset),
    set to `new` (n, ...); a position past the pool is dropped. The
    scatter runs over the pool as the device holds it (`order`), row by
    row of its minor dim: a scatter over the logical (block, offset) rows
    would copy a pool whose device order differs into that order and
    back, in every commit. `memo`: the cell rows of `rows`, shared by the
    pools of one shape and order (every layer's, as a rule)."""
    shape, minor = pool.shape, order[-1]
    if minor < 2:       # one number a position: give it a minor dim
        return _put(pool[..., None], rows, new[..., None],
                    order + (pool.ndim,), memo)[..., 0]
    if (shape, order) not in memo:
        memo[shape, order] = _cells_at(shape, rows, order)
    cells = pool.transpose(order).reshape(-1, shape[minor])
    new = jnp.moveaxis(new.reshape((-1,) + shape[2:]), minor - 1, -1)
    cells = cells.at[memo[shape, order]].set(
        new.reshape(-1, shape[minor]).astype(pool.dtype), mode="drop")
    return cells.reshape([shape[d] for d in order]).transpose(
        np.argsort(order))


@functools.lru_cache(maxsize=None)
def store_program_for(entries):
    """The commit program of a cache whose layers hold `entries` entries:
    ``(*pools, *new, rows, orders)``, the pools entry by entry, each
    every layer's, then what a forward produced in the same order.

    Every pool comes back with ``new[e][i]`` (S, C, ...) scattered to the
    flat pool positions `rows` (S, C). The pools are donated: the scatter
    is in place. `orders`: the `device_order` of each pool, entry by
    entry. One compiled program a (S, C)."""
    def store_program(*args):
        pools, new = args[:entries], args[entries:2 * entries]
        rows, orders = args[2 * entries:]
        rows, memo, orders = rows.reshape(-1), {}, iter(orders)
        return tuple([_put(p, rows, of_entry[i], next(orders), memo)
                      for i, p in enumerate(entry_pools)]
                     for entry_pools, of_entry in zip(pools, new))
    return jax.jit(store_program, donate_argnums=tuple(range(entries)),
                   static_argnums=2 * entries + 1)


#: per-head keys and values: ``(k_pools, v_pools, new_k, new_v, rows,
#: orders)``, the `device_order` of each k pool, then of each v pool
store_program = store_program_for(2)


@functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
def _store_one(pool, row, value, order):
    return _put(pool, row.reshape(1), value, order, {})


def _by_layer(names):
    """Entry names ``<entry><layer>`` grouped by entry in the order they
    come, each in layer order: ``(["k0", "k1"], ["v0", "v1"])``. None
    unless every entry has the same layers (every layer, or the subset
    ``layer_spec(layers=)`` gave them)."""
    layers = {}
    for name in names:
        m = re.fullmatch(r"(.*?)(\d+)", name)
        if m is None:
            return None
        layers.setdefault(m.group(1), set()).add(int(m.group(2)))
    found = next(iter(layers.values()), None)
    if found is None or any(got != found for got in layers.values()):
        return None
    return tuple(["%s%d" % (entry, i) for i in sorted(found)]
                 for entry in layers)


@functools.partial(jax.jit, donate_argnums=0)
def _zero_rows(pools, rows):
    """Every state pool with its rows `rows` (a slot past the pools:
    none) set to 0, in place."""
    return tuple(p.at[rows].set(0, mode="drop") for p in pools)


@functools.partial(jax.jit, donate_argnums=0)
def _set_row(pool, slot, value):
    return pool.at[slot].set(value.astype(pool.dtype))


class _Rows:
    """The book-keeping of kv entries that advance in lockstep: the rows a
    slot holds of them (``lengths``), the pool blocks those lie in
    (``tables``), and the free blocks of their pools. A cache whose spec
    names no groups has one; a grouped one has one a group."""

    def __init__(self, slots, max_len, block_size, num_blocks=None):
        if block_size < 1:
            raise ValueError("block_size must be >= 1, got %r" % block_size)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        self.max_blocks_per_slot = max(
            1, math.ceil(self.max_len / self.block_size))
        self.num_blocks = int(num_blocks or slots * self.max_blocks_per_slot)
        self.lengths = np.zeros(slots, np.int64)
        self.tables = {}            # slot -> [block ids]
        self.free_blocks = list(range(self.num_blocks - 1, -1, -1))
        self.entries = []           # the kv entries' names, in spec order
        self.layer_names = None     # `_by_layer(entries)`

    @property
    def blocks_in_use(self):
        return self.num_blocks - len(self.free_blocks)


class PagedKVCache:
    """Drop-in paged replacement for ``serving.kv_cache.KVCache``.

    Not thread-safe by itself: the decode loop is the single owner.

    ``block_size`` defaults to ``MXTPU_GEN_BLOCK_SIZE`` (16); ``num_blocks``
    defaults to ``slots * ceil(max_len / block_size)`` — full capacity
    parity with the dense grid, so the drop-in can never refuse an
    append the dense cache would have accepted. Size it smaller to
    oversubscribe (appends raise when the pool is exhausted).

    ``groups``: for a spec whose kv entries name groups, ``{group:
    {"max_len": the rows a slot may hold of it, "block_size", "num_blocks"
    (each optional: the cache's own)}}``, the leading group first.
    """

    @staticmethod
    def layer_spec(num_layers, entries, groups=None, layers=None,
                   states=None):
        """The spec of a decoder's cache: each of `num_layers` holds the
        `entries`, name -> (shape, dtype) of a position (``{"k": ..., "v":
        ...}`` of one per-head shape; one latent row ``{"c": ((640,),
        bfloat16)}``), under the names ``<entry><layer>`` that
        ``forward_inputs`` and ``commit`` find them by, in this order.
        `groups`: ``{group: the names of its entries}``, every entry in
        one; the entry's spec then names its group. `states`: state
        entries, name -> (shape, dtype) of a slot's value, named likewise.
        `layers`: ``{entry: the layers that hold it}`` (an entry not named
        there: every layer)."""
        group_of = {name: (group,) for group, names in (groups or {}).items()
                    for name in names}
        if groups and set(group_of) != set(entries):
            raise ValueError("groups %r do not part the entries %s"
                             % (groups, sorted(entries)))
        layers = layers or {}
        spec = {}
        for i in range(num_layers):
            for kind, declared in (("kv", entries), ("state", states or {})):
                for name, (shape, dtype) in declared.items():
                    if i in layers.get(name, range(num_layers)):
                        spec["%s%d" % (name, i)] = (
                            (kind, tuple(shape), dtype)
                            + group_of.get(name, ()))
        return spec

    def __init__(self, slots, spec, max_len=512, block_size=None,
                 num_blocks=None, name="default", groups=None):
        if slots < 1:
            raise ValueError("need at least one slot, got %r" % slots)
        self.slots = int(slots)
        self.max_len = int(max_len)
        block_size = int(block_size or _env_int("MXTPU_GEN_BLOCK_SIZE",
                                                DEFAULT_BLOCK_SIZE))
        # MXTPU_GEN_NUM_BLOCKS oversubscribes every pool in the process
        # (capacity drills, llm_capacity bench) without threading a
        # num_blocks argument through make_cache/load signatures
        num_blocks = int(num_blocks or _env_int("MXTPU_GEN_NUM_BLOCKS", 0))
        # group -> its rows; a spec that names no group has the one None
        self._groups = {
            group: _Rows(self.slots, geo.get("max_len", self.max_len),
                         geo.get("block_size") or block_size,
                         geo.get("num_blocks") or num_blocks)
            for group, geo in (groups or {None: {}}).items()}
        self._lead = next(iter(self._groups.values()))
        self.name = name
        self.spec = {}
        self.data = {}
        self._group_of = {}         # kv entry -> the name of its group
        for ent_name, ent in spec.items():
            kind, shape = ent[0], tuple(ent[1])
            dtype = np.dtype(ent[2]) if len(ent) > 2 else np.float32
            if kind not in _KINDS:
                raise ValueError("entry %r: kind must be one of %s, got %r"
                                 % (ent_name, _KINDS, kind))
            if kind == "kv":
                group = ent[3] if len(ent) > 3 else None
                if group not in self._groups:
                    raise ValueError(
                        "entry %r: group %r has no geometry (groups=%s)"
                        % (ent_name, group, sorted(map(str, self._groups))))
                self._group_of[ent_name] = group
                rows = self._groups[group]
                rows.entries.append(ent_name)
                full = (rows.num_blocks, rows.block_size) + shape
            else:
                full = (self.slots,) + shape
            self.spec[ent_name] = (kind, shape, dtype)
            self.data[ent_name] = _device_zeros(full, dtype)
        # a forward reads and `commit` stores the entries by layer:
        # <entry><i>, as `layer_spec` names them
        self._orders = {n: device_order(self.data[n])
                        for n in self._group_of}
        for rows in self._groups.values():
            rows.layer_names = _by_layer(rows.entries)
        self._state_names = [n for n, ent in self.spec.items()
                             if ent[0] == "state"]
        # the state entries by layer, as a forward takes their pools; ()
        # where there are none or they are not named by layer (a forward
        # is then given no slots)
        self._state_layers = (_by_layer(self._state_names) or ()
                              if self._state_names else ())
        self._resets = set()        # slots allocated, rows not yet zeroed
        self.state_resets = 0       # slots whose state rows were zeroed
        # (S, C) -> a compiled `store_program` put there by an owner that
        # ships executables (the serving family's warm grid); `commit`
        # takes the jitted one where there is none
        self.programs = {}
        # the leading group's geometry is the cache's: with no groups, all
        # there is
        self.block_size = self._lead.block_size
        self.max_blocks_per_slot = self._lead.max_blocks_per_slot
        self.num_blocks = sum(r.num_blocks for r in self._groups.values())
        self._tables = self._lead.tables
        self._free_blocks = self._lead.free_blocks
        # positions a slot holds: the rows themselves where no group
        # restarts; counted beside the leading group's rows where one may
        self.lengths = (np.zeros(self.slots, np.int64) if groups
                        else self._lead.lengths)
        # the group whose rows are counted a second time, as positions
        self._counted = self._lead if groups else None
        self._free = list(range(self.slots - 1, -1, -1))
        self._live = set()
        self._peak_blocks = 0
        self._pressure_noted = False
        _memz.register_kv_cache(self)
        self._note_blocks()

    # ------------------------------------------------------------- slots
    @property
    def in_use(self):
        return len(self._live)

    def alloc(self):
        """Claim a zeroed slot; None when the grid is full. Blocks are
        mapped lazily by `commit` / `append`, so alloc itself never
        exhausts the pool."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._live.add(slot)
        self.lengths[slot] = 0
        for rows in self._groups.values():
            rows.lengths[slot] = 0
            rows.tables[slot] = []
        if self._state_names:
            self._resets.add(slot)
        self._note_blocks()
        return slot

    def _zero_resets(self):
        """Zero the state rows of the slots allocated since the last time:
        one launch over every state pool, whatever their number."""
        if not self._resets:
            return
        names = self._state_names
        fresh = sorted(self._resets)
        rows = np.full(self.slots, self.slots, np.int32)
        rows[:len(fresh)] = fresh
        self.data.update(zip(names, _zero_rows(
            tuple(self.data[n] for n in names), rows)))
        self.state_resets += len(fresh)
        _cat.gen_ssm_state_resets.inc(len(fresh), model=self.name)
        self._resets = set()

    def free(self, slot):
        if slot not in self._live:
            raise ValueError("slot %r is not live" % slot)
        self._live.remove(slot)
        self._free.append(slot)
        self.lengths[slot] = 0
        for rows in self._groups.values():
            rows.free_blocks.extend(reversed(rows.tables.pop(slot, [])))
            rows.lengths[slot] = 0
        self._note_blocks()

    # ------------------------------------------------------------ access
    def _check(self, slot):
        if slot not in self._live:
            raise ValueError("slot %r is not live" % slot)

    def _rows(self, group=None):
        """The rows of `group`; None: the leading group's."""
        if group is None:
            return self._lead
        try:
            return self._groups[group]
        except KeyError:
            raise ValueError("no group %r in this cache (groups: %s)"
                             % (group, sorted(map(str, self._groups))))

    def _kv_rows(self, name):
        kind = self.spec[name][0]
        if kind != "kv":
            raise ValueError("%r is a %r entry, not kv" % (name, kind))
        return self._groups[self._group_of[name]]

    def set_state(self, name, slot, value):
        kind, shape, _ = self.spec[name]
        if kind != "state":
            raise ValueError("%r is a %r entry, not state" % (name, kind))
        self._check(slot)
        self._zero_resets()
        self.data[name] = _set_row(self.data[name], np.int32(slot),
                                   np.asarray(value).reshape(shape))

    def state(self, name, slot):
        """A slot's value of a state entry, fetched to the host."""
        self._check(slot)
        self._zero_resets()
        return np.asarray(self.data[name][slot])

    def _row_at(self, rows, slot, pos):
        """The flat pool row of row `pos`, `slot`'s next, in every kv
        entry of `rows`; maps a fresh pool block when the row opens one. A
        reused block keeps its last owner's rows: every read of a pool is
        cut at the lengths, so a stale tail is never seen."""
        if pos >= rows.max_len:
            raise ValueError("slot %d is full (max_len=%d)"
                             % (slot, rows.max_len))
        if rows is self._counted and self.lengths[slot] >= self.max_len:
            raise ValueError("slot %d is full (max_len=%d)"
                             % (slot, self.max_len))
        bi, off = divmod(pos, rows.block_size)
        table = rows.tables[slot]
        if bi == len(table):
            if not rows.free_blocks:
                _cat.gen_kv_pool_exhausted.inc(name=self.name)
                _memz.on_pool_exhausted(self, slot=slot, block=bi)
                raise KVPoolExhausted(
                    "paged KV pool exhausted (%d blocks of %d positions); "
                    "slot %d needs block %d"
                    % (rows.num_blocks, rows.block_size, slot, bi),
                    name=self.name, slot=slot, block=bi,
                    num_blocks=rows.num_blocks,
                    block_size=rows.block_size)
            table.append(rows.free_blocks.pop())
            self._note_blocks()
        return table[bi] * rows.block_size + off

    def _advance(self, rows, slot):
        rows.lengths[slot] += 1
        if rows is self._counted:
            self.lengths[slot] += 1         # a leading row is a position

    def append(self, name, slot, value):
        """Write `value` at this slot's current position (all kv entries
        of a group share the position counter; call `advance` once per
        step after every entry is written). Maps a fresh pool block when
        the position crosses a block boundary. The slow surface: one
        launch a call; a forward's K and V go through `commit`."""
        rows = self._kv_rows(name)
        self._check(slot)
        row = self._row_at(rows, slot, int(rows.lengths[slot]))
        self.data[name] = _store_one(
            self.data[name], np.int32(row),
            np.asarray(value).reshape(self.spec[name][1]),
            self._orders[name])

    def commit(self, slots, *new_and_count, group=None):
        """``commit(slots, *new, count)``: store what a forward produced,
        the first `count` (one number, or one a row) chunk positions of
        row r of ``new[e][i]`` ((S, C) + shape device arrays, entry e of
        layer i, as the forward returned them: a sequence a layer, or one
        array stacked over layers) at the next positions of ``slots[r]``
        in the entries ``"<entry><i>"``, and advance the slots. `new` is
        in the spec's order of entries: ``commit(slots, new_k, new_v,
        count)`` for a cache of ``"k<i>"`` / ``"v<i>"``. In a grouped
        cache `new` holds the entries of `group` (None: the leading one,
        whose rows are the sequence's positions), stored at that group's
        length.

        The host maps the blocks those positions need, row by row as a
        loop of `append`s would (so the pool runs out at the same
        position and leaves the same lengths and tables), and ONE donated
        program scatters every layer's rows; the positions not stored (a
        prefill chunk's pads, a row whose count is 0) point past the pool
        and are dropped. What was mapped before an error is stored."""
        slots = list(slots)
        *new, count = new_and_count
        rows_of = self._rows(group)
        names = self._layers(rows_of)
        if rows_of is self._lead and self._state_layers:
            new, written = new[:len(names)], new[len(names):]
            for entry_names, pools in zip(self._state_layers, written):
                self.data.update(zip(entry_names, pools))
        # per layer: a sequence of (S, C, ...) arrays, or one stacked
        chunk = (new[0].shape[2] if hasattr(new[0], "shape")
                 else new[0][0].shape[1])
        counts = np.broadcast_to(np.asarray(count), (len(slots),))
        rows = np.full((len(slots), chunk),
                       rows_of.num_blocks * rows_of.block_size, np.int32)
        lengths = rows_of.lengths
        try:
            for r, slot in enumerate(slots):
                if counts[r]:
                    self._check(slot)
                for c in range(int(counts[r])):
                    rows[r, c] = self._row_at(rows_of, slot,
                                              int(lengths[slot]))
                    lengths[slot] += 1
                    if rows_of is self._counted:
                        self.lengths[slot] += 1
        finally:
            for entry_names, pools in zip(names,
                                          self._store(rows_of, rows, new)):
                self.data.update(zip(entry_names, pools))
            self._note_blocks()

    def _layers(self, rows):
        if rows.layer_names is None:
            raise ValueError("commit stores a forward's layers in kv entries "
                             "named <entry><i> (k<i> and v<i>, say); this "
                             "cache's are not")
        return rows.layer_names

    def _layer_pools(self, rows=None):
        """(every layer's pool, entry by entry: K pools, V pools; their
        device orders alike, flat): what a forward reads and the commit
        program takes. `rows`: of one group; None: every group's, group
        by group."""
        names = [entry for r in ([rows] if rows else self._groups.values())
                 for entry in self._layers(r)]
        return (tuple([self.data[n] for n in entry] for entry in names),
                tuple(self._orders[n] for entry in names for n in entry))

    def _store(self, rows_of, rows, new):
        pools, orders = self._layer_pools(rows_of)
        args = pools + tuple(new) + (rows,)
        # a shipped executable is the leading group's
        program = (self.programs.get(rows.shape) if rows_of is self._lead
                   else None)
        if program is not None:
            try:
                return program(*args)
            except TypeError:   # bound for other pools: retire it
                del self.programs[rows.shape]
        return store_program_for(len(pools))(*args, orders)

    def lower_commit(self, *new):
        """`commit`'s program lowered for what a forward returns (`new`:
        arrays or their shapes and dtypes, entry by entry, each stacked
        over layers) against this cache's pools, for an owner that ships
        executables: compiled with the pools donated (the first
        ``len(new)`` arguments) it is what ``programs[(S, C)]`` holds."""
        pools, orders = self._layer_pools(self._lead)
        return store_program_for(len(pools)).lower(
            *pools, *new, jax.ShapeDtypeStruct(new[0].shape[1:3], np.int32),
            orders)

    def sync(self):
        """Wait until every commit launched so far has stored its rows
        (and so every forward before it has run): `commit` itself waits
        for nothing."""
        pools, _orders = self._layer_pools()
        jax.block_until_ready((pools, self._state_pools()))

    def advance(self, slot, group=None):
        self._check(slot)
        self._advance(self._rows(group), slot)
        self._note_blocks()

    def restart(self, slot, group):
        """`group` of `slot` starts over: its length is 0 again and its
        blocks stay mapped, so the rows that come next reuse them in place
        (a window of exact rows once its summaries are stored). The
        sequence's positions (``lengths``) stay as they are."""
        self._check(slot)
        self._rows(group).lengths[slot] = 0

    def prefix(self, name, slot):
        """The filled (length, ...) rows of a kv entry for one slot: a
        gathered copy (pool rows are not contiguous), fetched to the
        host."""
        rows_of = self._kv_rows(name)
        self._check(slot)
        length = int(rows_of.lengths[slot])
        if length == 0:
            _kind, shape, dtype = self.spec[name]
            return np.zeros((0,) + shape, dtype)
        nb = math.ceil(length / rows_of.block_size)
        rows = np.asarray(
            self.data[name][np.asarray(rows_of.tables[slot][:nb])])
        return rows.reshape((nb * rows_of.block_size,)
                            + rows.shape[2:])[:length]

    # ------------------------------------------------- paged extensions
    def pool(self, name):
        """The (num_blocks, block_size, ...) backing device array of a kv
        entry."""
        self._kv_rows(name)
        return self.data[name]

    def table(self, slot, group=None):
        self._check(slot)
        return list(self._rows(group).tables[slot])

    def group_pools(self, group=None):
        """Every layer's pool of `group`'s entries, entry by entry (None:
        the leading group's): device arrays."""
        return self._layer_pools(self._rows(group))[0]

    def group_lengths(self, group):
        """The rows every slot holds of `group`, (slots,) int64: the
        cache's own array (read it, do not write it)."""
        return self._rows(group).lengths

    def _state_pools(self):
        """Every state pool, entry by entry, each a list by layer."""
        return tuple([self.data[n] for n in entry]
                     for entry in self._state_layers)

    def forward_inputs(self, slots, fed=1):
        """What a forward over `slots` reads of the cache, in the order a
        paged forward takes them: the committed lengths (S,) and the block
        tables (S, max_blocks_per_slot), int32 host arrays it ships, and
        every layer's pool entry by entry (the K pools, the V pools), the
        device arrays (not shipped). A grouped cache hands each group's
        lengths and tables, group by group, then the pools likewise. A
        cache with state entries then hands the rows' slots (S,) and `fed`
        (one number, or one a row: the chunk positions of the row that are
        real, a pad's 0), int32 host arrays, and every state pool entry by
        entry, its rows zeroed where a slot was allocated since."""
        slots = list(slots)
        pools, _orders = self._layer_pools()
        shipped = tuple(
            a for group, rows in self._groups.items()
            for a in (rows.lengths[slots].astype(np.int32),
                      self.tables_array(slots, group)))
        if not self._state_layers:
            return shipped + pools
        self._zero_resets()
        fed = np.broadcast_to(np.asarray(fed, np.int32), (len(slots),))
        return (shipped + pools + (np.asarray(slots, np.int32), fed.copy())
                + self._state_pools())

    def tables_array(self, slots=None, group=None):
        """Block tables as an (S, max_blocks_per_slot) int32 array for
        the kernel. Unmapped entries pad with block 0 — padded fetches
        are masked by ``lengths`` downstream, so any valid row is safe.
        ``slots=None`` covers the full grid in slot order."""
        rows = self._rows(group)
        order = list(range(self.slots)) if slots is None else list(slots)
        out = np.zeros((len(order), rows.max_blocks_per_slot), np.int32)
        for row, slot in enumerate(order):
            table = rows.tables.get(slot, [])
            out[row, :len(table)] = table
        return out

    def truncate(self, slot, new_len):
        """Roll a slot back to ``new_len`` committed positions, freeing
        now-unused blocks (speculative decode rejects a drafted suffix
        this way). No-op when new_len >= current length. In a grouped
        cache the positions given up must all be live rows of the leading
        group: what a restart closed is not rolled back."""
        self._check(slot)
        if self._state_names:
            raise ValueError(
                "cache %r holds state entries (%s): a recurrent state has "
                "no rows to give back, so a sequence cannot be truncated "
                "(that would take snapshots of the state)"
                % (self.name, ", ".join(self._state_names)))
        new_len = int(new_len)
        if new_len < 0:
            raise ValueError("new_len must be >= 0, got %r" % new_len)
        back = int(self.lengths[slot]) - new_len
        if back <= 0:
            return
        rows = self._lead
        if back > int(rows.lengths[slot]):
            raise ValueError(
                "slot %d cannot roll back to %d: its leading group holds "
                "the last %d positions alone, the rest were closed"
                % (slot, new_len, int(rows.lengths[slot])))
        keep_rows = int(rows.lengths[slot]) - back
        keep = math.ceil(keep_rows / rows.block_size)
        table = rows.tables[slot]
        rows.free_blocks.extend(reversed(table[keep:]))
        del table[keep:]
        rows.lengths[slot] = keep_rows
        self.lengths[slot] = new_len
        self._note_blocks()

    @property
    def blocks_in_use(self):
        return sum(r.blocks_in_use for r in self._groups.values())

    @property
    def blocks_free(self):
        return self.num_blocks - self.blocks_in_use

    def fragmentation(self):
        """1 - filled_positions / mapped capacity: the ragged-last-block
        waste. 0.0 when nothing is mapped."""
        mapped = sum(r.blocks_in_use * r.block_size
                     for r in self._groups.values())
        if mapped == 0:
            return 0.0
        filled = int(sum(int(r.lengths[s]) for r in self._groups.values()
                         for s in self._live))
        return 1.0 - filled / float(mapped)

    def _note_blocks(self):
        in_use = self.blocks_in_use
        free = self.num_blocks - in_use
        if in_use > self._peak_blocks:
            self._peak_blocks = in_use
        # the gauges drop what they are handed while metrics are off, and
        # fragmentation() is a sum over the live slots, on every commit
        if _metrics.enabled():
            _cat.gen_kv_blocks_in_use.set(in_use, name=self.name)
            _cat.gen_kv_blocks_free.set(free, name=self.name)
            _cat.gen_kv_free_fraction.set(free / float(self.num_blocks),
                                          name=self.name)
            _cat.gen_kv_blocks_in_use_peak.set(self._peak_blocks,
                                               name=self.name)
            _cat.gen_kv_fragmentation.set(self.fragmentation(),
                                          name=self.name)
        _memz.note_kv(self)
        # near-exhaustion flight event, edge-triggered so a pool parked
        # at 95% doesn't spam the ring on every append
        low = free < 0.1 * self.num_blocks
        if low and not self._pressure_noted:
            self._pressure_noted = True
            _flight.record("gen.kv_pool_pressure", name=self.name,
                           free=free, total=self.num_blocks)
        elif not low and self._pressure_noted:
            self._pressure_noted = False
