"""Generation engine: chunked prefill, sampling, speculative decoding.

The engine owns sequences end to end: it allocates paged-KV slots,
ingests prompts in fixed-size chunks (one program shape, not one step
per prompt token), runs batched greedy/temperature decode, and — given
a draft model — Leviathan-style speculative decoding:

    round:  draft proposes d_1..d_k one token at a time
            target scores [ctx[-1], d_1..d_k] in ONE (k+1)-wide forward
            accept a = longest prefix with d_j == argmax(target row j-1)
            commit d_1..d_a plus the target's own next token t_a
            truncate both caches to the committed length

Every committed token is argmax of a target-model distribution given
previously committed tokens — exactly what plain greedy commits — so
speculative greedy output is bit-identical to non-speculative greedy
(both paths run the same lax reference numerics; pinned in tests).
A round commits between 1 (a=0, the target's own token) and k+1 tokens
for ~1 target forward, which is the decode speedup when the draft
agrees often.

All cache mutation happens here (commit a forward's K/V, truncate
rejected suffixes); the model adapter is a pure shape-cached forward.
``GPTPagedLM`` adapts ``models/gpt.py`` to that contract. The K/V pools
live on the device (``paged_kv``): a forward is given them where it runs,
returns the chunk's K and V as device arrays, and ``cache.commit`` stores
those there; the host fetches only what it reads (a greedy step's token
ids, a sampled step's logits; ``x0`` and its confidence; the expert
loads).

Between two forwards of a greedy plain decode nothing is waited for
(``_plain_loop``): the adapter's ``forward_token`` chooses the next token
in the forward's program and leaves it on the device, the next forward
is fed that array, and the host reads ids and expert loads ONE forward
behind, after it has launched the next. A prompt's prefill chunks are
read the same way (``prefill_slot``).

The step itself is four functions over (adapter, cache, slots), the one
implementation under this engine's loops and under ``serving.DecodeLoop``
(``generate/family.py`` calls them): ``forward_slots`` asks the cache for
a forward's inputs and calls the adapter, ``commit_slots`` stores what it
returned, ``step_slots`` is both for one token a slot, ``prefill_slot``
both for a prompt in padded chunks.

A model adapter that declares a ``window`` (``EvaPagedLM`` over
``models/eva_byte.py``) keeps a window of exact rows beside summaries of
the windows that closed, in two groups of cache entries. The loops are the
same: ``commit_slots``, told the adapter, asks it after every commit to
close the windows the commit filled (``close_windows``: known from the
cache's host lengths, nothing fetched).

A model adapter that declares a ``block_length`` (``SDARPagedLM`` over
``models/sdar_moe.py``) is a block-diffusion decoder, and the engine
drives it by blocks, not tokens (``_block_loop``; docs/GENERATE.md):

    block:  the row's next B positions, the prompt's tail fixed, the
            rest MASK
            up to `denoise_steps` forwards over the block that STORE
            NOTHING; in each, the ceil(masked / steps left) most
            confident masked positions take their argmax token
            once none is masked, one forward over the final tokens
            whose K and V are committed: B positions a row at once

The schedule is applied in the denoising forward's program
(``forward_denoise``), which hands the next forward its tokens and mask as
device arrays; the host reads every block forward one forward behind, as
the plain loop reads its tokens.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.paged_latent import (cache_row_width, latent_block_size,
                                       latent_path,
                                       paged_latent_decode_available,
                                       paged_latent_prefill_available,
                                       rows_walked)
from ..telemetry import catalog as _cat
from ..telemetry import tracing as _tr
from .paged_kv import PagedKVCache, _env_int

__all__ = ["GenerateEngine", "GPTPagedLM", "SDARPagedLM", "MLAPagedLM",
           "EvaPagedLM"]


def default_prefill_chunk():
    """``MXTPU_GEN_PREFILL_CHUNK`` (32): the chunk width of the engine and
    of the serving family, read here alone."""
    return _env_int("MXTPU_GEN_PREFILL_CHUNK", 32)


def _host_bytes(args):
    """Bytes of the host (numpy) arrays among `args`, lists looked into:
    what a jitted call given them has to ship to the device. An array
    that already lives on the device counts 0."""
    total = 0
    for a in args:
        if isinstance(a, (list, tuple)):
            total += _host_bytes(a)
        elif isinstance(a, np.ndarray):
            total += a.nbytes
    return total


def _dispatch(fn, params, args, programs=None, **attrs):
    """The call of an adapter's forward under its ``lm.dispatch`` span; a
    real span also counts the host bytes the call ships and carries
    `attrs`. `programs`: the adapter's (S, C) -> shipped executable of
    `fn`; one is preferred to the jitted `fn`, and retired when it
    refuses its arguments (compiled for other pools or weights, or an
    export of another signature)."""
    sp = _tr.span("lm.dispatch")
    if sp is not _tr.NULL_SPAN:     # counted only for a real span
        sp.set_attr("h2d_bytes", _host_bytes(args))
        for key, value in attrs.items():
            sp.set_attr(key, value)
    with sp:
        program = programs.get(args[0].shape) if programs else None
        if program is not None:
            try:
                return program(params, *args)
            except TypeError:
                del programs[args[0].shape]
        return fn(params, *args)


def _fetch(arrays):
    """`arrays` (what the caller reads of a forward's outputs: a list or
    a dict of them) as host arrays, under an ``lm.fetch`` span: the wait
    for the device, then the copies, all started before the first is
    waited for; the span counts the bytes copied."""
    with _tr.span("lm.fetch") as sp:
        out = jax.device_get(arrays)
        sp.set_attr("d2h_bytes", sum(
            a.nbytes for a in jax.tree_util.tree_leaves(out)))
    return out


def _fetch_behind(adapter, read, note):
    """`read` (what a forward that fetched nothing left on the device for
    the host: a dict of arrays, ``{}`` where there is no such forward) as
    host arrays, fetched once a later forward is launched; `note` is
    told (the expert loads' tally, one forward late)."""
    host = _fetch(read)
    if note is not None and host:
        note(adapter, host)
    return host


def forward_slots(adapter, cache, slots, tokens, call=None, note=None):
    """One adapter forward for `slots` (list) feeding `tokens` (S, C);
    returns ``(logits, *new)``, `new` what the chunk adds to each cache
    entry (new_k, new_v), WITHOUT committing. `call`: another forward of
    the adapter's with the same arguments (the block loop's
    ``forward_denoise`` / ``forward_kv``, the plain loop's
    ``forward_token``); `tokens` may then be a device array. `note`:
    called with the adapter after the forward (the engine's tallies of
    what it last did: an expert layer's loads, a latent cache's attention
    path)."""
    with _tr.span("kv.gather"):
        inputs = cache.forward_inputs(slots)
    out = (call or adapter.forward)(tokens, *inputs)
    if note is not None:
        note(adapter)
    return out


def commit_slots(cache, slots, *new_and_count, adapter=None, phase="decode",
                 note=None):
    """``commit_slots(cache, slots, *new, count)``: store the first
    `count` (one number, or one a row) chunk positions of every row (row
    r belongs to ``slots[r]``) of `new` (a forward's additions, entry by
    entry: new_k, new_v) in the cache; the span counts the pool rows
    written an entry. `adapter`: the forward's; one that declares
    ``close_windows`` is then asked to close the windows this commit
    filled (a ``gen.window_close`` span a closing, of the engine's `phase`;
    `note` is told what was closed)."""
    count = new_and_count[-1]
    rows = (len(slots) * count if isinstance(count, int)
            else int(np.sum(count)))
    with _tr.span("kv.commit", rows=rows):
        cache.commit(slots, *new_and_count)
    close = getattr(adapter, "close_windows", None)
    if close is not None:
        closed = close(cache, slots, phase)
        if note is not None and closed:
            note(adapter, closed=closed)


def step_slots(adapter, cache, slots, tokens, count=1, note=None):
    """Feed one token per slot ((S, 1)); commit what it adds to the cache
    (of the rows whose `count` is 1: a serving grid's active ones);
    return the (S, V) next-token logits, on the host: the caller chooses
    from them (temperature sampling, a speculative round's draft,
    ``serving.DecodeLoop``, which admits and retires rows a step). It
    waits for the forward; a greedy ``GenerateEngine`` call does not take
    it (``_plain_loop``)."""
    logits, *new = forward_slots(adapter, cache, slots, tokens, note=note)
    commit_slots(cache, slots, *new, count, adapter=adapter, note=note)
    return logits[:, -1]


def prefill_slot(adapter, cache, slot, tokens_1d, chunk, note=None):
    """Chunked prompt ingestion: commit K/V for every prompt token in
    fixed `chunk`-wide forwards (last chunk padded; pad positions sit
    AFTER the valid ones, so causality keeps them out of every valid
    position's attention window and they are simply not committed; under
    a block mask the valid tokens are whole blocks, so the pads begin a
    later block). No logits are read: the adapter's ``forward_kv``, where
    it has one, fetches nothing, and what it leaves for the host (an
    expert layer's loads) is read one chunk behind: chunk n's after chunk
    n + 1 and its commit are launched, the last chunk's before returning.
    The last commit is NOT waited for (``cache.sync``)."""
    call = getattr(adapter, "forward_kv", None)
    behind = None
    for start in range(0, len(tokens_1d), chunk):
        piece = tokens_1d[start:start + chunk]
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :len(piece)] = piece
        read, *new = forward_slots(adapter, cache, [slot], padded, call,
                                   note)
        commit_slots(cache, [slot], *new, len(piece), adapter=adapter,
                     phase="prefill", note=note)
        if call is not None:
            if behind is not None:
                _fetch_behind(adapter, behind, note)
            behind = read
    if behind is not None:
        _fetch_behind(adapter, behind, note)


class _PagedLM:
    """What the adapters share: the cache of their layers. An adapter
    sets ``config``, ``num_layers`` and ``kv_entries``: what a layer
    caches, entry name -> the (shape, dtype) of a position (``k`` and
    ``v`` of one per-head shape; one latent row ``c``), in the order its
    forward takes the pools and returns the chunk's additions."""

    def cache_spec(self):
        return PagedKVCache.layer_spec(self.num_layers, self.kv_entries)

    def make_cache(self, slots, max_len=None, **kw):
        return PagedKVCache(slots, self.cache_spec(),
                            max_len=max_len or self.config["max_len"], **kw)


class GPTPagedLM(_PagedLM):
    """Shape-cached jit adapter over ``gpt_forward_paged``.

    ``forward(tokens, lengths, tables, k_pools, v_pools)`` takes host
    tokens, lengths and tables and the cache's pools (device arrays: not
    shipped), and returns ``(logits (S, C, V), new_k, new_v)``: the logits
    a host array, new_k / new_v DEVICE arrays (layers, S, C, H, D) for
    ``cache.commit``, one array each: on the chip's host a launch costs
    some 50 us an output buffer, so the 2 x 48 per-layer arrays are
    stacked in the program.

    Two more forwards of the same arguments fetch NOTHING and return
    ``(read, new_k, new_v)``, `read` a dict of the device arrays the host
    may fetch when it will (``engine._fetch_behind``):

    - ``forward_token`` (the token head): ``read["token"]`` (S, 1) int32,
      the argmax of the last chunk position's logits, taken in the
      program on the logits ``forward`` returns; shaped as the next
      step's `tokens`, which it may be given as (any forward takes
      `tokens` as a host or a device array). The logits are no output of
      that program;
    - ``forward_kv`` (prefill): the ``forward`` program, ``read`` empty.

    One XLA program per head and (S, C) shape — the engine keeps shapes
    fixed (padded prefill chunks, fixed spec width), so steady state is
    two programs: prefill (S, chunk) and decode (S, 1) plus (1, k+1) for
    speculative verify.

    ``programs``: (S, C) -> a shipped executable of ``forward``'s program
    (``lower(...)`` compiled), put there by an owner that ships them (the
    serving family's bind / warm grid) as ``cache.programs`` holds the
    commit's; a call prefers it to the jit and retires it when it refuses
    its arguments. ``params`` is passed a call, so replacing the dict (of
    the same shapes and dtypes) swaps the weights under every program.
    """

    def __init__(self, params, config, use_kernel=False, interpret=False):
        from ..models.gpt import gpt_config, gpt_forward_paged
        self.config = gpt_config(config)
        self.params = {n: jnp.asarray(v) for n, v in params.items()}
        self.num_layers = self.config["num_layers"]
        H = self.config["num_heads"]
        self.kv_entries = dict.fromkeys(
            "kv", ((H, self.config["units"] // H), jnp.float32))
        self.programs = {}

        def program(head):
            def pure(params, tokens, lengths, tables, kps, vps):
                out, nk, nv = gpt_forward_paged(
                    params, self.config, tokens, lengths, tables, kps, vps,
                    use_kernel=use_kernel, interpret=interpret, head=head)
                if head == "token":
                    out = out[:, None]
                return out, jnp.stack(nk), jnp.stack(nv)
            return jax.jit(pure)
        self._fns = {head: program(head) for head in ("logits", "token")}

    def lower(self, tokens, lengths, tables, k_pools, v_pools,
              head="logits"):
        """A forward's program lowered for arguments of these shapes:
        ``forward``'s is what an owner compiles into ``programs``."""
        return self._fns[head].lower(self.params, tokens, lengths, tables,
                                     k_pools, v_pools)

    def forward(self, tokens, lengths, tables, k_pools, v_pools):
        logits, nk, nv = _dispatch(
            self._fns["logits"], self.params,
            (tokens, lengths, tables, k_pools, v_pools), self.programs)
        (logits,) = _fetch([logits])
        return logits, nk, nv

    def forward_token(self, tokens, lengths, tables, k_pools, v_pools):
        token, nk, nv = _dispatch(
            self._fns["token"], self.params,
            (tokens, lengths, tables, k_pools, v_pools))
        return {"token": token}, nk, nv

    def forward_kv(self, tokens, lengths, tables, k_pools, v_pools):
        _logits, nk, nv = _dispatch(
            self._fns["logits"], self.params,
            (tokens, lengths, tables, k_pools, v_pools), self.programs)
        return {}, nk, nv


class SDARPagedLM(_PagedLM):
    """Shape-cached jit adapter over ``sdar_forward_paged``: the
    block-diffusion decoder of ``models/sdar_moe.py``, served in
    `dtype` (bfloat16: weights, activations and the K/V pools).

    What it declares to the engine: ``block_length`` (B; the engine then
    runs its block loop) and ``mask_id`` (the token a position holds
    until a denoising forward fixes it). Three programs a shape, one a
    kind of forward:

    - ``forward`` — the ``GPTPagedLM`` contract, ``(logits (S, C, V),
      new_k, new_v)``;
    - ``forward_denoise(tokens, ..., masked=, steps_left=)`` — the block
      loop's denoising forward: the argmax token ``x0`` of every position
      and its softmax probability (the confidence), then the static
      schedule applied in the program (``GenerateEngine.
      _fix_most_confident``, traced: of each row's masked positions the
      ``ceil(masked / steps_left)`` most confident take ``x0``).
      ``steps_left`` is an int32 operand, so one program serves every
      step. -> ``(read, next_tokens, next_masked)``, all on the device,
      nothing fetched: the next forward's `tokens` and `masked` (S, C),
      and ``read`` ``{"x0", "confidence", "masked" (next_masked),
      "expert_loads"}`` for the host to fetch when it will
      (``engine._fetch_behind``); the positions it fixed are ``masked &
      ~read["masked"]``. No K and V come back: nothing is stored;
    - ``forward_kv`` — prefill and a block's store pass: ``(read, new_k,
      new_v)``, no final norm, no head, and nothing fetched:
      ``read["expert_loads"]`` stays on the device for the host to fetch
      when it will (``engine._fetch_behind``).

    Tokens (and ``forward_denoise``'s mask) are host or device arrays,
    lengths and tables host arrays, the pools the cache's device arrays;
    ``forward``'s logits come back as a host array, new_k / new_v stay on
    the device, (layers, S, C, Hkv, D) each, for ``cache.commit``. After
    ``forward``, ``last_expert_loads`` holds the (layers, experts) routes
    each expert got, on the host; after a forward that fetched nothing,
    None.
    """

    def __init__(self, params, config, dtype="bfloat16"):
        from ..models.sdar_moe import sdar_config, sdar_forward_paged
        self.config = sdar_config(config)
        self.dtype = jnp.dtype(dtype)
        self.params = {n: jnp.asarray(v, self.dtype)
                       for n, v in params.items()}
        self.num_layers = self.config["num_layers"]
        self.kv_entries = dict.fromkeys(
            "kv", ((self.config["num_kv_heads"], self.config["head_dim"]),
                   self.dtype))
        self.block_length = int(self.config["block_length"])
        self.mask_id = int(self.config["mask_id"])
        self.last_expert_loads = None

        def program(head):
            def pure(params, tokens, lengths, tables, kps, vps):
                out, nk, nv, loads = sdar_forward_paged(
                    params, self.config, tokens, lengths, tables, kps, vps,
                    head=head)
                # -> (what the host reads, what stays on the device)
                kv = (jnp.stack(nk), jnp.stack(nv))
                return ((loads,) if out is None else (out, loads)), kv
            return jax.jit(pure)
        self._fns = {head: program(head) for head in ("logits", "none")}

        def denoise(params, tokens, masked, steps_left, lengths, tables,
                    kps, vps):
            (x0, confidence), _nk, _nv, loads = sdar_forward_paged(
                params, self.config, tokens, lengths, tables, kps, vps,
                head="choice")
            # looked up when traced: the schedule has one definition
            fixed = GenerateEngine._fix_most_confident(masked, confidence,
                                                       steps_left)
            return (jnp.where(fixed, x0, tokens), masked & ~fixed, x0,
                    confidence, loads)
        self._fns["denoise"] = jax.jit(denoise)

    def _call(self, head, *args):
        """One forward, nothing fetched -> the program's outputs, all on
        the device."""
        self.last_expert_loads = None
        return _dispatch(self._fns[head], self.params, args)

    def forward(self, tokens, lengths, tables, k_pools, v_pools):
        read, (nk, nv) = self._call("logits", tokens, lengths, tables,
                                    k_pools, v_pools)
        logits, self.last_expert_loads = _fetch(read)
        return logits, nk, nv

    def forward_denoise(self, tokens, lengths, tables, k_pools, v_pools, *,
                        masked, steps_left):
        nxt, masked, x0, confidence, loads = self._call(
            "denoise", tokens, masked, np.int32(steps_left), lengths, tables,
            k_pools, v_pools)
        return ({"x0": x0, "confidence": confidence, "masked": masked,
                 "expert_loads": loads}, nxt, masked)

    def forward_kv(self, tokens, lengths, tables, k_pools, v_pools):
        (loads,), (nk, nv) = self._call("none", tokens, lengths, tables,
                                        k_pools, v_pools)
        return {"expert_loads": loads}, nk, nv


class MLAPagedLM(_PagedLM):
    """Shape-cached jit adapter over ``mla_forward_paged``: the
    latent-attention decoders of ``models/mla_moe.py`` (one residual
    stream, or several hyper-connected ones; every expert held, or the
    configuration's ``experts_held`` share of each expert layer), served in
    `dtype` (bfloat16: weights, activations and the cache).

    Its cache holds ONE entry a layer, ``c``: a row of ``kv_rank +
    rope_dim`` values a position (the normalised latent and the rotated
    key part; zeros up to whole lanes, ``paged_latent.cache_row_width``:
    576 values in a row of 640), shared by all heads, and no per-head K or
    V. It declares no ``block_length``: the engine decodes it a token a
    step.

    - ``forward`` — ``(logits (S, C, V), new_rows)``, the logits a host
      array;
    - ``forward_token`` — the token head, as ``GPTPagedLM``'s: ``(read,
      new_rows)`` with ``read["token"]`` (S, 1) int32 and
      ``read["expert_loads"]`` left on the device, nothing fetched (the
      (S, V) float32 logits of a step, megabytes, are no output);
    - ``forward_kv`` — prefill: ``(read, new_rows)``, the loads alone
      left on the device, no final norm, no head (a chunk's logits,
      positions x V float32, can pass a gigabyte).

    Lengths and tables are host arrays, tokens a host or a device array,
    the pools the cache's device arrays; new_rows stays on the device,
    (layers, S, C, cache_row_width), for ``cache.commit``. After a forward
    that fetched, ``last_expert_loads`` holds the forward's loads on the
    host (None after one that did not): (expert layers, experts), the
    routes each routed expert got; where the configuration holds a share,
    the routes of the experts HELD here and two columns more, a layer's
    ``routes_elsewhere`` and ``rows_moved`` (``split_loads`` parts them);
    and after every forward ``last_latent_path`` which attention
    path the chunk's width chose and the rows it counts, by
    ``last_stats["mla"]``'s names: ``("expanded", {"expanded_rows": the
    sequences' committed lengths summed, "expanded_kernel_forwards": 1
    where the chunk's attention was the launch ``paged_latent_prefill``,
    else 0})`` for a chunk, the cached rows
    it expanded again; ``("absorbed", {"absorbed_rows_live": the same
    sum, "absorbed_rows_read": the rows a layer's walk over the past
    fetches for them})`` for a decode step (``paged_latent.rows_walked``:
    the kernel's walk reads each sequence's own blocks, the ``lax`` walk
    every sequence's tiles up to the longest one's).
    """

    def __init__(self, params, config, dtype="bfloat16"):
        from ..models.mla_moe import mla_config, mla_forward_paged
        self.config = mla_config(config)
        self.dtype = jnp.dtype(dtype)
        self.params = {n: jnp.asarray(v, self.dtype)
                       for n, v in params.items()}
        self.num_layers = self.config["num_layers"]
        self.kv_entries = {"c": ((cache_row_width(
            self.config["kv_rank"], self.config["rope_dim"]),), self.dtype)}
        self.last_expert_loads = self.last_latent_path = None

        def program(head):
            def pure(params, tokens, lengths, tables, pools):
                out, rows, loads = mla_forward_paged(
                    params, self.config, tokens, lengths, tables, pools,
                    head=head)
                # -> (what the host reads, what stays on the device)
                if head == "token":
                    out = out[:, None]
                return ((loads,) if out is None else (out, loads),
                        jnp.stack(rows))
            return jax.jit(pure)
        self._fns = {head: program(head)
                     for head in ("logits", "token", "none")}

    def make_cache(self, slots, max_len=None, **kw):
        """The cache of ``_PagedLM.make_cache`` in blocks sized by the
        row's bytes (``paged_latent.latent_block_size``: 128 positions of
        640 bfloat16 lanes), not by ``MXTPU_GEN_BLOCK_SIZE``: a row is a
        twentieth of a per-head cache's position, and the decode kernel
        copies the cache block by block. A `block_size` given wins."""
        max_len = max_len or self.config["max_len"]
        (shape, dtype), = self.kv_entries.values()
        kw.setdefault("block_size", latent_block_size(
            shape[0] * dtype.itemsize, max_len))
        return super().make_cache(slots, max_len=max_len, **kw)

    def lower(self, tokens, lengths, tables, pools, head="logits"):
        """A forward's program lowered for arguments of these shapes."""
        return self._fns[head].lower(self.params, tokens, lengths, tables,
                                     pools)

    def _call(self, head, tokens, lengths, tables, pools):
        """One forward, nothing fetched -> (the head's outputs then the
        expert loads, new_rows), all on the device."""
        path = latent_path(tokens.shape[1])
        read, rows = _dispatch(self._fns[head], self.params,
                               (tokens, lengths, tables, pools),
                               mla_path=path)
        self.last_expert_loads = None
        live = int(np.sum(lengths))
        if path == "expanded":
            cfg = self.config
            counts = {
                "expanded_rows": live,
                "expanded_kernel_forwards": int(
                    paged_latent_prefill_available(
                        pools[0], tokens.shape[1], cfg["kv_rank"],
                        cfg["nope_dim"], cfg["v_dim"]))}
        else:
            counts = {
                "absorbed_rows_live": live,
                "absorbed_rows_read": rows_walked(
                    lengths, pools[0].shape[1], tables.shape[1],
                    paged_latent_decode_available(pools[0]))}
        self.last_latent_path = (path, counts)
        return read, rows

    def split_loads(self, loads):
        """A forward's ``expert_loads`` as the host fetched them -> (the
        routes each expert held here got (expert layers, experts held),
        what a held share says of itself: ``{"routes_elsewhere",
        "rows_moved"}`` summed over the layers, ``{}`` where every expert
        is held)."""
        held = self.config["experts_held"]
        if held is None:
            return loads, {}
        return loads[:, :held[1]], {
            "routes_elsewhere": int(loads[:, held[1]].sum()),
            "rows_moved": int(loads[:, held[1] + 1].sum())}

    def forward(self, tokens, lengths, tables, pools):
        read, rows = self._call("logits", tokens, lengths, tables, pools)
        logits, self.last_expert_loads = _fetch(read)
        return logits, rows

    def forward_token(self, tokens, lengths, tables, pools):
        (token, loads), rows = self._call("token", tokens, lengths, tables,
                                          pools)
        return {"token": token, "expert_loads": loads}, rows

    def forward_kv(self, tokens, lengths, tables, pools):
        (loads,), rows = self._call("none", tokens, lengths, tables, pools)
        return {"expert_loads": loads}, rows


class EvaPagedLM(_PagedLM):
    """Shape-cached jit adapter over ``eva_forward_paged``: the byte-level
    decoder of ``models/eva_byte.py`` (EVA attention: a window of exact
    keys and values beside a summary a chunk of every closed window),
    served in `dtype` (bfloat16: weights, activations and the cache).

    Its cache holds two GROUPS of entries a layer
    (``paged_kv.PagedKVCache(groups=...)``): ``window``, the leading one
    (``wk``, ``wv``: at most ``window`` rows a slot in blocks of 256, row
    ``t % window``), and ``summary`` (``sk``, ``sv``: ``window // chunk``
    rows a closing, a block a closing); a row holds all heads side by
    side (H x D lanes). On a TPU a decode step walks each group by one
    launch a layer (``ops/pallas/paged_heads.py``; `interpret` runs the
    launches anywhere), elsewhere and for every wider chunk by the
    ``lax`` gather. It declares ``window``: the engine
    then keeps a prefill chunk from straddling a closing, and
    ``commit_slots`` asks ``close_windows`` after every commit.

    - ``forward`` — ``(logits (S, C, V), new_k, new_v)``: the served
      head's logits (head 0: position t scores byte t + 1) on the host;
      ``last_pred_logits`` (S, C, pred_heads, V) holds every head's;
    - ``forward_token`` — ``(read, new_k, new_v)`` with ``read["token"]``
      (S, 1) int32, head 0's argmax at the last chunk position, nothing
      fetched;
    - ``forward_kv`` — prefill: ``(read, new_k, new_v)``, no final norm,
      no head; ``last_stream`` is the last layer's stream (S, C, d)
      float32 on the device, what a pipeline stage hands on;
      ``read["window_rows"]`` (S,) int32 is the program's smallest output,
      left for ``prefill_slot`` to fetch one chunk behind: a chunk's keys,
      values and temporaries hold some 0.8 GB of HBM until its commit has
      run, so the host keeps to one chunk ahead of the device (the device
      never waits: the next chunk is queued by then);
    - ``close_windows(cache, slots, phase)`` — for each of `slots` whose
      window group holds ``window`` rows (the cache's host lengths: no
      fetch): one launch that reads the window's rows and pools them into
      its summaries, their commit to the summary group, and the window
      restarted in place; a ``gen.window_close`` span a closing. ->
      ``{"windows_closed", "summary_rows_written", "window_rows_read"}``,
      None where nothing closed.

    Each group's lengths and tables are host arrays, tokens a host or a
    device array, the pools the cache's device arrays; new_k / new_v stay
    on the device, (layers, S, C, H D), for ``cache.commit``. A chunk that
    finds every window empty (a prefill chunk of ``window`` positions)
    takes a program that leaves the window pools unread. After every
    forward ``last_eva`` holds what it read, by ``last_stats["eva"]``'s
    names: ``window_rows_read`` (the live window rows and the chunk's
    own), ``summary_rows_read``, ``positions`` (the contexts' lengths
    after the chunk, summed over the rows).
    """

    def __init__(self, params, config, dtype="bfloat16", interpret=False):
        from ..models.eva_byte import (eva_close_window, eva_config,
                                       eva_forward_paged)
        self.config = cfg = eva_config(config)
        self.dtype = jnp.dtype(dtype)
        self.params = {n: jnp.asarray(v, self.dtype)
                       for n, v in params.items()}
        self.num_layers = cfg["num_layers"]
        self.window = int(cfg["window"])
        self.summaries_per_window = self.window // int(cfg["chunk"])
        # a row holds all heads side by side: H x D lanes
        self.kv_entries = dict.fromkeys(
            ("wk", "wv", "sk", "sv"), ((cfg["units"],), self.dtype))
        self.kv_groups = {"window": ("wk", "wv"), "summary": ("sk", "sv")}
        self.last_eva = self.last_pred_logits = self.last_stream = None

        def program(head, fresh):
            def pure(params, tokens, wlen, wtables, slen, stables, wk, wv,
                     sk, sv):
                out, nk, nv = eva_forward_paged(
                    params, cfg, tokens, wlen, wtables, slen, stables, wk,
                    wv, sk, sv, head=head, fresh=fresh, interpret=interpret)
                if head == "token":
                    out = out[:, None]
                elif head == "none":    # the stream; the window rows after
                    out = (out, wlen + tokens.shape[1])
                return out, jnp.stack(nk), jnp.stack(nv)
            return jax.jit(pure)
        self._fns = {(head, fresh): program(head, fresh)
                     for head in ("logits", "token", "none")
                     for fresh in (False, True)}

        def close(params, wtables, wk, wv):
            sk, sv = eva_close_window(params, cfg, wtables, wk, wv)
            return jnp.stack(sk), jnp.stack(sv)
        self._close = jax.jit(close)

    def cache_spec(self):
        return PagedKVCache.layer_spec(self.num_layers, self.kv_entries,
                                       groups=self.kv_groups)

    def make_cache(self, slots, max_len=None, **kw):
        """The grouped cache: the window group in blocks of 256 rows (a
        whole window where it is shorter), the summary group a closing a
        block and as many as ``max_len`` positions close."""
        max_len = max_len or self.config["max_len"]
        per = self.summaries_per_window
        kw.setdefault("groups", {
            "window": {"max_len": self.window,
                       "block_size": min(self.window, 256)},
            "summary": {"max_len": max(1, max_len // self.window) * per,
                        "block_size": per}})
        return super().make_cache(slots, max_len=max_len, **kw)

    def lower(self, tokens, wlen, wtables, slen, stables, wk, wv, sk, sv,
              head="logits", fresh=False):
        """A forward's program lowered for arguments of these shapes."""
        return self._fns[head, fresh].lower(
            self.params, tokens, wlen, wtables, slen, stables, wk, wv, sk, sv)

    def lower_close(self, wtables, wk, wv):
        """The closing's program lowered likewise."""
        return self._close.lower(self.params, wtables, wk, wv)

    def _call(self, head, tokens, wlen, *rest):
        """One forward, nothing fetched -> (out, new_k, new_v), all on
        the device."""
        slen = rest[1]
        chunk = tokens.shape[1]
        window_rows = int(wlen.sum()) + chunk * len(wlen)
        self.last_eva = {
            "window_rows_read": window_rows,
            "summary_rows_read": int(slen.sum()),
            "positions": int((slen // self.summaries_per_window).sum())
            * self.window + window_rows}
        fresh = chunk > 1 and not wlen.any()
        return _dispatch(self._fns[head, fresh], self.params,
                         (tokens, wlen) + rest)

    def forward(self, *args):
        logits, nk, nv = self._call("logits", *args)
        (logits,) = _fetch([logits])
        V = self.config["vocab_size"]
        self.last_pred_logits = logits.reshape(logits.shape[:2] + (-1, V))
        return logits[..., :V], nk, nv

    def forward_token(self, *args):
        token, nk, nv = self._call("token", *args)
        return {"token": token}, nk, nv

    def forward_kv(self, *args):
        (self.last_stream, reached), nk, nv = self._call("none", *args)
        return {"window_rows": reached}, nk, nv

    def close_windows(self, cache, slots, phase):
        full = cache.group_lengths("window")
        due = [slot for slot in slots if full[slot] >= self.window]
        if not due:
            return None
        per = self.summaries_per_window
        for slot in due:        # one slot a launch: one compiled shape
            with _tr.span("gen.window_close", slots=1, rows_written=per,
                          phase=phase):
                sk, sv = _dispatch(
                    self._close, self.params,
                    (cache.tables_array([slot], "window"),)
                    + cache.group_pools("window"))
                cache.commit([slot], sk, sv, per, group="summary")
                cache.restart(slot, "window")
        return {"windows_closed": len(due),
                "summary_rows_written": per * len(due),
                "window_rows_read": self.window * len(due)}


class GenerateEngine:
    """Drives one model (plus optional draft) over paged KV caches.

    model / draft: adapters with ``num_layers``, ``forward(...)``
    (``GPTPagedLM`` contract). ``spec_k`` > 0 with a draft enables
    speculative decoding (greedy only — temperature sampling with a
    draft raises, the acceptance rule here is the deterministic
    argmax-match variant).

    A model with a token head (``forward_token``) is decoded greedily
    without a wait between two forwards (``_plain_loop``): the forward
    chooses the next token in its program, the next step is fed that
    device array, and the host reads ids and expert loads one forward
    behind. Chosen from what the engine sees (`temperature` <= 0, no
    speculative rounds, no ``block_length``, the head), by no flag.
    Temperature sampling, speculative rounds and ``serving.DecodeLoop``
    read a forward's own outputs and wait for it.
    Every wait for the device lies inside a timed region, so
    ``last_stats["prefill_seconds"]`` + ``["decode_seconds"]`` is the
    call's time, and the device's.

    A model that declares ``block_length`` is decoded by blocks
    (``_block_loop``): ``denoise_steps`` forwards a block at most
    (default: the block length, one position a step), greedy, no draft,
    every forward fed on the device but a block's first and read one
    forward behind;
    ``prefill_chunk`` is then a multiple of the block length, as a chunk
    must not split a block.
    """

    def __init__(self, model, cache, draft=None, draft_cache=None,
                 spec_k=None, prefill_chunk=None, temperature=0.0,
                 seed=0, name="gpt", denoise_steps=None):
        if (draft is None) != (draft_cache is None):
            raise ValueError("draft model and draft cache come together")
        self.model = model
        self.cache = cache
        self.draft = draft
        self.draft_cache = draft_cache
        self.spec_k = (spec_k if spec_k is not None
                       else _env_int("MXTPU_GEN_SPEC_K", 4))
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else default_prefill_chunk())
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.temperature = float(temperature)
        self.name = name
        self._rng = np.random.RandomState(seed)
        if self.draft is not None and self.temperature > 0:
            raise ValueError(
                "speculative decoding is greedy-only: the accept rule "
                "compares draft tokens to target argmax; run with "
                "temperature=0 or drop the draft model")
        self.block_length = int(getattr(model, "block_length", 0) or 0)
        self.denoise_steps = int(denoise_steps or self.block_length)
        if self.block_length:
            if self.draft is not None or self.temperature > 0:
                raise ValueError(
                    "block decoding is greedy and takes no draft model: "
                    "a denoising forward fixes argmax tokens by their "
                    "confidence")
            if self.prefill_chunk % self.block_length:
                raise ValueError(
                    "prefill_chunk (%d) must be a multiple of the model's "
                    "block_length (%d): a chunk must not split a block"
                    % (self.prefill_chunk, self.block_length))
            if self.denoise_steps < 1:
                raise ValueError("denoise_steps must be >= 1")
        window = int(getattr(model, "window", 0) or 0)
        if window:
            if self.draft is not None:
                raise ValueError(
                    "a model that closes windows takes no draft model: a "
                    "rejected suffix may lie across a closing, which the "
                    "cache does not roll back")
            if window % self.prefill_chunk:
                raise ValueError(
                    "prefill_chunk (%d) must divide the model's window "
                    "(%d): a chunk must not straddle a closing"
                    % (self.prefill_chunk, window))
        self.last_stats = {}
        # a model with an expert layer, a latent cache or windows that
        # close: every forward of its is tallied into the call's
        # ``last_stats["moe"]`` / ``["mla"]`` / ``["eva"]``
        self._tallies = {}
        self._phase = "prefill"     # of the forward in flight: ``_run``
        self._note = (self._note_forward
                      if any(hasattr(model, said) for said in (
                          "last_expert_loads", "last_latent_path",
                          "last_eva")) else None)

    # ---------------------------------------------------------- plumbing
    def _note_forward(self, model, read=None, closed=None):
        """What a forward of `model`'s says of itself, into this call's
        ``last_stats``. `closed`: what a commit's window closings counted
        (``EvaPagedLM.close_windows``), into ``"eva"`` under the phase.
        Right after a forward (`read` None): ``last_eva`` (the rows it
        read) likewise with the forward counted, ``last_latent_path``
        (the attention path over a latent cache and the cached rows it
        expanded, or walked) into ``"mla"``, and ``last_expert_loads``
        (layers, experts: the routes each expert got) into ``"moe"`` if the
        forward fetched them. One that fetched nothing left them on the device:
        they are tallied from `read`, its outputs as the host fetched
        them a forward later."""
        split = getattr(model, "split_loads", None)
        eva = closed
        if closed is None and read is None and getattr(model, "last_eva",
                                                       None):
            eva = {"forwards": 1, **model.last_eva}
        for key, count in (eva or {}).items():
            self._tallies["eva"][self._phase][key] += count
            getattr(_cat, "eva_" + key).inc(count, model=self.name,
                                            phase=self._phase)
        if closed is not None:
            return
        if read is None:
            path = getattr(model, "last_latent_path", None)
            if path is not None:
                mla = self._tallies["mla"]
                for key, count in {path[0] + "_forwards": 1,
                                   **path[1]}.items():
                    mla[key] += count
                    getattr(_cat, "mla_" + key).inc(count, model=self.name)
            loads = getattr(model, "last_expert_loads", None)
        else:
            loads = read.get("expert_loads")
        if loads is None:
            return
        loads, share = split(loads) if split else (loads, {})
        moe = self._tallies["moe"]
        routes, hit = int(loads.sum()), int((loads > 0).sum())
        # the totals, and the same a second time under the forward's phase
        for tally in (moe, moe["by_phase"][self._phase]):
            tally["forwards"] += 1
            tally["routes"] += routes
            tally["experts_hit"] += hit
            for key, count in share.items():
                tally[key] = tally.get(key, 0) + count
        _cat.moe_routes.inc(routes, model=self.name, phase=self._phase)
        _cat.moe_experts_hit.inc(hit, model=self.name, phase=self._phase)
        for key, count in share.items():
            getattr(_cat, "moe_" + key).inc(count, model=self.name)
        # a share's layer may get no route at all: it has no fullest expert
        routed = loads[loads.sum(axis=1) > 0]
        if len(routed):
            uneven = float(np.mean(routed.max(axis=1) / routed.mean(axis=1)))
            moe["load_max_over_mean"].append(uneven)
            _cat.moe_load_max_over_mean.observe(uneven, model=self.name)

    def _sample(self, logits_row):
        if self.temperature <= 0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / self.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    # ---------------------------------------------------------- generate
    def generate(self, prompts, max_new_tokens, eos_id=None):
        """Generate continuations for ``prompts`` (lists of int token
        ids); returns a list of generated-token lists (prompt excluded).
        Stats for the run land in ``self.last_stats``. Where spans are
        real the call is one ``gen.call`` span whose children are
        ``gen.admit`` (the prompts checked and given their slots), the
        regions (``gen.prefill``, then ``gen.decode_step`` or
        ``gen.block``) and ``gen.release`` (the slots freed)."""
        prompts = [list(map(int, p)) for p in prompts]
        if not prompts:
            return []
        # a child of the caller's span, or (metrics on, no caller's span) a
        # root, whose journey ``tracing`` keeps whole
        with _tr.span("gen.call", model=self.name, rows=len(prompts),
                      prompt_tokens=sum(map(len, prompts)),
                      max_new_tokens=max_new_tokens) as call:
            seqs = []      # per sequence: dict(ctx, slot, dslot, out, done)
            try:
                with _tr.span("gen.admit"):
                    stats = self._admit(prompts, max_new_tokens, seqs)
                self._run(seqs, max_new_tokens, eos_id, stats)
                call.set_attr("tokens_committed", stats["decode_tokens"])
                self.last_stats = stats
                return [s["out"] for s in seqs]
            finally:
                with _tr.span("gen.release"):
                    for s in seqs:
                        if (s["slot"] is not None
                                and s["slot"] in self.cache._live):
                            self.cache.free(s["slot"])
                        if (s["dslot"] is not None
                                and s["dslot"] in self.draft_cache._live):
                            self.draft_cache.free(s["dslot"])

    def _admit(self, prompts, max_new_tokens, seqs):
        """Check that every prompt fits, open the call's tallies and give
        each prompt its cache slots, appended to `seqs` one by one (the
        caller frees what was given before a refusal). -> the call's
        ``stats``."""
        for p in prompts:
            if not p:
                raise ValueError("empty prompt")
            need = len(p) + max_new_tokens
            if self.block_length:       # the last block is stored whole
                need = -(-need // self.block_length) * self.block_length
            if need > self.cache.max_len:
                raise ValueError(
                    "prompt (%d) + max_new_tokens (%d) exceeds cache "
                    "max_len (%d)" % (len(p), max_new_tokens,
                                      self.cache.max_len))
        stats = {"prefill_seconds": 0.0, "decode_seconds": 0.0,
                 "prefill_tokens": 0, "decode_tokens": 0,
                 "proposed": 0, "accepted": 0}
        if hasattr(self.model, "last_expert_loads"):
            stats["moe"] = {"forwards": 0, "routes": 0, "experts_hit": 0,
                            "load_max_over_mean": [],
                            "by_phase": {phase: dict.fromkeys(
                                ("forwards", "routes", "experts_hit"), 0)
                                for phase in ("prefill", "decode")}}
        if hasattr(self.model, "last_latent_path"):
            stats["mla"] = dict.fromkeys(
                ("absorbed_forwards", "expanded_forwards",
                 "expanded_kernel_forwards", "expanded_rows",
                 "absorbed_rows_live", "absorbed_rows_read"), 0)
        if hasattr(self.model, "last_eva"):
            stats["eva"] = {phase: dict.fromkeys(
                ("forwards", "windows_closed", "summary_rows_written",
                 "window_rows_read", "summary_rows_read", "positions"), 0)
                for phase in ("prefill", "decode")}
        self._tallies = stats
        for p in prompts:
            slot = self.cache.alloc()
            if slot is None:
                raise ValueError("no free KV slot for prompt %d"
                                 % len(seqs))
            dslot = None
            if self.draft is not None:
                dslot = self.draft_cache.alloc()
                if dslot is None:
                    raise ValueError("no free draft KV slot")
            seqs.append({"ctx": list(p), "slot": slot, "dslot": dslot,
                         "out": [], "done": False})
        return stats

    def _run(self, seqs, max_new_tokens, eos_id, stats):
        """The admitted call: every prompt's prefill, then the decode loop
        the model and the engine's options choose. ``self._phase`` says
        which of the two a forward belongs to (``_note_forward``'s tallies
        by phase): what a forward leaves on the device is read before its
        phase ends (``prefill_slot`` reads the last chunk's loads before it
        returns, the loops their last step's), so a tally is its forward's
        phase's."""
        # prefill: commit ctx[:-1]; the last prompt token is fed by
        # the first decode step (its logits choose token 1). Each
        # region (a prompt's prefill, a decode step or round) is
        # timed once: its span, its histogram and last_stats hold
        # that one reading.
        # A block model prefills the prompt's WHOLE blocks, with the
        # forward that skips the head; the tail opens the first
        # generated block. A prefill waits for no commit, so the last
        # region waits for the last: the device's time for the prompts
        # lies inside the regions that launched it.
        B = self.block_length
        self._phase = "prefill"
        for s in seqs:
            n = len(s["ctx"]) // B * B if B else len(s["ctx"]) - 1
            with _tr.span("gen.prefill", model=self.name,
                          slot=s["slot"], tokens=max(n, 0)) as sp:
                t0 = time.monotonic()
                if n > 0:
                    prefill_slot(self.model, self.cache, s["slot"],
                                 s["ctx"][:n], self.prefill_chunk,
                                 self._note)
                    if self.draft is not None:
                        prefill_slot(self.draft, self.draft_cache,
                                     s["dslot"], s["ctx"][:n],
                                     self.prefill_chunk)
                    stats["prefill_tokens"] += n
                if s is seqs[-1]:
                    with _tr.span("kv.sync"):
                        self.cache.sync()
                        if self.draft is not None:
                            self.draft_cache.sync()
                dt = time.monotonic() - t0
                sp.set_duration(dt)
            stats["prefill_seconds"] += dt
            _cat.gen_prefill_seconds.observe(dt, model=self.name)

        self._phase = "decode"
        if B:
            self._block_loop(seqs, max_new_tokens, eos_id, stats)
        elif self.draft is not None and self.spec_k > 0:
            for s in seqs:
                self._speculative_loop(s, max_new_tokens, eos_id, stats)
        else:
            self._plain_loop(seqs, max_new_tokens, eos_id, stats)
        _cat.gen_tokens_committed.inc(
            stats["prefill_tokens"], model=self.name, phase="prefill")
        _cat.gen_tokens_committed.inc(
            stats["decode_tokens"], model=self.name, phase="decode")

    # ------------------------------------------------------ plain decode
    def _plain_loop(self, seqs, max_new_tokens, eos_id, stats):
        """Batched autoregressive decode: one (S, 1) forward per step
        over the still-active rows.

        A greedy call over an adapter with a token head waits for nothing
        between two forwards. The forward chooses each row's next token
        in its program (``forward_token``) and the next step is fed that
        DEVICE array; the host fetches a forward's ids and expert loads
        one forward behind, after the next forward and its commit are
        launched, and only then appends them, tests `eos_id` and tallies
        the loads. A step after which the rows change (one has its
        ``max_new_tokens``, one was found to have emitted `eos_id`, none
        is left) fetches its own ids too before its region closes, and
        the step after it is fed from the host: the one wait, inside a
        timed region as every wait is. A row found to have stopped at
        step n was fed once more by then: that token is dropped, and its
        cache row lies under ``max_len`` (the row stopped short of
        ``max_new_tokens``; one that has them is never launched again).

        Temperature sampling draws from the logits on the host
        (``_sample``, the engine's ``RandomState`` stream), as does an
        adapter without the head: a step then waits for its forward.

        ``stats`` gains ``decode_steps`` and ``decode_steps_fed_on_device``
        (of a call with no stop token: all but the first)."""
        on_device = (self.temperature <= 0
                     and hasattr(self.model, "forward_token"))
        stats["decode_steps"] = stats["decode_steps_fed_on_device"] = 0

        def take(rows, tokens):
            """`tokens`: one a row of `rows`, for those still going."""
            taken = 0
            for s, tok in zip(rows, tokens):
                if s["done"]:
                    continue
                s["ctx"].append(tok)
                s["out"].append(tok)
                taken += 1
                if tok == eos_id or len(s["out"]) >= max_new_tokens:
                    s["done"] = True
            stats["decode_tokens"] += taken
            return taken

        def ids_of(read):
            host = _fetch_behind(self.model, read, self._note)
            return host["token"].ravel().tolist() if host else []

        live, read = [], {}     # the last forward's rows, its ids if unread
        while True:
            if read:    # the same rows, fed what their forward chose
                tokens, fed = read["token"], "device"
            else:
                live = [s for s in seqs if not s["done"]]
                if not live:
                    return
                tokens, fed = np.asarray([[s["ctx"][-1]] for s in live],
                                         np.int32), "host"
            slots = [s["slot"] for s in live]
            with _tr.span("gen.decode_step", model=self.name,
                          rows=len(live), fed=fed) as sp:
                t0 = time.monotonic()
                if on_device:
                    behind = read
                    read, *new = forward_slots(
                        self.model, self.cache, slots, tokens,
                        self.model.forward_token, self._note)
                    commit_slots(self.cache, slots, *new, 1,
                                 adapter=self.model, note=self._note)
                    committed = take(live, ids_of(behind))
                    # with the token in flight: do the same rows go on?
                    if any(s["done"] or len(s["out"]) + 1 >= max_new_tokens
                           for s in live):
                        committed += take(live, ids_of(read))
                        read = {}
                else:
                    logits = step_slots(self.model, self.cache, slots,
                                        tokens, note=self._note)
                    committed = take(live, [self._sample(row)
                                            for row in logits])
                sp.set_attr("tokens_committed", committed)
                dt = time.monotonic() - t0
                sp.set_duration(dt)
            stats["decode_seconds"] += dt
            stats["decode_steps"] += 1
            stats["decode_steps_fed_on_device"] += fed == "device"
            _cat.gen_decode_seconds.observe(dt, model=self.name)
            _cat.gen_decode_steps.inc(model=self.name, fed=fed)

    # ------------------------------------------------------ block decode
    @staticmethod
    def _fix_most_confident(masked, confidence, steps_left):
        """The static low-confidence schedule: of a row's still-masked
        positions the ``ceil(masked / steps_left)`` most confident are
        fixed by this forward (ties: the leftmost). masked (R, B) bool,
        confidence (R, B) -> fixed (R, B) bool.

        One definition for numpy arrays and for the denoising program,
        which traces it (``SDARPagedLM.forward_denoise``). A position's
        rank is the count of positions ahead of it by a (B, B) comparison:
        a smaller key, or an equal key further left, where the key is
        ``-confidence`` and a position not masked comes last; that is a
        stable argsort's rank, bit for bit."""
        xp = np if isinstance(confidence, np.ndarray) else jnp
        count = -(-masked.sum(axis=1) // steps_left)
        key = xp.where(masked, -confidence, xp.inf)
        left = xp.arange(key.shape[1])
        ahead = ((key[:, None, :] < key[:, :, None])
                 | ((key[:, None, :] == key[:, :, None])
                    & (left[None, :] < left[:, None])))
        rank = ahead.sum(axis=2)
        return masked & (rank < count[:, None])

    def _block_loop(self, seqs, max_new_tokens, eos_id, stats):
        """Block-diffusion decode: a step commits a block, not a token.

        All live rows advance by one block of B positions a round, each
        at its own absolute positions (``cache.lengths``, a multiple of
        B). A row's first block opens with its prompt's tail as fixed
        tokens. ``denoise_steps`` forwards at most fix the masked
        positions (a row with none left rides along) and store nothing;
        one forward over the final tokens commits the block's K and V.
        A row returns exactly ``max_new_tokens`` tokens (fewer after
        `eos_id`): the last block is denoised and stored whole and cut
        on the way out.

        The host runs one forward ahead of the device's results. A
        block's first denoising forward is fed from the host; the
        program applies the schedule (``forward_denoise``) and the next
        denoising forward, and then the store pass, are fed the tokens
        and mask it left on the device. A forward's choice and expert
        loads are fetched after the next forward (and a store pass's
        commit) is launched, and only then go into the block's record.
        Whether a denoising forward follows is known without a read: the
        schedule fixes ``ceil(m / steps left)`` of a row's m masked
        positions. A block's last denoising forward is read after its
        store pass is launched, so its tokens reach the rows (and
        `eos_id` ends them) before the next block is built; the store
        pass's loads are read after the next block's first forward is
        launched, or, after the call's last block, inside its region.

        ``stats`` gains ``block_forwards`` by phase,
        ``block_forwards_launched_ahead`` (those launched while the
        forward before them was unread: all but a call's first),
        ``block_row_forwards`` (rows summed over forwards),
        ``block_positions_committed`` and ``blocks``: a record a round of
        what every forward was given, chose and fixed (docs/GENERATE.md).
        """
        B, mask_id = self.block_length, self.model.mask_id
        stats.update(block_forwards={"denoise": 0, "store": 0},
                     block_forwards_launched_ahead=0, block_row_forwards=0,
                     block_positions_committed=0, blocks=[])
        for index, s in enumerate(seqs):
            s["index"] = index
            s["open"] = s["ctx"][len(s["ctx"]) // B * B:]   # prompt's tail
        # the forward launched last and not read yet: (its read, the record
        # of a denoising forward's block; None for a store pass), and the
        # block as that denoising forward was fed it, on the host
        unread, host = None, {}

        def read_behind():
            nonlocal unread
            if unread is None:
                return
            (read, record), unread = unread, None
            got = _fetch_behind(self.model, read, self._note)
            if record is not None:
                tokens, masked = host["tokens"], host["masked"]
                fixed = masked & ~got["masked"]
                record["steps"].append(
                    {"tokens": tokens, "masked": masked, "fixed": fixed,
                     "x0": got["x0"], "confidence": got["confidence"]})
                host.update(tokens=np.where(fixed, got["x0"], tokens),
                            masked=got["masked"])

        def count(phase, ahead, rows):
            stats["block_forwards"][phase] += 1
            stats["block_forwards_launched_ahead"] += ahead
            stats["block_row_forwards"] += rows
            _cat.gen_block_forwards.inc(model=self.name, phase=phase,
                                        ahead=str(ahead).lower())

        while True:
            live = [s for s in seqs if not s["done"]]
            if not live:
                return
            slots = [s["slot"] for s in live]
            rows = len(live)
            with _tr.span("gen.block", model=self.name, rows=rows) as bsp:
                t0 = time.monotonic()
                tokens = np.full((rows, B), mask_id, np.int32)
                masked = np.ones((rows, B), bool)
                for r, s in enumerate(live):
                    tokens[r, :len(s["open"])] = s["open"]
                    masked[r, :len(s["open"])] = False
                host.update(tokens=tokens, masked=masked)
                left = masked.sum(axis=1)       # a row's masked positions
                record = {"rows": [s["index"] for s in live],
                          "starts": [int(self.cache.lengths[slot])
                                     for slot in slots],
                          "steps": []}
                for step in range(self.denoise_steps):
                    if not left.any():
                        break
                    steps_left = self.denoise_steps - step
                    fixing = -(-left // steps_left)
                    ahead = unread is not None
                    with _tr.span("gen.denoise_step", model=self.name,
                                  rows=rows, ahead=ahead) as sp:
                        t1 = time.monotonic()
                        read, tokens, masked = forward_slots(
                            self.model, self.cache, slots, tokens,
                            functools.partial(self.model.forward_denoise,
                                              masked=masked,
                                              steps_left=steps_left),
                            self._note)
                        read_behind()
                        unread = (read, record)
                        sp.set_attr("fixed", int(fixing.sum()))
                        sp.set_duration(time.monotonic() - t1)
                    left = left - fixing
                    count("denoise", ahead, rows)
                ahead = unread is not None
                with _tr.span("gen.block_store", model=self.name,
                              rows=rows, ahead=ahead) as sp:
                    t1 = time.monotonic()
                    read, *new = forward_slots(
                        self.model, self.cache, slots, tokens,
                        self.model.forward_kv, self._note)
                    commit_slots(self.cache, slots, *new, B)
                    read_behind()   # the block's last denoising forward
                    unread = (read, None)
                    sp.set_duration(time.monotonic() - t1)
                count("store", ahead, rows)
                stats["block_positions_committed"] += rows * B
                _cat.gen_block_positions_committed.inc(rows * B,
                                                       model=self.name)
                tokens = record["final"] = host["tokens"]
                stats["blocks"].append(record)
                for r, s in enumerate(live):
                    for tok in tokens[r, len(s["open"]):].tolist():
                        s["ctx"].append(tok)
                        s["out"].append(tok)
                        stats["decode_tokens"] += 1
                        if tok == eos_id or len(s["out"]) >= max_new_tokens:
                            s["done"] = True
                            break
                    s["open"] = []
                if all(s["done"] for s in seqs):
                    read_behind()   # the call's last read, in its region
                bsp.set_attr("tokens_committed", rows * B)
                dt = time.monotonic() - t0
                bsp.set_duration(dt)
            stats["decode_seconds"] += dt
            _cat.gen_decode_seconds.observe(dt, model=self.name)

    # ------------------------------------------------ speculative decode
    def _speculative_loop(self, s, max_new_tokens, eos_id, stats):
        """Draft-propose / target-verify rounds for ONE sequence.

        Cache invariants between rounds, with n = len(ctx):
        target cache holds exactly n-1 committed positions; draft cache
        holds n-1 or n+k-1 capped by truncation to n-1 ... self-healed
        by the catch-up loop, which feeds ctx[m:] and whose final feed
        (always ctx[-1]) yields the draft's first proposal.
        """
        ctx, slot, dslot = s["ctx"], s["slot"], s["dslot"]
        while not s["done"]:
            t0 = time.monotonic()
            n = len(ctx)
            # per-round proposal width: never commit past
            # max_new_tokens (a round lands at most k+1 tokens) and
            # never let the k+1-wide verify overflow the cache (it
            # commits k+1 entries onto the target's n-1). k == 0
            # degenerates to a plain 1-wide target step — the final
            # round when one token remains.
            remaining = max_new_tokens - len(s["out"])
            k = max(0, min(self.spec_k, remaining - 1,
                           self.cache.max_len - n))
            drafts = []
            if k > 0:
                # 1) draft catch-up: feed every committed token the
                #    draft cache is missing; the last feed (always
                #    ctx[-1]) returns d_1's logits
                m = int(self.draft_cache.lengths[dslot])
                d_logits = None
                while m < n:
                    d_logits = step_slots(
                        self.draft, self.draft_cache, [dslot],
                        np.asarray([[ctx[m]]], np.int32))[0]
                    m += 1
                # 2) propose d_2..d_k autoregressively
                drafts.append(int(np.argmax(d_logits)))
                for _ in range(k - 1):
                    d_logits = step_slots(
                        self.draft, self.draft_cache, [dslot],
                        np.asarray([[drafts[-1]]], np.int32))[0]
                    drafts.append(int(np.argmax(d_logits)))
            # 3) target verifies all k in ONE (1, k+1) forward; row j
            #    is the target's next-token distribution after
            #    ctx + drafts[:j]
            verify = np.asarray([[ctx[-1]] + drafts], np.int32)
            logits, *new = forward_slots(self.model, self.cache, [slot],
                                         verify, note=self._note)
            commit_slots(self.cache, [slot], *new, k + 1)
            target = [int(np.argmax(logits[0, j])) for j in range(k + 1)]
            # 4) longest accepted prefix + the target's own token
            a = 0
            while a < k and drafts[a] == target[a]:
                a += 1
            commit = drafts[:a] + [target[a]]
            stats["proposed"] += k
            stats["accepted"] += a
            _cat.gen_spec_proposed.inc(k, model=self.name)
            _cat.gen_spec_accepted.inc(a, model=self.name)
            # 5) roll both caches back to the committed history: the
            #    target holds n+k (ctx[-1] + k drafts), the draft n+k-1
            for tok in commit:
                ctx.append(tok)
                s["out"].append(tok)
                stats["decode_tokens"] += 1
                if tok == eos_id or len(s["out"]) >= max_new_tokens:
                    s["done"] = True
                    break
            self.cache.truncate(slot, len(ctx) - 1)
            self.draft_cache.truncate(dslot, len(ctx) - 1)
            dt = time.monotonic() - t0
            stats["decode_seconds"] += dt
            _cat.gen_decode_seconds.observe(dt, model=self.name)
            cur = _tr.current()
            if cur is not None:
                # one span per propose+verify round, carrying the spec
                # accounting the journey timeline reports
                t1w = time.time()
                _tr.record_span(
                    "gen.decode_step", cur.trace_id,
                    parent_id=cur.span_id, t0=t1w - dt, t1=t1w,
                    sampled=cur.sampled, model=self.name, speculative=True,
                    proposed=k, accepted=a, tokens_committed=len(commit))
