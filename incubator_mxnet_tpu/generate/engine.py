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
rejected suffixes, close full windows); the model adapter is a pure
shape-cached forward. The K/V pools live on the device (``paged_kv``): a
forward is given them where it runs, returns the chunk's additions as
device arrays, and ``cache.commit`` stores those there; the host fetches
only what it reads.

THE ADAPTER CONTRACT. A model reaches the engine as a ``PagedLM``
(``adapters.py``; ``GPTPagedLM``, ``SDARPagedLM``, ``MLAPagedLM``,
``EvaPagedLM``, ``OuroPagedLM`` and ``NemotronHPagedLM`` are re-exported
here), and the engine names no model:

- a forward takes ``(tokens, *cache.forward_inputs(slots, fed))``: tokens
  (S, C), a host or a device array, each cache group's host lengths and
  tables, the pools (device arrays, not shipped); a cache with state
  entries adds the rows' slots, ``fed`` (each row's real chunk positions)
  and its state pools, which the forward writes in place and returns
  after its kv additions;
- heads: ``forward`` -> ``(logits (S, C, V), *new)``, the logits
  fetched; ``forward_token`` -> ``(read, *new)``, ``read["token"]`` (S, 1)
  int32 the last position's argmax (where ``"token"`` is in ``heads``);
  ``forward_kv`` -> ``(read, *new)``, the prefill's, without a head where
  the model has the ``"none"`` one. `new`: what the chunk adds to each
  cache entry, stacked over layers, on the device for ``cache.commit``;
  `read`: device arrays the host fetches when it will, one forward behind
  (``"expert_loads"``, ``"window_rows"``, ``"reached"``). One jitted
  program a ``(head, fresh)`` and (S, C);
- declarations the loops read: ``num_layers``, ``kv_entries``,
  ``kv_groups``; ``block_length``, ``mask_id`` and ``forward_denoise``
  (the block loop); ``window`` and ``close_windows`` (closings after a
  commit);
- tallies: ``tallies()`` the ``last_stats`` entries a call opens;
  ``tally(stats, phase, name, read=, committed=)`` what a forward (or a
  commit's window closings), a read behind or a commit adds to them and
  to the catalog's counters; ``span_attrs`` / ``prefill_attrs`` what the
  spans carry.

Hooks that ``tests/benchmark/`` patches, which stay where they are:
``EvaPagedLM._call(self, head, tokens, wlen, wtables, slen, *rest)``, the
one path of every Eva forward; ``SDARPagedLM.forward_kv(self, tokens,
*rest)``, the prefill's and the block loop's store pass;
``GenerateEngine._fix_most_confident``, looked up when the denoising
program is traced; and the model functions, looked up by module
attribute when a program is traced.

Adding a model: its file under ``models/`` (a paged forward), one
``PagedLM`` subclass in ``adapters.py`` (config, heads, cache entries,
``outputs``, its host counts and tallies) and its counters in
``telemetry/catalog.py``; nothing in this module. What this module knows
of state entries: ``forward_slots(fed=)`` hands the cache each row's real
positions (a prefill round's counts), ``position_bytes`` counts the layers
an entry holds (``kv_layers``), and a model with state entries takes no
draft.

Between two forwards of a greedy plain decode nothing is waited for
(``_plain_loop``): the adapter's ``forward_token`` chooses the next token
in the forward's program and leaves it on the device, the next forward
is fed that array, and the host reads ids and expert loads ONE forward
behind, after it has launched the next. The prefill goes in ROUNDS
(``prefill_slots``): one forward feeds several prompts their next chunk,
each prompt's chunks in order, and is read the same way, one round
behind.

The step itself is four functions over (adapter, cache, slots), the one
implementation under this engine's loops and under ``serving.DecodeLoop``
(``generate/family.py`` calls them): ``forward_slots`` asks the cache for
a forward's inputs and calls the adapter, ``commit_slots`` stores what it
returned, ``step_slots`` is both for one token a slot, ``prefill_slots``
both for rounds of several prompts' padded chunks (``prefill_slot``: one
prompt).

A model that declares a ``block_length`` (``SDARPagedLM``) is a
block-diffusion decoder, and the engine drives it by blocks, not tokens
(``_block_loop``; docs/GENERATE.md):

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

import jax.numpy as jnp
import numpy as np

from ..telemetry import catalog as _cat
from ..telemetry import tracing as _tr
from . import adapters as _ad
from .adapters import (EvaPagedLM, GPTPagedLM, MLAPagedLM,
                       NemotronHPagedLM, OuroPagedLM, SDARPagedLM)
from .paged_kv import _env_int

__all__ = ["GenerateEngine", "GPTPagedLM", "SDARPagedLM", "MLAPagedLM",
           "EvaPagedLM", "OuroPagedLM", "NemotronHPagedLM"]


def default_prefill_chunk():
    """``MXTPU_GEN_PREFILL_CHUNK`` (32): the chunk width of the engine and
    of the serving family, read here alone."""
    return _env_int("MXTPU_GEN_PREFILL_CHUNK", 32)


def _fetch_behind(adapter, read, note):
    """`read` (what a forward that fetched nothing left on the device for
    the host: a dict of arrays, ``{}`` where there is no such forward) as
    host arrays, fetched once a later forward is launched; `note` is
    told (the expert loads' tally, one forward late)."""
    host = _ad._fetch(read)
    if note is not None and host:
        note(adapter, host)
    return host


def forward_slots(adapter, cache, slots, tokens, call=None, note=None,
                  pads=0, fed=None):
    """One adapter forward for `slots` (list) feeding `tokens` (S, C);
    returns ``(logits, *new)``, `new` what the chunk adds to each cache
    entry (new_k, new_v), WITHOUT committing. `call`: another forward of
    the adapter's with the same arguments (the block loop's
    ``forward_denoise`` / ``forward_kv``, the plain loop's
    ``forward_token``); `tokens` may then be a device array. `note`:
    called with the adapter after the forward (the engine's tallies of
    what it last did, ``adapter.tally``). `pads`: the last rows are that
    many pad rows of a prefill round, given length 0 in every group, so
    they read nothing of the cache (and are committed nothing). `fed`: the
    real chunk positions of each row (a prefill round's counts; None:
    every position), which a cache with state entries hands the forward,
    so that a row's padding moves no state."""
    with _tr.span("kv.gather"):
        inputs = cache.forward_inputs(
            slots, tokens.shape[1] if fed is None else fed)
        if pads:
            for lengths in inputs:      # the (S,) host arrays: a group's
                if isinstance(lengths, np.ndarray) and lengths.ndim == 1:
                    lengths[len(lengths) - pads:] = 0
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
    written an entry. `adapter`: the forward's; `note` is told the
    positions stored, and an adapter that declares ``close_windows`` is
    asked to close the windows this commit filled (a ``gen.window_close``
    span a closing, of the engine's `phase`; `note` is told after a
    closing)."""
    count = new_and_count[-1]
    rows = (len(slots) * count if isinstance(count, int)
            else int(np.sum(count)))
    with _tr.span("kv.commit", rows=rows):
        cache.commit(slots, *new_and_count)
    if note is not None:
        note(adapter, committed=rows)
    close = getattr(adapter, "close_windows", None)
    if close is not None:   # a prefill round's pad rows repeat a slot
        if close(cache, list(dict.fromkeys(slots)), phase) and note:
            note(adapter)


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


#: the widest prefill forward, in positions, that a family compiled at one
#: prompt a forward (EvaByte's whole window of 2,048 bytes): a round of a
#: batched prefill is never wider
PREFILL_ROUND_POSITIONS = 2048
#: the cache rows a round's forward returns, in bytes: a temporary in device
#: memory until the round's commit has stored it
PREFILL_ROUND_BYTES = 128 << 20


def position_bytes(adapter):
    """What a prefill chunk adds to `adapter`'s cache a position, in bytes:
    every entry of the group a chunk writes (a grouped cache's leading
    group, ``kv_groups``; every entry where there are none), over the
    layers that hold it (``kv_layers``; every layer where it names none).
    State entries add no rows."""
    groups = getattr(adapter, "kv_groups", None)
    written = next(iter(groups.values())) if groups else adapter.kv_entries
    layers = getattr(adapter, "kv_layers", None) or {}
    return sum(
        len(layers.get(name, range(adapter.num_layers)))
        * int(np.prod(shape)) * np.dtype(dtype).itemsize
        for name, (shape, dtype) in adapter.kv_entries.items()
        if name in written)


def prefill_rows(chunk, per_position, prompts):
    """R, the prompts a round of a batched prefill feeds: at most the
    `prompts` that have a chunk to feed, as many `chunk`-wide rows as
    ``PREFILL_ROUND_POSITIONS`` holds, and as many as return at most
    ``PREFILL_ROUND_BYTES`` of new cache rows at `per_position` bytes a
    position (``position_bytes``); one at the least."""
    return min(prompts, max(1, PREFILL_ROUND_POSITIONS // chunk),
               max(1, PREFILL_ROUND_BYTES // (chunk * per_position)))


def prefill_rounds(chunks, rows):
    """The rounds of a batched prefill of prompts that need `chunks` (a
    count a prompt) chunk forwards, at most `rows` prompts a round: a list
    of rounds, each the prompts (indices into `chunks`) it feeds their next
    chunk. A round takes the prompts with the most chunks left (the earlier
    of equals first), so that a long prompt does not end the prefill alone;
    every round is full but those at the tail."""
    left, rounds = list(chunks), []
    while any(left):
        taken = sorted((i for i, n in enumerate(left) if n),
                       key=lambda i: -left[i])[:rows]
        for i in taken:
            left[i] -= 1
        rounds.append(taken)
    return rounds


def prefill_slots(adapter, cache, slots, prompts, chunk, rows, note=None):
    """Batched chunked prompt ingestion: commit K/V for every token of
    `prompts` (prompt i into ``slots[i]``) in ROUNDS (``prefill_rounds``),
    each ONE `chunk`-wide forward of ``rows`` rows that feeds up to `rows`
    prompts their next chunk, and one commit with a count a row. A prompt's
    chunks go in order, chunk j + 1 in a later round than chunk j, so the
    cache's lengths and causality hold as at one prompt a forward.

    A chunk is padded AFTER its valid tokens (causality keeps the pads out
    of every valid position's attention window, and they are not
    committed; under a block mask the valid tokens are whole blocks, so
    the pads begin a later block). Every round is fed at the width of the
    first, so a call compiles one prefill program: a round with fewer
    prompts left is padded with rows of count 0 on a fed row's slot that
    read nothing of the cache (``forward_slots(pads=)``). No logits are
    read: the adapter's ``forward_kv``, where it has one, fetches nothing,
    and what it leaves for the host (an expert layer's loads) is read one
    round behind: round k's after round k + 1 and its commit are launched,
    the last round's before the last yield. The last commit is NOT waited
    for (``cache.sync``).

    A generator: runs a round a step and yields the prompt tokens it
    fed."""
    call = getattr(adapter, "forward_kv", None)
    rounds = prefill_rounds([-(-len(p) // chunk) for p in prompts], rows)
    fed, behind = [0] * len(prompts), None
    for k, taken in enumerate(rounds):
        pads = len(rounds[0]) - len(taken)
        tokens = np.zeros((len(taken) + pads, chunk), np.int32)
        counts = np.zeros(len(taken) + pads, np.int64)
        for r, i in enumerate(taken):
            piece = prompts[i][fed[i]:fed[i] + chunk]
            tokens[r, :len(piece)] = piece
            counts[r] = len(piece)
            fed[i] += len(piece)
        fed_slots = [slots[i] for i in taken] + [slots[taken[0]]] * pads
        read, *new = forward_slots(adapter, cache, fed_slots, tokens, call,
                                   note, pads, counts)
        commit_slots(cache, fed_slots, *new, counts, adapter=adapter,
                     phase="prefill", note=note)
        if call is not None:
            if behind is not None:
                _fetch_behind(adapter, behind, note)
            behind = read
        if k == len(rounds) - 1 and behind is not None:
            _fetch_behind(adapter, behind, note)
        yield int(counts.sum())


def prefill_slot(adapter, cache, slot, tokens_1d, chunk, note=None):
    """One prompt's prefill into `slot`: ``prefill_slots`` of one row, a
    round a chunk, in order (``serving.DecodeLoop``'s admission)."""
    for _tokens in prefill_slots(adapter, cache, [slot], [tokens_1d], chunk,
                                 1, note):
        pass


class GenerateEngine:
    """Drives one model (plus optional draft) over paged KV caches.

    model / draft: ``PagedLM`` adapters (the module's contract).
    ``spec_k`` (4) > 0 with a draft enables speculative decoding (greedy
    only — temperature sampling with a draft raises, the acceptance rule
    here is the deterministic argmax-match variant).

    A model with a token head (``"token"`` in ``heads``) is decoded
    greedily without a wait between two forwards (``_plain_loop``): the
    forward chooses the next token in its program, the next step is fed
    that device array, and the host reads ids and expert loads one
    forward behind. Chosen from what the engine sees (`temperature` <= 0, no
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
        self.spec_k = 4 if spec_k is None else spec_k
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
        if self.draft is not None and any(
                getattr(m, "state_entries", None) for m in (model, draft)):
            raise ValueError(
                "a model whose cache holds state entries takes no draft "
                "model: a rejected suffix would have to be rolled out of "
                "its recurrent state, and the cache keeps no snapshots")
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
        self._position_bytes = position_bytes(model)
        self.last_stats = {}
        self._tallies = {}          # the call's stats: what ``_note`` fills
        self._phase = "prefill"     # of the forward in flight: ``_run``

    # ---------------------------------------------------------- plumbing
    def _note(self, model, read=None, committed=None):
        """What a forward of `model`'s, a read of it one forward behind, a
        commit or a commit's closings says of itself (``model.tally``),
        into this call's ``last_stats`` under the phase in flight."""
        model.tally(self._tallies, self._phase, self.name, read, committed)

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
        prefill (``gen.prefill`` a prompt, over the regions
        ``gen.prefill_round`` it leads), the decode regions
        (``gen.decode_step`` or ``gen.block``) and ``gen.release`` (the
        slots freed)."""
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
        stats.update(self.model.tallies())
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
        """The admitted call: every prompt's prefill (``_prefill``: in
        rounds of several prompts a forward, each prompt's chunks in
        order), then the decode loop the model and the engine's options
        choose. ``self._phase`` says which of the two a forward belongs to
        (``_note``'s tallies by phase): what a forward leaves on
        the device is read before its phase ends (``prefill_slots`` reads
        the last round's loads before its last yield, the loops their last
        step's), so a tally is its forward's phase's."""
        self._phase = "prefill"
        self._prefill(seqs, stats)
        self._phase = "decode"
        if self.block_length:
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

    # ----------------------------------------------------------- prefill
    def _prefill(self, seqs, stats):
        """Commit every prompt's ctx[:-1] (a block model's WHOLE blocks:
        the tail opens the first generated block); the last prompt token
        is fed by the first decode step. Prompts with nothing to commit
        are skipped.

        The prompts go in ROUNDS (``prefill_slots``): a round is one
        forward of R rows (``prefill_rows``: from the chunk's width and the
        cache's bytes a position) feeding up to R prompts their next chunk,
        and one commit; the expert loads are read one round behind. A
        round is the timed region, ``gen.prefill_round`` (``rows``: the
        prompts it fed, ``slots``, ``tokens``), timed once: its span,
        ``last_stats["prefill_seconds"]`` and the forward counters hold
        that one reading. A prefill waits for no commit, so the last round
        waits for the last (``kv.sync``): the device's time for the prompts
        lies inside the rounds that launched it. A prompt's prefill is a
        ``gen.prefill`` record (``slot``, ``tokens``; the adapter's
        ``prefill_attrs``), a child of ``gen.call``, from the start of its
        first round to the end of its last round's work (its last chunk's
        commit launched, the round's reads done) and the parent of the
        rounds it leads (their first row); the records of prompts that
        share rounds overlap, and its seconds are the
        ``mxtpu_gen_prefill_seconds`` of a sequence.

        ``stats`` gains ``prefill_forwards`` (rounds) and ``prefill_rows``
        (the prompt chunks they fed, pad rows not counted)."""
        B, chunk = self.block_length, self.prefill_chunk
        stats["prefill_forwards"] = stats["prefill_rows"] = 0
        work = []           # (sequence, the tokens it commits)
        for s in seqs:
            n = len(s["ctx"]) // B * B if B else len(s["ctx"]) - 1
            if n > 0:
                work.append((s, s["ctx"][:n]))
        if not work:
            return
        prompts = [p for _s, p in work]
        chunks = [-(-len(p) // chunk) for p in prompts]
        rows = prefill_rows(chunk, self._position_bytes, len(work))
        rounds = prefill_slots(self.model, self.cache,
                               [s["slot"] for s, _p in work], prompts, chunk,
                               rows, self._note)
        drafts = None if self.draft is None else prefill_slots(
            self.draft, self.draft_cache, [s["dslot"] for s, _p in work],
            prompts, chunk, rows)
        call = _tr.current()        # the gen.call span, where spans are real
        plan = prefill_rounds(chunks, rows)
        left, began, ids = list(chunks), {}, {}
        for k, taken in enumerate(plan):
            sp = _tr.NULL_SPAN
            if call is not None:
                if taken[0] not in ids:
                    ids[taken[0]] = _tr.new_span_id()
                sp = _tr.Span("gen.prefill_round", trace_id=call.trace_id,
                              parent_id=ids[taken[0]], sampled=call.sampled,
                              attrs={"model": self.name, "rows": len(taken)})
            wall0, t0 = time.time(), time.monotonic()
            with sp:
                sp.set_attr("slots", [work[i][0]["slot"] for i in taken])
                sp.set_attr("tokens", next(rounds))
                if drafts is not None:
                    next(drafts)
                if k == len(plan) - 1:
                    with _tr.span("kv.sync"):
                        self.cache.sync()
                        if self.draft is not None:
                            self.draft_cache.sync()
                stats["prefill_forwards"] += 1
                stats["prefill_rows"] += len(taken)
                _cat.gen_prefill_forwards.inc(model=self.name)
                _cat.gen_prefill_rows.inc(len(taken), model=self.name)
                for i in taken:     # the prompts whose last chunk this is
                    began.setdefault(i, (wall0, t0))
                    left[i] -= 1
                    if left[i]:
                        continue
                    s, prompt = work[i]
                    stats["prefill_tokens"] += len(prompt)
                    _cat.gen_prefill_seconds.observe(
                        time.monotonic() - began[i][1], model=self.name)
                    if call is not None:
                        _tr.record_span(
                            "gen.prefill", call.trace_id,
                            parent_id=call.span_id, t0=began[i][0],
                            sampled=call.sampled, span_id=ids.get(i),
                            model=self.name, slot=s["slot"],
                            tokens=len(prompt),
                            **self.model.prefill_attrs(chunks[i]))
                dt = time.monotonic() - t0
                sp.set_duration(dt)
            stats["prefill_seconds"] += dt

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
        on_device = self.temperature <= 0 and "token" in self.model.heads
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
