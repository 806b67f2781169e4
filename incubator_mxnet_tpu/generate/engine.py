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

All cache mutation happens here (append committed K/V, advance,
truncate rejected suffixes); the model adapter is a pure shape-cached
forward. ``GPTPagedLM`` adapts ``models/gpt.py`` to that contract.
"""

import os
import time

import numpy as np

from ..telemetry import catalog as _cat
from ..telemetry import tracing as _tr
from .paged_kv import PagedKVCache

__all__ = ["GenerateEngine", "GPTPagedLM"]


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


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


class GPTPagedLM:
    """Shape-cached jit adapter over ``gpt_forward_paged``.

    ``forward(tokens, lengths, tables, k_pools, v_pools)`` takes numpy
    arrays, returns numpy ``(logits (S, C, V), new_k, new_v)``. One
    XLA program per (S, C) shape — the engine keeps shapes fixed
    (padded prefill chunks, fixed spec width), so steady state is two
    programs: prefill (S, chunk) and decode (S, 1) plus (1, k+1) for
    speculative verify.
    """

    def __init__(self, params, config, use_kernel=False, interpret=False):
        import jax
        import jax.numpy as jnp
        from ..models.gpt import gpt_config, gpt_forward_paged
        self.config = gpt_config(config)
        self.params = {n: jnp.asarray(v) for n, v in params.items()}
        self.num_layers = self.config["num_layers"]

        def pure(params, tokens, lengths, tables, kps, vps):
            return gpt_forward_paged(params, self.config, tokens, lengths,
                                     tables, kps, vps,
                                     use_kernel=use_kernel,
                                     interpret=interpret)
        self._fn = jax.jit(pure)

    def cache_spec(self):
        H = self.config["num_heads"]
        D = self.config["units"] // H
        spec = {}
        for i in range(self.num_layers):
            spec["k%d" % i] = ("kv", (H, D))
            spec["v%d" % i] = ("kv", (H, D))
        return spec

    def make_cache(self, slots, max_len=None, **kw):
        return PagedKVCache(slots, self.cache_spec(),
                            max_len=max_len or self.config["max_len"], **kw)

    def forward(self, tokens, lengths, tables, k_pools, v_pools):
        args = (tokens, lengths, tables, k_pools, v_pools)
        sp = _tr.span("lm.dispatch")
        if sp is not _tr.NULL_SPAN:     # counted only for a real span
            sp.set_attr("h2d_bytes", _host_bytes(args))
        with sp:
            logits, nk, nv = self._fn(self.params, *args)
        with _tr.span("lm.fetch"):
            return (np.asarray(logits), [np.asarray(a) for a in nk],
                    [np.asarray(a) for a in nv])


class GenerateEngine:
    """Drives one model (plus optional draft) over paged KV caches.

    model / draft: adapters with ``num_layers``, ``forward(...)``
    (``GPTPagedLM`` contract). ``spec_k`` > 0 with a draft enables
    speculative decoding (greedy only — temperature sampling with a
    draft raises, the acceptance rule here is the deterministic
    argmax-match variant).
    """

    def __init__(self, model, cache, draft=None, draft_cache=None,
                 spec_k=None, prefill_chunk=None, temperature=0.0,
                 seed=0, name="gpt", use_kernel=False):
        if (draft is None) != (draft_cache is None):
            raise ValueError("draft model and draft cache come together")
        self.model = model
        self.cache = cache
        self.draft = draft
        self.draft_cache = draft_cache
        self.spec_k = (spec_k if spec_k is not None
                       else _env_int("MXTPU_GEN_SPEC_K", 4))
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else _env_int("MXTPU_GEN_PREFILL_CHUNK", 32))
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
        self.last_stats = {}

    # ---------------------------------------------------------- plumbing
    def _forward(self, adapter, cache, slots, tokens):
        """One adapter forward for `slots` (list) feeding `tokens`
        (S, C); returns (logits, new_k, new_v) WITHOUT committing."""
        with _tr.span("kv.gather"):
            lengths = np.asarray([int(cache.lengths[s]) for s in slots],
                                 np.int32)
            tables = cache.tables_array(slots)
            kps = [cache.pool("k%d" % i) for i in range(adapter.num_layers)]
            vps = [cache.pool("v%d" % i) for i in range(adapter.num_layers)]
        return adapter.forward(tokens, lengths, tables, kps, vps)

    def _commit(self, adapter, cache, slots, new_k, new_v, count):
        """Append the first `count` chunk positions of every row (row r
        belongs to ``slots[r]``) into the cache."""
        with _tr.span("kv.commit"):
            for row, slot in enumerate(slots):
                for c in range(count):
                    for i in range(adapter.num_layers):
                        cache.append("k%d" % i, slot, new_k[i][row, c])
                        cache.append("v%d" % i, slot, new_v[i][row, c])
                    cache.advance(slot)

    def _step(self, adapter, cache, slots, tokens, commit=True):
        """Feed one token per slot ((S, 1)); commit K/V; return the
        (S, V) next-token logits."""
        logits, nk, nv = self._forward(adapter, cache, slots, tokens)
        if commit:
            self._commit(adapter, cache, slots, nk, nv, 1)
        return logits[:, -1]

    def _prefill(self, adapter, cache, slot, tokens_1d):
        """Chunked prompt ingestion: commit K/V for every prompt token
        in fixed ``prefill_chunk``-wide forwards (last chunk padded;
        pad positions sit AFTER the valid ones, so causality keeps them
        out of every valid position's attention window and they are
        simply not committed)."""
        n = len(tokens_1d)
        chunk = self.prefill_chunk
        for start in range(0, n, chunk):
            piece = tokens_1d[start:start + chunk]
            valid = len(piece)
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :valid] = piece
            _logits, nk, nv = self._forward(adapter, cache, [slot], padded)
            self._commit(adapter, cache, [slot], nk, nv, valid)

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
        Stats for the run land in ``self.last_stats``."""
        prompts = [list(map(int, p)) for p in prompts]
        if not prompts:
            return []
        for p in prompts:
            if not p:
                raise ValueError("empty prompt")
            if len(p) + max_new_tokens > self.cache.max_len:
                raise ValueError(
                    "prompt (%d) + max_new_tokens (%d) exceeds cache "
                    "max_len (%d)" % (len(p), max_new_tokens,
                                      self.cache.max_len))
        stats = {"prefill_seconds": 0.0, "decode_seconds": 0.0,
                 "prefill_tokens": 0, "decode_tokens": 0,
                 "proposed": 0, "accepted": 0}
        seqs = []      # per sequence: dict(ctx, slot, dslot, out, done)
        try:
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

            # prefill: commit ctx[:-1]; the last prompt token is fed by
            # the first decode step (its logits choose token 1). Each
            # region (a prompt's prefill, a decode step or round) is
            # timed once: its span, its histogram and last_stats hold
            # that one reading.
            for s in seqs:
                with _tr.span("gen.prefill", model=self.name,
                              slot=s["slot"],
                              tokens=max(len(s["ctx"]) - 1, 0)) as sp:
                    t0 = time.monotonic()
                    if len(s["ctx"]) > 1:
                        self._prefill(self.model, self.cache, s["slot"],
                                      s["ctx"][:-1])
                        if self.draft is not None:
                            self._prefill(self.draft, self.draft_cache,
                                          s["dslot"], s["ctx"][:-1])
                        stats["prefill_tokens"] += len(s["ctx"]) - 1
                    dt = time.monotonic() - t0
                    sp.set_duration(dt)
                stats["prefill_seconds"] += dt
                _cat.gen_prefill_seconds.observe(dt, model=self.name)

            if self.draft is not None and self.spec_k > 0:
                for s in seqs:
                    self._speculative_loop(s, max_new_tokens, eos_id,
                                           stats)
            else:
                self._plain_loop(seqs, max_new_tokens, eos_id, stats)
            _cat.gen_tokens_committed.inc(
                stats["prefill_tokens"], model=self.name, phase="prefill")
            _cat.gen_tokens_committed.inc(
                stats["decode_tokens"], model=self.name, phase="decode")
            self.last_stats = stats
            return [s["out"] for s in seqs]
        finally:
            for s in seqs:
                if s["slot"] is not None and s["slot"] in self.cache._live:
                    self.cache.free(s["slot"])
                if (s["dslot"] is not None
                        and s["dslot"] in self.draft_cache._live):
                    self.draft_cache.free(s["dslot"])

    # ------------------------------------------------------ plain decode
    def _plain_loop(self, seqs, max_new_tokens, eos_id, stats):
        """Batched autoregressive decode: one (S, 1) forward per step
        over the still-active rows."""
        while True:
            live = [s for s in seqs if not s["done"]]
            if not live:
                return
            with _tr.span("gen.decode_step", model=self.name,
                          rows=len(live)) as sp:
                t0 = time.monotonic()
                tokens = np.asarray([[s["ctx"][-1]] for s in live],
                                    np.int32)
                logits = self._step(self.model, self.cache,
                                    [s["slot"] for s in live], tokens)
                committed = 0
                for row, s in enumerate(live):
                    tok = self._sample(logits[row])
                    s["ctx"].append(tok)
                    s["out"].append(tok)
                    stats["decode_tokens"] += 1
                    committed += 1
                    if tok == eos_id or len(s["out"]) >= max_new_tokens:
                        s["done"] = True
                sp.set_attr("tokens_committed", committed)
                dt = time.monotonic() - t0
                sp.set_duration(dt)
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
                    d_logits = self._step(
                        self.draft, self.draft_cache, [dslot],
                        np.asarray([[ctx[m]]], np.int32))[0]
                    m += 1
                # 2) propose d_2..d_k autoregressively
                drafts.append(int(np.argmax(d_logits)))
                for _ in range(k - 1):
                    d_logits = self._step(
                        self.draft, self.draft_cache, [dslot],
                        np.asarray([[drafts[-1]]], np.int32))[0]
                    drafts.append(int(np.argmax(d_logits)))
            # 3) target verifies all k in ONE (1, k+1) forward; row j
            #    is the target's next-token distribution after
            #    ctx + drafts[:j]
            verify = np.asarray([[ctx[-1]] + drafts], np.int32)
            logits, nk, nv = self._forward(self.model, self.cache,
                                           [slot], verify)
            self._commit(self.model, self.cache, [slot], nk, nv, k + 1)
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
