"""``gpt_decoder`` serving family: the GPT decoder on the slot grid.

Wires ``models/gpt.py`` + ``paged_kv`` into the serving plane's
continuous-batching contract (``step_fn(tokens, cache, active)`` over a
fixed slot grid) plus the family-owned extras this decoder adds:

- ``prefill_fn(slot, tokens, cache)`` — chunked prompt ingestion, so
  the DecodeLoop commits a joining prompt in ``ceil(P/chunk)`` wide
  forwards instead of P one-token steps;
- AOT programs for the decode step (``gptdecode/s%d``), the prefill
  chunk (``gptprefill/s%dxc%d``), the cache's commit of each
  (``gptcommit/s%d/r%dxc%d``: the pools live on the device and one
  donated program stores a forward's K and V there) and — when the
  checkpoint carries a draft model — the draft's decode step
  (``gptdraft/s%d``), all built through the persistent compile cache
  and exported/bound via the checkpoint ``executables`` section like
  every other family;
- ``extra_warmup(slots)`` — called by the warmup driver to pre-build
  the full program grid (target decode × prefill × their commits ×
  draft decode), so a warm replica's first generative request compiles
  nothing.

The programs are pure functions over the flat param dict (sorted-name
``BlockProgram`` convention), NOT gluon traces — the paged forward
takes the cache pools/tables as explicit inputs, which gluon's forward
protocol has no slot for.
"""

import logging
import math
import os

import numpy as np

from ..compilecache import aot as _aot
from ..compilecache import store as _ccstore
from ..models.gpt import gpt_config, gpt_forward_paged, gpt_param_shapes
from ..serving.loader import (GenerationMismatchError, ServedModel,
                              serving_family)
from ..utils.checkpoint import CheckpointManager
from .paged_kv import PagedKVCache, device_order, store_program

__all__ = ["export_gpt_for_serving", "gpt_cache_spec"]

log = logging.getLogger(__name__)

_DRAFT_PREFIX = "draft/"


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def gpt_cache_spec(cfg):
    """PagedKVCache spec for a gpt config: per-layer k/v (H, D) entries."""
    cfg = gpt_config(cfg)
    H = cfg["num_heads"]
    D = cfg["units"] // H
    spec = {}
    for i in range(cfg["num_layers"]):
        spec["k%d" % i] = ("kv", (H, D))
        spec["v%d" % i] = ("kv", (H, D))
    return spec


class _PagedProgramSet:
    """Builds/binds the paged-forward programs for ONE param set
    (target or draft). Calling convention per program: input arrays
    ``[tokens (S, C), lengths (S,), tables (S, MB), k_pool x L,
    v_pool x L]`` then the params in sorted-name order; outputs
    ``[logits, new_k x L, new_v x L]``."""

    def __init__(self, cfg, params, tag):
        import jax.numpy as jnp
        self.cfg = cfg
        self.tag = tag
        self.num_layers = cfg["num_layers"]
        self.pnames = sorted(gpt_param_shapes(cfg))
        missing = [n for n in self.pnames if n not in params]
        if missing:
            raise IOError("gpt serving checkpoint is missing params "
                          "(%s): %s" % (tag, ", ".join(missing[:8])))
        self.pvals = [jnp.asarray(params[n]) for n in self.pnames]
        self.n_inputs = 3 + 2 * self.num_layers
        self._jit = None

    def _pure(self):
        L = self.num_layers

        def pure_fn(input_vals, param_vals):
            params = dict(zip(self.pnames, param_vals))
            tokens, lengths, tables = input_vals[:3]
            kps = list(input_vals[3:3 + L])
            vps = list(input_vals[3 + L:])
            logits, nk, nv = gpt_forward_paged(
                params, self.cfg, tokens, lengths, tables, kps, vps)
            return [logits] + nk + nv
        return pure_fn

    def example_inputs(self, rows, chunk, slots, max_len):
        """Zero arrays shaped like one program invocation against a
        ``slots``-slot cache of ``max_len`` (pool geometry follows the
        PagedKVCache defaults for the current env)."""
        import jax.numpy as jnp
        H = self.cfg["num_heads"]
        D = self.cfg["units"] // H
        bs = _env_int("MXTPU_GEN_BLOCK_SIZE", 16)
        mb = max(1, math.ceil(max_len / bs))
        nb = slots * mb
        ins = [jnp.zeros((rows, chunk), jnp.int32),
               jnp.zeros((rows,), jnp.int32),
               jnp.zeros((rows, mb), jnp.int32)]
        ins += [jnp.zeros((nb, bs, H, D), jnp.float32)
                for _ in range(2 * self.num_layers)]
        return ins

    def build(self, name, rows, chunk, slots, max_len):
        import jax
        ins = self.example_inputs(rows, chunk, slots, max_len)
        lowered = jax.jit(self._pure()).lower(ins, self.pvals)
        compiled, blob = _aot.cached_compile(lowered, name=name,
                                             where="serving",
                                             want_blob=True)
        return _aot.BlockProgram(compiled, self.pvals, self.n_inputs,
                                 name, blob=blob)

    def bind(self, name, blob):
        compiled = _aot.deserialize_compiled(blob)
        return _aot.BlockProgram(compiled, self.pvals, self.n_inputs,
                                 name, blob=blob)

    def build_commit(self, name, rows, chunk, slots, max_len):
        """The cache's ``store_program`` for what a (rows, chunk) forward
        of this set returns, held as a BlockProgram for its name and
        blob; it takes no params and is called as ``.compiled``."""
        import jax.numpy as jnp
        L = self.num_layers
        pools = self.example_inputs(rows, chunk, slots, max_len)[3:]
        new = [jnp.zeros((rows, chunk) + pools[0].shape[2:], jnp.float32)
               for _ in range(L)]
        lowered = store_program.lower(
            pools[:L], pools[L:], new, new,
            jnp.zeros((rows, chunk), jnp.int32),
            tuple(device_order(p) for p in pools))
        compiled, blob = _aot.cached_compile(
            lowered, name=name, where="serving", donation=(0, 1),
            want_blob=True)
        return _aot.BlockProgram(compiled, [], 0, name, blob=blob)

    def eager(self, tokens, lengths, tables, kps, vps):
        """jit fallback (compiles on first use — the non-warm path)."""
        if self._jit is None:
            import jax
            self._jit = jax.jit(self._pure())
        return self._jit([tokens, lengths, tables] + list(kps)
                         + list(vps), self.pvals)

    def stage_swap(self, params):
        """Validate an incoming param dict against this set's avals and
        return the replacement value list — nothing is mutated here, so
        a mismatch on the draft set can't leave the target half-swapped.
        Raises GenerationMismatchError on missing params or shape/dtype
        drift (the swap would retrace the bound executables)."""
        import jax.numpy as jnp
        missing = [n for n in self.pnames if n not in params]
        if missing:
            raise GenerationMismatchError(
                "incoming generation is missing gpt params (%s): %s"
                % (self.tag, ", ".join(missing[:8])))
        vals, drift = [], []
        for n, cur in zip(self.pnames, self.pvals):
            arr = params[n]
            # checkpoint restores hand back NDArrays; unwrap before the
            # aval check (np.asarray on one yields an object scalar)
            arr = arr.asnumpy() if hasattr(arr, "asnumpy") \
                else np.asarray(arr)
            if tuple(arr.shape) != tuple(cur.shape) \
                    or np.dtype(arr.dtype) != np.dtype(cur.dtype):
                drift.append("%s: %s%s -> %s%s"
                             % (n, np.dtype(cur.dtype), tuple(cur.shape),
                                arr.dtype, arr.shape))
                continue
            vals.append(jnp.asarray(arr))
        if drift:
            raise GenerationMismatchError(
                "incoming generation's gpt avals drifted (%s): %s"
                % (self.tag, "; ".join(drift[:8])))
        return vals

    def apply_swap(self, vals):
        """Install staged values IN PLACE: ``pvals`` is the live list
        the jit fallback passes per call, so mutating it (not rebinding)
        swaps the eager path too."""
        self.pvals[:] = vals


@serving_family("gpt_decoder")
def _build_gpt_decoder(config, params, quantize):
    """Autoregressive GPT decode over a paged KV cache. The checkpoint
    may carry a draft model (params under ``draft/``, config under
    ``config["draft"]``) for engine-side speculative decoding; the
    serving DecodeLoop itself always steps the target one token at a
    time and prefills through ``prefill_fn``."""
    cfg = gpt_config({k: v for k, v in config.items() if k != "draft"})
    if quantize:
        log.info("serving: gpt_decoder has no int8 path yet; serving "
                 "full precision")
    target = _PagedProgramSet(cfg, params, "target")
    draft = None
    draft_cfg = config.get("draft")
    if isinstance(draft_cfg, dict):
        dparams = {k[len(_DRAFT_PREFIX):]: v for k, v in params.items()
                   if k.startswith(_DRAFT_PREFIX)}
        draft = _PagedProgramSet(gpt_config(draft_cfg), dparams, "draft")

    L = cfg["num_layers"]
    prefill_chunk = _env_int("MXTPU_GEN_PREFILL_CHUNK", 32)
    geom = {"slots": None, "max_len": None}
    decode_programs = {}

    def make_cache(slots, max_len):
        geom["slots"], geom["max_len"] = int(slots), int(max_len)
        return PagedKVCache(slots, gpt_cache_spec(cfg), max_len=max_len,
                            name="gpt")

    def commit_name(slots, rows, chunk):
        return "gptcommit/s%d/r%dxc%d" % (int(slots), rows, chunk)

    def _geometry(slots):
        return (int(slots),
                geom["max_len"] or _env_int("MXTPU_SERVE_CACHE_LEN", 512))

    def _program(build, name, rows, chunk, slots):
        if name not in decode_programs:
            slots_n, max_len = _geometry(slots)
            try:
                decode_programs[name] = build(name, rows, chunk, slots_n,
                                              max_len)
            except Exception as e:  # noqa: BLE001 — an AOT build
                # failure falls back to the jit path
                log.warning("serving: cannot build %r (%s: %s); this "
                            "shape serves through plain jit", name,
                            type(e).__name__, e)
                decode_programs[name] = None
        return decode_programs[name]

    def decode_program_for(slots):
        return _program(target.build, "gptdecode/s%d" % int(slots),
                        int(slots), 1, int(slots))

    def prefill_program_for(slots):
        name = "gptprefill/s%dxc%d" % (int(slots), prefill_chunk)
        return _program(target.build, name, 1, prefill_chunk, int(slots))

    def commit_program_for(slots, rows, chunk):
        return _program(target.build_commit,
                        commit_name(slots, rows, chunk), rows, chunk,
                        int(slots))

    def draft_program_for(slots):
        if draft is None:
            return None
        return _program(draft.build, "gptdraft/s%d" % int(slots),
                        int(slots), 1, int(slots))

    def bind(name, blob):
        if name.startswith("gptdecode/s") or name.startswith("gptprefill/s"):
            decode_programs[name] = target.bind(name, blob)
            return True
        if name.startswith("gptdraft/s") and draft is not None:
            decode_programs[name] = draft.bind(name, blob)
            return True
        if name.startswith("gptcommit/s"):
            decode_programs[name] = _aot.BlockProgram(
                _aot.deserialize_compiled(blob), [], 0, name, blob=blob)
            return True
        return False

    def _gather(cache, slots):
        lengths = np.asarray([int(cache.lengths[s]) for s in slots],
                             np.int32)
        tables = cache.tables_array(slots)
        kps = [cache.pool("k%d" % i) for i in range(L)]
        vps = [cache.pool("v%d" % i) for i in range(L)]
        return lengths, tables, kps, vps

    def _run(pset, prog_name, prog_factory, slots_arg, tokens, lengths,
             tables, kps, vps):
        """One paged forward: AOT program when available/gated, jit
        fallback otherwise. Returns the flat [logits, k..., v...], device
        arrays (the pools are the cache's device arrays: not shipped)."""
        if _ccstore.enabled() or decode_programs:
            prog = prog_factory(slots_arg)
            if prog is not None:
                try:
                    return prog(tokens, lengths, tables, *kps, *vps)
                except TypeError:   # aval drift — retire the program
                    decode_programs[prog_name] = None
        return pset.eager(tokens, lengths, tables, kps, vps)

    def _commit(cache, slots_arg, slots, flat, count):
        """Store a forward's K and V (``flat[1:]``) in the cache, through
        the AOT commit program of the forward's shape when available/
        gated (the cache keeps it; jit inside the cache otherwise)."""
        shape = tuple(flat[1].shape[:2])
        if shape not in cache.programs and (_ccstore.enabled()
                                            or decode_programs):
            prog = commit_program_for(slots_arg, *shape)
            if prog is not None:
                cache.programs[shape] = prog.compiled
        cache.commit(slots, list(flat[1:1 + L]), list(flat[1 + L:]), count)

    def step(tokens, cache, active):
        """DecodeLoop contract: tokens (slots,) int32 over the FULL
        grid; commit K/V for active slots only; return (slots, V)."""
        s = int(tokens.shape[0])
        lengths, tables, kps, vps = _gather(cache, range(s))
        flat = _run(target, "gptdecode/s%d" % s, decode_program_for, s,
                    np.asarray(tokens, np.int32).reshape(s, 1), lengths,
                    tables, kps, vps)
        _commit(cache, s, range(s), flat, np.asarray(active, np.int32))
        return np.asarray(flat[0])[:, 0]

    def prefill(slot, tokens, cache):
        """Commit a prompt prefix into one slot in fixed-width chunks
        (pad tokens sit after the valid ones — causal masking keeps
        them out of every committed position's window — and their K/V
        are simply not committed)."""
        n_slots = geom["slots"] or cache.slots
        name = "gptprefill/s%dxc%d" % (n_slots, prefill_chunk)
        tokens = np.asarray(tokens, np.int32).ravel()
        for start in range(0, len(tokens), prefill_chunk):
            piece = tokens[start:start + prefill_chunk]
            padded = np.zeros((1, prefill_chunk), np.int32)
            padded[0, :len(piece)] = piece
            lengths, tables, kps, vps = _gather(cache, [slot])
            flat = _run(target, name, prefill_program_for, n_slots,
                        padded, lengths, tables, kps, vps)
            _commit(cache, n_slots, [slot], flat, len(piece))

    def extra_warmup(slots):
        """Pre-build the generative program grid for a slot count:
        target decode, prefill chunk, and the draft decode when the
        checkpoint carries one. Returns {built: [...], failed: [...]}."""
        built, failed = [], []
        jobs = [("gptdecode/s%d" % slots, decode_program_for),
                ("gptprefill/s%dxc%d" % (slots, prefill_chunk),
                 prefill_program_for)]
        for shape in ((slots, 1), (1, prefill_chunk)):
            jobs.append((commit_name(slots, *shape),
                         lambda s, shape=shape: commit_program_for(
                             s, *shape)))
        if draft is not None:
            jobs.append(("gptdraft/s%d" % slots, draft_program_for))
        for name, factory in jobs:
            (built if factory(slots) is not None else failed).append(name)
        return {"built": built, "failed": failed}

    def swap(params):
        """Live weight push for the paged family: params-only, cache
        untouched — the paged K/V pools and block tables are inputs to
        the programs, not captured state, so in-flight sessions that
        survive the server's drain keep their committed prefix and the
        next step simply reads the new weights. Both param sets are
        validated BEFORE either is touched (an aval drift on the draft
        must not leave the target half-swapped); the program walk
        rewrites each BlockProgram's own param list (BlockProgram copies
        it at build time) as well as the sets' jit-fallback lists."""
        staged = [(target, target.stage_swap(params))]
        if draft is not None:
            staged.append((draft, draft.stage_swap(
                {k[len(_DRAFT_PREFIX):]: v for k, v in params.items()
                 if k.startswith(_DRAFT_PREFIX)})))
        for pset, vals in staged:
            pset.apply_swap(vals)
        for name, prog in decode_programs.items():
            if prog is None or name.startswith("gptcommit/"):
                continue        # a commit program reads no weight
            pset = draft if name.startswith("gptdraft/") else target
            prog.param_vals[:] = pset.pvals

    served = ServedModel("gpt_decoder", config, step_fn=step,
                         make_cache=make_cache, pad_token=0,
                         quantized=False,
                         decode_program_factory=decode_program_for,
                         program_binder=bind,
                         decode_programs=decode_programs,
                         prefill_fn=prefill,
                         prefill_chunk=prefill_chunk,
                         params_swapper=swap)
    served.extra_warmup = extra_warmup
    served.draft_program_factory = draft_program_for
    return served


def export_gpt_for_serving(directory, config, model, draft=None,
                           executables=None, generation=None):
    """Write a gpt_decoder serving checkpoint: the target decoder's
    params (flat local names), optionally a draft model's params under
    ``draft/`` with its config under ``config["draft"]``, plus the
    family stanza — same atomic checkpoint machinery as
    ``export_for_serving``, extended for the two-model layout. Like
    every serving export this publishes a new GENERATION (monotonic,
    pointer re-pointed atomically, older generations retained)."""
    from ..serving.loader import generation_steps, publish_generation
    params = {k: v.data() for k, v
              in model._collect_params_with_prefix().items()}
    config = dict(config)
    if draft is not None:
        params.update({_DRAFT_PREFIX + k: v.data() for k, v
                       in draft._collect_params_with_prefix().items()})
        config.setdefault("draft", getattr(draft, "config", None)
                          or config.get("draft"))
        if not isinstance(config.get("draft"), dict):
            raise ValueError("draft model carries no config dict; pass "
                             "config['draft'] explicitly")
    mgr = CheckpointManager(directory, keep=None, async_save=False,
                            prefix="serve")
    gens = generation_steps(directory)
    if generation is None:
        generation = max(gens, default=-1) + 1
    elif gens and int(generation) <= max(gens):
        raise ValueError("generation numbers are monotonic: %d is not "
                         "newer than the retained max %d"
                         % (int(generation), max(gens)))
    step = mgr.latest_step()
    step = 0 if step is None else step + 1
    mgr.save(step, params, extra={"serving": {"family": "gpt_decoder",
                                              "config": config},
                                  "generation": int(generation)},
             executables=executables)
    publish_generation(directory, generation, step)
    return directory
