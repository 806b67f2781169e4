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

The step is the engine's (``engine.step_slots`` / ``prefill_slot`` over a
``GPTPagedLM`` and the cache): this module owns no forward, gather or
commit of its own. What it owns is the program grid. A forward program
is the adapter's one jitted function lowered (``GPTPagedLM.lower``) for
the inputs a cache of ``make_cache`` hands it, a commit program the
cache's own (``PagedKVCache.lower_commit``); built or bound, a program
goes into the table its caller looks in (``adapter.programs``,
``cache.programs``), and a call that is refused retires it there and the
shape is served through jit.
"""

import logging
import re
import weakref

import jax
import numpy as np

from ..compilecache import aot as _aot
from ..compilecache import store as _ccstore
from ..models.gpt import gpt_config, gpt_param_shapes
from ..serving.loader import (GenerationMismatchError, ServedModel,
                              serving_family)
from ..utils.checkpoint import CheckpointManager
from . import engine as _eng
from .paged_kv import _env_int

__all__ = ["export_gpt_for_serving"]

log = logging.getLogger(__name__)

_DRAFT_PREFIX = "draft/"


def _host(value):
    """A checkpoint's value as a host array: a restore hands back
    NDArrays, and ``jnp.asarray`` of one walks it element by element
    (minutes for a real embedding)."""
    return value.asnumpy() if hasattr(value, "asnumpy") else np.asarray(value)


def _adapter(cfg, params, tag):
    """The ``GPTPagedLM`` over a checkpoint's params of `cfg`'s names."""
    names = sorted(gpt_param_shapes(cfg))
    missing = [n for n in names if n not in params]
    if missing:
        raise IOError("gpt serving checkpoint is missing params "
                      "(%s): %s" % (tag, ", ".join(missing[:8])))
    return _eng.GPTPagedLM({n: _host(params[n]) for n in names}, cfg)


def _draft_params(params):
    return {k[len(_DRAFT_PREFIX):]: v for k, v in params.items()
            if k.startswith(_DRAFT_PREFIX)}


def _stage_swap(adapter, params, tag):
    """Validate an incoming param dict against the adapter's avals and
    return the replacement dict — nothing is mutated here, so a mismatch
    on the draft can't leave the target half-swapped. Raises
    GenerationMismatchError on missing params or shape/dtype drift (the
    swap would retrace the bound executables)."""
    import jax.numpy as jnp
    missing = [n for n in adapter.params if n not in params]
    if missing:
        raise GenerationMismatchError(
            "incoming generation is missing gpt params (%s): %s"
            % (tag, ", ".join(missing[:8])))
    vals, drift = {}, []
    for n, cur in adapter.params.items():
        arr = _host(params[n])
        if tuple(arr.shape) != tuple(cur.shape) \
                or np.dtype(arr.dtype) != np.dtype(cur.dtype):
            drift.append("%s: %s%s -> %s%s"
                         % (n, np.dtype(cur.dtype), tuple(cur.shape),
                            arr.dtype, arr.shape))
            continue
        vals[n] = jnp.asarray(arr)
    if drift:
        raise GenerationMismatchError(
            "incoming generation's gpt avals drifted (%s): %s"
            % (tag, "; ".join(drift[:8])))
    return vals


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
    target = _adapter(cfg, params, "target")
    draft = None
    if isinstance(config.get("draft"), dict):
        draft = _adapter(gpt_config(config["draft"]), _draft_params(params),
                         "draft")

    prefill_chunk = _eng.default_prefill_chunk()
    decode_programs = {}    # name -> BlockProgram, held for name and blob
    commit_tables = {}      # slots -> the ``programs`` of its caches
    live = []               # weakref of the cache `make_cache` built last

    def make_cache(slots, max_len):
        cache = target.make_cache(int(slots), max_len=int(max_len),
                                  name="gpt")
        cache.programs = commit_tables.setdefault(int(slots), {})
        live[:] = [weakref.ref(cache)]
        return cache

    def _grid(slots):
        """The programs of a `slots` grid, kind -> (name, the table its
        caller looks in, the (S, C) it stands under there, the adapter
        whose forward it is or whose forward it commits, commit?)."""
        slots = int(slots)
        decode, chunk = (slots, 1), (1, prefill_chunk)
        commits = commit_tables.setdefault(slots, {})
        grid = {
            "decode": ("gptdecode/s%d" % slots, target.programs, decode,
                       target, False),
            "prefill": ("gptprefill/s%dxc%d" % (slots, prefill_chunk),
                        target.programs, chunk, target, False),
            "decode_commit": ("gptcommit/s%d/r%dxc%d" % ((slots,) + decode),
                              commits, decode, target, True),
            "prefill_commit": ("gptcommit/s%d/r%dxc%d" % ((slots,) + chunk),
                               commits, chunk, target, True)}
        if draft is not None:
            grid["draft"] = ("gptdraft/s%d" % slots, draft.programs, decode,
                             draft, False)
        return grid

    def _build(name, shape, adapter, commit, slots):
        """Compile (through the compile cache) `adapter`'s forward of
        `shape`, or the commit of what it returns, for the inputs of the
        serving cache where that has `slots` slots, else of one as
        `make_cache` builds it at the serving (or
        ``MXTPU_SERVE_CACHE_LEN``) length. -> (compiled, blob)"""
        cache = live[0]() if live else None
        if adapter is not target or cache is None or cache.slots != slots:
            cache = adapter.make_cache(
                slots, name="gpt/aot",
                max_len=(cache.max_len if cache is not None
                         else _env_int("MXTPU_SERVE_CACHE_LEN", 512)))
        lowered = adapter.lower(np.zeros(shape, np.int32),
                                *cache.forward_inputs(range(shape[0])))
        donation = ()
        if commit:      # of what the forward adds, entry by entry
            _logits, *new = lowered.out_info
            lowered = cache.lower_commit(*new)
            donation = tuple(range(len(new)))
        return _aot.cached_compile(lowered, name=name, where="serving",
                                   donation=donation, want_blob=True)

    def _program(slots, kind):
        """The grid's program of `kind` (a BlockProgram holding its name
        and blob; None where the grid has none, a build failed or a call
        retired it), built on first ask and put where its caller finds
        it."""
        entry = _grid(slots).get(kind)
        if entry is None:
            return None
        name, table, shape, adapter, commit = entry
        if name not in decode_programs:
            try:
                compiled, blob = _build(name, shape, adapter, commit,
                                        int(slots))
                decode_programs[name] = _aot.BlockProgram(
                    compiled, [], 0, name, blob=blob)
                table[shape] = compiled
            except Exception as e:  # noqa: BLE001 — an AOT build
                # failure falls back to the jit path
                log.warning("serving: cannot build %r (%s: %s); this "
                            "shape serves through plain jit", name,
                            type(e).__name__, e)
                decode_programs[name] = None
        elif decode_programs[name] is not None and shape not in table:
            decode_programs[name] = None        # a call retired it
        return decode_programs[name]

    def decode_program_for(slots):
        return _program(slots, "decode")

    def draft_program_for(slots):
        return _program(slots, "draft")

    def bind(name, blob):
        """A checkpoint's executable into the table its caller looks in;
        refused (False: the shape recompiles on demand) when the grid has
        no such program or it was exported under another calling
        convention."""
        m = re.match(r"gpt[a-z]+/s(\d+)", name)
        entry = [e for e in _grid(m.group(1)).values()
                 if e[0] == name] if m else []
        if not entry:
            return False
        (_name, table, shape, adapter, commit), = entry
        pools = ([0] * adapter.num_layers,) * len(adapter.kv_entries)
        args = (pools + (0,) * len(pools) + (0,) if commit else
                ((adapter.params, 0), 0, 0, 0) + pools)   # as `weights()`
        compiled = _aot.deserialize_compiled(blob)
        if compiled.in_tree != jax.tree_util.tree_structure((args, {})):
            log.info("serving: executable %r was exported under another "
                     "calling convention; it recompiles on demand", name)
            return False
        decode_programs[name] = _aot.BlockProgram(compiled, [], 0, name,
                                                  blob=blob)
        table[shape] = compiled
        return True

    def _ship(slots, kind):
        """Where executables are shipped (the compile cache is on, or the
        checkpoint bound some) a forward of `kind` and its commit run the
        grid's programs, built on first use."""
        if _ccstore.enabled() or decode_programs:
            _program(slots, kind)
            _program(slots, kind + "_commit")

    def step(tokens, cache, active):
        """DecodeLoop contract: tokens (slots,) int32 over the FULL
        grid; commit K/V for active slots only; return (slots, V)."""
        s = int(tokens.shape[0])
        _ship(s, "decode")
        return _eng.step_slots(
            target, cache, range(s),
            np.asarray(tokens, np.int32).reshape(s, 1),
            np.asarray(active, np.int32))

    def prefill(slot, tokens, cache):
        """Commit a prompt prefix into one slot in fixed-width chunks."""
        _ship(cache.slots, "prefill")
        _eng.prefill_slot(target, cache, slot,
                          np.asarray(tokens, np.int32).ravel(),
                          prefill_chunk)

    def extra_warmup(slots):
        """Pre-build the generative program grid for a slot count:
        target decode, prefill chunk, the commit of each, and the draft
        decode when the checkpoint carries one. Returns {built: [...],
        failed: [...]}."""
        built, failed = [], []
        for kind, entry in _grid(slots).items():
            (built if _program(slots, kind) is not None
             else failed).append(entry[0])
        return {"built": built, "failed": failed}

    def swap(params):
        """Live weight push for the paged family: params-only, cache
        untouched — the weights, the paged K/V pools and the block tables
        are inputs to the programs, not captured state, so in-flight
        sessions that survive the server's drain keep their committed
        prefix and the next step simply reads the new weights (and the
        lookup table each adapter derives from the new ``wte``). Both
        adapters' params are validated BEFORE either is touched (an aval
        drift on the draft must not leave the target half-swapped)."""
        staged = [(target, _stage_swap(target, params, "target"))]
        if draft is not None:
            staged.append((draft, _stage_swap(draft, _draft_params(params),
                                              "draft")))
        for adapter, vals in staged:
            adapter.params = vals

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
