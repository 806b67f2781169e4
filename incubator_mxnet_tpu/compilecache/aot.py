"""Ahead-of-time executable serialization + cache-aware compilation.

Two mechanisms get a restarted replica past XLA compilation:

- ``cached_compile(lowered, ...)`` — the drop-in replacement for
  ``lowered.compile()``. It keys the lowered program's StableHLO text
  (which embeds the in/out shardings) together with the mesh geometry,
  donation signature, backend identity and jax/jaxlib versions, consults
  the persistent store, and either deserializes a previous process's
  executable (NO backend_compile event fires) or compiles fresh and
  publishes the result. Any serialization failure degrades to the plain
  compile path.

- ``serialize_compiled``/``deserialize_compiled`` — the raw blob codec
  (jax.experimental.serialize_executable under the hood) used by the
  checkpoint ``executables`` section, so a compiled program travels WITH
  the weights to machines that never saw the cache directory.

Deserialized executables are ``jax.stages.Compiled`` objects pinned to
the avals they were compiled for: calling one with different shapes
raises TypeError, which every integration point (trainer step/step_scan,
serving programs) catches to fall back to a fresh trace/compile — a
stale executable can cost one compile, never a wrong answer.

``BlockProgram`` packages a gluon ``HybridBlock`` inference forward as
one cached executable: the pure function mirrors
``HybridBlock._build_jit`` (params fed as arguments in sorted-name
order, no RNG key, training=False), so its calling convention is a
deterministic function of (block, input signature) and a warm process
can rebind an imported executable without re-tracing anything.
"""

import hashlib
import logging
import pickle
import time

from ..telemetry import catalog as _cat
from ..telemetry import costs as _costs
from ..telemetry import memz as _memz
from . import store as _store

__all__ = ["compile_key", "serialize_compiled", "deserialize_compiled",
           "cached_compile", "BlockProgram", "block_program",
           "bind_block_program", "capture_cost", "capture_memory"]

log = logging.getLogger(__name__)

_BLOB_VERSION = 2   # 2: the blob names the devices it was compiled for


# ----------------------------------------------------------------- keying
def compile_key(lowered, mesh=None, donation=(), extra=()):
    """Content key for a ``jax.stages.Lowered`` program.

    Folds in everything that changes the produced executable: StableHLO
    text (operand shardings included), mesh shape + axis names, device
    platform/kind/count, donation signature, jax + jaxlib versions, and
    caller-supplied ``extra`` parts (e.g. a program name-space)."""
    import jax
    h = hashlib.sha256()
    h.update(lowered.as_text().encode("utf-8"))
    if mesh is not None:
        h.update(repr(sorted(dict(mesh.shape).items())).encode("utf-8"))
        h.update(repr(tuple(mesh.axis_names)).encode("utf-8"))
        devs = list(mesh.devices.flat)
    else:
        devs = jax.devices()
    h.update(("%d:%s:%s" % (len(devs), devs[0].platform,
                            getattr(devs[0], "device_kind", "?")))
             .encode("utf-8"))
    h.update(repr(tuple(donation)).encode("utf-8"))
    h.update(jax.__version__.encode("utf-8"))
    try:
        import jaxlib
        h.update(getattr(jaxlib, "__version__", "?").encode("utf-8"))
    except ImportError:
        pass
    for part in extra:
        h.update(str(part).encode("utf-8"))
    return h.hexdigest()


# ------------------------------------------------------------- blob codec
def serialize_compiled(compiled):
    """``jax.stages.Compiled`` -> bytes (raises on backends that cannot
    serialize executables — callers treat that as 'cache this one not')."""
    from jax.experimental import serialize_executable as _se
    payload, in_tree, out_tree = _se.serialize(compiled)
    # ids in device-assignment order: one device for a plain jit, the
    # mesh's for a sharded program
    device_ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((_BLOB_VERSION, payload, in_tree, out_tree,
                         device_ids), protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(blob):
    """bytes -> callable ``jax.stages.Compiled`` loaded onto the devices
    it was compiled for, found by id among this process's devices (raises
    on version/backend mismatch or a device this process does not have).
    ``deserialize_and_load`` would otherwise load onto ALL local devices,
    and a one-device program then refuses its arguments."""
    import jax
    from jax.experimental import serialize_executable as _se
    version, *rest = pickle.loads(blob)
    if version != _BLOB_VERSION:
        raise ValueError("unsupported executable blob version %r" % version)
    payload, in_tree, out_tree, device_ids = rest
    by_id = {d.id: d for d in jax.devices()}
    missing = [i for i in device_ids if i not in by_id]
    if missing:
        raise ValueError("executable was compiled for device ids %r; this "
                         "process has no %r" % (device_ids, missing))
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


# -------------------------------------------------------- cached_compile
def cached_compile(lowered, name, where="other", mesh=None, donation=(),
                   store=None, extra=(), want_blob=False):
    """Compile ``lowered`` through the persistent cache.

    Cache off (no MXTPU_COMPILE_CACHE_DIR): exactly ``lowered.compile()``
    inside a ``compiling(where)`` region. Cache on: a hit deserializes the
    stored executable (zero backend_compile events); a miss compiles,
    then best-effort publishes the serialized result so the NEXT process
    hits.

    ``want_blob=True`` returns ``(compiled, blob_or_None)`` instead —
    the blob the executable was loaded from (hit) or published as
    (miss). Callers that re-export executables into checkpoints MUST use
    this blob rather than re-serializing: a deserialized executable does
    not round-trip through ``serialize`` again (the backend strips the
    symbol definitions), so only the ORIGINAL compile's blob is the
    durable transport form."""
    _cat.install_jax_compile_hook()
    st = store if store is not None else _store.default_store()
    if st is None:
        with _cat.compiling(where):
            compiled = lowered.compile()
        capture_memory(name, compiled)
        return (compiled, None) if want_blob else compiled
    key = compile_key(lowered, mesh=mesh, donation=donation,
                      extra=(name,) + tuple(extra))
    ent = st.get(key, where=where)
    if ent is not None:
        payload, header = ent
        try:
            compiled = deserialize_compiled(payload)
            capture_memory(name, compiled)
            return (compiled, payload) if want_blob else compiled
        except Exception as e:  # noqa: BLE001 — a stale/foreign entry
            # (jaxlib drift the key missed, partial backend support)
            # must fall back to a fresh compile, never crash
            log.warning("compile cache: entry %s for %r failed to "
                        "deserialize (%s: %s); recompiling",
                        key[:12], name, type(e).__name__, e)
            _cat.compile_cache_errors.inc(kind="deserialize")
    t0 = time.perf_counter()
    with _cat.compiling(where):
        compiled = lowered.compile()
    dt = time.perf_counter() - t0
    capture_memory(name, compiled)
    try:
        blob = serialize_compiled(compiled)
    except Exception as e:  # noqa: BLE001 — backends without executable
        # serialization still get the compiled program, just no cache
        log.info("compile cache: %r is not serializable on this backend "
                 "(%s: %s); not cached", name, type(e).__name__, e)
        _cat.compile_cache_errors.inc(kind="serialize")
        return (compiled, None) if want_blob else compiled
    st.put(key, blob, compile_seconds=dt, name=name)
    return (compiled, blob) if want_blob else compiled


# ------------------------------------------------------ gluon programs
class BlockProgram:
    """One compiled inference forward of a gluon block.

    Calling convention (deterministic given the block): positional input
    arrays in their forward() slot order, then the block's materialized
    param values in sorted-name order; outputs are the flattened forward
    outputs (``gluon.block._flatten_outputs`` order). ``__call__`` takes
    just the input arrays — param values were captured at build time."""

    def __init__(self, compiled, param_vals, n_inputs, name, blob=None):
        self.compiled = compiled
        self.param_vals = list(param_vals)
        self.n_inputs = int(n_inputs)
        self.name = name
        self.blob = blob

    def __call__(self, *input_vals):
        if len(input_vals) != self.n_inputs:
            raise TypeError("%s takes %d input arrays, got %d"
                            % (self.name, self.n_inputs, len(input_vals)))
        return self.compiled(list(input_vals), self.param_vals)

    def dump(self):
        """Serialize for a checkpoint ``executables`` section. Reuses
        the blob this program was loaded from when there is one — a
        deserialized executable cannot be re-serialized (the backend
        strips symbol definitions), only the original blob round-trips."""
        if self.blob is not None:
            return self.blob
        return serialize_compiled(self.compiled)


def _block_pure_fn(block, pnames, example_args):
    """The inference pure function over (input_vals, param_vals) —
    mirrors HybridBlock._build_jit with training=False and no RNG."""
    from ..gluon.block import _TraceCtx, _trace_state, _flatten_outputs

    def pure_fn(input_vals, param_vals):
        ctx = _TraceCtx(dict(zip(pnames, param_vals)), None,
                        training=False)
        prev = getattr(_trace_state, "ctx", None)
        _trace_state.ctx = ctx
        try:
            it = iter(input_vals)
            new_args = []
            for a in example_args:
                if a is None:
                    new_args.append(None)
                elif isinstance(a, (list, tuple)):
                    new_args.append([next(it) for _ in a])
                else:
                    new_args.append(next(it))
            out = block.forward(*new_args)
        finally:
            _trace_state.ctx = prev
        flat, _rebuild = _flatten_outputs(out)
        return [getattr(a, "_data", a) for a in flat]

    return pure_fn


def _block_param_state(block):
    """(sorted param names, their jax values) — the deterministic param
    half of a BlockProgram's calling convention."""
    params = {p.name: p for p in block.collect_params().values()}
    pnames = sorted(n for n, p in params.items() if p._data is not None)
    return pnames, [params[n]._data._data for n in pnames]


def block_program(block, example_args, name, where="serving", store=None,
                  extra=()):
    """Build (through the cache) a ``BlockProgram`` running ``block``'s
    inference forward on arrays shaped like ``example_args``. Entries may
    be None (optional forward args stay None), a host array, or a
    list/tuple of host arrays (e.g. an RNN state list) — list entries are
    flattened into the program's positional inputs in order, so callers
    flatten the same way at call time."""
    import jax
    import jax.numpy as jnp
    pnames, pvals = _block_param_state(block)
    pure_fn = _block_pure_fn(block, pnames, example_args)
    in_vals = []
    for a in example_args:
        if a is None:
            continue
        if isinstance(a, (list, tuple)):
            in_vals.extend(jnp.asarray(x) for x in a)
        else:
            in_vals.append(jnp.asarray(a))
    lowered = jax.jit(pure_fn).lower(in_vals, pvals)
    compiled, blob = cached_compile(lowered, name=name, where=where,
                                    store=store, extra=extra,
                                    want_blob=True)
    return BlockProgram(compiled, pvals, len(in_vals), name, blob=blob)


def bind_block_program(block, blob, n_inputs, name, where="serving"):
    """Rebind an imported executable blob to ``block``'s current params
    as a ``BlockProgram`` (no tracing, no compile). Raises if the blob
    cannot deserialize on this backend."""
    compiled = deserialize_compiled(blob)
    _pnames, pvals = _block_param_state(block)
    _cat.aot_executables_imported.inc(where=where)
    return BlockProgram(compiled, pvals, n_inputs, name, blob=blob)


def capture_cost(name, compiled, samples_per_exec=None):
    """Best-effort ``telemetry.costs`` capture off an already-compiled
    executable — the satellite fix for the MXTPU_COSTS double compile:
    callers hand in the SAME executable they will run."""
    capture_memory(name, compiled)   # memz rides the same seam
    if not _costs.capture_enabled():
        return
    try:
        _costs.capture(name, compiled, samples_per_exec=samples_per_exec)
    except Exception:  # noqa: BLE001 — accounting must never fail the
        pass           # step (deserialized executables may lack costs)


def capture_memory(name, compiled):
    """Best-effort ``telemetry.memz`` footprint capture off an
    already-compiled executable — every ``cached_compile`` return path
    calls this, so trainer, serving and the gpt program grid each get a
    footprint-table row from the SAME executable the step runs.  One
    predicate check with the memz plane off."""
    try:
        _memz.capture_memory(name, compiled)
    except Exception:  # noqa: BLE001 — accounting must never fail the
        pass           # step (deserialized executables may lack
                       # memory analysis on some backends)
