"""compilecache/ — persistent compile cache + AOT executable transport.

Every process in a fleet used to pay full XLA compilation on start; this
subsystem makes compilation a fleet-level, once-per-program cost:

- ``store``  — content-addressed on-disk cache of serialized executables
  (``MXTPU_COMPILE_CACHE_DIR`` / ``MXTPU_COMPILE_CACHE_MAX_MB``), atomic
  rename-published, corruption-safe, LRU-capped;
- ``aot``    — ``cached_compile`` (the cache-aware ``.compile()``) and
  the serialize/deserialize codec that lets executables ride in
  checkpoint ``executables`` sections;
- ``warmup`` — precompile the serving bucket grid and trainer step avals
  before a process takes traffic (CLI: ``tools/warmup.py``).

With no cache dir configured the subsystem costs one env lookup per
query and touches no files.

``use_jax_cache`` is separate from all of that: it places JAX's OWN
persistent compilation cache for the entry-point scripts
(``benchmarks/run.py``, ``chip_smoke.py``).
"""

import os

from . import aot, store, warmup
from .aot import (block_program, cached_compile, compile_key,
                  deserialize_compiled, serialize_compiled)
from .store import CompileCacheStore, cache_dir, default_store, enabled
from .warmup import warmup_serving, warmup_trainer


def use_jax_cache():
    """Place JAX's persistent compilation cache; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is set here. Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of the cache's key and a directory that moves never hits. Returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["aot", "store", "warmup", "block_program", "cached_compile",
           "compile_key", "deserialize_compiled", "serialize_compiled",
           "CompileCacheStore", "cache_dir", "default_store", "enabled",
           "warmup_serving", "warmup_trainer", "use_jax_cache"]
