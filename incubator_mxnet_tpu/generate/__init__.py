"""Generative inference engine: decoder LLMs over a paged KV cache.

The subsystem ROADMAP item 1 names: a GPT-style causal decoder served
through the continuous-batching plane, with

- :mod:`.paged_kv` — block-table + free-list KV allocator that drops in
  behind the ``serving/kv_cache.py`` alloc/free/append surface,
- :mod:`.adapters` — ``PagedLM``, the one adapter contract, and the
  model adapters over it (what each model's forwards are and count),
- :mod:`.engine` — the paged step (gather, forward, commit) and over it
  chunked prefill, greedy/temperature sampling, draft-model speculative
  decoding (Leviathan et al., ICML 2023), and block-diffusion decoding
  for a model that declares a block length,
- :mod:`.family` — the ``gpt_decoder`` ``@serving_family`` putting the
  engine's step under ModelServer's slot grid, with AOT programs.

Importing this package registers the serving family.
"""

from .paged_kv import PagedKVCache
from .engine import (EvaPagedLM, GenerateEngine, GPTPagedLM, MLAPagedLM,
                     OuroPagedLM, SDARPagedLM)
from . import family  # noqa: F401  (registers the gpt_decoder family)
from .family import export_gpt_for_serving

__all__ = [
    "PagedKVCache",
    "GenerateEngine",
    "GPTPagedLM",
    "SDARPagedLM",
    "MLAPagedLM",
    "EvaPagedLM",
    "OuroPagedLM",
    "export_gpt_for_serving",
]
