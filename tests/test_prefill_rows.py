"""A batched prefill (``engine.prefill_slots``): rounds of several prompts a
forward, against one prompt a forward, on the toy configurations the
benchmark's CPU tests build (float32: GPT, the SDAR block decoder, the
latent cache with hyper-connected streams and with a held share of the
experts, Ouro's looped decoder, EvaByte's windows and summaries).

- The cache holds the same rows and the same lengths, for prompts of mixed
  lengths (one that needs more chunks than the others, one of a single
  chunk, one shorter than a chunk): bit for bit where XLA's CPU products
  sum each row alike whatever the rows beside it (GPT, SDAR, Ouro), within
  1e-5, the toys' own tolerance for a path through the cache, where a
  product of more rows sums in another order (the latent cache, EvaByte:
  some 1e-6 seen);
- a greedy ``generate`` call serves the same tokens (a prompt shorter than
  a block, or of one token, skipped by the prefill);
- the rule for R, from the chunk's width and the cache's bytes a position,
  on the benchmark's cells as their files state them;
- a second call of as many prompts compiles nothing;
- ``last_stats`` and the two counters count the rounds and the prompt
  chunks fed, pad rows not.
"""

import importlib
import json
import os

import numpy as np
import pytest

import jax

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.generate import (EvaPagedLM, GenerateEngine,
                                          GPTPagedLM, MLAPagedLM, OuroPagedLM,
                                          SDARPagedLM)
from incubator_mxnet_tpu.generate import engine as engine_mod
from incubator_mxnet_tpu.generate.engine import (position_bytes,
                                                 prefill_rounds,
                                                 prefill_rows, prefill_slot,
                                                 prefill_slots)
from incubator_mxnet_tpu.telemetry import catalog as cat
from incubator_mxnet_tpu.telemetry import metrics as _met

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# family module, toy configuration, adapter, chunk, prompt ids [lo, hi),
# how far a cache row may lie from one prefilled a prompt a forward
TOYS = {
    "gpt": ("gpt2", "gpt2_tiny", GPTPagedLM, 4, (1, 1100), 0.0),
    "sdar_blocks": ("sdar_moe", "sdar_tiny", SDARPagedLM, 8, (1, 1090), 0.0),
    "latent_streams": ("xing4", "xing4_tiny", MLAPagedLM, 8, (1, 1100),
                       1e-5),
    "latent_held_share": ("kimi_k2", "kimi_tiny", MLAPagedLM, 8, (1, 1100),
                          1e-5),
    "ouro_loop": ("ouro", "ouro_tiny", OuroPagedLM, 8, (20, 320), 0.0),
    "eva_windows": ("evabyte", "eva_tiny", EvaPagedLM, 16, (64, 320), 1e-5),
}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=sorted(TOYS))
def toy(request):
    family_name, config, adapter, chunk, ids, atol = TOYS[request.param]
    family = importlib.import_module("benchmarks.families." + family_name)
    cfg = _json("tests", "benchmark", "configs", config + ".json")
    kw = {} if adapter is GPTPagedLM else {"dtype": "float32"}
    model = adapter(family.reference.init_weights(cfg, 3),
                    family.program_config(cfg), **kw)
    return {"name": request.param, "model": model, "chunk": chunk,
            "ids": ids, "atol": atol,
            "block": getattr(model, "block_length", 0)}


def _prompts(toy, lengths, seed=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(*toy["ids"], n).tolist() for n in lengths]


def _cache(toy, slots=4):
    # 64 positions fit every toy's configured max_len and its windows
    return toy["model"].make_cache(slots, max_len=64)


def _rows(model, cache, slots):
    """Every kv entry's rows of each slot, and the lengths of each slot in
    the cache and in every group of a grouped one."""
    kv = [name for name, spec in cache.spec.items() if spec[0] == "kv"]
    lengths = [[int(cache.lengths[s]) for s in slots]] + [
        [int(cache.group_lengths(group)[s]) for s in slots]
        for group in getattr(model, "kv_groups", ())]
    return {(name, slot): cache.prefix(name, slot)
            for name in kv for slot in slots}, lengths


def test_a_round_of_several_prompts_leaves_the_cache_one_a_forward_leaves(
        toy):
    """Prompts of 3 chunks and a bit, of one chunk, of two, and shorter
    than a chunk (whole blocks for the block decoder): one round of four
    rows, then rounds padded with rows that commit nothing; the same rows
    and lengths as a forward a prompt, in every entry and group."""
    chunk, block = toy["chunk"], toy["block"]
    lengths = ([3 * chunk + block, chunk, 2 * chunk, block] if block
               else [3 * chunk + 1, chunk, 2 * chunk, chunk - 3])
    prompts = _prompts(toy, lengths)
    model = toy["model"]
    one, batched = _cache(toy), _cache(toy)
    slots = [[c.alloc() for _ in prompts] for c in (one, batched)]
    with jax.default_matmul_precision("highest"):
        for slot, prompt in zip(slots[0], prompts):
            prefill_slot(model, one, slot, prompt, chunk)
        fed = list(prefill_slots(model, batched, slots[1], prompts, chunk,
                                 len(prompts)))
        one.sync()
        batched.sync()
    assert fed == [sum(min(chunk, n) for n in lengths)] + [
        sum(min(chunk, max(0, n - k * chunk)) for n in lengths)
        for k in (1, 2, 3)]
    got, want = _rows(model, batched, slots[1]), _rows(model, one, slots[0])
    assert got[1] == want[1]
    assert got[1][0] == lengths
    for (name, slot), rows in want[0].items():
        np.testing.assert_allclose(
            got[0][name, slots[1][slots[0].index(slot)]], rows, rtol=0,
            atol=toy["atol"],
            err_msg="%s %s of slot %d" % (toy["name"], name, slot))


def test_a_greedy_call_serves_what_one_prompt_a_forward_serves(
        toy, monkeypatch):
    """The same call with R from the rule (4 rows a round) and with R held
    to one: the same tokens; a prompt with nothing to prefill (one token,
    or shorter than a block) is skipped, and the rounds are fewer."""
    chunk, block = toy["chunk"], toy["block"]
    short = block - 1 if block else 1
    lengths = [3 * chunk + 3, short, chunk + 2, 2 * chunk - 1]
    prompts = _prompts(toy, lengths, seed=6)
    kw = {"denoise_steps": 2} if block else {}
    served, rounds = [], []
    for positions in (engine_mod.PREFILL_ROUND_POSITIONS, chunk):
        monkeypatch.setattr(engine_mod, "PREFILL_ROUND_POSITIONS", positions)
        eng = GenerateEngine(toy["model"], _cache(toy), prefill_chunk=chunk,
                             **kw)
        with jax.default_matmul_precision("highest"):
            served.append(eng.generate(prompts, max_new_tokens=6))
        rounds.append(eng.last_stats["prefill_forwards"])
        n = [len(p) // block * block if block else len(p) - 1
             for p in prompts]
        assert eng.last_stats["prefill_rows"] == sum(
            -(-k // chunk) for k in n)
        assert eng.last_stats["prefill_tokens"] == sum(n)
    assert served[0] == served[1]
    assert [len(s) for s in served[0]] == [6] * 4
    assert rounds[0] < rounds[1] == eng.last_stats["prefill_rows"]


# ------------------------------------------------------------ the rule for R
CELLS = [("gpt2_xl.generate_short", 4), ("sdar_30b_a3b.generate_blocks", 16),
         ("xing4_29b_a4b.generate_long_prompts", 2),
         ("kimi_k2_7_code.generate_wide_batch", 4),
         ("evabyte_6_5b.generate_file_completions", 1),
         ("ouro_2_6b.generate_reasoning_answers", 1)]
ADAPTERS = {"gpt2": GPTPagedLM, "sdar_moe": SDARPagedLM, "xing4": MLAPagedLM,
            "kimi_k2": MLAPagedLM, "evabyte": EvaPagedLM,
            "ouro": OuroPagedLM}


@pytest.mark.parametrize("cell,rows", CELLS, ids=[c for c, _r in CELLS])
def test_the_rows_a_round_of_each_cell(cell, rows):
    """R of the benchmark's generation cells, from their files as they
    stand: the chunk the family gives the engine (the configuration's, the
    traffic's, or the engine's default) and the bytes a position adds to
    the cache, of an adapter made with no weights."""
    bench = _json("BENCHMARK.json")
    (work,) = [w for w in bench["workloads"] if w["name"] == cell]
    (config,) = [c for c in bench["configs"] if c["name"] == work["config"]]
    cfg = _json(config["file"])
    traffic = _json("benchmarks", "traffic", work["traffic"] + ".json")
    family = importlib.import_module(cfg["family"])
    name = cfg["family"].rsplit(".", 1)[-1]
    kw = {} if name == "gpt2" else {"dtype": cfg["dtype"]}
    model = ADAPTERS[name]({}, family.program_config(cfg), **kw)
    chunk = traffic.get("prefill_chunk") or cfg.get("assumed", {}).get(
        "prefill_chunk", {}).get("value") or engine_mod.default_prefill_chunk()
    block = getattr(model, "block_length", 0)
    prompts = sum(1 for n in traffic["prompt_lens"]
                  if (n // block * block if block else n - 1) > 0)
    assert prefill_rows(chunk, position_bytes(model), prompts) == rows


def test_the_rule_takes_the_least_of_its_three_bounds():
    assert engine_mod.PREFILL_ROUND_POSITIONS == 2048
    assert engine_mod.PREFILL_ROUND_BYTES == 128 << 20
    # the prompts; the positions; the bytes; one at the least
    assert prefill_rows(32, 1000, 3) == 3
    assert prefill_rows(512, 1000, 128) == 4
    assert prefill_rows(96, 2 ** 20, 16) == 1
    assert prefill_rows(4096, 1, 8) == 1
    # cell 8's cache: 48 layers x K and V x 4 loop steps x 2,048 bf16 lanes
    assert prefill_rows(256, 48 * 2 * 8192 * 2, 8) == 1


def test_the_rounds_feed_the_longest_first_and_every_chunk_in_order():
    rounds = prefill_rounds([3, 1, 2, 1], 2)
    assert rounds == [[0, 2], [0, 1], [0, 2], [3]]
    for i, n in enumerate([3, 1, 2, 1]):
        assert sum(i in r for r in rounds) == n
    assert prefill_rounds([2, 2], 4) == [[0, 1], [0, 1]]
    assert prefill_rounds([], 4) == prefill_rounds([0], 1) == []


# -------------------------------------------- programs, forwards and counters
@pytest.fixture
def gpt():
    family = importlib.import_module("benchmarks.families.gpt2")
    cfg = _json("tests", "benchmark", "configs", "gpt2_tiny.json")
    return GPTPagedLM(family.reference.init_weights(cfg, 3),
                      family.program_config(cfg))


@pytest.fixture
def metrics_on():
    telemetry.enable()
    _met.reset()
    yield
    _met.reset()
    telemetry.disable()


def test_a_second_call_of_as_many_prompts_compiles_nothing(gpt, metrics_on):
    """Every round of a call is fed at (R, chunk): a call of four prompts
    of other lengths, so other rounds and other pad rows, runs the
    programs the first compiled."""
    cat.install_jax_compile_hook()
    eng = GenerateEngine(gpt, gpt.make_cache(4, max_len=64), prefill_chunk=4)
    rng = np.random.default_rng(1)
    eng.generate([rng.integers(1, 1100, n).tolist() for n in (13, 2, 9, 6)],
                 max_new_tokens=3)
    first = eng.last_stats["prefill_forwards"]
    before = cat.compile_events()
    assert before > 0
    eng.generate([rng.integers(1, 1100, n).tolist() for n in (5, 17, 3, 8)],
                 max_new_tokens=3)
    assert eng.last_stats["prefill_forwards"] != first
    assert cat.compile_events() == before


def test_the_stats_and_counters_count_rounds_and_prompt_chunks(gpt,
                                                               metrics_on):
    """Prompts of 13, 2, 9 and 1 tokens commit 12, 1, 8 and 0 in chunks of
    4: R is the three with work, three rounds of 3, 2 and 1 prompts (the
    last two padded), 6 prompt chunks; the pad rows count nowhere."""
    eng = GenerateEngine(gpt, gpt.make_cache(4, max_len=64), prefill_chunk=4,
                         name="rounds")
    dispatched = []
    dispatch = engine_mod._dispatch

    def logged(fn, params, args, *rest, **kw):
        dispatched.append(args[0].shape)
        return dispatch(fn, params, args, *rest, **kw)
    engine_mod._dispatch = logged
    try:
        rng = np.random.default_rng(2)
        eng.generate([rng.integers(1, 1100, n).tolist()
                      for n in (13, 2, 9, 1)], max_new_tokens=2)
    finally:
        engine_mod._dispatch = dispatch
    st = eng.last_stats
    assert dispatched[:3] == [(3, 4)] * 3      # the rounds, then the steps
    assert (st["prefill_forwards"], st["prefill_rows"]) == (3, 6)
    assert st["prefill_tokens"] == 12 + 1 + 8
    assert cat.gen_prefill_forwards.value(model="rounds") == 3
    assert cat.gen_prefill_rows.value(model="rounds") == 6
    assert cat.gen_prefill_seconds.count(model="rounds") == 3
    eng.generate([[5, 6]], max_new_tokens=2)   # R = 1: one round, one row
    assert (eng.last_stats["prefill_forwards"],
            eng.last_stats["prefill_rows"]) == (1, 1)
    assert cat.gen_prefill_forwards.value(model="rounds") == 4
    assert cat.gen_prefill_rows.value(model="rounds") == 7
