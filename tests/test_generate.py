"""generate/ suite: paged KV cache drop-in parity with the dense
KVCache (lifecycle, error messages, eviction reuse, ragged blocks,
truncate, pool exhaustion), flash-decode numerics (lax reference vs
naive softmax, Pallas kernel in interpret mode, randomized
shapes/dtypes), GPT full-forward vs incremental paged decode, engine
invariants (prefill-chunk invariance, speculative-vs-plain greedy
BIT-IDENTICAL pin, greedy-only guard), the serving gpt_decoder family
end to end through ModelServer, the retire-path token-accounting pin,
and the two-process zero-compile warm drill for the decode grid."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, serving, telemetry
from incubator_mxnet_tpu.generate import (GenerateEngine, GPTPagedLM,
                                          MLAPagedLM,
                                          PagedKVCache,
                                          export_gpt_for_serving)
from incubator_mxnet_tpu.generate import paged_kv
from incubator_mxnet_tpu.generate.engine import (forward_slots, prefill_slot,
                                                 step_slots)
from incubator_mxnet_tpu.generate.paged_kv import KVPoolExhausted
from incubator_mxnet_tpu.models.gpt import (GPTDecoder, gpt_config,
                                            gpt_logits, gpt_param_shapes)
from incubator_mxnet_tpu.ops.pallas import (paged_causal_attention,
                                            paged_flash_decode)
from incubator_mxnet_tpu.serving import kv_cache
from incubator_mxnet_tpu.serving.decode import DecodeLoop, DecodeRequest
from incubator_mxnet_tpu.telemetry import catalog as cat
from incubator_mxnet_tpu.telemetry import metrics as _met


@pytest.fixture(autouse=True)
def _telemetry():
    telemetry.enable()
    _met.reset()
    yield
    _met.reset()
    telemetry.disable()


def _kv_spec(layers=1, H=2, D=4):
    spec = {}
    for i in range(layers):
        spec["k%d" % i] = ("kv", (H, D))
        spec["v%d" % i] = ("kv", (H, D))
    return spec


def _params(cfg, seed, scale=0.05):
    rng = np.random.RandomState(seed)
    return {n: (rng.randn(*s) * scale).astype(np.float32)
            for n, s in gpt_param_shapes(cfg).items()}


_TCFG = gpt_config({"vocab_size": 29, "units": 24, "num_layers": 2,
                    "num_heads": 2, "max_len": 64})
_DCFG = gpt_config({"vocab_size": 29, "units": 12, "num_layers": 1,
                    "num_heads": 2, "max_len": 64})


@pytest.fixture(scope="module")
def target_lm():
    return GPTPagedLM(_params(_TCFG, 7), _TCFG)


@pytest.fixture(scope="module")
def draft_lm():
    return GPTPagedLM(_params(_DCFG, 8), _DCFG)


# ------------------------------------------------------- paged KV cache
def test_paged_kv_is_dropin_for_dense_surface():
    """Same op sequence against KVCache and PagedKVCache: identical
    alloc order, lengths, prefix contents, and state round trips."""
    spec = {"h": ("state", (3,)), "k0": ("kv", (2, 4)), "v0": ("kv", (2, 4))}
    dense = kv_cache.KVCache(3, spec, max_len=10)
    paged = PagedKVCache(3, spec, max_len=10, block_size=4)
    rng = np.random.RandomState(0)
    for step in range(7):
        if step == 0:
            assert dense.alloc() == paged.alloc() == 0
            assert dense.alloc() == paged.alloc() == 1
        if step == 3:
            dense.free(0)
            paged.free(0)
            assert dense.alloc() == paged.alloc() == 0   # LIFO reuse
        for slot in (0, 1):
            k = rng.randn(2, 4).astype(np.float32)
            v = rng.randn(2, 4).astype(np.float32)
            for c in (dense, paged):
                c.append("k0", slot, k)
                c.append("v0", slot, v)
                c.advance(slot)
        h = rng.randn(3).astype(np.float32)
        dense.set_state("h", 1, h)
        paged.set_state("h", 1, h)
    for slot in (0, 1):
        assert int(dense.lengths[slot]) == int(paged.lengths[slot])
        for name in ("k0", "v0"):
            np.testing.assert_array_equal(dense.prefix(name, slot),
                                          paged.prefix(name, slot))
    np.testing.assert_array_equal(dense.state("h", 1), paged.state("h", 1))
    assert dense.in_use == paged.in_use == 2


def test_paged_kv_guards_match_dense_errors():
    paged = PagedKVCache(1, _kv_spec(), max_len=4, block_size=4)
    with pytest.raises(ValueError, match="not live"):
        paged.append("k0", 0, np.zeros((2, 4)))
    with pytest.raises(ValueError, match="not live"):
        paged.free(99)
    slot = paged.alloc()
    assert paged.alloc() is None                    # grid full
    with pytest.raises(KeyError):
        paged.append("nope", slot, 0)
    for _ in range(4):
        paged.append("k0", slot, np.zeros((2, 4)))
        paged.append("v0", slot, np.zeros((2, 4)))
        paged.advance(slot)
    with pytest.raises(ValueError, match=r"slot 0 is full \(max_len=4\)"):
        paged.append("k0", slot, np.zeros((2, 4)))
    mixed = PagedKVCache(1, {"h": ("state", (2,)), "k0": ("kv", (2, 4))},
                         max_len=4)
    s = mixed.alloc()
    with pytest.raises(ValueError, match="not state"):
        mixed.set_state("k0", s, np.zeros((2, 4)))
    with pytest.raises(ValueError, match="not kv"):
        mixed.append("h", s, np.zeros(2))
    with pytest.raises(ValueError, match="not kv"):
        mixed.prefix("h", s)
    with pytest.raises(ValueError, match="not kv"):
        mixed.pool("h")


def test_paged_kv_ragged_last_block_and_single_block():
    paged = PagedKVCache(2, _kv_spec(), max_len=12, block_size=4)
    slot = paged.alloc()
    for i in range(6):                              # 1.5 blocks
        paged.append("k0", slot, np.full((2, 4), i, np.float32))
        paged.append("v0", slot, np.full((2, 4), -i, np.float32))
        paged.advance(slot)
    assert len(paged.table(slot)) == 2              # ragged last block
    got = paged.prefix("k0", slot)
    assert got.shape == (6, 2, 4)
    np.testing.assert_array_equal(got[:, 0, 0], np.arange(6))
    single = paged.alloc()
    paged.append("k0", single, np.ones((2, 4)))
    paged.append("v0", single, np.ones((2, 4)))
    paged.advance(single)
    assert len(paged.table(single)) == 1
    assert paged.prefix("k0", single).shape == (1, 2, 4)
    # ragged waste is what fragmentation measures: 7 filled / 12 mapped
    assert paged.fragmentation() == pytest.approx(1.0 - 7.0 / 12.0)


def test_paged_kv_eviction_reuse_never_shows_a_stale_tail():
    """A freed slot's blocks go back to the pool, and another slot maps
    them as they are: the reused block still holds its last owner's rows
    beyond the new owner's length, and no read goes there."""
    paged = PagedKVCache(2, _kv_spec(), max_len=8, block_size=4,
                         num_blocks=2)
    a = paged.alloc()
    for _ in range(8):
        paged.append("k0", a, np.full((2, 4), 9.0))
        paged.append("v0", a, np.full((2, 4), 9.0))
        paged.advance(a)
    blocks_a = paged.table(a)
    assert paged.blocks_free == 0
    paged.free(a)
    assert paged.blocks_free == 2
    b = paged.alloc()
    paged.append("k0", b, np.ones((2, 4)))
    paged.append("v0", b, np.ones((2, 4)))
    paged.advance(b)
    assert paged.table(b)[0] in blocks_a            # block reuse
    pool = np.asarray(paged.pool("k0"))
    assert (pool[paged.table(b)[0], 0] == 1).all()
    assert (pool[paged.table(b)[0], 1:] == 9).all()  # the stale tail
    np.testing.assert_array_equal(paged.prefix("k0", b),
                                  np.ones((1, 2, 4), np.float32))


def _prefill_and_step(lm, cache, prompt, feed):
    """-> (slot, the (len(feed), 1, V) logits of feeding `feed` token by
    token after `prompt` is prefilled)."""
    slot = cache.alloc()
    prefill_slot(lm, cache, slot, prompt, 4)
    return slot, np.stack([step_slots(lm, cache, [slot],
                                      np.asarray([[t]], np.int32))
                           for t in feed])


def test_a_reused_block_with_a_stale_tail_changes_no_logit_bit(target_lm):
    """The same sequence through a fresh cache and through blocks that
    another sequence filled and freed: after the prefill of 5 positions
    the second block holds one row of this sequence and three of the
    last, and every logit of every step is equal to the bit, so nothing
    has to zero a block on reuse."""
    prompt, feed = [3, 5, 7, 2, 11], [1, 5, 9]
    _slot, fresh = _prefill_and_step(
        target_lm, target_lm.make_cache(2, max_len=16, block_size=4),
        prompt, feed)
    used = target_lm.make_cache(2, max_len=16, block_size=4, num_blocks=4)
    slot, _ = _prefill_and_step(
        target_lm, used, [9, 8, 4, 6, 2, 7, 1, 3, 5, 6, 2, 4], [8, 1, 3])
    assert used.blocks_free == 0                    # 15 positions
    used.free(slot)
    before = np.asarray(used.pool("k0"))
    slot, again = _prefill_and_step(target_lm, used, prompt, feed)
    # every row of the blocks it mapped held the last sequence's keys
    assert before[used.table(slot)].any(axis=(2, 3)).all()
    np.testing.assert_array_equal(again, fresh)


def test_paged_kv_pool_exhaustion_and_truncate():
    paged = PagedKVCache(2, _kv_spec(), max_len=8, block_size=2,
                         num_blocks=3)
    a, b = paged.alloc(), paged.alloc()
    for _ in range(4):                  # a maps 2 blocks
        paged.append("k0", a, np.zeros((2, 4)))
        paged.append("v0", a, np.zeros((2, 4)))
        paged.advance(a)
    paged.append("k0", b, np.zeros((2, 4)))
    paged.append("v0", b, np.zeros((2, 4)))
    paged.advance(b)                    # b maps the 3rd — pool full
    assert paged.blocks_free == 0
    # b can still use its ragged block's second position...
    paged.append("k0", b, np.zeros((2, 4)))
    paged.append("v0", b, np.zeros((2, 4)))
    paged.advance(b)
    # ...but crossing into a 2nd block needs the pool
    with pytest.raises(ValueError, match="pool exhausted"):
        paged.append("k0", b, np.zeros((2, 4)))
    # truncating a to one block frees its suffix block for b
    paged.truncate(a, 2)
    assert int(paged.lengths[a]) == 2 and paged.blocks_free == 1
    paged.append("k0", b, np.zeros((2, 4)))
    paged.append("v0", b, np.zeros((2, 4)))
    paged.advance(b)
    assert int(paged.lengths[b]) == 3
    # truncate past current length is a no-op
    paged.truncate(a, 99)
    assert int(paged.lengths[a]) == 2
    with pytest.raises(ValueError, match=">= 0"):
        paged.truncate(a, -1)


def test_paged_kv_tables_array_and_gauges():
    paged = PagedKVCache(3, _kv_spec(), max_len=8, block_size=2,
                         name="gauged")
    slot = paged.alloc()
    for _ in range(3):
        paged.append("k0", slot, np.zeros((2, 4)))
        paged.append("v0", slot, np.zeros((2, 4)))
        paged.advance(slot)
    tables = paged.tables_array()
    assert tables.shape == (3, 4) and tables.dtype == np.int32
    np.testing.assert_array_equal(tables[slot, :2], paged.table(slot))
    assert (tables[slot, 2:] == 0).all()            # padded with block 0
    sub = paged.tables_array([slot])
    assert sub.shape == (1, 4)
    assert cat.gen_kv_blocks_in_use.value(name="gauged") == 2
    assert cat.gen_kv_blocks_free.value(name="gauged") == 10
    assert cat.gen_kv_fragmentation.value(name="gauged") \
        == pytest.approx(1.0 - 3.0 / 4.0)


# ---------------------------------------------------- commit on device
def _chunk(rng, layers, S, C, H=2, D=4):
    """A forward's new_k, new_v: per layer (S, C, H, D), on the device."""
    return [[jnp.asarray(rng.randn(S, C, H, D), jnp.float32)
             for _ in range(layers)] for _ in "kv"]


def _append_loop(cache, slots, new_k, new_v, counts):
    """What `commit` replaced: the token x layer loop of appends."""
    for row, slot in enumerate(slots):
        for c in range(counts[row]):
            for i in range(len(new_k)):
                cache.append("k%d" % i, slot, np.asarray(new_k[i])[row, c])
                cache.append("v%d" % i, slot, np.asarray(new_v[i])[row, c])
            cache.advance(slot)


def _same_cache(a, b):
    for name in a.spec:
        np.testing.assert_array_equal(np.asarray(a.pool(name)),
                                      np.asarray(b.pool(name)))
    np.testing.assert_array_equal(a.lengths, b.lengths)
    assert a._tables == b._tables and a._free_blocks == b._free_blocks


@pytest.mark.parametrize("chunks", [
    [(4, 4)],                           # a whole chunk, one block
    [(4, 3)],                           # count < C
    [(8, 5), (8, 8), (8, 2)],           # padded prefill chunks over blocks
    [(1, 1)] * 6,                       # decode steps across a boundary
    [(6, [6, 0, 3]), (6, [1, 6, 0])],   # a count a row, rows that sit out
], ids=["whole", "count_below_C", "padded_chunks", "steps", "count_a_row"])
def test_commit_stores_what_a_loop_of_appends_stores(chunks):
    """Pool for pool, bit for bit, with the same lengths, tables and
    free list; `stored` is chunk after chunk of one sequence of forwards."""
    rng = np.random.RandomState(len(chunks))
    layers, S = 2, 3
    ours, oracle = [PagedKVCache(S, _kv_spec(layers), max_len=24,
                                 block_size=4) for _ in range(2)]
    slots = [ours.alloc() for _ in range(S)]
    assert slots == [oracle.alloc() for _ in range(S)]
    for C, count in chunks:
        new_k, new_v = _chunk(rng, layers, S, C)
        ours.commit(slots, new_k, new_v, count)
        _append_loop(oracle, slots, new_k, new_v,
                     np.broadcast_to(count, S))
        _same_cache(ours, oracle)
    assert cat.gen_kv_blocks_in_use.value(name="default") \
        == ours.blocks_in_use


def test_commit_takes_one_array_stacked_over_layers():
    """As the adapters return K and V: an output buffer costs a launch
    some 50 us on the chip's host, so a forward stacks its layers."""
    rng = np.random.RandomState(0)
    ours, oracle = [PagedKVCache(2, _kv_spec(3), max_len=8, block_size=4)
                    for _ in range(2)]
    slots = [ours.alloc(), ours.alloc()]
    assert slots == [oracle.alloc(), oracle.alloc()]
    new_k, new_v = _chunk(rng, 3, 2, 3)
    ours.commit(slots, jnp.stack(new_k), jnp.stack(new_v), [3, 2])
    oracle.commit(slots, new_k, new_v, [3, 2])
    _same_cache(ours, oracle)


def test_commit_refuses_a_cache_whose_entries_are_not_layers():
    new_k, new_v = _chunk(np.random.RandomState(0), 1, 1, 3)
    named = PagedKVCache(1, {"keys": ("kv", (2, 4))}, max_len=8)
    slot = named.alloc()
    with pytest.raises(ValueError, match="k<i> and v<i>"):
        named.commit([slot], new_k, new_v, 1)
    named.append("keys", slot, np.ones((2, 4)))     # the slow surface
    named.advance(slot)
    assert named.prefix("keys", slot).shape == (1, 2, 4)


# ------------------------------------------------ groups of kv entries
def _grouped_cache(slots=2, layers=2, window=8, per=2, closings=3,
                   block=4):
    """A window of `window` exact rows in blocks of `block` beside `per`
    summary rows a closing, a block a closing: the leading group first."""
    spec = PagedKVCache.layer_spec(
        layers, dict.fromkeys(("wk", "wv", "sk", "sv"),
                              ((2, 4), np.float32)),
        groups={"window": ("wk", "wv"), "summary": ("sk", "sv")})
    return PagedKVCache(slots, spec, max_len=window * closings + 3, groups={
        "window": {"max_len": window, "block_size": block},
        "summary": {"max_len": per * closings, "block_size": per}})


def test_a_grouped_specs_entries_name_their_groups():
    spec = PagedKVCache.layer_spec(
        2, {"wk": ((2, 4), np.float32), "sk": ((2, 4), np.float32)},
        groups={"window": ("wk",), "summary": ("sk",)})
    assert spec == {"wk0": ("kv", (2, 4), np.float32, "window"),
                    "sk0": ("kv", (2, 4), np.float32, "summary"),
                    "wk1": ("kv", (2, 4), np.float32, "window"),
                    "sk1": ("kv", (2, 4), np.float32, "summary")}
    with pytest.raises(ValueError, match="do not part the entries"):
        PagedKVCache.layer_spec(1, {"wk": ((2,), np.float32)},
                                groups={"window": ("wk", "wv")})
    with pytest.raises(ValueError, match="has no geometry"):
        PagedKVCache(1, spec, groups={"window": {}})
    assert PagedKVCache.layer_spec(1, {"k": ((2,), np.float32)}) \
        == {"k0": ("kv", (2,), np.float32)}


@pytest.mark.parametrize("case", ["lengths_and_tables", "window_reused",
                                  "free_returns_both", "truncate",
                                  "slow_surface", "full"])
def test_a_grouped_cache_keeps_a_length_and_a_table_a_group(case):
    rng = np.random.RandomState(3)
    cache = _grouped_cache()
    a, b = cache.alloc(), cache.alloc()
    window, summary = cache.group_lengths("window"), \
        cache.group_lengths("summary")

    def step(slots, count, C=None):
        nk, nv = _chunk(rng, 2, len(slots), C or max(np.atleast_1d(count)))
        cache.commit(slots, nk, nv, count)
        return nk, nv

    def close(slot):
        sk, sv = _chunk(rng, 2, 1, 2)
        cache.commit([slot], sk, sv, 2, group="summary")
        cache.restart(slot, "window")
        return sk, sv

    if case == "lengths_and_tables":
        step([a, b], [5, 2], C=5)
        assert cache.lengths[[a, b]].tolist() == [5, 2]
        assert window[[a, b]].tolist() == [5, 2]
        assert summary[[a, b]].tolist() == [0, 0]
        assert [len(cache.table(s)) for s in (a, b)] == [2, 1]
        assert cache.table(a, "summary") == []
        sk, _sv = close(a)
        assert (cache.lengths[a], window[a], summary[a]) == (5, 0, 2)
        np.testing.assert_array_equal(cache.prefix("sk1", a),
                                      np.asarray(sk[1])[0])
        wlen, wtab, slen, stab, *pools = cache.forward_inputs([b, a])
        assert wlen.tolist() == [2, 0] and slen.tolist() == [0, 2]
        assert wlen.dtype == slen.dtype == np.int32
        assert wtab.shape == (2, 2) and stab.shape == (2, 3)
        assert stab[1, 0] == cache.table(a, "summary")[0]
        assert [len(p) for p in pools] == [2, 2, 2, 2]
        assert pools[0][0] is cache.pool("wk0")
        assert pools[3][1] is cache.pool("sv1")
        assert pools[0][0].shape == (4, 4, 2, 4)       # 2 slots x 8 / 4
        assert pools[2][0].shape == (6, 2, 2, 4)       # 2 slots x 3 x 2 / 2
        assert cache.group_pools("window") == tuple(pools[:2])
        assert cache.tables_array(group="summary").shape == (2, 3)
    elif case == "window_reused":
        step([a], 8)
        blocks = cache.table(a)
        assert window[a] == 8 and len(blocks) == 2
        close(a)
        nk, _nv = step([a], 3)
        # no growth past a window's rows: the same blocks, rewritten from
        # row 0; the sequence's positions go on
        assert cache.table(a) == blocks and window[a] == 3
        assert cache.lengths[a] == 11 and summary[a] == 2
        np.testing.assert_array_equal(cache.prefix("wk0", a),
                                      np.asarray(nk[0])[0, :3])
        step([a], 5)
        close(a)
        assert (cache.lengths[a], window[a], summary[a]) == (16, 0, 4)
        assert cache.table(a) == blocks and len(cache.table(a, "summary")) == 2
    elif case == "free_returns_both":
        step([a, b], [8, 3], C=8)
        close(a)
        assert cache.blocks_in_use == 2 + 1 + 1
        assert cache.num_blocks == 4 + 6
        assert 0 < cache.fragmentation() < 1
        cache.free(a)
        assert cache.blocks_in_use == 1 and summary[a] == window[a] == 0
        cache.free(b)
        assert cache.blocks_in_use == 0 and cache.blocks_free == 10
        c = cache.alloc()
        assert (cache.lengths[c], cache.table(c),
                cache.table(c, "summary")) == (0, [], [])
    elif case == "truncate":
        step([a], 8)
        close(a)
        with pytest.raises(ValueError, match="the rest were closed"):
            cache.truncate(a, 7)        # across the closing
        step([a], 6)
        cache.truncate(a, 20)           # not a roll back: a no-op
        cache.truncate(a, 9)            # inside the live window
        assert (cache.lengths[a], window[a], len(cache.table(a))) \
            == (9, 1, 1)
        with pytest.raises(ValueError, match="the rest were closed"):
            cache.truncate(a, 7)
        cache.truncate(a, 8)
        assert (cache.lengths[a], window[a], summary[a]) == (8, 0, 2)
    elif case == "slow_surface":
        cache.append("wk0", a, np.ones((2, 4)))
        cache.advance(a)
        cache.append("sk0", a, np.full((2, 4), 2.0))
        cache.advance(a, "summary")
        assert (cache.lengths[a], window[a], summary[a]) == (1, 1, 1)
        assert cache.prefix("sk0", a)[0, 0, 0] == 2.0
        with pytest.raises(ValueError, match="no group 'recent'"):
            cache.advance(a, "recent")
    else:
        step([a], 8)
        with pytest.raises(ValueError, match=r"slot 0 is full \(max_len=8\)"):
            step([a], 1)                # a full window takes no row
        close(a)
        step([a], 8)
        close(a)
        step([a], 8)
        close(a)
        step([a], 3)
        with pytest.raises(ValueError,
                           match=r"slot 0 is full \(max_len=27\)"):
            step([a], 1)                # the sequence's positions
        with pytest.raises(ValueError, match=r"slot 0 is full \(max_len=6\)"):
            close(a)                    # the summaries' rows


def test_an_ungrouped_specs_inputs_and_commit_are_what_they_were():
    """A spec that names no group: ``forward_inputs`` is (lengths, tables,
    K pools, V pools), ``commit`` runs the one program of two entries, and
    the cache's own arrays ARE its one group's."""
    rng = np.random.RandomState(1)
    cache = PagedKVCache(2, _kv_spec(2), max_len=8, block_size=4)
    a, b = cache.alloc(), cache.alloc()
    new_k, new_v = _chunk(rng, 2, 2, 3)
    before = paged_kv.store_program_for(2)._cache_size()
    cache.commit([a, b], new_k, new_v, [3, 1])
    assert paged_kv.store_program_for(2)._cache_size() == before + 1
    assert paged_kv.store_program_for(2) is paged_kv.store_program
    lengths, tables, k_pools, v_pools = cache.forward_inputs([b, a])
    assert lengths.tolist() == [1, 3] and lengths.dtype == np.int32
    assert tables.shape == (2, 2) and tables.dtype == np.int32
    assert k_pools[1] is cache.pool("k1") and v_pools[0] is cache.pool("v0")
    assert cache.group_lengths(None) is cache.lengths
    assert cache.group_pools() == (k_pools, v_pools)
    assert (cache.num_blocks, cache.block_size,
            cache.max_blocks_per_slot) == (4, 4, 2)
    with pytest.raises(ValueError, match="no group 'window'"):
        cache.commit([a], new_k, new_v, 1, group="window")


@pytest.mark.parametrize("steps", [1, 4], ids=["unaxed", "four_loop_steps"])
def test_a_row_of_loop_steps_is_one_entry_behind_one_table(steps):
    """A looped model's spec (``OuroPagedLM``): one k and one v entry a
    layer whose row holds every loop step's H D lanes side by side. One
    table and one length serve all steps, ``forward_inputs`` hands 2 x L
    pools as for a plain decoder, ONE commit stores a forward's T steps
    (the one program of two entries, as a plain decoder's), and step u's
    rows read back from lanes ``[u H D, (u + 1) H D)``; at one step it is
    the plain per-head cache."""
    rng = np.random.RandomState(steps)
    width = 2 * 4                                   # H D
    spec = PagedKVCache.layer_spec(3, dict.fromkeys(
        "kv", ((steps * width,), np.float32)))
    assert sorted(spec) == ["k0", "k1", "k2", "v0", "v1", "v2"]
    cache = PagedKVCache(2, spec, max_len=16, block_size=4)
    a, b = cache.alloc(), cache.alloc()
    # a forward's additions: (L, S, C, T H D), step u in its own lanes
    new_k, new_v = [jnp.asarray(rng.randn(3, 2, 5, steps * width),
                                jnp.float32) for _ in "kv"]
    before = paged_kv.store_program_for(2)._cache_size()
    cache.commit([a, b], new_k, new_v, [5, 2])
    assert paged_kv.store_program_for(2)._cache_size() == before + 1
    lengths, tables, k_pools, v_pools = cache.forward_inputs([a, b])
    assert lengths.tolist() == [5, 2] and tables.shape == (2, 4)
    assert len(k_pools) == len(v_pools) == 3
    assert k_pools[2].shape == (8, 4, steps * width)
    for u in range(steps):
        lanes = slice(u * width, (u + 1) * width)
        for layer in range(3):
            np.testing.assert_array_equal(
                cache.prefix("k%d" % layer, a)[:, lanes],
                np.asarray(new_k)[layer, 0, :5, lanes])
            np.testing.assert_array_equal(
                cache.prefix("v%d" % layer, b)[:, lanes],
                np.asarray(new_v)[layer, 1, :2, lanes])
    assert cache.table(a) == [tables[0, 0], tables[0, 1]]


@pytest.mark.parametrize("shape,order", [
    ((2, 4), (0, 2, 1, 3)), ((2, 4), (2, 0, 1, 3)), ((3, 2, 4), None),
    ((5,), (1, 0, 2)), ((), None)],
    ids=["heads_over_positions", "heads_first", "rank5", "rank3", "rank2"])
def test_the_scatter_stores_the_same_rows_in_any_device_order(shape, order):
    """The commit's scatter runs over the pool as the device holds it
    (a TPU keeps 25 heads above the 16 positions of a block); whatever
    that order, the rows stored are the same, and a position past the
    pool is dropped."""
    from incubator_mxnet_tpu.generate.paged_kv import _put
    rng = np.random.RandomState(len(shape))
    pool = rng.randn(3, 4, *shape).astype(np.float32)
    new = rng.randn(5, *shape).astype(np.float32)
    rows = np.asarray([7, 0, 12, 3, 10], np.int32)      # 12: past the pool
    want = pool.reshape((12,) + shape).copy()
    want[rows[rows < 12]] = new[rows < 12]
    order = order or tuple(range(pool.ndim))
    got = jax.jit(lambda *a: _put(*a, order, {}))(pool, rows, new)
    np.testing.assert_array_equal(np.asarray(got), want.reshape(pool.shape))


def test_commit_retires_a_program_bound_for_other_pools():
    """An owner that ships executables puts compiled commit programs into
    ``cache.programs``; one compiled for another geometry refuses its
    arguments before it runs, is retired, and the jitted program stores
    the same rows."""
    from incubator_mxnet_tpu.generate.paged_kv import (device_order,
                                                       store_program)
    rng = np.random.RandomState(3)
    ours, oracle, other = [PagedKVCache(1, _kv_spec(), max_len=n,
                                        block_size=4) for n in (8, 8, 16)]
    new_k, new_v = _chunk(rng, 1, 1, 3)
    rows = np.zeros((1, 3), np.int32)
    orders = (device_order(ours.pool("k0")),) * 2
    ours.programs[(1, 3)] = store_program.lower(
        [ours.pool("k0")], [ours.pool("v0")], new_k, new_v, rows,
        orders).compile()
    oracle.programs[(1, 3)] = store_program.lower(
        [other.pool("k0")], [other.pool("v0")], new_k, new_v, rows,
        orders).compile()
    ours.commit([ours.alloc()], new_k, new_v, 2)
    oracle.commit([oracle.alloc()], new_k, new_v, 2)
    assert list(ours.programs) == [(1, 3)] and not oracle.programs
    _same_cache(ours, oracle)
    np.testing.assert_array_equal(ours.prefix("v0", 0),
                                  np.asarray(new_v[0])[0, :2])


def test_commit_runs_out_of_blocks_where_the_appends_did():
    """Three blocks of two positions: row 0 takes two, row 1 the third and
    needs a fourth at its third position. Both ways raise there and leave
    the same lengths, tables and pools: what was mapped is stored."""
    rng = np.random.RandomState(1)
    ours, oracle = [PagedKVCache(2, _kv_spec(), max_len=8, block_size=2,
                                 num_blocks=3, name="tight")
                    for _ in range(2)]
    slots = [ours.alloc(), ours.alloc()]
    assert slots == [oracle.alloc(), oracle.alloc()]
    new_k, new_v = _chunk(rng, 1, 2, 4)
    with pytest.raises(KVPoolExhausted, match="slot 1 needs block 1"):
        ours.commit(slots, new_k, new_v, [4, 3])
    with pytest.raises(KVPoolExhausted, match="slot 1 needs block 1"):
        _append_loop(oracle, slots, new_k, new_v, [4, 3])
    assert ours.lengths.tolist() == [4, 2]
    _same_cache(ours, oracle)
    assert cat.gen_kv_pool_exhausted.value(name="tight") == 2
    # a full slot: the same error as append's, after what fitted
    full = PagedKVCache(1, _kv_spec(), max_len=3, block_size=2)
    slot = full.alloc()
    with pytest.raises(ValueError, match="slot 0 is full"):
        full.commit([slot], [new_k[0][:1]], [new_v[0][:1]], 4)
    assert int(full.lengths[slot]) == 3
    np.testing.assert_array_equal(full.prefix("k0", slot),
                                  np.asarray(new_k[0])[0, :3])


def test_the_pools_stay_on_the_device_and_commit_donates_them(target_lm):
    """Device arrays before and after a call; the commit program's pools
    are donated, so the arrays handed to it are gone after it (a second
    copy of every pool would be the cost of a donation that did not
    take)."""
    cache = target_lm.make_cache(2, max_len=32)
    names = list(cache.spec)
    assert all(isinstance(cache.pool(n), jax.Array) for n in names)
    eng = GenerateEngine(target_lm, cache, prefill_chunk=4)
    eng.generate([[3, 5, 7, 2, 11, 1], [9, 8, 4]], max_new_tokens=3)
    assert all(isinstance(cache.pool(n), jax.Array) for n in names)
    before = [cache.pool(n) for n in names]
    slot = cache.alloc()
    new_k, new_v = _chunk(np.random.RandomState(2), 2, 1, 1, H=2, D=12)
    cache.commit([slot], new_k, new_v, 1)
    assert all(b.is_deleted() for b in before)
    assert not any(cache.pool(n).is_deleted() for n in names)
    assert not any(a.is_deleted() for a in new_k + new_v)
    before = cache.pool("k0")
    cache.append("k0", slot, np.zeros((2, 12)))
    assert before.is_deleted() and not cache.pool("k0").is_deleted()


# ------------------------------------------------------ flash decode op
def _fill_pool(rng, S, lengths, bs, mb, H, D, dtype=np.float32):
    """A paged pool + block tables with `lengths[s]` live positions."""
    nb = S * mb
    kp = rng.randn(nb, bs, H, D).astype(dtype)
    vp = rng.randn(nb, bs, H, D).astype(dtype)
    tables = np.zeros((S, mb), np.int32)
    for s in range(S):
        tables[s] = np.arange(s * mb, (s + 1) * mb)
    return kp, vp, tables


def _naive_past(q, kp, vp, tables, lengths, scale):
    """Dense softmax oracle for the past term."""
    S, C, H, D = q.shape
    bs = kp.shape[1]
    out = np.zeros((S, C, H, D), np.float32)
    for s in range(S):
        P = int(lengths[s])
        if P == 0:
            continue
        k = kp[tables[s]].reshape(-1, H, D)[:P].astype(np.float32)
        v = vp[tables[s]].reshape(-1, H, D)[:P].astype(np.float32)
        sc = np.einsum("chd,phd->chp", q[s].astype(np.float32), k) * scale
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[s] = np.einsum("chp,phd->chd", w, v)
    return out


@pytest.mark.parametrize("seed,S,H,D,bs,mb", [
    (0, 3, 2, 8, 4, 4), (1, 1, 1, 16, 8, 2), (2, 4, 3, 8, 16, 3)])
def test_flash_decode_lax_matches_naive_softmax(seed, S, H, D, bs, mb):
    rng = np.random.RandomState(seed)
    lengths = rng.randint(0, bs * mb + 1, S).astype(np.int32)
    lengths[0] = 0                                   # always one dead row
    q = rng.randn(S, 1, H, D).astype(np.float32)
    kp, vp, tables = _fill_pool(rng, S, lengths, bs, mb, H, D)
    scale = 1.0 / np.sqrt(D)
    o, m, l = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), tables, lengths,
                                 use_kernel=False)
    ref = _naive_past(q, kp, vp, tables, lengths, scale)
    np.testing.assert_allclose(np.asarray(o), ref, atol=1e-5)
    assert (np.asarray(o)[0] == 0).all()             # dead row exact zero
    assert float(np.asarray(l)[0, 0, 0]) == 0.0


@pytest.mark.parametrize("seed,S,H,D,bs,mb,dtype", [
    (3, 2, 2, 8, 8, 2, np.float32),
    (4, 3, 1, 16, 4, 4, np.float32),
    (5, 2, 2, 8, 8, 2, jnp.bfloat16)])
def test_flash_decode_kernel_interpret_matches_lax(seed, S, H, D, bs, mb,
                                                   dtype):
    """The Pallas kernel (interpret mode — the CPU tier-1 path) agrees
    with the lax reference on o, m, and l, including a dead row."""
    rng = np.random.RandomState(seed)
    lengths = rng.randint(0, bs * mb + 1, S).astype(np.int32)
    lengths[-1] = 0
    q = jnp.asarray(rng.randn(S, 1, H, D), dtype)
    kp = jnp.asarray(rng.randn(S * mb, bs, H, D), dtype)
    vp = jnp.asarray(rng.randn(S * mb, bs, H, D), dtype)
    tables = np.zeros((S, mb), np.int32)
    for s in range(S):
        tables[s] = np.arange(s * mb, (s + 1) * mb)
    o_ref, m_ref, l_ref = paged_flash_decode(q, kp, vp, tables, lengths,
                                             use_kernel=False)
    o_k, m_k, l_k = paged_flash_decode(q, kp, vp, tables, lengths,
                                       use_kernel=True, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_ref),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_ref),
                               rtol=2e-2 if dtype == jnp.bfloat16
                               else 1e-5, atol=1e-6)
    assert (np.asarray(o_k, np.float32)[-1] == 0).all()


@pytest.mark.parametrize("seed,C,past", [(0, 1, 5), (1, 4, 0),
                                         (2, 3, 7), (3, 8, 11)])
def test_paged_causal_attention_matches_dense_reference(seed, C, past):
    """Past-plus-chunk merge == dense causal softmax over the
    concatenated sequence, including the empty-past edge."""
    S, H, D, bs, mb = 2, 2, 8, 4, 4
    rng = np.random.RandomState(seed)
    lengths = np.full(S, past, np.int32)
    q = rng.randn(S, C, H, D).astype(np.float32)
    k_new = rng.randn(S, C, H, D).astype(np.float32)
    v_new = rng.randn(S, C, H, D).astype(np.float32)
    kp, vp, tables = _fill_pool(rng, S, lengths, bs, mb, H, D)
    scale = 1.0 / np.sqrt(D)
    out = np.asarray(paged_causal_attention(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kp), jnp.asarray(vp), tables, lengths,
        use_kernel=False))
    for s in range(S):
        k_all = np.concatenate(
            [kp[tables[s]].reshape(-1, H, D)[:past], k_new[s]], 0)
        v_all = np.concatenate(
            [vp[tables[s]].reshape(-1, H, D)[:past], v_new[s]], 0)
        for c in range(C):
            n = past + c + 1
            sc = np.einsum("hd,phd->hp", q[s, c],
                           k_all[:n].astype(np.float32)) * scale
            w = np.exp(sc - sc.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            ref = np.einsum("hp,phd->hd", w, v_all[:n])
            np.testing.assert_allclose(out[s, c], ref, atol=1e-5)


# ------------------------------------------------------------ gpt model
def test_gpt_full_forward_matches_incremental_paged_decode(target_lm):
    """Feeding a sequence token by token through the paged path yields
    the same next-token logits as the full dense causal forward."""
    tokens = [3, 5, 7, 2, 11, 1, 4, 9]
    full = np.asarray(gpt_logits(target_lm.params, _TCFG,
                                 jnp.asarray([tokens], jnp.int32)))[0]
    cache = target_lm.make_cache(1, max_len=32)
    slot = cache.alloc()
    inc = []
    for t in tokens:
        logits = step_slots(target_lm, cache, [slot],
                            np.asarray([[t]], np.int32))
        inc.append(logits[0])
    np.testing.assert_allclose(np.asarray(inc), full, atol=1e-4)
    cache.free(slot)


def test_a_forward_over_the_held_lookup_table_is_the_full_forward(target_lm):
    """The paged forward gathers its embeddings from the adapter's held
    table (``gpt_lookup_rows``: rows of 24 units, 16 side by side, fill 3
    lanes of 128) and its head reads ``wte``: a prefill round of two
    prompts gives every position the logits of ``gpt_logits`` over the
    same prefix, and the token head their argmax."""
    params, rows = target_lm.weights()
    assert rows.shape == (2, 384)
    np.testing.assert_array_equal(rows.reshape(32, 24)[:29], params["wte"])
    assert not np.asarray(rows.reshape(32, 24)[29:]).any()
    prompts = np.asarray([[3, 5, 7, 2, 11, 1], [9, 8, 4, 4, 0, 2]], np.int32)
    full = np.asarray(gpt_logits(params, _TCFG, jnp.asarray(prompts)))
    cache = target_lm.make_cache(2, max_len=32)
    slots = [cache.alloc(), cache.alloc()]
    logits, *_new = forward_slots(target_lm, cache, slots, prompts)
    np.testing.assert_allclose(logits, full, atol=1e-4)
    read, *_new = forward_slots(target_lm, cache, slots, prompts,
                                target_lm.forward_token)
    np.testing.assert_array_equal(np.asarray(read["token"])[:, 0],
                                  logits[:, -1].argmax(-1))


def test_gpt_decoder_block_registers_flat_params():
    m = GPTDecoder(prefix="tp_", vocab_size=11, units=8, num_layers=1,
                   num_heads=2, max_len=16)
    m.initialize(mx.init.Normal(0.05))
    out = m(nd.array(np.zeros((2, 3), np.int32)))
    assert out.shape == (2, 3, 11)
    names = set(m._collect_params_with_prefix())
    assert names == set(gpt_param_shapes(m.config))


def test_gpt_moe_config_shapes_and_forward():
    cfg = gpt_config({"vocab_size": 13, "units": 8, "num_layers": 1,
                      "num_heads": 2, "max_len": 16, "moe_experts": 2})
    shapes = gpt_param_shapes(cfg)
    assert shapes["h0_gate_weight"] == (8, 2)
    assert shapes["h0_expert_w1"] == (2, 8, 32)
    assert "h0_fc_w" not in shapes
    params = {n: jnp.asarray(np.random.RandomState(0).randn(*s) * 0.05,
                             jnp.float32) for n, s in shapes.items()}
    out = np.asarray(gpt_logits(params, cfg,
                                jnp.asarray([[1, 2, 3]], jnp.int32)))
    assert out.shape == (1, 3, 13) and np.isfinite(out).all()


# --------------------------------------------------------------- engine
def test_engine_prefill_chunk_invariance(target_lm):
    """Chunk width is an execution detail: any chunking of the prompt
    commits identical K/V, so greedy output can't depend on it."""
    prompts = [[3, 5, 7, 2, 11, 1, 4, 9, 8, 6, 2], [9, 8]]
    outs = []
    for chunk in (1, 3, 32):
        eng = GenerateEngine(target_lm, target_lm.make_cache(4, max_len=64),
                             prefill_chunk=chunk)
        outs.append(eng.generate(prompts, max_new_tokens=8))
    assert outs[0] == outs[1] == outs[2]


def test_generate_under_a_parent_span_leaves_the_forward_phases(target_lm):
    """One generate call under a parent span: every forward leaves
    kv.gather, lm.dispatch and kv.commit as children of its
    gen.prefill_round / gen.decode_step and an lm.fetch for what the host
    read BEHIND it, at most 5 records a forward, and the spans count what
    crosses where it crosses: lm.dispatch ships lengths and tables, the
    tokens only where the host feeds them (a prefill round, a call's first
    step), and no pool; lm.fetch copies back the ids of the forward before
    (the last step its own too) and nothing of a prefill round; kv.commit
    says how many pool rows an entry it stored; the last prefill round
    waits for the last commit (kv.sync). A prompt's gen.prefill, a child
    of gen.call, spans its rounds and is the parent of those it leads."""
    from incubator_mxnet_tpu.telemetry import tracing
    cache = target_lm.make_cache(2, max_len=64)
    eng = GenerateEngine(target_lm, cache, prefill_chunk=4)
    prompts = [[3, 5, 7, 2, 11, 1], [9, 8, 4]]
    tracing.clear_spans()
    with tracing.Span("test.call") as call:
        out = eng.generate(prompts, max_new_tokens=3)
    assert [len(o) for o in out] == [3, 3]
    recs = tracing.recent_spans()
    by_id = {r["span_id"]: r for r in recs}
    phases = ("kv.gather", "lm.dispatch", "kv.commit", "lm.fetch")
    forwards = [r for r in recs if r["name"] == "lm.dispatch"]
    # prefill: 5 tokens in chunks of 4 and 2 tokens are two rounds of two
    # rows (the second padded); decode: three steps of both rows
    assert len(forwards) == 2 + 3
    # the caller's span, the engine's call with its admission and release,
    # 2 prompts' prefills, 2 rounds, 3 steps, 4 phases a forward, the last
    # step's second fetch and the prefill's one wait
    assert len(recs) == 1 + 3 + 2 + 2 + 3 + 4 * len(forwards) + 1 + 1 \
        <= 4 + 6 * len(forwards) + 2
    (gen_call,) = [r for r in recs if r["name"] == "gen.call"]
    assert gen_call["parent_id"] == call.span_id
    for r in recs:
        if r["name"] in phases + ("kv.sync",):
            assert r["dur_us"] > 0
            assert by_id[r["parent_id"]]["name"] in ("gen.prefill_round",
                                                     "gen.decode_step")
        elif r["name"] == "gen.prefill_round":
            assert by_id[r["parent_id"]]["name"] == "gen.prefill"
        elif r["name"] not in ("test.call", "gen.call"):
            assert r["parent_id"] == gen_call["span_id"]
    steps = [r for r in recs if r["name"] == "gen.decode_step"]
    assert [s["fed"] for s in steps] == ["host", "device", "device"]
    for n, step in enumerate(steps):
        kids = [r for r in recs if r.get("parent_id") == step["span_id"]]
        # in the order they ran: the fetch comes after the launches
        assert tuple(k["name"] for k in kids) \
            == phases + ("lm.fetch",) * (n == 2)
        # lengths (2,) and tables (2, 64 / 16), int32; the first step's
        # tokens (2, 1) too
        assert kids[1]["h2d_bytes"] == 8 * (n == 0) + 8 + 32
        # ids (2, 1) int32 of the step before, the last step's own too:
        # no logits (2, 1, 29), and no K or V
        assert [k["d2h_bytes"] for k in kids[3:]] \
            == [[0], [8], [8, 8]][n]
        assert kids[2]["rows"] == 2
        assert step["tokens_committed"] == [0, 2, 4][n]
        assert sum(k["dur_us"] for k in kids) <= step["dur_us"]
    rounds = [r for r in recs if r["name"] == "gen.prefill_round"]
    assert [(r["rows"], r["slots"], r["tokens"]) for r in rounds] \
        == [(2, [0, 1], 6), (1, [0], 1)]
    chunks = [r for r in recs
              if by_id.get(r.get("parent_id"), {}).get("name")
              == "gen.prefill_round"]
    # round 1's read after round 2 is launched, round 2's own after it
    assert [r["d2h_bytes"] for r in chunks if r["name"] == "lm.fetch"] \
        == [0, 0]
    assert [r["rows"] for r in chunks if r["name"] == "kv.commit"] \
        == [4 + 2, 1]
    # tokens (2, 4), lengths (2,), tables (2, 4)
    assert {r["h2d_bytes"] for r in chunks if r["name"] == "lm.dispatch"} \
        == {32 + 8 + 32}
    assert [by_id[r["parent_id"]]["slots"] for r in chunks
            if r["name"] == "kv.sync"] == [[0]]     # the last round's
    # a prompt's prefill: from its first round's start into its last
    # round, once that round's work is done; the longest leads both
    prefills = [r for r in recs if r["name"] == "gen.prefill"]
    assert [(p["slot"], p["tokens"]) for p in prefills] == [(1, 2), (0, 5)]
    assert [r["parent_id"] for r in rounds] == [prefills[1]["span_id"]] * 2
    for p in prefills:
        inside = [r for r in rounds if p["slot"] in r["slots"]]
        assert p["ts_us"] <= inside[0]["ts_us"]
        assert inside[-1]["ts_us"] < p["ts_us"] + p["dur_us"] \
            <= inside[-1]["ts_us"] + inside[-1]["dur_us"]
    # one reading a region: the spans' durations ARE last_stats' seconds
    assert sum(s["dur_us"] for s in steps) / 1e6 == pytest.approx(
        eng.last_stats["decode_seconds"], rel=1e-6)
    assert sum(r["dur_us"] for r in rounds) / 1e6 == pytest.approx(
        eng.last_stats["prefill_seconds"], rel=1e-6)
    assert cat.gen_decode_seconds.count(model="gpt") == 3
    assert cat.gen_prefill_seconds.count(model="gpt") == 2


def test_a_call_is_one_gen_call_with_its_admission_regions_and_release(
        target_lm):
    """Under a caller's span a call is ONE ``gen.call`` (what it was asked
    and what it committed as attributes) whose children are ``gen.admit``,
    the prefills, the steps and ``gen.release``, in that order; the slots
    are given inside the admission and freed inside the release. Alone
    with metrics on the call is a root, and its journey is kept whole."""
    from incubator_mxnet_tpu.telemetry import tracing
    cache = target_lm.make_cache(2, max_len=64)
    eng = GenerateEngine(target_lm, cache, prefill_chunk=4, name="owned")
    prompts = [[3, 5, 7, 2, 11, 1], [9, 8, 4]]
    with tracing.Span("test.call") as caller:
        eng.generate(prompts, max_new_tokens=3)
    journey = tracing.recent_journeys("test.call")[-1]
    (call,) = [r for r in journey if r["name"] == "gen.call"]
    assert call["parent_id"] == caller.span_id
    assert {k: call[k] for k in ("model", "rows", "prompt_tokens",
                                 "max_new_tokens", "tokens_committed")} \
        == {"model": "owned", "rows": 2, "prompt_tokens": 9,
            "max_new_tokens": 3, "tokens_committed": 6}
    kids = sorted((r for r in journey
                   if r.get("parent_id") == call["span_id"]),
                  key=lambda r: r["ts_us"])
    assert [k["name"] for k in kids] == (
        ["gen.admit"] + ["gen.prefill"] * 2 + ["gen.decode_step"] * 3
        + ["gen.release"])
    assert all(call["ts_us"] <= k["ts_us"] and k["ts_us"] + k["dur_us"]
               <= call["ts_us"] + call["dur_us"] + 1 for k in kids)
    assert cache.in_use == 0
    # a refused call has its admission and its release all the same
    with tracing.Span("test.refused"):
        with pytest.raises(ValueError, match="exceeds cache"):
            eng.generate([[1] * 70], max_new_tokens=3)
    refused = [r["name"] for r in tracing.recent_journeys("test.refused")[-1]]
    assert refused == ["gen.admit", "gen.release", "gen.call",
                       "test.refused"]
    # alone, metrics on: the call is the root of its own journey
    telemetry.enable()
    try:
        eng.generate(prompts, max_new_tokens=3)
    finally:
        telemetry.disable()
    own = tracing.recent_journeys("gen.call")[-1]
    assert own[-1]["name"] == "gen.call" and "parent_id" not in own[-1]
    assert len(own) == len(journey) - 1
    # telemetry idle: no span is real and nothing is kept
    kept = len(tracing.recent_journeys())
    eng.generate(prompts, max_new_tokens=3)
    assert len(tracing.recent_journeys()) == kept
    assert tracing.recent_journeys("gen.call")[-1] is own


def test_the_span_readers_split_a_decode_step_with_the_pools_on_the_device(
        target_lm):
    """What tests/benchmark's traced toy run asserted of a decode step and
    still holds (its last line, that the pools cross in every step, is the
    bottleneck ISSUE 30 removed: tests/conftest.py marks it): the
    benchmark's own readers find all four parts of a step, each longer
    than 0, their medians sum to the median step within a half, and a
    step ships less than 4,096 bytes: lengths and tables, and since ISSUE
    34 no token (the median is over steps fed on the device)."""
    from benchmarks import span_metrics
    from incubator_mxnet_tpu.telemetry import tracing
    cache = target_lm.make_cache(4, max_len=64)
    eng = GenerateEngine(target_lm, cache)
    tracing.clear_spans()
    with tracing.Span("bench.generate_call"):
        eng.generate([[3, 5, 7, 2, 11, 1, 4], [9, 8], [4, 6, 1], [2]],
                     max_new_tokens=24)
    facts = {"trace": True}
    steps, children = span_metrics.decode_steps(facts)
    assert len(steps) == 24
    parts = [np.median([span_metrics.self_us(s, children[s["span_id"]])
                        for s in steps]) / 1e3,
             span_metrics.median_ms_per_step(facts, ("lm.dispatch",)),
             span_metrics.median_ms_per_step(facts, ("lm.fetch",)),
             span_metrics.median_ms_per_step(facts,
                                             ("kv.gather", "kv.commit"))]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(
        np.median([s["dur_us"] for s in steps]) / 1e3, rel=0.5)
    h2d = span_metrics.median_per_step(facts, ("lm.dispatch",), "h2d_bytes")
    assert 0 < h2d == 16 + 64 < 4096
    pools = sum(cache.pool(n).nbytes for n in cache.spec)
    assert pools == 4 * 4 * 16 * 24 * 4 * 4 > h2d


def test_plain_and_speculative_greedy_return_the_pinned_tokens(target_lm,
                                                               draft_lm):
    """The tokens the tree before ISSUE 30 returned (host pools, a loop of
    appends) on these weights and prompts: moving the pools moved no
    token."""
    prompts = [[3, 5, 7, 2, 11, 1, 4], [9, 8]]
    pinned = [[24, 16, 16, 16, 16, 6, 16, 16, 6, 16, 6, 16],
              [6, 16, 16, 6, 16, 6, 16, 16, 16, 16, 6, 16]]
    plain = GenerateEngine(target_lm, target_lm.make_cache(4, max_len=64))
    assert plain.generate(prompts, max_new_tokens=12) == pinned
    spec = GenerateEngine(
        target_lm, target_lm.make_cache(4, max_len=64), draft=draft_lm,
        draft_cache=draft_lm.make_cache(4, max_len=64), spec_k=3)
    assert spec.generate(prompts, max_new_tokens=12) == pinned


def test_engine_speculative_bit_identical_to_plain_greedy(target_lm,
                                                          draft_lm):
    """THE speculation pin: same tokens as plain greedy, token for
    token — including at exact cache capacity, where the verify width
    shrinks rather than overflowing the paged pool."""
    prompts = [[3, 5, 7, 2, 11, 1, 4], [9, 8]]
    plain = GenerateEngine(
        target_lm, target_lm.make_cache(4, max_len=64)).generate(
            prompts, max_new_tokens=12)
    spec = GenerateEngine(
        target_lm, target_lm.make_cache(4, max_len=64), draft=draft_lm,
        draft_cache=draft_lm.make_cache(4, max_len=64), spec_k=3)
    assert spec.generate(prompts, max_new_tokens=12) == plain
    st = spec.last_stats
    assert st["proposed"] > 0 and st["decode_tokens"] == 24
    assert cat.gen_spec_proposed.value(model="gpt") == st["proposed"]
    assert cat.gen_spec_accepted.value(model="gpt") == st["accepted"]
    # prompt 7 + new 12 == max_len 19: the last verify must narrow
    tight = GenerateEngine(
        target_lm, target_lm.make_cache(2, max_len=19), draft=draft_lm,
        draft_cache=draft_lm.make_cache(2, max_len=19), spec_k=3)
    assert tight.generate(prompts, max_new_tokens=12) == plain


def test_engine_self_speculation_accepts_every_proposal(target_lm):
    """Draft == target: every draft token matches the target argmax, so
    the accept-rate pins at 1.0 — the counters' sanity anchor."""
    eng = GenerateEngine(
        target_lm, target_lm.make_cache(2, max_len=64), draft=target_lm,
        draft_cache=target_lm.make_cache(2, max_len=64), spec_k=4)
    plain = GenerateEngine(
        target_lm, target_lm.make_cache(2, max_len=64)).generate(
            [[3, 5, 7]], max_new_tokens=10)
    assert eng.generate([[3, 5, 7]], max_new_tokens=10) == plain
    st = eng.last_stats
    assert st["proposed"] > 0 and st["accepted"] == st["proposed"]


def test_engine_guards(target_lm, draft_lm):
    with pytest.raises(ValueError, match="greedy-only"):
        GenerateEngine(target_lm, target_lm.make_cache(2),
                       draft=draft_lm,
                       draft_cache=draft_lm.make_cache(2),
                       temperature=0.7)
    with pytest.raises(ValueError, match="come together"):
        GenerateEngine(target_lm, target_lm.make_cache(2), draft=draft_lm)
    eng = GenerateEngine(target_lm, target_lm.make_cache(2, max_len=8))
    with pytest.raises(ValueError, match="exceeds cache max_len"):
        eng.generate([[1, 2, 3]], max_new_tokens=6)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate([[]], max_new_tokens=2)
    # every slot freed even after the raise above
    assert eng.cache.in_use == 0


def test_engine_eos_and_slot_release(target_lm):
    eng = GenerateEngine(target_lm, target_lm.make_cache(2, max_len=64))
    out = eng.generate([[3, 5, 7]], max_new_tokens=10)[0]
    eos = out[2]
    got = eng.generate([[3, 5, 7]], max_new_tokens=10, eos_id=eos)[0]
    assert got == out[:out.index(eos) + 1] and got[-1] == eos
    assert eng.cache.in_use == 0
    # telemetry: committed decode tokens account every generated token
    assert cat.gen_tokens_committed.value(model="gpt", phase="decode") \
        == len(out) + len(got)


# ------------------------------------- the token head, read one forward behind
_LAG_PROMPTS = [[3, 5, 7, 2, 11, 1, 4, 9, 8, 6, 2, 13, 12], [9, 8],
                [4, 6, 1, 7, 7, 2, 5, 3, 10]]
_LAG_NEW, _LAG_CHUNK = 12, 4


@pytest.fixture(scope="module")
def latent_lm():
    """The ``xing4_0`` family at the toy size of the benchmark's tests: a
    latent cache, one dense and two expert layers of 8 experts."""
    from benchmarks import spec
    from benchmarks.families import xing4
    from benchmarks.reference import xing4 as reference
    from incubator_mxnet_tpu.generate import MLAPagedLM
    cfg = spec.load_json(os.path.join(
        spec.ROOT, "tests", "benchmark", "configs", "xing4_tiny.json"))
    return MLAPagedLM(reference.init_weights(cfg, 3),
                      xing4.program_config(cfg), dtype="float32")


@pytest.fixture(scope="module")
def varied_lm():
    """`target_lm`'s decoder with weights drawn four times as wide: its
    rows do not all lock onto the same two tokens, so a stop token can
    end one row and leave the others going."""
    return GPTPagedLM(_params(_TCFG, 7, scale=0.2), _TCFG)


@pytest.fixture(params=["gpt", "latent"])
def lagged_lm(request, varied_lm, latent_lm):
    return {"gpt": varied_lm, "latent": latent_lm}[request.param]


def _logits_loop(lm, prompts, max_new_tokens, eos_id=None, max_len=64):
    """Greedy decoding as it was before the token head, written out:
    every step waits for ``adapter.forward``'s logits (``step_slots``)
    and takes ``np.argmax`` on the host; a row stops at `eos_id` or at
    `max_new_tokens` and the others go on."""
    cache = lm.make_cache(len(prompts), max_len=max_len)
    rows = [{"ctx": list(p), "out": [], "slot": cache.alloc()}
            for p in prompts]
    for r in rows:
        prefill_slot(lm, cache, r["slot"], r["ctx"][:-1], _LAG_CHUNK)
    while True:
        live = [r for r in rows if len(r["out"]) < max_new_tokens
                and r["out"][-1:] != [eos_id]]
        if not live:
            return [r["out"] for r in rows]
        logits = step_slots(lm, cache, [r["slot"] for r in live],
                            np.asarray([[r["ctx"][-1]] for r in live],
                                       np.int32))
        for r, row in zip(live, logits):
            r["ctx"].append(int(np.argmax(row)))
            r["out"].append(r["ctx"][-1])


def _stops_one_row(out, lo, hi, rows=None):
    """(row, index, token) of a token that no other row holds and whose
    FIRST place in its row lies in [lo, hi): as `eos_id` it stops that
    row there, not before, and no other row."""
    for r in range(len(out)) if rows is None else rows:
        for k in range(lo, hi):
            tok = out[r][k]
            if out[r].index(tok) == k and not any(
                    tok in other for o, other in enumerate(out) if o != r):
                return r, k, tok
    raise AssertionError("no token of one row alone in [%d, %d)" % (lo, hi))


@pytest.mark.parametrize("case", ["no_stop", "a_row_stops_midway",
                                  "a_row_stops_at_its_last_step",
                                  "the_cache_is_full"])
def test_the_lagged_loop_returns_the_logits_loops_tokens(lagged_lm, case):
    """`temperature` 0 over an adapter with the token head takes the loop
    that reads one forward behind; its tokens are those of the loop that
    waits for every forward's logits, whatever ends a row: nothing, a stop
    token midway while the others go on (the row was fed once more by the
    time the host saw it: that token never reaches `out`), a stop token at
    the row's very last step, the cache's last position (prompt +
    max_new_tokens == max_len: no row is stored past it)."""
    free = _logits_loop(lagged_lm, _LAG_PROMPTS, _LAG_NEW)
    new, eos, max_len = _LAG_NEW, None, 64
    if case == "a_row_stops_midway":
        row, at, eos = _stops_one_row(free, 2, _LAG_NEW - 2)
    elif case == "a_row_stops_at_its_last_step":
        row, at, eos = _stops_one_row(free, 3, _LAG_NEW)
        new = at + 1
    elif case == "the_cache_is_full":
        # the longest prompt's row stops one step short of the count: it
        # is launched once more, into the cache's last position
        row, at, eos = _stops_one_row(free, 3, _LAG_NEW - 1, rows=[0])
        new = at + 2
        max_len = len(_LAG_PROMPTS[0]) + new
    want = _logits_loop(lagged_lm, _LAG_PROMPTS, new, eos, max_len)
    if eos is not None:
        assert [len(w) for w in want] \
            == [at + 1 if r == row else new for r in range(3)]
    cache = lagged_lm.make_cache(3, max_len=max_len)
    stored = []
    commit = cache.commit

    def watched(slots, *new_and_count):
        commit(slots, *new_and_count)
        stored.append(int(cache.lengths.max()))
    cache.commit = watched
    eng = GenerateEngine(lagged_lm, cache, prefill_chunk=_LAG_CHUNK)
    got = eng.generate(_LAG_PROMPTS, max_new_tokens=new, eos_id=eos)
    assert got == want
    assert max(stored) <= max_len and cache.in_use == 0
    if case == "the_cache_is_full":     # the forward too many took place
        assert max(stored) == max_len - 1 == len(_LAG_PROMPTS[0]) + at + 1
    st = eng.last_stats
    assert st["decode_tokens"] == sum(map(len, got))
    if eos is None:     # the same rows from the first step to the last
        assert (st["decode_steps"], st["decode_steps_fed_on_device"]) \
            == (new, new - 1)
    else:               # a wait where the rows changed, and only there
        assert 0 < st["decode_steps_fed_on_device"] < st["decode_steps"]
    # temperature sampling keeps the logits path and the engine's stream
    hot = GenerateEngine(lagged_lm, lagged_lm.make_cache(3, max_len=64),
                         prefill_chunk=_LAG_CHUNK, temperature=0.8, seed=5)
    again = GenerateEngine(lagged_lm, lagged_lm.make_cache(3, max_len=64),
                           prefill_chunk=_LAG_CHUNK, temperature=0.8, seed=5)
    assert hot.generate(_LAG_PROMPTS, 4) == again.generate(_LAG_PROMPTS, 4)
    assert hot.last_stats["decode_steps_fed_on_device"] == 0


def test_a_greedy_call_waits_for_nothing_between_two_forwards(lagged_lm):
    """What a greedy call of the lagged loop leaves behind: every
    gen.decode_step has the four phases as children, all steps but the
    first are fed on the device (attribute, ``last_stats``, counter),
    lm.fetch copies ids and expert loads and never a vocabulary's logits,
    the expert layer and the latent cache count every forward as before
    (one forward late), and the timed regions cover the call: no wait for
    the device lies outside them."""
    import time
    from incubator_mxnet_tpu.telemetry import tracing
    latent = hasattr(lagged_lm, "last_latent_path")
    name = "latent" if latent else "gpt"
    eng = GenerateEngine(lagged_lm, lagged_lm.make_cache(3, max_len=64),
                         prefill_chunk=_LAG_CHUNK, name=name)
    eng.generate(_LAG_PROMPTS, max_new_tokens=2)        # compiles
    _met.reset()
    shares = []
    for _ in range(3):
        tracing.clear_spans()
        t0 = time.monotonic()
        with tracing.Span("test.call"):
            out = eng.generate(_LAG_PROMPTS, max_new_tokens=_LAG_NEW)
        wall = time.monotonic() - t0
        st = eng.last_stats
        shares.append((st["prefill_seconds"] + st["decode_seconds"]) / wall)
    assert [len(o) for o in out] == [_LAG_NEW] * 3
    assert 0.9 < max(shares) <= 1.0
    recs = tracing.recent_spans()
    steps = [r for r in recs if r["name"] == "gen.decode_step"]
    assert [s["fed"] for s in steps] == ["host"] + ["device"] * (_LAG_NEW - 1)
    assert (st["decode_steps"], st["decode_steps_fed_on_device"]) \
        == (_LAG_NEW, _LAG_NEW - 1)
    assert cat.gen_decode_steps.value(model=name, fed="device") \
        == 3 * (_LAG_NEW - 1)
    assert cat.gen_decode_steps.value(model=name, fed="host") == 3
    # ids (3, 1) int32 and, of an expert layer, the loads (2, 8) int32
    behind = 3 * 4 + (2 * 8 * 4 if latent else 0)
    vocab = lagged_lm.config["vocab_size"]
    for n, step in enumerate(steps):
        kids = [r for r in recs if r.get("parent_id") == step["span_id"]]
        assert {k["name"] for k in kids} == {"kv.gather", "lm.dispatch",
                                             "lm.fetch", "kv.commit"}
        fetched = sum(k.get("d2h_bytes", 0) for k in kids)
        assert fetched == behind * ((n > 0) + (n == _LAG_NEW - 1))
        assert fetched < 3 * vocab * 4
    # prompts of 13, 2 and 9 commit 12, 1 and 8 tokens in 3 + 1 + 2
    # chunks of 4: three prefill rounds of three rows (the last two padded)
    rounds = 3
    assert (st["prefill_forwards"], st["prefill_rows"]) == (rounds, 6)
    if latent:
        assert st["moe"]["forwards"] == rounds + _LAG_NEW
        assert len(st["moe"]["load_max_over_mean"]) == rounds + _LAG_NEW
        # two expert layers, two routes a token: a round's 3 x 4 positions
        # (pad positions and pad rows too), a step's 3 rows
        assert st["moe"]["routes"] \
            == (rounds * 3 * 4 + _LAG_NEW * 3) * 2 * 2
        live = st["mla"].pop("absorbed_rows_live")
        assert 0 < live <= st["mla"].pop("absorbed_rows_read")
        assert st["mla"] == {"absorbed_forwards": _LAG_NEW,
                             "expanded_forwards": rounds,
                             "expanded_kernel_forwards": 0,
                             "expanded_rows": 4 + 8 + 4}
    else:
        assert "moe" not in st and "mla" not in st


# ---- what two closed tests under tests/benchmark/ asserted, with the token
# ---- chosen in the forward's program (tests/conftest.py marks them)
_BENCH_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                "hbm_bytes": 1e10}
_BENCH_SEED = 2 ** 31 + 77


def test_an_altered_token_at_the_token_heads_output_fails_the_toy_cell(
        monkeypatch):
    """test_benchmark_harness.py's planted fault ``token_altered``, moved
    to where a greedy token is now produced: one id in seven of the token
    head's output altered (served, and fed on to the next step), through
    the same ``drive`` of ``gpt2_tiny.generate_tiny``."""
    from benchmarks import run, spec
    forward_token = GPTPagedLM.forward_token
    made = {"n": 0}

    def faulty(self, *args):
        read, nk, nv = forward_token(self, *args)
        ids = np.array(read["token"])
        for row in range(len(ids)):
            made["n"] += 1
            if made["n"] % 7 == 0:
                ids[row] = (ids[row] + 1) % self.config["vocab_size"]
        return dict(read, token=jnp.asarray(ids)), nk, nv
    monkeypatch.setattr(GPTPagedLM, "forward_token", faulty)
    bench = spec.load_json(os.path.join(spec.ROOT, "tests", "benchmark",
                                        "BENCHMARK_tiny.json"))
    result = run.drive(bench, "gpt2_tiny.generate_tiny", _BENCH_SEED, 1.0,
                       False, jax.devices(), peaks=_BENCH_PEAKS)
    assert made["n"] > 7
    assert result["correct"] is False
    assert any(row["value"] > row["limit"]
               for row in result["compared"].values())


def test_a_traced_toy_latent_run_reports_every_layer_a_cpu_can_read(
        tmp_path):
    """test_benchmark_latent.py's traced run, line for line, but for the
    bytes a decode step ships: a length and a table a row, and no token
    (the median is over steps fed on the device; a call's first step
    still ships its tokens). The run keeps its trace under a root of its
    own: the closed test still runs, in another worker perhaps, and
    removes ``.bench_trace/<cell>`` of the checkout."""
    from benchmarks import run, spec
    for name in ("benchmarks", "tests"):
        os.symlink(os.path.join(spec.ROOT, name), str(tmp_path / name))
    cell = "xing4_tiny.generate_long_tiny"
    bench = spec.load_json(os.path.join(spec.ROOT, "tests", "benchmark",
                                        "BENCHMARK_latent_tiny.json"))
    result = run.drive(bench, cell, _BENCH_SEED, 0.3, True, jax.devices(),
                       root=str(tmp_path), peaks=_BENCH_PEAKS)
    # a CPU has no device plane: idle share, peak memory and the expert
    # products' roofline are left out, never reported as 0
    assert set(result["metrics"]) == {
        "prefill_share", "gen_mfu", "compile_s", "decode_step_p50_ms",
        "gen_step_self_ms", "lm_dispatch_ms_per_step",
        "lm_fetch_wait_ms_per_step", "kv_host_ms_per_step",
        "kv_h2d_bytes_per_step", "moe_load_max_over_mean", "prefill_mfu",
        "mla_expanded_rows_per_prompt_token"}
    values = {k: v["value"] for k, v in result["metrics"].items()}
    # prompts of 9, 12, 17 and 23 commit 8, 11, 16 and 22 tokens in chunks
    # of 8: the chunks after a prompt's first find 8, 8 and 8 + 16 rows
    assert values["mla_expanded_rows_per_prompt_token"] == pytest.approx(
        40 / 57)
    assert 0 < values["prefill_mfu"] < 100 and 0 < values["gen_mfu"] < 100
    # a length and a table of 2 blocks of 16 a row, 4 B each
    assert values["kv_h2d_bytes_per_step"] == 4 * (4 + 2 * 4)
    assert values["decode_step_p50_ms"] > values["gen_step_self_ms"] > 0
    assert values["lm_fetch_wait_ms_per_step"] > 0
    assert values["moe_load_max_over_mean"] >= 1
    assert result["correct"] is True
    assert not os.path.exists(str(tmp_path / ".bench_trace" / cell))


# ------------------------------------------- an expert layer's held share
@pytest.fixture(scope="module")
def share_lm():
    """The ``kimi_k2`` family at the toy size of the benchmark's tests: one
    residual stream, two expert layers that hold the experts 6-8 of 24
    (share 2 of 8), top-8."""
    from benchmarks import spec
    from benchmarks.families import kimi_k2
    cfg = spec.load_json(os.path.join(
        spec.ROOT, "tests", "benchmark", "configs", "kimi_tiny.json"))
    return MLAPagedLM(kimi_k2.reference.init_weights(cfg, 3),
                      kimi_k2.program_config(cfg), dtype="float32")


def test_a_held_shares_tallies_count_the_work_done_here(share_lm):
    """``last_stats["moe"]`` keeps its keys with the meaning "of the
    experts held here": routes and experts hit are what this chip
    computed (the readers of the grouped products' roofline and of the
    step's floor divide by them), the routes on other chips' experts are
    counted apart, and together they are every route the router made."""
    telemetry.enable()
    eng = GenerateEngine(share_lm, share_lm.make_cache(3, max_len=64),
                         prefill_chunk=_LAG_CHUNK, name="share_tally")
    def counted(counter):      # over the counter's other labels (phase)
        return sum(value for labels, value in counter.snapshot().items()
                   if ("model", "share_tally") in labels)
    before = {c: counted(c) for c in (
        cat.moe_routes, cat.moe_experts_hit, cat.moe_routes_elsewhere,
        cat.moe_rows_moved)}
    out = eng.generate(_LAG_PROMPTS, max_new_tokens=_LAG_NEW)
    assert out == _logits_loop(share_lm, _LAG_PROMPTS, _LAG_NEW)
    moe = eng.last_stats["moe"]
    assert set(moe) == {"forwards", "routes", "experts_hit",
                        "load_max_over_mean", "routes_elsewhere",
                        "rows_moved", "by_phase"}
    # prompts of 13, 2 and 9 commit 12, 1 and 8 tokens in chunks of 4 (a
    # chunk's padding routes too): 3 prefill rounds of 3 rows of 4
    # positions (pad rows too), then 12 steps of 3 rows; 2 expert layers,
    # 8 routes a position
    positions = 3 * 3 * 4 + 12 * 3
    assert moe["forwards"] == 3 + 12
    assert moe["routes"] + moe["routes_elsewhere"] == 2 * 8 * positions
    # 3 of 24 experts held: about an eighth of the routes fall here
    assert 0 < moe["routes"] < moe["routes_elsewhere"] / 3
    assert 0 < moe["experts_hit"] <= 2 * 3 * moe["forwards"]
    # a pass of 3 or 4 tokens lays 32 routes out (the bound's floor); a
    # layer-forward with no route here moves nothing
    assert moe["rows_moved"] % 32 == 0
    assert 32 <= moe["rows_moved"] <= 2 * 32 * moe["forwards"]
    assert all(u >= 1 for u in moe["load_max_over_mean"])
    assert len(moe["load_max_over_mean"]) <= moe["forwards"]
    for counter, key in ((cat.moe_routes, "routes"),
                         (cat.moe_experts_hit, "experts_hit"),
                         (cat.moe_routes_elsewhere, "routes_elsewhere"),
                         (cat.moe_rows_moved, "rows_moved")):
        assert counted(counter) - before[counter] == moe[key]


def test_the_expert_tallies_by_phase_sum_to_the_totals(latent_lm):
    """``last_stats["moe"]["by_phase"]`` parts the prefill chunks' loads
    from the decode steps'; the loads are read one forward behind, and
    still each lands in the phase of the forward that made it: the last
    chunk's before its prefill returns, the last step's before the loop
    ends."""
    telemetry.enable()
    eng = GenerateEngine(latent_lm, latent_lm.make_cache(3, max_len=64),
                         prefill_chunk=_LAG_CHUNK, name="phased")
    phases = []
    note = eng._note_forward

    def watched(model, read=None):
        if read is not None and "expert_loads" in read:
            phases.append(eng._phase)
        return note(model, read)
    eng._note = watched
    eng.generate(_LAG_PROMPTS, max_new_tokens=_LAG_NEW)
    moe = eng.last_stats["moe"]
    by_phase = moe["by_phase"]
    # prompts of 13, 2 and 9: 3 + 1 + 2 chunks of 4 in 3 rounds of 3
    # rows, then 12 steps
    assert by_phase["prefill"]["forwards"] == 3
    assert by_phase["decode"]["forwards"] == _LAG_NEW
    assert phases == ["prefill"] * 3 + ["decode"] * _LAG_NEW
    # two expert layers, 2 routes a position: a round's 3 x 4 positions
    # (pad positions and pad rows route too), a step's 3 rows
    assert by_phase["prefill"]["routes"] == 3 * 3 * 4 * 2 * 2
    assert by_phase["decode"]["routes"] == _LAG_NEW * 3 * 2 * 2
    for key in ("forwards", "routes", "experts_hit"):
        assert by_phase["prefill"][key] + by_phase["decode"][key] \
            == moe[key]
    # a round of 12 positions hits more experts a forward than 3 rows do
    assert by_phase["prefill"]["experts_hit"] / 3 \
        >= by_phase["decode"]["experts_hit"] / _LAG_NEW
    for phase, tally in by_phase.items():
        assert cat.moe_routes.value(model="phased", phase=phase) \
            == tally["routes"]
        assert cat.moe_experts_hit.value(model="phased", phase=phase) \
            == tally["experts_hit"]


def test_a_model_that_holds_every_expert_tallies_no_share(latent_lm):
    eng = GenerateEngine(latent_lm, latent_lm.make_cache(3, max_len=64),
                         prefill_chunk=_LAG_CHUNK, name="whole_tally")
    eng.generate(_LAG_PROMPTS, max_new_tokens=2)
    assert set(eng.last_stats["moe"]) == {
        "forwards", "routes", "experts_hit", "load_max_over_mean",
        "by_phase"}
    assert all(set(tally) == {"forwards", "routes", "experts_hit"}
               for tally in eng.last_stats["moe"]["by_phase"].values())
    loads = np.arange(16).reshape(2, 8)
    assert latent_lm.split_loads(loads)[1] == {}
    assert latent_lm.split_loads(loads)[0] is loads


def test_a_share_whose_experts_get_no_route_has_no_fullest_expert(share_lm):
    """A forward in which no layer's held experts got a route appends no
    ``load_max_over_mean`` (0 / 0), and counts as a forward all the
    same."""
    eng = GenerateEngine(share_lm, share_lm.make_cache(1, max_len=16),
                         name="idle_share")
    eng._tallies = {"moe": {"forwards": 0, "routes": 0, "experts_hit": 0,
                            "load_max_over_mean": [], "routes_elsewhere": 0,
                            "rows_moved": 0, "by_phase": {"prefill": {
                                "forwards": 0, "routes": 0,
                                "experts_hit": 0}}}}
    idle = np.asarray([[0, 0, 0, 8, 0], [0, 0, 0, 8, 0]], np.int32)
    eng._note_forward(share_lm, {"expert_loads": idle})
    one = np.asarray([[0, 3, 1, 4, 32], [0, 0, 0, 8, 0]], np.int32)
    eng._note_forward(share_lm, {"expert_loads": one})
    counted = {"forwards": 2, "routes": 4, "experts_hit": 2,
               "routes_elsewhere": 28, "rows_moved": 32}
    assert eng._tallies["moe"] == dict(
        counted, load_max_over_mean=[3 / (4 / 3)],
        by_phase={"prefill": counted})


# ------------------------------------------- serving: accounting + loop
def _counting_step(vocab=10):
    def step(tokens, cache, active):
        logits = np.zeros((tokens.shape[0], vocab), np.float32)
        for slot in range(tokens.shape[0]):
            if active[slot]:
                logits[slot, (int(tokens[slot]) + 1) % vocab] = 1.0
        return logits
    return step


def test_decode_loop_runs_unchanged_on_paged_cache():
    """The DecodeLoop acceptance: PagedKVCache slots in behind the
    dense cache's surface with no loop changes."""
    cache = PagedKVCache(2, {"h": ("state", (1,))}, max_len=64)
    loop = DecodeLoop("lm", _counting_step(), cache, pad_token=0)
    loop.start()
    try:
        r = loop.submit(DecodeRequest("lm", [3, 4], max_new_tokens=4))
        np.testing.assert_array_equal(r.wait(10.0)["tokens"], [5, 6, 7, 8])
        r2 = loop.submit(DecodeRequest("lm", [7], max_new_tokens=5,
                                       eos_id=9))
        np.testing.assert_array_equal(r2.wait(10.0)["tokens"], [8, 9])
    finally:
        loop.stop()
    assert cache.in_use == 0 and cache.blocks_in_use == 0


def test_retire_path_counts_the_final_step_token():
    """The round-14 bugfix pin: per-step token accounting runs in the
    retire pass AFTER consume, so the buzzer token of a retiring
    sequence is counted. prompt P, max_new N => exactly P-1 prefill +
    N decode tokens, the last of which lands on the retiring step."""
    cache = PagedKVCache(1, {"h": ("state", (1,))}, max_len=64)
    loop = DecodeLoop("acct", _counting_step(), cache, pad_token=0)
    loop.start()
    try:
        r = loop.submit(DecodeRequest("acct", [1, 2, 3], max_new_tokens=4))
        assert r.wait(10.0)["tokens"].size == 4
    finally:
        loop.stop()
    assert cat.gen_tokens_committed.value(model="acct",
                                          phase="decode") == 4
    assert cat.gen_tokens_committed.value(model="acct",
                                          phase="prefill") == 2
    # grid steps: P-1 prefill-feeds + N decode steps = 6
    assert cat.serving_decode_steps.value(model="acct") == 6


def test_decode_loop_family_prefill_fn_is_used_and_counted():
    """With a family prefill_fn the prompt prefix commits at admission
    (chunked) and only the LAST prompt token goes through the grid."""
    calls = []

    def prefill(slot, tokens, cache):
        calls.append((slot, list(map(int, tokens))))
        for _ in tokens:
            cache.advance(slot)     # commit positions like the family

    cache = PagedKVCache(1, {"h": ("state", (1,))}, max_len=64)
    loop = DecodeLoop("pf", _counting_step(), cache, pad_token=0,
                      prefill_fn=prefill, prefill_chunk=8)
    loop.start()
    try:
        r = loop.submit(DecodeRequest("pf", [1, 2, 3, 4], max_new_tokens=3))
        np.testing.assert_array_equal(r.wait(10.0)["tokens"], [5, 6, 7])
    finally:
        loop.stop()
    assert calls == [(0, [1, 2, 3])]                # prefix only
    assert cat.gen_tokens_committed.value(model="pf", phase="prefill") == 3
    assert cat.gen_tokens_committed.value(model="pf", phase="decode") == 3
    assert cat.serving_decode_steps.value(model="pf") == 3  # no prompt steps
    assert cat.gen_prefill_seconds.count(model="pf") == 1


def test_decode_loop_prefill_failure_fails_request_not_loop():
    def broken(slot, tokens, cache):
        raise RuntimeError("prefill exploded")

    cache = PagedKVCache(1, {"h": ("state", (1,))}, max_len=64)
    loop = DecodeLoop("pfx", _counting_step(), cache, pad_token=0,
                      prefill_fn=broken, prefill_chunk=8)
    loop.start()
    try:
        bad = loop.submit(DecodeRequest("pfx", [1, 2], max_new_tokens=2))
        with pytest.raises(RuntimeError, match="prefill exploded"):
            bad.wait(10.0)
        # single-token prompts skip prefill: the loop still serves
        ok = loop.submit(DecodeRequest("pfx", [5], max_new_tokens=2))
        np.testing.assert_array_equal(ok.wait(10.0)["tokens"], [6, 7])
    finally:
        loop.stop()
    assert cache.in_use == 0


# ------------------------------------------------- serving: gpt family
def _tiny_gpt(prefix="sgpt_", **over):
    cfg = dict(vocab_size=37, units=16, num_layers=1, num_heads=2,
               max_len=64)
    cfg.update(over)
    m = GPTDecoder(prefix=prefix, **cfg)
    m.initialize(mx.init.Normal(0.05))
    m(nd.array(np.zeros((1, 4), np.int32)))
    return m, cfg


def test_gpt_family_serves_and_matches_engine_greedy(tmp_path):
    model, cfg = _tiny_gpt()
    draft, dcfg = _tiny_gpt(prefix="sgptd_", units=8)
    ckpt = str(tmp_path / "gpt_serve")
    export_gpt_for_serving(ckpt, cfg, model, draft=draft)
    srv = serving.ModelServer()
    srv.load("gpt", directory=ckpt, slots=2, cache_len=64)
    srv.start()
    try:
        client = serving.ServingClient(srv.addr)
        prompt = np.array([3, 5, 7, 2, 11, 1, 4], np.int32)
        toks = client.decode("gpt", prompt, max_new_tokens=8)
        assert toks.shape == (8,)
        one = client.decode("gpt", np.array([5], np.int32),
                            max_new_tokens=3)
        assert one.shape == (3,)
        # the loop's chunked prefill committed exactly the prompt
        # prefix (the 1-token prompt has no prefix)
        assert cat.gen_tokens_committed.value(
            model="gpt", phase="prefill") == prompt.size - 1
        params = {k: np.asarray(v.data()._data)
                  for k, v in model._collect_params_with_prefix().items()}
        lm = GPTPagedLM(params, cfg)
        eng = GenerateEngine(lm, lm.make_cache(2, max_len=64))
        ref = eng.generate([prompt.tolist()], max_new_tokens=8)[0]
        assert toks.tolist() == ref
        client.close()
    finally:
        srv.stop()


def _served_gpt(tmp_path, tiny=None, name="seam", executables=None):
    """-> the ServedModel of a checkpoint of `tiny` (model, cfg; default: a
    new ``_tiny_gpt``) under `tmp_path`/`name`, with what `executables`
    (cfg, model) exports attached."""
    from incubator_mxnet_tpu.serving import loader as L
    model, cfg = tiny or _tiny_gpt(prefix=name + "_")
    ckpt = str(tmp_path / name)
    export_gpt_for_serving(ckpt, cfg, model)
    if executables is not None:
        L.attach_executables(ckpt, executables(cfg, model))
    return L.load_served_model(ckpt, quantize=False)


def _serve_by_hand(prefill, step, cache, prompt, feed):
    """What a DecodeLoop does for one request on a grid of two slots:
    -> the (len(feed), V) logits its slot's row got, step by step."""
    slot = cache.alloc()
    prefill(slot, np.asarray(prompt, np.int32), cache)
    tokens = np.zeros(2, np.int32)
    active = np.arange(2) == slot
    out = []
    for t in feed:
        tokens[slot] = t
        out.append(np.asarray(step(tokens, cache, active))[slot])
    return np.stack(out)


def test_the_served_step_is_the_engines_step_to_the_bit(tmp_path):
    """One prompt on one cache geometry through ``step_fn`` / ``prefill_fn``
    and through the engine's ``prefill_slot`` / ``step_slots`` over the
    same grid: every logit of every step is equal to the bit, and so are
    the lengths and the pools the two leave behind."""
    model, cfg = tiny = _tiny_gpt(prefix="seam_")
    served = _served_gpt(tmp_path, tiny)
    lm = GPTPagedLM({k: np.asarray(v.data()._data) for k, v
                     in model._collect_params_with_prefix().items()}, cfg)
    prompt, feed = [3, 5, 7, 2, 11, 1], [4, 9, 30, 2]
    ours = served.make_cache(2, 64)
    got = _serve_by_hand(served.prefill_fn, served.step_fn, ours, prompt,
                         feed)
    theirs = lm.make_cache(2, max_len=64)
    want = _serve_by_hand(
        lambda slot, tokens, cache: prefill_slot(
            lm, cache, slot, tokens, served.prefill_chunk),
        lambda tokens, cache, active: step_slots(
            lm, cache, range(2), tokens.reshape(2, 1),
            active.astype(np.int32)),
        theirs, prompt, feed)
    assert got.shape == (4, 37) and np.abs(got).max() > 0
    np.testing.assert_array_equal(got, want)
    _same_cache(ours, theirs)
    assert ours.lengths.tolist() == [len(prompt) + len(feed), 0]


def test_a_decode_loop_request_leaves_the_steps_spans(tmp_path):
    """The served path runs the engine's step, so a DecodeLoop request
    sent under a parent span leaves what a generate call leaves: kv.gather,
    lm.dispatch, lm.fetch and kv.commit for the prefill chunk and for each
    of the three grid steps, counting what crossed."""
    from incubator_mxnet_tpu.telemetry import tracing
    served = _served_gpt(tmp_path)
    cache = served.make_cache(2, 64)
    loop = DecodeLoop("gpt", served.step_fn, cache,
                      prefill_fn=served.prefill_fn,
                      prefill_chunk=served.prefill_chunk).start()
    tracing.clear_spans()
    try:
        with tracing.Span("test.call"):
            req = loop.submit(DecodeRequest("gpt", [3, 5, 7, 2, 11, 1],
                                            max_new_tokens=3))
            assert req.wait(60.0)["tokens"].shape == (3,)
    finally:
        loop.stop()
    recs = tracing.recent_spans()
    by_name = {name: [r for r in recs if r["name"] == name]
               for name in ("kv.gather", "lm.dispatch", "lm.fetch",
                            "kv.commit")}
    assert [len(v) for v in by_name.values()] == [4, 4, 4, 4]
    assert all(r["dur_us"] > 0 for v in by_name.values() for r in v)
    # the chunk's 5 positions, then one row of the grid's two a step
    assert [r["rows"] for r in by_name["kv.commit"]] == [5, 1, 1, 1]
    # nothing of the prefill chunk, then the grid's logits (2, 1, 37)
    assert [r["d2h_bytes"] for r in by_name["lm.fetch"]] \
        == [0] + [2 * 37 * 4] * 3
    # tokens, lengths and tables, and no pool: (1, 32), (1,), (1, 4), then
    # (2, 1), (2,), (2, 4)
    assert [r["h2d_bytes"] for r in by_name["lm.dispatch"]] \
        == [128 + 4 + 16] + [8 + 8 + 32] * 3


def test_loading_a_gpt_checkpoint_walks_no_array_by_elements(tmp_path,
                                                            monkeypatch):
    """A restore hands back NDArrays, and ``jnp.asarray`` of one iterates
    it row by row, scalar by scalar: minutes for a real embedding (found
    on the chip at GPT-2-small sizes, ISSUE 31). Load and weight swap
    unwrap them whole: with iteration forbidden both still work."""
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.serving import loader as L
    tiny = _tiny_gpt(prefix="walk_")
    ckpt = str(tmp_path / "walk")
    export_gpt_for_serving(ckpt, tiny[1], tiny[0])

    def refuse(self):
        raise AssertionError("an NDArray was walked element by element")
    monkeypatch.setattr(NDArray, "__iter__", refuse)
    served = L.load_served_model(ckpt, quantize=False)
    params, _meta = L.load_generation_params(ckpt, 0)
    assert any(isinstance(v, NDArray) for v in params.values())
    served.swap_params(params, 1)
    got = _serve_by_hand(served.prefill_fn, served.step_fn,
                         served.make_cache(2, 64), [3, 5, 7], [4])
    assert got.shape == (1, 37)


def test_a_swap_serves_the_tokens_of_an_adapter_built_on_the_new_weights(
        tmp_path):
    """A live weight push through the family's swap replaces ``wte``, and
    with it the lookup table the forwards gather from: what is served after
    it is, to the bit, what a fresh adapter on the new weights serves,
    and not what the old weights served (a stale table would feed the old
    embeddings to the new layers)."""
    served = _served_gpt(tmp_path, name="swap")
    prompt, feed = [3, 5, 7, 2, 11, 1], [4, 9, 30, 2]
    before = _serve_by_hand(served.prefill_fn, served.step_fn,
                            served.make_cache(2, 64), prompt, feed)
    params = _params(gpt_config(served.config), 11, scale=0.2)
    served.swap_params(params, 1)
    got = _serve_by_hand(served.prefill_fn, served.step_fn,
                         served.make_cache(2, 64), prompt, feed)
    lm = GPTPagedLM(params, served.config)
    want = _serve_by_hand(
        lambda slot, tokens, cache: prefill_slot(
            lm, cache, slot, tokens, served.prefill_chunk),
        lambda tokens, cache, active: step_slots(
            lm, cache, range(2), tokens.reshape(2, 1),
            active.astype(np.int32)),
        lm.make_cache(2, max_len=64), prompt, feed)
    np.testing.assert_array_equal(got, want)
    assert got.argmax(-1).tolist() != before.argmax(-1).tolist()


def _old_export(cfg, model):
    """``gptdecode/s2`` as the tree before ISSUE 31 exported it: flat
    inputs, the params a list, 1 + 2L outputs."""
    from incubator_mxnet_tpu.compilecache import aot
    from incubator_mxnet_tpu.models.gpt import (gpt_forward_paged,
                                                gpt_lookup_rows)
    params = {k: jnp.asarray(v.data()._data)
              for k, v in model._collect_params_with_prefix().items()}
    names = sorted(params)

    def pure(input_vals, param_vals):
        tokens, lengths, tables, k_pool, v_pool = input_vals
        named = dict(zip(names, param_vals))
        logits, nk, nv = gpt_forward_paged(
            named, cfg, tokens, lengths, tables, [k_pool], [v_pool],
            gpt_lookup_rows(named["wte"]))
        return [logits] + nk + nv
    pool = jnp.zeros((8, 16, 2, 8), jnp.float32)
    ins = [jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
           jnp.zeros((2, 4), jnp.int32), pool, pool]
    compiled = jax.jit(pure).lower(ins, [params[n] for n in names]).compile()
    return {"gptdecode/s2": aot.serialize_compiled(compiled)}


def _export_for_a_shorter_cache(cfg, model):
    """Today's whole grid, for caches of 32 positions (two blocks a
    slot) where the request's has 64."""
    from incubator_mxnet_tpu.serving import loader as L
    import tempfile
    with tempfile.TemporaryDirectory() as scratch:
        export_gpt_for_serving(scratch, cfg, model)
        served = L.load_served_model(scratch, quantize=False)
        served.make_cache(2, 32)
        assert not served.extra_warmup(2)["failed"]
        return served.export_executables()


_GRID = ["gptcommit/s2/r1xc32", "gptcommit/s2/r2xc1", "gptdecode/s2",
         "gptprefill/s2xc32"]


@pytest.mark.parametrize("executables,bound,rebuilt", [
    (_old_export, [], _GRID), (_export_for_a_shorter_cache, _GRID, [])],
    ids=["another-signature", "another-geometry"])
def test_a_shipped_program_that_does_not_fit_is_retired_not_raised(
        tmp_path, executables, bound, rebuilt):
    """A checkpoint whose executables were exported before ISSUE 31 (the
    old calling convention: refused at bind, so a later warm-up builds the
    program anew) or for another cache geometry (bound, refused by its
    first call and retired: the warm-up reports it failed and the next
    export ships none): the request is served through jit, with the logits
    of a checkpoint that shipped nothing."""
    tiny = _tiny_gpt(prefix="ship_")
    served = _served_gpt(tmp_path, tiny, "shipped", executables)
    assert sorted(served.decode_programs) == bound
    plain = _served_gpt(tmp_path, tiny, "plain")
    prompt, feed = [3, 5, 7, 2, 11, 1], [4, 9]
    got = _serve_by_hand(served.prefill_fn, served.step_fn,
                         served.make_cache(2, 64), prompt, feed)
    want = _serve_by_hand(plain.prefill_fn, plain.step_fn,
                          plain.make_cache(2, 64), prompt, feed)
    np.testing.assert_array_equal(got, want)
    assert not plain.decode_programs                # nothing shipped: jit
    assert sorted(served.extra_warmup(2)["built"]) == rebuilt
    assert sorted(served.export_executables()) == rebuilt


def test_the_grid_is_compiled_for_the_pools_make_cache_builds(
        tmp_path, monkeypatch):
    """The family's AOT example inputs are a cache's own: with
    MXTPU_GEN_BLOCK_SIZE=8 when ``make_cache`` ran (and unset when the
    programs are built, so that a formula over the environment would say
    16) every pool the decode program and its commit were compiled for
    has the shape, dtype and device order of the cache's, and a step runs
    them: none is retired."""
    from incubator_mxnet_tpu.generate.paged_kv import device_order
    served = _served_gpt(tmp_path)
    monkeypatch.setenv("MXTPU_GEN_BLOCK_SIZE", "8")
    cache = served.make_cache(2, 64)
    monkeypatch.delenv("MXTPU_GEN_BLOCK_SIZE")
    assert cache.pool("k0").shape == (2 * 8, 8, 2, 8)
    assert not served.extra_warmup(2)["failed"]
    decode = served.decode_programs["gptdecode/s2"].compiled
    commit = served.decode_programs["gptcommit/s2/r2xc1"].compiled
    pools = [cache.pool("k0")], [cache.pool("v0")]

    def facts(arrays, formats):
        return [[(a.shape, a.dtype, tuple(f.layout.major_to_minor))
                 for a, f in zip(*pair)] for pair in zip(arrays, formats)]
    want = [[(p.shape, p.dtype, device_order(p)) for p in ps] for ps in pools]
    (_p, _tokens, _lengths, tables, *kv), _kw = decode.in_avals
    assert tables.shape == (2, 8)
    assert facts(kv, decode.input_formats[0][4:]) == want
    assert facts(commit.in_avals[0][:2], commit.input_formats[0][:2]) == want
    _serve_by_hand(served.prefill_fn, served.step_fn, cache, [3, 5, 7], [4])
    assert served.decode_program_for(2).compiled is decode
    assert cache.programs[(2, 1)] is commit


def test_export_gpt_requires_draft_config(tmp_path):
    model, cfg = _tiny_gpt()

    class NoConfig:
        def _collect_params_with_prefix(self):
            return {}
    with pytest.raises(ValueError, match="draft model carries no config"):
        export_gpt_for_serving(str(tmp_path / "x"), cfg, model,
                               draft=NoConfig())


_WARM_GPT_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, sys.argv[3])
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.serving import loader as L
from incubator_mxnet_tpu.telemetry import catalog as cat

telemetry.enable()
cat.install_jax_compile_hook()
served = L.load_served_model(sys.argv[1], quantize=False)
assert served.decode_programs, "warm child bound no decode programs"
cache = served.make_cache(2, 64)
slot = cache.alloc()
base = cat.compile_events()
served.prefill_fn(slot, np.array([3, 5, 7, 2, 11, 1], np.int32), cache)
toks = np.zeros(2, np.int32)
toks[slot] = 4
out = []
active = np.array([True, False])
for _ in range(4):
    logits = served.step_fn(toks, cache, active)
    nxt = int(np.argmax(logits[slot]))
    out.append(nxt)
    toks[slot] = nxt
events = cat.compile_events() - base
print(json.dumps({"tag": "warm_child", "events": events, "tokens": out}))
"""


def test_warm_gpt_serving_two_process_drill(tmp_path, monkeypatch):
    """The round-14 acceptance drill: a restarted replica that binds
    the gpt decode-grid executables (decode step + prefill chunk) from
    the checkpoint serves its first generative request with ZERO
    backend_compile events — and the same tokens."""
    cat.install_jax_compile_hook()
    cache_dir = str(tmp_path / "ccache")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", cache_dir)
    monkeypatch.setenv("MXTPU_SERVE_CACHE_LEN", "64")
    from incubator_mxnet_tpu.serving import loader as L
    model, cfg = _tiny_gpt(prefix="wgpt_")
    ckpt = str(tmp_path / "serve")
    export_gpt_for_serving(ckpt, cfg, model)
    served = L.load_served_model(ckpt, quantize=False)
    cache = served.make_cache(2, 64)
    slot = cache.alloc()
    served.prefill_fn(slot, np.array([3, 5, 7, 2, 11, 1], np.int32), cache)
    toks = np.zeros(2, np.int32)
    toks[slot] = 4
    ref = []
    active = np.array([True, False])
    for _ in range(4):
        logits = served.step_fn(toks, cache, active)
        nxt = int(np.argmax(logits[slot]))
        ref.append(nxt)
        toks[slot] = nxt
    wu = served.extra_warmup(2)
    assert not wu["failed"], wu
    L.attach_executables(ckpt, served.export_executables())
    env = dict(os.environ)
    env.pop("MXTPU_COMPILE_CACHE_DIR", None)     # executables only
    env["MXTPU_SERVE_CACHE_LEN"] = "64"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _WARM_GPT_CHILD, ckpt, "-", repo],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = next(json.loads(ln) for ln in proc.stdout.splitlines()
               if ln.strip().startswith("{") and "warm_child" in ln)
    assert rec["events"] == 0, \
        "warm replica compiled %d time(s)" % rec["events"]
    assert rec["tokens"] == ref
