"""Ouro, a looped decoder, on the generation path, at a toy size on the CPU
(hidden 64, 4 heads of 16, 3 layers run T = 3 times a token, 320 tokens,
float32): prefill through the cache and then decoding against the plain
reference's full forward on all logits; the loop's two identities against
an independent plain decoder; two slots of different lengths in one
decode step; the exit rule; the loop as a loop in the compiled program;
the engine's call with its ``loop`` tallies, spans and counter; the decode
walk and the flash prefill on the launches in interpret mode.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import ouro as family
from benchmarks.reference import ouro as reference
from incubator_mxnet_tpu.generate import GenerateEngine, OuroPagedLM
from incubator_mxnet_tpu.generate.engine import prefill_slot, step_slots
from incubator_mxnet_tpu.models import ouro
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.telemetry import catalog, tracing

# float32 on both sides, every product at `highest`: the two differ in the
# order of their sums alone (a cache's two-part softmax and chunked
# prefill against one dense softmax over the whole sequence), which moves
# a logit of size 2 by some 1e-6; a row of another loop step, or a norm
# left out, moves it by 1e-2 and more
TOLERANCE = 1e-4


def toy(**over):
    cfg = {"vocab_size": 320, "hidden_size": 64, "num_hidden_layers": 3,
           "num_attention_heads": 4, "intermediate_size": 96,
           "total_ut_steps": 3, "early_exit_threshold": 1,
           "rms_norm_eps": 1e-6, "rope_theta": 1e6,
           "max_position_embeddings": 512, "dtype": "float32",
           "assumed": {"initializer_range": {"value": 0.2},
                       "prefill_chunk": {"value": 32}}}
    cfg.update(over)
    return cfg


def build(cfg, seed=5, slots=2, max_len=256, interpret=False):
    weights = reference.init_weights(cfg, seed)
    model = OuroPagedLM(weights, family.program_config(cfg),
                        dtype="float32", interpret=interpret)
    return weights, model, model.make_cache(slots, max_len=max_len)


def through_the_cache(model, cache, slot, tokens, prompt, chunk):
    """Prefill `tokens[:prompt - 1]` in chunks, then feed the rest a step
    at a time -> the logits at the positions prompt - 1 ...
    len(tokens) - 2, (n, V)."""
    got = []
    with jax.default_matmul_precision("highest"):
        prefill_slot(model, cache, slot, tokens[:prompt - 1], chunk)
        for t in range(prompt - 1, len(tokens) - 1):
            got.append(step_slots(model, cache, [slot],
                                  np.asarray([[tokens[t]]], np.int32))[0])
    return np.stack(got)


def reference_logits(weights, cfg, tokens, first):
    at = np.arange(first, len(tokens) - 1)[None]
    return reference.logits(weights, cfg, np.asarray(tokens)[None], at)[0]


@pytest.mark.parametrize("prompt,new,chunk", [
    (20, 40, 8),        # the issue's case: 40 decoded positions
    (33, 40, 16),       # the prefill's chunks find cached rows
    (17, 40, 16),       # a prompt of one whole chunk
    (7, 40, 32),        # shorter than a chunk
], ids=["forty_decoded", "chunks_over_cached_rows", "whole_chunk", "short"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        prompt, new, chunk):
    cfg = toy()
    weights, model, cache = build(cfg)
    tokens = np.random.default_rng(prompt).integers(1000 % 320, 320,
                                                    prompt + new)
    slot = cache.alloc()
    got = through_the_cache(model, cache, slot, tokens, prompt, chunk)
    want = reference_logits(weights, cfg, tokens, prompt - 1)
    assert got.shape == want.shape == (new, 320)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOLERANCE
    # one table and one length serve every loop step: a row a position
    # holds T steps of 64 lanes
    assert cache.lengths[slot] == len(tokens) - 1
    assert cache.pool("k0").shape[-1] == 3 * 64


# ------------------------------------------------------- the two identities
def plain_decoder_logits(weights, cfg, tokens, steps):
    """An independent plain decoder, float64: `steps` x L sandwich-norm
    layers, layer u L + l carrying layer l's weights, the final norm after
    every L layers, every position's logits."""
    H, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    T, d = len(tokens), cfg["hidden_size"] // H
    w = {n: np.asarray(a, np.float64) for n, a in weights.items()}

    def rms(x, g):
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True)
                           + cfg["rms_norm_eps"]) * g

    def rope(x):
        half = d // 2
        ang = np.arange(T)[:, None, None] * cfg["rope_theta"] ** (
            -np.arange(half) / half)
        a, b = x[..., :half], x[..., half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1)
    x = w["embed"][tokens]
    for n in range(steps * L):
        p = "l%d_" % (n % L)
        a = rms(x, w[p + "attn_norm"])
        q, k, v = [(a @ w[p + m]).reshape(T, H, d)
                   for m in ("q_w", "k_w", "v_w")]
        s = np.einsum("qhd,khd->hqk", rope(q), rope(k)) * d ** -0.5
        s = np.where(np.tril(np.ones((T, T), bool))[None], s, -np.inf)
        prob = np.exp(s - s.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        o = np.einsum("hqk,khd->qhd", prob, v).reshape(T, -1)
        x = x + rms(o @ w[p + "o_w"], w[p + "attn_post_norm"])
        m = rms(x, w[p + "ffn_norm"])
        gate = m @ w[p + "gate_w"]
        x = x + rms((gate / (1 + np.exp(-gate)) * (m @ w[p + "up_w"]))
                    @ w[p + "down_w"], w[p + "ffn_post_norm"])
        if n % L == L - 1:
            x = rms(x, w["final_norm"])
    return x @ w["head"]


@pytest.mark.parametrize("steps", [1, 3], ids=["one_step_is_a_decoder",
                                               "t_steps_are_t_l_layers"])
def test_the_loops_identities_with_a_plain_decoder(steps):
    """With T = 1 the model is an L-layer sandwich-norm decoder; for any T
    it is a plain decoder of T x L layers, layer u L + l carrying layer l's
    weights, with the final norm after every L: the reference and the
    program through its cache both agree with an independent one."""
    cfg = toy(total_ut_steps=steps)
    weights, model, cache = build(cfg)
    tokens = np.random.default_rng(steps).integers(0, 320, 37)
    want = plain_decoder_logits(weights, cfg, tokens, steps)
    assert np.abs(want).max() > 1.0
    ref = reference.logits(weights, cfg, tokens[None],
                           np.arange(len(tokens))[None])[0]
    assert np.abs(ref - want).max() < TOLERANCE
    got = through_the_cache(model, cache, cache.alloc(), tokens, 18, 8)
    assert np.abs(got - want[17:-1]).max() < TOLERANCE
    # and the loop is no plain decoder of L layers run once
    assert np.abs(plain_decoder_logits(weights, cfg, tokens, 1)
                  - want).max() > 10 * TOLERANCE or steps == 1


# ------------------------------------------------ two slots in one step
def test_two_slots_of_different_lengths_in_one_decode_step():
    cfg = toy()
    weights, model, cache = build(cfg)
    rng = np.random.default_rng(11)
    prompts = (9, 30)
    rows = [rng.integers(0, 320, n + 6) for n in prompts]
    slots = [cache.alloc(), cache.alloc()]
    got = [[], []]
    with jax.default_matmul_precision("highest"):
        for r in (0, 1):
            prefill_slot(model, cache, slots[r], rows[r][:prompts[r] - 1], 8)
        for step in range(6):
            tokens = np.asarray([[rows[r][prompts[r] - 1 + step]]
                                 for r in (0, 1)], np.int32)
            logits = step_slots(model, cache, slots, tokens)
            for r in (0, 1):
                got[r].append(logits[r])
    assert cache.lengths[slots].tolist() == [9 - 1 + 6, 30 - 1 + 6]
    for r in (0, 1):
        want = reference_logits(weights, cfg, rows[r], prompts[r] - 1)
        assert np.abs(np.stack(got[r]) - want).max() < TOLERANCE


# --------------------------------------------------------- the exit rule
def test_the_exit_rule_picks_the_last_step_at_threshold_one():
    # (steps, positions): a gate that all but closes the loop at once, or
    # at step 2, still leaves a token in the loop to the last step at 1.0
    lambdas = np.asarray([[0.5, 0.9, 0.999999, 0.0],
                          [0.5, 0.9, 0.999999, 1.0 - 1e-12],
                          [0.5, 0.9, 0.999999, 0.3]])
    assert reference.exit_steps(lambdas, 1.0).tolist() == [3, 3, 3, 3]
    # below 1.0 a token leaves at the first step whose sum reaches it:
    # sums (0.5, 0.75, 1), (0.9, 0.99, 1), (0.999999, ...), (0, ~1, 1)
    assert reference.exit_steps(lambdas, 0.5).tolist() == [1, 1, 1, 2]
    assert reference.exit_steps(lambdas, 0.96).tolist() == [3, 2, 1, 2]
    cfg = toy()
    weights = reference.init_weights(cfg, 2)
    tokens = np.random.default_rng(0).integers(0, 320, 12)[None]
    at = np.arange(12)[None]
    last = reference.logits(weights, cfg, tokens, at)
    assert np.array_equal(
        reference.logits(weights, cfg, tokens, at, exit_threshold=1.0), last)
    # an earlier exit is another model: the program refuses it
    assert not np.allclose(
        reference.logits(weights, cfg, tokens, at, exit_threshold=0.0), last)
    with pytest.raises(ValueError, match="ROADMAP.md B12"):
        OuroPagedLM(weights, family.program_config(
            toy(early_exit_threshold=0.9)), dtype="float32")


# ------------------------------------------- the loop is a loop in the program
@pytest.mark.parametrize("head,chunk", [("token", 1), ("none", 16)],
                         ids=["decode_step", "prefill_chunk"])
def test_the_program_holds_the_stack_once_whatever_the_loop_steps(head,
                                                                  chunk):
    """The forward lowered at T = 2 and at T = 4 holds the same number of
    products: the L layers' body appears once, inside a loop, and its new
    keys and values come out of it as one (L, S, C, T x 64) array each."""
    counts = []
    for steps in (2, 4):
        cfg = toy(total_ut_steps=steps)
        _weights, model, cache = build(cfg)
        slots = [cache.alloc(), cache.alloc()]
        lengths, tables, k_pools, v_pools = cache.forward_inputs(slots)
        lowered = model.lower(np.zeros((2, chunk), np.int32), lengths,
                              tables, k_pools, v_pools, head=head)
        text = lowered.as_text()
        counts.append(len(re.findall(r"stablehlo\.dot_general", text)))
        assert "stablehlo.while" in text
        assert [o.shape for o in jax.tree_util.tree_leaves(
            lowered.out_info)][1:] == [(3, 2, chunk, steps * 64)] * 2
    assert counts[0] == counts[1] > 0


# ------------------------------------------------------------ the engine
@pytest.fixture
def counting():
    telemetry.enable()
    yield
    telemetry.disable()


def test_a_generate_call_runs_the_loop_and_tallies_it(counting):
    """``GenerateEngine.generate`` over the adapter: the plain loop with
    the token head, greedy ids that are the reference's argmax, and
    ``last_stats["loop"]`` by phase, the ``loop_steps`` attribute of
    ``gen.prefill`` and ``lm.dispatch`` and the counter."""
    cfg = toy()
    weights, model, cache = build(cfg, slots=3, max_len=128)
    engine = GenerateEngine(model, cache, prefill_chunk=32, name="ouro_toy")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 320, n).tolist() for n in (40, 9, 70)]
    tracing.clear_spans()
    before = catalog.gen_loop_steps.value(model="ouro_toy", phase="decode")
    with jax.default_matmul_precision("highest"):
        with tracing.Span("test.ouro_call"):
            served = engine.generate(prompts, max_new_tokens=8)
    stats = engine.last_stats
    assert stats["decode_steps"] == 8
    assert stats["decode_steps_fed_on_device"] == 7
    loop = stats["loop"]
    T, L = 3, 3
    # prefill commits 39, 8, 69 positions in 2 + 1 + 3 chunks of 32: three
    # rounds of three rows (the last two padded with rows of length 0),
    # the second chunk of the first and the last two of the third over
    # cached rows
    assert loop["prefill"]["forwards"] == 3
    assert loop["prefill"]["loop_steps"] == 3 * T
    assert loop["prefill"]["layer_passes"] == 3 * T * L
    assert loop["prefill"]["cache_rows_read"] == (32 + 32 + 64) * T * L
    assert loop["prefill"]["cache_rows_written"] == (39 + 8 + 69) * T * L
    assert loop["decode"]["forwards"] == 8
    assert loop["decode"]["loop_steps"] == 8 * T
    assert loop["decode"]["cache_rows_read"] == sum(
        n - 1 + s for n in (40, 9, 70) for s in range(8)) * T * L
    assert loop["decode"]["cache_rows_written"] == 3 * 8 * T * L
    assert loop["decode"]["positions"] == sum(
        n + s for n in (40, 9, 70) for s in range(8))
    assert catalog.gen_loop_steps.value(model="ouro_toy", phase="decode") \
        - before == 8 * T
    spans = tracing.recent_spans()
    # a prompt's gen.prefill, recorded when its last chunk is committed
    assert [s["loop_steps"] for s in spans if s["name"] == "gen.prefill"] \
        == [1 * T, 2 * T, 3 * T]
    assert {s.get("loop_steps") for s in spans
            if s["name"] == "lm.dispatch"} == {T}
    for prompt, out in zip(prompts, served):
        sequence = np.asarray(prompt + out)
        ref = reference.logits(weights, cfg, sequence[None], np.arange(
            len(prompt) - 1, len(sequence) - 1)[None])[0]
        assert out == ref.argmax(-1).tolist()
    assert cache.in_use == 0 and cache.blocks_in_use == 0


def test_the_parameter_shapes_and_the_cache_rows():
    cfg = ouro.ouro_config(family.program_config(toy()))
    shapes = ouro.ouro_param_shapes(cfg)
    assert shapes["head"] == (64, 320) and shapes["l2_ffn_post_norm"] == (64,)
    assert {n: tuple(a.shape) for n, a in reference.init_weights(
        toy(), 1).items()} == shapes
    _weights, model, cache = build(toy())
    # one k and one v entry a layer, T steps of H D lanes a row; the exit
    # gate is no argument of the program
    assert sorted(cache.spec) == sorted(
        "%s%d" % (e, i) for e in "kv" for i in range(3))
    assert cache.spec["k1"][1] == (3 * 64,)
    assert not any(n.startswith("exit_") for n in model.params)


# --------------------------------------------------- on the launches
def test_prefill_and_decoding_on_the_launches_are_the_references_forward():
    """The adapter with ``interpret=True`` takes a chunk over an empty slot
    through the tiled flash forward and walks every (step, layer) entry by
    the decode launch given the step (heads of 128 lanes, as they need):
    a prefill chunk, then decoding, against the reference and against the
    ``lax`` path."""
    cfg = toy(hidden_size=256, num_attention_heads=2, total_ut_steps=2,
              num_hidden_layers=2)
    weights = reference.init_weights(cfg, 7)
    tokens = np.random.default_rng(1).integers(0, 320, 129 + 5)
    got = []
    for interpret in (True, False):
        model = OuroPagedLM(weights, family.program_config(cfg),
                            dtype="float32", interpret=interpret)
        cache = model.make_cache(1, max_len=256, block_size=16)
        got.append(through_the_cache(model, cache, cache.alloc(), tokens,
                                     129, 128))
    want = reference_logits(weights, cfg, tokens, 128)
    # at 256 lanes the logits reach 10 and every path, the ``lax`` one
    # too, lies some 4e-4 from the reference: a sublayer's small output
    # normed up by its post-norm carries its sums' rounding with it
    assert np.abs(got[0] - want).max() < 10 * TOLERANCE
    assert np.abs(got[0] - got[1]).max() < TOLERANCE
