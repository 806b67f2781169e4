"""The main path's kernels and one whole BERT-base train step, compiled for
a DESCRIBED TPU v5e — no chip attached, nothing runs.

Interpret mode says nothing about Mosaic: the fused AdamW kernel passed
every interpret-mode test and was refused by the chip's compiler
(``math.powf`` has no Mosaic lowering). These cases hand real widths to
that compiler on every tier-1 run.

The topology is described inside a fixture of THIS file, never at import:
only one process may load libtpu, and every xdist worker imports every
test file. A compile that passes is not a chip run.
"""

import collections
import importlib
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops import pallas
from incubator_mxnet_tpu.ops.pallas import fused_norm, fused_optim

# the package re-exports the function under the module's own name
flash_mod = importlib.import_module(
    "incubator_mxnet_tpu.ops.pallas.flash_attention")

BERT_BASE_PARAMS = 110_000_000      # ≈ the packed f32 buffer of BERT-base


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing a chip here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# ------------------------------------------------------------ fused optimizer
def _adamw(w, g, m, v, t):
    return fused_optim.fused_adamw_flat(w, g, m, v, 1e-4, 0.01, 1.0, 0.9,
                                        0.999, 1e-8, t, 1.0, -1.0)


def _adam(w, g, m, v, t):
    return fused_optim.fused_adam_flat(w, g, m, v, 1e-4, 0.01, 0.9, 0.999,
                                       1e-8, t, 1.0, -1.0)


def _sgd_mom(w, g, m, v, t):
    return fused_optim.fused_sgd_mom_flat(w, g, m, 0.1, 1e-4, 0.9, 1.0, -1.0)


@pytest.mark.parametrize("update", [_adamw, _adam, _sgd_mom],
                         ids=["adamw", "adam", "sgd_mom"])
def test_fused_optimizer_compiles_for_v5e(one_chip, update):
    buf = jax.ShapeDtypeStruct((BERT_BASE_PARAMS,), jnp.float32,
                               sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = _compile(update, buf, buf, buf, buf, t)
    # the bias-correction powers are taken outside the kernel
    assert "powf" not in text


# ----------------------------------------------------------- fused LayerNorm
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_layer_norm_compiles_for_v5e(one_chip, direction):
    x = jax.ShapeDtypeStruct((64, 128, 768), jnp.bfloat16, sharding=one_chip)
    gb = jax.ShapeDtypeStruct((768,), jnp.bfloat16, sharding=one_chip)

    def fwd(x, g, b):
        return fused_norm.fused_layer_norm(x, g, b, 1e-12)

    def bwd(x, g, b):
        # the backward is plain jnp over the saved input; squaring keeps
        # the forward kernel's output live in the same program
        return jax.grad(
            lambda *a: jnp.square(fwd(*a).astype(jnp.float32)).sum(),
            argnums=(0, 1, 2))(x, g, b)

    _compile(fwd if direction == "fwd" else bwd, x, gb, gb)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, direction):
    qkv = jax.ShapeDtypeStruct((16, 12, 512, 64), jnp.bfloat16,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((16, 512), jnp.float32, sharding=one_chip)

    def fwd(q, k, v, mask):
        return flash_mod.flash_attention(q, k, v, kv_mask=mask)

    def bwd(q, k, v, mask):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, mask).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    _compile(fwd if direction == "fwd" else bwd, qkv, qkv, qkv, mask)


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kv_mask"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_one_tile_compiles_for_v5e(one_chip, direction, masked):
    """Cell 3's own attention call, (32, 512, 768) bfloat16 as the dense
    layers leave it: Mosaic's verdict on the lane handling (two heads of 64
    in a 128-lane block, taken by zeroing lanes and selecting lanes) and on
    the tile's float32 temporaries in scoped VMEM. The launches carry the
    names a trace is searched for, with q as first operand."""
    qkv = jax.ShapeDtypeStruct((32, 512, 768), jnp.bfloat16,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((32, 512), jnp.float32, sharding=one_chip)

    def fwd(q, k, v, mask):
        return flash_mod.flash_attention_bthd(
            q, k, v, 12, kv_mask=mask if masked else None)

    def bwd(q, k, v, mask):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, mask).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile(fwd if direction == "fwd" else bwd, qkv, qkv, qkv, mask)
    launches = re.findall(
        r"^\s*(?:ROOT )?%(?:\w+?_)??(flash_\w+?)[_.\d]* = (\(.*?\)) "
        r"custom-call\(.*?operand_layout_constraints=\{(\w+\[[\d,]+\])",
        text, re.M)
    want = [("flash_attention_tile_fwd", "bf16[32,512,768]")]
    if direction == "bwd":
        want.append(("flash_attention_tile_bwd", "bf16[32,512,768]"))
    assert [(name, first) for name, _, first in launches] == want
    # what benchmarks/metrics/flash_attention_roofline.py tells a forward
    # from a backward launch by: float32 statistics among the results
    assert ["f32[" in result for _, result, _ in launches] == \
        [True, False][:len(launches)]


def test_flash_one_tile_bound_compiles_for_v5e(one_chip):
    """The longest sequence the one-tile path takes, with its widest
    temporaries (a mask, the backward): it fits the scoped VMEM."""
    T = flash_mod._TILE_MAX_T
    qkv = jax.ShapeDtypeStruct((4, T, 256), jnp.bfloat16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((4, T), jnp.float32, sharding=one_chip)
    for heads in (4, 2):                # heads of 64 and of 128
        assert flash_mod._one_tile(T, heads, 256 // heads, False, None)
        _compile(lambda q, k, v, mask: jax.grad(
            lambda q, k, v: flash_mod.flash_attention_bthd(
                q, k, v, heads, kv_mask=mask).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), qkv, qkv, qkv, mask)


# ------------------------------------------------------------ expert layer
@pytest.mark.parametrize("rows,k,n", [(512, 2048, 768), (512, 768, 2048),
                                      (768, 2048, 768)],
                         ids=["up_block", "down_block", "up_prefill"])
def test_grouped_matmul_compiles_for_v5e(one_chip, rows, k, n):
    """The dropless layer's products at the SDAR-30B-A3B cell's sizes: 128
    experts of 2048 x 768, 64 or 96 tokens x 8 routes."""
    from incubator_mxnet_tpu.ops.pallas.grouped_matmul import grouped_matmul
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((128, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one_chip)
    text = _compile(lambda x, w, s: grouped_matmul(x, w, s, use_kernel=True),
                    x, w, sizes)
    assert "moe_grouped_matmul" in text


@pytest.mark.parametrize("rows,k,n", [(8192, 3584, 1024), (8192, 1024, 3584),
                                      (64, 3584, 1024)],
                         ids=["up_prefill", "down_prefill", "up_step"])
def test_grouped_matmul_of_full_groups_compiles_for_v5e(one_chip, rows, k, n):
    """The latent-cache cell's products, 64 experts of 3584 x 1024: a
    prefill chunk's 2,048 tokens x 4 routes fill the groups and take tiles
    of 128 rows; a decode step's 16 x 4 keep the 16-row tiles."""
    from incubator_mxnet_tpu.ops.pallas.grouped_matmul import grouped_matmul
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    text = _compile(lambda x, w, s: grouped_matmul(x, w, s, use_kernel=True),
                    x, w, sizes)
    assert "moe_grouped_matmul" in text


@pytest.mark.parametrize("program", ["choice", "denoise"])
def test_sdar_block_forward_compiles_for_v5e(one_chip, monkeypatch, program):
    """One layer of the cell's denoising forward (16 rows x 4 positions
    over 16-block tables of bfloat16 pools, published widths, the whole
    vocabulary) with the grouped launch in it: the choice alone, and the
    block loop's program, which applies the schedule to it with the steps
    left an int32 operand and returns the next tokens and mask."""
    from incubator_mxnet_tpu.generate import GenerateEngine
    from incubator_mxnet_tpu.models import sdar_moe
    from incubator_mxnet_tpu.ops.pallas import grouped_matmul as gm
    monkeypatch.setattr(gm, "grouped_matmul_available", lambda: True)
    cfg = sdar_moe.sdar_config({
        "vocab_size": 151936, "units": 2048, "num_layers": 1,
        "num_heads": 32, "num_kv_heads": 4, "head_dim": 128,
        "num_experts": 128, "experts_per_token": 8, "expert_hidden": 768,
        "block_length": 4, "mask_id": 151669})

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    params = {n: shape(s) for n, s in sdar_moe.sdar_param_shapes(cfg).items()}
    pools = [shape((256, 16, 4, 128))]

    def forward(params, tokens, masked, steps_left, lengths, tables, kps,
                vps):
        (x0, conf), _nk, _nv, loads = sdar_moe.sdar_forward_paged(
            params, cfg, tokens, lengths, tables, kps, vps, head="choice")
        if program == "choice":
            return x0, conf, loads
        fixed = GenerateEngine._fix_most_confident(masked, conf, steps_left)
        return (jnp.where(fixed, x0, tokens), masked & ~fixed, x0, conf,
                loads)
    text = _compile(forward, params, shape((16, 4), jnp.int32),
                    shape((16, 4), jnp.bool_), shape((), jnp.int32),
                    shape((16,), jnp.int32), shape((16, 16), jnp.int32),
                    pools, pools)
    assert text.count("moe_grouped_matmul") >= 3


# -------------------------------------------------- the latent-cache decoder
XING4_SLOTS, XING4_MAX_LEN, XING4_LAYERS = 16, 16896, 2


@pytest.mark.parametrize("rows,heads,max_len", [(16, 32, 16896),
                                               (128, 64, 896)],
                         ids=["long_prompts", "wide_batch"])
def test_latent_decode_launch_compiles_for_v5e(one_chip, rows, heads,
                                               max_len):
    """The absorbed path's walk alone at the two latent cells' shapes: the
    absorbed queries of a step against a pool of 640-lane rows in the
    blocks a latent cache takes (128 positions), the block tables and
    lengths as scalar-prefetch operands, the pool left in HBM and copied
    by the launch itself, a tile of 512 positions in each half of its
    buffer."""
    from incubator_mxnet_tpu.ops.pallas.paged_latent import (
        latent_block_size, paged_latent_decode)

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    block = latent_block_size(640 * 2, max_len)
    assert block == 128
    blocks = max_len // block
    compiled = jax.jit(
        lambda q, pool, tables, lengths: paged_latent_decode(
            q, pool, tables, lengths, 0.1, 512)).lower(
        shape((rows, heads, 640)), shape((rows * blocks, block, 640)),
        shape((rows, blocks), jnp.int32), shape((rows,), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_latent_decode" in text
    # the state of the past and nothing else: no copy of a tile in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("chunk,heads,max_len", [(1024, 32, 16896),
                                                 (512, 64, 896)],
                         ids=["long_prompts", "wide_batch"])
def test_latent_prefill_launch_compiles_for_v5e(one_chip, chunk, heads,
                                                max_len):
    """The expanded path alone at the two latent cells' shapes: one
    prompt's chunk of queries, its own rows and ``W_kvb`` against a pool
    of 640-lane rows in blocks of 128 positions, tables and lengths as
    scalar-prefetch operands, the pool left in HBM. What the launch holds
    (queries, weights, rows and state of a head group, a tile's keys,
    values and scores) fits the VMEM it asks for, and nothing of a tile
    is a temporary in HBM."""
    from incubator_mxnet_tpu.ops.pallas.paged_latent import (
        latent_block_size, paged_latent_prefill)

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    block = latent_block_size(640 * 2, max_len)
    blocks = max_len // block
    compiled = jax.jit(
        lambda q_n, q_r, new, kv_b, pool, tables, lengths:
        paged_latent_prefill(q_n, q_r, new, kv_b, pool, tables, lengths,
                             0.1)).lower(
        shape((1, chunk, heads, 128)), shape((1, chunk, heads, 64)),
        shape((1, chunk, 640)), shape((512, heads, 256)),
        shape((16 * blocks, block, 640)), shape((1, blocks), jnp.int32),
        shape((1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_latent_prefill" in text
    # the queries and the weights heads-major, and no more: 24 MB at most
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def _xing4_programs(one_chip, monkeypatch):
    """The cell's configuration at its published widths, cut for the
    compile to one dense and one expert layer, as abstract arguments for
    a described chip: -> (adapter over shapes, the forward's arguments
    for tokens of a shape, the cache's pool shape)."""
    from benchmarks import spec
    from benchmarks.families import xing4
    from incubator_mxnet_tpu.generate import MLAPagedLM
    from incubator_mxnet_tpu.models import mla_moe
    from incubator_mxnet_tpu.ops.pallas import grouped_matmul as gm
    from incubator_mxnet_tpu.ops.pallas import paged_latent
    monkeypatch.setattr(gm, "grouped_matmul_available", lambda: True)
    monkeypatch.setattr(paged_latent, "paged_latent_decode_available",
                        lambda pool=None: True)
    monkeypatch.setattr(paged_latent, "paged_latent_prefill_available",
                        lambda *shapes: True)
    cfg = spec.load_json(spec.ROOT + "/benchmarks/configs/xing4_29b_a4b.json")
    program = dict(xing4.program_config(cfg), num_layers=XING4_LAYERS)

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    shapes = mla_moe.mla_param_shapes(mla_moe.mla_config(program))
    model = MLAPagedLM({}, program)     # the weights are a call's argument
    model.params = {n: shape(s) for n, s in shapes.items()}
    block = paged_latent.latent_block_size(640 * 2, XING4_MAX_LEN)
    blocks = XING4_MAX_LEN // block
    pool = shape((XING4_SLOTS * blocks, block, 640))

    def arguments(S, C):
        return (shape((S, C), jnp.int32), shape((S,), jnp.int32),
                shape((S, blocks), jnp.int32), [pool] * XING4_LAYERS)
    return model, arguments, xing4.assumed(cfg, "prefill_chunk")


def test_latent_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The cell's decode step, 16 rows of one token over 132-block
    tables: the absorbed path with its walk as one launch a layer, the
    grouped launch in the expert layer and the head over the whole
    vocabulary."""
    model, arguments, _chunk = _xing4_programs(one_chip, monkeypatch)
    compiled = model.lower(*arguments(XING4_SLOTS, 1)).compile()
    text = compiled.as_text()
    assert text.count("moe_grouped_matmul") >= 3
    assert "paged_latent_decode" in text
    # no tile of every row's blocks gathered into a copy
    assert not re.search(r"bf16\[(16,512|16,4,128|64,128),640\]", text)
    # no per-head key or value of the whole table: the step reads rows
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_a_held_shares_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The wide-batch cell's decode step at its published widths (hidden
    7,168, 64 heads, 12 of 384 experts of 7,168 x 2,048 held, an eighth of
    the vocabulary), cut for the compile to one dense and one expert
    layer: 128 rows of one token over 7-block tables, the token head,
    the absorbed walk one launch a layer. The share's passes are a loop
    around the three grouped launches, on 240 slots of a tile layout (64 routes a pass in 16-row tiles of 12
    groups), and nothing as tall as the 1,024 routes of the step is 7,168
    wide."""
    from benchmarks import spec
    from benchmarks.families import kimi_k2
    from incubator_mxnet_tpu.generate import MLAPagedLM
    from incubator_mxnet_tpu.models import mla_moe
    from incubator_mxnet_tpu.ops.pallas import grouped_matmul as gm
    from incubator_mxnet_tpu.ops.pallas import paged_latent
    monkeypatch.setattr(gm, "grouped_matmul_available", lambda: True)
    monkeypatch.setattr(paged_latent, "paged_latent_decode_available",
                        lambda pool=None: True)
    cfg = spec.load_json(spec.ROOT
                         + "/benchmarks/configs/kimi_k2_7_code.json")
    program = dict(kimi_k2.program_config(cfg), num_layers=2)

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    shapes = mla_moe.mla_param_shapes(mla_moe.mla_config(program))
    assert shapes["l1_gate_w"] == (12, 7168, 2048)
    assert shapes["l1_router_w"] == (7168, 384)
    model = MLAPagedLM({}, program)     # the weights are a call's argument
    model.params = {n: shape(s) for n, s in shapes.items()}
    rows, blocks = 128, 896 // 128
    pools = [shape((rows * blocks, 128, 640))] * 2
    lowered = model.lower(shape((rows, 1), jnp.int32),
                          shape((rows,), jnp.int32),
                          shape((rows, blocks), jnp.int32), pools,
                          head="token")
    outputs = [o.shape for o in jax.tree_util.tree_leaves(lowered.out_info)]
    # the ids, the held loads with the share's two numbers, the cache rows
    assert sorted(outputs) == [(1, 14), (2, 128, 1, 640), (128, 1)]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("moe_grouped_matmul") >= 3
    assert "bf16[240,7168]" in text and "[1024,7168]" not in text
    assert "paged_latent_decode" in text
    assert not re.search(r"bf16\[(128,512|128,4,128|512,128),640\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def _gpt2_xl_step(one_chip):
    """Cell 2's decode step: GPT-2-XL's widths cut to two layers, 4 rows
    of one token over a cache of 96 positions, float32."""
    from benchmarks import spec
    from benchmarks.families import gpt2
    from incubator_mxnet_tpu.generate import GPTPagedLM
    from incubator_mxnet_tpu.models.gpt import gpt_param_shapes
    cfg = spec.load_json(spec.ROOT + "/benchmarks/configs/gpt2_xl.json")
    program = dict(gpt2.program_config(cfg), num_layers=2)

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    model = GPTPagedLM({}, program)     # the weights are a call's argument
    model.params = {n: shape(s)
                    for n, s in gpt_param_shapes(model.config).items()}
    pools = [shape((4 * 6, 16, 25, 64))] * 2
    return model, (shape((4, 1), jnp.int32), shape((4,), jnp.int32),
                   shape((4, 6), jnp.int32), pools, pools)


@pytest.mark.parametrize("cell", ["gpt2_xl", "xing4"])
def test_the_token_head_step_returns_no_vocabulary_on_v5e(one_chip,
                                                          monkeypatch, cell):
    """The decode steps of cells 2 and 5 with the token head: the program
    compiles for the chip and returns the rows' ids, (S, 1) int32, and
    what the chunk adds to the cache (the expert loads where there are
    experts), and NO output as wide as the vocabulary: the logits (0.8 MB
    and 8.4 MB a step) stay inside."""
    if cell == "gpt2_xl":
        model, arguments = _gpt2_xl_step(one_chip)
    else:
        model, of, _chunk = _xing4_programs(one_chip, monkeypatch)
        arguments = of(XING4_SLOTS, 1)
    rows, vocab = arguments[0].shape[0], model.config["vocab_size"]
    lowered = model.lower(*arguments, head="token")
    outputs = jax.tree_util.tree_leaves(lowered.out_info)
    assert (rows, 1) in [o.shape for o in outputs]
    assert not any(vocab in o.shape for o in outputs)
    with_logits = jax.tree_util.tree_leaves(
        model.lower(*arguments).out_info)
    assert (rows, 1, vocab) in [o.shape for o in with_logits]
    compiled = lowered.compile()
    assert compiled.memory_analysis().output_size_in_bytes \
        < rows * vocab * 4


def test_latent_prefill_chunk_fits_beside_the_weights_on_v5e(one_chip,
                                                              monkeypatch):
    """The prefill chunk the family sets, over a table of 16,384 cached
    positions and more: no (C, L, 32) score array exists (4.3 GB at C =
    2048), a tile's temporaries stay under 2 GB beside 8.1 GB of weights
    and 1.7 GB of pools."""
    model, arguments, chunk = _xing4_programs(one_chip, monkeypatch)
    compiled = model.lower(*arguments(1, chunk), head="none").compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    # a prefill's last layer stops at its cache rows: no expert product
    assert "moe_grouped_matmul" not in compiled.as_text()


def test_latent_prefill_chunk_is_one_launch_a_layer_on_v5e(one_chip,
                                                           monkeypatch):
    """The cell's prefill program with a head (a prompt's last chunk: no
    layer is dropped), 1,024 positions over a table of 132 blocks: the
    expanded attention of each layer is the launch
    ``paged_latent_prefill``, so the program holds no float32 score tile
    of 32 heads x 1,024 queries x 512 keys in any order of dimensions (67
    MB that the ``lax`` path writes and reads three times a tile), and no
    loop that up-projects rows (a ``while`` whose body multiplies by
    ``W_kvb``: the ``lax`` path's walk over the past)."""
    model, arguments, chunk = _xing4_programs(one_chip, monkeypatch)
    assert chunk == 1024
    compiled = model.lower(*arguments(1, chunk), head="token").compile()
    text = compiled.as_text()
    assert text.count("paged_latent_prefill") >= XING4_LAYERS
    for shape in re.findall(r"f32\[([\d,]+)\]", text):
        dims = sorted(int(n) for n in shape.split(",") if int(n) > 1)
        assert dims != [32, 512, 1024], "a score tile in HBM: f32[%s]" % shape
    # a loop that up-projects carries W_kvb's halves, (512, 32, 128) each
    for loop in re.findall(r"= \((.*?)\) while\(", text):
        for shape in re.findall(r"bf16\[([\d,]+)\]", loop):
            dims = [int(n) for n in shape.split(",")]
            assert not {512, 32} <= set(dims), "W_kvb in a loop: %s" % shape
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("block", [128, 16], ids=["latent_block", "16"])
@pytest.mark.parametrize("chunk", [(16, 1), (1, 2048)],
                         ids=["step", "prefill_chunk"])
def test_the_latent_commit_copies_no_pool_on_v5e(one_chip, chunk, block):
    """One entry a layer: the commit program of a latent cache takes the
    pools, the rows and the positions, aliases every pool and copies
    none, in the 128-position blocks ``MLAPagedLM.make_cache`` takes and
    in blocks of 16. A row is 576 values in 640, five whole lane tiles
    (11 % of the pools, 0.17 GB of the cell's 1.73): the chip holds that pool in row
    order, and one of 576-wide rows with its BLOCKS minor, which every
    forward would copy into row order to gather from."""
    wide = jax.ShapeDtypeStruct((16 * XING4_MAX_LEN // 16, 16, 576),
                                jnp.bfloat16, sharding=one_chip)
    held = jax.jit(lambda p: p).lower(wide).compile().input_formats[0][0]
    assert tuple(held.layout.major_to_minor) == (1, 2, 0)
    from incubator_mxnet_tpu.generate.paged_kv import store_program_for
    layers, pool = 5, (16 * XING4_MAX_LEN // block, block, 640)
    kv = jax.ShapeDtypeStruct(pool, jnp.bfloat16, sharding=one_chip)
    new = jax.ShapeDtypeStruct((layers,) + chunk + pool[2:], jnp.bfloat16,
                               sharding=one_chip)
    rows = jax.ShapeDtypeStruct(chunk, jnp.int32, sharding=one_chip)
    held = jax.jit(lambda p: p).lower(kv).compile().input_formats[0][0]
    assert tuple(held.layout.major_to_minor) == (0, 1, 2)
    compiled = store_program_for(1).lower(
        [kv] * layers, new, rows, ((0, 1, 2),) * layers).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= layers * kv.size * 2
    assert memory.temp_size_in_bytes < 1 << 20
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
    assert not [dims for dims in copies
                if sorted(map(int, dims.split(","))) == sorted(pool)]


# ------------------------------------------------- one whole BERT-base step
def _answer_tpu(monkeypatch):
    """The kernel gates ask the backend, which is the CPU here, so the test
    answers for them; the program gets no option."""
    def on_tpu():
        return True

    for name in ("fused_norm_available", "flash_attention_available"):
        monkeypatch.setattr(pallas, name, on_tpu)
    monkeypatch.setattr(fused_norm, "fused_norm_available", on_tpu)
    monkeypatch.setattr(flash_mod, "flash_attention_available", on_tpu)


# ----------------------------------------------------- the paged KV commit
@pytest.mark.parametrize("layers,pool,dtype,chunk,held_as", [
    # gpt2_xl: a decode step, a prefill chunk; sdar_30b_a3b: a block
    (48, (24, 16, 25, 64), jnp.float32, (4, 1), (0, 2, 1, 3)),
    (48, (24, 16, 25, 64), jnp.float32, (1, 32), (0, 2, 1, 3)),
    (6, (256, 16, 4, 128), jnp.bfloat16, (16, 4), (0, 1, 2, 3)),
], ids=["gpt2_xl_step", "gpt2_xl_chunk", "sdar_block"])
def test_the_kv_commit_copies_no_pool_on_v5e(one_chip, layers, pool, dtype,
                                             chunk, held_as):
    """``PagedKVCache.commit``'s program at the benchmark's sizes: every
    pool aliases its output (the donation takes) and none is copied into
    another layout for the scatter and back. The chip keeps a pool whose
    head count its tiles do not divide in another dim order (25 heads:
    blocks, heads, positions, width), which `device_order` reads off the
    array and is read here off a compiled identity."""
    from incubator_mxnet_tpu.generate.paged_kv import store_program
    kv = jax.ShapeDtypeStruct(pool, dtype, sharding=one_chip)
    # K and V as the adapters return them: one array stacked over layers
    new = jax.ShapeDtypeStruct((layers,) + chunk + pool[2:], dtype,
                               sharding=one_chip)
    rows = jax.ShapeDtypeStruct(chunk, jnp.int32, sharding=one_chip)
    held = jax.jit(lambda p: p).lower(kv).compile().input_formats[0][0]
    order = tuple(held.layout.major_to_minor)
    assert order == held_as
    compiled = store_program.lower([kv] * layers, [kv] * layers, new, new,
                                   rows, (order,) * 2 * layers).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * layers * kv.size * kv.dtype.itemsize
    assert memory.temp_size_in_bytes < 1 << 20
    copies = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", compiled.as_text())
    assert not [dims for dims in copies      # a pool, in whatever order
                if sorted(map(int, dims.split(","))) == sorted(pool)]


def test_bert_base_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """chip_smoke.py's shape A: the BERT-base AdamW step at B=64,T=128,
    built on the CPU mesh and lowered for the described chip."""
    import chip_smoke
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = chip_smoke.bert_trainer(mesh)
    host_data, host_label = chip_smoke.bert_batch(64, 128)
    fn, args = tr._inspection_step([mx.nd.array(a) for a in host_data],
                                   [mx.nd.array(a) for a in host_label])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)

    # from here on the trace is for the chip (the trainer's own eager
    # shape-materializing forward above ran on the CPU, kernels off)
    _answer_tpu(monkeypatch)
    compiled = fn.lower(*shapes).compile()
    text = compiled.as_text()
    # names on the device: the launches are called after their kernels, not
    # after the scope they happen to sit in
    # (a kernel under autodiff is prefixed by its transform: %jvp_fused_...)
    kernels = collections.Counter(
        re.sub(r"\.\d+$", "", name) for name in re.findall(
            r"^\s*%([\w.]+) = .*custom_call_target=\"tpu_custom_call\"",
            text, re.M))
    # fused LayerNorm (2 a layer + embedding + MLM head) and softmax (1 a
    # layer); nothing gave way to a lax reference
    assert kernels == {"jvp_fused_layernorm_": 26, "jvp_fused_softmax_": 12}
    # the optimizer is applied leaf by leaf under the trainer's scope: no
    # packed launch, no copy of the parameters into or out of one buffer
    assert "/optim/" in text
    for gone in ("fused_adamw", "/optim/pack/", "/optim/unpack/"):
        assert gone not in text, gone
    assert not re.search(r"^\s*%concatenate.*op_name=\"[^\"]*/optim/", text,
                         re.M)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < 16e9, "does not fit one v5e chip: %d bytes" % need


def test_attention_layer_step_at_t512_carries_no_head_transpose(
        one_chip, monkeypatch):
    """A MultiHeadAttention layer's compiled train step at cell 3's shape:
    the one-tile launches read and write (B, T, H*D), so no array is carried
    into (32, 12, 512, 64) and back, forward or backward."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.bert import MultiHeadAttention
    from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh

    net = MultiHeadAttention(768, 12, prefix="t512attn_")
    net.initialize(mx.init.Normal(0.02))
    net(mx.nd.array(np.zeros((1, 8, 768), np.float32)))
    tr = ShardedTrainer(
        net, lambda out, label: ((out.astype(jnp.float32) - label) ** 2).mean(),
        make_mesh({"dp": 1}, devices=jax.devices()[:1]), optimizer="sgd",
        compute_dtype="bfloat16")
    x = mx.nd.array(np.zeros((32, 512, 768), np.float32))
    fn, args = tr._inspection_step([x], [x])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    _answer_tpu(monkeypatch)
    text = fn.lower(*shapes).compile().as_text()
    kernels = collections.Counter(
        re.sub(r"[_.\d]+$", "", name) for name in re.findall(
            r"^\s*%([\w.]+) = .*custom_call_target=\"tpu_custom_call\"",
            text, re.M))
    assert kernels == {"jvp_flash_attention_tile_fwd": 1,
                       "transpose_jvp_flash_attention_tile_bwd": 1}
    # no instruction of any kind yields heads outermost (or its reverse)
    assert not re.search(r"\[32,12,512,64\]|\[32,512,12,64\]", text)


def test_bert_train_step_compiles_for_v5e_mesh(topo, monkeypatch):
    """chip_smoke.py --chips 4: the dp2 x tp2 step (depth cut to 2 layers
    here; every layer shards alike). jit refuses a Mosaic kernel in a
    program for several devices, so over a mesh the step must take the XLA
    form of LayerNorm, decided from its mesh — with every gate answering
    "tpu" it still lowers, and holds no kernel."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import chip_smoke
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.bert import bert_sharding_rules
    from incubator_mxnet_tpu.parallel import make_mesh

    tr = chip_smoke.bert_trainer(
        make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4]),
        rules=bert_sharding_rules("tp"), data_spec=P("dp"), num_layers=2)
    host_data, host_label = chip_smoke.bert_batch(64, 128)
    _fn, args = tr._inspection_step([mx.nd.array(a) for a in host_data],
                                    [mx.nd.array(a) for a in host_label])
    # the same mesh and shardings over the described chips: nothing can be
    # placed on them, so the trainer is built on the CPU mesh and handed
    # the described one for the trace
    chips = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))

    def described(a):
        spec = a.sharding.spec if isinstance(a.sharding, NamedSharding) \
            else P()
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(chips, spec))

    shapes = jax.tree_util.tree_map(described, args)
    monkeypatch.setattr(tr, "_mesh", chips)
    monkeypatch.setattr(tr, "_param_shardings", {
        n: NamedSharding(chips, s.spec)
        for n, s in tr._param_shardings.items()})
    _answer_tpu(monkeypatch)
    compiled = jax.jit(tr._build_raw(3)).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text          # dp grads, tp activations
    # a tp-ruled weight is held in halves
    name = next(n for n in shapes[0] if n.endswith("ffn1_weight"))
    assert shapes[0][name].sharding.shard_shape(shapes[0][name].shape) == \
        (3072 // 2, 768)


# ------------------------------------------- windows and summaries (cell 7)
@pytest.mark.parametrize("program", ["prefill_chunk", "decode_step",
                                     "closing"])
def test_evabytes_three_programs_compile_at_published_widths(
        one_chip, monkeypatch, program):
    """Cell 7's programs at ``evabyte_6_5b``'s widths cut to two layers,
    over the cell's own pools (24 slots: a window of 2,048 rows in blocks
    of 256, 768 summary rows in blocks of 128, rows of all 32 heads side
    by side), as the chip takes them: a prefill chunk of one window that
    opens on an empty window, its own causal part one tiled
    ``flash_attention_fwd`` a layer, the window pools unread; a decode step
    of 24 rows with the token head, whose walks over the two groups are
    two ``paged_heads_decode`` launches a layer and gather nothing; and
    the closing of one slot's window (``lax``)."""
    from benchmarks import spec
    from benchmarks.families import evabyte
    from incubator_mxnet_tpu.generate import EvaPagedLM
    from incubator_mxnet_tpu.models import eva_byte
    monkeypatch.setattr(eva_byte, "paged_heads_decode_available",
                        lambda pool, heads: True)
    monkeypatch.setattr(eva_byte, "flash_attention_available", lambda: True)
    cfg = spec.load_json(spec.ROOT + "/benchmarks/configs/evabyte_6_5b.json")
    config = dict(evabyte.program_config(cfg), num_layers=2)

    def shape(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    shapes = eva_byte.eva_param_shapes(eva_byte.eva_config(config))
    assert shapes["l1_q_w"] == (4096, 4096) and shapes["l1_mu"] == (32, 128)
    assert shapes["l1_gate_w"] == (4096, 11008)
    assert shapes["head"] == (4096, 8 * 320)
    model = EvaPagedLM({}, config)      # the weights are a call's argument
    model.params = {n: shape(s) for n, s in shapes.items()}
    slots = 24
    window = [shape((slots * 8, 256, 4096))] * 2
    summary = [shape((slots * 6, 128, 4096))] * 2

    def inputs(rows, chunk):
        return (shape((rows, chunk), jnp.int32), shape((rows,), jnp.int32),
                shape((rows, 8), jnp.int32), shape((rows,), jnp.int32),
                shape((rows, 6), jnp.int32), window, window, summary,
                summary)
    if program == "prefill_chunk":
        lowered = model.lower(*inputs(1, 2048), head="none", fresh=True)
        # the stage's stream, the window rows after, the keys and values
        wanted = [(1, 2048, 4096), (1,), (2, 1, 2048, 4096),
                  (2, 1, 2048, 4096)]
    elif program == "decode_step":
        lowered = model.lower(*inputs(slots, 1), head="token")
        wanted = [(24, 1), (2, 24, 1, 4096), (2, 24, 1, 4096)]
    else:
        lowered = model.lower_close(shape((1, 8), jnp.int32), window,
                                    window)
        wanted = [(2, 1, 128, 4096)] * 2
    outputs = [o.shape for o in jax.tree_util.tree_leaves(lowered.out_info)]
    assert outputs == wanted
    compiled = lowered.compile()
    text = compiled.as_text()
    if program == "decode_step":
        assert text.count("paged_heads_decode") >= 4
        assert not re.search(r"bf16\[24,(8,256|6,128|2048|768),", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28
    elif program == "prefill_chunk":    # no window block is gathered
        assert text.count("flash_attention_fwd") >= 2
        assert "bf16[1,8,256,4096]" not in text
        assert not re.search(r"f32\[1,32,(512|2048),(512|1024|2048)\]", text)
    else:
        assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
