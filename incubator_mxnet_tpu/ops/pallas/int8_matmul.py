"""Pallas probe: int8 x int8 -> s32 matmul on the MXU (VERDICT r4 #8).

BENCHMARKS.md's int8 finding ("bf16 beats int8 because XLA upcasts int8
conv accumulation") rested entirely on XLA's lowering; this kernel asks
the silicon directly: a Mosaic matmul fed int8 operands with an s32
accumulator. If the MXU's int8 mode is reachable through this stack it
should clear the bf16 calibration (~150-166 TF/s on this part);
if Mosaic also upcasts, the probe confirms the ceiling is the stack,
not the benchmark. No caller outside the tests and ``serving/quant.py``
(ROADMAP C3).

Reference counterpart: src/operator/quantization/ (the reference's int8
wins come from backend int8 kernels, mkldnn/cuDNN).
"""

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

__all__ = ["int8_matmul", "int8_matmul_available"]


def int8_matmul_available():
    return jax.default_backend() == "tpu"


def _kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         preferred_element_type=jnp.int32)


def int8_matmul(a, b, block_m=512, block_n=512, interpret=False):
    """a: (M, K) int8, b: (K, N) int8 -> (M, N) int32. K is unsplit
    (one contraction per program); M/N tile the grid."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and a.dtype == jnp.int8 and b.dtype == jnp.int8
    bm, bn = min(block_m, M), min(block_n, N)
    assert M % bm == 0 and N % bn == 0
    return pl.pallas_call(
        _kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
        name="int8_matmul",
    )(a, b)
