"""Pallas TPU kernel: class crossbar tile (weighted vote sum).

The paper's class crossbar sums weighted clause votes per class column via
Kirchhoff's law.  On TPU this is an int8 x int32 matmul accumulated in VMEM:

    scores = clauses @ W          # (B, N) x (N, M) -> (B, M) int32

M (the class count) is tiny (10 in the paper) — ``ops.class_sum`` pads it to
one 128-lane tile so the MXU stays aligned; the kernel grids over B and the
clause (N) axis and keeps the (bm, bn_cls) accumulator resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

BLOCK_B = 128
BLOCK_N = 512   # clause-axis (contraction) block
BLOCK_M = 128   # class-axis block (paper: m=10, padded)


def _class_kernel(cl_ref, w_ref, out_ref, acc_ref, *, n_n: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        cl_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(n == n_n - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_m", "interpret"))
def class_sum(clauses: Array, weights: Array, *, block_b: int = BLOCK_B,
              block_n: int = BLOCK_N, block_m: int = BLOCK_M,
              interpret: bool = False) -> Array:
    """clauses (B, N) int8, weights (N, M) int32 -> scores (B, M) int32."""
    B, N = clauses.shape
    N2, M = weights.shape
    assert N == N2
    assert B % block_b == 0 and N % block_n == 0 and M % block_m == 0, (
        (B, N, M, block_b, block_n, block_m))
    n_n = N // block_n

    return pl.pallas_call(
        functools.partial(_class_kernel, n_n=n_n),
        grid=(B // block_b, M // block_m, n_n),
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda b, m, n: (b, n)),
            pl.BlockSpec((block_n, block_m), lambda b, m, n: (n, m)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda b, m, n: (b, m)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, block_m), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(clauses, weights)
