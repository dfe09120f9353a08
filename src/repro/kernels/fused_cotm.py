"""Pallas TPU kernel: fused CoTM inference (clause tile + class tile).

Beyond-paper optimization.  The paper wires two physical crossbars
back-to-back through CSA latches; the digital-twin equivalent of that wiring
is keeping the Boolean clauses in VMEM and never writing them to HBM:

    per clause-chunk n:
        viol   = (1 - L) @ inc[:, n]        # int8 MXU matmul, (bm, bn)
        fired  = (viol == 0) & nonempty[n]  # CSA epilogue, stays in VMEM
        scores += fired @ W[n, :]           # class tile partial sum

The class scores are linear in the clause bits, so chunking the clause axis
and accumulating the (bm, M) score block is exact.  One HBM round-trip for
the whole inference instead of two (the clause matrix (B, N) is never
materialized) — for the paper's 2048x500x10 MNIST shape this removes the
largest intermediate entirely.

Constraint: the literal axis K is kept whole per block (lit block (bm, K)),
which bounds K at a few thousand for VMEM residency — exactly the regime of
one physical crossbar tile.  Larger K goes through the sharded path
(``clause_eval(mode="viol")`` + psum) mirroring the paper's Fig. 14.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

BLOCK_B = 128
BLOCK_N = 256


def _fused_kernel(lit_ref, inc_ref, ne_ref, w_ref, out_ref, acc_ref, *,
                  n_n: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    not_l = (1 - lit_ref[...]).astype(jnp.int8)
    viol = jax.lax.dot_general(
        not_l, inc_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    fired = (viol == 0) & (ne_ref[...] != 0)
    acc_ref[...] += jax.lax.dot_general(
        fired.astype(jnp.int8), w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(n == n_n - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def fused_cotm(literals: Array, include: Array, nonempty: Array,
               weights: Array, *, block_b: int = BLOCK_B,
               block_n: int = BLOCK_N, interpret: bool = False) -> Array:
    """literals (B, K) int8, include (K, N) int8, nonempty (1, N) int8,
    weights (N, M) int32 -> scores (B, M) int32.

    B % block_b == 0, N % block_n == 0, K % 128 == 0, M % 128 == 0 required
    (``ops.fused_cotm`` pads arbitrary shapes).
    """
    B, K = literals.shape
    K2, N = include.shape
    N2, M = weights.shape
    assert K == K2 and N == N2 and nonempty.shape == (1, N)
    assert (B % block_b == 0 and N % block_n == 0 and K % 128 == 0
            and M % 128 == 0), (B, K, N, M)
    n_n = N // block_n

    return pl.pallas_call(
        functools.partial(_fused_kernel, n_n=n_n),
        grid=(B // block_b, n_n),
        in_specs=[
            pl.BlockSpec((block_b, K), lambda b, n: (b, 0)),
            pl.BlockSpec((K, block_n), lambda b, n: (0, n)),
            pl.BlockSpec((1, block_n), lambda b, n: (0, n)),
            pl.BlockSpec((block_n, M), lambda b, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, M), lambda b, n: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, M), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(literals, include, nonempty, weights)
