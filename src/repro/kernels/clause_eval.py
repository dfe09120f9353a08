"""Pallas TPU kernel: clause crossbar tile (binary matmul + CSA epilogue).

The paper's clause crossbar computes, per column j, the current
``I_j = sum_i TA_inc[i,j] * (1-L[i]) * V_R`` and a current-sense amplifier
thresholds it at 4.1 uA (== "at least one (literal 0, include) pair").  On
TPU the same computation is an int8 MXU matmul with a ``== 0`` epilogue:

    viol  = (1 - L) @ TA_inc          # int8 x int8 -> int32 on the MXU
    fired = (viol == 0) & nonempty    # the CSA + empty-clause digital mask

The kernel keeps the int32 violation counts in a VMEM accumulator across the
K (literal) grid axis and only writes the 1-byte Boolean clause bits to HBM,
i.e. the "currents" never round-trip — exactly the in-memory-computing
property the paper gets from Kirchhoff's law.

``mode="viol"`` instead emits the raw violation counts; this is the partial
result exchanged between literal shards in the Fig. 14 multi-tile scheme
(psum of viol == the paper's digital AND of partial clauses).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# MXU-aligned default tiling: int8 min tile on TPU is (32, 128); we use
# 128-multiples everywhere so both MXU matmul dims are hardware aligned.
BLOCK_B = 128
BLOCK_N = 128
BLOCK_K = 512


def _clause_kernel(lit_ref, inc_ref, ne_ref, out_ref, acc_ref, *,
                   n_k: int, mode: str):
    """Grid (B/bm, N/bn, K/bk); acc_ref is a (bm, bn) int32 VMEM scratch."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    not_l = (1 - lit_ref[...]).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        not_l, inc_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        viol = acc_ref[...]
        if mode == "viol":
            out_ref[...] = viol
        else:
            fired = (viol == 0) & (ne_ref[...] != 0)
            out_ref[...] = fired.astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "block_n", "block_k",
                              "interpret"))
def clause_eval(literals: Array, include: Array, nonempty: Array, *,
                mode: str = "fired", block_b: int = BLOCK_B,
                block_n: int = BLOCK_N, block_k: int = BLOCK_K,
                interpret: bool = False) -> Array:
    """literals (B, K) int8, include (K, N) int8, nonempty (1, N) int8.

    Returns fired (B, N) int8 (mode="fired") or viol (B, N) int32
    (mode="viol").  All dims must already be multiples of the block sizes
    (``ops.clause_eval`` pads arbitrary shapes).
    """
    B, K = literals.shape
    K2, N = include.shape
    assert K == K2 and nonempty.shape == (1, N)
    assert B % block_b == 0 and N % block_n == 0 and K % block_k == 0, (
        (B, K, N, block_b, block_n, block_k))
    n_k = K // block_k
    out_dtype = jnp.int32 if mode == "viol" else jnp.int8

    return pl.pallas_call(
        functools.partial(_clause_kernel, n_k=n_k, mode=mode),
        grid=(B // block_b, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda b, n, k: (b, k)),
            pl.BlockSpec((block_k, block_n), lambda b, n, k: (k, n)),
            pl.BlockSpec((1, block_n), lambda b, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda b, n, k: (b, n)),
        out_shape=jax.ShapeDtypeStruct((B, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(literals, include, nonempty)
