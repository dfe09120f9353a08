"""Pallas TPU kernel: analog crossbar matrix-vector multiply (digital twin).

Simulates the physics of a Y-Flash crossbar read: each cell contributes
``I = G * V_R * nl(G)`` where ``nl`` is the paper's low-conductance read
nonlinearity (Fig. 5c: LCS cells read ~3 nA instead of the ohmic 2 nA), and
driven rows sum onto columns by Kirchhoff's law.  Used by the variability
benchmarks to evaluate programmed conductance arrays at scale.

The nonlinearity is applied to the conductance block in VMEM right before
the MXU dot, so the "effective current matrix" is never materialized in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

BLOCK_B = 128
BLOCK_N = 128
BLOCK_K = 512


def _mvm_kernel(drive_ref, g_ref, out_ref, acc_ref, *, n_k: int,
                v_read: float, nonlin: float, cutoff: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...]
    i_cell = g * v_read * jnp.where(g < cutoff, nonlin, 1.0)
    # HIGHEST: Mosaic's default contracts f32 in bf16 passes (~1e-3
    # relative on the cell currents); the analog sums need f32.
    acc_ref[...] += jax.lax.dot_general(
        drive_ref[...], i_cell,
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("v_read", "nonlin", "cutoff", "block_b",
                              "block_n", "block_k", "interpret"))
def crossbar_mvm(drive: Array, g: Array, *, v_read: float = 2.0,
                 nonlin: float = 1.5, cutoff: float = 10e-9,
                 block_b: int = BLOCK_B, block_n: int = BLOCK_N,
                 block_k: int = BLOCK_K, interpret: bool = False) -> Array:
    """drive (B, K) f32 row voltages (in V_R units), g (K, N) f32 S.

    Returns column currents (B, N) f32.
    """
    B, K = drive.shape
    K2, N = g.shape
    assert K == K2
    assert B % block_b == 0 and N % block_n == 0 and K % block_k == 0, (
        (B, K, N))
    n_k = K // block_k

    return pl.pallas_call(
        functools.partial(_mvm_kernel, n_k=n_k, v_read=v_read,
                          nonlin=nonlin, cutoff=cutoff),
        grid=(B // block_b, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda b, n, k: (b, k)),
            pl.BlockSpec((block_k, block_n), lambda b, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda b, n, k: (b, n)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(drive, g)
