"""Bitplane packing for the ternary clause crossbar.

IMPACT's TA-action matrices are ternary at the device abstraction: a
crossbar cell is either a high-conductance include (HCS), a
low-conductance exclude (LCS), or absent/pruned (no current).  The int8
datapath nevertheless streams a float32 read current per cell every
sweep.  This module packs the clause crossbar into 2-bit codes — four
cells per byte along the literal-row (contraction) axis — plus two
scalar dequant levels, shrinking the dominant operand ~16x (f32 -> 2
bits) and the total sweep input bytes well past the 4x gate.

Layout contract (shared by the Pallas kernels, the einsum oracle, and
the shard_map lowering): bit-field ``j`` (shift ``2*j``) of packed row
``q`` holds the code of original row ``4*q + j``.  Codes:

* ``CODE_DEAD = 0`` — no device / pruned / padding; contributes 0 A.
* ``CODE_LCS  = 1`` — exclude cell; dequants to the mean LCS current.
* ``CODE_HCS  = 2`` — include cell; dequants to the mean HCS current.
* ``3`` is reserved.

Classification splits the bimodal device populations at the geometric
midpoint of the smallest and largest positive cell currents (decades
from either population — LCS leakage sits at nA, HCS reads at uA), so
this module needs no ``impact.yflash`` constants; callers may pass an
explicit ``split`` instead.  The CSA threshold is deliberately NOT the
default split: it is a *column*-level decision current, and a far-tail
HCS cell just below it would mis-bin as LCS and flip CSA bits.  Packing
is lossless on ideal (variability-free) systems, where every HCS/LCS
cell carries the identical current; on device-variability systems the
CSA decision bits are preserved (column currents sit decades away from
the threshold), so argmax parity survives quantization even though
per-cell currents collapse to their class means.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

CODE_DEAD = 0
CODE_LCS = 1
CODE_HCS = 2
CELLS_PER_BYTE = 4
_CODE_BITS = 2
_CODE_MASK = (1 << _CODE_BITS) - 1


class PackedClause(NamedTuple):
    """A packed clause crossbar: codes + dequantization levels.

    ``bits`` has shape ``(R, C, ceil(tr/4), tc)`` uint8 — the clause
    tile grid with the literal-row axis packed 4:1.  ``levels`` is a
    ``(2,)`` float32 array ``[i_lcs, i_hcs]`` of class-mean read
    currents.  NamedTuple => a pytree, so it flows through jit/shard_map
    as two ordinary operands.
    """

    bits: jnp.ndarray
    levels: jnp.ndarray


def packed_rows(n_rows: int) -> int:
    """Number of packed (byte) rows covering ``n_rows`` cell rows."""
    return -(-n_rows // CELLS_PER_BYTE)


def pack_ternary(codes):
    """Pack a ``(K, N)`` matrix of 2-bit codes into ``(ceil(K/4), N)`` uint8.

    Rows beyond K pad with ``CODE_DEAD``.
    """
    codes = jnp.asarray(codes)
    k, _ = codes.shape
    k4 = packed_rows(k)
    pad = k4 * CELLS_PER_BYTE - k
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)),
                        constant_values=CODE_DEAD)
    planes = codes.astype(jnp.uint8).reshape(k4, CELLS_PER_BYTE, -1)
    packed = jnp.zeros(planes.shape[::2], jnp.uint8)
    for j in range(CELLS_PER_BYTE):
        packed = packed | (planes[:, j] << (_CODE_BITS * j))
    return packed


def unpack_ternary(packed, n_rows: int):
    """Inverse of :func:`pack_ternary`: ``(K4, N)`` uint8 -> ``(n_rows, N)``."""
    packed = jnp.asarray(packed)
    planes = [(packed >> (_CODE_BITS * j)) & _CODE_MASK
              for j in range(CELLS_PER_BYTE)]
    full = jnp.stack(planes, axis=1).reshape(-1, packed.shape[1])
    return full[:n_rows].astype(jnp.uint8)


def population_split(currents):
    """Geometric midpoint of the smallest and largest positive currents.

    The Y-Flash cell populations are bimodal with a ~3-decade gap (LCS
    leakage ~nA, HCS reads ~uA); the log-midpoint lands in that gap for
    any physical device-variability spread, with no dependence on
    ``impact.yflash`` constants.  Degenerate single-population operands
    classify everything as HCS (split == the common value).
    """
    currents = jnp.asarray(currents, jnp.float32)
    hi = jnp.maximum(currents.max(), 0.0)
    lo = jnp.min(jnp.where(currents > 0.0, currents, hi))
    return jnp.sqrt(jnp.maximum(hi, 1e-30) * jnp.maximum(lo, 1e-30))


def classify_currents(currents, *, split=None):
    """Ternary codes for per-cell read currents.

    ``<= 0`` A is a dead/pruned cell, ``>= split`` is HCS, anything
    between is LCS leakage.  ``split=None`` (default) uses
    :func:`population_split`.
    """
    currents = jnp.asarray(currents)
    if split is None:
        split = population_split(currents)
    return jnp.where(
        currents <= 0.0, jnp.uint8(CODE_DEAD),
        jnp.where(currents >= split, jnp.uint8(CODE_HCS),
                  jnp.uint8(CODE_LCS)))


def quant_levels(currents, codes):
    """``[i_lcs, i_hcs]`` float32 — class-mean currents (0.0 for empty classes)."""
    currents = jnp.asarray(currents, jnp.float32)

    def mean_of(code):
        mask = (codes == code).astype(jnp.float32)
        n = jnp.maximum(mask.sum(), 1.0)
        return (currents * mask).sum() / n

    return jnp.stack([mean_of(CODE_LCS), mean_of(CODE_HCS)])


def dequant_codes(codes, levels):
    """Codes -> float32 currents via the two scalar levels."""
    codes = jnp.asarray(codes)
    return jnp.where(
        codes == CODE_HCS, levels[1],
        jnp.where(codes == CODE_LCS, levels[0], 0.0)).astype(jnp.float32)


def pack_clause_operand(clause_i, *, split=None) -> PackedClause:
    """Pack a ``(R, C, tr, tc)`` clause-current operand.

    Returns :class:`PackedClause` with ``bits`` of shape
    ``(R, C, ceil(tr/4), tc)`` — the row axis packed 4:1 — and the two
    dequant levels.  Traceable: it can pack inside jit, and an
    ``InferenceSession`` packs concretely at compile time.
    """
    clause_i = jnp.asarray(clause_i, jnp.float32)
    r, c, tr, tc = clause_i.shape
    codes = classify_currents(clause_i, split=split)
    levels = quant_levels(clause_i, codes)
    tr4 = packed_rows(tr)
    pad = tr4 * CELLS_PER_BYTE - tr
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, 0), (0, pad), (0, 0)),
                        constant_values=CODE_DEAD)
    planes = codes.reshape(r, c, tr4, CELLS_PER_BYTE, tc)
    bits = jnp.zeros((r, c, tr4, tc), jnp.uint8)
    for j in range(CELLS_PER_BYTE):
        bits = bits | (planes[:, :, :, j] << (_CODE_BITS * j))
    return PackedClause(bits=bits, levels=levels)


def dequant_clause(bits, levels, tr: int):
    """Unpack ``(R, C, tr4, tc)`` bits back to ``(R, C, tr, tc)`` currents."""
    bits = jnp.asarray(bits)
    r, c, tr4, tc = bits.shape
    planes = [(bits >> (_CODE_BITS * j)) & _CODE_MASK
              for j in range(CELLS_PER_BYTE)]
    codes = jnp.stack(planes, axis=3).reshape(r, c, tr4 * CELLS_PER_BYTE, tc)
    return dequant_codes(codes[:, :, :tr], levels)


def packed_nbytes(packed: PackedClause) -> int:
    """Total bytes of the packed operand (codes + levels)."""
    return int(packed.bits.size * packed.bits.dtype.itemsize
               + packed.levels.size * packed.levels.dtype.itemsize)
