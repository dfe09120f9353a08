"""Pallas TPU kernel: fused ANALOG IMPACT inference (both crossbars).

Digital twin of the paper's two-crossbar datapath with the Fig. 14 modular
scaling baked into the tiling.  Where ``fused_cotm`` fuses the *logical*
CoTM (include mask + integer weights), this kernel fuses the *physical*
simulation — per-cell Y-Flash read currents, the CSA threshold, and the
digital periphery — in one VMEM residency:

    per clause-column chunk n:
        for each of the R literal row-shards r:
            I_col[r]  = drive[r] @ I_cell[r][:, n]     # Kirchhoff column sum
            partial_r = I_col[r] < I_CSA_THRESHOLD     # CSA latch
        fired   = AND_r partial_r  &  nonempty[n]      # digital AND (Fig. 14)
        scores += fired @ I_class[n, :]                # class column currents

The Boolean clause chunk ``fired`` never leaves VMEM: the (B, n_pad) clause
matrix — the largest intermediate of the un-fused path — is never
materialized in HBM.  The class crossbar's S row-shards are flattened onto
the clause-chunk axis, so the per-shard ADC + digital add is subsumed by
the chunk accumulation (exact: the class read is linear in the drive).

Layouts (prepared by ``ops.fused_impact``):
  drive   (R, B, tr)   f32   1 - literal, row-shard major; padding rows 0
  ccur    (R, tr, N)   f32   clause-cell read currents, columns flattened
  ne      (1, N)       int8  digital empty-clause mask
  wcur    (N, M)       f32   class-cell read currents, S shards flattened
  out     (B, M)       f32   class column currents (argmax = prediction)

R stays whole per block (the digital AND needs every shard's partial bit),
mirroring ``fused_cotm`` keeping K whole; this bounds R*tr at a few
thousand rows — exactly the regime of a physical crossbar column height.

``fused_impact_metered`` is the same datapath with in-kernel energy
metering: the paper (and IMBUE, arXiv:2305.12914) measure read energy as
``E = V_R * I_col * t_read`` summed over the very column currents the
inference already computes, so the metered kernel folds each chunk's
``I_col`` into a second VMEM accumulator while the CSA consumes it —
joules come out of the single fused pass with no staged second pass and
without ever materializing the (B, n_pad) clause matrix in HBM.
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


def _dot_f32(a, b):
    """``a @ b`` on the MXU with f32 contraction.  Mosaic's default
    contracts f32 operands in reduced-precision (bf16) passes, which on
    a TPU moves the cell currents -- and so the class currents, the CSA
    margin and the energy meters -- by ~1e-3 relative; HIGHEST keeps the
    digital twin at the f32 precision its parity tolerances assume."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _fused_impact_kernel(drive_ref, ccur_ref, ne_ref, wcur_ref, out_ref,
                         acc_ref, *, n_n: int, n_r: int, thresh: float):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bb = drive_ref.shape[1]
    bn = ne_ref.shape[1]
    fired = jnp.broadcast_to(ne_ref[...] != 0, (bb, bn))
    for r in range(n_r):                       # static unroll over row shards
        i_col = _dot_f32(drive_ref[r], ccur_ref[r])
        fired = fired & (i_col < thresh)       # CSA + digital AND, in VMEM
    acc_ref[...] += _dot_f32(fired.astype(jnp.float32), wcur_ref[...])

    @pl.when(n == n_n - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("thresh", "block_b", "block_n", "interpret"))
def fused_impact(drive: Array, ccur: Array, nonempty: Array, wcur: Array, *,
                 thresh: float, block_b: int = BLOCK_B,
                 block_n: int = BLOCK_N, interpret: bool = False) -> Array:
    """drive (R, B, tr) f32, ccur (R, tr, N) f32, nonempty (1, N) int8,
    wcur (N, M) f32 -> class currents (B, M) f32.

    B % block_b == 0, N % block_n == 0, tr % 128 == 0, M % 128 == 0 required
    (``ops.fused_impact`` pads arbitrary shapes and shard layouts).
    """
    R, B, tr = drive.shape
    R2, tr2, N = ccur.shape
    N2, M = wcur.shape
    assert R == R2 and tr == tr2 and N == N2 and nonempty.shape == (1, N)
    assert (B % block_b == 0 and N % block_n == 0 and tr % 128 == 0
            and M % 128 == 0), (B, R, tr, N, M)
    n_n = N // block_n

    return pl.pallas_call(
        functools.partial(_fused_impact_kernel, n_n=n_n, n_r=R,
                          thresh=thresh),
        grid=(B // block_b, n_n),
        in_specs=[
            pl.BlockSpec((R, block_b, tr), lambda b, n: (0, b, 0)),
            pl.BlockSpec((R, tr, block_n), lambda b, n: (0, 0, n)),
            pl.BlockSpec((1, block_n), lambda b, n: (0, n)),
            pl.BlockSpec((block_n, M), lambda b, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, M), lambda b, n: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, M), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(drive, ccur, nonempty, wcur)


#: Lane layout of the metered kernel's (B, METER_LANES) meter output:
#: lane 0 carries the summed clause-crossbar column currents, lane 1 the
#: summed class-crossbar column currents.  128 lanes (one VREG row) keep
#: the output MXU/VPU tile-aligned; the wrapper slices the two live lanes.
METER_LANE_CLAUSE = 0
METER_LANE_CLASS = 1
METER_LANES = 128


def _fused_impact_metered_kernel(drive_ref, ccur_ref, ne_ref, wcur_ref,
                                 out_ref, meter_ref, acc_ref, macc_ref, *,
                                 n_n: int, n_r: int, thresh: float):
    """The fused datapath + in-kernel energy meter.

    Identical clause/class compute to ``_fused_impact_kernel``; on top,
    each chunk's clause column currents are folded into a second VMEM
    accumulator (``macc_ref``) the moment the CSA consumes them.  The
    class-current meter needs no extra accumulation at all: the class
    read is linear, so the summed class column current is exactly the
    row-sum of the score accumulator — computed once in the epilogue.
    """
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        macc_ref[...] = jnp.zeros_like(macc_ref)

    bb = drive_ref.shape[1]
    bn = ne_ref.shape[1]
    fired = jnp.broadcast_to(ne_ref[...] != 0, (bb, bn))
    i_chunk = jnp.zeros((bb, 1), jnp.float32)
    for r in range(n_r):                       # static unroll over row shards
        i_col = _dot_f32(drive_ref[r], ccur_ref[r])
        fired = fired & (i_col < thresh)       # CSA + digital AND, in VMEM
        i_chunk += i_col.sum(axis=1, keepdims=True)
    # Every meter lane accumulates the same per-lane clause current (a
    # plain VPU broadcast-add — no per-chunk lane select); the epilogue
    # picks METER_LANE_CLAUSE.  Padded rows/columns carry 0 A by the
    # wrapper's neutral padding, so they add exactly zero here.
    macc_ref[...] += i_chunk
    acc_ref[...] += _dot_f32(fired.astype(jnp.float32), wcur_ref[...])

    @pl.when(n == n_n - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, macc_ref.shape, 1)
        i_class = acc_ref[...].sum(axis=1, keepdims=True)
        meter_ref[...] = jnp.where(
            lane == METER_LANE_CLAUSE, macc_ref[...],
            jnp.where(lane == METER_LANE_CLASS, i_class, 0.0))


@functools.partial(
    jax.jit, static_argnames=("thresh", "block_b", "block_n", "interpret"))
def fused_impact_metered(drive: Array, ccur: Array, nonempty: Array,
                         wcur: Array, *, thresh: float,
                         block_b: int = BLOCK_B, block_n: int = BLOCK_N,
                         interpret: bool = False,
                         ) -> tuple[Array, Array]:
    """Metered variant of ``fused_impact``: same layouts and constraints,
    returns ``(class currents (B, M) f32, meters (B, METER_LANES) f32)``
    where meter lane ``METER_LANE_CLAUSE`` holds the per-lane summed
    clause-crossbar column current and ``METER_LANE_CLASS`` the per-lane
    summed class-crossbar column current — the quantities
    ``impact.energy.per_lane_read_energy`` converts to joules.  The
    backend plumbing (``PallasBackend.fused_impact_metered``) pads inputs
    and slices the live meter lanes back out.
    """
    R, B, tr = drive.shape
    R2, tr2, N = ccur.shape
    N2, M = wcur.shape
    assert R == R2 and tr == tr2 and N == N2 and nonempty.shape == (1, N)
    assert (B % block_b == 0 and N % block_n == 0 and tr % 128 == 0
            and M % 128 == 0), (B, R, tr, N, M)
    n_n = N // block_n

    return pl.pallas_call(
        functools.partial(_fused_impact_metered_kernel, n_n=n_n, n_r=R,
                          thresh=thresh),
        grid=(B // block_b, n_n),
        in_specs=[
            pl.BlockSpec((R, block_b, tr), lambda b, n: (0, b, 0)),
            pl.BlockSpec((R, tr, block_n), lambda b, n: (0, 0, n)),
            pl.BlockSpec((1, block_n), lambda b, n: (0, n)),
            pl.BlockSpec((block_n, M), lambda b, n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, M), lambda b, n: (b, 0)),
            pl.BlockSpec((block_b, METER_LANES), lambda b, n: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M), jnp.float32),
            jax.ShapeDtypeStruct((B, METER_LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, M), jnp.float32),
                        pltpu.VMEM((block_b, METER_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(drive, ccur, nonempty, wcur)


# -- bitplane-packed datapath -------------------------------------------------
#
# The clause crossbar is ternary at the device abstraction (HCS include /
# LCS exclude / dead), so streaming a float32 current per cell moves 16x
# more bytes than the information content.  The packed kernels consume
# the ``kernels.packing`` layout instead: 2-bit codes, four literal rows
# per byte, unpacked INSIDE the kernel — the f32 cell-current operand
# never exists in HBM.  Layouts (prepared by ``ops.fused_impact_packed``):
#
#   drive_p (R, 4, B, tr4)  f32   bitplane-major drive: plane j row q is
#                                 literal row 4q+j of shard r; pad rows 0
#   pbits   (R, tr4, N)     uint8 packed codes, columns flattened
#   levels  (1, 128)        f32   [i_lcs, i_hcs] in lanes 0/1 (VREG row)
#   ne / wcur / out               as in the unpacked kernel
#
# Column current = sum_j drive_p[r, j] @ dequant(plane_j), identical MACs
# to the unpacked kernel but ~4x fewer clause bytes through HBM/VMEM
# (uint8 codes vs f32 currents over 4x fewer rows).

_PLANES = 4
_CODE_BITS = 2
_CODE_MASK = 3


def _dequant_plane(codes32, j, i_lcs, i_hcs):
    plane = (codes32 >> (_CODE_BITS * j)) & _CODE_MASK
    return jnp.where(plane == 2, i_hcs,
                     jnp.where(plane == 1, i_lcs, 0.0)).astype(jnp.float32)


def _packed_column_current(drive_ref, pbits_ref, r, i_lcs, i_hcs):
    codes32 = pbits_ref[r].astype(jnp.int32)            # (tr4, bn)
    i_col = None
    for j in range(_PLANES):                            # static bitplane unroll
        cur = _dequant_plane(codes32, j, i_lcs, i_hcs)
        part = _dot_f32(drive_ref[r, j], cur)
        i_col = part if i_col is None else i_col + part
    return i_col


def _fused_impact_packed_kernel(drive_ref, pbits_ref, lvl_ref, ne_ref,
                                wcur_ref, out_ref, acc_ref, *, n_n: int,
                                n_r: int, thresh: float):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lvl = lvl_ref[...]
    i_lcs, i_hcs = lvl[0, 0], lvl[0, 1]
    bb = drive_ref.shape[2]
    bn = ne_ref.shape[1]
    fired = jnp.broadcast_to(ne_ref[...] != 0, (bb, bn))
    for r in range(n_r):                       # static unroll over row shards
        i_col = _packed_column_current(drive_ref, pbits_ref, r, i_lcs, i_hcs)
        fired = fired & (i_col < thresh)       # CSA + digital AND, in VMEM
    acc_ref[...] += _dot_f32(fired.astype(jnp.float32), wcur_ref[...])

    @pl.when(n == n_n - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]


def _packed_specs(R, block_b, tr4, block_n, M):
    return [
        pl.BlockSpec((R, _PLANES, block_b, tr4), lambda b, n: (0, 0, b, 0)),
        pl.BlockSpec((R, tr4, block_n), lambda b, n: (0, 0, n)),
        pl.BlockSpec((1, 128), lambda b, n: (0, 0)),
        pl.BlockSpec((1, block_n), lambda b, n: (0, n)),
        pl.BlockSpec((block_n, M), lambda b, n: (n, 0)),
    ]


def _check_packed_shapes(drive, pbits, levels, nonempty, wcur,
                         block_b, block_n):
    R, P, B, tr4 = drive.shape
    R2, tr42, N = pbits.shape
    N2, M = wcur.shape
    assert P == _PLANES and R == R2 and tr4 == tr42 and N == N2
    assert nonempty.shape == (1, N) and levels.shape == (1, 128)
    assert pbits.dtype == jnp.uint8
    assert (B % block_b == 0 and N % block_n == 0 and tr4 % 128 == 0
            and M % 128 == 0), (B, R, tr4, N, M)
    return R, B, N, M


@functools.partial(
    jax.jit, static_argnames=("thresh", "block_b", "block_n", "interpret"))
def fused_impact_packed(drive: Array, pbits: Array, levels: Array,
                        nonempty: Array, wcur: Array, *, thresh: float,
                        block_b: int = BLOCK_B, block_n: int = BLOCK_N,
                        interpret: bool = False) -> Array:
    """drive (R, 4, B, tr4) f32, pbits (R, tr4, N) uint8, levels (1, 128)
    f32, nonempty (1, N) int8, wcur (N, M) f32 -> class currents (B, M).

    Same alignment contract as ``fused_impact`` with ``tr4`` (the packed
    row count) in place of ``tr``; ``ops.fused_impact_packed`` pads
    arbitrary shapes.
    """
    R, B, N, M = _check_packed_shapes(drive, pbits, levels, nonempty, wcur,
                                      block_b, block_n)
    n_n = N // block_n
    tr4 = drive.shape[3]

    return pl.pallas_call(
        functools.partial(_fused_impact_packed_kernel, n_n=n_n, n_r=R,
                          thresh=thresh),
        grid=(B // block_b, n_n),
        in_specs=_packed_specs(R, block_b, tr4, block_n, M),
        out_specs=pl.BlockSpec((block_b, M), lambda b, n: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, M), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(drive, pbits, levels, nonempty, wcur)


# -- online TA feedback (arXiv:2408.09456 in-array updates) -------------------
#
# The feedback pass of the companion in-memory-learning paper reuses the
# clause-output datapath in reverse: the same (literal row x clause
# column) geometry that reads clause outputs accumulates, per TA cell,
# how often its literal was present/absent in the clauses selected for
# Type I/II feedback over one update batch.  Three matmuls on the
# doubled-batch feedback masks — identical contraction geometry to the
# clause read, so they share the MXU datapath and the VMEM residency
# pattern of the fused inference kernels:
#
#   present = lit^T     @ (sel & match & fired)       # Type Ia reward
#   absent  = (1-lit)^T @ (sel & match & fired)       # Type Ib penalty
#   inval   = (1-lit)^T @ (sel & ~match & fired)      # Type II inclusion
#   decay   = sum_b (sel & match & ~fired)            # Type Ib erasure
#   delta   = hi*present - lo*(absent + decay) + excl*inval
#
# The whole 2B contraction happens inside one block (like R staying whole
# in the inference kernels), so each (block_k, block_n) output tile is
# independent — no cross-chunk accumulator.  f32 MACs are exact for the
# integer mask counts involved (< 2**24).  Layouts (prepared by
# ``backends.PallasBackend.ta_feedback``):
#
#   litT          (K, B2)  f32   transposed doubled literals; pads 0
#   sel/match/fd  (B2, N)  f32   feedback masks; pads 0 (neutral: a padded
#                                row/column selects nothing)
#   hi/lo/excl   (K, N)    f32   per-TA draws + exclude mask; pads 0, so
#                                padded cells produce delta == 0
#   out          (K, N)    i32   TA state deltas


def _ta_feedback_kernel(litT_ref, sel_ref, match_ref, fired_ref, hi_ref,
                        lo_ref, excl_ref, out_ref):
    s = sel_ref[...]
    mt = match_ref[...]
    f = fired_ref[...]
    t1f = s * mt * f
    t1nf = s * mt * (1.0 - f)
    t2f = s * (1.0 - mt) * f
    litT = litT_ref[...]
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    present = dot(litT, t1f)
    absent = dot(1.0 - litT, t1f)
    inval = dot(1.0 - litT, t2f)
    decay = t1nf.sum(axis=0, keepdims=True)
    delta = (hi_ref[...] * present - lo_ref[...] * (absent + decay)
             + excl_ref[...] * inval)
    out_ref[...] = delta.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_k", "block_n", "interpret"))
def ta_feedback(litT: Array, sel: Array, match: Array, fired2: Array,
                hi: Array, lo: Array, excl: Array, *, block_k: int = 128,
                block_n: int = 128, interpret: bool = False) -> Array:
    """litT (K, B2) f32, sel/match/fired2 (B2, N) f32, hi/lo/excl (K, N)
    f32 -> ta_delta (K, N) int32.

    K % block_k == 0, N % block_n == 0, B2 % 128 == 0 required
    (``backends.PallasBackend.ta_feedback`` pads arbitrary shapes).
    """
    K, B2 = litT.shape
    B2b, N = sel.shape
    assert B2 == B2b and match.shape == sel.shape == fired2.shape
    assert hi.shape == lo.shape == excl.shape == (K, N)
    assert (K % block_k == 0 and N % block_n == 0 and B2 % 128 == 0), (
        K, B2, N)

    return pl.pallas_call(
        _ta_feedback_kernel,
        grid=(K // block_k, N // block_n),
        in_specs=[
            pl.BlockSpec((block_k, B2), lambda k, n: (k, 0)),
            pl.BlockSpec((B2, block_n), lambda k, n: (0, n)),
            pl.BlockSpec((B2, block_n), lambda k, n: (0, n)),
            pl.BlockSpec((B2, block_n), lambda k, n: (0, n)),
            pl.BlockSpec((block_k, block_n), lambda k, n: (k, n)),
            pl.BlockSpec((block_k, block_n), lambda k, n: (k, n)),
            pl.BlockSpec((block_k, block_n), lambda k, n: (k, n)),
        ],
        out_specs=pl.BlockSpec((block_k, block_n), lambda k, n: (k, n)),
        out_shape=jax.ShapeDtypeStruct((K, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(litT, sel, match, fired2, hi, lo, excl)


def _fused_impact_packed_metered_kernel(drive_ref, pbits_ref, lvl_ref,
                                        ne_ref, wcur_ref, out_ref, meter_ref,
                                        acc_ref, macc_ref, *, n_n: int,
                                        n_r: int, thresh: float):
    """Packed datapath + the in-kernel energy meter: the meters bill the
    QUANTIZED column currents — the currents the packed cells actually
    draw — keeping the energy story consistent with the datapath."""
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        macc_ref[...] = jnp.zeros_like(macc_ref)

    lvl = lvl_ref[...]
    i_lcs, i_hcs = lvl[0, 0], lvl[0, 1]
    bb = drive_ref.shape[2]
    bn = ne_ref.shape[1]
    fired = jnp.broadcast_to(ne_ref[...] != 0, (bb, bn))
    i_chunk = jnp.zeros((bb, 1), jnp.float32)
    for r in range(n_r):                       # static unroll over row shards
        i_col = _packed_column_current(drive_ref, pbits_ref, r, i_lcs, i_hcs)
        fired = fired & (i_col < thresh)       # CSA + digital AND, in VMEM
        i_chunk += i_col.sum(axis=1, keepdims=True)
    macc_ref[...] += i_chunk
    acc_ref[...] += _dot_f32(fired.astype(jnp.float32), wcur_ref[...])

    @pl.when(n == n_n - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, macc_ref.shape, 1)
        i_class = acc_ref[...].sum(axis=1, keepdims=True)
        meter_ref[...] = jnp.where(
            lane == METER_LANE_CLAUSE, macc_ref[...],
            jnp.where(lane == METER_LANE_CLASS, i_class, 0.0))


@functools.partial(
    jax.jit, static_argnames=("thresh", "block_b", "block_n", "interpret"))
def fused_impact_packed_metered(drive: Array, pbits: Array, levels: Array,
                                nonempty: Array, wcur: Array, *,
                                thresh: float, block_b: int = BLOCK_B,
                                block_n: int = BLOCK_N,
                                interpret: bool = False,
                                ) -> tuple[Array, Array]:
    """Metered variant of ``fused_impact_packed``: returns
    ``(class currents (B, M), meters (B, METER_LANES))`` with the same
    lane layout as ``fused_impact_metered``.
    """
    R, B, N, M = _check_packed_shapes(drive, pbits, levels, nonempty, wcur,
                                      block_b, block_n)
    n_n = N // block_n
    tr4 = drive.shape[3]

    return pl.pallas_call(
        functools.partial(_fused_impact_packed_metered_kernel, n_n=n_n,
                          n_r=R, thresh=thresh),
        grid=(B // block_b, n_n),
        in_specs=_packed_specs(R, block_b, tr4, block_n, M),
        out_specs=[
            pl.BlockSpec((block_b, M), lambda b, n: (b, 0)),
            pl.BlockSpec((block_b, METER_LANES), lambda b, n: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M), jnp.float32),
            jax.ShapeDtypeStruct((B, METER_LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, M), jnp.float32),
                        pltpu.VMEM((block_b, METER_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(drive, pbits, levels, nonempty, wcur)
