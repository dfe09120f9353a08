"""Pallas TPU kernel: fused ANALOG IMPACT inference (both crossbars).

Digital twin of the paper's two-crossbar datapath with the Fig. 14 modular
scaling baked into the tiling.  Where ``fused_cotm`` fuses the *logical*
CoTM (include mask + integer weights), this kernel fuses the *physical*
simulation — per-cell Y-Flash read currents, the CSA threshold, and the
digital periphery — in one VMEM residency:

    grid (batch block b, clause-column block n, literal row-shard r):
        I_col    = drive[r, b] @ I_cell[r][:, n]        # Kirchhoff column sum
        fired[n] = fired[n] & (I_col < I_CSA_THRESHOLD)  # CSA + digital AND
                   (shard 0 starts from nonempty[n])    # (Fig. 14)
        after the last shard:
            scores[b] += fired[n] @ I_class[n, :]       # class column currents

The Boolean clause block ``fired`` never leaves VMEM: a (block_b, block_n)
scratch carries it across the row-shard axis, so the (B, n_pad) clause
matrix — the largest intermediate of the un-fused path — is never
materialized in HBM.  The class crossbar's S row-shards are flattened onto
the clause-column axis, so the per-shard ADC + digital add is subsumed by
the column-block accumulation (exact: the class read is linear in the
drive).

Layouts (prepared by ``PallasBackend._fused_impact_operands``):
  drive   (R, B, tr)      f32   1 - literal, row-shard major; padding rows 0
  ccur    (R, C, tr, tc)  f32   clause-cell read currents: the programmed
                                grid itself, read where it lies (see
                                ``column_tiling`` for narrow tiles)
  ne      (C, 1, tc)      int8  digital empty-clause mask, tile-major
  wcur    (C, tc, M)      f32   class-cell read currents of each clause
                                column, tile-major
  out     (B, M)          f32   class column currents (argmax = prediction)

The column axis walks the clause grid's C*tc columns and no further:
column block n is block ``n % (tc // block_n)`` of clause tile
``n // (tc // block_n)``, so a grid of whole-block tiles is never
transposed or padded for the kernel (``column_tiling``).  Class rows
past the clause grid (a class crossbar wider than C*tc) are driven by no
clause and are never read.

Each grid step holds ONE row-shard's (block_b, tr) drive and (tr, block_n)
currents, so VMEM per step is the same for any R and any C: a CoTM of any
literal count runs on the paper's 2048-row tiles.  The shard axis is the
innermost: at R=1 every block index but ccur's stays put across the
column axis, so the pipeline fetches the drive once per batch block; at
R>1 it streams the drive once per column block (R*B*tr*4 bytes, n_n
times) and the currents once per batch block.

``fused_impact_metered`` is the same datapath with in-kernel energy
metering: the paper (and IMBUE, arXiv:2305.12914) measure read energy as
``E = V_R * I_col * t_read`` summed over the very column currents the
inference already computes, so the metered kernel folds each step's
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


def column_tiling(C: int, tc: int,
                  block_n: int = BLOCK_N) -> tuple[int, int, int]:
    """How the fused kernel walks a grid of C clause tiles of ``tc``
    columns: -> (tiles, tile width, column block width).  The block is
    ``block_n`` wide, or 128 where the grid has at most 128 columns.
    Tiles a whole number of blocks wide are read in place; others (test
    sized grids) are laid end to end as one tile padded to whole blocks.
    Either way the class read sums the clause columns in the same
    block-wide chunks, whatever the tile split."""
    n = C * tc
    bn = min(block_n, max(128, -(-n // 128) * 128))
    if tc % bn == 0:
        return C, tc, bn
    return 1, -(-n // bn) * bn, bn


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


#: Lane layout of the metered kernel's (B, METER_LANES) meter output:
#: lane 0 carries the summed clause-crossbar column currents, lane 1 the
#: summed class-crossbar column currents.  128 lanes (one VREG row) keep
#: the output MXU/VPU tile-aligned; the wrapper slices the two live lanes.
METER_LANE_CLAUSE = 0
METER_LANE_CLASS = 1
METER_LANES = 128


def _fused_impact_kernel(drive_ref, ccur_ref, ne_ref, wcur_ref, *refs,
                         n_n: int, n_r: int, thresh: float, metered: bool):
    """One grid step (b, n, r): row-shard r's column currents for column
    block n, its CSA decisions AND-ed into ``fired_ref`` (the block's 0/1
    clause bits, carried in VMEM across the shard axis; shard 0 starts
    from the digital ``nonempty`` mask, so empty and padded columns never
    fire), and after the last shard the class read of the block.

    ``metered`` adds the in-kernel energy meter: each step's clause
    column currents are folded into a second VMEM accumulator
    (``macc_ref``) the moment the CSA consumes them.  The class-current
    meter needs no extra accumulation at all: the class read is linear,
    so the summed class column current is exactly the row-sum of the
    score accumulator — computed once in the epilogue.
    """
    if metered:
        out_ref, meter_ref, acc_ref, fired_ref, macc_ref = refs
    else:
        out_ref, acc_ref, fired_ref = refs
    n, r = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(n == 0, r == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if metered:
            macc_ref[...] = jnp.zeros_like(macc_ref)

    i_col = _dot_f32(drive_ref[0], ccur_ref[0, 0])   # Kirchhoff column sums

    @pl.when(r == 0)
    def _first():
        ne = jnp.broadcast_to(ne_ref[0] != 0, i_col.shape)
        fired_ref[...] = (ne & (i_col < thresh)).astype(jnp.float32)

    @pl.when(r > 0)
    def _and():                                   # digital AND (Fig. 14)
        fired_ref[...] = jnp.where(i_col < thresh, fired_ref[...], 0.0)

    if metered:
        # Every meter lane accumulates the same per-lane clause current
        # (a plain VPU broadcast-add — no per-step lane select); the
        # epilogue picks METER_LANE_CLAUSE.  Padded batch rows (0 V drive)
        # and padded columns (0 A cells) add exactly zero.
        macc_ref[...] += i_col.sum(axis=1, keepdims=True)

    @pl.when(r == n_r - 1)
    def _class():
        acc_ref[...] += _dot_f32(fired_ref[...], wcur_ref[0])

    @pl.when(jnp.logical_and(n == n_n - 1, r == n_r - 1))
    def _epilogue():
        out_ref[...] = acc_ref[...]
        if metered:
            lane = jax.lax.broadcasted_iota(jnp.int32, macc_ref.shape, 1)
            i_class = acc_ref[...].sum(axis=1, keepdims=True)
            meter_ref[...] = jnp.where(
                lane == METER_LANE_CLAUSE, macc_ref[...],
                jnp.where(lane == METER_LANE_CLASS, i_class, 0.0))


def _shard_grid_call(drive, ccur, nonempty, wcur, *, thresh, block_b,
                     block_n, interpret, metered):
    """The ``pallas_call`` of both unpacked kernels on the (batch block,
    column block, literal row-shard) grid; -> a list of outputs.  Column
    block n reads block ``n % nb`` of clause tile ``n // nb`` in place."""
    R, B, tr = drive.shape
    R2, C, tr2, tc = ccur.shape
    C2, tc2, M = wcur.shape
    assert (R == R2 and tr == tr2 and (C, tc) == (C2, tc2)
            and nonempty.shape == (C, 1, tc)), (drive.shape, ccur.shape)
    assert (B % block_b == 0 and tc % block_n == 0
            and M % 128 == 0), (B, R, tr, C, tc, M)
    nb = tc // block_n
    n_n = C * nb
    lanes = [M, METER_LANES] if metered else [M]
    scratch = [pltpu.VMEM((block_b, M), jnp.float32),        # class currents
               pltpu.VMEM((block_b, block_n), jnp.float32)]  # AND-ed bits
    if metered:                                              # clause meter
        scratch.append(pltpu.VMEM((block_b, METER_LANES), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fused_impact_kernel, n_n=n_n, n_r=R,
                          thresh=thresh, metered=metered),
        grid=(B // block_b, n_n, R),
        in_specs=[
            pl.BlockSpec((1, block_b, tr), lambda b, n, r: (r, b, 0)),
            pl.BlockSpec((1, 1, tr, block_n),
                         lambda b, n, r: (r, n // nb, 0, n % nb)),
            pl.BlockSpec((1, 1, block_n),
                         lambda b, n, r: (n // nb, 0, n % nb)),
            pl.BlockSpec((1, block_n, M),
                         lambda b, n, r: (n // nb, n % nb, 0)),
        ],
        out_specs=[pl.BlockSpec((block_b, m), lambda b, n, r: (b, 0))
                   for m in lanes],
        out_shape=[jax.ShapeDtypeStruct((B, m), jnp.float32)
                   for m in lanes],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="fused_impact_metered" if metered else "fused_impact",
    )(drive, ccur, nonempty, wcur)


@functools.partial(
    jax.jit, static_argnames=("thresh", "block_b", "block_n", "interpret"))
def fused_impact(drive: Array, ccur: Array, nonempty: Array, wcur: Array, *,
                 thresh: float, block_b: int = BLOCK_B,
                 block_n: int = BLOCK_N, interpret: bool = False) -> Array:
    """drive (R, B, tr) f32, ccur (R, C, tr, tc) f32, nonempty (C, 1, tc)
    int8, wcur (C, tc, M) f32 -> class currents (B, M) f32.

    B % block_b == 0, tc % block_n == 0 and M % 128 == 0 required
    (``PallasBackend`` pads the batch and the class lanes, and lays the
    grid out by ``column_tiling``).
    """
    return _shard_grid_call(drive, ccur, nonempty, wcur, thresh=thresh,
                            block_b=block_b, block_n=block_n,
                            interpret=interpret, metered=False)[0]


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
    out, meters = _shard_grid_call(drive, ccur, nonempty, wcur,
                                   thresh=thresh, block_b=block_b,
                                   block_n=block_n, interpret=interpret,
                                   metered=True)
    return out, meters


# -- bitplane-packed datapath -------------------------------------------------
#
# The clause crossbar is ternary at the device abstraction (HCS include /
# LCS exclude / dead), so streaming a float32 current per cell moves 16x
# more bytes than the information content.  The packed kernels consume
# the ``kernels.packing`` layout instead: 2-bit codes, four literal rows
# per byte, unpacked INSIDE the kernel — the f32 cell-current operand
# never exists in HBM.  Layouts (prepared by
# ``PallasBackend._fused_impact_packed_operands``):
#
#   drive_p (R, 4, B, tr4)  f32   bitplane-major drive: plane j row q is
#                                 literal row 4q+j of shard r; pad rows 0
#   pbits   (R, tr4, N)     uint8 packed codes, columns flattened
#   levels  (1, 128)        f32   [i_lcs, i_hcs] in lanes 0/1 (VREG row)
#   ne      (1, N)          int8  digital empty-clause mask, flattened
#   wcur    (N, M)          f32   class-cell read currents, S shards flattened
#   out     (B, M)          f32   as in the unpacked kernel
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
    row count) in place of ``tr``; ``PallasBackend`` pads arbitrary
    shapes.
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
# in the packed inference kernels), so each (block_k, block_n) output
# tile is independent — no cross-chunk accumulator.  f32 MACs are exact for the
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
