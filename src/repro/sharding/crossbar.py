"""Distributed lowering of the fused analog IMPACT crossbar.

The paper's Fig. 14 modular scaling IS a ``psum`` decomposition (see
``rules.py``): partial clauses from the R literal row-shards are combined
by a digital AND, and partial class currents from the S class row-shards
are digitised per shard (ADC) and summed digitally.  This module makes
that correspondence executable: a ``shard_map`` over the ``model`` mesh
axis places clause row-shards and/or class row-shards on each device, the
batch is sharded over the data axes (``("pod", "data")`` when present),
and

* the digital AND becomes ``psum`` of per-device partial CSA violation
  bits (a column fires iff NO shard on ANY device sees current above the
  CSA threshold);
* the per-shard ADC + digital adder tree becomes ``psum`` of per-device
  partial class currents (exact — the class read is linear in the drive).

**Asymmetric plans.**  R and S need not both divide the model axis: when
only one does, that operand shards and the other crossbar is REPLICATED —
every device evaluates the replicated stage in full (its inputs are fully
known on-device after the other stage's psum), so no combine is needed
for it.  ``shard_plan`` picks the placement; ``(True, True)`` is the
PR-3 fully-sharded grid, ``(True, False)`` / ``(False, True)`` are the
R-only / S-only asymmetric plans, and ``None`` means no usable plan
(fall back to the single-device kernel — correctness never depends on
the mesh).

Each device runs the existing Pallas ``crossbar_mvm`` kernel on its local
shards (``impl="xla"`` swaps in the einsum oracle for A/B parity runs),
so the single-device kernels and the distributed lowering share one
numerical core.  ``kernels.ops.fused_impact`` routes here when a mesh is
passed and a plan exists; the compiled-session runtime
(``impact.runtime``) resolves the plan ONCE at ``compile()`` time from
``RuntimeSpec.topology`` instead of re-deriving it per call.

**Energy metering.**  ``meter=True`` psums the per-lane summed column
currents of both crossbars across the model axis — the partial stages
each device materializes anyway, billed exactly once (a replicated
operand's currents are already the full quantity on every device, so
its psum is skipped).  This one lowering backs BOTH metering modes of a
sharded ``RuntimeSpec`` (``"staged"`` and ``"fused"``): on a mesh the
currents exist per device regardless, so there is no staged-vs-fused
distinction to make — the in-kernel fused meter is a single-device
specialization, pinned equal to this path by the parity suites.

Parity contract (enforced in ``tests/test_crossbar_sharding.py``): CSA
bits and argmax predictions are EXACTLY equal to the single-device kernel
and the einsum oracle on ideal devices; raw class-current scores are
float sums whose association order changes under ``psum``, so they agree
to tight rtol.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import ops, ref
from .rules import crossbar_rules

Array = jax.Array

#: Topology shard modes accepted by ``shard_plan`` / ``Topology.shard``.
SHARD_MODES = ("auto", "both", "r", "s", "none")


def model_size(mesh) -> int:
    """Size of the ``model`` axis (1 when absent or no mesh)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("model", 1))


def data_axes(mesh) -> tuple[str, ...]:
    """The batch axes of ``mesh`` actually present, in rule-table order."""
    if mesh is None:
        return ()
    return tuple(a for a in crossbar_rules(mesh)["batch"]
                 if a in mesh.shape)


def shard_plan(mesh, n_row_shards: int, n_class_shards: int,
               mode: str = "auto") -> tuple[bool, bool] | None:
    """Resolve the (shard_r, shard_s) placement of an (R, S) grid on
    ``mesh``'s model axis, or ``None`` when nothing can shard.

    ``mode``: ``"auto"`` shards whichever of R / S divides the axis
    (both when both do); ``"both"`` / ``"r"`` / ``"s"`` demand that
    placement and raise ``ValueError`` when the shard count doesn't
    divide the axis (compile-time validation for explicit topologies);
    ``"none"`` always returns ``None`` (force single-device).
    """
    if mode not in SHARD_MODES:
        raise ValueError(f"shard mode must be one of {SHARD_MODES}, "
                         f"got {mode!r}")
    m = model_size(mesh)
    if mode == "none":
        return None
    if m <= 1:
        if mode == "auto":
            return None
        raise ValueError(
            f"shard mode {mode!r} demands a sharded placement but the "
            f"mesh has no model axis larger than 1 (model={m})")
    r_ok = n_row_shards % m == 0
    s_ok = n_class_shards % m == 0
    if mode == "auto":
        return (r_ok, s_ok) if (r_ok or s_ok) else None
    want_r = mode in ("both", "r")
    want_s = mode in ("both", "s")
    if (want_r and not r_ok) or (want_s and not s_ok):
        raise ValueError(
            f"shard mode {mode!r} needs "
            f"{'R=' + str(n_row_shards) if want_r and not r_ok else ''}"
            f"{' and ' if want_r and not r_ok and want_s and not s_ok else ''}"
            f"{'S=' + str(n_class_shards) if want_s and not s_ok else ''} "
            f"to divide the model axis ({m} devices)")
    return (want_r, want_s)


def shardable(mesh, n_row_shards: int, n_class_shards: int) -> bool:
    """True when ANY shard plan exists for the (R, S) grid on ``mesh`` —
    fully sharded or asymmetric (one operand replicated)."""
    return shard_plan(mesh, n_row_shards, n_class_shards) is not None


def _local_column_currents(drive_loc: Array, ci_loc: Array, *, impl: str,
                           interpret: bool | None) -> Array:
    """Per-shard clause-crossbar column currents on ONE device.

    drive_loc (B, R_loc, tr) f32; ci_loc (R_loc, C, tr, tc) f32 cell read
    currents -> (B, R_loc, C*tc) f32.  Runs the same Pallas ``crossbar_mvm``
    kernel (or einsum oracle) per local shard as the single-device staged
    path, so per-shard currents are bit-identical across lowerings.
    """
    R_loc, C, tr, tc = ci_loc.shape
    cols = []
    for r in range(R_loc):                      # static local-shard unroll
        cur = ci_loc[r].transpose(1, 0, 2).reshape(tr, C * tc)
        cols.append(ops.crossbar_mvm(drive_loc[:, r], cur, v_read=1.0,
                                     cutoff=0.0, impl=impl,
                                     interpret=interpret))
    return jnp.stack(cols, axis=1)


def _local_column_currents_packed(drive_loc: Array, pb_loc: Array,
                                  lv_loc: Array, *, impl: str,
                                  interpret: bool | None) -> Array:
    """Packed-operand twin of ``_local_column_currents``.

    drive_loc (B, R_loc, 4, tr4) bitplane-major drive; pb_loc
    (R_loc, C, tr4, tc) uint8 packed codes; lv_loc (2,) dequant levels
    -> (B, R_loc, C*tc) f32.  Each bitplane is dequantized on-device and
    driven through the same ``crossbar_mvm`` kernel, so the psum
    structure above this function is untouched by packing.
    """
    R_loc, C, tr4, tc = pb_loc.shape
    cols = []
    for r in range(R_loc):                      # static local-shard unroll
        codes = pb_loc[r].transpose(1, 0, 2).reshape(tr4, C * tc)
        codes = codes.astype(jnp.int32)
        i_col = None
        for j in range(4):                      # static bitplane unroll
            plane = (codes >> (2 * j)) & 3
            cur = jnp.where(plane == 2, lv_loc[1],
                            jnp.where(plane == 1, lv_loc[0], 0.0))
            part = ops.crossbar_mvm(drive_loc[:, r, j],
                                    cur.astype(jnp.float32), v_read=1.0,
                                    cutoff=0.0, impl=impl,
                                    interpret=interpret)
            i_col = part if i_col is None else i_col + part
        cols.append(i_col)
    return jnp.stack(cols, axis=1)


def fused_impact_shmap(literals: Array, clause_i: Array | None,
                       nonempty: Array, class_i: Array, *, thresh: float,
                       mesh, impl: str = "pallas",
                       interpret: bool | None = None,
                       valid: Array | None = None, meter: bool = False,
                       shard_r: bool = True, shard_s: bool = True,
                       packed=None, packed_tr: int | None = None,
                       lane_cols: Array | None = None):
    """Sharded analog inference: literals (B, K) -> class currents (B, M).

    Same contract as ``ops.fused_impact`` (which is the normal entry
    point — it calls here when ``shard_plan`` finds a placement).
    ``(shard_r, shard_s)`` is that placement: a False entry replicates
    the corresponding crossbar on every device and skips its psum (the
    replicated stage computes identical values everywhere).  With
    ``meter=True`` additionally returns per-lane summed clause / class
    crossbar currents (B,) f32 — the quantities
    ``impact.energy.per_lane_read_energy`` converts to joules — computed
    with the same valid-lane masking as the single-device staged path,
    so per-request bills sum to the batch meter under every plan.

    ``packed`` (a ``kernels.packing.PackedClause``) swaps the clause
    operand for the 2-bit bitplane layout: the codes shard over the
    model axis exactly like the f32 currents (same axis-0 placement, so
    the packed operands ride the same psum lowering) and each device
    dequantizes only its local shards.  ``packed_tr`` is the unpacked
    per-shard row count; ``clause_i`` must be None in packed mode.

    ``lane_cols`` (B, C*tc) bool is the co-residency tenant mask (see
    ``kernels.ref.coresident_lane_mask``): ANDed into the fired bits
    AFTER the cross-device violation psum and BEFORE the class drive,
    so a lane's spuriously-fired foreign columns (0 A < CSA threshold)
    never reach foreign class rows.  It shards over the batch axes like
    ``valid`` and is replicated over ``model``, which composes with all
    four shard plans unchanged — the clause psum is mask-independent and
    the class psum sees already-masked drives.
    """
    B, K = literals.shape
    if packed is not None:
        assert clause_i is None and packed_tr is not None
        R, C, tr4, tc = packed.bits.shape
        tr = packed_tr
    else:
        R, C, tr, tc = clause_i.shape
    S, sr, M = class_i.shape
    n = C * tc
    m = model_size(mesh)
    assert nonempty.shape == (n,), (nonempty.shape, n)
    assert shard_r or shard_s, "no-op plan: use the single-device kernel"
    assert not shard_r or R % m == 0, (R, m)
    assert not shard_s or S % m == 0, (S, m)

    dp = data_axes(mesh)
    n_data = math.prod(mesh.shape[a] for a in dp) if dp else 1
    # Batch shards over the data axes only when it divides them; an
    # indivisible batch replicates (every data shard computes the full
    # batch) rather than failing — the model axis still shards.
    bspec = dp if (dp and B % n_data == 0) else None

    lit = ref.pad_to(literals.astype(jnp.float32), R * tr, axis=1, value=1)
    drive = (1.0 - lit).reshape(B, R, tr)       # padding rows float ('Z')
    rspec = "model" if shard_r else None
    if packed is not None:
        # Bitplane-major drive (B, R, 4, tr4): plane j row q drives
        # literal row 4q+j of shard r; rows past tr pad with 0 V.
        drive = ref.pad_to(drive, 4 * tr4, axis=2, value=0.0)
        drive = drive.reshape(B, R, tr4, 4).transpose(0, 1, 3, 2)
        clause_op = packed.bits
        levels = packed.levels.astype(jnp.float32)
        drive_spec = P(bspec, rspec, None, None)
    else:
        clause_op = clause_i.astype(jnp.float32)
        levels = jnp.zeros((2,), jnp.float32)   # unused, keeps one wiring
        drive_spec = P(bspec, rspec, None)
    ne = nonempty.astype(jnp.int8)
    vmask = (jnp.ones((B,), bool) if valid is None
             else valid.astype(bool))
    lcols = (jnp.ones((B, n), bool) if lane_cols is None
             else lane_cols.astype(bool))        # all-ones keeps one wiring

    def local_fn(drive_loc, ci_loc, ne_loc, wi_loc, valid_loc, lv_loc,
                 lc_loc):
        # drive_loc (B_loc, R_loc, tr) — or (B_loc, R_loc, 4, tr4)
        # packed; ci_loc (R_loc, C, tr, tc) f32 — or (R_loc, C, tr4, tc)
        # uint8 packed codes with lv_loc the dequant levels; wi_loc
        # (S_loc, sr, M); R_loc/S_loc are full R/S for a replicated
        # operand; everything else replicated over "model".
        if packed is not None:
            i_col = _local_column_currents_packed(drive_loc, ci_loc, lv_loc,
                                                  impl=impl,
                                                  interpret=interpret)
        else:
            i_col = _local_column_currents(drive_loc, ci_loc, impl=impl,
                                           interpret=interpret)
        # Partial CSA bits: count of local shards whose column current
        # trips the sense amp; with R sharded, the cross-device psum is
        # Fig. 14's digital AND (a clause fires iff the total violation
        # count is zero); with R replicated the local count is already
        # total, identical on every device.
        viol = (i_col >= thresh).astype(jnp.int32).sum(axis=1)
        if shard_r:
            viol = jax.lax.psum(viol, "model")
        fired = jnp.logical_and(viol == 0, ne_loc.astype(bool)[None, :])
        fired = jnp.logical_and(fired, valid_loc[:, None])
        fired = jnp.logical_and(fired, lc_loc)  # co-residency tenant mask

        # Class stage: with S sharded, this device drives only its local
        # S_loc row-shards with the matching slice of clause bits and
        # the per-shard ADC + digital add is the psum below; with S
        # replicated it drives the whole class crossbar (fired is fully
        # known on-device) and no combine is needed.
        S_loc = wi_loc.shape[0]
        drv = ref.pad_to(fired.astype(jnp.float32), S * sr, axis=1)
        drv = drv[:, :S * sr].reshape(-1, S, sr)
        if shard_s:
            lo = jax.lax.axis_index("model") * S_loc
            mine = jax.lax.dynamic_slice_in_dim(drv, lo, S_loc, axis=1)
        else:
            mine = drv
        i_cls = jnp.stack(
            [ops.crossbar_mvm(mine[:, s], wi_loc[s], v_read=1.0, cutoff=0.0,
                              impl=impl, interpret=interpret)
             for s in range(S_loc)], axis=1)    # (B_loc, S_loc, M)
        scores = i_cls.sum(axis=1)
        if shard_s:
            scores = jax.lax.psum(scores, "model")
        if not meter:
            return (scores,)
        # Per-lane meters: psum exactly the partial stages — a
        # replicated stage's currents are already the full quantity on
        # every device, so psumming them would bill m-fold.
        i_col = i_col * valid_loc[:, None, None].astype(i_col.dtype)
        i_cl_lane = i_col.sum(axis=(1, 2))
        if shard_r:
            i_cl_lane = jax.lax.psum(i_cl_lane, "model")
        i_cs_lane = i_cls.sum(axis=(1, 2))
        if shard_s:
            i_cs_lane = jax.lax.psum(i_cs_lane, "model")
        return scores, i_cl_lane, i_cs_lane

    out_specs = ((P(bspec, None),) if not meter
                 else (P(bspec, None), P(bspec), P(bspec)))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(drive_spec,
                  P(rspec, None, None, None),
                  P(None),
                  P("model" if shard_s else None, None, None),
                  P(bspec),
                  P(None),
                  P(bspec, None)),
        out_specs=out_specs, check_vma=False)
    out = fn(drive, clause_op, ne, class_i.astype(jnp.float32), vmask,
             levels, lcols)
    return out[0] if not meter else out
