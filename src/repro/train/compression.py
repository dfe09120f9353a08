"""Model + gradient compression.

Two independent compressors live here:

* **Clause pruning** (:func:`prune_clauses`): a post-training pass over a
  programmed ``IMPACTSystem`` that (a) retires clause columns that never
  fire on a calibration batch — their cells stop drawing leakage current
  every sweep — and (b) merges duplicate clause columns (identical at
  the ternary device abstraction) by summing their class-crossbar rows,
  exact for ideal systems because the class read is linear in the drive.
  The returned :class:`PruneStats` re-anchors the paper's Table 4 energy
  per *effective* clause.  Pairs with ``RuntimeSpec(packing="2bit")``:
  pruning shrinks the live column population, packing shrinks the bytes
  per column.

* **Gradient compression** (below): int8 ring all-reduce with error
  feedback for data-parallel training traffic.

For data-parallel traffic on slow inter-pod links, gradients are exchanged
as int8 with a shared per-tensor scale.  The all-reduce is decomposed so
the WIRE format is int8 in both phases (the accumulation happens locally
in int32):

    1. shared scale     = pmax(max|v|) / 127
    2. reduce-scatter   : all_to_all of the int8 shards; each device sums
                          its shard in int32 and REQUANTIZES to int8
                          (second scale = pmax of shard maxima)
    3. all-gather       : int8 shards gathered, dequantized once

Error feedback (Seide et al. / 1-bit SGD lineage): each device carries the
quantization residual ``e`` and adds it to the next step's gradient, so
the compression bias cancels over steps instead of accumulating — the
property test in ``tests/test_compression.py`` checks exactly this.

Wire bytes: 1/4 of f32 (plus two scalar scales), at <1% relative error per
step on typical gradient distributions.  Used by the shard_map-based DP
trainer in ``examples/train_lm.py --compress-grads``; the GSPMD paths keep
XLA's native collectives.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# -- clause pruning ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PruneStats:
    """What a :func:`prune_clauses` pass removed, and the re-anchored
    Table 4 energy figure.

    ``n_effective`` is the count of clause columns that still draw
    meaningful current after pruning — the denominator the paper's
    per-clause energy story should use once dead columns stop burning
    leakage every sweep.  ``energy_per_effective_clause_j`` is the
    pruned system's read energy per calibration datapoint per effective
    clause (``impact.energy.energy_per_effective_clause``).
    """
    n_clauses: int
    n_effective: int
    n_never_fired: int
    n_duplicates: int
    calibration_batch: int
    energy_per_effective_clause_j: float


def _g_from_current(i: np.ndarray, *, v_read: float, nonlin: float,
                    cutoff: float) -> np.ndarray:
    """Exact inverse of ``yflash.read_current`` (piecewise linear): the
    conductance that reads back as current ``i``."""
    return np.where(i >= cutoff * v_read, i / v_read, i / (v_read * nonlin))


def prune_clauses(system, literals, *, merge_duplicates: bool = True):
    """Prune a programmed ``IMPACTSystem`` against a calibration batch.

    Two reductions, both physical erase operations on the clause
    crossbar (a retired column's cells go to 0 S and its ``nonempty``
    bit clears, so it neither fires nor draws leakage):

    1. **Never-fired columns**: clauses that fire on no calibration
       datapoint.  Exact on the calibration batch (a clause that never
       fires contributes nothing to any class current there); on other
       inputs this is the usual calibration-pruning bet.
    2. **Duplicate columns** (``merge_duplicates=True``): columns with
       identical ternary code patterns (``kernels.packing``
       classification) compute the same clause function, so all but the
       first are erased and their class-crossbar rows are summed into
       the survivor's row — EXACT for ideal (variability-free) systems
       because the class read is linear in the drive; under device
       variability the merged column's quantized current is the class
       mean (same contract as ``packing="2bit"``).

    Returns ``(pruned_system, PruneStats)``.  The pruned system is a new
    ``IMPACTSystem`` (same geometry — tiles are not re-packed, columns
    are erased in place) whose ``encode_stats`` carries the pruning
    record; compile it with ``RuntimeSpec(packing="2bit")`` to stack
    both compressions.
    """
    from ..impact import yflash
    from ..kernels import packing, ref

    lits = jnp.asarray(literals)
    B = int(lits.shape[0])
    R, C, tr, tc = system.clause_i.shape
    S, sr, M = system.class_i.shape
    n_pad = C * tc
    nonempty = np.asarray(system._nonempty_eff()).astype(bool)

    fired, _ = ref.impact_clause_bits_ref(
        lits, system.clause_i, system._nonempty_eff(),
        thresh=yflash.I_CSA_THRESHOLD)
    ever = np.asarray(fired).any(axis=0)
    alive = nonempty & ever
    n_never = int((nonempty & ~ever).sum())

    clause_i = np.asarray(system.clause_i, np.float32).copy()
    clause_g = np.asarray(system.clause_g, np.float32).copy()
    class_i = np.asarray(system.class_i, np.float32).copy()
    class_g = np.asarray(system.class_g, np.float32).copy()
    # Flat views: clause column j lives at tile (j // tc, j % tc) and
    # class-crossbar flat row j (n_clauses <= S*sr by construction).
    cls_i_flat = class_i.reshape(S * sr, M)

    n_dup = 0
    if merge_duplicates:
        flat_ci = clause_i.transpose(0, 2, 1, 3).reshape(R * tr, n_pad)
        codes = np.asarray(packing.classify_currents(jnp.asarray(flat_ci)))
        keep_of: dict[bytes, int] = {}
        for j in np.flatnonzero(alive):
            key = codes[:, j].tobytes()
            keep = keep_of.setdefault(key, int(j))
            if keep != j:
                cls_i_flat[keep] += cls_i_flat[j]
                cls_i_flat[j] = 0.0
                alive[j] = False
                n_dup += 1
        class_g = _g_from_current(
            class_i, v_read=yflash.V_READ, nonlin=yflash.LCS_NONLINEARITY,
            cutoff=yflash.G_NONLIN_CUTOFF).astype(np.float32)

    # Erase every retired column: cells to 0 S / 0 A, nonempty cleared.
    dead = nonempty & ~alive
    col_mask = (~dead).reshape(C, tc)[None, :, None, :]
    clause_i *= col_mask
    clause_g *= col_mask
    new_nonempty = np.asarray(system.nonempty).astype(bool) & ~dead

    pruned = dataclasses.replace(
        system,
        clause_g=jnp.asarray(clause_g), clause_i=jnp.asarray(clause_i),
        class_g=jnp.asarray(class_g), class_i=jnp.asarray(class_i),
        nonempty=jnp.asarray(new_nonempty))

    n_eff = int(alive.sum())
    from ..impact import energy as energy_mod
    _, i_cl, i_cs = ref.fused_impact_metered_ref(
        lits, pruned.clause_i, pruned._nonempty_eff(), pruned.class_i,
        thresh=yflash.I_CSA_THRESHOLD)
    read_j = float(yflash.V_READ * yflash.T_READ
                   * (np.asarray(i_cl).sum() + np.asarray(i_cs).sum()))
    stats = PruneStats(
        n_clauses=int(system.n_clauses), n_effective=n_eff,
        n_never_fired=n_never, n_duplicates=n_dup, calibration_batch=B,
        energy_per_effective_clause_j=energy_mod.energy_per_effective_clause(
            read_j, B, n_eff))
    pruned.encode_stats = dict(system.encode_stats,
                               pruning=dataclasses.asdict(stats))
    return pruned, stats


def _quantize(v: Array, scale: Array) -> Array:
    q = jnp.round(v / jnp.maximum(scale, 1e-30))
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def int8_psum(v: Array, axis_name: str) -> Array:
    """All-reduce ``v`` over ``axis_name`` with int8 wire format.

    Must be called inside shard_map/pmap with ``axis_name`` bound.
    The leading dimension of the flattened tensor is padded to the axis
    size for the all_to_all phase.
    """
    n = jax.lax.axis_size(axis_name)
    shape = v.shape
    flat = v.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))

    # Phase 1: shared input scale.
    scale1 = jax.lax.pmax(jnp.max(jnp.abs(flat)), axis_name) / 127.0
    q = _quantize(flat, scale1).reshape(n, -1)

    # Phase 2: reduce-scatter via all_to_all (int8 on the wire).
    shards = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)            # (n, chunk) int8
    local_sum = shards.astype(jnp.int32).sum(axis=0)    # my shard, int32
    local_f = local_sum.astype(jnp.float32) * scale1

    # Phase 3: requantize + all-gather (int8 on the wire).
    scale2 = jax.lax.pmax(jnp.max(jnp.abs(local_f)), axis_name) / 127.0
    q2 = _quantize(local_f, scale2)
    gathered = jax.lax.all_gather(q2, axis_name)        # (n, chunk) int8
    out = gathered.astype(jnp.float32).reshape(-1) * scale2
    return out[:flat.size - pad if pad else None][:v.size].reshape(shape)


def compressed_grad_allreduce(grads, errors, axis_name: str):
    """Error-feedback wrapper: returns (summed grads, new error state)."""
    def one(g, e):
        v = g.astype(jnp.float32) + e
        total = int8_psum(v, axis_name)
        # Residual = what this device meant to send minus what survived
        # phase-1 quantization (the part it can still correct next step).
        e_new = v - _roundtrip(v, axis_name)
        return total, e_new

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(errors)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (treedef.unflatten([o[0] for o in outs]),
            treedef.unflatten([o[1] for o in outs]))


def _roundtrip(v: Array, axis_name: str) -> Array:
    """This device's contribution as it survives quantization (phase-1
    quantize/dequantize) — the error-feedback residual reference."""
    flat = v.reshape(-1)
    scale1 = jax.lax.pmax(jnp.max(jnp.abs(flat)), axis_name) / 127.0
    q = _quantize(flat, scale1)
    return (q.astype(jnp.float32) * scale1).reshape(v.shape)
