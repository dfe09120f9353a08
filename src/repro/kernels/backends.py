"""Pluggable inference-backend registry.

A *backend* is one lowering of the crossbar primitives the IMPACT
runtime is built from: ``"pallas"``, the Pallas kernels, and ``"xla"``,
the pure-einsum oracles.  Dispatch used to be an ``if impl == "xla"``
string switch copy-pasted into every jitted entry point; it now lives
here, so a new backend slots in by registering an object instead of
touching call sites:

    class MyLowering(PallasBackend):
        name = "pallas-mine"
        ...
    register_backend(MyLowering())

A backend is a lowering, not a datapath: which kernel variant runs
(metered or not, packed or not) is the ``RuntimeSpec``'s ``metering``
and ``packing`` fields, routed by ``ops.fused_datapath``.

Every backend also lowers ``fused_impact_metered`` — inference plus the
per-lane read-current meters (the Table 4 energy accounting) in one
call: the Pallas backend accumulates the meters inside the fused
kernel's VMEM residency, the reference backend uses the whole-array
metered oracle, and the base class composes the staged per-shard
primitives so any third backend meters correctly out of the box.

``kernels.ops`` keeps the public wrapper signatures (``impl=`` is simply
the registry key) and the compiled-session runtime (``impact.runtime``)
resolves a backend ONCE per ``RuntimeSpec`` instead of per call.

Two policies are shared across every op and hoisted here from the four
copies that used to live in ``ops.py``:

* **interpret resolution** (``Backend.resolve_interpret``): Pallas
  kernels run in interpret mode automatically off-TPU so the same call
  sites work in CI (CPU) and production (TPU); reference backends have
  no kernel to interpret and always resolve ``False``.
* **neutral padding** (``pad_axis`` + the per-op plumbing in
  ``PallasBackend``): arbitrary shapes are padded to MXU-aligned tiles
  with *semantically neutral* values (literal rows pad with 1 — a
  floating 'Z' row contributes no current; clause columns pad with
  include=0/nonempty=0/weight=0; conductances pad above the
  nonlinearity cutoff) and outputs are sliced back.

The staged analog compositions (``impact_clause_bits`` /
``impact_class_scores``) have a backend-generic default built from
``crossbar_mvm`` — the Fig. 14 per-shard unroll — which reference
backends override with their whole-array oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import clause_eval as _clause_kernel
from . import class_sum as _class_kernel
from . import crossbar_mvm as _mvm_kernel
from . import fused_cotm as _fused_kernel
from . import fused_impact as _impact_kernel
from . import packing
from . import ref

Array = jax.Array


def pad_axis(x: Array, mult: int, axis: int, value) -> Array:
    """Pad ``axis`` up to the next multiple of ``mult`` with ``value``."""
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


class Backend:
    """One lowering of the crossbar primitives.

    Subclass, set ``name``, implement the primitive ops, and
    ``register_backend`` an instance.  Instances are stateless
    singletons: jitted entry points pass the *name* through static
    arguments and resolve the object inside the trace, so registering a
    backend never invalidates jit caches.
    """

    name: str = ""
    #: True for oracle backends (pure jnp, no kernel, nothing to
    #: interpret) — used by tests and benchmarks to pick A/B sides.
    reference: bool = False

    # -- shape policy ------------------------------------------------------
    def resolve_interpret(self, interpret: bool | None) -> bool:
        """The ONE interpret-mode resolver (was copy-pasted per wrapper):
        ``None`` means "interpret off-TPU", so CI (CPU) and production
        (TPU) share call sites."""
        if interpret is None:
            return jax.default_backend() != "tpu"
        return bool(interpret)

    # -- primitive ops -----------------------------------------------------
    def clause_eval(self, literals: Array, include: Array, nonempty: Array,
                    *, mode: str = "fired", interpret: bool | None = None,
                    block_b: int = 128, block_n: int = 128,
                    block_k: int = 512) -> Array:
        raise NotImplementedError

    def class_sum(self, clauses: Array, weights: Array, *,
                  interpret: bool | None = None, block_b: int = 128,
                  block_n: int = 512, block_m: int = 128) -> Array:
        raise NotImplementedError

    def fused_cotm(self, literals: Array, include: Array, nonempty: Array,
                   weights: Array, *, interpret: bool | None = None,
                   block_b: int = 128, block_n: int = 256) -> Array:
        raise NotImplementedError

    def fused_impact(self, literals: Array, clause_i: Array, nonempty: Array,
                     class_i: Array, *, thresh: float,
                     interpret: bool | None = None, block_b: int = 128,
                     block_n: int = 256) -> Array:
        raise NotImplementedError

    def fused_impact_metered(self, literals: Array, clause_i: Array,
                             nonempty: Array, class_i: Array, *,
                             thresh: float, interpret: bool | None = None,
                             block_b: int = 128, block_n: int = 256,
                             ) -> tuple[Array, Array, Array]:
        """-> (scores (B, M), per-lane summed clause-crossbar column
        currents (B,), per-lane summed class-crossbar column currents
        (B,)) — inference plus the Table 4 energy meters in one pass.

        Default composition: the staged per-shard primitives, summing the
        column currents they already materialize.  Kernel backends
        override this with a fused lowering (``PallasBackend`` accumulates
        the meters inside the fused kernel's VMEM residency), but ANY
        registered backend supports ``RuntimeSpec(metering="fused")``
        through this fallback — correctness never depends on the
        override, only throughput does.
        """
        fired, i_col = self.impact_clause_bits(
            literals, clause_i, nonempty, thresh=thresh, interpret=interpret)
        scores, i_cls = self.impact_class_scores(fired, class_i,
                                                 interpret=interpret)
        return scores, i_col.sum(axis=(1, 2, 3)), i_cls.sum(axis=(1, 2))

    def crossbar_mvm(self, drive: Array, g: Array, *, v_read: float = 2.0,
                     nonlin: float = 1.5, cutoff: float = 10e-9,
                     interpret: bool | None = None, block_b: int = 128,
                     block_n: int = 128, block_k: int = 512) -> Array:
        raise NotImplementedError

    # -- bitplane-packed datapath (kernels.packing layout) -----------------
    def fused_impact_packed(self, literals: Array,
                            packed: packing.PackedClause, nonempty: Array,
                            class_i: Array, *, thresh: float, tr: int,
                            interpret: bool | None = None,
                            block_b: int = 128, block_n: int = 256) -> Array:
        """``fused_impact`` on a packed clause operand.  ``tr`` is the
        UNPACKED per-shard row count (not recoverable from the packed
        bits — the shard row mapping needs it)."""
        raise NotImplementedError

    def fused_impact_packed_metered(self, literals: Array,
                                    packed: packing.PackedClause,
                                    nonempty: Array, class_i: Array, *,
                                    thresh: float, tr: int,
                                    interpret: bool | None = None,
                                    block_b: int = 128, block_n: int = 256,
                                    ) -> tuple[Array, Array, Array]:
        """Metered packed datapath; meters bill the QUANTIZED currents
        (what the packed cells draw), same triple as
        ``fused_impact_metered``."""
        raise NotImplementedError

    # -- online training (arXiv:2408.09456 in-array TA updates) ------------
    def ta_feedback(self, lit2: Array, fired2: Array, sel: Array,
                    match: Array, hi: Array, lo: Array, include: Array, *,
                    interpret: bool | None = None, block_k: int = 128,
                    block_n: int = 128) -> Array:
        """CoTM Type I/II TA feedback deltas over one doubled update batch
        -> ta_delta (K, n) int32 (see ``ref.ta_feedback_ref`` for the full
        mask semantics).  All stochastic draws (``sel``/``hi``/``lo``) are
        precomputed operands, so every backend computes bit-identical
        deltas from the same inputs — the parity contract the online
        trainer's write path depends on.

        Default: the einsum oracle; ``PallasBackend`` overrides with the
        fused kernel that accumulates the three feedback matmuls in one
        VMEM residency of the clause-output datapath.
        """
        return ref.ta_feedback_ref(lit2, fired2, sel, match, hi, lo,
                                   include)

    # -- staged analog compositions (Fig. 14 per-shard unroll) -------------
    def impact_clause_bits(self, literals: Array, clause_i: Array,
                           nonempty: Array, *, thresh: float,
                           interpret: bool | None = None,
                           ) -> tuple[Array, Array]:
        """-> (fired (B, C*tc) bool, shard column currents (B, R, C, tc)).

        Default composition shared by every kernel backend: per-shard
        ``crossbar_mvm`` column currents, CSA threshold, digital AND
        over the R row shards, ``nonempty`` mask.
        """
        B = literals.shape[0]
        R, C, tr, tc = clause_i.shape
        lit = ref.pad_to(literals.astype(jnp.float32), R * tr, axis=1,
                         value=1)
        drive = (1.0 - lit).reshape(B, R, tr)
        cols = []
        for r in range(R):                      # static shard unroll
            cur = clause_i[r].transpose(1, 0, 2).reshape(tr, C * tc)
            cols.append(self.crossbar_mvm(drive[:, r], cur, v_read=1.0,
                                          cutoff=0.0, interpret=interpret))
        i_col = jnp.stack(cols, axis=1).reshape(B, R, C, tc)
        fired = jnp.all(i_col < thresh, axis=1).reshape(B, C * tc)
        return jnp.logical_and(fired, nonempty.astype(bool)), i_col

    def impact_class_scores(self, clauses: Array, class_i: Array, *,
                            interpret: bool | None = None,
                            ) -> tuple[Array, Array]:
        """-> (scores (B, m) = summed shard currents, currents (B, S, m))."""
        B = clauses.shape[0]
        S, sr, m = class_i.shape
        drive = ref.pad_to(clauses.astype(jnp.float32), S * sr, axis=1)
        drive = drive[:, :S * sr].reshape(B, S, sr)
        i_col = jnp.stack(
            [self.crossbar_mvm(drive[:, s], class_i[s], v_read=1.0,
                               cutoff=0.0, interpret=interpret)
             for s in range(S)],
            axis=1)                             # per-shard ADC
        return i_col.sum(axis=1), i_col         # digital add


class PallasBackend(Backend):
    """The production lowering: Pallas TPU kernels (interpret mode
    off-TPU), with the neutral-padding plumbing around each one.

    On a packed clause operand (``kernels.packing`` 2-bit layout) the
    fused kernel unpacks the bitplanes in VMEM, so the f32 clause-current
    operand never exists in HBM."""

    name = "pallas"

    def clause_eval(self, literals, include, nonempty, *, mode="fired",
                    interpret=None, block_b=128, block_n=128, block_k=512):
        B, K = literals.shape
        N = include.shape[1]
        interpret = self.resolve_interpret(interpret)
        block_k = min(block_k, max(128, -(-K // 128) * 128))
        lit = pad_axis(pad_axis(literals.astype(jnp.int8), block_b, 0, 1),
                       block_k, 1, 1)      # pad literals with 1 ('Z' rows)
        inc = pad_axis(pad_axis(include.astype(jnp.int8), block_k, 0, 0),
                       block_n, 1, 0)
        ne = pad_axis(nonempty.astype(jnp.int8)[None, :], block_n, 1, 0)
        out = _clause_kernel.clause_eval(
            lit, inc, ne, mode=mode, block_b=block_b, block_n=block_n,
            block_k=block_k, interpret=interpret)[:B, :N]
        return out if mode == "viol" else out.astype(bool)

    def class_sum(self, clauses, weights, *, interpret=None, block_b=128,
                  block_n=512, block_m=128):
        B, N = clauses.shape
        M = weights.shape[1]
        interpret = self.resolve_interpret(interpret)
        block_n = min(block_n, max(128, -(-N // 128) * 128))
        cl = pad_axis(pad_axis(clauses.astype(jnp.int8), block_b, 0, 0),
                      block_n, 1, 0)
        w = pad_axis(pad_axis(weights.astype(jnp.int32), block_n, 0, 0),
                     block_m, 1, 0)
        out = _class_kernel.class_sum(
            cl, w, block_b=block_b, block_n=block_n, block_m=block_m,
            interpret=interpret)
        return out[:B, :M]

    def fused_cotm(self, literals, include, nonempty, weights, *,
                   interpret=None, block_b=128, block_n=256):
        B, K = literals.shape
        N, M = weights.shape
        interpret = self.resolve_interpret(interpret)
        block_n = min(block_n, max(128, -(-N // 128) * 128))
        lit = pad_axis(pad_axis(literals.astype(jnp.int8), block_b, 0, 1),
                       128, 1, 1)
        inc = pad_axis(pad_axis(include.astype(jnp.int8), 128, 0, 0),
                       block_n, 1, 0)
        ne = pad_axis(nonempty.astype(jnp.int8)[None, :], block_n, 1, 0)
        w = pad_axis(pad_axis(weights.astype(jnp.int32), block_n, 0, 0),
                     128, 1, 0)
        out = _fused_kernel.fused_cotm(
            lit, inc, ne, w, block_b=block_b, block_n=block_n,
            interpret=interpret)
        return out[:B, :M]

    def _fused_impact_operands(self, literals, clause_i, nonempty, class_i,
                               *, block_b, block_n):
        """Operands of the fused IMPACT kernels: -> (drive, ccur, ne,
        wcur, block_n) in the kernel layouts.  A grid of whole-block
        tiles goes in as programmed, (R, C, tr, tc), and the kernel walks
        its C*tc columns only.  Padded batch rows drive 0 V, and padded
        columns and class lanes hold 0 A, which keeps the in-kernel
        meters exact."""
        B, K = literals.shape
        R, C, tr, tc = clause_i.shape
        S, sr, M = class_i.shape
        Ck, tck, block_n = _impact_kernel.column_tiling(C, tc, block_n)

        lit = pad_axis(literals.astype(jnp.float32), R * tr, 1, 1)
        drive = (1.0 - lit).reshape(B, R, tr).transpose(1, 0, 2)
        drive = pad_axis(drive, block_b, 1, 0.0)

        ccur = clause_i.astype(jnp.float32)
        ne = nonempty.astype(jnp.int8)
        if (Ck, tck) != (C, tc):
            # Tiles that are not a whole number of blocks wide: their
            # columns end to end, as one tile padded to whole blocks.
            ccur = ccur.transpose(0, 2, 1, 3).reshape(R, 1, tr, C * tc)
            ccur = pad_axis(ccur, tck, 3, 0.0)
            ne = pad_axis(ne, tck, 0, 0)
        ne = ne.reshape(Ck, 1, tck)

        # Class row j holds clause column j's weights.  Rows past the
        # clause grid (S*sr > C*tc) are driven by no clause; clause
        # columns past the class grid (S*sr < C*tc) drive 0 A rows.
        wcur = class_i.astype(jnp.float32).reshape(S * sr, M)
        wcur = ref.pad_to(wcur, Ck * tck, 0)[:Ck * tck].reshape(Ck, tck, M)
        wcur = pad_axis(wcur, 128, 2, 0.0)
        return drive, ccur, ne, wcur, block_n

    def fused_impact(self, literals, clause_i, nonempty, class_i, *,
                     thresh, interpret=None, block_b=128, block_n=256):
        B, M = literals.shape[0], class_i.shape[2]
        interpret = self.resolve_interpret(interpret)
        drive, ccur, ne, wcur, block_n = self._fused_impact_operands(
            literals, clause_i, nonempty, class_i, block_b=block_b,
            block_n=block_n)
        out = _impact_kernel.fused_impact(
            drive, ccur, ne, wcur, thresh=thresh, block_b=block_b,
            block_n=block_n, interpret=interpret)
        return out[:B, :M]

    def fused_impact_metered(self, literals, clause_i, nonempty, class_i,
                             *, thresh, interpret=None, block_b=128,
                             block_n=256):
        """The tentpole lowering: scores AND both per-lane current meters
        from ONE fused kernel pass (second VMEM accumulator), no staged
        second pass.  Padding contributes exactly zero current, so the
        sliced meters equal the staged per-shard sums to f32 tolerance."""
        B, M = literals.shape[0], class_i.shape[2]
        interpret = self.resolve_interpret(interpret)
        drive, ccur, ne, wcur, block_n = self._fused_impact_operands(
            literals, clause_i, nonempty, class_i, block_b=block_b,
            block_n=block_n)
        out, meters = _impact_kernel.fused_impact_metered(
            drive, ccur, ne, wcur, thresh=thresh, block_b=block_b,
            block_n=block_n, interpret=interpret)
        return (out[:B, :M],
                meters[:B, _impact_kernel.METER_LANE_CLAUSE],
                meters[:B, _impact_kernel.METER_LANE_CLASS])

    def _fused_impact_packed_operands(self, literals, packed, nonempty,
                                      class_i, *, tr, block_b, block_n):
        """Neutral-padding plumbing for the packed kernel layouts:
        -> (drive_p, pbits, levels, ne, wcur, block_n).  Padding packs to
        CODE_DEAD (0 A) and pads drive with 0, so padded rows/columns
        contribute exactly zero current — the meters stay exact."""
        B, K = literals.shape
        R, C, tr4, tc = packed.bits.shape
        S, sr, M = class_i.shape
        n_clause = C * tc

        N = max(n_clause, S * sr)
        block_n = min(block_n, max(128, -(-N // 128) * 128))
        tr4_pad = max(128, -(-tr4 // 128) * 128)

        # Bitplane-major drive: drive_p[r, j, b, q] = 1 - lit[b, r*tr+4q+j].
        lit = pad_axis(literals.astype(jnp.float32), R * tr, 1, 1)
        drive = (1.0 - lit).reshape(B, R, tr)
        drive = pad_axis(drive, packing.CELLS_PER_BYTE * tr4, 2, 0.0)
        drive = drive.reshape(B, R, tr4, packing.CELLS_PER_BYTE)
        drive = drive.transpose(1, 3, 0, 2)         # (R, 4, B, tr4)
        drive = pad_axis(pad_axis(drive, block_b, 2, 0.0), tr4_pad, 3, 0.0)

        pbits = packed.bits.transpose(0, 2, 1, 3).reshape(R, tr4, n_clause)
        pbits = pad_axis(pad_axis(pbits, tr4_pad, 1, 0), block_n, 2, 0)
        if N > n_clause:
            pbits = pad_axis(pbits, -(-N // block_n) * block_n, 2, 0)

        levels = jnp.zeros((1, 128), jnp.float32)
        levels = levels.at[0, :2].set(packed.levels.astype(jnp.float32))

        ne = pad_axis(nonempty.astype(jnp.int8)[None, :],
                      -(-N // block_n) * block_n, 1, 0)

        wcur = class_i.astype(jnp.float32).reshape(S * sr, M)
        wcur = pad_axis(pad_axis(wcur, ne.shape[1], 0, 0.0), 128, 1, 0.0)
        return drive, pbits, levels, ne, wcur, block_n

    def fused_impact_packed(self, literals, packed, nonempty, class_i, *,
                            thresh, tr, interpret=None, block_b=128,
                            block_n=256):
        B, M = literals.shape[0], class_i.shape[2]
        interpret = self.resolve_interpret(interpret)
        drive, pbits, levels, ne, wcur, block_n = (
            self._fused_impact_packed_operands(
                literals, packed, nonempty, class_i, tr=tr,
                block_b=block_b, block_n=block_n))
        out = _impact_kernel.fused_impact_packed(
            drive, pbits, levels, ne, wcur, thresh=thresh, block_b=block_b,
            block_n=block_n, interpret=interpret)
        return out[:B, :M]

    def fused_impact_packed_metered(self, literals, packed, nonempty,
                                    class_i, *, thresh, tr, interpret=None,
                                    block_b=128, block_n=256):
        B, M = literals.shape[0], class_i.shape[2]
        interpret = self.resolve_interpret(interpret)
        drive, pbits, levels, ne, wcur, block_n = (
            self._fused_impact_packed_operands(
                literals, packed, nonempty, class_i, tr=tr,
                block_b=block_b, block_n=block_n))
        out, meters = _impact_kernel.fused_impact_packed_metered(
            drive, pbits, levels, ne, wcur, thresh=thresh, block_b=block_b,
            block_n=block_n, interpret=interpret)
        return (out[:B, :M],
                meters[:B, _impact_kernel.METER_LANE_CLAUSE],
                meters[:B, _impact_kernel.METER_LANE_CLASS])

    def ta_feedback(self, lit2, fired2, sel, match, hi, lo, include, *,
                    interpret=None, block_k=128, block_n=128):
        B2, K = lit2.shape
        n = hi.shape[1]
        interpret = self.resolve_interpret(interpret)
        b2p = max(128, -(-B2 // 128) * 128)
        block_k = min(block_k, max(128, -(-K // 128) * 128))
        block_n = min(block_n, max(128, -(-n // 128) * 128))
        # Neutral padding: padded batch rows / clause columns carry sel=0
        # (they select nothing), padded TA rows carry hi=lo=excl=0 (their
        # delta is exactly 0) — so the sliced output equals the oracle's.
        litT = pad_axis(pad_axis(lit2.astype(jnp.float32).T,
                                 block_k, 0, 0.0), b2p, 1, 0.0)
        mask = lambda x: pad_axis(pad_axis(x.astype(jnp.float32),
                                           b2p, 0, 0.0), block_n, 1, 0.0)
        cell = lambda x: pad_axis(pad_axis(x.astype(jnp.float32),
                                           block_k, 0, 0.0),
                                  block_n, 1, 0.0)
        excl = jnp.logical_not(include.astype(bool))
        out = _impact_kernel.ta_feedback(
            litT, mask(sel), mask(match), mask(fired2), cell(hi), cell(lo),
            cell(excl), block_k=block_k, block_n=block_n,
            interpret=interpret)
        return out[:K, :n]

    def crossbar_mvm(self, drive, g, *, v_read=2.0, nonlin=1.5,
                     cutoff=10e-9, interpret=None, block_b=128,
                     block_n=128, block_k=512):
        B, K = drive.shape
        N = g.shape[1]
        interpret = self.resolve_interpret(interpret)
        block_k = min(block_k, max(128, -(-K // 128) * 128))
        dr = pad_axis(pad_axis(drive.astype(jnp.float32), block_b, 0, 0.0),
                      block_k, 1, 0.0)
        # Pad conductances ABOVE the nonlinearity cutoff so padded cells
        # do not get the LCS boost; padded drive rows are 0 so they
        # contribute nothing.
        gp = pad_axis(pad_axis(g.astype(jnp.float32), block_k, 0, 1.0),
                      block_n, 1, 1.0)
        out = _mvm_kernel.crossbar_mvm(
            dr, gp, v_read=v_read, nonlin=nonlin, cutoff=cutoff,
            block_b=block_b, block_n=block_n, block_k=block_k,
            interpret=interpret)
        return out[:B, :N]


class XLABackend(Backend):
    """Pure-einsum oracles (``kernels.ref``) for A/B parity runs and
    wall-clock-sensitive CPU callers; every test ground-truths against
    this backend."""

    name = "xla"
    reference = True

    def resolve_interpret(self, interpret):
        return False                      # nothing to interpret

    def clause_eval(self, literals, include, nonempty, *, mode="fired",
                    interpret=None, block_b=128, block_n=128, block_k=512):
        if mode == "viol":
            return ref.clause_viol_ref(literals, include)
        return ref.clause_eval_ref(literals, include, nonempty)

    def class_sum(self, clauses, weights, *, interpret=None, block_b=128,
                  block_n=512, block_m=128):
        return ref.class_sum_ref(clauses, weights)

    def fused_cotm(self, literals, include, nonempty, weights, *,
                   interpret=None, block_b=128, block_n=256):
        return ref.fused_cotm_ref(literals, include, weights, nonempty)

    def fused_impact(self, literals, clause_i, nonempty, class_i, *,
                     thresh, interpret=None, block_b=128, block_n=256):
        return ref.fused_impact_ref(literals, clause_i, nonempty, class_i,
                                    thresh=thresh)

    # fused_impact_metered is inherited: the base composition over THIS
    # backend's staged primitives is exactly the whole-array metered
    # oracle (``ref.fused_impact_metered_ref`` spells out the same
    # expression for direct use in tests).

    def crossbar_mvm(self, drive, g, *, v_read=2.0, nonlin=1.5,
                     cutoff=10e-9, interpret=None, block_b=128,
                     block_n=128, block_k=512):
        return ref.crossbar_mvm_ref(drive, g, v_read=v_read, nonlin=nonlin,
                                    cutoff=cutoff)

    def impact_clause_bits(self, literals, clause_i, nonempty, *, thresh,
                           interpret=None):
        return ref.impact_clause_bits_ref(literals, clause_i, nonempty,
                                          thresh=thresh)

    def impact_class_scores(self, clauses, class_i, *, interpret=None):
        return ref.impact_class_scores_ref(clauses, class_i)

    def fused_impact_packed(self, literals, packed, nonempty, class_i, *,
                            thresh, tr, interpret=None, block_b=128,
                            block_n=256):
        return ref.fused_impact_packed_ref(
            literals, packed.bits, packed.levels, nonempty, class_i,
            thresh=thresh, tr=tr)

    def fused_impact_packed_metered(self, literals, packed, nonempty,
                                    class_i, *, thresh, tr, interpret=None,
                                    block_b=128, block_n=256):
        return ref.fused_impact_packed_metered_ref(
            literals, packed.bits, packed.levels, nonempty, class_i,
            thresh=thresh, tr=tr)


# -- registry ---------------------------------------------------------------

_REGISTRY: dict[str, Backend] = {}

#: The primitive contract every registered backend must satisfy: the
#: ops the session/entry points may route to.  ``Backend`` supplies
#: working compositions for most, so subclasses only override what they
#: specialize — but a registrant that *deletes* one of these (sets it to
#: None, or shadows it with a non-callable) would fail at serving time;
#: ``register_backend`` refuses it up front, and the IMPACT004 lint rule
#: proves the same contract (plus signatures) statically.
REQUIRED_PRIMITIVES: tuple[str, ...] = (
    "resolve_interpret", "clause_eval", "class_sum",
    "fused_cotm", "fused_impact", "fused_impact_metered",
    "crossbar_mvm", "fused_impact_packed", "fused_impact_packed_metered",
    "impact_clause_bits", "impact_class_scores", "ta_feedback",
)


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    """Register a backend under ``backend.name``.  Registering is how a
    new lowering (TPU-native, ...) plugs into every entry
    point — ``RuntimeSpec(backend=<name>)`` and ``ops.*(impl=<name>)``
    resolve through here, so no call site changes."""
    if not backend.name:
        raise ValueError("backend must define a non-empty .name")
    missing = [p for p in REQUIRED_PRIMITIVES
               if not callable(getattr(backend, p, None))]
    if missing:
        raise TypeError(
            f"backend {backend.name!r} does not satisfy the primitive "
            f"contract: {', '.join(missing)} "
            f"{'is' if len(missing) == 1 else 'are'} missing or not "
            f"callable (see backends.REQUIRED_PRIMITIVES)")
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} is already registered "
                         f"(pass overwrite=True to replace it)")
    _REGISTRY[backend.name] = backend
    return backend


def unregister_backend(name: str) -> Backend:
    """Remove a registered backend (tests / plugin teardown)."""
    try:
        return _REGISTRY.pop(name)
    except KeyError:
        raise ValueError(f"backend {name!r} is not registered") from None


def get_backend(name: str | Backend) -> Backend:
    """Resolve a registry key (or pass a backend instance through)."""
    if isinstance(name, Backend):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{sorted(_REGISTRY)}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend(PallasBackend())
register_backend(XLABackend())
