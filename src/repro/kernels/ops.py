"""Public wrappers around the crossbar primitives.

Each wrapper resolves ``impl`` through the backend registry
(``kernels.backends``) and delegates: ``impl="pallas"`` runs the Pallas
kernels (interpret mode off-TPU), ``impl="xla"`` the pure-einsum oracles,
and any registered third backend slots in without touching these call
sites.  The padding / interpret plumbing that used to be copy-pasted
across the wrappers lives on the backend objects now (the shape-policy
hooks); oracles live in ``ref.py`` and every kernel backend is
exact-equality tested against them over shape sweeps and
hypothesis-generated inputs.

``fused_impact`` additionally routes to the ``shard_map`` lowering
(``sharding.crossbar``) when a mesh with a usable ``model`` axis is
passed — including the asymmetric R-only / S-only plans where the
non-dividing operand is replicated — falling back to the single-device
backend otherwise, so callers can pass a mesh unconditionally.  That
routing is ``fused_datapath``, which the compiled session calls too.
"""
from __future__ import annotations

import jax

from . import backends, packing
from .backends import pad_axis as _pad_axis  # noqa: F401  (legacy import path)

Array = jax.Array


def clause_eval(literals: Array, include: Array,
                nonempty: Array | None = None, *, mode: str = "fired",
                impl: str = "pallas", interpret: bool | None = None,
                block_b: int = 128, block_n: int = 128,
                block_k: int = 512) -> Array:
    """Boolean clause outputs (B, N) bool, or violation counts int32.

    literals (B, K) bool/{0,1}; include (K, N) bool/{0,1};
    nonempty (N,) bool (defaults to ``include.any(0)``).
    """
    if nonempty is None:
        nonempty = include.astype(bool).any(axis=0)
    return backends.get_backend(impl).clause_eval(
        literals, include, nonempty, mode=mode, interpret=interpret,
        block_b=block_b, block_n=block_n, block_k=block_k)


def class_sum(clauses: Array, weights: Array, *, impl: str = "pallas",
              interpret: bool | None = None, block_b: int = 128,
              block_n: int = 512, block_m: int = 128) -> Array:
    """Class scores (B, M) int32 from clauses (B, N) and weights (N, M)."""
    return backends.get_backend(impl).class_sum(
        clauses, weights, interpret=interpret, block_b=block_b,
        block_n=block_n, block_m=block_m)


def fused_cotm(literals: Array, include: Array, weights: Array,
               nonempty: Array | None = None, *, impl: str = "pallas",
               interpret: bool | None = None, block_b: int = 128,
               block_n: int = 256) -> Array:
    """Fused literals -> class scores (B, M) int32 (clauses stay in VMEM).

    weights is (N, M) — i.e. the class-crossbar layout (paper stores W^T).
    """
    if nonempty is None:
        nonempty = include.astype(bool).any(axis=0)
    return backends.get_backend(impl).fused_cotm(
        literals, include, nonempty, weights, interpret=interpret,
        block_b=block_b, block_n=block_n)


def fused_impact(literals: Array, clause_i, nonempty: Array,
                 class_i: Array, *, thresh: float, impl: str = "pallas",
                 interpret: bool | None = None, block_b: int = 128,
                 block_n: int = 256, mesh=None, meter: bool = False,
                 tr: int | None = None):
    """Fused analog IMPACT inference: literals -> class currents (B, M) f32.

    literals (B, K) bool/{0,1}; clause_i (R, C, tr, tc) f32 per-cell clause
    crossbar read currents in the ``IMPACTSystem`` shard layout; nonempty
    (C*tc,) digital mask; class_i (S, sr, M) f32 class crossbar currents.
    ``thresh`` is the CSA decision current (``yflash.I_CSA_THRESHOLD``).

    ``clause_i`` may instead be a ``kernels.packing.PackedClause`` —
    2-bit codes ``(R, C, ceil(tr/4), tc)`` uint8 plus the ``(2,)``
    dequant levels — and then ``tr`` is the UNPACKED per-shard row count
    (the packed bits alone cannot recover it): the backend's packed
    kernel runs, and the meters bill the quantized currents.

    ``meter=True`` additionally returns the per-lane energy meters —
    ``(scores, summed clause-crossbar column currents (B,), summed
    class-crossbar column currents (B,))`` — accumulated inside the fused
    kernel (``Backend.fused_impact_metered``), so the Table 4 joules
    cost no staged second pass.  Padding rows/columns contribute exactly
    zero current to the meters.

    ``mesh``: a jax Mesh with a ``model`` axis distributes the R/S row
    shards across devices via ``sharding.crossbar`` (digital AND == psum
    of partial CSA bits, ADC + add == psum of partial class currents) and
    shards the batch over the data axes; with ``meter=True`` the per-lane
    meters are psummed alongside.  When only one of R/S divides the model
    axis, that operand shards and the other is replicated (asymmetric
    plan); when neither divides, the single-device backend runs, so
    callers can pass a mesh unconditionally.

    Padding is semantically neutral: padded literal rows drive 0 V (a
    floating row contributes no current), padded clause columns carry
    nonempty=0, padded class rows carry 0 S conductance.
    """
    packed = isinstance(clause_i, packing.PackedClause)
    R, C, _, tc = (clause_i.bits if packed else clause_i).shape
    assert nonempty.shape == (C * tc,), (nonempty.shape, C * tc)
    plan = None
    if mesh is not None:
        from ..sharding import crossbar as _crossbar  # lazy: avoids cycle
        plan = _crossbar.shard_plan(mesh, R, class_i.shape[0])
    return fused_datapath(
        backends.get_backend(impl), literals, clause_i, nonempty, class_i,
        thresh=thresh, meter=meter, tr=tr, interpret=interpret, mesh=mesh,
        plan=plan, block_b=block_b, block_n=block_n)


def fused_datapath(backend: backends.Backend, literals: Array, clause,
                   nonempty: Array, class_i: Array, *, thresh: float,
                   meter: bool, tr: int | None = None,
                   interpret: bool | None = None, mesh=None, plan=None,
                   valid: Array | None = None,
                   lane_cols: Array | None = None, block_b: int = 128,
                   block_n: int = 256):
    """The one mesh / backend / meter routing of the fused datapath,
    shared by ``fused_impact`` and the compiled session.

    A ``plan`` (``sharding.crossbar.shard_plan`` on ``mesh``) runs the
    ``shard_map`` lowering, which also takes the ``valid`` lane mask and
    the co-residency ``lane_cols``; otherwise the backend's kernel runs,
    the packed one when ``clause`` is a ``PackedClause`` (``tr`` its
    unpacked row count).  -> scores, or the metered triple when
    ``meter``."""
    packed = isinstance(clause, packing.PackedClause)
    if plan is not None:
        from ..sharding import crossbar as _crossbar  # lazy: avoids cycle
        return _crossbar.fused_impact_shmap(
            literals, None if packed else clause, nonempty, class_i,
            thresh=thresh, mesh=mesh, impl=backend.name,
            interpret=interpret, valid=valid, meter=meter,
            shard_r=plan[0], shard_s=plan[1],
            packed=clause if packed else None,
            packed_tr=tr if packed else None, lane_cols=lane_cols)
    if packed:
        fn = (backend.fused_impact_packed_metered if meter
              else backend.fused_impact_packed)
        return fn(literals, clause, nonempty, class_i, thresh=thresh, tr=tr,
                  interpret=interpret, block_b=block_b, block_n=block_n)
    fn = backend.fused_impact_metered if meter else backend.fused_impact
    return fn(literals, clause, nonempty, class_i, thresh=thresh,
              interpret=interpret, block_b=block_b, block_n=block_n)


def crossbar_mvm(drive: Array, g: Array, *, v_read: float = 2.0,
                 nonlin: float = 1.5, cutoff: float = 10e-9,
                 impl: str = "pallas", interpret: bool | None = None,
                 block_b: int = 128, block_n: int = 128,
                 block_k: int = 512) -> Array:
    """Analog crossbar column currents (B, N) f32."""
    return backends.get_backend(impl).crossbar_mvm(
        drive, g, v_read=v_read, nonlin=nonlin, cutoff=cutoff,
        interpret=interpret, block_b=block_b, block_n=block_n,
        block_k=block_k)
