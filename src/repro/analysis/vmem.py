"""Pallas VMEM working-set estimator, derived from the kernels' BlockSpecs.

A TPU core has ~16 MiB of VMEM; a Pallas kernel whose per-grid-step
blocks (double-buffered by the pipeline) plus scratch accumulators
exceed it OOMs at compile time *on the TPU* — which CPU CI, running the
same kernels in interpret mode, can never see.  This module prices the
working set STATICALLY, by mirroring the exact padding/tiling math of
``kernels.backends`` (``_fused_impact_operands`` /
``_fused_impact_packed_operands``, ``fused_impact.column_tiling``) and
the BlockSpecs of
``kernels.fused_impact`` / ``kernels.crossbar_mvm``, so a block-shape or
grid-geometry change that blows VMEM fails the IR-audit gate before any
TPU exists to OOM (the static half of the ROADMAP's autotuning item).

The block constants are imported from the kernel modules themselves —
change ``BLOCK_B``/``BLOCK_N`` there and this estimate moves with it.

Estimates are per-core upper bounds: a sharded topology only shrinks
per-device operands, and interpret mode has no VMEM at all, so the
estimate is conservative in both directions that matter.

``session_plan`` names the kernel an ``(InferenceSession, entry, batch)``
executable runs, with its grid extents and its bytes per grid step: the
plan a session records for every executable it compiles.
"""
from __future__ import annotations

import dataclasses

# The kernels package re-exports same-named entry FUNCTIONS
# (kernels.fused_impact is the function, not the module), so bind the
# block constants by module path.
from ..kernels.crossbar_mvm import (BLOCK_B as _MVM_BLOCK_B,
                                    BLOCK_K as _MVM_BLOCK_K,
                                    BLOCK_N as _MVM_BLOCK_N)
from ..kernels.fused_impact import (BLOCK_B as _FUSED_BLOCK_B,
                                    BLOCK_N as _FUSED_BLOCK_N,
                                    METER_LANES as _METER_LANES,
                                    column_tiling as _column_tiling)

#: ~VMEM per TensorCore on current TPUs (v4/v5e: 16 MiB; v5p: ~32).
DEFAULT_VMEM_BUDGET_BYTES = 16 * 1024 * 1024

#: Pallas pipelines in/out blocks double-buffered (copy next while
#: computing current); scratch accumulators are single-buffered.
PIPELINE_BUFFERS = 2

_F32 = 4
_I32 = 4
_I8 = 1


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class WorkingSet:
    """Per-grid-step VMEM footprint of one kernel variant.

    ``blocks`` are the single-buffered in/out block sizes in bytes
    (the pipeline holds ``PIPELINE_BUFFERS`` copies of each), ``scratch``
    the VMEM scratch accumulators; ``total_bytes`` is the budgeted sum.
    """
    variant: str
    blocks: dict[str, int]
    scratch: dict[str, int]
    #: Grid extent of the kernel's literal (contraction-row) axis, and of
    #: its clause-column axis.
    literal_chunks: int = 1
    column_blocks: int = 1

    @property
    def total_bytes(self) -> int:
        return (PIPELINE_BUFFERS * sum(self.blocks.values())
                + sum(self.scratch.values()))


def fused_working_set(*, R: int, C: int, tr: int, tc: int, M: int,
                      metered: bool, block_b: int | None = None,
                      block_n: int | None = None) -> WorkingSet:
    """Working set of the fused IMPACT kernel (unpacked f32 operands) on
    an (R, C, tr, tc) clause grid, laid out by ``column_tiling``.  A grid
    step holds one literal row-shard's block of one clause tile, so the
    bytes are the same for any ``R`` and ``C``; the grid walks ``R``
    shards and the grid's C*tc columns.  VMEM holds a block in (8, 128)
    tiles, so an unaligned ``tr`` is priced at its 128-padded size."""
    block_b = block_b or _FUSED_BLOCK_B
    C, tc, block_n = _column_tiling(C, tc, block_n or _FUSED_BLOCK_N)
    tr_pad = _ceil_to(tr, 128)
    m_pad = _ceil_to(M, 128)
    blocks = {
        "drive": block_b * tr_pad * _F32,
        "ccur": tr_pad * block_n * _F32,
        "nonempty": block_n * _I8,
        "wcur": block_n * m_pad * _F32,
        "out": block_b * m_pad * _F32,
    }
    scratch = {"acc": block_b * m_pad * _F32,
               "fired": block_b * block_n * _F32}
    if metered:
        blocks["meter_out"] = block_b * _METER_LANES * _F32
        scratch["macc"] = block_b * _METER_LANES * _F32
    return WorkingSet("fused_impact_metered" if metered else "fused_impact",
                      blocks, scratch, literal_chunks=R,
                      column_blocks=C * (tc // block_n))


def packed_working_set(*, R: int, tr4: int, n_clause: int, class_rows: int,
                       M: int, metered: bool,
                       block_b: int | None = None,
                       block_n: int | None = None) -> WorkingSet:
    """Working set of the bitplane-packed fused kernel, mirroring
    ``PallasBackend._fused_impact_packed_operands`` padding.
    ``tr4`` is the packed per-shard row count (4 cells/byte).  This kernel
    holds all ``R`` row-shards in one block, so its bytes grow with R."""
    block_b = block_b or _FUSED_BLOCK_B
    block_n = block_n or _FUSED_BLOCK_N
    N = max(n_clause, class_rows)
    block_n = min(block_n, max(128, _ceil_to(N, 128)))
    tr4_pad = max(128, _ceil_to(tr4, 128))
    m_pad = _ceil_to(M, 128)
    blocks = {
        "drive": R * 4 * block_b * tr4_pad * _F32,
        "pbits": R * tr4_pad * block_n * _I8,
        "levels": 128 * _F32,
        "nonempty": block_n * _I8,
        "wcur": block_n * m_pad * _F32,
        "out": block_b * m_pad * _F32,
    }
    scratch = {"acc": block_b * m_pad * _F32}
    if metered:
        blocks["meter_out"] = block_b * _METER_LANES * _F32
        scratch["macc"] = block_b * _METER_LANES * _F32
    return WorkingSet(
        "fused_impact_packed_metered" if metered else "fused_impact_packed",
        blocks, scratch, column_blocks=_ceil_to(N, block_n) // block_n)


def mvm_working_set(*, k_rows: int, n_cols: int = 1,
                    block_b: int | None = None,
                    block_n: int | None = None,
                    block_k: int | None = None) -> WorkingSet:
    """Working set of one staged ``crossbar_mvm`` call over ``k_rows``
    drive rows and ``n_cols`` columns (the Fig. 14 per-shard unroll runs
    one such kernel per crossbar stage; each call's footprint is
    independent)."""
    block_b = block_b or _MVM_BLOCK_B
    block_n = block_n or _MVM_BLOCK_N
    block_k = min(block_k or _MVM_BLOCK_K,
                  max(128, _ceil_to(k_rows, 128)))
    blocks = {
        "drive": block_b * block_k * _F32,
        "g": block_k * block_n * _F32,
        "out": block_b * block_n * _F32,
    }
    scratch = {"acc": block_b * block_n * _F32}
    return WorkingSet("crossbar_mvm", blocks, scratch,
                      literal_chunks=_ceil_to(k_rows, block_k) // block_k,
                      column_blocks=-(-n_cols // block_n))


def ta_feedback_working_set(*, K: int, n_clause: int, batch2: int,
                            block_k: int | None = None,
                            block_n: int | None = None) -> WorkingSet:
    """Working set of the ``ta_feedback`` training kernel, mirroring
    ``PallasBackend.ta_feedback`` padding.  ``batch2`` is the DOUBLED
    feedback row count (positive + negative target copies, 2B); the
    grid tiles (K, n) while every block streams the full batch2 axis,
    so batch2 — not K or n — is the VMEM lever at serving batch sizes.
    No scratch: each (block_k, block_n) output tile is one matmul
    accumulation, written directly."""
    block_k = min(block_k or 128, max(128, _ceil_to(K, 128)))
    block_n = min(block_n or 128, max(128, _ceil_to(n_clause, 128)))
    b2p = max(128, _ceil_to(batch2, 128))
    blocks = {
        "litT": block_k * b2p * _F32,
        "sel": b2p * block_n * _F32,
        "match": b2p * block_n * _F32,
        "fired2": b2p * block_n * _F32,
        "hi": block_k * block_n * _F32,
        "lo": block_k * block_n * _F32,
        "excl": block_k * block_n * _F32,
        "out": block_k * block_n * _I32,
    }
    return WorkingSet("ta_feedback", blocks, {},
                      literal_chunks=_ceil_to(K, block_k) // block_k,
                      column_blocks=_ceil_to(n_clause, block_n) // block_n)


def session_working_set(session, entry: str,
                        batch: int | None = None) -> WorkingSet | None:
    """The VMEM working set of the kernel the ``(session, entry)``
    executable runs, read off the session's route for the entry
    (``InferenceSession.routes``):

    * reference (oracle) backends run no kernel -> ``None``;
    * the ``"staged"`` and ``"shard_map"`` routes run the staged
      ``crossbar_mvm`` compositions -> the larger of the clause / class
      stage calls;
    * the ``"packed"`` and ``"fused"`` routes -> that kernel, its
      metered variant where the entry meters;
    * the ``ta_feedback`` training entry -> the feedback-delta kernel
      (``batch`` is its compiled DOUBLED row count, from
      ``compiled_shapes``).
    """
    if getattr(session.backend, "reference", False):
        return None
    sys_ = session.system
    R, C, tr, tc = sys_.clause_i.shape
    S, sr, M = sys_.class_i.shape

    if entry == "ta_feedback":
        return ta_feedback_working_set(K=sys_.n_literals,
                                       n_clause=sys_.n_clauses,
                                       batch2=batch or 128)

    route, meter = session.routes[entry]
    if route in ("staged", "shard_map"):
        # Staged per-shard unroll (also each device's stage of a sharded
        # grid): one crossbar_mvm per clause row-shard (tr drive rows) +
        # one per class row-shard (sr drive rows).
        clause = mvm_working_set(k_rows=tr, n_cols=C * tc)
        klass = mvm_working_set(k_rows=sr, n_cols=M)
        return clause if clause.total_bytes >= klass.total_bytes else klass
    if route == "packed":
        tr4 = session._packed.bits.shape[2]
        return packed_working_set(R=R, tr4=tr4, n_clause=C * tc,
                                  class_rows=S * sr, M=M, metered=meter)
    return fused_working_set(R=R, C=C, tr=tr, tc=tc, M=M, metered=meter)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """What one session executable runs on a TPU core: the kernel
    variant, the system's literal row-shards, the kernel's grid extents
    along its literal and clause-column axes, and its VMEM bytes per
    grid step (blocks x``PIPELINE_BUFFERS`` + scratch)."""
    variant: str
    row_shards: int
    literal_chunks: int
    column_blocks: int
    vmem_step_bytes: int


def session_plan(session, entry: str,
                 batch: int | None = None) -> KernelPlan | None:
    """The ``KernelPlan`` of the ``(session, entry)`` executable, from
    ``session_working_set``; ``None`` where no kernel runs."""
    ws = session_working_set(session, entry, batch)
    if ws is None:
        return None
    return KernelPlan(variant=ws.variant,
                      row_shards=int(session.system.clause_i.shape[0]),
                      literal_chunks=ws.literal_chunks,
                      column_blocks=ws.column_blocks,
                      vmem_step_bytes=ws.total_bytes)


def refuse_packed_over_budget(session) -> None:
    """Raise ``ValueError`` if the session would run the packed kernel,
    which holds every literal row-shard in one block, with a working set
    over the spec's VMEM budget: the TPU compiler would refuse it later,
    at the first executable."""
    budget = session.spec.vmem_budget_bytes or DEFAULT_VMEM_BUDGET_BYTES
    for entry in ("predict", "infer_with_report"):
        ws = session_working_set(session, entry)
        if (ws is not None and ws.variant.startswith("fused_impact_packed")
                and ws.total_bytes > budget):
            R = session.system.clause_i.shape[0]
            raise ValueError(
                f"packing='2bit' on backend {session.spec.backend!r} runs "
                f"{ws.variant}, which holds all R={R} literal row-shards "
                f"in one block: {ws.total_bytes} B of VMEM per grid step, "
                f"over the budget of {budget} B.  Compile packing='none' "
                f"(the shard-gridded kernel) or use fewer row-shards.")
