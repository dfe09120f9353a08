"""Compiled-session runtime: ``RuntimeSpec`` -> ``InferenceSession``.

The paper's deployment story is a *fixed* fabricated system — tile
geometry, shard topology, and metering are decided once at programming
time, not per inference call.  This module gives the reproduction the
same shape: a frozen declarative **``RuntimeSpec``** (backend name, mesh
topology, metering mode, precision, interpret policy, slot capacity)
that ``IMPACTSystem.compile(spec)`` resolves ONCE into an immutable
**``InferenceSession``**:

* the backend is looked up in the registry (``kernels.backends``) at
  compile time — no per-call ``impl=`` string switches;
* the shard placement (``sharding.crossbar.shard_plan``: fully sharded,
  asymmetric R-only / S-only, or single-device) is resolved from the
  spec's topology at compile time — no per-call ``mesh=`` plumbing;
* every entry point (``predict`` / ``infer_step`` /
  ``infer_with_report``) is an AOT-lowered executable
  (``jax.jit(...).lower(...).compile()``) at the session's fixed shapes:
  ``capacity`` and ``batch_sizes`` compile at session build, other batch
  shapes compile once on first use and are cached — an executable can
  never retrace, which the session's trace counters
  (``session.trace_count``) pin in tests;
* results come back as a unified ``InferenceResult`` (predictions,
  scores, optional ``EnergyReport`` / per-lane energies) instead of
  per-entry-point tuple shapes;
* what the host needs of a call crosses to it in ONE device->host
  transfer (``InferenceSession.fetch`` for ``infer_step``, the joules
  vector inside ``infer_with_report``), counted by
  ``session.fetch_count`` as compiles are by ``trace_count``;
* ``infer_with_report`` takes its literal rows bit-packed, 32 to a word
  (``pack_literals_host``), and unpacks them inside the executable, so
  a host batch crosses host->device in 1/8 of its int8 bytes
  (``session.packed_uploads``, ``session.literal_upload_bytes``).

The legacy per-call kwargs (``impl=``, ``mesh=``, ``meter=``,
``meter_energy=``) keep working through thin shims on ``IMPACTSystem``
and ``IMPACTEngine`` that emit ``SpecDeprecationWarning`` and forward to
a session cached on the system, so old call sites run unchanged (and
bit-identically) while the repo itself is held warning-clean by the
tier-1 filter in ``pytest.ini``.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import vmem
from ..kernels import backends, ops
from ..kernels import packing as packing_mod
from ..kernels import ref as kernels_ref
from ..sharding import crossbar as crossbar_sh
from . import energy as energy_mod
from .energy import EnergyReport
from .yflash import I_CSA_THRESHOLD, T_READ, V_READ

Array = jax.Array

METERING_MODES = ("off", "staged", "fused")
PRECISIONS = ("float32",)
#: Clause-crossbar operand layouts: ``"none"`` streams f32 per-cell
#: currents, ``"2bit"`` packs the ternary cells into the
#: ``kernels.packing`` bitplane layout at session build (compile time)
#: — the executable's dominant operand shrinks ~16x and unpacking fuses
#: into the Pallas kernel.
PACKINGS = ("none", "2bit")

#: Canonical input dtypes of every session executable.  Callers may pass
#: bool / int / float {0,1} literals; the session casts ONCE before the
#: executable so AOT avals never fragment by caller dtype.
LITERAL_DTYPE = jnp.int8

#: ``infer_with_report`` takes its literal rows bit-packed: bit ``k % 32``
#: of word ``k // 32`` is literal ``k``, and the bits past the last
#: literal are 0.  A host batch crosses to the device as these words (8x
#: fewer bytes than int8 rows) and the executable unpacks them.
LITERAL_WORD_DTYPE = jnp.uint32
LITERALS_PER_WORD = 32


def literal_words(n_literals: int) -> int:
    """Words per bit-packed literal row."""
    return -(-n_literals // LITERALS_PER_WORD)


def pack_literals_host(literals) -> np.ndarray:
    """(B, K) {0,1} literal rows on the host -> (B, ``literal_words(K)``)
    uint32 words, in one ``np.packbits`` pass (any non-zero is a 1)."""
    lits = np.asarray(literals)
    if lits.dtype.kind not in "biu":
        lits = lits != 0
    B, K = lits.shape
    out = np.zeros((B, 4 * literal_words(K)), np.uint8)
    out[:, :-(-K // 8)] = np.packbits(lits, axis=1, bitorder="little")
    return out.view("<u4")


def pack_literals(literals: Array) -> Array:
    """``pack_literals_host`` traced, for a batch already on the device."""
    B, K = literals.shape
    W = literal_words(K)
    bits = jnp.pad((literals != 0).astype(LITERAL_WORD_DTYPE),
                   ((0, 0), (0, W * LITERALS_PER_WORD - K)))
    shifts = jnp.arange(LITERALS_PER_WORD, dtype=LITERAL_WORD_DTYPE)
    return jnp.sum(bits.reshape(B, W, LITERALS_PER_WORD) << shifts, axis=2,
                   dtype=LITERAL_WORD_DTYPE)


def unpack_literals(words: Array, n_literals: int) -> Array:
    """(B, W) bit-packed words -> (B, ``n_literals``) ``LITERAL_DTYPE``
    rows, traced: the inverse of ``pack_literals`` on {0,1} rows."""
    B, W = words.shape
    shifts = jnp.arange(LITERALS_PER_WORD, dtype=LITERAL_WORD_DTYPE)
    bits = (words[:, :, None] >> shifts) & 1
    return bits.reshape(B, W * LITERALS_PER_WORD)[:, :n_literals].astype(
        LITERAL_DTYPE)


class SpecDeprecationWarning(DeprecationWarning):
    """Per-call runtime-config kwargs (``impl=`` / ``mesh=`` / ``meter=``
    / ``meter_energy=``) are deprecated: encode them in a ``RuntimeSpec``
    and run through ``IMPACTSystem.compile(spec)``.  Tier-1 promotes this
    warning to an error for the repo's own callers (``pytest.ini``)."""


@dataclasses.dataclass(frozen=True)
class Topology:
    """Where the crossbar grid lives on the device mesh.

    ``mesh``: a jax Mesh with a ``model`` axis (and optional
    ``pod``/``data`` batch axes); ``None`` inherits the system-level mesh
    from ``build_system(..., mesh=...)``.  ``shard`` picks the placement
    of the (R, S) shard grid on the model axis — ``"auto"`` shards
    whatever divides (both, R-only, or S-only with the other operand
    replicated), ``"both"``/``"r"``/``"s"`` demand a placement (compile
    raises if the shard count doesn't divide), ``"none"`` forces the
    single-device kernels even on a meshed system.
    """
    mesh: Any = None
    shard: str = "auto"

    def __post_init__(self):
        if self.shard not in crossbar_sh.SHARD_MODES:
            raise ValueError(
                f"topology shard mode must be one of "
                f"{crossbar_sh.SHARD_MODES}, got {self.shard!r}")


@dataclasses.dataclass(frozen=True)
class TenantSpan:
    """Half-open block spans of ONE resident tenant inside a co-resident
    combined grid: literal rows ``[lit_lo, lit_hi)``, clause columns
    ``[col_lo, col_hi)``, class columns ``[cls_lo, cls_hi)``.  Produced
    by ``build_coresident`` — the spans ARE the block-diagonal placement,
    and everything off-block is 0 A by construction."""
    lit_lo: int
    lit_hi: int
    col_lo: int
    col_hi: int
    cls_lo: int
    cls_hi: int

    def __post_init__(self):
        for lo, hi, what in ((self.lit_lo, self.lit_hi, "literal"),
                             (self.col_lo, self.col_hi, "clause"),
                             (self.cls_lo, self.cls_hi, "class")):
            if not 0 <= lo < hi:
                raise ValueError(f"tenant {what} span [{lo}, {hi}) is "
                                 f"empty or negative")


@dataclasses.dataclass(frozen=True)
class CoResidentPlan:
    """Hashable placement of T tenants on one shared crossbar grid.

    Ordered, non-overlapping ``TenantSpan`` blocks; tenant t's *model
    id* is its index here, and a co-resident session's executables take
    a per-lane ``model_ids`` (B,) int32 operand selecting which tenant
    each slot-table lane belongs to.  A frozen ``RuntimeSpec`` carries
    the plan (``coresident=``), so session caching and retrace guards
    work unchanged.
    """
    spans: tuple[TenantSpan, ...]

    def __post_init__(self):
        object.__setattr__(self, "spans", tuple(self.spans))
        if not self.spans:
            raise ValueError("a CoResidentPlan needs at least one tenant")
        for a, b in zip(self.spans, self.spans[1:]):
            if (b.lit_lo < a.lit_hi or b.col_lo < a.col_hi
                    or b.cls_lo < a.cls_hi):
                raise ValueError(
                    "tenant spans must be ordered and non-overlapping "
                    f"(got {a} then {b})")

    @property
    def n_tenants(self) -> int:
        return len(self.spans)

    @property
    def clause_spans(self) -> tuple[tuple[int, int], ...]:
        return tuple((s.col_lo, s.col_hi) for s in self.spans)

    @property
    def class_spans(self) -> tuple[tuple[int, int], ...]:
        return tuple((s.cls_lo, s.cls_hi) for s in self.spans)

    @property
    def literal_spans(self) -> tuple[tuple[int, int], ...]:
        return tuple((s.lit_lo, s.lit_hi) for s in self.spans)

    def validate_against(self, system) -> None:
        last = self.spans[-1]
        if (last.lit_hi > system.n_literals
                or last.col_hi > system.n_clauses
                or last.cls_hi > system.n_classes):
            raise ValueError(
                f"co-resident plan {last} exceeds the combined grid "
                f"(K={system.n_literals}, n={system.n_clauses}, "
                f"M={system.n_classes}) — compile the plan against the "
                f"system build_coresident returned it with")


@dataclasses.dataclass(frozen=True)
class RuntimeSpec:
    """Declarative, hashable description of ONE inference runtime.

    Resolved exactly once by ``IMPACTSystem.compile`` — everything that
    used to be a per-call kwarg is a field here:

    ==================  =============================================
    field               replaces
    ==================  =============================================
    ``backend``         ``impl="pallas" | "xla"`` (registry key)
    ``topology``        ``mesh=`` threading (+ asymmetric placement)
    ``metering``        ``meter=`` / ``meter_energy=``
    ``interpret``       ``interpret=`` (None = auto off-TPU)
    ``capacity``        the serving slot-table shape (``max_batch``)
    ``batch_sizes``     extra predict shapes to AOT-compile eagerly
    ``packing``         (new) clause-operand layout, see ``PACKINGS``
    ==================  =============================================

    ``metering="fused"`` accumulates the read-energy meters INSIDE the
    fused kernel (a second VMEM accumulator over the column currents the
    datapath already computes), so ``infer_with_report`` and per-request
    billing ride the fused single-pass path at serving speed;
    ``"staged"`` meters on the staged per-shard path — the slower oracle
    the fused meters are pinned against; ``"off"`` serves through the
    fused kernel at max throughput and bills nothing.  On a sharded
    topology both metered modes lower to the same ``shard_map`` datapath
    (its per-device stages materialize the partial currents anyway, and
    the per-lane meters are psummed exactly once).  ``precision`` is
    validated for forward compatibility (the analog model is float32 end
    to end today).

    ``packing="2bit"`` compiles the COMPRESSED datapath: the session
    quantizes the clause crossbar to the 2-bit bitplane layout once at
    build time, the executables take the packed codes + dequant levels
    as operands (~16x smaller than the f32 currents), and the Pallas
    backend unpacks them inside the kernel.  Argmax parity with the unpacked
    path holds on every backend and shard plan (the CSA decision bits
    survive quantization); ``"none"`` (default) is the f32 datapath.

    ``coresident`` (a ``CoResidentPlan`` from ``build_coresident``)
    compiles the MULTI-TENANT datapath: the system is a block-diagonal
    combined grid, every executable takes a per-lane ``model_ids``
    operand, predictions are tenant-LOCAL (argmax restricted to the
    lane's own class span), and per-lane meters are tenant-pure.
    Composes with ``packing="2bit"`` and all four shard plans.
    """
    backend: str = "pallas"
    topology: Topology = Topology()
    metering: str = "staged"
    precision: str = "float32"
    packing: str = "none"
    interpret: bool | None = None
    capacity: int | None = None
    batch_sizes: tuple[int, ...] = ()
    coresident: CoResidentPlan | None = None
    #: VMEM budget (bytes/core) the static IR audit prices kernel
    #: working sets against; None = analysis.vmem default (16 MiB).
    vmem_budget_bytes: int | None = None

    def __post_init__(self):
        if self.metering not in METERING_MODES:
            raise ValueError(f"metering must be one of {METERING_MODES}, "
                             f"got {self.metering!r}")
        if self.precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {self.precision!r}")
        if self.packing not in PACKINGS:
            raise ValueError(f"packing must be one of {PACKINGS}, "
                             f"got {self.packing!r}")
        if self.capacity is not None and self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if self.vmem_budget_bytes is not None and self.vmem_budget_bytes < 1:
            raise ValueError(f"vmem_budget_bytes must be >= 1, "
                             f"got {self.vmem_budget_bytes}")
        object.__setattr__(self, "batch_sizes",
                           tuple(int(b) for b in self.batch_sizes))
        if any(b < 1 for b in self.batch_sizes):
            raise ValueError(f"batch_sizes must be >= 1, "
                             f"got {self.batch_sizes}")


@dataclasses.dataclass(frozen=True)
class InferenceResult:
    """Unified result of every session entry point.

    ``predictions`` is always set (sentinel -1 on invalid lanes for
    ``infer_step``); ``scores`` rides the fused paths that materialise
    class currents; ``report`` is the batch-level ``EnergyReport`` from
    ``infer_with_report``; the per-lane energies (J) ride ``infer_step``
    so a serving scheduler can bill each request individually.

    ``host_buffer`` (set by ``infer_step`` only) is the single-transfer
    host view of the three per-lane fields: one (3, B) float32 device
    array, rows predictions / clause joules / class joules, emitted by
    the same executable beside them.  ``InferenceSession.fetch`` copies
    it to the host in one transfer; the separate fields stay device
    arrays for callers that keep results on the device.  It is no
    ``__init__`` argument, so a result rebuilt from other fields
    (``dataclasses.replace``) carries none and cannot disagree with them.
    """
    predictions: Array
    scores: Array | None = None
    report: EnergyReport | None = None
    e_clause_lanes: Array | None = None
    e_class_lanes: Array | None = None
    host_buffer: Array | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)


class InferenceSession:
    """Immutable compiled runtime for one ``(IMPACTSystem, RuntimeSpec)``.

    Built by ``IMPACTSystem.compile(spec)`` (which caches sessions per
    spec — compiling the same spec twice returns the same session).  All
    spec resolution (backend lookup, mesh/shard-plan placement, each
    entry's datapath in ``routes``) happens here, once; the entry points
    only look up an executable and run it.

    Device->host traffic is one transfer per call: ``fetch`` brings an
    ``infer_step`` result's predictions and per-lane joules over as one
    buffer, and ``infer_with_report`` fetches its two joule figures as
    one vector.  ``fetch_count`` counts these transfers.

    Host->device, ``infer_with_report`` ships a host batch as bit-packed
    words (``packed_uploads`` counts such calls) and packs a batch that
    is already on the device there; ``literal_upload_bytes`` counts the
    literal bytes every entry shipped from the host.
    """

    def __init__(self, system, spec: RuntimeSpec):
        self.spec = spec
        self.system = system
        self.backend = backends.get_backend(spec.backend)
        top = spec.topology
        self.mesh = top.mesh if top.mesh is not None else system.mesh
        R, S = system.clause_i.shape[0], system.class_i.shape[0]
        self.plan = (crossbar_sh.shard_plan(self.mesh, R, S, top.shard)
                     if self.mesh is not None else None)
        if self.mesh is None and top.shard not in ("auto", "none"):
            raise ValueError(
                f"topology demands shard={top.shard!r} but neither the "
                f"spec nor the system provides a mesh")
        self._nonempty = system._nonempty_eff()
        # Co-residency: the spec's plan is validated against the combined
        # grid once, and the tenant span tables become small embedded
        # constants of every executable (the per-lane model_ids operand
        # indexes them at run time).
        self.coresident = spec.coresident
        if self.coresident is not None:
            self.coresident.validate_against(system)
            self._clause_spans = jnp.asarray(self.coresident.clause_spans,
                                             jnp.int32)
            self._class_spans = jnp.asarray(self.coresident.class_spans,
                                            jnp.int32)
        # Compile-time packing: the quantized clause operand is built
        # ONCE here (concrete arrays), so every executable of this
        # session takes the 2-bit codes + levels instead of the f32
        # currents — the compressed layout is a property of the session,
        # not of any call.
        self._packed = (packing_mod.pack_clause_operand(system.clause_i)
                        if spec.packing == "2bit" else None)
        # The datapath of each serving entry, decided here once from the
        # spec and the system: ``(route, meter)``, where ``meter`` says
        # whether the entry meters (``predict`` never does) and
        # ``route`` is ``"shard_map"`` on a mesh plan, ``"staged"`` (the
        # per-shard oracle) for co-resident sessions and for metered
        # entries under ``metering="staged"``, else the ``"packed"`` or
        # ``"fused"`` kernel.  The traced router (``_datapath``) and the
        # VMEM planner both read it.
        self.routes = {entry: (self._route(meter), meter)
                       for entry, meter in (
                           ("predict", False),
                           ("infer_step", self.meters_energy),
                           ("infer_with_report", self.meters_energy))}
        vmem.refuse_packed_over_budget(self)
        self._exes: dict[tuple[str, int], Any] = {}
        self._plans: dict[tuple[str, int], vmem.KernelPlan | None] = {}
        self._irs: dict[tuple[str, int], str] = {}
        self._traces: collections.Counter = collections.Counter()
        self._fetches = 0
        self._packed_uploads = 0
        self._literal_upload_bytes = 0
        # Packers of device-resident ``infer_with_report`` batches, one
        # per (batch, dtype).
        self._packers: dict[tuple[int, Any], Any] = {}
        # All-valid masks of ``infer_with_report(valid=None)``, made on
        # the device once per batch size.
        self._all_valid: dict[int, Array] = {}
        # Programming-time compilation: the serving sweep and any
        # declared predict shapes are executables before the first
        # request arrives.
        if spec.capacity is not None:
            self._exe("infer_step", spec.capacity)
        for b in spec.batch_sizes:
            self._exe("predict", b)

    def _route(self, meter: bool) -> str:
        if self.plan is not None:
            return "shard_map"
        if self.coresident is not None or (
                meter and self.spec.metering == "staged"):
            return "staged"
        return "packed" if self._packed is not None else "fused"

    # -- properties ---------------------------------------------------------
    @property
    def capacity(self) -> int | None:
        return self.spec.capacity

    @property
    def meters_energy(self) -> bool:
        return self.spec.metering != "off"

    @property
    def trace_count(self) -> int:
        """Total number of times any entry point's python body was traced
        (== number of compiles).  Frozen after warmup: the retrace-guard
        tests assert this does not move across serving."""
        return int(sum(self._traces.values()))

    @property
    def fetch_count(self) -> int:
        """Device->host transfers made by ``fetch`` and
        ``infer_with_report``: one per call on the results this session
        made."""
        return self._fetches

    @property
    def packed_uploads(self) -> int:
        """``infer_with_report`` calls whose host literals crossed to the
        device bit-packed."""
        return self._packed_uploads

    @property
    def literal_upload_bytes(self) -> int:
        """Bytes of literal operands shipped host->device by every entry:
        packed words for ``infer_with_report``, int8 rows otherwise.  A
        batch already on the device ships nothing."""
        return self._literal_upload_bytes

    def compiled_shapes(self, entry: str | None = None) -> list[tuple]:
        return sorted(k for k in self._exes
                      if entry is None or k[0] == entry)

    def is_compiled(self, entry: str, batch: int) -> bool:
        return (entry, batch) in self._exes

    def warm(self, batch: int, entry: str = "infer_step") -> None:
        """Ensure the ``(entry, batch)`` executable exists (AOT compile
        only — nothing is executed, unlike the old warmup sweeps)."""
        self._exe(entry, batch)

    def cost_analysis(self, entry: str, batch: int) -> dict[str, float]:
        """XLA's cost analysis of the ``(entry, batch)`` executable,
        normalized to ``{"flops", "bytes_accessed"}`` floats (missing
        counters report 0.0 — some lowerings omit them).  Compiles the
        executable on demand like every other session access; feeding
        the analytic cost model (``impact.costmodel``) this way means
        predictions always price the exact executable that serves."""
        exe = self._exe(entry, batch)
        ca = exe.cost_analysis()
        # jax has returned both a bare dict and a one-element list of
        # dicts across versions; normalize either.
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = ca or {}
        return dict(flops=float(ca.get("flops", 0.0)),
                    bytes_accessed=float(ca.get("bytes accessed", 0.0)))

    def kernel_plan(self, entry: str, batch: int) -> vmem.KernelPlan | None:
        """The kernel the ``(entry, batch)`` executable runs: variant,
        literal row-shards, grid extents along the literal and column
        axes, and VMEM bytes per grid step (``analysis.vmem``); ``None``
        on a reference backend.  Compiles on demand like every other
        session access."""
        self._exe(entry, batch)
        return self._plans[(entry, batch)]

    def ir_text(self, entry: str, batch: int) -> str:
        """Lowered StableHLO of the ``(entry, batch)`` executable — the
        exact artifact handed to XLA, captured at compile time.  Compiles
        on demand like every other session access."""
        self._exe(entry, batch)
        return self._irs[(entry, batch)]

    def audit(self, entry: str | None = None, batch: int | None = None, *,
              baselines=None):
        """Static IR audit of this session's executables (see
        ``analysis.ir_audit``): precision ladder (no f64, no sub-f32
        meters), host isolation (no callbacks/infeed/outfeed), Pallas
        VMEM working set vs ``spec.vmem_budget_bytes``, and executable
        fingerprints (diffed against ``baselines`` when given), and the
        kernel plan of each (``kernel_plan``).  Audits every compiled
        executable by default, or one ``(entry, batch)`` pair — compiling
        it on demand."""
        from ..analysis import ir_audit as _ir_audit
        if entry is not None and batch is not None:
            self._exe(entry, batch)
        return _ir_audit.audit_session(self, entry, batch,
                                       baselines=baselines)

    # -- entry points -------------------------------------------------------
    def _model_ids(self, model_ids, batch: int) -> Array | None:
        """Canonicalize the per-lane tenant selector: required (and only
        accepted) on a co-resident session."""
        if self.coresident is None:
            if model_ids is not None:
                raise ValueError(
                    "model_ids= only applies to a co-resident session "
                    "(RuntimeSpec(coresident=...))")
            return None
        if model_ids is None:
            raise ValueError(
                "a co-resident session needs model_ids (B,) int32 — "
                "which tenant does each lane belong to?")
        mids = jnp.asarray(model_ids, jnp.int32)
        if mids.shape != (batch,):
            raise ValueError(f"model_ids shape {mids.shape} does not "
                             f"match the batch ({batch},)")
        return mids

    def predict(self, literals, model_ids=None) -> InferenceResult:
        """Fast path: fused crossbar->CSA->class-sum scores + argmax.

        On a co-resident session ``model_ids`` (B,) int32 selects each
        lane's tenant; predictions are tenant-LOCAL class indices and
        ``scores`` is the combined (B, M_total) current vector (zero
        outside each lane's own class span).
        """
        lits = self._lits(literals)
        mids = self._model_ids(model_ids, lits.shape[0])
        exe = self._exe("predict", lits.shape[0])
        if mids is None:
            preds, scores = exe(lits, *self._operands())
        else:
            preds, scores = exe(lits, mids, *self._operands())
        return InferenceResult(predictions=preds, scores=scores)

    def infer_step(self, literals, valid, model_ids=None) -> InferenceResult:
        """One scheduler sweep over a fixed-capacity slot buffer.

        ``valid`` (B,) marks occupied lanes; invalid lanes predict the
        sentinel -1 and bill exactly zero.  Per-lane read energies are
        zeros when the spec's metering is ``"off"`` (fused-kernel path).
        On a co-resident session ``model_ids`` selects each lane's
        tenant and predictions are tenant-local.
        """
        lits = self._lits(literals)
        v = jnp.asarray(valid, jnp.bool_)
        mids = self._model_ids(model_ids, lits.shape[0])
        exe = self._exe("infer_step", lits.shape[0])
        if mids is None:
            preds, e_cl, e_cs, host = exe(lits, v, *self._operands())
        else:
            preds, e_cl, e_cs, host = exe(lits, v, mids, *self._operands())
        res = InferenceResult(predictions=preds, e_clause_lanes=e_cl,
                              e_class_lanes=e_cs)
        object.__setattr__(res, "host_buffer", host)
        return res

    def fetch(self, result: InferenceResult,
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """An ``infer_step`` result on the host: ``(predictions int32,
        clause joules float64, class joules float64)`` per lane, equal to
        ``np.asarray`` of the three fields.  One device->host transfer of
        ``host_buffer``; a result without one (rebuilt from other
        fields) costs a transfer per field.  The joules are float64
        before any caller sums them, so request bills add up to the
        float64 batch meter and not to float32 rounding."""
        if result.host_buffer is not None:
            lanes = self._to_host(result.host_buffer)
        else:
            lanes = [self._to_host(x) for x in (result.predictions,
                                                result.e_clause_lanes,
                                                result.e_class_lanes)]
        return (np.asarray(lanes[0], np.int32),
                np.asarray(lanes[1], np.float64),
                np.asarray(lanes[2], np.float64))

    def infer_with_report(self, literals, valid=None,
                          model_ids=None) -> InferenceResult:
        """Metered inference with the paper's batch-level ``EnergyReport``
        — a single fused pass under ``metering="fused"``, the staged
        per-shard path under ``"staged"`` (same joules either way).
        ``valid`` (B,) bool marks real lanes in a padded batch; padding
        lanes are excluded from the energy/ops/datapoint accounting and
        predict the sentinel -1 (same contract as ``infer_step``).

        The literals reach the executable bit-packed (``literal_words``):
        a host batch is packed on the host and uploaded as words, a
        ``jax.Array`` is packed where it lies."""
        if not self.meters_energy:
            raise RuntimeError(
                "this session was compiled with metering='off' — "
                "infer_with_report needs RuntimeSpec(metering='fused') "
                "(single-pass, serving speed) or 'staged' (the oracle)")
        words = self._words(literals)
        B = words.shape[0]
        if valid is None:
            v = self._all_valid.get(B)
            if v is None:
                v = self._all_valid[B] = jnp.ones((B,), jnp.bool_)
            n_dp = B
        else:
            v_np = np.asarray(valid, bool)
            v = jnp.asarray(v_np)
            n_dp = int(v_np.sum())
        mids = self._model_ids(model_ids, B)
        exe = self._exe("infer_with_report", B)
        if mids is None:
            preds, joules = exe(words, v, *self._operands())
        else:
            preds, joules = exe(words, v, mids, *self._operands())
        sys_ = self.system
        e_clause, e_class = (float(j) for j in self._to_host(joules))
        ops_xp = n_dp * (sys_.n_literals * sys_.n_clauses
                         + sys_.n_clauses * sys_.n_classes)
        report = EnergyReport(
            read_energy_j=e_clause + e_class,
            clause_energy_j=e_clause, class_energy_j=e_class,
            program_energy_j=sys_.encode_stats["program_energy_j"],
            erase_energy_j=sys_.encode_stats["erase_energy_j"],
            latency_s=sys_._grid_latency(), ops_crosspoint=ops_xp,
            datapoints=n_dp, area_mm2=sum(sys_.area_mm2().values()))
        return InferenceResult(predictions=preds, report=report)

    def ta_feedback(self, lit2, fired2, sel, match, hi, lo, include) -> Array:
        """CoTM Type I/II TA feedback deltas -> (K, n) int32 — the online
        trainer's compiled update primitive (arXiv:2408.09456), routed
        through the session's registered backend like every serving entry.

        ``lit2`` (2B, K) doubled literal rows; ``fired2``/``sel``/``match``
        (2B, n) feedback masks; ``hi``/``lo`` (K, n) int32 Bernoulli
        draws; ``include`` (K, n) current TA actions.  All stochastic
        draws are precomputed operands, so the Pallas kernel and the
        einsum oracle return bit-identical deltas (see
        ``kernels.ref.ta_feedback_ref``).
        """
        lit2 = jnp.asarray(lit2, LITERAL_DTYPE)
        exe = self._exe("ta_feedback", lit2.shape[0])
        return exe(lit2, jnp.asarray(fired2, jnp.bool_),
                   jnp.asarray(sel, jnp.bool_),
                   jnp.asarray(match, jnp.bool_),
                   jnp.asarray(hi, jnp.int32), jnp.asarray(lo, jnp.int32),
                   jnp.asarray(include, jnp.bool_))

    # -- compiled-function plumbing -----------------------------------------
    def _to_host(self, x: Array) -> np.ndarray:
        self._fetches += 1
        return np.asarray(x)

    def _lits(self, literals) -> Array:
        lits = jnp.asarray(literals, LITERAL_DTYPE)
        if not isinstance(literals, jax.Array):
            self._literal_upload_bytes += lits.nbytes
        return lits

    def _words(self, literals) -> Array:
        """(B, K) literal rows -> the bit-packed (B, W) words of
        ``infer_with_report``: packed on the host and uploaded, or, for a
        ``jax.Array``, packed on the device by a packer compiled once per
        (batch, dtype)."""
        on_device = isinstance(literals, jax.Array)
        if not on_device:
            literals = np.asarray(literals)
        K = self.system.n_literals
        if literals.ndim != 2 or literals.shape[1] != K:
            raise ValueError(f"literal rows of shape {literals.shape} do "
                             f"not have this system's K={K}")
        if on_device:
            key = (literals.shape[0], literals.dtype)
            packer = self._packers.get(key)
            if packer is None:
                packer = self._packers[key] = jax.jit(
                    self._pack_fn).lower(literals).compile()
            return packer(literals)
        words = pack_literals_host(literals)
        self._packed_uploads += 1
        self._literal_upload_bytes += words.nbytes
        return jax.device_put(words)

    def _operands(self) -> tuple[Array, ...]:
        """The weight-side executable operands: ``(clause_i, nonempty,
        class_i)`` unpacked, ``(bits, levels, nonempty, class_i)`` for a
        ``packing="2bit"`` session."""
        sys_ = self.system
        if self._packed is not None:
            return (self._packed.bits, self._packed.levels,
                    self._nonempty, sys_.class_i)
        return sys_.clause_i, self._nonempty, sys_.class_i

    def input_bytes(self, entry: str, batch: int) -> int:
        """Exact byte count of the ``(entry, batch)`` executable's input
        arrays per sweep (the HBM-resident operand footprint the sweep
        must stream).  Independent of XLA's ``cost_analysis`` counters —
        this is the layout-level number the packing gate compares."""
        K = self.system.n_literals
        if entry == "infer_with_report":
            n = (batch * literal_words(K)
                 * jnp.dtype(LITERAL_WORD_DTYPE).itemsize)
        else:
            n = batch * K * jnp.dtype(LITERAL_DTYPE).itemsize
        if entry != "predict":
            n += batch * jnp.dtype(jnp.bool_).itemsize      # valid mask
        if self.coresident is not None:
            n += batch * jnp.dtype(jnp.int32).itemsize      # model_ids
        for op in self._operands():
            n += op.size * op.dtype.itemsize
        return int(n)

    def _exe(self, entry: str, batch: int):
        key = (entry, batch)
        exe = self._exes.get(key)
        if exe is None:
            exe = self._compile_entry(entry, batch)
            self._exes[key] = exe
            self._plans[key] = vmem.session_plan(self, entry, batch)
        return exe

    def _compile_entry(self, entry: str, batch: int):
        sys_ = self.system
        if entry == "ta_feedback":
            # The feedback entry is span-independent (no weight-side
            # constants, no tenant routing): ``batch`` is the DOUBLED
            # update-row count 2B.
            K, n = sys_.n_literals, sys_.n_clauses
            row = lambda dt: jax.ShapeDtypeStruct((batch, n), dt)
            cell = lambda dt: jax.ShapeDtypeStruct((K, n), dt)
            lowered = jax.jit(self._ta_feedback_fn).lower(
                jax.ShapeDtypeStruct((batch, K), LITERAL_DTYPE),
                row(jnp.bool_), row(jnp.bool_), row(jnp.bool_),
                cell(jnp.int32), cell(jnp.int32), cell(jnp.bool_))
            self._irs[(entry, batch)] = lowered.as_text()
            return lowered.compile()
        lit = jax.ShapeDtypeStruct((batch, sys_.n_literals), LITERAL_DTYPE)
        valid = jax.ShapeDtypeStruct((batch,), jnp.bool_)
        # Co-resident executables take the per-lane tenant selector as
        # one extra runtime operand, between the masks and the
        # weight-side constants.
        args = ((jax.ShapeDtypeStruct((batch,), jnp.int32),)
                if self.coresident is not None else ()) + self._operands()
        if entry == "predict":
            lowered = jax.jit(self._predict_fn).lower(lit, *args)
        elif entry == "infer_step":
            lowered = jax.jit(self._infer_step_fn).lower(lit, valid, *args)
        elif entry == "infer_with_report":
            words = jax.ShapeDtypeStruct(
                (batch, literal_words(sys_.n_literals)), LITERAL_WORD_DTYPE)
            lowered = jax.jit(self._report_fn).lower(words, valid, *args)
        else:
            raise ValueError(f"unknown entry point {entry!r}")
        # The lowered StableHLO is the artifact the static IR audit
        # scans; keep the text (the Lowered object does not survive
        # .compile()) so audits never retrace or recompile.
        self._irs[(entry, batch)] = lowered.as_text()
        return lowered.compile()

    # The traced bodies below run ONLY inside ``.lower()`` — the trace
    # counter bumps are python side effects that count compilations.
    def _datapath(self, entry, literals, valid, model_ids, *operands):
        """The ONE traced router: -> (scores (B, m), per-lane summed
        clause currents (B,), per-lane summed class currents (B,)) of
        ``entry`` on its route (``self.routes``); the sums are zeros
        where the entry does not meter.  ``valid`` (B,) bool masks the
        lanes that bill, ``model_ids`` (B,) int32 is the co-resident
        tenant selector (None on a single-tenant session).

        Every route bills identically (pinned by the parity and property
        suites): per-lane meters are zero on invalid lanes and padding
        contributes zero current everywhere.  The fused and packed
        kernels meter every lane and mask invalid ones after (exact —
        meters are per-lane); the staged oracle and the shard_map
        lowering AND ``valid`` into the fired bits before the class
        drive.  A co-resident lane's fired bits are ANDed with its
        tenant's clause columns before the class drive on every route,
        so both per-lane meters are tenant-pure (foreign clause columns
        draw 0 A).  A ``packing="2bit"`` session meters the QUANTIZED
        currents (what the packed cells draw): the staged route
        dequantizes the same codes the packed kernel unpacks.
        """
        route, meter = self.routes[entry]
        interpret = self.spec.interpret
        tr = self.system.clause_i.shape[2]
        if self._packed is not None:
            bits, levels, nonempty, class_i = operands
            clause = packing_mod.PackedClause(bits=bits, levels=levels)
        else:
            clause, nonempty, class_i = operands
        if route == "staged":
            if self._packed is not None:
                clause = packing_mod.dequant_clause(bits, levels, tr)
            fired, i_clause = self.backend.impact_clause_bits(
                literals, clause, nonempty, thresh=I_CSA_THRESHOLD,
                interpret=interpret)
            if model_ids is not None:
                fired = jnp.logical_and(fired, self._co_lane_cols(model_ids))
            if meter:
                fired = jnp.logical_and(fired, valid[:, None])
                i_clause = i_clause * valid[:, None, None, None]
            scores, i_class = self.backend.impact_class_scores(
                fired, class_i, interpret=interpret)
            i_cl, i_cs = i_clause.sum(axis=(1, 2, 3)), i_class.sum(axis=(1, 2))
        else:
            sharded = route == "shard_map"
            out = ops.fused_datapath(
                self.backend, literals, clause, nonempty, class_i,
                thresh=I_CSA_THRESHOLD, meter=meter, tr=tr,
                interpret=interpret, mesh=self.mesh,
                plan=self.plan if sharded else None,
                valid=valid if sharded and meter else None,
                lane_cols=(None if model_ids is None
                           else self._co_lane_cols(model_ids)))
            scores, i_cl, i_cs = out if meter else (out, None, None)
            if meter and not sharded:
                v = valid.astype(scores.dtype)
                i_cl, i_cs = i_cl * v, i_cs * v
        if not meter:
            i_cl = i_cs = jnp.zeros((literals.shape[0],), jnp.float32)
        return scores, i_cl, i_cs

    def _split(self, args):
        """Entry args after the masks -> (model_ids or None, operands):
        co-resident executables take the tenant selector first."""
        if self.coresident is None:
            return None, args
        return args[0], args[1:]

    def _co_lane_cols(self, model_ids):
        """(B, n) per-lane clause-column ownership mask (the CSA gating
        step of co-residency — see ``kernels.ref.coresident_lane_mask``)."""
        return kernels_ref.coresident_lane_mask(
            model_ids, self._clause_spans, self.system.n_clauses)

    def _argmax(self, scores, model_ids):
        """Predictions; on a co-resident session tenant-LOCAL: each
        lane's argmax is restricted to its own class span and rebased to
        span-local indices, so a co-resident lane predicts exactly what a
        standalone single-tenant session would."""
        if model_ids is None:
            return jnp.argmax(scores, axis=-1)
        lo = self._class_spans[model_ids, 0]
        hi = self._class_spans[model_ids, 1]
        col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
        mask = jnp.logical_and(col >= lo[:, None], col < hi[:, None])
        masked = jnp.where(mask, scores, -jnp.inf)
        return jnp.argmax(masked, axis=-1).astype(jnp.int32) - lo

    def _ta_feedback_fn(self, lit2, fired2, sel, match, hi, lo, include):
        self._traces["ta_feedback"] += 1
        return self.backend.ta_feedback(lit2, fired2, sel, match, hi, lo,
                                        include,
                                        interpret=self.spec.interpret)

    def _predict_fn(self, literals, *args):
        self._traces["predict"] += 1
        model_ids, operands = self._split(args)
        scores, _, _ = self._datapath("predict", literals, None, model_ids,
                                      *operands)
        return self._argmax(scores, model_ids), scores

    def _infer_step_fn(self, literals, valid, *args):
        """Per-lane (predictions, clause joules, class joules) of one
        sweep, and the three stacked for one transfer; invalid lanes
        predict -1 and bill zero."""
        self._traces["infer_step"] += 1
        valid = valid.astype(bool)
        model_ids, operands = self._split(args)
        scores, i_cl, i_cs = self._datapath("infer_step", literals, valid,
                                            model_ids, *operands)
        e_cl, e_cs = (energy_mod.per_lane_read_energy(i_cl, i_cs)
                      if self.meters_energy else (i_cl, i_cs))
        preds = jnp.where(valid, self._argmax(scores, model_ids), -1)
        # float32 holds every prediction -1 .. m-1 exactly.
        host = jnp.stack([preds.astype(jnp.float32), e_cl, e_cs])
        return preds, e_cl, e_cs, host

    def _pack_fn(self, literals):
        self._traces["pack_literals"] += 1
        return pack_literals(literals)

    def _report_fn(self, words, valid, *args):
        """The metered batch from bit-packed literal words, unpacked
        here, ahead of the route, into the int8 rows every route reads."""
        self._traces["infer_with_report"] += 1
        literals = unpack_literals(words, self.system.n_literals)
        preds, i_cl, i_cs = self._report_sums(literals, valid, *args)
        # E = V_R * I * t_read, the same float32 products in the same
        # order as the per-lane meters.  XLA folds V_READ * T_READ into
        # one constant; V_READ is a power of two, so that is exact.
        return preds, jnp.stack([V_READ * i_cl * T_READ,
                                 V_READ * i_cs * T_READ])

    def _report_sums(self, literals, valid, *args):
        """(predictions, batch-summed clause current, batch-summed class
        current) of one metered batch."""
        valid = valid.astype(bool)
        model_ids, operands = self._split(args)
        scores, i_cl, i_cs = self._datapath("infer_with_report", literals,
                                            valid, model_ids, *operands)
        # Sentinel invalid lanes like infer_step does: the staged and
        # fused lowerings see different scores on an excluded lane (one
        # zeroes its clause drive, the other doesn't), so its argmax is
        # meaningless — mask it instead of leaking a mode-dependent value.
        return (jnp.where(valid, self._argmax(scores, model_ids), -1),
                i_cl.sum(), i_cs.sum())

    def __repr__(self) -> str:
        return (f"InferenceSession(backend={self.spec.backend!r}, "
                f"plan={self.plan}, metering={self.spec.metering!r}, "
                f"packing={self.spec.packing!r}, "
                f"capacity={self.spec.capacity}, "
                f"compiled={self.compiled_shapes()})")


def build_coresident(systems) -> tuple[Any, CoResidentPlan]:
    """Pack several small single-tile systems block-diagonally onto ONE
    shared crossbar grid -> ``(combined IMPACTSystem, CoResidentPlan)``.

    Tenant t's clause grid occupies literal rows ``[lit_lo, lit_hi)`` x
    clause columns ``[col_lo, col_hi)`` and its class grid clause rows
    ``[col_lo, col_hi)`` x class columns ``[cls_lo, cls_hi)``; every
    off-block cell holds 0 S / 0 A — a physically absent device — so
    cross-tenant current leakage is exactly zero by construction, not
    merely below a tolerance.  Member tile *padding* cells (rows/columns
    beyond each member's true dims) are dropped: only the real
    ``[:K_t, :n_t]`` / ``[:n_t, :M_t]`` regions are copied, which keeps
    score and argmax parity with each standalone session exact (padding
    rows float, padding columns never fire).

    Members must be single-tile (R = C = S = 1): co-residency is the
    many-small-models regime (IMBUE-style — several TM clause grids fit
    one crossbar's footprint); a model big enough to shard has the whole
    fabric to itself.  The combined grid must also still fit one tile of
    the first member's ``IMPACTConfig``.

    Compile with ``combined.compile(RuntimeSpec(coresident=plan, ...))``;
    tenant t's lanes pass ``model_ids == t``.
    """
    systems = list(systems)
    if not systems:
        raise ValueError("build_coresident needs at least one system")
    from .pipeline import IMPACTSystem  # avoid import cycle at module load

    for i, s in enumerate(systems):
        R, C = s.clause_i.shape[0], s.clause_i.shape[1]
        S = s.class_i.shape[0]
        if (R, C, S) != (1, 1, 1):
            raise ValueError(
                f"co-residency packs single-tile systems; member {i} has "
                f"a (R={R}, C={C}, S={S}) shard grid — a model that "
                f"large should own the fabric (shard it) instead of "
                f"co-residing")
    K_tot = sum(s.n_literals for s in systems)
    n_tot = sum(s.n_clauses for s in systems)
    M_tot = sum(s.n_classes for s in systems)
    cfg = systems[0].cfg
    if (K_tot > cfg.max_tile_rows or n_tot > cfg.max_tile_cols
            or n_tot > cfg.max_class_rows):
        raise ValueError(
            f"combined co-resident grid (K={K_tot}, n={n_tot}) does not "
            f"fit one tile (max_tile_rows={cfg.max_tile_rows}, "
            f"max_tile_cols={cfg.max_tile_cols}, "
            f"max_class_rows={cfg.max_class_rows}) — fewer residents per "
            f"fabric, or bigger tiles")

    clause_g = np.zeros((1, 1, K_tot, n_tot), np.float32)
    clause_i = np.zeros((1, 1, K_tot, n_tot), np.float32)
    nonempty = np.zeros((n_tot,), bool)
    class_g = np.zeros((1, n_tot, M_tot), np.float32)
    class_i = np.zeros((1, n_tot, M_tot), np.float32)
    spans = []
    k0 = c0 = m0 = 0
    prog = erase = 0.0
    for s in systems:
        K, n, M = s.n_literals, s.n_clauses, s.n_classes
        clause_g[0, 0, k0:k0 + K, c0:c0 + n] = np.asarray(
            s.clause_g[0, 0, :K, :n])
        clause_i[0, 0, k0:k0 + K, c0:c0 + n] = np.asarray(
            s.clause_i[0, 0, :K, :n])
        nonempty[c0:c0 + n] = np.asarray(s.nonempty[:n])
        class_g[0, c0:c0 + n, m0:m0 + M] = np.asarray(s.class_g[0, :n, :M])
        class_i[0, c0:c0 + n, m0:m0 + M] = np.asarray(s.class_i[0, :n, :M])
        spans.append(TenantSpan(lit_lo=k0, lit_hi=k0 + K,
                                col_lo=c0, col_hi=c0 + n,
                                cls_lo=m0, cls_hi=m0 + M))
        k0, c0, m0 = k0 + K, c0 + n, m0 + M
        prog += float(s.encode_stats.get("program_energy_j", 0.0))
        erase += float(s.encode_stats.get("erase_energy_j", 0.0))
    combined = IMPACTSystem(
        clause_g=jnp.asarray(clause_g), nonempty=jnp.asarray(nonempty),
        class_g=jnp.asarray(class_g), clause_i=jnp.asarray(clause_i),
        class_i=jnp.asarray(class_i), n_literals=K_tot, n_clauses=n_tot,
        n_classes=M_tot, cfg=cfg,
        encode_stats=dict(program_energy_j=prog, erase_energy_j=erase,
                          coresident_members=len(systems)),
        mesh=systems[0].mesh)
    return combined, CoResidentPlan(spans=tuple(spans))


def legacy_spec(*, impl: str | None = None, mesh=None,
                metering: str | None = None,
                capacity: int | None = None) -> RuntimeSpec:
    """Map the deprecated per-call kwargs onto a ``RuntimeSpec`` (the
    shims' forwarding table; see the migration table in the README)."""
    return RuntimeSpec(
        backend=impl if impl is not None else "pallas",
        topology=Topology(mesh=mesh),
        metering=metering if metering is not None else "staged",
        capacity=capacity)
