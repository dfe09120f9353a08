"""Layer 1: StableHLO audit of compiled session executables.

``InferenceSession`` keeps the lowered StableHLO text of every AOT
executable (``session.ir_text(entry, batch)``); this module walks that
text and proves three datapath invariants *of the artifact XLA will
actually run*, not of the python that generated it:

* **Precision ladder** — the analog datapath is f32 end to end and the
  energy/billing ladder widens to f64 only on the host (numpy, after
  device transfer).  So a session executable must contain NO f64 type
  anywhere (an in-graph f64 means billing math leaked into the
  executable, or a numpy float64 constant got traced in), and no
  f16/bf16 (a sub-f32 meter accumulation silently loses billing
  precision at serving batch sizes).
* **Host isolation** — executables must be pure device programs: no
  ``custom_call`` (the lowering target of ``io_callback`` /
  ``pure_callback`` / ``debug.print``), no infeed/outfeed/send/recv.
  A host callback in the sweep loop would serialize every scheduler
  sweep on the python GIL.
* **VMEM budget** — the Pallas working set priced by ``analysis.vmem``
  must fit ``RuntimeSpec.vmem_budget_bytes`` (default 16 MiB/core); the
  report keeps each executable's kernel plan beside it.

It also fingerprints each executable (op histogram + operand bytes) so
CI can diff the lowered artifact against a committed baseline: a jax
upgrade or refactor that reroutes a session through a different kernel
variant shows up as a fingerprint drift even when numerics still pass.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Iterable

from . import vmem

# -- findings ---------------------------------------------------------------

SEVERITIES = ("error", "warning", "info")


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One violation in one executable's lowered IR."""
    check: str            # "precision" | "host_io" | "vmem" | "fingerprint"
    severity: str         # one of SEVERITIES
    entry: str            # session entry point ("predict", ...)
    batch: int
    message: str
    line: int | None = None   # 1-based line in the IR text, when line-anchored

    def __str__(self) -> str:
        where = f"{self.entry}@{self.batch}"
        if self.line is not None:
            where += f":{self.line}"
        return f"[{self.check}] {where}: {self.message}"


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """Every finding plus the per-executable evidence the gate records."""
    findings: tuple[AuditFinding, ...]
    fingerprints: dict[str, dict[str, Any]]      # "entry@batch" -> fingerprint
    vmem_bytes: dict[str, int]                   # "entry@batch" -> working set
    vmem_budget_bytes: int
    plans: dict[str, dict[str, Any]] = dataclasses.field(
        default_factory=dict)                    # "entry@batch" -> KernelPlan

    @property
    def ok(self) -> bool:
        return not any(f.severity == "error" for f in self.findings)

    def to_json(self) -> dict[str, Any]:
        return {
            "ok": self.ok,
            "findings": [dataclasses.asdict(f) for f in self.findings],
            "fingerprints": self.fingerprints,
            "vmem_bytes": self.vmem_bytes,
            "vmem_budget_bytes": self.vmem_budget_bytes,
            "plans": self.plans,
        }


# -- precision ladder -------------------------------------------------------

# StableHLO glues the dtype token to the dims with 'x'
# (tensor<8x10xf64>) or opens with it (tensor<f64>), so a plain \b
# boundary never fires — allow either an 'x' or a true non-word char
# before the token.  The guard keeps identifiers (my_f64_helper) out.
_F64_RE = re.compile(r"(?:(?<=x)|(?<![0-9a-zA-Z_]))f64\b")
_BF16_RE = re.compile(r"(?:(?<=x)|(?<![0-9a-zA-Z_]))bf16\b")
# f16 but not bf16: the 'b' of xbf16 fails both lookbehinds.
_F16_RE = re.compile(r"(?:(?<=x)|(?<![0-9a-zA-Z_]))f16\b")

_HOST_IO_RE = re.compile(
    r"stablehlo\.(custom_call|infeed|outfeed|send|recv)\b|"
    r"\b(io_callback|pure_callback|python_callback|CustomCall)\b")


def scan_precision(ir_text: str, *, entry: str = "?",
                   batch: int = 0) -> list[AuditFinding]:
    """Flag every IR line carrying an f64 / bf16 / f16 type."""
    findings = []
    for i, line in enumerate(ir_text.splitlines(), start=1):
        if _F64_RE.search(line):
            findings.append(AuditFinding(
                "precision", "error", entry, batch,
                "f64 type in executable — billing/energy widening must "
                "stay host-side (numpy), the device program is f32",
                line=i))
        elif _BF16_RE.search(line):
            findings.append(AuditFinding(
                "precision", "error", entry, batch,
                "bf16 type in executable — sub-f32 meter accumulation "
                "loses billing precision", line=i))
        elif _F16_RE.search(line):
            findings.append(AuditFinding(
                "precision", "error", entry, batch,
                "f16 type in executable — sub-f32 meter accumulation "
                "loses billing precision", line=i))
    return findings


def scan_host_io(ir_text: str, *, entry: str = "?",
                 batch: int = 0) -> list[AuditFinding]:
    """Flag host round-trips: custom_call/callback/infeed/outfeed."""
    findings = []
    for i, line in enumerate(ir_text.splitlines(), start=1):
        m = _HOST_IO_RE.search(line)
        if m:
            findings.append(AuditFinding(
                "host_io", "error", entry, batch,
                f"host round-trip op ({m.group(0)}) in executable — "
                "sweeps must be pure device programs", line=i))
    return findings


# -- fingerprints -----------------------------------------------------------

# Only structural dialect ops count toward the histogram; module
# attributes like mhlo.num_partitions must not (they look like op names
# to a broad regex but are metadata).
_OP_RE = re.compile(r"\b((?:stablehlo|func)\.[a-z_]+)\b")


def fingerprint_text(ir_text: str) -> dict[str, Any]:
    """Histogram of StableHLO ops — a cheap structural hash of the
    lowering.  Two executables with the same fingerprint route through
    the same kernel composition even if constants differ."""
    hist: dict[str, int] = {}
    for m in _OP_RE.finditer(ir_text):
        op = m.group(1)
        hist[op] = hist.get(op, 0) + 1
    return {"ops": dict(sorted(hist.items())), "n_ops": sum(hist.values())}


def diff_fingerprints(baseline: dict[str, Any],
                      current: dict[str, Any]) -> list[str]:
    """Human-readable op-histogram deltas (empty list == match)."""
    deltas = []
    b_ops, c_ops = baseline.get("ops", {}), current.get("ops", {})
    for op in sorted(set(b_ops) | set(c_ops)):
        b, c = b_ops.get(op, 0), c_ops.get(op, 0)
        if b != c:
            deltas.append(f"{op}: {b} -> {c}")
    return deltas


# -- the session-level audit ------------------------------------------------

def _keys(session, entry, batch) -> Iterable[tuple[str, int]]:
    if entry is not None and batch is not None:
        return [(entry, int(batch))]
    keys = session.compiled_shapes(entry)
    if not keys:
        raise ValueError(
            "session has no compiled executables to audit — call "
            "session.warm(batch, entry) (or set capacity/batch_sizes on "
            "the spec) first")
    return keys


def audit_session(session, entry: str | None = None,
                  batch: int | None = None, *,
                  baselines: dict[str, dict[str, Any]] | None = None,
                  ) -> AuditReport:
    """Audit the session's compiled executables (all of them by default,
    or one ``(entry, batch)`` pair).

    ``baselines`` maps ``"entry@batch"`` to a committed fingerprint; a
    mismatch is a *warning* (drift is evidence, not automatically a
    bug — ``check_static.py --update-baselines`` re-records it).
    """
    findings: list[AuditFinding] = []
    fingerprints: dict[str, dict[str, Any]] = {}
    vmem_bytes: dict[str, int] = {}
    plans: dict[str, dict[str, Any]] = {}
    budget = (session.spec.vmem_budget_bytes
              or vmem.DEFAULT_VMEM_BUDGET_BYTES)

    for e, b in _keys(session, entry, batch):
        ir = session.ir_text(e, b)
        tag = f"{e}@{b}"
        findings += scan_precision(ir, entry=e, batch=b)
        findings += scan_host_io(ir, entry=e, batch=b)
        fingerprints[tag] = fingerprint_text(ir)

        plan = session.kernel_plan(e, b)
        if plan is not None:
            plans[tag] = dataclasses.asdict(plan)
            vmem_bytes[tag] = plan.vmem_step_bytes
            if plan.vmem_step_bytes > budget:
                findings.append(AuditFinding(
                    "vmem", "error", e, b,
                    f"{plan.variant} working set {plan.vmem_step_bytes} B "
                    f"exceeds the VMEM budget {budget} B "
                    f"(blocks x{vmem.PIPELINE_BUFFERS} + scratch)"))

        if baselines is not None:
            base = baselines.get(tag)
            if base is None:
                findings.append(AuditFinding(
                    "fingerprint", "warning", e, b,
                    "no committed fingerprint baseline for this "
                    "executable — run check_static.py --update-baselines"))
            else:
                deltas = diff_fingerprints(base, fingerprints[tag])
                if deltas:
                    findings.append(AuditFinding(
                        "fingerprint", "warning", e, b,
                        "lowered-op histogram drifted from baseline: "
                        + "; ".join(deltas[:8])
                        + ("; ..." if len(deltas) > 8 else "")))

    return AuditReport(findings=tuple(findings), fingerprints=fingerprints,
                       vmem_bytes=vmem_bytes, vmem_budget_bytes=budget,
                       plans=plans)


def audit_ir_text(ir_text: str, *, entry: str = "hlo",
                  batch: int = 0) -> list[AuditFinding]:
    """Audit a bare StableHLO dump (no session): precision + host IO.
    This is the ``check_static.py --hlo FILE`` path and what the tests
    feed known-bad toy modules through."""
    return (scan_precision(ir_text, entry=entry, batch=batch)
            + scan_host_io(ir_text, entry=entry, batch=batch))
