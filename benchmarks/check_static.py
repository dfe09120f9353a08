"""CI static-analysis gate: contract lint + compiled-IR audit.

Two layers, one exit code (same contract as check_perf.py /
check_hygiene.py — 0 clean, 1 on any violation):

**Layer 2 — contract lint** (``repro.analysis.lint``, stdlib ``ast``
only, no jax needed): IMPACT001-005 over ``src/repro/**``.  Runs in the
jax-free hygiene CI job via ``--lint-only``.

**Layer 1 — IR audit** (``repro.analysis.ir_audit``, needs jax):
compiles a deterministic reference system under the representative
runtime specs (fused, staged, packed, oracle) and audits
every executable's lowered StableHLO — precision ladder (no f64, no
sub-f32 meters), host isolation (no callbacks/infeed/outfeed), Pallas
VMEM working set vs budget — and diffs each executable's op-histogram
fingerprint against ``benchmarks/baselines/IR_fingerprints.json``.
Fingerprint drift is reported as a warning (recorded, not gated): the
lowering legitimately moves across jax versions; refresh the committed
baselines with ``--update-baselines`` when a drift is intentional.

Usage:
    python benchmarks/check_static.py                # both layers
    python benchmarks/check_static.py --lint-only    # layer 2, no jax
    python benchmarks/check_static.py --hlo DUMP.mlir  # audit a raw dump
    python benchmarks/check_static.py --update-baselines
    python benchmarks/check_static.py --vmem-budget 1048576
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

BASELINES = os.path.join(REPO, "benchmarks", "baselines",
                         "IR_fingerprints.json")
REPORT = os.path.join(REPO, "artifacts", "STATIC_audit.json")

#: The audited runtime matrix: the kernel-variant families the sessions
#: route to (fused and metered-fused / staged oracle / bit-packed), and
#: the einsum oracle, each with one predict shape so the audit stays
#: cheap.
AUDIT_SPECS = (
    ("fused", dict(backend="pallas", metering="fused",
                   batch_sizes=(8,), capacity=8)),
    ("staged", dict(backend="pallas", metering="staged",
                    batch_sizes=(8,), capacity=8)),
    ("packed", dict(backend="pallas", packing="2bit",
                    batch_sizes=(8,))),
    ("oracle", dict(backend="xla", batch_sizes=(8,))),
)


def run_lint(root: str) -> list[str]:
    """Layer 2 over ``root`` -> list of failure strings."""
    from repro.analysis import lint
    findings = lint.lint_tree(root)
    active = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    for f in waived:
        print(f"  waived: {f}")
    for f in active:
        # GitHub annotation on the offending line.
        print(f"::error file={f.path},line={f.line}::{f.rule}: {f.message}")
    print(f"lint: {len(active)} finding(s), {len(waived)} waived "
          f"({sum(1 for _ in lint.iter_target_files(root))} files)")
    return [str(f) for f in active]


def _reference_system():
    """The deterministic small system every audit run compiles — fixed
    seeds so executable fingerprints are reproducible run to run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import CoTMConfig
    from repro.core.cotm import CoTMParams
    from repro.impact import IMPACTConfig, build_system

    K, n, m, n_states = 64, 32, 4, 64
    cfg = CoTMConfig(n_literals=K, n_clauses=n, n_classes=m,
                     n_states=n_states)
    rng = np.random.default_rng(0)
    ta = np.where(rng.random((K, n)) < 0.1, n_states + 1, n_states)
    w = rng.integers(-20, 20, (m, n))
    params = CoTMParams(ta_state=jnp.asarray(ta, jnp.int32),
                        weights=jnp.asarray(w, jnp.int32))
    return build_system(params, cfg, jax.random.key(0),
                        IMPACTConfig(variability=False, finetune=False))


def run_audit(vmem_budget: int | None,
              update_baselines: bool) -> tuple[list[str], dict]:
    """Layer 1 -> (failures, report-JSON dict)."""
    from repro.impact import RuntimeSpec

    baselines = None
    if os.path.exists(BASELINES) and not update_baselines:
        with open(BASELINES) as f:
            baselines = json.load(f)
    elif not update_baselines:
        print(f"  note: no committed baselines at {BASELINES} — "
              f"run --update-baselines to record them")

    system = _reference_system()
    failures: list[str] = []
    report: dict = {"sessions": {}}
    new_baselines: dict = {}
    for tag, kw in AUDIT_SPECS:
        if vmem_budget is not None:
            kw = dict(kw, vmem_budget_bytes=vmem_budget)
        try:
            session = system.compile(RuntimeSpec(**kw))
        except ValueError as e:         # refused at compile (over budget)
            print(f"  audit[{tag}]: refused at compile: {e}")
            failures.append(f"audit[{tag}]: {e}")
            continue
        # The online-training feedback executable rides every
        # non-co-resident session: audit it alongside the serving
        # entries (batch 8 = the doubled 2B feedback row count).
        session.warm(8, "ta_feedback")
        base = (baselines or {}).get(tag)
        rep = session.audit(baselines=base)
        report["sessions"][tag] = rep.to_json()
        new_baselines[tag] = rep.fingerprints
        n_err = sum(f.severity == "error" for f in rep.findings)
        n_warn = len(rep.findings) - n_err
        print(f"  audit[{tag}]: {len(rep.fingerprints)} executable(s), "
              f"{n_err} error(s), {n_warn} warning(s), "
              f"vmem max {max(rep.vmem_bytes.values(), default=0)} B "
              f"/ budget {rep.vmem_budget_bytes} B")
        for f in rep.findings:
            print(f"    {f.severity}: {f}")
            if f.severity == "error":
                failures.append(f"audit[{tag}]: {f}")
    if update_baselines:
        os.makedirs(os.path.dirname(BASELINES), exist_ok=True)
        with open(BASELINES, "w") as f:
            json.dump(new_baselines, f, indent=1, sort_keys=True)
        print(f"  wrote {BASELINES}")
    return failures, report


def run_hlo(path: str) -> list[str]:
    """Audit a raw StableHLO text dump (precision + host-IO scans)."""
    from repro.analysis import ir_audit
    with open(path) as f:
        text = f.read()
    findings = ir_audit.audit_ir_text(text, entry=os.path.basename(path))
    for f in findings:
        print(f"  {f.severity}: {f}")
    print(f"hlo audit: {len(findings)} finding(s) in {path}")
    return [str(f) for f in findings if f.severity == "error"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lint-only", action="store_true",
                    help="run only the stdlib contract lint (no jax)")
    ap.add_argument("--root", default=REPO,
                    help="repo root to lint (default: this repo)")
    ap.add_argument("--hlo", default=None,
                    help="audit a raw StableHLO text file instead of "
                         "compiling sessions")
    ap.add_argument("--vmem-budget", type=int, default=None,
                    help="override RuntimeSpec.vmem_budget_bytes for the "
                         "audited sessions")
    ap.add_argument("--update-baselines", action="store_true",
                    help="re-record benchmarks/baselines/"
                         "IR_fingerprints.json from this run")
    ap.add_argument("--report", default=REPORT,
                    help=f"audit report JSON path (default {REPORT})")
    args = ap.parse_args(argv)

    if args.hlo:
        failures = run_hlo(args.hlo)
    else:
        failures = run_lint(args.root)
        if not args.lint_only:
            audit_failures, report = run_audit(args.vmem_budget,
                                               args.update_baselines)
            failures += audit_failures
            os.makedirs(os.path.dirname(args.report), exist_ok=True)
            with open(args.report, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
            print(f"  wrote {args.report}")

    if failures:
        print("\nSTATIC GATE FAILED:")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    print("static gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
