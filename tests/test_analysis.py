"""The static-analysis subsystem: every lint rule fires on a known-bad
fixture and stays quiet on the shipped tree; the IR audit flags injected
f64 widening, host callbacks, VMEM-busting budgets and fingerprint
drift, and passes the real compiled sessions clean.
"""
import dataclasses
import importlib.util
import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ir_audit, lint, vmem
from repro.core import CoTMConfig
from repro.core.cotm import CoTMParams
from repro.impact import IMPACTConfig, RuntimeSpec, build_system
from repro.kernels import backends

REPO = pathlib.Path(__file__).resolve().parent.parent
SERVE = "src/repro/serve/fixture.py"          # runtime-scoped path


def _lint(src: str, path: str = SERVE):
    return lint.lint_source(textwrap.dedent(src), path)


def _rules(findings, *, waived=False):
    return [f.rule for f in findings if f.waived == waived]


# -- layer 2: the lint rules -------------------------------------------------

def test_impact001_bare_assert_fires_in_scope_only():
    src = """
    def admit(reqs):
        assert reqs, "no requests"
        return reqs
    """
    assert _rules(_lint(src)) == ["IMPACT001"]
    assert _rules(_lint(src, "src/repro/kernels/fixture.py")) == []
    raised = """
    def admit(reqs):
        if not reqs:
            raise ValueError("no requests")
        return reqs
    """
    assert _rules(_lint(raised)) == []


def test_impact002_wall_clock_fires_only_with_injectable_clock():
    clocked = """
    import time

    class Engine:
        def __init__(self, clock=time.time):
            self.clock = clock

        def step(self):
            return time.monotonic()
    """
    assert _rules(_lint(clocked)) == ["IMPACT002"]
    unclocked = """
    import time

    def stamp():
        return time.time()
    """
    assert _rules(_lint(unclocked)) == []


def test_impact003_energy_sum_needs_f64_cast():
    dirty = """
    def bill(res):
        return sum(res.e_clause_lanes)
    """
    assert _rules(_lint(dirty)) == ["IMPACT003"]
    blessed = """
    import numpy as np

    def bill(res):
        return sum(np.asarray(res.e_clause_lanes, np.float64))
    """
    assert _rules(_lint(blessed)) == []
    tainted_name = """
    def bill(res):
        lanes = res.e_class_lanes
        total = lanes + lanes
        return total
    """
    assert _rules(_lint(tainted_name)) == ["IMPACT003"]


def test_impact004_backend_conformance():
    bad = """
    class Backend:
        def fused_impact(self, literals, clause_i, *, thresh):
            raise NotImplementedError

        def crossbar_mvm(self, drive, g):
            raise NotImplementedError

    def register_backend(b):
        pass

    class Partial(Backend):
        def fused_impact(self, literals, *, thresh):   # wrong arity
            return literals

    class Rogue:
        name = "rogue"

    register_backend(Partial())
    register_backend(Rogue())
    """
    path = "src/repro/kernels/fixture.py"
    rules = _rules(_lint(bad, path))
    # Partial: signature mismatch; Rogue: misses both primitives.
    assert rules.count("IMPACT004") == 3
    good = """
    class Backend:
        def fused_impact(self, literals, clause_i, *, thresh):
            raise NotImplementedError

    def register_backend(b):
        pass

    class Mine(Backend):
        def fused_impact(self, literals, clause_i, *, thresh,
                         interpret=None):
            return literals

    register_backend(Mine())
    """
    assert _rules(_lint(good, path)) == []


def test_impact005_shim_kwargs_outside_shims():
    src = """
    def run(session, lits, mesh):
        session.predict(lits, impl="pallas")
        session.infer_step(lits, None, meter=True)
        helper(lits, meter_energy=True)
        other(lits, impl="not-a-shimmed-callee")
    """
    assert _rules(_lint(src, "src/repro/impact/ops.py")) \
        == ["IMPACT005"] * 3
    # The shim modules themselves are exempt by design.
    assert _rules(_lint(src, "src/repro/impact/pipeline.py")) == []


def test_waiver_suppresses_but_is_counted():
    src = """
    def admit(reqs):
        assert reqs  # lint: waive IMPACT001 checked by caller
        return reqs
    """
    findings = _lint(src)
    assert _rules(findings) == []
    assert _rules(findings, waived=True) == ["IMPACT001"]


def test_syntax_error_is_an_unwaivable_finding():
    assert _rules(_lint("def broken(:\n")) == ["SYNTAX"]


def test_shipped_tree_is_lint_clean():
    findings = [f for f in lint.lint_tree(REPO) if not f.waived]
    assert findings == [], "\n".join(str(f) for f in findings)


# -- layer 1: IR audit on text -----------------------------------------------

F64_HLO = """\
module @jit_f attributes {mhlo.num_partitions = 1 : i32} {
  func.func public @main(%arg0: tensor<8x10xf32>) -> tensor<8x10xf64> {
    %0 = stablehlo.convert %arg0 : (tensor<8x10xf32>) -> tensor<8x10xf64>
    return %0 : tensor<8x10xf64>
  }
}
"""

CLEAN_HLO = """\
module @jit_f attributes {mhlo.num_partitions = 1 : i32} {
  func.func public @main(%arg0: tensor<8x10xf32>) -> tensor<8x10xf32> {
    %0 = stablehlo.add %arg0, %arg0 : tensor<8x10xf32>
    return %0 : tensor<8x10xf32>
  }
}
"""


def test_precision_scan_flags_every_wide_and_narrow_type():
    assert [f.check for f in ir_audit.scan_precision(F64_HLO)] \
        == ["precision"] * 3
    assert ir_audit.scan_precision(CLEAN_HLO) == []
    narrow = CLEAN_HLO.replace("tensor<8x10xf32>", "tensor<8x10xbf16>")
    msgs = [f.message for f in ir_audit.scan_precision(narrow)]
    assert msgs and all("bf16" in m for m in msgs)
    half = CLEAN_HLO.replace("tensor<8x10xf32>", "tensor<f16>")
    msgs = [f.message for f in ir_audit.scan_precision(half)]
    assert msgs and all("f16" in m and "bf16" not in m for m in msgs)


def test_host_io_scan():
    assert ir_audit.scan_host_io(CLEAN_HLO) == []
    bad = CLEAN_HLO.replace(
        "stablehlo.add %arg0, %arg0",
        'stablehlo.custom_call @xla_python_cpu_callback(%arg0)')
    findings = ir_audit.scan_host_io(bad)
    assert [f.check for f in findings] == ["host_io"]


def test_fingerprint_counts_ops_not_module_attributes():
    fp = ir_audit.fingerprint_text(CLEAN_HLO)
    assert fp["ops"] == {"func.func": 1, "stablehlo.add": 1}
    assert "mhlo.num_partitions" not in fp["ops"]
    drift = ir_audit.fingerprint_text(
        CLEAN_HLO.replace("stablehlo.add", "stablehlo.multiply"))
    deltas = ir_audit.diff_fingerprints(fp, drift)
    assert any("stablehlo.add" in d for d in deltas)
    assert ir_audit.diff_fingerprints(fp, fp) == []


def test_f64_widened_toy_executable_is_flagged():
    """A REAL lowered artifact with injected f64 widening (x64 mode), not
    just a crafted string, must trip the precision scan."""
    with jax.enable_x64(True):
        lowered = jax.jit(
            lambda x: jnp.asarray(x, jnp.float64) * 2.0,
        ).lower(jax.ShapeDtypeStruct((8,), jnp.float32))
        text = lowered.as_text()
    findings = ir_audit.audit_ir_text(text)
    assert any(f.check == "precision" and "f64" in f.message
               for f in findings)


# -- the VMEM estimator ------------------------------------------------------

def test_vmem_estimates_are_positive_and_ordered():
    ws = vmem.fused_working_set(R=1, C=1, tr=64, tc=32, M=4, metered=False)
    wm = vmem.fused_working_set(R=1, C=1, tr=64, tc=32, M=4, metered=True)
    assert 0 < ws.total_bytes < vmem.DEFAULT_VMEM_BUDGET_BYTES
    assert wm.total_bytes > ws.total_bytes          # meters cost VMEM
    assert wm.variant == "fused_impact_metered"
    # At realistic shard sizes the packed kernel's working set beats the
    # f32 one (the 1-byte pbits block replaces the 4-byte ccur block); at
    # tiny padded shapes the 4-bitplane drive dominates, so compare at a
    # full 512-row shard (tr4 = 512/4 = 128).
    big = vmem.fused_working_set(R=1, C=1, tr=512, tc=512, M=4,
                                 metered=False)
    packed = vmem.packed_working_set(R=1, tr4=128, n_clause=512,
                                     class_rows=512, M=4, metered=False)
    assert packed.total_bytes < big.total_bytes     # 2-bit beats f32
    mvm = vmem.mvm_working_set(k_rows=64)
    assert 0 < mvm.total_bytes < ws.total_bytes


@pytest.mark.parametrize("metered", [False, True])
def test_fused_working_set_is_independent_of_row_shards(metered):
    """A grid step of the fused kernel holds one literal row-shard: the
    10,000-literal text CoTM (R=5 shards of 2048 rows, 20 clause tiles
    of 512 columns, 2 classes) prices the same per step as one shard,
    within the default budget; only the grid's shard extent grows."""
    dims = dict(C=20, tr=2048, tc=512, M=2, metered=metered)
    one = vmem.fused_working_set(R=1, **dims)
    five = vmem.fused_working_set(R=5, **dims)
    assert one.total_bytes == five.total_bytes
    assert five.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES
    assert (one.literal_chunks, five.literal_chunks) == (1, 5)
    assert five.column_blocks == 10240 // 256
    # The packed kernel still holds every shard in one block.
    p1, p5 = (vmem.packed_working_set(R=r, tr4=512, n_clause=10240,
                                      class_rows=10240, M=2,
                                      metered=metered) for r in (1, 5))
    assert p5.total_bytes > p1.total_bytes and p5.literal_chunks == 1


@pytest.mark.parametrize("C,tc,tiling,blocks", [
    (1, 512, (1, 512, 256), 2),      # paper MNIST: one 2048x512 tile
    (2, 512, (2, 512, 256), 4),      # CIFAR-2: two tiles
    (20, 512, (20, 512, 256), 40),   # the 10,000-clause text CoTM
    (1, 128, (1, 128, 128), 1),      # at most 128 columns: 128-wide
    (3, 384, (1, 1280, 256), 5),     # not whole blocks: end to end
    (4, 16, (1, 128, 128), 1),       # narrow test tiles: end to end
])
def test_fused_column_blocks_walk_the_clause_grid(C, tc, tiling, blocks):
    """The fused kernel's column axis spans the clause grid's C*tc
    columns, however wide the class grid is; tiles a whole number of
    blocks wide are read in place, others are laid end to end."""
    from repro.kernels.fused_impact import column_tiling
    assert column_tiling(C, tc) == tiling
    ws = vmem.fused_working_set(R=1, C=C, tr=2048, tc=tc, M=10,
                                metered=True)
    assert ws.column_blocks == blocks
    assert ws.total_bytes <= vmem.DEFAULT_VMEM_BUDGET_BYTES


# -- session-level audit -----------------------------------------------------

@pytest.fixture(scope="module")
def small_system():
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


def test_session_executables_pass_the_audit(small_system):
    session = small_system.compile(RuntimeSpec(
        backend="pallas", metering="fused", batch_sizes=(8,), capacity=8))
    report = session.audit()
    assert report.ok, [str(f) for f in report.findings]
    assert set(report.fingerprints) == {"predict@8", "infer_step@8"}
    assert all(v > 0 for v in report.vmem_bytes.values())
    # The IR itself honors the precision ladder.
    ir = session.ir_text("predict", 8)
    assert "f64" not in ir and "custom_call" not in ir
    # Round-trips through JSON (the check_static report artifact).
    json.dumps(report.to_json())


def test_audit_records_kernel_plans(small_system):
    session = small_system.compile(RuntimeSpec(
        backend="pallas", metering="fused", batch_sizes=(8,), capacity=8))
    report = session.audit()
    assert set(report.plans) == set(report.fingerprints)
    plan = report.plans["infer_step@8"]
    assert plan == dataclasses.asdict(session.kernel_plan("infer_step", 8))
    assert plan["variant"] == "fused_impact_metered"
    assert plan["row_shards"] == plan["literal_chunks"] == 1
    # The clause grid's 512 columns in 256-wide blocks; the class grid's
    # 2048 rows do not widen the walk.
    assert plan["column_blocks"] == 2
    assert plan["vmem_step_bytes"] == report.vmem_bytes["infer_step@8"]
    assert report.to_json()["plans"] == report.plans
    oracle = small_system.compile(RuntimeSpec(backend="xla",
                                              batch_sizes=(8,)))
    assert oracle.kernel_plan("predict", 8) is None
    assert oracle.audit().plans == {}


def test_compile_refuses_over_budget_packed_spec(small_system):
    """The packed kernel holds every row-shard in one block; a spec
    whose packed working set exceeds its budget is refused at compile,
    before the TPU compiler could refuse its first executable.  The
    unpacked kernel at the same budget compiles, and the audit flags it
    (``test_vmem_busting_spec_is_flagged``)."""
    with pytest.raises(ValueError, match="packing='2bit'.*over the budget"):
        small_system.compile(RuntimeSpec(
            backend="pallas", packing="2bit",
            vmem_budget_bytes=4096))
    small_system.compile(RuntimeSpec(backend="pallas", packing="2bit"))
    small_system.compile(RuntimeSpec(backend="pallas",
                                     vmem_budget_bytes=4096))


def test_session_route_is_the_kernel_plan(small_system):
    """The session decides each entry's datapath once and the planner
    reads that decision: for every audited spec and a co-resident
    session, each entry's kernel plan names the kernel its route runs."""
    from repro.impact import build_coresident
    kernel = {("fused", False): "fused_impact",
              ("fused", True): "fused_impact_metered",
              ("packed", False): "fused_impact_packed",
              ("packed", True): "fused_impact_packed_metered",
              ("staged", False): "crossbar_mvm",
              ("staged", True): "crossbar_mvm"}
    sessions = {tag: small_system.compile(RuntimeSpec(**kw))
                for tag, kw in _load_check_static().AUDIT_SPECS}
    combined, plan = build_coresident([small_system, small_system])
    sessions["coresident"] = combined.compile(RuntimeSpec(
        backend="pallas", metering="fused", coresident=plan))
    assert sessions["fused"].routes == {
        "predict": ("fused", False), "infer_step": ("fused", True),
        "infer_with_report": ("fused", True)}
    assert sessions["staged"].routes["infer_step"] == ("staged", True)
    assert sessions["packed"].routes["predict"] == ("packed", False)
    assert {r for r, _ in sessions["coresident"].routes.values()} \
        == {"staged"}
    for tag, session in sessions.items():
        for entry, route in session.routes.items():
            got = session.kernel_plan(entry, 8)
            if session.backend.reference:
                assert got is None, (tag, entry)
            else:
                assert got.variant == kernel[route], (tag, entry, route)


def test_vmem_busting_spec_is_flagged(small_system):
    session = small_system.compile(RuntimeSpec(
        backend="pallas", metering="fused", batch_sizes=(8,),
        vmem_budget_bytes=1024))
    report = session.audit()
    assert not report.ok
    assert any(f.check == "vmem" and f.severity == "error"
               for f in report.findings)


def test_fingerprint_drift_is_detected(small_system):
    session = small_system.compile(RuntimeSpec(
        backend="pallas", metering="off", batch_sizes=(8,)))
    base = dict(session.audit().fingerprints)
    clean = session.audit(baselines=base)
    assert not any(f.check == "fingerprint" for f in clean.findings)
    perturbed = {k: {"ops": {"stablehlo.add": 1}, "n_ops": 1}
                 for k in base}
    drifted = session.audit(baselines=perturbed)
    assert any(f.check == "fingerprint" and f.severity == "warning"
               for f in drifted.findings)
    assert drifted.ok            # drift warns, never errors
    missing = session.audit(baselines={})
    assert any("no committed fingerprint baseline" in f.message
               for f in missing.findings)


def test_audit_compiles_on_demand_without_new_traces(small_system):
    session = small_system.compile(RuntimeSpec(
        backend="pallas", metering="off", batch_sizes=(4,)))
    before = session.trace_count
    session.audit("predict", 4)        # already compiled: no retrace
    assert session.trace_count == before
    report = session.audit("predict", 16)  # new shape: compiles once
    assert "predict@16" in report.fingerprints
    with pytest.raises(ValueError, match="no compiled executables"):
        ir_audit.audit_session(session, "infer_with_report", None)


def test_spec_validates_vmem_budget():
    with pytest.raises(ValueError, match="vmem_budget_bytes"):
        RuntimeSpec(vmem_budget_bytes=0)


def test_register_backend_enforces_primitive_contract():
    class Gutted(backends.Backend):
        name = "gutted-fixture"
        fused_impact = None            # deletes an inherited primitive

    with pytest.raises(TypeError, match="fused_impact"):
        backends.register_backend(Gutted())
    assert "gutted-fixture" not in backends.available_backends()
    missing = [p for p in backends.REQUIRED_PRIMITIVES
               if not callable(getattr(backends.Backend, p, None))]
    assert missing == []               # base class satisfies its contract


# -- the check_static driver -------------------------------------------------

def _load_check_static():
    path = REPO / "benchmarks" / "check_static.py"
    spec = importlib.util.spec_from_file_location("check_static", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_static_lint_only_exit_codes(tmp_path, capsys):
    check_static = _load_check_static()
    assert check_static.main(["--lint-only", "--root", str(REPO)]) == 0
    bad = tmp_path / "src" / "repro" / "serve"
    bad.mkdir(parents=True)
    (bad / "engine.py").write_text(
        "def admit(reqs):\n    assert reqs\n    return reqs\n")
    assert check_static.main(["--lint-only", "--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "::error file=src/repro/serve/engine.py" in out
    assert "IMPACT001" in out


def test_check_static_hlo_mode(tmp_path, capsys):
    check_static = _load_check_static()
    good = tmp_path / "clean.mlir"
    good.write_text(CLEAN_HLO)
    assert check_static.main(["--hlo", str(good)]) == 0
    bad = tmp_path / "f64.mlir"
    bad.write_text(F64_HLO)
    assert check_static.main(["--hlo", str(bad)]) == 1
    assert "STATIC GATE FAILED" in capsys.readouterr().out


def test_committed_fingerprint_baselines_exist():
    path = REPO / "benchmarks" / "baselines" / "IR_fingerprints.json"
    baselines = json.loads(path.read_text())
    assert set(baselines) >= {"fused", "staged", "packed", "oracle"}
    for tag, per_exe in baselines.items():
        for key, fp in per_exe.items():
            assert fp["n_ops"] > 0, (tag, key)
