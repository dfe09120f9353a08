"""CI perf gate: fail when samples/s regresses against a committed baseline.

Usage:
    python benchmarks/check_perf.py CURRENT.json BASELINE.json \
        [--max-regression 0.30] [--serve BENCH_serve.json] \
        [--train BENCH_train.json]

Compares the ``normalized`` samples/s ratios of ``BENCH_throughput.json``
(each path's samples/s divided by its impl family's in-run reference at
the smallest batch) rather than raw samples/s: a machine-speed difference
between the baseline machine and the CI runner cancels out within a
family (Pallas interpret mode and multithreaded XLA scale differently
with core count, so families are never cross-ratioed), while a
batch-scaling or engine-overhead regression local to one path does not.
A key is a failure when its ratio drops more than ``--max-regression``
(default 30%) below baseline, or when it disappears from the current
run.  A cpu-count mismatch between baseline and current machines is
printed as a warning — if the runner class changes, refresh
``benchmarks/baselines/`` from the perf-smoke artifact of a trusted run.

With ``--serve`` the gate also enforces the continuous-batching
acceptance invariant recorded in ``BENCH_serve.json``: continuous p95
per-request latency strictly below flush-to-completion p95 on the same
Poisson trace.

The ``metered`` section (always produced) is gated on three invariants:
it must exist, the fused-metered and staged-metered runs must have
agreed on argmax and per-lane joules (``parity_ok``), and fused-metered
throughput must stay within a generous floor of the unmetered fused
kernel (the in-kernel meter's whole point is that billing is nearly
free; a collapse of that ratio is a regression even when every absolute
number moved).  The fused/staged ratio is printed for the record — on
CPU interpret mode it gauges dispatch plumbing, not TPU speed.

The ``compressed`` section (always produced) gates the bit-packed
datapath: packed-backend argmax parity against both the int8 fused
kernel and the einsum oracle, per-batch int8/packed byte-traffic ratios
(XLA ``bytes_accessed`` AND the exact operand ``input_bytes``) at a
>= 4x floor, and a non-degenerate clause-pruning record.

The ``predicted_vs_measured`` section (always produced) is the
calibrated analytic cost model's self-check: every session executable's
predicted sweep time must land within the recorded band of its measured
warm-sweep time, and the raw executable-cost ordering invariants
(metered >= unmetered) must hold — a flip means the lowering lost the
in-kernel meter.

When the current run carries a ``sharded`` section (multi-device hosts:
the CI multi-device leg runs the benchmark under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), the gate also
requires argmax parity between the shard_map crossbar lowering and the
single-device kernel, and prints the sharded/single throughput ratios.

Stdlib-only on purpose — runs before (and regardless of) the jax install.
"""
from __future__ import annotations

import argparse
import json
import sys


def check_throughput(current: dict, baseline: dict,
                     max_regression: float) -> list[str]:
    failures = []
    cur = current.get("normalized", {})
    base = baseline.get("normalized", {})
    if not base:
        failures.append("baseline has no 'normalized' section")
    b_cpu = baseline.get("machine", {}).get("cpu_count")
    c_cpu = current.get("machine", {}).get("cpu_count")
    if b_cpu != c_cpu:
        print(f"  WARNING: baseline machine had cpu_count={b_cpu}, this "
              f"run has {c_cpu} — within-family ratios should still hold, "
              f"but refresh the baseline if the runner class changed")
    for key, b in sorted(base.items()):
        c = cur.get(key)
        if c is None:
            failures.append(f"{key}: missing from current run")
            continue
        floor = b * (1.0 - max_regression)
        verdict = "FAIL" if c < floor else "ok"
        print(f"  {key:24s} baseline {b:8.3f}  current {c:8.3f}  "
              f"floor {floor:8.3f}  {verdict}")
        if c < floor:
            failures.append(
                f"{key}: normalized samples/s {c:.3f} < floor {floor:.3f} "
                f"(baseline {b:.3f}, max regression {max_regression:.0%})")
    return failures


def check_sharded(current: dict) -> list[str]:
    """Gate the sharded-vs-single-device sweep when this run produced one
    (multi-device hosts; the CI multi-device leg).  Argmax parity between
    the shard_map lowering and the single-device kernel is a hard
    invariant; throughput ratios are printed for the record but not
    floored against a baseline (host-device psum overhead on CPU says
    nothing about TPU ICI behaviour)."""
    sharded = current.get("sharded")
    if not sharded:
        print("  (no sharded sweep in this run: single-device host)")
        return []
    mesh = sharded.get("mesh", {})
    print(f"  sharded sweep: {sharded.get('n_devices')} devices, "
          f"mesh {mesh}, grid {sharded.get('grid')}")
    for b, ratio in sorted(
            sharded.get("speedup_sharded_over_single", {}).items(),
            key=lambda kv: int(kv[0].lstrip("b"))):
        print(f"    {b:8s} sharded/single samples/s ratio {ratio:8.3f}")
    if not sharded.get("parity_ok"):
        return ["sharded sweep: shard_map predictions diverged from the "
                "single-device kernel (parity_ok is false)"]
    return []


def check_roofline(current: dict) -> list[str]:
    """Require the roofline section (every run must place its compiled
    executables on the roofline) and print it for the record — the
    values themselves are NOT gated: CI interpret-mode-on-CPU achieved
    fractions say nothing about TPU behaviour, the section exists so
    the scoreboard is never silently dropped."""
    roof = current.get("roofline")
    if not roof:
        return ["roofline: section missing from BENCH_throughput.json "
                "(benchmarks/roofline.py impact_roofline must run in "
                "every sweep)"]
    sessions = roof.get("sessions", {})
    if not sessions:
        return ["roofline: section has no per-session rows"]
    bad = [k for k, r in sessions.items()
           if not all(key in r for key in
                      ("intensity_flops_per_byte", "bound_side",
                       "roofline_bound_samples_per_s"))]
    if bad:
        return [f"roofline: malformed rows (missing keys): {sorted(bad)}"]
    print(f"  roofline ({roof.get('entry')}, peak {roof.get('peak_flops'):.3g}"
          f" flop/s, hbm {roof.get('hbm_bw'):.3g} B/s):")
    for key, r in sorted(sessions.items()):
        ach = r.get("achieved_fraction")
        print(f"    {key:14s} intensity {r['intensity_flops_per_byte']:8.2f} "
              f"flop/B  bound={r['bound_side']:7s} "
              f"cap {r['roofline_bound_samples_per_s']:12.3e} samples/s  "
              f"achieved {'n/a' if ach is None else f'{ach:.2e}'}")
    return []


def check_metered(current: dict, min_fused_ratio: float = 0.25) -> list[str]:
    """Gate the in-kernel-metering sweep: the section is mandatory (the
    benchmark always produces it), the fused and staged meters must have
    agreed (argmax + per-lane joules), and the fused-metered kernel must
    hold a sane fraction of unmetered-fused throughput.  The floor is
    deliberately loose — CPU interpret mode prices kernel dispatch, not
    the TPU meter — but a collapse below it means the metered path fell
    off the fused kernel entirely."""
    metered = current.get("metered")
    if not metered:
        return ["metered sweep missing from BENCH_throughput.json "
                "(benchmarks.impact_throughput must always produce it)"]
    failures = []
    for b, ratio in sorted(
            metered.get("ratio_fused_metered_over_unmetered", {}).items(),
            key=lambda kv: int(kv[0].lstrip("b"))):
        verdict = "FAIL" if ratio < min_fused_ratio else "ok"
        print(f"  metered {b:6s} fused/unmetered samples/s ratio "
              f"{ratio:6.3f}  floor {min_fused_ratio:.2f}  {verdict}")
        if ratio < min_fused_ratio:
            failures.append(
                f"metered {b}: fused-metered throughput fell to "
                f"{ratio:.3f}x of the unmetered fused kernel "
                f"(floor {min_fused_ratio})")
    for b, ratio in sorted(
            metered.get("ratio_fused_metered_over_staged", {}).items(),
            key=lambda kv: int(kv[0].lstrip("b"))):
        print(f"  metered {b:6s} fused/staged    samples/s ratio "
              f"{ratio:6.3f}  (for the record)")
    if not metered.get("parity_ok"):
        failures.append(
            "metered sweep: fused-metered argmax or per-lane joules "
            "diverged from the staged oracle (parity_ok is false)")
    return failures


def check_compressed(current: dict, min_bytes_ratio: float = 4.0) -> list[str]:
    """Gate the compressed-datapath sweep: the section is mandatory (the
    benchmark always produces it), the packed kernel must have agreed
    on argmax with both the int8 fused kernel and the einsum oracle
    (``parity_ok``), and BOTH byte-traffic ratios must clear the 4x
    floor per batch — XLA ``bytes_accessed`` (what the compiled sweep
    touches) and the exact operand footprint ``input_bytes``.  They fail
    differently: a packing pass that dequantizes outside the kernel
    keeps operands small but restores the full in-kernel traffic; an
    operand-layout regression does the reverse.  The pruning record must
    carry a positive effective-clause count and packed-backend parity on
    its calibration batch."""
    comp = current.get("compressed")
    if not comp:
        return ["compressed sweep missing from BENCH_throughput.json "
                "(benchmarks.impact_throughput must always produce it)"]
    failures = []
    for b, c in sorted(comp.get("cost_analysis", {}).items(),
                       key=lambda kv: int(kv[0].lstrip("b"))):
        for metric in ("ratio_bytes_accessed", "ratio_input_bytes"):
            ratio = c.get(metric)
            ok = ratio is not None and ratio >= min_bytes_ratio
            shown = "missing" if ratio is None else f"{ratio:7.3f}"
            print(f"  compressed {b:6s} int8/packed {metric:21s} {shown}  "
                  f"floor {min_bytes_ratio:.2f}  {'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(
                    f"compressed {b}: {metric} {shown} below the "
                    f"{min_bytes_ratio}x floor — the packed clause "
                    f"operand is not shrinking sweep traffic")
    if not comp.get("cost_analysis"):
        failures.append("compressed sweep has no cost_analysis record")
    if not comp.get("parity_ok"):
        failures.append(
            "compressed sweep: packed-backend argmax diverged from the "
            "int8 kernel or the einsum oracle (parity_ok is false)")
    pr = comp.get("pruning", {})
    print(f"  compressed pruning: {pr.get('n_effective', '?')}/"
          f"{pr.get('n_clauses', '?')} clauses effective "
          f"({pr.get('n_never_fired', '?')} never fired, "
          f"{pr.get('n_duplicates', '?')} duplicates), "
          f"{pr.get('energy_per_effective_clause_j', 0.0):.3e} J per "
          f"effective clause per datapoint")
    if pr.get("n_effective", 0) <= 0:
        failures.append(
            "compressed pruning: no effective clauses survived the "
            "calibration batch (degenerate pruning record)")
    if not pr.get("packed_parity_on_calibration"):
        failures.append(
            "compressed pruning: pruned-system packed predictions "
            "diverged from the einsum oracle on the calibration batch")
    return failures


def check_cost_model(current: dict) -> list[str]:
    """Gate the calibrated cost model's predicted-vs-measured section:
    the section is mandatory (the benchmark always produces it), every
    entry's predicted/measured ratio must sit inside the recorded band,
    and the raw-cost ordering invariants carrying a ``must_be_at_least``
    floor hard-fail on a flip (a metered kernel whose executable costs
    *less* than the unmetered one has lost its meter — a sign error no
    throughput floor can see)."""
    pvm = current.get("predicted_vs_measured")
    if not pvm:
        return ["predicted_vs_measured section missing from "
                "BENCH_throughput.json (benchmarks.impact_throughput "
                "must always produce it)"]
    failures = []
    lo, hi = pvm.get("band", (0.0, float("inf")))
    for key, e in sorted(pvm.get("entries", {}).items()):
        ratio = e["ratio_pred_over_meas"]
        ok = lo <= ratio <= hi
        ref = " (calibration ref)" if e.get("calibration_ref") else ""
        print(f"  costmodel {key:28s} pred/meas {ratio:7.3f}  "
              f"band [{lo:.2f}, {hi:.2f}]  "
              f"{'ok' if ok else 'FAIL'}{ref}")
        if not ok:
            failures.append(
                f"cost model {key}: predicted/measured ratio {ratio:.3f} "
                f"outside band [{lo}, {hi}]")
    if not pvm.get("entries"):
        failures.append("predicted_vs_measured has no entries")
    for key, o in sorted(pvm.get("orderings", {}).items()):
        ratio = o["raw_cost_ratio"]
        floor = o.get("must_be_at_least")
        if floor is None:
            print(f"  costmodel {key:28s} raw-cost ratio {ratio:7.3f}  "
                  f"(for the record)")
            continue
        ok = ratio >= floor
        print(f"  costmodel {key:28s} raw-cost ratio {ratio:7.3f}  "
              f"floor {floor:.2f}  {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(
                f"cost model {key}: raw executable cost ratio {ratio:.3f} "
                f"< {floor} — the metered kernel prices below the "
                f"unmetered one (meter lost in lowering?)")
    return failures


def check_train(train: dict) -> list[str]:
    """Gate the online-training benchmark (``BENCH_train.json``): the
    Pallas feedback kernel must have walked a bit-identical TA trajectory
    to the einsum oracle (``parity.exact`` — the draws are precomputed
    operands, so this is equality, not a tolerance), held-out accuracy
    after the interleaved run must clear the stored floor AND improve on
    the deployment accuracy, the f64 per-update write bills must equal
    the running meter and the aggregated report lane at 1e-9, per-request
    read bills must have kept reconciling at 1e-9 while updates mutated
    the fabric, and serving-only reports must bill exactly zero write
    energy."""
    failures = []
    parity = train.get("parity", {})
    if not parity.get("exact"):
        failures.append(
            "train: ta_feedback kernel and oracle TA trajectories "
            "diverged (parity.exact is false) — the feedback primitive "
            "lost bit-exactness")
    online = train.get("online", {})
    floor = train.get("acc_floor")
    acc_b, acc_a = online.get("acc_before"), online.get("acc_after")
    if acc_a is None or floor is None:
        failures.append("train: online section missing acc_after/acc_floor")
    else:
        print(f"  train accuracy: {acc_b:.3f} -> {acc_a:.3f} after "
              f"{online.get('n_updates', '?')} updates  floor {floor:.2f}  "
              f"{'ok' if acc_a >= floor else 'FAIL'}")
        if acc_a < floor:
            failures.append(
                f"train: held-out accuracy {acc_a:.3f} after online "
                f"updates is below the floor {floor:.2f}")
        if not acc_a > acc_b:
            failures.append(
                f"train: online updates did not improve held-out accuracy "
                f"({acc_b:.3f} -> {acc_a:.3f})")
    wm = train.get("write_meter", {})
    rel = wm.get("rel_err", float("inf"))
    agg, meter = wm.get("aggregate_j"), wm.get("running_meter_j")
    print(f"  train write meter: {meter if meter is not None else '?'} J, "
          f"per-update-sum rel err {rel:.3e}")
    if not rel <= 1e-9:
        failures.append(
            f"train: f64 sum of per-update write bills drifts {rel:.3e} "
            f"from the running write meter (> 1e-9)")
    if agg != meter:
        failures.append(
            f"train: aggregated report write lane {agg} != running "
            f"meter {meter}")
    read_rel = train.get("read_billing", {}).get("max_rel_err",
                                                 float("inf"))
    if not read_rel <= 1e-9:
        failures.append(
            f"train: per-request read bills drifted {read_rel:.3e} from "
            f"the batch meter during the interleaved run (> 1e-9)")
    serving_w = train.get("serving_only", {}).get("write_energy_j")
    if serving_w != 0.0:
        failures.append(
            f"train: serving-only report bills {serving_w} J of write "
            f"energy (must be exactly 0.0)")
    return failures


def check_serve(serve: dict) -> list[str]:
    # A run where a scheduler completed nothing has no percentiles at
    # all — that is a gate failure to report, not a KeyError to crash
    # on (zero-completed BENCH_serve.json files happen when the Poisson
    # trace sheds everything, e.g. a mis-set queue_capacity).
    missing = [mode for mode in ("continuous", "flush")
               if "p95_s" not in serve.get(mode, {})]
    if missing:
        return [
            f"serve: no p95_s for {mode} (completed="
            f"{serve.get(mode, {}).get('completed', 0)}, offered="
            f"{serve.get('n_requests', '?')}) — scheduler completed "
            f"no requests" for mode in missing]
    p95_c = serve["continuous"]["p95_s"]
    p95_f = serve["flush"]["p95_s"]
    shed = serve["continuous"].get("shed", 0)
    print(f"  serve p95: continuous {p95_c * 1e3:.2f} ms, "
          f"flush {p95_f * 1e3:.2f} ms "
          f"(ratio {serve.get('p95_ratio_flush_over_continuous', 0):.2f}x)")
    failures = []
    if not p95_c < p95_f:
        failures.append(
            f"continuous p95 {p95_c:.4f}s is not below flush p95 "
            f"{p95_f:.4f}s")
    if shed:
        failures.append(f"continuous scheduler shed {shed} requests")
    return failures


def check_multi_tenant(serve: dict) -> list[str]:
    """Gate the multi-tenant model-zoo claims: co-resident argmax parity
    against the per-tenant oracle, tenant-pure billing (bill sums ==
    shared batch meter), strictly fewer fused sweeps than N independent
    per-tenant engines on the same trace, and SLO-class ordering (gold
    p99 below standard p99 — priority admission + immediate firing must
    actually buy latency)."""
    mt = serve.get("multi_tenant")
    if mt is None:
        return ["serve: BENCH_serve.json has no multi_tenant section "
                "(benchmarks/impact_throughput.py did not run the "
                "model-zoo sweep)"]
    failures = []
    mism = mt.get("parity_mismatches")
    if mism != 0:
        failures.append(
            f"multi_tenant: {mism} co-resident predictions diverge from "
            f"the per-tenant single-session oracle "
            f"(of {mt.get('parity_checked', '?')} checked)")
    rel = mt.get("billing_rel_err", float("inf"))
    if not rel < 1e-9:
        failures.append(
            f"multi_tenant: per-tenant bill sums drift {rel:.3e} from "
            f"the shared batch meter (>= 1e-9) — billing is not "
            f"tenant-pure")
    sweeps = mt.get("sweeps", {})
    co = sweeps.get("coresident", float("inf"))
    per = sweeps.get("per_tenant_engines", 0)
    if not co < per:
        failures.append(
            f"multi_tenant: co-resident serving took {co} sweeps vs "
            f"{per} for per-tenant engines — crossbar co-residency is "
            f"not coalescing work")
    slo = mt.get("per_slo", {})
    gold = slo.get("gold", {}).get("p99_s")
    std = slo.get("standard", {}).get("p99_s")
    if gold is None or std is None:
        failures.append(
            f"multi_tenant: missing per-SLO p99 (classes present: "
            f"{sorted(slo)}) — need both 'gold' and 'standard'")
    else:
        print(f"  multi-tenant p99: gold {gold * 1e3:.2f} ms, standard "
              f"{std * 1e3:.2f} ms; sweeps {co} coresident vs {per} "
              f"per-tenant engines")
        if not gold < std:
            failures.append(
                f"multi_tenant: gold p99 {gold:.4f}s is not below "
                f"standard p99 {std:.4f}s — SLO classes are not "
                f"differentiating service")
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("current", help="BENCH_throughput.json from this run")
    ap.add_argument("baseline", help="committed baseline JSON")
    ap.add_argument("--max-regression", type=float, default=0.30,
                    help="tolerated fractional drop in normalized "
                         "samples/s (default 0.30)")
    ap.add_argument("--serve", default=None,
                    help="BENCH_serve.json to gate the continuous-vs-flush "
                         "p95 invariant")
    ap.add_argument("--train", default=None,
                    help="BENCH_train.json to gate the online-training "
                         "parity/accuracy/write-meter invariants")
    args = ap.parse_args(argv)

    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    print(f"perf gate: {args.current} vs {args.baseline} "
          f"(max regression {args.max_regression:.0%})")
    failures = check_throughput(current, baseline, args.max_regression)
    failures += check_metered(current)
    failures += check_compressed(current)
    failures += check_cost_model(current)
    failures += check_roofline(current)
    failures += check_sharded(current)
    if args.serve:
        with open(args.serve) as f:
            serve = json.load(f)
        failures += check_serve(serve)
        failures += check_multi_tenant(serve)
    if args.train:
        with open(args.train) as f:
            train = json.load(f)
        failures += check_train(train)
    if failures:
        print("\nPERF GATE FAILED:")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    print("perf gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
