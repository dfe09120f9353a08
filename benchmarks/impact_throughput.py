"""IMPACT serving throughput: einsum-vs-Pallas sweep + mixed-traffic serve.

All measurements run through the compiled-session runtime: each
configuration is a frozen ``RuntimeSpec`` resolved once by
``IMPACTSystem.compile`` into an ``InferenceSession`` of AOT executables,
so the timed loops never pay (or hide) jit-cache lookups or retraces.

Four measurements:

1. **Throughput sweep** — ``session.predict`` samples/s at the paper's
   MNIST dims (K=1568, n=500, m=10) across batch sizes, for both
   ``backend="xla"`` (the einsum oracle) and ``backend="pallas"`` (the
   fused crossbar kernel — interpret mode on CPU, so CPU numbers gauge
   correctness plumbing and dispatch overhead rather than TPU speed),
   plus the batched ``IMPACTEngine`` front end to expose queueing +
   padding overhead.  Written to ``BENCH_throughput.json`` with
   machine-portable normalized ratios (each key / its backend family's
   reference at the smallest batch) that CI gates against a committed
   baseline.

2. **Poisson mixed-traffic serve** — the same seeded arrival trace is
   replayed through the continuous-batching scheduler and the legacy
   flush-to-completion scheduler; per-request p50/p95/p99 tail latency and
   throughput of both land in ``BENCH_serve.json``.  This is the PR-2
   acceptance artifact: continuous must show lower p95 at equal offered
   load.

3. **Metered sweep** — prices the in-kernel energy meter: the SAME
   ``infer_step`` sweep through three sessions (``metering="off"`` — the
   unmetered fused kernel, ``"fused"`` — meters accumulated inside the
   fused kernel, ``"staged"`` — the per-shard oracle the fused meters
   are pinned against), with argmax + per-lane-joule parity between the
   two metered modes asserted and recorded.  Lands under the
   ``"metered"`` key of ``BENCH_throughput.json``; ``check_perf.py``
   requires the section, its parity flag, and a sane fused-metered /
   unmetered ratio.

4. **Compressed sweep** — the bit-packed datapath: ``predict`` through
   the ``pallas`` backend with ``packing="2bit"`` (2-bit ternary
   clause codes, four cells per byte, dequantized inside the fused
   kernel) vs the int8-literal/f32-operand fused kernel, with argmax
   parity against the einsum oracle asserted.  The per-batch
   ``cost_analysis`` record carries both XLA ``bytes_accessed`` and the
   exact operand footprint (``session.input_bytes``); ``check_perf.py``
   gates both ratios at >= 4x.  A clause-pruning record
   (``train.compression.prune_clauses`` on a calibration batch) lands
   alongside with the re-anchored energy-per-effective-clause figure.
   Lands under the ``"compressed"`` key of ``BENCH_throughput.json``.

5. **Sharded sweep** (multi-device hosts only) — the same predict path
   from a (data, model=2) mesh via a ``RuntimeSpec`` topology on an
   R=2/S=2 split grid vs the identical split grid on one device, with
   argmax parity asserted; lands under the ``"sharded"`` key of
   ``BENCH_throughput.json`` and is exercised by the CI multi-device leg
   under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--quick`` shrinks the sweep (B<=32) for the CI perf-smoke job.

CSV rows:  impact_throughput/<impl>_b<B>, us_per_batch, samples_per_s
           impact_metered/<mode>_b<B>, us_per_batch, samples_per_s
           impact_compressed/<int8|packed>_b<B>, us_per_batch, s/s
           impact_sharded/<single|sharded>_xla_b<B>, us_per_batch, s/s
           impact_serve/<mode>, p95_us, samples_per_s
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import ARTIFACTS, emit
from .roofline import impact_roofline

from repro.core import CoTMConfig
from repro.impact import (IMPACTConfig, RuntimeSpec, Topology, build_system)
from repro.impact.costmodel import bench_section, bytes_per_sweep
from repro.train.compression import prune_clauses
from repro.serve import (IMPACTEngine, ModelZoo, SLOClass, poisson_arrivals,
                         replay_trace, replay_zoo_trace)

BATCH_SIZES = (32, 128, 512)
QUICK_BATCH_SIZES = (8, 32)
REPEATS = 3


def _random_cotm(key, K=1568, n=500, m=10, n_states=128, density=0.05):
    """Random (untrained) CoTM at paper dims — throughput does not depend
    on training quality, and this keeps the benchmark CPU-budget friendly."""
    cfg = CoTMConfig(n_literals=K, n_clauses=n, n_classes=m,
                     n_states=n_states)
    k1, k2 = jax.random.split(key)
    ta = jnp.where(jax.random.bernoulli(k1, density, (K, n)),
                   n_states + 1, n_states).astype(jnp.int32)
    w = jax.random.randint(k2, (m, n), -40, 40).astype(jnp.int32)
    params = cfg.init(key)
    params = type(params)(ta_state=ta, weights=w)
    return cfg, params


def _time_predict(session, lits) -> float:
    preds = session.predict(lits).predictions   # compile + warm
    jax.block_until_ready(preds)
    t0 = time.time()
    for _ in range(REPEATS):
        jax.block_until_ready(session.predict(lits).predictions)
    return (time.time() - t0) / REPEATS


def throughput_sweep(system, cfg, *, quick: bool) -> dict:
    """Predict-path + engine-front samples/s; returns the BENCH payload."""
    rng = np.random.default_rng(0)
    results: dict[str, dict] = {}
    batch_sizes = QUICK_BATCH_SIZES if quick else BATCH_SIZES
    sessions = {impl: system.compile(RuntimeSpec(backend=impl,
                                                 metering="off"))
                for impl in ("xla", "pallas")}
    for B in batch_sizes:
        lits = jnp.asarray(rng.random((B, cfg.n_literals)) < 0.5)
        for impl, session in sessions.items():
            dt = _time_predict(session, lits)
            key = f"{impl}_b{B}"
            results[key] = dict(us_per_batch=dt * 1e6,
                                samples_per_s=B / dt)
            emit(f"impact_throughput/{key}", dt * 1e6, f"{B / dt:.1f}")

    # Batched front end: request burst through the continuous scheduler.
    B = max(batch_sizes)
    lits = np.asarray(rng.random((B, cfg.n_literals)) < 0.5)
    eng = IMPACTEngine(system.compile(RuntimeSpec(
        backend="xla", metering="off", capacity=min(B, 128))))
    t0 = time.time()
    _, stats = eng.run(lits)
    dt = time.time() - t0
    results["engine_xla_burst"] = dict(
        us_per_batch=dt * 1e6 / stats["batches"], samples_per_s=B / dt)
    emit("impact_throughput/engine_xla_burst", dt * 1e6 / stats["batches"],
         f"{B / dt:.1f}")

    # Machine-portable gate metric: every samples/s ratioed to its OWN
    # backend family's reference at the smallest batch.  Pallas interpret
    # mode is mostly single-threaded interpreter work while the XLA
    # einsum scales with CPU threads, so a cross-family ratio would shift
    # with core count; within a family the machine-speed factor cancels
    # and batch-scaling / engine-overhead regressions still show.
    def family(key: str) -> str:
        return "pallas" if key.startswith("pallas") else "xla"

    refs = {fam: results[f"{fam}_b{batch_sizes[0]}"]["samples_per_s"]
            for fam in ("xla", "pallas")}
    return dict(
        dims=dict(K=cfg.n_literals, n=cfg.n_clauses, m=cfg.n_classes),
        quick=quick,
        reference_keys={fam: f"{fam}_b{batch_sizes[0]}" for fam in refs},
        machine=dict(cpu_count=os.cpu_count()),
        results=results,
        normalized={k: v["samples_per_s"] / refs[family(k)]
                    for k, v in results.items()})


def _time_step(session, lits, valid) -> float:
    res = session.infer_step(lits, valid)       # compile + warm
    jax.block_until_ready((res.predictions, res.e_clause_lanes))
    t0 = time.time()
    for _ in range(REPEATS):
        out = session.infer_step(lits, valid)
        jax.block_until_ready((out.predictions, out.e_clause_lanes))
    return (time.time() - t0) / REPEATS


def metered_sweep(system, cfg, *, quick: bool) -> dict:
    """The ``metered_fused`` acceptance sample: fused-metered vs
    unmetered-fused vs staged-metered ``infer_step`` samples/s, plus the
    parity record ``check_perf.py`` gates on (fused and staged meters
    must agree — billing at speed is only a win if the joules are the
    same).  Pallas family throughout: the fused kernel is the production
    path the meter rides."""
    rng = np.random.default_rng(0)
    batch_sizes = QUICK_BATCH_SIZES if quick else BATCH_SIZES
    sessions = {mode: system.compile(RuntimeSpec(backend="pallas",
                                                 metering=mode))
                for mode in ("off", "fused", "staged")}
    results: dict[str, dict] = {}
    parity_ok = True
    for B in batch_sizes:
        lits = jnp.asarray(rng.random((B, cfg.n_literals)) < 0.5)
        valid = np.ones((B,), bool)
        res = {mode: s.infer_step(lits, valid)
               for mode, s in sessions.items()}
        parity_ok &= bool(
            (np.asarray(res["fused"].predictions)
             == np.asarray(res["staged"].predictions)).all())
        # atol=0: per-lane energies are ~1e-11 J, far below np.allclose's
        # default atol=1e-8 — the relative tolerance must do all the work
        # or an all-zeros meter regression would pass as "parity".
        parity_ok &= bool(np.allclose(
            np.asarray(res["fused"].e_clause_lanes),
            np.asarray(res["staged"].e_clause_lanes), rtol=1e-4, atol=0.0))
        parity_ok &= bool(np.allclose(
            np.asarray(res["fused"].e_class_lanes),
            np.asarray(res["staged"].e_class_lanes), rtol=1e-4, atol=0.0))
        for mode, session in sessions.items():
            dt = _time_step(session, lits, valid)
            key = f"metered_{mode}_b{B}"
            results[key] = dict(us_per_batch=dt * 1e6,
                                samples_per_s=B / dt)
            emit(f"impact_metered/{mode}_b{B}", dt * 1e6, f"{B / dt:.1f}")
    return dict(
        quick=quick, parity_ok=parity_ok, results=results,
        ratio_fused_metered_over_unmetered={
            f"b{B}": (results[f"metered_fused_b{B}"]["samples_per_s"]
                      / results[f"metered_off_b{B}"]["samples_per_s"])
            for B in batch_sizes},
        ratio_fused_metered_over_staged={
            f"b{B}": (results[f"metered_fused_b{B}"]["samples_per_s"]
                      / results[f"metered_staged_b{B}"]["samples_per_s"])
            for B in batch_sizes})


def compressed_sweep(system, cfg, *, quick: bool) -> dict:
    """The compressed-datapath acceptance sample: ``packing="2bit"``
    (2-bit ternary clause codes, four cells per byte, in-kernel dequant)
    vs the int8-literal fused kernel, argmax-parity-checked against the
    einsum oracle, with the per-batch byte-traffic record
    (``costmodel.bytes_per_sweep``) ``check_perf.py`` gates at >= 4x.

    The pruning record runs ``prune_clauses`` against a calibration
    batch drawn at 95% ones-density: at the benchmark's 5% include
    density a clause carries ~78 include literals, so uniform 50/50
    literals fire nothing (P ~ 2^-78) while 95%-ones rows fire each
    clause with P ~ 0.018/row — a realistic mix of firing and dead
    columns instead of an all-dead or all-alive degenerate record.
    """
    rng = np.random.default_rng(0)
    batch_sizes = QUICK_BATCH_SIZES if quick else BATCH_SIZES
    sessions = dict(
        int8=system.compile(RuntimeSpec(backend="pallas", metering="off")),
        packed=system.compile(RuntimeSpec(
            backend="pallas", metering="off", packing="2bit")),
        oracle=system.compile(RuntimeSpec(backend="xla", metering="off")))
    results: dict[str, dict] = {}
    cost: dict[str, dict] = {}
    parity_ok = True
    for B in batch_sizes:
        lits = jnp.asarray(rng.random((B, cfg.n_literals)) < 0.5)
        preds = {kind: np.asarray(s.predict(lits).predictions)
                 for kind, s in sessions.items()}
        parity_ok &= bool((preds["packed"] == preds["int8"]).all())
        parity_ok &= bool((preds["packed"] == preds["oracle"]).all())
        for kind in ("int8", "packed"):
            dt = _time_predict(sessions[kind], lits)
            key = f"{kind}_b{B}"
            results[key] = dict(us_per_batch=dt * 1e6,
                                samples_per_s=B / dt)
            emit(f"impact_compressed/{key}", dt * 1e6, f"{B / dt:.1f}")
        c8 = bytes_per_sweep(sessions["int8"], "predict", B)
        cp = bytes_per_sweep(sessions["packed"], "predict", B)
        cost[f"b{B}"] = dict(
            int8=c8, packed=cp,
            ratio_bytes_accessed=(c8["bytes_accessed"]
                                  / max(cp["bytes_accessed"], 1.0)),
            ratio_input_bytes=(c8["input_bytes"]
                               / max(cp["input_bytes"], 1.0)))

    calib = jnp.asarray(rng.random((64, cfg.n_literals)) < 0.95)
    pruned, stats = prune_clauses(system, calib)
    sess_pruned = pruned.compile(RuntimeSpec(
        backend="pallas", metering="off", packing="2bit"))
    sess_oracle = pruned.compile(RuntimeSpec(backend="xla", metering="off"))
    prune_parity = bool(
        (np.asarray(sess_pruned.predict(calib).predictions)
         == np.asarray(sess_oracle.predict(calib).predictions)).all())
    return dict(
        quick=quick, parity_ok=parity_ok, results=results,
        cost_analysis=cost,
        pruning=dict(dataclasses.asdict(stats),
                     packed_parity_on_calibration=prune_parity))


def sharded_sweep(cfg, params, *, quick: bool) -> dict | None:
    """Sharded-vs-single-device ``predict`` at a Fig. 14 split layout.

    The paper's MNIST layout fits one tile (R=S=1), so the grid is
    rebuilt with R=2 literal row-shards and S=2 class row-shards and
    served from a (data, model=2) mesh via the session topology; the
    same split system compiled without a mesh is the baseline, and
    argmax parity between the two is asserted and recorded.  Returns
    None on single-device hosts (the CI multi-device leg runs this with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on CPU the
    numbers gauge partitioning + psum overhead, not TPU speed).
    """
    n_dev = jax.device_count()
    if n_dev < 2 or n_dev % 2:
        return None
    from repro.launch.mesh import make_crossbar_mesh

    mesh = make_crossbar_mesh(n_model=2)
    split = IMPACTConfig(variability=False, finetune=False,
                         max_tile_rows=cfg.n_literals // 2,
                         max_class_rows=-(-cfg.n_clauses // 2))
    system = build_system(params, cfg, jax.random.key(1), split)
    R, S = system.clause_g.shape[0], system.class_g.shape[0]
    assert R == 2 and S == 2, (R, S)
    sess_single = system.compile(RuntimeSpec(backend="xla",
                                             metering="off"))
    sess_shard = system.compile(RuntimeSpec(
        backend="xla", metering="off", topology=Topology(mesh=mesh)))
    assert sess_shard.plan == (True, True), sess_shard.plan

    rng = np.random.default_rng(0)
    results: dict[str, dict] = {}
    parity_ok = True
    batch_sizes = QUICK_BATCH_SIZES if quick else BATCH_SIZES
    for B in batch_sizes:
        lits = jnp.asarray(rng.random((B, cfg.n_literals)) < 0.5)
        p_single = np.asarray(sess_single.predict(lits).predictions)
        p_shard = np.asarray(sess_shard.predict(lits).predictions)
        parity_ok &= bool((p_single == p_shard).all())
        for key, sess in (("single", sess_single), ("sharded", sess_shard)):
            dt = _time_predict(sess, lits)
            results[f"{key}_xla_b{B}"] = dict(us_per_batch=dt * 1e6,
                                              samples_per_s=B / dt)
            emit(f"impact_sharded/{key}_xla_b{B}", dt * 1e6,
                 f"{B / dt:.1f}")
    speedup = {f"b{B}": (results[f"sharded_xla_b{B}"]["samples_per_s"]
                         / results[f"single_xla_b{B}"]["samples_per_s"])
               for B in batch_sizes}
    return dict(
        n_devices=n_dev, mesh={k: int(v) for k, v in mesh.shape.items()},
        grid=dict(R=R, S=S), quick=quick, parity_ok=parity_ok,
        results=results, speedup_sharded_over_single=speedup)


def serve_comparison(system, cfg, *, n_requests: int, rate_rps: float,
                     capacity: int, flush_wait_s: float, seed: int,
                     impl: str = "xla",
                     trace_dir: pathlib.Path | None = None) -> dict:
    """Replay one seeded Poisson trace through both scheduler modes (one
    shared compiled session — the schedulers, not the runtime, differ).
    With ``trace_dir``, each mode's run also lands a Chrome-tracing
    timeline (``SERVE_<mode>.trace.json``, loadable in Perfetto) as a CI
    artifact."""
    rng = np.random.default_rng(seed)
    lits = rng.random((n_requests, cfg.n_literals)) < 0.5
    arrivals = poisson_arrivals(n_requests, rate_rps, seed=seed)
    session = system.compile(RuntimeSpec(backend=impl, metering="off",
                                         capacity=capacity))
    out: dict = dict(seed=seed, n_requests=n_requests, rate_rps=rate_rps,
                     capacity=capacity, flush_wait_s=flush_wait_s,
                     impl=impl)
    engines = dict(
        continuous=IMPACTEngine(session, max_wait_s=0.0),
        flush=IMPACTEngine(session, mode="flush", buckets=(capacity,),
                           max_wait_s=flush_wait_s))
    for mode, eng in engines.items():
        eng.warmup()
        trace_path = (str(trace_dir / f"SERVE_{mode}.trace.json")
                      if trace_dir is not None else None)
        out[mode] = replay_trace(eng, lits, arrivals,
                                 trace_path=trace_path)
        emit(f"impact_serve/{mode}", out[mode]["p95_s"] * 1e6,
             f"{out[mode]['samples_per_s']:.1f}")
    out["p95_ratio_flush_over_continuous"] = (
        out["flush"]["p95_s"] / max(out["continuous"]["p95_s"], 1e-12))
    return out


def multi_tenant_sweep(*, n_tenants: int, n_requests: int, rate_rps: float,
                       capacity: int, seed: int,
                       trace_dir: pathlib.Path | None = None) -> dict:
    """Mixed Poisson traffic over a co-resident model zoo (>= 8 tenants,
    two SLO classes) vs N independent per-tenant engines.

    Three gated claims land in the ``multi_tenant`` section of
    ``BENCH_serve.json``:

    * **parity_mismatches == 0** — every co-resident sweep's prediction
      equals the per-tenant single-session oracle (checked exhaustively
      on a deterministic pass before the timed replay);
    * **billing_rel_err < 1e-9** — the per-tenant bill sums reproduce
      the shared batch meter (tenant-pure energy attribution);
    * **sweeps.coresident < sweeps.per_tenant_engines** — the shared
      block-diagonal grid serves the same trace in strictly fewer fused
      sweeps than one engine per tenant (the co-residency payoff).

    Per-SLO-class p99 comes from the zoo's tenant-threaded ledger; with
    ``trace_dir`` the replay lands ``SERVE_multitenant.trace.json`` (one
    Perfetto process track per tenant) as a CI artifact.
    """
    rng = np.random.default_rng(seed)
    # Small per-tenant CoTMs with distinct class counts; the combined
    # block-diagonal grid stays inside one tile (the co-residency
    # builder's constraint).
    systems, cfgs = [], []
    for t in range(n_tenants):
        cfg, params = _random_cotm(jax.random.key(100 + t), K=128, n=48,
                                   m=4 + t % 4, density=0.08)
        systems.append(build_system(
            params, cfg, jax.random.key(200 + t),
            IMPACTConfig(variability=False, finetune=False)))
        cfgs.append(cfg)
    gold = SLOClass(name="gold", priority=0, max_wait_s=0.0)
    std = SLOClass(name="standard", priority=1, target_occupancy=0.5,
                   max_wait_s=0.02)
    slo_of = lambda t: gold if t < 2 else std
    spec = RuntimeSpec(backend="xla", metering="staged")
    zoo = ModelZoo.build(
        [(f"t{t}", s, slo_of(t)) for t, s in enumerate(systems)],
        spec, capacity=capacity, clock=time.monotonic)
    zoo.warmup()

    # Oracle sessions + deterministic parity pass: mixed batches through
    # the shared grid, every prediction against the standalone session.
    oracle = [s.compile(dataclasses.replace(spec, capacity=1))
              for s in systems]
    tenant_of, rows = [], []
    for i in range(n_requests):
        t = int(rng.integers(n_tenants))
        tenant_of.append(t)
        rows.append((rng.random(cfgs[t].n_literals) < 0.5).astype(np.int8))
    mismatches = 0
    rid_to_idx = {}
    for i, (t, row) in enumerate(zip(tenant_of, rows)):
        rid_to_idx[zoo.submit(f"t{t}", row)] = i
    done = dict(zoo.drain())
    for rid, pred in done.items():
        i = rid_to_idx[rid]
        t = tenant_of[i]
        ref = int(np.asarray(oracle[t].predict(
            rows[i][None, :]).predictions)[0])
        mismatches += int(pred != ref)
    st = zoo.stats()
    bill = sum(v["e_read_j"] for v in st["per_tenant"].values())
    meter = st["energy"].read_energy_j
    billing_rel_err = abs(bill - meter) / max(meter, 1e-300)

    # Timed replay of one mixed Poisson trace -> per-SLO p99 + the
    # co-resident sweep count.
    arrivals = poisson_arrivals(n_requests, rate_rps, seed=seed)
    reqs = [(f"t{t}", row) for t, row in zip(tenant_of, rows)]
    sweeps0 = zoo.resident_sweeps + zoo.standby_sweeps
    rec0 = len(zoo.request_records)
    trace_path = (str(trace_dir / "SERVE_multitenant.trace.json")
                  if trace_dir is not None else None)
    replay = replay_zoo_trace(zoo, reqs, arrivals, trace_path=trace_path)
    coresident_sweeps = (zoo.resident_sweeps + zoo.standby_sweeps
                         - sweeps0)
    # Per-SLO-class tails over the TIMED replay only (the parity pass
    # above also lands in the zoo's lifetime ledger).
    from repro.serve import latency_percentiles
    slo_name = {f"t{t}": slo_of(t).name for t in range(n_tenants)}
    slo_lat: dict[str, list[float]] = {}
    for r in zoo.request_records[rec0:]:
        slo_lat.setdefault(slo_name[r.tenant], []).append(r.latency_s)
    per_slo = {name: dict(priority=(gold if name == "gold"
                                    else std).priority,
                          **latency_percentiles(lat))
               for name, lat in slo_lat.items()}

    # Baseline: the same per-tenant sub-traces through N independent
    # engines (same capacity/policy knobs), counting their sweeps.
    per_engine_sweeps = 0
    for t in range(n_tenants):
        idx = [i for i in range(n_requests) if tenant_of[i] == t]
        if not idx:
            continue
        slo = slo_of(t)
        eng = IMPACTEngine(
            systems[t].compile(dataclasses.replace(spec,
                                                   capacity=capacity)),
            max_wait_s=slo.max_wait_s,
            target_occupancy=slo.target_occupancy,
            clock=time.monotonic)
        eng.warmup()
        sub_arrivals = arrivals[idx] - arrivals[idx[0]]
        replay_trace(eng, np.stack([rows[i] for i in idx]), sub_arrivals)
        per_engine_sweeps += len(eng.batch_stats)

    out = dict(
        n_tenants=n_tenants, n_requests=n_requests, rate_rps=rate_rps,
        capacity=capacity, seed=seed, impl=spec.backend,
        parity_checked=len(done), parity_mismatches=mismatches,
        billing_rel_err=billing_rel_err,
        sweeps=dict(coresident=coresident_sweeps,
                    per_tenant_engines=per_engine_sweeps),
        completed=replay["completed"], shed=replay["shed"],
        samples_per_s=replay["samples_per_s"],
        per_slo={name: dict(priority=d["priority"], p50_s=d["p50_s"],
                            p99_s=d["p99_s"], n=d["n"])
                 for name, d in per_slo.items()},
        per_tenant={tid: dict(completed=d["completed"], shed=d["shed"],
                              e_read_j=d["e_read_j"])
                    for tid, d in replay["zoo"]["per_tenant"].items()},
    )
    if trace_path is not None:
        out["trace_path"] = trace_path
    for name, d in sorted(per_slo.items()):
        emit(f"impact_multitenant/{name}", d["p99_s"] * 1e6,
             f"n={d['n']}")
    emit("impact_multitenant/sweeps",
         float(coresident_sweeps),
         f"vs {per_engine_sweeps} per-tenant")
    return out


def main(quick: bool = False, json_dir: pathlib.Path | None = None) -> None:
    json_dir = pathlib.Path(json_dir) if json_dir else ARTIFACTS
    json_dir.mkdir(parents=True, exist_ok=True)
    key = jax.random.key(0)
    cfg, params = _random_cotm(key)
    # Ideal devices: benchmark the inference path, not encode stochasticity.
    system = build_system(params, cfg, jax.random.key(1),
                          IMPACTConfig(variability=False, finetune=False))

    bench = throughput_sweep(system, cfg, quick=quick)
    bench["metered"] = metered_sweep(system, cfg, quick=quick)
    bench["compressed"] = compressed_sweep(system, cfg, quick=quick)
    # Calibrated analytic cost model over the sessions the sweeps just
    # timed (compile cache hit — no re-lowering): predicted-vs-measured
    # ratios check_perf.py gates per backend and metering mode.
    bench["predicted_vs_measured"] = bench_section(
        system, bench,
        batch_sizes=QUICK_BATCH_SIZES if quick else BATCH_SIZES)
    # Roofline placement of the same executables (XLA cost counters vs
    # the v5e peaks) — recorded for the scoreboard, not gated.
    bench["roofline"] = impact_roofline(
        system, bench["results"],
        batch_sizes=QUICK_BATCH_SIZES if quick else BATCH_SIZES)
    sharded = sharded_sweep(cfg, params, quick=quick)
    if sharded is not None:            # multi-device hosts only
        bench["sharded"] = sharded
    with open(json_dir / "BENCH_throughput.json", "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)

    serve = serve_comparison(
        system, cfg,
        n_requests=80 if quick else 256,
        rate_rps=300.0, capacity=16 if quick else 32,
        flush_wait_s=0.05, seed=0, trace_dir=json_dir)
    serve["multi_tenant"] = multi_tenant_sweep(
        n_tenants=8, n_requests=96 if quick else 320,
        rate_rps=400.0, capacity=16, seed=0, trace_dir=json_dir)
    with open(json_dir / "BENCH_serve.json", "w") as f:
        json.dump(serve, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    import warnings

    from repro.impact import SpecDeprecationWarning

    # The CI perf legs invoke this module directly: enforce the
    # migration off the deprecated per-call kwargs here too (pytest.ini
    # covers the test suite, benchmarks/run.py the orchestrator).
    warnings.simplefilter("error", SpecDeprecationWarning)
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI perf-smoke scale: B<=32 sweep, short trace")
    ap.add_argument("--json-dir", default=None,
                    help="where BENCH_*.json land (default: artifacts/)")
    args = ap.parse_args()
    from repro.compile_cache import use_compilation_cache
    use_compilation_cache()
    print("name,us_per_call,derived")
    main(quick=args.quick, json_dir=args.json_dir)
