"""Online in-memory training benchmark: the PR-10 acceptance artifact.

One deployed system takes live-traffic serving sweeps interleaved with
``OnlineTrainer`` update sweeps, all through the same compiled-session
runtime.  Four gated sections land in ``BENCH_train.json``
(``check_perf.py --train`` enforces them):

* **parity** — the Pallas ``ta_feedback`` kernel and the einsum oracle
  must walk bit-identical TA/weight trajectories (all stochastic
  feedback draws are precomputed operands, so EXACT equality, not a
  tolerance).
* **online** — held-out accuracy on the synthetic glyph problem must
  improve over the pre-deployment accuracy and clear the stored floor
  after N update sweeps (ideal devices, so the figure is deterministic).
* **write_meter / read_billing** — the f64 sum of per-update write
  bills must equal the running write meter and the aggregated report
  lane at 1e-9, and per-request read bills must keep reconciling with
  the batch meter at 1e-9 while updates mutate the fabric under the
  serving executable.
* **serving_only** — pure inference reports bill exactly 0.0 J of
  write energy.

``--quick`` shrinks the update count for the CI perf-smoke job (the
accuracy floor is stored per scale).  A Chrome trace of the interleaved
run (serve spans + train_update spans) lands next to the JSON.

CSV rows:  impact_train/update_b<B>, us_per_update, updates_per_s
           impact_train/serve_b<B>, us_per_sweep, samples_per_s
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import ARTIFACTS, emit

from repro.core import CoTMConfig
from repro.core.train import train_step_batch
from repro.data.synthetic import prototype
from repro.impact import IMPACTConfig, RuntimeSpec, build_system
from repro.serve.impact_engine import aggregate_reports
from repro.serve.tracing import Tracer
from repro.train import OnlineTrainer

BATCH = 64


def _problem(seed=3):
    cfg = CoTMConfig(n_literals=64, n_clauses=40, n_classes=4,
                     n_states=64, threshold=16, specificity=4.0)
    x, y = prototype(640, n_classes=4, n_features=32, flip=0.05, seed=seed)
    lits = jnp.asarray(np.concatenate([x, 1 - x], -1).astype(bool))
    labels = jnp.asarray(y)
    return cfg, (lits[:512], labels[:512]), (lits[512:], labels[512:])


def _deploy(cfg, tr_l, tr_y, *, backend, seed=0):
    """One digital pre-train epoch (a half-trained deployment), then
    encode into an ideal-device system (deterministic accuracy; the
    trainer itself owns the write-path noise model)."""
    params = cfg.init(jax.random.key(seed))
    key = jax.random.key(seed + 1)
    for b in range(0, 512, BATCH):
        key, k = jax.random.split(key)
        params = train_step_batch(params, tr_l[b:b + BATCH],
                                  tr_y[b:b + BATCH], k, cfg)
    system = build_system(params, cfg, jax.random.key(seed + 2),
                          IMPACTConfig(variability=False, finetune=False))
    session = system.compile(RuntimeSpec(backend=backend, interpret=True))
    return params, system, session


def parity_sweep(cfg, tr_l, tr_y, n_steps=3):
    """Oracle-vs-kernel TA-state parity: two trainers differing only in
    backend, same keys, must agree EXACTLY after every update."""
    states = {}
    for backend in ("xla", "pallas"):
        params, _, session = _deploy(cfg, tr_l, tr_y, backend=backend)
        trainer = OnlineTrainer(session, params, cfg,
                                key=jax.random.key(11), variability=True)
        for step in range(n_steps):
            trainer.update(tr_l[step * BATCH:(step + 1) * BATCH],
                           tr_y[step * BATCH:(step + 1) * BATCH],
                           key=jax.random.key(100 + step))
        states[backend] = trainer
    a, b = states["xla"], states["pallas"]
    exact = bool(
        np.array_equal(np.asarray(a.params.ta_state),
                       np.asarray(b.params.ta_state))
        and np.array_equal(np.asarray(a.params.weights),
                           np.asarray(b.params.weights))
        and a.write_energy_j == b.write_energy_j)
    return {"exact": exact, "n_steps": n_steps,
            "write_energy_j": a.write_energy_j}


def interleaved_run(cfg, splits, *, epochs, trace_dir):
    (tr_l, tr_y), (ho_l, ho_y) = splits
    params, system, session = _deploy(cfg, tr_l, tr_y, backend="pallas")
    trace = Tracer()
    trainer = OnlineTrainer(session, params, cfg, key=jax.random.key(7),
                            variability=False, trace=trace)
    acc_before = trainer.evaluate(ho_l, ho_y)
    session.warm(BATCH, "infer_step")

    serve_us, update_us, max_read_rel_err = [], [], 0.0
    serving_write_j = None
    for epoch in range(epochs):
        for b in range(0, 512, BATCH):
            lo = tr_l[b:b + BATCH]
            t0 = time.perf_counter()
            ts0 = trace.clock()
            res = session.infer_step(np.asarray(lo, np.int8),
                                     np.ones((BATCH,), bool))
            jax.block_until_ready(res.predictions)
            trace.span("serve_sweep", ts0, trace.clock())
            serve_us.append((time.perf_counter() - t0) * 1e6)

            e_cl = np.asarray(res.e_clause_lanes, np.float64)
            e_cs = np.asarray(res.e_class_lanes, np.float64)
            rep = system.step_report(e_cl, e_cs, BATCH)
            lane_sum = e_cl.sum() + e_cs.sum()
            if lane_sum > 0.0:
                max_read_rel_err = max(
                    max_read_rel_err,
                    abs(rep.read_energy_j - lane_sum) / lane_sum)
            serving_write_j = rep.write_energy_j

            t0 = time.perf_counter()
            trainer.update(lo, tr_y[b:b + BATCH])
            update_us.append((time.perf_counter() - t0) * 1e6)

    acc_after = trainer.evaluate(ho_l, ho_y)
    per_update_sum = sum(r["write_energy_j"] for r in trainer.records)
    agg = aggregate_reports(trainer.reports)
    meter = trainer.write_energy_j
    trace.write(trace_dir / "impact_train.trace.json")

    emit(f"impact_train/update_b{BATCH}", float(np.mean(update_us)),
         f"{1e6 / np.mean(update_us):.1f}")
    emit(f"impact_train/serve_b{BATCH}", float(np.mean(serve_us)),
         f"{BATCH * 1e6 / np.mean(serve_us):.1f}")
    return {
        "online": {
            "acc_before": acc_before, "acc_after": acc_after,
            "n_updates": len(trainer.records),
            "write_energy_j": meter,
            "prog_pulses": sum(r["prog_pulses"] for r in trainer.records),
            "erase_pulses": sum(r["erase_pulses"] for r in trainer.records),
            "n_unconverged": sum(r["n_unconverged"]
                                 for r in trainer.records),
            "us_per_update": float(np.mean(update_us)),
        },
        "write_meter": {
            "per_update_sum_j": per_update_sum,
            "running_meter_j": meter,
            "aggregate_j": agg.write_energy_j,
            "rel_err": (abs(per_update_sum - meter) / meter
                        if meter > 0.0 else 0.0),
        },
        "read_billing": {"max_rel_err": max_read_rel_err},
        "serving_only": {"write_energy_j": serving_write_j},
    }


def main(quick: bool = False, json_dir=None):
    json_dir = pathlib.Path(json_dir) if json_dir else ARTIFACTS
    json_dir.mkdir(parents=True, exist_ok=True)
    cfg, train, holdout = _problem()
    epochs = 2 if quick else 6
    bench = {"quick": quick, "batch": BATCH, "epochs": epochs,
             # Deterministic (ideal devices, fixed keys): quick clears
             # ~0.75 after 16 updates, full ~0.85 after 48 — floors sit
             # well below so a legitimate refactor has headroom while a
             # broken feedback path (which collapses to ~0.3) still trips.
             "acc_floor": 0.55 if quick else 0.65}
    bench["parity"] = parity_sweep(cfg, *train)
    bench.update(interleaved_run(cfg, (train, holdout), epochs=epochs,
                                 trace_dir=json_dir))
    with open(json_dir / "BENCH_train.json", "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    import warnings

    from repro.impact import SpecDeprecationWarning

    warnings.simplefilter("error", SpecDeprecationWarning)
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI perf-smoke scale: fewer update epochs")
    ap.add_argument("--json-dir", default=None,
                    help="where BENCH_train.json lands (default: artifacts/)")
    args = ap.parse_args()
    from repro.compile_cache import use_compilation_cache
    use_compilation_cache()
    print("name,us_per_call,derived")
    main(quick=args.quick, json_dir=args.json_dir)
