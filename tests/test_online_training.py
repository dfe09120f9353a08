"""Online in-memory TA training: kernel/oracle parity, learnability
under live traffic, billing reconciliation, and trainer input contracts.

The acceptance bar for the ``ta_feedback`` primitive is EXACT parity:
all stochastic feedback draws are precomputed operands, so the Pallas
kernel and the einsum oracle must produce bit-identical TA deltas —
and two trainers differing only in backend must walk bit-identical TA
trajectories.  The serving seam is exercised end to end: updates mutate
the deployed conductances in place, the compiled serving executables
pick them up WITHOUT a retrace, and per-request read bills keep
reconciling with the batch meter afterwards.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cotm import CoTMConfig, predict as digital_predict
from repro.core.train import train_step_batch
from repro.data.synthetic import prototype
from repro.impact import RuntimeSpec
from repro.impact.pipeline import IMPACTConfig, build_system
from repro.serve.tracing import Tracer, validate_events
from repro.train import OnlineTrainer

from test_fused_impact import _make_system

# (B2, K, n, M, R, tr, C, tc, S, sr): doubled-batch feedback shapes over
# ragged / multi-shard grids (the grid only matters through the session
# plumbing — ta_feedback itself is grid-free).
PARITY_SHAPES = [
    (16, 70, 33, 4, 1, 70, 1, 33, 1, 33),
    (64, 256, 128, 8, 2, 128, 2, 64, 2, 64),
    (32, 300, 190, 6, 3, 100, 2, 95, 2, 95),
]


def _feedback_operands(rng, B2, K, n):
    lit2 = jnp.asarray(rng.integers(0, 2, (B2, K)).astype(np.int8))
    fired2 = jnp.asarray(rng.integers(0, 2, (B2, n)).astype(bool))
    sel = jnp.asarray(rng.integers(0, 2, (B2, n)).astype(bool))
    match = jnp.asarray(rng.integers(0, 2, (B2, n)).astype(bool))
    hi = jnp.asarray(rng.integers(0, 2, (K, n)).astype(np.int32))
    lo = jnp.asarray(rng.integers(0, 2, (K, n)).astype(np.int32))
    include = jnp.asarray(rng.integers(0, 2, (K, n)).astype(bool))
    return lit2, fired2, sel, match, hi, lo, include


@pytest.mark.parametrize("shape", PARITY_SHAPES)
@pytest.mark.parametrize("packing", ["none", "2bit"])
def test_ta_feedback_session_parity_sweep(shape, packing):
    """Compiled ``ta_feedback`` entries agree EXACTLY across backends,
    shard grids, and packing modes (packing changes the serving operand
    layout, never the feedback deltas)."""
    B2, K, n, M, R, tr, C, tc, S, sr = shape
    _, sys_ = _make_system(4, K, n, M, R, tr, C, tc, S, sr, seed=B2)
    ops = _feedback_operands(np.random.default_rng(B2), B2, K, n)
    oracle = sys_.compile(RuntimeSpec(backend="xla")).ta_feedback(*ops)
    kernel = sys_.compile(RuntimeSpec(backend="pallas", packing=packing,
                                      interpret=True)).ta_feedback(*ops)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(oracle))
    assert kernel.dtype == jnp.int32


def _prototype_problem(seed=3, n_train=512, n_holdout=128):
    cfg = CoTMConfig(n_literals=64, n_clauses=40, n_classes=4,
                     n_states=64, threshold=16, specificity=4.0)
    x, y = prototype(n_train + n_holdout, n_classes=4, n_features=32,
                     flip=0.05, seed=seed)
    lits = jnp.asarray(np.concatenate([x, 1 - x], -1).astype(bool))
    labels = jnp.asarray(y)
    return cfg, (lits[:n_train], labels[:n_train]), \
        (lits[n_train:], labels[n_train:])


def _deployed(cfg, tr_l, tr_y, *, backend="xla", variability=False,
              pretrain_batches=8, seed=0):
    """Digitally pre-train one epoch (a half-trained deployment), then
    encode the model into a system + compiled session."""
    params = cfg.init(jax.random.key(seed))
    key = jax.random.key(seed + 1)
    for b in range(pretrain_batches):
        key, k = jax.random.split(key)
        params = train_step_batch(params, tr_l[b * 64:(b + 1) * 64],
                                  tr_y[b * 64:(b + 1) * 64], k, cfg)
    system = build_system(params, cfg, jax.random.key(seed + 2),
                          IMPACTConfig(variability=variability,
                                       finetune=variability))
    session = system.compile(RuntimeSpec(backend=backend, interpret=True))
    return params, system, session


@pytest.mark.parametrize("variability", [False, True])
def test_online_trainer_ta_trajectory_parity(variability):
    """Two trainers differing ONLY in backend (oracle vs Pallas kernel)
    walk bit-identical TA/weight trajectories and bill identical write
    energy — under ideal AND noisy devices (the noise draws are keyed,
    so parity must survive them too)."""
    cfg, (tr_l, tr_y), _ = _prototype_problem()
    states = {}
    for backend in ("xla", "pallas"):
        params, _, session = _deployed(cfg, tr_l, tr_y, backend=backend,
                                       variability=variability)
        trainer = OnlineTrainer(session, params, cfg,
                                key=jax.random.key(11),
                                variability=variability)
        for step in range(3):
            trainer.update(tr_l[step * 64:(step + 1) * 64],
                           tr_y[step * 64:(step + 1) * 64],
                           key=jax.random.key(100 + step))
        states[backend] = trainer
    a, b = states["xla"], states["pallas"]
    np.testing.assert_array_equal(np.asarray(a.params.ta_state),
                                  np.asarray(b.params.ta_state))
    np.testing.assert_array_equal(np.asarray(a.params.weights),
                                  np.asarray(b.params.weights))
    assert a.write_energy_j == b.write_energy_j
    assert [r["n_flips"] for r in a.records] == \
        [r["n_flips"] for r in b.records]


def test_interleaved_train_serve_improves_and_reconciles():
    """The whole tentpole in one run: updates interleave with serving
    sweeps through the SAME compiled session; held-out accuracy improves,
    the serving executable is never retraced, per-request read bills
    keep reconciling with the batch meter at 1e-9, serving reports bill
    zero write energy, and the Chrome trace carries balanced
    train_update spans between the serving spans."""
    cfg, (tr_l, tr_y), (ho_l, ho_y) = _prototype_problem()
    params, system, session = _deployed(cfg, tr_l, tr_y)
    trace = Tracer()
    trainer = OnlineTrainer(session, params, cfg, key=jax.random.key(7),
                            variability=False, trace=trace)
    acc0 = trainer.evaluate(ho_l, ho_y)
    session.warm(64, "infer_step")
    traces0 = dict(session._traces)

    for epoch in range(4):
        for b in range(0, 512, 64):
            # serving sweep ... (live traffic between updates)
            t0 = trace.clock()
            res = session.infer_step(np.asarray(tr_l[b:b + 64], np.int8),
                                     np.ones((64,), bool))
            trace.span("serve_sweep", t0, trace.clock())
            e_lanes = (np.asarray(res.e_clause_lanes, np.float64)
                       + np.asarray(res.e_class_lanes, np.float64))
            # ... whose per-request bills reconcile with the batch meter
            # at 1e-9 (the lane fold is the billing ledger)
            batch_rep = system.step_report(
                np.asarray(res.e_clause_lanes, np.float64),
                np.asarray(res.e_class_lanes, np.float64), 64)
            np.testing.assert_allclose(batch_rep.read_energy_j,
                                       e_lanes.sum(), rtol=1e-9, atol=0.0)
            # the one-shot report path measures the same physics (f32
            # device accumulation order differs) and bills zero writes
            rep = session.infer_with_report(tr_l[b:b + 64]).report
            np.testing.assert_allclose(rep.read_energy_j, e_lanes.sum(),
                                       rtol=1e-5, atol=1e-30)
            assert rep.write_energy_j == 0.0
            assert batch_rep.write_energy_j == 0.0
            # ... then one update sweep on the same fabric
            trainer.update(tr_l[b:b + 64], tr_y[b:b + 64])

    acc1 = trainer.evaluate(ho_l, ho_y)
    assert acc1 > acc0, (acc0, acc1)
    # conductance swaps propagated WITHOUT retracing the serving entries
    assert dict(session._traces)["infer_step"] == traces0["infer_step"]
    assert dict(session._traces)["predict"] == traces0["predict"]
    # the serving path now agrees with the trainer's digital twin (up to
    # the write-hysteresis band on the class tile)
    dp = np.asarray(digital_predict(trainer.params, ho_l, cfg))
    ap = np.asarray(session.predict(ho_l).predictions)
    assert (dp == ap).mean() > 0.7
    # balanced, loadable trace with one span per update
    events = trace.to_json()
    validate_events(events)
    spans = [e for e in events if e["name"] == "train_update"]
    assert len(spans) == 2 * len(trainer.records)       # B/E pairs
    assert all(s["ph"] in ("B", "E") for s in spans)


def test_trainer_write_meter_identity_f64():
    """The f64 sum of per-update write bills equals the running meter
    and the aggregated report lane EXACTLY (same accumulation order)."""
    from repro.serve.impact_engine import aggregate_reports
    cfg, (tr_l, tr_y), _ = _prototype_problem()
    params, _, session = _deployed(cfg, tr_l, tr_y, variability=True)
    trainer = OnlineTrainer(session, params, cfg, key=jax.random.key(3),
                            variability=True)
    for step in range(4):
        trainer.update(tr_l[step * 64:(step + 1) * 64],
                       tr_y[step * 64:(step + 1) * 64])
    per_update = sum(r["write_energy_j"] for r in trainer.records)
    assert per_update == trainer.write_energy_j
    assert aggregate_reports(trainer.reports).write_energy_j \
        == trainer.write_energy_j
    assert trainer.write_energy_j > 0.0


def test_trainer_rejects_packed_and_coresident_sessions():
    from repro.impact import build_coresident
    cfg, (tr_l, tr_y), _ = _prototype_problem()
    params, system, _ = _deployed(cfg, tr_l, tr_y)
    packed = system.compile(RuntimeSpec(backend="pallas",
                                        packing="2bit", interpret=True))
    with pytest.raises(ValueError, match="unpacked"):
        OnlineTrainer(packed, params, cfg, key=jax.random.key(0))
    combined, plan = build_coresident([system, system])
    co = combined.compile(RuntimeSpec(backend="xla", coresident=plan))
    with pytest.raises(ValueError, match="single-tenant"):
        OnlineTrainer(co, params, cfg, key=jax.random.key(0))
