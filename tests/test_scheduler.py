"""Continuous-batching scheduler: slot-table invariants, mid-flight
admission neutrality, backpressure, admission policy, and the tail-latency
claim (continuous < flush-to-completion p95 under a seeded Poisson arrival
trace) — all on compiled ``InferenceSession`` runtimes."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoTMConfig
from repro.core.cotm import CoTMParams
from repro.impact import IMPACTConfig, RuntimeSpec, build_system
from repro.serve import (Backpressure, IMPACTEngine, SlotTable,
                         latency_percentiles, poisson_arrivals,
                         replay_trace)


def spec(backend="xla", *, meter=True, capacity=None):
    return RuntimeSpec(backend=backend,
                       metering="staged" if meter else "off",
                       capacity=capacity)


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
    system = build_system(params, cfg, jax.random.key(0),
                          IMPACTConfig(variability=False, finetune=False))
    lits = rng.random((80, K)) < 0.5
    return system, lits


# -- SlotTable ---------------------------------------------------------------

def test_slot_table_admit_release_mask():
    t = SlotTable(4)
    assert t.occupancy == 0 and t.free == 4
    a = t.admit("a")
    b = t.admit("b")
    assert (a, b) == (0, 1)                   # lowest free slot, stable
    np.testing.assert_array_equal(t.valid_mask(), [True, True, False, False])
    assert t.release(a) == "a"
    assert t.free_slots() == [0, 2, 3]
    assert t.admit("c") == 0                  # freed lane is reused
    assert dict(t.occupied()) == {0: "c", 1: "b"}
    with pytest.raises(KeyError):
        t.release(3)                          # double-free / free-free


def test_slot_table_full_raises_backpressure():
    t = SlotTable(2)
    t.admit(1)
    t.admit(2)
    with pytest.raises(Backpressure):
        t.admit(3)
    t.release(0)
    assert t.admit(3) == 0                    # release makes room again


def test_slot_table_compact():
    t = SlotTable(5)
    for x in "abcd":
        t.admit(x)
    t.release(0)
    t.release(2)
    moves = t.compact()
    assert moves == [(1, 0), (3, 1)]          # stable order, dense prefix
    np.testing.assert_array_equal(
        t.valid_mask(), [True, True, False, False, False])
    assert [t.slots[i] for i in range(2)] == ["b", "d"]


def test_slot_table_rejects_bad_capacity():
    with pytest.raises(ValueError):
        SlotTable(0)


# -- mid-flight admission neutrality ----------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_admission_never_perturbs_inflight_lanes(small_system, backend):
    """A lane admitted mid-flight must not change any other lane's class
    scores or energy bill — free lanes are all-1 literals (rows float, no
    current), so a sweep with {A} and a sweep with {A, B} agree exactly on
    A.  This is the slot-table form of the padding-neutrality argument."""
    system, lits = small_system
    session = system.compile(spec(backend, capacity=8))
    cap = 8
    buf = np.ones((cap, system.n_literals), np.int8)
    buf[0] = lits[0]
    valid = np.zeros((cap,), bool)
    valid[0] = True
    solo = session.infer_step(buf, valid)
    p_solo = np.asarray(solo.predictions)
    # admit three more requests into free lanes, A untouched
    for j, row in enumerate(lits[1:4], start=1):
        buf[j] = row
        valid[j] = True
    co = session.infer_step(buf, valid)
    assert np.asarray(co.predictions)[0] == p_solo[0]
    np.testing.assert_allclose(np.asarray(co.e_clause_lanes)[0],
                               np.asarray(solo.e_clause_lanes)[0],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(co.e_class_lanes)[0],
                               np.asarray(solo.e_class_lanes)[0],
                               rtol=1e-6)
    # and the free lanes metered exactly zero in the solo sweep
    np.testing.assert_array_equal(np.asarray(solo.e_clause_lanes)[1:], 0.0)
    np.testing.assert_array_equal(np.asarray(solo.e_class_lanes)[1:], 0.0)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("meter", [False, True])
def test_invalid_lanes_predict_sentinel(small_system, backend, meter):
    """Free lanes (all-1 literals) fire every nonempty clause, so their
    argmax would look like a real class; ``infer_step`` must return the
    sentinel -1 for ``valid == False`` lanes on BOTH the fused
    (metering='off') and staged (metering='staged') paths, while valid
    lanes keep matching the direct predict path."""
    system, lits = small_system
    session = system.compile(spec(backend, meter=meter, capacity=8))
    cap = 8
    buf = np.ones((cap, system.n_literals), np.int8)
    buf[:3] = lits[:3]
    valid = np.zeros((cap,), bool)
    valid[:3] = True
    preds = np.asarray(session.infer_step(buf, valid).predictions)
    assert (preds[3:] == -1).all(), preds
    direct = np.asarray(session.predict(jnp.asarray(lits[:3])).predictions)
    np.testing.assert_array_equal(preds[:3], direct)


def test_engine_release_refill_reuses_lanes(small_system):
    """Released lanes are reset to the currentless all-1 pattern and
    refilled on the next step; predictions across refills match the
    direct path."""
    system, lits = small_system
    session = system.compile(spec(meter=False, capacity=4))
    direct = np.asarray(session.predict(jnp.asarray(lits[:12])).predictions)
    eng = IMPACTEngine(session)
    done = {}
    for i in range(12):
        eng.submit(lits[i])
    while len(done) < 12:
        done.update(eng.step(force=True))
        # between sweeps the table fully drains (single-sweep workload)
        assert eng.table.occupancy == 0
        assert (eng._lane_lits == 1).all()
    assert [done[i] for i in range(12)] == list(direct)
    assert len(eng.batch_stats) == 3           # 12 requests / 4 lanes


# -- backpressure ------------------------------------------------------------

def test_engine_backpressure_and_recovery(small_system):
    system, lits = small_system
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)),
                       queue_capacity=2)
    # free slots (4) + queue capacity (2) absorb 6 submissions
    for i in range(6):
        eng.submit(lits[i])
    with pytest.raises(Backpressure):
        eng.submit(lits[6])
    assert eng.try_submit(lits[6]) is None
    done = eng.step(force=True)                # sweep frees 4 lanes
    assert len(done) == 4
    assert eng.try_submit(lits[6]) is not None  # room again


def test_engine_unbounded_queue_never_sheds(small_system):
    system, lits = small_system
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)))
    for row in lits:
        eng.submit(row)                        # queue_capacity=None
    assert len(eng.queue.pending) == len(lits)


# -- admission policy --------------------------------------------------------

def test_target_occupancy_defers_sparse_sweeps(small_system):
    """With target_occupancy=1.0 and a long max_wait, a partially filled
    table holds; filling it (or forcing) fires the sweep."""
    system, lits = small_system
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)),
                       max_wait_s=30.0, target_occupancy=1.0)
    for i in range(3):
        eng.submit(lits[i])
    assert eng.step() == []                    # 3/4 occupied, not stale
    assert eng.table.occupancy == 3            # admitted but held in-flight
    eng.submit(lits[3])
    assert len(eng.step()) == 4                # full table fires


def test_injected_clock_drives_staleness_and_latency(small_system):
    """The engine stamps arrivals, measures staleness, and records
    latencies on ONE injectable clock — a virtual clock makes the
    admission policy and the latency ledger fully deterministic."""
    system, lits = small_system
    t = [100.0]
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)),
                       max_wait_s=0.5, target_occupancy=1.0,
                       clock=lambda: t[0])
    eng.submit(lits[0])
    assert eng.step() == []                    # 1/4 lanes, fresh on t
    t[0] += 1.0                                # virtual second elapses
    out = eng.step()                           # now stale: fires
    assert len(out) == 1
    (rec,) = eng.request_records
    assert rec.arrived == 100.0 and rec.completed == 101.0
    assert rec.latency_s == pytest.approx(1.0)
    assert rec.queue_s == 0.0     # admitted into a free lane on step 1,
                                  # then held in-flight by the policy


def test_staleness_clock_starts_at_admission_not_arrival(small_system):
    """The staleness window is measured from ADMISSION, as the policy
    documents — not from arrival.  A request that sat queued behind a
    full table must not fire a premature partial sweep the moment it
    finally wins a lane (queue wait is backpressure's job); the window
    restarts when the lane is granted."""
    system, lits = small_system
    t = [100.0]
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=2)),
                       max_wait_s=0.5, target_occupancy=1.0,
                       clock=lambda: t[0])
    for i in range(3):
        eng.submit(lits[i])
    assert len(eng.step()) == 2       # full table fires; 3rd still queued
    t[0] = 100.9                      # 3rd has now *arrived* 0.9s ago
    assert eng.step() == []           # admitted at 100.9: fresh, holds
    assert eng.table.occupancy == 1
    t[0] = 101.5                      # 0.6s since ADMISSION: stale
    out = eng.step()
    assert len(out) == 1
    rec = eng.request_records[-1]
    assert rec.arrived == 100.0 and rec.admitted == 100.9
    assert rec.queue_s == pytest.approx(0.9)


def test_max_wait_fires_stale_partial_sweep(small_system):
    system, lits = small_system
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)),
                       max_wait_s=0.02, target_occupancy=1.0)
    eng.submit(lits[0])
    assert eng.step() == []                    # fresh: policy holds it
    time.sleep(0.03)
    out = eng.step()                           # stale: fires despite 1/4
    assert len(out) == 1
    assert eng.batch_stats[-1].occupancy == 0.25


# -- per-request accounting --------------------------------------------------

def test_per_request_energy_attribution(small_system):
    """Each request carries its own read-energy bill; the bills sum to the
    batch meters and a solo request's bill equals the reference report."""
    system, lits = small_system
    session = system.compile(spec(capacity=8))
    ref = session.infer_with_report(jnp.asarray(lits[:1])).report
    eng = IMPACTEngine(session)
    preds, stats = eng.run(lits[:20])
    recs = eng.request_records
    assert len(recs) == 20
    assert all(r.e_read_j > 0 for r in recs)
    np.testing.assert_allclose(sum(r.e_read_j for r in recs),
                               stats["energy"].read_energy_j, rtol=1e-9)
    # solo-request bill == single-sample reference report
    solo = IMPACTEngine(session)
    solo.submit(lits[0])
    solo.step(force=True)
    np.testing.assert_allclose(solo.request_records[0].e_read_j,
                               ref.read_energy_j, rtol=1e-6)


def test_request_latency_percentiles_in_stats(small_system):
    system, lits = small_system
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=8)))
    _, stats = eng.run(lits[:24])
    lat = stats["latency"]
    assert lat["n"] == 24
    assert 0 < lat["p50_s"] <= lat["p95_s"] <= lat["p99_s"] <= lat["max_s"]
    assert stats["queue_wait"]["n"] == 24


def test_latency_percentiles_helper():
    assert latency_percentiles([]) == {}
    out = latency_percentiles([0.1] * 99 + [1.0])
    assert out["p50_s"] == pytest.approx(0.1)
    assert out["max_s"] == 1.0 and out["n"] == 100


# -- tail latency under mixed traffic ---------------------------------------

def test_continuous_beats_flush_p95_under_poisson(small_system):
    """The PR-2 acceptance invariant: under a seeded Poisson arrival trace,
    continuous batching shows lower p95 per-request latency than
    flush-to-completion at equal offered load.  Flush holds late arrivals
    for a whole accumulate/flush cycle (max_wait_s staleness), continuous
    admits them into the next sweep.

    The expected margin is ~6x (sweep-time p95 vs a 60 ms staleness
    window), but this is wall-clock measurement on a possibly shared
    runner, so one retry absorbs a freak scheduler stall (the strict gate
    runs in the perf-smoke CI job on the full benchmark trace)."""
    system, lits = small_system
    arrivals = poisson_arrivals(60, rate_rps=250.0, seed=3)
    session = system.compile(spec(meter=False, capacity=16))

    def replay_pair():
        cont = IMPACTEngine(session, max_wait_s=0.0)
        cont.warmup()
        r_cont = replay_trace(cont, lits, arrivals)
        flush = IMPACTEngine(session, mode="flush", buckets=(16,),
                             max_wait_s=0.06)
        flush.warmup()
        r_flush = replay_trace(flush, lits, arrivals)
        assert r_cont["completed"] == r_flush["completed"] == 60
        return r_cont, r_flush

    r_cont, r_flush = replay_pair()
    if not r_cont["p95_s"] < r_flush["p95_s"]:     # pragma: no cover
        r_cont, r_flush = replay_pair()
    assert r_cont["p95_s"] < r_flush["p95_s"], (r_cont, r_flush)
