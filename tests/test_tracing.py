"""Chrome-tracing observability: emitted traces must be loadable (valid
event array, monotonic timestamps, balanced B/E pairs per track) and
their span durations must reconcile exactly with the RequestRecord /
BatchStats latency ledger they are cut from."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoTMConfig
from repro.core.cotm import CoTMParams
from repro.impact import IMPACTConfig, RuntimeSpec, build_system
from repro.serve import (IMPACTEngine, REQUEST_PHASES, Tracer,
                         poisson_arrivals, replay_trace, validate_events)
from repro.serve.tracing import PID_ENGINE, PID_REQUESTS


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
    lits = rng.random((40, K)) < 0.5
    return system, lits


def _spans(events, *, pid, tid=None, name=None):
    """[(name, tid, b_ts, e_ts, b_args)] for balanced B/E pairs."""
    open_, out = {}, []
    for e in events:
        if e.get("pid") != pid or e["ph"] not in ("B", "E"):
            continue
        if tid is not None and e["tid"] != tid:
            continue
        if name is not None and e["name"] != name:
            continue
        key = (e["tid"], e["name"])
        if e["ph"] == "B":
            open_[key] = e
        else:
            b = open_.pop(key)
            out.append((e["name"], e["tid"], b["ts"], e["ts"],
                        b.get("args", {})))
    assert not open_, open_
    return out


# -- validator ---------------------------------------------------------------

def test_validate_events_catches_broken_traces():
    tr = Tracer()
    tr.span("ok", 1.0, 2.0)
    validate_events(tr.to_json())            # balanced: passes

    tr = Tracer()
    tr.begin("dangling", ts=1.0)
    with pytest.raises(ValueError, match="unbalanced"):
        validate_events(tr.to_json())

    tr = Tracer()
    tr.end("orphan", ts=1.0)
    with pytest.raises(ValueError, match="without matching B"):
        validate_events(tr.to_json())

    # Interleaved spans on ONE track (A-B-A-B) are not a flame graph.
    tr = Tracer()
    tr.begin("a", ts=1.0)
    tr.begin("b", ts=2.0)
    tr.end("a", ts=3.0)
    tr.end("b", ts=4.0)
    with pytest.raises(ValueError, match="interleaved"):
        validate_events(tr.to_json())

    with pytest.raises(ValueError, match="non-monotonic"):
        validate_events([
            dict(name="x", ph="i", s="t", ts=2.0, pid=0, tid=0),
            dict(name="y", ph="i", s="t", ts=1.0, pid=0, tid=0)])
    with pytest.raises(ValueError, match="missing"):
        validate_events([dict(name="x", ph="i", ts=0.0, pid=0)])


def test_to_json_rebases_sorts_and_scales():
    """Rendered timestamps are microseconds since the earliest event,
    sorted, with metadata pinned at ts=0."""
    t = [1000.0]
    tr = Tracer(clock=lambda: t[0])
    tr.span("late", 1000.5, 1000.75)
    tr.span("early", 1000.0, 1000.25)        # emitted second, starts first
    ev = tr.to_json()
    validate_events(ev)
    timed = [e for e in ev if e["ph"] != "M"]
    assert timed[0]["name"] == "early" and timed[0]["ts"] == 0.0
    assert timed[-1]["name"] == "late" and timed[-1]["ts"] == pytest.approx(
        0.75e6)
    assert all(e["ts"] == 0.0 for e in ev if e["ph"] == "M")
    # json round-trip: the array is what a viewer loads
    validate_events(json.loads(json.dumps(ev)))


# -- IMPACT engine integration ----------------------------------------------

def test_engine_burst_trace_is_valid_and_reconciles(small_system):
    """A burst through the continuous scheduler yields a loadable trace
    whose per-request lifecycle spans sum EXACTLY to the RequestRecord
    ledger and whose scheduler sweep span matches BatchStats.latency_s
    — same clock readings, zero tolerance beyond float/us rounding."""
    system, lits = small_system
    tr = Tracer()
    eng = IMPACTEngine(system.compile(spec(capacity=8)), trace=tr)
    eng.run(lits[:20])
    ev = tr.to_json()
    validate_events(ev)

    # Scheduler track: one sweep span per recorded batch, equal duration.
    sweeps = _spans(ev, pid=PID_ENGINE, tid=0, name="sweep")
    assert len(sweeps) == len(eng.batch_stats)
    for (_, _, b, e, args), st in zip(sweeps, eng.batch_stats):
        assert (e - b) / 1e6 == pytest.approx(st.latency_s, abs=1e-6)
        assert args["shape"] == st.bucket
        assert args["n_valid"] == st.n_valid
        assert args["occupancy"] == pytest.approx(st.occupancy)

    # Request tracks: the documented 4-phase lifecycle, contiguous, and
    # queued+admitted+sweep == the ledger's end-to-end latency.
    recs = {r.rid: r for r in eng.request_records}
    assert len(recs) == 20
    for rid, rec in recs.items():
        phases = {n: (b, e) for n, _, b, e, _ in
                  _spans(ev, pid=PID_REQUESTS, tid=rid)}
        assert tuple(phases) == REQUEST_PHASES or \
            set(phases) == set(REQUEST_PHASES)
        for a, b in zip(REQUEST_PHASES, REQUEST_PHASES[1:]):
            assert phases[a][1] == phases[b][0]          # contiguous
        lat_us = phases["sweep"][1] - phases["queued"][0]
        assert lat_us / 1e6 == pytest.approx(rec.latency_s, abs=1e-6)


#: The scheduler cycle's live spans: each sweep has one of each.
SWEEP_STAGES = ("upload", "dispatch", "ready", "fetch")


def _live(tr, name):
    """[(begin_s, end_s)] of scheduler-track spans ``name``, on the
    engine's clock (raw, unrebased seconds)."""
    return [(b, e) for _, _, b, e, _ in
            _spans(tr.events, pid=PID_ENGINE, tid=0, name=name)]


def _inside(inner, outer):
    return any(a <= inner[0] and inner[1] <= b for a, b in outer)


def _traced_burst(system, lits, tr, n=20):
    """Serve ``n`` rows through a capacity-8 engine, one ``step()`` at a
    time, after one idle step; returns (engine, step() calls)."""
    eng = IMPACTEngine(system.compile(spec(capacity=8)), trace=tr)
    assert eng.step() == []                    # idle step: no sweep
    calls = 1
    for row in lits[:n]:
        eng.submit(row)
    done = {}
    while len(done) < n:
        done.update(eng.step(force=True))
        calls += 1
    return eng, calls


def test_scheduler_cycle_spans_tile_and_nest(small_system):
    """Every ``step()`` call is one ``step`` span; every sweep has one
    ``upload``, ``dispatch``, ``ready`` and ``fetch``; dispatch, ready
    and fetch tile ``sweep`` (which still equals BatchStats.latency_s),
    and every scheduler span lies inside a ``step``."""
    system, lits = small_system
    tr = Tracer()
    eng, calls = _traced_burst(system, lits, tr)
    validate_events(tr.to_json())
    assert tr._mirrors == []                   # every annotation closed
    steps = _live(tr, "step")
    sweeps = _live(tr, "sweep")
    assert len(steps) == calls
    assert len(sweeps) == len(eng.batch_stats) >= 3
    for (b, e), st in zip(sweeps, eng.batch_stats):
        assert e - b == st.latency_s           # same clock readings
    stages = {name: _live(tr, name) for name in SWEEP_STAGES}
    for name, items in stages.items():
        assert len(items) == len(sweeps), name
    for i, (b, e) in enumerate(sweeps):
        d, r, f = (stages[n][i] for n in ("dispatch", "ready", "fetch"))
        assert (d[0], d[1], r[1], f[1]) == (b, r[0], f[0], e)
        assert stages["upload"][i][1] <= b
    for name in ("admission", "upload", "sweep", "billing", "release"):
        for span in _live(tr, name):
            assert _inside(span, steps), name
    for name in ("dispatch", "ready", "fetch"):
        for span in stages[name]:
            assert _inside(span, sweeps), name


def test_sweep_span_carries_kernel_plan():
    """On a kernel backend each ``sweep`` span carries the session's
    kernel plan: the literal row-shards (3 here, 48 literals on 16-row
    tiles), the column blocks the kernel walks (2: one 512-column clause
    tile in 256-wide blocks) and the kernel's VMEM bytes per grid step."""
    K, n, m, n_states = 48, 20, 3, 64
    rng = np.random.default_rng(5)
    ta = np.where(rng.random((K, n)) < 0.1, n_states + 1, n_states)
    params = CoTMParams(ta_state=jnp.asarray(ta, jnp.int32),
                        weights=jnp.asarray(rng.integers(-9, 9, (m, n)),
                                            jnp.int32))
    system = build_system(
        params, CoTMConfig(n_literals=K, n_clauses=n, n_classes=m,
                           n_states=n_states),
        jax.random.key(0),
        IMPACTConfig(max_tile_rows=16, variability=False, finetune=False))
    session = system.compile(RuntimeSpec(backend="pallas",
                                         metering="fused", capacity=8))
    plan = session.kernel_plan("infer_step", 8)
    assert (plan.row_shards, plan.literal_chunks) == (3, 3)
    assert plan.column_blocks == 2
    tr = Tracer()
    eng = IMPACTEngine(session, trace=tr)
    eng.run(rng.random((12, K)) < 0.5)
    sweeps = _spans(tr.to_json(), pid=PID_ENGINE, tid=0, name="sweep")
    assert len(sweeps) == len(eng.batch_stats) >= 2
    for *_, args in sweeps:
        assert args["row_shards"] == 3
        assert args["column_blocks"] == 2
        assert args["vmem_step_bytes"] == plan.vmem_step_bytes > 0


def test_detached_tracer_records_nothing(small_system):
    """With ``trace=None`` the scheduler records no span: a tracer that
    was attached and then detached gains no event from a burst."""
    system, lits = small_system
    tr = Tracer()
    eng = IMPACTEngine(system.compile(spec(capacity=8)), trace=tr)
    eng.trace = None
    n_meta = len(tr)
    eng.run(lits[:12])
    assert eng.trace is None and len(eng.batch_stats) >= 2
    assert len(tr) == n_meta and tr._mirrors == []


def test_scheduler_spans_reach_the_profiler_host_plane(small_system,
                                                       tmp_path):
    """Live scheduler spans are mirrored as profiler annotations: a
    profile of a traced burst holds ``step``, ``sweep`` and ``fetch`` on
    its host plane; past-timestamp request spans are not mirrored."""
    system, lits = small_system
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python frames would name `step`
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _traced_burst(system, lits, Tracer(), n=12)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in profile.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"step", "sweep", "fetch"} <= names
    assert "queued" not in names


def test_flush_trace_carries_bucket_shape(small_system):
    """Flush-mode sweeps run at bucketed shapes; the trace must say
    which bucket each sweep was padded to."""
    system, lits = small_system
    tr = Tracer()
    eng = IMPACTEngine(system.compile(spec(capacity=8)), mode="flush",
                       buckets=(2, 4, 8), max_wait_s=0.0, trace=tr)
    for i in range(3):
        eng.submit(lits[i])
    eng.step(force=True)
    ev = tr.to_json()
    validate_events(ev)
    (sweep,) = _spans(ev, pid=PID_ENGINE, tid=0, name="sweep")
    assert sweep[4]["shape"] == 4              # 3 requests -> bucket 4
    assert sweep[4]["n_valid"] == 3
    assert eng.batch_stats[-1].bucket == 4


def test_trace_rides_injected_virtual_clock(small_system):
    """The tracer is re-clocked onto the engine's injected clock, so a
    virtual-time run traces deterministically (and the admission span
    vocabulary shows up where the policy acted)."""
    system, lits = small_system
    t = [100.0]
    tr = Tracer()
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)),
                       max_wait_s=0.5, target_occupancy=1.0,
                       clock=lambda: t[0], trace=tr)
    assert tr.clock() == 100.0                 # re-clocked at attach
    eng.submit(lits[0])
    assert eng.step() == []                    # fresh: holds
    t[0] = 101.0
    assert len(eng.step()) == 1                # stale: fires
    ev = tr.to_json()
    validate_events(ev)
    (rec,) = eng.request_records
    phases = {n: (b, e) for n, _, b, e, _ in
              _spans(ev, pid=PID_REQUESTS, tid=rec.rid)}
    assert phases["queued"] == (0.0, 0.0)      # arrived==admitted==100.0
    assert phases["sweep"][0] == pytest.approx(1.0e6)   # fired at 101.0
    names = {e["name"] for e in ev if e["ph"] == "B"}
    assert {"admission", "sweep", "billing", "release"} <= names


def test_replay_trace_writes_loadable_chrome_json(small_system, tmp_path):
    """The acceptance artifact: replay_trace(trace_path=...) writes a
    Chrome-tracing JSON array that loads, covers every completed request
    with a balanced lifecycle, and marks shed requests as instants."""
    system, lits = small_system
    n = 24
    eng = IMPACTEngine(system.compile(spec(meter=False, capacity=4)),
                       max_wait_s=0.0, queue_capacity=4)
    eng.warmup()
    arrivals = poisson_arrivals(n, 800.0, seed=3)
    path = tmp_path / "serve.trace.json"
    out = replay_trace(eng, lits[:n], arrivals, trace_path=str(path))
    assert out["trace_path"] == str(path)
    with open(path) as f:
        ev = json.load(f)
    validate_events(ev)
    done_rids = {r.rid for r in eng.request_records}
    assert out["completed"] == len(done_rids) == n - out["shed"]
    for rid in done_rids:
        names = [nm for nm, *_ in _spans(ev, pid=PID_REQUESTS, tid=rid)]
        assert sorted(names) == sorted(REQUEST_PHASES)
    sheds = [e for e in ev if e["name"] == "shed"]
    assert len(sheds) == out["shed"]
    assert all(e["ph"] == "i" for e in sheds)


# -- LM engine integration ---------------------------------------------------

def test_lm_engine_emits_same_span_vocabulary():
    """The LM front emits prefill/decode + per-request spans through the
    same Tracer, so both engines open in one viewer."""
    from repro.configs import get_config
    from repro.models import build
    from repro.serve import Engine, Request, ServeConfig

    cfg = get_config("qwen3-8b").smoke()
    model = build(cfg)
    params = model.init(jax.random.key(0))
    tr = Tracer()
    eng = Engine(model, params, ServeConfig(max_len=64, temperature=0.0),
                 trace=tr)
    prompts = jax.random.randint(jax.random.key(1), (3, 8), 0, cfg.vocab)
    eng.generate(prompts, 3)
    reqs = [Request(i, np.asarray(prompts[i]), max_new=3) for i in range(3)]
    eng.serve_continuous(reqs, capacity=2, seed=0)
    ev = tr.to_json()
    validate_events(ev)
    names = {e["name"] for e in ev if e["ph"] == "B"}
    assert {"prefill", "decode", "decode_step", "request"} <= names
    assert len(_spans(ev, pid=PID_REQUESTS, name="request")) == 3
