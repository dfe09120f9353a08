"""Continuous-batching IMPACT inference front: crossbar serving under
request traffic.

The LM zoo's ``Engine`` serves autoregressive token streams; this engine
serves the other workload the paper targets — high-throughput CoTM
classification on the Y-Flash crossbar twin.

Scheduler design (the PR-2 rebuild):

* **Slot table, not flush-and-drain.**  A fixed-capacity ``SlotTable``
  (capacity = ``max_batch``) backs a persistent (capacity, K) literal
  buffer.  Free lanes hold all-1 literals (every crossbar row floats, so
  they draw no current); the validity mask is derived from occupancy.
  Each scheduler step admits queued requests into free lanes, runs ONE
  jitted crossbar sweep (``IMPACTSystem.infer_step`` — fixed shape, so
  admission patterns never retrace), then releases every lane that
  finished.  Classification completes in one sweep, so the table drains
  and refills between steps — a late arrival waits at most one sweep,
  never a whole flushed bucket (the head-of-line blocking the old
  flush-to-completion mode exhibits under mixed traffic).

* **Admission policy.**  ``target_occupancy`` (fraction of capacity) and
  ``max_wait_s`` trade latency for fuller sweeps: a step fires when
  occupancy reaches the target, when the oldest admitted request has
  waited ``max_wait_s``, or when the table is full.  The default
  ``target_occupancy=0.0`` fires on any occupancy (lowest latency).

* **Backpressure.**  ``queue_capacity`` bounds the admission queue;
  ``submit`` raises ``Backpressure`` when slots and queue are both full
  (``try_submit`` returns ``None`` instead) so load sheds at the edge
  rather than growing an unbounded backlog.

* **Per-request metering.**  Every request gets a ``RequestRecord`` with
  end-to-end latency (arrival -> completion, through the queue) and its
  own read-energy bill from the per-lane meters in ``infer_step``; step-
  level ``BatchStats`` carry occupancy and p50/p95/p99 of the requests
  they completed, and ``stats()``/``replay_trace`` aggregate tail
  percentiles across a run.

* **Flush mode kept for A/B.**  ``mode="flush"`` preserves the PR-1
  accumulate/pad-to-bucket scheduler (shape-bucketed jit) so benchmarks
  can measure continuous vs. flush-to-completion tail latency on the same
  arrival trace (``benchmarks/impact_throughput.py`` writes the
  comparison to ``BENCH_serve.json``).

Runtime configuration (PR-4): the engine takes a compiled
``InferenceSession`` — backend, mesh topology, metering mode, and the
slot-table shape are all resolved ONCE by ``IMPACTSystem.compile(spec)``
before the first request arrives, and the scheduler knows nothing about
impl/mesh/metering.  Passing a bare ``IMPACTSystem`` compiles the default
spec at ``max_batch`` as a convenience; the legacy ``impl=`` / ``mesh=``
/ ``meter_energy=`` kwargs keep working through a ``SpecDeprecationWarning``
shim that folds them into the spec.

Energy metering note: ``metering="fused"`` bills every request from the
meters the fused kernel accumulates in VMEM while it infers — per-lane
summed column currents ride the single fused pass, so metered serving
runs at (near-)unmetered fused throughput (``benchmarks/
impact_throughput.py`` prices the overhead as the ``metered_fused``
sample).  ``metering="staged"`` keeps the per-shard oracle path the
fused meters are pinned against; ``metering="off"`` serves the fused
kernel and bills nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
import warnings
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..impact.energy import EnergyReport
from ..impact.pipeline import IMPACTSystem
from ..impact.runtime import (InferenceSession, SpecDeprecationWarning,
                              legacy_spec)
from .engine import (Backpressure, BatchingQueue, Request, SlotTable,
                     latency_percentiles)
from .tracing import Tracer

Array = jax.Array

DEFAULT_BUCKETS = (8, 32, 128, 512)


def aggregate_reports(reports: Sequence[EnergyReport]) -> EnergyReport:
    """Sum energy/op/datapoint accounting over per-batch reports; latency
    is the serial crossbar time of the whole run (batches stream through
    the same physical tiles).

    ``area_mm2`` is deliberately NOT carried over: ``tops_per_mm2``
    divides per-datapoint ops by ``latency_s``, so on a summed-latency
    aggregate it would shrink with the number of sweeps instead of
    describing the hardware — read it off the per-step reports (which
    carry the area), not the aggregate; the aggregate raises."""
    if not reports:
        raise ValueError("no reports to aggregate")
    return EnergyReport(
        read_energy_j=sum(r.read_energy_j for r in reports),
        clause_energy_j=sum(r.clause_energy_j for r in reports),
        class_energy_j=sum(r.class_energy_j for r in reports),
        program_energy_j=reports[0].program_energy_j,   # one-time encode
        erase_energy_j=reports[0].erase_energy_j,
        latency_s=sum(r.latency_s for r in reports),
        ops_crosspoint=sum(r.ops_crosspoint for r in reports),
        datapoints=sum(r.datapoints for r in reports),
        # Unlike the one-time encode cost above, write energy accrues per
        # window: an interleaved train+serve run's aggregate must carry
        # every update's pulse bill.
        write_energy_j=sum(r.write_energy_j for r in reports),
    )


@dataclasses.dataclass
class RequestRecord:
    """Per-request accounting: queue wait + service latency and the read
    energy this request's datapoint drew on the crossbar.  ``tenant``
    threads the owning tenant through the ledger (multi-tenant zoos);
    the single-tenant engine records everything under ``"default"``."""
    rid: int
    arrived: float
    admitted: float
    completed: float
    pred: int
    e_read_j: float = 0.0
    tenant: str = "default"

    @property
    def latency_s(self) -> float:
        return self.completed - self.arrived

    @property
    def queue_s(self) -> float:
        return self.admitted - self.arrived


@dataclasses.dataclass
class BatchStats:
    bucket: int           # kernel shape: slot capacity (continuous) / bucket
    n_valid: int
    latency_s: float      # wall time of this sweep
    samples_per_s: float
    cold: bool = False    # first sweep of this shape: includes jit compile
    occupancy: float = 0.0


@dataclasses.dataclass
class _Lane:
    """Slot-table payload: the request plus its admission timestamp."""
    req: Request
    admitted: float


class IMPACTEngine:
    """Crossbar inference with a continuous-batching scheduler.

    ``submit`` enqueues a literal vector (raising ``Backpressure`` when the
    engine is saturated); ``step`` runs one scheduler iteration — admit
    into free slots, fire at most one crossbar sweep, release finished
    lanes — and returns completed ``(rid, prediction)`` pairs; ``run``
    drives a whole request burst to completion.

    The engine serves through a compiled ``InferenceSession``: backend,
    mesh topology, and metering are properties of the session's
    ``RuntimeSpec``, resolved before the first request — the scheduler
    only admits, sweeps, releases, and bills.  Per-lane energy
    attribution still sums exactly to the batch meter under sharding
    (the per-device partial currents are psummed before billing).

    ``mode="flush"`` selects the legacy flush-to-completion scheduler;
    its ``buckets`` pad each flushed batch up to a compiled shape.
    Kwargs are validated per mode — ``buckets`` in continuous mode and
    ``target_occupancy`` in flush mode are rejected instead of silently
    ignored.

    ``trace`` (a ``serve.tracing.Tracer``) records the scheduler
    timeline as Chrome-tracing spans: per continuous-mode ``step``, the
    ``admission`` / ``upload`` / ``sweep`` (``dispatch`` -> ``ready`` ->
    ``fetch``) / ``billing`` / ``release`` regions on the scheduler track
    (lane ids and occupancy as span args; on ``sweep`` its count of
    device->host ``fetches`` and, from the session's kernel plan, its
    ``row_shards``, ``column_blocks`` and ``vmem_step_bytes``), mirrored
    as profiler annotations while they run, and the ``queued`` ->
    ``admitted`` ->
    ``sweep`` -> ``billed`` lifecycle on one track per request, cut from
    the same clock readings the ``RequestRecord`` ledger stores.  The
    tracer is re-clocked onto the engine's clock so an injected virtual
    clock traces deterministically.
    """

    def __init__(self, runtime: "InferenceSession | IMPACTSystem", *,
                 mode: str = "continuous", max_batch: int | None = None,
                 max_wait_s: float = 0.01,
                 buckets: Sequence[int] | None = None,
                 target_occupancy: float = 0.0,
                 queue_capacity: int | None = None,
                 clock: Callable[[], float] = time.time,
                 trace: Tracer | None = None,
                 impl: str | None = None, mesh=None,
                 meter_energy: bool | None = None):
        if mode not in ("continuous", "flush"):
            raise ValueError(f"mode must be 'continuous' or 'flush', "
                             f"got {mode!r}")
        # Per-mode kwarg validation: a knob the chosen scheduler never
        # reads is a configuration bug, not a default to shadow.
        if mode == "continuous" and buckets is not None:
            raise ValueError(
                "buckets only apply to mode='flush' (the continuous "
                "scheduler always sweeps the fixed slot-table shape); "
                f"got buckets={tuple(buckets)!r}")
        if mode == "flush" and target_occupancy != 0.0:
            raise ValueError(
                "target_occupancy only applies to mode='continuous' "
                "(flush fires on full/stale batches); got "
                f"target_occupancy={target_occupancy!r}")
        if not 0.0 <= target_occupancy <= 1.0:
            raise ValueError(f"target_occupancy must be in [0, 1], "
                             f"got {target_occupancy}")

        if isinstance(runtime, IMPACTSystem):
            # Convenience/legacy path: compile a session for this engine.
            legacy = sorted(k for k, v in dict(
                impl=impl, mesh=mesh, meter_energy=meter_energy).items()
                if v is not None)
            if legacy:
                warnings.warn(
                    f"IMPACTEngine({', '.join(legacy)}=...) is deprecated:"
                    f" encode runtime configuration in a RuntimeSpec and "
                    f"pass IMPACTEngine(system.compile(spec)) (see the "
                    f"README migration table)",
                    SpecDeprecationWarning, stacklevel=2)
            meter = meter_energy is None or meter_energy
            session = runtime.compile(legacy_spec(
                impl=impl, mesh=mesh,
                metering="staged" if meter else "off",
                capacity=128 if max_batch is None else max_batch))
        else:
            session = runtime
            if impl is not None or mesh is not None \
                    or meter_energy is not None:
                raise ValueError(
                    "impl/mesh/meter_energy cannot override a compiled "
                    "InferenceSession — encode them in its RuntimeSpec")
            if session.capacity is None:
                raise ValueError(
                    "IMPACTEngine needs a session compiled with "
                    "RuntimeSpec(capacity=...) — the slot-table sweep "
                    "shape is fixed at compile time")
            if max_batch is not None and max_batch != session.capacity:
                raise ValueError(
                    f"max_batch={max_batch} does not match the session's "
                    f"compiled capacity {session.capacity}")
        if session.coresident is not None:
            raise ValueError(
                "IMPACTEngine is the single-tenant front — a co-resident "
                "session routes per-lane model ids and needs the "
                "multi-tenant router (serve.zoo.ModelZoo)")
        self.session = session
        self.system = session.system
        self.impl = session.spec.backend
        self.mesh = session.mesh
        self.meter_energy = session.meters_energy
        self.mode = mode
        self.capacity = session.capacity
        max_batch = self.capacity
        self.max_wait_s = max_wait_s
        self.target_occupancy = target_occupancy
        self.queue_capacity = queue_capacity
        self.clock = clock
        if mode == "flush":
            # Buckets above max_batch are unreachable (a flush never
            # exceeds max_batch and max_batch itself is always a bucket)
            # — drop them so warmup() doesn't compile dead shapes.
            buckets = DEFAULT_BUCKETS if buckets is None else buckets
            self.buckets = sorted(b for b in set(int(b) for b in buckets)
                                  | {max_batch} if b <= max_batch)
        else:
            self.buckets = [max_batch]
        # The engine is the single-tenant special case of the model zoo:
        # one tenant ("default") owning the whole grid, its SLO class
        # carrying the engine's admission knobs.  Queue, slot table,
        # lane buffer, and all ledgers live on the zoo; the engine
        # exposes them as properties so existing callers (and the
        # flush-mode scheduler below) see one state.
        from .zoo import ModelZoo, SLOClass   # deferred: zoo imports us
        slo = SLOClass(name="default", priority=0,
                       target_occupancy=target_occupancy,
                       max_wait_s=max_wait_s,
                       queue_capacity=queue_capacity)
        self._zoo = ModelZoo(session, [("default", slo)], clock=clock,
                             trace=trace)

    # -- zoo-backed state (the engine IS a one-tenant zoo) -------------------
    @property
    def queue(self) -> BatchingQueue:
        return self._zoo.tenants[0].queue

    @property
    def table(self) -> SlotTable:
        return self._zoo.table

    @property
    def _lane_lits(self) -> np.ndarray:
        return self._zoo._lane_lits

    @property
    def batch_stats(self) -> list[BatchStats]:
        return self._zoo.batch_stats

    @property
    def reports(self) -> list[EnergyReport]:
        return self._zoo.reports

    @property
    def request_records(self) -> list[RequestRecord]:
        return self._zoo.request_records

    @property
    def _next_rid(self) -> int:
        return self._zoo._next_rid

    @property
    def _warm(self) -> set[int]:
        return self._zoo._warm

    @property
    def trace(self) -> Tracer | None:
        return self._zoo.trace

    @trace.setter
    def trace(self, tracer: Tracer | None) -> None:
        # One time source: span timestamps must be comparable with the
        # RequestRecord ledger, so the tracer rides the engine's clock
        # (attach_trace re-clocks it).
        self._zoo.attach_trace(tracer)

    def warmup(self) -> None:
        """Ensure every sweep shape this engine can fire is a compiled
        executable (the single slot-table shape in continuous mode —
        already compiled at session build; every bucket in flush mode) so
        no serving step pays compile latency.  AOT-compiles only; unlike
        the pre-session warmup no dummy traffic is executed or metered."""
        shapes = [self.capacity] if self.mode == "continuous" else self.buckets
        for b in shapes:
            self.session.warm(b)
            self._warm.add(b)

    # -- request plumbing ---------------------------------------------------
    def submit(self, literals: np.ndarray) -> int:
        """Enqueue one (K,) literal vector; returns the request id.  Raises
        ``ValueError`` on a mis-shaped request (the persistent slot-table
        buffer is compiled at (capacity, K) — admitting a wrong shape
        would corrupt it; a rejected submit leaves queue and table
        untouched) and ``Backpressure`` when every slot is occupied and
        the admission queue is at ``queue_capacity``."""
        return self._zoo.submit("default", literals)

    def try_submit(self, literals: np.ndarray) -> int | None:
        """``submit`` that signals backpressure as ``None`` instead of
        raising — the polling-loop idiom for load generators."""
        try:
            return self.submit(literals)
        except Backpressure:
            return None

    def bucket_for(self, n: int) -> int:
        """Smallest configured bucket >= n (largest bucket caps max_batch)."""
        i = bisect.bisect_left(self.buckets, n)
        return self.buckets[min(i, len(self.buckets) - 1)]

    @staticmethod
    def pad_to_bucket(batch: list[Request], bucket: int, n_literals: int,
                      ) -> tuple[Array, np.ndarray]:
        """Stack requests into (bucket, K) literals + validity mask.

        Padding lanes are all-1 literals: every crossbar row floats ('Z'),
        so they draw no current in the analog model.
        """
        lits = np.ones((bucket, n_literals), np.int8)
        valid = np.zeros((bucket,), bool)
        for i, r in enumerate(batch):
            lits[i] = r.tokens
            valid[i] = True
        return jnp.asarray(lits), valid

    # -- execution ----------------------------------------------------------
    def _execute(self, lits: Array, valid: np.ndarray, shape: int,
                 lanes: list[tuple[int, _Lane]]) -> list[tuple[int, int]]:
        """Fire one crossbar sweep and do all per-step accounting (on the
        zoo's shared ledger path, under the engine's one tenant)."""
        from .zoo import _ZooLane
        tenant = self._zoo.tenants[0]
        zlanes = [(i, _ZooLane(l.req, l.admitted, tenant))
                  for i, l in lanes]
        return self._zoo.execute_batch(lits, valid, shape, zlanes)

    def _step_flush(self, force: bool) -> list[tuple[int, int]]:
        if not (self.queue.ready() or (force and self.queue.pending)):
            return []
        t_take = self.clock()
        batch = self.queue.take()
        bucket = self.bucket_for(len(batch))
        lits, valid = self.pad_to_bucket(batch, bucket,
                                         self.system.n_literals)
        now = self.clock()
        lanes = [(i, _Lane(r, now)) for i, r in enumerate(batch)]
        if self.trace is not None:
            self.trace.span("admission", t_take, now, args=dict(
                lanes=list(range(len(batch))), bucket=bucket,
                occupancy=len(batch) / bucket))
        return self._execute(lits, valid, bucket, lanes)

    def step(self, *, force: bool = False) -> list[tuple[int, int]]:
        """One scheduler iteration; returns completed (rid, pred) pairs.
        ``force`` fires below the admission-policy thresholds (used to
        drain the tail of a run)."""
        if self.mode == "flush":
            return self._step_flush(force)
        return self._zoo.step(force=force)

    def run(self, literals: np.ndarray) -> tuple[np.ndarray, dict]:
        """Serve a (B, K) request burst to completion; returns predictions
        in submission order + statistics for THIS burst only (``stats()``
        with no arguments reports engine-lifetime aggregates)."""
        b0, r0, q0 = (len(self.batch_stats), len(self.reports),
                      len(self.request_records))
        rows = np.asarray(literals)
        rids: list[int] = []
        done: dict[int, int] = {}
        i = 0
        while len(done) < rows.shape[0]:
            while i < rows.shape[0]:        # submit until backpressure
                rid = self.try_submit(rows[i])
                if rid is None:
                    break
                rids.append(rid)
                i += 1
            done.update(self.step(force=not self.queue.ready()))
        preds = np.asarray([done[r] for r in rids])
        return preds, self.stats(since_batch=b0, since_report=r0,
                                 since_request=q0)

    def stats(self, *, since_batch: int = 0, since_report: int = 0,
              since_request: int = 0) -> dict:
        bs = self.batch_stats[since_batch:]
        total = sum(s.n_valid for s in bs)
        wall = sum(s.latency_s for s in bs)
        # Throughput from WARM batches only — a shape's first sweep pays
        # jit compile and would skew the serving-rate headline; fall back
        # to all batches when everything was cold (e.g. a single burst).
        warm = [s for s in bs if not s.cold] or bs
        w_total = sum(s.n_valid for s in warm)
        w_wall = sum(s.latency_s for s in warm)
        out = dict(
            mode=self.mode,
            batches=len(bs), samples=total, wall_s=wall,
            cold_batches=sum(s.cold for s in bs),
            samples_per_s=w_total / max(w_wall, 1e-9),
            mean_batch_latency_s=w_wall / max(len(warm), 1),
            mean_occupancy=(sum(s.occupancy for s in bs) / len(bs)
                            if bs else 0.0),
            buckets_used=sorted({s.bucket for s in bs}),
        )
        recs = self.request_records[since_request:]
        if recs:
            out["latency"] = latency_percentiles(
                [r.latency_s for r in recs])
            out["queue_wait"] = latency_percentiles(
                [r.queue_s for r in recs])
        reports = self.reports[since_report:]
        if reports:
            agg = aggregate_reports(reports)
            out["energy"] = agg
            out["energy_per_datapoint_j"] = agg.energy_per_datapoint_j
        return out


# -- arrival-trace replay (mixed-traffic benchmarking) ----------------------

def poisson_arrivals(n: int, rate_rps: float, seed: int = 0) -> np.ndarray:
    """Cumulative arrival offsets (seconds) of a seeded Poisson process.

    ``rate_rps`` must be positive (it is the mean arrival rate; zero or
    negative rates have no inter-arrival distribution) and ``n`` must be
    non-negative — both raise ``ValueError`` instead of returning NaN/
    empty-on-negative surprises from numpy."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))

def replay_trace(engine: IMPACTEngine, literals: np.ndarray,
                 arrivals: np.ndarray, *,
                 trace_path: str | None = None) -> dict:
    """Replay an arrival trace through an engine in wall-clock time:
    request ``i`` is submitted once ``arrivals[i]`` seconds have elapsed,
    the scheduler steps continuously, and per-request end-to-end latency
    comes from the engine's ``RequestRecord`` ledger.  Works for both
    scheduler modes, so continuous vs. flush-to-completion is an equal-
    traffic A/B.  The engine must be on a wall clock (replay paces itself
    with real ``time.sleep``); a frozen injected clock raises instead of
    hanging.  Returns tail-latency percentiles + throughput.

    ``trace_path`` writes the run's Chrome-tracing timeline (loadable in
    ``chrome://tracing`` / Perfetto) on exit: the engine's attached
    ``Tracer`` if it has one, else a fresh tracer attached for this
    replay.  Shed requests appear as ``shed`` instant events on the
    scheduler track."""
    n = len(arrivals)
    if literals.shape[0] < n:
        raise ValueError(
            f"replay_trace needs one literal row per arrival: got "
            f"{literals.shape[0]} rows for {n} arrivals")
    tracer = engine.trace
    if trace_path is not None and tracer is None:
        tracer = Tracer(clock=engine.clock)
        engine.trace = tracer
    q0 = len(engine.request_records)
    shed = 0
    i = 0
    ndone = 0
    t0 = engine.clock()
    while ndone < n - shed:
        now = engine.clock() - t0
        while i < n and arrivals[i] <= now:
            if engine.try_submit(literals[i]) is None:
                shed += 1              # load shed at the backpressure edge
                if tracer is not None:
                    tracer.instant("shed", args=dict(offered_index=i))
            i += 1
        out = engine.step(force=i >= n)
        ndone += len(out)
        if not out:
            # Don't busy-spin while the scheduler defers (staleness /
            # occupancy windows): a sub-ms tick keeps the replay loop's
            # CPU off the latencies being measured.  When fully idle,
            # sleep toward the next arrival instead.
            idle = (not engine.queue.pending
                    and engine.table.occupancy == 0)
            gap = (arrivals[i] - (engine.clock() - t0)
                   if (idle and i < n) else 0.0)
            before = engine.clock()
            time.sleep(min(max(gap, 2e-4), 1e-3))
            if engine.clock() == before:
                raise RuntimeError(
                    "replay_trace requires a wall clock: the engine's "
                    "injected clock did not advance across a sleep — "
                    "construct the engine with clock=time.monotonic (or "
                    "another real clock) to replay traces")
    wall = engine.clock() - t0
    recs = engine.request_records[q0:]
    out = dict(mode=engine.mode, offered=n, shed=shed,
               completed=len(recs), wall_s=wall,
               samples_per_s=len(recs) / max(wall, 1e-9))
    out.update(latency_percentiles([r.latency_s for r in recs]))
    if trace_path is not None:
        tracer.write(trace_path)
        out["trace_path"] = str(trace_path)
    return out
