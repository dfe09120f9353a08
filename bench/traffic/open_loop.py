"""Open-loop serving: independent users send one literal row each, on a
Poisson schedule fixed by the seed, whether or not earlier requests have
finished.

Requests go through ``IMPACTEngine`` (continuous batching over a slot
table of ``capacity`` lanes) on the README's serving spec: the fused
Pallas kernel with in-kernel energy metering.  Every latency runs from
the time a request was *due*, not from when the loop got round to
submitting it, so a stalled sweep delays the requests behind it and that
delay is counted (no coordinated omission).  How late the loop submitted
is reported apart, as generator lateness.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from repro.impact import RuntimeSpec
from repro.serve import IMPACTEngine
from repro.serve.tracing import Tracer

import planted

#: How long past the window's close the loop waits for answers.
DRAIN_S = 60.0


def poisson_arrivals(rate_rps: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Offsets (s) of a Poisson process of rate ``rate_rps`` over
    [0, seconds), conditioned on its expected count: the sorted uniform
    order statistics.  Every seed gets the same number of requests, in
    another arrangement."""
    if rate_rps <= 0 or seconds <= 0:
        raise ValueError(f"rate and window must be > 0, got {rate_rps}, "
                         f"{seconds}")
    n = int(round(rate_rps * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))


@dataclasses.dataclass
class Outcome:
    """Per-request arrays, index i = the i-th request by due time."""
    due: np.ndarray          # absolute due times (clock s)
    submitted: np.ndarray    # when the loop submitted it
    rid: np.ndarray          # engine request id, -1 if refused
    row: np.ndarray          # pool row it carried
    completed: np.ndarray    # completion time, NaN if never answered
    admitted: np.ndarray     # admission into a lane, NaN if never
    pred: np.ndarray         # prediction, -1 if never answered
    bill: np.ndarray         # read-energy bill (J), NaN if never
    start: float             # window opens
    close: float             # window closes
    sweep_class_j: np.ndarray    # each sweep's report.class_energy_j
    sweep_members: list          # each sweep's request indices

    @property
    def answered(self) -> np.ndarray:
        return ~np.isnan(self.completed)

    def latency_s(self) -> np.ndarray:
        return (self.completed - self.due)[self.answered]


def drive(engine, rows: np.ndarray, offsets: np.ndarray, *, seconds: float,
          clock=time.monotonic, sleep=time.sleep, hooks=(),
          waits: list | None = None) -> Outcome:
    """Offer ``rows[i % len(rows)]`` at ``start + offsets[i]`` and step the
    engine until every request is answered or ``DRAIN_S`` past the close.

    ``hooks`` are ``(offset_s, callable)`` pairs run once when the window
    reaches that offset (the traced run starts and stops its trace so);
    ``waits`` collects ``("wait", start, end)`` for each idle sleep.
    """
    n = len(offsets)
    q0 = len(engine.request_records)
    b0, r0 = len(engine.batch_stats), len(engine.reports)
    submitted = np.full(n, np.nan)
    rid = np.full(n, -1, np.int64)
    hooks = sorted(hooks, key=lambda h: h[0])
    start = clock()
    due = start + offsets
    close = start + seconds
    i = accepted = answered = 0
    while True:
        now = clock()
        while hooks and now >= start + hooks[0][0]:
            hooks.pop(0)[1]()
            now = clock()
        while i < n and due[i] <= now:
            r = engine.try_submit(rows[i % len(rows)])
            submitted[i] = now
            if r is not None:
                rid[i] = r
                accepted += 1
            i += 1
        out = engine.step()
        answered += len(out)
        if i >= n and answered >= accepted and not hooks:
            break
        if now > close + DRAIN_S:
            break
        if not out and answered >= accepted:
            nxt = due[i] if i < n else close
            if hooks:
                nxt = min(nxt, start + hooks[0][0])
            t_wait = clock()
            gap = nxt - t_wait
            if gap > 1e-4:
                sleep(min(gap, 1e-3))
                if waits is not None:
                    waits.append(("wait", t_wait, clock()))

    completed = np.full(n, np.nan)
    admitted = np.full(n, np.nan)
    pred = np.full(n, -1, np.int64)
    bill = np.full(n, np.nan)
    index = {int(r): k for k, r in enumerate(rid) if r >= 0}
    members, k_of = [], []
    for rec in engine.request_records[q0:]:
        k = index.get(rec.rid, -1)
        k_of.append(k)
        if k < 0:
            continue
        completed[k] = rec.completed
        admitted[k] = rec.admitted
        pred[k] = rec.pred
        bill[k] = rec.e_read_j
    pos = 0
    for stats in engine.batch_stats[b0:]:
        members.append(np.asarray(k_of[pos:pos + stats.n_valid], np.int64))
        pos += stats.n_valid
    class_j = np.asarray([r.class_energy_j for r in engine.reports[r0:]])
    return Outcome(due=due, submitted=submitted, rid=rid,
                   row=np.arange(n) % len(rows), completed=completed,
                   admitted=admitted, pred=pred, bill=bill, start=start,
                   close=close, sweep_class_j=class_j,
                   sweep_members=members)


def lateness(out: Outcome) -> dict:
    """How far behind its schedule the loop submitted (s)."""
    late = (out.submitted - out.due)[~np.isnan(out.submitted)]
    if late.size == 0:
        return {}
    return dict(p50=float(np.percentile(late, 50)),
                p99=float(np.percentile(late, 99)), max=float(late.max()))


def end_to_end(out: Outcome, seconds: float) -> dict:
    """req_p50_ms / req_p99_ms over every answered request, from its due
    time; served_rps counts the requests answered by the window's close."""
    lat = out.latency_s()
    if lat.size == 0:
        return {}
    served = int(np.sum(out.completed[out.answered] <= out.close))
    return dict(req_p50_ms=float(np.percentile(lat, 50) * 1e3),
                req_p99_ms=float(np.percentile(lat, 99) * 1e3),
                served_rps=served / seconds)


def compare(out: Outcome, ref: dict, cfg: dict) -> dict:
    """The numbers a run is judged by: requests never answered, answers
    that are not a best class of the reference, and the median over
    sweeps of the relative gap of the sweep's class-crossbar energy from
    the reference's; the bills' gaps go to the notes."""
    import reference
    ok = out.answered
    rows = out.row[ok]
    want = ref["e_clause"][rows] + ref["e_class"][rows]
    wrong = reference.wrong_predictions(out.pred[ok], ref["scores"][rows])
    full = [i for i, m in enumerate(out.sweep_members) if np.all(m >= 0)]
    want_class = np.asarray([ref["e_class"][out.row[out.sweep_members[i]]]
                             .sum() for i in full])
    return dict(unanswered=int(np.sum(~ok)), pred_wrong=int(wrong.sum()),
                **reference.energy_numbers(out.sweep_class_j[full],
                                           want_class),
                **reference.bill_numbers(out.bill[ok], want))


def control_outcome(ctrl: dict, n_rows: int, capacity: int) -> Outcome:
    """Every pool row answered once by the control, in full sweeps of
    ``capacity`` rows."""
    zeros = np.zeros(n_rows)
    bill = (ctrl["e_clause"].astype(np.float64)
            + ctrl["e_class"].astype(np.float64))
    members = [np.arange(k, min(k + capacity, n_rows))
               for k in range(0, n_rows, capacity)]
    class_j = np.asarray([ctrl["e_class"][m].astype(np.float64).sum()
                          for m in members])
    return Outcome(due=zeros, submitted=zeros, rid=np.arange(n_rows),
                   row=np.arange(n_rows), completed=zeros, admitted=zeros,
                   pred=ctrl["pred"].astype(np.int64), bill=bill, start=0.0,
                   close=0.0, sweep_class_j=class_j, sweep_members=members)


# -- the harness's interface (bench/run.py) ----------------------------------

class SchedulerTracer(Tracer):
    """The engine's tracer with the scheduler track only: per-request
    lifecycle spans are dropped, so tracing costs the scheduler a few
    spans per sweep rather than four per request."""

    def request_spans(self, **_):
        pass


class GCPauses:
    """Records the interpreter's garbage-collection pauses (start, end,
    generation) while installed; the window's latency tail is read
    beside them."""

    def __init__(self):
        self.pauses, self._t0 = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.monotonic(),
                                info["generation"]))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


@dataclasses.dataclass
class State:
    engine: IMPACTEngine
    traffic: dict
    waits: list
    gc: GCPauses
    tracer: Tracer | None = None


def session_spec(traffic: dict, interpret: bool) -> RuntimeSpec:
    return RuntimeSpec(backend="pallas", metering="fused",
                       capacity=traffic["capacity"], interpret=interpret)


def setup(session, pool: np.ndarray, traffic: dict) -> State:
    """The engine, with its one sweep shape compiled and run: two full
    slot tables of rows are served before the window opens."""
    engine = IMPACTEngine(session, clock=time.monotonic)
    engine.warmup()
    engine.run(pool[:2 * traffic["capacity"]])
    return State(engine=engine, traffic=traffic, waits=[], gc=GCPauses())


def window(state: State, pool: np.ndarray, seconds: float, seed: int,
           hooks) -> Outcome:
    rng = np.random.default_rng(planted.derive(seed, planted.ARRIVALS))
    offsets = poisson_arrivals(state.traffic["rate_rps"], seconds, rng)
    with state.gc:
        return drive(state.engine, pool, offsets, seconds=seconds,
                     hooks=hooks, waits=state.waits)


def trace_start(state: State) -> None:
    state.tracer = SchedulerTracer(clock=time.monotonic)
    state.engine.trace = state.tracer


def trace_stop(state: State) -> None:
    state.engine.trace = None


def traced(state: State, out: Outcome, t0: float, t1: float) -> dict:
    """What the per-layer readers see of the traced span [t0, t1]: the
    scheduler's spans, host spans for labelling idle gaps, and the
    requests admitted in it."""
    spans, opened = {}, {}
    for ev in (state.tracer.events if state.tracer else []):
        if ev.get("pid") != 0 or ev["ph"] not in "BE":
            continue
        if ev["ph"] == "B":
            opened[ev["name"]] = ev
        elif ev["name"] in opened:
            b = opened.pop(ev["name"])
            spans.setdefault(ev["name"], []).append(
                (b["ts"], ev["ts"], b.get("args", {})))
    inside = (out.admitted >= t0) & (out.admitted <= t1)
    host = [(name, a, b) for name, items in spans.items()
            for a, b, _ in items]
    host += [w for w in state.waits if w[2] > t0 and w[1] < t1]
    host += [(f"gc gen{g}", a, b) for a, b, g in state.gc.pauses
             if b > t0 and a < t1]
    return dict(spans=spans, host_spans=host, calls=None,
                requests=dict(due=out.due[inside],
                              admitted=out.admitted[inside]))


def counts(out: Outcome) -> tuple[int, int]:
    """(attempted, failed): refused and never-answered requests fail."""
    return len(out.due), int(np.sum(~out.answered))


def notes(state: State, out: Outcome) -> dict:
    stats = state.engine.stats()
    pauses = [(b - a, g) for a, b, g in state.gc.pauses
              if out.start <= a <= out.close]
    return dict(generator_lateness_s=lateness(out),
                sweeps=stats["batches"],
                mean_occupancy=stats["mean_occupancy"],
                gc_pauses=len(pauses),
                gc_gen2_pauses=sum(g == 2 for _, g in pauses),
                gc_longest_s=max((d for d, _ in pauses), default=0.0))

