"""Closed-loop bulk scoring: one client sends batches of ``batch`` rows
back to back, each as soon as the previous one returned, through
``InferenceSession.infer_with_report`` with fused metering: predictions
and the batch's ``EnergyReport`` for every call.  The host pool of
literal rows is made from the seed and cycled.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.impact import RuntimeSpec

import planted


@dataclasses.dataclass
class Outcome:
    """Per-call records of one window."""
    first_row: np.ndarray      # pool offset of each call's batch
    clause_j: np.ndarray       # report.clause_energy_j
    class_j: np.ndarray        # report.class_energy_j
    datapoints: np.ndarray     # report.datapoints
    ops: np.ndarray            # report.ops_crosspoint
    t_start: np.ndarray        # call issued (clock s)
    t_end: np.ndarray          # call returned
    preds: dict                # call index -> predictions, for the sample
    batch: int
    start: float
    end: float

    @property
    def rows(self) -> int:
        return len(self.first_row) * self.batch


def drive(session, pool: np.ndarray, batch: int, *, seconds: float,
          sample: np.ndarray, clock=time.monotonic, hooks=()) -> Outcome:
    """Call ``infer_with_report`` on consecutive ``batch``-row slices of
    ``pool`` until ``seconds`` have passed.  ``sample[k]`` marks the calls
    whose predictions are kept for the comparison; ``hooks`` as in
    ``open_loop.drive``."""
    n_batches = len(pool) // batch
    slices = [pool[b * batch:(b + 1) * batch] for b in range(n_batches)]
    rec = dict(first_row=[], clause_j=[], class_j=[], datapoints=[], ops=[],
               t_start=[], t_end=[])
    preds = {}
    hooks = sorted(hooks, key=lambda h: h[0])
    start = clock()
    k = 0
    while True:
        t = clock()
        while hooks and t >= start + hooks[0][0]:
            hooks.pop(0)[1]()
            t = clock()
        if t >= start + seconds and not hooks:
            break
        b = k % n_batches
        res = session.infer_with_report(slices[b])
        t_end = clock()
        r = res.report
        rec["first_row"].append(b * batch)
        rec["clause_j"].append(r.clause_energy_j)
        rec["class_j"].append(r.class_energy_j)
        rec["datapoints"].append(r.datapoints)
        rec["ops"].append(r.ops_crosspoint)
        rec["t_start"].append(t)
        rec["t_end"].append(t_end)
        if k < len(sample) and sample[k]:
            preds[k] = res.predictions
        k += 1
    end = clock()
    preds = {k: np.asarray(p) for k, p in preds.items()}
    return Outcome(**{key: np.asarray(v) for key, v in rec.items()},
                   preds=preds, batch=batch, start=start, end=end)


def end_to_end(out: Outcome, seconds: float) -> dict:
    """rows_per_s: every row billed, over the whole window."""
    return dict(rows_per_s=out.rows / (out.end - out.start))


def compare(out: Outcome, ref: dict, cfg: dict) -> dict:
    """The numbers a run is judged by: sampled predictions that are not a
    best class of the reference, reports whose datapoint or operation
    counts are wrong, and the median over calls of the relative gap of
    the report's class-crossbar energy from the reference's batch sum;
    the read energy's gaps go to the notes."""
    import reference
    b = out.batch
    per_row_ops = (cfg["n_literals"] * cfg["n_clauses"]
                   + cfg["n_clauses"] * cfg["n_classes"])
    wrong = 0
    for k, pred in out.preds.items():
        first = out.first_row[k]
        wrong += int(reference.wrong_predictions(
            pred, ref["scores"][first:first + b]).sum())
    idx = out.first_row // b
    per_batch = {key: ref[key].reshape(-1, b).sum(axis=1)[idx]
                 for key in ("e_clause", "e_class")}
    bad_report = ((out.datapoints != b) | (out.ops != b * per_row_ops))
    return dict(pred_wrong=wrong, report_wrong=int(bad_report.sum()),
                **reference.energy_numbers(out.class_j, per_batch["e_class"]),
                **reference.bill_numbers(
                    out.clause_j + out.class_j,
                    per_batch["e_clause"] + per_batch["e_class"]))


def control_outcome(ctrl: dict, batch: int, cfg: dict) -> Outcome:
    """One call per pool batch, answered by the control: batch energies
    summed in float32 as the program's meters are."""
    n = len(ctrl["pred"]) // batch
    per_row_ops = (cfg["n_literals"] * cfg["n_clauses"]
                   + cfg["n_clauses"] * cfg["n_classes"])
    cl = ctrl["e_clause"][:n * batch].reshape(n, batch).sum(
        axis=1, dtype=np.float32)
    cs = ctrl["e_class"][:n * batch].reshape(n, batch).sum(
        axis=1, dtype=np.float32)
    return Outcome(first_row=np.arange(n) * batch, clause_j=cl.astype(float),
                   class_j=cs.astype(float), datapoints=np.full(n, batch),
                   ops=np.full(n, batch * per_row_ops), t_start=np.zeros(n),
                   t_end=np.zeros(n),
                   preds={k: ctrl["pred"][k * batch:(k + 1) * batch]
                          for k in range(n)},
                   batch=batch, start=0.0, end=1.0)


# -- the harness's interface (bench/run.py) ----------------------------------

#: Calls whose predictions are kept for the comparison: a share drawn
#: from the seed (every call's report is compared).
SAMPLE_SHARE = 1 / 16
SAMPLE_CALLS = 1 << 20


@dataclasses.dataclass
class State:
    session: object
    traffic: dict
    sample: np.ndarray


def session_spec(traffic: dict, interpret: bool) -> RuntimeSpec:
    return RuntimeSpec(backend="pallas", metering="fused",
                       interpret=interpret)


def setup(session, pool: np.ndarray, traffic: dict) -> State:
    """The session, with the batch shape compiled and run twice."""
    b = traffic["batch"]
    session.warm(b, "infer_with_report")
    for k in range(2):
        session.infer_with_report(pool[k * b:(k + 1) * b])
    return State(session=session, traffic=traffic, sample=None)


def window(state: State, pool: np.ndarray, seconds: float, seed: int,
           hooks) -> Outcome:
    rng = np.random.default_rng(planted.derive(seed, planted.SAMPLE))
    state.sample = rng.random(SAMPLE_CALLS) < SAMPLE_SHARE
    return drive(state.session, pool, state.traffic["batch"],
                 seconds=seconds, sample=state.sample, hooks=hooks)


def trace_start(state: State) -> None:
    pass


def trace_stop(state: State) -> None:
    pass


def traced(state: State, out: Outcome, t0: float, t1: float) -> dict:
    """What the per-layer readers see of the traced span [t0, t1]: the
    calls inside it, and each call as a host span for labelling idle
    gaps."""
    inside = (out.t_start >= t0) & (out.t_end <= t1)
    host = [("infer_with_report", a, b)
            for a, b in zip(out.t_start[inside], out.t_end[inside])]
    return dict(spans=None, host_spans=host, requests=None,
                calls=dict(n=int(inside.sum()), batch=out.batch,
                           seconds=t1 - t0))


def counts(out: Outcome) -> tuple[int, int]:
    """(attempted, failed) in rows: a call either returns or raises."""
    return out.rows, 0


def notes(state: State, out: Outcome) -> dict:
    return dict(calls=len(out.first_row),
                call_ms_p50=float(np.median(out.t_end - out.t_start) * 1e3)
                if len(out.t_end) else None,
                sampled_calls=len(out.preds))

