"""The open-loop generator times every request from its due time: a stall
in the server delays the requests due during it, and the delay counts."""
from __future__ import annotations

import types

import numpy as np
import pytest

from traffic import open_loop


class Clock:
    """A virtual clock: time moves only when the engine works or the
    loop sleeps."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class StubEngine:
    """Answers every queued request in one 1 ms sweep; the sweep that
    starts at or after ``stall_at`` takes ``stall`` seconds instead."""

    def __init__(self, clock, stall_at, stall):
        self.clock, self.stall_at, self.stall = clock, stall_at, stall
        self.queue, self.request_records, self.next = [], [], 0
        self.batch_stats, self.reports = [], []
        self.stalled = False

    def try_submit(self, row):
        rid = self.next
        self.next += 1
        self.queue.append((rid, self.clock()))
        return rid

    def step(self):
        if not self.queue:
            return []
        t0 = self.clock()
        if not self.stalled and t0 >= self.stall_at:
            self.stalled = True
            self.clock.t += self.stall
        else:
            self.clock.t += 1e-3
        done = []
        for rid, arrived in self.queue:
            self.request_records.append(types.SimpleNamespace(
                rid=rid, arrived=arrived, admitted=t0,
                completed=self.clock(), pred=0, e_read_j=1.0))
            done.append((rid, 0))
        self.batch_stats.append(types.SimpleNamespace(n_valid=len(done)))
        self.reports.append(types.SimpleNamespace(class_energy_j=1.0))
        self.queue = []
        return done


def test_stall_counts_for_requests_due_during_it():
    clock = Clock()
    offsets = np.arange(0.0, 1.0, 0.01)             # one request / 10 ms
    stall_at, stall = clock.t + 0.3, 0.2
    engine = StubEngine(clock, stall_at, stall)
    out = open_loop.drive(engine, np.zeros((4, 8), np.int8), offsets,
                          seconds=1.0, clock=clock, sleep=clock.sleep)
    assert out.answered.all()
    lat = out.completed - out.due
    stall_end = stall_at + stall
    during = (out.due > stall_at + 1e-3) & (out.due < stall_end)
    assert during.sum() >= 15
    # Each request due inside the stall waits at least until it ends:
    # measured from its due time, not from its (late) submission.
    assert np.all(lat[during] >= stall_end - out.due[during] - 1e-9)
    assert lat[during].max() >= 0.19
    # The loop itself could not submit them before the stall ended, and
    # that lateness is reported apart.
    late = open_loop.lateness(out)
    assert late["max"] >= 0.18
    # Requests well before the stall see one sweep of latency at most.
    before = out.due < stall_at - 0.02
    assert np.all(lat[before] <= 1.1e-3 + 1e-3)
    e2e = open_loop.end_to_end(out, 1.0)
    assert e2e["req_p99_ms"] >= 150.0
    assert e2e["served_rps"] == pytest.approx(100.0)


def test_arrivals_have_the_same_count_for_every_seed():
    n = {len(open_loop.poisson_arrivals(
        2500.0, 4.0, np.random.default_rng(s))) for s in range(5)}
    assert n == {10000}
    a = open_loop.poisson_arrivals(2500.0, 4.0, np.random.default_rng(1))
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 4.0
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 2500.0, rel=0.05)
    assert gaps.std() == pytest.approx(1 / 2500.0, rel=0.1)  # exponential
