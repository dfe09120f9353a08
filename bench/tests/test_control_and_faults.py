"""The comparison that decides ``correct`` fails what it must.

* The control, the reference at bfloat16 in the program's place, reads
  above each cell's ``bill_err_max`` limit and gets predictions wrong.
* The harness, driven end to end at a small size with the chip check
  skipped (Pallas in interpret mode), reports ``correct`` true on the
  sound program and false when the timed path is broken underneath:
  half of each batch left out, or one answer altered where it is
  produced.
"""
from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

import readings
import run
from conftest import BENCH, small_cell

SEEDS = (2 ** 33 + 1, 2 ** 33 + 2, 2 ** 33 + 3)
CELLS = ("mnist-serve", "mnist-bulk512", "cifar2-bulk512", "cifar2-serve")


def _limit(cell: str) -> float:
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text()
                      )["bill_err_max"]


@pytest.mark.parametrize("kind", ["open_loop", "closed_batch"])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_every_cells_limit(kind, seed):
    cell = small_cell(kind)
    got = readings.control_numbers(run, cell, seed)
    assert got["bill_err_max"] > max(_limit(c) for c in CELLS)
    assert got["bill_err_max"] > 10 * max(_limit(c) for c in CELLS)


def _half_lanes(valid):
    v = np.array(valid, bool)
    live = np.flatnonzero(v)
    v[live[: (len(live) + 1) // 2]] = False
    return v


def _alter(res, n_classes):
    p = jnp.asarray(res.predictions)
    return dataclasses.replace(
        res, predictions=p.at[0].set((p[0] + 1) % n_classes))


FAULTS = {
    "half_batch": dict(
        infer_step=lambda orig, self, lits, valid, **kw: orig(
            self, lits, _half_lanes(valid), **kw),
        infer_with_report=lambda orig, self, lits, valid=None, **kw: orig(
            self, lits, _half_lanes(np.ones(len(lits), bool)), **kw)),
    "altered_answer": dict(
        infer_step=lambda orig, self, *a, **kw: _alter(
            orig(self, *a, **kw), self.system.n_classes),
        infer_with_report=lambda orig, self, *a, **kw: _alter(
            orig(self, *a, **kw), self.system.n_classes)),
}


def _run(cell, out_dir, seed=SEEDS[0]):
    return run.run_cell(cell, seed, 0.6, False, interpret=True,
                        out_dir=out_dir)


@pytest.mark.parametrize("kind", ["open_loop", "closed_batch"])
def test_sound_program_is_correct(kind, out_dir):
    res = _run(small_cell(kind), out_dir)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("kind", ["open_loop", "closed_batch"])
def test_broken_timed_path_is_not_correct(kind, fault, out_dir,
                                          monkeypatch):
    from repro.impact.runtime import InferenceSession
    entry = "infer_step" if kind == "open_loop" else "infer_with_report"
    orig = getattr(InferenceSession, entry)
    wrap = FAULTS[fault][entry]
    monkeypatch.setattr(InferenceSession, entry,
                        lambda self, *a, **kw: wrap(orig, self, *a, **kw))
    cell = small_cell(kind)
    if kind == "open_loop":
        # Keep several requests in a sweep, so halving a batch drops some.
        cell["traffic"]["rate_rps"] = 2000.0
    res = _run(cell, out_dir)
    assert not res["correct"], res["checks"]
