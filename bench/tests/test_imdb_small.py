"""The ``imdb-cotm`` configuration at a CPU size, through the normal
path (``build_system`` -> ``RuntimeSpec(backend="pallas",
metering="fused")`` -> ``InferenceSession.infer_with_report``), against
the float64 reference on seeded planted weights.  Small tiles keep its
shape: R=5 literal row-shards, C=5 clause column tiles, S=5 class
shards, so the fused kernel ANDs five shards' CSA bits per column."""
from __future__ import annotations

import copy
import json

import numpy as np
import pytest

import reference
import run
from conftest import BENCH, small_cell

SEEDS = (2 ** 33 + 21, 2 ** 33 + 22)


def small_imdb() -> dict:
    cfg = json.loads((BENCH / "configs" / "imdb-cotm.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(n_literals=640, n_clauses=300)
    cfg["tile"] = dict(max_tile_rows=128, max_tile_cols=64,
                       max_class_rows=64)
    cfg["planted"]["frequency_rows"] = 300
    return cfg


@pytest.mark.parametrize("seed", SEEDS)
def test_session_matches_float64_reference(seed):
    cell = small_cell("closed_batch", small_imdb())
    built = run.build(cell, seed, interpret=True)
    R, C, _, _ = built.system.clause_i.shape
    assert (R, C, built.system.class_i.shape[0]) == (5, 5, 5)
    plan = built.session.kernel_plan("infer_with_report", 32)
    assert (plan.row_shards, plan.literal_chunks) == (5, 5)
    rows = built.pool[:32]
    res = built.session.infer_with_report(rows)
    refs = reference.infer(reference.program(np.asarray(built.ta_state),
                                             np.asarray(built.weights),
                                             cell["cfg"]), rows)
    # Predictions: a best class of some valid fabric (ties within
    # reference.TIE_REL); the batch's read energy within the cells'
    # bill limit (2e-5), which float32 summation meets by ~10x.
    wrong = [int(reference.wrong_predictions(np.asarray(res.predictions),
                                             ref["scores"]).sum())
             for ref in refs]
    assert min(wrong) == 0
    got = res.report.clause_energy_j + res.report.class_energy_j
    want = [ref["e_clause"].sum() + ref["e_class"].sum() for ref in refs]
    assert min(reference.rel_err(got, w) for w in want) < 2e-5
    assert refs[0]["fired"].mean() > 0.05      # clauses fire


def test_harness_run_is_correct(out_dir):
    res = run.run_cell(small_cell("closed_batch", small_imdb()), SEEDS[0],
                       0.6, False, interpret=True, out_dir=out_dir)
    assert res["correct"], res["checks"]
    assert res["checks"]["pred_wrong"]["value"] == 0
    assert res["checks"]["report_wrong"]["value"] == 0
