"""The planted CoTM fires clauses on its own traffic, and the float64
reference agrees with the program's own oracle (``kernels/ref.py``) on
the fabric the program programs from the same planted weights."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import planted
import reference
from conftest import BENCH, small_config

SEED = 2 ** 33 + 11          # beyond 32 bits, as the harness's seeds are


def _build(cfg, ta, w):
    from repro.core import CoTMConfig, CoTMParams
    from repro.impact import IMPACTConfig, build_system
    tile = cfg["tile"]
    return build_system(
        CoTMParams(ta_state=ta, weights=w),
        CoTMConfig(n_literals=cfg["n_literals"], n_clauses=cfg["n_clauses"],
                   n_classes=cfg["n_classes"], n_states=cfg["n_states"],
                   threshold=cfg["threshold"]),
        jax.random.key(0),
        IMPACTConfig(max_tile_rows=tile["max_tile_rows"],
                     max_tile_cols=tile["max_tile_cols"],
                     max_class_rows=tile["max_class_rows"],
                     variability=False, finetune=False))


@pytest.mark.parametrize("name", ["mnist-cotm", "cifar2-cotm"])
def test_planted_model_fires_and_varies(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    ta, w = planted.planted(cfg, SEED)
    assert ta.shape == (cfg["n_literals"], cfg["n_clauses"])
    assert w.shape == (cfg["n_classes"], cfg["n_clauses"])
    pool = planted.pool(cfg, 1024, SEED)
    out = reference.infer(reference.program(np.asarray(ta), np.asarray(w),
                                            cfg), pool)[0]
    assert out["fired"].mean() > 0.05           # clauses fire on traffic
    assert out["fired"].min() > 0               # on every row
    pred = out["scores"].argmax(axis=1)
    hist = np.bincount(pred, minlength=cfg["n_classes"])
    assert (hist > 0).sum() >= min(cfg["n_classes"], 5)


def test_same_seed_same_inputs():
    cfg = small_config()
    a = [np.asarray(x) for x in planted.planted(cfg, SEED)]
    b = [np.asarray(x) for x in planted.planted(cfg, SEED)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert np.array_equal(planted.pool(cfg, 64, SEED),
                          planted.pool(cfg, 64, SEED))
    assert not np.array_equal(planted.pool(cfg, 64, SEED),
                              planted.pool(cfg, 64, SEED + 1))


@pytest.mark.parametrize("name", ["mnist-cotm", "cifar2-cotm"])
def test_reference_agrees_with_program_oracle(name):
    from repro.impact.yflash import I_CSA_THRESHOLD
    from repro.kernels import ref as oracle
    cfg = small_config(name)
    ta, w = planted.planted(cfg, SEED)
    system = _build(cfg, ta, w)
    fabs = reference.program(np.asarray(ta), np.asarray(w), cfg)
    # The program's currents agree with one valid fabric: the class cells
    # exactly, the clause cells to the rounding of one exp.
    R, C, tr, tc = system.clause_i.shape
    ci = np.asarray(system.clause_i).transpose(0, 2, 1, 3).reshape(
        R * tr, C * tc)
    wi = np.asarray(system.class_i).reshape(-1, cfg["n_classes"])
    assert np.max(reference.rel_err(ci, fabs[0].clause_i)) < 1e-6
    errs = [np.max(reference.rel_err(wi, f.class_i)) for f in fabs]
    assert min(errs) == 0.0
    fab = fabs[int(np.argmin(errs))]
    pool = planted.pool(cfg, 256, SEED)
    with jax.default_matmul_precision("highest"):
        scores, i_cl, i_cs = oracle.fused_impact_metered_ref(
            jnp.asarray(pool), system.clause_i, system.nonempty,
            system.class_i, thresh=I_CSA_THRESHOLD)
    want = reference.infer([fab], pool)[0]
    assert not reference.wrong_predictions(
        np.asarray(scores).argmax(axis=1), want["scores"]).any()
    vt = fab.v_read * fab.t_read
    assert np.max(reference.rel_err(vt * np.asarray(i_cl, np.float64),
                                    want["e_clause"])) < 1e-5
    assert np.max(reference.rel_err(vt * np.asarray(i_cs, np.float64),
                                    want["e_class"])) < 1e-5


def test_wrong_predictions_accepts_any_tied_best():
    scores = np.array([[1.0, 3.0, 3.0], [2.0, 1.0, 0.0]])
    assert not reference.wrong_predictions(np.array([1, 0]), scores).any()
    assert not reference.wrong_predictions(np.array([2, 0]), scores).any()
    assert reference.wrong_predictions(np.array([0, 1]), scores).all()
    assert reference.wrong_predictions(np.array([-1, 5]), scores).all()


def test_tie_level_gives_both_fabrics():
    """Weights spanning w_max = 120 levels: level 100's band edge is the
    erased conductance, so it may be pulsed or not, and the reference
    offers both; a level without a tie differs between fabrics only by
    the float32 rounding of the pulse factors."""
    cfg = small_config()
    K, n, m = cfg["n_literals"], cfg["n_clauses"], cfg["n_classes"]
    ta = np.full((K, n), cfg["n_states"], np.int64)
    ta[0] += 1
    w = np.zeros((m, n), np.int64)
    w[0, :] = np.arange(n) % 97
    w[0, 0], w[1, 0] = 96, -24
    for weight, tie in ((76, True), (75, False)):     # levels 100, 99
        w[0, 1] = weight
        fabs = reference.program(ta, w, cfg)
        assert all(f.clause_i is fabs[0].clause_i for f in fabs)
        cell = np.array([f.class_i[1, 0] for f in fabs])
        if tie:
            # Inside its band the cell keeps the erased 2.5 uS (5 uA).
            assert np.any(np.abs(cell - 5e-6) < 1e-11)
            assert np.any(cell < 4e-6)
        else:
            assert np.max(cell) / np.min(cell) - 1 < 1e-5
