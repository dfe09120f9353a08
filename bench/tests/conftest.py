"""Shared set-up of the benchmark's own tests: import paths, a JAX on
the CPU, and a small cell that a test run can hold (the harness runs the
Pallas kernels in interpret mode there)."""
from __future__ import annotations

import copy
import json
import os
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (ROOT / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def small_config(name: str = "cifar2-cotm") -> dict:
    """The configuration ``name`` at a size the CPU holds: 48 clauses on
    32-column tiles (two column tiles); the prototype generator at 128
    literals and 3 classes, the digit generator at its fixed 1568 and 10."""
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["n_clauses"] = 48
    if cfg["data"]["generator"] == "prototype":
        cfg.update(n_literals=128, n_classes=3)
    cfg["tile"] = dict(max_tile_rows=max(128, cfg["n_literals"]),
                       max_tile_cols=32, max_class_rows=64)
    cfg["planted"]["frequency_rows"] = 300
    return cfg


def small_cell(kind: str, cfg: dict | None = None) -> dict:
    """A cell of the given traffic kind on ``small_config``."""
    cfg = cfg or small_config()
    if kind == "open_loop":
        traffic = dict(kind=kind, rate_rps=400.0, capacity=16,
                       pool_rows=256)
        limits = dict(unanswered=0, pred_wrong=0, bill_err_max=2e-5)
        e2e = ["req_p50_ms", "served_rps", "setup_s"]
    else:
        traffic = dict(kind=kind, batch=32, pool_rows=256)
        limits = dict(pred_wrong=0, report_wrong=0, bill_err_max=2e-5)
        e2e = ["rows_per_s", "setup_s"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return dict(name=f"test-{kind}", chips=1, cfg=cfg, traffic=traffic,
                limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if m["name"] in e2e],
                per_layer=[])


@pytest.fixture
def out_dir(tmp_path):
    return tmp_path / "out"
