"""Operations and bytes come from the configuration's live sizes; peaks
come from the table, and an unknown device is an error."""
from __future__ import annotations

import json

import pytest

import work
from conftest import BENCH


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_live_dims_not_padded_operands():
    cfg = _cfg("mnist-cotm")
    # 1568 literals x 500 clauses x 10 classes, not the kernel's padded
    # 2048 x 2048 clause operand or its 128 class lanes.
    assert work.ops_per_row(cfg) == 2 * 1568 * 500 + 2 * 500 * 10
    assert work.fabric_bytes(cfg) == (1568 * 500 + 500 * 10) * 4
    assert work.call_bytes(cfg, 512) == (1568 * 500 + 500 * 10) * 4 \
        + 512 * 1568
    cifar = _cfg("cifar2-cotm")
    assert work.ops_per_row(cifar) == 2 * 2048 * 1000 + 2 * 1000 * 2
    assert work.ops_per_row(cifar) / work.ops_per_row(cfg) == \
        pytest.approx(2.6, abs=0.02)


def test_bound_takes_the_slower_side():
    cfg = _cfg("mnist-cotm")
    peak = work.peaks("TPU v5 lite")
    t, side = work.bound_s(cfg, [512], peak)
    c = 512 * work.ops_per_row(cfg) / peak["flops_per_s"]
    m = work.call_bytes(cfg, 512) / peak["hbm_bytes_per_s"]
    assert t == pytest.approx(max(c, m))
    assert side == "memory" and m > c
    # One lane: the fabric's bytes bound it all the more.
    t1, side1 = work.bound_s(cfg, [1, 1], peak)
    assert t1 == pytest.approx(2 * work.call_bytes(cfg, 1)
                               / peak["hbm_bytes_per_s"])
    assert side1 == "memory"


def test_unknown_device_kind_is_refused():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no peaks for device kind"):
        work.peaks("cpu")
