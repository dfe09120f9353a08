"""``kernel_ms.bulk`` on the reduced recorded trace: 20 sweeps at MNIST
widths on a TPU v5e, one ``fused_impact_metered`` kernel each."""
from __future__ import annotations

import dataclasses
import pathlib
import types

import jax
import pytest

import run
import trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "serve_sweeps.xplane.pb"


def _read(summary):
    read = run._module(run.BENCH / "metrics" / "kernel_ms.bulk.py").read
    return read(types.SimpleNamespace(device=summary))


@pytest.fixture(scope="module")
def summary():
    profile = jax.profiler.ProfileData.from_file(str(TRACE))
    return trace_reduce.reduce(profile, "bench_window")


def test_named_kernel_time_per_call(summary):
    got = _read(summary)
    # The trace's only custom call is the named kernel: its time per
    # call is the kernel time per call, ~40 us of each sweep's ~51 us
    # on the device.
    assert got == pytest.approx(summary.kernel_s / summary.kernel_calls
                                * 1e3)
    assert 0 < got < summary.busy_s / summary.kernel_calls * 1e3


def test_silent_without_the_named_kernel(summary):
    renamed = dataclasses.replace(
        summary, op_s={("other" if k == "fused_impact_metered" else k): v
                       for k, v in summary.op_s.items()})
    assert _read(renamed) is None
    assert _read(dataclasses.replace(summary, kernel_calls=0)) is None
    assert _read(None) is None
