"""The trace reduction on a small trace recorded on a TPU v5e: 20
serving sweeps (``infer_step`` at 128 lanes, MNIST widths) inside one
``bench_window`` annotation."""
from __future__ import annotations

import pathlib

import jax
import pytest

import trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "serve_sweeps.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    return jax.profiler.ProfileData.from_file(str(TRACE))


def test_busy_kernel_and_idle(profile):
    s = trace_reduce.reduce(profile, "bench_window")
    assert s.devices == 1
    assert s.kernel_calls == 20                     # one kernel per sweep
    assert 0 < s.kernel_s < s.busy_s < s.window_s
    # The module events of the 20 sweeps last ~51 us each; ops cover
    # most of that, and the host loop leaves the device idle otherwise.
    assert 0.9e-3 < s.busy_s < 1.1e-3
    assert 0.95 < s.idle_share < 0.99
    assert s.nonkernel_s == pytest.approx(s.busy_s - s.kernel_s)


def test_ops_named_without_suffix(profile):
    s = trace_reduce.reduce(profile, "bench_window")
    assert "fused_impact_metered" in s.op_s
    assert max(s.op_s, key=s.op_s.get) == "fused_impact_metered"
    assert trace_reduce.op_name("%copy.22 = s8[1] copy(...)") == "copy"
    assert trace_reduce.op_name("%fusion = f32[1] fusion(...)") == "fusion"


def test_gaps_labelled_by_host_spans(profile):
    plain = trace_reduce.reduce(profile, "bench_window")
    # A span on another clock, anchored at the window's start, that
    # covers the whole window labels every gap the profiler's own host
    # events leave unlabelled; inner profiler events keep their names.
    labelled = trace_reduce.reduce(
        profile, "bench_window", [("loop", 10.0, 10.0 + plain.window_s)],
        anchor=10.0)
    assert "host" not in labelled.gap_s
    assert sum(labelled.gap_s.values()) == pytest.approx(
        plain.window_s - plain.busy_s)
    assert "PjitFunction(jit(_infer_step_fn))" in labelled.gap_s


def test_breakdown_shape(profile):
    b = trace_reduce.breakdown(trace_reduce.reduce(profile, "bench_window"))
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and isinstance(v, float)
                   for n, v in b[key])
    assert b["device_ops"][0][0] == "fused_impact_metered"


def test_missing_window_is_an_error(profile):
    with pytest.raises(ValueError, match="no host annotation"):
        trace_reduce.reduce(profile, "no_such_window")


def test_recorder_marks_its_window(tmp_path):
    """The recorder's window annotation is in the trace it writes (on the
    CPU there is no device plane to reduce, but the host mark is there)."""
    import time
    rec = trace_reduce.Recorder(tmp_path / "trace", "bench_window",
                                time.monotonic)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    rec.start()
    for _ in range(3):
        f(x).block_until_ready()
    rec.stop()
    assert rec.t1 > rec.t0
    marks = [ev for plane in rec.profile().planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "bench_window"]
    assert len(marks) == 1
    assert marks[0].duration_ns == pytest.approx((rec.t1 - rec.t0) * 1e9,
                                                 rel=0.5, abs=2e6)
