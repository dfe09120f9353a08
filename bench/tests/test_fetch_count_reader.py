"""``fetches_per_sweep.serve`` on span dicts of the shape
``open_loop.traced`` builds: name -> [(begin_s, end_s, args)]."""
from __future__ import annotations

import types

import run


def _read(spans):
    read = run._module(run.BENCH / "metrics"
                       / "fetches_per_sweep.serve.py").read
    return read(types.SimpleNamespace(spans=spans))


def test_mean_fetches_per_sweep():
    sweeps = [(0.0, 0.002, dict(fetches=1)), (0.004, 0.006, dict(fetches=3))]
    assert _read(dict(sweep=sweeps)) == 2.0
    assert _read(dict(sweep=sweeps[:1])) == 1.0


def test_silent_without_the_count():
    """Sweep spans without a ``fetches`` arg (a program that records
    none), no sweeps, or no tracer read as no value."""
    assert _read(dict(sweep=[(0.0, 0.002, dict(shape=128))])) is None
    assert _read(dict(step=[(0.0, 0.004, {})])) is None
    assert _read(None) is None
