"""The serving cycle's span readers (``bench/metrics/*_ms.serve.py``) on
a synthetic span dict of the shape ``open_loop.traced`` builds: name ->
[(begin_s, end_s, args)]."""
from __future__ import annotations

import types

import pytest

import run


def _cycle(t0: float) -> dict:
    """One fired step at ``t0``: admission, upload, sweep (dispatch,
    ready, fetch), billing, release, 4 ms in all."""
    def at(a, b):
        return (t0 + a * 1e-4, t0 + b * 1e-4, {})

    return dict(step=[at(0, 40)], admission=[at(0, 1)], upload=[at(2, 3)],
                sweep=[at(3, 30)], dispatch=[at(3, 5)], ready=[at(5, 20)],
                fetch=[at(20, 30)], billing=[at(30, 38)],
                release=[at(38, 39)])


def _spans() -> dict:
    """Two fired steps 1 ms apart, then an idle step 0.5 ms later, and a
    sweep outside any step (as the flush scheduler records one)."""
    spans: dict = {}
    for t0 in (0.0, 0.005):
        for name, items in _cycle(t0).items():
            spans.setdefault(name, []).extend(items)
    spans["step"].append((0.0095, 0.0096, {}))
    spans["sweep"].append((0.02, 0.021, {}))
    return spans


@pytest.mark.parametrize("name, want_ms", [
    # gaps 1.0 and 0.5 ms
    ("intake_ms.serve", 0.75),
    # steps 4 + 4 + 0.1 ms, less 2 x (upload 0.1 + sweep 2.7 + billing 0.8)
    ("schedule_ms.serve", (8.1 - 7.2) / 3),
    ("upload_ms.serve", 0.1),
    ("dispatch_ms.serve", 0.2),
    ("ready_ms.serve", 1.5),
    ("fetch_ms.serve", 1.0),
])
def test_reader_value(name, want_ms):
    read = run._module(run.BENCH / "metrics" / f"{name}.py").read
    ctx = types.SimpleNamespace(spans=_spans())
    assert read(ctx) == pytest.approx(want_ms, rel=1e-9)


@pytest.mark.parametrize("name", [
    "intake_ms.serve", "schedule_ms.serve", "upload_ms.serve",
    "dispatch_ms.serve", "ready_ms.serve", "fetch_ms.serve"])
def test_reader_is_silent_without_its_spans(name):
    """A program that records only the older spans (or no tracer at
    all) reads as no value, not an error."""
    read = run._module(run.BENCH / "metrics" / f"{name}.py").read
    older = {k: v for k, v in _spans().items()
             if k in ("admission", "sweep", "billing", "release")}
    assert read(types.SimpleNamespace(spans=older)) is None
    assert read(types.SimpleNamespace(spans=None)) is None
