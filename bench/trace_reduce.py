"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Device planes are the ``/device:...`` planes; their ``XLA Ops`` line holds
one event per operation that ran on the core.  Within the traced window
(a host annotation on the profiler's own clock):

* busy time is the union of those op intervals, idle is the rest;
* kernel time is the time of Pallas kernels, recognised as TPU custom
  calls (``custom_call_target="tpu_custom_call"`` in the op's HLO), not
  by kernel name, so a renamed kernel is still found;
* every other op is non-kernel device time (layout copies, pads, the
  small reductions around the kernel);
* each idle gap is labelled with what the host was doing at its middle:
  the innermost host interval covering it, from the profiler's host
  events and any spans the caller adds on the same clock.

Busy and kernel times are averaged over the device planes.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib

import numpy as np

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceSummary:
    window_s: float
    busy_s: float                 # mean over devices
    kernel_s: float               # mean over devices
    kernel_calls: float           # mean over devices
    devices: int
    op_s: dict                    # op name -> seconds (summed over devices)
    gap_s: dict                   # host activity -> idle seconds (mean)
    longest_gaps: list            # [(activity, seconds)], longest first

    @property
    def nonkernel_s(self) -> float:
        return self.busy_s - self.kernel_s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


class Recorder:
    """Starts and stops a profiler trace of part of a run, and marks that
    part with a host annotation named ``window`` on the profiler's clock.
    ``t0`` / ``t1`` are the marks on ``clock``, for aligning the run's
    own spans with the trace.  The annotation is made once the profiler
    runs: one made before would record nothing."""

    def __init__(self, log_dir, window: str, clock):
        import jax
        self._jax, self.log_dir, self.window, self.clock = (
            jax, pathlib.Path(log_dir), window, clock)
        self.t0 = self.t1 = None
        self._mark = None

    def start(self) -> None:
        jax = self._jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # Python calls cost too much to log
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(self.window)
        self._mark.__enter__()
        self.t0 = self.clock()

    def stop(self) -> None:
        self.t1 = self.clock()
        self._mark.__exit__(None, None, None)
        self._jax.profiler.stop_trace()

    def profile(self):
        return self._jax.profiler.ProfileData.from_file(
            str(find_xplane(self.log_dir)))


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """``%fused_impact_metered.1 = (...) custom-call(...)`` ->
    ``fused_impact_metered``: the HLO instruction name without its
    ``%`` and numeric suffix."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [iv[0].copy()]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append(np.array([a, b]))
    return np.asarray(out)


def _label(points: np.ndarray, spans: list) -> list:
    """Name of the innermost span covering each point ("host" if none):
    paint spans longest first, so shorter, inner ones win."""
    labels = np.full(len(points), "host", dtype=object)
    order = np.argsort(points)
    sorted_pts = points[order]
    for name, a, b in sorted(spans, key=lambda s: s[1] - s[2]):
        lo, hi = np.searchsorted(sorted_pts, [a, b], side="left")
        hi = np.searchsorted(sorted_pts, b, side="right")
        labels[order[lo:hi]] = name
    return list(labels)


def reduce(profile, window: str, host_spans=(),
           anchor: float = 0.0) -> DeviceSummary:
    """Summarise the trace inside the host annotation named ``window``.
    ``host_spans`` are extra ``(name, start_s, end_s)`` on another host
    clock, on which the annotation opened at ``anchor`` seconds."""
    win = None
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            mark = next((ev for ev in events if ev.name == window), None)
            if mark is None:
                continue
            # The thread that opened the window: its events say what the
            # host was doing.
            win = (mark.start_ns, mark.start_ns + mark.duration_ns)
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in events
                      if ev is not mark and ev.duration_ns > 0]
    if win is None:
        raise ValueError(f"no host annotation {window!r} in the trace")
    w0, w1 = win
    spans += [(name, w0 + (a - anchor) * 1e9, w0 + (b - anchor) * 1e9)
              for name, a, b in host_spans]
    spans = [s for s in spans if s[2] > w0 and s[1] < w1]

    busy, kern, calls = [], [], []
    op_s = collections.Counter()
    gap_s = collections.Counter()
    gaps_all = []
    n_dev = 0
    for plane in profile.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        line = next((l for l in plane.lines if l.name == OPS_LINE), None)
        if line is None:
            continue
        n_dev += 1
        iv, k_s, k_n = [], 0.0, 0
        for ev in line.events:
            a = max(ev.start_ns, w0)
            b = min(ev.start_ns + ev.duration_ns, w1)
            if b <= a:
                continue
            iv.append((a, b))
            op_s[op_name(ev.name)] += (b - a) * 1e-9
            if KERNEL_MARK in ev.name:
                k_s += (b - a) * 1e-9
                k_n += 1
        merged = _union(np.asarray(iv, dtype=np.float64).reshape(-1, 2))
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        kern.append(k_s)
        calls.append(k_n)
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        lengths = edges[:, 1] - edges[:, 0]
        keep = lengths > 0
        edges, lengths = edges[keep], lengths[keep]
        names = _label((edges[:, 0] + edges[:, 1]) / 2, spans)
        for name, length in zip(names, lengths):
            gap_s[name] += length * 1e-9
            gaps_all.append((name, float(length) * 1e-9))
    if n_dev == 0:
        raise ValueError("no device plane with an 'XLA Ops' line in the "
                         "trace")
    gaps_all.sort(key=lambda g: -g[1])
    return DeviceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=float(np.mean(busy)),
        kernel_s=float(np.mean(kern)), kernel_calls=float(np.mean(calls)),
        devices=n_dev, op_s=dict(op_s),
        gap_s={k: float(v) / n_dev for k, v in gap_s.items()},
        longest_gaps=gaps_all[:10])


def breakdown(summary: DeviceSummary, top: int = 10) -> dict:
    """The result line's ``breakdown``: device ops by time, and idle time
    by what the host was doing."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.gap_s.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[k, float(v)] for k, v in ops],
                idle_gaps=[[k, float(v)] for k, v in gaps])
