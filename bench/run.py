#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the generator
module ``bench/traffic/<kind>.py``), checked against the limits in
``bench/limits/<cell>.json``.  Per-layer metrics are read by
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds
files; no file here changes.

One run, one process:
1. refuse to run without a TPU, or with fewer chips than the cell asks;
2. turn on JAX's persistent compilation cache (``<checkout>/.jax_cache``,
   or ``JAX_COMPILATION_CACHE_DIR``);
3. set up from the seed: traffic rows, a planted CoTM made on the device,
   the programmed fabric (``build_system``), the compiled session, and a
   warm-up of the cell's own shapes; all of that is ``setup_s``;
4. run the window for ``--seconds``; with ``--trace 1`` a profiler trace
   covers a short steady part of it;
5. free the program's state and compare what the window produced with
   the float64 reference (``bench/reference.py``);
6. print notes on earlier lines, each compared number beside its limit
   as the last lines of standard error, and one JSON result as the last
   line of standard output.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / "out"
#: The traced part of a ``--trace 1`` window starts at this share of the
#: window and lasts TRACE_S seconds, or up to TRACE_END of the window.
TRACE_AT, TRACE_S, TRACE_END = 0.3, 2.0, 0.9
WINDOW = "bench_window"


def _module(path: pathlib.Path):
    """Import a file of the benchmark by path (metric files have dots in
    their names), once per process."""
    name = "bench_" + path.stem.replace(".", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, bench_file: pathlib.Path = ROOT / "BENCHMARK.json"
              ) -> dict:
    """Everything a run of cell ``name`` needs, found by name."""
    spec = _json(bench_file)
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in {bench_file.name}")
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    traffic = _json(BENCH / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return dict(name=name, chips=cell["chips"], cfg=_json(ROOT / config["file"]),
                traffic=traffic,
                limits=_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


class CompileCounter:
    """Counts, from now on, executables built (``requests``: every XLA
    compile request, served from the persistent cache or not) and
    persistent-cache misses (``misses``: real compiles)."""

    def __init__(self, jax):
        self.requests = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build(cell: dict, seed: int, interpret: bool = False
          ) -> types.SimpleNamespace:
    """Set a cell up from the seed: the traffic pool, the planted CoTM
    (made on the device), the programmed fabric, the compiled session
    and the traffic kind's warm state."""
    import jax
    from repro.core import CoTMConfig, CoTMParams
    from repro.impact import IMPACTConfig, build_system

    import planted

    cfg, traffic = cell["cfg"], cell["traffic"]
    kind = _module(BENCH / "traffic" / f"{traffic['kind']}.py")
    phases, t = {}, time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        phases[name], t = now - t, now

    ta, w = planted.planted(cfg, seed)
    jax.block_until_ready((ta, w))
    lap("plant_s")
    pool = planted.pool(cfg, traffic["pool_rows"], seed)
    lap("pool_s")
    tile, cls = cfg["tile"], cfg["programming"]["class"]
    system = build_system(
        CoTMParams(ta_state=ta, weights=w),
        CoTMConfig(n_literals=cfg["n_literals"], n_clauses=cfg["n_clauses"],
                   n_classes=cfg["n_classes"], n_states=cfg["n_states"],
                   threshold=cfg["threshold"]),
        jax.random.key(0),
        IMPACTConfig(max_tile_rows=tile["max_tile_rows"],
                     max_tile_cols=tile["max_tile_cols"],
                     max_class_rows=tile["max_class_rows"],
                     variability=cfg["device"]["variability"],
                     finetune=cls["finetune"]))
    jax.block_until_ready(system.class_i)
    lap("program_fabric_s")
    session = system.compile(kind.session_spec(traffic, interpret))
    lap("compile_session_s")
    state = kind.setup(session, pool, traffic)
    lap("warm_s")
    return types.SimpleNamespace(
        kind=kind, pool=pool, ta_state=ta, weights=w, system=system,
        session=session, state=state, phases=phases)


def judge(kind, outcome, refs: list, cfg: dict, limits: dict):
    """Compare an outcome with each reference fabric's answers (one per
    valid resolution of a programming tie, see ``reference.program``):
    -> (numbers, index of the fabric judged against, correct).  The
    outcome is correct if every number that ``limits`` names is within
    its limit against some fabric; the fabric reported is the one on
    which the worst number, as a share of its limit, is least.  Numbers
    without a limit are notes."""
    import numpy as np
    best = None
    for i, ref in enumerate(refs):
        numbers = kind.compare(outcome, ref, cfg)
        ratio = max((numbers[k] / limit if limit else
                     (0.0 if numbers[k] == 0 else np.inf))
                    for k, limit in limits.items())
        if best is None or ratio < best[0]:
            best = (ratio, numbers, i)
    return best[1], best[2], best[0] <= 1.0


def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             interpret: bool = False, t_start: float | None = None,
             out_dir: pathlib.Path = OUT) -> dict:
    """One run of ``cell``; returns the result object (not yet printed)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    import numpy as np
    from repro.compile_cache import use_compilation_cache

    import reference
    import trace_reduce
    import work

    cache = use_compilation_cache()
    compiles = CompileCounter(jax)
    cfg, name = cell["cfg"], cell["name"]
    dev = jax.devices()[0]
    peak = work.peaks(dev.device_kind) if not interpret else None

    # -- set-up ---------------------------------------------------------
    built = build(cell, seed, interpret)
    kind, state, pool = built.kind, built.state, built.pool
    setup_s = time.perf_counter() - t_start
    setup_compiles = (compiles.requests, compiles.misses)
    _log(f"{name}: device {dev.platform}:{dev.device_kind} x"
         f"{len(jax.devices())}, compile cache {cache}, setup_s "
         f"{setup_s!r}, executables built in set-up {setup_compiles[0]}, "
         f"of them compiled (cache misses) {setup_compiles[1]}")

    # -- window ---------------------------------------------------------
    hooks, rec = [], None
    if trace:
        trace_dir = out_dir / "trace" / name
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec = trace_reduce.Recorder(trace_dir, WINDOW, time.monotonic)

        def start():
            rec.start()
            kind.trace_start(state)

        def stop():
            kind.trace_stop(state)
            rec.stop()

        a = TRACE_AT * seconds
        hooks = [(a, start), (min(a + TRACE_S, TRACE_END * seconds), stop)]
    outcome = kind.window(state, pool, seconds, seed, hooks)
    window_compiles = compiles.requests - setup_compiles[0]
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    notes = dict(setup_phases=built.phases, **kind.notes(state, outcome))
    seen = kind.traced(state, outcome, rec.t0, rec.t1) if trace else None
    ta_np, w_np = np.asarray(built.ta_state), np.asarray(built.weights)
    del state, built
    gc.collect()

    # -- correctness ----------------------------------------------------
    t_ref = time.perf_counter()
    refs = reference.infer(reference.program(ta_np, w_np, cfg), pool)
    numbers, variant, correct = judge(kind, outcome, refs, cfg,
                                      cell["limits"])
    t_ref = time.perf_counter() - t_ref
    ref = refs[variant]
    pred = ref["scores"].argmax(axis=1)
    notes.update(
        reference_s=t_ref, compiles_in_window=window_compiles,
        reference_fabrics=len(refs), judged_against=variant,
        fired_share=float(ref["fired"].mean()),
        class_histogram=np.bincount(pred, minlength=cfg["n_classes"]
                                    ).tolist(),
        smallest_margin=reference.margin(ref["scores"]))
    checks = {k: dict(value=numbers[k], limit=limit)
              for k, limit in cell["limits"].items()}
    notes.update({k: v for k, v in numbers.items() if k not in checks})
    attempted, failed = kind.counts(outcome)

    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()), memory_peak_bytes=memory_peak)
    result = dict(correct=correct, attempted=attempted, failed=failed)
    if not trace:
        values = dict(kind.end_to_end(outcome, seconds), setup_s=setup_s)
        result["metrics"] = {
            m["name"]: dict(value=values[m["name"]], unit=m["unit"])
            for m in cell["end_to_end"] if m["name"] in values}
        notes.update({k: v for k, v in values.items()
                      if k not in result["metrics"]})
    else:
        summary = trace_reduce.reduce(rec.profile(), WINDOW,
                                      seen["host_spans"], anchor=rec.t0)
        ctx = types.SimpleNamespace(cfg=cfg, peak=peak, device=summary,
                                    **{k: v for k, v in seen.items()
                                       if k != "host_spans"})
        result["metrics"] = {}
        for m in cell["per_layer"]:
            value = _module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = dict(value=value,
                                                    unit=m["unit"])
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = trace_reduce.breakdown(summary)
        notes["longest_idle_gaps"] = summary.longest_gaps
    result["device"] = device
    result["checks"] = checks

    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{name}.last.json").write_text(json.dumps(
        dict(seed=seed, seconds=seconds, trace=trace, notes=notes,
             result=result), indent=1, default=float))
    print(f"notes {json.dumps(notes, default=float)}", flush=True)
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    if not (ROOT / "src" / "repro").is_dir():
        _log(f"bench/run.py: no program under {ROOT / 'src'}; run from a "
             f"checkout of the repository")
        return 2
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError, StopIteration) as e:
        _log(f"bench/run.py: {e!r}")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        _log(f"bench/run.py: cell {cell['name']!r} needs {cell['chips']} "
             f"TPU chip(s); JAX found {len(devices)} "
             f"{devices[0].platform!r} device(s)")
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    for k, c in result["checks"].items():
        _log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
