#!/usr/bin/env python3
"""The two readings every correctness limit is set from, on the chip.

    python3 bench/readings.py --workload mnist-serve --seeds 1,2,3 \\
        --seconds 3 [--control-only]

For each seed, in one process (the compile cache is shared):
* the program: one run of the cell (``run.run_cell``, a short window at
  the cell's own load), and the numbers its comparison gives;
* the control (``bench/control.py``): the reference at bfloat16 in the
  program's place, on the same pool at the cell's own batch, and the
  numbers the same comparison gives it.

The lower reading of a number is the largest the program gives over the
seeds; the upper one the smallest the control gives.  Prints one JSON
line per seed and side, and a summary line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_numbers(run, cell: dict, seed: int) -> dict:
    """The cell's comparison numbers with the control in the program's
    place, on the run's pool and planted weights for ``seed``."""
    import numpy as np
    import control
    import planted
    import reference
    cfg, traffic = cell["cfg"], cell["traffic"]
    kind = run._module(run.BENCH / "traffic" / f"{traffic['kind']}.py")
    ta, w = planted.planted(cfg, seed)
    pool = planted.pool(cfg, traffic["pool_rows"], seed)
    fabs = reference.program(np.asarray(ta), np.asarray(w), cfg)
    refs = reference.infer(fabs, pool)
    fab = fabs[0]
    if traffic["kind"] == "open_loop":
        ctrl = control.infer(fab, pool, traffic["capacity"])
        out = kind.control_outcome(ctrl, len(pool), traffic["capacity"])
    else:
        ctrl = control.infer(fab, pool, traffic["batch"])
        out = kind.control_outcome(ctrl, traffic["batch"], cfg)
    return run.judge(kind, out, refs, cfg, cell["limits"])[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-only", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
    import jax
    import run
    if jax.devices()[0].platform != "tpu":
        print("bench/readings.py: no TPU", file=sys.stderr)
        return 1
    cell = run.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    lower, upper = {}, {}
    for seed in seeds:
        if not args.control_only:
            res = run.run_cell(cell, seed, args.seconds, False)
            got = {k: c["value"] for k, c in res["checks"].items()}
            print(json.dumps(dict(side="program", seed=seed, **got)),
                  flush=True)
            for k, v in got.items():
                lower[k] = max(lower.get(k, v), v)
        got = control_numbers(run, cell, seed)
        print(json.dumps(dict(side="control", seed=seed, **got)), flush=True)
        for k, v in got.items():
            upper[k] = min(upper.get(k, v), v)
    print(json.dumps(dict(workload=args.workload, seeds=len(seeds),
                          lower=lower, upper=upper)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
