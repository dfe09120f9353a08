#!/usr/bin/env python3
"""Find the highest request rate a serving cell sustains, on the chip.

    python3 bench/sweep.py --workload mnist-serve --seed 7 --seconds 4 \\
        --rates 5000,10000,20000

Sets the cell up once, then offers each rate in turn for ``--seconds``
through the cell's own open-loop generator and engine, and prints one
JSON line per rate: offered and served rate, p50/p99 latency from the due
time, generator lateness, and whether the backlog grew (median latency of
the last fifth of the window over that of the first fifth).  A rate is
sustained when the backlog does not grow and nearly every request is
served inside the window.  The cell's traffic file then takes 0.8x the
highest sustained rate, written in by hand.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
GROWTH = 2.0          # last-fifth over first-fifth median latency
SERVED_SHARE = 0.98   # of the offered rate, inside the window


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
    import jax
    import numpy as np
    import run

    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py: no TPU", file=sys.stderr)
        return 1
    cell = run.load_cell(args.workload)
    if cell["traffic"]["kind"] != "open_loop":
        print("bench/sweep.py: not a serving cell", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compilation_cache
    use_compilation_cache()
    built = run.build(cell, args.seed)
    state, pool, kind = built.state, built.pool, built.kind
    best = None
    for rate in (float(r) for r in args.rates.split(",")):
        state.traffic = dict(cell["traffic"], rate_rps=rate)
        out = kind.window(state, pool, args.seconds, args.seed, [])
        lat = out.completed - out.due
        fifth = len(lat) // 5
        growth = (np.nanmedian(lat[-fifth:]) / np.nanmedian(lat[:fifth])
                  if fifth else float("nan"))
        e2e = kind.end_to_end(out, args.seconds)
        ok = (growth < GROWTH
              and e2e.get("served_rps", 0) >= SERVED_SHARE * len(out.due)
              / args.seconds)
        best = rate if ok else best
        print(json.dumps(dict(workload=args.workload, rate_rps=rate,
                              offered=len(out.due), growth=growth,
                              sustained=ok, lateness=kind.lateness(out),
                              **e2e), default=float), flush=True)
        if not ok and growth > 10:
            break
    print(json.dumps(dict(workload=args.workload, highest_sustained=best,
                          cell_rate=None if best is None else 0.8 * best)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
