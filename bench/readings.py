#!/usr/bin/env python3
"""Readings for the limits of ``correct``: in one process, whole runs of a
cell (its own window and load) on many seeds, each printing the numbers it
compared and, over the same served inputs, what the configuration's
control reads in the program's place (the reference one precision step
down) and what a bfloat16 reference reads.

    python bench/readings.py --workload mnist_rnn.closed --seconds 20 \\
        --seeds 11,12,13

The lower reading of a number is the largest over the sound runs, the
upper reading the smallest over the control's (PERF.md gives both).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def lower_precision_readings(cell, served, window) -> dict:
    """``max_rel_err`` of the control and of bfloat16 against the float32
    reference, over every sample the window answered."""
    import numpy as np

    from bench import check

    xs = [x for o in window.outcomes if o.handle.status == "done"
          for x in window.inputs[o.request.index]]
    ref = check.reference_outputs(cell, served.params, xs)
    out = {}
    for precision in (cell.config["control"]["precision"], "bfloat16"):
        low = check.reference_outputs(cell, served.params, xs, precision)
        out[precision] = float(check.rel_errors(low, ref).max()) if len(xs) else None
    out["samples"] = len(xs)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    from bench import run
    from bench.cell import resolve, run_cell

    cell = resolve(args.workload, ROOT)
    run.chips_or_exit(cell.chips)
    run.enable_cache()
    quiet = lambda *a, **k: None  # noqa: E731
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        low = {}
        r = run_cell(args.workload, seed, args.seconds, False, t_process=t,
                     root=ROOT, log=quiet,
                     keep=lambda c, s, w: low.update(lower_precision_readings(c, s, w)))
        print(json.dumps({
            "seed": seed, "run_in_process": n, "correct": r["correct"],
            "attempted": r["attempted"], "failed": r["failed"],
            "in_window": r["in_window"], "run_s": time.perf_counter() - t,
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "checks": r["checks"], "lower_precision": low}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
