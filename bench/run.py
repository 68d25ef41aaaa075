#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared with its limit (also the last lines of standard error).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: JAX's persistent compilation cache: a fixed directory in the checkout
#: (the path is part of the cache key), unless the environment names one
CACHE_DIR = ROOT / ".jax_cache"


def chips_or_exit(chips: int):
    """The devices, or exit non-zero when they are not ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (first device: {devs[0].platform}); "
                 f"nothing was run")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chips, JAX found {len(devs)}")
    return devs


def enable_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, however quick to compile: set-up is then the
    # same work from the second run of a cell on
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devs, mem: int, trace=None) -> dict:
    d = {"platform": devs[0].platform, "kind": devs[0].device_kind,
         "count": len(devs), "memory_peak_bytes": mem}
    if trace is not None:
        d["busy_s"] = trace["busy_s"]
        d["window_s"] = trace["window_s"]
    return d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.cell import resolve, run_cell

    cell = resolve(args.workload, ROOT)
    devs = chips_or_exit(cell.chips)
    enable_cache()
    r = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_process=T_PROCESS, root=ROOT)
    trace = r.get("trace")
    line = {
        "correct": r["correct"],
        "attempted": r["attempted"],
        "failed": r["failed"],
        "metrics": r["metrics"],
        "device": device_info(devs, r["memory_peak_bytes"], trace),
    }
    if trace is not None:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["in_window"] = r["in_window"]
    line["generator_late_ms_max"] = r["generator_late_ms_max"]
    line["checks"] = r["checks"]
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
