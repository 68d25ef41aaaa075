"""Benchmark driver: one function per paper table + framework benches.

Prints ``name,us_per_call,derived`` CSV (plus human-readable tables) and
writes the machine-readable ``BENCH_cosim.json`` (see benchmarks/_bench_io)
so the co-sim perf trajectory — steady-state throughput, cold-vs-warm,
pipelined-vs-sync speedup, batch crossover — is tracked across PRs.
Usage: PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import os
import sys


def main() -> None:
    fast = "--fast" in sys.argv
    if fast:
        os.environ.setdefault("REPRO_TABLE2_N", "5")
        os.environ.setdefault("REPRO_TABLE4_N", "10")
        os.environ.setdefault("REPRO_TABLE4_STEPS", "150")

    from repro.launch.jax_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_campaign, bench_extraction, bench_kernels,
                            bench_sim_speed, roofline_report, table1_matching,
                            table2_mapping_validation, table3_formal,
                            table4_cosim)

    rows = []
    rows += table1_matching.run()
    rows += bench_extraction.run()
    rows += table2_mapping_validation.run()
    rows += table3_formal.run()
    rows += bench_sim_speed.run()
    rows += bench_campaign.run()
    rows += bench_kernels.run()
    rows += roofline_report.run()
    rows += table4_cosim.run()

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},\"{derived}\"")

    from benchmarks._bench_io import write_bench_json

    path = write_bench_json(rows, fresh=True)
    print(f"\nwrote {len(rows)} rows to {path}")


if __name__ == "__main__":
    main()
