"""Static-verifier cost: lint latency and what tier 0 saves a campaign.

The static verifier (``repro.core.ilalint``) is the campaign's tier 0: it
runs **zero simulated commands**, so its only costs are (a) tracing every
``Instruction.update`` to a jaxpr once per ILA (cached process-wide) and
(b) classifying numpy command streams. This bench measures both sides of
that bargain:

* ``lint_cold`` / ``lint_warm`` — full-registry lint (all three passes,
  every registered target) with fresh vs cached jaxpr effect summaries.
  The warm number is what every later campaign pays for tier 0.
* ``campaign_protocol_escalate`` / ``campaign_protocol_full`` — an
  apps-free protocol-fault campaign (``decode_alias`` + ``cmd_reorder``)
  under ``ladder="escalate"`` (static detections skip every simulated
  tier) vs ``ladder="full"`` (every tier simulates regardless), both on
  warm golden caches. The gap is the simulation time tier 0 removes from
  the ladder for the fault classes it owns.

Run as __main__ the rows merge into BENCH_cosim.json (benchmarks/_bench_io).
"""
from __future__ import annotations

import time


def run():
    import repro.accel  # noqa: F401  (registers the bundled targets)
    from repro.core import ilalint
    from repro.core.campaign import run_campaign
    from repro.core.ila import TARGETS

    n_targets = len(TARGETS.names())

    print(f"\n== static-verifier latency ({n_targets} registered targets, "
          "3 passes, zero simulation) ==")
    ilalint._EFFECTS_CACHE.clear()
    t0 = time.perf_counter()
    cold = ilalint.lint_registry()
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = ilalint.lint_registry()
    warm_s = time.perf_counter() - t0
    n_cold = sum(len(v) for v in cold.values())
    n_warm = sum(len(v) for v in warm.values())
    assert n_cold == n_warm, "lint result changed between cold and warm runs"
    print(f"cold: {cold_s * 1e3:.1f} ms (fresh jaxpr traces), "
          f"warm: {warm_s * 1e3:.1f} ms (cached effects, "
          f"{cold_s / warm_s:.1f}x); {n_warm} results")
    rows = [
        ("lint_cold", cold_s / n_targets * 1e6,
         f"full 3-pass lint, fresh jaxpr effect traces, per target "
         f"({n_targets} targets, {n_cold} results)"),
        ("lint_warm", warm_s / n_targets * 1e6,
         f"full 3-pass lint, cached effects, per target "
         f"({cold_s / warm_s:.1f}x vs cold)"),
    ]

    kwargs = dict(
        targets=("flexasr", "vecunit", "hlscnn"),
        faults=("identity", "decode_alias", "cmd_reorder"),
        apps=(),                      # protocol-fault ladder cost only
        engine="pipelined", devices_per_target=1,
        op_samples=1, vt2_n=2, stat_calib_seeds=0,
    )
    print("\n== protocol-fault ladder cost, escalate (tier-0 skips) "
          "vs full (every tier simulates) ==")
    run_campaign(ladder="full", **kwargs)   # warm the golden caches (untimed)
    t0 = time.perf_counter()
    esc = run_campaign(ladder="escalate", **kwargs)
    esc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    full = run_campaign(ladder="full", **kwargs)
    full_s = time.perf_counter() - t0
    n = len(esc.reports)
    n_static = sum(1 for r in esc.reports if r.detected_at == "static")
    print(f"escalate: {n} mutants in {esc_s:.1f}s "
          f"({n_static} detected at tier 0, simulated tiers skipped)")
    print(f"full:     {n} mutants in {full_s:.1f}s "
          f"({full_s / esc_s:.2f}x vs escalate)")
    rows += [
        ("campaign_protocol_escalate", esc_s / n * 1e6,
         f"{n} protocol-fault mutants, escalate ladder: {n_static} "
         "static-tier detections skip all simulated tiers"),
        ("campaign_protocol_full", full_s / n * 1e6,
         f"same mutants, full ladder ({full_s / esc_s:.2f}x vs escalate): "
         "the simulation cost tier 0 removes for protocol faults"),
    ]
    return rows


if __name__ == "__main__":
    try:
        from benchmarks._bench_io import write_bench_json
    except ImportError:  # invoked as a script: benchmarks/ itself is on sys.path
        from _bench_io import write_bench_json

    rows = run()
    path = write_bench_json(rows)
    print(f"wrote {len(rows)} rows to {path}")
