"""Arithmetic shared by the metric readers under ``bench/metrics/``.

Each reader is a file named after its metric with one function,
``read(ctx) -> float | None`` (``ctx`` is ``bench.cell.Context``). None
means the window held nothing to read, and the metric is left out of the
result line.
"""
from __future__ import annotations

from typing import Optional


def span_ms_per_sample(ctx, name: str) -> Optional[float]:
    """Total duration of the program's ``name`` spans in the window, in
    milliseconds per completed sample."""
    durs = [s["dur"] for s in ctx.spans if s["name"] == name]
    if not durs or not ctx.samples_done:
        return None
    return sum(durs) * 1e-3 / ctx.samples_done


def idle_share_pct(ctx) -> Optional[float]:
    t = ctx.trace
    if not t or t.get("idle_share") is None or not t.get("devices"):
        return None
    return 100.0 * t["idle_share"]
