"""Share of the window in which no operation ran on the device, from the
profiler's trace (bench/trace_reduce.py)."""
from bench.readers import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx)
