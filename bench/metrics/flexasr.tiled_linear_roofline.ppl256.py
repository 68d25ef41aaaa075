"""The tiled FlexASR linears' share of the chip's bf16 peak: their
multiply-accumulates in the window (from the configuration's widths, for
the 256 tokens of each answered sample, and the routed experts at the rows
the ``moe.routed_rows`` counter gives; bench/configs/moonlight_16b_a3b_work.py)
times 2, over the device's busy time in the traced window. Busy time holds
every device operation, so this is a lower bound on the runner's own."""
from pathlib import Path

from bench.cell import load_module

WORK = Path(__file__).resolve().parents[1] / "configs" / "moonlight_16b_a3b_work.py"


def read(ctx):
    rows = ctx.counters.get("moe.routed_rows")
    t = ctx.trace
    if rows is None or not ctx.samples_done or ctx.peak is None or not t \
            or not t.get("busy_s"):
        return None
    macs = load_module(WORK).tiled_linear_macs(ctx.samples_done, rows)
    return 100.0 * 2.0 * macs / t["busy_s"] / ctx.peak["bf16_flops_per_s"]
