"""The whole co-simulation step's share of the chip's bf16 peak in the
Moonlight cell: the tiled linears' multiply-accumulates (as the roofline
metric counts them: routed experts at the ``moe.routed_rows`` counter) plus
the causal attention products, times 2, per second of the window.
(``cosim.mfu`` counts the source IR, where each routed expert's dense runs
at its capacity of 256 rows.)"""
from pathlib import Path

from bench.cell import load_module

WORK = Path(__file__).resolve().parents[1] / "configs" / "moonlight_16b_a3b_work.py"


def read(ctx):
    rows = ctx.counters.get("moe.routed_rows")
    if rows is None or not ctx.samples_done or ctx.peak is None:
        return None
    work = load_module(WORK)
    macs = work.tiled_linear_macs(ctx.samples_done, rows) \
        + ctx.samples_done * work.attention_macs_per_window()
    return 100.0 * 2.0 * macs / ctx.window_s / ctx.peak["bf16_flops_per_s"]
