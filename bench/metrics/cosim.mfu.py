"""The whole co-simulation's share of the chip's peak: the application's
forward operations per sample (bench/flops.py, counted on the source
program) times samples per second, over the device's bf16 peak
(bench/peaks.json)."""


def read(ctx):
    if not ctx.samples_done or ctx.peak is None or not ctx.flops_per_sample:
        return None
    rate = ctx.samples_done / ctx.window_s
    return 100.0 * ctx.flops_per_sample * rate / ctx.peak["bf16_flops_per_s"]
