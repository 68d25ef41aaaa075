"""MoE routing on the host (``moe.route`` spans: scores, top-k, combine
weights; ``moe.dispatch`` spans: gathering each held expert's rows and
scattering them back) per answered sample."""
from bench.readers import span_ms_per_sample


def read(ctx):
    parts = [span_ms_per_sample(ctx, n) for n in ("moe.route", "moe.dispatch")]
    if all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None)
