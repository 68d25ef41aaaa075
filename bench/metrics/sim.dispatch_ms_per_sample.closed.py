"""Time the executor spends dispatching the ILA simulators' compiled runners
(``pipeline.dispatch_group`` spans) per answered sample."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "pipeline.dispatch_group")
