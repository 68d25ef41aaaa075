"""Accuracy statistics of each accelerator invocation: the fp32 ideal the
planners compute for them and ``Executor._record`` (``executor.stats``
spans), per answered sample. Nested in pack and readback."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "executor.stats")
