"""Host evaluation of the IR nodes that are not offloaded, and of the
operands handed to the accelerators (``executor.host_eval`` spans), per
answered sample."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "executor.host_eval")
