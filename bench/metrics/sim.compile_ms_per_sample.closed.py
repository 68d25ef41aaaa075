"""First calls (trace, lower, compile) of the jitted simulator runners and
batched reads the window creates (``executor.compile`` spans), per
answered sample."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "executor.compile")
