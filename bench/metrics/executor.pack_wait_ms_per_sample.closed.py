"""Time the dispatch thread spends blocked on the pack worker
(``pipeline.pack_wait`` spans) per answered sample: the part of host
packing on the critical path."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "pipeline.pack_wait")
