"""Host packing time of the executor (``pipeline.pack`` spans) per
answered sample."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "pipeline.pack")
