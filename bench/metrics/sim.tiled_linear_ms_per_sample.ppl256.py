"""Dispatch of the tiled FlexASR linears (``flexasr.tiled_linear`` spans:
one per tiled linear and request, over all its tile invocations) per
answered sample."""
from bench.readers import span_ms_per_sample


def read(ctx):
    return span_ms_per_sample(ctx, "flexasr.tiled_linear")
