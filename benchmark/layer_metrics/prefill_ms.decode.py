"""Median length of the `serve/prefill` spans inside the traced window: one
admitted request's prefill dispatch and its token read.
Source: program_span."""
from benchmark.lib.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "serve/prefill")
