"""Median length of the trainer's `dispatch` span (the jitted step call:
enqueue only) over the traced window's steps. Source: program_span."""
from benchmark.lib.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "dispatch")
