"""Median time from a request's due time to the start of the tick that
admitted it (from `queue_ticks` and the benchmark's tick stamps)."""
from benchmark.lib.layer_common import median_of


def read(ctx):
    return median_of(ctx, "queue_wait_ms")
