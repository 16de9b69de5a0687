"""Median wall time of the ticks that held at least one prefill."""
from benchmark.lib.layer_common import median_tick_ms


def read(ctx):
    return median_tick_ms(ctx, lambda t: t["prefills"] > 0)
