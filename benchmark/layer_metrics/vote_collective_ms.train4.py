"""Time per optimizer step on device 0 during which a collective op was in
flight (all-to-all, all-gather, all-reduce, collective-permute,
reduce-scatter): the union of their intervals, taken from the ``XLA Ops``
line (synchronous collectives) and the ``Async XLA Ops`` line (the
start-to-done spans of asynchronous ones)."""
from benchmark.lib.collectives import collective_intervals
from benchmark.lib.layer_common import device0, units


def read(ctx):
    n, plane = units(ctx), device0(ctx)
    if not n or plane is None:
        return None
    coll = collective_intervals(plane)
    return sum(hi - lo for lo, hi in coll) / 1e6 / n if coll else None
