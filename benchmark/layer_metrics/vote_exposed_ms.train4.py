"""The part of the collectives' time, per step on device 0, during which
no other op ran there: the collectives' intervals minus their overlap with
the union of every non-collective op of the ``XLA Ops`` line."""
from benchmark.lib import xplane
from benchmark.lib.collectives import collective_intervals, is_collective
from benchmark.lib.layer_common import device0, units


def read(ctx):
    n, plane = units(ctx), device0(ctx)
    if not n or plane is None:
        return None
    coll = collective_intervals(plane)
    if not coll:
        return None
    other = xplane.merged([e for e in xplane.line_events(plane, xplane.OPS_LINE)
                           if not is_collective(e) and not xplane.is_container(e)])
    total = sum(hi - lo for lo, hi in coll)
    return (total - xplane.overlap_ns(coll, other)) / 1e6 / n
