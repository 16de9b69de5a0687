"""Device time of one prefill: the median duration of the executed programs
named `jit_prefill` on the trace's `XLA Modules` line (one event a run of the
engine's prefill program, whatever its bucket), in the traced window. Since
PR 36 the `serve/prefill` span is the enqueue alone and
`decode_device_ms.decode` holds the prefills only as a share of a tick; this
is the prefill's own device time. A trace that names no such program (no
prefill in the traced window, a training cell) reports nothing."""
import re
import statistics

from benchmark.lib import xplane
from benchmark.lib.layer_common import device0

# the engine's prefill program among the trace's executed programs (the way
# lib/hyper_stream.DECODE_MODULE names the decode tick)
PREFILL_MODULE = r"^jit_prefill\b"


def read(ctx):
    plane = device0(ctx)
    if plane is None:
        return None
    runs = [e[2] for e in xplane.line_events(plane, xplane.MODULES_LINE)
            if re.search(PREFILL_MODULE, e[0])]
    return statistics.median(runs) / 1e6 if runs else None
