"""The hyper-connection mix's share of the traced window's device time: the
device seconds of `mhc_pre` and `mhc_post` (prefills and decode ticks
together) over the seconds in which any op ran. A program without the
kernels reports nothing."""
from benchmark.lib import hyper_stream, xplane
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    if plane is None:
        return None
    kernel_s = xplane.matching_s(plane, hyper_stream.MHC_KERNELS)
    busy_s = xplane.device_busy_s(ctx["trace"])
    if kernel_s <= 0 or busy_s <= 0:
        return None
    return 100.0 * kernel_s / busy_s
