"""Device time per optimizer step of the two Lion kernels (`lion_ballot`
and `lion_apply`, ops/pallas_lion), by kernel name in the trace."""
from benchmark.lib.layer_common import LION_KERNELS, kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, LION_KERNELS)
