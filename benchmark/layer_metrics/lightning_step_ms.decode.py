"""Device time per engine tick of the Lightning step kernel
(`lightning_step`, ops/pallas_lightning: one call a Lightning layer a decode
tick), by kernel name in the trace. A program without the kernel has no such
op and reports nothing."""
from benchmark.lib.layer_common import kernel_ms_per_unit
from benchmark.lib.sparse_linear import LIGHTNING_KERNEL


def read(ctx):
    return kernel_ms_per_unit(ctx, LIGHTNING_KERNEL)
