"""Device time per engine tick of the gated-delta-rule step kernel
(`kda_step`, ops/pallas_kda: one call a KDA layer a decode tick), by kernel
name in the trace. A program without the kernel has no such op and reports
nothing."""
from benchmark.lib.layer_common import kernel_ms_per_unit
from benchmark.lib.linear_state import KDA_KERNEL


def read(ctx):
    return kernel_ms_per_unit(ctx, KDA_KERNEL)
