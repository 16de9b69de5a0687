"""Device time per engine tick of the experts' grouped matmul (`moe_gmm`,
ops/pallas_moe_gmm: three calls an expert layer a dispatch), by kernel name
in the trace, the prefills that ran in the traced ticks included. A program
without the kernel reports nothing."""
from benchmark.lib.latent_moe import GMM_KERNEL
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, GMM_KERNEL)
