"""Device time per engine tick of the absorbed latent-attention decode
kernel (`mla_paged_attn`, ops/pallas_mla_attn: one call a layer a decode
tick), by kernel name in the trace. A program without the kernel has no
such op and reports nothing."""
from benchmark.lib.latent_moe import MLA_KERNEL
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, MLA_KERNEL)
