"""Device time per engine tick of the decode kernel that reads the KV pool
in place (`paged_attn`, ops/pallas_paged_attn), by kernel name in the
trace. A program without the kernel has no such op and reports nothing."""
from benchmark.lib.layer_common import PAGED_ATTN_KERNEL, kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, PAGED_ATTN_KERNEL)
