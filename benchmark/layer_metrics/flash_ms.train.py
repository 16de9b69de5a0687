"""Device time per optimizer step of the flash-attention kernels (forward,
dq, dkv), by kernel name in the trace."""
from benchmark.lib.layer_common import FLASH_KERNELS, kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, FLASH_KERNELS)
