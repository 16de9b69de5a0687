"""Device time per optimizer step of the grouped-query attention kernels
(`flash_gqa_lse` forward, run again under a remat rung, `flash_gqa_dq`,
`flash_gqa_dkv`: ops/pallas_flash_attn), by kernel name in the trace."""
from benchmark.lib.expert_train import GQA_TRAIN_KERNELS
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, GQA_TRAIN_KERNELS)
