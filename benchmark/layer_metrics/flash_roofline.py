"""The flash-attention kernels' share of their roofline: FLOPs the causal
attention of one step needs (forward + backward, the program's remat
recompute not counted) over the bf16 peak, over the kernels' device time.
Compute-bound at head_dim 64, seq 1024."""
from benchmark.lib import roofline
from benchmark.lib.layer_common import FLASH_KERNELS, kernel_ms_per_unit


def read(ctx):
    ms = kernel_ms_per_unit(ctx, FLASH_KERNELS)
    if ms is None:
        return None
    cfg, job = ctx["cell"]["config"], ctx["facts"]["job"]
    seqs = job["accum"] * job["micro"]          # one chip's rows a step
    flops = cfg["n_layer"] * roofline.flash_attention_flops(
        seqs, cfg["n_head"], job["block"], cfg["n_embd"] // cfg["n_head"])
    least_ms = flops / ctx["peaks"]["bf16_flops_per_s"] * 1e3
    return 100.0 * least_ms / ms
