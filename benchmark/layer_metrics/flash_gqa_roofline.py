"""The grouped-query attention kernels' share of their roofline: FLOPs the
attention of one step needs (forward + backward over the band's pairs on a
window layer and the causal half's on a full layer, the remat's second
forward not counted: `lib/expert_train.attention_flops`) over the bf16 peak,
over the kernels' device time. Compute-bound at heads of 128 and 8,192 keys."""
from benchmark.lib import expert_train
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    ms = kernel_ms_per_unit(ctx, expert_train.GQA_TRAIN_KERNELS)
    if ms is None:
        return None
    cfg, job = ctx["cell"]["config"], ctx["facts"]["job"]
    seqs = job["accum"] * job["micro"]          # one chip's rows a step
    flops = expert_train.attention_flops(cfg, seqs, job["block"])
    least_ms = flops / ctx["peaks"]["bf16_flops_per_s"] * 1e3
    return 100.0 * least_ms / ms
