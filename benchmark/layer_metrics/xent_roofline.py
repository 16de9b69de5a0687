"""The loss head's kernels' share of their roofline: the least FLOPs of
the tied head and its gradients in one step over the bf16 peak, over the
kernels' device time. THREE products of 2 x rows x d x vocab (rows =
sequences x (block - 1)): the logits, dh and dwte; both gradients form
inside `fused_xent_bwd`, none is left to XLA, and the logits that kernel
recomputes are not counted. Compute-bound (1,536 MXU FLOPs a logit against
a dozen vector operations). Reported as it reads, never clamped: over 100%
is a fault of the count."""
from benchmark.lib import loss_head
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    ms = kernel_ms_per_unit(ctx, loss_head.XENT_KERNELS)
    if ms is None:
        return None
    cfg, job = ctx["cell"]["config"], ctx["facts"]["job"]
    flops = loss_head.head_flops(job["accum"] * job["micro"], job["block"],
                                 cfg["n_embd"], cfg["vocab_size"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] * 1e3 / ms
