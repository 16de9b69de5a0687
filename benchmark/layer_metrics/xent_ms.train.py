"""Device time per optimizer step of the loss head's kernels
(`fused_xent_fwd`, `fused_xent_bwd`: the tied head's products and the
softmax cross-entropy, ops/pallas_xent), by kernel name in the trace. A
program without them (the parent of the PR that added them) has no such op
and reports nothing."""
from benchmark.lib.layer_common import kernel_ms_per_unit
from benchmark.lib.loss_head import XENT_KERNELS


def read(ctx):
    return kernel_ms_per_unit(ctx, XENT_KERNELS)
