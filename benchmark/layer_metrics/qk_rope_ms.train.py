"""Device time per optimizer step of q's and k's head norm and rotation
(`qk_rope_fwd`, run again under a remat rung, and `qk_rope_bwd`:
ops/pallas_qk_rope, one call each for q and for k a layer a microbatch), by
kernel name in the trace. A program that norms and rotates in XLA fusions (a
commit before the kernels, a shape they do not take) reports nothing."""
from benchmark.lib.layer_common import kernel_ms_per_unit

QK_ROPE_KERNELS = r"qk_rope_(fwd|bwd)"


def read(ctx):
    return kernel_ms_per_unit(ctx, QK_ROPE_KERNELS)
