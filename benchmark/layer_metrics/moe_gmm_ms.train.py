"""Device time per optimizer step of the experts' grouped products: `moe_gmm`
(the three forward products a layer, run again under a remat rung, and the
three `dlhs` products) and `moe_gmm_drhs` (ops/pallas_moe_gmm), by kernel name
in the trace. A program without them reports nothing."""
from benchmark.lib.expert_train import GMM_TRAIN_KERNELS
from benchmark.lib.layer_common import kernel_ms_per_unit


def read(ctx):
    return kernel_ms_per_unit(ctx, GMM_TRAIN_KERNELS)
