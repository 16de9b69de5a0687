"""The grouped matmul's share of its roofline over the traced window: the
larger of its FLOPs over the bf16 peak (assignments x 3 matmuls of
hidden x expert width) and its least HBM bytes over the HBM peak (the banks
of the experts each dispatch touched, once a dispatch, and the rows'
activations), over the kernel's device seconds. Assignments and experts hit
are the engine's own counters between the trace's edges, decode and prefill
dispatches together. The larger of the two SUMS is at most the sum of each
dispatch's larger one (decode ticks are bound by bytes, prefills by
operations), so the share is understated rather than overstated. Never
clamped."""
from benchmark.lib import latent_moe, xplane
from benchmark.lib.latent_moe import GMM_KERNEL
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    rows = latent_moe.counter_delta(ctx, "moe_assignments",
                                    "moe_prefill_assignments")
    hit = latent_moe.counter_delta(ctx, "moe_experts_hit",
                                   "moe_prefill_experts_hit")
    if plane is None or not rows or not hit:
        return None
    kernel_s = xplane.matching_s(plane, GMM_KERNEL)
    if kernel_s <= 0:
        return None
    cfg, peaks = ctx["cell"]["config"], ctx["peaks"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    by_flops = latent_moe.moe_gmm_flops(rows, d, f) / peaks["bf16_flops_per_s"]
    by_bytes = latent_moe.moe_gmm_bytes(rows, hit, d, f) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * max(by_flops, by_bytes) / kernel_s
