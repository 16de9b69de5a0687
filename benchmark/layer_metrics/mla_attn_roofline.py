"""The latent-attention decode kernel's share of its roofline over the
traced window: the larger of its least HBM bytes over the HBM peak (the
pages the live rows' lengths need, the engine's `kv_pages_read` between the
trace's edges, each read once in every layer's one leaf) and its FLOPs over
the bf16 peak (32 heads against every cached row: 576 values for the score,
512 for the value), over the kernel's device seconds. Reported as it reads,
never clamped: over 100% is a fault of the count."""
from benchmark.lib import latent_moe, xplane
from benchmark.lib.latent_moe import MLA_KERNEL
from benchmark.lib.layer_common import device0


def read(ctx):
    plane, pool = device0(ctx), ctx["facts"].get("kv_pool")
    pages = latent_moe.counter_delta(ctx, "kv_pages_read")
    if plane is None or not pool or not pages:
        return None
    kernel_s = xplane.matching_s(plane, MLA_KERNEL)
    if kernel_s <= 0:
        return None
    cfg, peaks = ctx["cell"]["config"], ctx["peaks"]
    _, block_size, groups, width = pool["leaf_shape"]
    layers = pool["leaves"]                       # one latent leaf a layer
    by_bytes = latent_moe.mla_attn_bytes(
        pages, block_size, groups * width, layers,
        pool["itemsize"]) / peaks["hbm_bytes_per_s"]
    by_flops = latent_moe.mla_attn_flops(
        pages, block_size, cfg["num_attention_heads"],
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"],
        layers) / peaks["bf16_flops_per_s"]
    return 100.0 * max(by_bytes, by_flops) / kernel_s
