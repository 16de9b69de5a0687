"""Decode attention over selected pages as a share of its roofline over the
traced window: the least HBM bytes it moves
(`lib/sparse_linear.sparse_attn_bytes`: the engine's `kv_pages_selected`
between the trace's edges, one for every (page, kv head) pair a list holds in
either `minicpm4` layer, x 8,192 B, a kv head's keys and values of a page:
16 rows x 128 x 2 B, twice) over the HBM peak, over the device seconds of
`paged_attn` in the same window. Its numerator is what the lists hold (the
program's count of what it hands the kernel), not bytes observed on the
device: a page row holds both kv heads' lanes and the kernel's copy brings
both, so a share near 50% is that kernel at its own roofline.
Memory-bound: one query a head. Over 100% means a count is too high or the
time leaves work out; it is reported as it reads, never clamped."""
from benchmark.lib import sparse_linear, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import PAGED_ATTN_KERNEL, device0


def read(ctx):
    plane = device0(ctx)
    selected = counter_delta(ctx, "kv_pages_selected")
    cfg = ctx["cell"]["config"]
    if plane is None or not selected or "mixer_types" not in cfg:
        return None
    kernel_s = xplane.matching_s(plane, PAGED_ATTN_KERNEL)
    if kernel_s <= 0:
        return None
    block = ctx["cell"]["program"]["serve_config"]["block_size"]
    least_s = sparse_linear.sparse_attn_bytes(selected, cfg, block) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
