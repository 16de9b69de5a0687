"""Latent attention over the kept set as a share of its roofline over the
traced window: what the MATHEMATICS needs (`lib/dsa_layers.kept_attn_bytes`:
the engine's `dsa_keys_kept` between the trace's edges x 1,280 B, a latent
row in its lanes, plus a (row, layer)'s queries in and outputs back,
`dsa_rows` x 2 x 128 heads x 1,280 B) over the HBM peak, over the device
seconds of `dsa_attn` in the same window. The numerator is the kept rows
whatever the kernel reads: today's kernel walks every visible row under a
mask, so at 12-25% kept it reads a like share of what it moves; a kernel
that read the kept rows alone would read near its own roofline, and none can
read over 100%. Both counters are the decode ticks' alone. A program without
the kernel or the counters reports nothing; never clamped."""
from benchmark.lib import dsa_layers, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    kept = counter_delta(ctx, "dsa_keys_kept")
    rows = counter_delta(ctx, "dsa_rows")
    cfg = ctx["cell"]["config"]
    if plane is None or not kept or not rows or "index_topk" not in cfg:
        return None
    kernel_s = xplane.matching_s(plane, dsa_layers.ATTN_KERNEL)
    if kernel_s <= 0:
        return None
    least_s = dsa_layers.kept_attn_bytes(kept, rows, cfg) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
