"""The sliding layers' decode attention over their rings of latent rows as a
share of its roofline over the traced window: the rows the walks are handed
(`lib/dsa_layers.window_bytes`: the engine's `kv_window_pages_read` between
the trace's edges, ONE sliding layer's pages, x 16 rows x 2,304 B, a latent
row of 1,088 values in 1,152 lanes, x the configuration's count of sliding
layers) over the HBM peak, over the device seconds of `window_mla_attn` in
the same window. Memory-bound at one query a head: a walk of at most 34
pages a row. A program without the kernel or the counter reports nothing;
never clamped."""
from benchmark.lib import dsa_layers, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    pages = counter_delta(ctx, "kv_window_pages_read")
    cfg = ctx["cell"]["config"]
    if plane is None or not pages or "swa_kv_lora_rank" not in cfg:
        return None
    kernel_s = xplane.matching_s(plane, dsa_layers.WINDOW_KERNEL)
    if kernel_s <= 0:
        return None
    block = ctx["cell"]["program"]["serve_config"]["block_size"]
    least_s = dsa_layers.window_bytes(pages, block, cfg) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
