"""The indexer's decode scoring as a share of its roofline over the traced
window: the index keys the decode ticks must read
(`lib/dsa_layers.index_bytes` of the engine's `dsa_keys_visible` between the
trace's edges: every key a live row can see, over rows, ticks and full
layers, x 256 B) over the HBM peak, over the device seconds of `dsa_index`
in the same window. Memory-bound: 64 heads x 128 x 2 operations a key of 256
B, 64 a byte against the chip's 240. The counter is the decode ticks' alone
(a prefill counts nothing) and so is the kernel. A program without the
kernel or the counter reports nothing; never clamped."""
from benchmark.lib import dsa_layers, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    visible = counter_delta(ctx, "dsa_keys_visible")
    cfg = ctx["cell"]["config"]
    if plane is None or not visible or "index_head_dim" not in cfg:
        return None
    kernel_s = xplane.matching_s(plane, dsa_layers.INDEX_KERNEL)
    if kernel_s <= 0:
        return None
    least_s = dsa_layers.index_bytes(visible, cfg) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
