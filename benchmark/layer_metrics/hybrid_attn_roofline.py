"""The decode attention kernel's share of its roofline over a cache of two
lifetimes: the least HBM bytes it moves in the traced window (full layers:
the pages the live rows' lengths need, the engine's `kv_pages_read`; window
layers: the pages of the walks the kernel is handed, `kv_window_pages_read`,
counted in the program; each times a
page's bytes of keys and values times the layers of its kind:
`lib/hybrid_cache.hybrid_attn_bytes`) over the HBM peak, over the device
seconds of `paged_attn` (both kinds run it) in the same window.
Memory-bound: one query a head. Over 100% means a count is too high or the
time leaves work out; it is reported as it reads, never clamped."""
from benchmark.lib import hybrid_cache, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import PAGED_ATTN_KERNEL, device0


def read(ctx):
    plane, pool = device0(ctx), ctx["facts"].get("kv_pool")
    full = counter_delta(ctx, "kv_pages_read")
    window = counter_delta(ctx, "kv_window_pages_read")
    if plane is None or not pool or not full or window is None \
            or "layer_types" not in ctx["cell"]["config"]:
        return None
    kernel_s = xplane.matching_s(plane, PAGED_ATTN_KERNEL)
    if kernel_s <= 0:
        return None
    least_s = hybrid_cache.hybrid_attn_bytes(
        full, window, ctx["cell"]["config"], pool["leaf_shape"][1],
        pool["itemsize"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
