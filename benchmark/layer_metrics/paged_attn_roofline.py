"""The decode attention kernel's share of its roofline: the least HBM bytes
it moves in the traced window (the pages the live rows' lengths need, the
engine's own `kv_pages_read` between the trace's edges, times a page's bytes
in every leaf of the pool: `roofline.paged_attn_bytes`) over the HBM peak,
over the kernel's device time in the same window. Memory-bound: one query a
head. Over 100% means the count is too high or the time leaves work out; it
is reported as it reads, never clamped."""
from benchmark.lib import roofline, xplane
from benchmark.lib.layer_common import PAGED_ATTN_KERNEL, device0


def read(ctx):
    facts, plane = ctx["facts"], device0(ctx)
    stats, pool = facts.get("engine_stats") or {}, facts.get("kv_pool")
    if plane is None or not pool or "trace_close" not in stats:
        return None
    kernel_s = xplane.matching_s(plane, PAGED_ATTN_KERNEL)
    pages = stats["trace_close"].get("kv_pages_read", 0) \
        - stats["trace_open"].get("kv_pages_read", 0)
    if kernel_s <= 0 or pages <= 0:
        return None
    _, block_size, groups, width = pool["leaf_shape"]
    least_s = roofline.paged_attn_bytes(
        pages, block_size, groups * width, pool["leaves"] // 2,
        pool["itemsize"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
