"""The Lightning step's share of its roofline over the traced window: the
least HBM bytes it moves (`lib/sparse_linear.lightning_step_bytes`: every
stepped row's state read once and written once, 2,097,152 B each way at the
published shape, and its q, k, v in and o out; rows are the engine's
`state_rows_stepped`, live slots x Lightning layers summed over the decode
ticks between the trace's edges) over the HBM peak, over the device seconds
of `lightning_step` in the same window. Memory-bound: rank-one updates on the
vector unit. Over 100% means a count is too high or the time leaves work
out; it is reported as it reads, never clamped."""
from benchmark.lib import sparse_linear, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    rows = counter_delta(ctx, "state_rows_stepped")
    if plane is None or not rows \
            or "mixer_types" not in ctx["cell"]["config"]:
        return None
    kernel_s = xplane.matching_s(plane, sparse_linear.LIGHTNING_KERNEL)
    if kernel_s <= 0:
        return None
    least_s = sparse_linear.lightning_step_bytes(
        rows, ctx["cell"]["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
