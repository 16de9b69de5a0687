"""The Lion kernels' share of their roofline: the least bytes one update
moves (from shapes) over the HBM peak, over the kernels' device time.
Memory-bound."""
from benchmark.lib import roofline
from benchmark.lib.layer_common import LION_KERNELS, kernel_ms_per_unit


def read(ctx):
    ms = kernel_ms_per_unit(ctx, LION_KERNELS)
    if ms is None:
        return None
    facts = ctx["facts"]
    least_s = roofline.lion_kernel_bytes(facts["n_params"], facts["world"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s * 1e3 / ms
