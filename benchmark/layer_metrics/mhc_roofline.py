"""The hyper-connection mix's share of its roofline over the traced window:
its least HBM bytes (`lib/hyper_stream.mhc_bytes` of the engine's `mhc_rows`
between the trace's edges: rows with a token x sublayers, prefills and
decode ticks together) over the HBM peak, over the device seconds of
`mhc_pre` and `mhc_post` in the same window. The mix has some 60 operations
a byte of coefficients and 2 a value of the stream: the bytes bound it.
Rows a dispatch pads (a 4,096 bucket round a 3,072-token prompt) are
computed by the kernels and not counted here, so padding reads as a lower
share. A program without the kernels or the counter reports nothing; never
clamped."""
from benchmark.lib import hyper_stream, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    rows = counter_delta(ctx, "mhc_rows")
    cfg = ctx["cell"]["config"]
    if plane is None or not rows or "hc_mult" not in cfg:
        return None
    kernel_s = xplane.matching_s(plane, hyper_stream.MHC_KERNELS)
    if kernel_s <= 0:
        return None
    itemsize = 4 if ctx["cell"]["program"].get(
        "weights_dtype", "bfloat16") == "float32" else 2
    least_s = hyper_stream.mhc_bytes(
        rows, cfg["hc_mult"], cfg["hidden_size"], itemsize) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
