"""The prefill's chunked Lightning recurrence's share of its roofline over
the traced window: the least time the chip could take for the positions the
engine prefilled between the trace's edges (`padded_prefill_tokens` over
`prefill_dispatches` prompts, in every Lightning layer) over the device
seconds of `lightning_chunk` in the same window. The least time is the larger
of the least HBM bytes over the HBM peak
(`lib/sparse_linear.lightning_chunk_bytes`: q, k, v in at the weights' dtype,
o out in float32, a prompt's state written once) and the recurrence's own
operations over the bfloat16 peak (`lightning_chunk_flops`: 5 d d a position
a head). What the kernel spends beyond the recurrence (a chunk's products,
three bfloat16 passes a float32 matmul) is the program's choice and is not
counted: a low share says the kernel is bound by its own arithmetic, not by
HBM. A program without the kernel (the parent, or a prefill through the XLA
form) has no such op and reports nothing; never clamped."""
from benchmark.lib import sparse_linear, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    tokens = counter_delta(ctx, "padded_prefill_tokens")
    prompts = counter_delta(ctx, "prefill_dispatches")
    cfg = ctx["cell"]["config"]
    if plane is None or not tokens or not prompts \
            or "mixer_types" not in cfg:
        return None
    kernel_s = xplane.matching_s(plane, sparse_linear.LIGHTNING_CHUNK_KERNEL)
    if kernel_s <= 0:
        return None
    peaks = ctx["peaks"]
    itemsize = 4 if ctx["cell"]["program"].get(
        "weights_dtype", "bfloat16") == "float32" else 2
    least_s = max(
        sparse_linear.lightning_chunk_bytes(tokens, prompts, cfg, itemsize)
        / peaks["hbm_bytes_per_s"],
        sparse_linear.lightning_chunk_flops(tokens, cfg)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / kernel_s
