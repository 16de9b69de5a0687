"""The prefill's chunked delta rule's share of its roofline over the traced
window: the least time the chip could take for the positions the engine
prefilled between the trace's edges (`padded_prefill_tokens` over
`prefill_dispatches` prompts, in every KDA layer) over the device seconds of
`kda_chunk` in the same window. The least time is the larger of the least
HBM bytes over the HBM peak (`lib/linear_state.kda_chunk_bytes`: q, k, v,
decay, write strength in, o out, a prompt's state written once) and the
recurrence's own operations over the bfloat16 peak (`kda_chunk_flops`: 7
d_k d_v a position a head); at the published shape the bytes bound it, six
times over. What the kernel spends beyond the recurrence (a chunk's
products and triangular inverse, three bfloat16 passes a float32 matmul)
is why it reads far under 100%: a low share says the kernel is bound by
its own arithmetic, not by HBM. A program without the kernel (the parent,
or a prefill through the XLA form) has no such op and reports nothing;
never clamped."""
from benchmark.lib import linear_state, xplane
from benchmark.lib.latent_moe import counter_delta
from benchmark.lib.layer_common import device0


def read(ctx):
    plane = device0(ctx)
    tokens = counter_delta(ctx, "padded_prefill_tokens")
    prompts = counter_delta(ctx, "prefill_dispatches")
    if plane is None or not tokens or not prompts:
        return None
    kernel_s = xplane.matching_s(plane, linear_state.KDA_CHUNK_KERNEL)
    if kernel_s <= 0:
        return None
    cfg, peaks = ctx["cell"]["config"], ctx["peaks"]
    least_s = max(
        linear_state.kda_chunk_bytes(tokens, prompts, cfg)
        / peaks["hbm_bytes_per_s"],
        linear_state.kda_chunk_flops(tokens, cfg) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / kernel_s
