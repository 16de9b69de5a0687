"""Share of the expert banks a decode dispatch touches: the engine's
`moe_experts_hit` (distinct experts with a token, summed over the expert
layers, decode dispatches only) over expert layers x routed experts x
decode dispatches, between the traced window's edges. Near 100% the tick is
bound by reading every bank. Source: program_counter."""
from benchmark.lib import latent_moe


def read(ctx):
    hit = latent_moe.counter_delta(ctx, "moe_experts_hit")
    ticks = latent_moe.counter_delta(ctx, "decode_ticks")
    if not hit or not ticks:
        return None
    cfg = ctx["cell"]["config"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * hit / (ticks * layers * cfg["n_routed_experts"])
