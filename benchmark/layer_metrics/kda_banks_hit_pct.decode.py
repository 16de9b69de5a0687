"""Share of the expert banks held here that a decode dispatch touches, read
from this configuration's own keys: the engine's `moe_experts_hit` (distinct
held experts with a row, summed over the expert layers, decode dispatches
only) over expert layers (`num_hidden_layers` less `first_k_dense_replace`)
x experts held (`num_experts`) x decode dispatches, between the traced
window's edges: how much of the banks a tick reads.
Source: program_counter."""
from benchmark.lib import linear_state
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    hit = counter_delta(ctx, "moe_experts_hit")
    ticks = counter_delta(ctx, "decode_ticks")
    cfg = ctx["cell"]["config"]
    if not hit or not ticks or "first_k_dense_replace" not in cfg \
            or "num_experts" not in cfg:
        return None
    return 100.0 * hit / (ticks * linear_state.expert_layers(cfg)
                          * cfg["num_experts"])
