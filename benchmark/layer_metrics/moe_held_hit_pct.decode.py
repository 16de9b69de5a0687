"""Share of the expert banks held here that a decode dispatch touches: the
engine's `moe_experts_hit` (distinct held experts with a row, summed over
the expert layers, decode dispatches only) over expert layers x experts
held x decode dispatches, between the traced window's edges. Near 100% the
tick is bound by reading every bank it holds. Source: program_counter."""
from benchmark.lib import hybrid_cache
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    hit = counter_delta(ctx, "moe_experts_hit")
    ticks = counter_delta(ctx, "decode_ticks")
    cfg = ctx["cell"]["config"]
    if not hit or not ticks or "mlp_layer_types" not in cfg:
        return None
    return 100.0 * hit / (ticks * hybrid_cache.expert_layers(cfg)
                          * cfg["num_experts"])
