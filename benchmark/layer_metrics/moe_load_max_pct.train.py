"""The fullest held expert's rows over the mean held expert's, a step: the
trainer's `moe_load_max` (the most rows at one expert, summed over layers and
microbatches) over `moe_assignments` / experts held. 100% is a flat load; the
grouped products are dropless, so a fuller expert costs its rows and drops
none. Source: program_counter."""
from benchmark.lib.expert_train import step_counters


def read(ctx):
    counters = step_counters(ctx)
    if not counters or not counters["moe_assignments"]:
        return None
    held = ctx["cell"]["config"]["num_experts"]
    return 100.0 * counters["moe_load_max"] * held \
        / counters["moe_assignments"]
