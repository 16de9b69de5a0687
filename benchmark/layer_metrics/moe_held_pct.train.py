"""Share of a step's expert picks whose expert is held here: the trainer's
`moe_assignments` (rows the grouped products compute) over `moe_routed`
(tokens x experts a token, held or not), both summed over layers and
microbatches on the device and drained at `logging_steps`. A chip that holds
16 of 64 experts reads near 25% under a router at its seeded start.
Source: program_counter."""
from benchmark.lib.expert_train import step_counters


def read(ctx):
    counters = step_counters(ctx)
    if not counters or not counters["moe_routed"]:
        return None
    return 100.0 * counters["moe_assignments"] / counters["moe_routed"]
