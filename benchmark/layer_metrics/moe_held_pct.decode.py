"""Share of a decode dispatch's expert picks whose expert is held here: the
engine's `moe_assignments` (rows the grouped matmul computes) over
`moe_routed` (tokens x experts a token, held or not), decode dispatches
only, between the traced window's edges. A chip that holds 128 of 256
experts reads near 50%; 100% means every pick is computed here.
Source: program_counter."""
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    rows = counter_delta(ctx, "moe_assignments")
    routed = counter_delta(ctx, "moe_routed")
    if rows is None or not routed:
        return None
    return 100.0 * rows / routed
