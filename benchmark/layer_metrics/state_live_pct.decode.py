"""Share of the state rows that a decode tick steps: the engine's
`state_rows_stepped` (live slots x KDA layers, summed over decode ticks)
over slots x KDA layers x decode ticks, between the traced window's edges.
An engine that leaves slots empty moves it; near 100% every slot's state is
read and written every tick. Source: program_counter."""
from benchmark.lib import linear_state
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    rows = counter_delta(ctx, "state_rows_stepped")
    ticks = counter_delta(ctx, "decode_ticks")
    if rows is None or not ticks:
        return None
    return 100.0 * rows / (ticks * ctx["facts"]["max_seqs"]
                           * linear_state.state_layers(ctx["cell"]["config"]))
