"""Share of the traced window's decode ticks that were enqueued while a
read was still outstanding (the host one tick ahead of its reads):
`run_ahead_ticks` over `decode_ticks` between the trace's edges.
Source: program_counter."""
from benchmark.lib.latent_moe import counter_delta


def read(ctx):
    ahead = counter_delta(ctx, "run_ahead_ticks")
    ticks = counter_delta(ctx, "decode_ticks")
    return 100.0 * ahead / ticks if ahead is not None and ticks else None
