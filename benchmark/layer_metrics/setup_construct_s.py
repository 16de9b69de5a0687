"""Seconds of set-up spent in the trainer's or the engine's construction: its
`setup_lap` accounts that ended before the trace's `t0`, `setup/before` left
out (`lib/host_accounts.setup_parts`). Source: program_span."""
from benchmark.lib.host_accounts import setup_part


def read(ctx):
    return setup_part(ctx, "construct")
