"""Seconds of set-up spent in compiling or loading the PROGRAM's dispatches:
the compile ledger's `compile_s` until the trace's `t0` (what `compile_s`
would be without the reference's programs)
(`lib/host_accounts.setup_parts`). Source: program_counter."""
from benchmark.lib.host_accounts import setup_part


def read(ctx):
    return setup_part(ctx, "load")
