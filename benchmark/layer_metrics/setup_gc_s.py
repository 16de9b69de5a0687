"""Seconds of set-up spent in Python's collector: the `gc_pause` accounts that
ended before the window opened (they lie inside the other parts, not beside
them) (`lib/host_accounts.setup_parts`). Source: program_counter."""
from benchmark.lib.host_accounts import setup_part


def read(ctx):
    return setup_part(ctx, "gc")
