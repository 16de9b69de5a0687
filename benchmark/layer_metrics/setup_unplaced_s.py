"""Seconds of set-up spent in nothing the program names: `setup_s` less
`setup_construct_s`, the ledger's tracing and lowering, and `setup_load_s`.
Imports, the backend's start, the benchmark's own weights and warm-up
(`lib/host_accounts.setup_parts`). Source: host_clock."""
from benchmark.lib.host_accounts import setup_part


def read(ctx):
    return setup_part(ctx, "unplaced")
