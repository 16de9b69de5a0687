"""Programs that set-up compiled because the persistent cache did not hold
them (the compile ledger's misses before the traced window; 0 on a warm
run). Source: program_counter."""
from benchmark.lib.program_spans import setup_compile_totals


def read(ctx):
    totals = setup_compile_totals(ctx)
    return None if totals is None else totals["cache_misses"]
