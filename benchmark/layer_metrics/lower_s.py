"""Seconds of set-up spent tracing and lowering programs (jaxpr tracing +
jaxpr to MLIR, which no cache saves), summed over the programs the compile
ledger saw before the traced window. Source: program_counter."""
from benchmark.lib.program_spans import setup_compile_totals


def read(ctx):
    totals = setup_compile_totals(ctx)
    return None if totals is None else totals["trace_lower_s"]
