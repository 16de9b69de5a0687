"""What Python's collector took, in ms a tick of the window: `engine.stats
["gc_pause_s"]` (the process's collections, every generation, copied once a
tick) over `ticks` between the `open` and `close` copies. Source:
program_counter."""
from benchmark.lib.host_accounts import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, "gc_pause_s")
