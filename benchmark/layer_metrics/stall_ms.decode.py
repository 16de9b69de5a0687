"""What slow ticks cost, in ms a tick of the window: `engine.stats
["slow_tick_excess_s"]` (a stalled decode-only tick's wall less the median
of the ticks before it) over `ticks` between the `open` and `close` copies
(a tick and not a window: a traced run's window also holds the tracer's own
stall and is as long as that makes it); 0 in most runs. Source:
program_counter."""
from benchmark.lib.host_accounts import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, "slow_tick_excess_s")
