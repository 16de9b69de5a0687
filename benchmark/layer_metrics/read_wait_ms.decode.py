"""What the host waited in its blocking token reads, in ms a tick, over the
whole window: `engine.stats["read_wait_s"]` over `ticks` between the
`open` and `close` copies. The host's slack under the device's tick: it
falls to 0 where the host binds. Source: program_counter."""
from benchmark.lib.host_accounts import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, "read_wait_s")
