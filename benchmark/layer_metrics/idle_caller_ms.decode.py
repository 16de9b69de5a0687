"""Device idle time in the traced window, ms a tick: the part between device
events under no `serve/tick` span: the loop that calls `engine.step()`. One
of five, each measured, whose sum is checked against `host_gap_ms.decode`
(`lib/host_accounts.idle_split`). Source: device_trace."""
from benchmark.lib.host_accounts import idle_part


def read(ctx):
    return idle_part(ctx, "caller")
