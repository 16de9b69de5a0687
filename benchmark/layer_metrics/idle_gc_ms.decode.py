"""Device idle time in the traced window, ms a tick: the part overlapped by a
`gc_pause` account: the collector ran. One of five, each measured, whose sum
is checked against `host_gap_ms.decode` (`lib/host_accounts.idle_split`).
Source: device_trace."""
from benchmark.lib.host_accounts import idle_part


def read(ctx):
    return idle_part(ctx, "gc")
