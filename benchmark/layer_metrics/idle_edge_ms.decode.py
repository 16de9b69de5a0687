"""Device idle time in the traced window, ms a tick: the part before the first
and after the last device event of the trace, measured on the trace's clock
(a device trace shorter than its window reads here). One of five whose sum is
checked against `host_gap_ms.decode` (`lib/host_accounts.idle_split`).
Source: device_trace."""
from benchmark.lib.host_accounts import idle_part


def read(ctx):
    return idle_part(ctx, "edge")
