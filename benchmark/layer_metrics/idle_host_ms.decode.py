"""Device idle time in the traced window, ms a tick: the part under a
`serve/tick` span, outside its reads and any collection: the engine's own
work (admit, build, commit). One of five, each measured, whose sum is checked
against `host_gap_ms.decode` (`lib/host_accounts.idle_split`). Source:
device_trace."""
from benchmark.lib.host_accounts import idle_part


def read(ctx):
    return idle_part(ctx, "host")
