"""How late the load generator ran: 95th percentile of actual submit time
minus due time, over the requests due in the window."""
from benchmark.lib import ticklog


def read(ctx):
    late = ctx["facts"].get("late_ms") or []
    return ticklog.percentile(late, 95) if late else None
