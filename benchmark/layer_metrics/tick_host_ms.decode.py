"""Median over decode-only ticks of the `serve/tick` span less the
`serve/token_read` under it: what the host did itself in a tick.
Source: program_span."""
from benchmark.lib.program_spans import tick_host_ms as read  # noqa: F401
