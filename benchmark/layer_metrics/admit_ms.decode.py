"""Mean self time of `serve/admit` per tick (its span less the
`serve/prefill` spans under it): admission's own table work.
Source: program_span."""
from benchmark.lib.program_spans import admit_self_ms as read  # noqa: F401
