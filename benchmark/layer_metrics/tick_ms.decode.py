"""Median wall time of `engine.step()` on decode-only ticks, from the
benchmark's own span around the call."""
from benchmark.lib.layer_common import decode_only_tick_ms as read  # noqa: F401
