"""Device idle time per optimizer step inside the traced window: window
minus the union of device op intervals, over steps. Source: device_trace."""
from benchmark.lib.layer_common import idle_ms_per_unit as read  # noqa: F401
