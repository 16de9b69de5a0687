"""Device idle time per engine tick inside the traced window."""
from benchmark.lib.layer_common import idle_ms_per_unit as read  # noqa: F401
