"""Device busy time per optimizer step inside the traced window."""
from benchmark.lib.layer_common import busy_ms_per_unit as read  # noqa: F401
