"""Device busy time per engine tick inside the traced window (decode
program, plus the prefills that ran in those ticks)."""
from benchmark.lib.layer_common import busy_ms_per_unit as read  # noqa: F401
