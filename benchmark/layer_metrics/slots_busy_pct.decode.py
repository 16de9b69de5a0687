"""Active slots over max_seqs, mean over the window's ticks (a count)."""
from benchmark.lib.layer_common import slots_busy_pct as read  # noqa: F401
