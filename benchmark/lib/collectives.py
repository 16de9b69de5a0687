"""Finding the collective ops of a device plane by their HLO names."""

from __future__ import annotations

import re

from benchmark.lib import xplane

ASYNC_LINE = "Async XLA Ops"
PATTERN = re.compile(
    r"^(all-to-all|all-gather|all-reduce|collective-permute|reduce-scatter"
    r"|ragged-all-to-all)")


def is_collective(event) -> bool:
    return bool(PATTERN.match(event[0]))


def collective_intervals(plane: dict) -> list:
    events = [e for line in (xplane.OPS_LINE, ASYNC_LINE)
              for e in xplane.line_events(plane, line) if is_collective(e)]
    return xplane.merged(events)
