"""Bytes of the hyper-connection mix over the residual stream, computed from
shapes and from the engine's own counter (what the algorithm needs in two
passes, whatever implements it: not what a particular program does), and
the mix's kernels found in a trace by name and by the program they ran in."""

from __future__ import annotations

import re

from benchmark.lib import xplane

# the kernels by name in the trace (ops/pallas_mhc)
MHC_KERNELS = r"mhc_pre|mhc_post"
# the engine's decode program among the trace's executed programs
DECODE_MODULE = r"^jit_decode_tick\b"


def mhc_bytes(rows: int, n: int, d: int, itemsize: int = 2) -> int:
    """Least HBM bytes of ``rows`` (row, sublayer) pairs through the mix
    (the engine's ``mhc_rows``): the pre-mix reads a row's ``n`` streams of
    ``d`` and writes the sublayer's input (``d``); the write-back reads the
    ``n`` streams and the sublayer's output and writes the ``n`` streams:
    ``(3 n + 2) d`` values a pair, 100,352 B at 4 x 3,584 in bfloat16. The
    coefficients between the two (``n n + 2 n`` float32 a pair, written and
    read: 192 B, 0.2%) and ``phi`` (once a call) are not counted."""
    return rows * (3 * n + 2) * d * itemsize


def kernel_s_in(plane: dict, kernels: str, module: str):
    """(seconds of the ops matching ``kernels`` that started inside an
    executed program whose name matches ``module``, how many such programs
    ran), or None where the trace names no program."""
    runs = [(e[1], e[1] + e[2])
            for e in xplane.line_events(plane, xplane.MODULES_LINE)
            if re.search(module, e[0])]
    if not runs:
        return None
    runs.sort()
    rx, total, at = re.compile(kernels), 0.0, 0
    events = sorted((e for e in xplane.line_events(plane, xplane.OPS_LINE)
                     if not xplane.is_container(e) and rx.search(
                         (e[3] if len(e) > 3 else e[0]).split(" | ")[0])),
                    key=lambda e: e[1])
    for e in events:
        while at < len(runs) and runs[at][1] <= e[1]:
            at += 1
        if at < len(runs) and runs[at][0] <= e[1]:
            total += e[2]
    return total / 1e9, len(runs)
