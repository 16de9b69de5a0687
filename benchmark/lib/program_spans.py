"""What the per-layer readers take from the program itself: the run
journal's traced spans (``distributed_lion_tpu.train.journal.traced()``)
and the compile ledger (``utils.compile_cache.totals()``). Both are read
after the driver returns, in its process. A program without them (any
commit before the one that added them) gives ``None`` everywhere, and a
reader that gets ``None`` leaves its metric out of the line."""

from __future__ import annotations

import statistics


def window_spans(ctx):
    """The journal's records that lie wholly inside the traced window
    (``facts["trace"]`` ``t0``..``t1``, the same ``time.monotonic`` clock),
    or ``None`` when the program records none."""
    try:
        from distributed_lion_tpu.train import journal
    except ImportError:
        return None
    read = getattr(journal, "traced", None)
    window = ctx["facts"].get("trace") or {}
    if read is None or "t0" not in window or "t1" not in window:
        return None
    spans = [r for r in read()
             if window["t0"] <= r["t0"] and r["t1"] <= window["t1"]]
    return spans or None


def ms(span) -> float:
    return (span["t1"] - span["t0"]) * 1e3


def median_ms(ctx, name: str):
    """Median length of the window's spans called ``name``."""
    spans = window_spans(ctx)
    durs = [ms(r) for r in spans or () if r["name"] == name]
    return statistics.median(durs) if durs else None


def children_of(spans) -> dict:
    """``id -> [the spans whose parent it is]``."""
    out: dict = {}
    for r in spans:
        out.setdefault(r["parent"], []).append(r)
    return out


def under(span, kids: dict):
    """Every span below ``span`` in the tree."""
    stack = list(kids.get(span["id"], ()))
    while stack:
        r = stack.pop()
        yield r
        stack.extend(kids.get(r["id"], ()))


def tick_host_ms(ctx):
    """Median over decode-only ticks (a ``serve/tick`` with a
    ``serve/token_read`` and no ``serve/prefill`` under it) of the tick's
    span less the token reads under it: what the host did itself."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    kids = children_of(spans)
    own = []
    for tick in spans:
        if tick["name"] != "serve/tick":
            continue
        below = list(under(tick, kids))
        reads = [r for r in below if r["name"] == "serve/token_read"]
        if reads and not any(r["name"] == "serve/prefill" for r in below):
            own.append(ms(tick) - sum(ms(r) for r in reads))
    return statistics.median(own) if own else None


def admit_self_ms(ctx):
    """Mean over the window's ticks of ``serve/admit``'s self time: its
    span less the ``serve/prefill`` spans directly under it."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    kids = children_of(spans)
    own = [ms(a) - sum(ms(r) for r in kids.get(a["id"], ())
                       if r["name"] == "serve/prefill")
           for a in spans if a["name"] == "serve/admit"]
    return statistics.fmean(own) if own else None


def setup_compile_totals(ctx):
    """The compile ledger summed over what ended before the traced window
    opened (nothing compiles inside the window, so that is set-up; the
    reference's programs compile after it), or ``None`` when the program
    keeps no ledger or it holds nothing."""
    try:
        from distributed_lion_tpu.utils import compile_cache
    except ImportError:
        return None
    totals = getattr(compile_cache, "totals", None)
    t0 = (ctx["facts"].get("trace") or {}).get("t0")
    if totals is None or t0 is None:
        return None
    out = totals(until=t0)
    return out if out["compiles"] else None
