"""Host time that no span holds, read from the program's always-on record.

The program keeps, whether or not anything listens, a list of accounts
(``distributed_lion_tpu.train.journal.accounts()``: ``setup_lap``,
``gc_pause`` and ``slow_tick`` records on ``time.monotonic``) and, in
``engine.stats``, what the host waited in its reads, what the collector
took and what slow ticks cost. The readers here turn them into per-layer
numbers. A program without them (any commit before the one that added
them) gives ``None`` everywhere, and a reader that gets ``None`` leaves
its metric out of the line.

**Two clocks.** The device's events and the ``bench/step`` annotations of
``ctx["trace"]`` are on the profiler session's clock (nanoseconds); the
accounts, ``journal.traced()`` and ``facts["ticks"]`` (the same
``bench/step`` extents, stamped by the driver) are on ``time.monotonic``
(seconds). :func:`clock_offset` lays one over the other from those pairs.
"""

from __future__ import annotations

import statistics

from benchmark.lib import layer_common, program_spans, xplane

PAIR_REL, PAIR_ABS_S = 0.05, 50e-6   # how closely a pair's lengths agree
SUM_REL, SUM_ABS_MS = 0.02, 0.01     # and the five parts' sum the window's
OUTSIDE_TICKS = 0.5   # device time outside the stamped window, in ticks
IDLE_PARTS = ("gc", "read", "host", "caller", "edge")


def _journal():
    """The program's journal, if it keeps accounts."""
    try:
        from distributed_lion_tpu.train import journal
    except ImportError:
        return None
    return journal if hasattr(journal, "accounts") else None


def accounts(kind: str, since=None, until=None):
    """The program's accounts of ``kind`` that lie inside ``since``..
    ``until`` (``time.monotonic``), or ``None`` where it keeps none."""
    journal = _journal()
    return None if journal is None else journal.accounts(kind, since, until)


def seconds(records) -> float:
    return sum(r["t1"] - r["t0"] for r in records)


def stats_between(ctx, name: str, lo: str = "open", hi: str = "close"):
    """``engine.stats[name]`` between two of the copies the serving driver
    takes (``open``, ``trace_open``, ``trace_close``, ``close``), or
    ``None`` where a copy is missing or the program has no such counter."""
    stats = ctx["facts"].get("engine_stats") or {}
    if lo not in stats or hi not in stats or name not in stats[hi]:
        return None
    return stats[hi][name] - stats[lo].get(name, 0)


def per_tick_ms(ctx, name: str):
    """Seconds counted in ``engine.stats[name]`` between the window's
    ``open`` and ``close`` copies, in ms a tick of that stretch (its length
    differs from run to run: a traced run's holds the tracer's own stall)."""
    total, ticks = (stats_between(ctx, k) for k in (name, "ticks"))
    return total * 1e3 / ticks if total is not None and ticks else None


# ------------------------------------------------------------ the two clocks
def _apart(events, ticks):
    """How far the pairs' lengths lie apart in all (seconds), or ``None``
    where a pair's differ by more than ``PAIR_REL`` (or ``PAIR_ABS_S``)."""
    apart = [abs(e[2] / 1e9 - (t["t1"] - t["t0"]))
             for e, t in zip(events, ticks)]
    ok = len(events) == len(ticks) and all(
        d <= max(PAIR_REL * (t["t1"] - t["t0"]), PAIR_ABS_S)
        for d, t in zip(apart, ticks))
    return sum(apart) if ok else None


def clock_offset(ctx):
    """Seconds to add to a ``time.monotonic`` reading to land on the trace's
    clock: the median, over the traced window's ticks, of a ``bench/step``
    event's start less the driver's stamp before the same step. The events
    and ``layer_common.traced_ticks`` are paired in order; they have to be
    as many (or one more on either side, a partial one at an edge, which
    is dropped: of the two ways to drop it, the one whose lengths lie
    closer) and every pair's lengths have to agree within ``PAIR_REL`` (or
    ``PAIR_ABS_S``). Where they do not pair: ``None``, and nothing is
    guessed."""
    cache = ctx.setdefault("_host_accounts", {})
    if "offset" not in cache:
        cache["offset"] = _clock_offset(ctx)
    return cache["offset"]


def _clock_offset(ctx):
    if "t0" not in (ctx["facts"].get("trace") or {}):
        return None
    events = [e for e in xplane.host_spans(ctx["trace"])
              if e[0] == "bench/step"]
    ticks = sorted(layer_common.traced_ticks(ctx), key=lambda t: t["t0"])
    if not events or not ticks:
        return None
    tries = [(events, ticks)]
    if len(events) == len(ticks) + 1:
        tries = [(events[1:], ticks), (events[:-1], ticks)]
    elif len(ticks) == len(events) + 1:
        tries = [(events, ticks[1:]), (events, ticks[:-1])]
    fits = [(apart, i) for i, (ev, tk) in enumerate(tries)
            if (apart := _apart(ev, tk)) is not None]
    if not fits:
        return None
    ev, tk = tries[min(fits)[1]]
    return statistics.median(e[1] / 1e9 - t["t0"] for e, t in zip(ev, tk))


# ------------------------------------------------------------ intervals (ns)
def _total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def _minus(intervals, cover) -> list:
    """What of the disjoint sorted ``intervals`` lies outside the union
    ``cover`` (both as ``xplane.merged`` gives them)."""
    out, j = [], 0
    for lo, hi in intervals:
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k, at = j, lo
        while k < len(cover) and cover[k][0] < hi:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < hi:
            out.append([at, hi])
    return out


def _on_trace_clock(records, offset: float) -> list:
    """``[t0, t1]`` records of ``time.monotonic`` as a merged union of
    nanosecond intervals on the trace's clock."""
    return xplane.merged([None, (r["t0"] + offset) * 1e9,
                          (r["t1"] - r["t0"]) * 1e9] for r in records)


def idle_split(ctx):
    """Device 0's idle time in the traced window, in ms a tick, every
    nanosecond of it in exactly one of :data:`IDLE_PARTS`:

    - ``gc``: a gap between device events, overlapped by a ``gc_pause``
      account;
    - ``read``: else by a ``serve/token_read`` span: the device idle WHILE
      the host waits for it (a transfer or the runtime, not host work);
    - ``host``: else under a ``serve/tick`` span: the engine's own work
      (admit, build, commit);
    - ``caller``: else (between device events, under no ``serve/tick``):
      the loop that calls ``engine.step()``;
    - ``edge``: the window before the first and after the last device
      event, measured like the others on the trace's clock.

    Every part is measured; none is a remainder. Their sum is device 0's
    idle time inside the window as laid on the trace's clock;
    ``host_gap_ms.decode``'s own arithmetic (``layer_common.
    idle_ms_per_unit``) is the stamped ``window_s`` less the planes' busy
    time WHEREVER it lies, so it reads lower by the device time outside
    the window: the closing stamp is taken before ``stop_trace`` is
    called, and the events run 0.1-1.2 ms past it. Two checks, and all
    five are left out where one fails: that device time outside is under
    ``OUTSIDE_TICKS`` of a median tick (clocks paired a tick off put a
    whole tick there), and with it taken off the sum meets the window's
    arithmetic within ``SUM_REL`` (or ``SUM_ABS_MS`` a tick: device 0 is
    the planes' average and no gap was counted twice).

    ``None`` also where the program keeps no accounts, no trace closed,
    the clocks do not pair or the traced buffer pushed spans out
    (``journal.traced_dropped()``)."""
    cache = ctx.setdefault("_host_accounts", {})
    if "idle" not in cache:
        cache["idle"] = _idle_split(ctx)
    return cache["idle"]


def _idle_split(ctx):
    journal, offset = _journal(), clock_offset(ctx)
    total_ms = layer_common.idle_ms_per_unit(ctx)
    if journal is None or offset is None or total_ms is None \
            or journal.traced_dropped():     # spans of the window are gone
        return None
    window = ctx["facts"]["trace"]
    w0, w1 = ((window[k] + offset) * 1e9 for k in ("t0", "t1"))
    busy = xplane.merged(xplane.line_events(layer_common.device0(ctx),
                                            xplane.OPS_LINE))
    if not busy:
        return None
    gaps = [[max(a_hi, w0), min(b_lo, w1)]
            for (_, a_hi), (b_lo, _) in zip(busy, busy[1:])]
    gaps = [g for g in gaps if g[0] < g[1]]
    first, last = max(w0, min(busy[0][0], w1)), min(w1, max(busy[-1][1], w0))
    spans = journal.traced()
    parts, left = {}, gaps
    for part, records in (
            ("gc", journal.accounts("gc_pause")),
            ("read", [r for r in spans if r["name"] == "serve/token_read"]),
            ("host", [r for r in spans if r["name"] == "serve/tick"])):
        rest = _minus(left, _on_trace_clock(records, offset))
        parts[part] = _total(left) - _total(rest)
        left = rest
    parts["caller"] = _total(left)
    parts["edge"] = (first - w0) + (w1 - last)
    n = layer_common.units(ctx)
    out = {k: v / 1e6 / n for k, v in parts.items()}
    outside = _total(_minus(busy, [[w0, w1]]))
    tick_ns = 1e9 * statistics.median(
        t["t1"] - t["t0"] for t in layer_common.traced_ticks(ctx))
    apart = abs(sum(out.values()) - outside / 1e6 / n - total_ms)
    ok = outside <= OUTSIDE_TICKS * tick_ns \
        and apart <= max(SUM_REL * total_ms, SUM_ABS_MS)
    return out if ok else None


def idle_part(ctx, part: str):
    split = idle_split(ctx)
    return None if split is None else split[part]


# ------------------------------------------------------------------- set-up
def window_open(ctx):
    """When set-up ended, on ``time.monotonic``. Serving: the driver's
    ``facts["t_open"]``. Training: the driver keeps no such stamp, so the
    trace's ``t0`` less the timed steps that ran before it
    (``train_clm.TRACE_AFTER`` steps of the traced window's mean length);
    the profiler's own start lies in between and is not taken off."""
    facts = ctx["facts"]
    if "t_open" in facts:
        return facts["t_open"]
    trace = facts.get("trace") or {}
    if "t0" not in trace or not trace.get("steps"):
        return None
    from benchmark.drivers.train_clm import TRACE_AFTER

    return trace["t0"] - TRACE_AFTER * trace["window_s"] / trace["steps"]


def setup_parts(ctx):
    """``setup_s`` taken apart (seconds): ``construct`` (the ``setup_lap``
    accounts of the cell's trainer or engine that ended before the trace's
    ``t0``, ``setup/before`` left out), ``trace_lower`` and ``load`` (the
    compile ledger's tracing and lowering, and its compile-or-load, of the
    PROGRAM's dispatches until the trace's ``t0``: without the reference's
    programs, which the harness's own ``compile_s`` counts), ``gc`` (the
    ``gc_pause`` accounts that ended before the window opened; they lie
    INSIDE the other parts, not beside them) and ``unplaced`` (``setup_s``
    less construct, trace_lower and load: imports, the backend's start, the
    benchmark's own weights and warm-up). ``None`` where the program keeps
    no accounts or no ledger, or its bounded list has pushed accounts out
    (``journal.accounts_dropped()``)."""
    t0 = (ctx["facts"].get("trace") or {}).get("t0")
    laps = None if t0 is None else accounts("setup_lap", until=t0)
    ledger = program_spans.setup_compile_totals(ctx)
    if not laps or ledger is None or _journal().accounts_dropped():
        return None       # the oldest accounts are set-up's: none may be gone
    out = {"construct": seconds(r for r in laps
                                if r["name"] != "setup/before"),
           "trace_lower": ledger["trace_lower_s"],
           "load": ledger["compile_s"]}
    out["unplaced"] = ctx["facts"]["end_to_end"]["setup_s"] \
        - sum(out.values())
    opened = window_open(ctx)
    out["gc"] = None if opened is None else seconds(
        accounts("gc_pause", until=opened))
    return out


def setup_part(ctx, part: str):
    parts = setup_parts(ctx)
    return None if parts is None else parts[part]
