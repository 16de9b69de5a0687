"""From a profiler trace (``.xplane.pb``) to numbers.

:func:`load` turns the trace into a plain structure (planes -> lines ->
``[name, start_ns, duration_ns]`` events) so that every reduction below is
ordinary Python over lists, testable on a small recorded trace kept as
JSON. Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per executed HLO op or kernel and ``XLA Modules`` one per
executed program. Busy time is the union of the op intervals."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


DETAIL_CHARS = 240
ATTRS = re.compile(r'custom_call_target="[^"]*"|kind=k\w+|calls=%[\w.\-]+')
OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9\-]*)\(")


def split_name(text: str) -> tuple:
    """A device event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...), kind=kOutput, calls=...``).
    Returns the short name (``fusion.12``) and a bounded detail string that
    kernel patterns are matched against: short name, opcode, the
    attributes that identify a kernel (``custom_call_target``, ``kind``,
    ``calls``), then the start of the operand list."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:DETAIL_CHARS], text[:DETAIL_CHARS]
    short = head.lstrip("%")
    cut = OPCODE.search(rest)
    opcode = cut.group(1) if cut else "?"
    operands = rest[cut.end():] if cut else rest
    attrs = " ".join(ATTRS.findall(rest))
    return short, f"{short} {opcode}( {attrs} | {operands}"[:DETAIL_CHARS]


def load(path: str, keep_host: str = "bench/", stats: bool = False) -> dict:
    """The trace as plain data. Device planes are kept whole; of the host
    planes only events whose name starts with ``keep_host`` (the
    benchmark's own spans) are kept. A device event is
    ``[short name, start_ns, duration_ns, detail]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                if is_dev:
                    short, detail = split_name(e.name)
                    if stats:
                        detail = (detail + " | " + " ".join(
                            f"{k}={v}" for k, v in e.stats))[:4 * DETAIL_CHARS]
                    events.append([short, float(e.start_ns),
                                   float(e.duration_ns), detail])
                elif e.name.startswith(keep_host):
                    events.append([e.name, float(e.start_ns),
                                   float(e.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    out = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(out, key=lambda p: int(DEVICE_PLANE.match(p["name"])[1]))


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_spans(trace: dict) -> list:
    """The benchmark's own spans, from every host line, sorted by start."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend(line["events"])
    return sorted(out, key=lambda e: e[1])


def merged(events) -> list:
    """The union of the events' intervals as sorted disjoint [lo, hi]."""
    out = []
    for lo, hi in sorted((e[1], e[1] + e[2]) for e in events):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def busy_ns(events) -> float:
    return sum(hi - lo for lo, hi in merged(events))


def device_busy_s(trace: dict) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    return sum(busy_ns(line_events(p, OPS_LINE)) for p in planes) \
        / len(planes) / 1e9


CONTAINER = re.compile(r"^\S+ (while|conditional|call)\(")  # detail


def is_container(event) -> bool:
    """Loops, branches and calls hold other ops of the same line inside
    their interval: they count towards busy time (a union) but are left
    out of per-op totals, which would count their children twice."""
    return len(event) > 3 and bool(CONTAINER.match(event[3]))


def op_totals(plane: dict, line_name: str = OPS_LINE) -> dict:
    """name -> [count, total seconds] on one device, containers left out."""
    out: dict = {}
    for event in line_events(plane, line_name):
        if is_container(event):
            continue
        name, dur = event[0], event[2]
        slot = out.setdefault(name, [0, 0.0])
        slot[0] += 1
        slot[1] += dur / 1e9
    return out


def matching_s(plane: dict, pattern: str, line_name: str = OPS_LINE) -> float:
    """Total seconds of the events whose name, opcode and attributes (the
    detail up to its operand list: an op that merely consumes a kernel's
    output names it among its operands) match ``pattern``."""
    rx = re.compile(pattern)
    return sum(e[2] for e in line_events(plane, line_name)
               if not is_container(e)
               and rx.search((e[3] if len(e) > 3 else e[0]).split(" | ")[0])
               ) / 1e9


def top_ops(trace: dict, n: int = 10) -> list:
    planes = device_planes(trace)
    if not planes:
        return []
    totals = op_totals(planes[0])
    ranked = sorted(totals.items(), key=lambda kv: -kv[1][1])[:n]
    return [[name, secs] for name, (_, secs) in ranked]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """The longest idle gaps of device 0, summed by what the host was
    doing: each gap is named after the benchmark span that covers most of
    it (``untracked`` where none does). Returns ``[name, seconds]``."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = merged(line_events(planes[0], OPS_LINE))
    spans = host_spans(trace)
    by_name: dict = {}
    for (_, prev_hi), (lo, _) in zip(busy, busy[1:]):
        gap = lo - prev_hi
        if gap <= 0:
            continue
        best, best_cover = "untracked", 0.0
        for name, s_lo, s_dur, *_ in spans:
            cover = min(lo, s_lo + s_dur) - max(prev_hi, s_lo)
            if cover > best_cover:
                best, best_cover = name, cover
        by_name[best] = by_name.get(best, 0.0) + gap / 1e9
    return [[k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def overlap_ns(events, cover) -> float:
    """How much of the events' union lies inside the union ``cover``
    (both as produced by :func:`merged`)."""
    total, j = 0.0, 0
    for lo, hi in events:
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < hi:
            total += min(hi, cover[k][1]) - max(lo, cover[k][0])
            k += 1
    return total


def family(name: str) -> str:
    """``fusion.422`` -> ``fusion``: ops of one kind under one name."""
    return re.sub(r"[.\d]+$", "", name)


def op_table(trace: dict) -> list:
    """Device 0's ops by family: ``[family, count, seconds, a sample
    detail]``, longest first. For looking at a trace by hand."""
    planes = device_planes(trace)
    if not planes:
        return []
    table: dict = {}
    for e in line_events(planes[0], OPS_LINE):
        if is_container(e):
            continue
        row = table.setdefault(family(e[0]), [0, 0.0, e[3]])
        row[0] += 1
        row[1] += e[2] / 1e9
    return sorted(([k] + v for k, v in table.items()), key=lambda r: -r[2])


def skeleton(trace: dict, per_line: int = 40) -> dict:
    """A small copy of the trace for looking at by hand (and for the test
    fixture): every plane and line, its first ``per_line`` events and the
    two longest of every family of the rest."""
    def some(events):
        best: dict = {}
        for e in events[per_line:]:
            rows = best.setdefault(family(e[0]), [])
            rows.append(e)
            rows.sort(key=lambda x: -x[2])
            del rows[2:]
        rest = [e for rows in best.values() for e in rows]
        return events[:per_line] + sorted(rest, key=lambda e: e[1])

    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "n_events": len(ln["events"]),
             "events": some(ln["events"])} for ln in p["lines"]]}
        for p in trace["planes"]]}
