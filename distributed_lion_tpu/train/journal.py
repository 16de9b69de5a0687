"""Run journal: structured span/event tracing for the training control plane.

The reference has no profiling at all (PAPER/SURVEY §5) and our own
observability stopped at vote *semantics* (train/telemetry.py): nothing
explained where the wall clock goes, which is exactly what blocks the
ROADMAP-1 MFU push (37.4% measured, no attribution of the missing 60%) and
the ROADMAP-2 control plane (27 ad-hoc ``print()`` calls are not a
consumable event stream). This module is the recording half; the offline
half — multi-host merge, clock-skew correction, step-time attribution —
is ``cli/run_analyze.py`` (stdlib-only, loadable by file path like
``train/resilience``'s manifest verifier).

Design constraints, in order:

- **Zero step-side host syncs.** Every span is HOST wall time around a
  host-side region (``time.monotonic`` — immune to NTP slews); device time
  is never polled per step. The one device sync the journal relies on is
  the trainer's existing log-cadence drain (the host-float of the metrics
  pytree), which the trainer wraps in the ``device_wait`` span — so the
  journal's device-time estimate costs nothing the loop wasn't already
  paying.
- **Strict-JSON JSONL sink with atomic rotation.** One record per line,
  ``allow_nan=False`` (the MetricsLogger contract,
  scripts/validate_metrics.py validates journals too), newline-terminated
  records as the durability unit: a crash mid-write tears at most the last
  line, and re-opening the file truncates the torn tail back to the last
  complete record (the torn record was never durable — same atomicity
  story as the checkpoint commit marker). Rotation renames the live file
  to ``journal_rank<r>.<seq>.jsonl`` via ``os.replace`` and re-anchors a
  fresh meta record, so every file is self-describing for the analyzer.
- **Bounded memory.** A ring buffer (``deque(maxlen)``) keeps the last N
  records in memory for crash bundles (``journal_tail.jsonl``) — an
  anomaly carries its own timeline without re-reading the sink.
- **A sink failure must not take down training.** The first OSError from
  the file sink disables it LOUDLY (stderr); recording continues into the
  ring. The ``journal_torn_write`` fault (train/resilience registry) tears
  a write mid-line to prove the recovery path.

Record schema (validated by scripts/validate_metrics.py):

- every record: ``kind`` (meta | span | event | log), ``name``, ``t``
  (monotonic seconds, this process's clock), ``rank`` (process index).
- ``meta``/``journal_start``: adds ``wall`` (``time.time()`` at the same
  instant as ``t``) — the anchor the analyzer uses to map each rank's
  monotonic clock onto one wall timeline (skew correction).
- ``span``: adds ``dur`` (seconds). A span stamped with a ``thread`` field
  (``"committer"`` for the checkpoint commit thread, ``"dcn-link"`` for
  the emulated DCN link's residual waits) ran off the step thread and is
  excluded from step-wall attribution (it overlaps compute by design).
- free-form extra fields must be JSON scalars; non-finite floats are
  serialized as ``null`` with the repr under ``<k>_repr``.

- ``span`` records opened through :func:`span` also carry ``id`` (unique
  in the process) and ``parent`` (the ``id`` of the innermost span open on
  the same thread, or ``null``): the span tree, so a reader can take a
  parent's self time (its span less its children's).

Span taxonomy (the name's head — before any ``/`` — is the attribution
bucket): ``data_wait`` (batch fetch + host→device put), ``dispatch`` (the
jitted-step call), ``device_wait`` (the log-cadence device drain — the
loop's direct view of device-bound time), ``logging_drain`` (metric
assembly + telemetry drain + JSONL write), ``ckpt/*`` (checkpoint
serialize/drain on the step thread; committer-thread spans carry
``thread="committer"``), and the loop's own host work between them
(``retrace_check``, ``guard_apply``, ``sentinel_check``, ``membership``).
The serving engine's tree hangs under ``serve/tick`` (serve/engine.py's
module doc lists it); ``setup/*`` records time construction and ``gc/*``
the collector's pauses (both accounts, below: kept always, not gated).
Everything else lands in the analyzer's ``other`` bucket.

**One span primitive, gated by what is listening** (:func:`span`). Hot
paths open every span through the module-level ``journal.span(name,
**ids)``. With nothing listening — no profiler session and no installed
journal — it is one check and the shared null span. With a listener the
span (a) opens a ``jax.profiler.TraceAnnotation`` of the same extent, so
it lies in the profiler's ``.xplane.pb`` on the device trace's clock;
(b) appends ``{name, t0, t1, id, parent, **ids}`` (``time.monotonic``
seconds) to a process-global bounded buffer read by :func:`traced`, which
outlives the trainer and the engine and starts empty at each profiler
session; (c) goes to the installed journal in the schema above. The
profiler is reached through a hook (:func:`register_profiler`) that the
Trainer and the serving engine register, so this module still imports
without jax. Trace times are relative to the session's start, so the
first span of a session also emits a ``journal/clock`` annotation whose
``monotonic_ns`` lays journal files and buffer records over the trace.
The gate is read when a span opens, and only then: a span open when a
session starts is not in the buffer, one open when it ends is recorded
whole, and a session is seen to have ended by the first span that opens
after it (the loops open spans every step and tick).

**Accounts: host time that is nobody's span, kept always** (:func:`account`,
:func:`accounts`). The spans above are gated, and every end-to-end number
is decided by runs in which nothing listens, so what a run needs in order
to explain itself afterwards is recorded whether or not anything listens:
a process-global bounded list of ``{kind, name, t0, t1, **fields}``
(``time.monotonic`` seconds) that outlives the trainer and the engine,
is NOT emptied when a profiler session begins (it spans set-up and the
whole run) and counts what it pushes out (:func:`accounts_dropped`). An
account also goes to the installed journal as a span record (``dur``,
``account=<kind>``; a ``gc_pause`` or a ``slow_tick`` lies inside the span
it interrupted, so the analyzer leaves it out of the step wall's sum).
Three kinds, each with one producer:

- ``setup_lap``: :class:`SetupLaps` (the trainer's and the engine's
  construction, lap by lap, with ``owner``; before the first lap of a
  process one ``setup/before`` from this module's import to the laps'
  start: the imports after it, the backend's start, the caller's work).
- ``gc_pause``: :func:`watch_gc`, one ``gc.callbacks`` hook. Every
  collection is added to two running totals, count and seconds
  (:func:`gc_totals`); a generation-2 collection, or any pause of
  :data:`GC_LISTED_S` or more, is listed (``generation``, ``collected``).
- ``slow_tick``: the serving engine, a decode-only tick that took several
  times the median of the ticks before it (serve/engine.py's module doc).

What is always on, then: the accounts, ``SetupLaps``' clock, the
collector's hook, the compile ledger (utils/compile_cache) and the
engine's per-tick stamps. What is gated: every :func:`span`, its profiler
annotation and :func:`traced`.

Layering: stdlib + ``train.resilience`` (itself pure stdlib) only — no
jax, no numpy — so host-side consumers (``train/vote_guard``,
``data/native_loader``) stay importable without jax and the module can be
loaded by file path.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import math
import os
import sys
import threading
import time
from typing import Any, Optional

from distributed_lion_tpu.train import resilience

SCHEMA_VERSION = 1
KINDS = ("meta", "span", "event", "log")
DEFAULT_MAX_BYTES = 32 << 20  # rotate the sink at 32 MiB per file
DEFAULT_RING = 512


def journal_filename(rank: int) -> str:
    return f"journal_rank{rank}.jsonl"


def _safe_fields(fields: dict) -> dict:
    """Strict-JSON view of free-form record fields: non-finite floats become
    ``null`` + ``<k>_repr`` (the MetricsLogger convention); non-scalar
    values are repr'd rather than risking a non-serializable record.
    One-level dicts of scalars flatten to dotted keys (``stats.ticks``) —
    the serve metrics drain emits grouped counters and a nested object
    would otherwise collapse to an unqueryable repr string; deeper
    nesting still falls through to repr."""
    out: dict = {}
    for k, v in fields.items():
        if isinstance(v, float) and not math.isfinite(v):
            out[k] = None
            out[f"{k}_repr"] = repr(v)
        elif v is None or isinstance(v, (str, int, float, bool)):
            out[k] = v
        elif isinstance(v, (list, tuple)) and all(
                e is None or isinstance(e, (str, int, bool))
                or (isinstance(e, float) and math.isfinite(e))
                for e in v):
            # flat scalar lists are valid strict JSON and survive as data
            # (the control plane's mask_before/mask_after fields); anything
            # nested or non-finite still falls through to repr
            out[k] = list(v)
        elif isinstance(v, dict) and all(
                isinstance(kk, str) and (
                    e is None or isinstance(e, (str, int, bool))
                    or (isinstance(e, float) and math.isfinite(e)))
                for kk, e in v.items()):
            for kk, e in v.items():
                out[f"{k}.{kk}"] = e
        else:
            out[k] = repr(v)
    return out


TRACED_MAX = 65536   # records the traced buffer holds before it drops

_IDS = itertools.count(1)          # span ids, unique in the process
_OPEN = threading.local()          # .stack: ids of the spans open here
_BUF_LOCK = threading.Lock()
_TRACED: collections.deque = collections.deque(maxlen=TRACED_MAX)
_dropped = 0


class _Span:
    """Context manager for one span (see the module doc's span primitive):
    the profiler annotation, the buffer record and the journal record all
    cover the same extent. Exceptions propagate; the span still records,
    flagged ``error=True``, so a failing region is visible in the
    timeline."""

    __slots__ = ("_name", "_fields", "_sink", "_buffered", "_t0", "_id",
                 "_parent", "_note")

    def __init__(self, name: str, fields: dict, sink, buffered: bool):
        self._name = name
        self._fields = fields
        self._sink = sink          # a Journal, or None
        self._buffered = buffered  # also goes to the traced buffer

    def __enter__(self) -> "_Span":
        stack = _OPEN.__dict__.setdefault("stack", [])
        self._parent = stack[-1] if stack else None
        self._id = next(_IDS)
        stack.append(self._id)
        self._note = None
        if _ANNOTATION is not None:
            # a TraceMe outside a session costs well under a microsecond
            self._note = _ANNOTATION(self._name, **{
                k: v for k, v in self._fields.items()
                if isinstance(v, (str, int, float, bool))})
            self._note.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **fields) -> None:
        """Attach fields computed INSIDE the span; recorded at exit."""
        self._fields.update(fields)

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.monotonic()
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        stack = _OPEN.stack
        if stack and stack[-1] == self._id:
            stack.pop()
        elif self._id in stack:     # exits out of order: keep the rest
            stack.remove(self._id)
        fields = self._fields
        if exc_type is not None:
            fields = {**fields, "error": True}
        if self._buffered:
            _record_traced({"name": self._name, "t0": self._t0, "t1": t1,
                            "id": self._id, "parent": self._parent,
                            **fields})
        if self._sink is not None:
            self._sink.record({"kind": "span", "name": self._name,
                               "dur": round(t1 - self._t0, 9),
                               "id": self._id, "parent": self._parent,
                               **fields})
        return False


def _record_traced(rec: dict) -> None:
    global _dropped
    with _BUF_LOCK:
        if len(_TRACED) == TRACED_MAX:
            _dropped += 1
        _TRACED.append(rec)


# ------------------------------------------------------------- the accounts
T_IMPORT = time.monotonic()   # where ``setup/before`` starts
ACCOUNT_KINDS = ("setup_lap", "gc_pause", "slow_tick")
ACCOUNTS_MAX = 4096           # accounts kept before the oldest drop out
GC_LISTED_S = 1e-3            # a younger collection this long is listed

# an RLock: the collector's hook runs wherever an allocation lands, also
# inside account() itself on the same thread
_ACC_LOCK = threading.RLock()
_ACCOUNTS: collections.deque = collections.deque(maxlen=ACCOUNTS_MAX)
_accounts_dropped = 0


def account(kind: str, name: str, t0: float, t1: float, **fields) -> dict:
    """Keep ``{kind, name, t0, t1, **fields}`` (module doc: always, with or
    without a listener) and send the installed journal its span record.
    Returns the kept record: its producer may fill a field in later (the
    engine's ``next_read_wait_ms``)."""
    global _accounts_dropped
    if kind not in ACCOUNT_KINDS:
        raise ValueError(f"unknown account kind {kind!r} "
                         f"(one of {', '.join(ACCOUNT_KINDS)})")
    rec = {"kind": kind, "name": name, "t0": t0, "t1": t1, **fields}
    with _ACC_LOCK:
        _accounts_dropped += len(_ACCOUNTS) == ACCOUNTS_MAX
        _ACCOUNTS.append(rec)
    if _ACTIVE is not None:
        _ACTIVE.record({"kind": "span", "name": name,
                        "dur": round(max(t1 - t0, 0.0), 9),
                        "id": next(_IDS), "parent": None,
                        "account": kind, **fields})
    return rec


def accounts(kind: Optional[str] = None, since: Optional[float] = None,
             until: Optional[float] = None) -> list:
    """The accounts kept, oldest first: of ``kind`` alone, and those that
    lie wholly inside ``since``..``until`` (``time.monotonic`` seconds;
    either end may be left open)."""
    with _ACC_LOCK:
        kept = list(_ACCOUNTS)
    return [r for r in kept
            if (kind is None or r["kind"] == kind)
            and (since is None or r["t0"] >= since)
            and (until is None or r["t1"] <= until)]


def accounts_dropped() -> int:
    """Accounts pushed out of the bounded list since the process began."""
    return _accounts_dropped


_GC = [0, 0.0]                 # collections and their seconds so far
_gc_t0: Optional[float] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.monotonic()
        return
    t0, _gc_t0 = _gc_t0, None
    if t0 is None:          # hooked in the middle of a collection
        return
    t1 = time.monotonic()
    _GC[0] += 1
    _GC[1] += t1 - t0
    gen = info["generation"]
    if gen == 2 or t1 - t0 >= GC_LISTED_S:
        account("gc_pause", f"gc/gen{gen}", t0, t1, generation=gen,
                collected=info["collected"])


def watch_gc() -> None:
    """Hook the collector (``gc.callbacks``), once: every collection from
    here on is in :func:`gc_totals`, the long ones among :func:`accounts`."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_totals() -> tuple:
    """``(collections, seconds)`` of the watched collections so far, every
    generation together: what a loop reads at both ends of a stretch. The
    generations are told apart where it matters, in the ``gc_pause``
    accounts (every full collection is listed)."""
    return _GC[0], _GC[1]


class Journal:
    """Thread-safe, rank-stamped span/event recorder (see module doc).

    ``directory=None`` runs ring-only (no file sink) — bench harnesses use
    this to compute an attribution summary without touching disk.
    """

    def __init__(self, directory: Optional[str], rank: int = 0, *,
                 max_bytes: int = DEFAULT_MAX_BYTES, ring: int = DEFAULT_RING):
        self.rank = int(rank)
        self.directory = str(directory) if directory else None
        self.max_bytes = int(max_bytes)
        # RLock, not Lock: rotation runs inside record()'s critical section
        # and re-enters record() to anchor the fresh file's meta record
        self._lock = threading.RLock()
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._fh = None
        self._bytes = 0
        self._rotations = 0
        self._sink_failed = False
        if self.directory:
            os.makedirs(self.directory, exist_ok=True)
            self._rotations = self._next_rotation_seq()
            self._open_sink()
        self._write_meta()

    # ------------------------------------------------------------------ sink
    def _path(self) -> str:
        return os.path.join(self.directory, journal_filename(self.rank))

    def _next_rotation_seq(self) -> int:
        stem = journal_filename(self.rank)[:-len(".jsonl")]
        seqs = [0]
        try:
            for name in os.listdir(self.directory):
                if name.startswith(stem + ".") and name.endswith(".jsonl"):
                    mid = name[len(stem) + 1:-len(".jsonl")]
                    if mid.isdigit():
                        seqs.append(int(mid) + 1)
        except OSError:
            pass
        return max(seqs)

    def _open_sink(self) -> None:
        """Open (or re-open) the live file, truncating a torn tail left by
        a crash mid-write: newline-terminated records are the durability
        unit, so everything after the last newline was never committed."""
        path = self._path()
        recovered = 0
        if os.path.exists(path):
            with open(path, "rb") as f:
                raw = f.read()
            if raw and not raw.endswith(b"\n"):
                keep = raw.rfind(b"\n") + 1  # 0 when no newline at all
                recovered = len(raw) - keep
                with open(path, "r+b") as f:
                    f.truncate(keep)
        self._fh = open(path, "a", encoding="utf-8")
        self._bytes = os.path.getsize(path)
        if recovered:
            self.event("journal_recovered", torn_bytes=recovered)

    def _rotate(self) -> None:
        """Atomic rotation: flush + close the live file, ``os.replace`` it
        to its sequence name, open a fresh live file and re-anchor a meta
        record so the new file is independently analyzable."""
        self._fh.flush()
        self._fh.close()
        stem = journal_filename(self.rank)[:-len(".jsonl")]
        os.replace(self._path(), os.path.join(
            self.directory, f"{stem}.{self._rotations}.jsonl"))
        self._rotations += 1
        self._fh = open(self._path(), "a", encoding="utf-8")
        self._bytes = 0
        self._write_meta(rotated=self._rotations)

    def _write_meta(self, **extra) -> None:
        self.record({"kind": "meta", "name": "journal_start",
                     "wall": time.time(), "pid": os.getpid(),
                     "version": SCHEMA_VERSION, **extra})

    # ------------------------------------------------------------- recording
    def record(self, rec: dict) -> None:
        """Append one record (``t``/``rank`` stamped here). Sink I/O errors
        disable the file sink loudly; the ring keeps recording."""
        rec = {"kind": rec.get("kind", "event"),
               "name": str(rec.get("name", "")),
               "t": round(time.monotonic(), 9), "rank": self.rank,
               **_safe_fields({k: v for k, v in rec.items()
                               if k not in ("kind", "name")})}
        with self._lock:
            self._ring.append(rec)
            if self._fh is None or self._sink_failed:
                return
            line = json.dumps(rec, allow_nan=False)
            try:
                if resilience.consume_fault_count("journal_torn_write"):
                    # simulated death mid-write: half the record, no
                    # newline, then the failure surfaces like real I/O
                    self._fh.write(line[:max(len(line) // 2, 1)])
                    self._fh.flush()
                    raise OSError("injected torn journal write")
                self._fh.write(line + "\n")
                self._bytes += len(line) + 1
            except OSError as e:
                self._sink_failed = True
                print(f"[journal] sink write failed ({e}); journal file "
                      "DISABLED for the rest of this run — the in-memory "
                      "ring keeps recording", file=sys.stderr, flush=True)
                return
            if self._bytes >= self.max_bytes:
                try:
                    self._rotate()
                except OSError as e:
                    self._sink_failed = True
                    print(f"[journal] rotation failed ({e}); journal file "
                          "DISABLED for the rest of this run",
                          file=sys.stderr, flush=True)

    def event(self, name: str, **fields) -> None:
        self.record({"kind": "event", "name": name, **fields})

    def span(self, name: str, **fields) -> _Span:
        """``with jr.span("ckpt/digest", step=n): ...`` — a span that goes
        to THIS journal whatever is installed (off-thread recorders and
        ring-only bench journals hold their own); hot paths use the
        module-level :func:`span`."""
        return _Span(name, fields, self, False)

    def log(self, msg: str, stream: str = "stdout") -> None:
        self.record({"kind": "log", "name": "log", "msg": str(msg),
                     "stream": stream})

    # -------------------------------------------------------------- plumbing
    def tail(self) -> list:
        """The ring buffer's records, oldest first — the crash bundle's
        ``journal_tail.jsonl`` payload."""
        with self._lock:
            return list(self._ring)

    def records(self) -> list:
        """Alias of :meth:`tail` for ring-only journals (bench harnesses
        feed this straight to ``run_analyze.attribute``)."""
        return self.tail()

    def flush(self) -> None:
        with self._lock:
            if self._fh is not None and not self._sink_failed:
                self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.flush()
                    self._fh.close()
                except OSError:
                    pass  # a dead sink at teardown has already been
                    # reported by the write path; close must not mask the
                    # run's real exit status  # graft: disable=DLT006
                self._fh = None


class _NullJournal:
    """No-op stand-in with the full :class:`Journal` surface, so call sites
    never branch on whether journaling is enabled."""

    rank = 0
    directory = None

    def record(self, rec: dict) -> None:
        pass

    def event(self, name: str, **fields) -> None:
        pass

    def span(self, name: str, **fields) -> "_NullSpan":
        return _NULL_SPAN

    def log(self, msg: str, stream: str = "stdout") -> None:
        pass

    def tail(self) -> list:
        return []

    records = tail

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class _NullSpan:
    def __enter__(self):
        return self

    def set(self, **fields):
        pass

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
NULL = _NullJournal()

# ---------------------------------------------------------------- the emitter
# The ONE stdout/stderr emitter for train/ and data/ modules (graft-check
# DLT009 pins this: a bare print() there bypasses the journal, so the
# control plane loses the event). Messages mirror to the console exactly as
# before AND land in the active journal as `log` records.
_ACTIVE: Optional[Journal] = None


def install(journal: Journal) -> None:
    """Make ``journal`` the process's active journal — module-level
    ``emit``/``event`` route to it. Latest install wins (one Trainer at a
    time owns the stream; tests create/tear down many)."""
    global _ACTIVE
    _ACTIVE = journal


def uninstall(journal: Journal) -> None:
    """Release the active slot if ``journal`` still owns it."""
    global _ACTIVE
    if _ACTIVE is journal:
        _ACTIVE = None


def active() -> Any:
    return _ACTIVE if _ACTIVE is not None else NULL


def emit(msg: str, *, stderr: bool = False, record: bool = True) -> None:
    """Print ``msg`` (stdout by default, flushed — byte-for-byte what the
    old bare prints produced) and record it in the active journal.
    ``record=False`` is for streams that already have their own durable
    sink (the MetricsLogger console line: its record IS metrics.jsonl)."""
    print(msg, file=sys.stderr if stderr else sys.stdout, flush=True)
    if record and _ACTIVE is not None:
        _ACTIVE.log(msg, stream="stderr" if stderr else "stdout")


def event(name: str, **fields) -> None:
    """Record an event into the active journal (no console output) — for
    modules that don't hold a journal reference (data/native_loader's
    shard-retry counters)."""
    if _ACTIVE is not None:
        _ACTIVE.event(name, **fields)


# What a module's `auto` resolved to while a program was traced (ops/attention,
# ops/xent): recorded once a resolution, said by whoever drives the program
# (Trainer.train prints :func:`new_resolved_lines` after a dispatch that
# traced).
_RESOLVED: dict = {}
_resolved_said = 0


def resolved(name: str, line: str, **fields) -> None:
    """Record that a module resolved its `auto` to ``fields``: once, a
    ``name`` event in the active journal and ``line`` for
    :func:`new_resolved_lines`."""
    key = (name, tuple(sorted(fields.items())))
    if key not in _RESOLVED:
        _RESOLVED[key] = line
        event(name, **fields)


def new_resolved_lines() -> list:
    """The ``[setup]`` lines of the resolutions recorded since the last
    call (an integer compare when there is none)."""
    global _resolved_said
    if _resolved_said == len(_RESOLVED):
        return []
    fresh = list(_RESOLVED.values())[_resolved_said:]
    _resolved_said = len(_RESOLVED)
    return fresh


# ------------------------------------------------------------ the span gate
# jax.profiler.TraceAnnotation, handed in by whoever imports jax (Trainer,
# ServingEngine): this module stays importable without it.
_ANNOTATION: Any = None
_in_session = False


def _tracing() -> bool:
    """Is a profiler session on? (Replaced by ``TraceAnnotation.is_enabled``
    once a profiler is registered; none known, none listening.)"""
    return False


def register_profiler(annotation_cls) -> None:
    """Give the gate its profiler: ``jax.profiler.TraceAnnotation`` (its
    ``is_enabled()`` is the gate, the class itself the annotation every
    listened-to span opens)."""
    global _ANNOTATION, _tracing
    _ANNOTATION = annotation_cls
    _tracing = annotation_cls.is_enabled


def span(name: str, **ids):
    """``with journal.span("dispatch", step=n): ...`` — THE span primitive
    of the hot paths (module doc). One check and the shared null span when
    neither a profiler session nor an installed journal listens."""
    global _in_session
    if _tracing():
        if not _in_session:
            _begin_session()
    else:
        _in_session = False
        if _ACTIVE is None:
            return _NULL_SPAN
    return _Span(name, ids, _ACTIVE, True)


def _begin_session() -> None:
    """First span of a profiler session: the buffer starts empty, and a
    ``journal/clock`` annotation pins this process's monotonic clock to
    the trace's (whose times count from the session's start)."""
    global _in_session, _dropped
    _in_session = True
    with _BUF_LOCK:
        _TRACED.clear()
        _dropped = 0
    with _ANNOTATION("journal/clock", monotonic_ns=time.monotonic_ns()):
        pass


class SetupLaps:
    """Construction timed as consecutive laps: ``setup = SetupLaps("engine")``
    starts the clock, ``setup.lap("setup/init_pages")`` closes the lap
    that ran since the last one, ``setup.emit()`` prints the one
    ``[setup]`` line. Always on (a dozen laps a run, all before a profiler
    session can be on): each lap is a ``setup_lap`` account (``owner``,
    absolute ``t0`` / ``t1``; :func:`account` hands the installed journal
    its span record) and a part of the ``[setup]`` line; a lap that raises
    records nothing. The first ``SetupLaps`` of a process also accounts for
    what came before it, ``setup/before`` (module doc)."""

    _before_said = False

    def __init__(self, owner: str):
        self.owner = owner
        self._t = time.monotonic()
        self._parts: list = []
        if not SetupLaps._before_said:
            SetupLaps._before_said = True
            account("setup_lap", "setup/before", T_IMPORT, self._t,
                    owner=owner)

    def lap(self, name: str) -> None:
        t0, self._t = self._t, time.monotonic()
        account("setup_lap", name, t0, self._t, owner=self.owner)
        self._parts.append(f"{name.split('/', 1)[-1]} {self._t - t0:.2f} s")

    def emit(self, *, stderr: bool = False) -> None:
        emit(f"[setup] {self.owner}: " + ", ".join(self._parts),
             stderr=stderr)


def traced() -> list:
    """The spans recorded since the last profiler session began (or since
    the process started), oldest first: ``name``, ``t0``, ``t1``
    (``time.monotonic`` seconds), ``id``, ``parent`` and the span's ids."""
    with _BUF_LOCK:
        return list(_TRACED)


def traced_dropped() -> int:
    """Records the traced buffer pushed out since it was last emptied."""
    return _dropped
