"""Persistent XLA compilation cache, placed from outside.

One helper for every entry point that compiles for the chip (the train and
serve CLIs, ``chip_smoke.py``). The cache directory is part of the cache
key, so it must not move between runs: where ``JAX_COMPILATION_CACHE_DIR``
is set JAX already uses it and this module sets no directory at all;
otherwise the cache lives at one fixed path inside the checkout
(:data:`CHECKOUT_CACHE_DIR`, git-ignored).

**The compile ledger** (:func:`ledger`): which program was traced,
lowered, compiled or loaded from the cache, how often and for how long —
the operator's answer to "which step recompiled". Always on once
:func:`listen` has run (``enable_compilation_cache`` calls it, so do the
Trainer and the serving engine); fed by ``jax.monitoring`` events, which
carry the program as ``fun_name``. A row per name (the ``jit(...)`` /
``jit_`` wrapper dropped: tracing reports ``step``, lowering and
compiling ``jit(step)``):

- ``trace_s`` / ``traces``: jaxpr tracing (``jaxpr_trace_duration``). A
  function jitted inside another is traced inside the outer one's time
  and has a row of its own with no lowering: sum over rows with
  ``lowerings > 0`` to count each second once.
- ``lower_s`` / ``lowerings``: jaxpr to MLIR (``jaxpr_to_mlir_module_duration``).
- ``compile_s`` / ``compiles``: backend compile OR persistent-cache load
  (``backend_compile_duration`` covers both); ``compiles`` is the number
  of distinct specialisations this process built.
- ``cache_hits`` / ``cache_misses`` / ``retrieval_s``: the persistent
  cache's part of ``compile_s``. A miss is jax's own event: a compile the
  cache did not hold and then wrote (those of at least
  ``jax_persistent_cache_min_compile_time_secs``; quicker ones are never
  cached and count in ``compiles`` alone).

Every event is kept with the ``time.monotonic()`` at which its phase ended
(the last :data:`EVENTS_MAX`; older ones drop out of the sums and are
counted: the ``[compile]`` lines say how many once there are any), so
``ledger(until=t)`` / ``totals(until=t)`` tell what set-up paid from what
compiled later in the process.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Optional

# <checkout>/.jax_compile_cache — a pure function of where the package
# lives (no pid, time, hostname or CPU identity in the path)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_compile_cache")


_ROW = {"trace_s": 0.0, "traces": 0, "lower_s": 0.0, "lowerings": 0,
        "compile_s": 0.0, "compiles": 0, "cache_hits": 0, "cache_misses": 0,
        "retrieval_s": 0.0}
_DURATIONS = {"jaxpr_trace_duration": ("trace_s", "traces"),
              "jaxpr_to_mlir_module_duration": ("lower_s", "lowerings"),
              "backend_compile_duration": ("compile_s", "compiles")}
EVENTS_MAX = 32768   # events kept; older ones drop out of the sums (counted)
_LOCK = threading.Lock()
# one entry per duration event, in order: (time.monotonic() when the phase
# ended, program, seconds key, count key, seconds, cache hits, misses,
# retrieval seconds); the ledger is these folded by program
_EVENTS: collections.deque = collections.deque(maxlen=EVENTS_MAX)
_events_dropped = 0
# cache events carry no program name: they fire inside the compile whose
# duration event (which names it) follows on the same thread
_PENDING = threading.local()
_listening = False
_builds = 0          # backend compile events so far, any program
_builds_said = 0     # ... at the last new_lines() call
_said: dict = {}     # program -> its ``compiles`` when new_lines() named it


_WRAPPED = re.compile(r"^(?:jit|pmap)(?:_(.+)|\((.+)\))$")


def _program(fun_name) -> str:
    """``jit(step)`` and ``jit_step`` (what lowering and compiling report)
    under the name tracing reports: ``step``."""
    m = _WRAPPED.match(str(fun_name))
    return (m.group(1) or m.group(2)) if m else str(fun_name)


def _on_event(event: str, **_) -> None:
    if event.endswith("/compilation_cache/cache_hits"):
        _PENDING.hits = getattr(_PENDING, "hits", 0) + 1
    elif event.endswith("/compilation_cache/cache_misses"):
        _PENDING.misses = getattr(_PENDING, "misses", 0) + 1


def _on_duration(event: str, secs: float, **kw) -> None:
    global _builds, _events_dropped
    tail = event.rsplit("/", 1)[-1]
    if tail == "cache_retrieval_time_sec":
        _PENDING.retrieval_s = getattr(_PENDING, "retrieval_s", 0.0) + secs
        return
    keys = _DURATIONS.get(tail)
    if keys is None or "fun_name" not in kw:
        return
    cache = (0, 0, 0.0)
    if tail == "backend_compile_duration":
        took = _PENDING.__dict__
        cache = (took.pop("hits", 0), took.pop("misses", 0),
                 took.pop("retrieval_s", 0.0))
    with _LOCK:
        _builds += tail == "backend_compile_duration"
        _events_dropped += len(_EVENTS) == EVENTS_MAX
        _EVENTS.append((time.monotonic(), _program(kw["fun_name"]), *keys,
                        secs, *cache))


def listen() -> None:
    """Register the ledger's ``jax.monitoring`` listeners, once."""
    global _listening
    if _listening:
        return
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listening = True


def ledger(until: Optional[float] = None) -> dict:
    """``{program: row}`` as the module doc describes; with ``until`` (a
    ``time.monotonic()`` reading) only the phases that ended by then, which
    is how set-up is told from what compiled later in the process."""
    with _LOCK:
        events = list(_EVENTS)
    rows: dict = {}
    for t, program, key_s, key_n, secs, hits, misses, retrieval_s in events:
        if until is not None and t > until:
            continue
        row = rows.setdefault(program, dict(_ROW))
        row[key_s] += secs
        row[key_n] += 1
        row["cache_hits"] += hits
        row["cache_misses"] += misses
        row["retrieval_s"] += retrieval_s
    return rows


def totals(until: Optional[float] = None) -> dict:
    """The ledger summed: ``trace_lower_s`` counts tracing only for
    programs that were lowered (module doc: nested traces count once),
    the rest are plain sums over every row."""
    rows = list(ledger(until).values())
    return {
        "trace_lower_s": sum(r["trace_s"] + r["lower_s"] for r in rows
                             if r["lowerings"]),
        "compile_s": sum(r["compile_s"] for r in rows),
        "compiles": sum(r["compiles"] for r in rows),
        "cache_hits": sum(r["cache_hits"] for r in rows),
        "cache_misses": sum(r["cache_misses"] for r in rows),
    }


def ledger_lines(min_s: float = 1.0, only=None) -> list:
    """One line for each program (of ``only``, when given) whose tracing,
    lowering and compiling (or loading) cost more than ``min_s`` seconds
    together, costliest first."""
    rows = sorted(((r["trace_s"] + r["lower_s"] + r["compile_s"], name, r)
                   for name, r in ledger().items()
                   if only is None or name in only), reverse=True)
    lost = (f"; {_events_dropped} older events dropped from the sums"
            if _events_dropped else "")
    return [f"[compile] {name}: trace {r['trace_s']:.1f} s, lower "
            f"{r['lower_s']:.1f} s, compile or load {r['compile_s']:.1f} s "
            f"({r['compiles']} built; cache hits {r['cache_hits']}, misses "
            f"{r['cache_misses']}, retrieval {r['retrieval_s']:.1f} s{lost})"
            for total, name, r in rows if total > min_s]


def new_lines(min_s: float = 1.0) -> list:
    """:func:`ledger_lines` for the programs built since the last call (an
    integer compare when nothing was): the trainer and the engine print
    these after a dispatch, so a compile is named when it happens."""
    global _builds_said
    if _builds_said == _builds:
        return []
    _builds_said = _builds
    fresh = {name: row["compiles"] for name, row in ledger().items()
             if row["compiles"] > _said.get(name, 0)}
    _said.update(fresh)
    return ledger_lines(min_s, only=fresh)


def compiles_of(program: str) -> int:
    """How many specialisations of ``program`` this process has built."""
    return ledger().get(program, _ROW)["compiles"]


def enable_compilation_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for a TPU backend and
    return the directory in use (None when the cache stays off). Opt-out
    with ``DLION_COMPILE_CACHE=0``.

    TPU backend only: XLA:CPU AOT cache entries compiled on one host
    fatally abort the process when loaded on a host with different CPU
    features, and CPU compiles are fast enough that caching them buys
    little (ROADMAP tier-1 note). Initializes the backend — call it after
    ``jax.distributed.initialize()`` on multi-host launches."""
    import jax

    listen()
    if os.environ.get("DLION_COMPILE_CACHE", "1") == "0":
        return None
    if jax.default_backend() != "tpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
