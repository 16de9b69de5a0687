"""Resilience subsystem: preemption drain + fault injection.

Distributed Lion's whole optimizer state is the stacked ``[world, ...]``
per-worker momentum pytree — losing it or tearing it silently changes every
future election, so durability is a correctness feature here, not an ops
nicety. This module holds the pieces that are about *surviving the
environment* rather than writing bytes (that's ``train/checkpoint.py``):

- :class:`PreemptionGuard` — a SIGTERM/maintenance handler that sets a flag
  the Trainer checks once per dispatch. On trip the loop drains the
  in-flight async save, writes an emergency checkpoint tagged ``preempt``,
  and returns cleanly so the process exits 0 and whatever supervises the
  job restarts the same command into a normal resume.
- A **fault-injection registry** consumed by ``train/checkpoint.py``'s save
  pipeline, so tests (tests/test_resilience.py) and the runbook's
  resilience stage can simulate a crash mid-save, a slow serializer, or
  flaky save I/O *inside* the real code path instead of monkeypatching it.
- File-corruption helpers (:func:`tear_leaf_file`, :func:`corrupt_manifest`)
  that damage a committed checkpoint the way real incidents do — a torn
  write, a bit-flipped manifest — for the recovery matrix.

The elastic world-size remap itself lives with the optimizer
(``optim.distributed_lion.remap_worker_momentum``) because its semantics are
a statement about the vote distribution; the Trainer's resume path drives it.
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import threading
import time
from typing import Any, Iterable, Optional

# --------------------------------------------------------------------------
# Fault injection
# --------------------------------------------------------------------------
# A process-global name -> value registry. checkpoint.py consults it at the
# few points where real failures strike (the serializer call, the commit
# thread); everything else — torn files, corrupt manifests — is injected by
# mutating the on-disk checkpoint post-commit with the helpers below.
# Supported names (value semantics in parentheses):
#   ckpt_save_raise      (int: fail the first N manager.save calls)
#   ckpt_crash_before_manifest (bool: commit dies before the manifest lands)
#   ckpt_crash_before_marker   (bool: manifest lands, commit marker doesn't)
#   ckpt_slow_commit     (float: seconds the commit thread stalls, i.e. a
#                         slow serialize/write — what async saving must hide)
#   dcn_delay            (float: seconds of round-trip latency the hier
#                         wire's level-2 (DCN) leg is emulated to take.
#                         Consumed at TRACE time by parallel.collectives'
#                         launch/consume gates: the launch stamps a wall
#                         clock per optimizer step, the consume blocks
#                         until stamp + delay — so compute executed between
#                         launch and consume (the --dcn_pipeline_depth
#                         cross-step window) counts toward the deadline and
#                         only the UNHIDDEN residual is paid, recorded in
#                         collectives.DCN_WAIT. This is how the bench_dcn
#                         ablation shapes DCN latency on a CPU mesh. Arm
#                         BEFORE building the optimizer/trainer (trace
#                         time); call collectives.dcn_link_reset() between
#                         measured legs.)
#   journal_torn_write   (int: tear the next N journal sink writes)
#   ballot_poison        ((kind, worker, start_step) from parse_poison():
#                         the trainer's step bakes a worker-k gradient
#                         transform in at trace time — nan_grads → NaN,
#                         frozen_ballot → 0 (its vote freezes at sign(m)),
#                         flipped_ballot → −g (its ballot becomes the exact
#                         inverse of the honest one, adversarial voter).
#                         Inject BEFORE the first dispatch; start_step gates
#                         the onset against the traced optimizer count, so
#                         mid-run onset needs no retrace.)
#   membership           (list of (kind, worker, step) from
#                         parse_membership_specs(): live leave/join
#                         schedule the control plane
#                         (train/control_plane.py) consumes at dispatch
#                         boundaries — worker_drop masks the worker out of
#                         the election (departed, no restart), worker_rejoin
#                         re-absorbs it in-run (momentum healed from the
#                         healthy mean, ballot history reset, probation
#                         window). Host-side only: membership transitions
#                         are mask flips between dispatches, never traced.)
#   serve                (list of (kind, replica, tick, arg) from
#                         parse_serve_specs(): the serve-side replica
#                         fault schedule serve/replica_plane.ServingFleet
#                         consumes at fleet-tick boundaries —
#                         replica_crash kills the replica's engine (its
#                         residents migrate from the fleet's recovery
#                         shadow), replica_drain stops admission and lets
#                         residents finish, slow_tick:<r>:<ms> injects ms
#                         of latency into every tick of replica r (the
#                         tick-latency watch must detect it and route new
#                         work around), replica_rejoin re-enters a
#                         departed replica with a FRESH engine/page pool.
#                         Host-side only, like membership.)
_FAULTS: dict[str, Any] = {}
_FAULTS_LOCK = threading.Lock()


def inject_fault(name: str, value: Any = True) -> None:
    with _FAULTS_LOCK:
        _FAULTS[name] = value


def clear_faults() -> None:
    with _FAULTS_LOCK:
        _FAULTS.clear()


def fault(name: str, default: Any = None) -> Any:
    with _FAULTS_LOCK:
        return _FAULTS.get(name, default)


def consume_due(name: str, through: int, step_of=None) -> list:
    """Atomically pop the DUE entries of a list-valued schedule fault:
    entries whose step/tick (``step_of``, default ``entry[2]``) is
    ``<= through`` are returned in schedule order and removed from the
    registry; later entries stay armed. The membership schedule
    (train/control_plane.membership_due) and the serve-side replica
    schedule (serve/replica_plane.ServingFleet) both consume their
    boundaries through this one helper, so 'due' can never mean two
    different things."""
    if step_of is None:
        def step_of(e):
            return int(e[2])
    with _FAULTS_LOCK:
        pending = _FAULTS.get(name)
        if not pending:
            return []
        due = [e for e in pending if step_of(e) <= through]
        if due:
            _FAULTS[name] = [e for e in pending if step_of(e) > through]
        return due


POISON_KINDS = ("nan_grads", "frozen_ballot", "flipped_ballot")

MEMBERSHIP_KINDS = ("worker_drop", "worker_rejoin")


def parse_membership(spec: str) -> tuple[str, int, int]:
    """Parse one membership-fault spec — ``worker_drop:<w>[:<start_step>]``
    or ``worker_rejoin:<w>:<step>`` — into ``(kind, worker, step)``. The
    control plane (train/control_plane.py) consumes these at dispatch
    boundaries: a drop masks the worker out of the election at the first
    boundary at or after ``step`` (default 0 — departed from the very
    first dispatch), a rejoin re-absorbs it in-run (momentum healed from
    the healthy mean, ballot history reset). A rejoin REQUIRES an explicit
    step: rejoining a worker that never left is undefined, so the schedule
    must be stated. Single source of truth for the --inject_membership CLI
    flag and direct registry injection in tests/the runbook."""
    parts = spec.split(":")
    if len(parts) not in (2, 3) or parts[0] not in MEMBERSHIP_KINDS:
        raise ValueError(
            f"bad membership spec {spec!r}: expected '<kind>:<worker>"
            f"[:<step>]' with kind in {MEMBERSHIP_KINDS}")
    if parts[0] == "worker_rejoin" and len(parts) != 3:
        raise ValueError(
            f"bad membership spec {spec!r}: worker_rejoin requires an "
            "explicit step ('worker_rejoin:<worker>:<step>')")
    try:
        worker = int(parts[1])
        step = int(parts[2]) if len(parts) == 3 else 0
    except ValueError:
        raise ValueError(f"bad membership spec {spec!r}: worker/step must "
                         "be integers")
    if worker < 0 or step < 0:
        raise ValueError(f"bad membership spec {spec!r}: worker/step must "
                         "be >= 0")
    return parts[0], worker, step


def parse_membership_specs(specs: str) -> list:
    """Comma-separated membership specs (the --inject_membership flag) →
    the ``membership`` fault registry value: a list of (kind, worker, step)
    tuples, consumed in order by the control plane as their steps come
    due."""
    return [parse_membership(s.strip())
            for s in specs.split(",") if s.strip()]


SERVE_FAULT_KINDS = ("replica_crash", "replica_kill", "replica_drain",
                     "slow_tick", "replica_rejoin")


def parse_serve_fault(spec: str) -> tuple[str, int, int, int]:
    """Parse one serve-side replica-fault spec into the normalized
    ``(kind, replica, tick, arg)`` tuple the fleet consumes (the third
    field is ALWAYS the due tick, so the schedule pops through
    :func:`consume_due` like membership):

    - ``replica_crash:<r>:<tick>`` — replica r dies at that fleet tick
      (engine discarded; residents migrate from the recovery shadow)
    - ``replica_kill:<r>:<tick>`` — the PROCESS-death twin: on a
      process-isolated replica (serve/fleet_proc) a real SIGKILL is
      armed inside the child's next tick (mid-decode — the decode
      dispatch runs, the reply never arrives); on an in-process engine
      it degrades to the simulated crash above
    - ``replica_drain:<r>[:<tick>]`` — r stops admitting at tick (default
      0), finishes its residents, then departs
    - ``slow_tick:<r>:<ms>`` — every tick of replica r pays <ms> extra
      milliseconds, armed from tick 0 (``arg`` carries the ms)
    - ``replica_rejoin:<r>:<tick>`` — a departed r re-enters the rotation
      with a fresh engine/page pool; requires an explicit tick (rejoining
      a replica that never left is undefined, same rule as
      worker_rejoin)

    Single source of truth for the --inject_serve CLI flag and direct
    registry injection in tests/the bench."""
    parts = spec.split(":")
    if len(parts) not in (2, 3) or parts[0] not in SERVE_FAULT_KINDS:
        raise ValueError(
            f"bad serve fault spec {spec!r}: expected '<kind>:<replica>"
            f"[:<tick|ms>]' with kind in {SERVE_FAULT_KINDS}")
    if parts[0] in ("replica_crash", "replica_kill", "slow_tick",
                    "replica_rejoin") and len(parts) != 3:
        raise ValueError(
            f"bad serve fault spec {spec!r}: {parts[0]} requires an "
            f"explicit third field ('{parts[0]}:<replica>:"
            f"{'<ms>' if parts[0] == 'slow_tick' else '<tick>'}')")
    try:
        replica = int(parts[1])
        val = int(parts[2]) if len(parts) == 3 else 0
    except ValueError:
        raise ValueError(f"bad serve fault spec {spec!r}: replica/"
                         "tick/ms must be integers")
    if replica < 0 or val < 0:
        raise ValueError(f"bad serve fault spec {spec!r}: replica/"
                         "tick/ms must be >= 0")
    if parts[0] == "slow_tick":
        return parts[0], replica, 0, val   # armed from tick 0; arg = ms
    return parts[0], replica, val, 0


def parse_serve_specs(specs: str) -> list:
    """Comma-separated serve fault specs (the --inject_serve flag) → the
    ``serve`` fault registry value, consumed in order by the fleet as
    their ticks come due."""
    return [parse_serve_fault(s.strip())
            for s in specs.split(",") if s.strip()]


def parse_poison(spec: str) -> tuple[str, int, int]:
    """Parse a ballot-poisoning spec ``<kind>:<worker>[:<start_step>]``
    (e.g. ``nan_grads:2`` or ``flipped_ballot:0:100``) into the
    ``(kind, worker, start_step)`` tuple the ``ballot_poison`` fault
    carries. Single source of truth for the --inject_poison CLI flag and
    direct registry injection in tests/the runbook."""
    parts = spec.split(":")
    if len(parts) not in (2, 3) or parts[0] not in POISON_KINDS:
        raise ValueError(
            f"bad poison spec {spec!r}: expected '<kind>:<worker>"
            f"[:<start_step>]' with kind in {POISON_KINDS}")
    try:
        worker = int(parts[1])
        start = int(parts[2]) if len(parts) == 3 else 0
    except ValueError:
        raise ValueError(f"bad poison spec {spec!r}: worker/start_step "
                         "must be integers")
    if worker < 0 or start < 0:
        raise ValueError(f"bad poison spec {spec!r}: worker/start_step "
                         "must be >= 0")
    return parts[0], worker, start


def consume_fault_count(name: str) -> bool:
    """Decrement a counted fault; True while it still has charges. Lets a
    test say 'the first two save attempts fail' and have the retry loop
    observe exactly that."""
    with _FAULTS_LOCK:
        n = _FAULTS.get(name, 0)
        if isinstance(n, bool):
            return n
        if n and n > 0:
            _FAULTS[name] = n - 1
            return True
        return False


# --------------------------------------------------------------------------
# Manifest verification (pure stdlib — importable by scripts/check_evidence
# without dragging jax/orbax in; train/checkpoint.py writes these artifacts
# and re-exports the readers)
# --------------------------------------------------------------------------

MANIFEST = "manifest.json"
MARKER = "COMMITTED"
# root-level stamp: "steps in this directory are committed with manifests".
# Its presence flips the no-marker interpretation from 'legacy checkpoint,
# assume good' to 'commit never finished, reject' — without it a crash
# before the first manifest would masquerade as a legacy checkpoint.
MANIFESTS_STAMP = "MANIFESTS_ENABLED"
MANIFEST_FORMAT = 1


def sha256_file(path: pathlib.Path | str, chunk: int = 1 << 20) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            b = f.read(chunk)
            if not b:
                break
            h.update(b)
    return h.hexdigest()


def read_manifest(sdir: pathlib.Path | str) -> Optional[dict]:
    """The manifest of a COMMITTED step, after checking it against the
    marker's recorded digest (cheap — no data-file hashing). None when the
    step is uncommitted or its manifest doesn't match the marker."""
    import hashlib

    sdir = pathlib.Path(sdir)
    marker = read_json(sdir / MARKER)
    if not marker:
        return None
    try:
        raw = (sdir / MANIFEST).read_bytes()
    except OSError:
        return None
    if hashlib.sha256(raw).hexdigest() != marker.get("manifest_sha256"):
        return None
    try:
        return json.loads(raw)
    except json.JSONDecodeError:
        return None


def verify_step_dir(sdir: pathlib.Path | str) -> bool:
    """Full integrity check of one committed step: marker → manifest digest
    → every data file present with matching size and sha256."""
    sdir = pathlib.Path(sdir)
    manifest = read_manifest(sdir)
    if manifest is None:
        return False
    for rel, info in manifest.get("files", {}).items():
        p = sdir / rel
        try:
            if p.stat().st_size != info["bytes"]:
                return False
            if sha256_file(p) != info["sha256"]:
                return False
        except OSError:
            return False
    return True


def latest_valid_step_in(directory: str | os.PathLike) -> Optional[int]:
    """Standalone verified autodetect over a checkpoint root (no
    CheckpointManager needed — scripts/check_evidence.py's resilience stage
    runs this). Mirrors ``Checkpointer.latest_valid_step``: newest GOOD
    step wins; marker-less steps are valid only in pre-manifest (unstamped)
    directories."""
    root = pathlib.Path(directory)
    try:
        steps = sorted((int(p.name) for p in root.iterdir()
                        if p.is_dir() and p.name.isdigit()), reverse=True)
    except OSError:
        return None
    stamped = (root / MANIFESTS_STAMP).exists()
    for s in steps:
        sdir = root / str(s)
        if verify_step_dir(sdir):
            return s
        if not stamped and read_json(sdir / MARKER) is None:
            return s  # legacy pre-manifest checkpoint: assumed good
    return None


# --------------------------------------------------------------------------
# Checkpoint corruption helpers (the recovery matrix's torn/corrupt legs)
# --------------------------------------------------------------------------

def step_dir(directory: str | os.PathLike, step: int) -> pathlib.Path:
    """The Orbax step directory for ``step`` under a checkpoint root."""
    return pathlib.Path(directory) / str(step)


def tear_leaf_file(directory: str | os.PathLike, step: int) -> pathlib.Path:
    """Truncate the largest data file of a committed checkpoint in place —
    the classic torn write (process/node died mid-flush, filesystem kept
    the prefix). Returns the torn path. The manifest's digest for that
    file no longer matches, so verification must reject the step."""
    sdir = step_dir(directory, step)
    candidates = [
        p for p in sdir.rglob("*") if p.is_file()
        and p.name not in (MANIFEST, MARKER)
        and p.stat().st_size > 0
    ]
    if not candidates:
        raise FileNotFoundError(f"no data files under {sdir}")
    victim = max(candidates, key=lambda p: p.stat().st_size)
    size = victim.stat().st_size
    with open(victim, "r+b") as f:
        f.truncate(max(size // 2, 1) - 1 if size > 1 else 0)
    return victim


def corrupt_manifest(directory: str | os.PathLike, step: int) -> pathlib.Path:
    """Flip bytes inside a committed checkpoint's manifest. The commit
    marker records the manifest's own digest, so verification must reject
    the step without even re-hashing the data files."""
    path = step_dir(directory, step) / MANIFEST
    raw = bytearray(path.read_bytes())
    if not raw:
        raise OSError(f"empty manifest at {path}")
    mid = len(raw) // 2
    raw[mid] = raw[mid] ^ 0xFF
    path.write_bytes(bytes(raw))
    return path


def delete_commit_marker(directory: str | os.PathLike, step: int) -> None:
    """Simulate a crash between the manifest write and the commit marker:
    the checkpoint's bytes are all present but it was never committed."""
    (step_dir(directory, step) / MARKER).unlink()


# --------------------------------------------------------------------------
# Preemption
# --------------------------------------------------------------------------

class PreemptionGuard:
    """Signal-driven preemption flag, checked once per train dispatch.

    Installs handlers for ``signals`` (default SIGTERM — what TPU
    maintenance events and the watcher's ``timeout`` deliver) that only set
    a :class:`threading.Event`; all actual work (draining the in-flight
    save, writing the ``preempt``-tagged checkpoint) happens on the train
    loop's thread at the next dispatch boundary, where the program state is
    consistent. Off the main thread (bench harnesses drive Trainers from
    worker threads) signal installation is impossible; the guard degrades
    to a manually-triggerable flag (:meth:`trigger`) instead of failing.
    """

    def __init__(self, signals: Iterable[int] = (signal.SIGTERM,),
                 journal=None):
        self._flag = threading.Event()
        self._prev: dict[int, Any] = {}
        # run-journal hook (train/journal.py, duck-typed so this module
        # stays import-light): the drain event is recorded from
        # should_stop() on the TRAIN LOOP's thread, never from the signal
        # handler — a handler must stay async-signal-safe (flag + one
        # clock read, nothing that allocates or takes locks)
        self._journal = journal
        self._tripped_mono: Optional[float] = None
        self._drain_logged = False
        for sig in signals:
            try:
                self._prev[sig] = signal.signal(sig, self._on_signal)
            except ValueError:  # not the main thread
                pass

    def _on_signal(self, signum, frame) -> None:
        if self._flag.is_set():
            # second delivery: the loop never reached a dispatch boundary
            # (hung collective, wedged step) — stop absorbing the signal.
            # Restore the previous disposition and re-deliver so `timeout`
            # and operators can still kill a stuck process with TERM.
            prev = self._prev.get(signum, signal.SIG_DFL)
            signal.signal(signum, prev if prev is not None else signal.SIG_DFL)
            signal.raise_signal(signum)
            return
        # first delivery, async-signal-safe: stamp the clock + set the
        # flag, nothing else (the stamp is what lets the journal report
        # signal→drain-boundary latency — how long a preemption waits for
        # a consistent dispatch boundary)
        self._tripped_mono = time.monotonic()
        self._flag.set()

    def trigger(self) -> None:
        """Programmatic preemption (tests; cluster agents that learn of
        maintenance through an API rather than a signal)."""
        if self._tripped_mono is None:
            self._tripped_mono = time.monotonic()
        self._flag.set()

    def should_stop(self) -> bool:
        tripped = self._flag.is_set()
        if tripped and not self._drain_logged:
            # first observation at a dispatch boundary: THE preemption-
            # drain event (the trainer is about to drain the in-flight
            # save and write the emergency checkpoint)
            self._drain_logged = True
            if self._journal is not None:
                latency = (time.monotonic() - self._tripped_mono
                           if self._tripped_mono is not None else 0.0)
                self._journal.event("preempt_drain",
                                    signal_to_boundary_s=round(latency, 6))
        return tripped

    def close(self) -> None:
        """Restore the previous handlers (Trainers are created and torn
        down many times per test process)."""
        for sig, prev in self._prev.items():
            try:
                if signal.getsignal(sig) == self._on_signal:
                    signal.signal(sig, prev)
            except ValueError:
                pass
        self._prev.clear()


# --------------------------------------------------------------------------
# Small shared utilities
# --------------------------------------------------------------------------

def read_json(path: str | os.PathLike) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
