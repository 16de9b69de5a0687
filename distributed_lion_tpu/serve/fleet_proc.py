"""Process-isolated serving replicas: the parent-side transport.

ROADMAP item 2(b): today's :class:`serve.replica_plane.ServingFleet`
replica is a same-process Python object, so "crash" is a method call.
This module makes replica failure a real OS event: each replica is a
``python -m distributed_lion_tpu.serve.replica_worker`` subprocess
speaking a length-prefixed JSON protocol over its stdin/stdout pipes,
and :class:`ProcessReplica` is the parent-side handle that exposes the
exact duck surface the fleet already drives engines through —
``submit`` / ``step`` / ``export_records`` / ``has_work`` / ``pending``
/ ``stats`` — so the fleet's routing, recovery-shadow, and migration
machinery run UNCHANGED across the process boundary.

Wire protocol (one 4-byte big-endian length prefix + UTF-8 strict JSON
per frame):

- parent → child: ``{"cmd": "build", "builder": {...}}`` once, then
  ``{"cmd": "tick", "tick_seq": n, "submit": [...], "controls": [...]}``
  per fleet tick (at most ONE outstanding tick — the reply is the
  heartbeat), plus ``{"cmd": "chains"}`` (persistence cadence) and
  ``{"cmd": "exit"}``.
- child → parent: ``{"ok": true, "pid": p}`` after build, then per tick
  ``{"tick_seq": n, "completions": [...], "records": [...], "stats": {...},
  "pending_ids": [...], "has_work": b}``.

Heartbeats ARE the tick replies: a reply not arriving within
``heartbeat_timeout_s`` raises :class:`HeartbeatMiss` (the fleet
journals ``replica_heartbeat_missed`` and retries with the SAME
outstanding tick — a slow child's late reply is consumed on the next
poll, never lost); ``heartbeat_max_misses`` consecutive misses — or an
EOF/broken pipe (:class:`ReplicaGone`) — gets the replica declared
dead, SIGKILLed, and its requests migrated from the fleet's recovery
shadow exactly as the in-process crash path pins (token-identical by
construction: the shadow holds prompt + committed + seed, and the
per-request PRNG stream resumes at ``len(committed)``).

Wall-clock deadlines never cross the boundary as absolute stamps — the
two processes have different monotonic epochs — they travel as
REMAINING seconds and re-stamp against the receiver's clock.

Layering: stdlib-only at module scope (no jax — the child imports jax,
the parent never does on this path), every read behind a ``selectors``
poll with an explicit deadline (graft-check DLT012), and every clock
read through the injectable ``time_fn`` seam (DLT011).
"""

from __future__ import annotations

import json
import os
import selectors
import signal
import struct
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from distributed_lion_tpu.serve.engine import (
    Completion,
    RecoveryRecord,
    Request,
)

_HEADER = struct.Struct(">I")
MAX_FRAME_BYTES = 64 << 20   # a torn length prefix must not OOM the host

WORKER_MODULE = "distributed_lion_tpu.serve.replica_worker"


class HeartbeatMiss(RuntimeError):
    """The outstanding tick's reply missed its heartbeat deadline. The
    child may be slow, not dead — the caller decides after
    ``heartbeat_max_misses`` strikes; the outstanding tick stays armed
    and a late reply is consumed by the next read."""


class ReplicaGone(RuntimeError):
    """The pipe is closed or the frame stream is corrupt: the replica
    process is unrecoverable (exited, SIGKILLed, or garbled)."""


# ------------------------------------------------------------------- framing
def write_frame(fobj, obj: dict) -> None:
    """One length-prefixed strict-JSON frame. ``flush`` per frame — a
    buffered half-frame on a dying sender must never look like silence
    followed by garbage on the receiver."""
    payload = json.dumps(obj, allow_nan=False).encode("utf-8")
    fobj.write(_HEADER.pack(len(payload)) + payload)
    fobj.flush()


def read_frame_blocking(fd: int, poll_s: float = 60.0,
                        buf: Optional[bytearray] = None) -> Optional[dict]:
    """Child-side frame read: poll ``fd`` in bounded ``poll_s`` windows
    (never an unbounded block — the DLT012 discipline) until one full
    frame arrives or EOF (returns None — the parent died or hung up, and
    an orphaned worker must exit, not linger)."""
    buf = bytearray() if buf is None else buf
    sel = selectors.DefaultSelector()
    sel.register(fd, selectors.EVENT_READ)
    try:
        while True:
            frame = _take_frame(buf)
            if frame is not None:
                return frame
            if not sel.select(poll_s):
                continue   # re-poll: idle parents are legal, orphans EOF
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                return None
            buf += chunk
    finally:
        sel.close()


def _take_frame(buf: bytearray) -> Optional[dict]:
    if len(buf) < _HEADER.size:
        return None
    (n,) = _HEADER.unpack(bytes(buf[:_HEADER.size]))
    if n > MAX_FRAME_BYTES:
        raise ReplicaGone(f"frame length {n} exceeds {MAX_FRAME_BYTES} — "
                          "corrupt stream")
    if len(buf) < _HEADER.size + n:
        return None
    payload = bytes(buf[_HEADER.size:_HEADER.size + n])
    del buf[:_HEADER.size + n]
    try:
        return json.loads(payload)
    except ValueError as e:
        raise ReplicaGone(f"corrupt frame payload: {e}") from e


# --------------------------------------------------------------- wire codecs
def request_to_wire(req: Request, deadline_remaining_s: Optional[float]
                    ) -> dict:
    d = {"req_id": req.req_id, "tokens": [int(t) for t in req.tokens],
         "seed": int(req.seed),
         "committed": [int(t) for t in req.committed]}
    if req.max_new_tokens is not None:
        d["max_new_tokens"] = int(req.max_new_tokens)
    if req.prefix_group is not None:
        d["prefix_group"] = req.prefix_group
    if deadline_remaining_s is not None:
        d["deadline_remaining_s"] = float(deadline_remaining_s)
    return d


def request_from_wire(d: dict) -> Request:
    return Request(req_id=d["req_id"], tokens=list(d["tokens"]),
                   max_new_tokens=d.get("max_new_tokens"),
                   seed=int(d.get("seed", 0)),
                   prefix_group=d.get("prefix_group"),
                   committed=list(d.get("committed", ())))


def record_to_wire(rec: RecoveryRecord, now: float) -> dict:
    d = {"req_id": rec.req_id, "tokens": [int(t) for t in rec.tokens],
         "committed": [int(t) for t in rec.committed],
         "seed": int(rec.seed)}
    if rec.budget is not None:
        d["budget"] = int(rec.budget)
    if rec.prefix_group is not None:
        d["prefix_group"] = rec.prefix_group
    if rec.deadline_at is not None:
        # absolute monotonic stamps are meaningless across processes —
        # ship the REMAINING budget, re-stamp on the receiving clock
        d["deadline_remaining_s"] = float(rec.deadline_at - now)
    return d


def record_from_wire(d: dict, now: float) -> RecoveryRecord:
    remaining = d.get("deadline_remaining_s")
    return RecoveryRecord(
        req_id=d["req_id"], tokens=list(d["tokens"]),
        committed=list(d["committed"]), seed=int(d["seed"]),
        budget=d.get("budget"), prefix_group=d.get("prefix_group"),
        deadline_at=(now + float(remaining) if remaining is not None
                     else None))


def completion_to_wire(c: Completion) -> dict:
    return {"req_id": c.req_id, "prompt_len": int(c.prompt_len),
            "tokens": [int(t) for t in c.tokens], "reason": c.reason,
            "timing": c.timing}


def completion_from_wire(d: dict) -> Completion:
    return Completion(d["req_id"], int(d["prompt_len"]),
                      list(d["tokens"]), d["reason"],
                      timing=d.get("timing"))


# ------------------------------------------------------------ pending mirror
class _PendingMirror(list):
    """The fleet drains a replica by ``list(engine.pending)`` +
    ``engine.pending.clear()``. For a process replica the authoritative
    pending queue lives in the child; this mirror tracks it from tick
    replies, and ``clear()`` also schedules a ``drop_pending`` control
    so the child parts with those requests before its next admission."""

    def __init__(self, owner: "ProcessReplica"):
        super().__init__()
        self._owner = owner

    def clear(self) -> None:   # type: ignore[override]
        if self:
            self._owner._queue_control({"op": "drop_pending"})
        super().clear()


class ProcessReplica:
    """One serving replica in its own OS process (see module doc).

    Duck-compatible with the slice of :class:`ServingEngine` the fleet
    touches. ``metrics`` is None — a process replica's sketch plane
    cannot be merged parent-side without shipping raw bins every tick;
    its request timings still ride the completion records."""

    def __init__(self, builder: dict, heartbeat_timeout_s: float = 60.0,
                 spawn_timeout_s: float = 600.0,
                 label: str = "", env: Optional[dict] = None,
                 time_fn: Callable[[], float] = time.monotonic):
        self.builder = builder
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.label = label
        self._now = time_fn
        self.metrics = None
        self.stats: Dict[str, Any] = {}
        self.pending = _PendingMirror(self)
        self._known: Dict[Any, Request] = {}
        self._records: List[RecoveryRecord] = []
        self._submits: List[tuple] = []
        self._controls: List[dict] = []
        self._outstanding: Optional[int] = None    # seq of the armed tick
        self._seq = 0
        self._has_work = False
        self._rbuf = bytearray()
        self._dead = False
        child_env = dict(os.environ)  # the platform is inherited, never
        # chosen here: a child that cannot open its device fails its hello
        # token-identical across the boundary requires the child to
        # sample with the parent's PRNG layout: mirror jax config the
        # parent set PROGRAMMATICALLY (env vars already inherit) into
        # the child's env. sys.modules keeps this module jax-free — the
        # parent only has a config to mirror if it imported jax itself.
        parent_jax = sys.modules.get("jax")
        if parent_jax is not None:
            for opt in ("jax_threefry_partitionable", "jax_enable_x64"):
                try:
                    val = bool(getattr(parent_jax.config, opt))
                except AttributeError:
                    continue
                child_env.setdefault(opt.upper(), "1" if val else "0")
        if env:
            child_env.update(env)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", WORKER_MODULE],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=None,
            env=child_env)
        write_frame(self.proc.stdin, {"cmd": "build", "builder": builder})
        hello = self._read_reply(spawn_timeout_s,
                                 miss_ok=False)  # build may jit-compile
        if not (isinstance(hello, dict) and hello.get("ok")):
            self.close(kill=True)
            raise ReplicaGone(
                f"replica worker failed to build: {hello!r}")
        self.pid = int(hello["pid"])

    # ----------------------------------------------------------- transport
    def _read_reply(self, timeout_s: float, miss_ok: bool = True) -> dict:
        """One frame from the child within ``timeout_s`` — the heartbeat
        read. Timeout raises :class:`HeartbeatMiss` (the partial buffer
        is KEPT: a frame split across misses reassembles, never tears);
        EOF or stream corruption raises :class:`ReplicaGone`."""
        if self._dead:
            raise ReplicaGone("replica already closed")
        fd = self.proc.stdout.fileno()
        deadline = self._now() + float(timeout_s)
        sel = selectors.DefaultSelector()
        sel.register(fd, selectors.EVENT_READ)
        try:
            while True:
                frame = _take_frame(self._rbuf)
                if frame is not None:
                    return frame
                left = deadline - self._now()
                if left <= 0:
                    if miss_ok:
                        raise HeartbeatMiss(
                            f"no reply within {timeout_s}s")
                    raise ReplicaGone(
                        f"no build reply within {timeout_s}s")
                if not sel.select(min(left, 1.0)):
                    continue
                chunk = os.read(fd, 1 << 16)
                if not chunk:
                    raise ReplicaGone("replica pipe closed (EOF)")
                self._rbuf += chunk
        finally:
            sel.close()

    def _queue_control(self, ctl: dict) -> None:
        self._controls.append(ctl)

    # -------------------------------------------- the engine duck surface
    def submit(self, req: Request, deadline_at: Optional[float] = None
               ) -> None:
        self._submits.append((req, deadline_at))
        self._known[req.req_id] = req
        self.pending.append(req)
        self._has_work = True

    def has_work(self) -> bool:
        return (self._outstanding is not None or self._has_work
                or bool(self._submits) or bool(self._controls))

    def export_records(self) -> List[RecoveryRecord]:
        return list(self._records)

    def step(self) -> List[Completion]:
        """One replica tick across the boundary. Sends the tick command
        (buffered submits + controls) unless one is already outstanding
        from a missed heartbeat, then reads the reply under the
        heartbeat deadline. Raises HeartbeatMiss / ReplicaGone — the
        fleet owns the miss-count / declare-dead policy."""
        if self._dead:
            raise ReplicaGone("replica already closed")
        if self._outstanding is None:
            now = self._now()
            msg = {"cmd": "tick", "tick_seq": self._seq, "controls":
                   list(self._controls), "submit": []}
            for req, deadline_at in self._submits:
                remaining = (deadline_at - now
                             if deadline_at is not None else None)
                if remaining is None and req.deadline_s is not None:
                    remaining = float(req.deadline_s)
                msg["submit"].append(request_to_wire(req, remaining))
            self._submits.clear()
            self._controls.clear()
            try:
                write_frame(self.proc.stdin, msg)
            except (BrokenPipeError, OSError) as e:
                raise ReplicaGone(f"replica pipe closed: {e}") from e
            self._outstanding = self._seq
            self._seq += 1
        reply = self._read_reply(self.heartbeat_timeout_s)
        if reply.get("tick_seq") != self._outstanding:
            raise ReplicaGone(
                f"tick reply out of sequence: got {reply.get('tick_seq')}, "
                f"expected {self._outstanding}")
        self._outstanding = None
        now = self._now()
        self._records = [record_from_wire(d, now)
                         for d in reply.get("records", ())]
        self.stats = dict(reply.get("stats", ()))
        self._has_work = bool(reply.get("has_work"))
        completions = [completion_from_wire(d)
                       for d in reply.get("completions", ())]
        for c in completions:
            self._known.pop(c.req_id, None)
        pend_ids = set(reply.get("pending_ids", ()))
        super(_PendingMirror, self.pending).clear()
        self.pending.extend(self._known[r] for r in pend_ids
                            if r in self._known)
        return completions

    # --------------------------------------------------- control / faults
    def arm_kill(self) -> None:
        """Arm a real SIGKILL inside the child's NEXT tick: the worker
        steps its engine (the decode dispatch runs) and dies before the
        reply — the mid-decode process death the acceptance matrix
        pins. The parent observes EOF on the heartbeat read."""
        self._queue_control({"op": "kill_after_step"})

    def stall_next_tick(self, ms: int) -> None:
        """Make the child sleep ``ms`` before replying to its next tick
        (the cross-process straggler / heartbeat-miss injection)."""
        self._queue_control({"op": "stall_ms", "ms": int(ms)})

    def export_chains(self, timeout_s: Optional[float] = None
                      ) -> List[dict]:
        """Synchronous chain export for the persistence cadence. Never
        called with a tick outstanding (the fleet persists after a
        completed tick); a miss returns [] — persistence must degrade,
        not kill a slow replica."""
        if self._dead or self._outstanding is not None:
            return []
        try:
            write_frame(self.proc.stdin, {"cmd": "chains"})
            reply = self._read_reply(timeout_s or self.heartbeat_timeout_s)
            return list(reply.get("chains", ()))
        except (HeartbeatMiss, ReplicaGone, OSError):
            return []

    def close(self, kill: bool = False) -> None:
        """Tear the replica down. ``kill=True`` is the crash path (the
        ``--inject_serve replica_crash`` control message + SIGKILL
        backstop); ``kill=False`` asks for a clean exit first."""
        if self._dead:
            return
        self._dead = True
        try:
            write_frame(self.proc.stdin, {"cmd": "exit",
                                          "hard": bool(kill)})
        except (BrokenPipeError, OSError):
            pass
        if kill and self.proc.poll() is None:
            try:
                self.proc.send_signal(signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            # reap with a bounded wait; SIGKILL as the backstop so close
            # can never hang the fleet on a wedged child
            self.proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            try:
                self.proc.send_signal(signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass
            self.proc.wait(timeout=5.0)
        try:
            self.proc.stdout.close()
        except OSError:
            pass


def process_replica_factory(builder: dict,
                            heartbeat_timeout_s: float = 60.0,
                            spawn_timeout_s: float = 600.0,
                            time_fn: Callable[[], float] = time.monotonic
                            ) -> Callable[[], ProcessReplica]:
    """A fleet ``factory`` spawning one worker process per call — what
    ``ServingFleet(factory, ...)`` needs for process isolation (a
    rejoining replica gets a FRESH process, page pool included)."""
    def factory() -> ProcessReplica:
        return ProcessReplica(builder,
                              heartbeat_timeout_s=heartbeat_timeout_s,
                              spawn_timeout_s=spawn_timeout_s,
                              time_fn=time_fn)
    return factory
