"""Process-isolated serving replica: the child-side worker.

``python -m distributed_lion_tpu.serve.replica_worker`` is spawned once
per replica by :class:`serve.fleet_proc.ProcessReplica` and speaks the
length-prefixed JSON protocol over its stdin/stdout pipes (framing and
codecs live in fleet_proc — ONE definition for both ends). Protocol
stdout is dup'd away and fd 1 redirected to stderr before the engine
builds, so a stray library print can never corrupt the frame stream.

Builder specs (the ``build`` command's payload):

- ``{"kind": "gpt2_tiny", "init_seed": 0, "serve": {...}}`` — a
  deterministic tiny GPT-2 (``GPT2Config.tiny()`` + ``gpt2_init`` from
  the seed) over ``ServeConfig(**serve)``; what the fleet tests use, and
  why a killed-and-respawned replica is the SAME model: identical seed,
  identical weights, no checkpoint file needed.
- ``{"kind": "cli", "gen": {...}, "serve": {...}}`` — the full
  ``run_serve`` build surface (GenerateArguments + ServeArguments
  field dicts); the child loads the checkpoint itself, so N replica
  processes each own their weights (real process isolation — the price
  of surviving a real SIGKILL is not sharing an address space).

Per ``tick`` command the worker applies control ops (the
``--inject_serve`` path riding the transport), re-stamps wire deadlines
against its OWN monotonic clock, admits submits, steps the engine once,
and replies with completions + the RecoveryRecord shadow + stats. The
``kill_after_step`` control raises genuine mid-decode death: the engine
steps (the decode dispatch really runs, tokens are really sampled) and
the process SIGKILLs itself BEFORE the reply — from the parent's side,
a replica that did work and then vanished, which is exactly the window
the zero-token-loss migration guarantee must cover.

Orphan discipline: every read polls with a bounded window and EOF on
stdin means the parent is gone — the worker exits instead of lingering
as a zombie decode loop (and graft-check DLT012 holds: no unbounded
blocking reads in serve/).
"""

from __future__ import annotations

import os
import signal
import sys
import time


def _build_engine(builder: dict):
    """Builder spec → a fresh ServingEngine owned by THIS process."""
    kind = builder.get("kind")
    if kind == "gpt2_tiny":
        import jax

        from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
        from distributed_lion_tpu.serve.engine import (
            ServeConfig,
            ServeModel,
            ServingEngine,
        )

        cfg = GPT2Config.tiny()
        params = gpt2_init(jax.random.key(int(builder.get("init_seed", 0))),
                           cfg)
        model = ServeModel.for_gpt2(params, cfg)
        return ServingEngine(model, ServeConfig(**builder.get("serve", {})))
    if kind == "cli":
        from distributed_lion_tpu.cli.run_generate import GenerateArguments
        from distributed_lion_tpu.cli.run_serve import (
            ServeArguments,
            build_engine,
        )

        gen_args = GenerateArguments(**builder.get("gen", {}))
        serve_args = ServeArguments(**builder.get("serve", {}))
        _, engine = build_engine(gen_args, serve_args)
        return engine
    raise ValueError(f"unknown replica builder kind {kind!r}")


def main(time_fn=time.monotonic, sleep_fn=time.sleep) -> int:
    from distributed_lion_tpu.serve.fleet_proc import (
        completion_to_wire,
        read_frame_blocking,
        record_to_wire,
        request_from_wire,
        write_frame,
    )

    # protocol hygiene: keep the REAL stdout for frames, point fd 1 at
    # stderr so any stray print (jax warnings, user code) lands there
    proto = os.fdopen(os.dup(sys.stdout.fileno()), "wb")
    sys.stdout.flush()
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    in_fd = sys.stdin.fileno()
    rbuf = bytearray()

    hello = read_frame_blocking(in_fd, buf=rbuf)
    if hello is None or hello.get("cmd") != "build":
        return 1
    # the worker inherits its platform from the environment and never
    # picks one: a worker that cannot open its device (or build its
    # engine) says so in its hello and exits non-zero
    try:
        from distributed_lion_tpu.parallel.mesh import force_cpu_platform
        from distributed_lion_tpu.utils.compile_cache import (
            enable_compilation_cache,
        )

        force_cpu_platform()  # an inherited DLION_PLATFORM request
        enable_compilation_cache()
        engine = _build_engine(hello["builder"])
    except Exception as e:
        write_frame(proto, {"ok": False, "pid": os.getpid(),
                            "error": f"{type(e).__name__}: {e}"})
        raise
    write_frame(proto, {"ok": True, "pid": os.getpid()})

    while True:
        msg = read_frame_blocking(in_fd, buf=rbuf)
        if msg is None:
            return 0   # parent hung up — an orphan must exit, not decode
        cmd = msg.get("cmd")
        if cmd == "exit":
            return 0
        if cmd == "chains":
            export = getattr(engine, "export_prefix_chains", None)
            write_frame(proto, {"chains": export() if export else []})
            continue
        if cmd != "tick":
            write_frame(proto, {"error": f"unknown cmd {cmd!r}"})
            continue
        kill_after_step = False
        for ctl in msg.get("controls", ()):
            op = ctl.get("op")
            if op == "kill_after_step":
                kill_after_step = True
            elif op == "die_now":
                os.kill(os.getpid(), signal.SIGKILL)
            elif op == "stall_ms":
                # straggler injection: the reply (= the heartbeat) is
                # late by this much; the engine itself is untouched
                sleep_fn(float(ctl.get("ms", 0)) / 1000.0)
            elif op == "drop_pending":
                engine.pending.clear()
        now = time_fn()
        for d in msg.get("submit", ()):
            req = request_from_wire(d)
            remaining = d.get("deadline_remaining_s")
            engine.submit(req, deadline_at=(
                now + float(remaining) if remaining is not None else None))
        completions = engine.step()
        if kill_after_step:
            # mid-decode death, for real: work happened, tokens were
            # sampled, and the reply never arrives — the parent sees EOF
            # and must recover every accepted token from its shadow
            os.kill(os.getpid(), signal.SIGKILL)
        now = time_fn()
        write_frame(proto, {
            "tick_seq": msg.get("tick_seq"),
            "completions": [completion_to_wire(c) for c in completions],
            "records": [record_to_wire(r, now)
                        for r in engine.export_records()],
            "stats": dict(engine.stats),
            "pending_ids": [r.req_id for r in engine.pending],
            "has_work": engine.has_work(),
        })


if __name__ == "__main__":
    sys.exit(main())
