"""Process-isolated replicas (ISSUE 20 layer b): real OS processes
behind the fleet's engine duck surface. The acceptance matrix — a REAL
SIGKILL mid-decode under live socket traffic, greedy + sampled ×
prefix_cache on/off, zero accepted-token loss and token-identical
migrated outputs — plus heartbeat-miss strikes declaring a stalled
child dead, the wire framing/codec edges, and the ``--replica_procs``
CLI path with a killed child."""

import json
import threading

import jax
import numpy as np
import pytest

from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
from distributed_lion_tpu.serve import fleet_proc, net
from distributed_lion_tpu.serve.engine import (
    RecoveryRecord,
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.replica_plane import ServingFleet
from distributed_lion_tpu.train import resilience

_CFG = GPT2Config.tiny()
_PARAMS = gpt2_init(jax.random.key(0), _CFG)
_MODEL = ServeModel.for_gpt2(_PARAMS, _CFG)

_SERVE = dict(max_seqs=4, block_size=4, max_blocks_per_seq=8)


def _engine(**kw):
    return ServingEngine(_MODEL, ServeConfig(**{**_SERVE, **kw}))


def _builder(**kw):
    # init_seed 0 == the module-level _PARAMS: the child process builds
    # the SAME weights from the same seed, no checkpoint file involved
    return {"kind": "gpt2_tiny", "init_seed": 0,
            "serve": {**_SERVE, **kw}}


def _reqs(n=4, max_new=10, groups=False):
    rng = np.random.default_rng(23)
    shared = [int(t) for t in rng.integers(1, _CFG.vocab_size, 6)]
    out = []
    for i in range(n):
        toks = [int(t) for t in rng.integers(1, _CFG.vocab_size, 3 + i)]
        d = {"id": f"p{i}", "max_new_tokens": max_new, "seed": i}
        if groups and i % 2 == 0:
            d.update(tokens=shared + toks, prefix_group="sys")
        else:
            d["tokens"] = toks
        out.append(d)
    return out


def _as_request(d):
    return Request(req_id=d["id"], tokens=list(d["tokens"]),
                   max_new_tokens=d["max_new_tokens"],
                   seed=d.get("seed", 0),
                   prefix_group=d.get("prefix_group"))


@pytest.fixture(autouse=True)
def _clean_serve_faults():
    resilience.inject_fault("serve", [])
    yield
    resilience.inject_fault("serve", [])


# ------------------------------------------------------- framing + codecs
def test_frame_stream_edges():
    buf = bytearray()
    assert fleet_proc._take_frame(buf) is None          # empty
    frame = fleet_proc._HEADER.pack(7) + b'{"a": 1}'[:7]
    buf += frame[:5]
    assert fleet_proc._take_frame(buf) is None          # split mid-frame
    buf += frame[5:]
    with pytest.raises(fleet_proc.ReplicaGone, match="corrupt frame"):
        fleet_proc._take_frame(bytearray(
            fleet_proc._HEADER.pack(3) + b"}{!"))       # garbage payload
    with pytest.raises(fleet_proc.ReplicaGone, match="exceeds"):
        fleet_proc._take_frame(bytearray(
            fleet_proc._HEADER.pack(fleet_proc.MAX_FRAME_BYTES + 1)))


def test_record_codec_ships_deadlines_as_remaining_seconds():
    rec = RecoveryRecord(req_id="d", tokens=[1, 2], committed=[9],
                         seed=3, budget=8, prefix_group="g",
                         deadline_at=107.5)
    wire = fleet_proc.record_to_wire(rec, now=100.0)
    assert wire["deadline_remaining_s"] == 7.5          # never absolute
    back = fleet_proc.record_from_wire(wire, now=20.0)  # other epoch
    assert back.deadline_at == 27.5
    assert (back.tokens, back.committed, back.seed, back.budget,
            back.prefix_group) == ([1, 2], [9], 3, 8, "g")
    free = fleet_proc.record_to_wire(
        RecoveryRecord("f", [1], [], 0, None), now=0.0)
    assert "deadline_remaining_s" not in free and "budget" not in free


# --------------------------------------------------- single-replica round trip
def test_process_replica_round_trip_matches_in_process_engine():
    reqs = _reqs(n=3)
    offline = _engine().run([_as_request(d) for d in reqs])
    rep = fleet_proc.ProcessReplica(_builder())
    try:
        assert rep.pid != 0 and rep.proc.poll() is None  # a real process
        for d in reqs:
            rep.submit(_as_request(d))
        assert [r.req_id for r in rep.pending] == [d["id"] for d in reqs]
        done = {}
        ticks = 0
        while rep.has_work():
            for c in rep.step():
                done[c.req_id] = c
            ticks += 1
            assert ticks < 100
        for d in reqs:
            assert done[d["id"]].tokens == offline[d["id"]].tokens
            assert done[d["id"]].reason == offline[d["id"]].reason
        assert not rep.pending and rep.export_records() == []
        assert rep.stats["prefill_dispatches"] > 0  # stats mirror rode over
    finally:
        rep.close()
    assert rep.proc.poll() is not None              # reaped, not leaked
    with pytest.raises(fleet_proc.ReplicaGone, match="closed"):
        rep.step()


# --------------------------------------------------- THE acceptance matrix
# tier-1 runs the diagonal of the 2 x 2 (each value of each axis once); the
# other two are `slow`: 109 core-seconds of children that import JAX, in a
# run the driver cut at its limit (ROADMAP D9)
@pytest.mark.parametrize("prefix_cache,sampling", [
    (False, "greedy"),
    pytest.param(False, "stochastic", marks=pytest.mark.slow),
    pytest.param(True, "greedy", marks=pytest.mark.slow),
    (True, "stochastic")])
def test_sigkill_mid_decode_under_live_socket_traffic(sampling,
                                                      prefix_cache):
    """A replica child is SIGKILLed for real AFTER its engine stepped
    (tokens were truly sampled, the reply never sent) while a live
    socket client is mid-stream. The fleet sees EOF, declares the
    process dead, migrates from its shadow — and every response is
    token-identical to the never-killed offline run: zero accepted
    tokens lost, greedy and sampled, prefix cache on and off."""
    samp = (dict(temperature=0.0) if sampling == "greedy"
            else dict(temperature=0.8, top_k=20))
    eng_kw = dict(prefix_cache=prefix_cache, **samp)
    reqs = _reqs(groups=prefix_cache)
    offline = _engine(**eng_kw).run([_as_request(d) for d in reqs])
    resilience.inject_fault(
        "serve", resilience.parse_serve_specs("replica_kill:0:2"))
    fleet = ServingFleet(
        fleet_proc.process_replica_factory(_builder(**eng_kw)),
        replicas=2)
    srv = net.ServeServer(fleet, port=0)
    th = threading.Thread(target=srv.run, kwargs={"max_wall_s": 300.0},
                          daemon=True)
    th.start()
    try:
        out = net.drive_open_loop(*srv.addr, records=reqs, tick_s=0.0,
                                  max_wall_s=240.0)
    finally:
        srv.stop = True
        th.join(timeout=30)
        srv.close()
        fleet.close()
    lost = 0
    for d in reqs:
        got = out["responses"][d["id"]]["tokens"]
        assert got == offline[d["id"]].tokens, (sampling, prefix_cache,
                                                d["id"])
        lost += max(len(offline[d["id"]].tokens) - len(got), 0)
    assert lost == 0
    assert fleet.stats["replica_crashes"] == 1
    assert fleet.stats["replicas_declared_dead"] == 1
    assert fleet.stats["migrations"] >= 1
    assert fleet.stats["failed"] == 0 and fleet.stats["timeouts"] == 0
    assert fleet.lifecycle()[0] == "departed"


def test_heartbeat_stall_strikes_then_declares_dead(tmp_path):
    """A child that stalls (alive, not replying) accumulates
    ``replica_heartbeat_missed`` strikes and is declared dead at the
    miss budget — its requests migrate and finish token-identically on
    the healthy peer, with the journal carrying the strike trail."""
    from distributed_lion_tpu.train import journal as journal_mod

    reqs = _reqs(n=4, max_new=8)
    offline = _engine().run([_as_request(d) for d in reqs])
    jrnl = journal_mod.Journal(str(tmp_path))
    journal_mod.install(jrnl)
    try:
        fleet = ServingFleet(
            fleet_proc.process_replica_factory(_builder()),
            replicas=2, heartbeat_max_misses=2)
        # warm both children first (their first engine.step compiles) so
        # a tight heartbeat window only ever times a stalled reply
        fleet.run([Request("warm0", [1, 2], 2, 0),
                   Request("warm1", [3, 4], 2, 0)])
        done = {}
        stalled = False
        todo = [_as_request(d) for d in reqs]
        while todo or fleet.has_work():
            while todo:
                fleet.submit(todo.pop(0))
            if not stalled and all(
                    len(r.assigned) > 0 for r in fleet.replicas):
                for rep in fleet.replicas:
                    rep.engine.heartbeat_timeout_s = 0.3
                fleet.replicas[0].engine.stall_next_tick(3000)
                stalled = True
            for c in fleet.step():
                done[c.req_id] = c
        fleet.close()
    finally:
        journal_mod.uninstall(jrnl)
        jrnl.close()
    assert stalled
    assert fleet.stats["heartbeat_misses"] >= 2
    assert fleet.stats["replicas_declared_dead"] == 1
    for d in reqs:
        assert done[d["id"]].tokens == offline[d["id"]].tokens, d["id"]
    events = [r for r in jrnl.tail() if r.get("kind") == "event"]
    misses = [r for r in events if r["name"] == "replica_heartbeat_missed"]
    assert len(misses) >= 2
    assert all(r["replica"] == 0 and r["max_misses"] == 2 for r in misses)
    dead = next(r for r in events if r["name"] == "replica_declared_dead")
    assert dead["cause"] == "heartbeat_lost" and dead["misses"] == 2
    left = next(r for r in events if r["name"] == "replica_left")
    assert left["cause"] == "heartbeat_lost"


# ----------------------------------------------------------------- the CLI
def test_run_serve_cli_replica_procs_with_injected_kill(tmp_path):
    """``--replica_procs`` end to end: two worker processes serve a
    request file, one is SIGKILLed mid-decode by ``--inject_serve
    replica_kill``, and the responses match the in-process single-engine
    run — the CLI wiring of the whole layer."""
    from distributed_lion_tpu.cli.run_serve import main

    reqs = tmp_path / "requests.jsonl"
    reqs.write_text("".join(
        json.dumps({"id": f"c{i}", "tokens": [7 + i, 3, 5 + i],
                    "max_new_tokens": 6, "seed": i}) + "\n"
        for i in range(3)))
    out = tmp_path / "responses.jsonl"
    base = ["--model_family", "gpt2", "--model_name", "tiny",
            "--requests", str(reqs), "--out", str(out),
            "--temperature", "0", "--max_seqs", "2", "--block_size", "4"]
    records = main(base + ["--replicas", "2", "--replica_procs",
                           "--inject_serve", "replica_kill:0:2"])
    solo = main(base)
    assert [r["tokens"] for r in records] == [r["tokens"] for r in solo]
    assert all(r["n_generated"] == 6 for r in records)
