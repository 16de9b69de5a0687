"""Elastic serving (ISSUE 14): replica lifecycle + live request
migration. Migration identity pinned token-identical (greedy/sampled ×
prefix_cache on/off × speculative), the serve fault matrix
(crash/drain/slow/rejoin) with zero accepted-token loss, per-request
deadlines and retry budgets ending in honest timeout/failed statuses,
journal events + the run_analyze replica timeline, and the banked
serve_resilience evidence stage."""

import importlib.util
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
from distributed_lion_tpu.serve.engine import (
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve import replica_plane
from distributed_lion_tpu.serve.replica_plane import ServingFleet
from distributed_lion_tpu.train import resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CFG = GPT2Config.tiny()
_PARAMS = gpt2_init(jax.random.key(0), _CFG)
_MODEL = ServeModel.for_gpt2(_PARAMS, _CFG)


def _factory(**kw):
    base = dict(max_seqs=4, block_size=4, max_blocks_per_seq=8)
    base.update(kw)

    def factory():
        return ServingEngine(_MODEL, ServeConfig(**base))

    return factory


def _reqs(n=6, max_new=10, seed=3, **kw):
    rng = np.random.default_rng(seed)
    lens = (3, 9, 5, 14, 6, 4, 7, 11)[:n]
    return [Request(req_id=i,
                    tokens=list(map(int, rng.integers(1, _CFG.vocab_size,
                                                      L))),
                    max_new_tokens=max_new, seed=i, **kw)
            for i, L in enumerate(lens)]


def _clone(reqs):
    return [Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed,
                    prefix_group=r.prefix_group, deadline_s=r.deadline_s)
            for r in reqs]


@pytest.fixture(autouse=True)
def _clean_serve_faults():
    resilience.inject_fault("serve", [])
    yield
    resilience.inject_fault("serve", [])


_PLAIN = {}


def _plain(reqs, arrivals=None):
    """The never-disturbed run of ``reqs`` on one plain engine: what nine
    tests compare a fleet's output with, computed once a workload
    (tests/test_speculate.py's ``_plain_out`` idiom) and only read."""
    key = (tuple((r.req_id, tuple(r.tokens), r.max_new_tokens, r.seed)
                 for r in reqs), tuple(sorted((arrivals or {}).items())))
    if key not in _PLAIN:
        _PLAIN[key] = _factory()().run(_clone(reqs), arrivals)
    return _PLAIN[key]


def _fleet_run(specs, reqs, arrivals=None, replicas=2, eng_kw=None, **kw):
    if specs:
        resilience.inject_fault("serve", resilience.parse_serve_specs(specs))
    fleet = ServingFleet(_factory(**(eng_kw or {})), replicas=replicas,
                         **kw)
    done = fleet.run(_clone(reqs), arrivals or {})
    return fleet, done


ARRIVALS = {0: 0, 1: 0, 2: 1, 3: 2, 4: 3, 5: 6}


# ----------------------------------------------------------- fault grammar
def test_parse_serve_fault_grammar():
    assert resilience.parse_serve_fault("replica_crash:1:7") == \
        ("replica_crash", 1, 7, 0)
    assert resilience.parse_serve_fault("replica_drain:0") == \
        ("replica_drain", 0, 0, 0)
    assert resilience.parse_serve_fault("replica_drain:0:4") == \
        ("replica_drain", 0, 4, 0)
    # slow_tick's third field is MILLISECONDS, normalized to arg (due
    # tick 0) so the schedule pops uniformly through consume_due
    assert resilience.parse_serve_fault("slow_tick:1:250") == \
        ("slow_tick", 1, 0, 250)
    assert resilience.parse_serve_fault("replica_rejoin:2:9") == \
        ("replica_rejoin", 2, 9, 0)
    assert resilience.parse_serve_specs(
        "replica_crash:0:2, replica_rejoin:0:5") == [
        ("replica_crash", 0, 2, 0), ("replica_rejoin", 0, 5, 0)]
    for bad in ("replica_crash:0", "replica_rejoin:1", "slow_tick:1",
                "nonsense:0:1", "replica_crash:x:1", "replica_crash:-1:1",
                "replica_crash:0:1:2"):
        with pytest.raises(ValueError, match="serve fault"):
            resilience.parse_serve_fault(bad)


def test_consume_due_pops_only_due_entries():
    resilience.inject_fault("serve", [("replica_crash", 0, 2, 0),
                                      ("replica_rejoin", 0, 5, 0)])
    assert resilience.consume_due("serve", 1) == []
    assert resilience.consume_due("serve", 2) == [("replica_crash", 0, 2, 0)]
    assert resilience.fault("serve") == [("replica_rejoin", 0, 5, 0)]
    assert resilience.consume_due("serve", 9) == [("replica_rejoin", 0, 5, 0)]


# ------------------------------------------------------ recovery records
def test_recovery_record_resumes_token_identically():
    """THE migration primitive: a request cut mid-decode and re-admitted
    from its RecoveryRecord on a FRESH engine continues the exact same
    stream — the record is prompt + committed + seed and the pinned
    per-request keys do the rest."""
    reqs = _reqs()
    base = _plain(reqs)
    for cut in (1, 2, 4):
        a = _factory()()
        for r in _clone(reqs):
            a.submit(r)
        done = {}
        for _ in range(cut):
            for c in a.step():
                done[c.req_id] = c
        recs = a.export_records()
        for rec in recs:
            assert rec.tokens == reqs[rec.req_id].tokens  # original prompt
            assert rec.budget == 10
        b = _factory()()
        for rec in recs:
            b.submit(rec.to_request())
        ticks = 0
        while b.has_work():
            for c in b.step():
                done[c.req_id] = c
            ticks += 1
            assert ticks < 200
        for r in reqs:
            assert done[r.req_id].tokens == base[r.req_id].tokens, \
                (cut, r.req_id)
            assert done[r.req_id].reason == base[r.req_id].reason
        assert b.stats["resumed_requests"] > 0


def test_migration_at_page_horizon_matches_overflow():
    """Edge regression: a request crash-migrated when its history sits at
    (or past) the page-table horizon must reproduce the uninterrupted
    run's overflow eviction — same tokens AND same 'overflow' reason (the
    naive admit rule would have 'rejected' it, silently changing the
    status and, one tick earlier, dropping the final token)."""
    def eng():
        return ServingEngine(_MODEL, ServeConfig(max_seqs=2, block_size=4,
                                                 max_blocks_per_seq=2))

    toks = list(map(int, np.random.default_rng(1).integers(
        1, _CFG.vocab_size, 5)))
    base = eng().run([Request("big", list(toks), 64, 0)])["big"]
    assert base.reason == "overflow"
    for cut in range(1, 6):
        a = eng()
        a.submit(Request("big", list(toks), 64, 0))
        done = {}
        for _ in range(cut):
            for c in a.step():
                done[c.req_id] = c
        if "big" not in done:
            b = eng()
            for rec in a.export_records():
                b.submit(rec.to_request())
            while b.has_work():
                for c in b.step():
                    done[c.req_id] = c
        assert done["big"].tokens == base.tokens, cut
        assert done["big"].reason == "overflow", cut


# --------------------------------------------------- migration identity
# tier-1 runs the diagonal of the 2 x 2 (each value of each axis once); the
# other two are `slow`: 64 core-seconds (ROADMAP D9)
@pytest.mark.parametrize("prefix_cache,sampling", [
    (False, "greedy"),
    pytest.param(False, "stochastic", marks=pytest.mark.slow),
    pytest.param(True, "greedy", marks=pytest.mark.slow),
    (True, "stochastic")])
def test_crash_migration_identity(sampling, prefix_cache):
    """THE acceptance pin: a request crash-migrated at any tick yields
    the token-identical output stream of the never-migrated run — greedy
    and sampled, prefix_cache on and off (with the cache, the survivor
    re-prefills only the uncovered suffix; the outputs cannot tell)."""
    samp = (dict(temperature=0.0) if sampling == "greedy"
            else dict(temperature=0.9, top_k=40))
    eng_kw = dict(prefix_cache=prefix_cache, **samp)
    reqs = _reqs()
    base = _factory(**eng_kw)().run(_clone(reqs), dict(ARRIVALS))
    for crash_tick in (2, 5):
        fleet, done = _fleet_run(f"replica_crash:0:{crash_tick}", reqs,
                                 dict(ARRIVALS), eng_kw=eng_kw)
        for r in reqs:
            assert done[r.req_id].tokens == base[r.req_id].tokens, \
                (sampling, prefix_cache, crash_tick, r.req_id)
            assert done[r.req_id].reason == base[r.req_id].reason
        assert fleet.stats["replica_crashes"] == 1
        assert fleet.lifecycle()[0] == "departed"


def test_crash_migration_identity_speculative():
    """Migration × speculation: the ngram drafter's history re-syncs from
    the committed tokens on the survivor and the verify stream is the
    same pinned stream — outputs identical to the plain engine."""
    reqs = _reqs()
    base = _plain(reqs, dict(ARRIVALS))
    fleet, done = _fleet_run("replica_crash:0:3", reqs, dict(ARRIVALS),
                             eng_kw=dict(speculate="ngram:4"))
    for r in reqs:
        assert done[r.req_id].tokens == base[r.req_id].tokens, r.req_id
    assert fleet.stats["migrations"] > 0


def test_crash_mid_decode_loses_zero_accepted_tokens():
    """Zero-loss accounting, stated directly: every token the dead
    replica had committed by the crash tick appears in the final output
    (identity implies it, but the ledger must SAY so: the re-prefilled
    committed history is at least as long as what was accepted)."""
    reqs = _reqs()
    fleet, done = _fleet_run("replica_crash:0:4", reqs, dict(ARRIVALS))
    base = _plain(reqs, dict(ARRIVALS))
    assert fleet.stats["migrations"] > 0
    lost = sum(max(len(base[r.req_id].tokens) - len(done[r.req_id].tokens),
                   0) for r in reqs)
    assert lost == 0
    # the survivor really did resume mid-stream (not just restart):
    rep1 = fleet.replicas[1]
    assert rep1.engine is not None
    assert rep1.engine.stats["resumed_tokens"] > 0


def test_migrated_sharer_does_not_free_survivor_shared_pages():
    """Under --prefix_cache, a migrated request shares the survivor's
    cached pages like any other sharer; after the workload drains, the
    survivor's pool accounting must be exact — every live ref belongs to
    the cache, pages conserved (the engine-level twin of the mid-fuzz
    crash op in tests/test_serve.py)."""
    reqs = _reqs()
    fleet, done = _fleet_run("replica_crash:0:3", reqs, dict(ARRIVALS),
                             eng_kw=dict(prefix_cache=True, num_blocks=64))
    surv = fleet.replicas[1].engine
    assert surv is not None and all(s is None for s in surv.slots)
    bt = surv.tables
    assert bt.physical_pages + bt.free_blocks == bt.num_blocks
    assert int(bt.refs.sum()) == bt.physical_pages


# ------------------------------------------------------------ fault matrix
def test_drain_stops_admission_and_finishes_residents():
    reqs = _reqs()
    resilience.inject_fault("serve",
                            resilience.parse_serve_specs("replica_drain:0:3"))
    fleet = ServingFleet(_factory(), replicas=2)
    todo = _clone(reqs)
    done = {}
    arrivals = dict(ARRIVALS)
    seen_draining = probed = False
    while todo or fleet.has_work():
        while todo and arrivals.get(todo[0].req_id, 0) <= fleet.tick_no:
            fleet.submit(todo.pop(0))
        for c in fleet.step():
            done[c.req_id] = c
        if fleet.lifecycle()[0] == "draining" and not probed:
            seen_draining = probed = True
            fleet.submit(Request("probe", [1, 2, 3], 2, 0))
        if probed:
            # a draining replica admits NOTHING new
            assert "probe" not in fleet.replicas[0].assigned
    assert seen_draining
    assert fleet.lifecycle()[0] == "departed"
    assert "probe" in done  # served by the OTHER replica
    base = _plain(reqs, dict(ARRIVALS))
    for r in reqs:
        assert done[r.req_id].tokens == base[r.req_id].tokens
    # the drained replica's residents finished in place: nothing failed,
    # nothing timed out, and migrations only ever moved PENDING requests
    assert fleet.stats["failed"] == 0 and fleet.stats["timeouts"] == 0


def test_slow_replica_detected_and_routed_around(monkeypatch):
    # A counted clock: every reading costs 1 ms and the injected
    # straggler's sleep advances it by what it asked for, so "four times
    # the peers' median" is decided by the injected 40 ms and not by how
    # busy the box is (the wall clock failed this under six workers).
    clock = [0.0]

    def now():
        clock[0] += 1e-3
        return clock[0]

    def sleep(seconds):
        clock[0] += seconds

    monkeypatch.setattr(replica_plane, "time",
                        types.SimpleNamespace(sleep=sleep))
    reqs = _reqs(n=8, max_new=8)
    arrivals = {i: i for i in range(len(reqs))}
    fleet, done = _fleet_run("slow_tick:0:40", reqs, arrivals,
                             slow_min_ticks=3, time_fn=now)
    assert fleet.stats["slow_detected"] >= 1
    r0, r1 = fleet.replicas
    assert r0.admissions < r1.admissions  # new work routed around
    base = _plain(reqs)
    for r in reqs:  # outputs unaffected — slowness changes placement only
        assert done[r.req_id].tokens == base[r.req_id].tokens


def test_rejoin_serves_from_fresh_pool():
    reqs = _reqs(n=8, max_new=8)
    arrivals = {i: i for i in range(len(reqs))}
    resilience.inject_fault("serve", resilience.parse_serve_specs(
        "replica_crash:0:2,replica_rejoin:0:4"))
    fleet = ServingFleet(_factory(), replicas=2, rejoin_probe_ticks=2)
    todo = _clone(reqs)
    done = {}
    probation_admissions = None
    while todo or fleet.has_work():
        while todo and arrivals.get(todo[0].req_id, 0) <= fleet.tick_no:
            fleet.submit(todo.pop(0))
        for c in fleet.step():
            done[c.req_id] = c
        if fleet.lifecycle()[0] == "rejoining":
            # probation gates ROUTING: the healthy peer is admitting, so
            # the unprobed fresh engine gets no new work yet
            probation_admissions = fleet.replicas[0].engine.stats[
                "prefill_dispatches"]
            assert probation_admissions == 0
    assert probation_admissions is not None  # probation was observed
    assert fleet.stats["replica_rejoins"] == 1
    rep0 = fleet.replicas[0]
    assert rep0.engine is not None
    # after probation the fresh engine's stats count post-rejoin work
    assert rep0.engine.stats["prefill_dispatches"] > 0
    assert fleet.lifecycle() == ["healthy", "healthy"]
    base = _plain(reqs)
    for r in reqs:
        assert done[r.req_id].tokens == base[r.req_id].tokens


def test_retry_budget_exhaustion_fails_loudly():
    """A request whose every home crashes exhausts its retry budget and
    completes as ``failed`` with its partial output attached — never
    silent loss, never an infinite requeue loop."""
    reqs = _reqs()
    base = _plain(reqs)
    fleet, done = _fleet_run(
        "replica_crash:0:2,replica_rejoin:0:4,replica_crash:1:3,"
        "replica_crash:0:7", reqs, max_retries=0)
    assert fleet.stats["failed"] > 0
    failed = [c for c in done.values() if c.reason == "failed"]
    assert failed
    for c in failed:  # partial output = a prefix of the true stream
        assert c.tokens == base[c.req_id].tokens[:len(c.tokens)]
    # every request completed with SOME honest status
    assert set(done) == {r.req_id for r in reqs}


def test_fleet_refuses_unroutable_queue():
    """All replicas dead, no scheduled rejoin: the fleet refuses loudly
    instead of spinning forever."""
    reqs = _reqs(n=4)
    resilience.inject_fault("serve", resilience.parse_serve_specs(
        "replica_crash:0:1,replica_crash:1:2"))
    fleet = ServingFleet(_factory(), replicas=2, max_retries=5)
    with pytest.raises(RuntimeError, match="no admitting replica"):
        fleet.run(_clone(reqs))


def test_prefix_group_affinity_routing():
    """Requests of one prefix_group land on ONE replica (its prefix
    cache accumulates their shared pages); untagged requests still
    balance by load."""
    fleet = ServingFleet(_factory(prefix_cache=True), replicas=2)
    rng = np.random.default_rng(7)
    sys_p = list(map(int, rng.integers(1, _CFG.vocab_size, 9)))
    fleet.submit(Request("u0", [1, 2, 3], 6, 0))
    fleet.submit(Request("u1", [4, 5], 6, 0))
    fleet.step()
    fleet.submit(Request("g0", list(sys_p), 6, 0, prefix_group="sys"))
    fleet.step()
    home = fleet._home["sys"]
    assert "g0" in fleet.replicas[home].assigned
    for i in (1, 2):
        fleet.submit(Request(f"g{i}", list(sys_p), 6, i,
                             prefix_group="sys"))
        fleet.step()
        assert f"g{i}" in fleet.replicas[home].assigned
    while fleet.has_work():
        fleet.step()
    # affinity did what it exists for: the home replica's cache served
    # the group's shared prefix from one physical copy
    assert fleet.replicas[home].engine.stats["prefix_hits"] >= 2


# ---------------------------------------------------------------- deadlines
def test_pending_request_past_deadline_times_out_without_prefill():
    eng = _factory()()
    eng.submit(Request("d", [1, 2, 3], 8, 0, deadline_s=1e-6))
    time.sleep(0.01)
    done = {c.req_id: c for c in eng.step()}
    assert done["d"].reason == "timeout" and done["d"].tokens == []
    assert eng.stats["prefill_dispatches"] == 0  # expired before admit
    assert eng.stats["timeouts"] == 1


def test_deadline_times_out_mid_decode_under_slow_tick(tmp_path):
    """The satellite pin: a request with a wall-clock deadline on a
    slow-ticking replica is evicted MID-decode with the honest timeout
    status and its partial output — journaled like any other evict."""
    from distributed_lion_tpu.train import journal as journal_mod

    resilience.inject_fault("serve",
                            resilience.parse_serve_specs("slow_tick:0:60"))
    jrnl = journal_mod.Journal(str(tmp_path))
    journal_mod.install(jrnl)
    try:
        fleet = ServingFleet(_factory(), replicas=1)
        done = fleet.run([Request("slow", [1, 2, 3, 4], 64, 0,
                                  deadline_s=0.3)])
    finally:
        journal_mod.uninstall(jrnl)
        jrnl.close()
    c = done["slow"]
    assert c.reason == "timeout"
    assert 0 < len(c.tokens) < 64  # started decoding, then cut off
    evicts = [r for r in jrnl.tail() if r.get("name") == "serve/evict"]
    assert any(r.get("reason") == "timeout" for r in evicts)
    # the fleet puts the RESIDENT deadline miss on the replica timeline
    # too — an incident report must not omit it
    touts = [r for r in jrnl.tail() if r.get("name") == "request_timeout"]
    assert touts and touts[0]["req_id"] == "slow" \
        and "replica" in touts[0] and touts[0]["committed"] == len(c.tokens)


def test_api_deadline_validation_and_echo(tmp_path):
    from distributed_lion_tpu.serve import api

    inp = tmp_path / "requests.jsonl"
    inp.write_text(
        '{"id": "a", "tokens": [1, 2, 3], "max_new_tokens": 2, '
        '"deadline_s": 30.0}\n'
        '{"id": "b", "tokens": [4, 5], "max_new_tokens": 2}\n')
    out = tmp_path / "responses.jsonl"
    records = api.serve_request_file(_factory()(), str(inp), str(out))
    assert records[0]["deadline_s"] == 30.0
    assert "deadline_s" not in records[1]
    for bad in ('{"id": "x", "tokens": [1], "deadline_s": 0}\n',
                '{"id": "x", "tokens": [1], "deadline_s": -1}\n',
                '{"id": "x", "tokens": [1], "deadline_s": true}\n',
                '{"id": "x", "tokens": [1], "deadline_s": "fast"}\n'):
        p = tmp_path / "bad.jsonl"
        p.write_text(bad)
        with pytest.raises(ValueError, match="deadline_s"):
            api.load_request_file(str(p))


# ------------------------------------------------- journal + run_analyze
def test_journal_events_and_replica_timeline(tmp_path):
    from distributed_lion_tpu.train import journal as journal_mod

    jrnl = journal_mod.Journal(str(tmp_path))
    journal_mod.install(jrnl)
    try:
        reqs = _reqs(n=8, max_new=8)
        _fleet_run("replica_crash:0:2,replica_rejoin:0:5", reqs,
                   {i: i for i in range(len(reqs))})
    finally:
        journal_mod.uninstall(jrnl)
        jrnl.close()
    events = [r for r in jrnl.tail() if r["kind"] == "event"]
    names = {r["name"] for r in events}
    assert {"replica_left", "replica_rejoined", "request_migrated"} <= names
    left = next(r for r in events if r["name"] == "replica_left")
    assert left["cause"] == "injected_crash" and "residents" in left \
        and left["alive"] == 1 and left["world"] == 2
    mig = next(r for r in events if r["name"] == "request_migrated")
    for k in ("req_id", "from_replica", "to_replica", "committed",
              "attempt", "tick", "latency_ticks"):
        assert k in mig, k
    # the journal file passes the strict schema...
    spec = importlib.util.spec_from_file_location(
        "vm_rp", os.path.join(REPO, "scripts", "validate_metrics.py"))
    vm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(vm)
    assert vm.validate_journal_file(
        str(tmp_path / "journal_rank0.jsonl")) == []
    # ...and run_analyze renders the replica timeline beside membership
    spec = importlib.util.spec_from_file_location(
        "ra_rp", os.path.join(REPO, "distributed_lion_tpu", "cli",
                              "run_analyze.py"))
    ra = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ra)
    report = ra.analyze_dir(str(tmp_path))
    rows = report["replicas"]
    assert [r for r in rows if r["event"] == "replica_left"]
    assert [r for r in rows if r["event"] == "request_migrated"]
    rendered = ra.render(report)
    assert "replica timeline:" in rendered
    assert "replica 0: replica_left" in rendered


# ---------------------------------------------------------------- the CLI
def test_run_serve_cli_fleet_smoke(tmp_path):
    from distributed_lion_tpu.cli.run_serve import main

    reqs = tmp_path / "requests.jsonl"
    reqs.write_text(
        '{"id": "r1", "prompt": "ab", "max_new_tokens": 3, '
        '"deadline_s": 60.0}\n'
        '{"id": "r2", "prompt": "cd", "max_new_tokens": 3, '
        '"arrival_tick": 2}\n')
    out = tmp_path / "responses.jsonl"
    records = main(["--model_family", "gpt2", "--model_name", "tiny",
                    "--requests", str(reqs), "--out", str(out),
                    "--temperature", "0", "--max_seqs", "2",
                    "--block_size", "4", "--replicas", "2",
                    "--inject_serve", "replica_crash:0:1"])
    assert [r["id"] for r in records] == ["r1", "r2"]
    assert all(r["n_generated"] == 3 for r in records)
    assert records[0]["deadline_s"] == 60.0
    # identical to the single-engine run of the same file
    solo = main(["--model_family", "gpt2", "--model_name", "tiny",
                 "--requests", str(reqs), "--out", str(out),
                 "--temperature", "0", "--max_seqs", "2",
                 "--block_size", "4"])
    assert [r["tokens"] for r in records] == [r["tokens"] for r in solo]
    with pytest.raises(ValueError, match="replicas"):
        main(["--model_family", "gpt2", "--model_name", "tiny",
              "--requests", str(reqs), "--inject_serve",
              "replica_crash:0:1"])


# ------------------------------------------------- the evidence artifact
def _load_ce():
    spec = importlib.util.spec_from_file_location(
        "ce_rp", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    return ce


def test_banked_artifact_passes_serve_resilience_stage():
    """The committed CPU artifact satisfies the ISSUE 14 stage: strict
    schema, all eight markers, >= 3 crash cut points with zero loss and
    real migrations, slow-replica p99 above its clean peer — the gate
    runbook stage 5l re-judges after the on-chip recapture."""
    ce = _load_ce()
    assert ce.serve_resilience_ok()
    with open(ce.SERVE_ARTIFACT) as f:
        doc = json.load(f)
    sec = doc["serve_resilience"]
    assert len(sec["crash_matrix"]) >= 3
    assert all(r["tokens_lost"] == 0 for r in sec["crash_matrix"])
    assert sec["slow"]["p99_ms_slow_replica"] > \
        sec["slow"]["p99_ms_clean_replica"]


def test_serve_resilience_stage_rejects_bad_artifacts(tmp_path):
    ce = _load_ce()
    with open(ce.SERVE_ARTIFACT) as f:
        good = json.load(f)
    p = tmp_path / "serving.json"

    def reject(mutate):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        p.write_text(json.dumps(doc))
        assert not ce.serve_resilience_ok(str(p))

    # artifact predates ISSUE 14 entirely (also a schema violation now)
    reject(lambda d: d.pop("serve_resilience"))
    # each identity/behavior marker flips the stage
    for k in ("migrated_identity_greedy", "migrated_identity_sampled",
              "migrated_identity_speculative",
              "migrated_identity_prefix_cache", "zero_token_loss",
              "drain_completes_residents", "slow_detected_and_routed",
              "rejoin_serves"):
        reject(lambda d, k=k: d["serve_resilience"]["markers"].update(
            {k: False}))
    # a crash row that lost tokens / was not identical / never migrated
    reject(lambda d: d["serve_resilience"]["crash_matrix"][0].update(
        tokens_lost=3))
    reject(lambda d: d["serve_resilience"]["crash_matrix"][1].update(
        identical=False))
    reject(lambda d: [r.update(migrated=0)
                      for r in d["serve_resilience"]["crash_matrix"]])
    # too few cut points ('crash at any tick' needs a matrix, not a point)
    reject(lambda d: d["serve_resilience"].update(
        crash_matrix=d["serve_resilience"]["crash_matrix"][:1]))
    # the slow leg's measured story must hold
    reject(lambda d: d["serve_resilience"]["slow"].update(
        p99_ms_slow_replica=0.0))
    # strict schema: a non-int loss count (validate_metrics delegation)
    reject(lambda d: d["serve_resilience"]["crash_matrix"][0].update(
        tokens_lost="none"))
    # the untouched artifact still passes from the tmp copy
    p.write_text(json.dumps(good))
    assert ce.serve_resilience_ok(str(p))
