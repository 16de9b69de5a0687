"""Serve observability plane (ISSUE 17): the LogHistogram sketch pinned
against numpy (accuracy bound + merge algebra), the request clocks, the
SLO monitor's burn-rate/breach semantics under an injected clock, the
metrics-on == metrics-off bit-identity matrix (the plane must be
observationally free), the workload generator's determinism + schema,
the timing columns on every terminal status, and the banked slo section
of the serving evidence artifact."""

import importlib.util
import json
import os

import numpy as np
import pytest

from distributed_lion_tpu.serve.metrics import (
    LogHistogram,
    RequestTimes,
    ServeMetrics,
    SLOMonitor,
    TickLatencyWindow,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the sketch
def test_sketch_percentiles_match_numpy_within_bin_bound():
    """Percentile queries answer within the geometric-bin error bound: a
    value lands in a bin of width ratio base = 10**(1/bins_per_decade)
    and is reported as the bin's geometric midpoint, so the relative
    error is at most sqrt(base) - 1 (~3.7% at 32 bins/decade) plus the
    rank discretization — pinned at 8% against numpy on a heavy-tail
    sample, the shape serve latencies actually have."""
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=2.0, sigma=1.2, size=5000)
    sk = LogHistogram()
    for v in samples:
        sk.add(float(v))
    for q in (50.0, 95.0, 99.0):
        ref = float(np.percentile(samples, q))
        got = sk.percentile(q)
        assert abs(got - ref) / ref < 0.08, (q, got, ref)
    s = sk.summary()
    assert s["count"] == 5000
    assert s["min"] == pytest.approx(float(samples.min()))
    assert s["max"] == pytest.approx(float(samples.max()))
    assert s["mean"] == pytest.approx(float(samples.mean()))


def test_sketch_merge_is_associative_and_matches_union():
    """merge is pure bin-count addition: (a+b)+c == a+(b+c) == the
    sketch built from the concatenated samples, bin-for-bin — the
    property that lets a fleet fold replicas in any order."""
    rng = np.random.default_rng(11)
    parts = [rng.lognormal(1.0, s, size=400) for s in (0.5, 1.0, 1.5)]
    sks = []
    for p in parts:
        sk = LogHistogram()
        for v in p:
            sk.add(float(v))
        sks.append(sk)
    union = LogHistogram()
    for v in np.concatenate(parts):
        union.add(float(v))
    left = sks[0].merge(sks[1]).merge(sks[2])
    right = sks[0].merge(sks[1].merge(sks[2]))
    for m in (left, right):
        np.testing.assert_array_equal(m.counts, union.counts)
        assert m.n == union.n
        assert m.vmin == union.vmin and m.vmax == union.vmax
        assert m.percentile(99.0) == union.percentile(99.0)
    # inputs are untouched (merge is pure, not in-place)
    assert sks[0].n == 400
    # layout mismatch refuses instead of silently mis-binning
    with pytest.raises(ValueError, match="layout"):
        sks[0].merge(LogHistogram(bins_per_decade=16))


def test_sketch_refuses_bad_samples_and_empty_is_honest():
    sk = LogHistogram()
    with pytest.raises(ValueError, match="non-finite"):
        sk.add(float("nan"))
    with pytest.raises(ValueError, match="non-finite"):
        sk.add(float("inf"))
    with pytest.raises(ValueError, match="count"):
        sk.add(1.0, count=0)
    assert sk.percentile(99.0) == 0.0
    assert sk.summary()["count"] == 0
    # out-of-range values land in the under/overflow buckets, clamped to
    # the observed extrema on query — never dropped, never exaggerated
    sk.add(1e-9)
    sk.add(1e9)
    assert sk.n == 2
    assert sk.percentile(0.0) == pytest.approx(1e-9)
    assert sk.percentile(100.0) == pytest.approx(1e9)


def test_tick_latency_window_recency_vs_history():
    """The bounded window answers RECENT percentiles exactly (numpy over
    the last `window` samples) while the sketch keeps full history —
    the slow-replica gate reads the window, so a one-off jit-compile
    spike ages out instead of dominating p99 forever."""
    win = TickLatencyWindow(window=8)
    win.add(1000.0)                      # the compile spike
    for _ in range(20):
        win.add(1.0)
    assert len(win) == 21                # full history count
    assert win.percentile(99) == pytest.approx(1.0)   # spike aged out
    assert win.sketch.n == 21            # ...but not forgotten
    assert win.sketch.vmax == 1000.0


# ----------------------------------------------------- the request clocks
def test_request_times_derivations_and_queue_side_death():
    rt = RequestTimes()
    rt.submitted("a", 3)
    rt.first_token("a", 5)
    assert rt.finished("a", 9) == {
        "queue_ticks": 2, "ttft_ticks": 2, "decode_ticks": 4}
    # queue-side death: the whole life was queue wait
    rt.submitted("b", 1)
    assert rt.finished("b", 7) == {"queue_ticks": 6, "decode_ticks": 0}
    # clocks retire on finish — steady-state memory is inflight-bounded
    assert rt._submit == {} and rt._first == {}


# -------------------------------------------------------- the SLO monitor
def test_slo_monitor_burn_rate_and_edge_triggered_breach():
    """Burn rate = window violation fraction / error budget; crossing
    1.0 with enough samples counts ONE breach until the window recovers
    (edge-triggered — a sustained breach is one event, not one per
    request). With p99=0.90 the budget is 0.10, so 2 violations in a
    10-wide window burn at exactly 2.0."""
    m = SLOMonitor(ttft_ms=100.0, tok_ms=10.0, p99=0.90, window=10,
                   min_count=4)
    for _ in range(8):
        assert m.observe(50.0, 5.0) is False
    assert m.burn_rate() == 0.0 and m.breaches == 0
    assert m.observe(500.0, 5.0) is True          # TTFT violation
    assert m.observe(50.0, 50.0) is True          # tok-latency violation
    assert m.burn_rate() == pytest.approx(2.0)
    assert m.breaches == 1
    assert m.violations_ttft == 1 and m.violations_tok == 1
    # sustained breach: no double count
    m.observe(500.0, 5.0)
    assert m.breaches == 1
    # a request that never produced a token violates a monitored TTFT
    assert m.observe(None, None) is True
    # recovery re-arms the edge
    for _ in range(10):
        m.observe(50.0, 5.0)
    assert m.burn_rate() == 0.0
    m.observe(500.0, 5.0)
    m.observe(500.0, 5.0)
    assert m.breaches == 2
    snap = m.snapshot()
    assert snap["requests"] == m.requests
    assert snap["error_budget"] == pytest.approx(0.1)


def test_slo_breach_under_injected_slow_tick_journals_event(tmp_path):
    """The end-to-end breach path under a DETERMINISTIC injected clock:
    a ServeMetrics plane whose time_fn serves scripted stamps sees slow
    TTFTs, the armed monitor crosses burn rate 1.0, and the breach rides
    the run journal as a strict-JSON `slo_breach` event."""
    from distributed_lion_tpu.train import journal as journal_mod

    clock = iter(x / 1000.0 for x in range(0, 100000, 500))  # 500ms steps
    sm = ServeMetrics(RequestTimes(), slo=SLOMonitor(
        ttft_ms=100.0, p99=0.90, window=8, min_count=4),
        time_fn=lambda: next(clock))
    jrnl = journal_mod.Journal(str(tmp_path))
    journal_mod.install(jrnl)
    try:
        for i in range(8):
            sm.on_submit(i)
            sm.on_first_token(i)     # every TTFT is 500ms > the 100ms SLO
            sm.on_finish(i, {"queue_ticks": 0, "ttft_ticks": 1,
                             "decode_ticks": 0}, "length", tick=i)
        sm.drain(64)
    finally:
        journal_mod.uninstall(jrnl)
        jrnl.close()
    assert sm.slo.breaches == 1
    events = []
    with open(tmp_path / "journal_rank0.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "event":
                events.append(rec)
    breach = [r for r in events if r["name"] == "slo_breach"]
    assert len(breach) == 1
    assert breach[0]["burn_rate"] > 1.0
    assert breach[0]["window_violations"] >= 4
    drained = [r for r in events if r["name"] == "serve_metrics"]
    assert len(drained) == 1
    assert drained[0]["ttft_ms_count"] == 8
    assert drained[0]["slo_violations"] == 8
    # the journal file stays strict-schema under the flattened fields
    vm = _load("vm_sm", "scripts", "validate_metrics.py")
    assert vm.validate_journal_file(
        str(tmp_path / "journal_rank0.jsonl")) == []


# ------------------------------------------- metrics-on == metrics-off
def _tiny_engine(metrics=False, slo=False, moe=False, **kw):
    import jax

    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
    from distributed_lion_tpu.serve.engine import (
        ServeConfig, ServeModel, ServingEngine)

    cfg = GPT2Config.tiny(moe_experts=4) if moe else GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    scfg = ServeConfig(max_seqs=4, block_size=4, max_blocks_per_seq=8,
                       metrics=metrics, **kw)
    model = ServeModel.for_gpt2(params, cfg)
    draft = model if kw.get("speculate", "").startswith("draft") else None
    eng = ServingEngine(model, scfg, draft_model=draft)
    if slo:
        eng.metrics = ServeMetrics(eng.times, slo=SLOMonitor(
            ttft_ms=10_000.0, tok_ms=10_000.0))
    return eng, cfg


def _workload(cfg, n=6, seed=3):
    from distributed_lion_tpu.serve.engine import Request

    rng = np.random.default_rng(seed)
    lens = (3, 9, 5, 14, 2, 7, 11)
    reqs = [Request(req_id=i,
                    tokens=[int(t) for t in
                            rng.integers(1, cfg.vocab_size,
                                         lens[i % len(lens)])],
                    max_new_tokens=8, seed=i) for i in range(n)]
    arrivals = {i: i // 2 for i in range(n)}
    return reqs, arrivals


@pytest.mark.parametrize("variant", [
    {},                                          # greedy
    {"temperature": 0.9, "top_k": 40},           # sampled
    {"prefix_cache": True},                      # CoW prefix cache
    {"speculate": "ngram:4"},                    # speculative decode
    {"tp": 2},                                   # tensor-parallel tick
    {"moe": True, "ep": 2},                      # expert-parallel MoE
])
def test_metrics_on_is_bit_identical_to_metrics_off(variant):
    """The whole plane must be observationally free: the SAME workload
    through a metrics+SLO-armed engine and a bare engine produces
    byte-identical token streams and reasons across the decode-path
    matrix — greedy / sampled / prefix-cache / speculative / tp."""
    eng_off, cfg = _tiny_engine(**variant)
    reqs, arrivals = _workload(cfg)
    base = eng_off.run(reqs, dict(arrivals))

    eng_on, _ = _tiny_engine(metrics=True, slo=True, **variant)
    reqs2, _ = _workload(cfg)
    done = eng_on.run(reqs2, dict(arrivals))

    assert set(done) == set(base)
    for i in base:
        assert done[i].tokens == base[i].tokens, i
        assert done[i].reason == base[i].reason, i
        # every completion carries the tick clocks; wall TTFT only when
        # the plane is armed
        assert isinstance(done[i].timing["queue_ticks"], int)
        assert isinstance(done[i].timing["decode_ticks"], int)
        assert "ttft_ms" in done[i].timing
        assert "ttft_ms" not in (base[i].timing or {})
    snap = eng_on.metrics.snapshot()
    assert snap["ttft_ms"]["count"] == len(reqs)
    assert snap["tok_ms"]["count"] > 0
    assert snap["slo"]["requests"] == len(reqs)


@pytest.mark.parametrize("variant", [
    {"prefix_cache": True},                      # CoW spans inside the tick
    {"speculate": "ngram:4"},                    # draft / verify / commit
])
def test_profiler_session_on_is_bit_identical_to_off(variant, tmp_path):
    """The span gate's profiler side is as free as the metrics plane: with
    a session open (every tick a tree of TraceAnnotations and buffer
    records, ``serve/metrics`` among them) the token streams are those of
    the bare engine."""
    import jax

    from distributed_lion_tpu.train import journal

    eng_off, cfg = _tiny_engine(**variant)
    reqs, arrivals = _workload(cfg)
    base = eng_off.run(reqs, dict(arrivals))

    eng_on, _ = _tiny_engine(metrics=True, **variant)
    reqs2, _ = _workload(cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        done = eng_on.run(reqs2, dict(arrivals))
    finally:
        jax.profiler.stop_trace()
    assert {i: (c.tokens, c.reason) for i, c in done.items()} == \
        {i: (c.tokens, c.reason) for i, c in base.items()}
    spans = journal.traced()
    ids = {r["id"]: r for r in spans}
    names = {r["name"] for r in spans}
    assert {"serve/tick", "serve/admit", "serve/prefill", "serve/token_read",
            "serve/commit", "serve/metrics"} <= names
    assert ({"serve/draft", "serve/verify"} if "speculate" in variant
            else {"serve/decode_tick", "serve/decode_build",
                  "serve/decode_dispatch"}) <= names
    # every span but the ticks hangs under a span of the same tick
    for r in spans:
        if r["name"] == "serve/tick":
            assert r["parent"] is None
        elif r["parent"] in ids:
            assert ids[r["parent"]]["t0"] <= r["t0"]
            assert r["t1"] <= ids[r["parent"]]["t1"]
    prefills = [r for r in spans if r["name"] == "serve/prefill"]
    assert sorted(r["req_id"] for r in prefills) == sorted(
        str(q.req_id) for q in reqs2)


def test_metrics_on_fleet_migration_identity_and_aggregation():
    """The fleet leg of the matrix: a metrics-armed 2-replica fleet with
    an injected replica crash produces the same token streams as the
    bare single engine, every terminal status carries its timing, and
    metrics_snapshot() folds the surviving replicas' sketches."""
    from distributed_lion_tpu.serve.replica_plane import ServingFleet
    from distributed_lion_tpu.train import resilience

    eng, cfg = _tiny_engine()
    reqs, arrivals = _workload(cfg)
    base = eng.run(reqs, dict(arrivals))

    def factory():
        e, _ = _tiny_engine(metrics=True, slo=True)
        return e

    resilience.inject_fault(
        "serve", resilience.parse_serve_specs("replica_crash:0:2"))
    try:
        fleet = ServingFleet(factory, replicas=2)
        reqs2, _ = _workload(cfg)
        done = fleet.run(reqs2, dict(arrivals))
    finally:
        resilience.inject_fault("serve", [])
    assert fleet.stats["migrations"] > 0
    assert set(done) == set(base)
    for i in base:
        assert done[i].tokens == base[i].tokens, i
        assert isinstance(done[i].timing["queue_ticks"], int)
    snap = fleet.metrics_snapshot()
    assert snap is not None
    assert snap["ttft_ms"]["count"] >= len(reqs)
    assert snap["gauges"]["migrations"] == fleet.stats["migrations"]


def test_timing_columns_on_every_terminal_status():
    """A queue-side death is the status most tempted to skip the books:
    an engine with one slot and an immediate deadline must still emit
    queue_ticks/decode_ticks on the timeout completion (and the api
    response record echoes them)."""
    from distributed_lion_tpu.serve import api
    from distributed_lion_tpu.serve.engine import Request

    eng, cfg = _tiny_engine(metrics=True)
    reqs, _ = _workload(cfg, n=2)
    # req 1 expires while queued behind req 0 (deadline already passed)
    reqs[1] = Request(req_id=1, tokens=reqs[1].tokens, max_new_tokens=4,
                      seed=1, deadline_s=-1.0)
    done = eng.run(reqs, {0: 0, 1: 0})
    assert done[1].reason == "timeout"
    t = done[1].timing
    assert t["queue_ticks"] >= 0 and t["decode_ticks"] >= 0
    rec = api.completion_record(done[1])
    assert rec["reason"] == "timeout"
    assert isinstance(rec["queue_ticks"], int)
    assert isinstance(rec["decode_ticks"], int)


# ------------------------------------------------- workload_gen + schema
def test_workload_gen_deterministic_and_schema_valid(tmp_path):
    wg = _load("wg_sm", "scripts", "workload_gen.py")
    a = wg.generate(requests=40, seed=5, deadline_frac=0.3)
    b = wg.generate(requests=40, seed=5, deadline_frac=0.3)
    assert a == b                       # byte-identical workload per seed
    assert a != wg.generate(requests=40, seed=6, deadline_frac=0.3)
    # arrivals are non-decreasing (open-loop clock) and bursts exist
    ticks = [r["arrival_tick"] for r in a]
    assert ticks == sorted(ticks)
    assert any(ticks.count(t) > 1 for t in ticks)
    assert any("prefix_group" in r for r in a)
    assert any("deadline_s" in r for r in a)
    p = tmp_path / "requests.jsonl"
    wg.write_jsonl(a, str(p))
    vm = _load("vm_wg", "scripts", "validate_metrics.py")
    assert vm.validate_request_file(str(p)) == []
    # the CLI writes the same bytes the library call produced
    out2 = tmp_path / "cli.jsonl"
    wg.main(["--requests", "40", "--seed", "5", "--deadline_frac", "0.3",
             "--out", str(out2)])
    assert out2.read_bytes() == p.read_bytes()


def test_response_schema_requires_timing_columns(tmp_path):
    vm = _load("vm_resp", "scripts", "validate_metrics.py")
    good = {"id": "r1", "reason": "timeout", "tokens": [], "prompt_len": 3,
            "n_generated": 0, "queue_ticks": 4, "decode_ticks": 0}
    p = tmp_path / "responses.jsonl"
    p.write_text(json.dumps(good) + "\n")
    assert vm.validate_response_file(str(p)) == []
    for strip, bad in (("queue_ticks", None), ("decode_ticks", None),
                       ("queue_ticks", -1), ("queue_ticks", 1.5)):
        doc = dict(good)
        if bad is None:
            doc.pop(strip)
        else:
            doc[strip] = bad
        p.write_text(json.dumps(doc) + "\n")
        errs = vm.validate_response_file(str(p))
        assert errs and strip in errs[0], (strip, bad, errs)
    # negative wall TTFT is a lie, not a measurement
    doc = dict(good, ttft_ms=-3.0)
    p.write_text(json.dumps(doc) + "\n")
    assert vm.validate_response_file(str(p))


# ------------------------------------------------- the evidence artifact
def _load_ce():
    return _load("ce_sm", "scripts", "check_evidence.py")


def test_banked_artifact_passes_slo_stage():
    """The committed CPU artifact satisfies the ISSUE 17 stage: strict
    schema (ordered quantiles, status counts), all three markers, zero
    token loss, banked p99s inside the banked targets — the gate
    runbook stage 5n re-judges after the on-chip recapture."""
    ce = _load_ce()
    assert ce.slo_ok()
    with open(ce.SERVE_ARTIFACT) as f:
        doc = json.load(f)
    sec = doc["slo"]
    assert sec["markers"]["metrics_inert"] is True
    assert sec["tokens_lost"] == 0
    assert sec["ttft_ms"]["p50"] <= sec["ttft_ms"]["p99"]
    assert sec["status_counts"]["eos"] + sec["status_counts"]["length"] > 0


def test_slo_stage_rejects_bad_artifacts(tmp_path):
    ce = _load_ce()
    with open(ce.SERVE_ARTIFACT) as f:
        good = json.load(f)
    p = tmp_path / "serving.json"

    def reject(mutate):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        p.write_text(json.dumps(doc))
        assert not ce.slo_ok(str(p))

    # artifact predates ISSUE 17 entirely (also a schema violation now)
    reject(lambda d: d.pop("slo"))
    # each marker flips the stage
    for k in ("metrics_inert", "zero_token_loss", "responses_timed"):
        reject(lambda d, k=k: d["slo"]["markers"].update({k: False}))
    # a sketch that reports p50 > p99 is lying — schema rejects
    reject(lambda d: d["slo"]["ttft_ms"].update(
        p50=d["slo"]["ttft_ms"]["p99"] + 1.0))
    # a negative TTFT is not a latency
    reject(lambda d: d["slo"]["ttft_ms"].update(p50=-1.0))
    # missing status counts (the statuses that tempt silent dropping)
    reject(lambda d: d["slo"]["status_counts"].pop("timeout"))
    reject(lambda d: d["slo"].pop("status_counts"))
    # token loss is a regression even with markers forged true
    reject(lambda d: d["slo"].update(tokens_lost=2))
    # banked p99 outside the banked target = SLO regression
    reject(lambda d: d["slo"]["targets"].update(
        ttft_ms=d["slo"]["ttft_ms"]["p99"] / 2.0))
    # an empty soak proved nothing
    reject(lambda d: d["slo"].update(requests=0))
    # the untouched artifact still passes from the tmp copy
    p.write_text(json.dumps(good))
    assert ce.slo_ok(str(p))
