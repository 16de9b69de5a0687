"""Run journal (ISSUE 7): the step-time observability contract.

What these pin:

- the journal is OBSERVATIONAL — losses/params are bit-identical
  journal-on vs journal-off across vote_buckets {1,4} on BOTH kernel
  paths (XLA and Pallas): every span is host wall time around a host
  region, nothing reaches the traced step;
- per-event overhead is bounded (the recorder must be cheap enough to
  ride every dispatch);
- the JSONL sink rotates atomically and recovers from a crash mid-write
  (injected through the PR-3 fault registry): the torn record is the only
  loss, every surviving file passes the strict journal schema;
- the offline analyzer (cli/run_analyze, stdlib-only by file path)
  attributes ≥95% of measured step wall to named buckets on a real
  trainer leg, closes the wall identity, merges deliberately clock-skewed
  multi-host journals onto one timeline and reports step-skew
  percentiles;
- the caller-thread ckpt spans cross-check the existing ckpt_stall_s
  ledger; committer-thread spans are excluded from step-wall attribution;
- crash bundles carry journal_tail.jsonl; preemption drains and guard
  quarantine transitions land as events.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from distributed_lion_tpu.train import journal, resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "distributed_lion_tpu")


def _load_by_path(name, rel):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# stdlib-only contract: both load by FILE PATH, no package import, no jax
run_analyze = _load_by_path("journal_run_analyze",
                            "distributed_lion_tpu/cli/run_analyze.py")
validate_metrics = _load_by_path("journal_validate_metrics",
                                 "scripts/validate_metrics.py")


def _tiny_cfg(**kw):
    from distributed_lion_tpu.train.loop import TrainConfig

    base = dict(lion=True, async_grad=True, wire="sign_psum", vote_every=1,
                vote_buckets=1, learning_rate=1e-3, warmup_steps=1,
                max_steps=3, per_device_train_batch_size=1,
                gradient_accumulation_steps=1, block_size=32,
                logging_steps=1, output_dir=None, save_steps=10**6,
                resume_from_checkpoint=False)
    base.update(kw)
    return TrainConfig(**base)


def _train(mesh, cfg, steps=3, seed=4):
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    tr = Trainer.for_gpt2(cfg, mesh, model_cfg)
    blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                  model_cfg.vocab_size, seed=seed)
    hist = tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                    max_steps=steps)
    return tr, hist


# ------------------------------------------------------ observational contract
@pytest.mark.parametrize("kern", ["xla", "pallas"])
@pytest.mark.parametrize("buckets", [1, 4])
def test_bit_identity_journal_on_vs_off(mesh8, tmp_path, kern, buckets):
    """The acceptance pin: elections/params/losses are BIT-identical with
    the journal on vs off, for vote_buckets {1,4} x XLA/Pallas — the
    journal records host wall time only and can never move an election.
    (The Pallas cases take tiny's two ``[64, 256]`` fc weights where they
    lie and pool the other 26 leaves: ``[setup] lion: 2 leaves in
    place``.)"""
    runs = {}
    for on in (False, True):
        cfg = _tiny_cfg(kernel=kern, vote_buckets=buckets, journal=on,
                        output_dir=str(tmp_path / f"{kern}{buckets}{on}"))
        tr, hist = _train(mesh8, cfg)
        runs[on] = ([h["loss"] for h in hist if "loss" in h],
                    jax.device_get(tr.params))
        tr.close()
    assert runs[True][0] == runs[False][0]
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), runs[True][1], runs[False][1])


def test_bit_identity_profiler_session_on_vs_off(mesh8, tmp_path):
    """The same pin for the span gate's other listener: with a profiler
    session open around the whole leg (every loop span a TraceAnnotation
    and a buffer record) losses and params are bit-identical to the run
    with nothing listening, and the buffer holds the loop's spans with
    their step."""
    runs = {}
    for on in (False, True):
        cfg = _tiny_cfg(kernel="pallas", vote_buckets=4, nan_sentinel=True)
        if on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            tr, hist = _train(mesh8, cfg)
        finally:
            if on:
                jax.profiler.stop_trace()
        runs[on] = ([h["loss"] for h in hist if "loss" in h],
                    jax.device_get(tr.params))
        tr.close()
    assert runs[True][0] == runs[False][0]
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), runs[True][1], runs[False][1])
    spans = journal.traced()
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    assert [r["step"] for r in by_name["dispatch"]] == [0, 1, 2]
    assert {"data_wait", "retrace_check", "dispatch", "sentinel_check",
            "device_wait", "logging_drain"} <= set(by_name)
    assert all(r["parent"] is None and r["t0"] <= r["t1"] for r in spans)
    # the spans of one step do not overlap: they tile the loop body
    steps = sorted((r for r in spans if r.get("step") == 1
                    and r["name"] in ("retrace_check", "dispatch")),
                   key=lambda r: r["t0"])
    assert steps[0]["t1"] <= steps[1]["t0"]


# --------------------------------------------------- recorder micro-contracts
def test_event_overhead_bounded(tmp_path):
    """The recorder rides every dispatch: per-event cost (serialize +
    buffered write + ring append) must stay well under a millisecond even
    on a loaded CI box."""
    j = journal.Journal(str(tmp_path), ring=64)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        j.event("step_log", step=i, steps_per_sec=123.456)
    dt = time.perf_counter() - t0
    j.close()
    assert dt / n < 1e-3, f"{dt / n * 1e6:.1f} us/event"
    assert len(j.tail()) == 64  # ring stayed bounded


def test_rotation_and_crash_mid_write_recovery(tmp_path):
    """Atomic rotation + torn-write recovery: rotate at a tiny max_bytes,
    then tear a write mid-line through the PR-3 fault registry. The torn
    record is the ONLY loss — every file (rotated + live) passes the
    strict journal schema, and a re-opened journal truncates the torn tail
    and keeps appending."""
    d = str(tmp_path)
    try:
        j = journal.Journal(d, max_bytes=700, ring=16)
        for i in range(12):
            j.event("filler", step=i, pad="x" * 80)
        rotated = [f for f in os.listdir(d) if f.startswith("journal_rank0.")
                   and f != "journal_rank0.jsonl"]
        assert rotated, "tiny max_bytes produced no rotation"
        resilience.inject_fault("journal_torn_write", 1)
        j.event("doomed", step=99)          # torn on disk, sink disabled
        j.event("ring_only", step=100)      # ring keeps recording
        assert any(r["name"] == "ring_only" for r in j.tail())
        j.close()
        raw = open(os.path.join(d, "journal_rank0.jsonl"), "rb").read()
        assert not raw.endswith(b"\n")      # the tear is really on disk
        # recovery: a fresh journal truncates the torn tail and appends
        j2 = journal.Journal(d, ring=16)
        j2.event("after_recovery", step=101)
        j2.close()
        names = []
        for f in sorted(os.listdir(d)):
            errs = validate_metrics.validate_journal_file(os.path.join(d, f))
            assert errs == [], (f, errs)
            with open(os.path.join(d, f)) as fh:
                names += [json.loads(line)["name"] for line in fh]
        assert "after_recovery" in names and "journal_recovered" in names
        assert "doomed" not in names        # torn record stayed dead
    finally:
        resilience.clear_faults()


def test_emitter_mirrors_and_records(tmp_path, capsys):
    """journal.emit: byte-for-byte the old print to stdout, PLUS a log
    record in the active journal; inert (print-only) with none active."""
    journal.emit("[x] no journal yet")
    assert capsys.readouterr().out == "[x] no journal yet\n"
    j = journal.Journal(str(tmp_path))
    journal.install(j)
    try:
        journal.emit("[x] hello")
        journal.event("side_event", k=1)
        assert capsys.readouterr().out == "[x] hello\n"
        recs = j.tail()
        assert any(r["kind"] == "log" and r["msg"] == "[x] hello"
                   for r in recs)
        assert any(r["name"] == "side_event" for r in recs)
    finally:
        journal.uninstall(j)
        j.close()
    journal.emit("[x] after uninstall")  # must not raise or record


# ------------------------------------------------------------------- analyzer
def test_trainer_leg_attribution_coverage(mesh8, tmp_path):
    """THE acceptance criterion at test scale: a real journal-on trainer
    leg (with async checkpoints, so the ckpt bucket is exercised)
    attributes >=95% of measured step wall to the named buckets, closes
    the wall identity, and its files pass the strict schema + the
    check_evidence journal stage."""
    cfg = _tiny_cfg(journal=True, output_dir=str(tmp_path), save_steps=2,
                    max_steps=6, logging_steps=2)
    tr, _ = _train(mesh8, cfg, steps=6)
    ckpt_spans = []
    committer_spans = []
    tr.close()  # drains the last async save — its spans + stall included
    stall = tr.checkpointer.total_stall_s
    report = run_analyze.analyze_dir(str(tmp_path))
    assert report is not None and report["schema_errors"] == 0
    att = report["attribution"]
    assert att["closes"], att
    assert att["steps"] == 6
    assert att["coverage"] >= 0.95, att
    assert att["buckets"]["dispatch"]["s"] > 0
    assert att["buckets"]["logging"]["s"] > 0
    # the validator accepts what the trainer wrote
    jdir = os.path.join(str(tmp_path), "journal")
    for f in os.listdir(jdir):
        assert validate_metrics.validate_journal_file(
            os.path.join(jdir, f)) == []
    # ckpt span cross-check: caller-thread ckpt spans ~ the stall ledger
    # (same blocked regions, measured by the same clock); committer spans
    # exist and are excluded from attribution
    for f in os.listdir(jdir):
        with open(os.path.join(jdir, f)) as fh:
            for line in fh:
                r = json.loads(line)
                if r.get("kind") != "span" or \
                        not str(r["name"]).startswith("ckpt"):
                    continue
                (committer_spans if r.get("thread") == "committer"
                 else ckpt_spans).append(r)
    assert committer_spans, "async commit produced no committer spans"
    span_s = sum(r["dur"] for r in ckpt_spans)
    assert abs(span_s - stall) <= 0.05 + 0.25 * stall, (span_s, stall)
    # the check_evidence stage consumes exactly this directory shape
    ce = _load_by_path("journal_check_evidence", "scripts/check_evidence.py")
    assert ce.journal_ok(str(tmp_path))


def test_analyzer_merges_skewed_multi_host_journals(tmp_path):
    """Synthetic two-rank journals with DELIBERATE clock skew: the ranks'
    monotonic epochs differ by ~4900s (different boot times), related only
    through the meta wall anchors. The merge must put both on one
    timeline, the attribution must sum to the measured step wall, and the
    step-skew percentiles must report the real ~30ms arrival spread — not
    the 4900s monotonic gap."""
    def rec(**kw):
        return json.dumps(kw, allow_nan=False)

    r0 = [rec(kind="meta", name="journal_start", t=100.0, rank=0,
              wall=1000.0, pid=1, version=1),
          rec(kind="event", name="train_start", t=100.0, rank=0, step=0),
          rec(kind="span", name="data_wait", t=100.1, rank=0, dur=0.1,
              step=0),
          rec(kind="span", name="dispatch", t=100.7, rank=0, dur=0.6,
              step=0),
          rec(kind="span", name="device_wait", t=100.9, rank=0, dur=0.2,
              step=1),
          rec(kind="span", name="logging_drain", t=100.95, rank=0,
              dur=0.05, step=1),
          rec(kind="span", name="ckpt/drain", t=100.99, rank=0, dur=0.04,
              step=1),
          # committer-thread span overlapping the step wall: EXCLUDED
          rec(kind="span", name="ckpt/digest", t=100.99, rank=0, dur=0.5,
              step=1, thread="committer"),
          rec(kind="event", name="step_log", t=100.96, rank=0, step=1),
          rec(kind="event", name="train_end", t=101.0, rank=0, step=2)]
    r1 = [rec(kind="meta", name="journal_start", t=5000.0, rank=1,
              wall=1000.02, pid=2, version=1),
          rec(kind="event", name="step_log", t=5000.97, rank=1, step=1)]
    (tmp_path / "journal_rank0.jsonl").write_text("\n".join(r0) + "\n")
    (tmp_path / "journal_rank1.jsonl").write_text("\n".join(r1) + "\n")
    report = run_analyze.analyze_dir(str(tmp_path))
    assert report["ranks"] == [0, 1] and report["schema_errors"] == 0
    att = report["attribution"]
    assert att["rank"] == 0 and att["closes"]
    assert att["wall_s"] == pytest.approx(1.0)
    assert att["buckets"]["data"]["s"] == pytest.approx(0.1)
    assert att["buckets"]["dispatch"]["s"] == pytest.approx(0.6)
    assert att["buckets"]["device"]["s"] == pytest.approx(0.2)
    assert att["buckets"]["logging"]["s"] == pytest.approx(0.05)
    assert att["buckets"]["ckpt"]["s"] == pytest.approx(0.04)  # no committer
    named = sum(v["s"] for v in att["buckets"].values())
    assert named + att["other_s"] + att["unattributed_s"] == pytest.approx(
        att["wall_s"], abs=1e-6)
    # rank0 logged step 1 at wall 1000.96, rank1 at 1000.02+0.97=1000.99:
    # 30ms of real skew, 4900s of monotonic-epoch difference corrected away
    skew = report["step_skew"]
    assert skew["steps_compared"] == 1
    assert skew["max_s"] == pytest.approx(0.03, abs=1e-6)


def test_analyzer_latest_leg_window_and_overlap_detection(tmp_path):
    """Journals append across watcher re-fires: attribution must cover the
    LATEST train_start..train_end leg, not the union plus the dead
    inter-run gap (which would sink coverage below the evidence gate
    forever). And 'closes' must actually catch the one failure the
    residual arithmetic can see: overlapping spans driving unattributed
    negative."""
    def rec(**kw):
        return json.dumps(kw, allow_nan=False)

    rows = [rec(kind="meta", name="journal_start", t=0.0, rank=0,
                wall=1000.0, version=1),
            # leg 1 (a dropped window), then a 90s dead gap, then leg 2
            rec(kind="event", name="train_start", t=0.0, rank=0, step=0),
            rec(kind="span", name="dispatch", t=9.0, rank=0, dur=9.0,
                step=0),
            rec(kind="event", name="train_end", t=10.0, rank=0, step=9),
            rec(kind="event", name="train_start", t=100.0, rank=0, step=9),
            rec(kind="span", name="dispatch", t=100.9, rank=0, dur=0.9,
                step=9),
            rec(kind="event", name="step_log", t=100.95, rank=0, step=12),
            rec(kind="event", name="train_end", t=101.0, rank=0, step=12)]
    (tmp_path / "journal_rank0.jsonl").write_text("\n".join(rows) + "\n")
    att = run_analyze.analyze_dir(str(tmp_path))["attribution"]
    assert att["wall_s"] == pytest.approx(1.0)      # leg 2 only, no gap
    assert att["steps"] == 3
    assert att["buckets"]["dispatch"]["s"] == pytest.approx(0.9)
    assert att["closes"] and att["coverage"] >= 0.89
    # overlap: two spans claiming the same wall → unattributed negative
    rows += [rec(kind="span", name="device_wait", t=100.9, rank=0, dur=0.9,
                 step=12)]
    (tmp_path / "journal_rank0.jsonl").write_text("\n".join(rows) + "\n")
    att = run_analyze.analyze_dir(str(tmp_path))["attribution"]
    assert att["unattributed_s"] < 0 and not att["closes"]


def test_analyzer_baseline_diff_names_regressing_bucket(tmp_path):
    """--baseline: the bucket whose wall share GREW the most vs the bench
    row's journal_attribution is named; artifacts predating the journal
    diff to None instead of erroring."""
    base = {"value": 1.0, "journal_attribution": {
        "buckets": {b: {"s": 0.0, "frac": f} for b, f in
                    [("device", 0.8), ("dispatch", 0.1), ("data", 0.02),
                     ("ckpt", 0.02), ("logging", 0.06)]}}}
    bpath = tmp_path / "BENCH_base.json"
    bpath.write_text(json.dumps(base))
    cur = {"rank": 0, "wall_s": 1.0, "steps": 10, "closes": True,
           "other_s": 0.0, "unattributed_s": 0.0, "coverage": 1.0,
           "buckets": {b: {"s": f, "frac": f} for b, f in
                       [("device", 0.6), ("dispatch", 0.1), ("data", 0.22),
                        ("ckpt", 0.02), ("logging", 0.06)]}}
    diff = run_analyze.diff_vs_baseline(
        cur, run_analyze.load_baseline_attribution(str(bpath)))
    assert diff["regressing_bucket"] == "data"
    assert diff["frac_delta"]["data"] == pytest.approx(0.2)
    old = tmp_path / "BENCH_old.json"
    old.write_text(json.dumps({"value": 1.0}))
    assert run_analyze.load_baseline_attribution(str(old)) is None


# ------------------------------------------------------- subsystem event hooks
def test_crash_bundle_carries_journal_tail(mesh8, tmp_path):
    """An anomaly carries its own timeline: the NaN sentinel's crash
    bundle gains journal_tail.jsonl — the ring buffer's last records, in
    the same strict schema the live journal writes."""
    cfg = _tiny_cfg(journal=True, nan_sentinel=True, max_steps=3,
                    output_dir=str(tmp_path))
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    tr.params["wte"] = tr.params["wte"].at[0, 0].set(float("nan"))
    blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                  model_cfg.vocab_size, seed=4)
    with pytest.raises(FloatingPointError):
        tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                 max_steps=3)
    tr.close()
    bundles = sorted((tmp_path / "crash").iterdir())
    tail = bundles[0] / "journal_tail.jsonl"
    assert tail.exists()
    assert validate_metrics.validate_journal_file(str(tail)) == []
    kinds = {json.loads(line)["kind"] for line in open(tail)}
    assert "span" in kinds  # the timeline really is in the bundle


def test_preempt_drain_event_recorded(mesh8, tmp_path):
    """resilience.PreemptionGuard journals the drain (signal→boundary
    latency) when the trainer reaches the next dispatch boundary."""
    cfg = _tiny_cfg(journal=True, max_steps=8, output_dir=str(tmp_path),
                    save_steps=10**6)
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    tr._preempt_guard.trigger()
    blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                  model_cfg.vocab_size, seed=4)
    tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
             max_steps=8)
    assert tr.preempted
    tr.close()
    events = []
    jdir = tmp_path / "journal"
    for f in os.listdir(jdir):
        with open(jdir / f) as fh:
            events += [json.loads(line) for line in fh]
    drain = [r for r in events if r["name"] == "preempt_drain"]
    assert len(drain) == 1
    assert drain[0]["signal_to_boundary_s"] >= 0
    end = [r for r in events if r["name"] == "train_end"]
    assert end and end[0]["preempted"] is True


class _FakeJournal:
    def __init__(self):
        self.records_ = []

    def event(self, name, **fields):
        self.records_.append({"kind": "event", "name": name, **fields})

    def record(self, rec):
        self.records_.append(dict(rec))


def test_vote_guard_journals_transitions():
    """Quarantine/readmission transitions land as events — the state
    machine as a stream, not scraped log lines."""
    from distributed_lion_tpu.train.vote_guard import VoteGuard

    jr = _FakeJournal()
    g = VoteGuard(4, "enforce", strike_threshold=1, cooldown_steps=2,
                  journal=jr)
    obs = {"guard_nonfinite": np.array([0, 1, 0, 0]),
           "guard_frozen": np.zeros(4), "guard_disagree": np.zeros(4),
           "guard_voted_steps": np.array(1)}
    g.update(10, obs, 1)
    q = [r for r in jr.records_ if r["name"] == "guard_quarantine"]
    assert q and q[0]["worker"] == 1 and q[0]["step"] == 10
    clean = {"guard_nonfinite": np.zeros(4), "guard_frozen": np.zeros(4),
             "guard_disagree": np.zeros(4),
             "guard_voted_steps": np.array(1)}
    g.update(13, clean, 1)  # cooldown elapsed → readmission probe
    r = [x for x in jr.records_ if x["name"] == "guard_readmit"]
    assert r and r[0]["worker"] == 1
