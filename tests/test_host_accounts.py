"""The always-on record of host time that is nobody's span
(``train/journal.account``) and its three producers (ISSUE 48).

What these pin:

- the accounts are bounded, filter by kind and time, count what they push
  out and are NOT emptied when a profiler session begins;
- ``watch_gc`` hooks the collector once: a forced ``gc.collect()`` gives one
  ``gc_pause`` account and moves ``gc_totals()``;
- ``SetupLaps`` gives ``setup_lap`` accounts with no journal installed, one
  span record a lap with one, and one ``setup/before`` a process;
- a tiny engine on an injected clock whose read is made to wait gives ONE
  ``slow_tick`` account with its fields, one stderr line AT ONCE (a stall
  in a run's last ticks is said all the same), its ``next_read_wait_ms``
  filled by the next two ticks and said in a second line, and
  ``slow_ticks`` / ``slow_tick_excess_s`` / ``read_wait_s`` by hand;
- a decode-only tick reads the engine's clock ``CLOCK_READS_A_TICK`` times
  (the always-on cost, counted and not timed);
- token streams are identical under a session, without one, and with the
  accounts' producers stubbed out.
"""

import collections
import gc
import os
import re
import types

import jax
import numpy as np
import pytest

from distributed_lion_tpu.serve import engine as engine_mod
from distributed_lion_tpu.train import journal
from tests.test_span_gate import _requests, _start

journal.register_profiler(jax.profiler.TraceAnnotation)

CLOCK_READS_A_TICK = 8   # step 3 (start, admitted, end), build 2, read 2,
# commit 1: what a decode-only tick costs with nothing listening


@pytest.fixture
def small_accounts(monkeypatch):
    """An empty list of eight accounts in the place of the process's."""
    monkeypatch.setattr(journal, "ACCOUNTS_MAX", 8)
    monkeypatch.setattr(journal, "_ACCOUNTS", collections.deque(maxlen=8))
    monkeypatch.setattr(journal, "_accounts_dropped", 0)


def _tiny_engine(time_fn=None, **kw):
    """``test_span_gate._tiny_engine`` with the engine's clock to give."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
    from distributed_lion_tpu.serve.engine import (
        ServeConfig, ServeModel, ServingEngine)

    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    scfg = ServeConfig(max_seqs=4, block_size=4, max_blocks_per_seq=16, **kw)
    clock = {"time_fn": time_fn} if time_fn else {}
    return ServingEngine(ServeModel.for_gpt2(params, cfg), scfg, **clock), cfg


# -------------------------------------------------------------- the accounts
def test_accounts_are_bounded_filter_and_count_what_they_push_out(
        small_accounts):
    for i in range(11):
        kind = "gc_pause" if i % 2 else "setup_lap"
        rec = journal.account(kind, f"n{i}", 10.0 + i, 10.5 + i, index=i)
        assert rec["index"] == i and rec["kind"] == kind
    kept = journal.accounts()
    assert [r["index"] for r in kept] == list(range(3, 11))
    assert journal.accounts_dropped() == 3
    assert [r["index"] for r in journal.accounts("gc_pause")] == [3, 5, 7, 9]
    # wholly inside since..until, either end open
    assert [r["index"] for r in journal.accounts(since=17.0)] == [7, 8, 9, 10]
    assert [r["index"] for r in journal.accounts(until=15.4)] == [3, 4]
    assert [r["index"] for r in journal.accounts(
        "setup_lap", since=14.0, until=18.5)] == [4, 6, 8]
    assert kept[0] == {"kind": "gc_pause", "name": "n3", "t0": 13.0,
                       "t1": 13.5, "index": 3}
    with pytest.raises(ValueError, match="unknown account kind 'span'"):
        journal.account("span", "x", 0.0, 1.0)


def test_accounts_survive_the_start_of_a_profiler_session(small_accounts):
    journal.account("setup_lap", "setup/mesh", 1.0, 2.0, owner="trainer")
    journal._record_traced({"name": "dispatch", "t0": 1.0, "t1": 2.0,
                            "id": 1, "parent": None})
    try:
        journal._begin_session()      # what a session's first span does
    finally:
        journal._in_session = False
    assert journal.traced() == []     # the traced buffer starts empty,
    assert [r["name"] for r in journal.accounts()] == ["setup/mesh"]  # not it


def test_an_account_reaches_the_installed_journal_as_a_span(tmp_path,
                                                            small_accounts):
    from tests.test_journal import validate_metrics

    jr = journal.Journal(str(tmp_path))
    journal.install(jr)
    try:
        journal.account("gc_pause", "gc/gen2", 5.0, 5.25, generation=2,
                        collected=7)
    finally:
        journal.uninstall(jr)
        jr.close()
    (span,) = [r for r in jr.tail() if r["kind"] == "span"]
    assert (span["name"], span["dur"], span["account"], span["generation"],
            span["collected"], span["parent"]) == ("gc/gen2", 0.25,
                                                   "gc_pause", 2, 7, None)
    assert isinstance(span["id"], int)
    assert validate_metrics.validate_journal_file(
        os.path.join(str(tmp_path), journal.journal_filename(0))) == []


def test_the_analyzer_leaves_overlapping_accounts_out_of_the_step_wall():
    """A collection inside a ``dispatch`` span is that span's time already:
    counted again it would push the wall's sum past the wall."""
    from tests.test_journal import run_analyze

    def rec(kind, name, t, **kw):
        return {"kind": kind, "name": name, "t": t, "rank": 0, **kw}

    events = [rec("event", "train_start", 0.0, step=0),
              rec("span", "setup/mesh", 0.0, dur=3.0, account="setup_lap"),
              rec("span", "dispatch", 9.5, dur=6.0, id=1, parent=None),
              rec("span", "gc/gen2", 5.0, dur=2.0, account="gc_pause"),
              rec("span", "serve/slow_tick", 6.0, dur=1.0,
                  account="slow_tick"),
              rec("event", "train_end", 10.0, step=4)]
    got = run_analyze.attribute(events)
    assert got["closes"] and got["buckets"]["dispatch"]["s"] == 6.0
    assert got["other_s"] == 3.0        # the lap still tiles the wall
    assert got["unattributed_s"] == pytest.approx(1.0)   # -2.0 with them


# ------------------------------------------------------------ the collector
def test_a_forced_collection_is_one_account_and_moves_the_totals():
    journal.watch_gc()
    journal.watch_gc()                           # hooked once
    assert gc.callbacks.count(journal._on_gc) == 1
    gc.collect()                                 # settle what is pending
    n0, s0 = journal.gc_totals()
    n = len(journal.accounts("gc_pause"))
    dropped = journal.accounts_dropped()
    gc.disable()                # no young collection beside the forced one
    try:
        gc.collect()
        n1, s1 = journal.gc_totals()
    finally:
        gc.enable()
    assert n1 == n0 + 1 and s1 > s0
    fresh = journal.accounts("gc_pause")
    assert len(fresh) + journal.accounts_dropped() >= n + dropped + 1
    last = fresh[-1]
    assert (last["name"], last["generation"]) == ("gc/gen2", 2)
    assert last["t0"] <= last["t1"] and last["collected"] >= 0
    assert last["t1"] - last["t0"] == pytest.approx(s1 - s0)


def test_gc_hook_lists_a_young_collection_only_when_it_is_long(monkeypatch):
    kept = collections.deque(maxlen=8)
    totals = [0, 0.0]
    clock = iter([1.0, 1.0002, 2.0, 2.003])
    monkeypatch.setattr(journal, "_ACCOUNTS", kept)
    monkeypatch.setattr(journal, "_GC", totals)
    monkeypatch.setattr(journal, "_gc_t0", None)
    monkeypatch.setattr(journal, "time", types.SimpleNamespace(
        monotonic=lambda: next(clock)))
    gc.disable()           # the hook under test is also the process's own
    try:
        for _ in range(2):     # 0.2 ms: counted; 3 ms: counted and listed
            journal._on_gc("start", {"generation": 0})
            journal._on_gc("stop", {"generation": 0, "collected": 4})
    finally:
        gc.enable()
    assert totals == [2, pytest.approx(0.0032)]
    assert [(r["name"], r["t0"], r["t1"], r["collected"]) for r in kept] \
        == [("gc/gen0", 2.0, 2.003, 4)]
    journal._on_gc("stop", {"generation": 1, "collected": 0})  # no start seen
    assert totals == [2, pytest.approx(0.0032)]


# ------------------------------------------------------------------- set-up
def test_setup_laps_are_accounts_with_and_without_a_journal(
        small_accounts, monkeypatch, capsys):
    monkeypatch.setattr(journal.SetupLaps, "_before_said", False)
    assert journal.active() is journal.NULL
    setup = journal.SetupLaps("engine")
    setup.lap("setup/place_weights")
    jr = journal.Journal(None)
    journal.install(jr)
    try:
        setup.lap("setup/init_pages")
        journal.SetupLaps("trainer").lap("setup/mesh")   # no second `before`
    finally:
        journal.uninstall(jr)
    setup.emit(stderr=True)
    laps = journal.accounts("setup_lap")
    assert [(r["name"], r["owner"]) for r in laps] == [
        ("setup/before", "engine"), ("setup/place_weights", "engine"),
        ("setup/init_pages", "engine"), ("setup/mesh", "trainer")]
    assert laps[0]["t0"] == journal.T_IMPORT and laps[0]["t1"] == laps[1]["t0"]
    assert laps[1]["t1"] == laps[2]["t0"] <= laps[2]["t1"]   # consecutive
    # one span record a lap, from the one call: none by a route of its own
    spans = [r for r in jr.tail() if r["kind"] == "span"]
    assert [(r["name"], r["account"], r["owner"]) for r in spans] == [
        ("setup/init_pages", "setup_lap", "engine"),
        ("setup/mesh", "setup_lap", "trainer")]
    assert spans[0]["dur"] == pytest.approx(laps[2]["t1"] - laps[2]["t0"],
                                            abs=1e-8)
    assert re.search(r"\[setup\] engine: place_weights [\d.]+ s, init_pages "
                     r"[\d.]+ s\n", capsys.readouterr().err)


# ------------------------------------------------------- the serving tick
class _Clock:
    """Every reading costs 0.1 ms, so a tick's parts are counts of reads."""

    STEP = 1e-4

    def __init__(self):
        self.t, self.reads = 100.0, 0

    def __call__(self):
        self.reads += 1
        self.t += self.STEP
        return self.t


class _Waits:
    """A dispatch's output whose read makes the clock wait."""

    def __init__(self, vec, clock, seconds):
        self.vec, self.clock, self.seconds = vec, clock, seconds

    def __array__(self, dtype=None, copy=None):
        self.clock.t += self.seconds
        return np.asarray(self.vec)


def _two_long_requests(cfg):
    from distributed_lion_tpu.serve.engine import Request

    return [Request(req_id=i, tokens=[3 + i, 5, 7], max_new_tokens=30, seed=i)
            for i in range(2)]


def test_a_stalled_read_is_one_slow_tick_account_counters_and_line(
        tmp_path, capsys):
    from tests.test_journal import validate_metrics

    clock = _Clock()
    eng, cfg = _tiny_engine(time_fn=clock)
    assert {"read_wait_s", "gc_pause_s", "gc_collections", "slow_ticks",
            "slow_tick_excess_s"} <= set(eng.stats)     # ride serve_stats
    for r in _two_long_requests(cfg):
        eng.submit(r)
    since = journal.accounts_dropped(), len(journal.accounts("slow_tick"))
    for _ in range(13):           # tick 1 prefills both; 3.. are judged
        eng.step()
    assert eng.stats["slow_ticks"] == 0 and len(eng._walls) == 11
    # a decode-only tick: the clock is read a stated number of times, and
    # its wall is the reads between its first and its last
    reads = clock.reads
    eng.step()                                                   # tick 14
    assert clock.reads - reads == CLOCK_READS_A_TICK
    assert eng._walls[-1] == pytest.approx(
        (CLOCK_READS_A_TICK - 1) * clock.STEP)
    capsys.readouterr()
    jr = journal.Journal(str(tmp_path))
    journal.install(jr)
    try:
        eng._unread[-1].vec = _Waits(eng._unread[-1].vec, clock, 1.4)
        eng.step()                                               # tick 15
        assert eng.stats["slow_ticks"] == 1
        said = [ln for ln in capsys.readouterr().err.splitlines()
                if "slow tick" in ln]            # at once, with what is known
        eng.step()
        assert "slow tick" not in capsys.readouterr().err
        eng.step()                 # the second tick after it: their reads
    finally:
        journal.uninstall(jr)
        jr.close()
    median = (CLOCK_READS_A_TICK - 1) * clock.STEP
    assert eng.stats["slow_tick_excess_s"] == pytest.approx(1.4)
    fresh = journal.accounts("slow_tick")
    assert since[0] == journal.accounts_dropped() \
        and len(fresh) == since[1] + 1                    # ONE account
    rec = fresh[-1]
    assert rec["name"] == "serve/slow_tick" and rec["tick"] == 15
    assert rec["t1"] - rec["t0"] == pytest.approx(1.4 + median)
    assert rec["wall_ms"] == pytest.approx(1400.7)
    assert rec["median_ms"] == pytest.approx(0.7)
    assert rec["read_wait_ms"] == pytest.approx(1400.1)
    assert (rec["read_of"], rec["read_tick"]) == ("decode", 14)
    assert rec["admit_ms"] == pytest.approx(0.1)
    assert rec["build_ms"] == pytest.approx(0.1)
    assert rec["commit_ms"] == pytest.approx(0.1)
    assert rec["gc_ms"] >= 0.0 and rec["prefills"] == 0
    assert rec["next_read_wait_ms"] == pytest.approx([0.1, 0.1])
    assert said == [
        "[serve] slow tick 15: 1400.7 ms (median 0.7): read of decode tick "
        f"14 waited 1400.1, gc {rec['gc_ms']:.1f}, admit 0.1, build 0.1, "
        "commit 0.1"]
    assert [ln for ln in capsys.readouterr().err.splitlines()
            if "slow tick" in ln] == [
        "[serve] slow tick 15: next reads waited 0.1, 0.1 ms"]
    # the stall is in the history, and the next ticks are judged against
    # the same median: none of them is slow
    while eng.has_work():
        eng.step()
    assert eng.stats["slow_ticks"] == 1
    # every dispatch was read once, each read one interval of the clock
    n_reads = eng.stats["prefill_dispatches"] + eng.stats["decode_ticks"]
    assert eng.stats["read_wait_s"] == pytest.approx(
        1.4 + n_reads * clock.STEP)
    assert 0 < eng.stats["gc_collections"] <= journal.gc_totals()[0]
    # the account went to the installed journal as a span, in its schema
    spans = [r for r in jr.tail() if r.get("account") == "slow_tick"]
    assert len(spans) == 1 and spans[0]["name"] == "serve/slow_tick" \
        and spans[0]["dur"] == pytest.approx(1.4007)
    logged = [r for r in jr.tail() if r["kind"] == "log"
              and "slow tick 15" in r["msg"]]
    assert len(logged) == 2 and logged[0]["stream"] == "stderr"
    assert validate_metrics.validate_journal_file(
        os.path.join(str(tmp_path), journal.journal_filename(0))) == []


def test_a_prefills_device_time_is_no_stall():
    """A long read in the tick after a prefill is the prefill's device
    time: that tick and the prefill's own are not judged."""
    clock = _Clock()
    eng, cfg = _tiny_engine(time_fn=clock)
    reqs = _two_long_requests(cfg)
    eng.submit(reqs[0])
    for _ in range(12):
        eng.step()
    judged = len(eng._walls)
    assert judged >= engine_mod.SLOW_TICK_MIN_TICKS
    eng.submit(reqs[1])
    eng.step()                          # admits and prefills: not judged
    assert len(eng._walls) == judged
    eng._unread[-1].vec = _Waits(eng._unread[-1].vec, clock, 0.5)
    eng.step()                          # the tick after it: not judged
    assert len(eng._walls) == judged and eng.stats["slow_ticks"] == 0
    eng.step()                          # decode-only again
    assert len(eng._walls) == judged + 1
    assert eng.stats["read_wait_s"] > 0.5


def test_token_streams_identical_under_a_session_without_and_unaccounted(
        tmp_path, monkeypatch):
    """``test_span_gate``'s pair (a session on and off) with the always-on
    accounts in both, and a third run whose producers are stubbed out and
    whose clock is the test's: the stamps never touch the token path."""
    def run(trace_dir, **kw):
        eng, cfg = _tiny_engine(temperature=0.9, top_k=40, **kw)
        if trace_dir:
            _start(trace_dir)
        try:
            out = eng.run(_requests(cfg), {i: i // 2 for i in range(5)})
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        return {i: (c.tokens, c.reason) for i, c in out.items()}, eng.stats

    off, stats = run(None)
    on, _ = run(tmp_path)
    assert stats["read_wait_s"] > 0 and stats["gc_collections"] > 0
    monkeypatch.setattr(journal, "account", lambda *a, **kw: {})
    monkeypatch.setattr(journal, "gc_totals", lambda: (0, 0.0))
    bare, stats = run(None, time_fn=lambda: 0.0)
    assert on == off == bare
    assert (stats["read_wait_s"], stats["gc_collections"]) == (0.0, 0)
