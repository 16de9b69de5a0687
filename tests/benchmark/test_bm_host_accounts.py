"""The readers of host time that no span holds (``benchmark/lib/
host_accounts`` and the fourteen ``layer_metrics`` files of ISSUE 48), each
on a ctx worked by hand: a trace of a dozen device events and five
``bench/step`` events on the session's clock, the same five ticks, the
program's spans and accounts on ``time.monotonic``, and the engine's
counters at the windows' edges. The five ``idle_*`` are each measured and
their sum is held against ``host_gap_ms.decode``'s own arithmetic (further
apart than 2% and 0.01 ms: all five left out); clocks that do not pair give
``None`` for all five; a trace that ends early lands in
``idle_edge_ms.decode``; the ``setup_*`` add up to ``setup_s``; a program
without accounts gives ``None``. Then the manifest: the entries are listed
for their cells (by membership), and the three tests whose pins on an older
manifest this PR's entries break (``tests/conftest.
PINNED_TO_AN_OLDER_MANIFEST``) have their bodies run here, against the
names their own source expects, so that every fact they held is still held
and the next PR's names do not break them."""

import ast
import collections
import copy
import importlib.util
import inspect
import os
import textwrap
import types

import pytest

from benchmark.lib import harness, host_accounts, layer_common
from distributed_lion_tpu.train import journal
from distributed_lion_tpu.utils import compile_cache

OFFSET = -999.0          # trace clock = time.monotonic + OFFSET
SERVING = ["serve.gpt2-xl.decode-backlog", "serve.joyai-llm-flash.backlog-2k",
           "serve.laguna-s-2.1.backlog-8k",
           "serve.ling-3.0-flash-vl.backlog-1k-long",
           "serve.minicpm-sala.backlog-16k"]
TRAINING = ["train.gpt2-124m.readme", "train.gpt2-124m.vote-4chip"]
IDLE = [f"idle_{part}_ms.decode" for part in host_accounts.IDLE_PARTS]
LISTED = {**{name: SERVING for name in IDLE + [
    "read_wait_ms.decode", "stall_ms.decode", "gc_pause_ms.decode",
    "run_ahead_pct.decode"]},
    "gc_pause_ms.train": TRAINING,
    **{name: TRAINING + SERVING for name in (
        "setup_construct_s", "setup_load_s", "setup_gc_s",
        "setup_unplaced_s")}}


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def ns(seconds):
    return seconds * 1e9


# device 0 on the trace's clock (s): busy 305 ms of the window 1.0 .. 1.6
BUSY = [(1.10, 1.14), (1.14, 1.16), (1.17, 1.20), (1.20, 1.245),
        (1.26, 1.28), (1.28, 1.30), (1.31, 1.33), (1.33, 1.35),
        (1.40, 1.42), (1.42, 1.45), (1.46, 1.48), (1.48, 1.50)]
TICKS = [{"tick": 40 + k, "t0": 1000.05 + 0.1 * k,
          "t1": 1000.05 + 0.1 * k + 0.098, "active": 4, "pending": 0,
          "prefills": 0, "decode_tokens": 4} for k in range(5)]


def make_trace(busy=BUSY, steps=TICKS, stretch=1.0):
    ops = [[f"fusion.{i}", ns(lo), ns(hi - lo), f"fusion.{i} fusion( | x"]
           for i, (lo, hi) in enumerate(busy)]
    host = [["bench/step", ns(t["t0"] + OFFSET),
             ns((t["t1"] - t["t0"]) * stretch)] for t in steps]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


def span(name, lo, hi, ident, parent=None, **ids):
    """A traced span given on the TRACE's clock, kept on time.monotonic."""
    return {"name": name, "t0": lo - OFFSET, "t1": hi - OFFSET, "id": ident,
            "parent": parent, **ids}


@pytest.fixture
def program(monkeypatch):
    """What the program would hold after the run: spans of the traced
    window, accounts of the whole process, the compile ledger."""
    spans = [span("serve/tick", t["t0"] + OFFSET, t["t1"] + OFFSET, 10 + k,
                  tick=t["tick"]) for k, t in enumerate(TICKS)]
    spans += [span("serve/token_read", 1.165, 1.172, 30, 11, of="decode"),
              span("serve/token_read", 1.37, 1.39, 31, 13, of="decode")]
    monkeypatch.setattr(journal, "_TRACED", collections.deque(spans))
    monkeypatch.setattr(journal, "_ACCOUNTS", collections.deque([
        {"kind": "setup_lap", "name": "setup/before", "t0": 960.0,
         "t1": 970.0, "owner": "engine"},
        {"kind": "setup_lap", "name": "setup/place_weights", "t0": 970.0,
         "t1": 975.0, "owner": "engine"},
        {"kind": "setup_lap", "name": "setup/init_pages", "t0": 975.0,
         "t1": 976.0, "owner": "engine"},
        {"kind": "setup_lap", "name": "setup/build_dispatches", "t0": 976.0,
         "t1": 976.5, "owner": "engine"},
        {"kind": "gc_pause", "name": "gc/gen2", "t0": 990.0, "t1": 990.2,
         "generation": 2, "collected": 0},
        {"kind": "gc_pause", "name": "gc/gen2", "t0": 1000.355,
         "t1": 1000.375, "generation": 2, "collected": 3},
        {"kind": "setup_lap", "name": "setup/mesh", "t0": 1002.0,
         "t1": 1003.0, "owner": "trainer"}]))   # the reference's: after t0
    monkeypatch.setattr(compile_cache, "_EVENTS", collections.deque([
        (980.0, "decode_tick", "trace_s", "traces", 1.0, 0, 0, 0.0),
        (981.0, "decode_tick", "lower_s", "lowerings", 2.0, 0, 0, 0.0),
        (990.0, "decode_tick", "compile_s", "compiles", 20.0, 1, 0, 0.5),
        (1001.0, "reference", "compile_s", "compiles", 7.0, 0, 1, 0.0)]))


def make_ctx(trace=None, **facts):
    edge = {"ticks": 100, "decode_ticks": 90, "run_ahead_ticks": 80,
            "read_wait_s": 1.0, "gc_pause_s": 0.50, "slow_tick_excess_s": 0.0}
    stats = {"open": edge,
             "trace_open": dict(edge, decode_ticks=190, run_ahead_ticks=170),
             "trace_close": dict(edge, decode_ticks=390, run_ahead_ticks=366),
             "close": dict(edge, ticks=1100, read_wait_s=13.5,
                           gc_pause_s=0.62, slow_tick_excess_s=1.4)}
    return {"cell": {}, "peaks": None,
            "trace": make_trace() if trace is None else trace,
            "facts": {"ticks": TICKS, "t_open": 999.5, "t_close": 1029.5,
                      "trace": {"state": "done", "t0": 1000.0, "t1": 1000.6,
                                "window_s": 0.6},
                      "engine_stats": stats, "max_seqs": 4,
                      "end_to_end": {"setup_s": 40.0}, **facts}}


# ------------------------------------------------------- the two clocks
def test_the_clocks_pair_by_the_bench_step_events(program):
    assert host_accounts.clock_offset(make_ctx()) == pytest.approx(OFFSET)
    # one partial event at an edge is dropped, on either side
    extra = TICKS + [dict(TICKS[-1], t0=1000.56, t1=1000.62)]
    ctx = make_ctx(trace=make_trace(steps=extra))       # 6 events, 5 ticks
    assert host_accounts.clock_offset(ctx) == pytest.approx(OFFSET)
    ctx = make_ctx(trace=make_trace(steps=TICKS[1:]))   # 4 events, 5 ticks
    assert host_accounts.clock_offset(ctx) == pytest.approx(OFFSET)


@pytest.mark.parametrize("lost", [0, 4], ids=["first", "last"])
def test_of_two_ways_to_drop_one_the_closer_lengths_win(program, lost):
    """Steady ticks agree within 5% whichever way they are shifted: the
    pairing whose lengths lie closer is the one that is not a tick off."""
    ticks = [dict(t, t1=t["t1"] - 0.0007 * ((3 * k) % 5))
             for k, t in enumerate(TICKS)]
    ctx = make_ctx(trace=make_trace(steps=ticks[:lost] + ticks[lost + 1:]),
                   ticks=ticks)
    assert host_accounts.clock_offset(ctx) == pytest.approx(OFFSET)


@pytest.mark.parametrize("trace", [
    make_trace(steps=TICKS[2:]),         # two events short
    make_trace(stretch=1.08),            # lengths 8% apart
    make_trace(steps=[]),                # no benchmark spans in the trace
], ids=["count", "lengths", "none"])
def test_an_unpaired_clock_gives_none_for_all_five(program, trace):
    ctx = make_ctx(trace=trace)
    assert host_accounts.clock_offset(ctx) is None
    assert [read(name, ctx) for name in IDLE] == [None] * 5
    assert read("host_gap_ms.decode", ctx) == pytest.approx(59.0)  # stands


# ------------------------------------------------------------ the idle gaps
def test_the_five_idle_parts_by_hand_and_their_sum(program):
    """Gaps between device events (ms): 10 at 1.16 (5 under the read, 5 the
    tick's own), 15 at 1.245 (3 + 10 under ticks, 2 between them: the
    caller's), 10 at 1.30, 50 at 1.35 (20 collection, 15 of the read left
    outside it, 15 the tick's own), 10 at 1.45; 100 before the first event
    and 100 after the last. Five ticks."""
    ctx = make_ctx()
    got = {name: read(name, ctx) for name in IDLE}
    assert got == pytest.approx({
        "idle_gc_ms.decode": 20 / 5, "idle_read_ms.decode": 20 / 5,
        "idle_host_ms.decode": 53 / 5, "idle_caller_ms.decode": 2 / 5,
        "idle_edge_ms.decode": 200 / 5}, abs=1e-6)
    assert sum(got.values()) == pytest.approx(
        read("host_gap_ms.decode", ctx), abs=1e-9)
    assert read("host_gap_ms.decode", ctx) == pytest.approx(
        (600 - 305) / 5) == pytest.approx(layer_common.idle_ms_per_unit(ctx))


def test_a_trace_that_ends_early_lands_in_the_edge(program):
    """The device trace stops at 1.35 of a window that runs to 1.6: the
    window's arithmetic reads 250 ms more idle, all of it `edge` (and the
    collection and the read at 1.37 no longer lie between device events)."""
    ctx = make_ctx(trace=make_trace(busy=BUSY[:8]))
    got = {name: read(name, ctx) for name in IDLE}
    assert got == pytest.approx({
        "idle_gc_ms.decode": 0.0, "idle_read_ms.decode": 5 / 5,
        "idle_host_ms.decode": 28 / 5, "idle_caller_ms.decode": 2 / 5,
        "idle_edge_ms.decode": 350 / 5}, abs=1e-6)
    assert sum(got.values()) == pytest.approx(
        read("host_gap_ms.decode", ctx), abs=1e-9)


def test_no_part_is_a_remainder_so_the_sum_is_a_check(program):
    """Each of the five is measured on the trace's clock. The window's own
    arithmetic counts device time wherever it lies, so the sum reads higher
    by what lies outside the stamped window (the closing stamp is taken
    before the trace is stopped): a little of it is reported as it is; half
    a tick of it, which is what clocks paired a tick off look like, or a
    device 0 that is not the planes' average, and nothing is reported."""
    ctx = make_ctx(trace=make_trace(busy=BUSY + [(1.50, 1.6002)]))
    got = [read(name, ctx) for name in IDLE]
    assert got[-1] == pytest.approx(0.1 * 1e3 / 5)    # measured: no tail
    assert sum(got) - read("host_gap_ms.decode", ctx) == pytest.approx(0.04)
    ctx = make_ctx(trace=make_trace(busy=BUSY + [(1.50, 1.66)]))   # 60 ms
    assert [read(name, ctx) for name in IDLE] == [None] * 5
    assert read("host_gap_ms.decode", ctx) == pytest.approx((295 - 160) / 5)
    # a device busy from edge to edge, then its events one tick later
    # than the bench/step events say
    full = [(1.0, 1.10)] + BUSY + [(1.50, 1.60)]
    ctx = make_ctx(trace=make_trace(busy=full))
    assert read("idle_edge_ms.decode", ctx) == pytest.approx(0.0, abs=1e-6)
    ctx = make_ctx(trace=make_trace(busy=[(a + 0.1, b + 0.1)
                                          for a, b in full]))
    assert host_accounts.clock_offset(ctx) == pytest.approx(OFFSET)
    assert [read(name, ctx) for name in IDLE] == [None] * 5
    # a second chip that idles more: the window's arithmetic is the
    # planes' average, the five are device 0's
    trace = make_trace()
    trace["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": trace["planes"][0]["lines"][0][
            "events"][:6]}]})
    assert [read(name, make_ctx(trace=trace)) for name in IDLE] == [None] * 5


def test_interval_difference():
    minus = host_accounts._minus
    assert minus([[0, 10], [20, 30]], []) == [[0, 10], [20, 30]]
    assert minus([[0, 10], [20, 30]], [[2, 4], [8, 22], [29, 40]]) == \
        [[0, 2], [4, 8], [22, 29]]
    assert minus([[0, 10]], [[0, 10]]) == []
    assert minus([[5, 6]], [[0, 1], [9, 12]]) == [[5, 6]]


# ------------------------------------------------------------- the counters
def test_the_window_counters_by_hand(program):
    ctx = make_ctx()
    assert read("read_wait_ms.decode", ctx) == pytest.approx(12.5)
    assert read("stall_ms.decode", ctx) == pytest.approx(1.4)   # of 1,000
    assert read("gc_pause_ms.decode", ctx) == pytest.approx(0.12)
    assert read("run_ahead_pct.decode", ctx) == pytest.approx(98.0)
    ctx = make_ctx()
    del ctx["facts"]["engine_stats"]["close"]     # the window never closed
    assert [read(n, ctx) for n in ("read_wait_ms.decode", "stall_ms.decode",
                                   "gc_pause_ms.decode")] == [None] * 3


def test_training_reads_its_pauses_from_the_accounts(program):
    ctx = make_ctx()
    del ctx["facts"]["t_open"], ctx["facts"]["engine_stats"]
    ctx["facts"]["trace"]["steps"] = 3
    assert read("gc_pause_ms.train", ctx) == pytest.approx(20 / 3)
    # set-up ended two traced-window steps before the trace opened
    assert host_accounts.window_open(ctx) == pytest.approx(999.6)
    assert read("setup_gc_s", ctx) == pytest.approx(0.2)
    del ctx["facts"]["trace"]["steps"]
    assert read("gc_pause_ms.train", ctx) is None


# ------------------------------------------------------------------- set-up
def test_setup_is_taken_apart_and_adds_up(program):
    ctx = make_ctx()
    parts = {n: read(n, ctx) for n in (
        "setup_construct_s", "setup_load_s", "setup_gc_s",
        "setup_unplaced_s")}
    assert parts == pytest.approx({
        "setup_construct_s": 6.5,   # the engine's laps, `before` left out
        "setup_load_s": 20.0,       # without the reference's 7 s
        "setup_gc_s": 0.2,          # the one that ended before t_open
        "setup_unplaced_s": 40.0 - 6.5 - 3.0 - 20.0})
    assert read("lower_s", ctx) == pytest.approx(3.0)
    assert parts["setup_construct_s"] + read("lower_s", ctx) \
        + parts["setup_load_s"] + parts["setup_unplaced_s"] \
        == pytest.approx(ctx["facts"]["end_to_end"]["setup_s"])


def test_what_the_bounded_buffers_pushed_out_is_not_papered_over(
        program, monkeypatch):
    ctx = make_ctx()
    monkeypatch.setattr(journal, "_accounts_dropped", 1)
    assert read("setup_construct_s", ctx) is None
    assert read("idle_gc_ms.decode", ctx) == pytest.approx(4.0)
    ctx = make_ctx()
    monkeypatch.setattr(journal, "_dropped", 2)
    assert [read(name, ctx) for name in IDLE] == [None] * 5


# ------------------------------------------------ a program without accounts
def test_a_program_without_accounts_reports_nothing(program, monkeypatch):
    """The parent commit: no ``journal.accounts``, none of the new keys in
    ``engine.stats``. Only ``run_ahead_pct.decode`` reads counters an older
    program already has (PR 36's)."""
    monkeypatch.delattr(journal, "accounts")
    ctx = make_ctx()
    for copy_ in ctx["facts"]["engine_stats"].values():
        for key in ("read_wait_s", "gc_pause_s", "slow_tick_excess_s"):
            copy_.pop(key, None)
    for name in LISTED:
        want = pytest.approx(98.0) if name == "run_ahead_pct.decode" else None
        assert read(name, ctx) == want, name
    del ctx["facts"]["engine_stats"]
    assert read("run_ahead_pct.decode", ctx) is None


# ------------------------------------------------------------- the manifest
def test_the_entries_are_listed_for_their_cells():
    manifest = harness.load_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for name, cells in LISTED.items():
        entry = by_name[name]
        assert set(cells) <= set(entry["workloads"]), name
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "layer_metrics", name + ".py"))
        for cell in cells:
            mine = harness.load_cell(cell, manifest)
            assert name in {m["name"] for m in mine["per_layer"]}
            assert entry["moves"] in {m["name"] for m in mine["end_to_end"]}
        assert entry["moves"] in e2e
    assert {by_name[n]["source"] for n in IDLE} == {"device_trace"}
    assert by_name["setup_unplaced_s"]["source"] == "host_clock"
    assert by_name["setup_construct_s"]["source"] == "program_span"
    assert {by_name[n]["moves"] for n in LISTED if n.startswith("setup_")} \
        == {"setup_s"}


# --------- the bodies of the tests pinned to an older manifest, run again
def _pinned(file_name):
    spec = importlib.util.spec_from_file_location(
        "pinned_" + file_name[:-3],
        os.path.join(os.path.dirname(__file__), file_name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _names_in(fn) -> set:
    """Every string the function's own source holds."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    return {n.value for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


@pytest.mark.parametrize("file_name, cell", [
    ("test_bm_ling.py", "serve.ling-3.0-flash-vl.backlog-1k-long"),
    ("test_bm_minicpm_sala.py", "serve.minicpm-sala.backlog-16k")])
def test_the_cells_other_facts_stand_with_later_readers_listed(
        monkeypatch, file_name, cell):
    """Both files hold their cell's list of per-layer metrics by ``==``
    (``tests/conftest.PINNED_TO_AN_OLDER_MANIFEST``). Every fact the pinned
    body holds is held here through that body itself, which is shown the
    cell's metrics that its own source names: every one of them has to be
    listed still, and a reader that a later PR lists (this PR's thirteen,
    the next PR's) is not its business."""
    pinned = _pinned(file_name)
    body = pinned.test_the_cell_is_found_by_name_and_states_its_cut
    known = _names_in(body)
    now = {m["name"] for m in harness.load_cell(cell)["per_layer"]}
    assert set(LISTED) - {"gc_pause_ms.train"} <= now - known

    def load_cell(name, manifest=None):
        loaded = harness.load_cell(name, manifest)
        loaded["per_layer"] = [m for m in loaded["per_layer"]
                               if m["name"] in known]
        return loaded

    monkeypatch.setattr(pinned, "harness", types.SimpleNamespace(
        **{**vars(harness), "load_cell": load_cell}))
    args = [harness.load_family(harness.load_cell(cell)["config"])] \
        if inspect.signature(body).parameters else []
    body(*args)


def test_latent_prefills_entry_stands_wherever_it_is_listed(monkeypatch):
    """``test_bm_latent_prefill.py`` reads its entry at ``per_layer[-1]``
    (PINNED_TO_AN_OLDER_MANIFEST): the body is run on the manifest with
    that entry, found by NAME, put last; what it holds of the entry and of
    the cells it lists is held wherever a later PR's entries put it."""
    pinned = _pinned("test_bm_latent_prefill.py")

    def load_manifest():
        manifest = copy.deepcopy(harness.load_manifest())
        mine = [m for m in manifest["per_layer"] if m["name"] == pinned.NAME]
        assert len(mine) == 1
        manifest["per_layer"] = [m for m in manifest["per_layer"]
                                 if m["name"] != pinned.NAME] + mine
        return manifest

    monkeypatch.setattr(pinned, "harness", types.SimpleNamespace(
        **{**vars(harness), "load_manifest": load_manifest}))
    pinned.test_it_is_listed_for_the_cells_whose_buckets_take_the_kernel()
