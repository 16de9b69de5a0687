"""The per-layer readers that read the program's own spans and counters
(``journal.traced()``, ``compile_cache.totals()``): each on a made-up ``ctx``
and buffer against the value worked by hand, each giving ``None`` where
there is nothing to read, and ``lion_ms.train`` on a made-up trace of its
own with named Lion kernels."""

import json
import os

import pytest

from benchmark.lib import harness, xplane
from distributed_lion_tpu.train import journal
from distributed_lion_tpu.utils import compile_cache

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_READERS = ("dispatch_ms.train", "tick_host_ms.decode", "admit_ms.decode")
LEDGER_READERS = ("lower_s", "compile_misses")


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def ctx_of(t0=100.0, t1=110.0, **trace):
    return {"cell": {}, "trace": {"planes": []}, "peaks": None,
            "facts": {"trace": {"t0": t0, "t1": t1, **trace}}}


def span(name, t0, t1, id, parent=None, **ids):
    return {"name": name, "t0": t0, "t1": t1, "id": id, "parent": parent,
            **ids}


def serving_buffer():
    """Three ticks inside the window 100..110 and one that straddles its
    end. Tick 1 is decode-only: 0.400 s long with 0.390 s of token read.
    Tick 2 admits (a prefill under its admit). Tick 3 is decode-only:
    0.380 s with 0.374 s of token read. Admit self times: 1, 3 and 1 ms."""
    return [
        span("serve/tick", 100.5, 100.9, 1, tick=1),
        span("serve/expire", 100.5, 100.5001, 2, 1),
        span("serve/admit", 100.5001, 100.5011, 3, 1),
        span("serve/decode_tick", 100.5011, 100.8995, 4, 1),
        span("serve/decode_build", 100.5011, 100.504, 5, 4),
        span("serve/decode_dispatch", 100.504, 100.506, 6, 4),
        span("serve/token_read", 100.506, 100.896, 7, 4),
        span("serve/commit", 100.896, 100.8995, 8, 4),
        span("serve/tick", 101.0, 101.9, 10, tick=2),
        span("serve/admit", 101.0, 101.453, 11, 10),
        span("serve/prefill", 101.001, 101.451, 12, 11, req_id="r7"),
        span("serve/token_read", 101.002, 101.45, 13, 12),
        span("serve/decode_tick", 101.453, 101.9, 14, 10),
        span("serve/token_read", 101.46, 101.89, 15, 14),
        span("serve/tick", 102.0, 102.38, 20, tick=3),
        span("serve/admit", 102.0, 102.001, 21, 20),
        span("serve/decode_tick", 102.001, 102.38, 22, 20),
        span("serve/token_read", 102.005, 102.379, 23, 22),
        span("serve/tick", 109.8, 110.2, 30, tick=4),     # straddles t1
        span("serve/token_read", 109.81, 110.19, 31, 30),
    ]


def training_buffer():
    return [span("data_wait", 100.1, 100.2, 1, step=7),
            span("dispatch", 100.2, 100.2021, 2, step=7),
            span("dispatch", 101.2, 101.2017, 3, step=8),
            span("dispatch", 102.2, 102.2090, 4, step=9),
            span("dispatch", 99.9, 99.95, 5, step=6)]     # before t0


def test_span_readers_give_the_values_worked_by_hand(monkeypatch):
    monkeypatch.setattr(journal, "traced", training_buffer)
    # median of 2.1, 1.7 and 9.0 ms; the 50 ms one lies outside the window
    assert read("dispatch_ms.train", ctx_of()) == pytest.approx(2.1)
    monkeypatch.setattr(journal, "traced", serving_buffer)
    # tick 1: 400 - 390 = 10 ms; tick 3: 380 - 374 = 6 ms; median 8 ms
    assert read("tick_host_ms.decode", ctx_of()) == pytest.approx(8.0)
    # admit self times 1, 453 - 450 = 3 and 1 ms: mean 5/3 ms
    assert read("admit_ms.decode", ctx_of()) == pytest.approx(5.0 / 3.0)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_give_none_where_there_is_nothing(monkeypatch, name):
    monkeypatch.setattr(journal, "traced", lambda: [])
    assert read(name, ctx_of()) is None                  # empty buffer
    monkeypatch.setattr(journal, "traced", serving_buffer)
    assert read(name, ctx_of(200.0, 210.0)) is None      # nothing in window
    assert read(name, {"cell": {}, "trace": {}, "peaks": None,
                       "facts": {"trace": {"state": "off"}}}) is None
    monkeypatch.delattr(journal, "traced")               # an older program
    assert read(name, ctx_of()) is None


def test_tick_host_needs_a_decode_only_tick(monkeypatch):
    admits_only = [r for r in serving_buffer() if 10 <= r["id"] < 20]
    monkeypatch.setattr(journal, "traced", lambda: admits_only)
    assert read("tick_host_ms.decode", ctx_of()) is None
    assert read("admit_ms.decode", ctx_of()) == pytest.approx(3.0)


def ledger_events():
    """Set-up (before 100.0): ``train_step`` traced 2 s, lowered 16 s,
    compiled 40 s on a cache miss; a helper traced inside it (0.5 s, never
    lowered); ``make_weights`` traced 0.25 s, lowered 0.75 s, loaded from
    the cache in 3 s. After the window: the reference's ``loss_and_grad``."""
    return [
        (50.0, "_where", "trace_s", "traces", 0.5, 0, 0, 0.0),
        (52.0, "train_step", "trace_s", "traces", 2.0, 0, 0, 0.0),
        (68.0, "train_step", "lower_s", "lowerings", 16.0, 0, 0, 0.0),
        (88.0, "train_step", "compile_s", "compiles", 40.0, 0, 1, 0.0),
        (89.0, "make_weights", "trace_s", "traces", 0.25, 0, 0, 0.0),
        (90.0, "make_weights", "lower_s", "lowerings", 0.75, 0, 0, 0.0),
        (93.0, "make_weights", "compile_s", "compiles", 3.0, 1, 0, 2.5),
        (130.0, "loss_and_grad", "trace_s", "traces", 4.0, 0, 0, 0.0),
        (135.0, "loss_and_grad", "lower_s", "lowerings", 5.0, 0, 0, 0.0),
        (150.0, "loss_and_grad", "compile_s", "compiles", 15.0, 0, 1, 0.0),
    ]


def test_ledger_readers_sum_set_up_alone(monkeypatch):
    monkeypatch.setattr(compile_cache, "_EVENTS", ledger_events())
    # 2 + 16 + 0.25 + 0.75; the helper's 0.5 s lies inside train_step's 2 s
    assert read("lower_s", ctx_of()) == pytest.approx(19.0)
    assert read("compile_misses", ctx_of()) == 1
    row = compile_cache.ledger(until=100.0)["make_weights"]
    assert (row["cache_hits"], row["retrieval_s"]) == (1, 2.5)
    assert compile_cache.totals()["cache_misses"] == 2   # whole process


@pytest.mark.parametrize("name", LEDGER_READERS)
def test_ledger_readers_give_none_where_there_is_nothing(monkeypatch, name):
    monkeypatch.setattr(compile_cache, "_EVENTS", [])
    assert read(name, ctx_of()) is None                  # empty ledger
    monkeypatch.setattr(compile_cache, "_EVENTS", ledger_events())
    assert read(name, ctx_of(10.0, 20.0)) is None        # none by then
    assert read(name, {"cell": {}, "trace": {}, "peaks": None,
                       "facts": {"trace": {"state": "off"}}}) is None
    monkeypatch.delattr(compile_cache, "totals")         # an older program
    assert read(name, ctx_of()) is None


def test_lion_ms_counts_the_named_lion_kernels_alone():
    with open(os.path.join(HERE, "lion_trace_fixture.json")) as f:
        trace = json.load(f)
    ctx = dict(ctx_of(steps=2, window_s=0.0305), trace=trace)
    # a step: lion_ballot 1 ms + lion_apply 2 ms; not the flash kernel's
    # 5 ms, not the two ops that only consume a Lion kernel's output
    assert read("lion_ms.train", ctx) == pytest.approx(3.0)
    plane = xplane.device_planes(trace)[0]
    by_name = xplane.matching_s(plane, r"lion_ballot|lion_apply")
    assert by_name == pytest.approx(0.006)     # the names alone say the same
    assert xplane.matching_s(plane, r"flash_attention") == pytest.approx(0.01)


def test_every_new_reader_is_listed_for_its_cells():
    manifest = harness.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    train = ["train.gpt2-124m.readme", "train.gpt2-124m.vote-4chip"]
    serve = ["serve.gpt2-xl.decode-backlog"]
    assert listed["lion_ms.train"]["workloads"] == train
    assert listed["dispatch_ms.train"]["workloads"] == train
    assert listed["tick_host_ms.decode"]["workloads"] == serve
    assert listed["admit_ms.decode"]["workloads"] == serve
    for name in LEDGER_READERS:
        assert sorted(listed[name]["workloads"]) == sorted(train + serve)
        assert listed[name]["moves"] == "setup_s"
    assert "lion_roofline" not in listed
