"""``prefill_device_ms.decode`` (PR 40): the engine's prefill program found
by name among a trace's executed programs, on made-up traces against values
worked by hand, and its entry in the manifest."""

import pytest

from benchmark.lib import harness

NAME = "prefill_device_ms.decode"
CELL = "serve.gpt2-xl.decode-backlog"


def read(ctx):
    return harness.load_module("layer_metrics", NAME).read(ctx)


def ctx_of(modules, ops=()):
    lines = [{"name": "XLA Ops", "events": [list(e) for e in ops]}]
    if modules is not None:
        lines.append({"name": "XLA Modules",
                      "events": [list(e) for e in modules]})
    return {"cell": {"name": CELL}, "peaks": None, "facts": {},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": lines}]}}


def test_it_is_the_median_run_of_the_prefill_program():
    """Two buckets in one window (cell 2: four prefills in five fall in the
    1,024 bucket): the median is the larger bucket's; the decode tick, the
    copy-on-write program and a program whose name merely starts alike are
    not prefills."""
    modules = [("jit_decode_tick(123)", 0.0, 15e6),
               ("jit_prefill(456)", 15e6, 24e6),
               ("jit_prefill(789)", 39e6, 12e6),          # the 512 bucket
               ("jit_prefill(456)", 51e6, 26e6),
               ("jit_prefill_draft(1)", 77e6, 500e6),
               ("jit_cow_copy(2)", 577e6, 1e6),
               ("jit_decode_tick(123)", 578e6, 15e6)]
    assert read(ctx_of(modules)) == pytest.approx(24.0)
    assert read(ctx_of(modules[:3])) == pytest.approx(18.0)   # of two: mean


@pytest.mark.parametrize("why", ["no_prefill_in_the_window", "no_programs",
                                 "no_device", "a_training_cell"])
def test_it_reports_nothing_where_there_is_nothing_to_read(why):
    ops = [("fusion.1", 0.0, 1e6, "fusion.1 fusion( kind=kLoop | ")]
    ctx = {"no_prefill_in_the_window":
           ctx_of([("jit_decode_tick(123)", 0.0, 15e6)], ops),
           "no_programs": ctx_of(None, ops),
           "no_device": dict(ctx_of([]), trace={"planes": []}),
           "a_training_cell":
           ctx_of([("jit_train_step(9)", 0.0, 1300e6)], ops)}[why]
    assert read(ctx) is None


def test_it_is_listed_for_cell_2_alone():
    manifest = harness.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "serving dispatches (engine prefill, decode)",
        "moves": "serve_out_tokens_per_s", "workloads": [CELL]}
    cell = harness.load_cell(CELL, manifest)
    assert NAME in {m["name"] for m in cell["per_layer"]}
