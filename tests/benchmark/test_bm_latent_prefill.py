"""``latent_prefill_ms.decode`` (PR 47): the latent prefill's attention kernel
found by name inside the engine's prefill programs of a made-up trace, against
values worked by hand; nothing where the program holds none (the parent); and
the entry in the manifest."""

import pytest

from benchmark.lib import harness

NAME = "latent_prefill_ms.decode"
CELL = "serve.xing4.0-29b-a4b.backlog-4k-in"
CALL = 'custom-call( custom_call_target="tpu_custom_call" | '
OUT = "bf16[1,4096,4096]{2,1,0}"


def read(ctx):
    return harness.load_module("layer_metrics", NAME).read(ctx)


def ctx_of(modules, ops):
    lines = [{"name": "XLA Ops", "events": [list(e) for e in ops]}]
    if modules is not None:
        lines.append({"name": "XLA Modules",
                      "events": [list(e) for e in modules]})
    return {"cell": {"name": CELL}, "peaks": None, "facts": {},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": lines}]}}


def prefill_ops(t, kernel_ms, layers=6):
    """One prefill's ops from ``t`` on: the kernel once a layer, the fusion
    that reads its output (it names the kernel among its operands: not the
    kernel) and the expert layer's grouped matmul."""
    ops = []
    for n in range(layers):
        at = t + n * 10e6
        ops += [(f"latent_prefill.{6 + n}", at, kernel_ms * 1e6,
                 f"latent_prefill.{6 + n} {CALL}s32[1]{{0}} %bitcast.2, "
                 "bf16[1,32,4096,192]{3,2,1,0} %copy-done.30)"),
                (f"fusion.{300 + n}", at + 3e6, 0.4e6,
                 f"fusion.{300 + n} fusion( kind=kOutput | {OUT} "
                 f"%latent_prefill.{6 + n})"),
                (f"moe_gmm.{n}", at + 4e6, 1.7e6,
                 f"moe_gmm.{n} {CALL}bf16[16384,3584]{{1,0}} %fusion.9)")]
    return ops


def test_it_is_the_kernels_time_a_prefill_by_name_inside_the_program():
    """Two prefills of six layers at 2.5 and 2.1 ms a call and a decode tick
    between them: (6 x 2.5 + 6 x 2.1) / 2 prefills. A kernel event outside
    any prefill program (another program of the same process) is not in
    it."""
    modules = [("jit_prefill(456)", 0.0, 70e6),
               ("jit_decode_tick(123)", 70e6, 16e6),
               ("jit_prefill(456)", 100e6, 70e6)]
    ops = prefill_ops(1e6, 2.5) + prefill_ops(101e6, 2.1) + [
        ("mla_paged_attn.3", 71e6, 4e6, f"mla_paged_attn.3 {CALL}{OUT} %x)"),
        ("latent_prefill.99", 180e6, 9e6, f"latent_prefill.99 {CALL}{OUT})")]
    assert read(ctx_of(modules, ops)) == pytest.approx(6 * (2.5 + 2.1) / 2)


@pytest.mark.parametrize("why", ["the_parent", "no_prefill_in_the_window",
                                 "no_programs", "no_device"])
def test_it_reports_nothing_where_there_is_nothing_to_read(why):
    """The parent's prefill walks its chunks in XLA fusions and a while
    loop: no kernel of the name, so the line leaves the metric out."""
    parent = [("fusion.1346", 1e6, 0.9e6,
               "fusion.1346 fusion( kind=kOutput | f32[32,32,4096]{2,1,0} "
               "%bitcast.16)"),
              ("moe_gmm.39", 2e6, 1.7e6,
               f"moe_gmm.39 {CALL}bf16[16384,3584]{{1,0}} %fusion.9)")]
    prefill = [("jit_prefill(456)", 0.0, 96e6)]
    ctx = {"the_parent": ctx_of(prefill, parent),
           "no_prefill_in_the_window":
           ctx_of([("jit_decode_tick(123)", 0.0, 16e6)],
                  prefill_ops(1e6, 2.5)),
           "no_programs": ctx_of(None, prefill_ops(1e6, 2.5)),
           "no_device": dict(ctx_of(prefill, parent), trace={"planes": []}),
           }[why]
    assert read(ctx) is None


def test_it_is_listed_for_the_cells_whose_buckets_take_the_kernel():
    """Cell 9's one bucket of 4,096 and cell 5's of 2,048 (its 1,024 bucket
    is under the rule's bound); cell 7 runs the kernel in one layer of seven
    and cell 11 keeps the masked form: not listed."""
    manifest = harness.load_manifest()
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "serving dispatches (engine prefill, decode)",
        "moves": "serve_out_tokens_per_s",
        "workloads": [CELL, "serve.joyai-llm-flash.backlog-2k"]}
    for name in entry["workloads"]:
        cell = harness.load_cell(name, manifest)
        assert NAME in {m["name"] for m in cell["per_layer"]}
        assert "serve_out_tokens_per_s" in {m["name"]
                                            for m in cell["end_to_end"]}


def test_the_cells_other_facts_stand_with_the_reader_listed(monkeypatch):
    """``test_bm_xing.py`` pins cell 9's list of per-layer metrics by
    equality (``tests/conftest.PINNED_TO_AN_OLDER_MANIFEST``); every other
    fact it holds the cell to is held here, through its own body with this
    one name added to the list it expects."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "pinned_bm_xing", os.path.join(os.path.dirname(__file__),
                                       "test_bm_xing.py"))
    pinned = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pinned)
    monkeypatch.setattr(pinned, "NEW", pinned.NEW + [NAME])
    family = harness.load_family(harness.load_cell(CELL)["config"])
    pinned.test_the_cell_is_found_by_name_and_states_its_cut(family)
