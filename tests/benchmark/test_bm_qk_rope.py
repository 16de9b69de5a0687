"""``qk_rope_ms.train`` (PR 45): the head norm and rotation kernels found by
name in a made-up trace against values worked by hand, nothing where the
program holds none (the parent), and the entry in the manifest."""

import pytest

from benchmark.lib import harness

NAME = "qk_rope_ms.train"
CELL = "train.mellum2-12b-a2.5b.share-8k"
CALL = 'custom-call( custom_call_target="tpu_custom_call" | '


def read(ctx):
    return harness.load_module("layer_metrics", NAME).read(ctx)


def ctx_of(ops, steps):
    return {"cell": {"name": CELL}, "peaks": None,
            "facts": {"trace": {"t0": 100.0, "t1": 110.0, "steps": steps}},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops}]}]}}


def layer_ops(q_ms, k_ms, t=0.0):
    """One layer of one microbatch under the `full` rung: q's and k's
    forward, both again in the recompute, both backward kernels, the
    attention kernel that reads them and the fold of the weight's gradient
    partials (each names a kernel among its operands: not the kernel)."""
    q, k = "bf16[16384,4096]{1,0}", "bf16[16384,512]{1,0}"
    ops = []
    for n, (ms, shape) in enumerate(((q_ms, q), (k_ms, k))):
        ops += [[f"qk_rope_fwd.{n}", t, ms * 1e6,
                 f"qk_rope_fwd.{n} {CALL}{shape} %bitcast.77)"],
                [f"qk_rope_fwd.{n + 2}", t + 10e6, ms * 1e6,
                 f"qk_rope_fwd.{n + 2} {CALL}{shape} %bitcast.78)"],
                [f"qk_rope_bwd.{n}", t + 20e6, 1.5 * ms * 1e6,
                 f"qk_rope_bwd.{n} {CALL}{shape} %bitcast.79, {shape} "
                 "%flash_gqa_dq.1)"]]
    ops += [["flash_gqa_lse.1", t + 5e6, 2.3e6, f"flash_gqa_lse.1 {CALL}{q} "
             "%qk_rope_fwd.0, bf16[16384,512]{1,0} %qk_rope_fwd.1)"],
            ["reduce.5", t + 30e6, 0.01e6, "reduce.5 reduce( | f32[32,8,128]"
             "{2,1,0} %qk_rope_bwd.0)"]]
    return ops


def test_it_is_the_kernels_time_a_step_by_name_alone():
    """Two steps of two layer-microbatches: 3.5 forward-times each for q
    (0.4 ms) and k (0.06 ms); the attention kernel's 2.3 ms and the fold are
    not counted."""
    ops = [op for i in range(4) for op in layer_ops(0.4, 0.06, t=i * 100e6)]
    assert read(ctx_of(ops, steps=2)) == pytest.approx(
        2 * 3.5 * (0.4 + 0.06))


@pytest.mark.parametrize("why", ["the_parent", "no_ops", "no_device",
                                 "no_step_in_the_window"])
def test_it_reports_nothing_where_there_is_nothing_to_read(why):
    """The parent norms and rotates in XLA fusions over the head-tiled
    shape: no kernel of the name, so the line leaves the metric out."""
    parent = [["multiply_convert_fusion", 0.0, 1.1e6,
               "multiply_convert_fusion fusion( kind=kLoop | bf16[16384,32,1,"
               "128]{3,1,0,2} %bitcast.16)"],
              ["flash_gqa_lse.1", 2e6, 2.3e6, f"flash_gqa_lse.1 {CALL}"
               "bf16[2,8192,4096]{2,1,0} %reshape.26)"]]
    ctx = {"the_parent": ctx_of(parent, 2), "no_ops": ctx_of([], 2),
           "no_device": dict(ctx_of(parent, 2), trace={"planes": []}),
           "no_step_in_the_window": ctx_of(layer_ops(0.4, 0.06), 0)}[why]
    assert read(ctx) is None


def test_it_is_listed_for_cell_10_alone():
    manifest = harness.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "attention (ops/attention)",
        "moves": "train_tokens_per_s_per_chip", "workloads": [CELL]}
    cell = harness.load_cell(CELL, manifest)
    assert NAME in {m["name"] for m in cell["per_layer"]}


def test_the_cells_other_facts_stand_with_the_reader_listed(monkeypatch):
    """``test_bm_mellum.py`` pins cell 10's list of per-layer metrics by
    equality (``tests/conftest.PINNED_TO_AN_OLDER_MANIFEST``); every other
    fact it holds the cell to is held here, through its own body with this
    one name added to the list it expects."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "pinned_bm_mellum", os.path.join(os.path.dirname(__file__),
                                         "test_bm_mellum.py"))
    pinned = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pinned)
    monkeypatch.setattr(pinned, "NEW", pinned.NEW + [NAME])
    family = harness.load_family(harness.load_cell(CELL)["config"])
    pinned.test_the_cell_is_found_by_name_and_states_its_cut(family)
