"""``xent_ms.train`` and ``xent_roofline`` (the loss head's kernels,
ops/pallas_xent): the kernels' ops by name, a step; an op that merely
consumes a kernel's output is not the kernel; a program without them (the
parent of the PR that added them) reports nothing; three products of
2 x rows x d x vocab over the bf16 peak, never clamped; and both on a
piece of a recorded chip trace of ``train.gpt2-124m.readme``."""

import json
import os

import pytest

from benchmark.lib import harness, loss_head

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN = ["train.gpt2-124m.readme", "train.gpt2-124m.vote-4chip"]
PEAKS = {"bf16_flops_per_s": 197e12}
GPT2 = {"n_embd": 768, "vocab_size": 50257}
CALL = 'custom-call( custom_call_target="tpu_custom_call" | '


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def ctx_of(ops, steps, accum, micro, block=1024):
    return {"cell": {"config": GPT2}, "peaks": PEAKS,
            "facts": {"trace": {"t0": 100.0, "t1": 110.0, "steps": steps},
                      "job": {"accum": accum, "micro": micro, "block": block,
                              "world": 1}},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops}]}]}}


def step_ops(fwd_ms, bwd_ms, microbatches, t=0.0):
    """One step's loss-head ops: a forward and a backward kernel a
    microbatch, and the reduction that consumes the backward's partial head
    gradients (it names the kernel among its operands: not the kernel)."""
    ops = []
    for _ in range(microbatches):
        ops += [["fused_xent_fwd.11", t, fwd_ms * 1e6, "fused_xent_fwd.11 "
                 + CALL + "bf16[20480,768]{1,0} %fusion.9, bf16[50257,768]"
                 "{1,0} %convert.3)"],
                ["fused_xent_bwd.11", t + 50e6, bwd_ms * 1e6,
                 "fused_xent_bwd.11 " + CALL + "bf16[50257,768]{1,0} "
                 "%convert.3, bf16[20480,768]{1,0} %fusion.9)"],
                ["convert_reduce_fusion.4", t + 90e6, 0.4e6,
                 "convert_reduce_fusion.4 fusion( kind=kLoop | f32[2,50257,"
                 "768]{2,1,0} %fused_xent_bwd.11)"]]
        t += 100e6
    return ops


def test_kernel_time_a_step_by_name_alone():
    ops = step_ops(9.6, 24.85, 8) + step_ops(9.6, 24.85, 8, t=2e9)
    got = read("xent_ms.train", ctx_of(ops, steps=2, accum=8, micro=20))
    assert got == pytest.approx(8 * (9.6 + 24.85))        # not the 0.4 ms


def test_a_program_without_the_kernels_reports_nothing():
    """The parent's step: XLA fusions over the float32 logits."""
    ops = [["subtract_subtract_fusion.2", 0, 99.7e6,
            "subtract_subtract_fusion.2 fusion( kind=kLoop | "
            "f32[20,1023,50257]{2,1,0} %fusion.2982)"]]
    for name in ("xent_ms.train", "xent_roofline"):
        assert read(name, ctx_of(ops, 1, 8, 20)) is None
        assert read(name, ctx_of([], 1, 8, 20)) is None
        assert read(name, dict(ctx_of(ops, 1, 8, 20),
                               trace={"planes": []})) is None
    assert read("xent_ms.train", ctx_of(step_ops(1, 2, 1), 0, 8, 20)) is None


def test_least_work_is_three_products_over_the_labelled_rows():
    # a sequence of 1,024 tokens has 1,023 labels; logits, dh, dwte; the
    # backward's recomputed logits are not work the loss needs
    assert loss_head.PRODUCTS == 3
    one = 2 * 160 * 1023 * 768 * 50257
    assert loss_head.head_flops(160, 1024, 768, 50257) == 3 * one
    assert one / 197e12 == pytest.approx(64.14e-3, rel=1e-3)   # ISSUE 29


def test_roofline_share_at_cell_1s_first_traced_run():
    # my chip run, PR 29: fused_xent_fwd 76.7 ms + fused_xent_bwd 198.8 ms
    # a step of 8 microbatches of 20 sequences
    ops = step_ops(76.7 / 8, 198.8 / 8, 8)
    got = read("xent_roofline", ctx_of(ops, steps=1, accum=8, micro=20))
    want = 100.0 * (3 * 2 * 160 * 1023 * 768 * 50257 / 197e12) / 275.5e-3
    assert got == pytest.approx(want) and 69.0 < got < 70.5


def test_roofline_share_over_100_is_reported_not_clamped():
    ops = step_ops(4.0, 8.0, 8)           # faster than the MXU allows
    got = read("xent_roofline", ctx_of(ops, steps=1, accum=8, micro=20))
    assert got == pytest.approx(200.4, abs=0.1)


def test_both_are_listed_for_the_training_cells():
    listed = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    for name in ("xent_ms.train", "xent_roofline"):
        m = listed[name]
        assert m["workloads"] == TRAIN
        assert m["layer"] == "loss head (ops/xent)"
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    assert listed["xent_roofline"]["unit"] == "%"
    # the pattern and the count live with the new readers, not in the files
    # the benchmark already had
    for old in ("layer_common.py", "roofline.py"):
        with open(os.path.join(harness.BENCH_DIR, "lib", old)) as f:
            assert "xent" not in f.read()


def test_on_a_piece_of_a_recorded_chip_trace():
    """The loss head's ops of one step of cell 1, as the chip recorded
    them (``xent_trace_v5e.json`` says how it was cut): 8 forward and 8
    backward kernels and their 32 neighbours, which are not the kernels."""
    with open(os.path.join(HERE, "xent_trace_v5e.json")) as f:
        trace = json.load(f)
    events = trace["planes"][0]["lines"][0]["events"]
    names = [e[0].split(".")[0] for e in events]
    assert names.count("fused_xent_fwd") == names.count("fused_xent_bwd") == 8
    ctx = dict(ctx_of([], steps=1, accum=8, micro=20), trace=trace)
    ms = read("xent_ms.train", ctx)
    assert ms == pytest.approx(275.586843)           # not the others' 4.6 ms
    share = read("xent_roofline", ctx)
    assert share == pytest.approx(100 * 3 * 64.1409 / 275.586843, rel=1e-4)
    assert 69.5 < share < 70.1 and share <= 100.0
