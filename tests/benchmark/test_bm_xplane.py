"""The reduction from a profiler trace to numbers, on hand-made intervals
and on a small trace recorded on the chip (kept as plain JSON)."""

import json
import os

import pytest

from benchmark.lib import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def trace_of(ops, host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in host]}]}]}


def test_busy_is_the_union_of_op_intervals():
    tr = trace_of([("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("a", 32, 1)])
    assert xplane.merged(tr["planes"][0]["lines"][0]["events"]) \
        == [[0, 15], [30, 35]]
    assert xplane.device_busy_s(tr) == pytest.approx(20e-9)


def test_kernel_time_by_name_and_top_ops():
    tr = trace_of([("fusion.1", 0, 4e9), ("_flash_attention_kernel", 5e9, 2e9),
                   ("_flash_attention_dq_kernel", 8e9, 1e9)])
    plane = xplane.device_planes(tr)[0]
    assert xplane.matching_s(plane, r"flash_attention") == pytest.approx(3.0)
    assert xplane.top_ops(tr, 2) == [["fusion.1", 4.0],
                                     ["_flash_attention_kernel", 2.0]]


MOSAIC = 'custom-call( custom_call_target="tpu_custom_call" | '


def test_a_consumer_of_a_kernels_output_is_not_the_kernel():
    plane = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["flash_attention.1", 0, 5e9, "flash_attention.1 " + MOSAIC
         + "bf16[4,12,1024,64] %q)"],
        ["multiply_reduce_fusion.2", 6e9, 1e9, "multiply_reduce_fusion.2 "
         "fusion( kind=kLoop | bf16[4,12,1024,64] %jit_flash_attention_.6)"],
        ["lion_apply.9", 8e9, 2e9, "lion_apply.9 " + MOSAIC
         + "f32[32,128] %pad.1)"],
        ["slice.11", 11e9, 1e9, "slice.11 slice( | f32[32,128] %lion_apply.9)"]
    ]}]}
    from benchmark.lib.layer_common import FLASH_KERNELS, LION_KERNELS

    assert xplane.matching_s(plane, FLASH_KERNELS) == pytest.approx(5.0)
    assert xplane.matching_s(plane, LION_KERNELS) == pytest.approx(2.0)


def test_another_mosaic_kernel_is_not_lion_time():
    """The Lion kernels are found by the names the program gives them: a
    Mosaic call without a name of theirs (``step.9``, as the chip's trace
    had them before the program named its kernels) and another kernel's
    (``paged_attn``) are not counted, alone or beside a Lion kernel."""
    from benchmark.lib.layer_common import LION_KERNELS, PAGED_ATTN_KERNEL

    others = [["step.9", 0, 2e9, "step.9 " + MOSAIC + "f32[32,128] %pad.1)"],
              ["paged_attn.1", 3e9, 4e9, "paged_attn.1 " + MOSAIC
               + "s32[32] %multiply_minimum_fusion, s32[2048] %reshape.27)"]]
    lion = ["lion_ballot.7", 8e9, 1e9, "lion_ballot.7 " + MOSAIC
            + "f32[4608,128] %pad_bitcast_fusion.2)"]

    def plane(events):
        return {"name": "/device:TPU:0",
                "lines": [{"name": "XLA Ops", "events": events}]}

    assert xplane.matching_s(plane(others), LION_KERNELS) == 0.0
    assert xplane.matching_s(plane(others + [lion]), LION_KERNELS) \
        == pytest.approx(1.0)
    assert xplane.matching_s(plane(others + [lion]), PAGED_ATTN_KERNEL) \
        == pytest.approx(4.0)


def test_idle_gaps_are_named_after_the_covering_host_span():
    tr = trace_of([("a", 0, 10), ("b", 50, 10), ("c", 100, 10)],
                  host=[("bench/step", 8, 40), ("bench/idle_wait", 61, 30)])
    gaps = dict(xplane.idle_gaps(tr))
    assert gaps == {"bench/step": pytest.approx(40e-9),
                    "bench/idle_wait": pytest.approx(40e-9)}


def test_exposed_time_is_what_no_other_op_covers():
    coll = xplane.merged([("all-to-all", 0, 10), ("all-gather", 20, 10)])
    other = xplane.merged([("fusion", 5, 20)])
    assert xplane.overlap_ns(coll, other) == pytest.approx(5 + 5)


def test_collectives_from_both_lines_and_their_exposed_part():
    from benchmark.lib.collectives import collective_intervals

    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["all-to-all.3", 0, 10, "all-to-all.3 all-to-all(...)"],
            ["fusion.1", 20, 30, "fusion.1 fusion(...)"],
            ["all-gather-start.1", 25, 1, "all-gather-start.1 ..."],
            ["all-gather-done.1", 59, 1, "all-gather-done.1 ..."]]},
        {"name": "Async XLA Ops", "events": [
            ["all-gather-start.1", 25, 35, "all-gather-start.1 ..."]]}]}
    coll = collective_intervals(plane)
    assert coll == [[0, 10], [25, 60]]
    other = xplane.merged([["fusion.1", 20, 30]])
    exposed = sum(hi - lo for lo, hi in coll) - xplane.overlap_ns(coll, other)
    assert exposed == pytest.approx(10 + 10)      # [0,10] and [50,60]


def test_devices_are_averaged():
    tr = trace_of([("a", 0, 10)])
    tr["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["a", 0, 30]]}]})
    assert xplane.device_busy_s(tr) == pytest.approx(20e-9)


def test_recorded_chip_trace_reduces():
    with open(os.path.join(HERE, "recorded_trace_v5e.json")) as f:
        tr = json.load(f)
    planes = xplane.device_planes(tr)
    assert planes and planes[0]["name"] == "/device:TPU:0"
    ops = xplane.line_events(planes[0], xplane.OPS_LINE)
    assert ops, "the device plane has an 'XLA Ops' line"
    assert 0 < xplane.device_busy_s(tr) <= sum(e[2] for e in ops) / 1e9
    assert xplane.matching_s(planes[0], r"flash_attention") > 0
    assert xplane.top_ops(tr, 3)[0][1] > 0
