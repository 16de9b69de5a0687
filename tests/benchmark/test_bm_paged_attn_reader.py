"""``paged_attn_ms.decode`` on made-up traces: the decode kernel's ops by
name, per traced tick; an op that merely consumes the kernel's output is
not the kernel; a program without the kernel (the parent of the PR that
added it) reports nothing."""

import pytest

from benchmark.lib import harness


def read(ctx):
    return harness.load_module("layer_metrics", "paged_attn_ms.decode").read(ctx)


def ctx_of(ops):
    ticks = [{"t0": 100.0 + i, "t1": 100.9 + i} for i in range(4)]
    return {"cell": {}, "peaks": None,
            "facts": {"trace": {"t0": 100.0, "t1": 102.0}, "ticks": ticks},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops}]}]}}


KERNEL = ('custom-call( custom_call_target="tpu_custom_call" | '
          's32[32] %multiply_minimum_fusion, s32[2048] %reshape.27)')


def test_kernel_time_per_traced_tick():
    ops = [["paged_attn.2", 0, 3e6, "paged_attn.2 " + KERNEL],
           ["paged_attn.3", 4e6, 5e6, "paged_attn.3 " + KERNEL],
           ["fusion.9", 9e6, 7e6, "fusion.9 fusion( kind=kLoop | "
            "bf16[32,32,1664] %paged_attn.3)"]]
    # 8 ms of kernel over the 2 ticks that lie inside the traced window
    assert read(ctx_of(ops)) == pytest.approx(4.0)


def test_a_program_without_the_kernel_reports_nothing():
    ops = [["fusion.412", 0, 16e6, "fusion.412 fusion( kind=kLoop | "
            "bf16[32,64,16,25,64] %copy.41)"]]
    assert read(ctx_of(ops)) is None
    assert read(ctx_of([])) is None
