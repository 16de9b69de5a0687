"""``paged_attn_ms.decode`` and ``paged_attn_roofline`` on made-up traces:
the decode kernel's ops by name, per traced tick; an op that merely consumes
the kernel's output is not the kernel; a program without the kernel (the
parent of the PR that added it) reports nothing; the share of the roofline
from the engine's own page counter between the trace's edges, never
clamped."""

import pytest

from benchmark.lib import harness


def read(ctx, name="paged_attn_ms.decode"):
    return harness.load_module("layer_metrics", name).read(ctx)


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


def roofline_ctx(kernel_ns, pages, **facts):
    """A traced window whose kernel ops took ``kernel_ns`` and in which the
    engine counted ``pages`` pages read, over cell 2's pool."""
    ctx = ctx_of([["paged_attn.2", 0, kernel_ns, "paged_attn.2 " + KERNEL],
                  ["fusion.9", 9e9, 7e6, "fusion.9 fusion( kind=kLoop | "
                   "bf16[32,32,1664] %paged_attn.2)"]])
    ctx["peaks"] = {"hbm_bytes_per_s": 819e9}
    ctx["facts"].update(
        kv_pool={"leaf_shape": [2048, 16, 1, 1664], "leaves": 96,
                 "itemsize": 2},
        engine_stats={
            "open": {"kv_pages_read": 1000, "decode_ticks": 64},
            "trace_open": {"kv_pages_read": 50_000, "decode_ticks": 100},
            "trace_close": {"kv_pages_read": 50_000 + pages,
                            "decode_ticks": 182},
            "close": {"kv_pages_read": 9_000_000, "decode_ticks": 1100}})
    ctx["facts"].update(facts)
    return ctx


def test_roofline_share_from_the_engines_page_counter():
    # PR 24's traced run: 82 ticks of 1,376 pages, the kernel 10.13 ms a
    # tick; a page is 53,248 B in each of 96 leaves
    got = read(roofline_ctx(82 * 10.13e6, 82 * 1376), "paged_attn_roofline")
    want = 100.0 * (1376 * 53_248 * 96 / 819e9) / 10.13e-3
    assert got == pytest.approx(want) and 84.0 < got < 86.0


def test_roofline_share_over_100_is_reported_not_clamped():
    got = read(roofline_ctx(82 * 5e6, 82 * 1376), "paged_attn_roofline")
    assert got == pytest.approx(171.77, abs=0.01)


def test_roofline_reader_with_nothing_to_read_returns_nothing():
    name = "paged_attn_roofline"
    assert read(roofline_ctx(82 * 10e6, 0), name) is None       # no pages
    assert read(roofline_ctx(82 * 10e6, 9, kv_pool=None), name) is None
    untraced = roofline_ctx(82 * 10e6, 9)
    del untraced["facts"]["engine_stats"]["trace_close"]
    assert read(untraced, name) is None
    older = roofline_ctx(82 * 10e6, 9)
    del older["facts"]["engine_stats"]                # an older driver
    assert read(older, name) is None
    no_kernel = roofline_ctx(82 * 10e6, 9)
    no_kernel["trace"]["planes"][0]["lines"][0]["events"] = []
    assert read(no_kernel, name) is None
    assert read(dict(roofline_ctx(1e6, 9), trace={"planes": []}), name) is None
