"""The operations and bytes the benchmark's roofline shares divide by."""

from benchmark.lib import harness, roofline


def test_gpt2_124m_train_flops_per_token():
    flops = roofline.gpt2_train_flops_per_token(12, 768, 50257, 1024)
    assert flops == 854_438_400
    # bench.py counts 6 FLOPs for every parameter, matmul or not
    assert 0.99 < flops / 859_885_056 < 1.0


def test_flash_attention_flops():
    fwd = roofline.flash_attention_flops(1, 1, 1024, 64, causal=False,
                                         backward=False)
    assert fwd == 4 * 1024 * 1024 * 64
    assert roofline.flash_attention_flops(2, 3, 1024, 64, causal=False,
                                          backward=False) == 6 * fwd
    assert roofline.flash_attention_flops(1, 1, 1024, 64, causal=True,
                                          backward=True) == 3 * fwd // 2


def test_lion_kernel_bytes():
    # ballot: read g, m (4 + 4), write 1; apply: read p, g, m, total
    # (4 x 4), write p, m (2 x 4)
    assert roofline.lion_kernel_bytes(1000, world=1) == 1000 * (9 + 24)


def test_paged_attn_bytes_at_cell_2s_traced_run():
    # PR 24's traced run of serve.gpt2-xl.decode-backlog (PERF.md section
    # 5): 1,376 pages a tick, a page 16 rows of 1,664 bf16 lanes = 53,248 B
    # a leaf, keys and values, 48 layers: 7.03 GB a tick; the kernel took
    # 10.13 ms a tick: 694 GB/s, 85% of the chip's 819 GB/s
    per_tick = roofline.paged_attn_bytes(1376, 16, 1664, 48)
    assert per_tick == 1376 * 53_248 * 2 * 48 == 7_033_847_808
    share = per_tick / 819e9 / 10.13e-3
    assert 0.84 < share < 0.86
    # a float32 pool moves twice the bytes
    assert roofline.paged_attn_bytes(1376, 16, 1664, 48, itemsize=4) \
        == 2 * per_tick


def test_peaks_table_is_keyed_by_device_kind_with_source():
    table = harness.read_json(harness.BENCH_DIR, "peaks.json")
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9 and v5e["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in table["source"]
    try:
        harness.peaks_for("TPU v9 imaginary")
    except SystemExit as e:
        assert "not in" in str(e)
    else:
        raise AssertionError("an unknown device kind must be an error")
