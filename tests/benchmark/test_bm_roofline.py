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
