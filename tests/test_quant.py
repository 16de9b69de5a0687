"""Quantization tests: NF4/int8 round-trip error, packing, tree targeting."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_lion_tpu.ops.quant import (
    QuantizedTensor,
    dequantize,
    dequantize_tree,
    maybe_dequant,
    quantize_int8,
    quantize_nf4,
    quantize_tree,
)


def test_nf4_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32) * 0.02)
    qt = quantize_nf4(w)
    assert qt.codes.dtype == jnp.uint8
    assert qt.codes.size == w.size // 2  # 2 codes per byte → 0.5 B/param
    deq = dequantize(qt, jnp.float32)
    assert deq.shape == w.shape
    # NF4 relative error for gaussian weights: well under absmax/2 per block
    err = np.abs(np.asarray(deq) - np.asarray(w))
    assert err.max() < 0.02 * 0.5
    # correlation stays near 1
    c = np.corrcoef(np.asarray(deq).ravel(), np.asarray(w).ravel())[0, 1]
    assert c > 0.98


def test_int8_roundtrip_tighter_than_nf4():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    err8 = np.abs(np.asarray(dequantize(quantize_int8(w), jnp.float32)) - np.asarray(w)).max()
    err4 = np.abs(np.asarray(dequantize(quantize_nf4(w), jnp.float32)) - np.asarray(w)).max()
    assert err8 < err4


def test_nonmultiple_block_padding():
    w = jnp.asarray(np.random.default_rng(2).normal(size=(7, 13)).astype(np.float32))
    deq = dequantize(quantize_nf4(w, block=64), jnp.float32)
    assert deq.shape == (7, 13)


def test_quantize_tree_targets_large_2d_only():
    tree = {
        "big": jnp.ones((128, 64)),
        "norm": jnp.ones((64,)),
        "small": jnp.ones((4, 4)),
    }
    q = quantize_tree(tree, "nf4", min_size=1024)
    assert isinstance(q["big"], QuantizedTensor)
    assert not isinstance(q["norm"], QuantizedTensor)
    assert not isinstance(q["small"], QuantizedTensor)
    dense = dequantize_tree(q)
    assert dense["big"].shape == (128, 64)


def test_shaped_layout_selected_and_rank_aligned():
    """Aligned shapes get the shaped (TP-shardable) layout: codes/absmax
    keep the dense rank; odd shapes fall back to flat."""
    w = jnp.ones((128, 64))
    qt = quantize_nf4(w, block=16)
    assert qt.layout == "shaped"
    assert qt.codes.shape == (128, 32)      # last dim / 2
    assert qt.absmax.shape == (128, 4)      # last dim / block
    q8 = quantize_int8(w, block=16)
    assert q8.layout == "shaped" and q8.codes.shape == (128, 64)
    assert quantize_nf4(jnp.ones((7, 13)), block=64).layout == "flat"
    # 3-D (GPT-2's stacked qkv) keeps rank too
    q3 = quantize_nf4(jnp.ones((8, 3, 64)), block=16)
    assert q3.layout == "shaped" and q3.codes.shape == (8, 3, 32)


def test_shaped_matches_flat_numerics():
    """For aligned shapes the shaped layout is a pure re-layout: identical
    dequantized values to the flat path (row-major blocks never straddled
    rows when last%block==0)."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    shaped = quantize_nf4(w, block=32)
    assert shaped.layout == "shaped"
    flat = QuantizedTensor(
        *_flat_quant_nf4(np.asarray(w), 32), (32, 128), "nf4", 32, "flat")
    np.testing.assert_array_equal(
        np.asarray(dequantize(shaped, jnp.float32)),
        np.asarray(dequantize(flat, jnp.float32)))


def _flat_quant_nf4(w, block):
    """Reference flat packing in numpy (the pre-round-3 storage layout)."""
    from distributed_lion_tpu.ops.quant import NF4_LEVELS

    flat = w.reshape(-1).astype(np.float32)
    blocks = flat.reshape(-1, block)
    absmax = np.abs(blocks).max(1)
    scaled = blocks / np.maximum(absmax, 1e-12)[:, None]
    mids = (NF4_LEVELS[1:] + NF4_LEVELS[:-1]) / 2.0
    codes4 = np.searchsorted(mids, scaled).astype(np.uint8).reshape(-1)
    packed = (codes4[0::2] | (codes4[1::2] << 4)).astype(np.uint8)
    return jnp.asarray(packed), jnp.asarray(absmax)


def test_sharded_dequant_matches_dense_slice():
    """shard_map over a column-sharded shaped QuantizedTensor: each rank's
    local dequant == the corresponding columns of the full dequant (the
    invariant TP's maybe_dequant relies on)."""
    from _sharded import run_sharded
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[:2]
    mesh = Mesh(np.array(devs), ("tensor",))
    w = jnp.asarray(np.random.default_rng(4).normal(size=(32, 64)).astype(np.float32))
    qt = quantize_nf4(w, block=16)
    spec = P(None, "tensor")
    qt_sharded = jax.tree.map(
        lambda c: jax.device_put(c, NamedSharding(mesh, spec)), qt)

    def local_dequant(q):
        return dequantize(q, jnp.float32)

    out = run_sharded(local_dequant, mesh, spec, spec, qt_sharded)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dequantize(qt, jnp.float32)))


def test_maybe_dequant_passthrough():
    w = jnp.ones((4, 4))
    assert maybe_dequant(w, jnp.float32) is w


def test_quantized_tensor_is_pytree():
    qt = quantize_nf4(jnp.ones((64, 64)))
    moved = jax.tree.map(lambda x: x, qt)
    assert isinstance(moved, QuantizedTensor)
    assert moved.shape == (64, 64)
