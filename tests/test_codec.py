"""Codec round-trip + wire-format tests (SURVEY §4 unit tests; mirrors the
reference's pack/unpack/pad-trim at distributed_lion.py:14-31, 75-88)."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.ops.codec import (
    pack_signs,
    packed_size,
    unpack_signs,
    wire_bytes_per_param,
)


@pytest.mark.parametrize("shape", [(1,), (7,), (8,), (9,), (130,), (3, 5), (4, 8, 2)])
def test_roundtrip_lossless(shape):
    rng = np.random.default_rng(0)
    votes = jnp.asarray(rng.integers(0, 2, size=shape).astype(bool))
    packed = pack_signs(votes)
    assert packed.dtype == jnp.uint8, "wire format must be a REAL uint8 (the reference ships int64)"
    assert packed.shape == (packed_size(int(np.prod(shape))),)
    restored = unpack_signs(packed, shape)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(votes))


def test_padding_bits_are_zero_and_trimmed():
    votes = jnp.ones((9,), bool)  # pads 7 zero bits
    packed = pack_signs(votes)
    assert int(packed[1]) == 1  # only bit 0 of the second byte set
    assert unpack_signs(packed, (9,)).all()


def test_wire_accounting_beats_baseline():
    n, w = 124_000_000, 4
    psum = wire_bytes_per_param(n, w, "sign_psum")
    packed = wire_bytes_per_param(n, w, "packed_allgather")
    # packed path: 1 bit/param/worker → w/8 bytes... per-worker receive w*n/8
    assert packed["bytes_per_step"] == w * packed_size(n)
    # reference ships 8x more (int64 lanes)
    assert packed["reference_bytes_per_step"] == 8 * packed["bytes_per_step"]
    # BASELINE.md: ≤ 1/32 of bf16 grad all-reduce → packed path at W=4 is 1/4 byte/param vs 2
    assert packed["vs_bf16_allreduce"] <= 1 / 4
    assert psum["bits_per_param"] == 8.0
    # two-phase a2a wire: ~2 bits/param and INDEPENDENT of world size
    for w2 in (4, 64, 512):
        a2a = wire_bytes_per_param(n, w2, "packed_a2a")
        assert a2a["bits_per_param"] <= 2.0
        assert a2a["vs_bf16_allreduce"] <= 1 / 8


def test_unknown_wire_raises():
    with pytest.raises(ValueError):
        wire_bytes_per_param(8, 2, "carrier_pigeon")


def test_world1_wire_bytes_are_zero():
    """One voter -> every wire short-circuits: a single-chip run must not
    log phantom collective traffic."""
    for wire in ("sign_psum", "packed_allgather", "packed_a2a"):
        assert wire_bytes_per_param(1000, 1, wire)["bytes_per_step"] == 0


@pytest.mark.parametrize("n", [1, 8, 1023, 32768, 32769, 3 * 32768 + 5])
def test_packed_bytes_equal_numpy_packbits(n):
    """The wire format is numpy's little-endian packbits, at sizes on both
    sides of the codec's internal 32,768-bit group (whole groups, a ragged
    tail, less than one group) — the TPU-friendly formulation may never
    change a byte."""
    import jax

    from distributed_lion_tpu.ops.codec import pack_signs, unpack_signs

    votes = np.random.default_rng(n).random(n) < 0.5
    ref = np.packbits(votes, bitorder="little")
    got = np.asarray(jax.jit(pack_signs)(jnp.asarray(votes)))
    assert got.dtype == np.uint8
    np.testing.assert_array_equal(got, ref)
    back = jax.jit(lambda p: unpack_signs(p, (n,)))(jnp.asarray(ref))
    assert back.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(back), votes)


@pytest.mark.parametrize("rows,nbytes", [(1, 5), (4, 4096), (7, 3 * 4096 + 17)])
def test_tally_packed_rows_equals_unpacked_sum(rows, nbytes):
    """The packed wires' row tally (one row at a time under a scan) is the
    column sum of the unpacked bit matrix, plain and alive-weighted."""
    import jax

    from distributed_lion_tpu.ops.codec import tally_packed_rows

    rng = np.random.default_rng(rows)
    packed = rng.integers(0, 256, (rows, nbytes), dtype=np.uint8)
    alive = rng.integers(0, 2, (rows,)).astype(np.int32)
    bits = np.unpackbits(packed, axis=1, bitorder="little").astype(np.int32)
    got = jax.jit(tally_packed_rows)(jnp.asarray(packed))
    np.testing.assert_array_equal(np.asarray(got), bits.sum(0))
    got = jax.jit(tally_packed_rows)(jnp.asarray(packed), jnp.asarray(alive))
    np.testing.assert_array_equal(np.asarray(got),
                                  (bits * alive[:, None]).sum(0))


@pytest.mark.parametrize("shape", [(0,), (1,), (7,), (8,), (4095,), (32768,),
                                   (32769,), (100003,), (3, 32768 + 5)])
def test_planar_wire_roundtrip(shape):
    """The transient wires' planar codec, at sizes on both sides of its
    32,768-vote group: the same number of bytes as ``pack_signs`` and the
    same votes in them (equal popcount, so the pad bits are zeros) in
    another order, and ``unpack_wire`` undoes ``pack_wire``."""
    import jax

    from distributed_lion_tpu.ops.codec import pack_wire, unpack_wire

    n = int(np.prod(shape))
    votes = np.random.default_rng(n).random(shape) < 0.5
    packed = np.asarray(jax.jit(pack_wire)(jnp.asarray(votes)))
    assert packed.dtype == np.uint8 and packed.shape == (packed_size(n),)
    reference = np.asarray(pack_signs(jnp.asarray(votes)))
    assert np.unpackbits(packed).sum() == votes.sum()
    assert np.unpackbits(packed).sum() == np.unpackbits(reference).sum()
    back = jax.jit(lambda p: unpack_wire(p, shape))(jnp.asarray(packed))
    assert back.dtype == jnp.bool_ and back.shape == shape
    np.testing.assert_array_equal(np.asarray(back), votes)
    if n > 8 * 4096:  # plane j of a whole group is bit j of its 4,096 bytes
        first = votes.reshape(-1)[:32768].reshape(8, 4096)
        np.testing.assert_array_equal((packed[:4096] >> 3) & 1, first[3])


@pytest.mark.parametrize("masked", [False, True], ids=["all", "alive"])
@pytest.mark.parametrize("world", [2, 3, 4, 8])
def test_packed_row_election_is_the_majority_of_the_bits(world, masked):
    """``elect_packed_rows`` (bytes in, bytes out, nothing unpacked) elects
    ``2 * sum(bits) > quorum`` per bit, ties and the all-dead quorum
    included, for any bit order the rows share."""
    import jax

    from distributed_lion_tpu.ops.codec import elect_packed_rows

    rng = np.random.default_rng(world)
    nbytes = 4096 + 17
    rows = rng.integers(0, 256, (world, nbytes), dtype=np.uint8)
    rows[:, :64] = rng.integers(0, 2, (world, 1), dtype=np.uint8) * 255
    bits = np.unpackbits(rows, axis=1).astype(np.int32)
    masks = [None]
    if masked:
        masks = [rng.integers(0, 2, (world,)).astype(np.int32),
                 np.ones((world,), np.int32), np.zeros((world,), np.int32),
                 np.eye(world, dtype=np.int32)[0]]
    for alive in masks:
        weights = np.ones((world,), np.int32) if alive is None else alive
        want = 2 * (bits * weights[:, None]).sum(0) > weights.sum()
        if alive is None and world % 2 == 0:  # a tie elects 0 (-1)
            assert (2 * bits.sum(0) == world).any()
        got = jax.jit(elect_packed_rows)(
            jnp.asarray(rows), None if alive is None else jnp.asarray(alive))
        assert got.dtype == jnp.uint8 and got.shape == (nbytes,)
        np.testing.assert_array_equal(
            np.unpackbits(np.asarray(got)).astype(bool), want)


@pytest.mark.parametrize("wire,says", [
    ("sign_psum", "no codec (int8 ballots)"), ("packed_a2a", "planar codec"),
    ("packed_allgather", "reference-order codec"),
    ("hier:4", "reference-order codec")])
def test_wire_codec_names_the_bit_order(wire, says):
    from distributed_lion_tpu.ops.codec import wire_codec

    assert wire_codec(wire) == says
    with pytest.raises(ValueError):
        wire_codec("carrier_pigeon")
