"""Fused Pallas Lion kernels: numerical equivalence with the XLA path
(interpreter mode on CPU), both wire formats, padding edges."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sharded import assert_trees_equal, leafy_problem, sharded_opt_step
from distributed_lion_tpu.ops import pallas_lion
from distributed_lion_tpu.ops.codec import bucket_bounds
from distributed_lion_tpu.ops.pallas_lion import fused_apply, fused_ballots
from distributed_lion_tpu.optim import distributed_lion, init_global_state
from distributed_lion_tpu.optim.sharded import make_sharded_step, shard_state
from distributed_lion_tpu.parallel import make_mesh


def test_fused_ballots_matches_reference_encoding():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))  # non-multiple of tile
    m = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    out = fused_ballots(g, m, 0.9, interpret=True)
    assert out.dtype == jnp.int8 and out.shape == (1000,)
    u = 0.9 * np.asarray(m) + 0.1 * np.asarray(g)
    np.testing.assert_array_equal(np.asarray(out), np.where(u > 0, 1, -1))


def test_fused_ballots_zero_votes_minus_one():
    out = fused_ballots(jnp.zeros((8,)), jnp.zeros((8,)), 0.9, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), -1)


def test_fused_apply_matches_hand_algebra():
    rng = np.random.default_rng(1)
    n, lr, wd, b2 = 777, 0.01, 0.1, 0.99
    p = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    m = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    tot = jnp.asarray(rng.integers(-8, 9, size=(n,)).astype(np.int32))
    p_new, m_new = fused_apply(p, g, m, tot, lr, wd, b2, interpret=True)
    s = np.where(np.asarray(tot) > 0, 1.0, -1.0)  # tie (0) → −1
    np.testing.assert_allclose(np.asarray(p_new), np.asarray(p) * (1 - lr * wd) - lr * s, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m_new), b2 * np.asarray(m) + 0.01 * np.asarray(g), rtol=1e-5)


def test_fused_apply_bf16_params():
    p = jnp.ones((256,), jnp.bfloat16)
    g = jnp.ones((256,), jnp.bfloat16)
    m = jnp.zeros((256,), jnp.bfloat16)
    p_new, m_new = fused_apply(p, g, m, jnp.ones((256,), jnp.int32), 0.5, 0.0, 0.9,
                               interpret=True)
    assert p_new.dtype == jnp.bfloat16 and m_new.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(p_new, np.float32), 0.5)


@pytest.mark.parametrize("wire", ["sign_psum", "packed_allgather"])
def test_pallas_step_equals_xla_step(wire):
    """kernel='pallas' (interpreted) and kernel='xla' produce identical
    trajectories over several steps on the 8-device mesh."""
    mesh = make_mesh(data=8)
    rng = np.random.default_rng(7)
    params = {
        "w": jnp.asarray(rng.normal(size=(33, 7)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(130,)).astype(np.float32)),
    }
    grads = {
        "w": jnp.asarray(rng.normal(size=(8, 33, 7)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(8, 130)).astype(np.float32)),
    }
    results = []
    for kern in ("pallas", "xla"):
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05, wire=wire, kernel=kern)
        state = shard_state(init_global_state(opt, params, 8), mesh)
        step = make_sharded_step(opt, mesh)
        p = params
        for _ in range(3):
            p, state = step(p, grads, state)
        results.append((p, state))
    for k in params:
        np.testing.assert_array_equal(
            np.asarray(results[0][0][k]), np.asarray(results[1][0][k])
        )
        np.testing.assert_allclose(
            np.asarray(results[0][1].exp_avg[k]),
            np.asarray(results[1][1].exp_avg[k]),
            rtol=1e-6,
        )


def test_kernel_mode_validation():
    with pytest.raises(ValueError):
        distributed_lion(kernel="cuda")


@pytest.mark.parametrize("vote_buckets", [1, 4])
def test_elections_bit_identical_across_row_blocks(vote_buckets):
    """Any ``row_block`` (and the XLA path) produces BYTE-identical
    params/momenta across vote_buckets {1, 4} — tiling is never allowed to
    move an election or a weight."""
    mesh = make_mesh(data=8)
    rng = np.random.default_rng(11)
    params = {
        "w": jnp.asarray(rng.normal(size=(777, 13)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(259,)).astype(np.float32)),
    }
    grads = {
        "w": jnp.asarray(rng.normal(size=(8, 777, 13)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(8, 259)).astype(np.float32)),
    }
    results = []
    configs = [("xla", 0), ("pallas", 0), ("pallas", 128), ("pallas", 2048)]
    for kern, rb in configs:
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05,
                               wire="sign_psum", kernel=kern, row_block=rb,
                               vote_buckets=vote_buckets)
        state = shard_state(init_global_state(opt, params, 8), mesh)
        step = make_sharded_step(opt, mesh)
        p = params
        for _ in range(3):
            p, state = step(p, grads, state)
        results.append((kern, rb, p, state))
    _, _, p0, s0 = results[0]
    for kern, rb, p, s in results[1:]:
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(p0[k]), np.asarray(p[k]),
                err_msg=f"params diverged at kernel={kern} row_block={rb}")
            np.testing.assert_array_equal(
                np.asarray(s0.exp_avg[k]), np.asarray(s.exp_avg[k]),
                err_msg=f"momentum diverged at kernel={kern} row_block={rb}")


def test_bad_row_block_rejected_at_build():
    with pytest.raises(ValueError, match="multiple of 32"):
        distributed_lion(row_block=100)
    with pytest.raises(ValueError, match="multiple of 32"):
        distributed_lion(row_block=16)


# ------------------------------------------------- the leaf-shaped kernels
def _window_case(rng, n_rows, width, dtype=np.float32):
    return [jnp.asarray(rng.normal(size=(n_rows, width)).astype(dtype))
            for _ in range(3)]


@pytest.mark.parametrize("n_rows,width,rows,block", [
    (209, 768, (0, 209), 32),      # odd row count: the last block is ragged
    (209, 768, (64, 209), 32),     # a window that starts at a later block
    (209, 768, (64, 128), 64),     # whole blocks inside a leaf
    (40, 2304, (0, 40), 40),       # fewer rows than a row block: one block
    (1100, 128, (512, 1100), 512),  # the default block, ragged end
])
def test_leaf_kernels_match_hand_algebra_where_the_leaf_lies(
        n_rows, width, rows, block):
    """``leaf_ballots`` / ``leaf_apply`` over a window of whole rows: the
    ballots come out lane block major, the apply writes its window into
    ``p`` and ``m`` and passes every other row through."""
    rng = np.random.default_rng(n_rows + rows[0])
    p, g, m = _window_case(rng, n_rows, width)
    r0, r1 = rows
    lr, wd, b1, b2 = 0.01, 0.1, 0.9, 0.99
    ballots = pallas_lion.leaf_ballots(g, m, b1, rows=rows, block=block,
                                       interpret=True)
    assert ballots.shape == (width // 128, r1 - r0, 128)
    assert ballots.dtype == jnp.int8
    u = b1 * np.asarray(m)[r0:r1] + (1 - b1) * np.asarray(g)[r0:r1]
    as_leaf = np.asarray(ballots).transpose(1, 0, 2).reshape(r1 - r0, width)
    np.testing.assert_array_equal(as_leaf, np.where(u > 0, 1, -1))
    # the verdict in the same order, one byte a coordinate; 0 elects -1
    verdict = jnp.asarray(rng.integers(-3, 4, size=ballots.shape)
                          .astype(np.int8))
    p_new, m_new = pallas_lion.leaf_apply(p, g, m, verdict, lr, wd, b2,
                                          rows=rows, block=block,
                                          interpret=True)
    s = np.where(np.asarray(verdict) > 0, 1.0, -1.0).transpose(1, 0, 2)
    want_p, want_m = np.asarray(p).copy(), np.asarray(m).copy()
    want_p[r0:r1] = want_p[r0:r1] * (1 - lr * wd) - lr * s.reshape(-1, width)
    want_m[r0:r1] = b2 * want_m[r0:r1] + (1 - b2) * np.asarray(g)[r0:r1]
    np.testing.assert_allclose(np.asarray(p_new), want_p, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_new), want_m, rtol=1e-5,
                               atol=1e-6)
    for got, was in ((p_new, p), (m_new, m)):  # outside the window: bits
        np.testing.assert_array_equal(np.asarray(got)[:r0],
                                      np.asarray(was)[:r0])
        np.testing.assert_array_equal(np.asarray(got)[r1:],
                                      np.asarray(was)[r1:])


GPT2_124M_SHAPES = (
    [(768, 768), (768,), (768, 3, 768), (3, 768), (768,), (768,), (768,),
     (768,), (768, 3072), (3072,), (3072, 768), (768,)] * 12
    + [(768,), (768,), (1024, 768), (50257, 768)])


@pytest.mark.parametrize("shapes,world,wire,buckets,row_block,want", [
    (GPT2_124M_SHAPES, 4, "packed_a2a", 4, 0, (50, 98, 112)),
    (GPT2_124M_SHAPES, 1, "sign_psum", 1, 0, (50, 98, 104)),
    ([(209, 768), (130,), (40, 2304), (0,), (33, 7)], 8, "packed_a2a", 8, 32,
     (2, 2, None)),
    ([(4096, 16), (64, 4096), (4096,)], 8, "sign_psum", 4, 0, (1, 2, 4)),
    ([(4096, 16), (4096, 8), (64,)], 8, "hier:4", 4, 0, (0, 3, 2)),
])
def test_leaf_layout_tiles_every_bucket_with_whole_row_windows(
        shapes, world, wire, buckets, row_block, want):
    """The step's private order, from shapes alone: every coordinate sits in
    exactly one bucket, the buckets are ``codec.bucket_bounds``' sizes (the
    wire's bytes do not move), a window starts on its block and only a
    leaf's last window is not whole blocks, and a piece's verdict parts
    read back exactly what its ballot parts wrote."""
    n = sum(int(np.prod(s)) for s in shapes)
    bounds = bucket_bounds(n, buckets, world, wire)
    lay = pallas_lion.leaf_layout(shapes, bounds, row_block)
    want_in, want_pooled, want_calls = want
    assert (len(lay.in_place), len(lay.pooled)) == (want_in, want_pooled)
    assert lay.calls == 2 * len(lay.pieces)
    if want_calls is not None:
        assert lay.calls == want_calls
    assert sum(p.size for p in lay.pieces) == n
    seen = [np.zeros(p.size, np.int32) for p in lay.pieces]
    for (_, size), parts in zip(bounds, lay.buckets):
        assert sum(hi - lo for _, lo, hi in parts) == size
        for pi, lo, hi in parts:
            seen[pi][lo:hi] += 1
    assert all((s == 1).all() for s in seen)
    for pi, (piece, parts) in enumerate(zip(lay.pieces, lay.verdicts)):
        assert sum(ln for _, _, ln in parts) == piece.size
        at = 0
        for bucket, off, ln in parts:  # the same votes, back in piece order
            wire_at = 0
            for q, lo, hi in lay.buckets[bucket]:
                if q == pi:
                    assert (wire_at, lo, hi - lo) == (off, at, ln)
                wire_at += hi - lo
            at += ln
        assert pi in lay.apply_at[max(b for b, _, _ in parts)]
    rb = row_block or pallas_lion.ROW_BLOCK
    by_leaf: dict = {}
    for piece in lay.pieces:
        if piece.leaf >= 0:
            by_leaf.setdefault(piece.leaf, []).append(piece)
    for leaf, pieces in by_leaf.items():
        n_rows = int(np.prod(shapes[leaf][:-1]))
        assert [p.r0 for p in pieces] == [0] + [p.r1 for p in pieces[:-1]]
        assert pieces[-1].r1 == n_rows
        for piece in pieces:
            assert piece.r0 % piece.block == 0
            assert piece.block == rb or piece.block == n_rows < rb
        for piece in pieces[:-1]:
            assert (piece.r1 - piece.r0) % piece.block == 0


def test_the_setup_line_counts_what_the_step_runs():
    n = sum(int(np.prod(s)) for s in GPT2_124M_SHAPES)
    lay = pallas_lion.leaf_layout(
        GPT2_124M_SHAPES, bucket_bounds(n, 4, 4, "packed_a2a"))
    assert lay.line() == (
        "[setup] lion: 50 leaves in place (99.9% of coordinates), 98 "
        "through the flat path, 112 kernel calls a step")
    # a LoRA-like tree: no factor has whole 128-lane rows, one pooled call
    lora = [(4096, 16), (16, 4096 + 8), (4096, 8), (8, 11008 + 4)] * 8
    n = sum(int(np.prod(s)) for s in lora)
    lay = pallas_lion.leaf_layout(lora, bucket_bounds(n, 1, 8, "sign_psum"))
    assert lay.line() == (
        "[setup] lion: 0 leaves in place (0.0% of coordinates), 32 through "
        "the flat path, 2 kernel calls a step")


# -------------------------------- the step over leaves where they lie: parity
_XLA_RUNS: dict = {}


def _leafy_run(mesh, wire, kern, buckets, row_block=0, steps=2):
    params, grads = leafy_problem()
    opt = distributed_lion(learning_rate=0.02, weight_decay=0.05, wire=wire,
                           kernel=kern, vote_buckets=buckets,
                           row_block=row_block)
    state = shard_state(init_global_state(opt, params, 8), mesh)
    step = sharded_opt_step(opt, mesh, state)
    for _ in range(steps):
        params, state = step(params, grads, state)
    return params, state.exp_avg


def _xla_leafy_run(mesh, wire):
    """The XLA path's answer, once a wire: it is the same at every bucket
    count (tests/test_vote_buckets.py)."""
    if wire not in _XLA_RUNS:
        _XLA_RUNS[wire] = _leafy_run(mesh, wire, "xla", 1)
    return _XLA_RUNS[wire]


@pytest.mark.parametrize("buckets", [1, 4, 8])
@pytest.mark.parametrize("wire", ["sign_psum", "packed_allgather",
                                  "packed_a2a", "hier:4"])
def test_leaf_shaped_step_is_bit_identical_to_xla(mesh8, wire, buckets):
    """Leaves read and written where they lie, mixed with pooled ones, a
    leaf split across two (4 buckets) and three and more (8) buckets, at a
    row block that makes every in-place leaf several windows: parameters AND
    momenta bit for bit the XLA path's."""
    got = _leafy_run(mesh8, wire, "pallas", buckets, row_block=32)
    assert_trees_equal(got, _xla_leafy_run(mesh8, wire))


@pytest.mark.parametrize("mom_dtype", [jnp.float32, jnp.bfloat16])
def test_leaf_shaped_step_bf16_params(mesh8, mom_dtype):
    """bf16 parameters (16-sublane tiles) with float32 and bf16 momentum,
    at the default row block (every leaf here one ragged block), against
    the flat kernels over the same values as 1-D leaves, which nothing
    takes in place. (Not against XLA: its bf16 decay and update round
    twice where the kernels' float32 pass rounds once.)"""
    params, grads = leafy_problem(dtype=jnp.bfloat16)
    runs = []
    for flat in (False, True):
        p, g = params, grads
        if flat:
            p = jax.tree.map(lambda x: x.reshape(-1), params)
            g = jax.tree.map(lambda x: x.reshape(8, -1), grads)
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05,
                               wire="packed_a2a", kernel="pallas",
                               vote_buckets=4, mom_dtype=mom_dtype)
        state = shard_state(init_global_state(opt, p, 8), mesh8)
        step = sharded_opt_step(opt, mesh8, state)
        for _ in range(2):
            p, state = step(p, g, state)
        assert jax.tree.leaves(state.exp_avg)[0].dtype == mom_dtype
        runs.append(jax.tree.map(lambda x: np.asarray(x).reshape(-1),
                                 (p, state.exp_avg)))
    assert_trees_equal(*runs)
