"""Fused Pallas Lion kernels: numerical equivalence with the XLA path
(interpreter mode on CPU), both wire formats, padding edges."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
