"""Expert-parallel MoE: routing parity, all_to_all dispatch, capacity drops.

Invariants: the sharded (all_to_all) path equals the single-device path
token-for-token when capacity is not binding; overflowed tokens produce
zero output (residual carries them); the load-balance aux loss is ~1 at
uniform routing; everything is differentiable.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _sharded import run_sharded
from distributed_lion_tpu.parallel.expert import (
    capacity,
    moe_ffn,
    moe_init,
    moe_param_specs,
)

E, D, F = 8, 6, 12
EP = 4  # expert shards


# the single-device layer as ONE compiled program a shape (ISSUE 35): eagerly
# it is some eighty one-op programs
ffn = jax.jit(functools.partial(moe_ffn, axis_name=None),
              static_argnames="capacity_factor")


@pytest.fixture(scope="module")
def params():
    return moe_init(jax.random.key(0), E, D, F)


@pytest.fixture(scope="module")
def ep_mesh():
    return Mesh(np.array(jax.devices()[:EP]), ("expert",))


def _dense_reference(params, x):
    """Per-token: route to argmax expert, y = p * FFN_e(x) (no capacity)."""
    probs = jax.nn.softmax(x @ params["gate"], axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    p = jnp.take_along_axis(probs, idx[:, None], -1)[:, 0]
    h = jax.nn.gelu(
        jnp.einsum("nd,ndf->nf", x, params["w_in"][idx]) + params["b_in"][idx]
    )
    return p[:, None] * (
        jnp.einsum("nf,nfd->nd", h, params["w_out"][idx]) + params["b_out"][idx]
    )


def test_single_device_matches_dense_reference(params):
    x = jax.random.normal(jax.random.key(1), (32, D))
    y, aux = ffn(params, x, capacity_factor=E * 1.0)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_dense_reference(params, x)), rtol=1e-5, atol=1e-6
    )
    assert float(aux) > 0


def test_expert_parallel_matches_single_device(params, ep_mesh):
    """Tokens sharded over the expert axis + experts sharded: the two
    all_to_alls must reproduce the single-device routing exactly (capacity
    slack so per-shard drops can't differ)."""
    n_total = 64
    x = jax.random.normal(jax.random.key(2), (n_total, D))
    cf = float(E)  # capacity == n_local: nothing can drop

    def body(p, x_local):
        y, aux = moe_ffn(p, x_local, capacity_factor=cf, axis_name="expert")
        return y, aux[None]

    specs = moe_param_specs()
    y_sharded, _ = run_sharded(
        body, ep_mesh, (specs, P("expert")), (P("expert"), P("expert")),
        params, x)

    y_single, _ = ffn(params, x, capacity_factor=cf)
    np.testing.assert_allclose(
        np.asarray(y_sharded), np.asarray(y_single), rtol=1e-4, atol=1e-5
    )


def test_capacity_drops_zero_out_tokens(params):
    x = jax.random.normal(jax.random.key(3), (64, D))
    y_full, _ = ffn(params, x, capacity_factor=float(E))
    y_tight, _ = ffn(params, x, capacity_factor=0.25)
    # tight capacity: some tokens dropped (zero rows), none invented
    dropped = np.all(np.asarray(y_tight) == 0, axis=-1)
    assert dropped.any()
    kept = ~dropped
    np.testing.assert_allclose(
        np.asarray(y_tight)[kept], np.asarray(y_full)[kept], rtol=1e-5, atol=1e-6
    )


def test_aux_loss_near_one_for_uniform_routing():
    # a zero gate routes every token to expert 0 -> aux = E * (1 * 1/E) ...
    # uniform probs but argmax collapses; instead use random gate over many
    # tokens: frac_tokens ~ 1/E, frac_probs ~ 1/E -> aux ~ 1
    params = moe_init(jax.random.key(7), E, D, F)
    x = jax.random.normal(jax.random.key(8), (4096, D)) * 5.0
    _, aux = ffn(params, x)
    assert 0.8 < float(aux) < 1.6


def test_differentiable(params):
    x = jax.random.normal(jax.random.key(9), (16, D))

    def loss(p):
        y, aux = moe_ffn(p, x, axis_name=None)
        return jnp.sum(y**2) + 0.01 * aux

    g = jax.jit(jax.grad(loss))(params)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
    assert float(jnp.abs(g["gate"]).sum()) > 0


def test_capacity_formula():
    assert capacity(64, 8, 1.0) == 8
    assert capacity(64, 8, 1.25) == 10
    assert capacity(3, 8, 1.0) == 1  # floor at 1


def test_bf16_routing_no_slot_collisions():
    """bf16 cumsum can't count past 256 — routing must stay exact in int32.

    Regression: with bf16 activations and >256 tokens on one expert, a
    bf16 cumsum collides ranks and silently sums tokens into shared
    dispatch slots. Routing must match the float32 reference exactly.
    """
    n = 1024
    params = moe_init(jax.random.key(11), E, D, F, dtype=jnp.bfloat16)
    # strong gate bias: most tokens land on one expert (>256 local tokens)
    x = jax.random.normal(jax.random.key(12), (n, D), jnp.bfloat16)
    params["gate"] = params["gate"].at[:, 0].add(5.0)

    y16, _ = ffn(params, x, capacity_factor=float(E))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    y32, _ = ffn(p32, x.astype(jnp.float32), capacity_factor=float(E))
    # no dropped-vs-kept disagreement and no summed-slot corruption:
    # bf16 output tracks the float32 reference within bf16 tolerance
    np.testing.assert_allclose(
        np.asarray(y16, np.float32), np.asarray(y32), rtol=0.1, atol=0.1
    )
    # every dispatch slot holds at most one token
    logits = x.astype(jnp.float32) @ p32["gate"]
    idx = np.asarray(jnp.argmax(jax.nn.softmax(logits, -1), -1))
    assert (np.bincount(idx, minlength=E) > 256).any()  # premise holds
