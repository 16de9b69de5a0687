"""Pipeline parallelism: forward parity, autodiff, stacking round-trip.

Net-new vs the reference (data-parallel only, SURVEY §2.7). Invariants:
pipelined forward == sequential layer stack bit-for-bit, jax.grad through
the ppermute schedule == sequential grads, stack/unstack round-trips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _sharded import run_sharded
from distributed_lion_tpu.parallel.pipeline import (
    from_last_stage,
    from_microbatches,
    pipeline_apply,
    stack_stage_params,
    to_microbatches,
    unstack_stage_params,
)

N_STAGES = 4
N_LAYER = 8


def _layer_params(key, n_layer, d):
    keys = jax.random.split(key, n_layer)
    return [
        {"w": jax.random.normal(k, (d, d)) * 0.3, "b": jnp.zeros((d,))}
        for k in keys
    ]


def _layer_fn(p, x):
    return x + jnp.tanh(x @ p["w"] + p["b"])


def _sequential(layers, x):
    for p in layers:
        x = _layer_fn(p, x)
    return x


@pytest.fixture(scope="module")
def pipe_mesh():
    devs = np.array(jax.devices()[:N_STAGES]).reshape(N_STAGES)
    return Mesh(devs, ("pipe",))


def _run_pipeline(mesh, stacked, xm):
    def body(stage_params, xm):
        local = jax.tree.map(lambda a: a[0], stage_params)  # [1, L/S,...] -> [L/S,...]
        return pipeline_apply(_layer_fn, local, xm, axis_name="pipe")

    return run_sharded(body, mesh, (P("pipe"), P()), P("pipe"), stacked, xm)


def test_stack_unstack_roundtrip():
    layers = _layer_params(jax.random.key(0), N_LAYER, 6)
    stacked = stack_stage_params(layers, N_STAGES)
    assert jax.tree.leaves(stacked)[0].shape[:2] == (N_STAGES, N_LAYER // N_STAGES)
    back = unstack_stage_params(stacked, N_LAYER)
    for a, b in zip(layers, back):
        np.testing.assert_array_equal(a["w"], b["w"])


def test_stack_requires_divisibility():
    with pytest.raises(ValueError):
        stack_stage_params(_layer_params(jax.random.key(0), 6, 4), 4)


def test_forward_matches_sequential(pipe_mesh):
    d, n_micro, mb = 6, 8, 2
    layers = _layer_params(jax.random.key(1), N_LAYER, d)
    stacked = stack_stage_params(layers, N_STAGES)
    x = jax.random.normal(jax.random.key(2), (n_micro * mb, d))
    xm = to_microbatches(x, n_micro)

    acc = _run_pipeline(pipe_mesh, stacked, xm)
    # out_specs=P('pipe') stacks the per-stage [n_micro, mb, d] buffers along
    # axis 0: [S*n_micro, mb, d]; last stage's slice is the real one
    acc = np.asarray(acc).reshape(N_STAGES, n_micro, mb, d)
    got = from_microbatches(jnp.asarray(acc[-1]))
    want = _sequential(layers, x)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    # earlier stages' buffers are zeros (never written)
    assert np.all(acc[:-1] == 0)


def test_from_last_stage_broadcasts(pipe_mesh):
    def body(x):
        stage = jax.lax.axis_index("pipe")
        val = jnp.where(stage == N_STAGES - 1, x * 7.0, jnp.zeros_like(x))
        return from_last_stage(val, "pipe")[None]

    x = jnp.ones((3,))
    out = run_sharded(body, pipe_mesh, (P(),), P("pipe"), x)
    np.testing.assert_allclose(np.asarray(out), 7.0)  # every stage got it


def test_grads_match_sequential(pipe_mesh):
    d, n_micro, mb = 4, 4, 2
    layers = _layer_params(jax.random.key(3), N_LAYER, d)
    stacked = stack_stage_params(layers, N_STAGES)
    x = jax.random.normal(jax.random.key(4), (n_micro * mb, d))
    xm = to_microbatches(x, n_micro)
    target = jax.random.normal(jax.random.key(5), (n_micro * mb, d))

    def pipe_loss(stacked, xm):
        def body(stage_params, xm):
            local = jax.tree.map(lambda a: a[0], stage_params)
            acc = pipeline_apply(_layer_fn, local, xm, axis_name="pipe")
            y = from_last_stage(acc, "pipe")
            loss = jnp.mean((from_microbatches(y) - target) ** 2)
            return loss[None]

        return run_sharded(
            body, pipe_mesh, (P("pipe"), P()), P("pipe"), stacked, xm).mean()

    def seq_loss(stacked, xm):
        layers_l = unstack_stage_params(stacked, N_LAYER)
        y = _sequential(layers_l, from_microbatches(xm))
        return jnp.mean((y - target) ** 2)

    g_pipe = jax.grad(pipe_loss)(stacked, xm)
    g_seq = jax.grad(seq_loss)(stacked, xm)
    for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_microbatch_roundtrip():
    x = jnp.arange(24.0).reshape(12, 2)
    np.testing.assert_array_equal(from_microbatches(to_microbatches(x, 4)), x)
    with pytest.raises(ValueError):
        to_microbatches(x, 5)
