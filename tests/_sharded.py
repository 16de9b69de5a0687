"""What the mesh tests share, first the one way to run a body over the
virtual mesh: COMPILED.

``shard_map(body, ...)(*args)`` outside ``jit`` traces, compiles and
dispatches every primitive of the body one by one over the eight device
threads, once per call: a hier election that is one 0.7 s program took 17 s
that way (ISSUE 35). ``tests/test_analysis_lint.py`` fails a test file that
calls a ``shard_map`` result outside ``jit``; it goes through here instead.
The ``mesh8`` / ``mesh4`` fixtures live in ``conftest.py``; the optimizer
tests' step driver, toy problem and tree comparison follow.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.optim import (
    expand_worker_state,
    squeeze_worker_state,
)
from distributed_lion_tpu.optim.lion import LionState


def sharded(body, mesh, in_specs, out_specs, check_vma=True):
    """``jax.jit(shard_map(body, ...))``. Build it ONCE where a test calls
    the same body with the same shapes again, and pass what varies (an
    ``alive`` mask, a step's grads) as an argument, not a closure: the
    second call is then a cache hit."""
    return jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma))


def run_sharded(body, mesh, in_specs, out_specs, *args, check_vma=True):
    """Compile ``body`` over ``mesh`` and run it once on ``args``."""
    return sharded(body, mesh, in_specs, out_specs, check_vma)(*args)


_PER_WORKER = ("exp_avg", "prev_ballot", "dcn_ring", "moe_ring")


def lion_state_specs(state: LionState) -> LionState:
    """PartitionSpec prefix of a stacked ``LionState``, from the fields this
    one carries: momenta, previous ballot and the in-flight rings are
    per-worker (stacked [world, ...] over the data axis), the rest is
    replicated."""
    return LionState(*(
        None if value is None else P("data") if field in _PER_WORKER else P()
        for field, value in zip(LionState._fields, state)))


def sharded_opt_step(opt, mesh, state, extras=0):
    """The jitted ``(params, stacked_grads, state) -> (params, state,
    *frames)`` that drives ``opt.step`` the way the trainer's shard_map
    does: replicated params, grads with a leading [world] axis over the
    data axis, ``state`` from ``init_global_state``. ``extras`` is how many
    of ``opt.step``'s trailing frames (guard, telemetry) to return,
    replicated; the rest are dropped."""
    st_spec = lion_state_specs(state)

    def body(params, grads, st):
        outs = opt.step(params, jax.tree.map(lambda g: g[0], grads),
                        squeeze_worker_state(st))
        return (outs[0], expand_worker_state(outs[1])) + tuple(
            outs[2:2 + extras])

    return sharded(body, mesh, (P(), P("data"), st_spec),
                   (P(), st_spec) + (P(),) * extras, check_vma=False)


def toy_problem(world=8, n=40):
    """``(params, stacked_grads)`` of the optimizer tests: an n-vector and a
    3-vector, one gradient a worker."""
    params = {"w": jax.random.normal(jax.random.key(0), (n,)),
              "b": jnp.zeros((3,))}
    grads = {"w": jax.random.normal(jax.random.key(1), (world, n)),
             "b": jax.random.normal(jax.random.key(2), (world, 3))}
    return params, grads


def assert_trees_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def leafy_problem(world=8, dtype=jnp.float32):
    """``(params, stacked_grads)`` whose leaves take both of the Pallas
    path's routes (``ops/pallas_lion.takes_leaf_in_place``): matrices of
    whole 128-lane rows at C = 128 / 768 / 2304 (an odd row count, fewer
    rows than a row block, the fused qkv's 3-D form) beside what the flat
    path pools (1-D, a last dimension of 7, under a tile, zero-size)."""
    shapes = {"emb": (209, 768), "fc": (40, 2304), "proj": (96, 128),
              "qkv": (32, 3, 128), "bias": (130,), "lora": (33, 7),
              "under_a_tile": (8, 128), "empty": (0,)}
    keys = jax.random.split(jax.random.key(5), 2 * len(shapes))
    params = {k: jax.random.normal(keys[2 * i], s).astype(dtype)
              for i, (k, s) in enumerate(shapes.items())}
    grads = {k: jax.random.normal(keys[2 * i + 1], (world,) + s).astype(dtype)
             for i, (k, s) in enumerate(shapes.items())}
    return params, grads
