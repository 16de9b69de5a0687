"""Pipeline parallelism: GPipe-style microbatched stage execution.

Net-new vs the reference (which is data-parallel only, SURVEY §2.7) but a
first-class axis of this framework's mesh. Design is TPU-idiomatic rather
than a port of GPU pipeline runtimes:

- **Same program on every stage** (SPMD under ``jax.shard_map``): the layer
  stack is stored stacked ``[n_stages, layers_per_stage, ...]`` and sharded
  over the ``pipe`` mesh axis, so each device holds one stage's slice.
- **Activations rotate on the interconnect** with ``lax.ppermute`` — the
  classic shift-register schedule: at tick ``t`` stage 0 ingests microbatch
  ``t`` while stage ``s`` works on microbatch ``t-s``; after
  ``n_micro + n_stages - 1`` ticks every microbatch has exited the last
  stage. The whole schedule is one ``lax.scan`` — static shapes, one XLA
  compilation, no host round-trips.
- **Autodiff for free**: ``ppermute``'s transpose is the reverse permute, so
  ``jax.grad`` through :func:`pipeline_apply` yields exactly the backward
  pipeline (bubbles and all) without a hand-written schedule.

Bubble fraction is ``(S-1)/(M+S-1)`` for S stages / M microbatches — pick
``n_micro >= 4*stages`` to keep it small. Outputs are only *real* on the
last stage; :func:`from_last_stage` broadcasts (or use the value inside a
masked loss, which is cheaper than broadcasting activations).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from distributed_lion_tpu.parallel.mesh import PIPE_AXIS


def stack_stage_params(layer_params: list, n_stages: int):
    """[L layers] pytree-list → stacked pytree with leading [n_stages, L/S]
    axes, ready to shard with ``P('pipe', ...)``."""
    n_layer = len(layer_params)
    if n_layer % n_stages:
        raise ValueError(f"{n_layer} layers not divisible by {n_stages} stages")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)
    return jax.tree.map(
        lambda x: x.reshape((n_stages, n_layer // n_stages) + x.shape[1:]), stacked
    )


def unstack_stage_params(stacked, n_layer: int) -> list:
    """Inverse of :func:`stack_stage_params` (checkpoint export)."""
    flat = jax.tree.map(
        lambda x: x.reshape((n_layer,) + x.shape[2:]), stacked
    )
    return [jax.tree.map(lambda x: x[i], flat) for i in range(n_layer)]


def pipeline_apply(
    layer_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    *,
    axis_name: str = PIPE_AXIS,
) -> jnp.ndarray:
    """Run microbatches through the pipelined layer stack.

    Must be called inside ``shard_map`` with ``stage_params`` sharded over
    ``axis_name`` (leading stage axis already consumed — the local view is
    ``[layers_per_stage, ...]``) and ``x`` replicated along it.

    Args:
        layer_fn: ``layer_fn(one_layer_params, x) -> y`` (same shape).
        stage_params: this stage's layers, leading ``[layers_per_stage]``.
        x: ``[n_micro, micro_batch, ...]`` microbatched activations
            (embedded tokens), identical on every stage.

    Returns:
        ``[n_micro, micro_batch, ...]`` outputs — REAL on the last stage,
        zeros elsewhere (see :func:`from_last_stage`).
    """
    stage = lax.axis_index(axis_name)
    n_stages = lax.psum(1, axis_name)
    n_micro = x.shape[0]
    total_ticks = n_micro + n_stages - 1  # fill + drain

    def stage_fn(params, h):
        # sequentially apply this stage's layers_per_stage layers
        return lax.scan(lambda c, p: (layer_fn(p, c), None), h, params)[0]

    def tick(carry, t):
        state, acc = carry
        # stage 0 ingests microbatch t (clamped index keeps shapes static;
        # ticks past n_micro-1 feed garbage that drains before the last stage)
        cur = jnp.where(stage == 0, x[jnp.clip(t, 0, n_micro - 1)], state)
        y = stage_fn(stage_params, cur)
        out_idx = t - (n_stages - 1)
        acc = jnp.where(
            (stage == n_stages - 1) & (out_idx >= 0),
            acc.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y),
            acc,
        )
        # ring shift stage s -> s+1 (the wrap edge last->0 carries values
        # that stage 0 always overwrites with fresh ingest — harmless)
        state = lax.ppermute(y, axis_name, _shift_pairs(axis_name))
        return (state, acc), None

    # the carry becomes device-varying after the first ppermute/at-set, so
    # the init must already be marked varying over the pipe axis (JAX vma
    # typing under shard_map)
    init = jax.lax.pcast(
        (jnp.zeros_like(x[0]), jnp.zeros_like(x)), (axis_name,), to="varying"
    )
    (_, acc), _ = lax.scan(tick, init, jnp.arange(total_ticks))
    return acc


def _shift_pairs(axis_name: str):
    n = jax.lax.psum(1, axis_name)  # static under shard_map
    return [(i, (i + 1) % n) for i in range(n)]


def from_last_stage(val: jnp.ndarray, axis_name: str = PIPE_AXIS) -> jnp.ndarray:
    """Broadcast a value that is only real on the last stage (zeros
    elsewhere, as produced by :func:`pipeline_apply`) to every stage."""
    stage = lax.axis_index(axis_name)
    n_stages = lax.psum(1, axis_name)
    return lax.psum(jnp.where(stage == n_stages - 1, val, jnp.zeros_like(val)),
                    axis_name)


def to_microbatches(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """[batch, ...] → [n_micro, batch/n_micro, ...]."""
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by n_micro {n_micro}")
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])


def from_microbatches(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`to_microbatches`."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def validate_pipeline(model_cfg, cfg, pp: int, n_micro: int) -> None:
    """Config-time guards for ``--pipeline_parallel``, either family (a
    Llama config has no dropout to refuse)."""
    if model_cfg.n_layer % pp:
        raise ValueError(f"n_layer {model_cfg.n_layer} not divisible by "
                         f"pipeline stages {pp}")
    if getattr(model_cfg, "dropout", 0.0) > 0.0:
        raise ValueError("dropout is unsupported under pipeline parallelism "
                         "(per-microbatch keys would need schedule-aware "
                         "plumbing); set --dropout 0")
    if cfg.per_device_train_batch_size % n_micro:
        raise ValueError(
            f"per_device_train_batch_size {cfg.per_device_train_batch_size} "
            f"not divisible by pipeline_microbatches {n_micro}"
        )
    if cfg.per_device_eval_batch_size % n_micro:
        raise ValueError(
            f"per_device_eval_batch_size {cfg.per_device_eval_batch_size} "
            f"not divisible by pipeline_microbatches {n_micro}"
        )
