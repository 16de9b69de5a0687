"""Grouped matmul for dropless experts (Mosaic kernel ``moe_gmm``).

``lhs [M, K]`` holds token rows sorted by expert, ``group_sizes [E]`` how
many rows each expert got, ``rhs [E, K, N]`` the experts' weights; row r of
group e is multiplied by ``rhs[e]``: O(M K N), whatever the imbalance, with
no ``[E, tokens, D]`` buffer and no capacity. The schedule is megablox's
(``jax.experimental.pallas.ops.tpu.megablox``, whose
``make_group_metadata`` is used as it is): the rows are cut into tiles of
``tm``; a tile is visited once for every group that has rows in it, each
visit multiplies the whole tile by that group's weights and stores the
rows that belong to the group. Visits of one group follow one another, so
an expert's weights are read from HBM once.

What differs from megablox's ``gmm``: the kernel has a name on the device,
a visit takes all of K and a whole N tile (an expert's ``[2048, 768]`` bank
is one 3 MB block, so a decode tick's 263 visits are 263 grid steps and
not 25,000), and the grid is static (``M / tm + E - 1`` visits, the unused
ones repeating the last, which stores the same values again).

Rows past ``sum(group_sizes)`` belong to no group: their output is not
written and the caller must not read it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE_M = 128            # rows a visit: one pass of the 128 x 128 MXU
BLOCK_BYTES = 4 << 20   # most of rhs one visit holds (double-buffered)


def kernel_takes(k: int, n: int) -> bool:
    """Whole lane tiles in both of an expert's dims."""
    return k % LANES == 0 and n % LANES == 0


def _tile_n(k: int, n: int, itemsize: int) -> int:
    """All of N when a ``[K, N]`` bank fits one block, else the largest
    multiple of 128 lanes dividing N that does."""
    tn = n
    while k * tn * itemsize > BLOCK_BYTES and tn % (2 * LANES) == 0:
        tn //= 2
    return tn


def _kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref):
    i = pl.program_id(1)
    tm, tn = out_ref.shape
    group = group_ref[i]
    rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    mine = jnp.logical_and(rows >= offsets_ref[group],
                           rows < offsets_ref[group + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(lhs, rhs, group_sizes, *, interpret: bool = False):
    """``out[r] = lhs[r] @ rhs[group of r]`` for the rows of every group;
    ``[M, N]`` in lhs's dtype, float32 accumulation."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    m, k = lhs.shape
    n_groups, _, n = rhs.shape
    tm = min(TILE_M, -(-m // 16) * 16)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tn = _tile_n(k, n, rhs.dtype.itemsize)
    (offsets, group_ids, tile_ids), _ = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=n_groups,
        visit_empty_groups=False)
    with jax.named_scope("moe_gmm"):
        out = pl.pallas_call(
            _kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n // tn, group_ids.shape[0]),
                in_specs=[
                    pl.BlockSpec((tm, k), lambda j, i, o, g, t: (t[i], 0)),
                    pl.BlockSpec((None, k, tn),
                                 lambda j, i, o, g, t: (g[i], 0, j)),
                ],
                out_specs=pl.BlockSpec((tm, tn),
                                       lambda j, i, o, g, t: (t[i], j))),
            out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="moe_gmm",
        )(offsets, group_ids, tile_ids, lhs, rhs)
    return out[:m] if pad else out
