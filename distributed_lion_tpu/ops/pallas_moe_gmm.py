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
written and the caller must not read it. The metadata still gives each
tile of such rows a visit behind the groups' own (a tile read, multiplied
and stored for nothing). A caller whose rows are half tail (a layer that
holds 128 of the 256 experts its tokens pick) says ``tail=True``: the grid
steps past the visits the groups need keep the last one's blocks and skip
the matmul: a 4,096-token slice's call over ``[3072, 1024]`` banks 2.80 ->
2.12 ms, the groups' rows bit for bit (my chip run, PR 30).

The gradient (``parallel/expert.grouped_matmul``'s ``custom_vjp``) is two
more grouped products. ``dlhs = dy rhs^T`` is this same kernel against the
banks transposed. ``drhs[g] = lhs_g^T dy_g`` is ``moe_gmm_drhs``, megablox's
``tgmm`` schedule under the same differences: visits of ``TILE_M_DRHS`` rows
(the float32 accumulator is a whole ``[K, tn]`` block that every visit
reads and writes, so a visit has to be deep enough to pay for it), rows of
other groups zeroed in the tile, one float32 accumulation a group, stored
when the group's last visit is done; a group with no row gets one visit
that stores zeros.
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
TILE_M_DRHS = 512       # rows a visit of the weight gradient contracts over
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


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


def _visit(i, offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref):
    tm, tn = out_ref.shape
    group = group_ref[i]
    rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    mine = jnp.logical_and(rows >= offsets_ref[group],
                           rows < offsets_ref[group + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def _kernel(*refs):
    _visit(pl.program_id(1), *refs)


def _kernel_to(visits_ref, *refs):
    """The same visit, made only while ``i`` is one of the ``visits`` the
    groups need. The metadata gives every tile of rows past the last group
    one visit of its own behind those; under ``tail`` such a step keeps the
    last needed visit's blocks (the index maps stop there), so it moves no
    bytes, and here it multiplies nothing either."""
    i = pl.program_id(1)
    pl.when(i < visits_ref[0])(lambda: _visit(i, *refs))


@functools.partial(jax.jit, static_argnames=("tail", "interpret"))
def moe_gmm(lhs, rhs, group_sizes, *, tail: bool = False,
            interpret: bool = False):
    """``out[r] = lhs[r] @ rhs[group of r]`` for the rows of every group;
    ``[M, N]`` in lhs's dtype, float32 accumulation. ``tail``: the caller
    knows that many rows lie past the last group (a layer that holds a
    range of the experts its tokens pick: half its sorted rows), so their
    tiles are neither read, multiplied nor written; without it the kernel
    is the one every other caller compiles."""
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
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=n_groups,
        visit_empty_groups=False)
    scalars = (offsets, group_ids, tile_ids)
    if tail:
        scalars = (jnp.reshape(visits, (1,)).astype(jnp.int32),) + scalars

    def at(i, s):
        """The visit whose blocks grid step ``i`` holds."""
        return jnp.clip(i, 0, s[0][0] - 1) if tail else i

    with jax.named_scope("moe_gmm"):
        out = pl.pallas_call(
            _kernel_to if tail else _kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(n // tn, group_ids.shape[0]),
                in_specs=[
                    pl.BlockSpec((tm, k),
                                 lambda j, i, *s: (s[-1][at(i, s)], 0)),
                    pl.BlockSpec((None, k, tn),
                                 lambda j, i, *s: (s[-2][at(i, s)], 0, j)),
                ],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, i, *s: (s[-1][at(i, s)], j))),
            out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="moe_gmm",
        )(*scalars, lhs, rhs)
    return out[:m] if pad else out


def _drhs_kernel(visits_ref, offsets_ref, group_ref, tile_ref, lhs_ref,
                 dy_ref, out_ref, acc_ref):
    """One visit of ``drhs``: the tile's rows that belong to the visit's
    group, transposed, against their cotangent rows. Grid steps past the
    ``visits`` the groups need (tiles of rows past the last group) keep the
    last visit's blocks and do nothing."""
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    real = i < visits_ref[0]
    at = jnp.minimum(i, visits_ref[0] - 1)
    group = group_ref[at]
    first = jnp.logical_or(i == 0, jnp.logical_and(
        real, group_ref[jnp.maximum(at - 1, 0)] != group))
    final = jnp.logical_or(
        i == last, group_ref[jnp.minimum(i + 1, visits_ref[0] - 1)] != group)

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real)
    def _():
        lhs = lhs_ref[...]
        rows = tile_ref[at] * lhs.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, lhs.shape, 0)
        mine = jnp.logical_and(rows >= offsets_ref[group],
                               rows < offsets_ref[group + 1])
        acc_ref[...] += jax.lax.dot_general(
            jnp.where(mine, lhs, jnp.zeros_like(lhs)), dy_ref[...], _TN,
            preferred_element_type=jnp.float32)

    @pl.when(final)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_drhs(lhs, dy, group_sizes, *, interpret: bool = False):
    """``out[g] = lhs_g^T @ dy_g`` over the rows of group g: the gradient of
    :func:`moe_gmm` in its banks, ``[n_groups, K, N]`` in lhs's dtype,
    accumulated in float32 a group. lhs ``[M, K]`` and dy ``[M, N]`` sorted
    by group; rows past the last group add nothing."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    m, k = lhs.shape
    n, n_groups = dy.shape[1], group_sizes.shape[0]
    tm = min(TILE_M_DRHS, -(-m // 16) * 16)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
    tn = _tile_n(k, n, 4)
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=n_groups,
        visit_empty_groups=True)
    scalars = (jnp.reshape(visits, (1,)).astype(jnp.int32), offsets,
               group_ids, tile_ids)

    def at(i, s):
        return jnp.minimum(i, s[0][0] - 1)

    size = lhs.dtype.itemsize
    vmem = (k * tn * (4 + 2 * size) + 2 * tm * (k + tn) * size
            + 2 * tm * k * size + (8 << 20))
    with jax.named_scope("moe_gmm_drhs"):
        return pl.pallas_call(
            _drhs_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(n // tn, group_ids.shape[0]),
                in_specs=[
                    pl.BlockSpec((tm, k),
                                 lambda j, i, *s: (s[3][at(i, s)], 0)),
                    pl.BlockSpec((tm, tn),
                                 lambda j, i, *s: (s[3][at(i, s)], j)),
                ],
                out_specs=pl.BlockSpec(
                    (None, k, tn), lambda j, i, *s: (s[2][at(i, s)], 0, j)),
                scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((n_groups, k, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(vmem, 32 << 20)),
            interpret=interpret,
            name="moe_gmm_drhs",
        )(*scalars, lhs, dy)
