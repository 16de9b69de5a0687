"""A head's RMS norm and its rotation in one pass, where the projection
wrote it (Mosaic kernels, the training path).

``y [B, T, n * 128]`` is a q or k projection's output, token-major: a
token's row holds ``n`` heads of 128 lanes side by side, the layout
``ops/pallas_flash_attn.flash_gqa`` reads. Each head is RMS-normed over its
128 lanes with a learned weight and rotated (``rotate_half`` pairs ``(i, i +
64)`` over the whole head: ``models/laguna.apply_rope_half`` of
``models/llama._rms_norm``). Written as XLA ops over ``[B T, n, 1, 128]``
that is eight fusions and, on the chip, a relayout at every crossing between
the token tiling (rows x lanes) and the head tiling the compiler gives the
4-D shape: half of the bytes round the attention kernels of cell 10
(PERF.md section 6, PR 45).

Here the array stays 2-D, ``[B T, n * 128]`` (the reshape is a bitcast), and
a grid step takes a block of rows x a few heads. Per head, in float32: the
mean of squares over the lanes, ``rsqrt``, the weight, then ``x cos + roll(x,
64) sin`` against tables whose sine carries the pair's sign (``-sin`` in the
first 64 lanes), and ONE rounding to ``y``'s dtype on the write (the XLA
expression rounds after the norm and again after the rotation, with cos and
sin rounded too: this is the more exact of the two, not bit-equal to it). A
row's position is ``row mod T``: the tables are ``[T, 128]`` and their index
map wraps, nothing is broadcast over the batch.

Backward (the residual is ``y`` itself, which a checkpoint rung recomputes):
with ``r = rsqrt(mean x^2 + eps)`` and ``xh = x r``, ``dn = dy cos + roll(dy
sin, 64)`` (a roll by 64 of 128 is its own transpose), ``dscale = sum dn xh``
(float32, one ``(8, 128)`` partial a row tile, resident over the head axis;
the tiles are summed outside: 128 numbers), ``dx = r (dn g - xh mean(dn g
xh))``.

Names on the device: ``qk_rope_fwd`` and ``qk_rope_bwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HALF = LANES // 2
BLOCK_BYTES = 1 << 20   # a grid step's block of y: 512 rows x 8 heads of
# bfloat16. On the chip at [16384, 4096] (forward / backward, my chip run,
# PR 45): 256 x 1 head 1.08 / 1.40 ms, 512 x 1 0.78 / 1.08, 512 x 4 0.53 /
# 0.79, 512 x 8 0.50 / 0.75, 1024 x 4 0.49 / 0.72 (268 and 403 MB moved: 540
# GB/s); 1024 x 8 and 2048 x 4 do not fit VMEM. A step's fixed cost, not the
# lane reductions, is what small blocks pay: the means as products on the
# idle MXU read 0.53 / 0.77 at 512 x 4.


def rows_for(T: int) -> int:
    """Rows of a grid step's block: the largest of 512, 256, 128 that
    divides T (a block never crosses a sequence, so its positions are one
    block of the tables)."""
    return next((r for r in (512, 256, 128) if T % r == 0), 0)


def qk_rope_takes(T: int, head_dim: int, rot: int, dtype) -> bool:
    """Whether :func:`qk_norm_rope` takes ``y [B, T, n * head_dim]`` of this
    dtype under a rotation of the leading ``rot`` dims: heads of 128 lanes
    that rotate whole, and T in whole row tiles."""
    return (head_dim == LANES and rot == LANES and rows_for(T) > 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _head(ref, h):
    return ref[:, h * LANES:(h + 1) * LANES].astype(jnp.float32)


def _fwd_kernel(x_ref, g_ref, cos_ref, sin_ref, o_ref, *, eps, heads):
    g, cos, sin = g_ref[...], cos_ref[...], sin_ref[...]
    for h in range(heads):
        x = _head(x_ref, h)
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        n = x * r * g
        o_ref[:, h * LANES:(h + 1) * LANES] = (
            n * cos + pltpu.roll(n, HALF, 1) * sin).astype(o_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, g_ref, cos_ref, sin_ref, dx_ref, dg_ref, *,
                eps, heads):
    g, cos, sin = g_ref[...], cos_ref[...], sin_ref[...]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dg_ref[...] = jnp.zeros_like(dg_ref)

    dg = jnp.zeros(dg_ref.shape, jnp.float32)
    for h in range(heads):
        x, dy = _head(x_ref, h), _head(dy_ref, h)
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        xh = x * r
        dn = dy * cos + pltpu.roll(dy * sin, HALF, 1)
        u = dn * g
        dx_ref[:, h * LANES:(h + 1) * LANES] = (
            r * (u - xh * jnp.mean(u * xh, axis=-1, keepdims=True))
        ).astype(dx_ref.dtype)
        # rows folded onto 8 sublanes by whole-tile adds; the last fold (8
        # rows and the row tiles) is the caller's
        dg = dg + (dn * xh).reshape(-1, 8, LANES).sum(0)
    dg_ref[...] += dg


def _tables(cos, sin):
    """``[T, 64]`` cos and sin -> ``[T, 128]`` cos and sign-folded sin."""
    return (jnp.concatenate([cos, cos], -1).astype(jnp.float32),
            jnp.concatenate([-sin, sin], -1).astype(jnp.float32))


def _geometry(y):
    B, T, width = y.shape
    assert width % LANES == 0 and rows_for(T), y.shape
    n = width // LANES
    rows = rows_for(T)
    most = BLOCK_BYTES // (rows * LANES * y.dtype.itemsize)
    heads = next(h for h in (8, 4, 2, 1) if h <= most and n % h == 0)
    tiles = T // rows                     # row tiles a sequence
    block = pl.BlockSpec((rows, heads * LANES), lambda i, j: (i, j))
    table = pl.BlockSpec((rows, LANES), lambda i, j: (i % tiles, 0))
    weight = pl.BlockSpec((1, LANES), lambda i, j: (0, 0))
    return B * T, width, heads, (B * tiles, n // heads), block, table, weight


def _fwd(y, scale, cos, sin, eps, interpret):
    rows, width, heads, grid, block, table, weight = _geometry(y)
    with jax.named_scope("qk_rope_fwd"):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps, heads=heads),
            grid=grid,
            in_specs=[block, weight, table, table],
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct((rows, width), y.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="qk_rope_fwd",
        )(y.reshape(rows, width),
          scale.astype(jnp.float32).reshape(1, LANES), cos, sin)
    return out.reshape(y.shape)


def _bwd(y, scale, cos, sin, dy, eps, interpret):
    rows, width, heads, grid, block, table, weight = _geometry(y)
    with jax.named_scope("qk_rope_bwd"):
        dx, dg = pl.pallas_call(
            functools.partial(_bwd_kernel, eps=eps, heads=heads),
            grid=grid,
            in_specs=[block, block, weight, table, table],
            out_specs=[block,
                       pl.BlockSpec((None, 8, LANES), lambda i, j: (i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, width), y.dtype),
                       jax.ShapeDtypeStruct((grid[0], 8, LANES),
                                            jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="qk_rope_bwd",
        )(y.reshape(rows, width), dy.reshape(rows, width),
          scale.astype(jnp.float32).reshape(1, LANES), cos, sin)
    return dx.reshape(y.shape), dg.sum((0, 1)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def qk_norm_rope(y, scale, cos, sin, eps: float, interpret: bool = False):
    """Each head of ``y [B, T, n * 128]`` (a projection's output, token-
    major) RMS-normed over its 128 lanes with the weight ``scale [128]`` and
    rotated by the angles of its row's position: ``cos``, ``sin`` ``[T, 64]``
    float32 as ``models/laguna.Rope.angles(arange(T))`` gives them (YaRN's
    attention factor in them). ``y``'s shape and dtype; float32 inside, one
    rounding; differentiable in ``y`` and ``scale``.
    :func:`qk_rope_takes` says which shapes. On the device: ``qk_rope_fwd``,
    ``qk_rope_bwd``."""
    return _fwd(y, scale, *_tables(cos, sin), eps, interpret)


def _qk_norm_rope_fwd(y, scale, cos, sin, eps, interpret):
    cos, sin = _tables(cos, sin)
    return _fwd(y, scale, cos, sin, eps, interpret), (y, scale, cos, sin)


def _qk_norm_rope_bwd(eps, interpret, res, dy):
    y, scale, cos, sin = res
    dx, dscale = _bwd(y, scale, cos, sin, dy, eps, interpret)
    half = jnp.zeros((cos.shape[0], HALF), jnp.float32)   # angles: constants
    return dx, dscale, half, half


qk_norm_rope.defvjp(_qk_norm_rope_fwd, _qk_norm_rope_bwd)
