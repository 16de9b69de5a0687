"""The hyper-connection mix on the chip: two Mosaic kernels over the
residual stream ``X [N, n d]`` (``n`` streams of ``d`` a row, held flat:
``ops/mhc``'s note), one pass over it each. The mathematics is ``ops/mhc``'s module note; the sums are taken in
that file's order, so the two agree to the bit where the backend's
element-wise operations do.

**``mhc_pre``.** A tile of ``tm`` rows a grid step, all ``n d`` values of a
row resident in VMEM (28,672 B a row at 4 x 3,584 in bfloat16). The ``n n + 2
n`` projections are ONE matmul of the tile against the packed ``phi``
(``ops/mhc.pack_phi``: three bfloat16 parts on 72 of 128 lanes), the sum of
squares a second walk over the resident tile. Both are then turned so that
ROWS lie on the lanes: the Sinkhorn iteration is element-wise work on ``n n``
vectors of ``[1, tm]`` (``ops/mhc.coeff_rows``), no reduction inside it. The
coefficients are turned back, written as one ``[tm, 128]`` float32 tile, and
``u = sum_i Hpre[i] X[i]`` is a third walk over the resident tile. Name on
the device: ``mhc_pre``.

**``mhc_post``.** ``X'[i] = Hpost[i] y + sum_j Hres[i, j] X[j]`` for a tile of
rows, ``lane_chunk`` lanes of all ``n`` streams at a time, the stream aliased
in and out (``input_output_aliases``); a row whose ``valid`` is 0 is written
back as it was read. Name on the device: ``mhc_post``.

A tile may be longer than the rows there are (a decode tick's 64 rows in a
tile of 128): the rows past the end are whatever VMEM held, no row's result
reads another row's, and their writes are dropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_lion_tpu.ops.mhc import (
    COEF_LANES,
    MixConfig,
    coeff_rows,
    lane_chunk,
)

__all__ = ["kernel_takes", "mhc_post", "mhc_pre", "tile_rows"]

VMEM_LIMIT = 48 * 1024 * 1024
# what the double-buffered blocks of a grid step may hold of that limit; the
# rest is the kernel's own temporaries (a chunk of lanes of every stream in
# float32, the turned coefficient tiles)
BLOCK_BUDGET = 32 * 1024 * 1024


def kernel_takes(shape, dtype, cfg: MixConfig) -> bool:
    """A bfloat16 stream ``[..., n d]`` of whole lane tiles a stream whose
    packed ``phi`` and coefficient row fit one lane tile each, with a lane
    to spare for the sum of squares."""
    return (jnp.dtype(dtype) == jnp.bfloat16
            and shape[-1] % (128 * cfg.n) == 0
            and 3 * cfg.width < COEF_LANES)


def tile_rows(rows: int, row_bytes: int, fixed_bytes: int, least: int) -> int:
    """Rows a grid step: the largest power of two up to 512 whose
    double-buffered blocks (``row_bytes`` a row in and out, ``fixed_bytes``
    whatever the tile) stay within ``BLOCK_BUDGET``, no more than the rows
    there are rounded up to ``least``."""
    tm = 512
    while tm > least and 2 * (tm * row_bytes + fixed_bytes) > BLOCK_BUDGET:
        tm //= 2
    while tm > least and tm // 2 >= rows:
        tm //= 2
    return tm


def _pre_kernel(a_ref, b_ref, x_ref, w_ref, u_ref, c_ref, t_ref, *, d: int,
                cfg: MixConfig):
    f32 = jnp.float32
    n, w = cfg.n, cfg.width
    chunk = lane_chunk(d)
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT, preferred_element_type=f32)

    def lanes(at):
        return pl.ds(pl.multiple_of(at, 128), chunk)

    def squares(k, s):
        x = x_ref[:, lanes(k * chunk)].astype(f32)
        return s + x * x

    s = jax.lax.fori_loop(0, n * d // chunk, squares,
                          jnp.zeros((x_ref.shape[0], chunk), f32))
    ssq = jnp.sum(s, axis=-1, keepdims=True)                    # [tm, 1]
    # the spare last lane carries the sum of squares through the turn
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    t_ref[...] = jnp.where(lane == COEF_LANES - 1, ssq, acc).T   # [128, tm]
    r = jax.lax.rsqrt(t_ref[COEF_LANES - 1:COEF_LANES, :] / (n * d)
                      + cfg.rms_eps)                            # [1, tm]
    m = [(t_ref[k:k + 1, :] + t_ref[w + k:w + k + 1, :]
          + t_ref[2 * w + k:2 * w + k + 1, :]) * r for k in range(w)]
    rows = coeff_rows(m, [a_ref[i] for i in range(3)],
                      [b_ref[k] for k in range(w)], cfg)
    t_ref[...] = jnp.zeros_like(t_ref)
    for k, row in enumerate(rows):
        t_ref[k:k + 1, :] = row
    c_ref[...] = t_ref[...].T                                   # [tm, 128]
    pre = [c_ref[:, i:i + 1] for i in range(n)]                 # [tm, 1]

    @pl.loop(0, d // chunk)
    def _(k):
        u = None
        for i in range(n):
            term = pre[i] * x_ref[:, lanes(i * d + k * chunk)].astype(f32)
            u = term if u is None else u + term
        u_ref[:, lanes(k * chunk)] = u.astype(u_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def mhc_pre(X, phi, a, b, cfg: MixConfig, *, interpret: bool = False):
    """``ops/mhc.mhc_pre_xla``'s shapes: the stream ``X [..., n d]``
    bfloat16, the packed ``phi [n d, 128]`` bfloat16, ``a [3]`` and ``b
    [width]`` float32. Returns (``u [..., d]``, the coefficient rows ``[...,
    128]`` float32)."""
    lead, n = X.shape[:-1], cfg.n
    d = X.shape[-1] // n
    flat = X.reshape(-1, n * d)
    N = flat.shape[0]
    item = flat.dtype.itemsize
    tm = tile_rows(N, (n * d + d) * item + COEF_LANES * 4,
                   phi.size * phi.dtype.itemsize, least=128)

    def rows(width):
        return pl.BlockSpec((tm, width), lambda i: (i, 0))

    u, coef = pl.pallas_call(
        functools.partial(_pre_kernel, d=d, cfg=cfg),
        grid=(pl.cdiv(N, tm),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  rows(n * d),
                  pl.BlockSpec(phi.shape, lambda i: (0, 0))],
        out_specs=[rows(d), rows(COEF_LANES)],
        out_shape=[jax.ShapeDtypeStruct((N, d), X.dtype),
                   jax.ShapeDtypeStruct((N, COEF_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((COEF_LANES, tm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="mhc_pre",
    )(a.astype(jnp.float32), b.astype(jnp.float32), flat, phi)
    return u.reshape(lead + (d,)), coef.reshape(lead + (COEF_LANES,))


def _post_kernel(x_ref, y_ref, c_ref, v_ref, o_ref, *, d: int, n: int):
    f32 = jnp.float32
    chunk = lane_chunk(d)
    post = [c_ref[:, n + i:n + i + 1] for i in range(n)]        # [tm, 1]
    res = [[c_ref[:, 2 * n + i * n + j:2 * n + i * n + j + 1]
            for j in range(n)] for i in range(n)]
    live = v_ref[...] > 0                                       # [tm, 1]

    def lanes(at):
        return pl.ds(pl.multiple_of(at, 128), chunk)

    @pl.loop(0, d // chunk)
    def _(k):
        y = y_ref[:, lanes(k * chunk)].astype(f32)
        xs = [x_ref[:, lanes(j * d + k * chunk)].astype(f32)
              for j in range(n)]
        for i in range(n):
            new = post[i] * y
            for j in range(n):
                new = new + res[i][j] * xs[j]
            o_ref[:, lanes(i * d + k * chunk)] = jnp.where(
                live, new, xs[i]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def mhc_post(X, y, coef, valid, cfg: MixConfig, *, interpret: bool = False):
    """``ops/mhc.mhc_post_xla``'s shapes: ``X [..., n d]``, ``y [..., d]``,
    the coefficient rows ``[..., 128]`` float32, ``valid [...]`` bool.
    Returns the stream, rewritten in place."""
    lead, n = X.shape[:-1], cfg.n
    d = X.shape[-1] // n
    flat = X.reshape(-1, n * d)
    N = flat.shape[0]
    item = flat.dtype.itemsize
    # the valid column is a [tm, 1] int32 block: a lane tile a row in VMEM
    tm = tile_rows(N, (2 * n * d + d) * item + 2 * COEF_LANES * 4, 0,
                   least=16)

    def rows(width):
        return pl.BlockSpec((tm, width), lambda i: (i, 0))

    out = pl.pallas_call(
        functools.partial(_post_kernel, d=d, n=n),
        grid=(pl.cdiv(N, tm),),
        in_specs=[rows(n * d), rows(d), rows(COEF_LANES), rows(1)],
        out_specs=rows(n * d),
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="mhc_post",
    )(flat, y.reshape(N, d), coef.reshape(N, COEF_LANES),
      jnp.broadcast_to(valid, lead).reshape(N, 1).astype(jnp.int32))
    return out.reshape(X.shape)
