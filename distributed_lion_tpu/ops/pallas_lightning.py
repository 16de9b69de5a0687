"""The decayed outer-product recurrence (Lightning attention) on the chip:
one step over the slots' recurrent states, in place (``lightning_step``, the
decode tick), and a whole prompt in chunks (``lightning_chunk``, the
prefill). Mosaic kernels; the mathematics is ``ops/lightning``'s module
note. What they share with ``ops/pallas_kda``: the state leaf's layout, the
``live`` mask, the in-place aliasing, ``source_slots`` and the three-pass
float32 matmul ``_dot``. What they do not: there is no ``S'^T k`` correction
and the decay is one constant a head, so neither kernel can be handed the
other's recurrence.

**``lightning_step``.** A (slot) a grid step, every head of the slot inside
it: ``S <- lambda_h S + k v^T``, ``o = S^T q``. The state is aliased in and
out and a dead slot costs no DMA and is left bit for bit (``pallas_kda``'s
note says how: every block index is taken from ``src[b]``). With ``d_v`` on
the lanes the decay, the key and the query multiply the state a ROW each and
the one contraction is over sublanes; the caller hands the three vectors of
every head as columns of one ``[d_k, 4 heads]`` tile a slot (decay, ``k``,
``q``, and a spare column that keeps the tile's lanes whole). All float32 on
the vector unit. Name on the device: ``lightning_step``.

**``lightning_chunk``.** A (row, head, chunk of 128) a grid step, the chunks
of a head in order with the state kept in VMEM between them. With ``n_i`` the
count of positions of the chunk up to and including ``i`` that hold a token
(``i + 1`` inside the prompt; it stops growing past the prompt's length, so
a position there neither decays nor writes) and ``s`` the head's slope:

    O   = ((Q K^T) * D) V + diag(exp(-s n_i)) Q S,   D_ij = exp(-s (n_i - n_j)), i >= j
    S  <- exp(-s n_C) S + (K * exp(-s (n_C - n_j)))^T V

Every exponent is a count that is never negative, times ``-s``: every factor
is at most 1. Every product is a matmul of float32 operands in three
bfloat16 passes (``pallas_kda._dot``). Name on the device:
``lightning_chunk``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_lion_tpu.ops.pallas_kda import (
    CHUNK,
    VMEM_LIMIT,
    _dot,
    _nn,
    _nt,
    chunk_kernel_takes,
    kernel_takes,
    source_slots,
)

__all__ = ["CHUNK", "chunk_kernel_takes", "kernel_takes", "lightning_chunk",
           "lightning_step"]


def _kernel(live_ref, src_ref, s_ref, c_ref, v_ref, o_ref, so_ref, *,
            heads: int):
    b = pl.program_id(0)

    @pl.when(live_ref[b] == 1)
    def _():
        for h in range(heads):
            decay, key, query = (c_ref[:, 4 * h + i:4 * h + i + 1]
                                 for i in range(3))             # [dk, 1]
            new = s_ref[h] * decay + key * v_ref[h:h + 1, :]    # [dk, dv]
            so_ref[h] = new
            o_ref[h:h + 1, :] = jnp.sum(new * query, axis=0, keepdims=True)

    # no live slot: every step names block 0, which must come back as it was
    @pl.when(jnp.logical_and(b == 0, live_ref[src_ref[0]] == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_step(state, q, k, v, decay, live, *, interpret: bool = False):
    """Shapes as ``ops/lightning.lightning_step_xla``: ``state [B, H, d_k,
    d_v]`` float32; ``q``, ``k`` ``[B, H, d_k]``; ``v [B, H, d_v]``; ``decay
    [H]`` (``lambda_h``, in (0, 1]); ``live [B]`` bool. Returns (``o [B, H,
    d_v]`` float32, zeros on dead rows; the state, updated in place on live
    rows)."""
    f32 = jnp.float32
    B, H, dk, dv = state.shape
    q, k, v = (x.astype(f32) for x in (q, k, v))
    lam = jnp.broadcast_to(decay.astype(f32)[None, :, None], (B, H, dk))
    cols = jnp.stack([lam, k, q, jnp.zeros_like(q)], -1)
    cols = cols.transpose(0, 2, 1, 3).reshape(B, dk, 4 * H)     # [B, dk, 4H]
    live = live.astype(jnp.int32)

    def slot(*tail):
        return lambda b, live, src: (src[b],) + tail

    state_spec = pl.BlockSpec((None, H, dk, dv), slot(0, 0, 0))
    row_spec = pl.BlockSpec((None, H, dv), slot(0, 0))
    with jax.named_scope("lightning_step"):
        out, state = pl.pallas_call(
            functools.partial(_kernel, heads=H),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B,),
                in_specs=[state_spec,
                          pl.BlockSpec((None, dk, 4 * H), slot(0, 0)),
                          row_spec],
                out_specs=[row_spec, state_spec]),
            out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                       jax.ShapeDtypeStruct(state.shape, f32)],
            # operands count the two prefetched scalars: the state is the
            # third, and comes back as the second output
            input_output_aliases={2: 1},
            # slots run in order: a dead slot's step relies on the block the
            # step before it left in place
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name="lightning_step",
        )(live, source_slots(live > 0), state, cols, v)
    return jnp.where(live[:, None, None] > 0, out, 0.0), state


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, slope_ref, s0_ref, o_ref,
                  s_ref, st_ref):
    b, n = pl.program_id(0), pl.program_id(2)

    @pl.when(n == 0)
    def _():
        st_ref[...] = s0_ref[...]

    f32 = jnp.float32
    q, k, v = (r[...].astype(f32) for r in (q_ref, k_ref, v_ref))
    C = q.shape[0]
    length, base = len_ref[b], n * C
    slope = slope_ref[0:1, 0:1]                              # [1, 1]

    def held(at):
        """Positions of the chunk up to and including ``at`` that hold a
        token: it stops growing at the prompt's length."""
        return (jnp.minimum(base + at + 1, length)
                - jnp.minimum(base, length)).astype(f32)

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    n_i, n_end = held(at), held(jnp.full((1, 1), C - 1, jnp.int32))
    # a position past the prompt's length writes nothing
    k = jnp.where(base + at < length, k, 0.0)
    d = jnp.where(row >= col, jnp.exp(-slope * (held(row) - held(col))), 0.0)
    st = st_ref[...]                                         # [dk, dv]
    o_ref[...] = (_nn(_nt(q, k) * d, v)
                  + _nn(q * jnp.exp(-slope * n_i), st)).astype(o_ref.dtype)
    st = st * jnp.exp(-slope * n_end) + _dot(
        k * jnp.exp(-slope * (n_end - n_i)), v, ((0,), (0,)))
    st_ref[...] = st

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        s_ref[...] = st


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_chunk(q, k, v, slope, lengths, state, *,
                    interpret: bool = False):
    """Shapes as ``ops/lightning.lightning_chunked_xla``: ``q``, ``k`` ``[B,
    T, H, d_k]``; ``v [B, T, H, d_v]`` (any float dtype: raised to float32
    inside); ``slope [H]`` (``lambda_h = exp(-slope_h)``); ``lengths [B]``
    int32, the positions of each row that hold a token; ``state [B, H, d_k,
    d_v]`` float32. Returns (``o [B, T, H, d_v]`` float32, the state after
    position ``lengths - 1``). A ``T`` that is no multiple of the chunk is
    padded."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % CHUNK
    N = (T + pad) // CHUNK

    def flat(x):       # [B, T, H, d] -> [B, T + pad, H d]: a head a lane tile
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(B, T + pad, H * x.shape[-1])

    def rows(d):
        return pl.BlockSpec((None, CHUNK, d), lambda b, h, n, *_: (b, n, h))

    state_spec = pl.BlockSpec((None, None, dk, dv),
                              lambda b, h, n, *_: (b, h, 0, 0))
    slopes = jnp.broadcast_to(slope.astype(f32)[:, None, None], (H, 1, 128))
    with jax.named_scope("lightning_chunk"):
        o, state = pl.pallas_call(
            _chunk_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, H, N),
                in_specs=[rows(dk), rows(dk), rows(dv),
                          pl.BlockSpec((None, 1, 128),
                                       lambda b, h, n, *_: (h, 0, 0)),
                          state_spec],
                out_specs=[rows(dv), state_spec],
                scratch_shapes=[pltpu.VMEM((dk, dv), f32)]),
            out_shape=[jax.ShapeDtypeStruct((B, T + pad, H * dv), f32),
                       jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
            # a head's chunks run in order: the state is carried in VMEM
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="lightning_chunk",
        )(lengths.astype(jnp.int32), flat(q), flat(k), flat(v), slopes,
          state.astype(f32))
    return o.reshape(B, T + pad, H, dv)[:, :T], state
