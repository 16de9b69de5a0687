"""The gated delta rule on the chip: one step over the slots' recurrent
states, in place (``kda_step``, the decode tick), and a whole prompt in
chunks (``kda_chunk``, the prefill). Mosaic kernels; the mathematics is
``ops/kda``'s module note.

**``kda_step``.**

A KDA layer keeps ``S [heads, d_k, d_v]`` float32 a slot: 2.10 MB at 32
heads of 128 x 128, 268 MB a layer at 128 slots. A decode tick must read and
write each live slot's state exactly once and touch nothing else of it, so
the state is aliased in and out and walked a slot a grid step:

- **Dead slots are skipped, not copied.** ``live`` is scalar-prefetched and
  every block index is taken from ``src[b]``: ``b`` itself for a live slot,
  for a dead one the live slot before it (the first live slot, for the dead
  ones that lead). Consecutive grid steps that name the same block make no
  new copy in and none out, so a dead slot costs no DMA and its state (and
  output row) are never written; the body runs under ``pl.when(live[b])``.
  With no live slot at all the one block named is copied through.
- **No transposes.** With ``d_v`` on the lanes, decay, key, write and query
  multiply the state a ROW each (``[d_k, 1]`` against ``[d_k, d_v]``), and
  both contractions are over sublanes (``S'^T k`` and ``S^T q`` come out as
  rows ``[1, d_v]``, beside ``v`` and ``o``). The caller hands the four
  vectors of every head as columns of one ``[d_k, 4 heads]`` tile a slot
  (``exp g``, ``k``, ``beta k``, ``q``: 64 KB beside the 2 MB of state).

All float32 on the vector unit: the step has no matmul worth the MXU (rank
one). Name on the device: ``kda_step``.

**``kda_chunk``.** ``ops/kda.kda_chunked_xla``'s equations, a (row, head,
chunk of 128) a grid step, the chunks of a head in order with the state kept in
VMEM between them (transposed, ``[d_v, d_k]``: the chunk's decay is then a
row over its lanes). Nothing but q, k, ``beta k``, v and g ``[128, 128]``
comes in and the outputs go out: the chunk's products, the inverse and the
corrected values never see HBM, where the XLA form writes and reads them
all (13 ms a layer at 4,096 positions, 45% of a prefill: PERF.md section 6).
Every exponent is a difference of cumulative log-decays that is never
positive, as there, taken through a TREE of edges so that every product is
a matmul: at level ``s`` (1, 2, .. 64) the chunk is blocks of ``2 s`` rows,
a row ``t`` of a block's upper half meets a row ``j`` of its lower half
through the edge between the halves, ``exp(G_t - G_edge) exp(G_edge -
G_j)``, two factors at most 1: the first a running sum of ``g`` from the
upper half's start to ``t``, the second one from ``j + 1`` to the lower
half's end, both made by masked shifts down and up the sublanes (no
cumulative sum is ever subtracted from another). Every pair ``j < t`` meets
at exactly one level. The same tree inverts the unit lower-triangular
system: with ``X`` the inverse of the blocks of ``s`` rows and ``A_s`` the
level's products, ``X - X A_s X`` is the inverse of the blocks of ``2 s``.
Every matmul is three bfloat16 passes over float32 operands split in two
(``_dot``: products to 2^-16, sums in float32; against the float32 scan at
4,096 positions the outputs differ by 1.2e-6 where six passes read 2e-7 and
the state by 8e-6 for 4e-6, a quarter less time). Name on the device:
``kda_chunk``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a slot's state in and out, double-buffered: 4 x 2.10 MB, over the default
# scoped limit of 16 MB with the temporaries
VMEM_LIMIT = 48 * 1024 * 1024

# a chunk of the prefill kernel: one whole tile each way, so every product
# is a [128, 128] matmul (at 64 rows a chunk the products half-fill their
# tiles: 6.93 ms a layer at 4,096 positions for 5.61, PERF.md section 6)
CHUNK = 128


def kernel_takes(state_shape, dtype) -> bool:
    """Whole tiles only: ``d_k`` a multiple of 8 sublanes, ``d_v`` and
    ``4 heads`` of 128 lanes, float32."""
    _, heads, dk, dv = state_shape
    return (jnp.dtype(dtype) == jnp.float32 and dk % 8 == 0
            and dv % 128 == 0 and (4 * heads) % 128 == 0)


def _kernel(live_ref, src_ref, s_ref, c_ref, v_ref, o_ref, so_ref, *,
            heads: int):
    b = pl.program_id(0)

    @pl.when(live_ref[b] == 1)
    def _():
        for h in range(heads):
            decay, key, write, query = (c_ref[:, 4 * h + i:4 * h + i + 1]
                                        for i in range(4))      # [dk, 1]
            decayed = s_ref[h] * decay                          # [dk, dv]
            read = jnp.sum(decayed * key, axis=0, keepdims=True)
            new = decayed + write * (v_ref[h:h + 1, :] - read)
            so_ref[h] = new
            o_ref[h:h + 1, :] = jnp.sum(new * query, axis=0, keepdims=True)

    # no live slot: every step names block 0, which must come back as it was
    @pl.when(jnp.logical_and(b == 0, live_ref[src_ref[0]] == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def source_slots(live):
    """``src [B]`` int32: the slot whose blocks grid step ``b`` names (the
    module note): ``b`` where live, else the nearest live slot before it,
    else the first live slot (0 where none is)."""
    B = live.shape[0]
    at = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    return jnp.where(before >= 0, before, first)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(state, q, k, v, g, beta, live, *, interpret: bool = False):
    """Shapes as ``ops/kda.kda_step_xla``; ``live [B]`` bool. Returns
    (``o [B, H, d_v]`` float32, zeros on dead rows; the state, updated in
    place on live rows)."""
    f32 = jnp.float32
    B, H, dk, dv = state.shape
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    cols = jnp.stack([jnp.exp(g), k, k * beta[..., None], q], -1)
    cols = cols.transpose(0, 2, 1, 3).reshape(B, dk, 4 * H)     # [B, dk, 4H]
    live = live.astype(jnp.int32)

    def slot(*tail):
        return lambda b, live, src: (src[b],) + tail

    state_spec = pl.BlockSpec((None, H, dk, dv), slot(0, 0, 0))
    row_spec = pl.BlockSpec((None, H, dv), slot(0, 0))
    with jax.named_scope("kda_step"):
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
            name="kda_step",
        )(live, source_slots(live > 0), state, cols, v)
    return jnp.where(live[:, None, None] > 0, out, 0.0), state


def chunk_kernel_takes(state_shape, dtype) -> bool:
    """``d_k`` and ``d_v`` one whole tile of 128 lanes each, float32."""
    _, _, dk, dv = state_shape
    return jnp.dtype(dtype) == jnp.float32 and dk == 128 and dv == 128


def _dot(a, b, dims):
    """``a . b`` over ``dims`` with float32 operands, as three bfloat16
    passes: each operand its bfloat16 part and the rest, the product of the
    two rests left out."""
    f32, bf = jnp.float32, jnp.bfloat16

    def one(x, y):      # said outright: a caller's default_matmul_precision
        # must not reach a bfloat16 product
        return jax.lax.dot_general(x, y, (dims, ((), ())),
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=f32)

    ah, bh = a.astype(bf), b.astype(bf)
    al, bl = (a - ah.astype(f32)).astype(bf), (b - bh.astype(f32)).astype(bf)
    return one(ah, bh) + one(ah, bl) + one(al, bh)


def _nn(a, b):
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):
    return _dot(a, b, ((1,), (1,)))


def _run(x, at, seg: int, up: bool):
    """Running sums of ``x [C, d]`` along its rows inside segments of
    ``seg`` rows: from the segment's start to the row (``up`` False), or
    from the row to the segment's end (``up`` True). ``at``: the row index
    a lane. Masked shifts: no sum is ever taken apart again."""
    C = x.shape[0]
    d = 1
    while d < seg:
        inside = (at & (seg - 1)) < seg - d if up else (at & (seg - 1)) >= d
        x = x + jnp.where(inside, pltpu.roll(x, C - d if up else d, 0), 0.0)
        d *= 2
    return x


def _chunk_kernel(q_ref, k_ref, kb_ref, v_ref, g_ref, s0_ref, o_ref, s_ref,
                  st_ref):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        st_ref[...] = s0_ref[...].T

    q, k, kb, v, g = (r[...] for r in (q_ref, k_ref, kb_ref, v_ref, g_ref))
    C, d = g.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (C, d), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # j <= t on the diagonal: a row's own key, no decay between
    b = jnp.where(row == col, jnp.sum(q * kb, axis=1, keepdims=True), 0.0)
    inv = jnp.where(row == col, 1.0, 0.0)
    s, level = 1, 0
    while s < C:
        # a row of an upper half falls from its half's start; a row of a
        # lower half has yet to fall to its half's end
        upper = ((at >> level) & 1) == 1
        fell = jnp.exp(jnp.where(upper, _run(g, at, s, False),
                                 _run(g, at, s, True) - g))
        fall, rise = jnp.where(upper, fell, 0.0), jnp.where(upper, 0.0, fell)
        met = _nt(jnp.concatenate([k * fall, q * fall], 0), kb * rise)
        same = (row >> (level + 1)) == (col >> (level + 1))
        a_s = jnp.where(same, met[:C], 0.0)
        b = b + jnp.where(same, met[C:], 0.0)
        inv = inv - _nn(_nn(inv, a_s), inv)
        s, level = 2 * s, level + 1
    G = _run(g, at, C, False)
    eG = jnp.exp(G)
    st = st_ref[...]                                         # [dv, dk]
    u = _nn(inv, v) - _nt(_nn(inv, k * eG), st)
    o_ref[...] = _nt(q * eG, st) + _nn(b, u)
    last = G[C - 1:C, :]                                     # [1, dk]
    st = st * jnp.exp(last) + _dot(u, kb * jnp.exp(last - G),
                                   ((0,), (0,)))
    st_ref[...] = st

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        s_ref[...] = st.T


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(q, k, v, g, beta, state, *, interpret: bool = False):
    """Shapes as ``ops/kda.kda_chunked``: ``q``, ``k``, ``g`` ``[B, T, H,
    d_k]``; ``v [B, T, H, d_v]``; ``beta [B, T, H]``; ``state [B, H, d_k,
    d_v]`` float32. Returns (``o [B, T, H, d_v]`` float32, the state after
    position ``T - 1``). A ``T`` that is no multiple of the chunk is padded
    with inert positions (``g = 0``, ``beta = 0``)."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % CHUNK
    N = (T + pad) // CHUNK
    kb = k.astype(f32) * beta.astype(f32)[..., None]

    def flat(x):       # [B, T, H, d] -> [B, T + pad, H d]: a head a lane tile
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(B, T + pad, H * x.shape[-1])

    def rows(d):
        return pl.BlockSpec((None, CHUNK, d), lambda b, h, n: (b, n, h))

    state_spec = pl.BlockSpec((None, None, dk, dv),
                              lambda b, h, n: (b, h, 0, 0))
    with jax.named_scope("kda_chunk"):
        o, state = pl.pallas_call(
            _chunk_kernel,
            grid=(B, H, N),
            in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dk),
                      state_spec],
            out_specs=[rows(dv), state_spec],
            out_shape=[jax.ShapeDtypeStruct((B, T + pad, H * dv), f32),
                       jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
            scratch_shapes=[pltpu.VMEM((dv, dk), f32)],
            # a head's chunks run in order: the state is carried in VMEM
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_chunk",
        )(flat(q), flat(k), flat(kb), flat(v), flat(g), state.astype(f32))
    return o.reshape(B, T + pad, H, dv)[:, :T], state
