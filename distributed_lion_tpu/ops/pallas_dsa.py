"""The learned indexer's decode scoring over the index-key pages, read in
place (Mosaic kernel; ``ops/dsa`` holds the mathematics and says when this
runs).

One query a row (the serving engine's decode tick). Row ``b`` scores every
position it can see, ``I[b, s] = sum_j w[b, j] ReLU(q[b, j] . kI[s])``: its
``Hi`` index heads' queries against the ONE index key a position holds (a
page row of ``W >= index_head_dim`` lanes, pad lanes zero). The walk is
``ops/pallas_mla_attn``'s: lengths and tables scalar-prefetched, each page
(or aligned run of pages: ``ops/dsa.by_runs``) one DMA of ``[block_size,
W]`` into a double-buffered block of ``PAGES_PER_BLOCK`` pages, the next
row's first block started under this row's last. A block is one matmul
``[Hi, W] x [tokens, W]^T`` with float32 accumulation, then ReLU, the head
weights and the sum over heads on the vector unit in float32, and the
block's scores written to the row's output where they belong. Nothing is
kept between blocks: no softmax. Positions past the row's length are never
written (the caller masks them).

The latent attention over the kept set is ``ops/pallas_mla_attn``'s kernel
under a mask (``keep``; name ``dsa_attn``), the window layers' over a ring
the same kernel from a first row on (``starts``; ``window_mla_attn``).

A prefill's attention under the indexer's mask (:func:`dsa_prefill`): the
expanded form, one head a grid row, tiles of ``BLOCK_Q`` queries against
``BLOCK_K`` keys with the running max, sum and accumulator in VMEM (the
online softmax of ``ops/pallas_flash_attn``), the mask a tile of int8 that
every head reads. A tile above the diagonal is never copied (its index is
clamped to the last one the query tile can see) nor computed. What the XLA
walk (``ops/dsa``) writes to HBM and reads back a step, ``[heads, queries,
keys]`` float32 scores five times over, never leaves the chip's VMEM here.

The same tiles with no mask (:func:`latent_prefill`): every latent family's
prefill from position 0 (``ops/attention.latent_fresh_attention``). The
causal bound comes from two iotas on the tiles the diagonal crosses and
from nothing on the tiles under it; keys and values are read where the
up-projection wrote them (a token's row of every head's ``[k_nope | v]``),
the one ``k_rope`` a position holds is an operand of its own, the output is
token-major, and a query tile wholly past its row's length is zeros.

Names on the device: ``dsa_index``, ``dsa_prefill``, ``latent_prefill``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_lion_tpu.ops.pallas_paged_attn import (
    MASKED,
    PAGES_PER_BLOCK,
    Q_ROWS,
)


def _kernel(lens_ref, tables_ref, q_ref, w_ref, k_hbm, o_ref, k_buf, sems,
            ahead_ref, *, table_width: int):
    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    n_slots, pages, bs, width = k_buf.shape
    tokens = pages * bs

    def pages_of(row):
        return (lens_ref[row] + bs - 1) // bs

    n_pages = pages_of(b)
    n_blocks = (n_pages + pages - 1) // pages
    nxt = jnp.minimum(b + 1, last_row)
    nxt_pages = jnp.where(b < last_row, pages_of(nxt), 0)

    @pl.when(b == 0)
    def _():
        # unread pages of a block keep what an earlier block left there:
        # finite, and past the row's length, where the caller never looks
        k_buf[...] = jnp.zeros_like(k_buf)
        ahead_ref[0] = 0

    def block_copies(row, row_pages, blk, slot, wait=False):
        for i in range(pages):
            page = blk * pages + i

            @pl.when(page < row_pages)
            def _():
                pid = tables_ref[row * table_width + page]
                copy = pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, i],
                                             sems.at[slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()

    ahead = ahead_ref[0]
    ahead_ref[0] = 0
    first_slot = jnp.maximum(ahead - 1, 0)

    @pl.when(jnp.logical_and(n_blocks > 0, ahead == 0))
    def _():
        block_copies(b, n_pages, 0, 0)

    def body(blk, carry):
        slot = (first_slot + blk) % n_slots
        other = (slot + 1) % n_slots

        @pl.when(blk + 1 < n_blocks)
        def _():
            block_copies(b, n_pages, blk + 1, other)

        @pl.when(jnp.logical_and(blk + 1 == n_blocks, nxt_pages > 0))
        def _():
            block_copies(nxt, nxt_pages, 0, other)
            ahead_ref[0] = other + 1

        block_copies(b, n_pages, blk, slot, wait=True)
        k = k_buf[slot].reshape(tokens, width)
        s = jax.lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        part = (jnp.maximum(s, 0.0) * w_ref[...]).sum(axis=0, keepdims=True)
        o_ref[:, pl.ds(pl.multiple_of(blk * tokens, tokens), tokens)] = part
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index(q, w, k_pages, tables, lengths, *, interpret: bool = False):
    """q ``[B, Hi, W]`` the index heads' roped queries, padded to the page
    row's lanes; w ``[B, Hi]`` float32 head weights; k_pages ``[num_blocks,
    block_size, 1, W]`` (``pallas_paged_attn.kernel_takes`` says which
    pools); tables ``[B, nb]`` int32; lengths ``[B]`` int32, the positions
    row b scores (0 = read nothing). Returns ``[B, nb * block_size]``
    float32; entries at or past ``lengths[b]`` are undefined."""
    B, Hi, W = q.shape
    NB, bs = k_pages.shape[:2]
    nb = tables.shape[1]
    pad = -Hi % Q_ROWS                       # zero heads weigh nothing
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad)))[..., None]
    rows = Hi + pad
    tokens = PAGES_PER_BLOCK * bs
    width = -(-nb * bs // tokens) * tokens   # whole blocks of the walk
    with jax.named_scope("dsa_index"):
        out = pl.pallas_call(
            functools.partial(_kernel, table_width=nb),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B,),
                in_specs=[
                    pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0)),
                    pl.BlockSpec((None, rows, 1), lambda b, *_: (b, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((None, 1, width),
                                       lambda b, *_: (b, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, PAGES_PER_BLOCK, bs, W), k_pages.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SMEM((1,), jnp.int32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, 1, width), jnp.float32),
            # rows run in order: row 0 zero-fills the buffer and each row
            # starts the next one's first block
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="dsa_index",
        )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
          q, w, k_pages.reshape(NB, bs, W))
    return out[:, 0, :nb * bs]


# ------------------------------------------------- the prefill under a mask
BLOCK_Q = 512     # queries a tile
BLOCK_K = 1024    # keys a tile: scores [512, 1024] float32, 2 MB of VMEM


def prefill_takes(S: int, dv: int) -> bool:
    """Whether :func:`dsa_prefill` takes a prompt of ``S`` positions with
    values of ``dv``: whole tiles, whole lane tiles of values."""
    return S % BLOCK_K == 0 and dv % 128 == 0


def _prefill_kernel(*refs, scale: float, masked: bool):
    """One tile of a prefill's online softmax, in its two forms. Under a
    mask (:func:`dsa_prefill`): ``q, k [., dk]``, ``v``, the mask's tile.
    Without one (:func:`latent_prefill`): the rows' lengths (scalars), ``q
    [., dn + dr]``, every head's own ``k_nope [., dn]``, the ONE ``k_rope
    [., dr]`` the heads share (a second product into the same scores) and
    ``v``; the causal bound then comes from two iotas, on the tiles the
    diagonal crosses alone, and a query tile wholly past its row's length
    writes zeros."""
    if masked:
        q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref, l_ref, acc_ref = refs
        i, j = pl.program_id(1), pl.program_id(2)
        live = True
    else:
        len_ref, q_ref, k_ref, kr_ref, v_ref, o_ref, m_ref, l_ref, acc_ref \
            = refs
        i, j = pl.program_id(2), pl.program_id(3)
        live = i * q_ref.shape[0] < len_ref[pl.program_id(0)]
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    last = (i * bq + bq - 1) // bk     # the last key tile this one can see

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def scores():
        dims = (((1,), (1,)), ((), ()))
        if masked:
            return jax.lax.dot_general(q_ref[...], k_ref[...], dims,
                                       preferred_element_type=jnp.float32)
        dn = k_ref.shape[1]
        return jax.lax.dot_general(
            q_ref[:, :dn], k_ref[...], dims,
            preferred_element_type=jnp.float32) + jax.lax.dot_general(
            q_ref[:, dn:], kr_ref[...], dims,
            preferred_element_type=jnp.float32)

    def tile(seen):
        s = scores() * scale
        if seen is not None:
            s = jnp.where(seen, s, MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:                     # a tile of the mask may keep none
            p = jnp.where(seen, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if masked:
        @pl.when(j <= last)
        def _():
            tile(keep_ref[...].astype(jnp.int32) != 0)
    else:
        # key tiles wholly at or under the tile's first query: no bound
        clear = (i * bq + 1) // bk

        @pl.when(jnp.logical_and(live, j < clear))
        def _():
            tile(None)

        @pl.when(jnp.logical_and(live, jnp.logical_and(j >= clear,
                                                       j <= last)))
        def _():       # every row sees a key of such a tile: its own, or
            # the tile's first
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            tile(cols <= rows)

    @pl.when(j == last)
    def _():
        l = l_ref[...]                 # 0 where nothing ran: zeros out
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                      ).astype(o_ref.dtype)


def _last_seen(i, j, bq: int, bk: int):
    """The key tile a grid step copies: a tile past the diagonal is the
    last one the query tile sees, again (so nothing is copied)."""
    return jnp.minimum(j, (i * bq + bq - 1) // bk)


def _scratch(bq: int, dv: int) -> list:
    """A query tile's running max, sum and accumulator."""
    return [pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def dsa_prefill(q, k, v, keep, *, scale: float, interpret: bool = False):
    """Causal self-attention of S fresh tokens under a mask, expanded form:
    q, k ``[H, S, dk]``; v ``[H, S, dv]``; ``keep [S, S]`` int8, 1 where
    query t attends key s (the causal bound already in it: nothing above the
    diagonal). :func:`prefill_takes` says which shapes. Returns ``softmax(
    scale q k^T | keep) v`` ``[H, S, dv]`` in q's dtype, float32 scores and
    sums, the probabilities cast to v's dtype before the value product."""
    H, S, dk = q.shape
    dv = v.shape[-1]
    bq, bk = min(BLOCK_Q, S), min(BLOCK_K, S)
    seen = functools.partial(_last_seen, bq=bq, bk=bk)
    with jax.named_scope("dsa_prefill"):
        return pl.pallas_call(
            functools.partial(_prefill_kernel, scale=scale, masked=True),
            grid=(H, S // bq, S // bk),
            in_specs=[
                pl.BlockSpec((None, bq, dk), lambda h, i, j: (h, i, 0)),
                pl.BlockSpec((None, bk, dk),
                             lambda h, i, j: (h, seen(i, j), 0)),
                pl.BlockSpec((None, bk, dv),
                             lambda h, i, j: (h, seen(i, j), 0)),
                pl.BlockSpec((bq, bk), lambda h, i, j: (i, seen(i, j)))],
            out_specs=pl.BlockSpec((None, bq, dv), lambda h, i, j: (h, i, 0)),
            scratch_shapes=_scratch(bq, dv),
            out_shape=jax.ShapeDtypeStruct((H, S, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="dsa_prefill",
        )(q, k, v, keep)


# --------------------------------------------- the same tiles with no mask
def latent_prefill_takes(S: int, dn: int, dv: int) -> bool:
    """Whether :func:`latent_prefill` takes ``S`` positions of heads ``dn``
    (the keys' own part) / ``dv``: whole key tiles, and one lane tile each,
    so that a head's ``[k_nope | v]`` are two blocks of the row the
    up-projection wrote."""
    return S % BLOCK_K == 0 and dn == dv == 128


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_prefill(q, kv, k_rope, lengths, *, scale: float,
                   interpret: bool = False):
    """Causal self-attention of S fresh tokens at positions ``0 .. S - 1``
    of a latent family, expanded form, every operand as its projection wrote
    it: q ``[B, H, S, dn + dr]``; kv ``[B, S, H * (dn + dv)]``, a token's row
    of every head's ``[k_nope | v]`` (``c_kv W_kvb``, token-major: a head's
    keys and values are two lane blocks of it); k_rope ``[B, S, dr]``, the
    one roped key all heads share; ``lengths [B]`` int32, the real tokens of
    each row. :func:`latent_prefill_takes` says which shapes. Returns
    ``softmax(scale [q_nope | q_rope] [k_nope | k_rope]^T | causal) v``
    token-major ``[B, S, H * dv]`` in q's dtype (what the output projection
    reads): float32 scores and sums, the probabilities cast to v's dtype
    before the value product. Queries in a tile wholly past ``lengths[b]``
    come back zero (nothing is computed for them); the others past it attend
    pad keys and are the caller's to discard."""
    B, H, S, dk = q.shape
    dr = k_rope.shape[-1]
    dn = dv = dk - dr
    bq, bk = min(BLOCK_Q, S), min(BLOCK_K, S)

    def seen(b, i, j, lens):           # a dead query tile copies tile 0
        return jnp.where(i * bq < lens[b], _last_seen(i, j, bq, bk), 0)

    with jax.named_scope("latent_prefill"):
        return pl.pallas_call(
            functools.partial(_prefill_kernel, scale=scale, masked=False),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, H, S // bq, S // bk),
                in_specs=[
                    pl.BlockSpec((None, None, bq, dk),
                                 lambda b, h, i, j, lens: (b, h, i, 0)),
                    pl.BlockSpec((None, bk, dn), lambda b, h, i, j, lens:
                                 (b, seen(b, i, j, lens), 2 * h)),
                    pl.BlockSpec((None, bk, dr), lambda b, h, i, j, lens:
                                 (b, seen(b, i, j, lens), 0)),
                    pl.BlockSpec((None, bk, dv), lambda b, h, i, j, lens:
                                 (b, seen(b, i, j, lens), 2 * h + 1))],
                out_specs=pl.BlockSpec((None, bq, dv),
                                       lambda b, h, i, j, lens: (b, i, h)),
                scratch_shapes=_scratch(bq, dv)),
            out_shape=jax.ShapeDtypeStruct((B, S, H * dv), q.dtype),
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="latent_prefill",
        )(lengths.astype(jnp.int32), q, kv, k_rope, kv)
