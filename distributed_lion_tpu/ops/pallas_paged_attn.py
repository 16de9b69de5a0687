"""Decode attention over the paged KV pool, read in place (Mosaic kernel).

One query token a row (the serving engine's decode tick, S = 1). The pool
is never gathered: the block tables and each row's token count ride in as
scalar-prefetched operands, and row ``b`` walks its own
``ceil(length / block_size)`` pages only, copying each page (all its kv
heads, one contiguous ``[block_size, W]`` slab) from HBM into a
double-buffered VMEM block of :func:`pages_per_block` pages while the block
before it is computed. Online softmax in float32 (running max, sum and
accumulator) over ``PART_TOKENS`` positions at a time, operands in the cache
dtype, probabilities cast to the value dtype before the value contraction:
the gather path's arithmetic (``ops/attention.paged_decode_attention``),
blockwise.

Heads without a head loop. A page row holds its kv heads side by side
(``W = KV * hd`` lanes, padded to 128: ``serve/kv_cache.pool_row_width``),
and 64-lane head slices are not tile-aligned. So the caller spreads the
queries block-diagonally, ``q_bd[h, kv(h)*hd:(kv(h)+1)*hd] = q[h]`` and
zeros elsewhere, and the kernel runs two plain matmuls a block:
``q_bd [H, W] x k [T, W]^T -> scores [H, T]`` (the zeros drop every other
head's lanes: each score is exactly its own head's dot product) and
``p [H, T] x v [T, W] -> [H, W]``, of which the caller keeps head h's own
``hd`` lanes. GQA is the same thing with ``kv(h) = h // (H // KV)``: no
``jnp.repeat``. The MXU does H x W where H x hd would do; at one query a
head the page's weight tile is loaded either way.

A page is whatever ``block_size`` the operand has. A caller whose table
names aligned runs of ``r`` pages (``serve/kv_cache``'s runs;
``ops/sparse_select``'s block lists) hands the same pool viewed as
``[num_blocks / r, r * block_size, 1, W]`` and run ids for page ids, and
starts a quarter of the copies at ``r = 4``. Two sizes are kept apart. What
is COPIED ahead is a block of 16 pages whatever their length (up to 1,024
positions): the copies in flight have to cover the DMA's latency, and 16
pages of 8 KB do not (cell 8's walk alone read 2.27 ms a layer by the page;
by runs of 32 KB 1.41 ms with 4 a block and 0.92 with 16: PERF.md section
6, PR 41). What the softmax
TAKES at a time is 256 positions whatever the page: a block of pages of 16
is one part as it always was, a block of runs of 64 is four, so the walk by
runs updates max, sum and accumulator over the same partitions in the same
order and its output is bit for bit the walk by pages.

A window layer's walk (``starts``, optional): the caller hands the pages of
a row's window in logical order (a bounded ring, rotated:
``ops/attention.ring_decode_attention``) and the count of leading rows of the
first page that lie before the window; the kernel masks them. Without
``starts`` the program is the one it was before the operand existed.

Rows with nothing to read (``lengths[b] == 0``: an inactive slot) touch no
page and return zeros; ids at or past the pool (the sentinel) are never
dereferenced because the walk is bounded by the length, which the caller
bounds by the row's count of real table entries.

Name on the device: ``paged_attn`` (``name=`` and the innermost
``jax.named_scope``, as ``ops/pallas_lion`` names its kernels).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
PAGES_PER_BLOCK = 16  # pages a block, copied while the one before computes:
# k and v, two slots each, are 4 x 16 x 53 KB = 3.4 MB of VMEM at GPT-2 XL,
# 8.4 MB at 32 heads of 128. On the chip at cell 2's shapes 4 / 8 / 16 / 32
# pages read 329 / 253 / 247 / 248 us a call (my chip run, PR 24); at cell
# 8's, runs of 64 rows x 256 lanes, 4 / 8 / 16 / 32 read 1.406 / 1.005 /
# 0.917 / 0.872 ms (my chip run, PR 41): what is in flight while a block
# computes has to cover the DMA's latency, some 1 MB a leaf here
BLOCK_TOKENS = 1024   # ... but no more positions a block than this
PART_TOKENS = 256     # positions the online softmax takes at a time: 16 pages
# of 16, one block; of a block of larger pages, a part (so the walk by runs
# is bit for bit the walk by pages of 16; a block of runs taken whole read
# 0.751 ms where four parts read 0.917: the price of that)
Q_ROWS = 16          # query heads pad to whole bf16 sublane tiles
MASKED = -1e30       # the gather path's mask value


def pages_per_block(block_size: int) -> int:
    """Pages a block holds: ``PAGES_PER_BLOCK``, or as many as
    ``BLOCK_TOKENS`` positions fill where the pages are long."""
    return min(PAGES_PER_BLOCK, max(BLOCK_TOKENS // block_size, 1))


def parts_per_block(block_size: int) -> int:
    """Parts of ``PART_TOKENS`` positions (whole pages) the softmax takes a
    block in; 1 where a block holds no more, or no whole number of them."""
    pages = pages_per_block(block_size)
    part = max(PART_TOKENS // block_size, 1)
    return 1 if pages % part else pages // part


def kernel_takes(pool_shape, dtype) -> bool:
    """Whether the chip's compiler takes a pool of this shape as it lies:
    one kv-head group (``[num_blocks, block_size, 1, W]``), whole lane
    tiles, and pages that are whole sublane tiles of the dtype (16 rows of
    bf16, 8 of float32), so a block of pages is a plain 2-D operand."""
    if len(pool_shape) != 4 or pool_shape[2] != 1:
        return False
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return pool_shape[3] % LANES == 0 and pool_shape[1] % sublanes == 0


def _kernel(lens_ref, tables_ref, *refs, scale: float, table_width: int,
            windowed: bool, parts: int):
    if windowed:
        starts_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, acc_ref, ahead_ref = refs
    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    n_slots, pages, bs, width = k_buf.shape
    part = pages // parts              # pages the softmax takes at a time
    tokens = part * bs

    def pages_of(row):
        return (lens_ref[row] + bs - 1) // bs

    length = lens_ref[b]
    n_pages = pages_of(b)
    n_blocks = (n_pages + pages - 1) // pages
    nxt = jnp.minimum(b + 1, last_row)
    nxt_pages = jnp.where(b < last_row, pages_of(nxt), 0)

    @pl.when(b == 0)
    def _():
        # a block's unread pages (past the row's last) keep what an earlier
        # block left there; masked probabilities are 0, and 0 x finite is 0
        # where 0 x uninitialised memory need not be
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        ahead_ref[0] = 0

    def block_copies(row, row_pages, blk, slot, wait=False):
        """Start (or wait for) the copies of block ``blk`` of ``row``:
        one a page, k and v, of the pages the row really has."""
        for i in range(pages):
            page = blk * pages + i

            @pl.when(page < row_pages)
            def _():
                pid = tables_ref[row * table_width + page]
                for hbm, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                      (v_hbm, v_buf, sems.at[1, slot])):
                    copy = pltpu.make_async_copy(hbm.at[pid],
                                                 buf.at[slot, i], sem)
                    if wait:
                        copy.wait()
                    else:
                        copy.start()

    # the row before starts this row's first block while it computes its
    # own last one (1 + the slot it chose; 0 = nothing was copied ahead)
    ahead = ahead_ref[0]
    ahead_ref[0] = 0
    first_slot = jnp.maximum(ahead - 1, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

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
        for j in range(parts):
            # a part past the row's end is all masked: its probabilities
            # are 0 and it leaves the running max, sum and accumulator be
            m_prev, l_prev = carry

            def rows_of(buf):
                got = buf[slot] if parts == 1 \
                    else buf[slot, j * part:(j + 1) * part]
                return got.reshape(tokens, width)

            k = rows_of(k_buf)
            s = jax.lax.dot_general(
                q_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            first = blk * tokens if parts == 1 else (blk * parts + j) * tokens
            t_idx = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = t_idx < length
            if windowed:   # rows of the walk's first page before the window
                keep = jnp.logical_and(keep, t_idx >= starts_ref[b])
            s = jnp.where(keep, s, MASKED)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            v = rows_of(v_buf)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            carry = m_new, alpha * l_prev + p.sum(axis=1, keepdims=True)
        return carry

    rows = q_ref.shape[0]
    _, l = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((rows, 1), MASKED, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32)))
    o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _spread_heads(q, kv_heads: int, width: int):
    """[B, H, hd] -> block-diagonal [B, H', width]: head h's query in kv
    head ``h // (H // kv_heads)``'s lanes, zeros elsewhere; H' pads H to
    whole sublane tiles with zero rows."""
    B, H, hd = q.shape
    own = (jnp.arange(H)[:, None] // (H // kv_heads)
           == jnp.arange(kv_heads)[None, :])                      # [H, KV]
    q_bd = jnp.where(own[None, :, :, None], q[:, :, None, :], 0)
    q_bd = q_bd.reshape(B, H, kv_heads * hd)
    return jnp.pad(q_bd, ((0, 0), (0, -H % Q_ROWS),
                          (0, width - kv_heads * hd)))


def _own_lanes(o_bd, H: int, kv_heads: int, hd: int):
    """[B, H', width] -> [B, H, hd]: of each head's row, its own kv head's
    ``hd`` lanes."""
    B = o_bd.shape[0]
    o = o_bd[:, :H, :kv_heads * hd].reshape(B, H, kv_heads, hd)
    kv_of = (jnp.arange(H) // (H // kv_heads))[None, :, None, None]
    return jnp.take_along_axis(o, kv_of, axis=2)[:, :, 0]


@functools.partial(jax.jit, static_argnames=("kv_heads", "interpret"))
def paged_attn(q, k_pages, v_pages, tables, lengths, starts=None, *,
               kv_heads: int, interpret: bool = False):
    """q [B, H, hd] (one token a row); k_pages / v_pages
    ``[num_blocks, block_size, 1, W]`` (see :func:`kernel_takes`); tables
    [B, nb] int32 page ids; lengths [B] int32 — tokens row b attends
    (positions ``0 .. lengths[b] - 1``; 0 = read nothing, return zeros).
    Every id among a row's first ``ceil(lengths[b] / block_size)`` entries
    must lie inside the pool. ``starts`` (optional [B] int32, each below
    ``block_size * PAGES_PER_BLOCK`` so that no block is masked whole): row
    b attends positions ``starts[b] .. lengths[b] - 1`` of its walk.
    Returns [B, H, hd] in q's dtype."""
    B, H, hd = q.shape
    NB, bs, _, W = k_pages.shape
    nb = tables.shape[1]
    q_bd = _spread_heads(q, kv_heads, W)
    rows = q_bd.shape[1]
    pages = pages_per_block(bs)
    row_spec = pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0))
    scalars = (lengths.astype(jnp.int32),
               tables.reshape(-1).astype(jnp.int32))
    if starts is not None:
        scalars += (starts.astype(jnp.int32),)
    with jax.named_scope("paged_attn"):
        o_bd = pl.pallas_call(
            functools.partial(_kernel, scale=1.0 / math.sqrt(hd),
                              table_width=nb,
                              windowed=starts is not None,
                              parts=parts_per_block(bs)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(B,),
                in_specs=[row_spec,
                          pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=row_spec,
                scratch_shapes=[
                    pltpu.VMEM((2, pages, bs, W), k_pages.dtype),
                    pltpu.VMEM((2, pages, bs, W), v_pages.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((rows, W), jnp.float32),
                    pltpu.SMEM((1,), jnp.int32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, rows, W), q.dtype),
            # rows run in order: row 0 fills the buffers with zeros and each
            # row starts the next one's first block
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_attn",
        )(*scalars, q_bd, k_pages.reshape(NB, bs, W),
          v_pages.reshape(NB, bs, W))
    return _own_lanes(o_bd, H, kv_heads, hd)
