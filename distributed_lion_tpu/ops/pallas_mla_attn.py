"""Absorbed latent (MLA) decode attention over the paged pool, read in
place (Mosaic kernel).

Latent attention keeps ONE row a token a layer: ``[c_kv | k_rope]``, the
normed compressed key-value vector beside the one roped key all heads
share (``serve/kv_cache`` pads it to whole 128-lane tiles; pad lanes stay
zero). With the key up-projection folded into the query and the value
up-projection applied after the softmax (``models/joyai``), every head
attends that same row: one kv head of the row's whole width, scores from
all its lanes, values from its leading ``value_width`` lanes. So the kernel
is ``ops/pallas_paged_attn`` with one page read serving both matmuls:
lengths and tables scalar-prefetched, row ``b`` walks its own
``ceil(length / block_size)`` pages, each page one DMA of
``[block_size, W]`` into a double-buffered block of ``PAGES_PER_BLOCK``
pages, the next row's first block started under this row's last; online
softmax in float32, probabilities cast to the cache dtype before the value
matmul. The output is ``sum p * row`` over all W lanes (one matmul, no
lane slicing in the kernel); the caller keeps the value lanes.

Two optional operands, each a walk of its own under its own name on the
device; without them the program is the one it was before they existed:

- ``keep`` ``[B, T]`` bool: the positions row b attends among those its walk
  covers (a learned indexer's choice: ``ops/dsa.kept_positions``); the walk
  reads every page and masks. Name ``dsa_attn``.
- ``starts`` ``[B]``: the leading rows of the walk's first page that lie
  before a window (a ring of latent rows handed in logical order:
  ``ops/attention.ring_mla_decode_attention``), masked as
  ``ops/pallas_paged_attn`` masks them. Name ``window_mla_attn``.

Under either a masked probability is written as 0 outright, so a block with
no position to attend leaves sum and accumulator as they were.

Name on the device: ``mla_paged_attn`` (``name=`` says otherwise).
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


def _kernel(lens_ref, tables_ref, *refs, scale: float, table_width: int,
            windowed: bool, kept: bool):
    if windowed:
        starts_ref, *refs = refs
    q_ref, *refs = refs
    if kept:
        keep_ref, *refs = refs
    kv_hbm, o_ref, kv_buf, sems, acc_ref, ahead_ref = refs
    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    n_slots, pages, bs, width = kv_buf.shape
    tokens = pages * bs

    def pages_of(row):
        return (lens_ref[row] + bs - 1) // bs

    length = lens_ref[b]
    n_pages = pages_of(b)
    n_blocks = (n_pages + pages - 1) // pages
    nxt = jnp.minimum(b + 1, last_row)
    nxt_pages = jnp.where(b < last_row, pages_of(nxt), 0)

    @pl.when(b == 0)
    def _():
        # unread pages of a block keep what an earlier block left there:
        # masked probabilities are 0, and 0 x finite is 0
        kv_buf[...] = jnp.zeros_like(kv_buf)
        ahead_ref[0] = 0

    def block_copies(row, row_pages, blk, slot, wait=False):
        for i in range(pages):
            page = blk * pages + i

            @pl.when(page < row_pages)
            def _():
                pid = tables_ref[row * table_width + page]
                copy = pltpu.make_async_copy(kv_hbm.at[pid],
                                             kv_buf.at[slot, i],
                                             sems.at[slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()

    ahead = ahead_ref[0]
    ahead_ref[0] = 0
    first_slot = jnp.maximum(ahead - 1, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(n_blocks > 0, ahead == 0))
    def _():
        block_copies(b, n_pages, 0, 0)

    def body(blk, carry):
        m_prev, l_prev = carry
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
        kv = kv_buf[slot].reshape(tokens, width)
        s = jax.lax.dot_general(q_ref[...], kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        t_idx = blk * tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = t_idx < length
        if windowed:   # rows of the walk's first page before the window
            seen = jnp.logical_and(seen, t_idx >= starts_ref[b])
        if kept:       # the positions the row's indexer kept
            seen = jnp.logical_and(
                seen, keep_ref[:, pl.ds(pl.multiple_of(blk * tokens, tokens),
                                        tokens)] > 0)
        s = jnp.where(seen, s, MASKED)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if windowed or kept:   # a block may hold no position to attend
            p = jnp.where(seen, p, 0.0)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(kv.dtype), kv, preferred_element_type=jnp.float32)
        return m_new, alpha * l_prev + p.sum(axis=1, keepdims=True)

    rows = q_ref.shape[0]
    _, l = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((rows, 1), MASKED, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32)))
    o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def mla_paged_attn(q, kv_pages, tables, lengths, *, scale: float,
                   keep=None, starts=None, interpret: bool = False,
                   name: str = "mla_paged_attn"):
    """q [B, H, W] — the absorbed queries, laid out like a latent row
    (``[q_nope W_k | q_rope | 0]``); kv_pages ``[num_blocks, block_size, 1,
    W]`` (``pallas_paged_attn.kernel_takes`` says which pools); tables
    [B, nb] int32; lengths [B] int32 — tokens row b attends (0 = read
    nothing, return zeros). ``keep`` (optional [B, T] bool, T at least the
    positions the longest walk covers) and ``starts`` (optional [B] int32,
    each below ``block_size``): the module note. Returns ``softmax(scale * q
    . row) @ row`` [B, H, W] in q's dtype: the caller keeps the value
    lanes."""
    B, H, W = q.shape
    NB, bs = kv_pages.shape[:2]
    nb = tables.shape[1]
    q = jnp.pad(q, ((0, 0), (0, -H % Q_ROWS), (0, 0)))
    rows = q.shape[1]
    row_spec = pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0))
    scalars = (lengths.astype(jnp.int32),
               tables.reshape(-1).astype(jnp.int32))
    if starts is not None:
        scalars += (starts.astype(jnp.int32),)
    operands, in_specs = [q], [row_spec]
    if keep is not None:
        # whole blocks of the walk: a block's slice never leaves the operand
        tokens = PAGES_PER_BLOCK * bs
        width = -(-max(nb * bs, keep.shape[1]) // tokens) * tokens
        keep = jnp.pad(keep.astype(jnp.int32),
                       ((0, 0), (0, width - keep.shape[1])))[:, None]
        operands.append(keep)
        in_specs.append(pl.BlockSpec((None, 1, width),
                                     lambda b, *_: (b, 0, 0)))
    with jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_kernel, scale=scale, table_width=nb,
                              windowed=starts is not None,
                              kept=keep is not None),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(B,),
                in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=row_spec,
                scratch_shapes=[
                    pltpu.VMEM((2, PAGES_PER_BLOCK, bs, W), kv_pages.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.VMEM((rows, W), jnp.float32),
                    pltpu.SMEM((1,), jnp.int32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, rows, W), q.dtype),
            # rows run in order: row 0 zero-fills the buffer and each row
            # starts the next one's first block
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(*scalars, *operands, kv_pages.reshape(NB, bs, W))
    return out[:, :H]
