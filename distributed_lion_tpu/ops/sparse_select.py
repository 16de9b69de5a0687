"""Learned block-sparse attention (InfLLM-V2, the ``minicpm4`` mixer): a
compressed key a page, the selection of blocks from it, and attention over
the selected blocks only.

**Compressed keys.** ``c_j = mean(k_{s j} .. k_{s j + 2 s - 1})`` a kv head:
windows of ``kernel_size = 2 s`` keys at stride ``s = kernel_stride``. The
engine's pages hold ``m`` whole strides (``m = 1`` in the benchmark's cell:
pages of 16), and ``c_j`` lives in row ``j % m`` of page ``j // m`` of the row's
table, in the leaf ``ck [num_blocks, m, 1, W]``: it is written when its last
key is (for ``m = 1``, when the page after its own is full), and it has its
page's id and lifetime and no table of its own. A query at position ``p`` may
read ``c_j`` only where ``s j + 2 s - 1 <= p`` (:func:`visible`); that bound is
the whole protection a reused page has: what its last owner left in its rows
lies past every ``j`` the new owner can see until the new owner has
rewritten it.

**Selection** (:func:`kept_blocks`), a query past ``dense_len``, a kv
head ``g`` (its ``rep`` query heads): ``a_{h,j} = softmax_j(hd^-0.5 q_h .
c_j)`` over the visible ``j``, float32; ``A_{g,j} = sum_h a_{h,j}``; a block
``b`` of ``block_size`` positions (``r = block_size / s`` pages) scores ``max
A_{g,j}`` over the visible windows that touch it, ``r b - 1 <= j <= r b + r -
1``; the first ``init_blocks`` blocks and the last ``window_size /
block_size`` up to the query's own are forced (+inf); the best ``topk`` are
kept, ties to the lower index, a set a kv head. A query at ``p + 1 <=
dense_len`` sees every position.

**Decode** (:func:`sparse_decode_attention`): the selected blocks of each
(row, kv head) as one compacted list, ascending, the query's own partly
filled block last, and ``lengths`` the positions the list holds; the layer
has no RoPE, so attention over that list IS ``ops/pallas_paged_attn``'s walk
(``paged_decode_attention``, kernel or gather path) with the list for a
table. A block's pages are an aligned run of the pool (the engine mints them
so for this family: ``serve/kv_cache``), so the list names runs and the
walk reads the pool as ``[runs, block_size, 1, W]``: a block is one copy of
32 KB where its four pages were four of 8 KB, and the kernel was bound by
how many copies it starts (PERF.md section 6, PR 41). A row at or under
``dense_len`` gets its own table's blocks as the list of both kv heads: one
program holds both kinds of row.

**Prefill** (:func:`sparse_prefill_attention`): the positions up to
``dense_len`` through ``banded_causal_attention`` (the tiled kernel on a
TPU); the ones past it a tile of queries at a time in XLA, the same scores
and top-k for every query of the tile, the selected blocks as a mask over
the tile's causal keys, float32 softmax: no ``[heads, S, S]`` buffer, and a
masked walk computes more than a skipping one, never less.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from distributed_lion_tpu.ops.attention import (
    SCORE_BYTES,
    banded_causal_attention,
    paged_decode_attention,
)


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    window_size: int = 2048
    topk: int = 64
    init_blocks: int = 1
    dense_len: int = 8192

    def __post_init__(self):
        s = self.kernel_stride
        if self.kernel_size != 2 * s or self.block_size % s \
                or self.window_size % self.block_size \
                or self.window_size < self.block_size \
                or self.dense_len % self.block_size:
            raise ValueError(
                f"sparse_config {self}: windows of two strides, blocks and "
                "dense_len of whole strides and blocks, a local window of "
                "whole blocks are what is implemented")

    @staticmethod
    def from_hf(spec: dict) -> "SparseConfig":
        return SparseConfig(**{f.name: int(spec[f.name])
                               for f in dataclasses.fields(SparseConfig)})

    @property
    def windows_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size


def visible(p, j, sp: SparseConfig):
    """Window ``j`` is complete at a query's position ``p``."""
    return sp.kernel_stride * j + sp.kernel_size - 1 <= p


# ------------------------------------------------------- compressed keys
@jax.named_scope("sparse/compress")
def prefill_compressed(k_new, lengths, sp: SparseConfig):
    """The compressed keys of a prompt from its fresh keys. ``k_new [B, S,
    KV, hd]`` (the values the pages hold); ``lengths [B]`` the prompts' TRUE
    lengths. Returns (``rows [B, S / s, KV * hd]`` in ``k_new``'s dtype,
    ``whole [B, S / s]`` bool: window ``j`` lies inside the prompt)."""
    B, S = k_new.shape[:2]
    s = sp.kernel_stride
    assert S % s == 0, (S, s)
    sums = k_new.astype(jnp.float32).reshape(B, S // s, s, -1).sum(2)
    nxt = jnp.concatenate([sums[:, 1:], jnp.zeros_like(sums[:, :1])], 1)
    rows = ((sums + nxt) / sp.kernel_size).astype(k_new.dtype)
    j = jnp.arange(S // s)[None, :]
    return rows, s * j + sp.kernel_size <= lengths[:, None]


def scatter_compressed(ck, tables, rows, whole, windows=None):
    """Write the windows ``rows [B, J, W']`` that are ``whole [B, J]`` into
    the leaf ``ck [num_blocks, m, 1, W]`` under ``tables [B, nb]``: window
    ``j`` in row ``j % m`` of page ``tables[b, j // m]`` (a window that is
    not whole, or whose page is the sentinel, is dropped). ``windows [B, J]``:
    which window each row is; None: ``0 .. J - 1``, a prompt's."""
    NB, m, _, W = ck.shape
    B, J = rows.shape[:2]
    j = jnp.broadcast_to(jnp.arange(J), (B, J)) if windows is None \
        else windows
    ids = jnp.take_along_axis(
        tables, jnp.minimum(j // m, tables.shape[1] - 1), axis=1)
    ids = jnp.where(whole, ids, NB)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, W - rows.shape[-1])))
    return ck.at[ids.reshape(-1), (j % m).reshape(-1), 0].set(
        rows.reshape(B * J, W).astype(ck.dtype), mode="drop",
        unique_indices=False)


@jax.named_scope("sparse/compress")
def decode_compressed(ck, k_pages, tables, pos, live, sp: SparseConfig):
    """The decode tick's share: where the key just written at ``pos [B]``
    closes window ``j = (pos + 1) / s - 2``, the mean of its ``2 s`` keys
    (read back from the row's pages) goes into ``ck``. Returns (the leaf,
    ``closed [B]`` bool)."""
    bs = k_pages.shape[1]
    s = sp.kernel_stride
    j = (pos + 1) // s - 2
    closed = live & ((pos + 1) % s == 0) & (j >= 0)
    j = jnp.maximum(j, 0)
    # the window's keys lie in the page of its first key and the next one
    first = (s * j) // bs
    at = jnp.minimum(first[:, None] + jnp.arange(2)[None, :],
                     tables.shape[1] - 1)
    ids = jnp.take_along_axis(tables, at, axis=1)               # [B, 2]
    got = jnp.take(k_pages, ids, axis=0, mode="fill", fill_value=0)
    got = got.astype(jnp.float32).reshape(got.shape[0], 2 * bs, -1)
    t = (first * bs)[:, None] + jnp.arange(2 * bs)[None, :]     # positions
    inside = (t >= (s * j)[:, None]) & (t < (s * j)[:, None] + sp.kernel_size)
    rows = jnp.where(inside[..., None], got, 0.0).sum(1) / sp.kernel_size
    return scatter_compressed(ck, tables, rows[:, None], closed[:, None],
                              j[:, None]), closed


# ------------------------------------------------------------- selection
def kept_blocks(q, ck, p, sp: SparseConfig, n_blocks: int):
    """The blocks each query attends, a kv head, as a mask. ``q [Q, KV, rep,
    hd]``; ``ck [J, KV, hd]`` the row's compressed keys in order (rows that
    are not visible may hold anything); ``p [Q]`` the queries' positions.
    Returns bool ``[Q, KV, n_blocks]``: at most ``topk`` blocks, none past
    the query's own.

    The best ``topk`` are taken by rank, not by a sort (on the chip
    ``lax.top_k`` of 320 scores is a whole sort, 0.26 ms a layer a tick:
    PERF.md section 6): a block's rank is how many blocks score higher, or
    the same at a lower index."""
    f32 = jnp.float32
    J, r = ck.shape[0], sp.windows_per_block
    scores = jnp.einsum("qgrd,jgd->qgrj", q.astype(f32), ck.astype(f32),
                        precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(q.shape[-1])
    seen = visible(p[:, None], jnp.arange(J)[None, :], sp)       # [Q, J]
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    top = jnp.max(scores, -1, keepdims=True)
    e = jnp.where(seen[:, None, None, :],
                  jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    a = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    A = jnp.where(seen[:, None, :], a.sum(2), -1.0)              # [Q, KV, J]
    A = jnp.pad(A, ((0, 0), (0, 0), (0, max(r * n_blocks - J, 0))),
                constant_values=-1.0)[..., :r * n_blocks]
    A = A.reshape(A.shape[:2] + (n_blocks, r))
    before = jnp.concatenate([jnp.full_like(A[..., :1, -1], -1.0),
                              A[..., :-1, -1]], -1)   # window r b - 1
    score = jnp.maximum(A.max(-1), before)                       # [Q, KV, nb]
    b = jnp.arange(n_blocks)
    own = (p // sp.block_size)[:, None]
    forced = (b[None, :] < sp.init_blocks) \
        | (b[None, :] > own - sp.local_blocks)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    reach = (b[None, :] <= own)[:, None, :]
    score = jnp.where(reach, score, -2.0)
    mine, other = score[..., :, None], score[..., None, :]
    rank = ((other > mine) | ((other == mine) & (b[None, :] < b[:, None]))
            ).sum(-1)
    return (rank < sp.topk) & reach


def selected_blocks(q, ck, p, sp: SparseConfig, n_blocks: int):
    """:func:`kept_blocks` as a compacted list: (``idx [Q, KV, K]`` int32,
    ``K = min(topk, n_blocks)``: the kept blocks ascending, ``n_blocks`` in
    the places of a query with fewer than ``K`` blocks at or before its own;
    ``count [Q, KV]``)."""
    kept = kept_blocks(q, ck, p, sp, n_blocks)
    K = min(sp.topk, n_blocks)
    place = jnp.cumsum(kept, -1) - 1                 # a kept block's place
    hit = kept[..., None, :] & (place[..., None, :]
                                == jnp.arange(K)[:, None])   # [., K, nb]
    count = kept.sum(-1).astype(jnp.int32)
    idx = jnp.where(jnp.arange(K) < count[..., None],
                    (hit * jnp.arange(n_blocks)).sum(-1), n_blocks)
    return idx.astype(jnp.int32), count


def gather_compressed(ck, tables):
    """``ck [num_blocks, m, 1, W]``, ``tables [B, nb]`` -> the rows' compressed
    keys in order ``[B, nb * m, W]`` (sentinel pages read as zeros: never
    visible)."""
    got = jnp.take(ck[:, :, 0], tables, axis=0, mode="fill", fill_value=0)
    return got.reshape(tables.shape[0], -1, ck.shape[-1])


@jax.named_scope("sparse/select")
def decode_page_lists(q, ck, tables, pos, live, sp: SparseConfig,
                      kv_heads: int, page: int):
    """The compacted list of every (row, kv head) of a decode tick, by
    BLOCKS: a block's ``r = block_size / page`` pages are an aligned run of
    the pool (``serve/kv_cache``'s runs, which the engine mints for this
    family), so a block is named by its run, ``first page // r``, and a list
    is a quarter as long as its pages at ``r = 4``. ``q [B, H, hd]`` at
    positions ``pos [B]`` (keys and compressed keys already written);
    ``tables [B, nb]`` over pages of ``page`` positions. Returns (``lists [B,
    KV, Wd]`` run ids, ``num_blocks // r`` past a list's end; ``lengths [B,
    KV]`` the positions each list holds, 0 on a dead row; ``sparse [B]``
    bool: the row is past ``dense_len``)."""
    NB = ck.shape[0]
    B, H, hd = q.shape
    nb = tables.shape[1]
    if sp.block_size % page:
        raise ValueError(
            f"selection by blocks of {sp.block_size} needs pages that divide "
            f"them, got block_size {page}")
    r = sp.block_size // page                      # pages a block
    n_blocks = nb // r
    rows = gather_compressed(ck, tables)[..., :kv_heads * hd]
    idx, count = jax.vmap(
        lambda qb, cb, pb: selected_blocks(
            qb.reshape(1, kv_heads, H // kv_heads, hd),
            cb.reshape(-1, kv_heads, hd), pb[None], sp, n_blocks)
    )(q, rows, pos)
    idx, count = idx[:, 0], count[:, 0]                  # [B, KV, K], [B, KV]
    K = idx.shape[-1]
    heads = tables[:, ::r]     # each block's first page: [B, ceil(nb / r)]
    width = min(heads.shape[1], max(K, sp.dense_len // sp.block_size))
    picked = jnp.take_along_axis(
        jnp.broadcast_to(heads[:, None], (B, kv_heads, heads.shape[1])),
        jnp.minimum(idx, heads.shape[1] - 1), axis=2)
    picked = jnp.where(idx < n_blocks, picked, NB)
    picked = jnp.pad(picked, ((0, 0), (0, 0), (0, width - K)),
                     constant_values=NB)
    sparse = pos + 1 > sp.dense_len
    # the last kept block is the query's own (the local window forces it)
    held = (count - 1) * sp.block_size + (pos % sp.block_size)[:, None] + 1
    lists = jnp.where(sparse[:, None, None], picked,
                      heads[:, None, :width]) // r
    lengths = jnp.where(sparse[:, None], held, (pos + 1)[:, None])
    return lists, jnp.where(live[:, None], lengths, 0), sparse


def by_runs(pages, r: int):
    """A pool leaf ``[num_blocks, page, 1, W]`` as runs of ``r`` pages,
    ``[num_blocks // r, r * page, 1, W]``: row-major, so a bitcast (pages
    past the last whole run, which the allocator never hands out, are cut
    off: a copy, and only where the pool is not whole runs)."""
    NB, page = pages.shape[:2]
    if NB % r:
        pages = pages[:NB // r * r]
    return pages.reshape((NB // r, r * page) + pages.shape[2:])


def sparse_decode_attention(q, k_pages, v_pages, lists, lengths,
                            kv_heads: int, run_pages: int = 1):
    """One query token a row over each (row, kv head)'s own list. ``q [B,
    H, hd]``; ``lists [B, KV, Wd]`` naming runs of ``run_pages`` pages
    (:func:`decode_page_lists`); ``lengths [B, KV]``. Returns ``[B, H, hd]``
    in q's dtype. Each list is walked with every head of the row (the page
    rows hold both kv heads' lanes) and its own kv head's ``H / KV`` heads
    are kept. The walk is ``paged_decode_attention``'s over the pool viewed
    :func:`by_runs`: a run is its page, one copy of the kernel's."""
    B, H, hd = q.shape
    KV = kv_heads
    q2 = jnp.broadcast_to(q[:, None], (B, KV, H, hd)).reshape(B * KV, H, 1, hd)
    out = paged_decode_attention(
        q2, by_runs(k_pages, run_pages), by_runs(v_pages, run_pages),
        lists.reshape(B * KV, -1), lengths.reshape(-1) - 1, kv_heads=KV)
    out = out[:, :, 0].reshape(B, KV, KV, H // KV, hd)
    own = jnp.arange(KV)
    return out[:, own, own].reshape(B, H, hd)


# ---------------------------------------------------------------- prefill
def _masked_tiles(q, k, v, ck, sp: SparseConfig, first: int):
    """Queries at positions ``first .. S - 1`` of a prefill from position 0,
    each over the blocks it selects. q [B, H, S, hd]; k, v [B, KV, S, hd];
    ck [B, J, KV * hd] the prompt's compressed keys (a window that runs past
    the prompt holds padding and is visible to no query inside the prompt).
    Returns [B, H, S - first, hd]."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    J, n_blocks = ck.shape[1], -(-S // sp.block_size)
    n_q = S - first
    tile = 256
    while tile > 8 and B * H * tile * S * 4 > SCORE_BYTES:
        tile //= 2
    tile = min(tile, n_q)
    pad = -n_q % tile          # queries past the bucket's end: cut off below
    scale = 1.0 / math.sqrt(hd)
    ckr = ck.reshape(B, J, KV, hd)
    qg = jnp.pad(q[:, :, first:], ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = qg.reshape(B, KV, rep, (n_q + pad) // tile, tile, hd)

    def one(args):
        qc, c0 = args                                  # [B,KV,rep,t,hd], []
        p = c0 + jnp.arange(tile)
        with jax.named_scope("sparse/select"):
            kept = jax.vmap(lambda qb, cb: kept_blocks(
                qb.transpose(2, 0, 1, 3), cb, p, sp, n_blocks))(qc, ckr)
        seen = jnp.repeat(kept, sp.block_size, axis=-1)[..., :S] \
            & (jnp.arange(S)[None, :] <= p[:, None])[None, :, None, :]
        scores = jnp.einsum("bgrsd,bgtd->bgrst", qc, k,
                            preferred_element_type=jnp.float32) * scale
        seen = seen.transpose(0, 2, 1, 3)[:, :, None]   # [B, KV, 1, t, S]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30),
                               axis=-1).astype(q.dtype)
        return jnp.einsum("bgrst,bgtd->bgrsd", probs, v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    n = (n_q + pad) // tile
    starts = first + jnp.arange(n) * tile
    if n == 1:
        out = one((qg[:, :, :, 0], starts[0]))[:, :, :, None]
    else:
        out = jnp.moveaxis(jax.lax.map(one, (jnp.moveaxis(qg, 3, 0), starts)),
                           0, 3)
    return out.reshape(B, H, n_q + pad, hd)[:, :, :n_q]


def sparse_prefill_attention(q, k, v, ck, sp: SparseConfig):
    """Causal self-attention of S fresh tokens at positions ``0 .. S - 1``
    under the layer's rule (the module note). q [B, H, S, hd]; k, v [B, KV,
    S, hd]; ``ck`` the rows of :func:`prefill_compressed`. Returns [B, H, S,
    hd] in q's dtype."""
    S, D = q.shape[2], sp.dense_len
    if S <= D:
        with jax.named_scope("dense_attn"):
            return banded_causal_attention(q, k, v)
    with jax.named_scope("dense_attn"):
        dense = banded_causal_attention(q[:, :, :D], k[:, :, :D], v[:, :, :D])
    with jax.named_scope("sparse_attn"):
        rest = _masked_tiles(q, k, v, ck, sp, D)
    return jnp.concatenate([dense, rest], 2)
