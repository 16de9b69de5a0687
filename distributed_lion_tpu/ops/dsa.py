"""Learned sparse attention over a latent cache (the DeepSeek-V3.2 "DSA"
indexer), in ``jax.numpy`` for every platform; the chip's kernels are in
``ops/pallas_dsa``.

A layer with an indexer scores every cached position against the query with
a few small heads of its own and attends only the ``topk`` best:

    I[t, s] = sum_j w[t, j] * ReLU(qI[t, j] . kI[s])        (float32, s <= t)
    K_t     = every s <= t                  where t + 1 <= topk
            = the topk positions of largest I[t, s], ties to the lower s

``kI`` is one row of ``index_head_dim`` values a token: **the index-key cache
leaf**, a page leaf of its own beside the latent rows, under the same block
tables (``serve/kv_cache.init_page_leaves``). The attention proper then runs
over ``K_t`` alone.

**The selection is exact** (:func:`kept_positions`): not a sort (``lax.top_k``
of 12,000 scores is a whole sort a row) but a threshold found by counting.
The float32 scores map to integers of the same order (:func:`sort_key`); 32
counting passes find the ``topk``-th largest key bit by bit, and where
several positions tie AT the threshold, ``ceil(log2 T)`` more passes find the
position up to which the ties are kept, lowest first. What comes out is the
set a stable sort would give, position for position.

Where the keys are attended:

- the decode tick (one query a row) scores the row's index keys
  (:func:`decode_index_scores`: the kernel ``dsa_index`` over the index-key
  pages in place on a TPU, a gather elsewhere), takes the mask, and walks the
  row's latent pages under it in the absorbed form
  (:func:`kept_decode_attention`, kernel ``dsa_attn``: a masked walk reads
  every visible row and attends the kept ones; at 12-25% kept, spread evenly
  over pages of 16, nearly every page holds a kept row, so a walk that skipped
  pages would skip 3% of them, and a row-at-a-time gather of 2,048 rows a
  query costs more than the walk: PERF.md section 6, PR 46);
- a prefill from position 0 (:func:`dsa_prefill_attention`) attends its own
  fresh keys in the expanded form: a chunk of 128 queries at a time it takes
  the chunk's index scores, its thresholds and the mask ``I[t, s] >= tau_t``;
  on a TPU the masks of the whole prompt (int8) then go to the tiled kernel
  ``dsa_prefill`` (``ops/pallas_dsa``), which skips the tiles above the
  diagonal and keeps scores and softmax in VMEM; elsewhere the same chunk
  attends in plain XLA, a group of heads at a time so that no ``[heads,
  chunk, keys]`` float32 scores pass ``ops/attention.SCORE_BYTES``.

Counters (``DSA_COUNTERS``): rows that selected, the keys they could see and
the keys they kept, counted from the masks the attention is handed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_lion_tpu.ops.attention import (
    SCORE_BYTES,
    paged_gather_kv,
    paged_kernel_applies,
    walk_lengths,
)

DSA_COUNTERS = ("dsa_rows", "dsa_keys_visible", "dsa_keys_kept")


def index_scores(q, w, k):
    """``I = sum_j w_j ReLU(q_j . k)``: q ``[B, S, Hi, di]`` (roped), w ``[B,
    S, Hi]`` float32, k ``[B, T, di]`` (roped) -> ``[B, S, T]`` float32. The
    products accumulate in float32; the heads are taken a group at a time
    where all of them at once would hold more than ``SCORE_BYTES`` of
    per-head scores."""
    B, S, Hi, _ = q.shape
    T = k.shape[1]
    g = Hi
    while g > 1 and B * S * g * T * 4 > SCORE_BYTES:
        g //= 2
    w = w.astype(jnp.float32)

    def part(qg, wg):                        # [B, S, g, di], [B, S, g]
        s = jnp.einsum("bsjd,btd->bsjt", qg, k,
                       preferred_element_type=jnp.float32)
        return (jnp.maximum(s, 0.0) * wg[..., None]).sum(2)

    if g == Hi or Hi % g:
        return part(q, w)
    n = Hi // g
    qs = jnp.moveaxis(q.reshape(B, S, n, g, -1), 2, 0)
    ws = jnp.moveaxis(w.reshape(B, S, n, g), 2, 0)
    acc, _ = jax.lax.scan(lambda a, x: (a + part(*x), None),
                          jnp.zeros((B, S, T), jnp.float32), (qs, ws))
    return acc


def sort_key(scores):
    """float32 -> int32 of the same order (``a < b`` exactly where the keys
    are; -0.0 counts as 0.0, as a comparison of floats has it)."""
    scores = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kept_positions(scores, visible, topk: int):
    """The positions each query keeps: ``scores [..., T]`` float32, ``visible
    [..., T]`` bool (``s <= t``, inside the row) -> bool ``[..., T]``: every
    visible position where at most ``topk`` are visible, else exactly the
    ``topk`` of largest score, ties to the lower position. The module note
    says how: no sort."""
    T = scores.shape[-1]
    sign = jnp.uint32(1 << 31)
    # unsigned keys of the same order; a position that is not visible is 0,
    # below every real score's key
    u = jax.lax.bitcast_convert_type(sort_key(scores), jnp.uint32) ^ sign
    u = jnp.where(visible, u, jnp.uint32(0))

    def value_bit(i, t):
        cand = t | (sign >> i.astype(jnp.uint32))
        enough = (u >= cand[..., None]).sum(-1) >= topk
        return jnp.where(enough, cand, t)

    # the largest t with at least topk keys >= t: the topk-th largest key
    tau = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros(u.shape[:-1], jnp.uint32))
    above, tied = u > tau[..., None], u == tau[..., None]
    need = topk - above.sum(-1)              # ties to keep, lowest first
    pos = jnp.arange(T, dtype=jnp.int32)
    bits = max(T - 1, 1).bit_length()

    def position_bit(i, lo):
        cand = lo | (jnp.int32(1) << (bits - 1 - i))
        few = (tied & (pos < cand[..., None])).sum(-1) < need
        return jnp.where(few, cand, lo)

    # the largest position with fewer than ``need`` ties before it: the
    # ``need``-th tie itself
    last = jax.lax.fori_loop(0, bits, position_bit,
                             jnp.zeros(u.shape[:-1], jnp.int32))
    return visible & (above | (tied & (pos <= last[..., None])))


def run_pages(page_run: int, block_size: int, num_blocks: int,
              table_width: int) -> int:
    """Pages one copy of a decode walk brings: the engine mints this
    family's pages in aligned runs of ``page_run`` positions
    (``ServeModel.page_run``), so a run is one contiguous slab of every leaf.
    1 where the page does not divide the run or the pool is not whole runs
    (the engine's own rule: a run is then a page)."""
    r, rest = divmod(page_run, block_size)
    if rest or r < 1 or num_blocks % r or table_width % r:
        return 1
    return r


def by_runs(pages, tables, r: int):
    """A pool leaf and tables of page ids as runs of ``r`` pages
    (``ops/sparse_select.by_runs``: a bitcast) and the runs' ids (a run's
    head is a multiple of ``r``; the sentinel stays one past the pool)."""
    from distributed_lion_tpu.ops.sparse_select import by_runs as leaf_by_runs

    if r == 1:
        return pages, tables
    return leaf_by_runs(pages, r), tables[:, ::r] // r


@jax.named_scope("dsa/index")
def decode_index_scores(q, w, ik_pages, tables, pos, *, page_run: int = 0):
    """One query a row against the row's cached index keys: q ``[B, Hi,
    di]``, w ``[B, Hi]`` float32, ``ik_pages [num_blocks, block_size, 1,
    W]`` (``W >= di``, pad lanes zero), the new token's key already
    scattered. Returns ``[B, T]`` float32, ``T = table width x block_size``;
    entries past the row's own position are undefined (the caller's mask
    drops them). On a TPU the kernel ``dsa_index`` over the pages in place;
    elsewhere the rows gathered and :func:`index_scores`."""
    NB, bs, _, W = ik_pages.shape
    if paged_kernel_applies(1, ik_pages.shape, ik_pages.dtype):
        from distributed_lion_tpu.ops.pallas_dsa import dsa_index

        r = run_pages(page_run, bs, NB, tables.shape[1])
        runs, ids = by_runs(ik_pages, tables, r)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[-1])))
        return dsa_index(q, w, runs, ids, walk_lengths(tables, pos, NB, bs))
    rows = paged_gather_kv(ik_pages, tables)[:, :, 0, :q.shape[-1]]
    return index_scores(q[:, None], w[:, None], rows)[:, 0]


@jax.named_scope("dsa/attn")
def kept_decode_attention(q_abs, kv_pages, tables, pos, keep, *,
                          scale: float, page_run: int = 0):
    """Absorbed latent decode over the kept set, one query a row, over the
    pool in place (a caller asks ``paged_kernel_applies`` first): q_abs ``[B,
    H, W]`` as ``ops/attention.mla_decode_attention`` takes it; ``keep [B,
    T]`` bool, the positions row b attends (:func:`kept_positions`). Returns
    ``softmax(scale * q . row) @ row`` over those rows ``[B, H, W]``. The
    kernel ``dsa_attn`` walks every page the row holds and masks."""
    from distributed_lion_tpu.ops.pallas_mla_attn import mla_paged_attn

    NB, bs = kv_pages.shape[:2]
    r = run_pages(page_run, bs, NB, tables.shape[1])
    runs, ids = by_runs(kv_pages, tables, r)
    return mla_paged_attn(q_abs, runs, ids, walk_lengths(tables, pos, NB, bs),
                          keep=keep, scale=scale, name="dsa_attn")


# queries a step of the prefill's walk, and queries a block: a block's keys
# are the prompt up to its own end (a static prefix), so the walk does no
# work above the diagonal beyond a block's own triangle
PREFILL_CHUNK = 128
PREFILL_BLOCK = 1024


def _chunk_keep(qic, wic, ki, c0, lengths, topk: int):
    """A chunk of queries at positions ``c0 ..`` against the keys ``ki [B,
    Tk, di]``: (keep ``[B, c, Tk]`` bool, the chunk's (visible, kept) counts
    over the rows' real queries)."""
    B, c = qic.shape[:2]
    Tk = ki.shape[1]
    p = c0 + jnp.arange(c)
    visible = jnp.broadcast_to(jnp.arange(Tk)[None, :] <= p[:, None],
                               (B, c, Tk))
    with jax.named_scope("dsa/index"):
        scores = index_scores(qic, wic, ki)
    with jax.named_scope("dsa/select"):
        keep = kept_positions(scores, visible, topk)
    real = (p[None, :] < lengths[:, None])[..., None]
    tally = jnp.stack([(visible & real).sum(), (keep & real).sum()])
    return keep, tally.astype(jnp.int32)


def _chunks(x, n: int, chunk: int, axis: int):
    """``x`` with ``axis`` split into ``n`` chunks, the chunks leading."""
    shape = x.shape[:axis] + (n, chunk) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def dsa_prefill_attention(q, k, v, qi, wi, ki, lengths, *, topk: int,
                          scale: float):
    """Causal self-attention of S fresh tokens at positions ``0 .. S - 1``
    under the indexer's selection, in the expanded form. q, k ``[B, H, S,
    dk]``; v ``[B, H, S, dv]``; qi ``[B, S, Hi, di]``, wi ``[B, S, Hi]``, ki
    ``[B, S, di]`` the indexer's roped queries, head weights and keys;
    ``lengths [B]`` the real tokens of each row (counted; the rest computed
    and the caller's to discard). Returns (out ``[B, H, S, dv]`` in q's
    dtype, the counters of ``DSA_COUNTERS`` over the real queries).

    Two paths, chosen from what the call shows. On a TPU, where
    ``pallas_dsa.prefill_takes`` (whole tiles of 1,024 keys, values of whole
    lane tiles): the masks of all S queries first, a chunk of them at a time,
    as int8 ``[S, S]`` (151 MB at 12,288), then the tiled kernel
    ``dsa_prefill`` a row, which keeps scores and softmax in VMEM. Every
    other call (the CPU, a short bucket) walks a chunk of queries at a time
    in plain XLA over the keys up to its block's end, a group of heads at a
    time so that no ``[heads, chunk, keys]`` float32 scores pass
    ``SCORE_BYTES``; on the chip that walk wrote and read those scores five
    times a step (PERF.md section 6, PR 46)."""
    from distributed_lion_tpu.ops import pallas_dsa

    B, H, S, _ = q.shape
    chunk = min(PREFILL_CHUNK, S)
    block = min(PREFILL_BLOCK, S)
    assert S % block == 0 and block % chunk == 0, (S, block, chunk)

    def counters(counts):
        return {"dsa_rows": lengths.sum().astype(jnp.int32),
                "dsa_keys_visible": counts[0], "dsa_keys_kept": counts[1]}

    if jax.default_backend() == "tpu" and \
            pallas_dsa.prefill_takes(S, v.shape[-1]):
        n = S // chunk
        keep, tally = jax.lax.map(
            lambda a: _chunk_keep(a[0], a[1], ki, a[2], lengths, topk),
            (_chunks(qi, n, chunk, 1), _chunks(wi, n, chunk, 1),
             jnp.arange(n) * chunk))
        keep = jnp.moveaxis(keep.astype(jnp.int8), 0, 1).reshape(B, S, S)
        with jax.named_scope("dsa/attn"):
            out = jnp.stack([pallas_dsa.dsa_prefill(
                q[b], k[b], v[b], keep[b], scale=scale) for b in range(B)])
        return out, counters(tally.sum(0))
    # heads a step: their float32 scores over the longest prefix stay
    # within SCORE_BYTES (8 of 128 heads at 128 queries over 12,288 keys)
    g = H
    while g > 1 and B * g * chunk * S * 4 > SCORE_BYTES:
        g //= 2

    def grouped(x):                          # [B, H, ...] -> [H / g, B, g, ...]
        return jnp.moveaxis(x.reshape((B, H // g, g) + x.shape[2:]), 1, 0)

    kg, vg = grouped(k), grouped(v)
    n = block // chunk
    outs = []
    counts = jnp.zeros((2,), jnp.int32)
    for b0 in range(0, S, block):
        Tk = b0 + block                      # the keys this block can see
        kb, vb, kib = kg[:, :, :, :Tk], vg[:, :, :, :Tk], ki[:, :Tk]

        def one(args, kb=kb, vb=vb, kib=kib):
            qc, qic, wic, c0 = args    # [H/g,B,g,c,dk] [B,c,Hi,di] [B,c,Hi]
            keep, tally = _chunk_keep(qic, wic, kib, c0, lengths, topk)

            def heads(args):
                qh, kh, vh = args            # [B, g, c, dk], [B, g, Tk, .]
                s = jnp.einsum("bhsd,bhtd->bhst", qh, kh,
                               preferred_element_type=jnp.float32) * scale
                pr = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30),
                                    axis=-1).astype(q.dtype)
                return jnp.einsum("bhst,bhtd->bhsd", pr, vh,
                                  preferred_element_type=jnp.float32
                                  ).astype(q.dtype)

            with jax.named_scope("dsa/attn"):
                o = heads((qc[0], kb[0], vb[0]))[None] if g == H \
                    else jax.lax.map(heads, (qc, kb, vb))
            return o, tally                      # [H / g, B, g, c, dv]

        qb = grouped(q[:, :, b0:Tk])             # [H / g, B, g, block, dk]
        qib, wib = qi[:, b0:Tk], wi[:, b0:Tk]
        starts = b0 + jnp.arange(n) * chunk
        if n == 1:
            o, tally = one((qb, qib, wib, starts[0]))
        else:
            o, tally = jax.lax.map(one, (
                _chunks(qb, n, chunk, 3), _chunks(qib, n, chunk, 1),
                _chunks(wib, n, chunk, 1), starts))
            # [n, H / g, B, g, c, dv] -> [H / g, B, g, block, dv]
            o = jnp.moveaxis(o, 0, 3).reshape(qb.shape[:3] + (block, -1))
            tally = tally.sum(0)
        outs.append(jnp.moveaxis(o, 0, 1).reshape(B, H, block, -1))
        counts = counts + tally
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 2)
    return out, counters(counts)
