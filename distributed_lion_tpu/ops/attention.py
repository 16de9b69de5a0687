"""Attention implementations with a single dispatch point.

Two entries, one for each layout a caller holds:

- :func:`attention_qkv` — token-major: ``qkv [B, T, 3 * D]``, a token's q,
  k and v rows side by side as GPT-2's fused projection writes them;
  returns ``[B, T, D]`` as the output projection reads it.
- :func:`attention` — head-major: q, k, v ``[B, H, T, head_dim]``; returns
  the same. A family whose q and k are built separately (RoPE, fewer kv
  heads: ``models/llama``) calls this one.

``impl`` takes two names. ``xla`` is the materialized-scores reference
(einsum → masked f32 softmax → einsum) that every kernel is tested against.
``auto`` resolves from what the call shows, with two outcomes a platform:

- :func:`attention_qkv` on a TPU where :func:`qkv_kernel_applies` (a shape
  the kernel takes as it lies, ``pallas_flash_attn.kernel_takes``:
  head_dim 64 or 128, whole 128-lane blocks, T a multiple of 128 up to
  8,192; and T >= 1024) → the repo's training kernel
  (``ops/pallas_flash_attn``), reading ``qkv`` in place: token-major
  operands, one float32 a row as residual, a fused backward. Measured on
  the chip at the training cells' shape (T = 1024, head_dim 64: PERF.md,
  PR 27); ``chip_smoke.py`` checks another against ``xla``. Every other
  :func:`attention_qkv` call splits ``qkv`` head-major for
  :func:`attention`.
- :func:`attention` on a TPU and T >= 2048 → jax's bundled flash kernel
  (``pallas.ops.tpu.flash_attention``) at its own default tiles: the one
  path that runs ``models/llama`` at long context on the chip without
  ``[B, H, T, T]`` float32 scores. Everywhere else :func:`attention_xla`.
- off a TPU both entries always end in :func:`attention_xla` (Pallas
  kernels are TPU-only).

What ``auto`` resolved to is recorded once a shape at trace time
(:func:`_note_resolved`): an ``attn_resolved`` event in the run journal and
a line the trainer prints after its first dispatch.

Causal only (decoder framework).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_xla(q, k, v, *, causal: bool = True):
    """Materialized-scores attention: the reference every kernel is tested
    against. Scores and softmax are float32."""
    T = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v, preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attention_flash(q, k, v, *, causal: bool = True):
    """jax's bundled Pallas TPU flash attention at its default tiles."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention,
    )

    return flash_attention(
        q, k, v, causal=causal, sm_scale=1.0 / math.sqrt(q.shape[-1]),
    ).astype(q.dtype)


# ------------------------------------------------------------- paged decode
# The serving engine's KV layout (serve/kv_cache.py, vLLM's PagedAttention
# design): each layer's cache is a fixed pool of pages; a sequence owns an
# ordered list of page indices (its block table). Allocation/free is
# HOST-side table math — the device functions below are pure static-shape
# gathers/scatters, so the decode tick stays one jitted program no matter
# how sequences come and go. The sentinel block index == num_blocks (one
# past the pool) makes unused table entries inert: scatters drop
# out-of-range writes, gathers fill 0, the kernel never walks that far.
#
# A pool leaf is [num_blocks, block_size, G, W]: a page row holds G groups
# of kv heads, each group's KV/G heads side by side in W >= KV/G * hd lanes
# (zero-padded). The engine builds G = 1 a tensor shard and W a multiple of
# 128 (serve/kv_cache.init_pages), the one shape whose resident layout on
# the chip is a page = one contiguous [block_size, W] slab: the scatter
# writes it, the gather and the kernel read it and the donated output keeps
# it with no copy. [num_blocks, block_size, KV, hd] is the same thing with
# one head a group (tests and tools build it; on the chip XLA keeps such a
# leaf with num_blocks minor-most and every dispatch re-lays it out).
#
# Which calls take which path is decided from what the call shows:
#
# - one query token a row, no ``start``, a TPU backend, a pool the kernel
#   takes as it lies (:func:`paged_kernel_applies`): the Mosaic kernel
#   ``paged_attn`` (ops/pallas_paged_attn) over the pool in place: the
#   decode tick.
# - S > 1 whose first token is at position 0 (the dispatch says so: the
#   engine's ``fresh``), a TPU backend, S whole blocks of 128 rows up to
#   8,192, heads of 128 or ungrouped heads of 64
#   (:func:`fresh_kernel_applies`): the keys a prefill attends to are the
#   ones it has just projected, so it attends over q, k, v token-major
#   through the tiled forward kernel ``flash_gqa_fwd``
#   (:func:`fresh_causal_attention`), and the pool is only written, a page
#   at a time (:func:`paged_scatter_fresh`). GPT-2's
#   and Llama's prefills; the families with their own hooks take
#   :func:`banded_causal_attention` or :func:`chunked_causal_attention`.
# - every other call (a prefill behind a shared prefix, the speculative
#   verify window and the drafter's mirror, whose queries see pages they did
#   not write; left-padded batches; a bucket off a multiple of 128; the
#   CPU): the gather path below, which is the reference both kernels are
#   tested against and stays bit-identical to the dense cache.


@jax.named_scope("paged_scatter")
def paged_scatter_kv(pages: jnp.ndarray, tables: jnp.ndarray,
                     pos: jnp.ndarray, new: jnp.ndarray,
                     valid=None) -> jnp.ndarray:
    """Write per-row new k (or v) rows into their block-table pages.

    pages  [num_blocks, block_size, G, W] — one layer's pool (k or v; the
    layout note above); tables [B, blocks_per_seq] int32 page ids
    (sentinel = num_blocks);
    pos    [B] int32 — absolute position of each row's FIRST new token;
    new    [B, S, KV, hd] — the S new tokens' projections per row;
    valid  optional [B, S] bool — False entries are dropped (right-padded
    prefill tails must not write garbage pages).

    Token s of row b lands in page ``tables[b, (pos[b]+s)//block_size]`` at
    offset ``(pos[b]+s) % block_size``. Rows whose table entry is the
    sentinel (never allocated — e.g. an inactive decode slot) scatter out
    of range and are dropped by XLA's scatter mode, not branched on.

    A multi-token window commit ([B, S] with S > 1 — the bucketed prefill
    and the speculative verify window, serve/speculate.py) is bit-identical
    to S sequential single-token scatters: the writes land in the same
    (page, offset) cells with the same values, and masked/sentinel writes
    drop identically (pinned by tests/test_serve.py). Per-row VALID COUNTS
    ride ``valid`` as ``arange(S) < counts[:, None]`` — the rejected/padded
    tail never touches a page.
    """
    B, S = new.shape[:2]
    bs = pages.shape[1]
    abs_pos = pos[:, None] + jnp.arange(S, dtype=pos.dtype)[None, :]  # [B,S]
    blk = jnp.take_along_axis(tables, abs_pos // bs, axis=1,
                              mode="clip")  # sentinel rides the VALUE
    if valid is not None:
        # out-of-range page id ⇒ the scatter drops the write
        blk = jnp.where(valid, blk, pages.shape[0])
    off = abs_pos % bs
    G, W = pages.shape[2:]
    flat = new.reshape(B * S, G, -1)  # a group's kv heads side by side
    flat = jnp.pad(flat, ((0, 0), (0, 0), (0, W - flat.shape[-1])))
    return pages.at[blk.reshape(-1), off.reshape(-1)].set(
        flat, mode="drop", unique_indices=False)


def paged_copy_pages(pages: list, src: jnp.ndarray,
                     dst: jnp.ndarray) -> list:
    """Copy whole pages inside each layer's pool — the device half of
    copy-on-write prefix sharing (serve/kv_cache.BlockTables.cow).

    pages — the engine's per-layer pool list (``{"k", "v"}`` a layer, or
    the one latent leaf ``{"kv"}``: every leaf is copied alike);
    src/dst [C] int32 — page-id pairs to copy this dispatch, padded with
    the sentinel (== num_blocks): a sentinel ``dst`` drops the write and a
    sentinel ``src`` gathers zeros (never kept — its dst is sentinel too),
    so one fixed-width jitted program serves any number of copies ≤ C
    without recompiling. The copy is bytewise (no arithmetic): a CoW'd
    page attends bit-identically to the shared original, which is what
    keeps shared-prefix decode pinned to the unshared engine. Under
    tensor parallelism the pool's kv-head axis is sharded and the copy is
    shard-local — page ids are replicated host math."""
    out = []
    for layer in pages:
        out.append({
            name: layer[name].at[dst].set(
                jnp.take(layer[name], src, axis=0, mode="fill",
                         fill_value=0),
                mode="drop", unique_indices=False)
            for name in layer
        })
    return out


@jax.named_scope("paged_gather")
def paged_gather_kv(pages: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """[num_blocks, bs, G, W] pool + [B, nb] tables → [B, nb*bs, G, W]
    contiguous per-row history (sentinel pages read as zeros — they are
    masked out of attention by the caller's position bound anyway)."""
    B, nb = tables.shape
    bs = pages.shape[1]
    got = jnp.take(pages, tables, axis=0, mode="fill", fill_value=0)
    return got.reshape((B, nb * bs) + pages.shape[2:])


def walk_lengths(tables, pos, num_blocks: int, block_size: int):
    """Positions row b's decode walk covers: ``pos + 1``, bounded by the
    row's count of real table entries (an inactive slot's table is all
    sentinel: 0)."""
    return jnp.minimum(pos + 1,
                       jnp.sum(tables < num_blocks, axis=1) * block_size)


def paged_kernel_applies(n_new, pool_shape, pool_dtype, start=None) -> bool:
    """True when :func:`paged_decode_attention` takes the Mosaic kernel
    for a call with ``n_new`` query tokens a row over such a pool (the rule
    is in the layout note above). The serving engine asks the same
    question to count the ticks that ran it."""
    from distributed_lion_tpu.ops.pallas_paged_attn import kernel_takes

    return (n_new == 1 and start is None
            and jax.default_backend() == "tpu"
            and kernel_takes(pool_shape, pool_dtype))


@jax.named_scope("paged_attn")
def paged_decode_attention(q, k_pages, v_pages, tables, pos,
                           start=None, kv_heads=None):
    """Decode attention over a paged KV cache (new k/v already scattered).

    q [B, H, S, hd] — queries for the S newest tokens of each row (rope
    already applied by the model); k_pages/v_pages [num_blocks, bs, G, W];
    tables [B, nb]; pos [B] — absolute position of each row's first new
    token; ``start`` optional [B] — first VALID history slot (left-padded
    batches mask the pad prefix); ``kv_heads`` — kv heads in a page row
    (default: what the row's lanes hold, ``G * (W // hd)``; a caller whose
    pool pads a whole head's lanes, as 25 heads of 64 do, says so).
    Returns [B, H, S, hd] in q's dtype.

    The gather path reassembles each row's history into the SAME
    contiguous [B, T, KV, hd] layout the dense cache holds, then runs the
    identical masked-softmax einsum chain — so greedy decode through pages
    is bit-identical to the dense path whenever T matches (pinned by
    tests/test_serve.py). GQA kv heads are repeated at attend time, exactly
    like the dense caches store them un-repeated.

    S > 1 is the multi-token window (bucketed prefill; speculative verify,
    serve/speculate.py): query s attends causally INSIDE the window
    (``t_idx <= pos + s``), so a window whose first v entries are valid is
    safe without extra masking — a valid query s < v only ever sees
    history plus window tokens 0..s, all freshly scattered this dispatch;
    queries at invalid positions produce garbage rows the caller discards.

    The kernel path (:func:`paged_kernel_applies`) reads each row's own
    ``ceil((pos+1)/bs)`` pages where they lie, bounded besides by the
    row's count of real table entries: a row whose table is all sentinel
    (an inactive slot) reads nothing and returns zeros, which is what the
    gather path's zero-filled page gives it.
    """
    B, H, S, hd = q.shape
    NB, bs, G, W = k_pages.shape
    KV = kv_heads or G * (W // hd)
    if paged_kernel_applies(S, k_pages.shape, k_pages.dtype, start):
        from distributed_lion_tpu.ops.pallas_paged_attn import paged_attn

        return paged_attn(q[:, :, 0], k_pages, v_pages, tables,
                          walk_lengths(tables, pos, NB, bs),
                          kv_heads=KV)[:, :, None]

    def history(pages):  # [B, T, G, W] -> [B, KV, T, hd], pad lanes dropped
        got = paged_gather_kv(pages, tables)[..., :KV // G * hd]
        return got.reshape(B, -1, KV, hd).transpose(0, 2, 1, 3)

    k_full, v_full = history(k_pages), history(v_pages)
    if KV != H:
        rep = H // KV
        k_full = jnp.repeat(k_full, rep, axis=1)
        v_full = jnp.repeat(v_full, rep, axis=1)
    T = k_full.shape[2]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k_full,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    t_idx = jnp.arange(T)[None, None, :]
    valid = t_idx <= (pos[:, None] + jnp.arange(S)[None, :])[:, :, None]
    if start is not None:
        valid &= t_idx >= start[:, None, None]
    scores = jnp.where(valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v_full,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# --------------------------------------- a prefill over its own fresh keys
def fresh_kernel_applies(n_new: int, n_head: int, kv_heads: int,
                         head_dim: int, dtype) -> bool:
    """True when :func:`fresh_causal_attention` takes a window of ``n_new``
    tokens from position 0 (the rule is in the layout note above). The
    serving engine asks the same question, bucket by bucket, to decide
    which prefills it dispatches as fresh and to count them."""
    from distributed_lion_tpu.ops.pallas_flash_attn import gqa_kernel_takes

    return (n_new > 1 and jax.default_backend() == "tpu"
            and n_head % kv_heads == 0
            and gqa_kernel_takes(n_new, head_dim, dtype, n_head // kv_heads))


@jax.jit        # a model's blocks are a Python loop: traced once a shape
@jax.named_scope("paged_scatter")
def paged_scatter_fresh(pages, tables, new, valid=None):
    """:func:`paged_scatter_kv` for S fresh tokens at positions ``0 ..
    S-1``, a page at a time: the same cells get the same values and every
    other cell keeps what it held. ``valid`` [B, S] marks a right-padded
    prompt's real tokens (a prefix of each row; None: all S).

    The chip's scatter costs some 170 ns an index whatever it moves (a
    1,024-token prefill of GPT-2 XL spent 15.7 of its 40.5 ms writing
    98,304 rows of 3.3 KB one by one: my chip run, PR 40), and from
    position 0 token t lies in row ``t % block_size`` of the row's page
    ``t // block_size``: the pages a prompt fills are written whole (one
    index a page), the page it ends in row by row, the pages past it not
    at all."""
    B, S = new.shape[:2]
    NB, bs, G, W = pages.shape
    if S % bs:                       # a window that is not whole pages
        return paged_scatter_kv(pages, tables, jnp.zeros((B,), jnp.int32),
                                new, valid)
    n = S // bs
    length = (jnp.full((B,), S, jnp.int32) if valid is None
              else valid.sum(axis=1).astype(jnp.int32))
    full = length // bs                                   # whole pages a row
    ids = jnp.where(jnp.arange(n)[None, :] < full[:, None], tables[:, :n], NB)
    whole = new.reshape(B * n, bs, G, -1)
    whole = jnp.pad(whole, ((0, 0), (0, 0), (0, 0), (0, W - whole.shape[-1])))
    pages = pages.at[ids.reshape(-1)].set(whole, mode="drop",
                                          unique_indices=False)
    # the page the prompt ends in (none where it ends on a page's edge)
    first = jnp.minimum(full, n - 1) * bs
    last = jax.vmap(lambda x, at: jax.lax.dynamic_slice_in_dim(x, at, bs, 0))(
        new, first)
    at = first[:, None] + jnp.arange(bs)[None, :]
    return paged_scatter_kv(
        pages, tables, first, last,
        (at >= (full * bs)[:, None]) & (at < length[:, None]))


def fresh_causal_attention(q, k, v):
    """Causal self-attention of S fresh tokens at positions ``0 .. S-1``,
    token-major as the projections write them: q [B, S, H, hd]; k, v
    [B, S, KV, hd], head h reading kv head ``h // (H // KV)``. Returns
    [B, S, H * hd] in q's dtype, as the output projection reads it: no
    transpose either side of the kernel (``pallas_flash_attn.flash_gqa_fwd``;
    a caller asks :func:`fresh_kernel_applies` first). Float32 scores,
    running max and sum, the probabilities cast to v's dtype unnormalised
    before ``P v``, where the gather path casts them normalised: one
    rounding apart. Rows past a right-padded prompt's end attend to pad
    keys and are the caller's to discard, as on the gather path."""
    from distributed_lion_tpu.ops.pallas_flash_attn import flash_gqa_fwd

    B, S, H, _ = q.shape
    return flash_gqa_fwd(q.reshape(B, S, -1), k.reshape(B, S, -1),
                         v.reshape(B, S, -1), H)


# A latent family's prefill from position 0 (:func:`latent_fresh_applies`):
# the rows it attends are the ones it has just computed, so it expands keys
# and values from those, token-major as the up-projection writes them, and
# attends through the tiled kernel ``latent_prefill`` (ops/pallas_dsa: the
# indexer's prefill kernel without its mask). Nothing is gathered and no
# ``[H, chunk, T]`` float32 scores reach HBM; the pages are written as on
# every other path. Smaller buckets, a prefill behind a shared prefix, the
# verify window and the CPU keep :func:`chunked_causal_attention`.
LATENT_FRESH_MIN = 2048     # positions from which the kernel was faster


def latent_fresh_applies(n_new: int, dn: int, dv: int) -> bool:
    """True when :func:`latent_fresh_attention` takes a window of ``n_new``
    tokens from position 0 of heads ``dn`` (+ rope) / ``dv``: a TPU, whole
    key tiles, heads of one lane tile, and a window at or over the size where
    the kernel was measured faster than the chunked walk (32 heads of 192 /
    128 alone on the chip: 3.1 against 5.2 ms a layer at 4,096 positions, 1.4
    against 1.8 at 2,048; 1,024 is one key tile and was not tried: PERF.md,
    PR 47).
    The serving engine asks the same question, bucket by bucket
    (``ServeModel.fresh_prefill``)."""
    from distributed_lion_tpu.ops.pallas_dsa import latent_prefill_takes

    return (jax.default_backend() == "tpu" and n_new >= LATENT_FRESH_MIN
            and latent_prefill_takes(n_new, dn, dv))


@jax.named_scope("mla_attn")
def latent_fresh_attention(q, row, w_kvb, valid=None, *, scale: float):
    """Causal self-attention of S fresh tokens at positions ``0 .. S - 1``
    over their own latent rows: q ``[B, H, S, dn + dr]`` (roped); ``row [B,
    S, r + dr]`` the rows ``[c_kv | k_rope]`` the block has just computed
    (and scatters for the decode ticks); ``w_kvb [r, H, dn + dv]``; ``valid``
    [B, S] or [1, S] marks a right-padded prompt's real tokens (None: all).
    Returns ``[B, S, H * dv]`` in q's dtype, token-major as the output
    projection reads it. The arithmetic is :func:`chunked_causal_attention`'s
    over ``models/joyai.expand_rows``: float32 scores and sums, the
    probabilities cast before the value product (unnormalised here: one
    rounding apart). A caller asks :func:`latent_fresh_applies` first. Rows
    past a prompt's end are the caller's to discard (whole tiles of them
    come back zero, uncomputed)."""
    from distributed_lion_tpu.ops.pallas_dsa import latent_prefill

    B, _, S, _ = q.shape
    r = w_kvb.shape[0]
    kv = jnp.einsum("bsr,rm->bsm", row[..., :r], w_kvb.reshape(r, -1),
                    preferred_element_type=jnp.float32).astype(row.dtype)
    lengths = (jnp.full((B,), S, jnp.int32) if valid is None else
               jnp.broadcast_to(valid, (B, S)).sum(axis=1, dtype=jnp.int32))
    return latent_prefill(q, kv, row[..., r:], lengths, scale=scale)


# ------------------------------------------------ window layers: the ring
# A layer whose queries see only the last ``window`` positions keeps a
# bounded span a slot, whatever the sequence's length: ``R`` pages
# (:func:`ring_pages`), and the ring leaf is a pool leaf like any other,
# ``[slots * R, block_size, 1, W]``. Slot ``s`` owns pages ``s * R .. s * R +
# R - 1`` for good and position ``p`` lives in page ``s * R + (p //
# block_size) % R``: arithmetic on the row's slot id, no table, nothing
# allocated or freed. The decode tick hands the kernel the ring's pages in
# logical order, from the window's first page on, and the rows of that page
# before the window as ``starts``; every other call (the CPU) takes the
# gather path over the same walk. A prefill writes the last ``R`` pages of
# its prompt and drops the rest (:func:`ring_scatter_kv`); its own attention
# is over the fresh keys (:func:`banded_causal_attention`), not the ring.


def ring_pages(window: int, block_size: int) -> int:
    """Pages a slot holds in a window layer's ring: the pages ``window``
    positions fill, and one more for a window that starts inside a page
    (512 positions over pages of 16 touch 33)."""
    return -(-window // block_size) + 1


@jax.named_scope("paged_scatter")
def ring_scatter_kv(pages, slots, pos, new, lengths, *, window: int):
    """Write the new k (or v) rows of a window layer into their ring pages.
    pages [n_slots * R, block_size, G, W]; slots [B] the slot each row
    owns; pos [B] the position of each row's first new token; new [B, S,
    KV, hd]; lengths [B] how many of the S are real (0 = an inactive lane:
    nothing is written). Of a row's real tokens only those in the last
    ``R`` pages are written: an earlier one would land in a ring page a
    later one owns."""
    B, S = new.shape[:2]
    bs = pages.shape[1]
    R = ring_pages(window, bs)
    at = pos[:, None] + jnp.arange(S)[None, :]                     # [B, S]
    page, last = at // bs, (pos + lengths - 1) // bs
    keep = (jnp.arange(S)[None, :] < lengths[:, None]) \
        & (page > last[:, None] - R)
    walk = slots[:, None] * R + page % R                           # [B, S]
    # one table entry a token and offsets inside a page: the scatter's own
    # page arithmetic then lands token s in ``walk[b, s]``
    return paged_scatter_kv(pages, walk.reshape(-1, 1), (at % bs).reshape(-1),
                            new.reshape((B * S, 1) + new.shape[2:]),
                            keep.reshape(-1, 1))


def ring_walk(slots, pos, block_size: int, *, window: int, active=None):
    """A window layer's decode walk over slot ``slots[b]``'s ring, row b at
    position ``pos[b]`` (already scattered): (``walk [B, R]`` the ring's page
    ids in logical order from the window's first page on, ``length [B]`` the
    positions of the walk up to the query's own, ``start [B]`` the leading
    rows of the first page that lie before the window, ``read [B]`` the
    pages of the walk either path is handed). ``active`` (optional [B]
    bool): a lane with no sequence walks nothing."""
    R = ring_pages(window, block_size)
    length = pos + 1 if active is None else jnp.where(active, pos + 1, 0)
    first = jnp.maximum(length - window, 0)
    page0 = first // block_size
    walk = slots[:, None] * R + (page0[:, None] + jnp.arange(R)[None, :]) % R
    rel_len = length - page0 * block_size
    read = ((rel_len + block_size - 1) // block_size).astype(jnp.int32)
    return walk, rel_len, first - page0 * block_size, read


@jax.named_scope("paged_attn")
def ring_decode_attention(q, k_pages, v_pages, slots, pos, *, window: int,
                          active=None, kv_heads=None):
    """One query token a row over a window layer's ring (new k/v already
    scattered): row b at position ``pos[b]`` sees positions
    ``pos[b] - window + 1 .. pos[b]``, its own counted. q [B, H, 1, hd];
    slots [B] the slot each row owns; ``active`` (optional [B] bool): a
    lane with no sequence reads nothing. The Mosaic kernel where
    :func:`paged_kernel_applies`, else the gather path, both over the
    ring's pages in logical order from the window's first page on
    (:func:`ring_walk`). Returns
    (out [B, H, 1, hd], pages [B] int32: the pages of the walk that either
    path is handed, ``ceil(length / block_size)`` of the walk's own
    length, which is what the kernel reads)."""
    walk, rel_len, rel_start, read = ring_walk(
        slots, pos, k_pages.shape[1], window=window, active=active)
    KV = kv_heads or k_pages.shape[2] * (k_pages.shape[3] // q.shape[-1])
    if paged_kernel_applies(q.shape[2], k_pages.shape, k_pages.dtype):
        from distributed_lion_tpu.ops.pallas_paged_attn import paged_attn

        return paged_attn(q[:, :, 0], k_pages, v_pages, walk, rel_len,
                          rel_start, kv_heads=KV)[:, :, None], read
    return paged_decode_attention(q, k_pages, v_pages, walk, rel_len - 1,
                                  start=rel_start, kv_heads=KV), read


# most float32 score bytes one chunk of banded_causal_attention may hold
SCORE_BYTES = 64 << 20


def banded_causal_attention(q, k, v, *, window=None):
    """Causal self-attention of S fresh tokens at positions ``0 .. S-1`` (a
    prefill from the start of a sequence), grouped queries, with no
    ``[H, S, S]`` float32 scores held (72 heads x 8,192 x 8,192 would be
    19 GB). q [B, H, S, hd]; k, v [B, KV, S, hd], head h reading kv head
    ``h // (H // KV)`` with no repeat (v's heads may be narrower than
    k's: latent attention's are). ``window``: query i sees keys
    ``i - window + 1 .. i`` only; None: every earlier key. Returns
    [B, H, S, v's width] in q's dtype; float32 scores and softmax, the arithmetic
    of :func:`attention_xla`.

    Two paths, chosen from what the call shows. Without a band, on a TPU,
    where ``pallas_flash_attn.gqa_kernel_takes`` (heads of 128, or of 64
    with one kv head a query head; S a multiple of 128 up to 8,192): the
    repo's tiled forward kernel
    ``flash_gqa_fwd``, token-major, which visits no block above the
    diagonal: 48 heads over 8,192 keys, a layer, 7.2 ms on a v5e with the
    transposes either side, 2.1 over 4,096, 0.7 over 2,048. Every other
    call takes the queries a chunk at a time in plain XLA, and a window
    layer's chunk reads only the chunk + ``window`` keys its band can touch
    (O(S x window)). A chunk is the largest power of two up to 256 queries
    whose scores (B x heads x chunk x keys in reach, float32) stay within
    ``SCORE_BYTES``, because the chip's compiler tiles the softmax fusion
    ever worse above that: a window layer's 72 heads at 256 queries and
    768 keys in reach (57 MB) 2.6 ms over 8,192; the full layers' 48 heads
    over 8,192 keys took 18 ms at 32 queries a chunk alone and 56 inside
    the prefill program, 46 at 64, 1,168 at 128 (there the fusion's cost
    estimate overflows in the compiled HLO, which
    tests/test_chip_compile.py looks for), which is why they left this
    path (PERF.md, PR 30)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    if window is None and jax.default_backend() == "tpu":
        from distributed_lion_tpu.ops.pallas_flash_attn import (
            flash_gqa_fwd, gqa_kernel_takes,
        )

        if gqa_kernel_takes(S, hd, q.dtype, H // KV):
            # no band: the tiled kernel, token-major, which skips every
            # block above the diagonal (module note of pallas_flash_attn)
            def rows(x):
                return x.transpose(0, 2, 1, 3).reshape(B, S, -1)

            out = flash_gqa_fwd(rows(q), rows(k), rows(v), H)
            return out.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    chunk = 256
    while chunk > 8 and B * H * chunk * 4 * (
            S if window is None else min(S, chunk + window)) > SCORE_BYTES:
        chunk //= 2
    chunk = min(chunk, S)
    assert S % chunk == 0 and H % KV == 0, (S, chunk, H, KV)
    span = S if window is None else min(S, chunk + window)
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, H // KV, S // chunk, chunk, hd)

    def one(args):
        qc, c0 = args                                  # [B,KV,rep,c,hd], []
        k0 = jnp.maximum(c0 + chunk - span, 0)         # first key in reach
        kc = jax.lax.dynamic_slice_in_dim(k, k0, span, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(v, k0, span, axis=2)
        scores = jnp.einsum("bgrsd,bgtd->bgrst", qc, kc,
                            preferred_element_type=jnp.float32) * scale
        q_pos = (c0 + jnp.arange(chunk))[:, None]
        k_pos = (k0 + jnp.arange(span))[None, :]
        seen = k_pos <= q_pos
        if window is not None:
            seen &= k_pos > q_pos - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30),
                               axis=-1).astype(q.dtype)
        return jnp.einsum("bgrst,bgtd->bgrsd", probs, vc,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    n = S // chunk
    if n == 1:
        out = one((qg[:, :, :, 0], 0))
    else:
        out = jax.lax.map(one, (jnp.moveaxis(qg, 3, 0),
                                jnp.arange(n) * chunk))
        out = jnp.moveaxis(out, 0, 3)                  # [B,KV,rep,n,c,hd]
    return out.reshape(B, H, S, v.shape[-1])   # values may be narrower


# ---------------------------------------------------------- latent (MLA)
# One leaf a layer, ``[num_blocks, block_size, 1, W]``: a token's row is
# ``[c_kv | k_rope | 0]`` (serve/kv_cache.latent_row_width). Two attention
# paths read it (models/joyai chooses by :func:`paged_kernel_applies`, the
# rule the k/v pool's kernel follows): the decode tick absorbs the key and
# value up-projections into the query and the output and runs
# :func:`mla_decode_attention`, one page read for scores and values; every
# other call (S > 1, the CPU) gathers the rows, expands keys and values and
# runs :func:`chunked_causal_attention`, which takes keys wider than values,
# but for a long prefill from position 0 (:func:`latent_fresh_attention`).


@jax.named_scope("window_mla")
def ring_mla_decode_attention(q_abs, kv_pages, slots, pos, *, window: int,
                              scale: float, active=None):
    """:func:`mla_decode_attention` over a RING of latent rows: a window
    layer of latent attention keeps ``[c_kv | k_rope]`` rows in
    :func:`ring_pages` pages a slot (the ring note above: the leaf is a pool
    leaf like any other, written by :func:`ring_scatter_kv`), and the decode
    tick hands the kernel the ring in logical order with the rows before
    the window as ``starts`` (name ``window_mla_attn``; a caller asks
    :func:`paged_kernel_applies` first, and attends the gathered walk
    itself elsewhere). q_abs [B, H, W]. Returns (``[B, H, W]``, the pages
    of the walk ``[B]`` int32)."""
    from distributed_lion_tpu.ops.pallas_mla_attn import mla_paged_attn

    walk, rel_len, rel_start, read = ring_walk(
        slots, pos, kv_pages.shape[1], window=window, active=active)
    out = mla_paged_attn(q_abs, kv_pages, walk, rel_len, starts=rel_start,
                         scale=scale, name="window_mla_attn")
    return out, read


@jax.named_scope("mla_attn")
def mla_decode_attention(q_abs, kv_pages, tables, pos, *, scale: float):
    """Absorbed latent decode, one query token a row, over the pool in
    place (``ops/pallas_mla_attn``). q_abs [B, H, W]: head h's query in the
    row's own layout (``[q_nope W_k[h] | q_rope | 0]``); ``pos`` [B] the
    position of the row's new token, already scattered. Returns
    ``softmax(scale * q . row) @ row`` [B, H, W]; the value is its leading
    ``kv_lora_rank`` lanes. Rows with an all-sentinel table read nothing and
    return zeros."""
    from distributed_lion_tpu.ops.pallas_mla_attn import mla_paged_attn

    NB, bs = kv_pages.shape[:2]
    return mla_paged_attn(q_abs, kv_pages, tables,
                          walk_lengths(tables, pos, NB, bs), scale=scale)


def query_chunk(B: int, H: int, S: int, T: int) -> int:
    """Queries a step of :func:`chunked_causal_attention`: 256 while their
    float32 scores (B x H x 256 x T) stay within ``SCORE_BYTES``; past
    that the largest power of two whose scores stay within a quarter of it.
    The chip's compiler tiles the chunk's softmax fusion ever worse as its
    scores grow (:func:`banded_causal_attention`'s note), and past
    ``SCORE_BYTES`` staying just within it is not enough: 32 heads of 192 /
    128 over 4,096 keys, a layer, took 16.2 ms at 256 queries a chunk (128
    MB of scores), 9.8 at 128, 6.0 at 64 and 4.5 at 32 (16 MB), where the
    same heads over 2,048 keys took 1.1 ms at any of them (PERF.md, PR 39).
    Every shape that fitted keeps its 256. Since PR 47 that 4,096-key walk is
    what a prefill behind a shared prefix and the verify window take: a
    prefill from position 0 of ``LATENT_FRESH_MIN`` tokens attends through the tiled
    kernel (:func:`latent_fresh_attention`) and no chunk is cut for it."""
    chunk = 256
    if B * H * chunk * T * 4 > SCORE_BYTES:
        while chunk > 8 and B * H * chunk * T * 4 > SCORE_BYTES // 4:
            chunk //= 2
    return min(chunk, S)


@jax.named_scope("mla_attn")
def chunked_causal_attention(q, k, v, pos, *, scale: float, chunk=None):
    """Masked-softmax attention of S new tokens a row over T cached
    positions, the queries taken ``chunk`` at a time (default:
    :func:`query_chunk`) so that no ``[H, S, T]`` float32 scores are held
    (32 heads x 2,048 x 3,072 would be 0.8 GB; a chunk of 256 is 0.1 GB).
    q [B, H, S, dk]; k [B, H, T, dk];
    v [B, H, T, dv] (dv may differ from dk); query s of row b sits at
    position ``pos[b] + s`` and sees positions ``<=`` its own. Returns
    [B, H, S, dv] in q's dtype. The arithmetic is
    :func:`paged_decode_attention`'s gather path, chunk by chunk."""
    B, H, S, _ = q.shape
    T = k.shape[2]
    chunk = query_chunk(B, H, S, T) if chunk is None else min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    t_idx = jnp.arange(T)[None, None, :]

    def one(args):
        qc, first = args                                   # [B,H,c,dk], []
        scores = jnp.einsum("bhsd,bhtd->bhst", qc, k,
                            preferred_element_type=jnp.float32) * scale
        s_pos = pos[:, None] + first + jnp.arange(chunk)[None, :]
        valid = t_idx <= s_pos[:, :, None]
        scores = jnp.where(valid[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    if chunk == S:
        return one((q, 0))
    n = S // chunk
    qs = q.reshape(B, H, n, chunk, -1).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (qs, jnp.arange(n) * chunk))    # [n,B,H,c,dv]
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, S, -1)


def _note_resolved(entry: str, impl: str, T: int, head_dim: int, dtype,
                   tiles: str) -> None:
    """What `auto` resolved to, once a shape at trace time: an
    ``attn_resolved`` journal event and a ``[setup]`` line for whoever
    drives the program (``train/journal.resolved``)."""
    from distributed_lion_tpu.train import journal

    name = jnp.dtype(dtype).name
    journal.resolved(
        "attn_resolved",
        f"[setup] attention: {entry} auto -> {impl} (T {T}, head_dim "
        f"{head_dim}, {name}, tiles {tiles})",
        entry=entry, impl=impl, T=T, head_dim=head_dim, dtype=name,
        tiles=tiles)


def qkv_kernel_applies(T: int, n_head: int, head_dim: int, dtype) -> bool:
    """True when :func:`attention_qkv` ``auto`` takes the repo's training
    kernel for such a call (the rule is in the module doc)."""
    from distributed_lion_tpu.ops.pallas_flash_attn import kernel_takes

    return (jax.default_backend() == "tpu" and T >= 1024
            and kernel_takes(T, n_head, head_dim, dtype))


def attention_qkv(qkv, n_head: int, *, impl: str = "auto"):
    """Causal attention of a fused projection's output: ``qkv`` is
    ``[B, T, 3, D]`` or ``[B, T, 3 * D]`` (``D = n_head * head_dim``),
    the result ``[B, T, D]`` in its dtype. ``auto`` hands ``qkv`` to the
    repo's kernel as it lies where :func:`qkv_kernel_applies`; every other
    call splits it head-major for :func:`attention`."""
    B, T = qkv.shape[:2]
    D = math.prod(qkv.shape[2:]) // 3
    hd = D // n_head
    if impl == "auto" and qkv_kernel_applies(T, n_head, hd, qkv.dtype):
        from distributed_lion_tpu.ops.pallas_flash_attn import (
            block_for,
            flash_qkv,
        )

        blk = block_for(T)
        _note_resolved("qkv", "pallas_flash_attn", T, hd, qkv.dtype,
                       f"{blk}x{blk}")
        return flash_qkv(qkv.reshape(B, T, 3 * D), n_head)
    q, k, v = (x.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
               for x in jnp.split(qkv.reshape(B, T, 3, D), 3, axis=2))
    out = attention(q, k, v, impl=impl)
    return out.transpose(0, 2, 1, 3).reshape(B, T, D)


def library_kernel_applies(T: int) -> bool:
    """True when :func:`attention` ``auto`` takes jax's bundled kernel."""
    return jax.default_backend() == "tpu" and T >= 2048


def attention(q, k, v, *, causal: bool = True, impl: str = "auto"):
    """Attention of head-major q, k, v ``[B, H, T, head_dim]``. ``auto``
    takes the library's flash kernel on a TPU at T >= 2048 (its memory
    regime) and :func:`attention_xla` everywhere else."""
    if impl == "auto":
        T = q.shape[2]
        flash = library_kernel_applies(T)
        _note_resolved("head-major", "flash" if flash else "xla", T,
                       q.shape[3], q.dtype, "default" if flash else "-")
        if flash:
            return attention_flash(q, k, v, causal=causal)
        impl = "xla"
    if impl == "xla":
        return attention_xla(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")
