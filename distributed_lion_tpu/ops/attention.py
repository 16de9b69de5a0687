"""Attention implementations with a single dispatch point.

Two entries, one for each layout a caller holds:

- :func:`attention_qkv` — token-major: ``qkv [B, T, 3 * D]``, a token's q,
  k and v rows side by side as GPT-2's fused projection writes them;
  returns ``[B, T, D]`` as the output projection reads it.
- :func:`attention` — head-major: q, k, v ``[B, H, T, head_dim]``; returns
  the same. A family whose q and k are built separately (RoPE, fewer kv
  heads: ``models/llama``) calls this one.

Implementations (``impl``):

- ``xla``   — materialized-scores reference: einsum → masked f32 softmax →
  einsum. What every kernel is tested against.
- ``xla_bf16`` — ``xla`` with the [B,H,T,T] scores stored in bf16 (softmax
  still f32 internally): halves the largest attention intermediate's HBM
  round-trip at ~1e-2 relative error on probs. Opt-in throughput config.
- ``flash`` — jax's bundled Pallas kernel
  (``pallas.ops.tpu.flash_attention``), head-major, with caller-pinned
  tiles (``block_q`` ...). The tuner (``ops/autotune``) and
  ``chip_smoke.py`` call it by name.
- ``splash`` — the bundled splash kernel family (sparse-mask blocking),
  head-major, head_dim padded to 128.
- the repo's own training kernel (``ops/pallas_flash_attn``): token-major
  operands, one float32 a row as residual, a fused backward. Not an
  ``impl`` name: it is what ``auto`` resolves to, below.

``auto`` resolves from what the call shows, and takes no option to get
there:

- :func:`attention_qkv` on a TPU, no caller-pinned tiles, a shape the
  kernel takes as it lies (``pallas_flash_attn.kernel_takes``: head_dim 64
  or 128, whole 128-lane blocks, T a multiple of 128 up to 8,192) and
  T >= 1024 → the repo's kernel, reading ``qkv`` in place. Measured on the
  chip at the training cells' shape (T = 1024, head_dim 64: PERF.md,
  PR 27); the other shapes follow by what the kernel does not do (no
  [B,H,T,T] scores, no head-major copy), and ``chip_smoke.py`` checks one
  of them against ``xla``.
- every other :func:`attention_qkv` call splits ``qkv`` head-major and
  goes through :func:`attention`.
- :func:`attention` on a TPU, in priority order: caller-pinned tiles →
  ``flash`` with those tiles at any shape (an explicit ``auto@BQxBKV`` spec
  is an operator decision and stays sweepable); an autotune-cache hit for
  this device_kind × (T, head_dim) × dtype (``ops/autotune``, knob
  ``flash_tiles``: the LIBRARY kernel's tiles; ``scripts/tuning_cache.json``
  holds none for a TPU) → ``flash`` with them; T >= 2048 → ``flash`` at the
  library's default tiles (its memory regime); ``xla`` everywhere else.
  Such an entry never outranks the repo's kernel: :func:`attention_qkv`
  takes that before it gets here (a tuned tile pair, 512x1024, is what made
  the library's backward write a 1 GB ``di`` buffer a layer: PERF.md,
  PR 27).
- off a TPU: always ``xla`` (pinned tiles are dropped: Pallas kernels are
  TPU-only).

What ``auto`` resolved to is recorded once a shape at trace time
(:func:`_note_resolved`): an ``attn_resolved`` event in the run journal and
a line the trainer prints after its first dispatch.

Causal only (decoder framework).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_xla(q, k, v, *, causal: bool = True,
                  score_dtype=jnp.float32):
    """Materialized-scores attention. ``score_dtype=jnp.bfloat16`` is the
    ``xla_bf16`` impl: the [B, H, T, T] scores tensor — the largest
    attention intermediate (201 MB/layer at mb4 T=1024 in f32) and pure HBM
    traffic between the two matmuls — is stored in bf16, halving its
    round-trip. The softmax always runs in f32 (the upcast fuses into the
    softmax elementwise chain, costing registers, not HBM), so only the one
    rounding of the scores differs; max-subtraction bounds the exponent so
    bf16's 8 mantissa bits cost ~1e-2 relative on probs — an opt-in
    throughput config, not the parity default."""
    T = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    # accumulate in f32 regardless of score_dtype; only the STORED scores
    # are rounded (the cast fuses into the matmul/mask epilogue, so the
    # HBM write is score_dtype-wide) — rounding is the only delta vs f32
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = (scores * scale).astype(score_dtype)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, score_dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v, preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attention_flash(q, k, v, *, causal: bool = True,
                    block_q: int = 0, block_kv: int = 0,
                    block_q_bwd: int = 0, block_kv_bwd: int = 0):
    """Pallas TPU flash attention. ``block_q``/``block_kv`` override the
    kernel's VMEM tile sizes (0 = library defaults); exposed because the
    default blocking lost to XLA at T=1024 on v5e (scripts/SWEEP_v5e.md) and
    tile shape is the first knob to turn. ``block_q_bwd``/``block_kv_bwd``
    tune the dq/dkv backward passes independently (0 = inherit fwd) — the
    backward is ~2× the fwd FLOPs with different operand shapes, so its
    optimum tile need not match the forward's."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    T = q.shape[2]
    bs = None
    if block_q or block_kv or block_q_bwd or block_kv_bwd:
        bq = min(block_q or 512, T)
        bkv = min(block_kv or 512, T)
        bqb = min(block_q_bwd or bq, T)
        bkvb = min(block_kv_bwd or bkv, T)
        bs = BlockSizes(
            block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
            block_q_major_dkv=bqb, block_k_major_dkv=bkvb, block_k_dkv=bkvb,
            block_q_dkv=bqb, block_k_major_dq=bkvb, block_k_dq=bkvb,
            block_q_dq=bqb,
        )
    return flash_attention(
        q, k, v, causal=causal, sm_scale=1.0 / math.sqrt(q.shape[-1]),
        block_sizes=bs,
    ).astype(q.dtype)


def attention_splash(q, k, v, *, causal: bool = True,
                     block_q: int = 0, block_kv: int = 0,
                     interpret: bool = False):
    """Splash attention (the newer Pallas TPU kernel family): sparse-mask
    blocking, fused bwd option — typically faster than the older flash
    kernel at moderate T. Takes the same [B, H, T, hd] as the others; the
    kernel is per-(heads, T, hd) so batch rides a vmap. q is pre-scaled
    (splash applies no sm_scale)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as ml,
    )

    B, H, T, hd = q.shape
    # the installed splash kernel requires head_dim % 128 == 0 (lane width);
    # GPT-2's hd=64 (and any other non-multiple) is padded up with zero
    # columns and the output sliced back. Exact, not approximate: q·k over
    # the zero columns adds nothing to any score, and the zero v columns
    # only produce output columns that are sliced away. The pad costs real
    # MXU FLOPs (hd 64 → 128 doubles the qk/pv inner dim), which is why
    # `auto` never dispatches here — explicit splash requests and the
    # autotune tuner (which times the kernel PADDED, so its numbers stay
    # honest) accept the cost knowingly.
    hd_pad = -(-hd // 128) * 128
    if hd_pad != hd:
        pad = [(0, 0)] * 3 + [(0, hd_pad - hd)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    one = ml.CausalMask((T, T)) if causal else ml.FullMask((T, T))
    mask = ml.MultiHeadMask([one for _ in range(H)])
    bs = None
    if block_q or block_kv:
        bq = min(block_q or 512, T)
        bkv = min(block_kv or 512, T)
        bs = sk.BlockSizes(block_q=bq, block_kv=bkv,
                           block_q_dkv=bq, block_kv_dkv=bkv,
                           block_q_dq=bq, block_kv_dq=bkv)
    kernel = sk.make_splash_mha_single_device(mask=mask, block_sizes=bs,
                                              interpret=interpret)
    # scale by the REAL head_dim — the zero pad must not change the softmax
    qs = (q * (1.0 / math.sqrt(hd))).astype(q.dtype)
    out = jax.vmap(kernel)(qs, k, v)
    if hd_pad != hd:
        out = out[..., :hd]
    return out.astype(q.dtype)


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
# Which calls take the Mosaic kernel (ops/pallas_paged_attn) is decided
# from what the call shows, in :func:`paged_kernel_applies`: one query
# token a row, no ``start``, a TPU backend, a pool the kernel takes as it
# lies. Every other call (S > 1: bucketed prefill, speculative verify;
# left-padded batches; the CPU) takes the gather path below, which is the
# reference the kernel is tested against and stays bit-identical to the
# dense cache.


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

        lengths = jnp.minimum(pos + 1, jnp.sum(tables < NB, axis=1) * bs)
        return paged_attn(q[:, :, 0], k_pages, v_pages, tables, lengths,
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


# ---------------------------------------------------------- latent (MLA)
# One leaf a layer, ``[num_blocks, block_size, 1, W]``: a token's row is
# ``[c_kv | k_rope | 0]`` (serve/kv_cache.latent_row_width). Two attention
# paths read it (models/joyai chooses by :func:`paged_kernel_applies`, the
# rule the k/v pool's kernel follows): the decode tick absorbs the key and
# value up-projections into the query and the output and runs
# :func:`mla_decode_attention`, one page read for scores and values; every
# other call (S > 1, the CPU) gathers the rows, expands keys and values and
# runs :func:`chunked_causal_attention`, which takes keys wider than values.


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
    lengths = jnp.minimum(pos + 1, jnp.sum(tables < NB, axis=1) * bs)
    return mla_paged_attn(q_abs, kv_pages, tables, lengths, scale=scale)


@jax.named_scope("mla_attn")
def chunked_causal_attention(q, k, v, pos, *, scale: float,
                             chunk: int = 256):
    """Masked-softmax attention of S new tokens a row over T cached
    positions, the queries taken ``chunk`` at a time so that no
    ``[H, S, T]`` float32 scores are held (32 heads x 2,048 x 3,072 would be
    0.8 GB; a chunk of 256 is 0.1 GB). q [B, H, S, dk]; k [B, H, T, dk];
    v [B, H, T, dv] (dv may differ from dk); query s of row b sits at
    position ``pos[b] + s`` and sees positions ``<=`` its own. Returns
    [B, H, S, dv] in q's dtype. The arithmetic is
    :func:`paged_decode_attention`'s gather path, chunk by chunk."""
    B, H, S, _ = q.shape
    T = k.shape[2]
    chunk = min(chunk, S)
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


def parse_attn_spec(spec: str) -> tuple[str, int, int, int, int]:
    """Parse an attention spec ``impl[@BQxBKV[@BQBxBKVB]]`` into
    ``(impl, block_q, block_kv, block_q_bwd, block_kv_bwd)`` — e.g.
    ``"flash@512x1024"`` → ``("flash", 512, 1024, 0, 0)`` and
    ``"flash@512x1024@256x512"`` tunes the BACKWARD tiles independently
    (the bwd passes are ~2× the fwd FLOPs with different operand shapes,
    so their optimum need not match; 0 = inherit the fwd tiles). No ``@``
    → all 0 (kernel defaults). The one grammar shared by bench.py's
    BENCH_ATTN env knob and scripts/bench_sweep.py's config specs."""
    if "@" not in spec:
        return spec, 0, 0, 0, 0
    impl, _, blocks = spec.partition("@")
    fwd, _, bwd = blocks.partition("@")
    bq, bkv = (int(x) for x in fwd.split("x"))
    bqb, bkvb = (int(x) for x in bwd.split("x")) if bwd else (0, 0)
    return impl, bq, bkv, bqb, bkvb


# What `auto` resolved to, by (entry, T, head_dim, dtype): recorded once at
# trace time, said by whoever drives the program (Trainer.train prints
# :func:`new_resolved_lines` after a dispatch that traced).
_RESOLVED: dict = {}
_resolved_said = 0


def _note_resolved(entry: str, impl: str, T: int, head_dim: int, dtype,
                   tiles: str) -> None:
    key = (entry, T, head_dim, jnp.dtype(dtype).name)
    if key in _RESOLVED:
        return
    fields = {"entry": entry, "impl": impl, "T": T, "head_dim": head_dim,
              "dtype": key[3], "tiles": tiles}
    _RESOLVED[key] = fields
    from distributed_lion_tpu.train import journal

    journal.event("attn_resolved", **fields)


def new_resolved_lines() -> list:
    """One ``[setup]`` line for each resolution recorded since the last
    call (an integer compare when there is none)."""
    global _resolved_said
    if _resolved_said == len(_RESOLVED):
        return []
    fresh = list(_RESOLVED.values())[_resolved_said:]
    _resolved_said = len(_RESOLVED)
    return [f"[setup] attention: {f['entry']} auto -> {f['impl']} "
            f"(T {f['T']}, head_dim {f['head_dim']}, {f['dtype']}, "
            f"tiles {f['tiles']})" for f in fresh]


def qkv_kernel_applies(T: int, n_head: int, head_dim: int, dtype,
                       pinned: bool = False) -> bool:
    """True when :func:`attention_qkv` ``auto`` takes the repo's training
    kernel for such a call (the rule is in the module doc)."""
    from distributed_lion_tpu.ops.pallas_flash_attn import kernel_takes

    return (not pinned and jax.default_backend() == "tpu" and T >= 1024
            and kernel_takes(T, n_head, head_dim, dtype))


def attention_qkv(qkv, n_head: int, *, impl: str = "auto",
                  block_q: int = 0, block_kv: int = 0,
                  block_q_bwd: int = 0, block_kv_bwd: int = 0):
    """Causal attention of a fused projection's output: ``qkv`` is
    ``[B, T, 3, D]`` or ``[B, T, 3 * D]`` (``D = n_head * head_dim``),
    the result ``[B, T, D]`` in its dtype. ``auto`` hands ``qkv`` to the
    repo's kernel as it lies where :func:`qkv_kernel_applies`; every other
    call splits it head-major for :func:`attention`."""
    B, T = qkv.shape[:2]
    D = math.prod(qkv.shape[2:]) // 3
    hd = D // n_head
    pinned = bool(block_q or block_kv or block_q_bwd or block_kv_bwd)
    if impl == "auto" and qkv_kernel_applies(T, n_head, hd, qkv.dtype,
                                             pinned):
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
    out = attention(q, k, v, impl=impl, block_q=block_q, block_kv=block_kv,
                    block_q_bwd=block_q_bwd, block_kv_bwd=block_kv_bwd)
    return out.transpose(0, 2, 1, 3).reshape(B, T, D)


def attention(q, k, v, *, causal: bool = True, impl: str = "auto",
              block_q: int = 0, block_kv: int = 0,
              block_q_bwd: int = 0, block_kv_bwd: int = 0):
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        T = q.shape[2]
        tuned = None
        if on_tpu and not (block_q or block_kv or block_q_bwd or block_kv_bwd):
            # no caller pins → consult the autotune cache (ops/autotune,
            # knob 'flash_tiles'): a measured winner of the LIBRARY kernel
            # for THIS device_kind × (T, head_dim) × dtype outranks the
            # heuristics below — but never an explicit pin (the elif), which
            # is how sweeps measure non-cached tiles, and never the repo's
            # own kernel, which attention_qkv takes before it gets here.
            # Device-keyed, so a cache produced elsewhere never leaks here; a
            # corrupt cache is loud and reads as a miss. The lookup is
            # host-side at trace time — one file read per process
            # (module-level memo in autotune).
            from distributed_lion_tpu.ops.autotune import (
                attn_shape_key,
                lookup,
            )

            tuned = lookup("flash_tiles", attn_shape_key(T, q.shape[3]),
                           jnp.dtype(q.dtype).name)
        if tuned:
            impl = "flash"
            block_q = int(tuned.get("block_q", 0))
            block_kv = int(tuned.get("block_kv", 0))
            block_q_bwd = int(tuned.get("block_q_bwd", 0))
            block_kv_bwd = int(tuned.get("block_kv_bwd", 0))
        elif on_tpu and (block_q or block_kv or block_q_bwd or block_kv_bwd):
            # caller-pinned tiles are a flash knob: honor them at ANY shape
            # rather than silently running untiled xla (a config like
            # auto@256x512 would otherwise report numbers and tune nothing
            # — same trap the bwd-tile guard below raises for). Backward-only
            # pins (auto@@BQBxBKVB-style resolved specs) count too: falling
            # through to xla would hit that guard's ValueError instead of
            # honoring the tiles (advisor r4)
            impl = "flash"
        elif on_tpu and T >= 2048:
            impl = "flash"
        else:
            impl = "xla"
            # auto resolved AWAY from flash (no TPU backend, or a shape
            # below the library kernel's regime): pinned tiles
            # — bwd like fwd — are flash knobs with nothing left to tune.
            # Drop them instead of tripping the explicit-impl guard below:
            # an auto@...@BQBxBKVB spec must degrade off-TPU exactly like
            # auto@... does, not raise the flash-knob ValueError that
            # exists for EXPLICIT xla/splash requests
            block_q_bwd = block_kv_bwd = 0
        _note_resolved(
            "head-major", impl, T, q.shape[3], q.dtype,
            f"{block_q}x{block_kv}@{block_q_bwd}x{block_kv_bwd}"
            if impl == "flash" else "-")
    if impl == "flash":
        return attention_flash(q, k, v, causal=causal,
                               block_q=block_q, block_kv=block_kv,
                               block_q_bwd=block_q_bwd,
                               block_kv_bwd=block_kv_bwd)
    if block_q_bwd or block_kv_bwd:
        # fail loudly: a sweep config like splash@128x256@64x128 would
        # otherwise run, report numbers, and silently tune nothing
        raise ValueError(
            f"backward-tile overrides (@BQBxBKVB) are a flash-kernel knob; "
            f"impl {impl!r} does not consume them")
    if impl == "splash":
        return attention_splash(q, k, v, causal=causal,
                                block_q=block_q, block_kv=block_kv)
    if impl == "xla":
        return attention_xla(q, k, v, causal=causal)
    if impl == "xla_bf16":
        return attention_xla(q, k, v, causal=causal,
                             score_dtype=jnp.bfloat16)
    raise ValueError(f"unknown attention impl {impl!r}")
