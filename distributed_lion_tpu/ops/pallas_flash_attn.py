"""Causal flash attention for the training path, token-major (Mosaic kernels).

The projection's output is the operand: ``qkv [B, T, 3 * D]`` (``D = H *
head_dim``; q, k and v side by side in a token's row, as
``models/gpt2._qkv_project`` writes them) is handed in three times under
three index maps, the output is ``[B, T, D]`` as ``_proj`` reads it, and
the cotangent comes back as one ``[B, T, 3 * D]`` array, which the
projection's weight-gradient matmul reads as it is. No head-major copy of
an activation exists on either side.

Heads without a head axis. A 128-lane block of a token's row holds
``128 // head_dim`` heads (two of 64, one of 128), and a 64-lane slice is
not tile-aligned. So a grid step takes one lane block and, for each of its
heads, zeroes the other head's lanes of one operand: ``S_h = q_h k^T``
over the 128-deep contraction is exactly head h's scores (the zeros drop
the other head's products) and costs the 128 x 128 MXU what a 64-deep
contraction would; ``P_h v`` gives 128 lanes of which head h's own are
kept. No lane shuffles (``ops/pallas_paged_attn`` meets the same
misalignment by spreading the queries block-diagonally).

One float32 a row is the residual: the log-sum-exp, laid out as
``[B, lane_blocks, q_blocks, heads_per_block, block]`` (a row of ``block``
lanes a head: the backward works on transposed scores ``S^T = k q^T``,
where a query's statistic is a lane and broadcasts over sublanes for free).
``di = sum(o * do)`` is computed in the backward kernel, in VMEM. Nothing is
spread over 128 lanes in HBM.

Forward, ``flash_attention_fwd``: grid ``(B, lane_blocks, q_blocks)``; the
lane block's k and v for all T stay in VMEM across the q blocks (their
index map does not move), and a q block loops over the kv blocks at or
below its diagonal only: online softmax in float32 (running max, sum,
accumulator), MXU operands in the input dtype, probabilities cast to v's
dtype before ``P v``: the library kernel's precision
(``jax.experimental.pallas.ops.tpu.flash_attention``), and
``ops/attention.attention_xla`` is the reference the tests hold it to.

Backward, ``flash_mha_bwd``: one fused kernel, grid
``(B, lane_blocks, 3)``. Step 0 of the last axis does the work for the
lane block: for each kv block and head, over the q blocks at or above the
diagonal, ``S^T``, ``P^T = exp(S^T - lse)``, ``dV += P^T do``,
``dP^T = v do^T``, ``dS^T = P^T (dP^T - di)``, ``dK += dS^T q``,
``dQ[q block] += dS k`` (float32 accumulators in VMEM): five matmuls and one
exponential a block where a dq pass and a dkv pass make seven and two.
Steps 0, 1, 2 then write dq, dk, dv into the cotangent's three column
ranges (the output's index map moves with the step; the operands' do not,
so nothing is fetched again).

Only the diagonal blocks are masked; blocks above it are never visited.
The block is chosen from T (:func:`block_for`); :func:`kernel_takes` says
which shapes the kernels take as they lie (a caller with another shape
keeps ``ops/attention``'s other paths).

Names on the device: ``flash_attention_fwd`` and ``flash_mha_bwd``
(``name=`` and the innermost ``jax.named_scope``, as ``ops/pallas_lion``
names its kernels).

A serving prefill from position 0 (``flash_gqa_fwd``, on the device under
that name): the forward kernel alone over separate token-major q, k and v
as the projections write them. Two shapes, one kernel body:

- heads of 128, grouped queries: a lane block is a query head and its k
  and v block is picked by ``h // rep``.
  ``ops/attention.banded_causal_attention`` sends a full layer's prefill
  here (``models/laguna``: 48 query heads over 8 kv heads, 8,192 keys in
  7.2 ms with the transposes either side; my chip run, PR 30), and
  ``ops/attention.fresh_causal_attention`` a Llama prefill.
- heads of 64, one kv head a query head: a lane block is two heads, as in
  training; an odd head count (GPT-2 XL's 25) is padded with one head of
  zero lanes (1,600 -> 1,664), whose output is zero and is sliced off.
  ``ops/attention.fresh_causal_attention`` sends GPT-2's prefill here
  (PR 40).

The wrapper is jitted, as every kernel wrapper of the repo is: a model
whose blocks are a Python loop lowers the kernel once a shape, not once a
layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MASKED = -1e30       # attention_xla's mask value
MAX_T = 8192         # k, v (forward) and the five operands (backward) of a
# lane block stay in VMEM for all T: 256 B a token an operand, twice for the
# pipeline's second buffer; at 8,192 the backward holds 34 MB of 128

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def block_for(T: int) -> int:
    """Rows of a q block = rows of a kv block: the largest of 512, 256, 128
    that divides T. On the chip at T = 1024, B = 20 (forward / backward of
    one call): 128 3.40 / 4.38 ms, 256 1.35 / 2.66, 512 0.84 / 1.85 (my chip
    run, PR 27): a block's fixed cost (the loop step, the accumulators'
    round trip, the [block, 1] statistics) outweighs what a finer causal
    walk saves (10 of 16 blocks at 256 against 3 of 4 at 512)."""
    return next(b for b in (512, 256, 128) if T % b == 0)


def kernel_takes(T: int, n_head: int, head_dim: int, dtype) -> bool:
    """Whether the kernels take ``qkv [B, T, 3 * n_head * head_dim]`` of
    this dtype as it lies: whole lane blocks of whole heads, whole blocks
    of rows, and a lane block's operands inside VMEM."""
    return (head_dim in (64, 128) and (n_head * head_dim) % LANES == 0
            and T % 128 == 0 and 128 <= T <= MAX_T
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _head_masks(head_dim: int):
    """One ``[1, 128]`` bool row a head of the lane block (None for a head
    of 128: nothing to mask)."""
    if head_dim == LANES:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return [lane // head_dim == h for h in range(LANES // head_dim)]


def _own(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _merge(masks, parts):
    """Each head's own lanes of its part."""
    out = parts[0]
    for mask, part in zip(masks[1:], parts[1:]):
        out = jnp.where(mask, part, out)
    return out


def _rows(i, blk):
    return pl.ds(pl.multiple_of(i * blk, blk), blk)


# ----------------------------------------------------------------- forward
def _band(qi, blk: int, window: int):
    """Of the kv blocks below q block ``qi``'s diagonal, those its band
    reaches: ``lo`` the first with a visible key, ``inner`` the first with
    every key visible to every row (blocks ``lo .. inner - 1`` cross the
    band's lower edge). Query i sees keys ``i - window + 1 .. i``."""
    lo = jnp.maximum(qi * blk - (window - 1), 0) // blk
    return lo, jnp.clip(qi - (window // blk - 1), lo, qi)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, *,
                scale: float, head_dim: int, window: int = 0):
    blk = q_ref.shape[0]
    qi = pl.program_id(2)
    masks = _head_masks(head_dim)
    q = q_ref[...]
    q_heads = [_own(mask, q) for mask in masks]

    def block(ki, stats, diagonal, edge=False):
        """One kv block for every head of the lane block (the heads side by
        side in one loop body: one's matmuls run under the other's
        softmax). ``edge``: the block crosses the band's lower edge."""
        k, v = k_ref[_rows(ki, blk), :], v_ref[_rows(ki, blk), :]
        out = []
        for h, (q_h, (m_prev, l_prev)) in enumerate(zip(q_heads, stats)):
            s = jax.lax.dot_general(
                q_h, k, _NT, preferred_element_type=jnp.float32) * scale
            if diagonal:
                row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(col <= row, s, MASKED)
                if 0 < window < blk:
                    s = jnp.where(row - col < window, s, MASKED)
            if edge:
                row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(row - col < window - (qi - ki) * blk, s,
                              MASKED)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            out.append((m_new, alpha * l_prev + p.sum(axis=1, keepdims=True)))
        return tuple(out)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    stats = tuple((jnp.full((blk, 1), MASKED, jnp.float32),
                   jnp.zeros((blk, 1), jnp.float32)) for _ in masks)
    first = 0
    if window:
        # blocks wholly below the band are never visited
        lo, first = _band(qi, blk, window)
        stats = jax.lax.fori_loop(
            lo, first, lambda ki, stats: block(ki, stats, False, True), stats)
    stats = jax.lax.fori_loop(
        first, qi, lambda ki, stats: block(ki, stats, False), stats)
    stats = block(qi, stats, True)
    o_ref[...] = _merge(masks, [acc_ref[h] / l for h, (_, l) in
                                enumerate(stats)]).astype(o_ref.dtype)
    # a head's statistic is a column here and a row of lanes in HBM: one
    # [blk, 128] transpose, a head's value in its own lanes
    lse_t = _merge(masks, [jnp.broadcast_to(m + jnp.log(l), (blk, LANES))
                           for m, l in stats]).T
    for h in range(len(masks)):
        lse_ref[h:h + 1, :] = lse_t[h * head_dim:h * head_dim + 1, :]


def _vmem_limit(T: int, itemsize: int, operands: int, scratch: int) -> int:
    """Bytes the kernel may use: ``operands`` [T, 128] blocks of the input
    dtype, double-buffered, ``scratch`` bytes beside them, and room for the
    block-sized temporaries; never under the compiler's own default."""
    need = 2 * operands * T * LANES * itemsize + scratch + (12 << 20)
    return max(need, 32 << 20)


def _geometry(qkv, n_head: int):
    """B, T, D, head_dim, block, lane blocks, q blocks, heads a lane block."""
    B, T, width = qkv.shape
    D = width // 3
    hd, blk = D // n_head, block_for(T)
    return B, T, D, hd, blk, D // LANES, T // blk, LANES // hd


def _fwd(qkv, n_head: int, interpret: bool):
    B, T, D, hd, blk, nj, nq, hpb = _geometry(qkv, n_head)
    q_spec = pl.BlockSpec((None, blk, LANES), lambda b, j, i: (b, i, j))
    k_spec = pl.BlockSpec((None, T, LANES), lambda b, j, i: (b, 0, nj + j))
    v_spec = pl.BlockSpec((None, T, LANES),
                          lambda b, j, i: (b, 0, 2 * nj + j))
    with jax.named_scope("flash_attention_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(hd),
                              head_dim=hd),
            grid=(B, nj, nq),
            in_specs=[q_spec, k_spec, v_spec],
            out_specs=[
                pl.BlockSpec((None, blk, LANES), lambda b, j, i: (b, i, j)),
                pl.BlockSpec((None, None, None, hpb, blk),
                             lambda b, j, i: (b, j, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, T, D), qkv.dtype),
                jax.ShapeDtypeStruct((B, nj, nq, hpb, blk), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((hpb, blk, LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T, qkv.dtype.itemsize, 2, 0)),
            interpret=interpret,
            name="flash_attention_fwd",
        )(qkv, qkv, qkv)


def gqa_kernel_takes(T: int, head_dim: int, dtype, rep: int = 1) -> bool:
    """Whether :func:`flash_gqa_fwd` takes these operands: a head of 128
    is one lane block (any ``rep`` query heads a kv head), two heads of 64
    share one (``rep`` 1 only: a lane block's k and v are its own two
    heads'), whole blocks of rows, k and v of a lane block inside VMEM."""
    return ((head_dim == LANES or (head_dim == 64 and rep == 1))
            and kernel_takes(T, 1, LANES, dtype))


@functools.partial(jax.jit, static_argnames=("n_head", "interpret"))
def flash_gqa_fwd(q, k, v, n_head: int, interpret: bool = False):
    """The forward kernel alone, for a prefill from position 0 (no
    gradient): q ``[B, T, n_head * head_dim]``, k and v ``[B, T, KV *
    head_dim]``, all token-major as a projection writes them, head h
    reading kv head ``h // (n_head // KV)``; ``[B, T, n_head * head_dim]``
    in q's dtype (:func:`gqa_kernel_takes` says which shapes). The grid
    walks q's lane blocks (a head of 128, or two of 64 with ``KV ==
    n_head``; an odd count of those is padded with one head of zero lanes
    and its output sliced off) and a lane block's keys and values stay in
    VMEM under all of its query blocks (their index map does not move), so
    nothing is repeated in HBM. Causal from position 0; on the device the
    kernel is ``flash_gqa_fwd``."""
    B, T, width = q.shape
    hd = width // n_head
    pad = -width % LANES          # heads of 128: always 0
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (q, k, v))
    nj, hpb = (width + pad) // LANES, LANES // hd
    rep = nj // (k.shape[2] // LANES)
    blk, nq = block_for(T), T // block_for(T)
    kv_spec = pl.BlockSpec((None, T, LANES), lambda b, j, i: (b, 0, j // rep))
    with jax.named_scope("flash_gqa_fwd"):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(hd),
                              head_dim=hd),
            grid=(B, nj, nq),
            in_specs=[pl.BlockSpec((None, blk, LANES),
                                   lambda b, j, i: (b, i, j)),
                      kv_spec, kv_spec],
            out_specs=[
                pl.BlockSpec((None, blk, LANES), lambda b, j, i: (b, i, j)),
                pl.BlockSpec((None, None, None, hpb, blk),
                             lambda b, j, i: (b, j, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, T, width + pad), q.dtype),
                jax.ShapeDtypeStruct((B, nj, nq, hpb, blk), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((hpb, blk, LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T, q.dtype.itemsize, 2, 0)),
            interpret=interpret,
            name="flash_gqa_fwd",
        )(q, k, v)[0]
    return out[..., :width] if pad else out


# ---------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, out_ref,
                dq_ref, dkv_ref, di_ref, acc_ref, *, scale: float,
                head_dim: int):
    nq, _, blk = lse_ref.shape
    which = pl.program_id(2)
    masks = _head_masks(head_dim)

    @pl.when(which == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

        def di_block(i, _):
            # di = sum over a head's lanes of o * do, as a row of lanes
            od_t = (o_ref[_rows(i, blk), :].astype(jnp.float32)
                    * do_ref[_rows(i, blk), :].astype(jnp.float32)).T
            for h in range(len(masks)):
                di_ref[i, h:h + 1, :] = od_t[
                    h * head_dim:(h + 1) * head_dim].sum(axis=0,
                                                         keepdims=True)
            return 0

        jax.lax.fori_loop(0, nq, di_block, 0)

        def kv_block(ki, _):
            k, v = k_ref[_rows(ki, blk), :], v_ref[_rows(ki, blk), :]
            kv_heads = [(_own(mask, k), _own(mask, v)) for mask in masks]

            def q_block(qi, diagonal):
                """One q block for every head of the lane block (side by
                side in one loop body, as in the forward)."""
                q, do = q_ref[_rows(qi, blk), :], do_ref[_rows(qi, blk), :]
                dq = None
                for h, (k_h, v_h) in enumerate(kv_heads):
                    s_t = jax.lax.dot_general(
                        k_h, q, _NT, preferred_element_type=jnp.float32)
                    p_t = jnp.exp(s_t * scale - lse_ref[qi, h:h + 1, :])
                    if diagonal:
                        row = jax.lax.broadcasted_iota(jnp.int32, p_t.shape, 0)
                        col = jax.lax.broadcasted_iota(jnp.int32, p_t.shape, 1)
                        p_t = jnp.where(row <= col, p_t, 0.0)
                    acc_ref[1, h] += jnp.dot(
                        p_t.astype(do.dtype), do,
                        preferred_element_type=jnp.float32)
                    dp_t = jax.lax.dot_general(
                        v_h, do, _NT, preferred_element_type=jnp.float32)
                    ds_t = ((dp_t - di_ref[qi, h:h + 1, :]) * p_t
                            * scale).astype(q.dtype)
                    acc_ref[0, h] += jnp.dot(
                        ds_t, q, preferred_element_type=jnp.float32)
                    # k_h's other lanes are zero: each head adds to its own
                    dq_h = jax.lax.dot_general(
                        ds_t, k_h, _TN, preferred_element_type=jnp.float32)
                    dq = dq_h if dq is None else dq + dq_h
                dq_ref[_rows(qi, blk), :] += dq

            def below(qi, _):
                q_block(qi, False)
                return 0

            acc_ref[...] = jnp.zeros_like(acc_ref)
            q_block(ki, True)
            jax.lax.fori_loop(ki + 1, nq, below, 0)
            for n in range(2):           # dk, dv: each head's own lanes
                dkv_ref[n, _rows(ki, blk), :] = _merge(
                    masks, [acc_ref[n, h] for h in range(len(masks))]
                ).astype(dkv_ref.dtype)
            return 0

        jax.lax.fori_loop(0, nq, kv_block, 0)
        out_ref[...] = dq_ref[...].astype(out_ref.dtype)

    for n in range(2):
        @pl.when(which == n + 1)
        def _(n=n):
            out_ref[...] = dkv_ref[n]


def _bwd(qkv, o, lse, do, n_head: int, interpret: bool):
    B, T, _, hd, blk, nj, nq, hpb = _geometry(qkv, n_head)

    def cols(first):
        return pl.BlockSpec((None, T, LANES),
                            lambda b, j, w: (b, 0, first + j))

    scratch = T * LANES * (4 + 2 * qkv.dtype.itemsize)
    with jax.named_scope("flash_mha_bwd"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, scale=1.0 / math.sqrt(hd),
                              head_dim=hd),
            grid=(B, nj, 3),
            in_specs=[cols(0), cols(nj), cols(2 * nj), cols(0), cols(0),
                      pl.BlockSpec((None, None, nq, hpb, blk),
                                   lambda b, j, w: (b, j, 0, 0, 0))],
            out_specs=pl.BlockSpec((None, T, LANES),
                                   lambda b, j, w: (b, 0, w * nj + j)),
            out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
            scratch_shapes=[
                pltpu.VMEM((T, LANES), jnp.float32),             # dq
                pltpu.VMEM((2, T, LANES), qkv.dtype),            # dk, dv
                pltpu.VMEM((nq, hpb, blk), jnp.float32),         # di
                pltpu.VMEM((2, hpb, blk, LANES), jnp.float32),   # dk, dv acc
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T, qkv.dtype.itemsize, 6,
                                             scratch)),
            interpret=interpret,
            name="flash_mha_bwd",
        )(qkv, qkv, qkv, o, do, lse)


# ------------------------------------------------------------------- entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def flash_qkv(qkv, n_head: int, interpret: bool = False):
    """Causal attention of ``qkv [B, T, 3 * D]`` (a token's q, k, v rows
    side by side, ``n_head`` heads each, head h in lanes
    ``h * head_dim ..``): ``[B, T, D]`` in qkv's dtype, scores scaled by
    ``1 / sqrt(head_dim)``. :func:`kernel_takes` says which shapes."""
    return _fwd(qkv, n_head, interpret)[0]


def _flash_qkv_fwd(qkv, n_head, interpret):
    o, lse = _fwd(qkv, n_head, interpret)
    return o, (qkv, o, lse)


def _flash_qkv_bwd(n_head, interpret, res, do):
    qkv, o, lse = res
    return (_bwd(qkv, o, lse, do, n_head, interpret),)


flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


# ------------------------------------------- grouped queries, with a window
# The trainer's attention where q, k and v are projected apart (heads of
# 128, ``rep`` query heads a kv head) and a layer may see a window:
# ``flash_gqa`` below. Forward: ``_fwd_kernel`` as the prefill runs it
# (``flash_gqa_fwd``'s grid and index maps), with the statistics kept and
# the band's bounds. Backward: two kernels over the same transposed scores
# as ``_bwd_kernel``, split because a kv head's eight query heads do not fit
# VMEM side by side at T = 8,192: ``flash_gqa_dq`` walks a q block's band of
# kv blocks, ``flash_gqa_dkv`` a kv block's band of q blocks, one query head
# a grid step, and writes that head's share of dk and dv (the shares of a
# kv head's ``rep`` query heads are summed outside, ``[B, T, H x 128]`` once
# each). k and v are read through ``h // rep`` and never repeated in HBM.
# ``di = sum(o * do)`` is one fused pass outside, laid out like the
# statistics. Blocks wholly outside the band are never visited; only the
# diagonal block and the blocks that cross the band's lower edge are masked.

def _bwd_block(k, v, q, do, lse, di, scale, mask):
    """One (kv block, q block) pair on transposed scores: ``p^T`` and
    ``ds^T`` ``[kv rows, q rows]``; ``mask(row, col)`` or None."""
    s_t = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    p_t = jnp.exp(s_t * scale - lse)
    if mask is not None:
        row = jax.lax.broadcasted_iota(jnp.int32, p_t.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, p_t.shape, 1)
        p_t = jnp.where(mask(row, col), p_t, 0.0)
    dp_t = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return p_t, ((dp_t - di) * p_t * scale).astype(q.dtype)


def _pair_masks(blk: int, window: int):
    """The masks of the diagonal pair and of a pair ``gap`` blocks apart
    that crosses the band's edge, on transposed scores (row = key)."""
    def diagonal(row, col):
        seen = row <= col
        return seen & (col - row < window) if 0 < window < blk else seen

    def edge(gap):
        return lambda row, col: col - row < window - gap * blk

    return diagonal, edge


def _gqa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                   acc_ref, *, scale: float, window: int):
    blk = q_ref.shape[0]
    qi = pl.program_id(2)
    q, do, lse, di = q_ref[...], do_ref[...], lse_ref[...], di_ref[...]
    diagonal, edge = _pair_masks(blk, window)

    def pair(ki, mask):
        k, v = k_ref[_rows(ki, blk), :], v_ref[_rows(ki, blk), :]
        _, ds_t = _bwd_block(k, v, q, do, lse, di, scale, mask)
        acc_ref[...] += jax.lax.dot_general(
            ds_t, k, _TN, preferred_element_type=jnp.float32)

    def loop(lo, hi, mask_of):
        jax.lax.fori_loop(lo, hi,
                          lambda ki, _: pair(ki, mask_of(ki)) or 0, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    first = 0
    if window:
        lo, first = _band(qi, blk, window)
        loop(lo, first, lambda ki: edge(qi - ki))
    loop(first, qi, lambda ki: None)
    pair(qi, diagonal)
    dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _gqa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                    dv_ref, acc_ref, *, scale: float, window: int):
    blk = k_ref.shape[0]
    nq = lse_ref.shape[0]
    ki = pl.program_id(2)
    k, v = k_ref[...], v_ref[...]
    diagonal, edge = _pair_masks(blk, window)

    def pair(qi, mask):
        q, do = q_ref[_rows(qi, blk), :], do_ref[_rows(qi, blk), :]
        p_t, ds_t = _bwd_block(k, v, q, do, lse_ref[qi], di_ref[qi], scale,
                               mask)
        acc_ref[1] += jnp.dot(p_t.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        acc_ref[0] += jnp.dot(ds_t, q, preferred_element_type=jnp.float32)

    def loop(lo, hi, mask_of):
        jax.lax.fori_loop(lo, hi,
                          lambda qi, _: pair(qi, mask_of(qi)) or 0, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    pair(ki, diagonal)
    if window:
        # the last q block with a row that sees this block's last key, and
        # the last whose every row sees its every key
        hi = jnp.minimum((ki * blk + blk + window - 2) // blk, nq - 1)
        inner = jnp.clip(ki + window // blk - 1, ki, hi)
        loop(ki + 1, inner + 1, lambda qi: None)
        loop(inner + 1, hi + 1, lambda qi: edge(qi - ki))
    else:
        loop(ki + 1, nq, lambda qi: None)
    dk_ref[...] = acc_ref[0].astype(dk_ref.dtype)
    dv_ref[...] = acc_ref[1].astype(dv_ref.dtype)


def gqa_train_kernel_takes(T: int, head_dim: int, dtype) -> bool:
    """Whether :func:`flash_gqa` takes these operands: heads of 128 (one
    lane block a head, any ``rep``), whole blocks of rows, a lane block's
    whole-T operands inside VMEM."""
    return head_dim == LANES and kernel_takes(T, 1, LANES, dtype)


def _gqa_geometry(q, k, n_head: int):
    B, T, width = q.shape
    assert width == n_head * LANES and k.shape[2] % LANES == 0, (q.shape,
                                                                 k.shape)
    blk = block_for(T)
    return B, T, blk, T // blk, n_head // (k.shape[2] // LANES)


def _gqa_fwd(q, k, v, n_head: int, window: int, interpret: bool):
    B, T, blk, nq, rep = _gqa_geometry(q, k, n_head)
    kv_spec = pl.BlockSpec((None, T, LANES), lambda b, j, i: (b, 0, j // rep))
    with jax.named_scope("flash_gqa_lse"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(LANES),
                              head_dim=LANES, window=window),
            grid=(B, n_head, nq),
            in_specs=[pl.BlockSpec((None, blk, LANES),
                                   lambda b, j, i: (b, i, j)),
                      kv_spec, kv_spec],
            out_specs=[
                pl.BlockSpec((None, blk, LANES), lambda b, j, i: (b, i, j)),
                pl.BlockSpec((None, None, None, 1, blk),
                             lambda b, j, i: (b, j, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((B, n_head, nq, 1, blk), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((1, blk, LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(T, q.dtype.itemsize, 2, 0)),
            interpret=interpret,
            name="flash_gqa_lse",
        )(q, k, v)


def _gqa_bwd(q, k, v, o, lse, do, n_head: int, window: int, interpret: bool):
    B, T, blk, nq, rep = _gqa_geometry(q, k, n_head)
    scale = 1.0 / math.sqrt(LANES)
    with jax.named_scope("flash_gqa_di"):
        di = (o.astype(jnp.float32) * do.astype(jnp.float32)).reshape(
            B, nq, blk, n_head, LANES).sum(-1)
        di = di.transpose(0, 3, 1, 2)[:, :, :, None, :]   # like lse
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(T, q.dtype.itemsize, 2, 0))

    def block(b, j, i):
        return (b, i, j)

    def kv_block(b, j, i):
        return (b, i, j // rep)

    def stat_block(b, j, i):
        return (b, j, i, 0, 0)

    tile = pl.BlockSpec((None, blk, LANES), block)
    whole = pl.BlockSpec((None, T, LANES), lambda b, j, i: (b, 0, j))
    whole_kv = pl.BlockSpec((None, T, LANES),
                            lambda b, j, i: (b, 0, j // rep))
    stat = pl.BlockSpec((None, None, None, 1, blk), stat_block)
    stats = pl.BlockSpec((None, None, nq, 1, blk),
                         lambda b, j, i: (b, j, 0, 0, 0))
    with jax.named_scope("flash_gqa_dq"):
        dq = pl.pallas_call(
            functools.partial(_gqa_dq_kernel, scale=scale, window=window),
            grid=(B, n_head, nq),
            in_specs=[tile, whole_kv, whole_kv, tile, stat, stat],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((blk, LANES), jnp.float32)],
            compiler_params=params, interpret=interpret,
            name="flash_gqa_dq",
        )(q, k, v, do, lse, di)
    with jax.named_scope("flash_gqa_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_gqa_dkv_kernel, scale=scale, window=window),
            grid=(B, n_head, nq),
            in_specs=[whole, pl.BlockSpec((None, blk, LANES), kv_block),
                      pl.BlockSpec((None, blk, LANES), kv_block), whole,
                      stats, stats],
            out_specs=[tile, tile],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 2,
            scratch_shapes=[pltpu.VMEM((2, blk, LANES), jnp.float32)],
            compiler_params=params, interpret=interpret,
            name="flash_gqa_dkv",
        )(q, k, v, do, lse, di)

    def grouped(x):
        """A kv head's gradient: its ``rep`` query heads' shares."""
        x = x.reshape(B, T, n_head // rep, rep, LANES).astype(jnp.float32)
        return x.sum(3).reshape(k.shape).astype(k.dtype)

    return dq, grouped(dk), grouped(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_gqa(q, k, v, n_head: int, window: int = 0,
              interpret: bool = False):
    """Causal attention of q ``[B, T, n_head * 128]`` over k, v ``[B, T,
    KV * 128]``, all token-major as the projections write them, query head
    h reading kv head ``h // (n_head // KV)``; ``window`` > 0: query i sees
    keys ``i - window + 1 .. i`` only (0: every earlier key). ``[B, T,
    n_head * 128]`` in q's dtype, scores scaled by ``1 / sqrt(128)``;
    differentiable. :func:`gqa_train_kernel_takes` says which shapes. On
    the device: ``flash_gqa_lse``, ``flash_gqa_dq``, ``flash_gqa_dkv``."""
    return _gqa_fwd(q, k, v, n_head, window, interpret)[0]


def _flash_gqa_fwd(q, k, v, n_head, window, interpret):
    o, lse = _gqa_fwd(q, k, v, n_head, window, interpret)
    return o, (q, k, v, o, lse)


def _flash_gqa_bwd(n_head, window, interpret, res, do):
    return _gqa_bwd(*res, do, n_head, window, interpret)


flash_gqa.defvjp(_flash_gqa_fwd, _flash_gqa_bwd)
