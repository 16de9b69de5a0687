"""The tied head and the softmax cross-entropy as one Mosaic kernel pair.

``hidden [N, d]`` against the head as it lies (``wte [V, d]``, rows are
vocabulary entries) with ``labels [N]``: a row's loss needs one float32 (its
log-sum-exp), its label's logit and its argmax, so the logits ``[N, V]``
are formed a tile at a time on the MXU (operands in hidden's dtype, float32
accumulation: what ``jnp.einsum(..., preferred_element_type=float32)``
compiles to) and never leave VMEM, forward or backward. At GPT-2's
vocabulary a microbatch of 20 x 1,024 rows had two ``f32[20, 1023, 50257]``
tensors of 4.11 GB and seven passes over them in HBM (ISSUE 29).

Tiles are transposed: ``S^T = wte_tile @ hidden_block^T`` is ``[tv, tn]``
(vocabulary on sublanes, rows on lanes), so a row's statistic is a lane: it
broadcasts over the tile for free, reduces over the vocabulary elementwise
from vreg to vreg, and lies dense in HBM as ``[1, N]`` (the backward of
``ops/pallas_flash_attn`` works on transposed scores for the same reason).
``V`` need not divide by the vocabulary tile: the last tile is a partial
block, its rows past ``valid_v`` are masked by index (and zeroed where a
product would contract over them); no padded or transposed copy of the
head is made.

Forward, ``fused_xent_fwd``: grid ``(row blocks, vocab tiles)``; a row
block's hidden states stay in VMEM while the vocabulary streams past;
running max, sum of exponentials and first-index argmax are carried a row.
Outputs: log-sum-exp and argmax, ``[1, N]`` each. The label's logit is one
gathered row of the head a token and a 768-long product, left to XLA.

Backward, ``fused_xent_bwd``: residuals are hidden, head, labels and the
log-sum-exp. Grid ``(row groups, vocab tiles, row blocks)``: a vocab tile
of the head stays in VMEM while a group's row blocks stream past; a step
recomputes its tile of logits, forms ``dlogits = (exp(logits - lse) -
onehot) * g`` in VMEM, rounds it to hidden's dtype for the MXU (the
compiled dense step's ``dot`` takes its float32 cotangent at default
precision: one bf16 pass) and adds ``dlogits^T @ hidden`` into the head
tile's float32 gradient (the output block, resident over the row blocks)
and ``dlogits @ wte_tile`` into the group's float32 ``dh`` accumulator, a
VMEM scratch of ``group rows x d`` that lives through the whole group
(:data:`DH_VMEM_BYTES`); ``dh`` leaves in hidden's dtype during the last
vocab tile. Neither accumulator is read-modify-written in HBM and
``dlogits`` never exists there. More rows than one group holds make more
groups, each with its own partial head gradient ``[groups, V, d]`` that
XLA sums. Rows are padded to a whole row block and no further, in both
directions: where the blocks do not fill the groups evenly the last group
is short, and its missing grid steps fetch and compute nothing.

The cut is taken from the call's shape (:func:`tiles_for`,
:func:`row_groups`): at d 768, 1,024 x 512 tiles, two groups of ten blocks
at 20 x 1,024 rows and one of four at 4 x 1,024; at d 2,304 (2 x 8,192
rows, V 24,576), 512 x 512 tiles and four groups of eight blocks.

Names on the device: ``fused_xent_fwd`` and ``fused_xent_bwd`` (``name=``
and the innermost ``jax.named_scope``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MASKED = -1e30            # a masked column's logit; also the running max's start
# the float32 dh accumulator of one row group: eight 512-row blocks at d 2,304
# (37.7 MB: cell 10's 32 blocks are four full groups), twelve 1,024-row blocks
# at d 768 (cell 1's twenty, 62.9 MB, stay two groups of ten)
DH_VMEM_BYTES = 40 << 20
# multiply-adds of one product in a backward grid step (tn x tv x d) up to
# which the step ran at the MXU's pace: 512 x 512 x 2,304 (tiles_for)
STEP_MACS = 512 * 512 * 2304

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiles_for(n: int, v: int, d: int) -> tuple[int, int]:
    """(rows a block, vocabulary entries a tile): 1,024 x 512, the larger
    of the two halved until a backward grid step's ``tn x tv x d`` is inside
    :data:`STEP_MACS`. Forward / backward of one call on the chip
    (``scripts/xent_microbench.py``; the backward holds three products).

    20 x 1,024 rows, V 50,257, d 768, a product at the MXU's peak 8.03 ms
    (my chip run, PR 29): 1024x512 10.06 / 25.20 ms, 1024x1024 10.21 /
    25.22, 512x1024 10.53 / 25.45, 512x512 10.77 / 25.70, 2048x512 9.76 /
    38.74, 1024x2048 10.10 / 34.30.

    16,384 rows, V 24,576, d 2,304, a product at the peak 9.42 ms, groups x
    row blocks a group after the tiles (my chip runs, PR 49): 512x512 4x8
    10.98 / 29.82 (2x16 10.97 / 29.14), 1024x256 4x4 10.92 / 29.83, 512x256
    4x8 11.31 / 30.23, 256x512 4x16 11.56 / 30.24, 1024x128 4x4 11.25 /
    30.32, 256x256 4x16 12.82 / 31.18, 512x128 4x8 11.96 / 31.60; 1024x512
    4x4 10.73 / 44.69 (2x8 43.90; 3+3+3+3+3+1 48.63; PR 48's 6x3 over 18
    blocks, two of them padding, 12.21 / 51.11), 512x1024 4x8 10.89 /
    47.74, 2048x256 4x2 10.71 / 48.37.

    The forward is flat everywhere. The backward runs at 90-97% of the peak
    up to a step of 0.6 G multiply-adds and at 58-64% from 1.2 G, at either
    width and whichever of the three factors makes the step large (at d 768
    1024x1024, 0.8 G, still held and 2048x512, 0.8 G, did not): a step's
    float32 product results and ``[tv, tn]`` temporaries outgrow what the
    compiler keeps close."""
    tn, tv = min(1024, _round_up(n, LANES)), min(512, _round_up(v, LANES))

    def halves(t):
        return t % (2 * LANES) == 0

    while tn * tv * d > STEP_MACS and (halves(tn) or halves(tv)):
        if halves(tn) and (tn >= tv or not halves(tv)):
            tn //= 2
        else:
            tv //= 2
    return tn, tv


def row_groups(n: int, d: int, tn: int) -> tuple[int, int]:
    """(groups, row blocks a group) for ``n`` rows: as few groups as keep a
    group's float32 ``dh`` inside :data:`DH_VMEM_BYTES`, evenly filled.
    Where ``groups x blocks`` passes the ``cdiv(n, tn)`` row blocks there
    are, the last group is short: the backward skips its missing steps
    (nothing fetched, nothing computed), so no rows exist for the groups'
    sake."""
    blocks = pl.cdiv(n, tn)
    groups = pl.cdiv(blocks, max(1, DH_VMEM_BYTES // (tn * d * 4)))
    return groups, pl.cdiv(blocks, groups)


def kernel_takes(d: int, dtype) -> bool:
    """Whether the kernels take ``hidden [N, d]`` of this dtype as it lies:
    whole lanes of the contraction and the MXU's operand type (N and V are
    free: rows are padded to a block, the vocabulary's last tile is masked)."""
    return d % LANES == 0 and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)


# ----------------------------------------------------------------- forward
def _fwd_kernel(h_ref, w_ref, lse_ref, idx_ref, m_ref, l_ref, *,
                valid_v: int):
    tv = w_ref.shape[0]
    j, last = pl.program_id(1), pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    def tile(partial):
        s = jax.lax.dot_general(w_ref[...], h_ref[...], _NT,
                                preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if partial:
            s = jnp.where(row < valid_v - j * tv, s, MASKED)
        tile_max = s.max(axis=0, keepdims=True)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, tile_max)
        l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                      + jnp.exp(s - m_new).sum(axis=0, keepdims=True))
        m_ref[...] = m_new
        # the first index of the tile's max; a later tile wins only if
        # strictly larger: dense argmax's lowest-index tie rule
        first = jnp.where(s == tile_max, row, tv).min(axis=0, keepdims=True)
        idx_ref[...] = jnp.where(tile_max > m_prev, first + j * tv,
                                 idx_ref[...])

    pl.when(j < last)(lambda: tile(False))

    @pl.when(j == last)
    def _():
        tile(True)
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _fwd(h, w, valid_v: int, tiles, interpret: bool):
    """``h [Np, d]`` (Np whole row blocks), ``w [V, d]`` -> lse, argmax,
    ``[1, Np]`` each."""
    (n, d), (tn, tv) = h.shape, tiles
    stat = pl.BlockSpec((1, tn), lambda i, j: (0, i))
    with jax.named_scope("fused_xent_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, valid_v=valid_v),
            grid=(n // tn, pl.cdiv(valid_v, tv)),
            in_specs=[pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
                      pl.BlockSpec((tv, d), lambda i, j: (j, 0))],
            out_specs=[stat, stat],
            out_shape=[jax.ShapeDtypeStruct((1, n), jnp.float32),
                       jax.ShapeDtypeStruct((1, n), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((1, tn), jnp.float32),
                            pltpu.VMEM((1, tn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(tn, tv, d, h.dtype.itemsize, 0)),
            interpret=interpret,
            name="fused_xent_fwd",
        )(h, w)


# ---------------------------------------------------------------- backward
def _bwd_kernel(w_ref, h_ref, lse_ref, lab_ref, g_ref, dw_ref, dh_ref,
                acc_ref, *, valid_v: int, blocks: int | None):
    """``blocks``: the row blocks there are, where the last group is short
    of them (None: every group is full and no step asks)."""
    tv, tn = w_ref.shape[0], h_ref.shape[0]
    j, i = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(1) - 1
    rows = pl.ds(pl.multiple_of(i * tn, tn), tn)

    def live(cond):
        if blocks is None:
            return cond
        return cond & (pl.program_id(0) * pl.num_programs(2) + i < blocks)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live(j == 0))
    def _():
        acc_ref[rows, :] = jnp.zeros((tn, acc_ref.shape[1]), jnp.float32)

    def tile(partial):
        w, h = w_ref[...], h_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, (tv, tn), 0)
        if partial:
            # a partial block's rows past valid_v hold whatever the buffer
            # held: they may not reach a product that contracts over them
            w = jnp.where(jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
                          < valid_v - j * tv, w, jnp.zeros_like(w))
        s = jax.lax.dot_general(w, h, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse_ref[...])
        if partial:
            p = jnp.where(row < valid_v - j * tv, p, 0.0)
        dl = (jnp.where(row == lab_ref[...] - j * tv, p - 1.0, p)
              * g_ref[...]).astype(h.dtype)
        dw_ref[...] += jnp.dot(dl, h, preferred_element_type=jnp.float32)
        acc_ref[rows, :] += jax.lax.dot_general(
            dl, w, _TN, preferred_element_type=jnp.float32)

    pl.when(live(j < last))(lambda: tile(False))

    @pl.when(live(j == last))
    def _():
        tile(True)
        dh_ref[...] = acc_ref[rows, :].astype(dh_ref.dtype)


def _bwd(h, w, lse, labels, g, valid_v: int, tiles, interpret: bool):
    """-> ``dh [Np, d]`` in h's dtype, ``dw [groups, Vc, d]`` float32 (Vc:
    the rows of the head the vocabulary's tiles cover)."""
    (n, d), (tn, tv) = h.shape, tiles
    groups, per_group = row_groups(n, d, tn)
    nj, blocks = pl.cdiv(valid_v, tv), n // tn
    assert n == blocks * tn, (n, tn)
    short = groups * per_group > blocks

    def there(block):
        # a short last group's missing steps stay on the last block there
        # is: an index that does not move fetches and writes back nothing
        return jnp.minimum(block, blocks - 1) if short else block

    stat = pl.BlockSpec((1, tn), lambda s, j, i: (0, there(s * per_group + i)))
    with jax.named_scope("fused_xent_bwd"):
        dw, dh = pl.pallas_call(
            functools.partial(_bwd_kernel, valid_v=valid_v,
                              blocks=blocks if short else None),
            grid=(groups, nj, per_group),
            in_specs=[pl.BlockSpec((tv, d), lambda s, j, i: (j, 0)),
                      pl.BlockSpec((tn, d), lambda s, j, i: (
                          there(s * per_group + i), 0)),
                      stat, stat, stat],
            out_specs=[
                pl.BlockSpec((None, tv, d), lambda s, j, i: (s, j, 0)),
                # dh's block is written during the last vocab tile only:
                # until then the index stays on the group's first block,
                # which is not written back before its own last-tile step
                pl.BlockSpec((tn, d), lambda s, j, i: (there(
                    s * per_group + jnp.where(j == nj - 1, i, 0)), 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((groups, min(w.shape[0], nj * tv), d),
                                     jnp.float32),
                jax.ShapeDtypeStruct((n, d), h.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((per_group * tn, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(
                    tn, tv, d, h.dtype.itemsize, per_group * tn * d * 4)),
            interpret=interpret,
            name="fused_xent_bwd",
        )(w, h, lse, labels, g)
    return dh, dw


def _vmem_limit(tn: int, tv: int, d: int, itemsize: int, scratch: int) -> int:
    """Bytes a kernel may use: its double-buffered blocks (a head tile and
    its float32 gradient, a row block and its dh), ``scratch`` beside them
    and room for the ``[tv, tn]`` float32 temporaries of a step."""
    blocks = 2 * (tv * d * (itemsize + 4) + 2 * tn * d * itemsize)
    return max(blocks + scratch + 6 * tv * tn * 4 + (4 << 20), 32 << 20)


# ------------------------------------------------------------------- entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_xent(hidden, wte, labels, valid_v: int = 0, tiles=None,
               interpret: bool = False):
    """Per-row cross-entropy of ``hidden [N, d] @ wte[:valid_v].T`` against
    ``labels [N]``: ``(nll [N] float32, argmax [N] int32)``. ``wte [V, d]``
    is read in hidden's dtype (cast it before the call, as the dense path
    does); ``valid_v`` (0: all of V) marks the rows past it as padding;
    ``tiles`` overrides :func:`tiles_for`."""
    return _fused_fwd(hidden, wte, labels, valid_v, tiles, interpret)[0]


def _padded(hidden, wte, labels, valid_v, tiles):
    n, v = hidden.shape[0], wte.shape[0]
    valid_v = valid_v if valid_v > 0 else v
    if valid_v > v:
        raise ValueError(f"valid_v {valid_v} > head rows {v}")
    tn, tv = tiles or tiles_for(n, valid_v, hidden.shape[1])
    pad = _round_up(n, tn) - n
    return (jnp.pad(hidden, ((0, pad), (0, 0))),
            jnp.pad(labels.astype(jnp.int32), (0, pad))[None, :],
            valid_v, (tn, tv))


def _fused_fwd(hidden, wte, labels, valid_v, tiles, interpret):
    n = hidden.shape[0]
    h, lab, valid, tiles = _padded(hidden, wte, labels, valid_v, tiles)
    lse, idx = _fwd(h, wte, valid, tiles, interpret)
    lse, idx = lse[0, :n], idx[0, :n]
    label_logit = jnp.einsum("nd,nd->n", hidden, wte[labels],
                             preferred_element_type=jnp.float32)
    return (lse - label_logit, idx), (hidden, wte, labels, lse)


def _fused_bwd(valid_v, tiles, interpret, res, cts):
    hidden, wte, labels, lse = res
    n = hidden.shape[0]
    h, lab, valid, tiles = _padded(hidden, wte, labels, valid_v, tiles)
    pad = h.shape[0] - n

    def row(x):      # a padded row's g is 0: no gradient, whatever its lse
        return jnp.pad(x.astype(jnp.float32), (0, pad))[None, :]

    dh, dw = _bwd(h, wte, row(lse), lab, row(cts[0]), valid, tiles, interpret)
    dw = dw.sum(axis=0) if dw.shape[0] > 1 else dw[0]
    dw = jnp.pad(dw, ((0, wte.shape[0] - dw.shape[0]), (0, 0)))
    return dh[:n], dw.astype(wte.dtype), None


fused_xent.defvjp(_fused_fwd, _fused_bwd)
