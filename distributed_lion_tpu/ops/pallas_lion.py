"""Fused Pallas kernels for the Distributed Lion hot loop.

The reference's optimizer is the per-tensor Python loop SURVEY §3.1 flags as
the main bottleneck (~148 tensors × [sign → pack → all_gather → unpack ×W →
torch.mode → apply] per step; README.md:2 admits it is "currently slow").
Here the whole pytree is one flat vector and the step is two VMEM passes
(SURVEY §7 stage 6):

- :func:`fused_ballots` — one pass over (g, m): ``ballot = ±1 from
  b1*m + (1-b1)*g > 0`` as int8, ready for the on-fabric ``psum`` vote. No
  f32 intermediate ever reaches HBM.
- :func:`fused_apply` — one pass over (p, g, m, vote_total): weight decay,
  elected-sign application, and the momentum update together:
  ``p' = p*(1-lr*wd) - lr*sign(total>0)``; ``m' = b2*m + (1-b2)*g``.

Between the two sits the vote wire — ONE collective, or ``vote_buckets``
pipelined ones: the ``*_window`` entry points run the same kernels over a
static ``[start, start + length)`` window of shared flat buffers, so the
bucketed optimizer slices per-leaf views instead of materializing full flat
copies of params/grads/momentum, and bucket k's collective overlaps bucket
k−1's apply. The kernels are elementwise VPU work tiled (≤ROW_BLOCK, 128)
with dtype-uniform flat inputs; CPU tests run them in interpreter mode
(``interpret=True``).

Names on the device: each ``pallas_call`` sits directly inside a
``jax.named_scope`` and carries the same ``name=`` — ``lion_ballot``,
``lion_apply``, ``lion_stats`` (the window variants share them). A Mosaic
custom-call's HLO instruction is named after the innermost scope that
holds it, so a profiler trace shows ``lion_ballot.<n>`` / ``lion_apply.<n>``
instead of the enclosing function's name. Fixed strings: no leaf index or
step in them, so compile-cache entries do not depend on them.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_BLOCK = 512  # default rows per grid step → (512, 128) f32 blocks =
# 256 KiB. Every kernel below takes a ``row_block`` override (0 = this
# default): how a test of a few hundred coordinates drives a multi-step
# grid. Tile geometry is never a numerics knob: outputs are bit-identical
# at any row_block (pinned by tests/test_pallas_lion.py).
MIN_ROWS = 32    # min row granularity: covers the (8,128) f32, (16,128)
# bf16 and (32,128) int8 native tile shapes, so small bucket windows
# compile on hardware without padding all the way to a full ROW_BLOCK


def _resolve_row_block(row_block: int) -> int:
    if row_block == 0:
        return ROW_BLOCK
    if row_block < MIN_ROWS or row_block % MIN_ROWS:
        raise ValueError(
            f"row_block must be a positive multiple of {MIN_ROWS} "
            f"(the int8 native-tile sublane count), got {row_block}")
    return row_block


def _grid_rows(n: int, row_block: int = 0) -> tuple[int, int]:
    """(padded rows, rows per grid step) for an [n] flat operand. Large
    inputs tile at ``row_block`` (default ROW_BLOCK); small ones (per-leaf
    bucket windows) shrink the block to the input instead of zero-padding
    64K elements."""
    rb = _resolve_row_block(row_block)
    rows = max(1, math.ceil(n / LANES))
    rows = math.ceil(rows / MIN_ROWS) * MIN_ROWS
    block = min(rb, rows)
    return math.ceil(rows / block) * block, block


def _pad_to_grid(flat: jnp.ndarray, row_block: int = 0) -> tuple[jnp.ndarray, int]:
    """[n] → [rows, 128] zero-padded to the _grid_rows geometry."""
    n = flat.shape[0]
    rows, _ = _grid_rows(n, row_block)
    pad = rows * LANES - n
    return jnp.pad(flat, (0, pad)).reshape(rows, LANES), n


def _ballot_kernel(b1: float, g_ref, m_ref, out_ref):
    u = m_ref[:].astype(jnp.float32) * b1 + g_ref[:].astype(jnp.float32) * (1.0 - b1)
    out_ref[:] = jnp.where(u > 0, 1, -1).astype(jnp.int8)


def fused_ballots(
    g_flat: jnp.ndarray, m_flat: jnp.ndarray, b1: float, *,
    interpret: bool = False, row_block: int = 0
) -> jnp.ndarray:
    """[n] grads + momentum → [n] int8 ±1 ballots (ref :68-71 semantics:
    zero update votes −1, the ``> 0`` encoding)."""
    g2, n = _pad_to_grid(g_flat, row_block)
    m2, _ = _pad_to_grid(m_flat, row_block)
    rows, block = g2.shape[0], _grid_rows(n, row_block)[1]
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    with jax.named_scope("lion_ballot"):
        out = pl.pallas_call(
            functools.partial(_ballot_kernel, b1),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
            grid=(rows // block,),
            in_specs=[spec, spec],
            out_specs=spec,
            interpret=interpret,
            name="lion_ballot",
        )(g2, m2)
    return out.reshape(-1)[:n]


def _apply_kernel(wd: float, b2: float, lr_ref, p_ref, g_ref, m_ref, tot_ref,
                  p_out, m_out):
    lr = lr_ref[0]
    pdt = p_ref.dtype
    # elected sign: total > 0 → +1, ties/negative → −1 (tie rule SURVEY §2.3)
    s = jnp.where(tot_ref[:] > 0, 1.0, -1.0)
    p32 = p_ref[:].astype(jnp.float32)
    p_out[:] = (p32 * (1.0 - lr * wd) - lr * s).astype(pdt)
    m_out[:] = (
        m_ref[:].astype(jnp.float32) * b2 + g_ref[:].astype(jnp.float32) * (1.0 - b2)
    ).astype(m_ref.dtype)


def fused_apply(
    p_flat: jnp.ndarray,
    g_flat: jnp.ndarray,
    m_flat: jnp.ndarray,
    vote_total: jnp.ndarray,
    lr,
    wd: float,
    b2: float,
    *,
    interpret: bool = False,
    row_block: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused pass: decay + elected update + momentum (ref :64, :91-96)."""
    p2, n = _pad_to_grid(p_flat, row_block)
    g2, _ = _pad_to_grid(g_flat, row_block)
    m2, _ = _pad_to_grid(m_flat, row_block)
    t2, _ = _pad_to_grid(vote_total.astype(jnp.int32), row_block)
    rows, blk = p2.shape[0], _grid_rows(n, row_block)[1]
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    block = lambda: pl.BlockSpec((blk, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    with jax.named_scope("lion_apply"):
        p_new, m_new = pl.pallas_call(
            functools.partial(_apply_kernel, wd, b2),
            out_shape=(
                jax.ShapeDtypeStruct((rows, LANES), p_flat.dtype),
                jax.ShapeDtypeStruct((rows, LANES), m_flat.dtype),
            ),
            grid=(rows // blk,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # lr scalar
                block(), block(), block(), block(),
            ],
            out_specs=(block(), block()),
            interpret=interpret,
            name="lion_apply",
        )(lr_arr, p2, g2, m2, t2)
    return p_new.reshape(-1)[:n], m_new.reshape(-1)[:n]


def fused_ballots_window(
    g_flat: jnp.ndarray,
    m_flat: jnp.ndarray,
    b1: float,
    *,
    start: int,
    length: int,
    interpret: bool = False,
    row_block: int = 0,
) -> jnp.ndarray:
    """Ballots for the ``[start, start + length)`` window of shared flat
    (g, m) buffers — the per-bucket entry point of the pipelined optimizer
    (optim.distributed_lion). The window is sliced with static bounds, so
    XLA fuses the slice into the kernel's operand pass instead of the old
    path's full-pytree ``jnp.concatenate`` materialization."""
    g_w = jax.lax.slice(g_flat, (start,), (start + length,))
    m_w = jax.lax.slice(m_flat, (start,), (start + length,))
    return fused_ballots(g_w, m_w, b1, interpret=interpret,
                         row_block=row_block)


def fused_apply_window(
    p_flat: jnp.ndarray,
    g_flat: jnp.ndarray,
    m_flat: jnp.ndarray,
    bucket_total: jnp.ndarray,
    lr,
    wd: float,
    b2: float,
    *,
    start: int,
    length: int,
    total_offset: int = 0,
    interpret: bool = False,
    row_block: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused decay + elected update + momentum for one window of shared flat
    (p, g, m) buffers against ``bucket_total[total_offset :
    total_offset + length]`` (a single bucket's collective result). Returns
    the window's (p_new, m_new) only — the caller reassembles leaves, and a
    window depends on nothing but ITS bucket's wire, which is what lets the
    bucket-k collective run while bucket k−1 applies."""
    p_w = jax.lax.slice(p_flat, (start,), (start + length,))
    g_w = jax.lax.slice(g_flat, (start,), (start + length,))
    m_w = jax.lax.slice(m_flat, (start,), (start + length,))
    t_w = jax.lax.slice(bucket_total, (total_offset,),
                        (total_offset + length,))
    return fused_apply(p_w, g_w, m_w, t_w, lr, wd, b2, interpret=interpret,
                       row_block=row_block)


def _stats_kernel(w: int, nbins: int, ballot_ref, tot_ref, mask_ref, out_ref):
    """Per-bucket vote-health tallies, accumulated across grid steps into a
    single resident VMEM tile (constant output index map → the buffer
    persists between iterations; initialized at program_id 0). Row 0 lanes
    [0, nbins) hold the margin bincount, row 1 lane 0 the local-ballot
    disagreement count. Binning must match telemetry.margin_hist exactly
    (pinned by test): bin = min(|total| * nbins // w, nbins − 1)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    t = tot_ref[:].astype(jnp.int32)
    m = mask_ref[:] > 0  # zero-padded grid tail must not count
    binidx = jnp.minimum((jnp.abs(t) * nbins) // w, nbins - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    upd = jnp.zeros(out_ref.shape, jnp.int32)
    for b in range(nbins):  # static unroll: nbins full-tile VPU reductions
        cnt = jnp.sum(jnp.where(m & (binidx == b), 1, 0))
        upd = upd + jnp.where((row == 0) & (lane == b), cnt, 0)
    # widen the int8 ballots before comparing: Mosaic on v5e has no packed
    # int8 vector compare (arith.cmpi on vector<8x128x4xi8> is refused)
    b = ballot_ref[:].astype(jnp.int32)
    dis = jnp.sum(jnp.where(m & ((b > 0) != (t > 0)), 1, 0))
    upd = upd + jnp.where((row == 1) & (lane == 0), dis, 0)
    out_ref[...] = out_ref[...] + upd


def bucket_vote_stats(
    ballot: jnp.ndarray,
    total: jnp.ndarray,
    world: int,
    nbins: int,
    *,
    interpret: bool = False,
    row_block: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One bucket's vote-health tallies from its int8 ballots and the
    bucket's collective result: ``(margin bincount i32[nbins], local
    disagreement count i32)`` — the per-bucket telemetry emitted by the
    window-kernel optimizer path (optim.distributed_lion telemetry mode).
    Reads arrays the bucket pipeline already has in VMEM; never touches
    what is elected. Margin bins are only meaningful when ``total`` is an
    exact tally (the caller zeroes the histogram for ±1-proxy wires)."""
    b2, n = _pad_to_grid(ballot.astype(jnp.int8), row_block)
    t2, _ = _pad_to_grid(total.astype(jnp.int32), row_block)
    m2, _ = _pad_to_grid(jnp.ones((n,), jnp.int32), row_block)
    rows, block = b2.shape[0], _grid_rows(n, row_block)[1]
    spec = lambda: pl.BlockSpec((block, LANES), lambda i: (i, 0),  # noqa: E731
                                memory_space=pltpu.VMEM)
    with jax.named_scope("lion_stats"):
        out = pl.pallas_call(
            functools.partial(_stats_kernel, world, nbins),
            out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.int32),
            grid=(rows // block,),
            in_specs=[spec(), spec(), spec()],
            out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="lion_stats",
        )(b2, t2, m2)
    return out[0, :nbins], out[1, 0]


def pallas_available() -> bool:
    return jax.default_backend() == "tpu"


def resolve_kernel_mode(kernel: str) -> Optional[bool]:
    """'auto' → pallas on TPU, XLA elsewhere; 'pallas' forces (interpreted on
    CPU — for tests); 'xla' disables. Returns interpret flag or None for
    the XLA path."""
    if kernel == "xla":
        return None
    if kernel == "pallas":
        return not pallas_available()
    if kernel == "auto":
        return False if pallas_available() else None
    raise ValueError(f"unknown kernel mode {kernel!r}")
