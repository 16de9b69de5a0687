"""Fused Pallas kernels for the Distributed Lion hot loop.

The reference's optimizer is the per-tensor Python loop SURVEY §3.1 flags as
the main bottleneck (~148 tensors × [sign → pack → all_gather → unpack ×W →
torch.mode → apply] per step; README.md:2 admits it is "currently slow").
Here the step is two VMEM passes over every leaf (SURVEY §7 stage 6):

- ballots — one pass over (g, m): ``ballot = ±1 from b1*m + (1-b1)*g > 0``
  as int8, ready for the vote wire. No f32 intermediate ever reaches HBM.
- apply — one pass over (p, g, m, verdict): weight decay, elected-sign
  application, and the momentum update together:
  ``p' = p*(1-lr*wd) - lr*sign(verdict>0)``; ``m' = b2*m + (1-b2)*g``.

**A leaf is read and written where it lies.** On a TPU an ``f32[R, C]``
array lives in (8, 128) tiles, so ``[R, C] -> [R*C]`` and back is a real
copy whenever ``C`` is not 128, a window of the flat view that starts or
ends off a tile is a real slice, and none of it fuses into a Mosaic custom
call's operands. The leaf-shaped entries (:func:`leaf_ballots`,
:func:`leaf_apply`) therefore take ``p``, ``g``, ``m`` in the leaf's own
``[rows, C]`` shape with ``(row block, 128)`` BlockSpecs over a run of whole
rows (a *window*: the ragged last block of a leaf is masked by Pallas), and
``leaf_apply`` writes ``p'`` and ``m'`` into its own operands
(``input_output_aliases``): a second window's call takes the first's
outputs. The ballots of a window leave as ``int8[C/128, rows, 128]``: lane
block ``c // 128`` major, row minor, which flattens to a vector for free
and is the same index map the apply kernel reads the verdict by, one byte a
coordinate. Which coordinate sits where in a bucket's ballot vector is the
optimizer's own matter (:func:`leaf_layout`; an election is per coordinate
and every worker runs the same program), exactly as the planar bit order
is the codec's. What does not fit (1-D leaves, a last dimension that is not
a multiple of 128 lanes, fewer than ``MIN_ROWS`` rows) goes through the
flat entries (:func:`fused_ballots`, :func:`fused_apply`) as ONE
concatenated vector a step. The choice reads a leaf's shape and nothing
else. CPU tests run the kernels in interpreter mode (``interpret=True``).

Names on the device: each ``pallas_call`` sits directly inside a
``jax.named_scope`` and carries the same ``name=`` — ``lion_ballot``,
``lion_apply``, ``lion_stats`` (the leaf-shaped and flat entries share
them). A Mosaic custom-call's HLO instruction is named after the innermost
scope that holds it, so a profiler trace shows ``lion_ballot.<n>`` /
``lion_apply.<n>`` instead of the enclosing function's name. Fixed strings:
no leaf index or step in them, so compile-cache entries do not depend on
them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

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
# bf16 and (32,128) int8 native tile shapes, so a small flat operand
# compiles on hardware without padding all the way to a full ROW_BLOCK


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
    inputs tile at ``row_block`` (default ROW_BLOCK); small ones (a pool of
    a few biases) shrink the block to the input instead of zero-padding 64K
    elements."""
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
    # elected sign: total > 0 → +1, ties/negative → −1 (tie rule SURVEY §2.3).
    # Widened first: the verdict arrives at one byte a coordinate and
    # Mosaic on v5e has no packed int8 vector compare.
    s = jnp.where(tot_ref[:].astype(jnp.int32) > 0, 1.0, -1.0)
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
    """One fused pass: decay + elected update + momentum (ref :64, :91-96).
    ``vote_total`` is any integer dtype (int8 from the optimizer); only its
    sign is read."""
    p2, n = _pad_to_grid(p_flat, row_block)
    g2, _ = _pad_to_grid(g_flat, row_block)
    m2, _ = _pad_to_grid(m_flat, row_block)
    t2, _ = _pad_to_grid(vote_total, row_block)
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


def _window_specs(rows: tuple[int, int], block: int):
    """BlockSpecs of one window ``rows = (r0, r1)`` of an ``[R, C]`` leaf on
    a ``(C/128, row blocks)`` grid: the leaf's own ``(block, 128)`` tiles
    from the window's first block on, and the window's
    ``int8[C/128, r1 - r0, 128]`` ballot / verdict tiles."""
    first = rows[0] // block
    leaf = lambda: pl.BlockSpec(  # noqa: E731
        (block, LANES), lambda j, i: (first + i, j), memory_space=pltpu.VMEM)
    tiles = pl.BlockSpec((None, block, LANES), lambda j, i: (j, i, 0),
                         memory_space=pltpu.VMEM)
    return leaf, tiles


def leaf_ballots(
    g: jnp.ndarray, m: jnp.ndarray, b1: float, *, rows: tuple[int, int],
    block: int, interpret: bool = False,
) -> jnp.ndarray:
    """Ballots of rows ``[r0, r1)`` of a leaf ``[R, C]`` (``C`` a multiple of
    128) read where it lies → ``int8[C/128, r1 - r0, 128]``, lane block
    major. ``r0`` is a multiple of ``block``; a window that is not a whole
    number of blocks ends with its leaf (:func:`leaf_layout`)."""
    lanes, n_rows = g.shape[1] // LANES, rows[1] - rows[0]
    leaf, tiles = _window_specs(rows, block)
    with jax.named_scope("lion_ballot"):
        return pl.pallas_call(
            functools.partial(_ballot_kernel, b1),
            out_shape=jax.ShapeDtypeStruct((lanes, n_rows, LANES), jnp.int8),
            grid=(lanes, pl.cdiv(n_rows, block)),
            in_specs=[leaf(), leaf()],
            out_specs=tiles,
            interpret=interpret,
            name="lion_ballot",
        )(g, m)


def leaf_apply(
    p: jnp.ndarray, g: jnp.ndarray, m: jnp.ndarray, verdict: jnp.ndarray,
    lr, wd: float, b2: float, *, rows: tuple[int, int], block: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decay + elected update + momentum over rows ``[r0, r1)`` of a leaf
    ``[R, C]``, written into ``p`` and ``m`` themselves (aliased: every
    other row passes through). ``verdict`` is the window's
    ``int8[C/128, r1 - r0, 128]`` election in :func:`leaf_ballots`' order."""
    lanes, n_rows = p.shape[1] // LANES, rows[1] - rows[0]
    leaf, tiles = _window_specs(rows, block)
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    with jax.named_scope("lion_apply"):
        return pl.pallas_call(
            functools.partial(_apply_kernel, wd, b2),
            out_shape=(jax.ShapeDtypeStruct(p.shape, p.dtype),
                       jax.ShapeDtypeStruct(m.shape, m.dtype)),
            grid=(lanes, pl.cdiv(n_rows, block)),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # lr scalar
                leaf(), leaf(), leaf(), tiles,
            ],
            out_specs=(leaf(), leaf()),
            input_output_aliases={1: 0, 3: 1},
            interpret=interpret,
            name="lion_apply",
        )(lr_arr, p, g, m, verdict)


@dataclasses.dataclass(frozen=True)
class Piece:
    """One kernel call's share of the ballot vector: rows ``[r0, r1)`` of
    in-place leaf ``leaf`` in blocks of ``block`` rows, or (``leaf`` −1) the
    pool of every leaf that goes through the flat path."""
    leaf: int
    r0: int
    r1: int
    block: int
    size: int


@dataclasses.dataclass(frozen=True)
class LeafLayout:
    """Where every coordinate of a pytree sits in the step's ballot vector
    (:func:`leaf_layout`). ``buckets[k]`` lists bucket k's ``(piece, lo,
    hi)`` parts in wire order; ``verdicts[piece]`` the ``(bucket, offset in
    the bucket, length)`` parts of a piece's election in the piece's own
    order; ``apply_at[k]`` the pieces whose last part bucket k elects."""
    shapes: tuple
    in_place: tuple
    pooled: tuple
    pieces: tuple
    buckets: tuple
    verdicts: tuple
    apply_at: tuple

    @property
    def calls(self) -> int:
        """Kernel calls a step: one ballot and one apply a piece."""
        return 2 * len(self.pieces)

    def line(self) -> str:
        """The trainer's ``[setup] lion:`` line."""
        total = sum(math.prod(s) for s in self.shapes)
        inside = sum(math.prod(self.shapes[i]) for i in self.in_place)
        return (f"[setup] lion: {len(self.in_place)} leaves in place "
                f"({100.0 * inside / max(total, 1):.1f}% of coordinates), "
                f"{len(self.pooled)} through the flat path, "
                f"{self.calls} kernel calls a step")


def takes_leaf_in_place(shape: Sequence[int]) -> bool:
    """Whether the kernels read a leaf of this shape where it lies: whole
    128-lane rows, ``MIN_ROWS`` of them or more. Read from the shape alone."""
    return (len(shape) >= 2 and shape[-1] > 0 and shape[-1] % LANES == 0
            and math.prod(shape[:-1]) >= MIN_ROWS)


def _stored_middle_first(shape: Sequence[int]) -> bool:
    """A 3-D leaf whose middle dimension is under a sublane tile (GPT-2's
    fused ``qkv`` weight, ``[768, 3, 768]``): the chip stores it with that
    dimension outermost (``{2,0,1}``: padding 3 rows to 8 would cost 2.7
    times the memory), so its whole-row view is ``[B, A, C] -> [B*A, C]``
    and any other is a relayout."""
    return len(shape) == 3 and shape[1] < 8


def rows_view(leaf: jnp.ndarray) -> jnp.ndarray:
    """An in-place leaf as the ``[rows, C]`` matrix the kernels take: the
    identity on a matrix, and for more dimensions the collapse that the
    chip's layout of that shape makes a bitcast."""
    if _stored_middle_first(leaf.shape):
        leaf = leaf.transpose(1, 0, 2)
    return leaf.reshape(-1, leaf.shape[-1])


def from_rows_view(rows: jnp.ndarray, shape: Sequence[int]) -> jnp.ndarray:
    """Inverse of :func:`rows_view` onto a leaf of ``shape``."""
    if _stored_middle_first(shape):
        return rows.reshape(shape[1], shape[0], shape[2]).transpose(1, 0, 2)
    return rows.reshape(shape)


def _split_rows(n_rows: int, width: int, cuts: Sequence[int],
                rb: int) -> list[tuple[int, int]]:
    """Windows of an in-place ``[n_rows, width]`` leaf: split at multiples
    of ``rb`` that leave ``rb`` rows or more after them, so every window but
    the leaf's last is whole blocks (an aliased call must not run past its
    window) and the last has a block to itself. A bucket boundary (``cuts``:
    offsets into the leaf) gets the one block it falls in as a window of its
    own, which alone waits for two buckets; a row count off the int8 tile
    gets its ragged end likewise, so only that window's tiles are relaid
    when they are flattened."""
    last = (n_rows - rb) // rb * rb if n_rows >= 2 * rb else 0
    points = {last} if n_rows % MIN_ROWS else set()
    for x in cuts:
        lo = min(x // width // rb * rb, last)
        points.add(lo)
        if x != lo * width and lo + rb <= last:
            points.add(lo + rb)
    edges = [0, *sorted(points - {0}), n_rows]
    return list(zip(edges[:-1], edges[1:]))


_TILE = MIN_ROWS * LANES  # votes in one int8 native tile


def leaf_layout(shapes: Sequence[Sequence[int]],
                bounds: Sequence[tuple[int, int]],
                row_block: int = 0) -> LeafLayout:
    """The step's private coordinate order, from shapes alone.

    The ballot vector is every in-place leaf (tree order), each as its
    windows' ``[C/128, rows, 128]`` tiles, then the pool of the leaves that
    take the flat path; ``bounds`` (``codec.bucket_bounds``: the sizes are
    the wire's, and stay) cut it into buckets. Inside a bucket whole-tile
    parts come first, so that the int8 join and the verdict's slices move
    whole tiles and only the ragged parts at the end are off them. Nobody
    outside the step may rely on the order: what leaves the step in packed
    form (``prev_ballot``, the telemetry frame) is put back in flat order
    by the optimizer."""
    rb = _resolve_row_block(row_block)
    shapes = tuple(tuple(s) for s in shapes)
    sizes = [math.prod(s) for s in shapes]
    in_place = tuple(i for i, s in enumerate(shapes) if takes_leaf_in_place(s))
    pooled = tuple(i for i, s in enumerate(shapes)
                   if sizes[i] and not takes_leaf_in_place(s))
    cuts = [start for start, _ in bounds[1:]]
    pieces, pos = [], 0
    for i in in_place:
        width = shapes[i][-1]
        inside = [c - pos for c in cuts if pos < c < pos + sizes[i]]
        for r0, r1 in _split_rows(sizes[i] // width, width, inside, rb):
            pieces.append(Piece(i, r0, r1, min(rb, r1 - r0),
                                (r1 - r0) * width))
        pos += sizes[i]
    if pooled:
        pieces.append(Piece(-1, 0, 0, 0, sum(sizes[i] for i in pooled)))

    if sum(size for _, size in bounds) != sum(p.size for p in pieces):
        raise ValueError("the buckets do not tile the leaves' coordinates")
    buckets, verdicts = [], [[] for _ in pieces]
    idx, lo = 0, 0  # cursor: piece and offset into it
    for k, (_, size) in enumerate(bounds):
        parts, need = [], size
        while need:
            take = min(pieces[idx].size - lo, need)
            parts.append((idx, lo, lo + take))
            need, lo = need - take, lo + take
            if lo == pieces[idx].size:
                idx, lo = idx + 1, 0
        parts.sort(key=lambda t: (t[2] - t[1] != pieces[t[0]].size,
                                  (t[2] - t[1]) % _TILE != 0))
        off = 0
        for pi, a, b in parts:
            verdicts[pi].append((a, k, off, b - a))
            off += b - a
        buckets.append(tuple(parts))
    verdicts = tuple(tuple(v[1:] for v in sorted(vs)) for vs in verdicts)
    apply_at = tuple(
        tuple(pi for pi, vs in enumerate(verdicts)
              if max(b for b, _, _ in vs) == k)
        for k in range(len(bounds)))
    return LeafLayout(shapes, in_place, pooled, tuple(pieces),
                      tuple(buckets), verdicts, apply_at)


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
