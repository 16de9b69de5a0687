"""1-bit sign codec: pack boolean sign votes into a true uint8 wire format.

Capability parity with the reference's codec helpers
(/root/reference/distributed_lion.py:14-31 ``flatten_and_pad`` /
``restore_flattened_tensor`` and :75-77 / :84-88 inline bit pack/unpack), with
two deliberate differences:

1. **Real uint8 on the wire.** The reference's ``(bool.byte() << arange(8)).sum(-1)``
   silently promotes to int64, shipping 8 bytes per 8 params (SURVEY §2.3, wire
   format bug). Here the packed dtype is uint8 — 1 bit/param as the algorithm
   intends — an 8x wire-volume reduction.
2. **Static shapes.** JAX/XLA requires compile-time shapes, so padding is
   computed from the static leaf size; everything jit-compiles to vector ops.

All functions are pure and shape-polymorphic at trace time (no data-dependent
control flow), so they fuse into the surrounding optimizer update under jit.

**Two bit orders, and why.**

- *The reference's order* (:func:`pack_signs` / :func:`unpack_signs` /
  :func:`tally_packed_rows`): eight CONSECUTIVE coordinates a byte, numpy's
  little-endian ``packbits``. Everything that is stored or read by the host
  is in it — ``LionState.elected``, ``prev_ballot``, ``dcn_ring`` (so the
  whole ``hier`` wire), the telemetry frame's ``elected``, the guard's
  flips, ``packed_allgather`` — so checkpoints and host-side readers never
  see anything else.
- *The planar order* (:func:`pack_wire` / :func:`unpack_wire` /
  :func:`elect_packed_rows`): a group of 32,768 votes is 8 PLANES of one
  (32, 128) byte tile, and vote ``(plane j, position)`` is bit ``j`` of byte
  ``position``. Used where the bytes are transient — ``packed_a2a``'s
  all_to_all and all_gather operands, produced and consumed inside one step
  by workers that all pack alike. An election is per coordinate, so any bit
  order the workers share elects the same signs, and the bytes on the wire
  are the same count (``packed_size(n)``).

The second exists because of the chip's tile geometry. A uint8 array's two
minor dimensions live in tiles 128 lanes wide, so the consecutive order's
``[rows, 128, 8]`` view (minor dimension 8, padded to 128) turns one of GPT-2
124M's four 31 MB ballot buckets into a 0.5 GB array written by a
lane-scattering ``reshape`` and read back by a lane reduction: measured on
four v5e chips at 15.4 ms of pack (7.5 of them the ``reshape``) in a 96 ms
step, 1.1 ms in the planar order (PERF.md, PR 33). In the planar order
every view is ``[groups, 8, 32, 128]`` — a bitcast of the flat vector — and
pack / unpack combine eight whole tiles elementwise over a MAJOR axis; the
chunk owner's election (:func:`elect_packed_rows`) goes from arrived bytes
to verdict bytes without unpacking at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# pack/unpack geometry. On TPU the textbook ``[n] -> [n/8, 8]`` bit-lane
# reshape (and anything else that lands a big array in a layout XLA must
# physically re-tile: a pad fused in front of the reshape, a row count that
# is not a whole number of native (32, 128) uint8 tiles) compiles in time
# LINEAR in n — measured for a described v5e at 10 s (pack) and 20-28 s
# (unpack) per million coordinates, i.e. an hour at GPT-2 124M. Both codecs
# therefore work on whole groups of 32 lane rows of 128 bytes, which
# reshape as bitcasts and compile in about a second at any n; the ragged
# tail (< one group) is handled on its own. (The consecutive order's
# ``[rows, 128, 8]`` view compiles fast and RUNS slow: module docstring.)
_LANE_BYTES = 128
_GROUP_BYTES = 32 * _LANE_BYTES
_GROUP_BITS = 8 * _GROUP_BYTES


def _by_groups(flat: jnp.ndarray, group: int, op) -> jnp.ndarray:
    """``op`` (whole groups in, flat out) over non-empty ``flat``: applied
    to the group-aligned prefix and to the zero-padded ragged tail, results
    joined (the tail's pad is still on the end — callers slice)."""
    n = flat.shape[0]
    n_al = n - n % group
    parts = []
    if n_al:
        parts.append(op(flat[:n_al]))
    if n > n_al:
        pad = jnp.zeros((group - (n - n_al),), flat.dtype)
        parts.append(op(jnp.concatenate([flat[n_al:], pad])))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def packed_size(n: int) -> int:
    """Number of uint8 bytes needed to pack ``n`` sign bits (ceil(n/8))."""
    return (n + 7) // 8


def parse_wire(wire: str) -> tuple[str, int | None]:
    """Parse a wire-format string into ``(kind, group_size)``.

    Plain formats — ``sign_psum`` / ``packed_allgather`` / ``packed_a2a`` —
    parse to ``(wire, None)``. The hierarchical format ``"hier:<g>"`` parses
    to ``("hier", g)``: g consecutive workers form an ICI subgroup that
    reduce-scatters ±1 ballots on-fabric (each member owns 1/g of the
    coordinates), and only the owners' bit-packed 1-bit verdict chunks cross
    the (DCN) boundary between groups. Raises ValueError on anything else —
    single source of truth for wire validation (optimizer, trainer, byte
    accounting)."""
    if wire.startswith("hier:"):
        try:
            g = int(wire.split(":", 1)[1])
        except ValueError:
            raise ValueError(f"bad hier wire spec {wire!r}: expected 'hier:<int>'")
        if g < 1:
            raise ValueError(f"hier group size must be >= 1, got {g}")
        return "hier", g
    if wire in ("sign_psum", "packed_allgather", "packed_a2a"):
        return wire, None
    raise ValueError(f"unknown wire format: {wire!r}")


def wire_codec(wire: str) -> str:
    """Which of the module's two bit orders ``wire``'s bytes are in (module
    docstring), as the trainer's ``[setup] vote:`` line says it."""
    kind, _ = parse_wire(wire)
    return {"sign_psum": "no codec (int8 ballots)",
            "packed_a2a": "planar codec"}.get(kind, "reference-order codec")


def vote_chunk_elems(n: int, vote_every: int) -> int:
    """Coordinates refreshed per step under ``vote_every`` lazy refresh
    (optim.distributed_lion): the ballot vector is padded so every one of the
    K slots is an equal, byte-aligned chunk. Single source of truth for the
    optimizer's slicing and the byte accounting below."""
    return max(8, -(-n // (8 * vote_every)) * 8)


def bucket_alignment(world_size: int, wire: str) -> int:
    """Element alignment of bucket boundaries for ``wire`` (all but the last
    bucket are multiples of this). Chosen so that splitting a ballot at these
    boundaries changes NOTHING about what each wire moves: every full bucket
    packs to whole bytes (8), owns whole per-worker a2a chunks (8·W), or whole
    per-member hier chunks (8·g). That alignment is exactly what makes the
    per-bucket byte accounting sum to the unbucketed totals (ceil() terms
    become exact for every bucket but the last, and the last bucket's ceil
    absorbs precisely the global remainder)."""
    kind, group = parse_wire(wire)
    if kind == "packed_a2a":
        return 8 * world_size
    if kind == "hier":
        return 8 * group
    return 8  # sign_psum / packed_allgather: byte-pack granularity


def bucket_bounds(n: int, vote_buckets: int, world_size: int,
                  wire: str) -> list[tuple[int, int]]:
    """Split an ``n``-coordinate ballot into ≤ ``vote_buckets`` contiguous
    ``(start, size)`` chunks, boundaries aligned per :func:`bucket_alignment`.

    Single source of truth for the bucketed vote collectives
    (parallel.collectives), the optimizer's software-pipelined bucket loop
    (optim.distributed_lion), and the bucketed byte accounting below — the
    three MUST slice identically or accounting drifts from what moves.

    Invariants: chunks tile [0, n) exactly in order; every chunk but the
    last is a multiple of the wire alignment; small ballots yield fewer
    (possibly 1) buckets rather than empty ones.
    """
    if vote_buckets < 1:
        raise ValueError(f"vote_buckets must be >= 1, got {vote_buckets}")
    if n <= 0:
        return []
    align = bucket_alignment(world_size, wire)
    per = -(-n // vote_buckets)            # ceil: target bucket size
    per = -(-per // align) * align         # rounded up to the wire alignment
    bounds = []
    off = 0
    while off < n:
        size = min(per, n - off)
        bounds.append((off, size))
        off += size
    return bounds


def a2a_chunk_bytes(n: int, world_size: int) -> int:
    """uint8 bytes per worker-chunk in the packed_a2a wire: the ballot vector
    is padded so every worker owns an equal ceil(n/8W)-byte chunk. Single
    source of truth for collectives._packed_a2a_elect and the byte
    accounting below."""
    return max(1, -(-n // (8 * world_size)))


def hier_chunk_slot_bytes(nb: int, world_size: int, group: int) -> int:
    """uint8 bytes of one BUCKET's in-flight DCN slot segment for an
    ``nb``-coordinate ballot chunk on the ``hier:<g>`` wire: a [n_groups]
    launch-time group-alive byte mask followed by the [n_groups, chunk/8]
    packed per-group level-2 verdict stack for this worker's owned chunk
    (collectives.hier_launch's exact output)."""
    n_groups = world_size // group
    return n_groups * (1 + a2a_chunk_bytes(nb, group))


def hier_ring_slot_bytes(n: int, world_size: int, group: int,
                         vote_buckets: int = 1, vote_every: int = 1) -> int:
    """uint8 bytes of ONE in-flight slot of the hier wire's cross-step DCN
    ring (``--dcn_pipeline_depth``): the concatenation of the per-bucket
    segments (:func:`hier_chunk_slot_bytes`) over ``bucket_bounds`` of the
    per-step ballot.

    Single source of truth for the optimizer's ``dcn_ring`` state layout
    (optim.distributed_lion), the collectives' launch/consume slicing
    (collectives.hier_launch / hier_consume) and the trainer's restore
    templates — the three MUST agree or a checkpointed in-flight tally
    lands on the wrong coordinates.
    """
    if world_size % group:
        raise ValueError(
            f"hier wire: group size {group} does not divide world "
            f"{world_size}")
    # under lazy refresh the wire is handed the PADDED rotating slice
    # (optim._elect_lazy slices exactly vote_chunk_elems coordinates), so
    # the ring is laid out for the slice length, not min(n, slice)
    ballot = n if vote_every <= 1 else vote_chunk_elems(n, vote_every)
    return sum(hier_chunk_slot_bytes(size, world_size, group)
               for _, size in bucket_bounds(ballot, max(vote_buckets, 1),
                                            world_size, f"hier:{group}"))


@jax.named_scope("vote/pack")
def pack_signs(positive: jnp.ndarray) -> jnp.ndarray:
    """Pack a boolean array (True = +1 vote) into uint8, 8 votes per byte.

    Mirrors the reference's flatten→pad-to-multiple-of-8→bit-shift-pack
    (/root/reference/distributed_lion.py:71-77) but with an actual uint8
    result. Padding bits are zeros; they are dropped again by
    :func:`unpack_signs`, so they never bias a vote (the reference trims
    padding before voting too, distributed_lion.py:88).

    Args:
        positive: bool array of any shape.

    Returns:
        uint8 array of shape ``(packed_size(positive.size),)``.
    """
    flat = positive.reshape(-1).astype(jnp.uint8)
    n = flat.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8)

    def pack_groups(bits):  # [k * _GROUP_BITS] 0/1 -> [k * _GROUP_BYTES]
        lanes = bits.reshape(-1, _LANE_BYTES, 8)
        return jnp.sum(lanes << shifts, axis=-1).astype(jnp.uint8).reshape(-1)

    return _by_groups(flat, _GROUP_BITS, pack_groups)[: packed_size(n)]


@jax.named_scope("vote/unpack")
def unpack_signs(packed: jnp.ndarray, shape: tuple[int, ...]) -> jnp.ndarray:
    """Inverse of :func:`pack_signs`: uint8 bytes → bool array of ``shape``.

    Mirrors the reference's ``(x >> arange(8)) % 2 == 1`` unpack + trim +
    reshape (/root/reference/distributed_lion.py:84-88, 27-31).
    """
    n = int(np.prod(shape)) if shape else 1
    flat = packed.reshape(-1)
    if flat.shape[0] == 0:
        return jnp.zeros(shape, jnp.bool_)
    # [128, 1024] 0/1 matrix copying byte j into its bit slots 8j..8j+7
    # (built in-graph: a literal would put 256 KB into every call site)
    wide = (_LANE_BYTES, 8 * _LANE_BYTES)
    expand = (jax.lax.broadcasted_iota(jnp.int32, wide, 1) // 8
              == jax.lax.broadcasted_iota(jnp.int32, wide, 0)
              ).astype(jnp.bfloat16)

    def unpack_groups(b):  # [k * _GROUP_BYTES] uint8 -> [8 * len] bool
        # byte -> its 8 bit slots through the 0/1 expansion matmul (the
        # lane interleave ``b[:, None] >> arange(8)`` + reshape is the
        # slow-compiling form). Exact: every byte value 0..255 is
        # representable in bfloat16 and each output column has exactly one
        # nonzero term, accumulated in float32.
        rows = b.reshape(-1, _LANE_BYTES).astype(jnp.bfloat16)
        spread = jnp.dot(rows, expand,
                         preferred_element_type=jnp.float32).astype(jnp.int32)
        slot = jax.lax.broadcasted_iota(jnp.int32, spread.shape, 1) % 8
        return (((spread >> slot) & 1) > 0).reshape(-1)

    return _by_groups(flat, _GROUP_BYTES, unpack_groups)[:n].reshape(shape)


@jax.named_scope("vote/tally")
def tally_packed_rows(rows: jnp.ndarray, weights=None) -> jnp.ndarray:
    """Per-bit tally over packed ballot rows: ``rows`` [R, nbytes] uint8 →
    int32 [8 * nbytes], ``sum_r weights[r] * bit_r`` (``weights`` optional
    int32 [R] — the masked elections' alive weights; None = all ones).

    What the packed wires need from an ``[R, nbits]`` bit matrix, without
    ever forming one: re-tiling a flat bit vector into ``[R, m]`` is the
    same linear-compile-time trap as the textbook codec whenever ``m`` is a
    whole number of pack groups (100 s at m = 2^25, 213-227 s at 2^26 for a
    described v5e), so the rows are unpacked and added one at a time under
    a scan — O(1) trace in R, integer sums, bit-identical to
    ``unpack_signs(rows.reshape(-1), (R, m)).astype(int32).sum(0)``."""
    nbits = rows.shape[1] * 8
    if weights is None:
        weights = jnp.ones((rows.shape[0],), jnp.int32)

    weights = weights.astype(jnp.int32)

    def weighted(row, weight):
        return weight * unpack_signs(row, (nbits,)).astype(jnp.int32)

    # seeded with row 0 (not zeros) so the carry has the rows' own
    # device-varying type under shard_map
    return jax.lax.scan(
        lambda acc, rw: (acc + weighted(*rw), None),
        weighted(rows[0], weights[0]), (rows[1:], weights[1:]))[0]


def _wire_blocks(n: int) -> list[tuple[int, int, tuple[int, ...]]]:
    """The planar wire's layout of ``n`` votes as ``(first vote, votes,
    plane shape)`` blocks: the group-aligned prefix in planes of one
    (32, 128) byte tile, then the ragged tail (< one group) as ONE short
    group whose planes are ``packed_size(tail)`` bytes long — so the wire
    is ``packed_size(n)`` bytes whatever ``n`` is."""
    n_al = n - n % _GROUP_BITS
    blocks = []
    if n_al:
        blocks.append((0, n_al, (_GROUP_BYTES // _LANE_BYTES, _LANE_BYTES)))
    if n > n_al:
        blocks.append((n_al, n - n_al, (packed_size(n - n_al),)))
    return blocks


def _plane_shifts(plane: tuple[int, ...]) -> jnp.ndarray:
    """Bit position of each of a group's 8 planes, shaped to broadcast
    against ``[groups, 8, *plane]``."""
    return jnp.arange(8, dtype=jnp.uint8).reshape((8,) + (1,) * len(plane))


@jax.named_scope("vote/pack")
def pack_wire(positive: jnp.ndarray) -> jnp.ndarray:
    """Pack a boolean array (True = +1 vote) into the planar wire format:
    ``packed_size(n)`` uint8 bytes, the same count as :func:`pack_signs`,
    another bit order (module docstring). Within a group of 8 planes, vote
    ``(j, position)`` is bit ``j`` of byte ``position``:
    ``byte[k, r, l] = OR_j vote[k, j, r, l] << j`` — eight whole tiles
    combined elementwise, a reduction over a MAJOR axis. Pad bits (the
    tail's last plane) are zeros."""
    flat = positive.reshape(-1).astype(jnp.uint8)
    parts = []
    for start, votes, plane in _wire_blocks(flat.shape[0]):
        bits = flat[start:start + votes]
        short = -votes % (8 * int(np.prod(plane)))  # the tail alone
        if short:
            bits = jnp.concatenate([bits, jnp.zeros((short,), jnp.uint8)])
        planes = bits.reshape((-1, 8) + plane) << _plane_shifts(plane)
        parts.append(jax.lax.reduce(planes, np.uint8(0), jax.lax.bitwise_or,
                                    (1,)).reshape(-1))
    if not parts:
        return jnp.zeros((0,), jnp.uint8)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@jax.named_scope("vote/unpack")
def unpack_wire(packed: jnp.ndarray, shape: tuple[int, ...]) -> jnp.ndarray:
    """Inverse of :func:`pack_wire`: ``packed_size(n)`` planar bytes → bool
    array of ``shape``; ``bit[k, j, r, l] = (byte[k, r, l] >> j) & 1``."""
    n = int(np.prod(shape)) if shape else 1
    flat = packed.reshape(-1)
    parts = []
    for start, votes, plane in _wire_blocks(n):
        tiles = flat[start // 8: start // 8 + packed_size(votes)]
        tiles = tiles.reshape((-1, 1) + plane)
        bits = ((tiles >> _plane_shifts(plane)) & 1) > 0
        parts.append(bits.reshape(-1)[:votes])
    if not parts:
        return jnp.zeros(shape, jnp.bool_)
    bits = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return bits.reshape(shape)


@jax.named_scope("vote/tally")
def elect_packed_rows(rows: jnp.ndarray, weights=None) -> jnp.ndarray:
    """The packed wires' election at the chunk owner, bytes in and bytes
    out: ``rows`` [R, nbytes] uint8 (one packed ballot a row, in ANY bit
    order the rows share) → [nbytes] uint8 whose bit is set where a strict
    majority of the rows set it. ``weights`` (optional [R] bool or int, the
    masked elections' alive mask) shrink the quorum to their sum; a tie
    elects 0 (−1). An election is per coordinate, so nothing is unpacked:
    per bit position ``j`` the rows' bit-``j`` bytes are counted and
    compared elementwise. O(1) trace in R."""
    if weights is None:
        quorum = rows.shape[0]
    else:
        weights = weights.astype(jnp.int32)
        quorum = weights.sum()
    verdict = []
    for j in range(8):
        bit = ((rows >> j) & 1).astype(jnp.int32)
        if weights is not None:
            bit = bit * weights[:, None]
        verdict.append((bit.sum(0) * 2 > quorum).astype(jnp.uint8) << j)
    return functools.reduce(jnp.bitwise_or, verdict)


def _recv_bytes(n: int, world_size: int, kind: str,
                group: int | None) -> tuple[int, int]:
    """Bytes RECEIVED per worker for ONE contiguous ``n``-coordinate ballot
    on this wire: ``(total_bytes, dcn_leg_bytes)``. The per-bucket unit the
    (possibly bucketed) accounting below sums over."""
    if kind == "hier":
        n_groups = world_size // group
        # Mirrors collectives._hier_elect's three chunked ppermute rings:
        #   ICI leg 1 (reduce-scatter of ballots): (g−1) hops × chunk bytes
        #   ICI leg 3 (all-gather of packed elected): (g−1) hops × chunk/8
        #   DCN leg 2 (cross-group packed verdicts): (G−1) hops × chunk/8 —
        #     the flat packed vote's cross-boundary volume divided by g,
        #     because only each member's OWNED 1/g chunk crosses groups.
        acc_bytes = 1 if group <= 127 else 4
        chunk = 8 * a2a_chunk_bytes(n, group)  # same rule as _hier_elect
        dcn = (n_groups - 1) * (chunk // 8)
        ici = (group - 1) * (chunk * acc_bytes + chunk // 8)
        return ici + dcn, dcn
    if kind == "sign_psum":
        # Ring all-reduce of the ballot tensor: received payload per worker ≈
        # N bytes at the accumulator width (reduction happens on-fabric,
        # receive volume independent of W). int8 is exact only while partial
        # sums fit (W ≤ 127); larger worlds promote to int32, matching
        # collectives.majority_vote_psum.
        acc_bytes = 1 if world_size <= 127 else 4
        return n * acc_bytes, 0
    if kind == "packed_allgather":
        return world_size * packed_size(n), 0
    if kind == "packed_a2a":
        # phase 1: (W-1) peers each send me their packed copy of my chunk;
        # phase 2: (W-1) peers each send me their chunk's packed verdict.
        return 2 * (world_size - 1) * a2a_chunk_bytes(n, world_size), 0
    raise ValueError(f"unknown wire format: {kind!r}")


def wire_bytes_per_param(num_params: int, world_size: int, wire: str,
                         vote_every: int = 1, accum_steps: int = 1,
                         vote_buckets: int = 1,
                         dcn_pipeline_depth: int = 0) -> dict:
    """Accounting for bytes RECEIVED per worker, per optimizer step.

    The reference ships int64-packed tensors via all_gather: every worker
    receives ``world * ceil(n/8) * 8`` bytes per step
    (/root/reference/distributed_lion.py:80-81; dtype verified in SURVEY §2.3).
    BASELINE.md's comm budget asks for ≤ 1/32 of a bf16 gradient all-reduce
    (2 bytes/param → ≤ 0.5 bit/param).

    Two honest ways to judge that budget, both reported:

    - ``bits_per_param`` / ``vs_bf16_allreduce``: per *optimizer step*,
      against ONE bf16 all-reduce. ``packed_a2a`` is ~2 bits/param here
      (4x over budget); combining it with ``vote_every >= 4`` lazy refresh
      divides the wire by K and meets the budget outright.
    - ``bits_per_param_per_microbatch`` / ``vs_bf16_allreduce_equal_tokens``:
      amortized over ``accum_steps`` gradient-accumulation microbatches,
      against the bf16 volume DDP moves for the SAME tokens when it syncs
      every backward (torch DDP's default without ``no_sync``). Under the
      reference's canonical config (accum 8, README.md:31) ``packed_a2a``
      is 0.25 bit/param/microbatch — under budget with no algorithm change.

    Args:
        num_params: total parameters voted on.
        world_size: number of data-parallel voters.
        wire: 'sign_psum' (int8 on-fabric all-reduce), 'packed_allgather'
            (1-bit uint8 all-gather), 'packed_a2a' (two-phase 1-bit
            all_to_all + all_gather; ~2 bits/param, W-independent), or
            'hier:<g>' (two-level chunked vote: ballot reduce-scatter inside
            g-worker ICI subgroups, cross-group ring of the owners' packed
            1-bit verdict chunks, intra-group all-gather of the elected
            bits — the ``dcn_bytes_per_step`` extra key reports the
            cross-group leg alone, (W/g − 1)/g bits/param, the volume that
            actually rides the slow fabric on a multi-host mesh).
        vote_every: lazy-refresh period K (optim.distributed_lion): each step
            votes only ceil(n/K) coordinates → wire volume ÷ K.
        accum_steps: gradient-accumulation microbatches per optimizer step
            (for the equal-tokens comparison only).
        vote_buckets: number of contiguous ballot chunks voted as separate
            (pipelined) collectives (optim.distributed_lion bucket loop).
            Accounted as the SUM of the per-bucket wires over
            :func:`bucket_bounds` — which, by the bucket-boundary alignment,
            is exactly the unbucketed total: bucketing changes when bytes
            move (overlapped with compute), never how many.
        dcn_pipeline_depth: cross-step pipeline depth of the hier wire's
            level-2 (DCN) leg (optim.distributed_lion): at depth d > 0 the
            cross-group packed-verdict ring launched at step t is consumed
            only at step t+d, so its round-trip latency hides behind d
            steps of compute. The OVERLAPPED leg still moves exactly the
            same bytes every step — one launch and one consume execute per
            step in steady state, so ``bytes_per_step``/``dcn_bytes_per_
            step`` (and the measured counters they're cross-checked
            against: ``comm_drift_bytes`` stays 0) are depth-invariant.
            What depth changes is the ``dcn_overlap_frac`` extra: the
            fraction of the DCN leg's LATENCY eligible to leave the
            critical path (1.0 once the leg rides the ring, 0.0 for the
            synchronous depth-0 wire). The measured counterpart comes from
            the bench_dcn ablation (scripts/bench_dcn.py).

    Returns:
        dict with bytes received per worker per optimizer step for this
        build, the reference, and a bf16 gradient all-reduce, plus both
        bits/param views.
    """
    kind, group = parse_wire(wire)
    n_voted = (num_params if vote_every <= 1
               else min(num_params, vote_chunk_elems(num_params, vote_every)))
    extras: dict = {}
    if kind == "hier" and world_size % group:
        raise ValueError(
            f"hier group size {group} does not divide world {world_size}"
        )
    # One collective per bucket, each accounted with the same per-ballot
    # formula (_recv_bytes). bucket_bounds' alignment guarantees the sum is
    # EXACTLY the vote_buckets=1 number — pinned by the conservation test in
    # tests/test_vote_buckets.py.
    per_bucket = [_recv_bytes(size, world_size, kind, group)
                  for _, size in bucket_bounds(n_voted, max(vote_buckets, 1),
                                               world_size, wire)]
    ours = sum(b for b, _ in per_bucket)
    # Analytic pipelineable fraction of the wire: the optimizer's software
    # pipeline (optim.distributed_lion._step_pallas) overlaps bucket k's
    # collective with bucket k−1's fused apply, so every bucket AFTER the
    # first can hide behind compute — the fraction of wire bytes eligible
    # for overlap is buckets[1:]'s share. 0.0 for the monolithic vote and
    # at world=1 (no wire to hide). The MEASURED counterpart is the
    # benchmark's vote_exposed_ms.train4 (the wire time no compute covers).
    overlappable = (sum(b for b, _ in per_bucket[1:]) / ours
                    if ours and world_size > 1 else 0.0)
    if kind == "hier":
        dcn = sum(d for _, d in per_bucket)
        # the level-2 leg's latency leaves the critical path entirely once
        # it rides the cross-step ring (depth ≥ 1) — and only then; no leg
        # exists to hide at W=1 or single-group (g=W) topologies
        dcn_overlap = (1.0 if (dcn_pipeline_depth > 0 and dcn > 0
                               and world_size > 1) else 0.0)
        extras = {"hier_groups": world_size // group,
                  "dcn_bytes_per_step": dcn,
                  "dcn_bits_per_param": 8.0 * dcn / max(num_params, 1),
                  "dcn_pipeline_depth": max(dcn_pipeline_depth, 0),
                  "dcn_overlap_frac": dcn_overlap}
    if world_size <= 1:
        # one voter: every wire short-circuits (a psum/all_gather over a
        # 1-device axis is a no-op — no bytes cross any fabric). Reporting
        # the nominal ballot size here made single-chip metrics claim
        # MB/step of phantom traffic (observed in run_clm W=1 logs).
        ours = 0
    reference = world_size * packed_size(num_params) * 8  # int64 lanes
    bf16_allreduce = 2 * num_params
    if world_size <= 1:
        # the comparison baselines short-circuit identically at W=1 (a DDP
        # all-reduce over one device moves nothing either) — zero them so
        # the ratios read 0/0-style N/A, not an advantage over phantom
        # baseline traffic
        reference = bf16_allreduce = 0
    bits = 8.0 * ours / max(num_params, 1)
    return extras | {
        "wire": wire,
        "vote_every": vote_every,
        "vote_buckets": max(vote_buckets, 1),
        "overlappable_wire_frac": overlappable,
        "bytes_per_step": ours,
        "bits_per_param": bits,
        "bits_per_param_per_microbatch": bits / max(accum_steps, 1),
        "reference_bytes_per_step": reference,
        "bf16_allreduce_bytes_per_step": bf16_allreduce,
        "vs_bf16_allreduce": ours / max(bf16_allreduce, 1),
        "vs_bf16_allreduce_equal_tokens":
            ours / max(bf16_allreduce * max(accum_steps, 1), 1),
    }
