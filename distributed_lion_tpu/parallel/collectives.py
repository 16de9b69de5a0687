"""Vote collectives: the wire layer of Distributed Lion.

TPU-native replacement for the reference's only two collective calls —
``dist.get_world_size()`` and ``dist.all_gather`` of a packed sign tensor
(/root/reference/distributed_lion.py:80-81, 120-121) followed by a Python-side
``torch.mode`` vote (:33-43, :91). Here the vote itself is a collective:

- :func:`majority_vote_psum` — sum ±1 int8 votes with ``lax.psum``: the
  reduction happens *on the interconnect* (receive volume independent of
  world size), and ``sum > 0 ⇔ majority True``. The idiomatic ICI path.
- :func:`majority_vote_packed_allgather` — bit-pack votes to real uint8
  (1 bit/param/worker on the wire, 8× less than the reference's accidental
  int64 lanes) and ``lax.all_gather``, then popcount locally. The path for
  bandwidth-starved DCN edges, and byte-for-byte the wire format the
  reference *intended*.
- :func:`majority_vote_packed_a2a` — two-phase 1-bit vote: ``all_to_all``
  of packed ballot chunks (each worker tallies one chunk), then
  ``all_gather`` of the packed verdicts. ~2 bits/param received per worker
  **independent of world size** — the minimum-bandwidth path, and the wire
  to use when W is large enough that ``packed_allgather``'s W bits/param
  hurts.
- :func:`majority_vote_hier` (wire ``"hier:<g>"``) — two-level chunked vote
  for multi-host meshes: ballots reduce-scattered *inside* g-worker ICI
  subgroups (each member owns 1/g of the coordinates), then only the
  owners' bit-packed 1-bit verdict chunks cross the group boundary (the
  DCN leg: (W/g − 1)/g bits/param). Majority-of-majorities semantics;
  degenerates to the flat vote at g=1 and g=W.

Every wire also has a **bucketed** form (:func:`vote_total_buckets` /
:func:`vote_total_bucketed` / :func:`majority_vote_bucketed`): the ballot is
split at ``codec.bucket_bounds``' wire-aligned boundaries and each chunk is
voted with its OWN collective. Elections are elementwise, so the bucketed
result is bit-identical to the one-shot vote and the per-bucket byte
accounting sums to exactly the unbucketed totals; what bucketing buys is
*pipelining* — the optimizer overlaps bucket k's collective with bucket
k−1's fused apply (optim.distributed_lion).

Both must be called inside ``jax.shard_map`` (or any context where
``axis_name`` is bound). Tie rule: ties vote −1, matching ``torch.mode``'s
smaller-value behavior on even worlds (SURVEY §2.3 step 6).
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
from jax import lax

from distributed_lion_tpu.ops.codec import (
    a2a_chunk_bytes,
    bucket_bounds,
    elect_packed_rows,
    pack_signs,
    pack_wire,
    parse_wire,
    tally_packed_rows,
    unpack_signs,
    unpack_wire,
)
from distributed_lion_tpu.train import resilience


class WireTally:
    """Measured wire counters, recorded at TRACE time from the operands the
    vote collectives are actually handed.

    Bytes on the wire are a pure function of operand shapes, and the call
    sites below execute exactly once per compiled step — so a one-trace
    capture (``telemetry.measure_step_wire`` wraps ``jax.eval_shape``)
    yields the exact per-step ledger with zero runtime overhead. Each entry
    is ``(leg, received_bytes)`` per collective launch: one entry per bucket
    of the bucketed wire, per phase of the two-phase wires, per ring of the
    hier wire ('dcn' for its cross-group leg, 'ici' for everything else).

    The per-leg byte conventions deliberately mirror
    ``ops.codec._recv_bytes`` (bytes RECEIVED per worker) — what makes the
    cross-check against ``profiling.comm_report`` non-circular is that the
    values here come from the LIVE padded/chunked array shapes at the call
    sites, so any drift between the accounting's assumptions (alignment,
    chunk padding, per-bucket splits, call counts) and what the collectives
    actually move shows up as a nonzero ``comm_drift_bytes`` metric.
    Recording is inert (None sink) outside a capture, and W = 1 records
    nothing: every wire short-circuits on a 1-device axis.
    """

    def __init__(self):
        self._entries: list | None = None

    class _Capture:
        def __init__(self, tally: "WireTally"):
            self._tally = tally

        def __enter__(self):
            self._prev = self._tally._entries
            self._tally._entries = []
            return self._tally._entries

        def __exit__(self, *exc):
            self._tally._entries = self._prev
            return False

    def capture(self) -> "WireTally._Capture":
        return WireTally._Capture(self)

    def record(self, leg: str, nbytes: int) -> None:
        if self._entries is not None and nbytes > 0:
            self._entries.append((leg, int(nbytes)))


WIRE_TALLY = WireTally()


class DcnWaitTally:
    """Measured residual waits of the emulated DCN link (the ``dcn_delay``
    fault, train/resilience registry): per step key, the MAX wait any
    device/bucket paid at the consume gate — devices run concurrently, so
    the max is the step's critical-path exposure to the link's latency.
    Sub-delay values mean the cross-step pipeline (``--dcn_pipeline_depth``)
    hid part of the round trip behind compute; the trainer drains this at
    log cadence into the ``dcn_wait_s`` metric and bench_dcn derives its
    measured overlap fraction from it. Host-side only — the traced step
    never reads it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._waits: dict = {}

    def add(self, key, wait_s: float) -> None:
        with self._lock:
            self._waits[key] = max(self._waits.get(key, 0.0), float(wait_s))

    def pop(self) -> dict:
        """{step key: max wait seconds} accumulated since the last pop."""
        with self._lock:
            out, self._waits = self._waits, {}
            return out


DCN_WAIT = DcnWaitTally()

# launch wall-clock stamps of the emulated DCN link, keyed by the optimizer
# step count the launching program carried (first device to stamp a step
# wins; pruned as consumes pass)
_DCN_STAMPS: dict = {}
_DCN_STAMPS_LOCK = threading.Lock()


def dcn_link_reset() -> None:
    """Reset the emulated DCN link between runs: stamps are keyed by the
    optimizer step count, so a fresh run re-using counts 0..N would
    otherwise find a previous run's long-expired stamps and pay no latency
    at all. Benches and tests call this before every measured leg."""
    with _DCN_STAMPS_LOCK:
        _DCN_STAMPS.clear()
    DCN_WAIT.pop()


def _dcn_host_launch(slot, count, delay_s):
    """Host half of the launch gate: stamp 'the transfer for step `count`
    started now'. Identity on the data."""
    key = int(count)
    with _DCN_STAMPS_LOCK:
        _DCN_STAMPS.setdefault(key, time.monotonic())
        for k in [k for k in _DCN_STAMPS if k < key - 64]:
            del _DCN_STAMPS[k]
    return slot

def _dcn_host_consume(slot, count, delay_s, depth):
    """Host half of the consume gate: block until the transfer launched at
    step ``count − depth`` has been on the (emulated) link for ``delay_s``
    seconds. The wall clock already spent by the intervening steps counts
    toward the deadline — that is exactly what cross-step pipelining buys —
    so the residual wait recorded into DCN_WAIT measures the UNHIDDEN part
    of the round trip. Identity on the data."""
    key = int(count) - depth
    if key >= 0:
        with _DCN_STAMPS_LOCK:
            t0 = _DCN_STAMPS.get(key)
        if t0 is not None:
            rem = t0 + delay_s - time.monotonic()
            if rem > 0:
                time.sleep(rem)
            DCN_WAIT.add(key, max(rem, 0.0))
    return slot


def _dcn_gate_launch(slot: jnp.ndarray, count):
    """Trace-time hook of the ``dcn_delay`` fault on the level-2 launch: a
    no-op unless the fault is armed AT TRACE TIME (the unarmed step's jaxpr
    carries zero host callbacks — the trace_check contract). With no step
    count threaded (direct majority_vote_* callers) the link degrades to a
    synchronous sleep at the consume gate."""
    delay = resilience.fault("dcn_delay")
    if not delay or count is None:
        return slot
    from functools import partial as _partial

    # fault-injection-only path: the callback exists to EMULATE a slow DCN
    # link on CPU and is never traced in production steps
    return jax.pure_callback(  # graft: disable=DLT003
        _partial(_dcn_host_launch, delay_s=float(delay)),
        jax.ShapeDtypeStruct(slot.shape, slot.dtype), slot, count)


def _dcn_gate_consume(slot: jnp.ndarray, count, depth: int, token=None):
    """Trace-time hook of the ``dcn_delay`` fault on the level-2 consume.
    ``token`` (any small array computed from THIS step's launch) pins the
    gate behind the launch in XLA's serial CPU schedule, so the emulated
    gap between stamp and consume is the real ``depth`` steps of compute —
    without it XLA:CPU may hoist the wait to the start of the program and
    fake a synchronous link. No-op (and dependency-free) unless the fault
    is armed at trace time."""
    delay = resilience.fault("dcn_delay")
    if not delay:
        return slot
    from functools import partial as _partial

    if count is None:
        # no step key: synchronous-link fallback — sleep the full delay
        def _sync(slot_h):
            time.sleep(float(delay))
            DCN_WAIT.add(None, float(delay))
            return slot_h

        return jax.pure_callback(  # graft: disable=DLT003
            _sync, jax.ShapeDtypeStruct(slot.shape, slot.dtype), slot)
    args = (slot, count) if token is None else (slot, count, token)

    def _consume(slot_h, count_h, *_tok):
        return _dcn_host_consume(slot_h, count_h, float(delay), int(depth))

    return jax.pure_callback(  # graft: disable=DLT003
        _consume, jax.ShapeDtypeStruct(slot.shape, slot.dtype), *args)


def axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis (the reference's world_size,
    distributed_lion.py:80)."""
    return lax.psum(1, axis_name)


def vote_total(vote_pos: jnp.ndarray, axis_name: str, wire: str,
               alive=None, count=None) -> jnp.ndarray:
    """The vote reduction over workers. Every wire satisfies the contract
    callers rely on — ``total > 0`` ⇔ majority True, ``total ≤ 0`` ⇔ elect −1
    (ties → −1, the torch.mode smaller-value rule) — but only ``sign_psum``
    and ``packed_allgather`` return the exact tally Σ ±1 ballots in [-W, W];
    ``packed_a2a`` reduces at the chunk owner and returns the elected sign as
    a ±1 proxy (magnitude information never crosses the wire — that is the
    point of the two-phase format). Do not consume the magnitude for
    vote-margin metrics without excluding the a2a wire. Single source of
    truth for the XLA and Pallas optimizer paths and both ``majority_vote_*``
    views.

    ``alive`` (optional ``[W]`` bool, replicated — the vote guard's health
    mask) turns every wire into a **masked election**: workers with
    ``alive == False`` abstain — their ballots are zeroed out of the tally
    and the majority threshold shrinks to the healthy quorum (Σ alive), so
    ``total > 0`` still means "strict majority of the HEALTHY voters" with
    ties electing −1. With ``alive`` all-True the masked election is
    bit-identical to ``alive=None`` for every wire (pinned by
    tests/test_vote_guard.py) — the guard's all-healthy contract.

    ``count`` (optional replicated int32 scalar — the optimizer step count)
    is consumed ONLY by the ``dcn_delay`` fault's link emulator on the hier
    wire; it never enters the election math.
    """
    w = axis_size(axis_name)
    kind, group = parse_wire(wire)  # raises on unknown formats
    if kind == "sign_psum":
        # ±1 in int8 keeps the wire at 1 byte/param; XLA accumulates int8
        # exactly for |sum| ≤ 127, so promote only for large worlds.
        acc = jnp.int8 if w <= 127 else jnp.int32
        ballots = jnp.where(vote_pos, 1, -1).astype(acc)
        if alive is not None:
            # an abstainer ships 0-ballots: it drops out of the on-fabric
            # sum AND out of the implicit threshold (Σ±1 of the healthy)
            own = alive[lax.axis_index(axis_name)]
            ballots = jnp.where(own, ballots, jnp.zeros_like(ballots))
        if w > 1:  # ring all-reduce: received ≈ the tensor once, on-fabric
            WIRE_TALLY.record("ici", ballots.size * ballots.dtype.itemsize)
        with jax.named_scope("vote/wire"):
            return lax.psum(ballots, axis_name)
    if kind == "packed_allgather":
        # The reference's pack → all_gather → unpack → vote pipeline
        # (distributed_lion.py:71-91) with a true-uint8 wire format;
        # vote_pos must be 1-D (callers vote on a flattened pytree).
        packed = pack_signs(vote_pos)                  # [ceil(n/8)] uint8
        if w > 1:
            WIRE_TALLY.record("ici", w * packed.size)
        with jax.named_scope("vote/wire"):
            gathered = lax.all_gather(packed, axis_name)   # [W, ceil(n/8)] uint8
        if alive is not None:
            # every worker holds the full ballot matrix here, so masking is
            # a row weighting: count over healthy rows, threshold = quorum
            weights = alive.astype(jnp.int32)
            count = tally_packed_rows(gathered, weights)[: vote_pos.shape[0]]
            return count * 2 - weights.sum()
        count = tally_packed_rows(gathered)[: vote_pos.shape[0]]
        return count * 2 - w
    if kind == "packed_a2a":
        # Two-phase vote. The verdict (not the tally) crosses the wire in
        # phase 2, so the returned "total" is the ±1 proxy of the elected
        # sign — every caller only tests ``total > 0``, and the tie rule
        # (tie → −1) is applied at the tallying worker in phase 1.
        return jnp.where(_packed_a2a_elect(vote_pos, axis_name, w, alive),
                         1, -1)
    # kind == "hier": per-worker tallies never leave the ICI subgroup, so
    # (like packed_a2a) only a ±1 proxy of the elected sign is available.
    return jnp.where(_hier_elect(vote_pos, axis_name, w, group, alive,
                                 count), 1, -1)


def vote_total_buckets(
    vote_pos: jnp.ndarray, axis_name: str, wire: str, vote_buckets: int,
    alive=None, count=None,
) -> list[jnp.ndarray]:
    """The bucketed wire: one *independent* collective per contiguous ballot
    chunk (codec.bucket_bounds — the same boundaries the byte accounting
    sums over), returned per bucket so a caller can interleave each bucket's
    apply with the next bucket's collective (the optimizer's software
    pipeline). Elections are elementwise per coordinate, so the
    concatenation of the bucket results is bit-identical to the one-shot
    ``vote_total`` for EVERY wire — bucketing changes when bytes move,
    never what is elected (tests/test_vote_buckets.py pins this).
    """
    w = axis_size(axis_name)
    bounds = bucket_bounds(vote_pos.shape[0], vote_buckets, w, wire)
    return [
        vote_total(lax.slice(vote_pos, (start,), (start + size,)),
                   axis_name, wire, alive, count)
        for start, size in bounds
    ]


def vote_total_bucketed(
    vote_pos: jnp.ndarray, axis_name: str, wire: str, vote_buckets: int,
    alive=None, count=None,
) -> jnp.ndarray:
    """Concatenated bucketed vote — same contract (and bit pattern) as
    :func:`vote_total`, but issued as ``vote_buckets`` independent
    collectives XLA's async scheduler can overlap with unrelated compute."""
    if vote_buckets <= 1:
        return vote_total(vote_pos, axis_name, wire, alive, count)
    totals = vote_total_buckets(vote_pos, axis_name, wire, vote_buckets,
                                alive, count)
    return totals[0] if len(totals) == 1 else jnp.concatenate(totals)


def majority_vote_bucketed(
    vote_pos: jnp.ndarray, axis_name: str, wire: str, vote_buckets: int,
    alive=None,
) -> jnp.ndarray:
    """Elected bool votes via the bucketed wire; bit-identical to
    :func:`majority_vote` for every wire format."""
    return vote_total_bucketed(vote_pos, axis_name, wire, vote_buckets,
                               alive) > 0


def _packed_a2a_elect(vote_pos: jnp.ndarray, axis_name: str, w: int,
                      alive=None) -> jnp.ndarray:
    """Elected bool votes via all_to_all of 1-bit ballots + all_gather of
    1-bit verdicts (~2 bits/param received per worker, W-independent).

    The bytes are in the codec's PLANAR order (``ops/codec`` docstring):
    nothing but this function ever reads them, an election is per
    coordinate, and every worker packs alike — so the chunk owner elects
    bytes to bytes (``elect_packed_rows``) and only the final verdict is
    unpacked. Worker j's chunk is a byte range of the wire, not a
    coordinate range of the ballot."""
    n = vote_pos.shape[0]
    chunk = a2a_chunk_bytes(n, w)  # uint8 bytes per worker-chunk
    packed = pack_wire(vote_pos)   # [ceil(n/8)] uint8
    pad = w * chunk - packed.shape[0]
    if pad:  # zero bytes elect zero bytes; unpack_wire never reads them
        packed = jnp.concatenate([packed, jnp.zeros((pad,), jnp.uint8)])
    packed = packed.reshape(w, chunk)  # row j = my ballot for chunk j
    if w > 1:  # phase 1: (W−1) peers each send me their copy of my chunk
        WIRE_TALLY.record("ici", (w - 1) * chunk)
    # phase 1: worker j receives every worker's row j → [W, chunk]
    with jax.named_scope("vote/wire"):
        arrived = lax.all_to_all(packed, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # the chunk owner sees every worker's row, so the masked election is a
    # row weighting and the threshold shrinks to the healthy quorum; a tie
    # elects False (−1) either way
    verdict_bits = elect_packed_rows(arrived, alive)
    if w > 1:  # phase 2: (W−1) peers each send me their chunk's verdict
        WIRE_TALLY.record("ici", (w - 1) * chunk)
    # phase 2: broadcast my chunk's packed verdict to everyone
    with jax.named_scope("vote/wire"):
        gathered = lax.all_gather(verdict_bits, axis_name)  # [W, chunk]
    return unpack_wire(gathered.reshape(-1), (n,))


def _intra_perm(w: int, g: int) -> list:
    """The intra-group ring permutation (member i → member i+1 mod g)."""
    return [(s, (s // g) * g + ((s % g) + 1) % g) for s in range(w)]


def hier_launch(vote_pos: jnp.ndarray, axis_name: str, w: int,
                group_size: int, alive=None, count=None) -> jnp.ndarray:
    """Phases 1+2 of the hier election — everything UP TO the point where
    the level-2 (DCN) traffic has arrived: intra-group ballot
    reduce-scatter (ICI), then the cross-group ring of the owners' packed
    level-1 verdict chunks, gathered per source group instead of folded
    into a count so the consume half can re-judge group health later.

    Returns the flat uint8 *slot segment* for this ballot chunk
    (codec.hier_chunk_slot_bytes): a ``[n_groups]`` launch-time group-alive
    byte mask followed by the ``[n_groups, chunk/8]`` packed verdict stack
    for this worker's OWNED 1/g chunk of coordinates. Per-worker divergent
    (each member owns a different chunk id) — under cross-step pipelining
    (``--dcn_pipeline_depth``) the slot rides ``LionState.dcn_ring`` for
    ``d`` steps before :func:`hier_consume` turns it into elected bits; the
    synchronous wire (depth 0) consumes it immediately. In the jaxpr the
    slot's only consumer at depth ≥ 1 is the state output, which is what
    lets XLA's async collective scheduling (and ``lax.scan`` over fused
    steps) overlap the DCN ring with the following steps' compute.

    ``count`` is the optimizer step count, used ONLY by the ``dcn_delay``
    fault's link emulator (train/resilience registry) to stamp the
    transfer's launch wall time.
    """
    if w % group_size:
        raise ValueError(
            f"hier wire: group size {group_size} does not divide world {w}"
        )
    g = group_size
    n_groups = w // g
    n = vote_pos.shape[0]
    acc = jnp.int8 if g <= 127 else jnp.int32
    chunk = 8 * a2a_chunk_bytes(n, g)  # byte-aligned coords per member
    pad = g * chunk - n
    flat = (jnp.concatenate([vote_pos, jnp.zeros((pad,), vote_pos.dtype)])
            if pad else vote_pos)
    buf = jnp.where(flat, 1, -1).astype(acc).reshape(g, chunk)
    group_alive = None
    if alive is not None:
        # level 1: my ballots abstain from the reduce-scatter when I am
        # quarantined (I still relay partial sums — the ring needs me)
        own_alive = alive[lax.axis_index(axis_name)]
        buf = jnp.where(own_alive, buf, jnp.zeros_like(buf))
        group_alive = alive.reshape(w // g, g).any(axis=1)
    idx = lax.axis_index(axis_name) % g  # my position within the group
    intra_perm = _intra_perm(w, g)

    # phase 1 — reduce-scatter (lax.scan ring, one traced hop): at hop t I
    # pass on the partial sum of chunk (idx − t) mod g and fold my ballots
    # into the arriving partial, ending with the full tally of owned chunk
    # (idx + 1) mod g.
    def _rs_hop(msg, t):
        with jax.named_scope("vote/wire"):
            msg = lax.ppermute(msg, axis_name, intra_perm)
        recv = (idx - t - 1) % g
        return msg + lax.dynamic_slice(buf, (recv, 0), (1, chunk))[0], None

    msg = lax.dynamic_slice(buf, (idx % g, 0), (1, chunk))[0]
    if g > 1 and w > 1:  # leg 1: (g−1) ballot-chunk hops at the acc width
        WIRE_TALLY.record("ici", (g - 1) * chunk * jnp.dtype(acc).itemsize)
    if g > 1:
        msg, _ = lax.scan(_rs_hop, msg, jnp.arange(g - 1))
    verdict_own = msg > 0  # subgroup tie → −1, for my owned coords

    # phase 2 — cross-group ring of packed verdicts, GATHERED per source
    # group: member i of every group owns the SAME chunk id, so a ring over
    # same-position peers delivers every group's verdict for my coords. The
    # hop-t packet originated at group (my_group − t − 1) mod G; storing
    # arrivals by source (instead of folding them into a count here) moves
    # the health gating and the majority threshold to hier_consume, where
    # the CURRENT alive mask is known — that is what keeps a group
    # quarantined mid-flight from poisoning a stale tally.
    cross_perm = [
        (s, ((s // g + 1) % n_groups) * g + s % g) for s in range(w)
    ]
    my_group = lax.axis_index(axis_name) // g
    packed_own = pack_signs(verdict_own)  # [chunk/8] uint8
    stack = jnp.zeros((n_groups, chunk // 8), jnp.uint8)
    stack = lax.dynamic_update_slice(stack, packed_own[None], (my_group, 0))

    def _cross_hop(carry, t):
        stack, rot = carry
        with jax.named_scope("vote/wire"):
            rot = lax.ppermute(rot, axis_name, cross_perm)
        src = (my_group - t - 1) % n_groups
        stack = lax.dynamic_update_slice(stack, rot[None], (src, 0))
        return (stack, rot), None

    if n_groups > 1 and w > 1:  # leg 2: the ONLY cross-group (DCN) traffic
        WIRE_TALLY.record("dcn", (n_groups - 1) * (chunk // 8))
    if n_groups > 1:
        (stack, _), _ = lax.scan(_cross_hop, (stack, packed_own),
                                 jnp.arange(n_groups - 1))
    mask_row = (group_alive.astype(jnp.uint8) if group_alive is not None
                else jnp.ones((n_groups,), jnp.uint8))
    slot = jnp.concatenate([mask_row, stack.reshape(-1)])
    return _dcn_gate_launch(slot, count)


def hier_consume(slot: jnp.ndarray, n: int, axis_name: str, w: int,
                 group_size: int, alive=None, count=None, depth: int = 0,
                 token=None) -> jnp.ndarray:
    """Phase 3 of the hier election, fed by a (possibly ``depth`` steps
    stale) :func:`hier_launch` slot: gate each source group's verdict chunk
    by its health at BOTH ends of the flight (the slot's launch-time mask
    AND the current ``alive`` — a group fully quarantined mid-flight
    abstains from the stale tally), take the majority over the surviving
    quorum (ties → −1, both levels), then reassemble the full elected
    vector with the intra-group (ICI) ring all-gather of the packed elected
    chunks. Elections are replicated: every worker combines the same
    per-group verdicts under the same masks.

    A worker quarantined mid-flight inside a still-healthy group keeps its
    launch-time level-1 contribution — the per-worker ballots were folded
    into the group verdict before the guard could know, and only group-
    granular abstention is possible at level 2 (documented staleness
    semantics, ARCHITECTURE 'DCN overlap').

    ``count``/``depth``/``token`` feed the ``dcn_delay`` link emulator only
    (see :func:`_dcn_gate_consume`).
    """
    g = group_size
    n_groups = w // g
    chunk = 8 * a2a_chunk_bytes(n, g)
    slot = _dcn_gate_consume(slot, count, depth, token)
    launch_mask = slot[:n_groups] > 0
    stack = slot[n_groups:].reshape(n_groups, chunk // 8)
    effective = launch_mask
    if alive is not None:
        effective = launch_mask & alive.reshape(n_groups, g).any(axis=1)
    # [chunk] per-coordinate +1-verdict tally over the surviving groups
    counts = tally_packed_rows(stack, effective.astype(jnp.int32))
    elected_own = counts * 2 > effective.astype(jnp.int32).sum()

    # phase 3 — intra-group all-gather of the packed elected chunks.
    idx = lax.axis_index(axis_name) % g
    own = (idx + 1) % g
    intra_perm = _intra_perm(w, g)

    def _ag_hop(carry, t):
        out, rot = carry
        with jax.named_scope("vote/wire"):
            rot = lax.ppermute(rot, axis_name, intra_perm)
        # the hop-t packet originated at the member t+1 behind me, which
        # owns chunk (idx − t − 1 + 1) mod g
        out = lax.dynamic_update_slice(out, rot[None], ((idx - t) % g, 0))
        return (out, rot), None

    packed_own = pack_signs(elected_own)  # [chunk/8] uint8
    out = jnp.zeros((g, chunk // 8), jnp.uint8)
    out = lax.dynamic_update_slice(out, packed_own[None], (own, 0))
    if g > 1 and w > 1:  # leg 3: (g−1) packed elected-chunk hops
        WIRE_TALLY.record("ici", (g - 1) * (chunk // 8))
    if g > 1:
        (out, _), _ = lax.scan(_ag_hop, (out, packed_own), jnp.arange(g - 1))
    return unpack_signs(out.reshape(-1), (g * chunk,))[:n]


def _hier_elect(
    vote_pos: jnp.ndarray, axis_name: str, w: int, group_size: int,
    alive=None, count=None,
) -> jnp.ndarray:
    """Hierarchical majority-of-majorities vote over a two-level fabric.

    Workers [k*group_size, (k+1)*group_size) form subgroup k — on a
    multi-host mesh, construct the data axis so that a subgroup is one
    ICI-connected host/slice (jax orders devices process-major, so
    consecutive axis indices share a host by default). Member i of each
    subgroup *owns* 1/g of the coordinates: ballots are reduce-scattered
    inside the subgroup, only the owners' bit-packed verdict chunks ride the
    cross-group (DCN) ring, and the elected bits are re-assembled by an
    intra-group all-gather — see the leg-by-leg comment below and the
    mirrored byte accounting in ops/codec.wire_bytes_per_param.

    Tie rule at BOTH levels: ties elect −1 (torch.mode's smaller-value
    behavior, SURVEY §2.3 step 6). Majority-of-majorities can differ from
    the flat majority (e.g. W=8 g=4, ballots [+,+,−,−][+,+,+,+] → group
    verdicts [tie→−, +] → group-level tie → −1, where the flat 6−2 vote
    elects +1); it degenerates to the flat vote at g=1 and g=W. Every worker
    applies the same elected bits, so replicas stay bit-identical.

    Masked election (``alive``): a quarantined member abstains at level 1
    (its ±1 ballots are zeroed out of the subgroup tally, so the subgroup
    verdict is the majority of its HEALTHY members), and a subgroup with
    zero healthy members abstains at level 2 (its verdict chunk is dropped
    from the cross-group count and the group-level threshold shrinks to the
    number of groups that still hold a healthy member). A quarantined worker
    still computes/forwards ring traffic — elections stay replicated; only
    its ballot's weight is gone.

    All three legs run as ppermute rings under ``lax.scan`` (subgrouped
    psum/all_gather via axis_index_groups is not supported under
    shard_map), chunked so no leg ever moves the full ballot vector more
    than once:

    1. intra-group reduce-scatter — (g−1)·n/g ballot bytes, ICI;
    2. cross-group ring of the owners' bit-packed verdict chunks — the only
       traffic that crosses the group boundary ((W/g − 1)·n/(8g) bytes DCN);
    3. intra-group ring all-gather of the packed ELECTED chunks
       ((g−1)·n/(8g) ≈ n/8 bytes, ICI).

    Byte accounting in ops/codec.wire_bytes_per_param mirrors exactly this.

    Since the cross-step DCN pipeline (``--dcn_pipeline_depth``,
    optim.distributed_lion) the implementation is the launch/consume split:
    phases 1+2 live in :func:`hier_launch` (producing the per-group packed
    verdict slot), the masked threshold + phase 3 in :func:`hier_consume`.
    This synchronous composition — consume the slot in the same step it was
    launched — is the depth-0 wire, bit-identical to the pre-split election
    (integer tallies summed in a different order; pinned by
    tests/test_dcn_overlap.py against an independent reference).
    """
    slot = hier_launch(vote_pos, axis_name, w, group_size, alive, count)
    return hier_consume(slot, vote_pos.shape[0], axis_name, w, group_size,
                        alive, count, depth=0)


def majority_vote_hier(
    vote_pos: jnp.ndarray, axis_name: str, group_size: int
) -> jnp.ndarray:
    """Two-level chunked majority vote: ICI-subgroup ballot reduce-scatter,
    cross-group packed-verdict ring, intra-group elected-bits all-gather;
    ties → False (−1) at both levels."""
    return _hier_elect(vote_pos, axis_name, axis_size(axis_name), group_size)


def majority_vote_psum(vote_pos: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Majority vote via an on-fabric sum of ±1 votes; ties → False (−1)."""
    return vote_total(vote_pos, axis_name, "sign_psum") > 0


def majority_vote_packed_allgather(vote_pos: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Majority vote via 1-bit packed all-gather + local popcount."""
    return vote_total(vote_pos, axis_name, "packed_allgather") > 0


def majority_vote_packed_a2a(vote_pos: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Majority vote via two-phase 1-bit all_to_all + all_gather; ties → False."""
    return _packed_a2a_elect(vote_pos, axis_name, axis_size(axis_name))


def majority_vote(vote_pos: jnp.ndarray, axis_name: str, wire: str,
                  alive=None) -> jnp.ndarray:
    """Elected bool votes for any wire format (``total > 0`` ⇔ majority True;
    the ±1-proxy wires compute the election directly — XLA folds the
    round-trip). ``alive`` masks quarantined workers out of the tally (the
    vote guard's masked election — see :func:`vote_total`)."""
    return vote_total(vote_pos, axis_name, wire, alive) > 0


def masked_majority_vote_psum(
    vote_pos: jnp.ndarray, alive: jnp.ndarray, axis_name: str
) -> jnp.ndarray:
    """Drop-out-robust vote: workers with ``alive == False`` abstain.

    The reference README claims robustness to worker drop-out but its fixed
    world-size ``all_gather`` would hang (SURVEY §5, failure detection). Here
    drop-out is an algorithm-level feature: dead workers contribute 0 ballots
    and the majority is taken over the survivors.
    """
    ballots = jnp.where(vote_pos, 1, -1).astype(jnp.int32) * alive.astype(jnp.int32)
    with jax.named_scope("vote/wire"):
        total = lax.psum(ballots, axis_name)
    return total > 0


def unpack_gathered(gathered: jnp.ndarray, n: int) -> jnp.ndarray:
    """[W, ceil(n/8)] uint8 → [W, n] bool (per-worker ballots, for tests)."""
    return jnp.stack([unpack_signs(row, (n,)) for row in gathered])
