"""Distributed Lion: 1-bit majority-vote Lion over a JAX device mesh.

Capability parity with the reference's ``update_fn_distributed`` /
``update_fn_distributed_stoc`` (/root/reference/distributed_lion.py:61-136)
and its construction-time mode dispatch (:159-166), redesigned TPU-first:

- **One fused collective per step, not one per tensor.** The reference loops
  over ~148 parameter tensors calling a blocking NCCL ``all_gather`` each
  (SURVEY §3.1 hot loop). Here every leaf's votes are concatenated into a
  single 1-D ballot vector and voted with ONE ``psum`` (or one packed
  ``all_gather``) per optimizer step.
- **…and that collective is pipelined.** With ``vote_buckets > 1`` the
  ballot is split at ``codec.bucket_bounds``' wire-aligned boundaries and
  each chunk voted as its own collective, software-pipelined against the
  fused apply: bucket k rides the interconnect while bucket k−1's Pallas
  apply runs in VMEM, so the wire hides behind compute instead of sitting
  on the critical path. Elections and byte totals are bit-identical to the
  monolithic vote (tests/test_vote_buckets.py).
- **Reduction on the interconnect.** The default wire (``sign_psum``) sums ±1
  int8 ballots with ``lax.psum``: receive volume is independent of world
  size, vs the reference's O(W·N) gather-then-``torch.mode``-in-Python.
- **The intended dispatch, not the reference's broken one.** The reference's
  stochastic path is unreachable (lambda returns the function object;
  ``self.max_grad_norm`` never assigned — SURVEY §2.1). Here
  ``max_grad_norm=None`` → deterministic sign votes, set → stochastic
  binarization, and ``axis_name=None`` → plain local Lion (the reference's
  uninitialized-process-group fallback, :165-166).
- **Per-worker momentum is first-class state.** ``step`` must run inside
  ``jax.shard_map`` with params replicated; momentum is stored globally with
  a leading ``[world]`` axis sharded over the data axis, so Orbax checkpoints
  capture EVERY worker's momentum (the reference silently saves only rank
  0's — SURVEY §5, checkpoint gap).

Tie rule: ties elect −1, matching ``torch.mode``'s smaller-value behavior on
even worlds (SURVEY §2.3 step 6), so trajectories are comparable.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from distributed_lion_tpu.ops import lion_math
from distributed_lion_tpu.ops.codec import (
    bucket_bounds,
    hier_chunk_slot_bytes,
    hier_ring_slot_bytes,
    pack_signs,
    packed_size,
    parse_wire,
    vote_chunk_elems,
)
from distributed_lion_tpu.optim.lion import (
    FunctionalOptimizer,
    LionState,
    Schedule,
    _validate,
    lion,
    resolve_lr,
)
from distributed_lion_tpu.parallel import collectives
from distributed_lion_tpu.parallel.mesh import DATA_AXIS


def _flatten_votes(vote_tree):
    """Concatenate a pytree of bool vote arrays into one 1-D ballot vector."""
    leaves = jax.tree.leaves(vote_tree)
    return jnp.concatenate([l.reshape(-1) for l in leaves])


def _split_votes(flat, like_tree):
    """Inverse of :func:`_flatten_votes` against a template pytree."""
    leaves, treedef = jax.tree.flatten(like_tree)
    out, off = [], 0
    for l in leaves:
        n = l.size
        out.append(flat[off : off + n].reshape(l.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


def _guard_ballot_len(n: int, vote_every: int) -> int:
    """uint8 bytes of the guard's previous-ballot state: the elected-cache
    per-slot layout under lazy refresh (so the refreshed slot's bytes line
    up across steps), plain bit-packing otherwise. Single source of truth
    for init, init_global_state and the trainer's restore templates."""
    if vote_every > 1:
        return vote_every * vote_chunk_elems(n, vote_every) // 8
    return packed_size(n)


def _ballot_flips(packed_now: jnp.ndarray,
                  packed_prev: jnp.ndarray) -> jnp.ndarray:
    """Bit flips between two packed ballots: popcount of the XOR, summed.
    ≈ 0 across consecutive (re)votes is the frozen-voter signature."""
    xor = jnp.bitwise_xor(packed_now, packed_prev)
    return jnp.sum(lax.population_count(xor).astype(jnp.int32))


def _nonfinite_count(grads, exp_avg) -> jnp.ndarray:
    """i32 count of nonfinite elements in this worker's LOCAL grads and
    momentum — the ballot inputs, checked BEFORE sign-encoding (a NaN
    u-term silently votes −1: ``NaN > 0`` is False)."""
    tot = jnp.zeros((), jnp.int32)
    for leaf in jax.tree.leaves(grads) + jax.tree.leaves(exp_avg):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            tot = tot + jnp.sum(~jnp.isfinite(leaf)).astype(jnp.int32)
    return tot


def distributed_lion(
    learning_rate: Schedule = 1e-4,
    b1: float = 0.9,
    b2: float = 0.99,
    weight_decay: float = 0.0,
    *,
    axis_name: Optional[str] = DATA_AXIS,
    max_grad_norm: Optional[float] = None,
    wire: str = "sign_psum",
    vote_every: int = 1,
    vote_buckets: int = 1,
    dcn_pipeline_depth: int = 0,
    mom_dtype: Optional[jnp.dtype] = None,
    kernel: str = "auto",
    row_block: int = 0,
    telemetry: bool = False,
    guard: str = "off",
) -> FunctionalOptimizer:
    """Build the majority-vote Lion optimizer.

    Args:
        learning_rate: scalar or schedule ``step -> lr``.
        b1, b2, weight_decay: Lion hyperparameters (ref defaults :144-147).
        axis_name: mesh axis to vote across. ``None`` → local Lion fallback.
        max_grad_norm: ``None`` → deterministic sign votes (ref :61-96);
            set → stochastic binarization with range bound
            ``r = (1 + 1/b1) * max_grad_norm`` (ref :106-108). Requires an
            ``rng`` key at ``init``.
        wire: 'sign_psum' (int8 on-fabric reduce; ICI default),
            'packed_allgather' (1-bit uint8 wire; DCN-friendly),
            'packed_a2a' (two-phase 1-bit vote, ~2 bits/param independent
            of world size; minimum-bandwidth choice for large worlds), or
            'hier:<g>' (two-level chunked vote for ICI+DCN meshes: ballot
            reduce-scatter inside g-worker ICI subgroups, cross-group ring
            of the owners' packed 1-bit verdict chunks — (W/g − 1)/g
            bits/param on the slow fabric; majority-of-majorities,
            collectives.majority_vote_hier).
        vote_every: K > 1 enables *lazy sign refresh*: each step votes on a
            rotating 1/K slice of coordinates (wire volume ÷ K — e.g.
            packed_a2a at K=4 is ~0.5 bit/param/step, meeting BASELINE.md's
            ≤1/32-of-bf16-allreduce budget per optimizer step), while the
            other coordinates apply their *last elected* sign from a packed
            1-bit cache in the state. Replicas stay bit-identical because
            the cache holds voted (shared) results only. Coordinates not yet
            voted in the first K-1 steps receive no update. Sign staleness
            ≤ K steps is the accuracy trade — covered by a convergence test.
        vote_buckets: B > 1 splits the ballot into B contiguous wire-aligned
            chunks (codec.bucket_bounds) voted as B independent collectives,
            software-pipelined against the fused apply on the Pallas path:
            bucket k's vote rides the interconnect while bucket k−1's update
            runs in VMEM. Params/momentum are bit-identical to B = 1 for
            every wire, and the summed wire bytes equal the monolithic
            vote's exactly — bucketing changes WHEN bytes move, never what
            is elected or how much ships. Composes with ``vote_every``
            (the rotating 1/K slice is itself voted bucket-wise) and the
            stochastic path. 1 = the monolithic vote.
        dcn_pipeline_depth: d > 0 (hier wire only) enables the *cross-step
            DCN pipeline*: each step still computes and combines its level-1
            ICI tally immediately and launches the level-2 cross-group
            (DCN) ring for its own ballot — but the ring's result is only
            CONSUMED d steps later, riding ``LionState.dcn_ring`` (one slot
            per in-flight step, codec.hier_ring_slot_bytes layout) so the
            slow leg's round trip hides behind d steps of compute instead
            of sitting on every step's critical path. The elected signs
            applied at step t are therefore the complete two-level election
            of step t−d's ballots — uniformly d steps stale on every
            worker, so replicas stay bit-identical; the first d steps apply
            no update (momentum still accumulates — the same cold-start
            rule as ``vote_every``'s unvoted slots). Composes with
            ``vote_buckets`` (each bucket launches/consumes its own ring
            segment), ``vote_every`` (the consumed election lands in the
            elected cache's slot (t−d) mod K) and the vote guard (the ring
            slot carries its launch-time group-health mask; a group fully
            quarantined mid-flight abstains from the stale tally at
            consume). Byte volume per step is depth-invariant — one launch
            and one consume execute every step — so ``comm_drift_bytes``
            stays 0. 0 = today's synchronous hier wire (bit-identical to
            the pre-pipeline election). Routed to the XLA path (the Pallas
            fused-apply kernels assume fresh per-bucket totals).
        mom_dtype: momentum dtype override (default: param dtype, ref :185).
        kernel: 'auto' (fused Pallas kernels on TPU, plain XLA elsewhere),
            'pallas' (force; interpreted off-TPU — tests), or 'xla'.
            The Pallas path covers the deterministic mode with
            dtype-uniform pytrees; other cases fall back to XLA.
        row_block: Pallas kernel tile override (rows per grid step,
            multiple of 32; 0 = pallas_lion.ROW_BLOCK, what the
            Trainer always runs). A pure tiling knob for tests:
            params/momentum/elections are bit-identical at any value
            (tests/test_pallas_lion.py), only VMEM residency and grid
            geometry change.
        telemetry: True → ``step`` returns a third value, the per-step
            vote-health *frame* (train.telemetry: margin bincount over the
            voted coordinates for tally wires, packed elected-sign state,
            local-ballot disagreement / stochastic-flip / valid-update
            counts) — raw on-device arrays the trainer folds into its
            ``VoteHealth`` accumulator. Telemetry only OBSERVES the vote:
            elections, params and momentum are bit-identical to
            ``telemetry=False`` (pinned by tests/test_telemetry.py).
        guard: the vote guard (Byzantine-tolerant elections). ``'off'`` —
            no guard state, no extra outputs. ``'observe'`` / ``'enforce'``
            → ``LionState`` carries a ``[W]`` health mask + the packed
            previous LOCAL ballot, and ``step`` returns an extra *guard
            frame* (after the telemetry frame when both are on): per-worker
            nonfinite-input counts, ballot-flip counts vs the previous vote
            (popcount XOR — a ≈0 count is a frozen voter), and local-ballot
            disagreement fractions, each a replicated ``[W]`` vector built
            from two one-hot scalar psums. Under ``'enforce'`` the election
            additionally EXCLUDES workers whose ``state.health`` bit is
            False (collectives masked vote — the majority threshold shrinks
            to the healthy quorum) and nonfinite local gradients are zeroed
            out of the momentum update so a transient NaN batch cannot
            poison ``exp_avg`` forever. With an all-healthy mask and finite
            inputs, 'enforce' is bit-identical to 'off' in elections,
            params and momentum (tests/test_vote_guard.py pins this across
            all four wires × vote_buckets × det/stoch × XLA/Pallas).
            ``'observe'`` computes the same signals but never touches the
            election. The quarantine decisions themselves (strikes,
            cooldown, readmission healing) live in the trainer's host-side
            state machine (train/vote_guard.py).

    Returns:
        A :class:`FunctionalOptimizer` whose ``step`` MUST be traced inside
        ``jax.shard_map`` with ``axis_name`` bound (unless ``axis_name`` is
        None). Params in/out are replicated; ``state.exp_avg`` is this
        worker's momentum shard (see :func:`init_global_state`).
    """
    wire_kind, wire_group = parse_wire(wire)  # raises on unknown formats
    if dcn_pipeline_depth < 0:
        raise ValueError(
            f"dcn_pipeline_depth must be >= 0, got {dcn_pipeline_depth}")
    if dcn_pipeline_depth > 0 and wire_kind != "hier":
        raise ValueError(
            f"dcn_pipeline_depth pipelines the hier wire's level-2 (DCN) "
            f"leg; wire {wire!r} has no such leg — use 'hier:<g>' or depth 0"
        )
    if axis_name is None:
        # The reference's uninitialized-process-group fallback is plain local
        # Lion (distributed_lion.py:165-166). Refuse to silently drop an
        # explicit stochastic request rather than mimic the reference's
        # broken max_grad_norm branch (SURVEY §2.1).
        if max_grad_norm is not None:
            raise ValueError(
                "max_grad_norm (stochastic binarization) requires a vote axis; "
                "pass axis_name or use lion() for the local optimizer"
            )
        if telemetry:
            raise ValueError(
                "telemetry instruments the vote; with axis_name=None there "
                "is no election to observe — use lion() for local training"
            )
        if guard != "off":
            raise ValueError(
                "the vote guard protects the election; with axis_name=None "
                "there is no election to guard — use lion() for local "
                "training"
            )
        if dcn_pipeline_depth > 0:
            raise ValueError(
                "dcn_pipeline_depth pipelines the vote wire; with "
                "axis_name=None there is no wire — use lion() for local "
                "training"
            )
        return lion(learning_rate, b1, b2, weight_decay, mom_dtype)

    _validate(learning_rate if not callable(learning_rate) else None, b1, b2)
    if vote_every < 1:
        raise ValueError(f"vote_every must be >= 1, got {vote_every}")
    if vote_buckets < 1:
        raise ValueError(f"vote_buckets must be >= 1, got {vote_buckets}")
    if guard not in ("off", "observe", "enforce"):
        raise ValueError(
            f"guard must be 'off', 'observe' or 'enforce', got {guard!r}")
    guard_on = guard != "off"
    enforce = guard == "enforce"
    stochastic = max_grad_norm is not None
    from distributed_lion_tpu.ops.pallas_lion import (
        _resolve_row_block,
        resolve_kernel_mode,
    )

    interpret = resolve_kernel_mode(kernel)  # None → XLA path
    _resolve_row_block(row_block)  # fail at build time, not mid-trace
    if telemetry:
        # train.telemetry is a leaf module (imports ops/parallel only), so
        # this upward import cannot cycle; it stays out of the default path.
        from distributed_lion_tpu.train import telemetry as _vt

        wire_has_tally = _vt.tally_wire(wire)

    def init(params, rng: Optional[jax.Array] = None) -> LionState:
        if stochastic and rng is None:
            raise ValueError("stochastic Distributed Lion requires an rng key at init")
        exp_avg = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=mom_dtype or p.dtype), params
        )
        n = sum(p.size for p in jax.tree.leaves(params))
        elected = None
        if vote_every > 1:
            chunk = vote_chunk_elems(n, vote_every)
            elected = jnp.zeros((vote_every * chunk // 8,), jnp.uint8)
        prev_ballot = None
        if guard_on:
            # the frozen-ballot detector's XOR base: the packed previous
            # LOCAL ballot, laid out like the elected cache under lazy
            # refresh (per-slot byte-aligned chunks) so the refreshed slot's
            # bytes line up across steps
            prev_ballot = jnp.zeros((_guard_ballot_len(n, vote_every),),
                                    jnp.uint8)
        # health is created by init_global_state (its [world] length is
        # unknown at worker level); None means "mask everything in"
        return LionState(count=jnp.zeros((), jnp.int32), exp_avg=exp_avg,
                         rng=rng, elected=elected, prev_ballot=prev_ballot)

    def _guard_frame(w, nf, flips, flip_valid, dis_frac, voted):
        """Assemble the per-step guard frame: the three per-worker scalars
        become replicated ``[W]`` vectors via one one-hot psum each — the
        only collectives the guard adds to the step (all O(W) scalars; no
        host traffic, the trainer reads them one dispatch behind)."""
        widx = lax.axis_index(axis_name)
        onehot = jnp.arange(w, dtype=jnp.int32) == widx

        def vec(x):
            return lax.psum(jnp.where(onehot, x, jnp.zeros_like(x)),
                            axis_name)

        return {
            "nonfinite": vec(nf),        # i32[W] local nonfinite counts
            "flips": vec(flips),         # i32[W] ballot bit flips vs prev
            "flip_valid": flip_valid,    # bool: prev ballot was a real vote
            "disagree": vec(dis_frac),   # f32[W] local-vs-elected fraction
            "voted": jnp.asarray(voted, jnp.int32),  # coords voted
        }

    def _step_pallas(params, grads, state: LionState, guard_nf=None):
        """Fused-kernel fast path: VMEM kernels over every leaf where it
        lies + the bucketed, software-pipelined vote wire. ``guard_nf`` is
        the pre-sanitize nonfinite count ``step`` measured (the guard's NaN
        signal must see the raw gradients; enforce mode zeroes them before
        this path).

        Between the gradient and the new parameters no float32 array of a
        leaf's size is relaid, sliced, padded, joined or reshaped: a leaf
        of whole 128-lane rows is handed to the kernels as it is
        (``pallas_lion.leaf_ballots`` / ``leaf_apply``, a window of whole
        rows a call, ``p'`` and ``m'`` written into their own operands) and
        the rest — biases, LayerNorm, LoRA factors: what
        ``pallas_lion.takes_leaf_in_place`` turns down by shape — is
        concatenated into ONE small flat vector for one ballot and one
        apply call. What crosses between the kernels is int8 and bits: the
        pieces' ballot tiles joined into a bucket's vector, in an order the
        step owns (``pallas_lion.leaf_layout``; the bucket SIZES are
        ``codec.bucket_bounds``', so the wire's bytes are what they were),
        and the verdict cut back out of it at one byte a coordinate.

        Pipeline order: compute + send bucket k's ballots, then run bucket
        k−1's fused apply while k is on the wire; XLA's async collectives
        turn that dataflow into interconnect/VMEM overlap. A piece is
        applied with the last bucket that elects a part of it (the one
        block a bucket boundary falls in waits for two). ``grads`` arrive
        already cast to the momentum dtype (hoisted once in ``step``).
        """
        from distributed_lion_tpu.ops import pallas_lion

        lr = resolve_lr(learning_rate, state.count)
        p_leaves, treedef = jax.tree.flatten(params)
        m_leaves = treedef.flatten_up_to(state.exp_avg)
        g_leaves = treedef.flatten_up_to(grads)
        sizes = [p.size for p in p_leaves]
        n = sum(sizes)
        w = collectives.axis_size(axis_name)
        bounds = bucket_bounds(n, vote_buckets, w, wire)
        if not bounds:  # zero-coordinate pytree: nothing to vote or apply
            out_state = LionState(state.count + 1, state.exp_avg,
                                  state.rng, state.elected,
                                  state.health, state.prev_ballot,
                                  state.dcn_ring)
            out = (params, out_state)
            if telemetry:
                out = out + (_vt.empty_frame(0),)
            if guard_on:
                out = out + (_guard_frame(
                    w, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                    jnp.asarray(False, jnp.bool_),
                    jnp.zeros((), jnp.float32), 0),)
            return out
        alive = state.health if enforce else None
        layout = pallas_lion.leaf_layout([p.shape for p in p_leaves], bounds,
                                         row_block)

        def _join(parts):
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        # the newest (p, m) of every leaf: an apply call writes its window
        # into its operands, so whoever reads the leaf next reads its result
        # (the rows a call leaves alone are the same bits in both)
        new_p = {i: pallas_lion.rows_view(p_leaves[i]) for i in layout.in_place}
        new_m = {i: pallas_lion.rows_view(m_leaves[i]) for i in layout.in_place}
        g_rows = {i: pallas_lion.rows_view(g_leaves[i]) for i in layout.in_place}
        pool = [_join([ls[i].reshape(-1) for i in layout.pooled])
                if layout.pooled else None
                for ls in (p_leaves, g_leaves, m_leaves)]
        ballots_of: dict = {}  # piece -> its int8 ballots, in its own order

        def _piece_ballots(pi):
            if pi not in ballots_of:
                pc = layout.pieces[pi]
                if pc.leaf < 0:
                    ballots_of[pi] = pallas_lion.fused_ballots(
                        pool[1], pool[2], b1, interpret=interpret,
                        row_block=row_block)
                else:
                    ballots_of[pi] = pallas_lion.leaf_ballots(
                        g_rows[pc.leaf], new_m[pc.leaf], b1,
                        rows=(pc.r0, pc.r1), block=pc.block,
                        interpret=interpret).reshape(-1)
            return ballots_of[pi]

        def _cut(vec, lo, hi):
            return vec if (lo, hi) == (0, vec.shape[0]) else lax.slice(
                vec, (lo,), (hi,))

        def _bucket_ballots(k):
            return _join([_cut(_piece_ballots(pi), lo, hi)
                          for pi, lo, hi in layout.buckets[k]])

        verdict_of: dict = {}  # piece -> its int8 election, same order

        def _bucket_apply(k):
            for pi in layout.apply_at[k]:
                pc = layout.pieces[pi]
                verdict = verdict_of[pi] = _join(
                    [_cut(verdicts[b], off, off + ln)
                     for b, off, ln in layout.verdicts[pi]])
                if pc.leaf < 0:
                    pool[0], pool[2] = pallas_lion.fused_apply(
                        pool[0], pool[1], pool[2], verdict, lr,
                        weight_decay, b2, interpret=interpret,
                        row_block=row_block)
                    continue
                li = pc.leaf
                new_p[li], new_m[li] = pallas_lion.leaf_apply(
                    new_p[li], g_rows[li], new_m[li],
                    verdict.reshape(-1, pc.r1 - pc.r0, pallas_lion.LANES),
                    lr, weight_decay, b2, rows=(pc.r0, pc.r1),
                    block=pc.block, interpret=interpret)

        verdicts = []  # per bucket: the election at one byte a coordinate
        # telemetry rides the bucket pipeline: each bucket's stats kernel
        # (margin bincount + local-ballot disagreement, pallas_lion.
        # bucket_vote_stats) consumes the bucket's ballots and totals, both
        # sums over coordinates that no order can move. Purely
        # observational — the vote/apply dataflow is untouched.
        hist_acc = jnp.zeros((_vt.NBINS,), jnp.int32) if telemetry else None
        dis_acc = jnp.zeros((), jnp.int32) if telemetry else None
        # guard accumulator: the local-vs-elected disagreement count, folded
        # per bucket. The mask is applied to the bucket ballot BEFORE the
        # collective (inside vote_total — a quarantined worker's int8
        # ballots become zeros on the wire), never to the guard's own
        # observation of them.
        guard_dis = jnp.zeros((), jnp.int32) if guard_on else None
        for k in range(len(bounds)):
            ballots = _bucket_ballots(k)
            total = collectives.vote_total(
                ballots > 0, axis_name, wire, alive, state.count)
            # only the sign is applied: int8 tallies go on as they are,
            # wider ones (and the packed wires' ±1 proxies) as 0 / 1
            verdicts.append(total if total.dtype == jnp.int8
                            else (total > 0).astype(jnp.int8))
            if telemetry:
                h, d = pallas_lion.bucket_vote_stats(
                    ballots, total, w, _vt.NBINS, interpret=interpret,
                    row_block=row_block)
                hist_acc, dis_acc = hist_acc + h, dis_acc + d
            if guard_on:
                guard_dis = guard_dis + jnp.sum(
                    ((ballots > 0) != (total > 0)).astype(jnp.int32))
            if k:  # apply k−1 while bucket k's collective is in flight
                _bucket_apply(k - 1)
        _bucket_apply(len(bounds) - 1)

        pool_at, off = {}, 0  # pooled leaf -> its span of the pool
        for i in layout.pooled:
            pool_at[i], off = (off, off + sizes[i]), off + sizes[i]
        pool_piece = len(layout.pieces) - 1  # the pool, where there is one

        def _from_pool(vec, i, shape):
            return _cut(vec, *pool_at[i]).reshape(shape)

        def _leaf_out(i, leaf, rows_of, pooled_vec):
            if i in rows_of:
                return pallas_lion.from_rows_view(rows_of[i], leaf.shape)
            if i in pool_at:
                return _from_pool(pooled_vec, i, leaf.shape)
            return leaf  # zero-size leaf: nothing was voted onto it

        out_p = [_leaf_out(i, p, new_p, pool[0])
                 for i, p in enumerate(p_leaves)]
        out_m = [_leaf_out(i, m, new_m, pool[2])
                 for i, m in enumerate(m_leaves)]

        def _flat_order(of_piece):
            """Per-piece int8 vectors, in the step's order -> one [n]
            vector in flat coordinate order: what checkpoints
            (``prev_ballot``) and the host (the frame's ``elected``) read.
            A transpose of bytes, paid by guard and telemetry runs alone."""
            per_leaf: dict = {i: [] for i in layout.in_place}
            for pi, pc in enumerate(layout.pieces):
                if pc.leaf >= 0:
                    tiles = of_piece[pi].reshape(
                        -1, pc.r1 - pc.r0, pallas_lion.LANES)
                    per_leaf[pc.leaf].append(tiles.transpose(1, 0, 2).reshape(
                        -1, p_leaves[pc.leaf].shape[-1]))
            return _join([
                pallas_lion.from_rows_view(
                    _join(per_leaf[i]), p_leaves[i].shape).reshape(-1)
                if i in per_leaf else
                _from_pool(of_piece[pool_piece], i, (-1,))
                for i in range(len(sizes)) if sizes[i]])

        new_prev = state.prev_ballot
        gframe = None
        if guard_on:
            packed_now = pack_signs(_flat_order(ballots_of) > 0)
            gframe = _guard_frame(
                w, guard_nf,
                _ballot_flips(packed_now, state.prev_ballot),
                state.count >= 1,
                guard_dis.astype(jnp.float32) / n, n)
            new_prev = packed_now
        out = (
            jax.tree.unflatten(treedef, out_p),
            # this path is gated to vote_every == 1 and dcn_depth == 0,
            # where the elected-sign cache and the DCN ring are None — but
            # the invariant is "state passes through", not "they may be
            # dropped": a future un-gating must not silently lose either
            LionState(state.count + 1, jax.tree.unflatten(treedef, out_m),
                      state.rng, state.elected, state.health, new_prev,
                      state.dcn_ring),
        )
        if not telemetry:
            return out if gframe is None else out + (gframe,)
        frame = {
            "margin_hist": (hist_acc if wire_has_tally
                            else jnp.zeros((_vt.NBINS,), jnp.int32)),
            "elected": pack_signs(_flat_order(verdict_of) > 0),
            "disagree": dis_acc,
            "voted": jnp.asarray(n, jnp.int32),
            "valid": jnp.asarray(n, jnp.int32),
            # this path is gated to the deterministic mode: no quantizer
            "stoch_flip_frac": jnp.zeros((), jnp.float32),
            # gated to vote_every == 1: every step is a full re-election
            "flip_valid": jnp.asarray(True, jnp.bool_),
        }
        return out + (frame,) if gframe is None else out + (frame, gframe)

    def _hier_pipelined(vec, count, ring, alive):
        """Cross-step pipelined hier election (``dcn_pipeline_depth`` > 0):
        launch this step's level-1 (ICI) + level-2 (DCN) tallies for every
        bucket of ``vec`` into the ring slot the consume just vacated, and
        elect from the slot launched ``dcn_pipeline_depth`` steps ago —
        the complete, uniformly-stale election of step count − d's ballots
        (replica-identical by construction). Returns ``(elected [n] bool,
        elect_valid scalar bool, new_ring)``; ``elect_valid`` is False for
        the first d cold-start steps, when no in-flight tally has landed
        yet. In the jaxpr the fresh launch slots feed ONLY the ring output,
        which is what lets the DCN ppermute ring overlap the following
        steps' compute (XLA async collectives; ``lax.scan`` over fused
        steps)."""
        n = vec.shape[0]
        w = collectives.axis_size(axis_name)
        bounds = bucket_bounds(n, max(vote_buckets, 1), w, wire)
        expected = sum(hier_chunk_slot_bytes(size, w, wire_group)
                       for _, size in bounds)
        if ring.shape[-1] != expected:
            raise ValueError(
                f"dcn_ring slot holds {ring.shape[-1]} bytes but this "
                f"ballot/bucket layout needs {expected} — the ring was "
                "built for a different world/wire/bucket config "
                "(init_global_state and the step must agree)")
        slot_idx = lax.rem(count, jnp.int32(dcn_pipeline_depth))
        old_slot = lax.dynamic_slice(
            ring, (slot_idx, jnp.int32(0)), (1, ring.shape[-1]))[0]
        seg_off = 0
        new_segs, elected_parts = [], []
        for start, size in bounds:
            seg_len = hier_chunk_slot_bytes(size, w, wire_group)
            votes_b = lax.slice(vec, (start,), (start + size,))
            new_seg = collectives.hier_launch(
                votes_b, axis_name, w, wire_group, alive, count)
            old_seg = lax.slice(old_slot, (seg_off,), (seg_off + seg_len,))
            # token=new_seg[:1]: inert on real hardware (the fault is not
            # armed, no dependency is traced); under the dcn_delay link
            # emulator it pins the consume gate behind this step's launch
            # so the emulated flight time spans the real d steps of compute
            elected_parts.append(collectives.hier_consume(
                old_seg, size, axis_name, w, wire_group, alive, count,
                depth=dcn_pipeline_depth, token=new_seg[:1]))
            new_segs.append(new_seg)
            seg_off += seg_len
        new_slot = (new_segs[0] if len(new_segs) == 1
                    else jnp.concatenate(new_segs))
        new_ring = lax.dynamic_update_slice(
            ring, new_slot[None], (slot_idx, jnp.int32(0)))
        elected = (elected_parts[0] if len(elected_parts) == 1
                   else jnp.concatenate(elected_parts))
        return elected, count >= dcn_pipeline_depth, new_ring

    def _elect_lazy(flat_votes, state: LionState, alive=None):
        """vote_every > 1: vote the rotating slice, refresh the packed sign
        cache, return (full elected bools, update-validity mask, new cache,
        telemetry aux, refreshed guard prev-ballot or None, new DCN ring or
        None). The aux — (slice ballots, slice totals, slice elections,
        real-coordinate mask over the CONSUMED slice, real-coordinate mask
        over the LAUNCHED slice) — feeds the vote-health and guard frames;
        it is dead code XLA prunes when both are off. ``alive`` masks
        quarantined workers out of the slice election (the guard's enforce
        mode).

        Under the cross-step DCN pipeline (``dcn_pipeline_depth`` d > 0)
        the slice LAUNCHED this step is slot count mod K as always, but the
        election CONSUMED — and written into the elected cache — is of the
        slice launched d steps ago, slot (count − d) mod K: sign staleness
        compounds to ≤ K + d steps, and slot j's coordinates first receive
        an update at count == j + d (the combined cold start)."""
        from distributed_lion_tpu.ops.codec import pack_signs, unpack_signs

        n = flat_votes.shape[0]
        chunk = vote_chunk_elems(n, vote_every)
        padded = jnp.concatenate(
            [flat_votes, jnp.zeros((vote_every * chunk - n,), flat_votes.dtype)]
        ) if vote_every * chunk > n else flat_votes
        slot = lax.rem(state.count, jnp.int32(vote_every))
        sl = lax.dynamic_slice(padded, (slot * chunk,), (chunk,))
        new_ring = None
        if dcn_pipeline_depth > 0:
            # launch the fresh slice's tallies into the ring; elect the
            # slice launched d steps ago. The consumed election belongs to
            # slot (count − d) mod K of the rotation.
            elected_sl, elect_valid, new_ring = _hier_pipelined(
                sl, state.count, state.dcn_ring, alive)
            totals_sl = jnp.where(elected_sl, 1, -1)
            write_slot = lax.rem(state.count - dcn_pipeline_depth,
                                 jnp.int32(vote_every))
        else:
            # the rotating 1/K slice votes bucket-wise too: same elected
            # bits, but the slice's wire splits into pipelineable chunks
            totals_sl = collectives.vote_total_bucketed(
                sl, axis_name, wire, vote_buckets, alive, state.count)
            elected_sl = totals_sl > 0
            elect_valid = jnp.asarray(True)
            write_slot = slot
        cache_upd = lax.dynamic_update_slice(
            state.elected, pack_signs(elected_sl), (write_slot * chunk // 8,)
        )
        # during the pipeline's cold start no election landed: the cache
        # must not adopt the zero-slot garbage (write_slot also clamps
        # negative there — the where() discards that write entirely)
        new_cache = (cache_upd if dcn_pipeline_depth == 0
                     else jnp.where(elect_valid, cache_upd, state.elected))
        new_prev = None
        if guard_on:
            # the guard's prev-ballot cache mirrors the elected cache's
            # slot layout and tracks the LAUNCHED slice (the local ballot
            # cast this step), so XOR-ing old vs new isolates this slot's
            # flips against the SAME slot's ballot one rotation (K steps)
            # ago — launch-side at any pipeline depth
            new_prev = lax.dynamic_update_slice(
                state.prev_ballot, pack_signs(sl), (slot * chunk // 8,))
        bits = unpack_signs(new_cache, (vote_every * chunk,))
        # cold start: slot j's election first LANDS at count == j + d, so
        # until then its coordinates get no update (replicas agree — count
        # is shared)
        slot_idx = jnp.arange(vote_every * chunk, dtype=jnp.int32) // chunk
        valid = slot_idx <= state.count - dcn_pipeline_depth
        # only the LAST slot can run past n: alignment pads the slice there.
        # The consume mask covers the slice the ELECTION belongs to (and is
        # all-False while no election has landed); the launch mask covers
        # the slice the local ballots were cast for.
        ar = jnp.arange(chunk, dtype=jnp.int32)
        mask_launch = (slot * chunk + ar) < n
        mask_consume = (((write_slot * chunk + ar) < n) & elect_valid
                        if dcn_pipeline_depth > 0 else mask_launch)
        return bits[:n], valid[:n], new_cache, (sl, totals_sl, elected_sl,
                                                mask_consume, mask_launch), \
            new_prev, new_ring

    def _make_frame(local, totals, elected, *, mask, voted, valid,
                    elected_packed, flip_valid):
        """Assemble the per-step vote-health frame (telemetry mode only) from
        the XLA path's vote internals: local bool ballots, the (possibly
        ±1-proxy) totals, the elected bools, and — under lazy refresh — the
        real-coordinate mask over the padded slice plus the refreshed packed
        cache. Observational: consumes the vote, never feeds back into it."""
        from distributed_lion_tpu.ops.codec import pack_signs

        w = collectives.axis_size(axis_name)
        hist = (_vt.margin_hist(totals, w, mask=mask) if wire_has_tally
                else jnp.zeros((_vt.NBINS,), jnp.int32))
        dis = local != elected
        if mask is not None:
            dis = dis & mask
        return {
            "margin_hist": hist,
            "elected": (pack_signs(elected) if elected_packed is None
                        else elected_packed),
            "disagree": jnp.sum(dis.astype(jnp.int32)),
            "voted": jnp.asarray(voted, jnp.int32),
            "valid": valid,
            "stoch_flip_frac": jnp.zeros((), jnp.float32),
            "flip_valid": jnp.asarray(flip_valid, jnp.bool_),
        }

    def step(params, grads, state: LionState):
        # grad → momentum-dtype cast, hoisted ONCE for both kernel paths
        # (the Pallas path used to re-cast internally after this cast)
        grads = jax.tree.map(lambda g, m: g.astype(m.dtype), grads, state.exp_avg)
        guard_nf = None
        if guard_on:
            # nonfinite ballot inputs, measured BEFORE enforce's sanitize
            # (and before sign-encoding hides them: NaN u-terms vote −1)
            guard_nf = _nonfinite_count(grads, state.exp_avg)
        if enforce:
            # degraded-mode training: a poisoned worker's nonfinite grad
            # coordinates are zeroed so they can neither poison its local
            # momentum forever nor steer its ballot; with finite grads
            # where() is the identity, preserving the all-healthy
            # bit-identity contract
            grads = jax.tree.map(
                lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g)),
                grads)
        if (interpret is not None and not stochastic and vote_every == 1
                and dcn_pipeline_depth == 0):
            p_dtypes = {p.dtype for p in jax.tree.leaves(params)}
            m_dtypes = {m.dtype for m in jax.tree.leaves(state.exp_avg)}
            if len(p_dtypes) == 1 and len(m_dtypes) == 1:
                return _step_pallas(params, grads, state, guard_nf)
        alive = state.health if enforce else None
        w_guard = collectives.axis_size(axis_name) if guard_on else None
        lr = resolve_lr(learning_rate, state.count)

        # 1) weight decay, multiplicatively, before the update (ref :64).
        decayed = jax.tree.map(lambda p: lion_math.decay_params(p, lr, weight_decay), params)

        # 2) binarize: this worker's bool ballots (ref :68-71 / :105-108).
        if stochastic:
            widx = lax.axis_index(axis_name)
            base = jax.random.fold_in(state.rng, state.count)
            worker_key = jax.random.fold_in(base, widx)
            leaves = jax.tree.leaves(state.exp_avg)
            keys = jax.random.split(worker_key, len(leaves))
            keytree = jax.tree.unflatten(jax.tree.structure(state.exp_avg), list(keys))
            votes = jax.tree.map(
                lambda k, g, m: lion_math.stochastic_vote_bool(k, g, m, b1, max_grad_norm),
                keytree, grads, state.exp_avg,
            )
        else:
            votes = jax.tree.map(
                lambda g, m: lion_math.sign_vote_bool(g, m, b1), grads, state.exp_avg
            )

        # 3) ONE collective for the whole pytree (vs per-tensor all_gather,
        #    ref :81): flatten → vote → split. The vote runs through
        #    vote_total (elected ⇔ total > 0) so telemetry can read the
        #    margin where the wire moves it; the election itself is the
        #    same function majority_vote_bucketed computes.
        flat = _flatten_votes(votes)
        new_cache = state.elected
        new_prev = state.prev_ballot
        new_ring = state.dcn_ring
        frame = None
        gframe = None
        if vote_every == 1 and dcn_pipeline_depth > 0:
            # cross-step pipelined hier wire: launch this step's tallies
            # into the ring, apply the election of step count − d's ballots
            # (uniformly stale → replicas agree); the first d steps apply
            # no sign update (decay still runs — the lazy-slot rule)
            elected, elect_valid, new_ring = _hier_pipelined(
                flat, state.count, state.dcn_ring, alive)
            totals = jnp.where(elected, 1, -1)  # ±1 proxy (hier never
            # moves the tally magnitude — the telemetry histogram is
            # zeroed for proxy wires regardless)
            signs = jnp.where(elected, 1.0, -1.0) * elect_valid
            signs_tree = _split_votes(signs, votes)
            new_params = jax.tree.map(
                lambda p, s: p - jnp.asarray(lr, p.dtype) * s.astype(p.dtype),
                decayed, signs_tree,
            )
            n_flat = flat.shape[0]
            if telemetry:
                frame = _make_frame(
                    flat, totals, elected,
                    mask=jnp.broadcast_to(elect_valid, flat.shape),
                    voted=jnp.where(elect_valid, n_flat, 0),
                    valid=jnp.where(elect_valid, n_flat, 0)
                    .astype(jnp.int32),
                    elected_packed=None,
                    # the first landed election (count == d) has only the
                    # zero-init accumulator to XOR against
                    flip_valid=state.count >= dcn_pipeline_depth + 1)
            if guard_on:
                packed_now = pack_signs(flat)
                gframe = _guard_frame(
                    w_guard, guard_nf,
                    _ballot_flips(packed_now, state.prev_ballot),
                    state.count >= 1,
                    # local FRESH ballot vs the d-step-stale consensus —
                    # staleness inflates every worker equally, so the
                    # guard's RELATIVE outlier rule still separates a sick
                    # voter; zero while no election has landed
                    jnp.where(elect_valid,
                              jnp.mean((flat != elected)
                                       .astype(jnp.float32)), 0.0),
                    n_flat)
                new_prev = packed_now
        elif vote_every == 1:
            totals = collectives.vote_total_bucketed(
                flat, axis_name, wire, vote_buckets, alive, state.count)
            elected = totals > 0
            elected_tree = _split_votes(elected, votes)
            # 4) apply the elected ±1 update (ref :91-92). The psum output is
            #    identical on every worker, so replicated params stay replicated.
            new_params = jax.tree.map(
                lambda p, v: lion_math.apply_signed_update(p, v, lr),
                decayed, elected_tree,
            )
            if telemetry:
                frame = _make_frame(flat, totals, elected, mask=None,
                                    voted=flat.shape[0],
                                    valid=jnp.asarray(flat.shape[0],
                                                      jnp.int32),
                                    elected_packed=None, flip_valid=True)
            if guard_on:
                packed_now = pack_signs(flat)
                gframe = _guard_frame(
                    w_guard, guard_nf,
                    _ballot_flips(packed_now, state.prev_ballot),
                    state.count >= 1,
                    jnp.mean((flat != elected).astype(jnp.float32)),
                    flat.shape[0])
                new_prev = packed_now
        else:
            elected, valid, new_cache, aux, lazy_prev, lazy_ring = \
                _elect_lazy(flat, state, alive)
            if lazy_ring is not None:
                new_ring = lazy_ring
            signs = jnp.where(elected, 1.0, -1.0) * valid
            signs_tree = _split_votes(signs, votes)
            new_params = jax.tree.map(
                lambda p, s: p - jnp.asarray(lr, p.dtype) * s.astype(p.dtype),
                decayed, signs_tree,
            )
            sl, totals_sl, elected_sl, mask_sl, mask_launch = aux
            # under the DCN pipeline the launched slice (local ballots sl)
            # and the consumed election (elected_sl) cover DIFFERENT
            # coordinate slots — a local-vs-elected comparison would be
            # cross-coordinate noise, so disagreement reports 0 there
            # (documented in ARCHITECTURE 'DCN overlap')
            dis_defined = dcn_pipeline_depth == 0
            if telemetry:
                frame = _make_frame(
                    sl, totals_sl, elected_sl,
                    mask=(mask_sl if dis_defined
                          else jnp.zeros_like(mask_sl)),
                    voted=jnp.sum(mask_sl.astype(jnp.int32)),
                    valid=jnp.sum(valid.astype(jnp.int32)),
                    elected_packed=new_cache,
                    # the refreshed slot last voted one rotation (K steps,
                    # + the pipeline's d) ago: before that its cache bytes
                    # are the zero init, not a previous election
                    flip_valid=state.count >= vote_every
                    + dcn_pipeline_depth)
            if guard_on:
                voted_launch = jnp.sum(mask_launch.astype(jnp.int32))
                dis_sl = (jnp.sum(((sl != elected_sl) & mask_sl)
                                  .astype(jnp.int32)) if dis_defined
                          else jnp.zeros((), jnp.int32))
                gframe = _guard_frame(
                    w_guard, guard_nf,
                    _ballot_flips(lazy_prev, state.prev_ballot),
                    # the refreshed slot's previous ballot is real only
                    # after a full rotation (same cold start as the flip
                    # telemetry; prev_ballot tracks LAUNCHES, so the
                    # pipeline depth does not enter)
                    state.count >= vote_every,
                    dis_sl.astype(jnp.float32)
                    / jnp.maximum(voted_launch, 1).astype(jnp.float32),
                    voted_launch)
                new_prev = lazy_prev
        if telemetry and stochastic:
            # quantizer noise: how often the stochastic ballot differs from
            # the deterministic sign it replaces (full-ballot local mean)
            det_flat = _flatten_votes(jax.tree.map(
                lambda g, m: lion_math.sign_vote_bool(g, m, b1),
                grads, state.exp_avg))
            frame["stoch_flip_frac"] = jnp.mean(
                (flat != det_flat).astype(jnp.float32))

        # 5) momentum with the LOCAL gradient — divergent by design (ref :96;
        #    under enforce the gradient was already nonfinite-sanitized, so
        #    one NaN batch cannot poison exp_avg forever).
        new_m = jax.tree.map(
            lambda g, m: lion_math.momentum_update(g, m, b2), grads, state.exp_avg
        )
        out_state = LionState(state.count + 1, new_m, state.rng, new_cache,
                              state.health, new_prev, new_ring)
        out = (new_params, out_state)
        if telemetry:
            out = out + (frame,)
        if guard_on:
            out = out + (gframe,)
        return out

    # meta: the comm config init_global_state needs to shape world-sized
    # state (the DCN pipeline ring) that init cannot know the width of
    return FunctionalOptimizer(init=init, step=step, meta={
        "wire": wire, "vote_every": vote_every,
        "vote_buckets": max(vote_buckets, 1),
        "dcn_pipeline_depth": dcn_pipeline_depth,
    })


# ---------------------------------------------------------------------------
# Global-state helpers: stacked per-worker momentum with a leading [world]
# axis, sharded P('data'), so divergent state coexists with replicated params
# under shard_map and checkpoints capture all workers (SURVEY §7 hard part 1/3).
# ---------------------------------------------------------------------------

def init_global_state(opt: FunctionalOptimizer, params, world: int,
                      rng: Optional[jax.Array] = None) -> LionState:
    """Initialize optimizer state with exp_avg stacked to ``[world, ...]``.

    The result should be device_put with the leading axis sharded over the
    data mesh axis (``parallel.mesh.data_sharded``).
    """
    st_shapes = jax.eval_shape(lambda p: opt.init(p, rng), params)
    exp_avg = jax.tree.map(
        lambda m: jnp.zeros((world,) + m.shape, m.dtype), st_shapes.exp_avg
    )
    elected = (None if st_shapes.elected is None
               else jnp.zeros(st_shapes.elected.shape, st_shapes.elected.dtype))
    # guard state: the per-worker previous ballot stacks [world, bytes] like
    # the momenta; the health mask is replicated [world], all-healthy at init
    prev_ballot = (None if st_shapes.prev_ballot is None
                   else jnp.zeros((world,) + st_shapes.prev_ballot.shape,
                                  st_shapes.prev_ballot.dtype))
    health = (None if st_shapes.prev_ballot is None
              else jnp.ones((world,), jnp.bool_))
    # DCN pipeline ring (dcn_pipeline_depth > 0, hier wire): one slot per
    # in-flight step of per-worker packed level-2 tallies. Like health, it
    # is created HERE — its slot width needs the world size (W/g groups),
    # which worker-level init cannot know. The comm config rides opt.meta.
    meta = opt.meta or {}
    depth = int(meta.get("dcn_pipeline_depth", 0) or 0)
    dcn_ring = None
    if depth > 0:
        _, group = parse_wire(meta["wire"])
        n = sum(p.size for p in jax.tree.leaves(params))
        slot = hier_ring_slot_bytes(n, world, group,
                                    meta.get("vote_buckets", 1) or 1,
                                    meta.get("vote_every", 1) or 1)
        dcn_ring = jnp.zeros((world, depth, slot), jnp.uint8)
    return LionState(count=jnp.zeros((), jnp.int32), exp_avg=exp_avg, rng=rng,
                     elected=elected, health=health, prev_ballot=prev_ballot,
                     dcn_ring=dcn_ring)


def squeeze_worker_state(state: LionState) -> LionState:
    """Inside shard_map: drop this worker's leading [1] momentum (and guard
    prev-ballot / DCN-ring) axis; the elected-sign cache and health mask are
    replicated and pass through."""
    return LionState(state.count, jax.tree.map(lambda m: m[0], state.exp_avg),
                     state.rng, state.elected, state.health,
                     None if state.prev_ballot is None
                     else state.prev_ballot[0],
                     None if state.dcn_ring is None else state.dcn_ring[0],
                     None if state.moe_ring is None else state.moe_ring[0])


def expand_worker_state(state: LionState) -> LionState:
    """Inside shard_map: restore the leading [1] axis before returning."""
    return LionState(state.count, jax.tree.map(lambda m: m[None], state.exp_avg),
                     state.rng, state.elected, state.health,
                     None if state.prev_ballot is None
                     else state.prev_ballot[None],
                     None if state.dcn_ring is None
                     else state.dcn_ring[None],
                     None if state.moe_ring is None
                     else state.moe_ring[None])


def remap_worker_momentum(exp_avg, old_world: int, new_world: int):
    """Remap stacked ``[W, ...]`` per-worker Lion momenta to ``[W', ...]``
    for elastic resume (train/loop._maybe_resume + --elastic_resume).

    The per-worker momenta are the algorithm's only divergent state; the
    defined remap preserves their cross-worker MEAN exactly in every case,
    so the center of the vote distribution — what the majority election
    estimates — is unchanged by a world-size change:

    - ``W' == W``: identity (bit-exact round trip, pinned by tests).
    - ``W' < W``, ``W % W' == 0`` (e.g. 4→2, 4→1): **shard-group
      re-averaging** — new worker i takes the mean of old workers
      ``[i*g, (i+1)*g)`` with ``g = W/W'``; the mean of group means over
      equal-size groups is the overall mean.
    - ``W' > W``, ``W' % W == 0`` (e.g. 2→4): each old worker's momentum is
      replicated to its ``W'/W`` successors (``repeat`` along axis 0); every
      old momentum appears equally often, so the mean is unchanged. The
      clones re-diverge immediately through their per-worker gradients (and,
      under stochastic binarization, per-worker RNG folds of the new index).
    - otherwise (coprime W→W'): every new worker starts from the old
      cross-worker mean — per-worker diversity is deliberately collapsed
      rather than invented, and the vote center is still preserved.

    Reductions run in f32 and cast back (bf16 ``mom_dtype`` momenta must not
    lose their mean to accumulation order)."""
    if new_world == old_world:
        return exp_avg
    if new_world < 1 or old_world < 1:
        raise ValueError(f"invalid world sizes {old_world}->{new_world}")

    def _remap(m):
        if m.shape[0] != old_world:
            raise ValueError(
                f"momentum leaf has leading dim {m.shape[0]}, expected "
                f"old world {old_world}")
        f32 = jnp.asarray(m, jnp.float32)
        if old_world % new_world == 0:
            g = old_world // new_world
            out = f32.reshape((new_world, g) + f32.shape[1:]).mean(axis=1)
        elif new_world % old_world == 0:
            out = jnp.repeat(f32, new_world // old_world, axis=0)
        else:
            out = jnp.broadcast_to(f32.mean(axis=0, keepdims=True),
                                   (new_world,) + f32.shape[1:])
        return out.astype(m.dtype)

    return jax.tree.map(_remap, exp_avg)


def heal_worker_momentum(exp_avg, healthy, workers):
    """Reset quarantined/healed workers' momenta to the HEALTHY mean.

    The vote guard's readmission (and elastic resume over a checkpoint with
    quarantined workers) must not let a sick worker's stale or poisoned
    momentum re-enter the election: each worker in ``workers`` gets the mean
    of the momenta whose ``healthy`` bit is True — the center of the healthy
    vote distribution, the same quantity :func:`remap_worker_momentum`
    preserves. The healed clone re-diverges immediately through its own
    gradients. Reductions run in f32 and cast back (same precision rule as
    the remap).

    Args:
        exp_avg: stacked ``[W, ...]`` momentum pytree (outside shard_map).
        healthy: ``[W]`` bool mask of momenta trusted as the mean's source.
        workers: iterable of worker indices to overwrite.
    """
    healthy = jnp.asarray(healthy, jnp.bool_)
    workers = [int(w) for w in workers]
    denom = jnp.maximum(jnp.sum(healthy.astype(jnp.float32)), 1.0)

    def _heal(m):
        f32 = jnp.asarray(m, jnp.float32)
        wmask = healthy.astype(jnp.float32).reshape(
            (-1,) + (1,) * (f32.ndim - 1))
        mean = jnp.sum(f32 * wmask, axis=0) / denom
        out = f32
        for w in workers:
            out = out.at[w].set(mean)
        return out.astype(m.dtype)

    return jax.tree.map(_heal, exp_avg)
