"""Cross-step DCN overlap (ISSUE 8): the pipelined level-2 hier vote.

The tentpole contract, pinned here:

- the launch/consume split of the hier election (collectives.hier_launch /
  hier_consume) is bit-identical to an INDEPENDENT numpy
  majority-of-majorities reference at depth 0, with and without health
  masks — the "depth-0 == today's hier wire" pin that survives the
  refactor;
- ``dcn_pipeline_depth=0`` is byte-for-byte the default hier wire across
  vote_buckets {1,4} × det/stoch × guard off/enforce × XLA/Pallas;
- at depth d the signs APPLIED at step t are exactly the signs the
  synchronous wire elects at step t−d (ballots are params-independent —
  momentum is a pure function of the grad sequence — so the shifted-delta
  identity is exact), and the first d steps apply no update;
- the elected-sign cache under ``vote_every`` × depth trails the
  synchronous cache by exactly d steps;
- a group fully quarantined at EITHER end of a tally's flight abstains
  from the stale election (the launch-mask ∩ current-mask rule);
- the ``dcn_delay`` link emulator charges the synchronous wire the full
  injected round trip while depth ≥ 1 demonstrably hides part of it
  (measured via collectives.DCN_WAIT — wall-clock-free, so the assertion
  survives a loaded CI box), and is timing-only (elections unchanged);
- the in-flight ring rides checkpoints (tests/test_crash_resume.py holds
  the resume cells) and misconfiguration fails loudly at build time.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from _sharded import (
    assert_trees_equal,
    sharded,
    sharded_opt_step,
    toy_problem,
)
from distributed_lion_tpu.ops.codec import (
    a2a_chunk_bytes,
    hier_chunk_slot_bytes,
    hier_ring_slot_bytes,
    vote_chunk_elems,
)
from distributed_lion_tpu.optim import distributed_lion, init_global_state
from distributed_lion_tpu.parallel import collectives
from distributed_lion_tpu.train import resilience


# ------------------------------------------------- independent reference
def _ref_hier(ballots: np.ndarray, g: int, alive=None) -> np.ndarray:
    """Majority-of-majorities over [W, n] bool ballots, straight from the
    definition (no packing, no rings): level-1 ties → −1 inside each
    g-worker group (healthy members only), a group with no healthy member
    abstains at level 2, level-2 ties → −1 over the participating groups."""
    w, n = ballots.shape
    alive = np.ones(w, bool) if alive is None else np.asarray(alive, bool)
    signs = np.where(ballots, 1, -1) * alive[:, None]
    verdicts, counted = [], []
    for k in range(w // g):
        grp = signs[k * g:(k + 1) * g]
        verdicts.append(grp.sum(0) > 0)
        counted.append(alive[k * g:(k + 1) * g].any())
    verdicts = np.stack(verdicts)
    counted = np.asarray(counted)
    return verdicts[counted].sum(0) * 2 > counted.sum()


def _election(mesh, wire, masked):
    """The compiled election ``(ballots[, alive]) -> elected``: the mask is
    an argument, so one masked program serves every mask."""
    def body(b, *alive):
        return collectives.majority_vote(b[0], "data", wire, *alive)

    specs = (P("data"), P()) if masked else (P("data"),)
    return sharded(body, mesh, specs, P(), check_vma=False)


@pytest.mark.parametrize("g", [2, 4, 8])
@pytest.mark.parametrize("n", [7, 64, 1003])
def test_hier_depth0_matches_reference(mesh8, g, n):
    """The refactored (launch/consume-split) hier election == the
    independent majority-of-majorities reference, masked and unmasked —
    the depth-0 bit-identity pin the ISSUE-8 refactor must not move."""
    rng = np.random.default_rng(5)
    ballots = jnp.asarray(rng.integers(0, 2, size=(8, n)).astype(bool))
    got = np.asarray(_election(mesh8, f"hier:{g}", masked=False)(ballots))
    np.testing.assert_array_equal(got, _ref_hier(np.asarray(ballots), g))
    # masked: one quarantined worker, and one fully-dead group
    masked = _election(mesh8, f"hier:{g}", masked=True)
    for alive in (np.array([True] * 7 + [False]),
                  np.array([False] * g + [True] * (8 - g))):
        got = np.asarray(masked(ballots, jnp.asarray(alive)))
        np.testing.assert_array_equal(
            got, _ref_hier(np.asarray(ballots), g, alive))


def test_mid_flight_quarantine_gates_stale_tally(mesh8):
    """The launch-mask ∩ current-mask rule: a group fully quarantined at
    EITHER end of the flight abstains from the stale election. Drives
    hier_launch/hier_consume directly with different masks at each end."""
    g, n = 4, 257
    rng = np.random.default_rng(9)
    ballots = jnp.asarray(rng.integers(0, 2, size=(8, n)).astype(bool))
    all_alive = np.ones(8, bool)
    g1_dead = np.array([True] * 4 + [False] * 4)

    def body(b, la, ca):
        slot = collectives.hier_launch(b[0], "data", 8, g, la)
        return collectives.hier_consume(slot, n, "data", 8, g, ca)

    flight = sharded(body, mesh8, (P("data"), P(), P()), P(),
                     check_vma=False)

    def run(launch_alive, consume_alive):
        return np.asarray(flight(ballots, jnp.asarray(launch_alive),
                                 jnp.asarray(consume_alive)))

    ref_excluded = _ref_hier(np.asarray(ballots), g, g1_dead)
    # dead at launch, revived before consume: still excluded (its launch
    # verdict was cast with zero healthy members — garbage forever)
    np.testing.assert_array_equal(run(g1_dead, all_alive), ref_excluded)
    # healthy at launch, fully quarantined before consume: excluded too
    np.testing.assert_array_equal(run(all_alive, g1_dead), ref_excluded)
    # healthy at both ends == the unmasked election
    np.testing.assert_array_equal(run(all_alive, all_alive),
                                  _ref_hier(np.asarray(ballots), g))


# ----------------------------------------------------- optimizer matrix
def _run_steps(opt, params, grads_per_step, mesh, world, rng=None):
    """Drive opt.step under shard_map over a SEQUENCE of per-step grads;
    returns the param trajectory (host copies) + final state."""
    state = init_global_state(opt, params, world, rng=rng)
    step = sharded_opt_step(opt, mesh, state)
    traj = [jax.device_get(params)]
    p, st = params, state
    for g in grads_per_step:
        p, st = step(p, g, st)
        traj.append(jax.device_get(p))
    return traj, st


def _grad_seq(steps, world=8, n=40):
    return [{
        "w": jax.random.normal(jax.random.key(100 + i), (world, n)),
        "b": jax.random.normal(jax.random.key(200 + i), (world, 3)),
    } for i in range(steps)]


@pytest.mark.parametrize("stoch", [False, True], ids=["det", "stoch"])
@pytest.mark.parametrize("buckets", [1, 4])
@pytest.mark.parametrize("guard", ["off", "enforce"])
def test_depth0_bit_identical_to_default_wire(mesh8, buckets, stoch, guard):
    """Acceptance cell: an EXPLICIT dcn_pipeline_depth=0 is byte-for-byte
    the default hier wire across vote_buckets × det/stoch × guard (XLA
    path; the Pallas cell is below — its gate only admits det × guard
    combinations it compiled before this PR)."""
    params, _ = toy_problem()
    gseq = _grad_seq(3)
    kw = dict(learning_rate=0.01, weight_decay=0.01, wire="hier:4",
              vote_buckets=buckets, guard=guard,
              max_grad_norm=1.0 if stoch else None)
    rng = jax.random.key(7) if stoch else None
    base, base_st = _run_steps(distributed_lion(**kw), params, gseq, mesh8,
                               8, rng=rng)
    expl, expl_st = _run_steps(distributed_lion(dcn_pipeline_depth=0, **kw),
                               params, gseq, mesh8, 8, rng=rng)
    for a, b in zip(base, expl):
        assert_trees_equal(a, b)
    assert_trees_equal(base_st.exp_avg, expl_st.exp_avg)


def test_depth0_bit_identical_pallas(mesh8):
    """The Pallas window path at depth 0 (its gate) still matches the XLA
    default wire — and a depth > 0 build routes to the XLA path instead of
    the fused kernels, bit-identical to an explicit kernel='xla' build."""
    params, _ = toy_problem(n=300)
    gseq = _grad_seq(3, n=300)
    base, _ = _run_steps(
        distributed_lion(learning_rate=0.01, wire="hier:4", kernel="xla"),
        params, gseq, mesh8, 8)
    pall, _ = _run_steps(
        distributed_lion(learning_rate=0.01, wire="hier:4", kernel="pallas",
                         dcn_pipeline_depth=0, vote_buckets=4),
        params, gseq, mesh8, 8)
    for a, b in zip(base, pall):
        assert_trees_equal(a, b)
    d_pall, _ = _run_steps(
        distributed_lion(learning_rate=0.01, wire="hier:4", kernel="pallas",
                         dcn_pipeline_depth=1),
        params, gseq, mesh8, 8)
    d_xla, _ = _run_steps(
        distributed_lion(learning_rate=0.01, wire="hier:4", kernel="xla",
                         dcn_pipeline_depth=1),
        params, gseq, mesh8, 8)
    for a, b in zip(d_pall, d_xla):
        assert_trees_equal(a, b)


@pytest.mark.parametrize("depth,buckets", [(1, 1), (2, 3)])
def test_staleness_shift_is_exact(mesh8, depth, buckets):
    """The semantics pin: Lion's ballots are params-independent (momentum
    is a pure function of the grad sequence), so with weight_decay=0 and a
    constant lr the signs applied at depth-d step t are EXACTLY the signs
    the synchronous wire applies at step t−d — param deltas shift by d
    steps, bit-for-bit — and the first d steps apply no update at all."""
    params, _ = toy_problem()
    gseq = _grad_seq(6)
    kw = dict(learning_rate=0.01, weight_decay=0.0, wire="hier:4",
              vote_buckets=buckets)
    t0, _ = _run_steps(distributed_lion(**kw), params, gseq, mesh8, 8)
    td, _ = _run_steps(distributed_lion(dcn_pipeline_depth=depth, **kw),
                       params, gseq, mesh8, 8)
    for t in range(depth):  # cold start: no update (wd=0 → params frozen)
        assert_trees_equal(td[t + 1], td[t])
    for t in range(depth, 6):
        d_now = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                             td[t + 1], td[t])
        d_ref = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                             t0[t - depth + 1], t0[t - depth])
        assert_trees_equal(d_now, d_ref)


def test_lazy_cache_trails_by_depth(mesh8):
    """vote_every × depth composition: the elected-sign cache at depth d
    after step t equals the synchronous lazy cache after step t−d (the
    consumed election lands in slot (t−d) mod K), and cold-start slots
    stay at their zero init."""
    params, _ = toy_problem()
    gseq = _grad_seq(9)
    kw = dict(learning_rate=0.01, weight_decay=0.0, wire="hier:4",
              vote_every=4)

    def caches(depth):
        opt = distributed_lion(dcn_pipeline_depth=depth, **kw)
        state = init_global_state(opt, params, 8)
        step = sharded_opt_step(opt, mesh8, state)
        out, p, st = [], params, state
        for g in gseq:
            p, st = step(p, g, st)
            out.append(np.asarray(jax.device_get(st.elected)))
        return out

    c0 = caches(0)
    c2 = caches(2)
    zero = np.zeros_like(c0[0])
    np.testing.assert_array_equal(c2[0], zero)  # nothing landed yet
    np.testing.assert_array_equal(c2[1], zero)
    for t in range(2, 9):
        np.testing.assert_array_equal(c2[t], c0[t - 2])


# -------------------------------------------------- the dcn_delay link
def test_dcn_delay_charges_sync_and_depth_hides(mesh4):
    """The link emulator: at depth 0 every step pays ~the full injected
    round trip at the consume gate (DCN_WAIT records it); at depth 1 the
    steps of compute inside the flight window count toward the deadline,
    so the residual wait measurably shrinks. Wait-based, not wall-based —
    immune to CI box noise — and the fault is timing-only: the parameter
    trajectory is bit-identical armed vs unarmed."""
    params, _ = toy_problem(world=4, n=20_000)
    gseq = _grad_seq(6, world=4, n=20_000)
    delay = 0.08
    kw = dict(learning_rate=0.01, wire="hier:2")

    def run(depth, armed):
        resilience.inject_fault("dcn_delay", delay if armed else None)
        collectives.dcn_link_reset()
        try:
            traj, _ = _run_steps(
                distributed_lion(dcn_pipeline_depth=depth, **kw), params,
                gseq, mesh4, 4)
            waits = collectives.DCN_WAIT.pop()
            return traj, sum(waits.values())
        finally:
            resilience.inject_fault("dcn_delay", None)
            collectives.dcn_link_reset()

    t0_armed, wait0 = run(0, True)
    t0_plain, _ = run(0, False)
    for a, b in zip(t0_armed, t0_plain):  # timing-only
        assert_trees_equal(a, b)
    # the synchronous wire pays ~the full round trip every step (first
    # consume may ride the compile window; demand 4 of 6)
    assert wait0 >= 4 * delay, wait0
    _, wait1 = run(1, True)
    # depth 1 hides at least the per-step compute behind the flight; even
    # on a trivial toy problem the steady-state residual is (L−c)/2 < L,
    # so demand a ≥25% cut with headroom for a loaded box
    assert wait1 <= 0.75 * wait0, (wait0, wait1)


# ------------------------------------------------- byte conservation
@pytest.mark.parametrize("depth", [0, 1, 2])
@pytest.mark.parametrize("ve,buckets", [(1, 1), (1, 4), (4, 1)])
def test_hier_depth_wire_bytes_drift_zero(mesh8, depth, ve, buckets):
    """ISSUE 8 satellite: the overlapped leg moves exactly the same bytes
    every step — one launch + one consume — so the trace-time measured
    ledger equals codec's analytic accounting EXACTLY for hier ×
    dcn_pipeline_depth {0,1,2} × vote_every {1,4} (and the accounting
    itself is depth-invariant). Abstract eval only: no compile."""
    from distributed_lion_tpu.ops.codec import wire_bytes_per_param
    from distributed_lion_tpu.train import telemetry

    params, grads = toy_problem()
    n = sum(p.size for p in jax.tree.leaves(params))
    opt = distributed_lion(0.01, wire="hier:4", vote_every=ve,
                           vote_buckets=buckets, dcn_pipeline_depth=depth)
    state = init_global_state(opt, params, 8)
    measured = telemetry.measure_step_wire(
        sharded_opt_step(opt, mesh8, state), params, grads, state)
    acct = wire_bytes_per_param(n, 8, "hier:4", vote_every=ve,
                                vote_buckets=buckets,
                                dcn_pipeline_depth=depth)
    assert measured["bytes_per_step"] == acct["bytes_per_step"], (
        measured, acct)
    assert measured["dcn_bytes_per_step"] == acct["dcn_bytes_per_step"]
    # the accounting itself must be depth-invariant (bytes never change;
    # only the latency eligibility flag does)
    base = wire_bytes_per_param(n, 8, "hier:4", vote_every=ve,
                                vote_buckets=buckets)
    assert acct["bytes_per_step"] == base["bytes_per_step"]
    assert acct["dcn_bytes_per_step"] == base["dcn_bytes_per_step"]
    assert acct["dcn_overlap_frac"] == (1.0 if depth else 0.0)


# --------------------------------------------------------- ring layout
def test_ring_slot_bytes_layout():
    w, g = 8, 4
    for n in (7, 64, 1003, 123_457):
        for buckets in (1, 3, 4):
            from distributed_lion_tpu.ops.codec import bucket_bounds

            per = [hier_chunk_slot_bytes(size, w, g)
                   for _, size in bucket_bounds(n, buckets, w, f"hier:{g}")]
            assert hier_ring_slot_bytes(n, w, g, buckets) == sum(per)
            # each segment: [G] mask + [G, chunk/8] stack
            for (_, size), seg in zip(
                    bucket_bounds(n, buckets, w, f"hier:{g}"), per):
                assert seg == (w // g) * (1 + a2a_chunk_bytes(size, g))
    # lazy refresh lays the ring out for the PADDED rotating slice
    assert hier_ring_slot_bytes(1003, w, g, 1, vote_every=4) == \
        hier_ring_slot_bytes(vote_chunk_elems(1003, 4), w, g, 1)
    with pytest.raises(ValueError, match="does not divide"):
        hier_ring_slot_bytes(100, 8, 3)


def test_ring_rides_state_with_expected_shape(mesh8):
    opt = distributed_lion(wire="hier:4", dcn_pipeline_depth=3,
                           vote_buckets=2)
    params, _ = toy_problem()
    n = sum(p.size for p in jax.tree.leaves(params))
    state = init_global_state(opt, params, 8)
    assert state.dcn_ring.shape == (8, 3, hier_ring_slot_bytes(n, 8, 4, 2))
    assert state.dcn_ring.dtype == jnp.uint8
    # depth 0: no ring state at all
    assert init_global_state(
        distributed_lion(wire="hier:4"), params, 8).dcn_ring is None


# ---------------------------------------------------------- validation
def test_depth_validation():
    with pytest.raises(ValueError, match="must be >= 0"):
        distributed_lion(wire="hier:4", dcn_pipeline_depth=-1)
    with pytest.raises(ValueError, match="no such leg"):
        distributed_lion(wire="sign_psum", dcn_pipeline_depth=1)
    with pytest.raises(ValueError, match="no such leg"):
        distributed_lion(wire="packed_a2a", dcn_pipeline_depth=2)
    with pytest.raises(ValueError, match="no wire"):
        distributed_lion(axis_name=None, wire="hier:2",
                         dcn_pipeline_depth=1)


def test_trainer_depth_validation():
    from distributed_lion_tpu.train.loop import TrainConfig, make_optimizer

    with pytest.raises(ValueError, match="nothing to overlap"):
        make_optimizer(TrainConfig(wire="packed_a2a", dcn_pipeline_depth=1))
    with pytest.raises(ValueError, match="unresolved 'auto'"):
        # the unresolved auto sentinel must not silently decide staleness
        make_optimizer(TrainConfig(dcn_pipeline_depth=1))
    with pytest.raises(ValueError, match="no vote collective"):
        make_optimizer(TrainConfig(lion=False, async_grad=False,
                                   dcn_pipeline_depth=1))
