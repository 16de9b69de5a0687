"""Bucketed, overlapped vote wire (the software-pipelined ballot collective).

The tentpole contract, pinned here: splitting the ballot into
``vote_buckets`` wire-aligned chunks and voting each with its own collective
(so bucket k's wire can ride behind bucket k−1's fused apply) changes WHEN
bytes move, never what is elected or how many bytes ship —

- params AND momentum are bit-identical to the monolithic vote for all four
  wires × {deterministic, stochastic} × vote_every ∈ {1, 4} on the 8-device
  CPU mesh;
- the summed per-bucket byte accounting equals the unbucketed totals exactly
  (and stays zero at world=1, commit 3d77603);
- the Pallas path (kernels over every leaf where it lies, the rest pooled:
  its layout's own tests are in tests/test_pallas_lion.py) matches the XLA
  path and preserves the elected-sign cache through ``_step_pallas`` (the
  state-pass-through invariant).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _sharded import (
    assert_trees_equal,
    run_sharded,
    sharded_opt_step,
    toy_problem,
)
from distributed_lion_tpu.ops.codec import (
    bucket_alignment,
    bucket_bounds,
    wire_bytes_per_param,
)
from distributed_lion_tpu.optim import distributed_lion, init_global_state
from distributed_lion_tpu.optim.lion import LionState
from distributed_lion_tpu.parallel import collectives
from distributed_lion_tpu.parallel.mesh import make_mesh

WIRES = ["sign_psum", "packed_allgather", "packed_a2a", "hier:4"]


# --------------------------------------------------------------- bounds math
@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("n", [1, 7, 64, 1000, 4096, 12345])
@pytest.mark.parametrize("buckets", [1, 2, 3, 4, 16, 64])
def test_bucket_bounds_tile_exactly(wire, n, buckets):
    bounds = bucket_bounds(n, buckets, 8, wire)
    assert len(bounds) <= max(buckets, 1)
    align = bucket_alignment(8, wire)
    off = 0
    for i, (start, size) in enumerate(bounds):
        assert start == off and size > 0
        if i < len(bounds) - 1:
            assert size % align == 0
        off += size
    assert off == n


# ----------------------------------------------------------- byte accounting
@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("world", [4, 8, 16])
@pytest.mark.parametrize("vote_every", [1, 4])
def test_bucketed_accounting_equals_unbucketed(wire, world, vote_every):
    """Conservation: bucket boundaries are wire-aligned, so the summed
    per-bucket bytes are EXACTLY the monolithic vote's — for every wire,
    including hier's DCN leg, at ragged ballot sizes."""
    for n in (123_457, 1_000_003, 8 * world * 64):
        base = wire_bytes_per_param(n, world, wire, vote_every=vote_every)
        for buckets in (2, 3, 4, 16):
            acct = wire_bytes_per_param(n, world, wire,
                                        vote_every=vote_every,
                                        vote_buckets=buckets)
            assert acct["bytes_per_step"] == base["bytes_per_step"], (
                wire, world, n, buckets)
            assert acct["bits_per_param"] == base["bits_per_param"]
            if "dcn_bytes_per_step" in base:
                assert (acct["dcn_bytes_per_step"]
                        == base["dcn_bytes_per_step"])
            assert 0.0 < acct["overlappable_wire_frac"] < 1.0


@pytest.mark.parametrize("wire", ["sign_psum", "packed_allgather",
                                  "packed_a2a", "hier:1"])
def test_bucketed_world1_wire_bytes_stay_zero(wire):
    """W=1 short-circuits every wire — bucketing must not resurrect phantom
    traffic (or phantom overlap) on single-chip runs."""
    for buckets in (1, 4, 16):
        acct = wire_bytes_per_param(1000, 1, wire, vote_buckets=buckets)
        assert acct["bytes_per_step"] == 0
        assert acct["overlappable_wire_frac"] == 0.0


def test_comm_report_overlap_frac():
    from distributed_lion_tpu.train.profiling import comm_report

    rep = comm_report(10_000_000, 8, "sign_psum", vote_buckets=4)
    # 4 near-equal buckets → buckets[1:] carry ~3/4 of the wire
    assert abs(rep["comm_overlap_frac"] - 0.75) < 0.01
    assert rep["vote_buckets"] == 4
    assert comm_report(10_000_000, 8, "sign_psum")["comm_overlap_frac"] == 0.0


# ------------------------------------------------------ collective bit-parity
# Only the cheapest and the trickiest wire at this level: sign_psum (the
# default) and packed_a2a (per-worker chunk padding interacts with bucket
# boundaries). hier/packed_allgather bucket-parity is covered at the
# optimizer level by the full trajectory matrix below — repeating them here
# would re-pay hier's scan-ring compiles (~11s of tier-1 wall clock) for no
# new coverage.
@pytest.mark.parametrize("wire", ["sign_psum", "packed_a2a"])
def test_majority_vote_bucketed_bit_identical(mesh8, wire):
    """Collective level: the concatenated bucketed election equals the
    one-shot vote, at a ragged ballot size."""
    n = 1003
    rng = np.random.default_rng(11)
    ballots = jnp.asarray(rng.integers(0, 2, size=(8, n)).astype(bool))

    def run(vote_buckets):
        def body(b):
            return collectives.majority_vote_bucketed(
                b[0], "data", wire, vote_buckets)

        return np.asarray(run_sharded(
            body, mesh8, (P("data"),), P(), ballots, check_vma=False))

    # one bucketed config suffices: 5 buckets of the 1003-coordinate ballot
    # exercise interior + ragged-tail chunks; each extra config is a fresh
    # shard_map compile (hier's scan rings are the slow ones) in tier-1
    np.testing.assert_array_equal(run(5), run(1))


# ------------------------------------------------------ optimizer bit-parity
def _run_steps(opt, params, grads_per_worker, n_steps, mesh, world,
               rng=None):
    """Drive opt.step under shard_map for n_steps; grads_per_worker is a
    [world, ...] stacked pytree reused every step."""
    state = init_global_state(opt, params, world, rng=rng)
    step = sharded_opt_step(opt, mesh, state)
    for _ in range(n_steps):
        params, state = step(params, grads_per_worker, state)
    return params, state


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("stochastic", [False, True],
                         ids=["deterministic", "stochastic"])
@pytest.mark.parametrize("vote_every", [1, 4])
def test_bucketed_trajectory_bit_identical(mesh8, wire, stochastic,
                                           vote_every):
    """The acceptance criterion: vote_buckets > 1 produces bit-identical
    params AND momentum to vote_buckets = 1 for every wire × binarization
    mode × vote cadence (the rotating 1/K slice votes bucket-wise too)."""
    params, grads = toy_problem()
    kw = dict(learning_rate=0.01, weight_decay=0.01, wire=wire,
              vote_every=vote_every,
              max_grad_norm=1.0 if stochastic else None)
    rng = jax.random.key(7) if stochastic else None
    steps = 5 if vote_every > 1 else 3  # cover a full rotation + reuse
    runs = {}
    for buckets in (1, 3):
        opt = distributed_lion(vote_buckets=buckets, **kw)
        runs[buckets] = _run_steps(opt, params, grads, steps, mesh8, 8,
                                   rng=rng)
    assert_trees_equal(runs[1][0], runs[3][0])
    assert_trees_equal(runs[1][1].exp_avg, runs[3][1].exp_avg)
    if vote_every > 1:
        np.testing.assert_array_equal(np.asarray(runs[1][1].elected),
                                      np.asarray(runs[3][1].elected))


@pytest.mark.parametrize("wire", ["sign_psum", "packed_a2a"])
def test_pallas_bucketed_equals_xla_monolithic(mesh8, wire):
    """The Pallas path's bucket pipeline must match the XLA path's
    monolithic vote bit-for-bit, here on leaves that all take the flat
    path (one pooled vector cut by every bucket boundary)."""
    params, grads = toy_problem(n=300)
    results = []
    for kern, buckets in (("pallas", 4), ("pallas", 1), ("xla", 1)):
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05,
                               wire=wire, kernel=kern, vote_buckets=buckets)
        p, st = _run_steps(opt, params, grads, 3, mesh8, 8)
        results.append((p, st))
    for other in results[1:]:
        assert_trees_equal(results[0][0], other[0])
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6),
            results[0][1].exp_avg, other[1].exp_avg)


def test_pallas_step_preserves_elected_cache(mesh8):
    """Satellite: _step_pallas used to rebuild LionState without ``elected``
    — harmless only because the Pallas gate requires vote_every == 1. The
    invariant is 'state passes through', pinned by smuggling a cache into a
    state the Pallas path consumes."""
    params, grads = toy_problem(n=64)
    opt = distributed_lion(learning_rate=0.01, kernel="pallas",
                           vote_buckets=2)
    state = init_global_state(opt, params, 8)
    cache = jnp.arange(16, dtype=jnp.uint8)
    state = LionState(state.count, state.exp_avg, state.rng, cache)
    _, new_state = sharded_opt_step(opt, mesh8, state)(params, grads, state)
    np.testing.assert_array_equal(np.asarray(new_state.elected),
                                  np.asarray(cache))


# ----------------------------------------------------------- auto resolution
def test_resolve_auto_vote_buckets(mesh8):
    from distributed_lion_tpu.train.loop import (
        AUTO_BUCKET_MIN_COORDS,
        TrainConfig,
        resolve_auto_comm,
    )

    # big replicated dp ballot → pipelined wire
    r = resolve_auto_comm(TrainConfig(), mesh8, 124_000_000,
                          params_replicated=True)
    assert r.vote_buckets == 4
    # the per-step slice (n/4 under an EXPLICIT lazy vote — auto resolves
    # vote_every to strict 1 until parity:lazy passes) is what must clear
    # the threshold — just below it stays monolithic
    r = resolve_auto_comm(TrainConfig(vote_every=4), mesh8,
                          AUTO_BUCKET_MIN_COORDS * 4 - 64,
                          params_replicated=True)
    assert r.vote_every == 4 and r.vote_buckets == 1
    # W=1: no wire, nothing to pipeline
    mesh1 = make_mesh(data=1, devices=jax.devices()[:1])
    r = resolve_auto_comm(TrainConfig(), mesh1, 124_000_000,
                          params_replicated=True)
    assert r.vote_buckets == 1
    # explicit values always respected
    cfg = TrainConfig(wire="sign_psum", vote_every=1, vote_buckets=7)
    assert resolve_auto_comm(cfg, mesh8, 124_000_000, True) is cfg
    r = resolve_auto_comm(TrainConfig(vote_buckets=2), mesh8, 1000, True)
    assert r.vote_buckets == 2


def test_make_optimizer_degrades_bucket_sentinel():
    """Standalone make_optimizer callers (no mesh) get the monolithic vote
    from an unresolved vote_buckets=0, not a crash."""
    from distributed_lion_tpu.train.loop import TrainConfig, make_optimizer

    make_optimizer(TrainConfig())  # vote_buckets=0 must not raise


def test_vote_buckets_validation():
    with pytest.raises(ValueError):
        distributed_lion(vote_buckets=0)
    with pytest.raises(ValueError):
        bucket_bounds(100, 0, 8, "sign_psum")


def test_trainer_bucketed_step_end_to_end(mesh8):
    """Smoke: a Trainer with explicit vote_buckets completes a train step,
    logs the analytic comm_overlap_frac, and matches the vote_buckets=1
    trainer's loss exactly (same seed, same data)."""
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    model_cfg = GPT2Config.tiny()
    losses = {}
    for buckets in (1, 4):
        cfg = TrainConfig(
            lion=True, async_grad=True, wire="packed_a2a", vote_every=1,
            vote_buckets=buckets, learning_rate=1e-3, warmup_steps=1,
            max_steps=2, per_device_train_batch_size=1,
            gradient_accumulation_steps=1, block_size=32, logging_steps=1,
            output_dir=None,
        )
        tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
        assert tr.cfg.vote_buckets == buckets
        blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                      model_cfg.vocab_size, seed=4)
        hist = tr.train(batch_iterator(blocks, tr.global_train_batch(),
                                       seed=0), max_steps=2)
        rows = [h for h in hist if "loss" in h]
        losses[buckets] = [h["loss"] for h in rows]
        frac = rows[-1]["comm_overlap_frac"]
        assert (frac == 0.0 if buckets == 1 else 0.5 < frac < 1.0)
        tr.close()
    assert losses[1] == losses[4]
