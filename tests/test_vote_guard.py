"""Vote guard (ISSUE 5): Byzantine-tolerant elections, worker quarantine,
degraded-mode training.

The tentpole contracts, pinned here:

- **masked elections** — with a health mask, every wire excludes quarantined
  ballots from the tally and shrinks the majority threshold to the healthy
  quorum (numpy reference model per wire, including hier's
  majority-of-majorities with group-level abstention);
- **all-healthy bit-identity** — guard 'enforce' with an all-True mask
  produces bit-identical params AND momentum to guard 'off' across all four
  wires × vote_buckets {1, 4} × det/stoch, on the XLA and Pallas paths (the
  acceptance criterion);
- **ballot-health signals** — per-worker nonfinite / frozen-ballot /
  outlier-disagreement detection from inside the jitted step;
- **the quarantine state machine** — strikes, cooldown, readmission
  healing, quorum refusal (host-side, train/vote_guard.py);
- **degraded-mode training** — with one poisoned worker, '--vote_guard
  enforce' tracks a clean W−1 run while guard-off demonstrably degrades
  (flipped ballot) or silently poisons momentum forever (NaN grads — the
  motivating latent bug);
- **quarantine × resilience** — the mask round-trips through checkpoints
  exactly; elastic resume heals quarantined momenta before the remap.
"""

import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from _sharded import (
    assert_trees_equal,
    leafy_problem,
    run_sharded,
    sharded,
    sharded_opt_step,
    toy_problem,
)
from distributed_lion_tpu.data.sources import (
    batch_iterator,
    synthetic_lm_dataset,
)
from distributed_lion_tpu.models.gpt2 import GPT2Config
from distributed_lion_tpu.optim import (
    distributed_lion,
    heal_worker_momentum,
    init_global_state,
)
from distributed_lion_tpu.parallel import collectives
from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train import resilience
from distributed_lion_tpu.train.loop import TrainConfig, Trainer
from distributed_lion_tpu.train.vote_guard import VoteGuard

WIRES = ["sign_psum", "packed_allgather", "packed_a2a", "hier:4"]


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


# ------------------------------------------------------- masked elections
def _ref_masked_election(ballots: np.ndarray, alive: np.ndarray,
                         wire: str) -> np.ndarray:
    """Numpy reference: the healthy-quorum majority each wire must
    implement. Flat wires: elected ⇔ healthy True-votes form a strict
    majority of the healthy quorum (tie → −1). hier: the same rule inside
    each group, then a strict majority of the groups that still hold a
    healthy member (a fully-quarantined group abstains)."""
    kind, group = wire.split(":") if ":" in wire else (wire, None)
    if kind != "hier":
        count = ballots[alive].sum(0)
        return count * 2 > alive.sum()
    g = int(group)
    w = ballots.shape[0]
    verdicts, galive = [], []
    for k in range(w // g):
        rows = slice(k * g, (k + 1) * g)
        a = alive[rows]
        tally = (np.where(ballots[rows], 1, -1)
                 * a[:, None].astype(int)).sum(0)
        verdicts.append(tally > 0)
        galive.append(bool(a.any()))
    verdicts = np.stack(verdicts)
    galive = np.asarray(galive)
    count = verdicts[galive].sum(0)
    return count * 2 > galive.sum()


@pytest.mark.parametrize("wire", WIRES)
def test_masked_election_matches_reference(mesh8, wire):
    """Quarantined ballots leave the tally; the threshold shrinks to the
    healthy quorum — per wire, at a ragged ballot size, with two sick
    workers (one of them the whole of no group: hier's group abstention
    needs a fully-sick group, covered by the second mask)."""
    n = 203
    rng = np.random.default_rng(3)
    ballots = rng.integers(0, 2, size=(8, n)).astype(bool)

    def body(b, a):
        return collectives.majority_vote(b[0], "data", wire, a)

    elect = sharded(body, mesh8, (P("data"), P()), P(), check_vma=False)
    for sick in ([2, 5], [4, 5, 6, 7]):  # the 2nd kills hier group 1 of 2
        alive = np.ones(8, bool)
        alive[sick] = False
        got = np.asarray(elect(jnp.asarray(ballots), jnp.asarray(alive)))
        np.testing.assert_array_equal(
            got, _ref_masked_election(ballots, alive, wire), err_msg=wire)


@pytest.mark.parametrize("wire", WIRES)
def test_masked_all_healthy_bit_identical_collective(mesh8, wire):
    """An all-True mask must be a bitwise no-op at the collective level —
    including the bucketed form."""
    n = 1003
    rng = np.random.default_rng(11)
    ballots = jnp.asarray(rng.integers(0, 2, size=(8, n)).astype(bool))
    alive = jnp.ones((8,), jnp.bool_)

    def run(buckets, *mask):
        def body(b, *mask):
            return collectives.majority_vote_bucketed(
                b[0], "data", wire, buckets, *mask)

        return np.asarray(run_sharded(
            body, mesh8, (P("data"),) + (P(),) * len(mask), P(),
            ballots, *mask, check_vma=False))

    np.testing.assert_array_equal(run(1, alive), run(1))
    np.testing.assert_array_equal(run(4, alive), run(4))


# --------------------------------------------------- optimizer bit-identity
def _run_steps(opt, params, grads_fn, n_steps, mesh, world, rng=None,
               sick=None):
    """Drive opt.step under shard_map with per-step grads via
    ``grads_fn(step)``; returns ``(params, state, guard frame or None)``."""
    state = init_global_state(opt, params, world, rng=rng)
    if sick is not None and state.health is not None:
        h = np.ones(world, bool)
        h[sick] = False
        state = state._replace(health=jnp.asarray(h))
    step = sharded_opt_step(opt, mesh, state,
                            extras=int(state.health is not None))
    frames = []
    for t in range(n_steps):
        params, state, *frames = step(params, grads_fn(t), state)
    return params, state, frames[0] if frames else None


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("stochastic", [False, True],
                         ids=["deterministic", "stochastic"])
@pytest.mark.parametrize("buckets", [1, 4])
def test_guard_all_healthy_bit_identical(mesh8, wire, stochastic, buckets):
    """The acceptance criterion: 'enforce' with an all-healthy mask is
    bit-identical to guard 'off' in params AND momentum, across all four
    wires × vote_buckets {1, 4} × det/stoch (XLA path)."""
    params, grads = toy_problem()
    kw = dict(learning_rate=0.01, weight_decay=0.01, wire=wire,
              vote_buckets=buckets,
              max_grad_norm=1.0 if stochastic else None)
    rng = jax.random.key(7) if stochastic else None
    runs = {}
    for guard in ("off", "enforce"):
        opt = distributed_lion(guard=guard, **kw)
        runs[guard] = _run_steps(opt, params, lambda t: grads, 3, mesh8, 8,
                                 rng=rng)
    assert_trees_equal(runs["off"][0], runs["enforce"][0])
    assert_trees_equal(runs["off"][1].exp_avg, runs["enforce"][1].exp_avg)


@pytest.mark.parametrize("buckets", [1, 4])
@pytest.mark.parametrize("wire", ["sign_psum", "packed_a2a"])
def test_guard_all_healthy_bit_identical_pallas(mesh8, wire, buckets):
    """Same contract on the Pallas path (the mask zeroes the bucket
    ballot before it reaches the wire; kernels untouched)."""
    params, grads = toy_problem(n=300)
    runs = {}
    for guard in ("off", "enforce"):
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05,
                               wire=wire, kernel="pallas",
                               vote_buckets=buckets, guard=guard)
        runs[guard] = _run_steps(opt, params, lambda t: grads, 3, mesh8, 8)
    assert_trees_equal(runs["off"][0], runs["enforce"][0])
    assert_trees_equal(runs["off"][1].exp_avg, runs["enforce"][1].exp_avg)


@pytest.mark.parametrize("buckets", [1, 4])
@pytest.mark.parametrize("wire", ["sign_psum", "packed_a2a"])
def test_guard_leaf_shaped_matches_xla(mesh8, wire, buckets):
    """Enforce over leaves taken where they lie (and pooled ones beside
    them): parameters, momenta, the checkpointed ``prev_ballot`` (flat
    coordinate order, whatever order the step votes in) and the guard
    frame are the XLA path's bit for bit."""
    params, grads = leafy_problem()
    runs = []
    for kern, b, rb in (("xla", 1, 0), ("pallas", buckets, 32)):
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05,
                               wire=wire, kernel=kern, vote_buckets=b,
                               row_block=rb, guard="enforce")
        runs.append(_run_steps(opt, params, lambda t: grads, 2, mesh8, 8))
    assert_trees_equal(runs[0][0], runs[1][0])
    assert_trees_equal(runs[0][1].exp_avg, runs[1][1].exp_avg)
    assert_trees_equal(runs[0][1].prev_ballot, runs[1][1].prev_ballot)
    assert_trees_equal(runs[0][2], runs[1][2])


def test_guard_lazy_vote_every_bit_identical(mesh8):
    """Guard × lazy refresh: the per-slot prev-ballot cache must not
    disturb the rotating-slice election (elected cache compared too)."""
    params, grads = toy_problem()
    runs = {}
    for guard in ("off", "enforce"):
        opt = distributed_lion(learning_rate=0.01, wire="sign_psum",
                               vote_every=4, guard=guard)
        runs[guard] = _run_steps(opt, params, lambda t: grads, 5, mesh8, 8)
    assert_trees_equal(runs["off"][0], runs["enforce"][0])
    np.testing.assert_array_equal(np.asarray(runs["off"][1].elected),
                                  np.asarray(runs["enforce"][1].elected))


def test_masked_optimizer_election_excludes_sick_worker(mesh8):
    """Semantics, not just identity: with worker 0 quarantined, the
    elections must equal those of an election among workers 1..7 alone
    (verified against the numpy healthy-majority over the actual ballots:
    ballot = b1*m + (1-b1)*g > 0, m = 0 at the first step)."""
    params, grads = toy_problem()
    b1 = 0.9
    opt = distributed_lion(learning_rate=0.01, b1=b1, wire="sign_psum",
                           guard="enforce")
    p1, _, _ = _run_steps(opt, params, lambda t: grads, 1, mesh8, 8,
                          sick=[0])
    flat_g = np.concatenate([np.asarray(grads["w"]),
                             np.asarray(grads["b"])], axis=1)
    ballots = (1 - b1) * flat_g > 0  # m == 0 at step 0
    alive = np.ones(8, bool)
    alive[0] = False
    expect = _ref_masked_election(ballots, alive, "sign_psum")
    flat_p0 = np.concatenate([np.asarray(params["w"]),
                              np.asarray(params["b"])])
    flat_p1 = np.concatenate([np.asarray(p1["w"]), np.asarray(p1["b"])])
    # Lion: p1 = p0*(1-lr*wd) - lr*sign → the update's sign IS the election
    got = (flat_p1 - flat_p0 * (1 - 0.01 * 0.01)) < 0
    np.testing.assert_array_equal(got, expect)


# ------------------------------------------------------------ guard signals
def _varied_grads(world, n, t, poison=None, kind=None):
    """Per-step-varying random grads (so honest ballots actually flip),
    with optional worker poisoning."""
    g = {
        "w": jax.random.normal(jax.random.key(100 + t), (world, n)),
        "b": jax.random.normal(jax.random.key(200 + t), (world, 3)),
    }
    if poison is None:
        return g

    def _p(x):
        x = np.array(x)  # writable copy (np.asarray of a jax array is RO)
        if kind == "nan":
            x[poison] = np.nan
        elif kind == "zero":
            x[poison] = 0.0
        return jnp.asarray(x)

    return jax.tree.map(_p, g)


def test_guard_frame_nonfinite_names_worker(mesh8):
    opt = distributed_lion(learning_rate=0.01, wire="sign_psum",
                           guard="observe")
    params, _ = toy_problem()
    _, _, gf = _run_steps(
        opt, params, lambda t: _varied_grads(8, 40, t, poison=3, kind="nan"),
        2, mesh8, 8)
    nf = np.asarray(gf["nonfinite"])
    assert nf[3] > 0 and (nf[[i for i in range(8) if i != 3]] == 0).all()


def test_guard_frame_frozen_ballot_names_worker(mesh8):
    """A zero-grad worker's ballot freezes at sign(m) — zero bit flips vs
    the previous vote, while honest workers (fresh random grads each step)
    keep flipping bits."""
    opt = distributed_lion(learning_rate=0.01, wire="sign_psum",
                           guard="observe")
    params, _ = toy_problem()
    _, _, gf = _run_steps(
        opt, params, lambda t: _varied_grads(8, 40, t, poison=2,
                                             kind="zero"),
        3, mesh8, 8)
    flips = np.asarray(gf["flips"])
    assert bool(np.asarray(gf["flip_valid"]))
    assert flips[2] == 0
    assert (flips[[i for i in range(8) if i != 2]] > 0).all()


def test_guard_enforce_sanitizes_momentum(mesh8):
    """enforce: nonfinite grads are zeroed out of the momentum update (the
    reference-lineage latent bug: one NaN batch used to poison exp_avg
    forever); observe keeps the raw semantics."""
    params, _ = toy_problem()
    for guard, finite in (("enforce", True), ("observe", False)):
        opt = distributed_lion(learning_rate=0.01, wire="sign_psum",
                               guard=guard)
        _, st, _ = _run_steps(
            opt, params,
            lambda t: _varied_grads(8, 40, t, poison=1, kind="nan"),
            2, mesh8, 8)
        mom = np.asarray(st.exp_avg["w"])
        assert np.isfinite(mom).all() == finite


# ----------------------------------------------------------- state machine
def _obs(world, nonfinite=(), frozen=(), disagree=None, voted=1):
    o = {
        "guard_nonfinite": np.zeros(world, np.int32),
        "guard_frozen": np.zeros(world, np.int32),
        "guard_disagree": (np.full(world, 0.25)
                           if disagree is None else np.asarray(disagree)),
        "guard_voted_steps": np.asarray(voted, np.int32),
    }
    for w in nonfinite:
        o["guard_nonfinite"][w] = 1
    for w in frozen:
        o["guard_frozen"][w] = 1
    return o


def test_state_machine_strikes_quarantine_cooldown_readmit():
    g = VoteGuard(4, "enforce", strike_threshold=2, cooldown_steps=10)
    ev = g.update(1, _obs(4, nonfinite=[2]), 1)
    assert not ev.quarantined and g.strikes[2] == 1
    ev = g.update(2, _obs(4, nonfinite=[2]), 1)
    assert ev.quarantined == [2] and ev.mask_changed
    assert not g.healthy[2] and g.healthy_count() == 3
    # still sick while quarantined: no further transitions until cooldown
    ev = g.update(5, _obs(4, nonfinite=[2]), 1)
    assert not ev.quarantined and not ev.readmitted
    # cooldown elapsed → readmission probe
    ev = g.update(12, _obs(4), 1)
    assert ev.readmitted == [2] and g.healthy[2]
    assert g.quarantine_events == 1 and g.readmit_events == 1


def test_state_machine_strike_decay_forgives_transients():
    g = VoteGuard(4, "enforce", strike_threshold=3, cooldown_steps=10)
    g.update(1, _obs(4, nonfinite=[0]), 1)
    g.update(2, _obs(4), 1)   # clean window: decay
    g.update(3, _obs(4), 1)   # back to zero
    assert g.strikes[0] == 0 and g.healthy.all()


def test_state_machine_outlier_rule():
    g = VoteGuard(4, "enforce", strike_threshold=1, cooldown_steps=10)
    # honest cluster ~0.26, one voter at 0.43 (the measured flipped-worker
    # signature): both arms fire
    ev = g.update(1, _obs(4, disagree=[0.26, 0.43, 0.25, 0.27]), 1)
    assert ev.quarantined == [1]
    # noise-dominated election: EVERYONE near 0.5 — the relative arm must
    # hold fire
    g2 = VoteGuard(4, "enforce", strike_threshold=1, cooldown_steps=10)
    ev = g2.update(1, _obs(4, disagree=[0.49, 0.51, 0.48, 0.5]), 1)
    assert not ev.quarantined


def test_state_machine_observe_mode_and_quorum():
    g = VoteGuard(4, "observe", strike_threshold=1, cooldown_steps=1000)
    for step, w in ((1, 0), (2, 1)):
        ev = g.update(step, _obs(4, nonfinite=[0, 1]), 1)
    assert g.healthy_count() == 2 and not g.quorum_ok()  # auto quorum = 3
    assert any("[observe] would have" in line for ev2 in [ev]
               for line in ev2.logs) or g.quarantine_events == 2
    rep = g.sick_report()
    assert set(rep["sick_workers"]) == {"0", "1"}


def test_state_machine_adopt_mask_and_validation():
    g = VoteGuard(4, "enforce")
    g.adopt_mask([True, False, True, True], step=7)
    assert not g.healthy[1] and g.quarantined_at[1] == 7
    with pytest.raises(ValueError):
        g.adopt_mask([True, True], step=0)
    with pytest.raises(ValueError):
        VoteGuard(4, "nonsense")
    with pytest.raises(ValueError):
        VoteGuard(4, "enforce", min_quorum=9)


def test_heal_worker_momentum_mean_of_healthy():
    exp_avg = {"w": jnp.asarray(np.arange(8, dtype=np.float32)
                                .reshape(4, 2))}
    healthy = np.array([True, False, True, True])
    healed = heal_worker_momentum(exp_avg, healthy, [1])
    got = np.asarray(healed["w"])
    expect = np.asarray(exp_avg["w"]).copy()
    expect[1] = expect[[0, 2, 3]].mean(0)
    np.testing.assert_allclose(got, expect)
    # untouched rows bit-identical
    np.testing.assert_array_equal(got[[0, 2, 3]],
                                  np.asarray(exp_avg["w"])[[0, 2, 3]])


# ------------------------------------------------- trainer: degraded mode
def _trainer_cfg(world_bs, steps, guard="off", poison="", outdir=None,
                 **kw):
    base = dict(
        lion=True, async_grad=True, wire="sign_psum", vote_every=1,
        vote_buckets=1, learning_rate=5e-3, lr_scheduler_type="constant",
        warmup_steps=0, max_steps=steps, weight_decay=0.0,
        per_device_train_batch_size=world_bs, gradient_accumulation_steps=1,
        block_size=32, logging_steps=1, output_dir=outdir, vote_guard=guard,
        guard_strikes=2, guard_cooldown=1000, inject_poison=poison,
    )
    base.update(kw)
    return TrainConfig(**base)


def _train(cfg, world, steps, model, seed=4):
    mesh = make_mesh(data=world, devices=jax.devices()[:world])
    tr = Trainer.for_gpt2(cfg, mesh, model)
    blocks = synthetic_lm_dataset(96, 32, model.vocab_size, seed=seed)
    hist = tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                    max_steps=steps)
    losses = [h["loss"] for h in hist if "loss" in h]
    return tr, losses


def test_poisoned_enforce_tracks_clean_w_minus_1(mesh8):
    """The acceptance pin: one flipped-ballot worker at W=4. Guard-off
    degrades the whole run; 'enforce' quarantines the adversary and tracks
    a clean W−1 (= 3 healthy voters, same global batch) run's loss. The
    W−1 leg uses bs 8 × 3 workers = bs 6 × 4 workers, so all three legs
    consume identical batches."""
    model = GPT2Config.tiny()
    steps = 40

    def tail(x):
        return float(np.mean(x[-10:]))

    _, clean = _train(_trainer_cfg(8, steps), 3, steps, model)
    tr_e, enf = _train(_trainer_cfg(6, steps, guard="enforce",
                                    poison="flipped_ballot:1"),
                       4, steps, model)
    rep = tr_e._guard.sick_report()
    tr_e.close()
    tr_o, off = _train(_trainer_cfg(6, steps, poison="flipped_ballot:1"),
                       4, steps, model)
    tr_o.close()
    # the adversary was identified and quarantined (outlier disagreement)
    assert rep["healthy_mask"] == [True, False, True, True]
    assert rep["sick_workers"]["1"]["outlier"] > 0
    gap_enforce = abs(tail(enf) - tail(clean))
    gap_off = abs(tail(off) - tail(clean))
    # enforce tracks clean W−1 within tolerance; guard-off demonstrably
    # degrades (measured: ~0.24 vs ~0.49 nats — the margins below leave
    # headroom for cross-version jitter while keeping the ordering strict)
    assert gap_enforce < 0.35, (gap_enforce, gap_off)
    assert gap_off > gap_enforce + 0.1, (gap_enforce, gap_off)


def test_nan_worker_poisons_momentum_only_without_guard(mesh8):
    """The motivating latent bug, end-to-end: a NaN-grad worker under guard
    'off' carries NaN momentum forever (invisible to the loss); 'enforce'
    quarantines it and keeps every momentum finite."""
    model = GPT2Config.tiny()
    tr_off, losses_off = _train(
        _trainer_cfg(2, 8, poison="nan_grads:3"), 8, 8, model)
    off_finite = all(np.isfinite(np.asarray(m)).all()
                     for m in jax.tree.leaves(tr_off.state.exp_avg))
    tr_off.close()
    tr_enf, losses_enf = _train(
        _trainer_cfg(2, 8, guard="enforce", poison="nan_grads:3"),
        8, 8, model)
    enf_finite = all(np.isfinite(np.asarray(m)).all()
                     for m in jax.tree.leaves(tr_enf.state.exp_avg))
    mask = np.asarray(tr_enf.state.health)
    tr_enf.close()
    assert not off_finite          # silently poisoned...
    assert all(np.isfinite(losses_off))  # ...while the loss looks fine
    assert enf_finite
    np.testing.assert_array_equal(mask, [True] * 3 + [False] + [True] * 4)


def test_readmission_probe_heals_and_requarantines(mesh8):
    """Short cooldown: the poisoned worker is quarantined, readmitted as a
    probe (momentum healed from the healthy mean), found still sick and
    re-quarantined — and every momentum stays finite throughout."""
    model = GPT2Config.tiny()
    tr, _ = _train(_trainer_cfg(2, 14, guard="enforce",
                                poison="nan_grads:1", guard_cooldown=4),
                   4, 14, model)
    g = tr._guard
    finite = all(np.isfinite(np.asarray(m)).all()
                 for m in jax.tree.leaves(tr.state.exp_avg))
    tr.close()
    assert g.quarantine_events >= 2 and g.readmit_events >= 1
    assert not g.healthy[1]
    assert finite


def test_min_quorum_refusal(mesh8):
    """Quorum floor: quarantining the only 'sick' worker below an absurd
    min_quorum must refuse loudly, not degrade silently."""
    model = GPT2Config.tiny()
    with pytest.raises(RuntimeError, match="quorum"):
        _train(_trainer_cfg(2, 10, guard="enforce", poison="nan_grads:0",
                            min_quorum=4), 4, 10, model)


def test_observe_mode_keeps_elections_untouched(mesh8):
    """Observe mode is purely observational: a poisoned run under
    'observe' must produce the SAME losses as guard 'off' (bit-identical
    elections), while still reporting what enforce would have done."""
    model = GPT2Config.tiny()
    tr_obs, obs = _train(_trainer_cfg(2, 8, guard="observe",
                                      poison="nan_grads:2"), 4, 8, model)
    rep = tr_obs._guard.sick_report()
    tr_obs.close()
    tr_off, off = _train(_trainer_cfg(2, 8, poison="nan_grads:2"),
                         4, 8, model)
    tr_off.close()
    np.testing.assert_array_equal(obs, off)
    assert "2" in rep["sick_workers"]


def test_guard_chunked_dispatch_counts_every_step(mesh8):
    """steps_per_call > 1: the guard's observations are SUMMED over the
    scanned chunk (not meaned like loss), so the host strike counter sees
    every poisoned step and the quarantine still lands."""
    model = GPT2Config.tiny()
    tr, losses = _train(_trainer_cfg(2, 9, guard="enforce",
                                     poison="nan_grads:2",
                                     steps_per_call=3, guard_strikes=3),
                        4, 9, model)
    mask = np.asarray(tr.state.health)
    rep = tr._guard.sick_report()
    tr.close()
    assert not mask[2]
    # 3 poisoned steps arrive in ONE chunk observation — enough strikes at
    # once to quarantine on the first applied window
    assert rep["sick_workers"]["2"]["nonfinite"] >= 3
    assert len(losses) >= 1


# ------------------------------------------------- sentinel interaction
def test_sentinel_bundle_names_sick_worker(mesh8, tmp_path):
    """Satellite: the crash bundle (and the trip reason) name the sick
    WORKER, not just the poisoned leaves — the guard's counters feed the
    sentinel."""
    model = GPT2Config.tiny()
    with pytest.raises(FloatingPointError, match="sick workers"):
        _train(_trainer_cfg(2, 8, guard="observe", poison="nan_grads:3",
                            nan_sentinel=True, outdir=str(tmp_path)),
               4, 8, model)
    bundles = sorted(pathlib.Path(tmp_path).glob("crash/step_*/bundle.json"))
    assert bundles
    bundle = json.loads(bundles[0].read_text())
    assert "3" in bundle["guard"]["sick_workers"]
    assert bundle["guard"]["sick_workers"]["3"]["nonfinite"] > 0


def test_sentinel_enforce_degraded_mode_survives(mesh8, tmp_path):
    """Under 'enforce' the sentinel must NOT kill a degraded-mode run: the
    sick worker's NaN is excluded from the healthy grad-norm and handled by
    quarantine instead."""
    model = GPT2Config.tiny()
    tr, losses = _train(_trainer_cfg(2, 8, guard="enforce",
                                     poison="nan_grads:3",
                                     nan_sentinel=True,
                                     outdir=str(tmp_path)), 4, 8, model)
    mask = np.asarray(tr.state.health)
    tr.close()
    assert len(losses) == 8 and all(np.isfinite(losses))
    assert not mask[3]
    assert not list(pathlib.Path(tmp_path).glob("crash/*"))


# --------------------------------------------- quarantine × resilience
def test_checkpoint_restores_quarantine_mask_exactly(mesh8, tmp_path):
    """A checkpoint saved with a quarantined worker restores the health
    mask (and the guard machine's view of it) exactly."""
    model = GPT2Config.tiny()
    out = str(tmp_path / "run")
    tr, _ = _train(_trainer_cfg(2, 6, guard="enforce",
                                poison="nan_grads:2", outdir=out,
                                save_steps=6), 4, 6, model)
    saved_mask = np.asarray(tr.state.health)
    tr.close()
    assert not saved_mask[2]
    resilience.clear_faults()  # the resumed run is clean — mask persists
    mesh = make_mesh(data=4, devices=jax.devices()[:4])
    tr2 = Trainer.for_gpt2(_trainer_cfg(2, 12, guard="enforce",
                                        outdir=out, save_steps=6), mesh,
                           model)
    assert tr2.step_count == 6
    np.testing.assert_array_equal(np.asarray(tr2.state.health), saved_mask)
    np.testing.assert_array_equal(tr2._guard.healthy, saved_mask)
    tr2.close()


def test_guard_toggle_across_checkpoint(mesh8, tmp_path):
    """has_guard meta: a guard-on checkpoint restores into a guard-off run
    (fields stripped) and a guard-off checkpoint into a guard-on run
    (fresh all-healthy state attached)."""
    model = GPT2Config.tiny()
    out = str(tmp_path / "run")
    tr, _ = _train(_trainer_cfg(2, 4, guard="enforce", outdir=out,
                                save_steps=4), 4, 4, model)
    tr.close()
    mesh = make_mesh(data=4, devices=jax.devices()[:4])
    tr2 = Trainer.for_gpt2(_trainer_cfg(2, 8, outdir=out, save_steps=4),
                           mesh, model)
    assert tr2.step_count == 4 and tr2.state.health is None
    tr2.close()
    out2 = str(tmp_path / "run2")
    tr3, _ = _train(_trainer_cfg(2, 4, outdir=out2, save_steps=4), 4, 4,
                    model)
    tr3.close()
    tr4 = Trainer.for_gpt2(_trainer_cfg(2, 8, guard="enforce", outdir=out2,
                                        save_steps=4), mesh, model)
    assert tr4.step_count == 4
    assert np.asarray(tr4.state.health).all()
    tr4.close()


def test_elastic_resume_heals_quarantined_momentum(mesh8, tmp_path):
    """--elastic_resume W→W′ with a quarantined worker: only HEALTHY
    momenta enter the remap — the sick worker's row is re-averaged from
    the healthy mean first (pinned numerically against the manual
    heal+remap)."""
    model = GPT2Config.tiny()
    out = str(tmp_path / "run")
    mesh4 = make_mesh(data=4, devices=jax.devices()[:4])
    tr = Trainer.for_gpt2(_trainer_cfg(2, 4, guard="enforce", outdir=out,
                                       save_steps=4), mesh4, model)
    blocks = synthetic_lm_dataset(96, 32, model.vocab_size, seed=4)
    tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
             max_steps=4)
    # poison worker 1's momentum with garbage and quarantine it, then save:
    # the garbage must NOT leak through the elastic remap
    garbage = jax.tree.map(
        lambda m: jnp.asarray(np.asarray(m)).at[1].set(1e9),
        tr.state.exp_avg)
    mask = jnp.asarray([True, False, True, True])
    tr.state = tr.state._replace(exp_avg=garbage, health=mask)
    tr.step_count += 1  # force a distinct save step
    tr.save()
    expect = jax.device_get(jax.tree.map(
        lambda m: np.asarray(m), heal_worker_momentum(
            garbage, np.array([True, False, True, True]), [1])))
    tr.close()

    mesh2 = make_mesh(data=2, devices=jax.devices()[:2])
    tr2 = Trainer.for_gpt2(_trainer_cfg(4, 10, guard="enforce", outdir=out,
                                        save_steps=100,
                                        elastic_resume=True), mesh2, model)
    got = jax.device_get(tr2.state.exp_avg)
    # W=4 → W'=2 group re-average of the HEALED stack
    jax.tree.map(
        lambda g, e: np.testing.assert_allclose(
            np.asarray(g),
            np.asarray(e).reshape((2, 2) + np.asarray(e).shape[1:])
            .mean(1).astype(np.asarray(g).dtype), rtol=1e-5, atol=1e-9),
        got, expect)
    # fresh all-healthy guard state at W'
    assert np.asarray(tr2.state.health).tolist() == [True, True]
    assert not np.any(np.asarray(jax.tree.leaves(got)[0]) > 1e8)
    tr2.close()


# ----------------------------------------------------------- validation
def test_guard_validation():
    with pytest.raises(ValueError):
        distributed_lion(guard="sometimes")
    with pytest.raises(ValueError):
        distributed_lion(axis_name=None, guard="enforce")
    with pytest.raises(ValueError, match="vote_guard"):
        from distributed_lion_tpu.train.loop import make_optimizer

        make_optimizer(TrainConfig(lion=False, async_grad=False,
                                   vote_guard="enforce"))
    with pytest.raises(ValueError):
        resilience.parse_poison("bad_kind:1")
    with pytest.raises(ValueError):
        resilience.parse_poison("nan_grads:x")
    assert resilience.parse_poison("nan_grads:2") == ("nan_grads", 2, 0)
    assert (resilience.parse_poison("flipped_ballot:0:100")
            == ("flipped_ballot", 0, 100))


def test_guard_metrics_are_strict_json(mesh8, tmp_path):
    """The guard's logged metrics (guard_healthy etc.) must pass the
    strict-JSON validator — the [W] observation vectors never reach the
    log."""
    import subprocess
    import sys

    model = GPT2Config.tiny()
    out = str(tmp_path / "run")
    tr, _ = _train(_trainer_cfg(2, 4, guard="enforce", outdir=out), 4, 4,
                   model)
    tr.close()
    proc = subprocess.run(
        [sys.executable, "scripts/validate_metrics.py",
         f"{out}/metrics.jsonl"],
        capture_output=True, text=True,
        cwd=str(pathlib.Path(__file__).resolve().parent.parent))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(line)
            for line in open(f"{out}/metrics.jsonl") if line.strip()]
    assert any("train/guard_healthy" in r for r in rows)


def test_sharded_step_wrapper_supports_guard(mesh8):
    """The standalone shard_map wrapper (optim.sharded — users who bring
    their own loop) must carry the guard state and return the guard frame;
    all-healthy results stay bit-identical to the guard-off wrapper."""
    from distributed_lion_tpu.optim.sharded import (
        make_sharded_step,
        shard_state,
    )

    params, grads = toy_problem()
    outs = {}
    for guard in ("off", "enforce"):
        opt = distributed_lion(learning_rate=0.01, guard=guard)
        state = shard_state(init_global_state(opt, params, 8), mesh8)
        step = make_sharded_step(opt, mesh8, has_guard=guard != "off")
        if guard == "off":
            p, st = step(params, grads, state)
            outs[guard] = (p, st)
        else:
            p, st, gf = step(params, grads, state)
            outs[guard] = (p, st)
            assert np.asarray(gf["nonfinite"]).shape == (8,)
            assert np.asarray(st.health).all()
    assert_trees_equal(outs["off"][0], outs["enforce"][0])
    assert_trees_equal(outs["off"][1].exp_avg, outs["enforce"][1].exp_avg)
