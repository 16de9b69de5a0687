"""Crash-resume equivalence (ISSUE 3 satellite): a run killed between a
save and the next step, then resumed from the checkpoint, must produce
BIT-identical losses and elections vs. an uninterrupted run — across
``vote_buckets`` {1, 4} × deterministic/stochastic binarization.

Bitwise parameter + momentum equality is the strongest form of "elected
signs identical": Lion's update is sign-valued, so any differing election
would move some parameter by ±2·lr·step and break exact equality. The
``vote_every=4`` leg additionally compares the packed elected-sign cache
itself bit-for-bit."""

import numpy as np
import pytest

import jax

from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
from distributed_lion_tpu.models.gpt2 import GPT2Config
from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train.loop import TrainConfig, Trainer


def _cfg(outdir, steps, **kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
        max_steps=steps, per_device_train_batch_size=1,
        gradient_accumulation_steps=1, block_size=32, logging_steps=1,
        save_steps=2, output_dir=outdir, seed=5,
    )
    base.update(kw)
    return TrainConfig(**base)


def _run(cfg, mesh, model, blocks):
    t = Trainer.for_gpt2(cfg, mesh, model, seed=3)
    h = t.train(batch_iterator(blocks, t.global_train_batch(), seed=5))
    return t, [x["loss"] for x in h if "loss" in x]


def _assert_resumed_matches(tmp_path, mesh, model, blocks, **kw):
    out = str(tmp_path / "run")

    t_ref, ref_losses = _run(_cfg(None, 4, **kw), mesh, model, blocks)
    ref_params = jax.device_get(t_ref.params)
    ref_mom = jax.device_get(t_ref.state.exp_avg)
    ref_elected = (None if t_ref.state.elected is None
                   else np.asarray(jax.device_get(t_ref.state.elected)))
    ref_ring = (None if t_ref.state.dcn_ring is None
                else np.asarray(jax.device_get(t_ref.state.dcn_ring)))
    t_ref.close()

    # interrupted run: checkpoint at step 2, then 'killed' between the save
    # and the next step (the loop never dispatches step 3)
    t1, part1 = _run(_cfg(out, 2, **kw), mesh, model, blocks)
    t1.close()

    t2 = Trainer.for_gpt2(_cfg(out, 4, **kw), mesh, model, seed=3)
    assert t2.step_count == 2
    h2 = t2.train(batch_iterator(blocks, t2.global_train_batch(), seed=5))
    part2 = [x["loss"] for x in h2 if "loss" in x]
    got_params = jax.device_get(t2.params)
    got_mom = jax.device_get(t2.state.exp_avg)
    got_elected = (None if t2.state.elected is None
                   else np.asarray(jax.device_get(t2.state.elected)))
    got_ring = (None if t2.state.dcn_ring is None
                else np.asarray(jax.device_get(t2.state.dcn_ring)))
    t2.close()

    np.testing.assert_array_equal(part1 + part2, ref_losses)
    jax.tree.map(np.testing.assert_array_equal, got_params, ref_params)
    jax.tree.map(np.testing.assert_array_equal, got_mom, ref_mom)
    if ref_elected is not None:
        np.testing.assert_array_equal(got_elected, ref_elected)
    if ref_ring is not None:
        np.testing.assert_array_equal(got_ring, ref_ring)


# tier-1 runs the diagonal of the 2 x 2 (each value of each axis once); the
# other two are `slow`: 63 core-seconds (ROADMAP D9)
@pytest.mark.parametrize("buckets,stoch", [
    pytest.param(1, False, id="1-det"),
    pytest.param(1, True, id="1-stoch", marks=pytest.mark.slow),
    pytest.param(4, False, id="4-det", marks=pytest.mark.slow),
    pytest.param(4, True, id="4-stoch")])
def test_crash_resume_bit_identical(tmp_path, buckets, stoch):
    mesh = make_mesh(data=8)
    model = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(64, 32, model.vocab_size, seed=1)
    kw = {"vote_buckets": buckets}
    if stoch:
        kw["max_grad_norm"] = 1.0
    _assert_resumed_matches(tmp_path, mesh, model, blocks, **kw)


def test_crash_resume_guard_bit_identical(tmp_path):
    """Vote guard (ISSUE 5 satellite): with --vote_guard enforce the health
    mask and the per-worker prev-ballot cache are live state across the
    interruption — crash-resume equivalence must stay bit-identical with
    the guard on (all-healthy run; the masked-election path is compiled
    in)."""
    mesh = make_mesh(data=8)
    model = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(64, 32, model.vocab_size, seed=1)
    _assert_resumed_matches(tmp_path, mesh, model, blocks,
                            vote_guard="enforce", vote_buckets=4)


def test_crash_resume_lazy_elected_cache_bit_identical(tmp_path):
    """vote_every=4: the packed elected-sign cache is live state across the
    interruption — stale signs applied on non-vote steps must come from the
    restored cache, pinned bit-for-bit against the uninterrupted run."""
    mesh = make_mesh(data=8)
    model = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(64, 32, model.vocab_size, seed=1)
    _assert_resumed_matches(tmp_path, mesh, model, blocks, vote_every=4)


def test_crash_resume_dcn_ring_mid_flight_bit_identical(tmp_path):
    """ISSUE 8 satellite: hier wire at dcn_pipeline_depth=2, killed at
    step 2 — the ring holds the IN-FLIGHT level-2 tallies of steps 0 and 1,
    neither yet consumed. The resumed run's steps 3/4 consume tallies
    launched on the other side of the crash; losses, params, momenta and
    the ring itself must stay bit-identical to the uninterrupted run."""
    mesh = make_mesh(data=8)
    model = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(64, 32, model.vocab_size, seed=1)
    _assert_resumed_matches(tmp_path, mesh, model, blocks, wire="hier:4",
                            dcn_pipeline_depth=2)


def test_resume_depth_toggle_errors_loudly(tmp_path):
    """A checkpoint written at one --dcn_pipeline_depth must refuse to
    restore at another: the ring's slot count IS the staleness semantics —
    there is no meaning-preserving reshape — and silently reinitializing
    it would drop in-flight elections."""
    mesh = make_mesh(data=8)
    model = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(64, 32, model.vocab_size, seed=1)
    out = str(tmp_path / "run")
    t1, _ = _run(_cfg(out, 2, wire="hier:4", dcn_pipeline_depth=2),
                 mesh, model, blocks)
    t1.close()
    for other in (0, 1):
        with pytest.raises(ValueError, match="dcn_pipeline_depth"):
            Trainer.for_gpt2(
                _cfg(out, 4, wire="hier:4", dcn_pipeline_depth=other),
                mesh, model, seed=3)
