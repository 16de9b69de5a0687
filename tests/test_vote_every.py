"""vote_every lazy sign refresh: the sub-bit wire (VERDICT r1 item 3).

BASELINE.md's comm budget: ≤ 1/32 of a bf16 gradient all-reduce = 0.5
bit/param. ``packed_a2a`` alone is ~2 bits/param/optimizer-step; with
``vote_every=4`` each step votes a quarter of the coordinates → ≤ 0.5
bit/param/step, replicas still bit-identical (the elected cache holds only
voted, shared results). These tests pin: the accounting, replica
consistency, the K=1 equivalence, cold-start masking, and convergence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sharded import sharded_opt_step, toy_problem
from distributed_lion_tpu.ops.codec import wire_bytes_per_param
from distributed_lion_tpu.optim import distributed_lion, init_global_state


def _run_steps(opt, params, grads_per_worker, n_steps, mesh, world):
    """Drive opt.step under shard_map for n_steps; grads_per_worker is a
    [world, ...] stacked pytree reused every step."""
    state = init_global_state(opt, params, world)
    step = sharded_opt_step(opt, mesh, state)
    for _ in range(n_steps):
        params, state = step(params, grads_per_worker, state)
    return params, state


@pytest.mark.parametrize("wire", ["sign_psum", "packed_a2a"])
def test_vote_every_replicas_consistent(mesh8, wire):
    params, grads = toy_problem()
    opt = distributed_lion(learning_rate=0.01, wire=wire, vote_every=4)
    p, st = _run_steps(opt, params, grads, n_steps=6, mesh=mesh8, world=8)
    # params stay replicated: every device holds identical values
    for leaf in jax.tree.leaves(p):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)
    assert st.elected is not None and st.elected.dtype == jnp.uint8


def test_vote_every_one_matches_plain(mesh8):
    """K=1 must be the plain voted optimizer bit-for-bit."""
    params, grads = toy_problem()
    p1, _ = _run_steps(distributed_lion(learning_rate=0.01), params, grads, 5, mesh8, 8)
    p2, _ = _run_steps(distributed_lion(learning_rate=0.01, vote_every=1),
                       params, grads, 5, mesh8, 8)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), p1, p2)


def test_vote_every_cold_start_mask(mesh8):
    """During the first K-1 steps, not-yet-voted coordinates must not move
    (beyond weight decay, which is off here)."""
    params, grads = toy_problem(n=40)
    opt = distributed_lion(learning_rate=0.01, vote_every=4)
    p, _ = _run_steps(opt, params, grads, n_steps=1, mesh=mesh8, world=8)
    n = 40 + 3
    from distributed_lion_tpu.ops.codec import vote_chunk_elems

    chunk = vote_chunk_elems(n, 4)
    # ballot order is jax.tree.leaves order (dict keys sorted: b before w)
    flat0 = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(params)])
    flat1 = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(p)])
    moved = flat0 != flat1
    # only slot-0 coordinates may move on step 0
    assert moved[:min(chunk, n)].any()
    assert not moved[chunk:].any()


def test_vote_every_accounting_meets_budget():
    acct = wire_bytes_per_param(124_000_000, 8, "packed_a2a", vote_every=4)
    assert acct["bits_per_param"] <= 0.5 + 1e-6
    assert acct["vs_bf16_allreduce"] <= 1 / 32 + 1e-9
    # and the amortized view under the canonical accum=8 config
    acct2 = wire_bytes_per_param(124_000_000, 8, "packed_a2a", accum_steps=8)
    assert acct2["bits_per_param_per_microbatch"] <= 0.5 + 1e-6
    assert acct2["vs_bf16_allreduce_equal_tokens"] <= 1 / 32 + 1e-9
    # sign_psum per-step is honestly ~8 bits/param — no overclaim
    acct3 = wire_bytes_per_param(124_000_000, 8, "sign_psum")
    assert 7.9 <= acct3["bits_per_param"] <= 8.1


def test_vote_every_trainer_converges(mesh8):
    """End-to-end: tiny GPT-2, vote_every=4 + packed_a2a (the ≤0.5 bit/param
    config), loss decreases and the comm report shows the budget met."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    cfg = TrainConfig(
        lion=True, async_grad=True, wire="packed_a2a", vote_every=4,
        learning_rate=3e-3, warmup_steps=2, max_steps=30,
        per_device_train_batch_size=2, gradient_accumulation_steps=1,
        block_size=32, logging_steps=5, output_dir=None,
    )
    model_cfg = GPT2Config.tiny()
    trainer = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    acct = trainer.comm_stats()
    assert acct["comm_bits_per_param"] <= 0.5 + 1e-6
    # memorizable corpus: few distinct blocks
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  model_cfg.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(), seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.3, losses
    trainer.close()


def test_vote_every_checkpoint_resume(tmp_path, mesh8):
    """The packed elected-sign cache survives checkpoint/resume: a 2+2-step
    resumed run equals a continuous 4-step run (same data stream)."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    model = GPT2Config.tiny(compute_dtype=jnp.float32)
    blocks = synthetic_lm_dataset(64, 32, model.vocab_size, seed=1)

    def cfg(outdir, steps):
        return TrainConfig(
            lion=True, async_grad=True, wire="packed_a2a", vote_every=4,
            learning_rate=1e-3, warmup_steps=1, max_steps=steps,
            per_device_train_batch_size=1, gradient_accumulation_steps=1,
            block_size=32, logging_steps=1, save_steps=2,
            output_dir=outdir, seed=5,
        )

    t0 = Trainer.for_gpt2(cfg(None, 4), mesh8, model, seed=3)
    h0 = t0.train(batch_iterator(blocks, t0.global_train_batch(), seed=5))
    ref = [h["loss"] for h in h0 if "loss" in h]
    params_ref = jax.tree.map(np.asarray, jax.device_get(t0.params))
    t0.close()

    out = str(tmp_path / "run")
    t1 = Trainer.for_gpt2(cfg(out, 2), mesh8, model, seed=3)
    t1.train(batch_iterator(blocks, t1.global_train_batch(), seed=5))
    t1.save()
    t1.close()

    t2 = Trainer.for_gpt2(cfg(out, 4), mesh8, model, seed=3)
    assert t2.step_count == 2
    assert t2.state.elected is not None  # cache restored, not re-zeroed
    h2 = t2.train(batch_iterator(blocks, t2.global_train_batch(), seed=5))
    resumed = [h["loss"] for h in h2 if "loss" in h]
    params_res = jax.tree.map(np.asarray, jax.device_get(t2.params))
    t2.close()

    np.testing.assert_allclose(resumed, ref[2:], rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(params_ref), jax.tree.leaves(params_res)):
        np.testing.assert_array_equal(a, b)
