"""Sequence-parallel TRAINING (not just the ring-attention op): the
dp×sp train step must reproduce the pure-dp trajectory exactly — same data
rows, same vote world, tokens merely sharded across the seq axis."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from _sharded import run_sharded
from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init
from distributed_lion_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, make_mesh
from distributed_lion_tpu.train.loop import TrainConfig, Trainer


def test_sp_forward_matches_single_device():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 64)), jnp.int32)
    expected = jax.jit(gpt2_apply, static_argnums=2)(params, toks, cfg)

    mesh = make_mesh(data=1, seq=4, devices=jax.devices()[:4])

    def f(p, t):
        return gpt2_apply(p, t, cfg, seq_axis=SEQ_AXIS)

    out = run_sharded(f, mesh, (P(), P(None, SEQ_AXIS)), P(None, SEQ_AXIS),
                      params, toks, check_vma=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-2, atol=2e-2)


def test_llama_sp_forward_matches_single_device():
    """Llama SP: rotary offsets per shard + ring attention == dense."""
    from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_init

    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.key(1), cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 64)), jnp.int32)
    expected = jax.jit(llama_apply, static_argnums=2)(params, toks, cfg)

    mesh = make_mesh(data=1, seq=4, devices=jax.devices()[:4])

    def f(p, t):
        return llama_apply(p, t, cfg, seq_axis=SEQ_AXIS)

    out = run_sharded(f, mesh, (P(), P(None, SEQ_AXIS)), P(None, SEQ_AXIS),
                      params, toks, check_vma=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-2, atol=2e-2)


def _cfg(**kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=3e-3, weight_decay=0.0,
        warmup_steps=5, max_steps=20, per_device_train_batch_size=4,
        gradient_accumulation_steps=1, block_size=32, logging_steps=5,
        eval_steps=10**6, save_steps=10**6, seed=0, output_dir=None,
    )
    base.update(kw)
    return TrainConfig(**base)


def test_sp_gradients_match_pure_dp():
    """dp=2 × sp=4 vs dp=2 after ONE step: each voter's Lion momentum is
    (1-β₂)·grad, so momentum equality ⇔ the seq-psum of shard gradients
    equals the full-sequence gradient (catches a missing/extra psum or
    broken boundary labels outright; tolerance covers bf16 noise between
    ring and dense attention orderings)."""
    model_cfg = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(512, 32, model_cfg.vocab_size)

    t_sp = Trainer.for_gpt2(_cfg(), make_mesh(data=2, seq=4), model_cfg)
    t_dp = Trainer.for_gpt2(_cfg(), make_mesh(data=2, devices=jax.devices()[:2]),
                            model_cfg)
    assert t_sp.global_train_batch() == t_dp.global_train_batch() == 8
    t_sp.train(batch_iterator(blocks, 8, seed=1), max_steps=1)
    t_dp.train(batch_iterator(blocks, 8, seed=1), max_steps=1)
    for a, b in zip(jax.tree.leaves(t_sp.state.exp_avg),
                    jax.tree.leaves(t_dp.state.exp_avg)):
        a, b = np.asarray(a), np.asarray(b)
        denom = np.maximum(np.abs(b).max(), 1e-8)
        np.testing.assert_allclose(a / denom, b / denom, atol=6e-2)
    t_sp.close()
    t_dp.close()


def test_ulysses_sp_forward_matches_single_device():
    """seq_impl='ulysses' (all_to_all to head sharding) == dense forward."""
    cfg = GPT2Config.tiny(seq_impl="ulysses")
    params = gpt2_init(jax.random.key(2), cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 64)), jnp.int32)
    expected = jax.jit(gpt2_apply, static_argnums=2)(params, toks, cfg)

    mesh = make_mesh(data=1, seq=4, devices=jax.devices()[:4])

    def f(p, t):
        return gpt2_apply(p, t, cfg, seq_axis=SEQ_AXIS)

    out = run_sharded(f, mesh, (P(), P(None, SEQ_AXIS)), P(None, SEQ_AXIS),
                      params, toks, check_vma=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-2, atol=2e-2)


def test_ulysses_sp_training_matches_pure_dp():
    """Full vote-Lion train step with the Ulysses seq impl: momentum after
    one step matches pure-dp (same invariant as the ring test above)."""
    model_cfg = GPT2Config.tiny(seq_impl="ulysses")
    blocks = synthetic_lm_dataset(512, 32, model_cfg.vocab_size)

    t_sp = Trainer.for_gpt2(_cfg(), make_mesh(data=2, seq=4), model_cfg)
    t_dp = Trainer.for_gpt2(_cfg(), make_mesh(data=2, devices=jax.devices()[:2]),
                            model_cfg)
    t_sp.train(batch_iterator(blocks, 8, seed=1), max_steps=1)
    t_dp.train(batch_iterator(blocks, 8, seed=1), max_steps=1)
    for a, b in zip(jax.tree.leaves(t_sp.state.exp_avg),
                    jax.tree.leaves(t_dp.state.exp_avg)):
        a, b = np.asarray(a), np.asarray(b)
        denom = np.maximum(np.abs(b).max(), 1e-8)
        np.testing.assert_allclose(a / denom, b / denom, atol=6e-2)
    t_sp.close()
    t_dp.close()


def test_dp_sp_adamw_trajectory_matches_pure_dp():
    """With the continuous AdamW optimizer (no sign discretization to
    amplify bf16 noise), the dp×sp run reproduces the pure-dp parameter
    trajectory over 20 steps."""
    model_cfg = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(512, 32, model_cfg.vocab_size)
    kw = dict(lion=False, async_grad=False, learning_rate=1e-3)

    t_sp = Trainer.for_gpt2(_cfg(**kw), make_mesh(data=2, seq=4), model_cfg)
    t_sp.train(batch_iterator(blocks, 8, seed=1), max_steps=20)
    t_dp = Trainer.for_gpt2(_cfg(**kw), make_mesh(data=2, devices=jax.devices()[:2]),
                            model_cfg)
    t_dp.train(batch_iterator(blocks, 8, seed=1), max_steps=20)

    for a, b in zip(jax.tree.leaves(t_sp.params), jax.tree.leaves(t_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=2e-2)
    t_sp.close()
    t_dp.close()


def test_sp_vote_lion_loss_decreases():
    """End-to-end: vote-Lion training under dp×sp converges."""
    model_cfg = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(512, 32, model_cfg.vocab_size)
    t = Trainer.for_gpt2(_cfg(max_steps=40), make_mesh(data=2, seq=4), model_cfg)
    h = t.train(batch_iterator(blocks, 8, seed=1), max_steps=40)
    losses = [x["loss"] for x in h if "loss" in x]
    assert losses[-1] < losses[0] - 0.3, losses
    t.close()


def test_sp_eval_matches_dp_eval():
    """Boundary-label ppermute: eval loss/accuracy under sp=4 equals the
    unsharded eval on the same blocks."""
    model_cfg = GPT2Config.tiny()
    blocks = synthetic_lm_dataset(64, 32, model_cfg.vocab_size)
    m_sp = Trainer.for_gpt2(_cfg(per_device_eval_batch_size=4),
                            make_mesh(data=2, seq=4), model_cfg)
    m_dp = Trainer.for_gpt2(_cfg(per_device_eval_batch_size=4),
                            make_mesh(data=2, devices=jax.devices()[:2]), model_cfg)
    e_sp = m_sp.evaluate(blocks)
    e_dp = m_dp.evaluate(blocks)
    np.testing.assert_allclose(e_sp["eval/loss"], e_dp["eval/loss"], rtol=2e-3)
    np.testing.assert_allclose(e_sp["eval/accuracy"], e_dp["eval/accuracy"], rtol=2e-3)
    m_sp.close()
    m_dp.close()
