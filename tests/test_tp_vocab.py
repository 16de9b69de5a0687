"""Megatron-style vocab-parallel cross entropy (ops/xent.tp_vocab_xent).

The lm_head's vocab columns shard over the tensor axis; the full [N, V]
logits never exist on one device. Must match the dense log_softmax + gather
exactly — values, gradients, argmax tie rule — and the for_llama --tp_vocab
path must reproduce the replicated-head TP trajectory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _sharded import run_sharded
from distributed_lion_tpu.ops.xent import tp_vocab_xent

TP = 4


def _mesh():
    return Mesh(np.array(jax.devices()[:TP]), ("tensor",))


def _dense(hidden, head, labels):
    logits = (hidden @ head).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[..., 0]
    return nll, logits.argmax(-1) == labels


def _sharded(hidden, head, labels):
    def body(h, hd, lab):
        return tp_vocab_xent(h, hd, lab, "tensor")

    return run_sharded(body, _mesh(), (P(), P(None, "tensor"), P()),
                       (P(), P()), hidden, head, labels, check_vma=False)


def _data(n=37, d=16, v=64, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    hidden = jax.random.normal(k1, (n, d), jnp.float32)
    head = jax.random.normal(k2, (d, v), jnp.float32)
    labels = jnp.asarray(
        np.random.default_rng(seed).integers(0, v, n), jnp.int32)
    return hidden, head, labels


def test_matches_dense_values():
    hidden, head, labels = _data()
    nll_d, cor_d = _dense(hidden, head, labels)
    nll_s, cor_s = _sharded(hidden, head, labels)
    np.testing.assert_allclose(np.asarray(nll_s), np.asarray(nll_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cor_s), np.asarray(cor_d))


def test_matches_dense_gradients():
    """Gradients are EXACT: jax.grad runs INSIDE the shard_map body (as in
    the train step), where the Megatron f/g custom-vjp pairing
    (copy_to_tp_region at entry, reduce_from_tp_region inside the loss)
    makes every cotangent count each contribution exactly once — raw psums
    would over-count by W per crossing (tensor_parallel.py docstring)."""
    hidden, head, labels = _data(seed=1)

    def dense_loss(h, hd):
        return _dense(h, hd, labels)[0].mean()

    def body(h, hd, lab):
        def loss(h, hd):
            return tp_vocab_xent(h, hd, lab, "tensor")[0].mean()

        gh, ghd = jax.grad(loss, argnums=(0, 1))(h, hd)
        return gh, ghd  # gh complete+replicated; ghd this rank's shard

    gh_s, ghd_s = run_sharded(
        body, _mesh(), (P(), P(None, "tensor"), P()),
        (P(), P(None, "tensor")), hidden, head, labels, check_vma=False)
    gh_d, ghd_d = jax.grad(dense_loss, argnums=(0, 1))(hidden, head)
    np.testing.assert_allclose(np.asarray(gh_s), np.asarray(gh_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ghd_s), np.asarray(ghd_d),
                               rtol=1e-4, atol=1e-5)


def test_argmax_tie_rule():
    """Dense argmax picks the lowest index on exact ties — including ties
    that span different ranks' vocab shards."""
    hidden = jnp.zeros((2, 4), jnp.float32)
    head = jnp.zeros((4, 64), jnp.float32)  # ALL logits equal → argmax = 0
    labels = jnp.asarray([0, 17], jnp.int32)
    _, cor_d = _dense(hidden, head, labels)
    _, cor_s = _sharded(hidden, head, labels)
    np.testing.assert_array_equal(np.asarray(cor_s), np.asarray(cor_d))
    assert bool(cor_s[0]) and not bool(cor_s[1])


def test_for_llama_tp_vocab_matches_replicated_head():
    """dp=4 x tp=2 with --tp_vocab reproduces the replicated-head TP
    trajectory; the lm_head leaf is actually sharded."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.llama import LlamaConfig
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    def run(tp_vocab):
        cfg = TrainConfig(
            lion=True, async_grad=True, learning_rate=3e-3, weight_decay=0.0,
            warmup_steps=2, max_steps=8, per_device_train_batch_size=2,
            gradient_accumulation_steps=1, block_size=32, logging_steps=2,
            eval_steps=1000, save_steps=1000, seed=0, tp_vocab=tp_vocab,
        )
        mesh = make_mesh(data=4, tensor=2)
        trainer = Trainer.for_llama(cfg, mesh, LlamaConfig.tiny())
        blocks = synthetic_lm_dataset(512, 32, 256)
        hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(),
                                            seed=1), max_steps=8)
        losses = [h["loss"] for h in hist if "loss" in h]
        head = trainer.params["lm_head"]
        trainer.close()
        return losses, head

    l_vp, head_vp = run(True)
    l_rep, _ = run(False)
    np.testing.assert_allclose(l_vp, l_rep, rtol=2e-2, atol=2e-2)
    # sharded head: each device holds a [d, V/2] slice
    shard_shape = head_vp.addressable_shards[0].data.shape
    assert shard_shape == (head_vp.shape[0], head_vp.shape[1] // 2)


def test_vocab_parallel_embed_matches_dense():
    """Megatron VocabParallelEmbedding == plain table lookup."""
    from distributed_lion_tpu.models.gpt2 import vocab_parallel_embed

    wte = jax.random.normal(jax.random.key(0), (64, 8), jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (3, 10)),
                         jnp.int32)
    dense = wte[tokens]

    def body(w, t):
        return vocab_parallel_embed(w, t, "tensor")

    out = run_sharded(body, _mesh(), (P("tensor"), P()), P(), wte, tokens,
                      check_vma=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


def test_for_gpt2_tp_vocab_matches_replicated_head():
    """GPT-2 (tied embedding): dp=4 x tp=2 --tp_vocab reproduces the
    replicated-embedding TP trajectory; wte is actually row-sharded."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    def run(tp_vocab):
        cfg = TrainConfig(
            lion=True, async_grad=True, learning_rate=3e-3, weight_decay=0.0,
            warmup_steps=2, max_steps=8, per_device_train_batch_size=2,
            gradient_accumulation_steps=1, block_size=32, logging_steps=2,
            eval_steps=1000, save_steps=1000, seed=0, tp_vocab=tp_vocab,
        )
        mesh = make_mesh(data=4, tensor=2)
        trainer = Trainer.for_gpt2(cfg, mesh, GPT2Config.tiny())
        blocks = synthetic_lm_dataset(512, 32, 256)
        hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(),
                                            seed=1), max_steps=8)
        losses = [h["loss"] for h in hist if "loss" in h]
        wte = trainer.params["wte"]
        trainer.close()
        return losses, wte

    l_vp, wte_vp = run(True)
    l_rep, _ = run(False)
    np.testing.assert_allclose(l_vp, l_rep, rtol=2e-2, atol=2e-2)
    shard_shape = wte_vp.addressable_shards[0].data.shape
    assert shard_shape == (wte_vp.shape[0] // 2, wte_vp.shape[1])


def test_tp_gradients_exact_vs_pure_dp():
    """The f/g custom-vjp pairing makes FULL-MODEL TP gradients equal the
    pure-dp gradients (per-leaf median ratio 1.0) — with raw psum exits the
    ratios were depth-dependent mixed powers of W with sign flips. One
    vote-Lion step: momentum = (1-β₂)·grad, so momentum ratios ARE grad
    ratios."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    def momenta(mesh):
        cfg = TrainConfig(
            lion=True, async_grad=True, learning_rate=3e-3, weight_decay=0.0,
            warmup_steps=2, max_steps=2, per_device_train_batch_size=2,
            gradient_accumulation_steps=1, block_size=32, logging_steps=10,
            eval_steps=1000, save_steps=1000, seed=0,
        )
        t = Trainer.for_gpt2(cfg, mesh, GPT2Config.tiny())
        blocks = synthetic_lm_dataset(256, 32, 256)
        t.train(batch_iterator(blocks, t.global_train_batch(), seed=1),
                max_steps=1)
        m = jax.tree.map(lambda x: np.asarray(x), t.state.exp_avg)
        t.close()
        return m

    m_dp = momenta(make_mesh(data=2, devices=jax.devices()[:2]))
    m_tp = momenta(make_mesh(data=2, tensor=2, devices=jax.devices()[:4]))
    for a, b in zip(jax.tree.leaves(m_dp), jax.tree.leaves(m_tp)):
        a0, b0 = a[0], b[0]  # worker 0's momentum
        big = np.abs(a0) > 1e-6  # above bf16 noise floor
        if big.sum() < 8:
            continue
        med = float(np.median(b0[big] / a0[big]))
        assert abs(med - 1.0) < 1e-2, med


def test_tp_vocab_guards():
    from distributed_lion_tpu.models.llama import LlamaConfig
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    base = dict(lion=True, async_grad=True, max_steps=1)
    with pytest.raises(ValueError, match="tensor_parallel"):
        Trainer.for_llama(TrainConfig(tp_vocab=True, **base),
                          make_mesh(data=8), LlamaConfig.tiny())
    with pytest.raises(NotImplementedError, match="alternative head"):
        Trainer.for_llama(TrainConfig(tp_vocab=True, vocab_chunks=4, **base),
                          make_mesh(data=4, tensor=2), LlamaConfig.tiny())
    with pytest.raises(ValueError, match="divisible"):
        Trainer.for_llama(TrainConfig(tp_vocab=True, **base),
                          make_mesh(data=4, tensor=2),
                          LlamaConfig.tiny(vocab_size=257))
