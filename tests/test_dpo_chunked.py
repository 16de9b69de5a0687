"""Chunked-vocab DPO: the four scoring passes (policy/ref × chosen/rejected)
stream their label logprobs through ops/xent's chunked logsumexp instead of
materializing [B, T, V] f32 log_softmax — the largest activation saving of
any workload (DPO holds TWO models and scores TWO sequences each). Exact
same math as the dense path (reference semantics: dpo_llama2.py:192-223);
these tests pin loss, gradients, trajectory, and the CLI flag end-to-end."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_hidden, llama_init
from distributed_lion_tpu.models.lora import LoraConfig, lora_apply_fn, lora_init
from distributed_lion_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, make_mesh
from distributed_lion_tpu.train.dpo import (
    make_dpo_loss_fn,
    sequence_logprob,
    sequence_logprob_chunked,
)
from distributed_lion_tpu.train.loop import TrainConfig, Trainer


def _rand_batch(rng, gb, T, vocab):
    b = {}
    for side in ("chosen", "rejected"):
        b[side] = rng.integers(0, vocab, size=(gb, T)).astype(np.int32)
        mask = np.zeros((gb, T), np.float32)
        for r in range(gb):
            start = int(rng.integers(2, T // 2))
            stop = int(rng.integers(T // 2 + 1, T))
            mask[r, start:stop] = 1.0
        b[f"{side}_mask"] = mask
    return b


def test_sequence_logprob_chunked_matches_dense():
    """−nll-from-hidden == gather-from-log_softmax, values AND gradients
    (hidden and head), at a vocab that doesn't divide the chunk count."""
    rng = np.random.default_rng(0)
    B, T, D, V = 2, 10, 8, 37
    hidden = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(D, V)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    mask = jnp.asarray((rng.random((B, T)) > 0.4), jnp.float32)

    def dense(hidden, head):
        logits = jnp.einsum("btd,dv->btv", hidden, head)
        return sequence_logprob(logits, tokens, mask).sum()

    def chunked(hidden, head):
        return sequence_logprob_chunked(hidden, head, tokens, mask,
                                        n_chunks=4, emb_layout="dv").sum()

    v_d, g_d = jax.jit(jax.value_and_grad(dense, argnums=(0, 1)))(hidden, head)
    v_c, g_c = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(hidden,
                                                                    head)
    np.testing.assert_allclose(v_d, v_c, rtol=1e-5, atol=1e-5)
    for a, b in zip(g_d, g_c):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _pieces():
    model_cfg = LlamaConfig.tiny()
    base = llama_init(jax.random.key(0), model_cfg)
    lcfg = LoraConfig(r=4, alpha=8)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    return model_cfg, base, lcfg, adapters


def _loss_fns(model_cfg, base, lcfg, vocab_chunks):
    """(dense, chunked) DPO loss fns over the same frozen base."""
    pol_dense = lora_apply_fn(
        lambda p, t: llama_apply(p, t, model_cfg), base, lcfg)
    dense, dense_spec = make_dpo_loss_fn(
        policy_apply=pol_dense,
        ref_apply=lambda t: llama_apply(base, t, model_cfg), beta=0.1)
    assert not dense_spec.vocab_chunks   # nothing to honour the flag with

    def hidden_head(p, t):
        return llama_hidden(p, t, model_cfg), p["lm_head"]

    pol_chunked = lora_apply_fn(hidden_head, base, lcfg)
    chunked, spec = make_dpo_loss_fn(
        policy_apply=pol_chunked,
        ref_apply=lambda t: hidden_head(base, t), beta=0.1,
        vocab_chunks=vocab_chunks)
    assert spec.vocab_chunks is True     # what the Trainer's guard reads
    return dense, chunked


def test_dpo_loss_and_grads_match_dense():
    model_cfg, base, lcfg, adapters = _pieces()
    dense, chunked = _loss_fns(model_cfg, base, lcfg, vocab_chunks=4)
    batch = jax.tree.map(jnp.asarray,
                         _rand_batch(np.random.default_rng(1), 2, 32,
                                     model_cfg.vocab_size))

    # each loss and its grads as ONE compiled program (ISSUE 35)
    (l_d, m_d), g_d = jax.jit(jax.value_and_grad(
        lambda a: dense(a, batch, None), has_aux=True))(adapters)
    (l_c, m_c), g_c = jax.jit(jax.value_and_grad(
        lambda a: chunked(a, batch, None), has_aux=True))(adapters)
    np.testing.assert_allclose(l_d, l_c, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(m_d["reward_margin"], m_c["reward_margin"],
                               rtol=1e-4, atol=1e-5)
    # adapter grads flow through bf16 compute; the chunked scan reorders
    # the backward sums, so leaves agree to bf16 resolution (~1%), while
    # loss/metrics (f32 reductions) pin at 1e-5 above
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_c)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        denom = np.abs(a).max() + 1e-12
        assert np.abs(a - b).max() / denom < 2e-2


def _train(mesh, sp, vocab_chunks, steps=6):
    model_cfg, base, lcfg, adapters = _pieces()
    seq_axis = SEQ_AXIS if sp > 1 else None
    kw = {} if seq_axis is None else {"seq_axis": seq_axis}

    if vocab_chunks > 0:
        def fwd(p, t):
            return llama_hidden(p, t, model_cfg, **kw), p["lm_head"]
        ref_fwd = lambda t: fwd(base, t)  # noqa: E731
    else:
        def fwd(p, t):
            return llama_apply(p, t, model_cfg, **kw)
        ref_fwd = lambda t: fwd(base, t)  # noqa: E731
    loss_fn, loss_spec = make_dpo_loss_fn(
        policy_apply=lora_apply_fn(fwd, base, lcfg), ref_apply=ref_fwd,
        beta=0.1, seq_axis=seq_axis, vocab_chunks=vocab_chunks)

    cfg = TrainConfig(
        lion=True, async_grad=True, learning_rate=1e-3, weight_decay=0.0,
        warmup_steps=2, max_steps=steps, per_device_train_batch_size=2,
        gradient_accumulation_steps=1, block_size=64, logging_steps=1,
        eval_steps=1000, save_steps=1000, seed=0,
        vocab_chunks=vocab_chunks,
    )
    assert loss_spec.batch_spec == (P(DATA_AXIS, SEQ_AXIS) if sp > 1
                                    else None)
    trainer = Trainer(cfg, mesh, apply_fn=None, params=adapters,
                      loss_fn=loss_fn, loss_spec=loss_spec)
    rng = np.random.default_rng(2)
    batches = [_rand_batch(rng, trainer.global_train_batch(), 64,
                           LlamaConfig.tiny().vocab_size)
               for _ in range(steps)]
    history = trainer.train(iter(batches), max_steps=steps)
    losses = [h["loss"] for h in history if "loss" in h]
    trainer.close()
    return losses


def test_dpo_chunked_trajectory_matches_dense():
    """Full vote-Lion DPO training with vocab_chunks reproduces the dense
    trajectory (same data, same world)."""
    mesh = make_mesh(data=2, devices=jax.devices()[:2])
    np.testing.assert_allclose(
        _train(mesh, sp=1, vocab_chunks=0),
        _train(mesh, sp=1, vocab_chunks=4), rtol=2e-3, atol=2e-3)


def test_dpo_chunked_seq_parallel_matches_dense_dp():
    """Chunked logprobs compose with the seq-axis boundary protocol: the
    dp×sp chunked trajectory == pure-dp dense trajectory."""
    mesh_sp = make_mesh(data=2, seq=4, devices=jax.devices()[:8])
    mesh_dp = make_mesh(data=2, devices=jax.devices()[:2])
    np.testing.assert_allclose(
        _train(mesh_sp, sp=4, vocab_chunks=4),
        _train(mesh_dp, sp=1, vocab_chunks=0), rtol=2e-2, atol=2e-2)


def test_run_dpo_cli_vocab_chunks_smoke(tmp_path):
    from distributed_lion_tpu.cli.run_dpo import main

    main([
        "--model_name", "tiny", "--dataset", "synthetic",
        "--num_train_samples", "48", "--size_valid_set", "8",
        "--max_length", "96", "--max_prompt_length", "48",
        "--lion", "--async_grad", "--max_steps", "2", "--warmup_steps", "1",
        "--per_device_train_batch_size", "1",
        "--gradient_accumulation_steps", "1", "--logging_steps", "1",
        "--eval_steps", "1000", "--save_steps", "1000", "--eval_iters", "1",
        "--vocab_chunks", "4",
        "--output_dir", str(tmp_path / "dpo_vc"),
    ])
    assert (tmp_path / "dpo_vc" / "metrics.jsonl").exists()
