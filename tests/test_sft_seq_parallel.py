"""Sequence-parallel SFT: the LoRA/frozen-base train step with tokens
sharded over the 'seq' axis (ring attention, boundary-label ppermute) must
reproduce the pure-dp trajectory — same rows, same vote world, tokens
merely split across devices. Net-new vs the reference (data-parallel only,
truncation at 1024 — SURVEY §5 long-context)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_init
from distributed_lion_tpu.models.lora import LoraConfig, apply_adapters, lora_init
from distributed_lion_tpu.models.loss import (
    clm_loss_and_metrics,
    clm_loss_seq_parallel,
)
from distributed_lion_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, make_mesh
from distributed_lion_tpu.train.loop import LossSpec, TrainConfig, Trainer


def _cfg(**kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=3e-3, weight_decay=0.0,
        warmup_steps=2, max_steps=8, per_device_train_batch_size=2,
        gradient_accumulation_steps=1, block_size=64, logging_steps=1,
        eval_steps=1000, save_steps=1000, seed=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _sft_pieces():
    model_cfg = LlamaConfig.tiny()
    base = llama_init(jax.random.key(0), model_cfg)
    lcfg = LoraConfig(r=4, alpha=8)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    return model_cfg, base, lcfg, adapters


def _train(mesh, sp, steps=8):
    model_cfg, base, lcfg, adapters = _sft_pieces()
    cfg = _cfg()
    if sp > 1:
        def loss_fn(params, batch, dropout_key):
            effective = apply_adapters(base, params, lcfg)
            logits = llama_apply(effective, batch, model_cfg, seq_axis=SEQ_AXIS)
            return clm_loss_seq_parallel(logits, batch, SEQ_AXIS)

        trainer = Trainer(cfg, mesh, apply_fn=None, params=adapters,
                          loss_fn=loss_fn,
                          loss_spec=LossSpec(batch_spec=P(DATA_AXIS, SEQ_AXIS)))
    else:
        def loss_fn(params, batch, dropout_key):
            effective = apply_adapters(base, params, lcfg)
            logits = llama_apply(effective, batch, model_cfg)
            return clm_loss_and_metrics(logits, batch, None)

        trainer = Trainer(cfg, mesh, apply_fn=None, params=adapters,
                          loss_fn=loss_fn)

    rng = np.random.default_rng(7)
    rows = rng.integers(0, model_cfg.vocab_size,
                        size=(steps, trainer.global_train_batch(), 64),
                        ).astype(np.int32)
    history = trainer.train(iter(list(rows)), max_steps=steps)
    losses = [h["loss"] for h in history if "loss" in h]
    trainer.close()
    return losses


def test_sft_sp_trajectory_matches_pure_dp():
    mesh_sp = make_mesh(data=2, seq=4, devices=jax.devices()[:8])
    mesh_dp = make_mesh(data=2, devices=jax.devices()[:2])
    losses_sp = _train(mesh_sp, sp=4)
    losses_dp = _train(mesh_dp, sp=1)
    assert len(losses_sp) == len(losses_dp) > 0
    np.testing.assert_allclose(losses_sp, losses_dp, rtol=2e-2, atol=2e-2)


def test_sft_tp_sp_trajectory_matches_pure_dp():
    """dp=2 x tp=2 x sp=2 SFT (sharded frozen base + ring attention) must
    reproduce the dp=2 trajectory — the long-context multi-chip QLoRA shape
    (round-3 composition unlock; mirrors cli/run_sft's tp x sp wiring)."""
    from distributed_lion_tpu.models.lora import lora_adapter_specs
    from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS
    from distributed_lion_tpu.parallel.tensor_parallel import (
        llama_param_specs, validate_tp)

    model_cfg, base, lcfg, adapters = _sft_pieces()
    cfg_dp = _cfg()
    mesh_dp = make_mesh(data=2, devices=jax.devices()[:2])

    def dp_loss(params, batch, dropout_key):
        effective = apply_adapters(base, params, lcfg)
        logits = llama_apply(effective, batch, model_cfg)
        return clm_loss_and_metrics(logits, batch, None)

    tr_dp = Trainer(cfg_dp, mesh_dp, apply_fn=None, params=adapters,
                    loss_fn=dp_loss)

    validate_tp(model_cfg, 2, "llama")
    base_specs = llama_param_specs(model_cfg)
    adapters2 = lora_init(jax.random.key(1), base, lcfg)
    adapter_specs = lora_adapter_specs(adapters2, base_specs, TENSOR_AXIS)
    mesh_tpsp = make_mesh(data=2, tensor=2, seq=2, devices=jax.devices()[:8])

    def tpsp_loss(params, frozen, batch, dropout_key):
        effective = apply_adapters(frozen, params, lcfg, tp_axis=TENSOR_AXIS,
                                   base_specs=base_specs)
        logits = llama_apply(effective, batch, model_cfg,
                             tp_axis=TENSOR_AXIS, seq_axis=SEQ_AXIS)
        return clm_loss_seq_parallel(logits, batch, SEQ_AXIS)

    tr_tpsp = Trainer(_cfg(tensor_parallel=2, seq_parallel=2), mesh_tpsp,
                      apply_fn=None, params=adapters2,
                      param_specs=adapter_specs, loss_fn=tpsp_loss,
                      frozen_params=base, frozen_specs=base_specs,
                      loss_spec=LossSpec(batch_spec=P(DATA_AXIS, SEQ_AXIS)))

    rng = np.random.default_rng(7)
    steps = 6
    rows = rng.integers(0, model_cfg.vocab_size,
                        size=(steps, tr_dp.global_train_batch(), 64),
                        ).astype(np.int32)
    h_dp = tr_dp.train(iter(list(rows)), max_steps=steps)
    h_tpsp = tr_tpsp.train(iter(list(rows)), max_steps=steps)
    l_dp = [h["loss"] for h in h_dp if "loss" in h]
    l_tpsp = [h["loss"] for h in h_tpsp if "loss" in h]
    tr_dp.close()
    tr_tpsp.close()
    assert len(l_dp) == len(l_tpsp) > 0
    np.testing.assert_allclose(l_tpsp, l_dp, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("vocab_chunks", ["0", "4"])
def test_run_sft_cli_tp_sp_smoke(vocab_chunks):
    """CLI wiring: --tensor_parallel 2 --seq_parallel 2 (+ NF4 base) runs,
    with both the dense and the chunked-vocab seq head."""
    from distributed_lion_tpu.cli.run_sft import main

    main([
        "--model_name", "tiny", "--dataset", "synthetic", "--lion",
        "--async_grad", "--max_steps", "2", "--per_device_train_batch_size",
        "1", "--gradient_accumulation_steps", "1", "--seq_length", "64",
        "--num_train_samples", "32", "--size_valid_set", "8",
        "--logging_steps", "10", "--eval_steps", "1000", "--save_steps",
        "1000", "--tensor_parallel", "2", "--seq_parallel", "2",
        "--quant", "nf4", "--quant_block", "16",
        "--vocab_chunks", vocab_chunks,
    ])


@pytest.mark.parametrize("vocab_chunks", ["0", "4"])
def test_run_sft_cli_seq_parallel_smoke(vocab_chunks):
    """sp-only CLI: dense and chunked-vocab seq heads both run."""
    from distributed_lion_tpu.cli.run_sft import main

    main([
        "--model_name", "tiny", "--dataset", "synthetic", "--lion",
        "--async_grad", "--max_steps", "2", "--per_device_train_batch_size",
        "1", "--gradient_accumulation_steps", "1", "--seq_length", "64",
        "--num_train_samples", "32", "--size_valid_set", "8",
        "--logging_steps", "10", "--eval_steps", "1000", "--save_steps",
        "1000", "--seq_parallel", "4", "--vocab_chunks", vocab_chunks,
    ])


def _dpo_batches(steps, gb, T, vocab, seed=0):
    """Random chosen/rejected pairs with realistic prompt/padding masks that
    CROSS shard boundaries (prompt lengths straddle T/sp multiples)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        b = {}
        for side in ("chosen", "rejected"):
            toks = rng.integers(0, vocab, size=(gb, T)).astype(np.int32)
            mask = np.zeros((gb, T), np.float32)
            for r in range(gb):
                start = int(rng.integers(3, T // 2))     # prompt end
                stop = int(rng.integers(T // 2 + 1, T))  # padding start
                mask[r, start:stop] = 1.0
            b[side] = toks
            b[f"{side}_mask"] = mask
        out.append(b)
    return out


def _train_dpo(mesh, sp, steps=6):
    from distributed_lion_tpu.train.dpo import make_dpo_loss_fn

    model_cfg, base, lcfg, adapters = _sft_pieces()
    from distributed_lion_tpu.models.lora import lora_apply_fn

    seq_axis = SEQ_AXIS if sp > 1 else None
    pol = lora_apply_fn(
        lambda p, t: llama_apply(p, t, model_cfg, seq_axis=seq_axis),
        base, lcfg)
    loss_fn, loss_spec = make_dpo_loss_fn(
        policy_apply=pol,
        ref_apply=lambda t: llama_apply(base, t, model_cfg, seq_axis=seq_axis),
        beta=0.1, seq_axis=seq_axis,
    )
    cfg = _cfg(learning_rate=1e-3)
    trainer = Trainer(cfg, mesh, apply_fn=None, params=adapters,
                      loss_fn=loss_fn, loss_spec=loss_spec)
    model_cfg_vocab = model_cfg.vocab_size
    batches = _dpo_batches(steps, trainer.global_train_batch(), 64,
                           model_cfg_vocab)
    history = trainer.train(iter(batches), max_steps=steps)
    losses = [h["loss"] for h in history if "loss" in h]
    trainer.close()
    return losses


def test_dpo_sp_trajectory_matches_pure_dp():
    mesh_sp = make_mesh(data=2, seq=4, devices=jax.devices()[:8])
    mesh_dp = make_mesh(data=2, devices=jax.devices()[:2])
    losses_sp = _train_dpo(mesh_sp, sp=4)
    losses_dp = _train_dpo(mesh_dp, sp=1)
    assert len(losses_sp) == len(losses_dp) > 0
    np.testing.assert_allclose(losses_sp, losses_dp, rtol=2e-2, atol=2e-2)


def test_run_dpo_cli_seq_parallel_smoke():
    from distributed_lion_tpu.cli.run_dpo import main

    main([
        "--model_name", "tiny", "--dataset", "synthetic", "--lion",
        "--async_grad", "--max_steps", "2", "--per_device_train_batch_size",
        "1", "--gradient_accumulation_steps", "1", "--max_length", "64",
        "--num_train_samples", "32", "--size_valid_set", "4",
        "--logging_steps", "10", "--eval_steps", "1000", "--save_steps",
        "1000", "--seq_parallel", "4",
    ])


def test_run_sft_sp_guards():
    import pytest

    from distributed_lion_tpu.cli.run_sft import main

    common = [
        "--model_name", "tiny", "--dataset", "synthetic", "--lion",
        "--async_grad", "--max_steps", "1", "--seq_length", "64",
        "--seq_parallel", "4",
    ]
    with pytest.raises(NotImplementedError, match="packing"):
        main(common + ["--packing", "false"])
    with pytest.raises(ValueError, match="divide evenly"):
        # 62 stays under tiny's n_ctx (no clamp) and 62 % 4 != 0
        main([a if a != "64" else "62" for a in common])
