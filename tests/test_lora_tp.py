"""TP for the LoRA/frozen-base path (VERDICT r1 items 6 + 8).

Pins: the factored LoRA forward (x@W + s·(x@A)@B, never forming W+ΔW)
equals the merged forward; SFT training with --tensor_parallel 2 matches
pure data parallelism; adapter replicas stay consistent across tensor
ranks (the copy_to_tp_region gradient boundary); 7B-width shapes train.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_init
from distributed_lion_tpu.models.lora import (
    LoraConfig,
    apply_adapters,
    lora_adapter_specs,
    lora_apply_fn,
    lora_init,
    merge_lora,
)
from distributed_lion_tpu.models.loss import clm_loss_and_metrics
from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS, make_mesh
from distributed_lion_tpu.parallel.tensor_parallel import llama_param_specs, validate_tp
from distributed_lion_tpu.train.loop import TrainConfig, Trainer

MODEL = LlamaConfig.tiny(compute_dtype=jnp.float32)
LORA = LoraConfig(r=4, alpha=8)


def test_factored_matches_merged():
    """The LoraTensor factored forward == merging W+ΔW densely."""
    base = llama_init(jax.random.key(0), MODEL)
    adapters = lora_init(jax.random.key(1), base, LORA)
    # break the B=0 identity so the delta actually contributes
    adapters = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(jax.random.key(2), x.shape, x.dtype),
        adapters,
    )
    tokens = np.random.default_rng(0).integers(0, MODEL.vocab_size,
                                               size=(2, 16)).astype(np.int32)
    # each as ONE compiled program (ISSUE 35), not op by op
    factored = jax.jit(lora_apply_fn(
        lambda p, t: llama_apply(p, t, MODEL), base, LORA))(adapters, tokens)
    merged = jax.jit(llama_apply, static_argnums=2)(
        merge_lora(base, adapters, LORA), tokens, MODEL)
    np.testing.assert_allclose(np.asarray(factored), np.asarray(merged),
                               rtol=2e-4, atol=2e-4)


def _cfg(**kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
        max_steps=5, per_device_train_batch_size=2,
        gradient_accumulation_steps=1, block_size=32, logging_steps=1,
        output_dir=None, seed=7,
    )
    base.update(kw)
    return TrainConfig(**base)


def _sft_trainer(mesh, cfg, tp: int):
    """Mirror cli/run_sft's wiring for tp>1 vs the closure path."""
    base = llama_init(jax.random.key(0), MODEL)
    adapters = lora_init(jax.random.key(1), base, LORA)
    if tp > 1:
        validate_tp(MODEL, tp, "llama")
        base_specs = llama_param_specs(MODEL)
        adapter_specs = lora_adapter_specs(adapters, base_specs, TENSOR_AXIS)

        def loss_fn(params, frozen, batch, dropout_key):
            eff = apply_adapters(frozen, params, LORA, tp_axis=TENSOR_AXIS,
                                 base_specs=base_specs)
            logits = llama_apply(eff, batch, MODEL, tp_axis=TENSOR_AXIS)
            return clm_loss_and_metrics(logits, batch)

        return Trainer(cfg, mesh, apply_fn=None, params=adapters,
                       param_specs=adapter_specs, loss_fn=loss_fn,
                       frozen_params=base, frozen_specs=base_specs)
    apply = lora_apply_fn(lambda p, t: llama_apply(p, t, MODEL), base, LORA)
    return Trainer(cfg, mesh, lambda p, t, key: apply(p, t), adapters)


def _train(trainer, n_steps=5):
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset

    blocks = synthetic_lm_dataset(
        max(64, trainer.global_train_batch() * 2), trainer.cfg.block_size,
        MODEL.vocab_size, seed=11)
    hist = trainer.train(
        batch_iterator(blocks, trainer.global_train_batch(), seed=0),
        max_steps=n_steps)
    adapters = jax.tree.map(np.asarray, jax.device_get(trainer.params))
    trainer.close()
    return [h["loss"] for h in hist if "loss" in h], adapters


def test_sft_tp_matches_dp():
    """dp=4 x tp=2 SFT ≡ dp=4 SFT: same losses, same adapters (f32)."""
    losses_dp, ad_dp = _train(
        _sft_trainer(make_mesh(data=4, devices=jax.devices()[:4]), _cfg(), 1))
    losses_tp, ad_tp = _train(
        _sft_trainer(make_mesh(data=4, tensor=2), _cfg(tensor_parallel=2), 2))
    np.testing.assert_allclose(losses_tp, losses_dp, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(ad_dp), jax.tree.leaves(ad_tp)):
        # ballot-flip envelope on zero-gradient coords (see pipeline test)
        assert np.abs(a - b).max() <= 2 * 1e-3 * 5 + 1e-6


def test_sft_tp_matches_dp_nf4_base():
    """dp=4 x tp=2 SFT with an NF4-quantized frozen base ≡ dp=4 with the
    SAME quantized base (the reference's flagship at scale: multi-chip
    QLoRA, sft_llama2.py:141-153). The shaped QuantizedTensor layout lets
    the dense PartitionSpecs shard codes/absmax; each rank dequantizes only
    its shard."""
    from distributed_lion_tpu.ops.quant import quantize_tree, validate_quant_tp

    base = llama_init(jax.random.key(0), MODEL)
    # block=16 so tiny-model projections (last dim 64/128) shard 2-way
    qbase = quantize_tree(base, "nf4", min_size=1024, block=16)

    apply = lora_apply_fn(lambda p, t: llama_apply(p, t, MODEL), qbase, LORA)
    tr_dp = Trainer(_cfg(), make_mesh(data=4, devices=jax.devices()[:4]),
                    lambda p, t, key: apply(p, t),
                    lora_init(jax.random.key(1), base, LORA))
    losses_dp, ad_dp = _train(tr_dp)

    base_specs = llama_param_specs(MODEL)
    validate_quant_tp(qbase, base_specs, 2, TENSOR_AXIS)
    adapters = lora_init(jax.random.key(1), base, LORA)
    adapter_specs = lora_adapter_specs(adapters, base_specs, TENSOR_AXIS)

    def loss_fn(params, frozen, batch, dropout_key):
        eff = apply_adapters(frozen, params, LORA, tp_axis=TENSOR_AXIS,
                             base_specs=base_specs)
        logits = llama_apply(eff, batch, MODEL, tp_axis=TENSOR_AXIS)
        return clm_loss_and_metrics(logits, batch)

    tr_tp = Trainer(_cfg(tensor_parallel=2), make_mesh(data=4, tensor=2),
                    apply_fn=None, params=adapters,
                    param_specs=adapter_specs, loss_fn=loss_fn,
                    frozen_params=qbase, frozen_specs=base_specs)
    losses_tp, ad_tp = _train(tr_tp)
    np.testing.assert_allclose(losses_tp, losses_dp, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(ad_dp), jax.tree.leaves(ad_tp)):
        assert np.abs(a - b).max() <= 2 * 1e-3 * 5 + 1e-6


def test_quant_tp_misaligned_block_rejected():
    """validate_quant_tp names the offending leaf when block alignment
    can't shard (e.g. default nf4 block 64 == the whole last dim here)."""
    import pytest

    from distributed_lion_tpu.ops.quant import quantize_tree, validate_quant_tp

    base = llama_init(jax.random.key(0), MODEL)
    qbase = quantize_tree(base, "nf4", min_size=1024)  # block 64 → 1 block/row
    with pytest.raises(ValueError, match="quant"):
        validate_quant_tp(qbase, llama_param_specs(MODEL), 2, TENSOR_AXIS)


def test_sft_tp_adapter_replicas_consistent():
    """The copy_to_tp_region boundary's job: after training, every
    REPLICATED adapter factor (A for the col-parallel wq/wv targets) must be
    bit-identical on all devices — without the boundary psum, per-rank A
    gradients/momenta diverge across the tensor axis and this fails."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset

    trainer = _sft_trainer(make_mesh(data=4, tensor=2),
                           _cfg(tensor_parallel=2, max_steps=3), 2)
    blocks = synthetic_lm_dataset(
        max(64, trainer.global_train_batch() * 2), trainer.cfg.block_size,
        MODEL.vocab_size, seed=11)
    hist = trainer.train(
        batch_iterator(blocks, trainer.global_train_batch(), seed=0),
        max_steps=3)
    assert all(np.isfinite(h["loss"]) for h in hist if "loss" in h)
    checked = 0
    for path, ab in trainer.params.items():
        a = ab["A"]
        if len(a.addressable_shards) > 1 and a.addressable_shards[0].data.shape == a.shape:
            shards = [np.asarray(s.data) for s in a.addressable_shards]
            for s in shards[1:]:
                np.testing.assert_array_equal(shards[0], s, err_msg=path)
            checked += 1
    assert checked > 0  # at least one replicated A factor was compared
    trainer.close()


def test_dpo_tp_trains():
    """DPO with tensor parallelism: policy + frozen ref both sharded."""
    from distributed_lion_tpu.models.lora import apply_adapters as apply_ad
    from distributed_lion_tpu.train.dpo import make_dpo_loss_fn_frozen

    mesh = make_mesh(data=4, tensor=2)
    base = llama_init(jax.random.key(0), MODEL)
    lora_cfg = LoraConfig(r=4, alpha=8, target_patterns=("wq", "wk", "wv", "wo"))
    adapters = lora_init(jax.random.key(1), base, lora_cfg)
    base_specs = llama_param_specs(MODEL)
    adapter_specs = lora_adapter_specs(adapters, base_specs, TENSOR_AXIS)

    def policy_apply(params, frozen, tokens):
        eff = apply_ad(frozen["base"], params, lora_cfg, tp_axis=TENSOR_AXIS,
                       base_specs=base_specs)
        return llama_apply(eff, tokens, MODEL, tp_axis=TENSOR_AXIS)

    loss_fn = make_dpo_loss_fn_frozen(
        policy_apply=policy_apply,
        ref_apply=lambda frozen, t: llama_apply(frozen["ref"], t, MODEL,
                                                tp_axis=TENSOR_AXIS),
    )
    cfg = _cfg(tensor_parallel=2, max_steps=3)
    trainer = Trainer(cfg, mesh, apply_fn=None, params=adapters,
                      loss_fn=loss_fn, param_specs=adapter_specs,
                      frozen_params={"base": base, "ref": base},
                      frozen_specs={"base": base_specs, "ref": base_specs})
    rng = np.random.default_rng(0)
    gb = trainer.global_train_batch()

    def batches():
        while True:
            tok = rng.integers(0, MODEL.vocab_size, size=(gb, 32)).astype(np.int32)
            mask = np.ones((gb, 32), np.float32)
            yield {"chosen": tok, "rejected": tok[::-1].copy(),
                   "chosen_mask": mask, "rejected_mask": mask}

    hist = trainer.train(batches(), max_steps=3)
    assert all(np.isfinite(h["loss"]) for h in hist if "loss" in h)
    trainer.close()


@pytest.mark.slow   # 131 core-seconds over 7B-wide constants (ROADMAP D9 c)
def test_lora_7b_widths_smoke():
    """Factored LoRA at Llama-2-7B widths (d=4096, d_ff=11008, vocab 32000;
    depth scaled to 2 layers): one SFT train step runs and is finite. Pins
    that the factored path never materializes W+dW at 7B-width shapes (the
    merged form would build a second full weight set inside the step)."""
    model = LlamaConfig.llama2_7b(n_layer=2, n_ctx=128,
                                  param_dtype=jnp.bfloat16)
    base = llama_init(jax.random.key(0), model)
    lora_cfg = LoraConfig(r=8, alpha=16)
    adapters = lora_init(jax.random.key(1), base, lora_cfg)
    apply = lora_apply_fn(lambda p, t: llama_apply(p, t, model), base, lora_cfg)
    mesh = make_mesh(data=1, devices=jax.devices()[:1])
    cfg = _cfg(per_device_train_batch_size=1, block_size=128, max_steps=1)
    trainer = Trainer(cfg, mesh, lambda p, t, key: apply(p, t), adapters)
    tokens = np.random.default_rng(0).integers(
        0, model.vocab_size, size=(1, 128)).astype(np.int32)

    def batches():
        while True:
            yield tokens

    hist = trainer.train(batches(), max_steps=1)
    assert np.isfinite(hist[-1]["loss"])
    trainer.close()


def test_gpt2_lora_decode():
    """GPT-2 generation consumes LoraTensor-adapted params (factored qkv and
    proj dispatch in the decode path)."""
    from distributed_lion_tpu.models.gpt2 import (
        GPT2Config, gpt2_apply, gpt2_decode, gpt2_init, gpt2_init_cache,
    )

    model = GPT2Config.tiny(compute_dtype=jnp.float32)
    base = gpt2_init(jax.random.key(0), model)
    cfg = LoraConfig(r=4, alpha=8, target_patterns=("qkv", "proj", "fc"))
    adapters = lora_init(jax.random.key(1), base, cfg)
    adapters = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(jax.random.key(2), x.shape, x.dtype),
        adapters)
    eff = apply_adapters(base, adapters, cfg)
    tokens = np.random.default_rng(0).integers(0, model.vocab_size,
                                               size=(2, 8)).astype(np.int32)
    # each as ONE compiled program (ISSUE 35), not op by op
    full = jax.jit(gpt2_apply, static_argnums=2)(eff, tokens, model)
    dec, _ = jax.jit(gpt2_decode, static_argnums=2)(
        eff, tokens, model, gpt2_init_cache(model, 2, 8), 0)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
