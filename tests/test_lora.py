"""LoRA tests: init identity, merge == wrapped apply, quantized base,
gradients flow only to adapters."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_init
from distributed_lion_tpu.models.lora import (
    LoraConfig,
    lora_apply_fn,
    lora_init,
    merge_lora,
)
from distributed_lion_tpu.ops.quant import quantize_tree


# Forward passes run COMPILED, one program a shape (ISSUE 35): eagerly each is
# a few hundred one-op programs.
_apply = jax.jit(llama_apply, static_argnums=2)


def _wrapped(cfg, base, lcfg):
    return jax.jit(lora_apply_fn(lambda p, t: llama_apply(p, t, cfg), base,
                                 lcfg))


def _setup(quant=None):
    cfg = LlamaConfig.tiny()
    base = llama_init(jax.random.key(0), cfg)
    if quant:
        base = quantize_tree(base, quant, min_size=1024)
    lcfg = LoraConfig(r=4, alpha=8)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    return cfg, base, lcfg, adapters


def test_adapters_target_q_and_v():
    cfg, base, lcfg, adapters = _setup()
    keys = set(adapters)
    assert all(k.endswith("wq") or k.endswith("wv") for k in keys)
    assert len(keys) == 2 * cfg.n_layer
    a = adapters["blocks/0/attn/wq"]
    assert a["A"].shape == (64, 4) and a["B"].shape == (4, 64)


def test_fresh_adapters_are_identity():
    cfg, base, lcfg, adapters = _setup()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, 8)), jnp.int32)
    wrapped = _wrapped(cfg, base, lcfg)
    np.testing.assert_allclose(
        np.asarray(wrapped(adapters, toks)),
        np.asarray(_apply(base, toks, cfg)),
        rtol=1e-5, atol=1e-5,
    )


def test_merge_matches_wrapped_apply():
    cfg, base, lcfg, adapters = _setup()
    # give the adapters nonzero B so the delta is real
    adapters = jax.tree.map(lambda x: x + 0.01, adapters)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 8)), jnp.int32)
    wrapped = _wrapped(cfg, base, lcfg)
    merged = merge_lora(base, adapters, lcfg)
    np.testing.assert_allclose(
        np.asarray(wrapped(adapters, toks)),
        np.asarray(_apply(merged, toks, cfg)),
        rtol=2e-2, atol=2e-2,  # bf16 compute tolerance
    )


def test_dpo_target_set_covers_mlp_and_embedding():
    """The reference's DPO adapts q/v/k/out + fc_in/fc_out + wte
    (dpo_llama2.py:192-207); our DPO_TARGET_PATTERNS must land on all four
    attention projections, the full SwiGLU MLP, and the token embedding."""
    from distributed_lion_tpu.models.lora import DPO_TARGET_PATTERNS

    cfg = LlamaConfig.tiny()
    base = llama_init(jax.random.key(0), cfg)
    lcfg = LoraConfig(r=4, alpha=8, target_patterns=DPO_TARGET_PATTERNS)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    assert "wte" in adapters
    assert adapters["wte"]["A"].shape == (cfg.vocab_size, 4)
    assert adapters["wte"]["B"].shape == (4, cfg.d_model)
    per_block = {k.split("/")[-1] for k in adapters if k.startswith("blocks/0/")}
    assert per_block == {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}


def test_embedding_adapter_factored_matches_merged():
    """Gather-side LoRA (lora_embed): the factored wte adapter equals
    merging A@B into the embedding table."""
    from distributed_lion_tpu.models.lora import DPO_TARGET_PATTERNS

    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    base = llama_init(jax.random.key(0), cfg)
    lcfg = LoraConfig(r=4, alpha=8, target_patterns=DPO_TARGET_PATTERNS)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    adapters = jax.tree.map(lambda x: x + 0.01, adapters)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 8)), jnp.int32)
    wrapped = _wrapped(cfg, base, lcfg)
    merged = merge_lora(base, adapters, lcfg)
    np.testing.assert_allclose(
        np.asarray(wrapped(adapters, toks)),
        np.asarray(_apply(merged, toks, cfg)),
        rtol=2e-4, atol=2e-4,
    )


def test_embedding_adapter_gets_gradient():
    from distributed_lion_tpu.models.lora import DPO_TARGET_PATTERNS

    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    base = llama_init(jax.random.key(0), cfg)
    lcfg = LoraConfig(r=4, alpha=8, target_patterns=DPO_TARGET_PATTERNS)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 8)), jnp.int32)
    wrapped = _wrapped(cfg, base, lcfg)
    g = jax.jit(jax.grad(
        lambda ad: wrapped(ad, toks).astype(jnp.float32).mean()))(adapters)
    # B=0 at init ⇒ signal arrives through wte's B via the gathered A rows
    assert np.abs(np.asarray(g["wte"]["B"])).sum() > 0


def test_adapter_dropout_train_vs_eval():
    """cfg.dropout armed by a dropout key (train) perturbs the adapter
    branch; without a key (eval) the forward is deterministic and matches
    dropout=0. PEFT semantics: base path never dropped (sft_llama2.py:48)."""
    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    base = llama_init(jax.random.key(0), cfg)
    lcfg = LoraConfig(r=4, alpha=8, dropout=0.5)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    adapters = jax.tree.map(lambda x: x + 0.05, adapters)  # nonzero branch
    toks = jnp.asarray(np.random.default_rng(5).integers(0, 256, (1, 16)), jnp.int32)
    wrapped = _wrapped(cfg, base, lcfg)
    eval_out = wrapped(adapters, toks)
    nodrop = _wrapped(cfg, base, LoraConfig(r=4, alpha=8, dropout=0.0))(
        adapters, toks)
    np.testing.assert_allclose(np.asarray(eval_out), np.asarray(nodrop),
                               rtol=1e-6, atol=1e-6)
    t1 = wrapped(adapters, toks, dropout_key=jax.random.key(2))
    t2 = wrapped(adapters, toks, dropout_key=jax.random.key(3))
    assert np.abs(np.asarray(t1) - np.asarray(eval_out)).max() > 1e-5
    assert np.abs(np.asarray(t1) - np.asarray(t2)).max() > 1e-5
    # same key ⇒ bit-identical (replica consistency across the vote world)
    t1b = wrapped(adapters, toks, dropout_key=jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t1b))


def test_embedding_adapter_peft_roundtrip(tmp_path):
    """wte adapter survives lora_to_peft → peft_to_lora (the PEFT
    Embedding layout: lora_embedding_A [r, V], lora_embedding_B [d, r])."""
    from distributed_lion_tpu.models.hf_export import lora_to_peft
    from distributed_lion_tpu.models.hf_import import peft_to_lora
    from distributed_lion_tpu.models.lora import DPO_TARGET_PATTERNS

    cfg = LlamaConfig.tiny()
    base = llama_init(jax.random.key(0), cfg)
    lcfg = LoraConfig(r=4, alpha=8, target_patterns=DPO_TARGET_PATTERNS)
    adapters = lora_init(jax.random.key(1), base, lcfg)
    adapters = jax.tree.map(lambda x: x + 0.01, adapters)
    lora_to_peft(adapters, cfg, lcfg, str(tmp_path))
    back, back_cfg = peft_to_lora(str(tmp_path), cfg)
    assert set(back) == set(adapters)
    np.testing.assert_allclose(np.asarray(back["wte"]["A"]),
                               np.asarray(adapters["wte"]["A"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(back["wte"]["B"]),
                               np.asarray(adapters["wte"]["B"]), rtol=1e-6)


def test_quantized_base_trains_only_adapters():
    cfg, base, lcfg, adapters = _setup(quant="int8")
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 8)), jnp.int32)
    wrapped = _wrapped(cfg, base, lcfg)

    def loss(ad):
        return wrapped(ad, toks).astype(jnp.float32).mean()

    g = jax.jit(jax.grad(loss))(adapters)
    # gradient exists for every adapter leaf and matches its shape
    for k, ab in g.items():
        assert ab["A"].shape == adapters[k]["A"].shape
    # at init B=0 ⇒ grad(A)=0 exactly; the signal arrives through B
    assert np.abs(np.asarray(g["blocks/0/attn/wq"]["B"])).sum() > 0
