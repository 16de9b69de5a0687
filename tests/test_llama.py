"""Llama model tests: shapes, causality, GQA, RoPE properties."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_lion_tpu.models.llama import (
    LlamaConfig,
    apply_rope,
    llama_apply,
    llama_init,
    rope_angles,
)


def test_forward_shapes():
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.key(0), cfg)
    logits = jax.jit(llama_apply, static_argnums=2)(
        params, jnp.zeros((2, 16), jnp.int32), cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_causality():
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.key(1), cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 10)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % cfg.vocab_size
    apply = jax.jit(llama_apply, static_argnums=2)
    l1 = apply(params, jnp.asarray(toks), cfg)
    l2 = apply(params, jnp.asarray(toks2), cfg)
    np.testing.assert_array_equal(np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]))


def test_gqa_head_counts():
    cfg = LlamaConfig.tiny()  # 4 heads, 2 kv heads
    params = llama_init(jax.random.key(0), cfg)
    attn = params["blocks"][0]["attn"]
    assert attn["wq"].shape == (64, 4 * 16)
    assert attn["wk"].shape == (64, 2 * 16)
    assert attn["wv"].shape == (64, 2 * 16)


def test_rope_preserves_norm_and_relativity():
    cos, sin = rope_angles(8, 16, 10000.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 2, 8, 16)), jnp.float32)
    rot = apply_rope(x, cos, sin)
    # rotation preserves per-pair norms
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rot), axis=-1),
        rtol=1e-5,
    )
    # position 0 is the identity rotation
    np.testing.assert_allclose(np.asarray(rot[:, :, 0]), np.asarray(x[:, :, 0]), rtol=1e-6)


def test_llama3_config():
    cfg = LlamaConfig.llama3_8b()
    assert cfg.n_kv_head == 8 and cfg.rope_theta == 500000.0
