"""The names ``impl`` takes and refuses, `auto` against ``xla`` through the
two model families on the CPU, and the guard that what the attention
decision lost stays lost (the tile tuner, its cache, its config fields and
the bench.py loop that fed them: PR 28). Which kernel `auto` takes from
which shape is pinned in tests/test_flash_attn_kernel.py.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.ops.attention import attention, attention_qkv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "distributed_lion_tpu")


def _qkv(B=2, H=4, T=64, hd=64, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k1, (B, H, T, hd), jnp.float32),
            jax.random.normal(k2, (B, H, T, hd), jnp.float32),
            jax.random.normal(k3, (B, H, T, hd), jnp.float32))


def _fused(q, k, v):
    """[B, H, T, hd] x 3 -> the projection's [B, T, 3, D]."""
    B, H, T, hd = q.shape
    return jnp.stack([x.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
                      for x in (q, k, v)], axis=2)


@pytest.mark.parametrize("impl", ["warp", "flash", "splash", "xla_bf16"])
def test_dispatch_names(impl):
    """``xla`` and ``auto`` are the names; every other one is refused, on
    both entries, the library kernels' former names like any typo."""
    q, k, v = _qkv()
    attention(q, k, v, impl="xla")
    attention(q, k, v, impl="auto")
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl=impl)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention_qkv(_fused(q, k, v), 4, impl=impl)


def test_entries_take_no_tile_argument():
    q, k, v = _qkv()
    for tile in ("block_q", "block_kv", "block_q_bwd", "block_kv_bwd"):
        with pytest.raises(TypeError, match=tile):
            attention(q, k, v, **{tile: 128})
        with pytest.raises(TypeError, match=tile):
            attention_qkv(_fused(q, k, v), 4, **{tile: 128})


def _loss_and_grads(family: str, attn_impl: str):
    if family == "gpt2":
        from distributed_lion_tpu.models.gpt2 import (
            GPT2Config,
            gpt2_apply,
            gpt2_init,
        )

        cfg = GPT2Config.tiny(attn_impl=attn_impl)
        params, apply = gpt2_init(jax.random.key(0), cfg), gpt2_apply
    else:
        from distributed_lion_tpu.models.llama import (
            LlamaConfig,
            llama_apply,
            llama_init,
        )

        cfg = LlamaConfig.tiny(attn_impl=attn_impl)
        params, apply = llama_init(jax.random.key(0), cfg), llama_apply
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0,
                                cfg.vocab_size)

    def loss(params):
        logits = apply(params, tokens[:, :-1], cfg)
        picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked[..., 0])

    return jax.jit(jax.value_and_grad(loss))(params)   # one program each


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_auto_is_xla_bit_for_bit_on_the_cpu(family):
    """Off a TPU `auto` is the reference itself: a tiny model's loss and
    every parameter gradient, through the token-major entry (GPT-2) and
    the head-major one (Llama)."""
    want, g_want = _loss_and_grads(family, "xla")
    got, g_got = _loss_and_grads(family, "auto")
    np.testing.assert_array_equal(got, want)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------- the guard
def test_gpt2_config_has_no_tile_field():
    from distributed_lion_tpu.models.gpt2 import GPT2Config

    names = [f.name for f in dataclasses.fields(GPT2Config)]
    assert [n for n in names if n.startswith("flash_block_")] == []
    assert "attn_impl" in names


def test_train_config_has_no_row_block():
    from distributed_lion_tpu.train.loop import TrainConfig

    assert "row_block" not in [f.name for f in dataclasses.fields(TrainConfig)]


def test_package_has_no_tuner():
    """No module and no line of the package names the tuner or its cache."""
    gone = ("autotune", "DLT_TUNE_CACHE", "run_tune", "tuning_cache")
    hits = []
    for root, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if any(g in name for g in gone):
                hits.append(path)
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    if any(g in line for g in gone):
                        hits.append(f"{path}:{n}")
    assert hits == []


@pytest.mark.parametrize("path", ["bench.py", "scripts/bench_sweep.py",
                                  "scripts/tuning_cache.json",
                                  "scripts/last_tpu_measurement.json"])
def test_the_sweep_promote_bench_loop_is_gone(path):
    assert not os.path.exists(os.path.join(REPO, path))
