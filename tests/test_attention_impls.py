"""Attention implementation parity: xla / xla_bf16 / flash / splash dispatch.

The XLA materialized-scores path is the semantic reference; the Pallas
kernels (flash, splash) must match it numerically — forward AND backward —
since those impls are pure perf knobs. The one exception is ``xla_bf16``,
which INTENTIONALLY trades ~bf16-rounding error on the stored scores for
HBM bandwidth (its test below bounds the divergence rather than demanding
parity). Kernels run in interpret mode here (no TPU in CI).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from distributed_lion_tpu.ops.attention import (
    attention,
    attention_splash,
    attention_xla,
)


def _qkv(B=2, H=4, T=128, hd=64, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k1, (B, H, T, hd), jnp.float32),
            jax.random.normal(k2, (B, H, T, hd), jnp.float32),
            jax.random.normal(k3, (B, H, T, hd), jnp.float32))


def test_splash_forward_matches_xla():
    q, k, v = _qkv()
    ref = attention_xla(q, k, v)
    got = attention_splash(q, k, v, interpret=True)
    assert float(jnp.abs(ref - got).max()) < 2e-3


def test_splash_backward_matches_xla():
    q, k, v = _qkv(seed=1)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(loss(attention_xla), argnums=(0, 1, 2))(q, k, v)
    g_spl = jax.grad(
        loss(lambda q, k, v: attention_splash(q, k, v, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_spl):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9))
        assert rel < 5e-3, rel


def test_splash_block_size_override():
    q, k, v = _qkv(T=256, seed=2)
    ref = attention_xla(q, k, v)
    got = attention_splash(q, k, v, interpret=True, block_q=128, block_kv=128)
    assert float(jnp.abs(ref - got).max()) < 2e-3


def test_dispatch_names():
    q, k, v = _qkv(T=64)
    # xla always available; unknown impl refused
    attention(q, k, v, impl="xla")
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl="warp")


def test_xla_bf16_close_to_xla():
    """xla_bf16 stores bf16 scores (throughput opt-in) — forward must stay
    within bf16 rounding of the f32-scores path, gradients finite and
    close in relative terms."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seed=3))
    ref = attention(q, k, v, impl="xla").astype(jnp.float32)
    got = attention(q, k, v, impl="xla_bf16").astype(jnp.float32)
    assert float(jnp.abs(ref - got).max()) < 5e-2

    def loss(impl):
        return lambda q, k, v: (attention(q, k, v, impl=impl)
                                .astype(jnp.float32) ** 2).sum()

    g_ref = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss("xla_bf16"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_got):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(b)))
        rel = float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9))
        assert rel < 5e-2, rel


def test_parse_attn_spec_grammar():
    """impl[@BQxBKV[@BQBxBKVB]] — fwd-only, fwd+bwd, and bare forms."""
    from distributed_lion_tpu.ops.attention import parse_attn_spec

    assert parse_attn_spec("xla") == ("xla", 0, 0, 0, 0)
    assert parse_attn_spec("flash@512x1024") == ("flash", 512, 1024, 0, 0)
    assert parse_attn_spec("flash@512x1024@256x512") == \
        ("flash", 512, 1024, 256, 512)
    assert parse_attn_spec("splash@128x256") == ("splash", 128, 256, 0, 0)


def test_bwd_tiles_refused_off_flash():
    from distributed_lion_tpu.ops.attention import attention

    q = k = v = jnp.zeros((1, 2, 8, 4), jnp.float32)
    with pytest.raises(ValueError, match="flash-kernel knob"):
        attention(q, k, v, impl="splash", block_q_bwd=64)
    with pytest.raises(ValueError, match="flash-kernel knob"):
        attention(q, k, v, impl="xla", block_kv_bwd=128)

def test_auto_picks_tuned_flash_at_swept_flagship_shape(monkeypatch):
    """What `auto` resolves to at the swept flagship shape (T=1024,
    head_dim 64) since PR 27: the token-major entry, which GPT-2 calls,
    takes the repo's own kernel there (``ops/pallas_flash_attn``, no tiles
    to pin: tests/test_flash_attn_kernel.py pins that resolution shape by
    shape), and the hard-coded flash@512x1024 is gone from the head-major
    entry, which keeps xla below the library kernel's regime, honors
    caller-pinned tiles at any shape and takes default flash from T=2048.
    Backend + kernels are monkeypatched: this pins DISPATCH, the kernels'
    math is pinned by the equivalence tests."""
    from distributed_lion_tpu.ops import attention as A
    from distributed_lion_tpu.ops import pallas_flash_attn as F

    calls = []

    def fake_flash(q, k, v, *, causal=True, block_q=0, block_kv=0,
                   block_q_bwd=0, block_kv_bwd=0):
        calls.append((block_q, block_kv, block_q_bwd, block_kv_bwd))
        return q

    def fake_xla(q, k, v, *, causal=True, score_dtype=None):
        calls.append("xla")
        return q

    def fake_kernel(qkv, n_head, interpret=False):
        calls.append("kernel")
        return qkv[..., :qkv.shape[-1] // 3]

    monkeypatch.setattr(A, "attention_flash", fake_flash)
    monkeypatch.setattr(A, "attention_xla", fake_xla)
    monkeypatch.setattr(F, "flash_qkv", fake_kernel)
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")

    def fused(q, k, v):
        """[B, H, T, hd] x 3 -> the projection's [B, T, 3, D]."""
        B, H, T, hd = q.shape
        return jnp.stack([x.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
                          for x in (q, k, v)], axis=2)

    q, k, v = _qkv(T=1024)
    A.attention_qkv(fused(q, k, v), 4, impl="auto")
    assert calls[-1] == "kernel"  # the swept shape: the repo's kernel

    A.attention_qkv(fused(q, k, v), 4, impl="auto", block_q=512,
                    block_kv=1024)
    assert calls[-1] == (512, 1024, 0, 0)  # pinned tiles: library flash

    A.attention(q, k, v, impl="auto")
    assert calls[-1] == "xla"  # head-major at T=1024: no tuned-tile branch

    q, k, v = _qkv(T=1024, hd=128)
    A.attention(q, k, v, impl="auto")
    assert calls[-1] == "xla"  # Llama shapes keep the 7B bench leg's xla

    A.attention(q, k, v, impl="auto", block_q=256, block_kv=256)
    assert calls[-1] == (256, 256, 0, 0)  # pinned tiles honored via flash

    q, k, v = _qkv(T=512)
    A.attention(q, k, v, impl="auto")
    assert calls[-1] == "xla"  # below the kernels' regime
    A.attention_qkv(fused(q, k, v), 4, impl="auto")
    assert calls[-1] == "xla"  # ... from the token-major entry too

    A.attention(q, k, v, impl="auto", block_q=128, block_kv=128)
    assert calls[-1] == (128, 128, 0, 0)  # pinned tiles win at any shape

    q, k, v = _qkv(T=2048)
    A.attention(q, k, v, impl="auto")
    assert calls[-1] == (0, 0, 0, 0)  # long-context regime: default flash
    A.attention_qkv(fused(q, k, v), 4, impl="auto")
    assert calls[-1] == "kernel"  # ... the repo's kernel where qkv is fused

    monkeypatch.setattr(A.jax, "default_backend", lambda: "cpu")
    q, k, v = _qkv(T=1024)
    A.attention(q, k, v, impl="auto")
    assert calls[-1] == "xla"  # no TPU: never a pallas kernel
    A.attention_qkv(fused(q, k, v), 4, impl="auto")
    assert calls[-1] == "xla"


def test_auto_bwd_only_tiles_dispatch(monkeypatch):
    """ISSUE 3 satellite: `auto` with ONLY backward tiles pinned must
    dispatch to flash on TPU (honoring the tiles), and off TPU must degrade
    to xla with the flash-only knobs dropped — never fall into the
    explicit-impl flash-knob ValueError (that guard is for explicit
    xla/splash requests that would silently tune nothing)."""
    from distributed_lion_tpu.ops import attention as A

    calls = []

    def fake_flash(q, k, v, *, causal=True, block_q=0, block_kv=0,
                   block_q_bwd=0, block_kv_bwd=0):
        calls.append((block_q, block_kv, block_q_bwd, block_kv_bwd))
        return q

    def fake_xla(q, k, v, *, causal=True, score_dtype=None):
        calls.append("xla")
        return q

    monkeypatch.setattr(A, "attention_flash", fake_flash)
    monkeypatch.setattr(A, "attention_xla", fake_xla)

    q, k, v = _qkv(T=512)
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    A.attention(q, k, v, impl="auto", block_q_bwd=256, block_kv_bwd=512)
    assert calls[-1] == (0, 0, 256, 512)  # bwd-only pins reach flash intact

    monkeypatch.setattr(A.jax, "default_backend", lambda: "cpu")
    A.attention(q, k, v, impl="auto", block_q_bwd=256, block_kv_bwd=512)
    assert calls[-1] == "xla"  # degrades like bare auto, no ValueError

    # the explicit-impl guard stays loud
    with pytest.raises(ValueError, match="flash-kernel knob"):
        A.attention(q, k, v, impl="xla", block_q_bwd=256)
