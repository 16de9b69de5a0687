"""Ring + Ulysses attention: exactness vs single-device full attention,
and gradient flow through the ring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _sharded import run_sharded
from distributed_lion_tpu.ops.attention import attention_xla
from distributed_lion_tpu.parallel.mesh import SEQ_AXIS, make_mesh
from distributed_lion_tpu.parallel.ring_attention import ring_attention, ulysses_attention


def _qkv(B=2, H=4, T=64, hd=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, T, hd)).astype(np.float32))
    return mk(), mk(), mk()


def _seq_mesh(s=4):
    return make_mesh(data=1, tensor=1, seq=s, devices=jax.devices()[:s])


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_matches_full_attention(impl):
    mesh = _seq_mesh(4)
    q, k, v = _qkv()
    expected = attention_xla(q, k, v, causal=True)

    def f(q, k, v):
        return impl(q, k, v, SEQ_AXIS)

    out = run_sharded(
        f, mesh, (P(None, None, SEQ_AXIS),) * 3, P(None, None, SEQ_AXIS),
        q, k, v, check_vma=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5)


def test_ring_gradients_flow():
    mesh = _seq_mesh(4)
    q, k, v = _qkv(T=32)

    def loss_sharded(q, k, v):
        def f(q, k, v):
            return ring_attention(q, k, v, SEQ_AXIS)

        out = run_sharded(
            f, mesh, (P(None, None, SEQ_AXIS),) * 3, P(None, None, SEQ_AXIS),
            q, k, v, check_vma=False)
        return (out.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_xla(q, k, v, causal=True).astype(jnp.float32) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-4)


def test_ulysses_rejects_bad_head_count():
    mesh = _seq_mesh(4)
    q, k, v = _qkv(H=2)  # 2 heads < 4-way seq axis

    def f(q, k, v):
        return ulysses_attention(q, k, v, SEQ_AXIS)

    with pytest.raises(ValueError):
        run_sharded(
            f, mesh, (P(None, None, SEQ_AXIS),) * 3, P(None, None, SEQ_AXIS),
            q, k, v, check_vma=False)
