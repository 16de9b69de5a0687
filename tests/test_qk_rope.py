"""q's and k's head norm and rotation in one kernel a direction
(``ops/pallas_qk_rope``; PR 45), interpret mode on the CPU: output, ``dx`` and
``dscale`` against autodiff of the plain expression the model keeps for every
other backend and shape (``models/mellum.head_norm_rope``:
``apply_rope_half(_rms_norm(...))`` over ``[B T, n, 1, 128]``), the rule that
says which shapes it takes, and ``models/mellum._attention`` with the kernel
in place against the plain path."""

import jax
import jax.numpy as jnp
import pytest

from distributed_lion_tpu.models import mellum
from distributed_lion_tpu.models.laguna import Rope
from distributed_lion_tpu.ops import pallas_flash_attn, pallas_qk_rope

EPS = 1e-6
PLAIN = Rope(5e5, 128)
YARN = Rope(5e5, 128, 16.0, 64, 32.0, 1.0, 1.2772588722239782)


def plain(y, scale, cos, sin):
    return mellum.head_norm_rope(y, scale, cos, sin, EPS)


def with_gradients(fn, w):
    """``(output, dx, dscale)`` of ``fn`` under the cotangent ``w``."""
    def run(y, scale):
        out, back = jax.vjp(fn, y, scale)
        return (out,) + back(w.astype(out.dtype))
    return jax.jit(run)


@pytest.mark.parametrize("n,T,rope,dtype", [
    (32, 128, PLAIN, "float32"),     # q's heads, one row tile a sequence
    (4, 384, YARN, "float32"),       # k's heads, three tiles of 128
    (4, 1024, PLAIN, "float32"),     # two tiles of 512
    (32, 256, YARN, "bfloat16"),     # one tile of 256
    (4, 384, PLAIN, "bfloat16"),
    (2, 128, YARN, "bfloat16"),      # fewer heads than a grid step takes
], ids=lambda v: v if isinstance(v, str) else
    ("yarn" if v is YARN else "plain") if isinstance(v, Rope) else str(v))
def test_the_kernels_are_the_plain_norm_and_rotation(n, T, rope, dtype):
    """B = 2: row T of the flat array is position 0 again. float32 agrees
    with autodiff of the plain expression to rounding; bfloat16 inputs are
    held to the float32 expression over the same values within ONE rounding
    of the result (2^-8 of the entry; the plain bfloat16 path rounds the
    norm, cos, sin and the rotation), ``dscale`` (float32, summed in the
    kernel) to float32's."""
    B = 2
    ks = jax.random.split(jax.random.key(n + T), 3)
    y = (3 * jax.random.normal(ks[0], (B, T, n * 128))).astype(dtype)
    scale = 1 + 0.1 * jax.random.normal(ks[1], (128,))
    w = jax.random.normal(ks[2], (B, T, n * 128)).astype(dtype)
    cos, sin = rope.angles(jnp.arange(T))
    if rope is YARN:
        assert float(cos[0, 0]) == pytest.approx(1.2772588722239782)
    assert pallas_qk_rope.qk_rope_takes(T, 128, rope.rotary_dim, dtype)
    got = with_gradients(lambda y, s: pallas_qk_rope.qk_norm_rope(
        y, s, cos, sin, EPS, True), w)(y, scale)
    want = with_gradients(lambda y, s: plain(y, s, cos, sin),
                          w.astype(jnp.float32))(y.astype(jnp.float32),
                                                 scale)
    assert got[0].dtype == got[1].dtype == y.dtype
    assert got[2].dtype == scale.dtype and got[2].shape == (128,)
    for name, a, b in zip(("out", "dx", "dscale"), got, want):
        a = a.astype(jnp.float32)
        one_rounding = 2.0 ** -8 if (dtype == "bfloat16"
                                     and name != "dscale") else 0.0
        slack = 2e-5 * float(jnp.abs(b).max())
        assert bool((jnp.abs(a - b) <= one_rounding * jnp.abs(b) + slack
                     ).all()), (name, float(jnp.abs(a - b).max()))


def test_the_rule_says_which_shapes_the_kernels_take():
    takes = pallas_qk_rope.qk_rope_takes
    assert takes(8192, 128, 128, jnp.bfloat16)
    assert takes(640, 128, 128, jnp.float32)      # five tiles of 128
    assert not takes(8192, 64, 64, jnp.bfloat16)     # heads of 128 only
    assert not takes(8192, 128, 64, jnp.bfloat16)    # Laguna's half-rotary
    assert not takes(100, 128, 128, jnp.bfloat16)    # whole row tiles
    assert not takes(8192, 128, 128, jnp.float16)
    assert [pallas_qk_rope.rows_for(T) for T in (8192, 768, 384, 100)] \
        == [512, 256, 128, 0]


@pytest.mark.parametrize("windowed", [True, False], ids=["window", "full"])
def test_mellum_attention_with_the_kernel_is_the_plain_path(monkeypatch,
                                                            windowed):
    """``models/mellum._attention`` as a TPU runs it (the backend's name
    said "tpu", the norm-and-rotation kernel in interpret mode, the attention
    kernels told off so both sides go through ``banded_causal_attention``)
    against the path the CPU takes, output and every gradient, at TINY-like
    widths with heads of 128 in float32; the window layer rotates by the
    plain table, the full layer by YaRN's."""
    cfg = mellum.MellumConfig.tiny(
        n_layer=1, d_model=64, n_head=4, n_kv_head=2, head_dim=128,
        window=64, rope_window=PLAIN, rope_full=YARN,
        compute_dtype=jnp.float32)
    B, T = 2, 256
    p = mellum.mellum_init(jax.random.key(0), cfg)["blocks"][0]["attn"]
    p = dict(p, q_norm={"scale": 1 + 0.1 * jax.random.normal(
        jax.random.key(1), (128,))}, k_norm={"scale": 1 - 0.1 * jax.random
                                             .normal(jax.random.key(2),
                                                     (128,))})
    x = jax.random.normal(jax.random.key(3), (B, T, 64))
    w = jax.random.normal(jax.random.key(4), (B, T, 64))

    def run():
        return jax.jit(jax.value_and_grad(lambda x, p: (
            mellum._attention(x, p, cfg, windowed) * w).sum(), (0, 1)))(x, p)

    want = run()
    calls = []
    real = pallas_qk_rope.qk_norm_rope

    def kernel(y, scale, cos, sin, eps):
        calls.append(y.shape)
        return real(y, scale, cos, sin, eps, True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_qk_rope, "qk_norm_rope", kernel)
    for rule in ("gqa_train_kernel_takes", "gqa_kernel_takes"):
        monkeypatch.setattr(pallas_flash_attn, rule, lambda *a, **k: False)
    got = run()
    assert calls == [(B, T, 4 * 128), (B, T, 2 * 128)]
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(jnp.abs(b).max())
