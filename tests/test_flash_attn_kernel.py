"""The trainer's own flash-attention kernels (ops/pallas_flash_attn), through
Pallas interpret mode at small sizes, against ``attention_xla``; the
token-major entry against the head-major one; and the rule by which
``auto`` takes the kernel, from the shapes a call shows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.ops import attention as A
from distributed_lion_tpu.ops import pallas_flash_attn as F
from distributed_lion_tpu.train import journal

FLASH_TOL = 2e-2   # chip_smoke.py's: |kernel - xla| on bf16 values of O(1)


def _heads(x, H):
    """[B, T, H * hd] -> [B, H, T, hd]."""
    B, T, D = x.shape
    return x.reshape(B, T, H, D // H).transpose(0, 2, 1, 3)


def _reference(qkv, H):
    """``attention_xla`` on the three column ranges of ``qkv``."""
    B, T, W = qkv.shape
    D = W // 3
    q, k, v = (_heads(qkv[:, :, i * D:(i + 1) * D], H) for i in range(3))
    return A.attention_xla(q, k, v).transpose(0, 2, 1, 3).reshape(B, T, D)


def _inputs(B, T, H, hd, dtype, seed=0):
    kq, kw = jax.random.split(jax.random.key(seed))
    return (jax.random.normal(kq, (B, T, 3 * H * hd), dtype),
            jax.random.normal(kw, (B, T, H * hd), dtype))


def _out_and_grad(fn, qkv, w):
    """fn's output and the gradient of ``sum(out * w)``: dq, dk, dv are
    the three column ranges of the one cotangent."""
    def loss(x):
        out = fn(x)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    (_, out), grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(qkv)
    return out, grad


SHAPES = [  # B, T, H, head_dim: T of one block and of several, B > 1
    (2, 128, 2, 64),      # one block of 128
    (2, 512, 4, 64),      # one block of 512, two lane blocks
    (2, 1024, 2, 64),     # two blocks of 512: the cells' T
    (3, 384, 2, 64),      # three blocks of 128
    (2, 256, 1, 128),     # a head a lane block, one block of 256
    (2, 768, 2, 128),     # three blocks of 256
]


@pytest.mark.parametrize("B,T,H,hd", SHAPES,
                         ids=[f"B{b}-T{t}-H{h}-hd{d}" for b, t, h, d in SHAPES])
def test_kernel_matches_xla_in_float32(B, T, H, hd):
    """Forward and the three gradients, float32 end to end."""
    assert F.kernel_takes(T, H, hd, jnp.float32)
    qkv, w = _inputs(B, T, H, hd, jnp.float32, seed=T + hd)
    got, g_got = _out_and_grad(lambda x: F.flash_qkv(x, H, True), qkv, w)
    want, g_want = _out_and_grad(lambda x: _reference(x, H), qkv, w)
    assert got.shape == (B, T, H * hd) and g_got.shape == qkv.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    D = H * hd
    for i, name in enumerate(("dq", "dk", "dv")):
        np.testing.assert_allclose(
            g_got[:, :, i * D:(i + 1) * D], g_want[:, :, i * D:(i + 1) * D],
            atol=5e-5, rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("B,T,H,hd", [(2, 1024, 2, 64), (2, 512, 1, 128)],
                         ids=["hd64", "hd128"])
def test_kernel_in_bfloat16_is_inside_the_smoke_tolerance(B, T, H, hd):
    """bf16 operands, float32 accumulation and statistics: within
    ``FLASH_TOL`` of the float32 ``attention_xla`` on the same bf16 values,
    relative to the largest reference value, output and gradients."""
    qkv, w = _inputs(B, T, H, hd, jnp.bfloat16, seed=7)
    got, g_got = _out_and_grad(lambda x: F.flash_qkv(x, H, True), qkv, w)
    want, g_want = _out_and_grad(
        lambda x: _reference(x, H), qkv.astype(jnp.float32),
        w.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16 and g_got.dtype == jnp.bfloat16
    for a, b in ((got, want), (g_got, g_want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= FLASH_TOL * max(1.0, np.abs(b).max())


def test_rows_see_nothing_above_the_diagonal():
    """Poison every token after position p: rows up to p do not move."""
    B, T, H, hd, p = 1, 512, 2, 64, 300
    qkv, _ = _inputs(B, T, H, hd, jnp.float32, seed=3)
    poisoned = qkv.at[:, p + 1:].set(1e4)
    a = F.flash_qkv(qkv, H, True)
    b = F.flash_qkv(poisoned, H, True)
    np.testing.assert_array_equal(a[:, :p + 1], b[:, :p + 1])


@pytest.mark.parametrize("T,H,hd,dtype,takes", [
    (1024, 12, 64, jnp.bfloat16, True),     # the training cells
    (2048, 32, 128, jnp.bfloat16, True),
    (8192, 8, 128, jnp.float32, True),
    (1024, 25, 64, jnp.bfloat16, False),    # GPT-2 XL: 12.5 lane blocks
    (1024, 12, 80, jnp.bfloat16, False),    # a head the lanes do not hold
    (1000, 12, 64, jnp.bfloat16, False),    # T not in whole blocks
    (16384, 8, 128, jnp.bfloat16, False),   # operands past VMEM
    (1024, 12, 64, jnp.float16, False),
], ids=["cells", "T2048-hd128", "T8192-f32", "xl-25-heads", "hd80", "T1000",
        "T16384", "f16"])
def test_kernel_takes(T, H, hd, dtype, takes):
    assert F.kernel_takes(T, H, hd, dtype) is takes


@pytest.mark.parametrize("T,block", [(128, 128), (384, 128), (256, 256),
                                     (768, 256), (1024, 512), (4096, 512)])
def test_block_is_chosen_from_T(T, block):
    assert F.block_for(T) == block


# ------------------------------------- the forward alone, for a prefill
def _gqa_inputs(B, T, H, KV, hd, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return [jax.random.normal(k, (B, T, n * hd), dtype)
            for k, n in zip(keys, (H, KV, KV))]


@pytest.mark.parametrize("T", [128, 256])
@pytest.mark.parametrize("H", [4, 5], ids=["even", "odd"])
def test_prefill_forward_takes_heads_of_64(H, T):
    """``flash_gqa_fwd`` over two heads of 64 a lane block, one kv head a
    query head; an odd count is padded with a head of zero lanes whose
    output is sliced off (GPT-2 XL's 25 heads: 1,600 -> 1,664 lanes)."""
    q, k, v = _gqa_inputs(2, T, H, H, 64, seed=H)
    got = F.flash_gqa_fwd(q, k, v, H, interpret=True)
    want = A.attention_xla(_heads(q, H), _heads(k, H), _heads(v, H))
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, want.transpose(0, 2, 1, 3).reshape(
        q.shape), atol=2e-6)
    assert F.gqa_kernel_takes(T, 64, q.dtype, 1)
    assert not F.gqa_kernel_takes(T, 64, q.dtype, 2)   # grouped heads of 64
    assert not F.gqa_kernel_takes(T + 64, 64, q.dtype, 1)
    assert F.gqa_kernel_takes(T, 128, jnp.bfloat16, 6)
    assert not F.gqa_kernel_takes(T, 128, jnp.float16, 6)


def _gqa_fwd_of_pr30(q, k, v, n_head):
    """The call ``flash_gqa_fwd`` made until PR 40, heads of 128 only."""
    import functools
    import math

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, width = q.shape
    rep = n_head // (k.shape[2] // 128)
    blk, nq = F.block_for(T), T // F.block_for(T)
    kv_spec = pl.BlockSpec((None, T, 128), lambda b, j, i: (b, 0, j // rep))
    return pl.pallas_call(
        functools.partial(F._fwd_kernel, scale=1.0 / math.sqrt(128),
                          head_dim=128),
        grid=(B, n_head, nq),
        in_specs=[pl.BlockSpec((None, blk, 128), lambda b, j, i: (b, i, j)),
                  kv_spec, kv_spec],
        out_specs=[pl.BlockSpec((None, blk, 128), lambda b, j, i: (b, i, j)),
                   pl.BlockSpec((None, None, None, 1, blk),
                                lambda b, j, i: (b, j, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, width), q.dtype),
                   jax.ShapeDtypeStruct((B, n_head, nq, 1, blk),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, blk, 128), jnp.float32)],
        interpret=True)(q, k, v)[0]


@pytest.mark.parametrize("T,H,KV,dtype", [(256, 4, 2, jnp.float32),
                                          (128, 3, 1, jnp.bfloat16)],
                         ids=["f32-rep2", "bf16-rep3"])
def test_prefill_forward_at_heads_of_128_is_the_call_it_was(T, H, KV, dtype):
    q, k, v = _gqa_inputs(1, T, H, KV, 128, dtype, seed=T)
    np.testing.assert_array_equal(
        np.asarray(F.flash_gqa_fwd(q, k, v, H, interpret=True), np.float32),
        np.asarray(_gqa_fwd_of_pr30(q, k, v, H), np.float32))


# ------------------------------------------------- the token-major entry
@pytest.fixture
def spy(monkeypatch):
    """``attention_qkv`` / ``attention`` with every implementation replaced
    by a recorder (this pins DISPATCH; the arithmetic is pinned above) and
    the resolution memo emptied."""
    calls = []

    def kernel(qkv, n_head, interpret=False):
        calls.append(("kernel", qkv.shape, n_head))
        return qkv[..., :qkv.shape[-1] // 3]

    def flash(q, k, v, **kw):
        calls.append(("flash", q.shape, kw))
        return q

    def xla(q, k, v, *, causal=True):
        calls.append(("xla", q.shape))
        return q

    monkeypatch.setattr(F, "flash_qkv", kernel)
    monkeypatch.setattr(A, "attention_flash", flash)
    monkeypatch.setattr(A, "attention_xla", xla)
    monkeypatch.setattr(journal, "_RESOLVED", {})
    monkeypatch.setattr(journal, "_resolved_said", 0)
    return calls


def _trace_qkv(B, T, H, hd, dtype=jnp.bfloat16, **kw):
    """Trace ``attention_qkv`` at a real shape without running it."""
    x = jax.ShapeDtypeStruct((B, T, 3, H * hd), dtype)
    return jax.eval_shape(lambda x: A.attention_qkv(x, H, **kw), x)


KERNEL = {  # where: B, T, H, head_dim, dtype
    "readme-20x8": (20, 1024, 12, 64, jnp.bfloat16),      # cell 1
    "vote-4x2": (4, 1024, 12, 64, jnp.bfloat16),          # cell 4
    "T2048-hd128": (1, 2048, 32, 128, jnp.bfloat16),
    "T4096-hd64": (2, 4096, 16, 64, jnp.bfloat16),
    # every GPT-2 width a CLI or a benchmark configuration can name that
    # the kernel takes, and the T it takes them at
    "gpt2_124m-f32": (4, 1024, 12, 64, jnp.float32),      # run_clm's default
    "gpt2_124m-T8192": (1, 8192, 12, 64, jnp.bfloat16),   # the kernel's MAX_T
    "gpt2_124m-T2176": (1, 2176, 12, 64, jnp.bfloat16),   # 17 blocks of 128
    "gpt2-medium": (2, 1024, 16, 64, jnp.bfloat16),
    "gpt2-large": (2, 1024, 20, 64, jnp.bfloat16),
}


@pytest.mark.parametrize("where", list(KERNEL))
def test_auto_takes_the_kernel_on_a_tpu(spy, monkeypatch, where):
    """At the two training cells' shapes, at the other GPT-2 widths and
    wherever the head-major `auto` would take the library's flash: the
    kernel, handed ``qkv`` as it lies."""
    B, T, H, hd, dtype = KERNEL[where]
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    out = _trace_qkv(B, T, H, hd, dtype)
    assert out.shape == (B, T, H * hd)
    assert spy == [("kernel", (B, T, 3 * H * hd), H)]
    (line,) = journal.new_resolved_lines()
    blk = F.block_for(T)
    assert line == (f"[setup] attention: qkv auto -> pallas_flash_attn "
                    f"(T {T}, head_dim {hd}, {jnp.dtype(dtype).name}, "
                    f"tiles {blk}x{blk})")
    assert journal.new_resolved_lines() == []       # said once


AWAY = {  # why: backend, T, H, head_dim, the call's options, where it goes
    "off-tpu": ("cpu", 1024, 12, 64, {}, "xla"),
    "head_dim-80": ("tpu", 1024, 12, 80, {}, "xla"),
    "xl-25-heads": ("tpu", 1024, 25, 64, {}, "xla"),
    "T512": ("tpu", 512, 12, 64, {}, "xla"),
    "T16384": ("tpu", 16384, 8, 128, {}, "flash"),
    "explicit-xla": ("tpu", 1024, 12, 64, {"impl": "xla"}, "xla"),
    "T1000": ("tpu", 1000, 12, 64, {}, "xla"),     # no whole row block
    "gpt2-tiny": ("tpu", 128, 4, 16, {}, "xla"),   # GPT2Config.tiny
    "gpt2_small": ("tpu", 256, 5, 64, {}, "xla"),  # GPT2Config.small: 2.5
                                                   # lane blocks
}


@pytest.mark.parametrize("why", list(AWAY))
def test_auto_resolves_away_from_the_kernel(spy, monkeypatch, why):
    """Off a TPU, at a shape the kernel does not take, below T = 1024 and
    under ``impl="xla"``: the head-major entry, which decides the rest."""
    backend, T, H, hd, kw, first = AWAY[why]
    monkeypatch.setattr(A.jax, "default_backend", lambda: backend)
    out = _trace_qkv(2, T, H, hd, **kw)
    assert out.shape == (2, T, H * hd)
    assert [c[0] for c in spy] == [first]
    assert spy[0][1] == (2, H, T, hd)


HEAD_MAJOR = {  # who: backend, B, T, H, head_dim, where `auto` goes
    # Llama-2-7B / Llama-3-8B: 32 query heads of 128 (kv heads repeated
    # before the call), at the T a chip's memory lets a block train at
    "llama-T1024": ("tpu", 1, 1024, 32, 128, "xla"),
    "llama-T2048": ("tpu", 1, 2048, 32, 128, "flash"),
    "llama-T4096": ("tpu", 1, 4096, 32, 128, "flash"),
    "llama-T8192": ("tpu", 1, 8192, 32, 128, "flash"),
    "llama-T4096-cpu": ("cpu", 1, 4096, 32, 128, "xla"),
    "llama-T512": ("tpu", 2, 512, 32, 128, "xla"),
    "llama-small": ("tpu", 2, 1024, 8, 64, "xla"),     # LlamaConfig.small
    "T2047": ("tpu", 1, 2047, 8, 64, "xla"),           # one under the line
    "hd64-T2048": ("tpu", 1, 2048, 8, 64, "flash"),
}


@pytest.mark.parametrize("who", list(HEAD_MAJOR))
def test_head_major_auto_has_two_outcomes(spy, monkeypatch, who):
    """``attention`` `auto`: the library's flash kernel on a TPU from
    T = 2048, called with q, k, v and ``causal`` and nothing else (its own
    default tiles); ``attention_xla`` everywhere else."""
    backend, B, T, H, hd, goes = HEAD_MAJOR[who]
    monkeypatch.setattr(A.jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct((B, H, T, hd), jnp.bfloat16)
    out = jax.eval_shape(lambda q, k, v: A.attention(q, k, v), x, x, x)
    assert out.shape == (B, H, T, hd)
    want = ("flash", (B, H, T, hd), {"causal": True}) if goes == "flash" \
        else ("xla", (B, H, T, hd))
    assert spy == [want]


def test_head_major_resolution_is_said_once(spy, monkeypatch):
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((1, 32, 2048, 128), jnp.bfloat16)
    for _ in range(2):
        jax.eval_shape(lambda q, k, v: A.attention(q, k, v), x, x, x)
    assert journal.new_resolved_lines() == [
        "[setup] attention: head-major auto -> flash (T 2048, head_dim 128, "
        "bfloat16, tiles default)"]
    assert journal.new_resolved_lines() == []


def test_dropout_keeps_the_xla_scores(spy, monkeypatch):
    """``models/gpt2._attention`` under attention dropout never reaches the
    dispatcher (materialized scores); without dropout, same shapes, it
    hands the projection's output to the kernel."""
    from distributed_lion_tpu.models import gpt2

    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gpt2, "_proj", lambda x, w: x)
    cfg = gpt2.GPT2Config(vocab_size=64, n_layer=1, n_head=2, d_model=128,
                          n_ctx=1024, dropout=0.1)
    p = jax.eval_shape(lambda: gpt2.gpt2_init(jax.random.key(0), cfg)
                       )["blocks"][0]["attn"]
    x = jax.ShapeDtypeStruct((2, 1024, 128), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    jax.eval_shape(lambda x, p, k: gpt2._attention(x, p, cfg, k), x, p, key)
    assert spy == []
    jax.eval_shape(lambda x, p: gpt2._attention(x, p, cfg, None), x, p)
    assert spy == [("kernel", (2, 1024, 3 * 128), 2)]


def test_resolution_reaches_the_journal(spy, monkeypatch, tmp_path):
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    j = journal.Journal(str(tmp_path))
    journal.install(j)
    try:
        _trace_qkv(4, 1024, 12, 64)
        _trace_qkv(4, 1024, 12, 64)          # same shape: recorded once
        events = [r for r in j.records() if r.get("name") == "attn_resolved"]
    finally:
        journal.uninstall(j)
        j.close()
    assert len(events) == 1
    assert {k: events[0][k] for k in ("entry", "impl", "T", "head_dim",
                                      "tiles")} == {
        "entry": "qkv", "impl": "pallas_flash_attn", "T": 1024,
        "head_dim": 64, "tiles": "512x512"}


@pytest.mark.parametrize("layout", ["fused-4d", "flat-3d"])
def test_token_major_entry_equals_head_major_entry(layout):
    """``attention_qkv`` (here: off a TPU, so through the split) against
    ``attention`` on the same values laid out head-major."""
    B, T, H, hd = 2, 64, 2, 16
    qkv, _ = _inputs(B, T, H, hd, jnp.float32, seed=5)
    x = qkv.reshape(B, T, 3, H * hd) if layout == "fused-4d" else qkv
    got = A.attention_qkv(x, H)
    D = H * hd
    q, k, v = (_heads(qkv[:, :, i * D:(i + 1) * D], H) for i in range(3))
    want = A.attention(q, k, v).transpose(0, 2, 1, 3).reshape(B, T, D)
    np.testing.assert_array_equal(got, want)


def test_kernel_entry_equals_head_major_entry(monkeypatch):
    """The same comparison with the kernel behind the token-major entry (a
    TPU's resolution, interpret mode), output and gradient."""
    real = F.flash_qkv
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(F, "flash_qkv",
                        lambda qkv, n_head: real(qkv, n_head, True))
    B, T, H, hd = 1, 1024, 2, 64
    qkv, w = _inputs(B, T, H, hd, jnp.float32, seed=11)
    got, g_got = _out_and_grad(
        lambda x: A.attention_qkv(x.reshape(B, T, 3, H * hd), H), qkv, w)
    monkeypatch.setattr(A.jax, "default_backend", lambda: "cpu")
    D = H * hd

    def head_major(x):
        q, k, v = (_heads(x[:, :, i * D:(i + 1) * D], H) for i in range(3))
        return A.attention(q, k, v).transpose(0, 2, 1, 3).reshape(B, T, D)

    want, g_want = _out_and_grad(head_major, qkv, w)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(g_got, g_want, atol=5e-5, rtol=5e-5)


def test_gpt2_through_the_kernel_equals_the_xla_path(monkeypatch):
    """One GPT-2 block's loss and parameter gradients at T = 1024 with the
    kernel in the step (a TPU's resolution, interpret mode) against the
    same block off a TPU (XLA scores)."""
    from distributed_lion_tpu.models import gpt2

    real = F.flash_qkv
    cfg = gpt2.GPT2Config(vocab_size=64, n_layer=1, n_head=2, d_model=128,
                          n_ctx=1024, dropout=0.0, remat=True,
                          compute_dtype=jnp.float32)
    params = gpt2.gpt2_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (1, 1024), 0, 64)

    def loss(params):
        logits = gpt2.gpt2_apply(params, tokens, cfg)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - logits[..., 0])

    want, g_want = jax.jit(jax.value_and_grad(loss))(params)
    seen = []

    def kernel(qkv, n_head):
        seen.append(qkv.shape)
        return real(qkv, n_head, True)

    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(F, "flash_qkv", kernel)
    jax.clear_caches()    # the remat block's trace is memoised by its avals
    got, g_got = jax.jit(jax.value_and_grad(loss))(params)
    jax.clear_caches()
    assert seen and all(s == (1, 1024, 3 * 128) for s in seen)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)
