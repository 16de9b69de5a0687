"""A latent family's prefill from position 0 over its own fresh rows
(``ops/pallas_dsa.latent_prefill``: the indexer's prefill kernel without its
mask; ``ops/attention.latent_fresh_attention`` and its rule;
``models/joyai._mla_block``'s ``fresh``; the engine's ``_fresh_buckets``), on
the CPU in interpret mode at two heads of 192 / 128.

Tolerances. In float32 the kernel and the plain softmax sum the same products
in another order: 1e-5 of outputs of order 1 (the whole hooks, in float32
too: 1e-4 of their logits and rows). In bfloat16 both round the
probabilities to 8 bits before the value product, the kernel unnormalised and
the walk normalised: 2 ** -6 of the largest output, where one rounding of it
is 2 ** -8.
"""

import functools
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributed_lion_tpu.models import joyai, xing  # noqa: E402
from distributed_lion_tpu.models.llama import rope_angles  # noqa: E402
from distributed_lion_tpu.ops import attention, pallas_dsa  # noqa: E402
from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.kv_cache import init_page_leaves  # noqa: E402

H, R, DN, DR, DV = 2, 64, 128, 64, 128
SCALE = 0.14468


def operands(B, S, dtype, seed=47):
    """q ``[B, H, S, 192]``, rows ``[B, S, R + 64]``, ``w_kvb``."""
    k = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k[0], (B, H, S, DN + DR)).astype(dtype)
    row = jax.random.normal(k[1], (B, S, R + DR)).astype(dtype)
    w = (jax.random.normal(k[2], (R, H, DN + DV)) / R ** 0.5).astype(dtype)
    return q, row, w


def plain(q, row, w, scale=SCALE):
    """The float32 causal softmax over the expanded rows, token-major."""
    B, _, S, _ = q.shape
    k, v = joyai.expand_rows(row, w, DN)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision="highest") * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    out = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(s, -1),
                     v.astype(jnp.float32), precision="highest")
    return out.transpose(0, 2, 1, 3).reshape(B, S, H * DV)


def interpreted(monkeypatch):
    monkeypatch.setattr(pallas_dsa, "latent_prefill", functools.partial(
        pallas_dsa.latent_prefill, interpret=True))


def rule_as_on_a_tpu(monkeypatch):
    """``latent_fresh_applies`` as the chip would answer it, the backend
    said to be a TPU while the rule is asked and at no other time (the
    model's other kernels keep the CPU's choices)."""
    real = attention.latent_fresh_applies

    def asked(*a, **kw):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return real(*a, **kw)

    monkeypatch.setattr(attention, "latent_fresh_applies", asked)


# ------------------------------------------------------------- the kernel
@pytest.mark.parametrize("B,S,dtype", [
    (1, 1024, "bfloat16"), (2, 1024, "float32"), (1, 2048, "float32"),
    (2, 2048, "bfloat16")])
def test_kernel_is_the_walk_and_the_plain_softmax(B, S, dtype, monkeypatch):
    """One key tile (1,024: the diagonal's tile alone) and two (2,048: a
    tile wholly under the diagonal takes no bound), one row and two."""
    interpreted(monkeypatch)
    dtype = jnp.dtype(dtype)
    q, row, w = operands(B, S, dtype)
    got = jax.jit(functools.partial(attention.latent_fresh_attention,
                                    scale=SCALE))(q, row, w)
    assert got.shape == (B, S, H * DV) and got.dtype == dtype
    k, v = joyai.expand_rows(row, w, DN)
    walk = attention.chunked_causal_attention(
        q, k, v, jnp.zeros((B,), jnp.int32), scale=SCALE)
    walk = walk.transpose(0, 2, 1, 3).reshape(B, S, H * DV)
    want = plain(q, row, w)
    tol = 1e-5 if dtype == jnp.float32 else \
        float(jnp.abs(want).max()) * 2.0 ** -6
    got = got.astype(jnp.float32)
    assert float(jnp.abs(got - want).max()) < tol
    assert float(jnp.abs(got - walk.astype(jnp.float32)).max()) < tol


def _parents_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, scale: float):
    """``_prefill_kernel`` as PR 46 left it, line for line."""
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = keep_ref.shape
    last = (i * bq + bq - 1) // bk

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, pallas_dsa.MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= last)
    def _():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        seen = keep_ref[...].astype(jnp.int32) != 0
        s = jnp.where(seen, s, pallas_dsa.MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == last)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                      ).astype(o_ref.dtype)


def test_masked_call_is_bit_for_bit_what_it_was():
    """``dsa_prefill`` shares its body with the mask-free form now; under a
    mask (a tile that keeps none for half its rows among them) it gives the
    bits the kernel it was gave, and lowers under the name it had."""
    rng = np.random.default_rng(46)
    S, dk = 2048, DN + DR
    q, k = (jnp.asarray(rng.standard_normal((H, S, dk)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((H, S, DV)), jnp.bfloat16)
    t = np.arange(S)
    keep = (rng.random((S, S)) < 0.1) | (t[:, None] == t[None, :])
    keep &= t[None, :] <= t[:, None]
    keep[1024:1300, :1024] = False
    keep = jnp.asarray(keep, jnp.int8)
    got = pallas_dsa.dsa_prefill(q, k, v, keep, scale=0.2, interpret=True)
    bq, bk = pallas_dsa.BLOCK_Q, pallas_dsa.BLOCK_K

    def seen(i, j):
        return jnp.minimum(j, (i * bq + bq - 1) // bk)

    was = pl.pallas_call(
        functools.partial(_parents_kernel, scale=0.2),
        grid=(H, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((None, bq, dk), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, bk, dk), lambda h, i, j: (h, seen(i, j), 0)),
            pl.BlockSpec((None, bk, DV), lambda h, i, j: (h, seen(i, j), 0)),
            pl.BlockSpec((bq, bk), lambda h, i, j: (i, seen(i, j)))],
        out_specs=pl.BlockSpec((None, bq, DV), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, DV), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((H, S, DV), q.dtype),
        interpret=True)(q, k, v, keep)
    assert bool((got == was).all())
    names = jax.jit(functools.partial(
        pallas_dsa.dsa_prefill, scale=0.2, interpret=True)).lower(
            q, k, v, keep).as_text()
    assert "dsa_prefill" in names and "latent_prefill" not in names


def test_tiles_past_a_rows_length_are_zeros_and_move_nothing_else(
        monkeypatch):
    """Row 1 holds 700 real tokens of 2,048: its query tiles from 1,024 on
    are written as zeros (not left as they lay), its tiles up to there are
    what a whole row's are, bit for bit; row 0 is whole."""
    interpreted(monkeypatch)
    q, row, w = operands(2, 2048, jnp.bfloat16, seed=3)
    attend = jax.jit(functools.partial(attention.latent_fresh_attention,
                                       scale=SCALE))
    whole = attend(q, row, w)
    valid = jnp.arange(2048)[None, :] < jnp.asarray([[2048], [700]])
    got = attend(q, row, w, valid)
    assert bool((got[0] == whole[0]).all())
    assert bool((got[1, :1024] == whole[1, :1024]).all())
    assert not bool(got[1, 1024:].any())
    one_row = attend(q, row, w, valid[1:])        # [1, S] broadcasts
    assert not bool(one_row[:, 1024:].any())


# --------------------------------------------------------------- the rule
@pytest.mark.parametrize("backend,S,dn,dv,takes", [
    ("tpu", 4096, 128, 128, True),
    ("tpu", attention.LATENT_FRESH_MIN, 128, 128, True),
    ("cpu", 4096, 128, 128, False),                # no kernel off the chip
    ("tpu", 4096 + 512, 128, 128, False),          # not whole key tiles
    ("tpu", 4096, 128, 64, False),                 # values of half a lane tile
    ("tpu", 4096, 64, 64, False),                  # heads of half a lane tile
    ("tpu", attention.LATENT_FRESH_MIN // 2, 128, 128, False),  # too short
    ("tpu", 1, 128, 128, False),                   # the decode tick
])
def test_rule_reads_the_backend_and_the_shapes(backend, S, dn, dv, takes,
                                               monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention.latent_fresh_applies(S, dn, dv) is takes


# ------------------------------------------------------ the block, served
def wide(config, **kw):
    """A tiny configuration with the published head widths, in float32 (in
    bfloat16 a rounding apart in one layer flips an expert pick in the
    next)."""
    return config.tiny(n_head=H, kv_lora_rank=R, qk_nope_head_dim=DN,
                       qk_rope_head_dim=DR, v_head_dim=DV,
                       param_dtype=jnp.float32, compute_dtype=jnp.float32,
                       **kw)


FAMILIES = {
    "joyai": (joyai.JoyAIConfig, joyai.joyai_init, joyai.joyai_decode_paged,
              ServeModel.for_joyai),
    "xing": (xing.XingConfig, xing.xing_init, xing.xing_decode_paged,
             ServeModel.for_xing),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    config, init, decode, serve = FAMILIES[request.param]
    cfg = wide(config, n_layer=1, first_dense=1)   # the block is the test
    return cfg, init(jax.random.key(5), cfg), decode, serve


def test_fresh_block_is_the_gather_block_and_writes_the_same_pages(
        family, monkeypatch):
    """The whole hook over a 2,048-token window, rows of 2,048 and 1,300
    tokens: ``fresh=True`` (the kernel, interpreted) against the gather path,
    logits at the real positions and every page of every layer."""
    cfg, params, decode, _ = family
    B, S, bs = 2, 2048, 16
    pages = init_page_leaves(cfg.n_layer, B * S // bs + 3, bs,
                             {"kv": (1, cfg.latent_dim)}, cfg.compute_dtype)
    tables = jnp.arange(B * S // bs, dtype=jnp.int32)[::-1].reshape(B, -1)
    toks = jax.random.randint(jax.random.key(6), (B, S), 0, cfg.vocab_size)
    valid = jnp.arange(S)[None, :] < jnp.asarray([[S], [1300]])
    pos = jnp.zeros((B,), jnp.int32)

    def run(fresh):
        return jax.jit(lambda t: decode(params, t, cfg, pages, tables, pos,
                                        valid, fresh=fresh))(toks)

    want, want_pages = run(False)
    on_cpu, _ = run(True)            # the rule says no: the parent's program
    assert bool((on_cpu == want).all())
    interpreted(monkeypatch)
    rule_as_on_a_tpu(monkeypatch)
    got, got_pages = run(True)
    real = np.asarray(valid)
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert 0 < gap.max() < 1e-4      # another program, the same numbers
    # the rows are computed before the attention: the same bits in the
    # same cells
    assert bool((got_pages[0]["kv"] == want_pages[0]["kv"]).all())
    assert bool(got_pages[0]["kv"].any())


def test_block_past_0_or_under_the_bound_keeps_the_walk(monkeypatch):
    """``fresh`` is the dispatch's word that rows start at 0: without it, or
    in a bucket under the rule's bound, the lowered block holds no kernel."""
    interpreted(monkeypatch)
    rule_as_on_a_tpu(monkeypatch)
    cfg = wide(joyai.JoyAIConfig)
    p = joyai.joyai_init(jax.random.key(5), cfg)["blocks"][0]["attn"]
    bs = 16

    def lowered(S, fresh):
        pool = jnp.zeros((S // bs + 2, bs, 1, 128), cfg.compute_dtype)
        tables = jnp.arange(S // bs + 2, dtype=jnp.int32)[None]
        x = jnp.zeros((1, S, cfg.d_model), cfg.compute_dtype)
        cos, sin = (a[None, :S] for a in rope_angles(S, DR, cfg.rope_theta))
        return jax.jit(lambda x: joyai._mla_block(
            x, p, cfg, {"kv": pool}, tables, jnp.zeros((1,), jnp.int32),
            cos, sin, None, fresh=fresh)).lower(x).as_text()

    assert "latent_prefill" in lowered(2048, True)
    assert "latent_prefill" not in lowered(2048, False)
    assert "latent_prefill" not in lowered(1024, True)


def test_engine_asks_the_models_rule_and_counts_fresh_prefills(monkeypatch):
    """On the CPU no bucket is fresh. With the chip's answers the engine
    dispatches every prefill of the 2,048 bucket as ``fresh`` (the counter
    the dense families have), the 1,024 bucket as before, and serves the
    tokens the gather engine serves."""
    cfg, serve = wide(joyai.JoyAIConfig), ServeModel.for_joyai
    params = joyai.joyai_init(jax.random.key(5), cfg)
    conf = ServeConfig(max_seqs=2, block_size=16, max_blocks_per_seq=136,
                       prefill_cap_tokens=4096)
    rng = np.random.default_rng(9)
    requests = [Request(req_id=i, tokens=rng.integers(
        0, cfg.vocab_size, n).tolist(), max_new_tokens=3)
        for i, n in enumerate((1500, 700))]
    plain_engine = ServingEngine(serve(params, cfg), conf)
    assert plain_engine._fresh_buckets == frozenset()
    want = plain_engine.run(list(requests))
    assert plain_engine.stats["prefill_fresh_dispatches"] == 0
    interpreted(monkeypatch)
    rule_as_on_a_tpu(monkeypatch)
    engine = ServingEngine(serve(params, cfg), conf)
    assert engine._fresh_buckets == frozenset({2048})
    got = engine.run(list(requests))
    assert engine.stats["prefill_dispatches"] == 2
    assert engine.stats["prefill_fresh_dispatches"] == 1
    assert {i: got[i].tokens for i in got} == {i: want[i].tokens
                                               for i in want}


def test_dense_families_keep_their_rule(monkeypatch):
    """GPT-2's and Llama's buckets come from ``fresh_kernel_applies`` with
    their own head widths, as before the model owned the question."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config.tiny(n_head=2, d_model=256)
    kernel, takes = ServeModel.for_gpt2(None, cfg).fresh_prefill
    assert kernel == "flash_gqa_fwd"
    assert not any(takes(b, 1) for b in (128, 256, 1024))         # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [b for b in (64, 128, 200, 256, 1024) if takes(b, 1)] \
        == [b for b in (64, 128, 200, 256, 1024)
            if attention.fresh_kernel_applies(b, cfg.n_head, cfg.n_head,
                                              cfg.head_dim,
                                              cfg.compute_dtype)] != []
