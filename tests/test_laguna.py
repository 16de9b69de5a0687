"""The Laguna serving path (models/laguna: window and full GQA layers with
their own head counts, per-head output gates, a bounded ring a slot beside
growing pages, dropless experts told which they hold) at a tiny size on the
CPU, seeded weights, float32, against the benchmark's plain reference
(``benchmark/reference/laguna``: float32, nothing imported from the
package).

Tolerances. Program and reference compute the same float32 arithmetic in
another order (ring and pages against one masked row, grouped queries
against repeated kv heads, a sorted grouped matmul against a masked scan
over the held experts); at these sizes their logits agree to 3e-6 and 1e-4
leaves thirty times that. The kernel in interpret mode runs float32 too.
"""

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import laguna as family  # noqa: E402
from distributed_lion_tpu.models import laguna  # noqa: E402
from distributed_lion_tpu.models.laguna import (  # noqa: E402
    LagunaConfig,
    Rope,
    laguna_decode_paged,
)
from distributed_lion_tpu.ops import attention as attn_ops  # noqa: E402
from distributed_lion_tpu.ops import pallas_moe_gmm, pallas_paged_attn  # noqa: E402
from distributed_lion_tpu.parallel import expert  # noqa: E402
from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.ops.attention import ring_pages  # noqa: E402
from distributed_lion_tpu.serve.kv_cache import init_page_leaves  # noqa: E402

ref = family.reference
TINY = family.TINY                 # window 8; experts 0-3 of 8 held
WHOLE = dict(TINY, num_experts=8, reduced=[], published={})   # all 8 held
TOL = 1e-4
BLOCK, PER_SEQ = 8, 8   # rows of up to 64 tokens: 8 windows. Pages of 8
# rows are whole float32 sublane tiles, which the kernel asks for
RING = ring_pages(TINY["sliding_window"], BLOCK)    # 2 pages of 8


def build(body):
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 30), body, jnp.float32)
    cfg = LagunaConfig.from_hf(body, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32)
    return weights, family.to_program(weights), cfg


@pytest.fixture(scope="module")
def model():
    """(reference weights, program params, LagunaConfig) at TINY, float32:
    the same values in both layouts."""
    return build(TINY)


def pool(cfg, n_seq):
    pages = init_page_leaves(
        cfg.n_layer, n_seq * PER_SEQ, BLOCK,
        {"k": (cfg.n_kv_head, cfg.head_dim),
         "v": (cfg.n_kv_head, cfg.head_dim)}, jnp.float32,
        ring=(cfg.window_layers, n_seq * RING))
    # shuffled ownership: every read has to go through the table
    tables = jnp.arange(n_seq * PER_SEQ, dtype=jnp.int32)[::-1].reshape(
        n_seq, PER_SEQ)
    # row b owns slot n_seq - 1 - b: a ring is found by the slot's id
    return pages, tables, jnp.arange(n_seq, dtype=jnp.int32)[::-1]


def rows_of(n_seq, width, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n_seq, width)).astype(np.int32)


# Model calls run COMPILED, one program a shape: eagerly a forward pass is a
# few hundred one-op programs (ISSUE 35).
reference = jax.jit(lambda weights, rows: ref.forward(weights, rows, TINY))
dropless_ffn = jax.jit(expert.moe_dropless_ffn, static_argnames=(
    "top_k", "scale", "return_counters", "held"))


def program_of(cfg):
    """``laguna_decode_paged`` at ``cfg``, compiled. Built anew in every
    test: a trace holds the choices the backend's name made, and
    ``interpret_kernels`` changes that name."""
    return jax.jit(
        lambda params, toks, pages, tables, slots, pos, valid=None,
        logit_index=None: laguna_decode_paged(
            params, toks, cfg, pages, tables, slots, pos, valid,
            logit_index=logit_index),
        static_argnames="logit_index")


def interpret_kernels(monkeypatch):
    """Take the TPU's choices on the CPU: the Mosaic kernels in interpret
    mode (the test says "tpu" in the backend's place, as
    tests/test_chip_compile.py does)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_paged_attn, "paged_attn", functools.partial(
        pallas_paged_attn.paged_attn, interpret=True))
    monkeypatch.setattr(pallas_moe_gmm, "moe_gmm", functools.partial(
        pallas_moe_gmm.moe_gmm, interpret=True))


# ------------------------------------------------------------ the blocks
@pytest.mark.parametrize("layer", [0, 1, 4],
                         ids=["full48", "window72", "full_last"])
def test_attention_block_of_each_kind_matches_the_reference(model, layer):
    """A 40-token prefill (five windows) through one layer's gated
    attention: the layer's own head count, RoPE and mask."""
    weights, params, cfg = model
    u = jnp.asarray(np.random.default_rng(layer).standard_normal(
        (2, 40, TINY["hidden_size"])), jnp.float32)
    pages, tables, ring = pool(cfg, 2)
    pos = jnp.zeros((2,), jnp.int32)
    rope = cfg.rope_window if cfg.windowed[layer] else cfg.rope_full
    got, leaves, _ = jax.jit(
        lambda u, attn, leaves, *angles: laguna._attention_block(
            u, attn, cfg, layer, leaves, tables, ring, pos,
            jnp.full((2,), 40, jnp.int32), None, *angles)
    )(u, params["blocks"][layer]["attn"], pages[layer],
      *rope.angles(jnp.arange(40)[None, :].repeat(2, 0)))
    want = jax.jit(lambda u, w: ref._attention(u, w, TINY, layer, None))(
        u, weights["layers"][layer])
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < TOL
    # a window layer keeps the ring's pages and no more; a full layer all
    assert leaves["k"].shape[0] == (2 * RING if cfg.windowed[layer]
                                    else 2 * PER_SEQ)


def test_expert_layer_told_its_range_matches_the_reference(model):
    weights, params, cfg = model
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 24, 64)),
                    jnp.float32)
    got = dropless_ffn(params["blocks"][2]["moe"], x.reshape(24, 64),
                       top_k=cfg.top_k, scale=cfg.routed_scale, held=cfg.held)
    want = ref._experts(x, weights["layers"][2], TINY, None)[0]
    assert cfg.held == (0, 4) and float(jnp.abs(got - want).max()) < TOL


def test_prefill_matches_the_reference(model):
    weights, params, cfg = model
    program = program_of(cfg)
    rows = rows_of(2, 48)
    pages, tables, ring = pool(cfg, 2)
    logits, _ = program(params, rows, pages, tables, ring,
                        jnp.zeros((2,), jnp.int32))
    want = reference(weights, rows)
    assert logits.shape == want.shape == (2, 48, TINY["vocab_size"])
    assert float(jnp.abs(logits - want).max()) < TOL
    one, _ = program(params, rows, pages, tables, ring,
                     jnp.zeros((2,), jnp.int32), logit_index=17)
    assert float(jnp.abs(one[:, 0] - want[:, 17]).max()) < TOL


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_prefill_then_decode_through_ring_and_pages(model, path, monkeypatch):
    """A ragged prefill window, then one token a step at each row's own
    position, out to 64 tokens (eight windows): every step's logits are the
    reference's full forward pass at that position. The ring is 2 pages of
    8 (16 positions); the prompts end inside its first lap (5), exactly at
    a wrap (16), one past it (17) and laps later, mid-page (30). ``kernel``:
    the S = 1 steps read pages and ring through ``paged_attn`` (interpret
    mode), the window layers with a first position."""
    weights, params, cfg = model
    if path == "kernel":
        interpret_kernels(monkeypatch)
        assert attn_ops.paged_kernel_applies(1, (4 * RING, BLOCK, 1, 128),
                                             jnp.float32)
    program = program_of(cfg)
    rows = rows_of(4, 64, seed=1)
    want = np.asarray(reference(weights, rows))
    plens = np.asarray([5, 16, 17, 30])
    pages, tables, ring = pool(cfg, 4)
    valid = jnp.arange(32)[None, :] < jnp.asarray(plens)[:, None]
    window, pages = program(params, rows[:, :32], pages, tables, ring,
                            jnp.zeros((4,), jnp.int32), valid)
    for i, n in enumerate(plens):
        assert np.abs(np.asarray(window[i, :n]) - want[i, :n]).max() < TOL
    step = jax.jit(lambda toks, pages, pos: laguna_decode_paged(
        params, toks, cfg, pages, tables, ring, pos, jnp.ones((4, 1), bool)))
    for j in range(34):
        pos = plens + j
        logits, pages = step(rows[np.arange(4), pos][:, None], pages,
                             jnp.asarray(pos, jnp.int32))
        assert np.abs(np.asarray(logits[:, 0])
                      - want[np.arange(4), pos]).max() < TOL, j


def test_a_wrong_window_or_rope_fails_the_comparison(model):
    """What the comparison above is for: a ring read one position short, or
    a window layer given the full layers' RoPE, moves the logits by far
    more than the tolerance."""
    weights, params, cfg = model
    rows = rows_of(1, 40, seed=5)
    want = reference(weights, rows)
    pages, tables, ring = pool(cfg, 1)
    for wrong in (dict(window=7), dict(rope_window=cfg.rope_full)):
        bad = dataclasses.replace(cfg, **wrong)
        got, _ = program_of(bad)(params, rows, pages, tables, ring,
                                 jnp.zeros((1,), jnp.int32))
        assert float(jnp.abs(got - want).max()) > 100 * TOL, wrong


# -------------------------------------------------- the kernel's first row
@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)],
                         ids=["bf16", "f32"])
def test_kernel_with_a_first_position_matches_the_gather_path(dtype, tol):
    """``paged_attn`` with ``starts`` (interpret mode) against the gather
    path with ``start``: rows whose window begins at a page's first row, in
    its middle and at its last row, a one-token row, an inactive row."""
    rng = np.random.default_rng(7)
    H, KV, hd, bs, nb_seq = 6, 2, 64, 16, 5
    lens = [1, 40, 0, 64, 80, 17]
    starts = [0, 7, 0, 0, 15, 16]
    B, nb = len(lens), len(lens) * nb_seq
    pools = [jnp.asarray(rng.standard_normal((nb, bs, 1, KV * hd)), dtype)
             for _ in range(2)]
    tables = jnp.asarray(rng.permutation(nb).reshape(B, nb_seq), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, 1, hd)), dtype)
    lens, starts = (jnp.asarray(x, jnp.int32) for x in (lens, starts))
    want = attn_ops.paged_decode_attention(q, *pools, tables, lens - 1,
                                           start=starts, kv_heads=KV)
    got = pallas_paged_attn.paged_attn(q[:, :, 0], *pools, tables, lens,
                                       starts, kv_heads=KV, interpret=True)
    got, want = (np.asarray(x, np.float32) for x in (got, want[:, :, 0]))
    live = np.asarray(lens) > 0
    np.testing.assert_array_equal(got[~live], 0.0)
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    # without a first position: the call as it was, bit for bit
    plain = pallas_paged_attn.paged_attn(q[:, :, 0], *pools, tables, lens,
                                         kv_heads=KV, interpret=True)
    none = pallas_paged_attn.paged_attn(q[:, :, 0], *pools, tables, lens,
                                        None, kv_heads=KV, interpret=True)
    zero = pallas_paged_attn.paged_attn(q[:, :, 0], *pools, tables, lens,
                                        jnp.zeros_like(lens), kv_heads=KV,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(none))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zero))


def test_kernel_without_a_first_position_lowers_as_before():
    """No ``starts``: two scalar-prefetch operands and no extra compare in
    the kernel's jaxpr; with one: three."""
    args = (jnp.zeros((2, 4, 64)), jnp.zeros((4, 16, 1, 128)),
            jnp.zeros((4, 16, 1, 128)), jnp.zeros((2, 2), jnp.int32),
            jnp.ones((2,), jnp.int32))

    def prefetch(*more):
        jaxpr = jax.make_jaxpr(functools.partial(
            pallas_paged_attn.paged_attn, kv_heads=2, interpret=True))(
                *args, *more)
        call = next(e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                    if e.primitive.name == "pallas_call")
        return call.params["grid_mapping"].num_index_operands

    assert prefetch() == 2 and prefetch(jnp.zeros((2,), jnp.int32)) == 3


@pytest.mark.parametrize("bs,window,path", [(4, 8, "gather"),
                                            (8, 20, "kernel")])
def test_ring_walk_reads_what_the_window_sees(bs, window, path, monkeypatch):
    """``ring_decode_attention`` against plain attention over the last
    ``window`` of a row written token by token into its ring (3 pages of 4
    for a window of 8, 4 pages of 8 for one of 20), at every position of
    some laps; ``kernel``: the walk through ``paged_attn`` with a first
    position (interpret mode)."""
    if path == "kernel":
        interpret_kernels(monkeypatch)
    rng = np.random.default_rng(11)
    H, KV, hd = 4, 2, 16
    R = ring_pages(window, bs)
    k_ring = v_ring = jnp.zeros((2 * R, bs, 1, 128), jnp.float32)
    ring = jnp.ones((1,), jnp.int32)                 # the second slot's
    keys = rng.standard_normal((72, KV, hd)).astype(np.float32)
    vals = rng.standard_normal((72, KV, hd)).astype(np.float32)
    for p in range(72):
        pos = jnp.asarray([p], jnp.int32)
        one = jnp.ones((1,), jnp.int32)
        k_ring = attn_ops.ring_scatter_kv(k_ring, ring, pos,
                                          keys[None, p:p + 1], one,
                                          window=window)
        v_ring = attn_ops.ring_scatter_kv(v_ring, ring, pos,
                                          vals[None, p:p + 1], one,
                                          window=window)
        q = jnp.asarray(rng.standard_normal((1, H, 1, hd)), jnp.float32)
        got, read = attn_ops.ring_decode_attention(
            q, k_ring, v_ring, ring, pos, window=window, kv_heads=KV)
        lo = max(p - window + 1, 0)
        # the walk is handed the pages from the window's first to the newest
        assert int(read[0]) == p // bs - lo // bs + 1 <= R
        k = np.repeat(keys[lo:p + 1], H // KV, 1)    # [t, H, hd]
        v = np.repeat(vals[lo:p + 1], H // KV, 1)
        s = np.einsum("hd,thd->ht", np.asarray(q[0, :, 0]), k) / math.sqrt(hd)
        w = np.exp(s - s.max(1, keepdims=True))
        want = np.einsum("ht,thd->hd", w / w.sum(1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(got[0, :, 0]), want, atol=1e-5)
    assert not np.asarray(k_ring[:R]).any()          # slot 0: never touched


@pytest.mark.parametrize("window", [None, 8, 24], ids=["full", "w8", "w24"])
def test_banded_prefill_is_the_masked_full_one(window, monkeypatch):
    """Chunked over queries, a window layer's chunk reading only the keys
    its band can touch: the same numbers as one masked softmax over the
    whole row, in one chunk and (the score budget cut to what 16 or 8
    queries hold) in four or eight."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((2, 6, 64, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 2, 64, 16)), jnp.float32)
            for _ in range(2))
    at = jnp.arange(64)
    seen = at[None, :] <= at[:, None]
    if window:
        seen &= at[None, :] > at[:, None] - window
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 3, 1)) / 4.0
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                      jnp.repeat(v, 3, 1))
    for chunk in (64, 16, 8):
        monkeypatch.setattr(attn_ops, "SCORE_BYTES", 2 * 6 * chunk * 4 * (
            min(64, chunk + window) if window else 64))
        got = attn_ops.banded_causal_attention(q, k, v, window=window)
        assert float(jnp.abs(got - want).max()) < 1e-5, chunk


def test_banded_prefill_holds_no_whole_row_of_scores(monkeypatch):
    """8 chunks of a window layer: float32 scores ``[.., 8, 8 + 8]`` a
    chunk, never ``[.., 64, 64]``."""
    q = jnp.zeros((1, 6, 64, 16))
    k = jnp.zeros((1, 2, 64, 16))
    monkeypatch.setattr(attn_ops, "SCORE_BYTES", 6 * 8 * 16 * 4)
    text = str(jax.make_jaxpr(functools.partial(
        attn_ops.banded_causal_attention, window=8))(q, k, k))
    assert "f32[1,2,3,8,16]" in text and "64,64]" not in text


def test_a_chunk_is_chosen_from_the_scores_it_would_hold():
    """At the published shapes (48 heads over every key, 72 over the band)
    a chunk's float32 scores stay within ``SCORE_BYTES``: the sizes timed
    on the chip (PERF.md, PR 30)."""
    def scores(heads, S, window):
        q = jax.ShapeDtypeStruct((1, heads, S, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 8, S, 128), jnp.bfloat16)
        text = str(jax.make_jaxpr(functools.partial(
            attn_ops.banded_causal_attention, window=window))(q, k, k))
        return text

    for S, chunk in ((1024, 256), (2048, 128), (4096, 64), (8192, 32)):
        assert f"f32[1,8,6,{chunk},{S}]" in scores(48, S, None), (S, chunk)
        assert "f32[1,8,9,256,768]" in scores(72, S, 512), S
    assert "f32[1,8,6,256,512]" in scores(48, 512, None)


@pytest.mark.parametrize("S,H,KV", [(128, 2, 1), (768, 6, 2), (1024, 3, 1)],
                         ids=["one_block", "blocks_of_256", "blocks_of_512"])
def test_full_layer_prefill_takes_the_tiled_kernel(S, H, KV, monkeypatch):
    """On a TPU a full layer of 128-wide heads goes through
    ``flash_gqa_fwd`` (here in interpret mode): grouped queries over kv
    heads that are not repeated, blocks above the diagonal never visited,
    the same numbers as the chunked path."""
    from distributed_lion_tpu.ops import pallas_flash_attn

    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.standard_normal((1, H, S, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, KV, S, 128)), jnp.float32)
            for _ in range(2))
    want = attn_ops.banded_causal_attention(q, k, v)
    calls = []
    real = pallas_flash_attn.flash_gqa_fwd

    def kernel(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_flash_attn, "flash_gqa_fwd", kernel)
    got = attn_ops.banded_causal_attention(q, k, v)
    assert calls == [(1, S, H * 128)]
    assert float(jnp.abs(got - want).max()) < 1e-5
    # a band, or heads of another width, keep the chunked path
    attn_ops.banded_causal_attention(q, k, v, window=64)
    attn_ops.banded_causal_attention(q[..., :64], k[..., :64], v[..., :64])
    assert len(calls) == 1


# ------------------------------------------------------------------ RoPE
def test_yarn_and_partial_rotary_angles_against_a_hand_table():
    """The published full-attention block: 64 of 128 dims rotate; dims 0-8
    keep their frequency, dims 18-31 have it divided by 128, a linear ramp
    between (low 9, high 18: ``64 ln(8192 / (2 pi r)) / (2 ln 500000)`` is
    9.04 at 32 turns and 17.49 at 1); cos and sin carry 1.4852."""
    body = dict(TINY["rope_parameters"]["full_attention"],
                original_max_position_embeddings=8192)
    rope = Rope.from_hf(body, 128)
    assert (rope.rotary_dim, rope.factor, rope.original_max) == (64, 128., 8192)
    inv = rope.inv_freq()
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
    for i in (10, 13, 17):
        ramp = (i - 9) / 9.0
        np.testing.assert_allclose(
            inv[i], plain[i] / 128 * ramp + plain[i] * (1 - ramp), rtol=1e-6)
    cos, sin = rope.angles(jnp.asarray([[0, 3]]))
    assert cos.shape == (1, 2, 32)
    np.testing.assert_allclose(cos[0, 0], 1.4852030263919618, rtol=1e-6)
    np.testing.assert_allclose(sin[0, 1, 0], 1.4852030263919618 * np.sin(3.0),
                               rtol=1e-5)
    # the reference reckons the same table on its own
    theirs, scale = ref.rope_inv_freq(body, 128)
    np.testing.assert_allclose(np.asarray(theirs), inv, rtol=1e-6)
    assert scale == pytest.approx(1.4852030263919618)
    # a window layer: every dim, theta 10,000, no scaling
    win = Rope.from_hf(TINY["rope_parameters"]["sliding_attention"], 128)
    np.testing.assert_allclose(win.inv_freq(),
                               10000.0 ** (-np.arange(64) / 64.0), rtol=1e-6)
    assert win.attention_factor == 1.0
    # rotate_half over the leading rot dims; the rest pass
    x = jnp.arange(8.0).reshape(1, 1, 1, 8)
    c, s = jnp.full((1, 1, 2), 0.5), jnp.full((1, 1, 2), 2.0)
    got = np.asarray(laguna.apply_rope_half(x, c, s))[0, 0, 0]
    np.testing.assert_allclose(
        got, [0 * .5 - 2 * 2, 1 * .5 - 3 * 2, 2 * .5 + 0 * 2, 3 * .5 + 1 * 2,
              4, 5, 6, 7])


# ----------------------------------------------------------- the share
@pytest.fixture(scope="module")
def whole():
    return build(WHOLE)


def shares(moe):
    """The two chips' parameter sets of one layer: banks 0-3 and 4-7, the
    router and the shared expert whole in both."""
    return [dict(moe, **{k: moe[k][lo:lo + 4]
                         for k in ("w_gate", "w_up", "w_down")})
            for lo in (0, 4)]


def test_the_two_shares_sum_to_the_whole_layer(whole):
    """Experts 0-3 and 4-7, each told its range, the shared expert counted
    once: the uncut reference's whole layer. And token conservation: the
    picks made are tokens x k on both chips, the rows computed on the two
    sum to them."""
    weights, params, cfg = whole
    moe = params["blocks"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)),
                    jnp.float32)
    valid = jnp.arange(40) < 37
    kw = dict(top_k=cfg.top_k, scale=cfg.routed_scale, valid=valid,
              return_counters=True)
    (lo, c_lo), (hi, c_hi) = (
        dropless_ffn(p, x, held=(first, 4), **kw)
        for p, first in zip(shares(moe), (0, 4)))
    shared = laguna._mlp(jnp.where(valid[:, None], x, 0), moe["shared"])
    want = ref._experts(x[None], weights["layers"][1], WHOLE, None)[0]
    assert float(jnp.abs((lo + hi - shared)[:37] - want[:37]).max()) < TOL
    assert not np.asarray(lo[37:]).any()
    assert int(c_lo["moe_routed"]) == int(c_hi["moe_routed"]) == 37 * 2
    assert int(c_lo["moe_assignments"]) + int(c_hi["moe_assignments"]) \
        == 37 * 2
    assert 0 < int(c_lo["moe_assignments"]) < 37 * 2
    assert int(c_lo["moe_experts_hit"]) <= 4 >= int(c_hi["moe_experts_hit"])


def test_holding_every_expert_is_the_layer_as_it_was(whole):
    """``held`` = all, and no ``held``: bit for bit, counters too."""
    _, params, cfg = whole
    moe = params["blocks"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((32, 64)),
                    jnp.float32)
    valid = jnp.arange(32) % 5 != 0
    kw = dict(top_k=cfg.top_k, scale=cfg.routed_scale, valid=valid,
              return_counters=True)
    plain, c0 = dropless_ffn(moe, x, **kw)
    told, c1 = dropless_ffn(moe, x, held=(0, 8), **kw)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(told))
    assert {k: int(v) for k, v in c0.items()} \
        == {k: int(v) for k, v in c1.items()}
    assert int(c0["moe_assignments"]) == int(c0["moe_routed"]) \
        == int(valid.sum()) * 2


@pytest.mark.parametrize("sizes", [[5, 0, 17, 9], [0, 0, 0, 0], [130, 3, 0, 60],
                                   [0, 300, 0, 1]])
def test_gmm_kernel_told_of_a_tail_leaves_its_tiles_alone(sizes):
    """``moe_gmm(tail=True)`` in interpret mode, three tiles of rows past
    the last group (the picks held elsewhere): the groups' rows are
    ``ragged_dot``'s, bit for bit the plain kernel's; the grid steps past
    the visits the groups need keep the last one's blocks and do nothing
    (what that saves is a chip's to show: PERF.md, PR 30)."""
    rng = np.random.default_rng(13)
    used = sum(sizes)
    m = -(-used // 128) * 128 + 3 * 128
    lhs = jnp.asarray(rng.normal(0, 1, (m, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(0, 1, (4, 128, 256)), jnp.float32)
    g = jnp.asarray(sizes, jnp.int32)
    got = pallas_moe_gmm.moe_gmm(lhs, rhs, g, tail=True, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, g)
    plain = pallas_moe_gmm.moe_gmm(lhs, rhs, g, interpret=True)
    assert got.shape == (m, 256)
    assert float(jnp.abs(got[:used] - want[:used]).max(initial=0)) < 1e-3
    np.testing.assert_array_equal(np.asarray(got[:used]),
                                  np.asarray(plain[:used]))


def test_an_expert_held_elsewhere_reads_no_bank(model):
    """NaN in every bank but one: a layer told it holds that one alone
    computes finite rows (the others are not even operands)."""
    _, params, cfg = model
    moe = params["blocks"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((16, 64)),
                    jnp.float32)
    one = dict(moe, **{k: moe[k][2:3] for k in ("w_gate", "w_up", "w_down")})
    out, c = dropless_ffn(one, x, top_k=2, scale=2.5, held=(2, 1),
                          return_counters=True)
    assert np.isfinite(np.asarray(out)).all()
    assert int(c["moe_experts_hit"]) <= 1
    assert int(c["moe_load_max"]) == int(c["moe_assignments"]) <= 16


# --------------------------------------------------------- the engine
def engine_of(model, **kw):
    _, params, cfg = model
    base = dict(max_seqs=3, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                prefill_cap_tokens=64, max_new_tokens=6, moe_stats=True)
    return ServingEngine(ServeModel.for_laguna(params, cfg),
                         ServeConfig(**dict(base, **kw)))


def requests(seed=12):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, tokens=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(37, 20), (9, 30), (12, 12),
                                        (40, 10), (5, 40)])]


@pytest.fixture(scope="module")
def batched(model):
    eng = engine_of(model)
    return eng, eng.run(requests(), arrivals={3: 2, 4: 5})


def test_engine_batched_equals_solo_and_the_reference(model, batched):
    weights = model[0]
    _, out = batched
    for req in requests():
        assert out[req.req_id].reason == "length"
        assert len(out[req.req_id].tokens) == req.max_new_tokens
    alone = engine_of(model)    # one engine, its programs compiled once
    for req in requests()[:3]:
        solo = alone.run([req])
        assert solo[req.req_id].tokens == out[req.req_id].tokens
    # served greedily, the tokens are the reference's own first choices
    req = requests()[0]
    seq = list(req.tokens) + out[0].tokens
    rows = np.zeros((1, 64), np.int32)
    rows[0, :len(seq)] = seq
    first = np.asarray(reference(weights, rows)[0].argmax(-1))
    assert first[len(req.tokens) - 1:len(seq) - 1].tolist() == out[0].tokens


def test_a_reused_slot_reads_nothing_of_its_old_ring(model):
    """One slot: a 40-token request fills and laps its ring, then a
    5-token request takes the slot with the ring as it was left; its tokens
    are those it gets on a fresh engine."""
    long, short = requests()[3], requests()[4]
    eng = engine_of(model, max_seqs=1)
    eng.run([long])
    ring_before = np.asarray(eng.pages[1]["k"])
    assert ring_before.any()
    got = eng.run([short])
    assert got[short.req_id].tokens \
        == engine_of(model, max_seqs=1).run([short])[short.req_id].tokens


def test_window_layers_hold_a_ring_whatever_the_length(model, batched):
    eng, _ = batched
    cfg, st = model[2], eng.stats
    shapes = [layer["k"].shape[0] for layer in eng.pages]
    assert shapes == [24, 3 * RING, 3 * RING, 3 * RING, 24]
    # admission and growth count full-layer pages only: everything back
    assert eng.tables.free_blocks == eng.tables.num_blocks == 24
    assert eng.tables.pages_allocated == st["freed_pages"]
    # a full layer's walk grows with the row, a window layer's stops at
    # the ring: rows of up to 57 tokens read up to 8 pages against 2
    assert st["kv_window_pages_read"] <= RING * st["decode_tokens"]
    assert st["kv_window_pages_read"] < 0.6 * st["kv_pages_read"]
    assert st["window_kernel_ticks"] == st["decode_attn_kernel_ticks"] == 0
    # token conservation through the engine: picks made, and rows here
    layers = cfg.n_layer - len(cfg.dense_layers)
    assert st["moe_routed"] == st["decode_tokens"] * cfg.top_k * layers
    assert st["moe_prefill_routed"] == (st["prefill_tokens"] * cfg.top_k
                                        * layers)
    assert 0.3 < st["moe_assignments"] / st["moe_routed"] < 0.7
    assert st["moe_experts_hit"] <= st["decode_ticks"] * layers * cfg.banks


@pytest.mark.parametrize("window", [8, 4], ids=["w8", "w4"])
def test_pages_read_by_hand(model, window):
    """One request of 9 prompt tokens and 6 outputs: 5 decode ticks at
    lengths 10..14 over pages of 4; a full layer reads ceil(L / 4) pages
    (the host's arithmetic), a window layer the pages from position L -
    window on: counted in the program from the walk the kernel is handed,
    so a program with another window reads another number."""
    _, params, cfg = model
    eng = ServingEngine(
        ServeModel.for_laguna(params, dataclasses.replace(cfg, window=window)),
        ServeConfig(max_seqs=3, block_size=4, max_blocks_per_seq=16,
                    prefill_cap_tokens=64, max_new_tokens=6, moe_stats=True))
    eng.run([Request(req_id=0, tokens=list(range(9)), max_new_tokens=6)])
    lengths = range(10, 15)
    assert eng.stats["decode_ticks"] == 5
    assert eng.stats["kv_pages_read"] == sum(-(-n // 4) for n in lengths)
    assert eng.stats["kv_window_pages_read"] == sum(
        -(-n // 4) - (n - window) // 4 for n in lengths) \
        == {8: 3 + 3 + 2 + 3 + 3, 4: 2 + 2 + 1 + 2 + 2}[window]


@pytest.mark.parametrize("kw,flag", [
    ({"prefix_cache": True}, "--prefix_cache"),
    ({"speculate": "ngram:2"}, "--speculate"),
    ({"tp": 2}, "--serve_tp"), ({"ep": 2}, "--serve_ep")])
def test_engine_refuses_what_a_ring_cannot_serve(model, kw, flag):
    with pytest.raises(ValueError, match=f"ring.*{flag}"):
        engine_of(model, **kw)


def test_engine_refuses_to_quantize_this_family(model):
    with pytest.raises(ValueError, match="serves on one device"):
        engine_of(model, quant="nf4")


def test_decode_tick_runs_the_kernel_for_both_kinds(model, monkeypatch):
    """The engine with the TPU's choices (kernels in interpret mode): the
    same tokens as the gather path's, and every decode tick counted for
    both layer kinds."""
    want = engine_of(model).run(requests()[:2])
    interpret_kernels(monkeypatch)
    eng = engine_of(model)
    got = eng.run(requests()[:2])
    assert {k: c.tokens for k, c in got.items()} \
        == {k: c.tokens for k, c in want.items()}
    assert eng.stats["window_kernel_ticks"] \
        == eng.stats["decode_attn_kernel_ticks"] \
        == eng.stats["decode_ticks"] > 0


# -------------------------------------------------------------------- CLI
def test_config_from_the_published_keys():
    path = os.path.join(ROOT, "benchmark", "configs", "laguna-s-2.1.json")
    cfg = LagunaConfig.named(path)
    assert (cfg.n_layer, cfg.n_experts, cfg.top_k) == (5, 256, 10)
    assert cfg.held == (0, 128) and cfg.banks == 128
    assert cfg.heads == (48, 72, 72, 72, 48)
    assert cfg.windowed == (False, True, True, True, False)
    assert cfg.window_layers == (1, 2, 3) and cfg.dense_layers == (0,)
    assert (cfg.vocab_size, cfg.d_model, cfg.d_ff) == (50176, 3072, 12288)
    assert (cfg.moe_d_ff, cfg.shared_d_ff, cfg.window) == (1024, 1024, 512)
    assert cfg.rope_full == LagunaConfig().rope_full
    assert cfg.rope_window == Rope(10000.0, 128)
    assert ring_pages(cfg.window, 16) == 33
    with pytest.raises(ValueError, match="not implemented"):
        LagunaConfig.from_hf(dict(TINY, gating="per-element"))
    with pytest.raises(ValueError, match="unknown laguna model_name"):
        LagunaConfig.named("laguna-m")


def test_run_serve_names_the_family():
    from distributed_lion_tpu.cli import run_generate, run_serve

    gen = run_generate.GenerateArguments(model_family="laguna",
                                         model_name="tiny", temperature=0.0,
                                         max_new_tokens=4)
    serve = run_serve.ServeArguments(max_seqs=2, block_size=8,
                                     max_blocks_per_seq=4)
    tok, engine = run_serve.build_engine(gen, serve)
    assert engine.model.family == "laguna"
    assert engine.model.window_layers == (1, 2, 3)
    out = engine.run([Request(req_id="a", tokens=tok.encode("The answer",
                                                             add_bos=False))])
    assert out["a"].reason == "length" and len(out["a"].tokens) == 4
    with pytest.raises(ValueError, match="serve it with run_serve"):
        run_generate.main(["--model_family", "laguna", "--model_name",
                           "tiny"])
