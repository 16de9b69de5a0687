"""The Xing4.0 serving path (models/xing: a residual of four mixed streams,
``ops/mhc`` and its kernels ``ops/pallas_mhc``, round YaRN-scaled latent
attention and dropless experts) at a tiny size on the CPU, seeded weights,
float32, against the benchmark's plain reference
(``benchmark/reference/xing4_0``: float32, nothing imported from the
package).

Tolerances. Program and reference compute the same float32 arithmetic in
another order (the mix's projection against three bfloat16 parts of ``phi``
whose sum is the float32 matrix, absorbed against expanded attention, sorted
grouped matmul against a masked scan over every expert); at these sizes
their logits agree to 2e-7 (logits up to 0.7) and 1e-4 leaves room for
another backend's sums. The kernels in interpret mode are held to
``ops/mhc``'s ``jax.numpy`` forms: the float32 coefficients to 1e-6 (the
two call the same element-wise function and take their sums in the same
order, and differ by an ulp or two where the backend contracts another
multiply-add in another loop), the bfloat16 stream to one rounding of its
largest value.
"""

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

from benchmark.families import xing4_0 as family  # noqa: E402
from distributed_lion_tpu.models.laguna import Rope  # noqa: E402
from distributed_lion_tpu.models.xing import (  # noqa: E402
    XingConfig,
    xing_decode_paged,
)
from distributed_lion_tpu.ops import mhc, pallas_mhc  # noqa: E402
from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.kv_cache import init_page_leaves  # noqa: E402

ref = family.reference
TINY = family.TINY
TOL = 1e-4
BLOCK, PER_SEQ = 8, 8
MIX = mhc.MixConfig()


@pytest.fixture(scope="module")
def model():
    """(reference weights, program params, XingConfig) at TINY, float32:
    the same values in both layouts."""
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 39), TINY, jnp.float32)
    cfg = XingConfig.from_hf(TINY, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32)
    return weights, family.to_program(weights, cfg.mix), cfg


# ------------------------------------------------------------- the mix
def a_mix(n_d, seed, diag=0.0, spread=0.5):
    """(float32 ``phi``, ``a``, ``b``) of one sublayer; ``diag`` on the
    mixing matrix's diagonal as the seeded weights have it (4) or not."""
    k = jax.random.split(jax.random.key(seed), 2)
    phi = jax.random.normal(k[0], (n_d, MIX.width)) * spread / math.sqrt(n_d)
    b = jax.random.normal(k[1], (MIX.width,)) * 0.5
    b = b.at[2 * MIX.n:].add(diag * jnp.eye(MIX.n).reshape(-1))
    return phi, jnp.asarray([1.0, 0.7, 1.3]), b


def ref_coeffs(X, phi, a, b, **knobs):
    """The reference's steps 1-2 as one ``[N, width]`` array."""
    cfg = dict(TINY, **knobs)
    pre, post, res = ref.mix_coeffs(
        X.reshape(X.shape[0], MIX.n, -1).astype(jnp.float32), phi, a, b, cfg)
    return jnp.concatenate([pre, post, res.reshape(res.shape[0], -1)], -1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coefficients_are_the_references(dtype):
    """``ops/mhc.mhc_coeffs`` (packed ``phi``, sums a lane tile at a time)
    against the reference's ``mix_coeffs`` on the same stream."""
    X = jax.random.normal(jax.random.key(0), (40, 4 * 64)).astype(dtype)
    phi, a, b = a_mix(4 * 64, 1, diag=4.0, spread=2.4)
    got = jax.jit(lambda X: mhc.mhc_coeffs(X, mhc.pack_phi(phi, MIX), a, b,
                                           MIX))(X)
    want = jax.jit(lambda X: ref_coeffs(X, phi, a, b))(X)
    assert got.shape == (40, mhc.COEF_LANES)
    assert float(jnp.abs(got[:, :MIX.width] - want).max()) < 2e-6
    assert float(jnp.abs(got[:, MIX.width:]).max()) == 0.0
    # the three bfloat16 parts sum to the float32 matrix, exactly
    parts = mhc.pack_phi(phi, MIX).astype(jnp.float32)
    w = MIX.width
    assert bool((parts[:, :w] + parts[:, w:2 * w] + parts[:, 2 * w:3 * w]
                 == phi).all())


def test_twenty_steps_reach_doubly_stochastic_where_the_matrix_mixes():
    """A mixing matrix with no dominant diagonal is doubly stochastic to
    1e-4 after 20 steps, and far from it after one. (With 4 on the diagonal,
    as the seeded weights have it, Sinkhorn's rate is the square of the
    limit's second singular value, near 0.9: 20 steps leave 1e-2, which
    ``mhc_res_defect_max`` reports and ``analysis/serve_check`` bounds.)"""
    X = jax.random.normal(jax.random.key(2), (64, 4 * 64))
    phi, a, b = a_mix(4 * 64, 3)
    live = jnp.ones((64,), bool)

    def defect(cfg):
        return float(jax.jit(lambda X: mhc.mhc_defect(mhc.mhc_coeffs(
            X, mhc.pack_phi(phi, cfg), a, b, cfg), live, cfg))(X))

    assert defect(MIX) < 1e-4
    assert defect(mhc.MixConfig(iters=1)) > 1e-2
    phi4, a4, b4 = a_mix(4 * 64, 3, diag=4.0, spread=2.4)
    seeded = float(jax.jit(lambda X: mhc.mhc_defect(mhc.mhc_coeffs(
        X, mhc.pack_phi(phi4, MIX), a4, b4, MIX), live, MIX))(X))
    assert 1e-4 < seeded < 0.1
    # rows without a token are not looked at
    assert float(mhc.mhc_defect(jnp.full((3, mhc.COEF_LANES), 9.0),
                                jnp.zeros((3,), bool), MIX)) == 0.0


def test_the_clamp_holds_and_the_eps_is_in_the_denominators():
    """``a_res`` of 1e4 drives ``Z`` far past +-30: the clamp keeps ``exp``
    finite (unclamped, ``exp(Z)`` overflows float32 and the first row sum is
    inf), and the result is the reference's with the same clamp. An
    ``hc_eps`` of 0.5 moves the coefficients as the reference's moves."""
    X = jax.random.normal(jax.random.key(4), (16, 4 * 64))
    phi, _, b = a_mix(4 * 64, 5, spread=2.4)
    a = jnp.asarray([1.0, 1.0, 1e4])
    got = jax.jit(lambda X: mhc.mhc_coeffs(X, mhc.pack_phi(phi, MIX), a, b,
                                           MIX))(X)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got[:, :MIX.width] - ref_coeffs(X, phi, a, b)
                         ).max()) < 2e-6
    wide = mhc.MixConfig(clamp=(-1e9, 1e9))
    loose = jax.jit(lambda X: mhc.mhc_coeffs(X, mhc.pack_phi(phi, wide), a,
                                             b, wide))(X)
    assert not bool(jnp.isfinite(loose).all())
    a = jnp.ones((3,))
    soft = mhc.MixConfig(eps=0.5)
    got = jax.jit(lambda X: mhc.mhc_coeffs(X, mhc.pack_phi(phi, soft), a, b,
                                           soft))(X)
    want = ref_coeffs(X, phi, a, b, hc_eps=0.5)
    assert float(jnp.abs(got[:, :MIX.width] - want).max()) < 2e-6
    assert float(jnp.abs(want - ref_coeffs(X, phi, a, b)).max()) > 1e-2


@pytest.mark.parametrize("rows,d", [(200, 256), (64, 128), (300, 512)])
def test_kernels_are_the_jax_numpy_forms(rows, d):
    """``mhc_pre`` and ``mhc_post`` in interpret mode against
    ``mhc_pre_xla`` / ``mhc_post_xla`` at row counts that are no multiple of
    the tile (200 rows in tiles of 128; 64 rows in one tile of 128, the
    decode tick's case; 300 in tiles of 256), rows without a token among
    them: coefficients to 1e-6, the stream to a bfloat16 rounding, a dead
    row's streams bit for bit as they were."""
    k = jax.random.split(jax.random.key(rows), 3)
    X = jax.random.normal(k[0], (rows, 4 * d)).astype(jnp.bfloat16)
    phi, a, b = a_mix(4 * d, 6, diag=4.0, spread=2.4)
    packed = mhc.pack_phi(phi, MIX)
    assert pallas_mhc.kernel_takes(X.shape, X.dtype, MIX)
    u0, c0 = jax.jit(lambda X: mhc.mhc_pre_xla(X, packed, a, b, MIX))(X)
    u1, c1 = pallas_mhc.mhc_pre(X, packed, a, b, MIX, interpret=True)
    assert c1.shape == (rows, mhc.COEF_LANES) and c1.dtype == jnp.float32
    assert float(jnp.abs(c0 - c1).max()) < 1e-6
    f32 = jnp.float32
    ulp = float(jnp.abs(u0.astype(f32)).max()) * 2.0 ** -7
    assert u1.dtype == jnp.bfloat16
    assert float(jnp.abs(u0.astype(f32) - u1.astype(f32)).max()) <= ulp
    y = jax.random.normal(k[1], (rows, d)).astype(jnp.bfloat16)
    valid = jnp.arange(rows) % 7 != 3
    o0 = jax.jit(lambda X: mhc.mhc_post_xla(X, y, c0, valid, MIX))(X)
    o1 = pallas_mhc.mhc_post(X, y, c0, valid, MIX, interpret=True)
    ulp = float(jnp.abs(o0.astype(f32)).max()) * 2.0 ** -7
    assert float(jnp.abs(o0.astype(f32) - o1.astype(f32)).max()) <= ulp
    assert bool((o1[~valid] == X[~valid]).all())
    assert not bool((o1[valid] == X[valid]).all())
    # the write-back, by hand, for one row
    n, r = MIX.n, 5
    xs = X[r].astype(f32).reshape(n, d)
    res = c0[r, 2 * n:2 * n + n * n].reshape(n, n)
    want = c0[r, n:2 * n, None] * y[r].astype(f32)[None] + res @ xs
    assert float(jnp.abs(o0[r].astype(f32).reshape(n, d) - want).max()) \
        <= ulp


def test_kernels_are_taken_on_a_tpu_at_whole_lane_tiles(monkeypatch):
    """``ops/mhc.mhc_pre`` / ``mhc_post`` choose: the kernels on a TPU for a
    bfloat16 stream whose streams are whole lane tiles, the ``jax.numpy``
    forms everywhere else (the test says "tpu" in the backend's place)."""
    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_mhc, "mhc_pre", lambda *a, **k: calls.append(
        "pre") or (None, None))
    monkeypatch.setattr(pallas_mhc, "mhc_post", lambda *a, **k: calls.append(
        "post"))
    phi, a, b = a_mix(4 * 128, 7)
    packed = mhc.pack_phi(phi, MIX)
    X = jnp.zeros((8, 4 * 128), jnp.bfloat16)
    mhc.mhc_pre(X, packed, a, b, MIX)
    mhc.mhc_post(X, X[:, :128], jnp.zeros((8, 128)), jnp.ones((8,), bool),
                 MIX)
    assert calls == ["pre", "post"]
    mhc.mhc_pre(X.astype(jnp.float32), packed, a, b, MIX)      # float32
    phi64 = mhc.pack_phi(a_mix(4 * 64, 7)[0], MIX)
    mhc.mhc_pre(jnp.zeros((8, 4 * 64), jnp.bfloat16), phi64, a, b, MIX)
    assert calls == ["pre", "post"]
    assert not pallas_mhc.kernel_takes((8, 4 * 128), jnp.bfloat16,
                                       mhc.MixConfig(n=8))     # 240 lanes
    # tiles: 256 rows of 4 x 3,584 fill the blocks' budget (in and out, to
    # the byte); a decode tick's 64 rows are one tile
    row = 4 * 3584 * 2
    assert pallas_mhc.tile_rows(4096, row + 3584 * 2 + 512, row * 64,
                                least=128) == 256
    assert pallas_mhc.tile_rows(4096, 2 * row + 3584 * 2 + 1024, 0,
                                least=16) == 256
    assert pallas_mhc.tile_rows(4096, 4 * row, 0, least=16) == 128
    assert pallas_mhc.tile_rows(64, row + 3584 * 2 + 512, row * 64,
                                least=128) == 128
    assert pallas_mhc.tile_rows(64, 2 * row + 3584 * 2 + 1024, 0,
                                least=16) == 64


# ------------------------------------------------------- the whole model
def pool(cfg, n_seq):
    pages = init_page_leaves(cfg.n_layer, n_seq * PER_SEQ, BLOCK,
                             {"kv": (1, cfg.latent_dim)}, jnp.float32)
    # shuffled ownership: every read has to go through the table
    tables = jnp.arange(n_seq * PER_SEQ, dtype=jnp.int32)[::-1].reshape(
        n_seq, PER_SEQ)
    return pages, tables


reference = jax.jit(lambda weights, rows: ref.forward(weights, rows, TINY))


def interpret_kernels(monkeypatch):
    """Take the TPU's choices on the CPU: the Mosaic kernels in interpret
    mode (the test says "tpu" in the backend's place, as
    tests/test_chip_compile.py does). The float32 stream keeps the mix on
    its ``jax.numpy`` forms: the mix's kernels have their own test."""
    import functools

    from distributed_lion_tpu.ops import pallas_mla_attn, pallas_moe_gmm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_mla_attn, "mla_paged_attn", functools.partial(
        pallas_mla_attn.mla_paged_attn, interpret=True))
    monkeypatch.setattr(pallas_moe_gmm, "moe_gmm", functools.partial(
        pallas_moe_gmm.moe_gmm, interpret=True))


@pytest.mark.parametrize("path", ["gather", "absorbed_kernel"])
def test_prefill_then_decode_is_the_references_full_forward(model, path,
                                                           monkeypatch):
    """A ragged prefill window, then one token a step at each row's own
    position through the latent pages: every step's logits are the
    reference's full forward pass at that position, and the counters count
    the rows with a token (``mhc_rows`` = rows x 2 sublayers x 2 layers).
    ``absorbed_kernel``: the S = 1 steps read the latent rows through
    ``mla_paged_attn`` (interpret mode) under YaRN's softmax scale."""
    weights, params, cfg = model
    if path == "absorbed_kernel":
        interpret_kernels(monkeypatch)
    program = jax.jit(lambda params, toks, pages, tables, pos, valid:
                      xing_decode_paged(params, toks, cfg, pages, tables,
                                        pos, valid, True))
    rows = np.random.default_rng(0).integers(0, 256, (2, 40)).astype(np.int32)
    want = reference(weights, rows)
    pages, tables = pool(cfg, 2)
    lens = jnp.asarray([24, 17], jnp.int32)
    valid = jnp.arange(24)[None, :] < lens[:, None]
    logits, pages, st = program(params, rows[:, :24], pages, tables,
                                jnp.zeros((2,), jnp.int32), valid)
    for r, n in enumerate([24, 17]):
        assert float(jnp.abs(logits[r, :n] - want[r, :n]).max()) < TOL
    assert int(st["mhc_rows"]) == (24 + 17) * 4
    assert int(st["moe_assignments"]) == (24 + 17) * cfg.top_k
    assert 100 < int(st["mhc_res_defect_max"]) < 100_000      # 1e-4 .. 0.1
    pos = np.asarray([24, 17])
    for _ in range(4):
        toks = rows[np.arange(2), pos][:, None]
        logits, pages, st = program(params, toks, pages, tables,
                                    jnp.asarray(pos, jnp.int32),
                                    jnp.ones((2, 1), bool))
        for r in range(2):
            assert float(jnp.abs(logits[r, 0] - want[r, pos[r]]).max()) < TOL
        assert int(st["mhc_rows"]) == 2 * 4
        pos += 1


@pytest.mark.parametrize("fault", ["sink1", "fp8"])
def test_the_controls_move_the_logits(model, fault):
    """One Sinkhorn step in place of 20, and fp8 matmuls, move the
    reference's own logits by a hundred times ``TOL`` and more: the
    comparison above would catch either."""
    weights = model[0]
    rows = np.random.default_rng(0).integers(0, 256, (2, 40)).astype(np.int32)
    low = jax.jit(lambda w, r: ref.forward(w, r, TINY, fault))(weights, rows)
    assert float(jnp.abs(low - reference(weights, rows)).max()) > 20 * TOL


def test_yarn_scale_and_frequencies():
    """The published configuration's softmax scale is 0.14468 and its rope
    angles are ``models/laguna.Rope``'s YaRN frequencies (which the
    reference computes for itself), cos and sin unscaled."""
    path = os.path.join(ROOT, "benchmark", "configs", "xing4.0-29b-a4b.json")
    cfg = XingConfig.named(path)
    assert abs(cfg.softmax_scale - 0.14468) < 5e-6
    assert abs(cfg.softmax_scale
               - (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192)) < 1e-12
    body = family.reference
    import json
    with open(path) as f:
        published = json.load(f)
    assert abs(body.softmax_scale(published) - cfg.softmax_scale) < 1e-12
    rope = cfg.rope
    assert rope == Rope(10000.0, 64, 64.0, 4096, 32.0, 1.0, 1.0)
    inv = rope.inv_freq()
    assert np.allclose(inv, np.asarray(body.yarn_inv_freq(published)),
                       rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(inv[:10], plain[:10], rtol=1e-6)       # fast dims kept
    assert np.allclose(inv[-8:], plain[-8:] / 64, rtol=1e-6)  # slow / factor
    cos, sin = rope.angles(jnp.asarray([[0, 5000]]))
    assert np.allclose(cos[0, 1], np.cos(5000 * inv), atol=2e-3)
    assert float(cos[0, 0].min()) == 1.0 and float(sin[0, 0].max()) == 0.0


def test_query_chunk_follows_the_scores_bytes():
    """``ops/attention.query_chunk``: every shape whose 256-query scores fit
    ``SCORE_BYTES`` keeps 256 (cell 5's 1,024 and 2,048 buckets, every
    short call); this family's 4,096 keys under 32 heads take 32 (16 MB)."""
    from distributed_lion_tpu.ops.attention import SCORE_BYTES, query_chunk

    assert SCORE_BYTES == 64 << 20
    assert query_chunk(1, 32, 2048, 2048) == 256      # 64 MiB: fits
    assert query_chunk(1, 32, 1024, 1024) == 256
    assert query_chunk(1, 32, 4096, 4096) == 32       # 128 MiB -> 16
    assert query_chunk(1, 32, 2048, 3072) == 32       # a whole table's width
    assert query_chunk(2, 4, 5, 4096) == 5            # a verify window
    assert query_chunk(1, 64, 8192, 65536) == 8       # never under 8


def test_config_from_the_published_keys():
    path = os.path.join(ROOT, "benchmark", "configs", "xing4.0-29b-a4b.json")
    cfg = XingConfig.named(path)
    assert (cfg.n_layer, cfg.first_dense, cfg.n_experts, cfg.top_k) \
        == (6, 1, 64, 4)
    assert (cfg.latent_dim, cfg.vocab_size, cfg.d_model) \
        == (576, 131072, 3584)
    assert (cfg.q_lora_rank, cfg.moe_d_ff, cfg.d_ff) == (768, 1024, 9216)
    assert cfg.mix == mhc.MixConfig(4, 20, 1e-6, (-30.0, 30.0), 1e-6)
    for key, value, names in [
            ("n_group", 8, "n_group=8"), ("topk_group", 4, "topk_group=4"),
            ("scoring_func", "softmax", "scoring_func='softmax'"),
            ("hc_mult", 0, "hc_mult=0"),
            ("rope_scaling", dict(TINY["rope_scaling"], type="linear"),
             "rope_scaling.type='linear'")]:
        with pytest.raises(ValueError, match="xing: " + names.replace(
                ".", r"\.") + " is not implemented"):
            XingConfig.from_hf(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="unknown xing model_name"):
        XingConfig.named("xing-29b")
    assert XingConfig.tiny() == XingConfig.from_hf(TINY)


# ----------------------------------------------------------------- engine
def engine_of(model, **kw):
    _, params, cfg = model
    base = dict(max_seqs=4, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                prefill_cap_tokens=64, moe_stats=True)
    base.update(kw)
    return ServingEngine(ServeModel.for_xing(params, cfg),
                         ServeConfig(**base))


def requests(seed=13):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, tokens=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=6)
            for i, n in enumerate([5, 17, 30, 9, 12])]


@pytest.fixture(scope="module")
def served(model):
    """A 64-slot engine with five requests in it, staggered."""
    eng = engine_of(model, max_seqs=64)
    return eng, eng.run(requests(), arrivals={3: 2, 4: 5})


def test_engine_tokens_are_the_references_first_choices(model, served):
    """Prefill then decode through ``ServingEngine`` (64 slots, run-ahead,
    five staggered requests among 59 empty slots): every served token is the
    float32 reference's first choice at its position."""
    weights = model[0]
    _, out = served
    rows = np.zeros((5, 40), np.int32)
    for req in requests():
        assert out[req.req_id].reason == "length"
        seq = list(req.tokens) + out[req.req_id].tokens
        rows[req.req_id, :len(seq)] = seq
    first = np.asarray(jax.jit(
        lambda r: ref.forward(weights, r, TINY).argmax(-1))(rows))
    for req in requests():
        n, m = len(req.tokens), req.max_new_tokens
        assert first[req.req_id, n - 1:n + m - 1].tolist() \
            == out[req.req_id].tokens, req.req_id


def test_engine_counters_count_rows_with_a_token(model, served):
    """``mhc_rows`` = (prompt tokens + decode rows with a request) x 2
    sublayers x 2 layers, whatever the 64 slots and the buckets pad;
    ``mhc_res_defect_max`` is the largest over every dispatch, reported and
    exported as a gauge; the latent pool is JoyAI's one leaf a layer."""
    eng, out = served
    prompts = sum(len(r.tokens) for r in requests())
    assert eng.stats["decode_tokens"] == 5 * 5
    assert eng.stats["mhc_rows"] == (prompts + eng.stats["decode_tokens"]) * 4
    assert eng.stats["moe_assignments"] + eng.stats[
        "moe_prefill_assignments"] == (prompts + 25) * 2
    assert 100 < eng.stats["mhc_res_defect_max"] < 100_000
    assert eng._gauge_snapshot()["mhc_res_defect"] \
        == eng.stats["mhc_res_defect_max"] / 1e6
    assert [sorted(p) for p in eng.pages] == [["kv"]] * 2
    assert eng.pages[0]["kv"].shape == (64 * PER_SEQ, BLOCK, 1, 128)
    from distributed_lion_tpu.analysis.serve_check import (
        MHC_DEFECT_LIMIT,
        check_counters,
    )

    assert check_counters(eng.stats) == []
    bad = check_counters(dict(eng.stats,
                              mhc_res_defect_max=MHC_DEFECT_LIMIT + 1))
    assert len(bad) == 1 and "mhc_res_defect_max" in bad[0]
    assert check_counters({"ticks": 3}) == []


def test_engine_refuses_to_shard_or_quantize_this_family(model):
    with pytest.raises(ValueError, match="serves on one device"):
        engine_of(model, tp=2)


def test_run_serve_names_the_family():
    from distributed_lion_tpu.cli import run_generate, run_serve

    gen = run_generate.GenerateArguments(model_family="xing",
                                         model_name="tiny", temperature=0.0,
                                         max_new_tokens=4)
    serve = run_serve.ServeArguments(max_seqs=2, block_size=8,
                                     max_blocks_per_seq=4)
    tok, engine = run_serve.build_engine(gen, serve)
    assert engine.model.family == "xing"
    out = engine.run([Request(req_id="a", tokens=tok.encode("The answer",
                                                             add_bos=False))])
    assert out["a"].reason == "length" and len(out["a"].tokens) == 4
    with pytest.raises(ValueError, match="serve it with run_serve"):
        run_generate.main(["--model_family", "xing", "--model_name", "tiny"])


def test_prefix_cache_and_speculation_serve_as_for_joyai(model, served):
    """The stream never reaches the cache, so what the latent leaf already
    serves under, it serves under here: the prefix cache (shared pages,
    copy on write: a prefill that starts past 0) and n-gram speculation (a
    verify window of k + 1 rows) give the plain engine's tokens."""
    rng = np.random.default_rng(17)
    head = rng.integers(0, 256, 19).tolist()
    reqs = [Request(req_id=i, tokens=head + rng.integers(0, 256, n).tolist(),
                    max_new_tokens=6) for i, n in enumerate([5, 9, 12])]
    plain = engine_of(model).run(list(reqs))
    eng = engine_of(model, prefix_cache=True, speculate="ngram:2")
    out = eng.run(list(reqs), arrivals={1: 3, 2: 3})
    for r in reqs:
        assert out[r.req_id].tokens == plain[r.req_id].tokens, r.req_id
    assert eng.stats["prefix_hits"] >= 2 and eng.stats["shared_tokens"] > 0
