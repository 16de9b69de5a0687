"""The dots3-note serving path (models/dots3: latent attention of two
geometries, a learned indexer with an index-key leaf beside the latent
pages, latent rows in a ring a slot, per-head gates, latents' rescale,
dropless experts told which they hold) at a tiny size on the CPU, seeded
weights, float32, against the benchmark's plain reference
(``benchmark/reference/dots3_note``: float32, nothing imported from the
package).

Tolerances. Program and reference compute the same float32 arithmetic in
another order (pages, index keys and ring against one masked row, a
threshold found by counting against ``lax.top_k``'s, a sorted grouped matmul
against a masked scan over the held experts); at these sizes their logits
agree to 3e-7 and 1e-4 leaves room. The kernels in interpret mode run
float32 too.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import dots3_note as family  # noqa: E402
from distributed_lion_tpu.models import dots3  # noqa: E402
from distributed_lion_tpu.models.dots3 import (  # noqa: E402
    Dots3Config,
    dots3_decode_paged,
)
from distributed_lion_tpu.ops import attention as attn_ops  # noqa: E402
from distributed_lion_tpu.ops import (  # noqa: E402
    dsa,
    pallas_dsa,
    pallas_mla_attn,
    pallas_moe_gmm,
)
from distributed_lion_tpu.ops.attention import ring_pages  # noqa: E402
from distributed_lion_tpu.parallel import expert  # noqa: E402
from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.kv_cache import init_page_leaves  # noqa: E402

ref = family.reference
TINY = family.TINY          # window 9, the 12 best kept; experts 0-3 of 8
WHOLE = dict(TINY, n_routed_experts=8, reduced=[], published={})
TOL = 1e-4
BLOCK, PER_SEQ = 8, 12      # rows of up to 96 tokens: 8 times index_topk,
# ten windows. Pages of 8 rows are whole float32 sublane tiles
RING = ring_pages(TINY["sliding_window_size"], BLOCK)      # 3 pages of 8


def build(body, **kw):
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 46), body, jnp.float32)
    # a LayerNorm bias that is not 0, so that it is seen
    for layer in weights["layers"]:
        if "idx_k_bias" in layer:
            layer["idx_k_bias"] = 0.1 * jnp.cos(
                jnp.arange(layer["idx_k_bias"].shape[0], dtype=jnp.float32))
    cfg = Dots3Config.from_hf(body, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32, page_run=0, **kw)
    return weights, family.to_program(weights), cfg


@pytest.fixture(scope="module")
def model():
    """(reference weights, program params, Dots3Config) at TINY, float32:
    the same values in both layouts."""
    return build(TINY)


def pool(cfg, n_seq, run=1):
    model = ServeModel.for_dots3(None, cfg)
    pages = init_page_leaves(
        cfg.n_layer, n_seq * PER_SEQ, BLOCK, model.page_leaves, jnp.float32,
        ring=(cfg.window_layers, n_seq * RING, model.window_leaves))
    # shuffled ownership, a run's pages consecutive (as the engine mints
    # them): every read has to go through the table
    heads = jnp.arange(n_seq * PER_SEQ // run, dtype=jnp.int32)[::-1] * run
    tables = (heads[:, None] + jnp.arange(run)).reshape(n_seq, PER_SEQ)
    # row b owns slot n_seq - 1 - b: a ring is found by the slot's id
    return pages, tables, jnp.arange(n_seq, dtype=jnp.int32)[::-1]


def rows_of(n_seq, width, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n_seq, width)).astype(np.int32)


# Model calls run COMPILED, one program a shape (ISSUE 35).
reference = jax.jit(lambda weights, rows, quant=None: ref.forward(
    weights, rows, TINY, quant), static_argnames="quant")
dropless_ffn = jax.jit(expert.moe_dropless_ffn, static_argnames=(
    "top_k", "scale", "return_counters", "held"))


def program_of(cfg):
    """``dots3_decode_paged`` at ``cfg``, compiled. Built anew in every
    test: a trace holds the choices the backend's name made."""
    return jax.jit(
        lambda params, toks, pages, tables, slots, pos, valid=None,
        logit_index=None, stats=False: dots3_decode_paged(
            params, toks, cfg, pages, tables, slots, pos, valid, stats,
            logit_index), static_argnames=("logit_index", "stats"))


def interpret_kernels(monkeypatch):
    """Take the TPU's choices on the CPU: the Mosaic kernels in interpret
    mode (the test says "tpu" in the backend's place, as
    tests/test_chip_compile.py does)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_dsa, "dsa_index", functools.partial(
        pallas_dsa.dsa_index, interpret=True))
    monkeypatch.setattr(pallas_mla_attn, "mla_paged_attn", functools.partial(
        pallas_mla_attn.mla_paged_attn, interpret=True))
    monkeypatch.setattr(pallas_moe_gmm, "moe_gmm", functools.partial(
        pallas_moe_gmm.moe_gmm, interpret=True))


# --------------------------------------------------------- the selection
def by_sort(scores, visible, topk):
    """A stable sort's answer, row by row in numpy."""
    out = np.zeros(scores.shape, bool)
    for i, (s, v) in enumerate(zip(scores, visible)):
        at = np.flatnonzero(v)
        order = at[np.argsort(-s[at].astype(np.float64), kind="stable")]
        out[i, order[:topk]] = True
    return out


@pytest.mark.parametrize("topk", [1, 5, 12, 40])
def test_kept_positions_is_a_sorts_answer(topk):
    """Random scores of both signs with planted ties (whole runs of equal
    values, 0.0 beside -0.0, ties AT the threshold), rows that see fewer
    than ``topk`` positions, exactly ``topk``, and more."""
    rng = np.random.default_rng(topk)
    T = 40
    scores = rng.standard_normal((24, T)).astype(np.float32)
    scores[:, ::3] = np.round(scores[:, ::3])       # many exact ties
    scores[3] = 0.0
    scores[4, ::2] = -0.0
    scores[5] = np.float32(2.5)                     # all tied: lowest kept
    scores[6, :20] = 1.0                            # a tie across the cut
    seen = np.arange(T)[None, :] <= rng.integers(0, T, 24)[:, None]
    seen[0], seen[1] = np.arange(T) < topk, np.arange(T) <= topk
    got = np.asarray(jax.jit(dsa.kept_positions, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(seen), topk))
    want = by_sort(scores, seen, topk)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(seen.sum(1), topk)).all()
    assert (got[5].nonzero()[0] == seen[5].nonzero()[0][:topk]).all()
    # the reference's own rule (lax.top_k's threshold, ties counted up)
    np.testing.assert_array_equal(
        np.asarray(ref.kept(jnp.asarray(scores), jnp.asarray(seen), topk)),
        want)


def test_sort_key_orders_as_floats_do():
    x = np.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf],
                   np.float32)
    k = np.asarray(dsa.sort_key(jnp.asarray(x)))
    assert (np.diff(k) >= 0).all() and k[3] == k[4]
    assert (np.diff(k)[[0, 1, 2, 4, 5, 6]] > 0).all()


def test_index_scores_by_groups_of_heads(monkeypatch):
    """The heads a group at a time give the sum all at once gives."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 3, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 3, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 50, 16)), jnp.float32)
    whole = dsa.index_scores(q, w, k)
    want = np.einsum("bsj,bsjt->bst", np.asarray(w), np.maximum(
        np.einsum("bsjd,btd->bsjt", np.asarray(q), np.asarray(k)), 0))
    assert np.abs(np.asarray(whole) - want).max() < 1e-4
    monkeypatch.setattr(dsa, "SCORE_BYTES", 2 * 3 * 2 * 50 * 4)
    parts = dsa.index_scores(q, w, k)
    assert np.abs(np.asarray(parts) - want).max() < 1e-4


# ------------------------------------------------- prefill and decode
def test_prefill_matches_the_reference(model):
    weights, params, cfg = model
    program = program_of(cfg)
    rows = rows_of(2, 64)
    pages, tables, ring = pool(cfg, 2)
    logits, _ = program(params, rows, pages, tables, ring,
                        jnp.zeros((2,), jnp.int32))
    want = reference(weights, rows)
    assert logits.shape == want.shape == (2, 64, TINY["vocab_size"])
    assert float(jnp.abs(logits - want).max()) < TOL
    one, _ = program(params, rows, pages, tables, ring,
                     jnp.zeros((2,), jnp.int32), logit_index=17)
    assert float(jnp.abs(one[:, 0] - want[:, 17]).max()) < TOL


@pytest.mark.parametrize("path", ["gather", "kernel_runs"])
def test_prefill_then_decode_through_pages_index_keys_and_ring(
        model, path, monkeypatch):
    """A ragged prefill window, then one token a step at each row's own
    position, out to 68 tokens: every step's logits are the reference's one
    full pass at that position. ``index_topk`` is 12 and the window 9 (a
    ring of 3 pages of 8): the prompts end under ``index_topk`` (5), at it
    (12), one past it (13), laps of the ring later (40) and in a dead slot
    (0: never admitted). The dead slot's pages, index keys and rings stay
    as they were, bit for bit. ``kernel_runs``: the S = 1 steps score, select and
    attend through ``dsa_index`` / ``dsa_attn`` / ``window_mla_attn`` in
    interpret mode, over pages minted in aligned runs of two."""
    weights, params, cfg = model
    run = 1
    if path != "gather":
        interpret_kernels(monkeypatch)
        assert attn_ops.paged_kernel_applies(1, (5 * RING, BLOCK, 1, 128),
                                             jnp.float32)
    if path == "kernel_runs":
        run, cfg = 2, dataclasses.replace(cfg, page_run=2 * BLOCK)
    program = program_of(cfg)
    rows = rows_of(5, 96, seed=1)
    want = np.asarray(reference(weights, rows))
    plens = np.asarray([5, 12, 13, 40, 0])
    live = plens > 0
    pages, tables, ring = pool(cfg, 5, run)
    # what a dead slot holds must stay: fill everything with a loud value
    pages = jax.tree.map(lambda x: x + 7.0, pages)
    valid = jnp.arange(48)[None, :] < jnp.asarray(plens)[:, None]
    window, pages = program(params, rows[:, :48], pages, tables, ring,
                            jnp.zeros((5,), jnp.int32), valid)
    for i, n in enumerate(plens[live]):
        assert np.abs(np.asarray(window[i, :n]) - want[i, :n]).max() < TOL
    step = jax.jit(lambda toks, pages, pos: dots3_decode_paged(
        params, toks, cfg, pages, tables, ring, pos,
        jnp.asarray(live)[:, None], True))
    kept = []
    for j in range(28):
        pos = plens + j
        logits, pages, st = step(rows[np.arange(5), pos][:, None], pages,
                                 jnp.asarray(pos, jnp.int32))
        got = np.asarray(logits[:, 0])[live]
        assert np.abs(got - want[np.arange(5), pos][live]).max() < TOL, j
        # rows x 2 full layers: every visible key, or the 12 best
        assert int(st["dsa_rows"]) == 4 * 2
        assert int(st["dsa_keys_visible"]) == 2 * int((pos[live] + 1).sum())
        assert int(st["dsa_keys_kept"]) == 2 * int(
            np.minimum(pos[live] + 1, 12).sum())
        kept.append(int(st["kv_window_pages_read"]))
    assert max(kept) <= 4 * RING and min(kept) >= 4
    # the dead slot (row 4: slot 0, the last table row) was never written
    dead_pages = np.asarray(tables[4])
    for layer in pages:
        if "ik" in layer:
            for leaf in layer.values():
                assert (np.asarray(leaf)[dead_pages] == 7.0).all()
        else:
            assert (np.asarray(layer["kv"])[:RING] == 7.0).all()


def test_decode_selects_what_the_prefill_masks(model):
    """At one position the decode tick's kept set (scores from the cached
    index keys) is the prefill's (scores from its fresh keys): compared
    through the layer's output, and position for position through
    ``kept_positions`` on both paths' scores."""
    weights, params, cfg = model
    rows = rows_of(1, 48, seed=3)
    pages, tables, ring = pool(cfg, 1)
    program = program_of(cfg)
    _, filled = program(params, rows[:, :40], pages, tables, ring,
                        jnp.zeros((1,), jnp.int32))
    # layer 0's indexer by hand on both sides
    p0, g = params["blocks"][0], cfg.full

    @jax.jit
    def by_hand(rows):
        x = params["wte"][rows]
        u = dots3._rms_norm(x, p0["ln_attn"], cfg.rms_eps)
        c_q = dots3._rms_norm(u @ p0["attn"]["wq_a"], p0["attn"]["q_norm"],
                              g.rms_eps) * g.rescale[0]
        cos, sin = dots3.rope_angles(41, g.qk_rope_head_dim, g.rope_theta)
        qi, wi, ki = dots3._indexer(u, c_q, p0["index"], cfg, cos[None],
                                    sin[None])
        return dsa.index_scores(qi[:, 40:], wi[:, 40:], ki)[0, 0], ki

    fresh, ki = by_hand(rows[:, :41])                             # [41]
    # the cached keys of positions 0..39 are layer 0's leaf
    cached = attn_ops.paged_gather_kv(filled[0]["ik"], tables)[0, :40, 0, :16]
    assert float(jnp.abs(cached - ki[0, :40]).max()) < 1e-6
    seen = jnp.arange(41) <= 40
    mine = dsa.kept_positions(fresh, seen, cfg.index_topk)
    theirs = ref.kept(fresh[None], seen[None], cfg.index_topk)[0]
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert int(mine.sum()) == 12 and not bool(mine[-12:].all())


def test_a_wrong_window_rescale_gate_or_selection_fails_the_comparison(model):
    """What the comparisons above are for (the window's two sides are
    ``test_latent_ring_walk_reads_what_the_window_sees``'s). No rescale, no gate, a program that
    attends every position moves the logits by far more than the tolerance; the reference's
    own controls ``nosel`` and ``noresc`` are the same two faults."""
    weights, params, cfg = model
    rows = rows_of(1, 64, seed=5)
    want = reference(weights, rows)
    pages, tables, ring = pool(cfg, 1)
    zero = jnp.zeros((1,), jnp.int32)
    one = dataclasses.replace(cfg.full, rescale=(1.0, 1.0))
    wrongs = {
        "no rescale": dict(full=one, swa=dataclasses.replace(
            cfg.swa, rescale=(1.0, 1.0))),
        "no selection": dict(index_topk=4096)}
    got = {}
    for name, wrong in wrongs.items():
        bad = dataclasses.replace(cfg, **wrong)
        got[name], _ = program_of(bad)(params, rows, pages, tables, ring,
                                       zero)
        assert float(jnp.abs(got[name] - want).max()) > 50 * TOL, name
    ungated = jax.tree.map(lambda x: x, params)
    for block in ungated["blocks"]:
        block["attn"] = {k: v for k, v in block["attn"].items() if k != "wg"}
    bare, _ = program_of(cfg)(ungated, rows, pages, tables, ring, zero)
    assert float(jnp.abs(bare - want).max()) > 50 * TOL
    for name, control in (("no selection", "nosel"),
                          ("no rescale", "noresc")):
        assert float(jnp.abs(
            got[name] - reference(weights, rows, control)).max()) < TOL


def test_the_two_geometries_cache_rows(model):
    """A full layer's page row is ``[c_kv (32, scaled) | k_rope (8)]`` in
    128 lanes with an index key of 16 in 128 beside it under the same page
    id; a sliding layer's ring row ``[c_kv (48, scaled) | k_rope (8)]``, 56
    values in another leaf, found by the slot."""
    weights, params, cfg = model
    rows = rows_of(1, 16, seed=7)
    pages, tables, ring = pool(cfg, 1)
    _, filled = program_of(cfg)(params, rows, pages, tables, ring,
                                jnp.zeros((1,), jnp.int32))
    assert [sorted(layer) for layer in filled] == [
        ["ik", "kv"], ["kv"], ["kv"], ["kv"], ["ik", "kv"]]
    assert filled[0]["kv"].shape == (PER_SEQ, BLOCK, 1, 128)
    assert filled[0]["ik"].shape == (PER_SEQ, BLOCK, 1, 128)
    assert filled[1]["kv"].shape == (RING, BLOCK, 1, 128)
    x = weights["embed"][rows[0]]
    for layer, width, rank in ((0, 40, 32), (1, 56, 48)):
        w = weights["layers"][layer]
        u = ref._rms_norm(x, w["input_norm"], 1e-5) if layer == 0 else None
        leaf = np.asarray(filled[layer]["kv"])
        table = np.asarray(tables[0]) if layer == 0 else np.arange(RING)
        got = leaf[table[:2]].reshape(16, 128)
        assert not got[:, width:].any() and got[:, :width].all()
        if layer == 0:
            kv = u @ w["kv_a"]
            c = ref._rms_norm(kv[:, :rank], w["kv_a_norm"], 1e-5) \
                * ref.lora_rescale(TINY, rank)
            assert np.abs(got[:, :rank] - np.asarray(c)).max() < 1e-5
            k_r = ref._rope(kv[:, rank:], 8e7)
            assert np.abs(got[:, rank:width] - np.asarray(k_r)).max() < 1e-5
            ki = ref.index_rope(ref._layer_norm(
                u @ w["idx_k"], w["idx_k_norm"], w["idx_k_bias"], 1e-5), TINY)
            ik = np.asarray(filled[0]["ik"])[table[:2]].reshape(16, 128)
            assert np.abs(ik[:, :16] - np.asarray(ki)).max() < 1e-5
            assert not ik[:, 16:].any()
    assert ref.lora_rescale(TINY, 32) == pytest.approx(2 ** 0.5) \
        == dots3.lora_rescale(64, 32)
    assert ref.lora_rescale(dict(TINY, apply_mla_qkv_lora_rescale=False),
                            32) == 1.0


# ----------------------------------------------------------- the kernels
@pytest.mark.parametrize("run", [1, 2], ids=["pages", "runs"])
def test_index_kernel_matches_the_gathered_rows(run):
    """``dsa_index`` in interpret mode over rows of 0 (a dead slot), 1, a
    page's edge and several blocks of the walk, against
    ``ops/dsa.index_scores`` over the gathered rows."""
    rng = np.random.default_rng(11)
    B, Hi, W, bs, nb = 5, 4, 128, 8, 40
    NB = B * nb
    ik = jnp.asarray(rng.standard_normal((NB, bs, 1, W)), jnp.float32)
    heads = rng.permutation(NB // run)[:B * nb // run].reshape(B, -1) * run
    tables = jnp.asarray((heads[:, :, None] + np.arange(run)).reshape(B, nb),
                         jnp.int32)
    tables = tables.at[0].set(NB)                         # a dead slot
    q = jnp.asarray(rng.standard_normal((B, Hi, W)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, Hi)), jnp.float32)
    lengths = jnp.asarray([0, 1, 8, 129, 320], jnp.int32)
    runs, ids = dsa.by_runs(ik, tables, run)
    got = pallas_dsa.dsa_index(q, w, runs, ids, lengths, interpret=True)
    want = dsa.index_scores(
        q[:, None], w[:, None],
        attn_ops.paged_gather_kv(ik, tables)[:, :, 0])[:, 0]
    seen = np.arange(nb * bs)[None, :] < np.asarray(lengths)[:, None]
    assert got.shape == want.shape == (B, nb * bs)
    assert np.abs(np.where(seen, np.asarray(got) - np.asarray(want), 0)
                  ).max() < 1e-4


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)],
                         ids=["bf16", "f32"])
def test_kept_and_windowed_walks_match_a_plain_softmax(dtype, tol):
    """``mla_paged_attn`` under a mask (``dsa_attn``) and from a first row on
    (``window_mla_attn``) in interpret mode against a float32 softmax over
    the rows the mask or the window leaves: rows whose first blocks hold no
    kept position, a row that keeps nothing but its last position, a dead
    row."""
    rng = np.random.default_rng(12)
    bs = 16 if dtype == jnp.bfloat16 else 8
    B, H, W, nb = 4, 6, 128, 20
    NB = B * nb
    kv = jnp.asarray(rng.standard_normal((NB, bs, 1, W)), dtype)
    tables = jnp.asarray(rng.permutation(NB).reshape(B, nb), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, W)), dtype)
    lengths = jnp.asarray([0, 3, 17 * bs + 5, nb * bs], jnp.int32)
    T = nb * bs
    keep = rng.random((B, T)) < 0.2
    keep[2, :16 * bs + 9] = False          # first blocks all masked
    keep[3] = np.arange(T) == T - 1
    keep &= np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    rows = np.asarray(attn_ops.paged_gather_kv(kv, tables)[:, :, 0],
                      np.float32)
    qf = np.asarray(q, np.float32)

    def plain(mask):
        s = np.einsum("bhw,btw->bht", qf, rows) * 0.25
        s = np.where(mask[:, None], s, -np.inf)
        p = np.exp(s - np.where(mask.any(1)[:, None, None],
                                s.max(-1, keepdims=True), 0))
        p = np.where(mask[:, None], p, 0)
        return np.einsum("bht,btw->bhw", p / np.maximum(
            p.sum(-1, keepdims=True), 1e-30), rows)

    got = pallas_mla_attn.mla_paged_attn(
        q, kv, tables, lengths, keep=jnp.asarray(keep), scale=0.25,
        interpret=True, name="dsa_attn")
    assert np.abs(np.asarray(got, np.float32) - plain(keep)).max() < tol
    if dtype == jnp.bfloat16:
        return
    starts = jnp.asarray([0, 2, bs - 1, 5], jnp.int32)
    seen = (np.arange(T)[None, :] < np.asarray(lengths)[:, None]) \
        & (np.arange(T)[None, :] >= np.asarray(starts)[:, None])
    got = pallas_mla_attn.mla_paged_attn(
        q, kv, tables, lengths, starts=starts, scale=0.25, interpret=True,
        name="window_mla_attn")
    assert np.abs(np.asarray(got, np.float32) - plain(seen)).max() < tol
    # without either operand the program is the one it was
    base = pallas_mla_attn.mla_paged_attn(q, kv, tables, lengths, scale=0.25,
                                          interpret=True)
    every = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    assert np.abs(np.asarray(base, np.float32) - plain(every)).max() < tol
    lowered = jax.jit(functools.partial(
        pallas_mla_attn.mla_paged_attn, scale=0.25, interpret=True)).lower(
            q, kv, tables, lengths).as_text()
    assert "mla_paged_attn" in lowered and "dsa_attn" not in lowered


def test_prefill_kernel_matches_the_plain_masked_softmax(monkeypatch):
    """``dsa_prefill`` in interpret mode over 2,048 positions (four query
    tiles against two key tiles: tiles above the diagonal skipped, a tile
    that keeps nothing for half its rows) against a plain masked softmax;
    then ``dsa_prefill_attention`` with the TPU's choice against its own XLA
    walk, counters too."""
    rng = np.random.default_rng(21)
    H, S, dk, dv = 2, 2048, 24, 128
    q, k = (jnp.asarray(rng.standard_normal((H, S, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((H, S, dv)), jnp.float32)
    t = np.arange(S)
    keep = (rng.random((S, S)) < 0.1) | (t[:, None] == t[None, :])
    keep &= t[None, :] <= t[:, None]
    keep[1024:1300, :1024] = False          # a whole tile's rows keep none
    got = pallas_dsa.dsa_prefill(q, k, v, jnp.asarray(keep, jnp.int8),
                                 scale=0.2, interpret=True)
    s = np.einsum("hsd,htd->hst", np.asarray(q), np.asarray(k)) * 0.2
    s = np.where(keep[None], s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hst,htd->hsd", pr / pr.sum(-1, keepdims=True),
                     np.asarray(v))
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert pallas_dsa.prefill_takes(12288, 128)
    assert not pallas_dsa.prefill_takes(512, 128)
    # the whole prefill path with the TPU's choice, against the XLA walk
    Hi, di, topk = 4, 16, 64
    S = 1024
    qi = jnp.asarray(rng.standard_normal((1, S, Hi, di)), jnp.float32)
    wi = jnp.asarray(rng.standard_normal((1, S, Hi)), jnp.float32)
    ki = jnp.asarray(rng.standard_normal((1, S, di)), jnp.float32)
    args = (q[None, :, :S], k[None, :, :S], v[None, :, :S], qi, wi, ki,
            jnp.asarray([1000], jnp.int32))
    walk = jax.jit(functools.partial(dsa.dsa_prefill_attention, topk=topk,
                                     scale=0.2))
    plain, c0 = walk(*args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_dsa, "dsa_prefill", functools.partial(
        pallas_dsa.dsa_prefill, interpret=True))
    tiled, c1 = jax.jit(functools.partial(
        dsa.dsa_prefill_attention, topk=topk, scale=0.2))(*args)
    assert float(jnp.abs(tiled - plain).max()) < 1e-4
    assert {n: int(x) for n, x in c0.items()} \
        == {n: int(x) for n, x in c1.items()}
    assert int(c1["dsa_keys_kept"]) == sum(min(t + 1, topk)
                                           for t in range(1000))


@pytest.mark.parametrize("window", [9, 16, 17])
def test_latent_ring_walk_reads_what_the_window_sees(window, monkeypatch):
    """Latent rows written through ``ring_scatter_kv`` as a prefill writes
    them and read back a decode row at a time, gather path and kernel
    (interpret mode), against a softmax over exactly the last ``window``
    rows of the plain sequence; a window one short or one long moves it."""
    rng = np.random.default_rng(window)
    bs, H, W, width = 8, 3, 128, 56
    ends = [3, window - 1, window, 5 * bs - 1, 5 * bs, 91]
    B, T = len(ends), max(ends) + 1
    rows = rng.standard_normal((B, T, width)).astype(np.float32)
    q = rng.standard_normal((B, H, width)).astype(np.float32)
    pos = jnp.asarray(ends, jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)[::-1]

    def want(win):
        out = np.zeros((B, H, width), np.float32)
        for b, p in enumerate(ends):
            lo = max(p - win + 1, 0)
            s = q[b] @ rows[b, lo:p + 1].T * 0.3
            w = np.exp(s - s.max(1, keepdims=True))
            out[b] = (w / w.sum(1, keepdims=True)) @ rows[b, lo:p + 1]
        return out

    leaf = jnp.zeros((B * ring_pages(window, bs), bs, 1, W), jnp.float32)
    leaf = attn_ops.ring_scatter_kv(
        leaf, slots, jnp.zeros_like(pos), jnp.asarray(rows)[:, :, None],
        pos + 1, window=window)
    monkeypatch.setattr(pallas_mla_attn, "mla_paged_attn", functools.partial(
        pallas_mla_attn.mla_paged_attn, interpret=True))
    q_abs = jnp.pad(jnp.asarray(q), ((0, 0), (0, 0), (0, W - width)))
    got, read = attn_ops.ring_mla_decode_attention(
        q_abs, leaf, slots, pos, window=window, scale=0.3)
    got = np.asarray(got)[..., :width]
    assert np.abs(got - want(window)).max() < 1e-5
    assert np.abs(got - want(window - 1)).max() > 1e-3
    assert np.abs(got - want(window + 1)).max() > 1e-3
    assert np.asarray(read).tolist() == [
        p // bs - max(p - window + 1, 0) // bs + 1 for p in ends]


# ---------------------------------------------------------- the share test
@pytest.fixture(scope="module")
def whole():
    return build(WHOLE)


def test_the_eight_shares_sum_to_the_whole_layer(whole):
    """Experts 0, 1, .. 7, each chip told its one, the shared expert counted
    once: the uncut reference's whole layer. And token conservation: the
    picks made are tokens x k on every chip, the rows computed on the eight
    sum to them."""
    weights, params, cfg = whole
    moe = params["blocks"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)),
                    jnp.float32)
    valid = jnp.arange(40) < 37
    kw = dict(top_k=cfg.top_k, scale=cfg.routed_scale, valid=valid,
              return_counters=True)
    total, rows_here = 0, 0
    for first in range(8):
        share = dict(moe, **{k: moe[k][first:first + 1]
                             for k in ("w_gate", "w_up", "w_down")})
        out, c = dropless_ffn(share, x, held=(first, 1), **kw)
        total = total + out
        rows_here += int(c["moe_assignments"])
        assert int(c["moe_routed"]) == 37 * 2
    shared = dots3._mlp(jnp.where(valid[:, None], x, 0), moe["shared"])
    want = ref._experts(x[None], weights["layers"][1], WHOLE, None)[0]
    assert float(jnp.abs((total - 7 * shared)[:37] - want[:37]).max()) < TOL
    assert rows_here == 37 * 2
    # the cut's own share (experts 0-3 of 8) is the reference's cut layer
    w4, p4, c4 = build(TINY)
    got = dropless_ffn(p4["blocks"][1]["moe"], x, top_k=2, scale=1.0,
                       valid=valid, held=(0, 4))
    want4 = ref._experts(x[None], w4["layers"][1], TINY, None)[0]
    assert float(jnp.abs(got[:37] - want4[:37]).max()) < TOL
    assert c4.held == (0, 4) and cfg.held is None


# --------------------------------------------------------------- the engine
def requests():
    rng = np.random.default_rng(9)
    return [Request(req_id=i, tokens=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(20, 10), (5, 30), (40, 8),
                                        (13, 12), (60, 20)])]


def engine_of(model, **kw):
    _, params, cfg = model
    base = dict(max_seqs=3, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                prefill_cap_tokens=64, moe_stats=True)
    base.update(kw)
    return ServingEngine(ServeModel.for_dots3(params, cfg),
                         ServeConfig(**base))


@pytest.fixture(scope="module")
def served(model):
    """(engine, its batched run's completions, the stats after it, the
    first two requests served again ALONE by the same engine: its programs
    compiled once, its slots holding what the batched run left in them)."""
    eng = engine_of(model)
    out = eng.run(requests(), arrivals={3: 2, 4: 5})
    stats = dict(eng.stats)
    solo = {r.req_id: eng.run([r])[r.req_id].tokens for r in requests()[:2]}
    return eng, out, stats, solo


def test_engine_batched_equals_solo_and_the_reference(model, served):
    """Batched = solo in a REUSED slot (its pages freed, its rings as the
    last request left them) = the reference's own first choices."""
    weights = model[0]
    _, out, _, solo = served
    for req in requests():
        assert out[req.req_id].reason == "length"
        assert len(out[req.req_id].tokens) == req.max_new_tokens
    for rid, tokens in solo.items():
        assert tokens == out[rid].tokens
    for req in (requests()[1], requests()[4]):
        seq = list(req.tokens) + out[req.req_id].tokens
        rows = np.zeros((1, 96), np.int32)
        rows[0, :len(seq)] = seq
        first = np.asarray(reference(weights, rows)[0].argmax(-1))
        assert first[len(req.tokens) - 1:len(seq) - 1].tolist() \
            == out[req.req_id].tokens


def test_engine_holds_three_kinds_of_leaf_and_counts(model, served):
    eng, _, st, _ = served
    cfg = model[2]
    assert [sorted(layer) for layer in eng.pages] == [
        ["ik", "kv"], ["kv"], ["kv"], ["kv"], ["ik", "kv"]]
    assert [layer["kv"].shape[0] for layer in eng.pages] \
        == [36, 3 * RING, 3 * RING, 3 * RING, 36]
    assert np.asarray(eng.pages[1]["kv"]).any()
    # admission and growth count full-layer pages only: everything back
    assert eng.tables.free_blocks == eng.tables.num_blocks == 36
    assert st["dsa_rows"] == 2 * st["decode_tokens"]
    assert st["dsa_keys_kept"] < st["dsa_keys_visible"]
    assert st["dsa_keys_kept"] <= 12 * st["dsa_rows"]
    assert 0 < st["kv_window_pages_read"] <= RING * st["decode_tokens"]
    assert st["mla_kernel_ticks"] == st["window_kernel_ticks"] == 0
    layers = cfg.n_layer - cfg.first_dense
    assert st["moe_routed"] == st["decode_tokens"] * cfg.top_k * layers
    assert st["moe_prefill_routed"] == (st["prefill_tokens"] * cfg.top_k
                                        * layers)
    assert 0.3 < st["moe_assignments"] / st["moe_routed"] < 0.7


def test_pages_in_runs_and_a_top_bucket_between_two_powers_of_two(
        model, served):
    """Pages minted in aligned runs of the decode walk's copy, and
    ``prefill_top_bucket``: a prompt of up to 48 tokens pads to 48, not 64;
    a longer one pads as it did; neither moves a token."""
    from distributed_lion_tpu.serve.kv_cache import bucket_tokens

    assert [bucket_tokens(n, 8, 12, 48) for n in (8, 9, 32, 33, 48, 49, 96)] \
        == [8, 16, 32, 48, 48, 64, 96]
    assert [bucket_tokens(n, 8, 12) for n in (33, 48, 49)] == [64, 64, 64]
    _, params, cfg = model
    eng = ServingEngine(
        ServeModel.for_dots3(params, dataclasses.replace(
            cfg, page_run=2 * BLOCK)),
        ServeConfig(max_seqs=3, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                    prefill_cap_tokens=64, prefill_top_bucket=48))
    assert eng.tables.run_pages == 2
    assert eng._buckets() == {8, 16, 32, 48, 64, 96}
    got = eng.run(requests()[:3])                 # 20, 5, 40 prompt tokens
    assert eng.stats["padded_prefill_tokens"] == 32 + 8 + 48
    assert {k: c.tokens for k, c in got.items()} \
        == {k: served[1][k].tokens for k in got}
    with pytest.raises(ValueError, match="whole pages"):
        engine_of(model, prefill_top_bucket=44)


@pytest.mark.parametrize("kw,flag", [
    ({"prefix_cache": True}, "--prefix_cache"),
    ({"speculate": "ngram:2"}, "--speculate"),
    ({"tp": 2}, "--serve_tp"), ({"ep": 2}, "--serve_ep")])
def test_engine_refuses_what_the_two_leaves_cannot_serve(model, kw, flag):
    with pytest.raises(ValueError,
                       match=f"index key.*'ik'.*'kv'.*ring of 9.*{flag}"):
        engine_of(model, **kw)


def test_engine_refuses_to_quantize_this_family(model):
    with pytest.raises(ValueError, match="serves on one device"):
        engine_of(model, quant="nf4")


# -------------------------------------------------------------------- CLI
def test_config_from_the_published_keys():
    path = os.path.join(ROOT, "benchmark", "configs", "dots3-note-prev.json")
    cfg = Dots3Config.named(path)
    assert (cfg.n_layer, cfg.n_experts, cfg.top_k) == (5, 256, 8)
    assert cfg.held == (0, 32) and cfg.banks == 32
    assert cfg.windowed == (False, True, True, True, False)
    assert cfg.window_layers == (1, 2, 3) and cfg.full_layers == (0, 4)
    assert (cfg.vocab_size, cfg.d_model, cfg.d_ff) == (19008, 5120, 13824)
    assert (cfg.moe_d_ff, cfg.window, cfg.first_dense) == (1536, 513, 1)
    assert cfg.full == Dots3Config().full and cfg.swa == Dots3Config().swa
    assert (cfg.full.latent_dim, cfg.swa.latent_dim) == (576, 1088)
    assert cfg.full.rescale == (5 ** 0.5, 10 ** 0.5)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) \
        == (64, 128, 2048)
    assert ring_pages(cfg.window, 16) == 34
    for key, value in (("rope_scaling", {"type": "yarn"}), ("n_group", 8),
                       ("attention_gate_type", "elementwise"),
                       ("swa_attention_gate_type", "none")):
        with pytest.raises(ValueError, match="not implemented"):
            Dots3Config.from_hf(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="unknown dots3 model_name"):
        Dots3Config.named("dots3-m")


def test_run_serve_names_the_family():
    from distributed_lion_tpu.cli import run_generate, run_serve

    gen = run_generate.GenerateArguments(model_family="dots3",
                                         model_name="tiny", temperature=0.0,
                                         max_new_tokens=4)
    serve = run_serve.ServeArguments(max_seqs=2, block_size=8,
                                     max_blocks_per_seq=4)
    tok, engine = run_serve.build_engine(gen, serve)
    assert engine.model.family == "dots3"
    assert engine.model.window_layers == (1, 2, 3)
    assert engine.model.index_leaves == ("ik",)
    out = engine.run([Request(req_id="a", tokens=tok.encode("The answer"),
                              max_new_tokens=4)])
    assert len(out["a"].tokens) == 4
