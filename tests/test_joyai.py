"""The JoyAI-LLM-Flash serving path (models/joyai, the latent page pool, the
absorbed decode kernel, the dropless expert layer) at a tiny size on the
CPU, seeded weights, float32, against the benchmark's plain reference
(``benchmark/reference/joyai_llm_flash``: float32, nothing imported from the
package).

Tolerances. Program and reference compute the same float32 arithmetic in
another order (absorbed against expanded attention, sorted grouped matmul
against a masked scan over every expert); at these sizes their logits agree
to 2e-6 and 1e-4 leaves fifty times that. The kernels in interpret mode run
float32 too.
"""

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

from benchmark.families import joyai_llm_flash as family  # noqa: E402
from distributed_lion_tpu.models.joyai import (  # noqa: E402
    JoyAIConfig,
    joyai_decode_paged,
)
from distributed_lion_tpu.ops import attention as attn_ops  # noqa: E402
from distributed_lion_tpu.ops import pallas_mla_attn, pallas_moe_gmm  # noqa: E402
from distributed_lion_tpu.parallel import expert  # noqa: E402
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


@pytest.fixture(scope="module")
def model():
    """(reference weights, program params, JoyAIConfig) at TINY, float32:
    the same values in both layouts."""
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 26), TINY, jnp.float32)
    cfg = JoyAIConfig.from_hf(TINY, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    return weights, family.to_program(weights), cfg


def pool(cfg, n_seq):
    pages = init_page_leaves(cfg.n_layer, n_seq * PER_SEQ, BLOCK,
                             {"kv": (1, cfg.latent_dim)}, jnp.float32)
    # shuffled ownership: every read has to go through the table
    tables = jnp.arange(n_seq * PER_SEQ, dtype=jnp.int32)[::-1].reshape(
        n_seq, PER_SEQ)
    return pages, tables


def rows_of(n_seq, width, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n_seq, width)).astype(np.int32)


# Model calls run COMPILED, one program a shape: eagerly a forward pass is a
# few hundred one-op programs (ISSUE 35).
reference = jax.jit(lambda weights, rows: ref.forward(weights, rows, TINY))


def program_of(cfg):
    """``joyai_decode_paged`` at ``cfg``, compiled. Built anew in every
    test: a trace holds the choices the backend's name made, and
    ``interpret_kernels`` changes that name."""
    return jax.jit(lambda params, toks, pages, tables, pos, valid=None:
                   joyai_decode_paged(params, toks, cfg, pages, tables, pos,
                                      valid))


def interpret_kernels(monkeypatch):
    """Take the TPU's choices on the CPU: the Mosaic kernels in interpret
    mode (the test says "tpu" in the backend's place, as
    tests/test_chip_compile.py does)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_mla_attn, "mla_paged_attn", functools.partial(
        pallas_mla_attn.mla_paged_attn, interpret=True))
    monkeypatch.setattr(pallas_moe_gmm, "moe_gmm", functools.partial(
        pallas_moe_gmm.moe_gmm, interpret=True))


def test_expanded_prefill_matches_the_reference(model):
    weights, params, cfg = model
    program = program_of(cfg)
    rows = rows_of(2, 32)
    pages, tables = pool(cfg, 2)
    logits, _ = program(params, rows, pages, tables,
                        jnp.zeros((2,), jnp.int32))
    want = reference(weights, rows)
    assert logits.shape == want.shape == (2, 32, TINY["vocab_size"])
    assert float(jnp.abs(logits - want).max()) < TOL


@pytest.mark.parametrize("path", ["gather", "absorbed_kernel"])
def test_prefill_then_decode_through_the_latent_pages(model, path,
                                                      monkeypatch):
    """A ragged prefill window, then one token a step at each row's own
    position: every step's logits are the reference's full forward pass at
    that position. ``absorbed_kernel``: the S = 1 steps fold the up
    projections into query and output and read the latent rows through
    ``mla_paged_attn`` (interpret mode)."""
    weights, params, cfg = model
    if path == "absorbed_kernel":
        interpret_kernels(monkeypatch)
    program = program_of(cfg)
    rows = rows_of(3, 40, seed=1)
    want = reference(weights, rows)
    plens = np.asarray([9, 16, 23])
    pages, tables = pool(cfg, 3)
    valid = jnp.arange(24)[None, :] < jnp.asarray(plens)[:, None]
    window, pages = program(params, rows[:, :24], pages, tables,
                            jnp.zeros((3,), jnp.int32), valid)
    for i, n in enumerate(plens):
        assert float(jnp.abs(window[i, :n] - want[i, :n]).max()) < TOL
    for j in range(10):
        pos = plens + j
        toks = rows[np.arange(3), pos][:, None]
        logits, pages = program(params, toks, pages, tables,
                                jnp.asarray(pos, jnp.int32),
                                jnp.ones((3, 1), bool))
        got = np.asarray(logits[:, 0])
        assert np.abs(got - np.asarray(want)[np.arange(3), pos]).max() < TOL


def test_decode_programs_hold_the_kernel_only_where_it_applies(model,
                                                               monkeypatch):
    _, params, cfg = model
    pages, tables = pool(cfg, 2)
    shape = pages[0]["kv"].shape
    assert shape == (2 * PER_SEQ, BLOCK, 1, 128)    # 40 values, lane padded
    assert not attn_ops.paged_kernel_applies(1, shape, jnp.float32)  # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn_ops.paged_kernel_applies(1, shape, jnp.float32)
    assert not attn_ops.paged_kernel_applies(4, shape, jnp.float32)  # S > 1


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_mla_kernel_matches_the_gather_path(dtype, tol):
    """``mla_paged_attn`` in interpret mode against gather + masked softmax
    over the same latent rows: ragged lengths, a row past one block of
    pages, an empty row."""
    rng = np.random.default_rng(5)
    B, H, W, bs, nb = 4, 4, 128, 16, 24          # 24 pages > PAGES_PER_BLOCK
    lengths = np.asarray([1, 37, 0, 16 * 19 + 3], np.int32)
    pages = jnp.asarray(rng.normal(0, 1, (B * nb, bs, 1, W)), dtype)
    pages = pages.at[..., 96:].set(0)            # pad lanes are zero
    tables = jnp.asarray(rng.permutation(B * nb).reshape(B, nb), jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, H, W)), dtype).at[..., 96:].set(0)
    got = pallas_mla_attn.mla_paged_attn(q, pages, tables,
                                         jnp.asarray(lengths), scale=0.1,
                                         interpret=True)
    rows = attn_ops.paged_gather_kv(pages, tables)[:, :, 0]      # [B, T, W]
    s = jnp.einsum("bhw,btw->bht", q, rows,
                   preferred_element_type=jnp.float32) * 0.1
    live = jnp.arange(nb * bs)[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), -1).astype(dtype)
    want = jnp.einsum("bht,btw->bhw", p, rows,
                      preferred_element_type=jnp.float32)
    want = jnp.where(lengths[:, None, None] > 0, want, 0)
    assert got.shape == (B, H, W) and got.dtype == dtype
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < tol


def test_chunked_attention_is_the_unchunked_one():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(0, 1, (2, 3, 64, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 3, 96, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 3, 96, 16)), jnp.float32)  # dv < dk
    pos = jnp.asarray([0, 20], jnp.int32)
    whole = attn_ops.chunked_causal_attention(q, k, v, pos, scale=0.2,
                                              chunk=64)
    parts = attn_ops.chunked_causal_attention(q, k, v, pos, scale=0.2,
                                              chunk=16)
    assert whole.shape == (2, 3, 64, 16)
    assert float(jnp.abs(whole - parts).max()) < 1e-6
    # causal: query s of row b sees positions <= pos[b] + s
    s = jnp.einsum("bhsd,bhtd->bhst", q, k) * 0.2
    live = jnp.arange(96)[None, None, :] <= (pos[:, None] + jnp.arange(64)
                                             )[:, :, None]
    want = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(
        jnp.where(live[:, None], s, -1e30), -1), v)
    assert float(jnp.abs(whole - want).max()) < 1e-5


# ----------------------------------------------------------- expert layer
def moe_params(weights):
    layer = next(w for w in weights["layers"] if "router" in w)
    block = family.to_program({**weights, "layers": [layer]})["blocks"][0]
    return layer, block["moe"]


def test_dropless_layer_matches_the_all_experts_sum(model):
    weights, _, cfg = model
    layer, moe = moe_params(weights)
    x = jnp.asarray(np.random.default_rng(7).normal(0, 1, (1, 50, 64)),
                    jnp.float32)
    want = ref._experts(x, layer, TINY, None)[0]
    got, st = expert.moe_dropless_ffn(moe, x[0], top_k=cfg.top_k,
                                      scale=cfg.routed_scale,
                                      return_counters=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # token conservation: every token reaches top_k experts
    assert int(st["moe_assignments"]) == 50 * cfg.top_k
    assert 1 <= int(st["moe_experts_hit"]) <= cfg.n_experts
    assert int(st["moe_load_max"]) <= 50


def test_every_token_to_one_expert_and_none_dropped(model):
    """The worst imbalance: a correction bias that sends every token to
    the same two experts. No capacity, so nothing is dropped: the layer
    still equals the reference, and one expert holds every token."""
    weights, _, cfg = model
    layer, moe = moe_params(weights)
    bias = jnp.zeros((cfg.n_experts,), jnp.float32).at[jnp.asarray([3, 5])
                                                       ].set(10.0)
    x = jnp.asarray(np.random.default_rng(8).normal(0, 1, (1, 40, 64)),
                    jnp.float32)
    want = ref._experts(x, dict(layer, router_bias=bias), TINY, None)[0]
    got, st = expert.moe_dropless_ffn(dict(moe, bias=bias), x[0],
                                      top_k=cfg.top_k, scale=cfg.routed_scale,
                                      return_counters=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert int(st["moe_experts_hit"]) == 2
    assert int(st["moe_load_max"]) == 40
    assert int(st["moe_assignments"]) == 80


def test_lanes_without_a_token_reach_no_expert(model):
    weights, _, cfg = model
    _, moe = moe_params(weights)
    x = jnp.asarray(np.random.default_rng(9).normal(0, 1, (24, 64)),
                    jnp.float32)
    valid = jnp.arange(24) % 3 != 1
    ffn = jax.jit(functools.partial(
        expert.moe_dropless_ffn, top_k=cfg.top_k, scale=cfg.routed_scale),
        static_argnames="return_counters")
    got, st = ffn(moe, x, valid=valid, return_counters=True)
    alone = ffn(moe, x[valid])
    assert float(jnp.abs(got[valid] - alone).max()) < 1e-6
    assert float(jnp.abs(got[~valid]).max()) == 0.0
    assert int(st["moe_assignments"]) == 16 * cfg.top_k


def test_routing_weights_follow_the_score_and_the_choice_the_bias():
    x = jnp.asarray(np.random.default_rng(10).normal(0, 1, (6, 16)),
                    jnp.float32)
    router = jnp.asarray(np.random.default_rng(11).normal(0, 1, (8, 16)),
                         jnp.float32)
    bias = jnp.zeros((8,)).at[7].set(5.0)
    idx, w = expert.sigmoid_topk_route(x, router, bias, 3, 2.5)
    assert (np.asarray(idx) == 7).any(axis=1).all()       # chosen by bias
    s = jax.nn.sigmoid(x @ router.T)
    picked = jnp.take_along_axis(s, idx, 1)               # weighed by score
    assert np.allclose(w, picked / picked.sum(-1, keepdims=True) * 2.5,
                       atol=1e-6)
    assert np.allclose(w.sum(-1), 2.5, atol=1e-5)


@pytest.mark.parametrize("sizes", [[5, 0, 17, 9], [0, 0, 40, 0],
                                   [1, 1, 1, 1], [130, 3, 0, 60]])
def test_gmm_kernel_matches_ragged_dot(sizes):
    """``moe_gmm`` in interpret mode: groups that end inside a tile, empty
    groups, rows past the last group (undefined, not compared), more rows
    than one tile."""
    rng = np.random.default_rng(12)
    used = sum(sizes)
    m = -(-(used + 7) // 8) * 8
    lhs = jnp.asarray(rng.normal(0, 1, (m, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(0, 1, (4, 128, 256)), jnp.float32)
    g = jnp.asarray(sizes, jnp.int32)
    got = pallas_moe_gmm.moe_gmm(lhs, rhs, g, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, g)
    assert got.shape == (m, 256)
    assert float(jnp.abs(got[:used] - want[:used]).max()) < 1e-3
    assert pallas_moe_gmm.kernel_takes(2048, 768)
    assert not pallas_moe_gmm.kernel_takes(64, 32)
    assert pallas_moe_gmm._tile_n(2048, 768, 2) == 768     # one 3 MB block
    assert pallas_moe_gmm._tile_n(7168, 2048, 2) == 256


# ----------------------------------------------------------------- engine
def engine_of(model, **kw):
    _, params, cfg = model
    base = dict(max_seqs=4, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                prefill_cap_tokens=64, moe_stats=True)
    base.update(kw)
    return ServingEngine(ServeModel.for_joyai(params, cfg),
                         ServeConfig(**base))


def requests(seed=13, shared=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(0, 256, shared).tolist()
    return [Request(req_id=i, tokens=head + rng.integers(0, 256, n).tolist(),
                    max_new_tokens=6)
            for i, n in enumerate([5, 17, 30, 9, 12])]


@pytest.fixture(scope="module")
def batched(model):
    eng = engine_of(model)
    return eng, eng.run(requests(), arrivals={3: 2, 4: 5})


def test_engine_batched_equals_solo(model, batched):
    _, out = batched
    assert all(c.reason == "length" and len(c.tokens) == 6
               for c in out.values())
    alone = engine_of(model)    # one engine, its programs compiled once
    for req in requests()[:3]:
        solo = alone.run([req])
        assert solo[req.req_id].tokens == out[req.req_id].tokens


def test_engine_counters_conserve_tokens(model, batched):
    eng, _ = batched
    cfg, st = model[2], eng.stats
    layers = cfg.n_layer - cfg.first_dense
    assert st["moe_assignments"] == st["decode_tokens"] * cfg.top_k * layers
    assert st["moe_prefill_assignments"] == (st["prefill_tokens"] * cfg.top_k
                                             * layers)
    assert 0 < st["moe_experts_hit"] <= (st["decode_ticks"] * layers
                                         * cfg.n_experts)
    assert st["moe_load_max"] <= 4 and st["moe_prefill_load_max"] <= 30
    assert st["mla_kernel_ticks"] == 0              # the CPU: gather path
    assert "decode_attn_kernel_ticks" not in st
    assert 0 < st["kv_pages_read"] < st["kv_pages_table"]
    assert eng.tables.free_blocks == eng.tables.num_blocks
    # one leaf a layer, a token's row padded to whole lane tiles
    assert [sorted(layer) for layer in eng.pages] == [["kv"]] * cfg.n_layer
    assert eng.pages[0]["kv"].shape == (32, BLOCK, 1, 128)


def test_prefix_sharing_and_copy_on_write_over_the_latent_leaf(model):
    """Requests with a shared 19-token head (two whole pages and a part):
    the cache shares the latent pages, the first divergent write copies a
    page, and every output equals the unshared engine's."""
    plain = engine_of(model).run(requests(seed=14, shared=19))
    eng = engine_of(model, prefix_cache=True)
    shared = eng.run(requests(seed=14, shared=19))
    assert {k: c.tokens for k, c in shared.items()} \
        == {k: c.tokens for k, c in plain.items()}
    assert eng.stats["prefix_hits"] >= 3 and eng.stats["shared_tokens"] >= 48
    assert eng.stats["cow_copies"] >= 1


def test_engine_refuses_to_shard_or_quantize_this_family(model):
    for kw in ({"tp": 2}, {"tp": 1}, {"ep": 2}, {"quant": "nf4"}):
        with pytest.raises(ValueError, match="serves on one device"):
            engine_of(model, **kw)


def test_decode_tick_runs_the_kernels_in_the_engine(model, monkeypatch):
    """The engine with the TPU's choices (kernels in interpret mode):
    the same tokens as the gather path's, and every decode tick counted."""
    want = engine_of(model).run(requests()[:2])
    interpret_kernels(monkeypatch)
    eng = engine_of(model)
    got = eng.run(requests()[:2])
    assert {k: c.tokens for k, c in got.items()} \
        == {k: c.tokens for k, c in want.items()}
    assert eng.stats["mla_kernel_ticks"] == eng.stats["decode_ticks"] > 0


# -------------------------------------------------------------------- CLI
def test_config_from_the_published_keys():
    path = os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash.json")
    cfg = JoyAIConfig.named(path)
    assert (cfg.n_layer, cfg.n_experts, cfg.top_k) == (5, 256, 8)
    assert (cfg.latent_dim, cfg.vocab_size) == (576, 129280)
    assert (cfg.q_lora_rank, cfg.moe_d_ff, cfg.d_ff) == (1536, 768, 7168)
    with pytest.raises(ValueError, match="not implemented"):
        JoyAIConfig.from_hf(dict(TINY, n_group=8))
    with pytest.raises(ValueError, match="unknown joyai model_name"):
        JoyAIConfig.named("flash-13b")


def test_run_serve_names_the_family():
    from distributed_lion_tpu.cli import run_generate, run_serve

    gen = run_generate.GenerateArguments(model_family="joyai",
                                         model_name="tiny", temperature=0.0,
                                         max_new_tokens=4)
    serve = run_serve.ServeArguments(max_seqs=2, block_size=8,
                                     max_blocks_per_seq=4)
    tok, engine = run_serve.build_engine(gen, serve)
    assert engine.model.family == "joyai"
    out = engine.run([Request(req_id="a", tokens=tok.encode("The answer",
                                                             add_bos=False))])
    assert out["a"].reason == "length" and len(out["a"].tokens) == 4
    with pytest.raises(ValueError, match="serve it with run_serve"):
        run_generate.main(["--model_family", "joyai", "--model_name", "tiny"])
