"""MoE serving (ISSUE 15): capacity-aware valid-lane routing, paged MoE
decode pinned BIT-identical to the dense-KV MoE path, the lifted batched
refusals (engine batched==solo, left-padded batched generate==solo),
expert-parallel serving (ep=1 bit-identical, ep>1 / ep×tp
token-identical on the CPU mesh, NF4 expert banks), the composition pins
(MoE × prefix_cache, MoE × ngram speculation) and loud refusals (dense +
ep, llama + ep, indivisible experts, MoE × draft:<k>), the engine's MoE
routing stats, and the moe_serving evidence stage."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.models.generate import generate
from distributed_lion_tpu.models.gpt2 import (
    GPT2Config,
    gpt2_decode,
    gpt2_decode_paged,
    gpt2_init,
    gpt2_init_cache,
)
from distributed_lion_tpu.parallel.expert import moe_ffn, moe_init
from distributed_lion_tpu.serve.engine import (
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MOE = GPT2Config.tiny(moe_experts=4)  # n_layer=2, moe_every=2: block 1 MoE


@pytest.fixture(scope="module")
def moe_params():
    return gpt2_init(jax.random.key(0), MOE)


def _requests(vocab, n=4, max_new=8, lens=(3, 9, 5, 14, 2), seed=7):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i,
                    tokens=list(map(int, rng.integers(1, vocab, L))),
                    max_new_tokens=max_new, seed=i)
            for i, L in enumerate(lens[:n])]


# The model calls below run COMPILED, one program a shape: eagerly each is a
# few hundred one-op programs (ISSUE 35).
_ffn = jax.jit(functools.partial(moe_ffn, axis_name=None),
               static_argnames=("capacity_factor", "capacity_override",
                               "return_stats"))
_dense = jax.jit(lambda p, t, cache, pos: gpt2_decode(p, t, MOE, cache, pos))
_paged = jax.jit(lambda p, t, pages, tables, lens, valid=None:
                 gpt2_decode_paged(p, t, MOE, pages, tables, lens, valid))


def _engine(params, cfg=MOE, **kw):
    base = dict(max_seqs=4, block_size=4, max_blocks_per_seq=8)
    base.update(kw)
    return ServingEngine(ServeModel.for_gpt2(params, cfg),
                         ServeConfig(**base))


def _run(eng, reqs, **kw):
    return eng.run([Request(r.req_id, list(r.tokens), r.max_new_tokens,
                            r.seed) for r in reqs], **kw)


# ------------------------------------------------- valid-lane routing pin
def test_pad_lanes_consume_zero_capacity_under_binding_cap():
    """THE acceptance-criterion unit pin: with a BINDING capacity (cap=2)
    a padded batch's routed output for its real tokens is bit-equal to
    the unpadded batch's — pads take no queue slot, so they never perturb
    which real tokens drop — and every pad lane's output row is exactly
    zero."""
    E, D, F = 4, 8, 16
    params = moe_init(jax.random.key(1), E, D, F)
    rng = np.random.default_rng(3)
    x_real = jnp.asarray(rng.standard_normal((10, D)), jnp.float32)
    real_pos = [0, 2, 3, 5, 6, 8, 10, 11, 13, 15]  # pads INTERLEAVED
    x_pad = jnp.asarray(rng.standard_normal((16, D)), jnp.float32)
    x_pad = x_pad.at[jnp.asarray(real_pos)].set(x_real)
    valid = np.zeros((16,), bool)
    valid[real_pos] = True

    y_ref, _ = _ffn(params, x_real, capacity_override=2)
    y_pad, _ = _ffn(params, x_pad, capacity_override=2,
                    valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(y_ref),
                                  np.asarray(y_pad)[real_pos])
    assert (np.asarray(y_pad)[~valid] == 0).all()
    _, _, st = _ffn(params, x_pad, capacity_override=2,
                    valid=jnp.asarray(valid), return_stats=True)
    assert float(st["valid"]) == 10.0  # pads counted in NO column
    # the binding cap actually dropped real tokens (zero output rows) —
    # the equality pin is not vacuous: 10 tokens / 4 experts / cap 2
    # cannot all be kept
    assert np.all(np.asarray(y_ref) == 0, axis=-1).any()


def test_all_valid_mask_is_bit_identical_to_no_mask():
    """valid=all-True must be the None code path bit-for-bit (training
    never passes a mask; the decode paths always do)."""
    params = moe_init(jax.random.key(2), 4, 8, 16)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((12, 8)),
                    jnp.float32)
    y0, a0 = _ffn(params, x)
    y1, a1 = _ffn(params, x, valid=jnp.ones((12,), bool))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert float(a0) == float(a1)


def test_moe_routing_stats_against_capacity_budget():
    """return_stats measures routing load vs the capacity_factor budget
    regardless of the no-drop override: kept <= valid, kept bounded by
    E*budget, and a skewed gate shows dropped demand (valid > kept)."""
    E, D, F = 4, 8, 16
    params = moe_init(jax.random.key(3), E, D, F)
    # a zero gate ties every logit; argmax routes ALL tokens to expert 0
    params["gate"] = jnp.zeros_like(params["gate"])
    x = jnp.asarray(np.random.default_rng(6).standard_normal((16, D)),
                    jnp.float32)
    _, _, st = _ffn(params, x, capacity_factor=1.0, capacity_override=16,
                    return_stats=True)
    valid, kept, slots = (float(st[k]) for k in
                          ("valid", "kept", "capacity_slots"))
    assert valid == 16.0 and slots == 16.0  # budget = ceil(1.0*16/4) = 4
    assert kept == 4.0  # one 4-slot expert holds everything it can
    assert valid - kept == 12.0  # the demand the budget would drop


# ------------------------------------------- paged == dense (bit-identity)
def test_paged_moe_decode_bit_identical_to_dense(moe_params):
    """The headline acceptance criterion: prefill + per-token decode
    through SHUFFLED block tables produces bit-identical logits to the
    dense KV cache at the same attended length — for a MoE config."""
    B, L, bs, nb_seq = 2, 7, 4, 4
    toks = jnp.asarray(
        np.random.default_rng(0).integers(1, MOE.vocab_size, (B, L)),
        jnp.int32)
    cache = gpt2_init_cache(MOE, B, bs * nb_seq)
    dl, cache = _dense(moe_params, toks, cache, 0)
    pages = [{k: jnp.zeros((B * nb_seq, bs, MOE.n_head, MOE.head_dim),
                           MOE.compute_dtype) for k in ("k", "v")}
             for _ in range(MOE.n_layer)]
    tables = jnp.asarray([[2, 0, 1, 3], [5, 7, 4, 6]], jnp.int32)
    pl, pages = _paged(moe_params, toks, pages, tables,
                       jnp.zeros((B,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(dl), np.asarray(pl))
    t_cur = jnp.argmax(dl[:, -1], -1)
    lens = jnp.full((B,), L, jnp.int32)
    for i in range(5):
        dl, cache = _dense(moe_params, t_cur[:, None], cache, L + i)
        pl, pages = _paged(moe_params, t_cur[:, None], pages, tables, lens)
        np.testing.assert_array_equal(np.asarray(dl), np.asarray(pl))
        t_cur = jnp.argmax(dl[:, -1], -1)
        lens = lens + 1


def test_paged_moe_prefill_pad_tail_is_inert(moe_params):
    """The engine's bucketed right-padded prefill shape: real-position
    logits and a later decode step match an unpadded prefill bit-for-bit
    — the pad tail neither writes pages nor routes through experts."""
    L, P, bs = 5, 8, 4
    toks = jnp.asarray(
        np.random.default_rng(2).integers(1, MOE.vocab_size, (1, L)),
        jnp.int32)
    padded = jnp.concatenate([toks, jnp.zeros((1, P - L), jnp.int32)],
                             axis=1)

    def pages():
        return [{k: jnp.zeros((4, bs, MOE.n_head, MOE.head_dim),
                              MOE.compute_dtype) for k in ("k", "v")}
                for _ in range(MOE.n_layer)]

    tables = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    ref, ref_pages = _paged(moe_params, toks, pages(), tables, zero)
    valid = (jnp.arange(P) < L)[None, :]
    got, got_pages = _paged(moe_params, padded, pages(), tables, zero, valid)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got[:, :L]))
    nxt = jnp.argmax(ref[:, L - 1], -1)[:, None]
    lens = jnp.full((1,), L, jnp.int32)
    a, _ = _paged(moe_params, nxt, ref_pages, tables, lens)
    b, _ = _paged(moe_params, nxt, got_pages, tables, lens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- lifted batch refusals
@pytest.mark.parametrize("sampling", ["greedy", "stochastic"])
def test_moe_engine_staggered_batched_matches_solo(moe_params, sampling):
    """Continuous batching never changes an MoE request's output: the
    no-drop per-token routing means batchmates cannot displace each
    other's expert slots — staggered arrivals == solo runs."""
    samp = ({} if sampling == "greedy"
            else dict(temperature=0.9, top_k=40))
    reqs = _requests(MOE.vocab_size)
    stag = _run(_engine(moe_params, **samp), reqs,
                arrivals={0: 0, 1: 1, 2: 1, 3: 4})
    alone = _engine(moe_params, **samp)  # one engine: programs compiled once
    for r in reqs:
        solo = _run(alone, [r])
        assert solo[r.req_id].tokens == stag[r.req_id].tokens, r.req_id


def test_moe_engine_matches_dense_kv_generate(moe_params):
    """The serve-vs-generate pin: the paged engine's greedy output equals
    the dense-KV ``generate`` path at matched attended length — on a MoE
    checkpoint (the claim PR 9's refusal existed to protect)."""
    bs, nblk, new = 4, 8, 8
    prompts = [list(map(int, np.random.default_rng(11).integers(
        1, MOE.vocab_size, 7))) for _ in range(3)]

    def dec(p, t, c, pos, off=None):
        return gpt2_decode(p, t, MOE, c, pos, off)

    def ic(b, m):
        return gpt2_init_cache(MOE, b, m)

    dense = np.asarray(generate(dec, ic, moe_params,
                                jnp.asarray(prompts, jnp.int32), new,
                                max_len=bs * nblk))
    eng = _engine(moe_params, block_size=bs, max_blocks_per_seq=nblk)
    done = eng.run([Request(req_id=i, tokens=list(t), max_new_tokens=new,
                            seed=0) for i, t in enumerate(prompts)])
    for i in range(len(prompts)):
        assert list(dense[i]) == done[i].tokens, i


def test_moe_batched_left_padded_generate_matches_solo(moe_params):
    """The models/generate satellite: the PR 9 left-pad refusal is lifted
    — per-row offsets mask pad lanes out of expert routing, so batched
    greedy MoE generate equals solo runs exactly."""
    rng = np.random.default_rng(13)
    lens = [3, 7, 5]
    prompts = [list(map(int, rng.integers(1, MOE.vocab_size, L)))
               for L in lens]
    T = max(lens)
    padded = np.zeros((len(prompts), T), np.int32)
    for i, p in enumerate(prompts):
        padded[i, T - len(p):] = p

    def dec(p, t, c, pos, off=None):
        return gpt2_decode(p, t, MOE, c, pos, off)

    def ic(b, m):
        return gpt2_init_cache(MOE, b, m)

    batched = np.asarray(generate(dec, ic, moe_params,
                                  jnp.asarray(padded), 8,
                                  prompt_lens=jnp.asarray(lens, jnp.int32)))
    for i, p in enumerate(prompts):
        solo = np.asarray(generate(dec, ic, moe_params,
                                   jnp.asarray([p], jnp.int32), 8))
        np.testing.assert_array_equal(batched[i], solo[0])


# -------------------------------------------------- expert-parallel serving
def test_ep1_bit_identical_to_unsharded(moe_params):
    """ep=1 runs the sharded program on a 1-expert mesh and must be the
    unsharded engine bit for bit: token streams AND every scattered k/v
    byte."""
    reqs = _requests(MOE.vocab_size)
    e0 = _engine(moe_params)
    e1 = _engine(moe_params, ep=1)
    out0, out1 = _run(e0, reqs), _run(e1, reqs)
    for r in reqs:
        assert out1[r.req_id].tokens == out0[r.req_id].tokens, r.req_id
        assert out1[r.req_id].reason == out0[r.req_id].reason
    for l0, l1 in zip(e0.pages, e1.pages):
        for k in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(l0[k]),
                                          np.asarray(l1[k]))


@pytest.mark.parametrize("ep", [2, 4])
@pytest.mark.parametrize("sampling", ["greedy", "stochastic"])
def test_ep_matches_single_device(moe_params, ep, sampling):
    """ep>1 shards the expert banks and routes tokens through the two
    all_to_all hops; the engine-level pin is token identity, greedy AND
    sampled (the per-request streams are batch- and mesh-independent)."""
    samp = ({} if sampling == "greedy"
            else dict(temperature=0.9, top_k=40))
    reqs = _requests(MOE.vocab_size, n=5)
    base = _run(_engine(moe_params, **samp), reqs)
    got = _run(_engine(moe_params, ep=ep, **samp), reqs)
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id


def test_ep_tp_composes(moe_params):
    """ep × tp: Megatron-split attention + per-expert FFNs on the tensor
    axis, expert banks on the expert axis — outputs still pinned to the
    plain engine."""
    reqs = _requests(MOE.vocab_size, n=3)
    base = _run(_engine(moe_params), reqs)
    eng = _engine(moe_params, ep=2, tp=2)
    got = _run(eng, reqs)
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id
    # the mesh really is (data=1, tensor=2, expert=2) over 4 devices
    assert eng._mesh is not None and eng._mesh.devices.size == 4


def test_ep_expert_banks_sharded_pages_replicated(moe_params):
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.parallel.mesh import EXPERT_AXIS

    eng = _engine(moe_params, ep=2)
    w_in = eng.params["blocks"][1]["moe"]["w_in"]
    assert w_in.sharding.spec == P(EXPERT_AXIS)
    # page pools untouched by ep: kv-head axis over a size-1 tensor axis
    assert eng.pages[0]["k"].sharding.spec[2] in (None, "tensor")
    assert isinstance(eng.tables.tables, np.ndarray)


def test_nf4_ep2_matches_nf4_single_device(moe_params):
    """NF4 expert banks shard with the dense specs (shaped layout: the
    expert dim is a leading dim, 1:1 on codes and absmax) — quantized ep
    serving matches the single-device quantized engine."""
    from distributed_lion_tpu.ops.quant import QuantizedTensor

    reqs = _requests(MOE.vocab_size, n=3)
    base = _run(_engine(moe_params, quant="nf4"), reqs)
    eng = _engine(moe_params, quant="nf4", ep=2)
    got = _run(eng, reqs)
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id
    assert isinstance(eng.params["blocks"][1]["moe"]["w_in"],
                      QuantizedTensor)


# ------------------------------------------------------------ compositions
def test_moe_prefix_cache_shared_matches_unshared(moe_params):
    """MoE × --prefix_cache: shared prefix pages hold bit-identical k/v
    and no-drop routing is per-token, so sharing cannot change any expert
    assignment — outputs pinned to the unshared engine, and sharing
    actually happened."""
    rng = np.random.default_rng(17)
    sys_p = list(map(int, rng.integers(1, MOE.vocab_size, 13)))
    prompts = [sys_p + list(map(int, rng.integers(1, MOE.vocab_size, 3)))
               for _ in range(5)]
    reqs = [Request(req_id=i, tokens=list(t), max_new_tokens=6, seed=i)
            for i, t in enumerate(prompts)]
    base = _run(_engine(moe_params, num_blocks=64), reqs)
    eng = _engine(moe_params, num_blocks=64, prefix_cache=True)
    got = _run(eng, reqs)
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id
    assert eng.stats["prefix_hits"] > 0


@pytest.mark.parametrize("sampling", ["greedy", "stochastic"])
def test_moe_ngram_speculation_matches_plain(moe_params, sampling):
    """MoE × ngram speculation: the verify window is a wider no-drop
    dispatch with its tail valid-masked, and rollback over MoE pages is
    attention-side only — speculative output pinned to the plain engine,
    with acceptances actually earned on repetitive traffic."""
    samp = ({} if sampling == "greedy"
            else dict(temperature=0.9, top_k=40))
    rng = np.random.default_rng(19)
    motif = list(map(int, rng.integers(1, MOE.vocab_size, 4)))
    reqs = [Request(req_id=i, tokens=motif * 4, max_new_tokens=10, seed=i)
            for i in range(3)]
    base = _run(_engine(moe_params, max_blocks_per_seq=16, **samp), reqs)
    eng = _engine(moe_params, max_blocks_per_seq=16, speculate="ngram:4",
                  **samp)
    got = _run(eng, reqs)
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id
    if sampling == "greedy":
        assert eng.stats["spec_accepted"] > 0


def test_moe_prefix_and_ep_compose_together(moe_params):
    """The full stack: prefix sharing × expert parallelism on one MoE
    engine still reproduces the plain engine's streams."""
    rng = np.random.default_rng(23)
    sys_p = list(map(int, rng.integers(1, MOE.vocab_size, 9)))
    prompts = [sys_p + list(map(int, rng.integers(1, MOE.vocab_size, 2)))
               for _ in range(4)]
    reqs = [Request(req_id=i, tokens=list(t), max_new_tokens=5, seed=i)
            for i, t in enumerate(prompts)]
    base = _run(_engine(moe_params, num_blocks=64), reqs)
    eng = _engine(moe_params, num_blocks=64, prefix_cache=True, ep=2)
    got = _run(eng, reqs)
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id


# ---------------------------------------------------------------- refusals
def test_serve_ep_refuses_dense_checkpoint():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="MoE checkpoint"):
        _engine(params, cfg, ep=2)


def test_serve_ep_refuses_indivisible_experts(moe_params):
    with pytest.raises(ValueError, match="divisible"):
        _engine(moe_params, ep=3)


def test_serve_ep_refuses_more_ranks_than_devices():
    cfg = GPT2Config.tiny(n_head=16, d_model=256, moe_experts=16)
    params = gpt2_init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="devices"):
        _engine(params, cfg, ep=16)


def test_serve_ep_refuses_llama():
    from distributed_lion_tpu.models.llama import LlamaConfig, llama_init

    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.key(0), cfg)
    model = ServeModel.for_llama(params, cfg)
    with pytest.raises(ValueError, match="MoE checkpoint"):
        ServingEngine(model, ServeConfig(max_seqs=2, block_size=4,
                                         max_blocks_per_seq=4, ep=2))


# ----------------------------------------------------------- routing stats
def test_engine_moe_stats_accumulate(moe_params):
    """ServeConfig.moe_stats: the engine folds per-dispatch routing-load
    scalars into stats — valid tokens counted, kept <= valid, slots > 0 —
    and the default engine pays nothing (keys absent)."""
    reqs = _requests(MOE.vocab_size)
    eng = _engine(moe_params, moe_stats=True)
    _run(eng, reqs)
    assert eng.stats["moe_valid_tokens"] > 0
    assert 0 < eng.stats["moe_kept_tokens"] <= eng.stats["moe_valid_tokens"]
    assert eng.stats["moe_capacity_slots"] > 0
    plain = _engine(moe_params)
    _run(plain, reqs)
    assert "moe_valid_tokens" not in plain.stats


def test_engine_moe_stats_accumulate_under_speculation(moe_params):
    """Regression (review round): the speculative VERIFY dispatch must
    feed the routing-stats counters too — with ngram speculation armed,
    decode-side stats keep growing after admissions, not just the
    prefill contribution."""
    rng = np.random.default_rng(31)
    motif = list(map(int, rng.integers(1, MOE.vocab_size, 4)))
    eng = _engine(moe_params, moe_stats=True, speculate="ngram:2",
                  max_blocks_per_seq=16)
    for i in range(3):
        eng.submit(Request(req_id=i, tokens=motif * 4, max_new_tokens=12,
                           seed=i))
    while eng.pending:
        eng.step()
    after_fill = eng.stats["moe_valid_tokens"]
    assert after_fill > 0  # prefill contributed
    for _ in range(3):
        eng.step()
    assert eng.stats["moe_valid_tokens"] > after_fill  # verify did too


def test_moe_stats_flag_inert_on_dense_checkpoint():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    eng = _engine(params, cfg, moe_stats=True)
    _run(eng, _requests(cfg.vocab_size, n=2))
    assert "moe_valid_tokens" not in eng.stats  # no MoE blocks to measure


# ------------------------------------------------- the evidence artifact
def _load_ce():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_moe", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    return ce


def test_banked_artifact_passes_moe_serving_stage():
    """The committed CPU artifact (captured under DLION_PLATFORM=cpu8 so
    the ep>=2 legs exist) satisfies the ISSUE 15+16 moe_serving stage:
    strict schema, all ten identity markers, dense + moe + moe_ep>=2
    matrix rows with measured tokens/s/chip and [0,1] capacity columns,
    and at least one batch-sharded row strictly above the replicated row
    at a matched (batch, ep) — the gate runbook stage 5m re-judges after
    the on-chip recapture."""
    ce = _load_ce()
    assert ce.moe_serving_ok()
    with open(ce.SERVE_ARTIFACT) as f:
        doc = json.load(f)
    sec = doc["moe_serving"]
    configs = {r["config"] for r in sec["rows"]}
    assert {"dense", "moe"} <= configs
    assert any(r["ep"] >= 2 for r in sec["rows"])
    for r in sec["rows"]:
        if r["experts"]:
            assert 0.0 <= r["capacity_utilization"] <= 1.0
            assert 0.0 <= r["dropped_rate"] <= 1.0
    # ISSUE 16: the banked matrix carries the throughput-lever evidence —
    # EVERY batch-sharded row beats its replicated twin per chip
    pairs = 0
    for r in sec["rows"]:
        if r["sharding"] != "batch":
            continue
        twins = [x for x in sec["rows"] if x["sharding"] == "replicated"
                 and x["ep"] == r["ep"] and x["batch"] == r["batch"]]
        assert twins, r
        for x in twins:
            assert r["tokens_per_sec_per_chip"] \
                > x["tokens_per_sec_per_chip"], (r, x)
        pairs += 1
    assert pairs >= 1


def test_moe_serving_stage_rejects_bad_artifacts(tmp_path):
    ce = _load_ce()
    with open(ce.SERVE_ARTIFACT) as f:
        good = json.load(f)
    p = tmp_path / "serving.json"

    def reject(mutate):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        p.write_text(json.dumps(doc))
        assert not ce.moe_serving_ok(str(p))

    # artifact predates ISSUE 15 entirely (also a schema violation now)
    reject(lambda d: d.pop("moe_serving"))
    # each identity marker flips the stage
    for k in ce.MOE_SERVE_MARKERS:
        reject(lambda d, k=k: d["moe_serving"]["markers"].update({k: False}))
    # matrix coverage: no expert-parallel row / no dense baseline
    reject(lambda d: d["moe_serving"].update(
        rows=[r for r in d["moe_serving"]["rows"] if r["ep"] < 2]))
    reject(lambda d: d["moe_serving"].update(
        rows=[r for r in d["moe_serving"]["rows"]
              if r["config"] != "dense"]))
    # throughput floor on a MoE row
    def slow(d):
        for r in d["moe_serving"]["rows"]:
            if r["experts"]:
                r["tokens_per_sec_per_chip"] = 1.0
                break
    reject(slow)
    # schema: capacity column outside [0, 1] (validate_metrics delegation)
    def bad_util(d):
        for r in d["moe_serving"]["rows"]:
            if r["experts"]:
                r["capacity_utilization"] = 1.5
                break
    reject(bad_util)
    # ISSUE 16: no batch-sharded row at all — 'throughput lever' unmeasured
    reject(lambda d: d["moe_serving"].update(
        rows=[r for r in d["moe_serving"]["rows"]
              if r["sharding"] != "batch"]))
    # batch-sharded rows that tie (not STRICTLY beat) the replicated twin
    def lever_lost(d):
        rows = d["moe_serving"]["rows"]
        for r in rows:
            if r["sharding"] != "batch":
                continue
            for x in rows:
                if (x["sharding"] == "replicated" and x["ep"] == r["ep"]
                        and x["batch"] == r["batch"]):
                    r["tokens_per_sec_per_chip"] = \
                        x["tokens_per_sec_per_chip"]
    reject(lever_lost)
    # schema: the sharding / beats_dense_per_chip columns are mandatory
    def bad_sharding(d):
        d["moe_serving"]["rows"][0]["sharding"] = "sideways"
    reject(bad_sharding)
    def no_beats_col(d):
        d["moe_serving"]["rows"][0].pop("beats_dense_per_chip")
    reject(no_beats_col)
    # the untouched artifact still passes from the tmp copy
    p.write_text(json.dumps(good))
    assert ce.moe_serving_ok(str(p))
