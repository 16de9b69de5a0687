"""Speculative decode (ISSUE 11): draft/verify/commit on the paged KV
cache pinned IDENTICAL to the non-speculative engine — greedy speculative
output bit-identical to plain paged decode (gpt2 AND llama), sampled
output token-identical to the same per-request PRNG stream, across both
drafters × k ∈ {2, 4} — plus the rollback state-equality pin (len/last/
table/free-list after a partial accept == what a token-by-token run
holds), drafter protocol/grammar guards, and the speculative evidence
stage."""

import json
import os

import jax
import numpy as np
import pytest

from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
from distributed_lion_tpu.models.llama import LlamaConfig, llama_init
from distributed_lion_tpu.serve.engine import (
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.speculate import (
    NGramDrafter,
    Speculator,
    build_speculator,
    ngram_propose,
    parse_speculate,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(family):
    if family == "gpt2":
        cfg = GPT2Config.tiny()
        return ServeModel.for_gpt2(gpt2_init(jax.random.key(0), cfg), cfg)
    cfg = LlamaConfig.tiny()
    return ServeModel.for_llama(llama_init(jax.random.key(0), cfg), cfg)


_MODELS = {}


def _cached_model(family):
    # one init + one ServeModel per family for the whole module: the pins
    # compare ENGINES, not inits, and tier-1 wall time is budgeted
    if family not in _MODELS:
        _MODELS[family] = _model(family)
    return _MODELS[family]


def _engine(family, **kw):
    model = _cached_model(family)
    base = dict(max_seqs=4, block_size=4, max_blocks_per_seq=8)
    draft = kw.pop("draft_model", None)
    if kw.get("speculate", "").startswith("draft") and draft is None:
        # self-drafting smoke: the target IS its own draft model — perfect
        # greedy acceptance, which exercises full-window commit + the
        # bonus-token path; the ngram legs exercise partial/zero accepts
        draft = _cached_model(family)
    base.update(kw)
    return ServingEngine(model, ServeConfig(**base), draft_model=draft)


def _workload(family, n=4, max_new=10):
    """Mixed traffic: two repetitive prompts (n-gram signal — repeated
    motifs make the suffix drafter actually propose) + two random ones
    (zero-signal slots ride the same verify dispatch)."""
    vocab = _cached_model(family).cfg.vocab_size
    rng = np.random.default_rng(11)
    motif = list(map(int, rng.integers(1, vocab, 5)))
    prompts = [motif * 2, motif * 3 + motif[:2],
               list(map(int, rng.integers(1, vocab, 6))),
               list(map(int, rng.integers(1, vocab, 3)))][:n]
    return [Request(req_id=f"r{i}", tokens=list(p), max_new_tokens=max_new,
                    seed=i) for i, p in enumerate(prompts)]


def _run(engine, reqs, **kw):
    return engine.run([Request(r.req_id, list(r.tokens), r.max_new_tokens,
                               r.seed) for r in reqs], **kw)


# --------------------------------------------------- the headline pins
_PLAIN = {}


def _plain_out(family, samp_key, samp):
    if (family, samp_key) not in _PLAIN:
        _PLAIN[(family, samp_key)] = _run(_engine(family, **samp),
                                          _workload(family))
    return _PLAIN[(family, samp_key)]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("drafter", ["ngram", "draft"])
@pytest.mark.parametrize("k", [2, 4])
def test_speculative_greedy_bit_identical_to_plain(family, drafter, k):
    """THE acceptance pin: greedy speculative decode — both drafters,
    k ∈ {2,4}, both families — produces exactly the non-speculative
    engine's tokens and finish reasons. The drafter changes how fast the
    stream is emitted, never what it says."""
    plain = _plain_out(family, "greedy", dict(temperature=0.0))
    eng = _engine(family, speculate=f"{drafter}:{k}")
    out = _run(eng, _workload(family))
    for rid in plain:
        assert out[rid].tokens == plain[rid].tokens, rid
        assert out[rid].reason == plain[rid].reason, rid
    assert eng.stats["spec_rounds"] > 0
    if drafter == "draft":
        # self-draft smoke: the draft model IS the target, so every greedy
        # proposal must be accepted — the full-window/bonus-token path
        assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"] > 0


@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("drafter", ["ngram", "draft"])
@pytest.mark.parametrize("k", [2, 4])
def test_speculative_sampled_token_identical_to_stream(family, drafter, k):
    """Sampled serving (temperature/top_k) under speculation is pinned
    token-identical to the same per-request fold_in(seed, token_index)
    stream the plain engine draws from — acceptance replays the pinned
    draw at every window position, so rejection can starve speedup but
    never change an output."""
    samp = dict(temperature=0.9, top_k=40)
    plain = _plain_out(family, "sampled", samp)
    out = _run(_engine(family, speculate=f"{drafter}:{k}", **samp),
               _workload(family))
    for rid in plain:
        assert out[rid].tokens == plain[rid].tokens, rid
        assert out[rid].reason == plain[rid].reason, rid


def test_speculative_staggered_arrivals_match_plain():
    """Continuous batching composes with speculation: staggered arrivals
    through the speculative tick still reproduce the plain engine's
    per-request outputs (slots join/leave mid-round; admit-tick prefills
    and verify windows interleave)."""
    reqs = _workload("gpt2")
    arrivals = {"r0": 0, "r1": 2, "r2": 2, "r3": 5}
    plain = _run(_engine("gpt2"), reqs, arrivals=arrivals)
    out = _run(_engine("gpt2", speculate="ngram:4"), reqs,
               arrivals=arrivals)
    for rid in plain:
        assert out[rid].tokens == plain[rid].tokens, rid


def test_ngram_accepts_on_repetitive_traffic():
    """The n-gram drafter must actually EARN accepts on repetitive
    prompts (the bench frontier's accept_rate > 0 claim is mechanism,
    not luck): a strongly periodic greedy stream yields nonzero
    acceptance with zero extra device dispatches."""
    vocab = _cached_model("gpt2").cfg.vocab_size
    rng = np.random.default_rng(5)
    motif = list(map(int, rng.integers(1, vocab, 4)))
    reqs = [Request(req_id=i, tokens=motif * 4, max_new_tokens=12, seed=0)
            for i in range(2)]
    eng = _engine("gpt2", speculate="ngram:4")
    _run(eng, reqs)
    assert eng.stats["spec_proposed"] > 0
    assert eng.stats["spec_accepted"] > 0


# -------------------------------------------- rollback state equality
class _ScriptedDrafter:
    """Deterministic partial-accept harness: proposes [true_next,
    corrupted, true, ...] from a pre-recorded plain-run stream, so every
    round accepts exactly the scripted prefix and rolls back the rest —
    the rollback path is exercised on EVERY tick, not when an n-gram
    happens to miss."""

    name = "scripted"

    def __init__(self, k, script, wrong_at=1):
        self.k, self.script, self.wrong_at = k, dict(script), wrong_at

    def admit(self, slot, tokens, n_committed=0):
        pass

    def evict(self, slot):
        pass

    def commit(self, slot, cache_len):
        pass

    def propose(self, active, slots, desired):
        drafts = np.zeros((len(slots), self.k), np.int32)
        counts = np.zeros((len(slots),), np.int32)
        for i in active:
            s = slots[i]
            true = self.script[s.req.req_id]
            done = len(s.gen)
            cont = true[done:done + int(desired[i])]
            for j, t in enumerate(cont):
                # corrupt every wrong_at-th draft (never a real token id:
                # vocab-1 xor keeps it in range but wrong)
                drafts[i, j] = t if (j + 1) % (self.wrong_at + 1) else \
                    (t + 1) % 256 or 1
            counts[i] = len(cont)
        return drafts, counts


def _alloc_state(bt):
    # bt._free became a list of PER-GROUP lists in the batch-sharded-ep
    # PR; list(bt._free) is now a SHALLOW copy whose inner lists keep
    # mutating as the run continues — every snapshot silently showed the
    # plain run's FINAL free list. Copy the inner lists too.
    return (bt.tables.copy(), bt.owned.copy(), [list(f) for f in bt._free])


def test_partial_accept_rollback_matches_token_by_token():
    """After EVERY speculative tick with a partial accept, the engine's
    visible state — gen stream, cache_len, last_tok, the slot's block
    table row, owned counts AND the allocator free list — equals the
    state the plain token-by-token engine holds at the same generated
    length. Single active request, so the equality is exact page ids,
    not just counts (multi-slot ticks batch their optimistic grows, which
    permutes which physical page serves which slot — pure indirection)."""
    req = _workload("gpt2", n=1, max_new=9)[0]

    plain = _engine("gpt2")
    plain.submit(Request(req.req_id, list(req.tokens), req.max_new_tokens,
                         req.seed))
    snaps = {}
    done = []
    while plain.has_work():
        done += plain.step()
        # the plain engine runs a tick ahead of its reads: read the tick's
        # tokens (a drain), so the snapshot is the in-order state
        plain.export_records()
        s = plain.slots[0]
        if s is not None:
            snaps[len(s.gen)] = (_alloc_state(plain.tables), s.cache_len,
                                 s.last_tok, list(s.gen))
    script = {req.req_id: done[0].tokens}

    spec = _engine("gpt2")
    spec._speculator = Speculator(
        spec, _ScriptedDrafter(k=3, script=script), k=3)
    spec.submit(Request(req.req_id, list(req.tokens), req.max_new_tokens,
                        req.seed))
    out = []
    while spec.has_work():
        out += spec.step()
        s = spec.slots[0]
        if s is None:
            continue
        alloc, cache_len, last, gen = snaps[len(s.gen)]
        assert (s.cache_len, s.last_tok, list(s.gen)) == (cache_len, last,
                                                          gen)
        tables, owned, free = _alloc_state(spec.tables)
        np.testing.assert_array_equal(tables, alloc[0])
        np.testing.assert_array_equal(owned, alloc[1])
        assert free == alloc[2]
    assert out[0].tokens == done[0].tokens
    st = spec.stats
    # the scripted drafter guarantees partial accepts happened: some
    # proposals accepted, some rejected — both halves of commit ran
    assert 0 < st["spec_accepted"] < st["spec_proposed"]


def test_constrained_pool_overflow_matches_plain():
    """Regression (the WITHIN-tick pin): on a symmetric workload under a
    tight explicit num_blocks pool, the speculative tick must
    overflow-evict the SAME requests with the SAME outputs as the plain
    engine. The original single-phase optimistic grow let an
    earlier-indexed slot take up to k draft pages before a later slot
    reserved its one mandatory write, flipping which request overflowed.
    The two-phase grow (mandatory writes first — the plain tick's exact
    loop — then drafts from the leftover pool only) pins the overflow
    rule identical; pool sizes below/at/above exhaustion all covered.
    (Asymmetric workloads, where cross-tick progress differs by design,
    get the weaker-but-unconditional pin in
    test_asymmetric_pool_overflow_stays_prefix_consistent.)"""
    vocab = _cached_model("gpt2").cfg.vocab_size
    rng = np.random.default_rng(11)
    motif = list(map(int, rng.integers(1, vocab, 5)))
    reqs = [Request("r0", motif * 2, 12, 0), Request("r1", motif * 2, 12, 1)]

    def run(speculate, nb):
        eng = _engine("gpt2", max_seqs=2, num_blocks=nb,
                      speculate=speculate)
        out = _run(eng, reqs)
        return {rid: (c.reason, list(c.tokens)) for rid, c in out.items()}

    for nb in (8, 10, 12):
        plain, spec = run("", nb), run("ngram:4", nb)
        assert plain == spec, f"num_blocks={nb}: {plain} vs {spec}"
        if nb == 8:  # the tight pool actually exercises the contention
            assert any(r == "overflow" for r, _ in plain.values())


def test_asymmetric_pool_overflow_stays_prefix_consistent():
    """The unconditional exhaustion invariant: on an ASYMMETRIC workload
    (one repetitive high-accept prompt + one random zero-signal prompt)
    a tight pool may overflow-evict a DIFFERENT request under speculation
    — the eviction is a race against pool exhaustion and speculation
    changes per-tick progress, not the stream — but every request's
    output in either run must be a PREFIX of its output in the other
    (both emit the same pinned per-request stream), and any request that
    completes (eos/length) in both runs must be identical."""
    vocab = _cached_model("gpt2").cfg.vocab_size
    rng = np.random.default_rng(11)
    motif = list(map(int, rng.integers(1, vocab, 4)))
    reqs = [Request("rep", motif * 4, 40, 0),
            Request("rand", list(map(int, rng.integers(1, vocab, 16))),
                    40, 1)]

    def run(speculate, nb):
        eng = _engine("gpt2", max_seqs=2, num_blocks=nb,
                      max_blocks_per_seq=16, speculate=speculate)
        return _run(eng, reqs)

    for nb in (12, 16, 32):
        plain, spec = run("", nb), run("ngram:4", nb)
        for rid in ("rep", "rand"):
            p, s = plain[rid], spec[rid]
            short, long_ = sorted((list(p.tokens), list(s.tokens)), key=len)
            assert long_[:len(short)] == short, \
                f"num_blocks={nb} {rid}: outputs not prefix-consistent"
            if p.reason != "overflow" and s.reason != "overflow":
                assert (p.reason, list(p.tokens)) == (s.reason,
                                                      list(s.tokens)), \
                    f"num_blocks={nb} {rid}: completed outputs differ"


def test_eos_inside_accepted_prefix_truncates_exactly():
    """An EOS token landing INSIDE the accepted prefix must finish the
    request exactly where the token-by-token run would — trailing
    accepted drafts after the EOS are discarded, never emitted."""
    req = _workload("gpt2", n=1, max_new=10)[0]
    base = _run(_engine("gpt2"), [req])[req.req_id]
    eos = base.tokens[4]  # pretend the 5th greedy token is EOS
    plain = _run(_engine("gpt2", eos_id=eos), [req])[req.req_id]
    assert plain.reason == "eos" and len(plain.tokens) <= len(base.tokens)

    spec = _engine("gpt2", eos_id=eos)
    spec._speculator = Speculator(
        spec, _ScriptedDrafter(k=4, script={req.req_id: base.tokens},
                               wrong_at=10), k=4)
    out = _run(spec, [req])[req.req_id]
    assert out.tokens == plain.tokens and out.reason == "eos"


# ------------------------------------------------- grammar and guards
def test_parse_speculate_grammar():
    assert parse_speculate("ngram:4") == ("ngram", 4)
    assert parse_speculate("draft:2") == ("draft", 2)
    with pytest.raises(ValueError, match="unknown drafter"):
        parse_speculate("medusa:4")
    with pytest.raises(ValueError, match="integer draft length"):
        parse_speculate("ngram")
    with pytest.raises(ValueError, match="integer draft length"):
        parse_speculate("ngram:x")
    with pytest.raises(ValueError, match=r"\[1, 16\]"):
        parse_speculate("ngram:0")
    with pytest.raises(ValueError, match=r"\[1, 16\]"):
        parse_speculate("draft:99")


def test_ngram_propose_suffix_lookup():
    # longest suffix [7,8] recurs at index 1; continuation follows it
    assert ngram_propose([5, 7, 8, 9, 4, 7, 8], 3) == [9, 4, 7]
    assert ngram_propose([5, 7, 8, 9, 4, 7, 8], 1) == [9]
    # no earlier occurrence of any suffix → no proposal
    assert ngram_propose([1, 2, 3, 4], 4) == []
    # the MOST RECENT earlier occurrence wins (prefer fresh context)
    assert ngram_propose([1, 2, 9, 1, 2, 5, 1, 2], 2) == [5, 1]
    # degenerate inputs propose nothing
    assert ngram_propose([], 4) == []
    assert ngram_propose([3], 4) == []
    assert ngram_propose([1, 2, 3], 0) == []


def test_ngram_incremental_index_matches_reference():
    """NGramDrafter's incremental suffix index proposes EXACTLY what the
    naive full-history rescan (ngram_propose, the reference) would, across
    random low-vocab histories grown token by token — the engine's shape:
    admit a prompt, then gen grows between proposes."""

    class _Req:
        def __init__(self, toks):
            self.tokens = toks

    class _Slot:
        def __init__(self, toks):
            self.req = _Req(toks)
            self.gen = []

    rng = np.random.default_rng(13)
    for _ in range(20):
        vocab = int(rng.integers(2, 6))  # tiny vocab → dense collisions
        prompt = list(map(int, rng.integers(0, vocab,
                                            int(rng.integers(1, 12)))))
        d = NGramDrafter(k=4)
        slot = _Slot(prompt)
        d.admit(0, list(prompt))
        for _ in range(30):
            slot.gen.append(int(rng.integers(0, vocab)))
            desired = np.array([int(rng.integers(0, 5))], np.int32)
            drafts, counts = d.propose([0], [slot], desired)
            ref = ngram_propose(prompt + slot.gen, int(desired[0]))
            assert int(counts[0]) == len(ref)
            assert list(map(int, drafts[0, :counts[0]])) == ref
        d.evict(0)
        assert not d._hist and not d._index  # eviction drops the state


def test_draft_spec_requires_draft_model():
    with pytest.raises(ValueError, match="needs a draft model"):
        ServingEngine(_cached_model("gpt2"),
                      ServeConfig(max_seqs=2, block_size=4,
                                  max_blocks_per_seq=4, speculate="draft:2"))


def test_cli_draft_without_path_refused(tmp_path):
    """`--speculate draft:<k>` with no --draft_model_path must refuse at
    the CLI: run_generate.build treats model_path=None as random-init
    smoke mode, so without the guard the user gets a random-weights
    drafter whose proposals all reject — every tick silently pays the
    draft dispatch plus the k+1-wide verify for nothing."""
    from distributed_lion_tpu.cli.run_serve import main

    reqs = tmp_path / "requests.jsonl"
    reqs.write_text('{"id": "r1", "prompt": "ab", "max_new_tokens": 2}\n')
    with pytest.raises(ValueError, match="draft_model_path"):
        main(["--model_family", "gpt2", "--model_name", "tiny",
              "--requests", str(reqs), "--out", str(tmp_path / "o.jsonl"),
              "--speculate", "draft:2"])


def test_draft_model_vocab_mismatch_refused():
    gpt2 = _cached_model("gpt2")
    other = _model("llama")  # vocab 256 too? ensure mismatch via config
    if other.cfg.vocab_size == gpt2.cfg.vocab_size:
        import dataclasses

        cfg = dataclasses.replace(GPT2Config.tiny(), vocab_size=128)
        other = ServeModel.for_gpt2(gpt2_init(jax.random.key(1), cfg), cfg)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(gpt2, ServeConfig(max_seqs=2, block_size=4,
                                        max_blocks_per_seq=4,
                                        speculate="draft:2"),
                      draft_model=other)


def test_moe_draft_speculation_refused_ngram_composes():
    """ISSUE 15: ngram speculation composes with MoE (speculative==plain
    pinned in tests/test_moe_serve.py), but draft:<k> keeps its loud
    refusal naming the mirror-pool residual — the draft mirror's own page
    pool has no sharded budget under expert parallelism."""
    cfg = GPT2Config.tiny(moe_experts=2)
    params = gpt2_init(jax.random.key(0), cfg)
    model = ServeModel.for_gpt2(params, cfg)
    with pytest.raises(ValueError, match="mirror"):
        ServingEngine(model, ServeConfig(max_seqs=2, block_size=4,
                                         max_blocks_per_seq=4,
                                         speculate="draft:2"),
                      draft_model=model)
    # ngram builds (and the equivalence pin lives in test_moe_serve)
    eng = ServingEngine(model, ServeConfig(max_seqs=2, block_size=4,
                                           max_blocks_per_seq=4,
                                           speculate="ngram:2"))
    assert eng._speculator is not None


def test_draft_cache_desync_is_loud():
    """A drafter bookkeeping bug (draft mirror length != target cache
    length) raises, never silently serves from a skewed cache."""
    eng = _engine("gpt2", speculate="draft:2")
    reqs = _workload("gpt2", n=1)
    eng.submit(Request(reqs[0].req_id, list(reqs[0].tokens), 6, 0))
    eng.step()
    drafter = eng._speculator.drafter
    drafter.len[0] += 1  # corrupt the mirror
    with pytest.raises(RuntimeError, match="desync"):
        eng.step()


def test_run_serve_cli_speculate_smoke(tmp_path):
    from distributed_lion_tpu.cli.run_serve import main

    reqs = tmp_path / "requests.jsonl"
    reqs.write_text(
        '{"id": "r1", "prompt": "abab", "max_new_tokens": 4}\n')
    out = tmp_path / "responses.jsonl"
    records = main(["--model_family", "gpt2", "--model_name", "tiny",
                    "--requests", str(reqs), "--out", str(out),
                    "--temperature", "0", "--max_seqs", "2",
                    "--block_size", "4", "--speculate", "ngram:2"])
    assert len(records) == 1 and records[0]["n_generated"] == 4


def test_speculative_journal_spans(tmp_path):
    from distributed_lion_tpu.train import journal

    j = journal.Journal(str(tmp_path))
    journal.install(j)
    try:
        eng = _engine("gpt2", speculate="ngram:2")
        _run(eng, _workload("gpt2", n=2, max_new=4))
    finally:
        journal.uninstall(j)
        j.close()
    names = {r["name"] for r in j.tail() if r["kind"] == "span"}
    assert {"serve/draft", "serve/verify", "serve/commit"} <= names


# -------------------------------------------- prefix sharing (ISSUE 13)
def test_speculative_rollback_over_shared_pages_pinned():
    """Speculation × prefix sharing: requests sharing a cached prompt
    prefix draft/verify/commit with rollback shrinking REFS, never
    freeing pages a neighbor or the cache still holds — outputs pinned
    to the plain unshared engine (greedy AND sampled) and the pool
    conserved after the workload drains."""
    vocab = _cached_model("gpt2").cfg.vocab_size
    rng = np.random.default_rng(23)
    motif = list(map(int, rng.integers(1, vocab, 4)))
    sys_p = motif * 3                       # 12 tokens: repetitive AND shared
    prompts = [sys_p + list(map(int, rng.integers(1, vocab, 2)))
               for _ in range(4)] + [list(sys_p)]
    reqs = [Request(req_id=f"r{i}", tokens=list(p), max_new_tokens=8,
                    seed=i) for i, p in enumerate(prompts)]
    for samp in (dict(temperature=0.0), dict(temperature=0.9, top_k=40)):
        plain = _run(_engine("gpt2", num_blocks=96, max_blocks_per_seq=16,
                             **samp),
                     [Request(r.req_id, list(r.tokens), r.max_new_tokens,
                              r.seed) for r in reqs])
        eng = _engine("gpt2", num_blocks=96, max_blocks_per_seq=16,
                      prefix_cache=True, speculate="ngram:4", **samp)
        out = _run(eng, [Request(r.req_id, list(r.tokens),
                                 r.max_new_tokens, r.seed) for r in reqs])
        for r in reqs:
            assert out[r.req_id].tokens == plain[r.req_id].tokens, r.req_id
            assert out[r.req_id].reason == plain[r.req_id].reason
        assert eng.stats["spec_rounds"] > 0
        assert eng.stats["prefix_hits"] > 0
        # rollback + eviction left the pool conserved: every live ref is
        # the cache's, free + physical == pool, and no slot holds pages
        assert all(s is None for s in eng.slots)
        assert (eng.tables.physical_pages + eng.tables.free_blocks
                == eng.tables.num_blocks)
        assert int(eng.tables.refs.sum()) == eng.tables.physical_pages


def test_speculative_shared_partial_accept_state_matches_unshared():
    """A partial accept over a table row whose PREFIX pages are shared:
    shrink hands back only the private tail pages (the shared run's refs
    are untouched), leaving len/last/table state equal to the unshared
    engine's on the same stream."""
    vocab = _cached_model("gpt2").cfg.vocab_size
    rng = np.random.default_rng(29)
    sys_p = list(map(int, rng.integers(1, vocab, 9)))
    reqs = [Request(req_id=f"r{i}", tokens=sys_p + [int(t)],
                    max_new_tokens=6, seed=i)
            for i, t in enumerate(rng.integers(1, vocab, 3))]
    plain_eng = _engine("gpt2", num_blocks=96, max_blocks_per_seq=16)
    plain = _run(plain_eng, [Request(r.req_id, list(r.tokens),
                                     r.max_new_tokens, r.seed)
                             for r in reqs])
    eng = _engine("gpt2", num_blocks=96, max_blocks_per_seq=16,
                  prefix_cache=True, speculate="ngram:2")
    out = _run(eng, [Request(r.req_id, list(r.tokens), r.max_new_tokens,
                             r.seed) for r in reqs])
    for r in reqs:
        assert out[r.req_id].tokens == plain[r.req_id].tokens, r.req_id
    # the cached run survived every rollback/evict cycle intact
    run, covered = eng.prefix.match(sys_p + [int(vocab - 1)])
    assert covered >= 8 and len(run) >= 2
