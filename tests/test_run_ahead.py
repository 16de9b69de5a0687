"""The serving engine's run-ahead tick (ISSUE 36): ``step()`` t+1 enqueues its
decode dispatch before the host reads tick t's tokens; the last token of a
row stays on the device (``prev``), ends the host can foresee (the budget)
retire a slot at dispatch, an end it cannot (EOS) costs one discarded row,
and a decision that needs an unread token drains first.

What these pin, at TINY on the CPU:

(a) every family builder: tokens, reasons and tick clocks against the greedy
    reference, with budgets that end on different ticks (1, 2, ...);
(b) an EOS mid-run: the output ends at EOS, one row is dropped, no token is
    counted that was not delivered, pages return, the prefix cache holds
    prompt tokens only;
(c) the order of spans, and the three drains (overflow, a resident's
    deadline, ``export_records``) with the partial output an in-order engine
    gives;
(d) ``has_work()`` while a read is outstanding, ``run()`` returns everything;
(e) an engine with a speculator runs, and says it runs, in order.
"""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.train import journal as journal_mod  # noqa: E402

# budgets that end on different ticks: 1 is retired at its prefill's
# dispatch, 2 at the same tick's decode dispatch, the rest later; more
# requests than slots, so a retired slot is taken again while its last
# token is still unread
BUDGETS = (5, 1, 7, 2, 3, 6)
PROMPT = 6


def _fresh(r):
    return Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)


def _requests(vocab, budgets=BUDGETS, seed=36):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, tokens=rng.integers(1, vocab, PROMPT).tolist(),
                    max_new_tokens=m, seed=0) for i, m in enumerate(budgets)]


# ------------------------------------------------------------- the families
def _dense(family):
    """(engine factory, vocab, greedy reference) of a dense family: the
    dense-KV ``generate`` at the engine's attended length (8 pages of 4),
    which the paged engine equals bit for bit (tests/test_serve.py)."""
    from distributed_lion_tpu.models import gpt2, llama
    from distributed_lion_tpu.models.generate import generate

    if family == "gpt2":
        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.gpt2_init(jax.random.key(0), cfg)
        model, decode, cache = (ServeModel.for_gpt2(params, cfg),
                                gpt2.gpt2_decode, gpt2.gpt2_init_cache)
    else:
        cfg = llama.LlamaConfig.tiny()
        params = llama.llama_init(jax.random.key(0), cfg)
        model, decode, cache = (ServeModel.for_llama(params, cfg),
                                llama.llama_decode, llama.llama_init_cache)

    def engine(time_fn=None, **kw):
        base = dict(max_seqs=3, block_size=4, max_blocks_per_seq=8)
        return ServingEngine(
            model, ServeConfig(**dict(base, **kw)),
            **({"time_fn": time_fn} if time_fn else {}))

    def reference(reqs):
        out = np.asarray(generate(
            partial(lambda c, p, t, k, pos, off=None:
                    decode(p, t, c, k, pos, off), cfg),
            partial(cache, cfg), params,
            jnp.asarray([r.tokens for r in reqs], jnp.int32),
            max(r.max_new_tokens for r in reqs), max_len=4 * 8))
        return {r.req_id: out[i, :r.max_new_tokens].tolist()
                for i, r in enumerate(reqs)}

    return engine, cfg.vocab_size, reference


def _expert(family):
    """The same of a dropless-expert family, built as its own test file
    builds it: the plain reference's first choices over the served rows."""
    import importlib

    fam = importlib.import_module("benchmark.families." + {
        "joyai": "joyai_llm_flash", "laguna": "laguna",
        "ling": "ling_3_flash"}[family])
    models = importlib.import_module(
        "distributed_lion_tpu.models." + family)
    config = {"joyai": "JoyAIConfig", "laguna": "LagunaConfig",
              "ling": "LingConfig"}[family]
    ref, tiny = fam.reference, fam.TINY
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 36), tiny, jnp.float32)
    cfg = getattr(models, config).from_hf(
        tiny, param_dtype=jnp.float32, compute_dtype=jnp.float32)
    model = getattr(ServeModel, "for_" + family)(
        fam.to_program(weights), cfg)

    def engine(**kw):
        base = dict(max_seqs=3, block_size=8, max_blocks_per_seq=8,
                    prefill_cap_tokens=64, moe_stats=True)
        return ServingEngine(model, ServeConfig(**dict(base, **kw)))

    def reference(reqs, served):
        rows = np.zeros((len(reqs), 64), np.int32)
        for i, r in enumerate(reqs):
            seq = list(r.tokens) + served[r.req_id]
            rows[i, :len(seq)] = seq
        first = np.asarray(jax.jit(
            lambda x: ref.forward(weights, x, tiny).argmax(-1))(rows))
        return {r.req_id: first[i, PROMPT - 1:PROMPT - 1
                                + r.max_new_tokens].tolist()
                for i, r in enumerate(reqs)}

    return engine, 256, reference


_BUILT = {}


def _family(name):
    if name not in _BUILT:
        _BUILT[name] = (_dense if name in ("gpt2", "llama")
                        else _expert)(name)
    return _BUILT[name]


# ------------------------------------------- (a) parity, family by family
@pytest.mark.parametrize("family", ["gpt2", "llama", "joyai", "laguna",
                                    "ling"])
def test_completions_equal_the_greedy_reference(family):
    engine, vocab, reference = _family(family)
    reqs = _requests(vocab)
    eng = engine()
    out = eng.run([_fresh(r) for r in reqs], arrivals={4: 2, 5: 3})
    served = {rid: c.tokens for rid, c in out.items()}
    want = reference(reqs) if family in ("gpt2", "llama") \
        else reference(reqs, served)
    for r in reqs:
        c = out[r.req_id]
        assert c.tokens == want[r.req_id], r.req_id
        assert c.reason == "length" and len(c.tokens) == r.max_new_tokens
        # the clocks name the tick whose DISPATCH made each token
        # (benchmark/lib/ticklog.consistent), however late the host read it
        assert c.timing["decode_ticks"] == max(r.max_new_tokens - 2, 0)
        assert c.timing["delivery_lag_ticks"] in (0, 1)
    # a budget of 1 ends with its prefill, whose read is made in the tick
    # that dispatched it; every longer one ends with a decode dispatch,
    # read one step() later
    assert {r.max_new_tokens: out[r.req_id].timing["delivery_lag_ticks"]
            for r in reqs} == {m: int(m > 1) for m in BUDGETS}
    st = eng.stats
    assert st["run_ahead_discarded"] == 0 and st["run_ahead_drains"] == 0
    # every decode dispatch but the first of a busy stretch found a read
    # outstanding
    assert 0 < st["run_ahead_ticks"] <= st["decode_ticks"]
    assert st["prefill_dispatches"] == len(reqs)
    assert st["decode_tokens"] == sum(BUDGETS) - len(reqs)
    assert eng.compile_counts()["decode"] == 1
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()
    assert not eng.has_work()


# ------------------------------------------------- (b) an end by EOS
def test_eos_mid_run_costs_one_discarded_row():
    engine, vocab, _ = _family("gpt2")
    # sampled, each request from its own seed, so that the tokens differ
    # (greedy, a TINY model repeats one): a row's key is (its seed, the
    # token's index), with or without an EOS
    reqs = [Request(r.req_id, r.tokens, r.max_new_tokens, seed=r.req_id)
            for r in _requests(vocab, budgets=(8, 8, 8))]
    samp = dict(temperature=1.0)
    full = {rid: c.tokens for rid, c in engine(**samp).run(
        [_fresh(r) for r in reqs]).items()}
    # a token that one request makes mid-run (not first, not last) and no
    # request makes anywhere else: declared EOS, it ends that request alone
    flat = [t for toks in full.values() for t in toks]
    rid, at = next((rid, i) for rid, toks in full.items()
                   for i, t in enumerate(toks)
                   if 1 <= i < len(toks) - 1 and flat.count(t) == 1)
    eng = engine(eos_id=full[rid][at], prefix_cache=True, **samp)
    out = eng.run([_fresh(r) for r in reqs])
    for r in reqs:
        if r.req_id == rid:
            assert out[rid].reason == "eos"
            assert out[rid].tokens == full[rid][:at + 1]
        else:
            assert out[r.req_id].reason == "length"
            assert out[r.req_id].tokens == full[r.req_id]
    st = eng.stats
    # its next row was enqueued before the host saw the EOS: dropped, never
    # appended, never counted
    assert st["run_ahead_discarded"] == 1
    delivered = sum(len(c.tokens) for c in out.values())
    assert st["prefill_dispatches"] + st["decode_tokens"] == delivered
    # what the cache still shares is prompt tokens (committed at
    # admission), never the dropped row; give those back and the pool is
    # whole
    chains = eng.export_prefix_chains()
    assert chains and all(
        any(chain == r.tokens[:len(chain)] for r in reqs)
        for chain in chains)
    assert int(eng.tables.refs.sum()) == eng.tables.physical_pages
    eng.prefix.reclaim(eng.cfg.resolved_num_blocks())
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()
    assert all(s is None for s in eng.slots) and not eng.has_work()


def test_eos_as_the_first_token_ends_the_request_there():
    """The prefill's token joins the deferred read too: the tick's decode
    dispatch is enqueued before the host sees it was EOS."""
    engine, vocab, reference = _family("gpt2")
    req = _requests(vocab, budgets=(6,))[0]
    first = reference([req])[req.req_id][0]
    eng = engine(eos_id=first)
    out = eng.run([_fresh(req)])[req.req_id]
    assert (out.tokens, out.reason) == ([first], "eos")
    assert eng.stats["run_ahead_discarded"] == 1
    assert eng.stats["decode_tokens"] == 0
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()


# --------------------------------------- (c) span order, and the drains
def _journaled(tmp_path, body):
    jrnl = journal_mod.Journal(str(tmp_path))
    journal_mod.install(jrnl)
    try:
        result = body()
    finally:
        journal_mod.uninstall(jrnl)
        jrnl.close()
    return result, [r for r in jrnl.tail() if r["kind"] == "span"]


def test_dispatch_of_the_next_tick_precedes_the_read_of_this_one(tmp_path):
    engine, vocab, _ = _family("gpt2")
    reqs = _requests(vocab, budgets=(6, 6, 6))
    _, spans = _journaled(
        tmp_path, lambda: engine().run([_fresh(r) for r in reqs]))
    # a span's id is drawn when it opens: ids order the openings
    tick_of = {s["id"]: s["tick"] for s in spans if s["name"] == "serve/tick"}
    parent = {s["id"]: s["parent"] for s in spans}

    def tick(span):
        at = span["id"]
        while at not in tick_of:
            at = parent[at]
        return tick_of[at]

    dispatch = {tick(s): s["id"] for s in spans
                if s["name"] == "serve/decode_dispatch"}
    reads = {s["tick"]: s["id"] for s in spans
             if s["name"] == "serve/token_read" and s["of"] == "decode"}
    assert sorted(dispatch) == [1, 2, 3, 4, 5] == sorted(reads)
    for t in (1, 2, 3, 4):
        assert dispatch[t] < dispatch[t + 1] < reads[t], t
    # the prefills' tokens are read behind the tick's decode dispatch too
    first = [s["id"] for s in spans if s["name"] == "serve/token_read"
             and s["of"] == "prefill"]
    assert len(first) == 3 and all(dispatch[1] < i for i in first)
    assert not any(s["name"] == "serve/drain" for s in spans)


def _drain_overflow(engine, req, full):
    # 8 positions a row: 6 of the prompt, then the writes of tokens 1 and 2;
    # token 3 is sampled from them and the write of it cannot be placed
    eng = engine(max_seqs=2, block_size=4, max_blocks_per_seq=2)
    out = eng.run([_fresh(req)])[req.req_id]
    return eng, out, "overflow", full[:3]


def _drain_deadline(engine, req, full):
    # the clock passes the deadline between step() 3 and step() 4: an
    # in-order engine has read four tokens by then (two in the first tick)
    now = [0.0]
    eng = engine(time_fn=lambda: now[0])
    eng.submit(Request(req.req_id, list(req.tokens), req.max_new_tokens, 0,
                       deadline_s=1.0))
    done = []
    for _ in range(3):
        done += eng.step()
    assert not done
    now[0] = 2.0
    done += eng.step()
    return eng, done[0], "timeout", full[:4]


def _drain_export(engine, req, full):
    eng = engine()
    eng.submit(_fresh(req))
    for _ in range(3):
        assert not eng.step()
    assert len(eng.slots[0].gen) == 3       # the fourth is made, not read
    (rec,) = eng.export_records()
    assert list(rec.committed) == full[:4]
    done = []
    while eng.has_work():
        done += eng.step()
    return eng, done[0], "length", full


@pytest.mark.parametrize("case,why", [
    pytest.param(_drain_overflow, "overflow", id="overflow"),
    pytest.param(_drain_deadline, "deadline", id="deadline"),
    pytest.param(_drain_export, "export_records", id="export_records")])
def test_a_decision_that_needs_unread_tokens_drains_first(tmp_path, case,
                                                          why):
    engine, vocab, reference = _family("gpt2")
    req = _requests(vocab, budgets=(12,))[0]
    full = reference([req])[req.req_id]
    (eng, out, reason, tokens), spans = _journaled(
        tmp_path, lambda: case(engine, req, full))
    assert (out.reason, out.tokens) == (reason, tokens)
    drains = [s for s in spans if s["name"] == "serve/drain"]
    assert [s["reason"] for s in drains] == [why]
    assert eng.stats["run_ahead_drains"] == 1
    assert eng.stats["run_ahead_discarded"] == 0
    assert eng.stats["prefill_dispatches"] + eng.stats["decode_tokens"] \
        == len(out.tokens)
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()
    assert not eng.has_work()


# --------------------------------------------- (d) an outstanding read
def test_has_work_while_a_read_is_outstanding():
    engine, vocab, reference = _family("gpt2")
    req = _requests(vocab, budgets=(2,))[0]
    eng = engine()
    eng.submit(_fresh(req))
    assert eng.step() == []
    # both tokens are dispatched and the slot is retired; the host has read
    # the prefill's token and counts that one alone
    assert all(s is None for s in eng.slots) and not eng.pending
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()
    assert eng.has_work()
    assert (eng.stats["prefill_dispatches"], eng.stats["decode_tokens"]) \
        == (1, 0)
    (c,) = eng.step()
    assert c.tokens == reference([req])[req.req_id] and c.reason == "length"
    assert c.timing["decode_ticks"] == 0
    assert c.timing["delivery_lag_ticks"] == 1
    assert eng.stats["decode_tokens"] == 1 and not eng.has_work()
    # the second step() dispatched nothing
    assert eng.stats["decode_ticks"] == 1 and eng.stats["ticks"] == 2


def test_run_returns_every_completion_and_counts_what_it_read():
    engine, vocab, reference = _family("gpt2")
    reqs = _requests(vocab, budgets=(3, 1, 2, 4, 1, 5, 2))
    eng = engine()
    seen = []
    for r in reqs:
        eng.submit(_fresh(r))
    while eng.has_work():
        done = eng.step()
        seen += done
        # never a token in the counters that the host has not read: they
        # hold what was delivered and what the unfinished requests (those
        # retired at dispatch among them) have in ``gen``
        live = {id(s): s for s in eng.slots if s is not None}
        live.update((id(s), s) for u in eng._unread for _, s in u.rows
                    if not s.done)
        assert eng.stats["prefill_dispatches"] + eng.stats["decode_tokens"] \
            == sum(len(c.tokens) for c in seen) \
            + sum(len(s.gen) for s in live.values())
    want = reference(reqs)
    assert {c.req_id: c.tokens for c in seen} == want
    assert len(seen) == len(reqs)


# ------------------------------------------------- (e) a speculator
def test_an_engine_with_a_speculator_runs_in_order(capsys):
    engine, vocab, reference = _family("gpt2")
    reqs = _requests(vocab, budgets=(5, 1, 7, 2))
    plain = engine()
    assert "[setup] decode: run-ahead 1 tick (device-fed last token)" \
        in capsys.readouterr().err
    spec = engine(speculate="ngram:2")
    assert "[setup] decode: in order (speculation)" in capsys.readouterr().err
    assert plain._run_ahead and not spec._run_ahead
    out = spec.run([_fresh(r) for r in reqs])
    assert {rid: c.tokens for rid, c in out.items()} == reference(reqs)
    assert all(c.timing["delivery_lag_ticks"] == 0 for c in out.values())
    assert spec.stats["run_ahead_ticks"] == 0
    assert spec.stats["run_ahead_drains"] == 0
    assert spec.stats["run_ahead_discarded"] == 0
