"""The one span primitive (train/journal.span), its gate, the traced buffer,
the span tree inside the serving tick, the compile ledger and the names the
model and op files put on the lowered programs (ISSUE 23).

What these pin:

- gate off (no profiler session, no installed journal): nothing is
  recorded and every span is the one shared null span;
- inside a ``start_trace``/``stop_trace`` pair the buffer holds ``id``,
  ``parent``, ``t0 <= t1`` and the span's identifier, and the profiler's
  ``.xplane.pb`` holds annotations of the same names plus ``journal/clock``;
- the buffer outlives ``journal.uninstall`` and a deleted engine, starts
  empty at each session, is bounded and counts what it drops;
- a tiny engine's ``serve/tick`` children cover at least 95% of it and the
  self times of a tick's tree sum to the tick;
- the ledger names a jitted function, splits trace / lower / compile and
  counts a second specialisation; the retrace guards name the program;
- the lowered train step and decode dispatch hold every named scope.
"""

import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.train import journal
from distributed_lion_tpu.utils import compile_cache

journal.register_profiler(jax.profiler.TraceAnnotation)


def _start(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # annotations only: a small trace
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, dict(e.stats)) for e in line.events]
    return out


def _tiny_engine(**kw):
    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
    from distributed_lion_tpu.serve.engine import (
        ServeConfig, ServeModel, ServingEngine)

    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    scfg = ServeConfig(max_seqs=4, block_size=4, max_blocks_per_seq=8, **kw)
    return ServingEngine(ServeModel.for_gpt2(params, cfg), scfg), cfg


def _requests(cfg, n=5):
    from distributed_lion_tpu.serve.engine import Request

    rng = np.random.default_rng(3)
    return [Request(req_id=i, tokens=[int(t) for t in rng.integers(
        1, cfg.vocab_size, (3, 9, 5, 14, 2)[i % 5])], max_new_tokens=6,
        seed=i) for i in range(n)]


# ------------------------------------------------------------------ the gate
def test_gate_off_records_nothing_and_shares_the_null_span():
    assert journal.active() is journal.NULL
    before = journal.traced()
    a = journal.span("dispatch", step=1)
    b = journal.span("serve/tick", tick=2)
    assert a is b is journal._NULL_SPAN
    with a as s:
        s.set(batch=3)
    assert journal.traced() == before


def test_session_records_tree_and_annotations(tmp_path):
    _start(tmp_path)
    try:
        with journal.span("serve/tick", tick=7):
            with journal.span("serve/admit", pending=2) as admit:
                admit.set(prefills=1)
                with journal.span("serve/prefill", req_id="r1"):
                    pass
        with journal.span("dispatch", step=11, steps=1):
            pass
    finally:
        jax.profiler.stop_trace()
    recs = {r["name"]: r for r in journal.traced()}
    assert set(recs) == {"serve/tick", "serve/admit", "serve/prefill",
                         "dispatch"}
    assert all(r["t0"] <= r["t1"] for r in recs.values())
    assert len({r["id"] for r in recs.values()}) == 4
    assert recs["serve/tick"]["parent"] is None
    assert recs["serve/admit"]["parent"] == recs["serve/tick"]["id"]
    assert recs["serve/prefill"]["parent"] == recs["serve/admit"]["id"]
    assert recs["dispatch"]["parent"] is None
    assert recs["serve/tick"]["tick"] == 7
    assert recs["serve/prefill"]["req_id"] == "r1"
    assert recs["dispatch"]["step"] == 11
    assert recs["serve/admit"]["prefills"] == 1      # set() inside the span
    # a parent encloses its children on the one clock
    assert recs["serve/tick"]["t0"] <= recs["serve/admit"]["t0"]
    assert recs["serve/admit"]["t1"] <= recs["serve/tick"]["t1"]
    events = _host_events(tmp_path)
    names = {name for name, _ in events}
    assert set(recs) | {"journal/clock"} <= names
    clock = next(stats for name, stats in events if name == "journal/clock")
    assert int(clock["monotonic_ns"]) <= recs["serve/tick"]["t0"] * 1e9 + 1e6
    tick = next(stats for name, stats in events if name == "serve/tick")
    assert int(tick["tick"]) == 7
    # the session is over: the gate is off again
    assert journal.span("dispatch", step=12) is journal._NULL_SPAN


def test_span_open_at_a_session_edge(tmp_path):
    straddles_start = journal.span("data_wait", step=1)   # gate off: null
    with straddles_start:
        _start(tmp_path)
    try:
        straddles_end = journal.span("dispatch", step=1)  # gate on: real
        straddles_end.__enter__()
    finally:
        jax.profiler.stop_trace()
    straddles_end.__exit__(None, None, None)
    assert [r["name"] for r in journal.traced()] == ["dispatch"]


def test_buffer_is_bounded_counts_drops_and_restarts(tmp_path, monkeypatch):
    import collections

    monkeypatch.setattr(journal, "TRACED_MAX", 8)
    monkeypatch.setattr(journal, "_TRACED", collections.deque(maxlen=8))
    _start(tmp_path / "a")
    try:
        for i in range(11):
            with journal.span("dispatch", step=i):
                pass
    finally:
        jax.profiler.stop_trace()
    assert [r["step"] for r in journal.traced()] == list(range(3, 11))
    assert journal.traced_dropped() == 3
    # the gate is read when a span opens: the loop's next span (off: the
    # null span) is what tells the buffer that the session has ended
    assert journal.span("data_wait", step=11) is journal._NULL_SPAN
    _start(tmp_path / "b")            # a new session starts empty
    try:
        with journal.span("dispatch", step=99):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [r["step"] for r in journal.traced()] == [99]
    assert journal.traced_dropped() == 0


def test_installed_journal_gets_id_and_parent_and_validates(tmp_path):
    from tests.test_journal import validate_metrics

    jr = journal.Journal(str(tmp_path))
    journal.install(jr)
    try:
        with journal.span("serve/tick", tick=1):
            with journal.span("serve/expire"):
                pass
        setup = journal.SetupLaps("engine")
        setup.lap("setup/init_pages")
    finally:
        journal.uninstall(jr)
        jr.close()
    spans = {r["name"]: r for r in jr.tail() if r["kind"] == "span"}
    assert spans["serve/expire"]["parent"] == spans["serve/tick"]["id"]
    assert spans["serve/tick"]["parent"] is None and "dur" in spans["serve/tick"]
    assert spans["setup/init_pages"]["owner"] == "engine"
    path = os.path.join(str(tmp_path), journal.journal_filename(0))
    assert validate_metrics.validate_journal_file(path) == []
    with open(path, "a") as f:
        f.write('{"kind": "span", "name": "x", "t": 1.0, "rank": 0, '
                '"dur": 0.1, "id": "7", "parent": 1.5}\n')
    errors = validate_metrics.validate_journal_file(path)
    assert len(errors) == 2 and "'id'" in errors[0] and "'parent'" in errors[1]


# ------------------------------------------------------- the serving tick
def test_tick_tree_covers_the_tick_and_outlives_the_engine(tmp_path, capsys):
    eng, cfg = _tiny_engine()
    assert re.search(r"\[setup\] engine: place_weights [\d.]+ s, init_pages "
                     r"[\d.]+ s, build_dispatches [\d.]+ s\n",
                     capsys.readouterr().err)
    for r in _requests(cfg):
        eng.submit(r)
    for _ in range(3):                 # compiles happen outside the trace
        eng.step()
    _start(tmp_path)
    try:
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    jr = journal.Journal(None)
    journal.install(jr)
    journal.uninstall(jr)              # the buffer is not the journal's
    del eng
    gc.collect()
    spans = journal.traced()
    ticks = [r for r in spans if r["name"] == "serve/tick"]
    assert len(ticks) >= 4 and [t["tick"] for t in ticks] == sorted(
        t["tick"] for t in ticks)
    kids = {}
    for r in spans:
        kids.setdefault(r["parent"], []).append(r)

    def dur(r):
        return r["t1"] - r["t0"]

    def self_time(r):
        return dur(r) - sum(dur(c) for c in kids.get(r["id"], ()))

    def tree(r):
        yield r
        for c in kids.get(r["id"], ()):
            yield from tree(c)

    names, covered_s = set(), 0.0
    for tick in ticks:
        names |= {r["name"] for r in tree(tick)}
        covered = sum(dur(c) for c in kids.get(tick["id"], []))
        covered_s += covered
        # a tiny model's tick is a millisecond on the CPU, where the spans'
        # own cost shows: 95%, or all but 0.3 ms, of every tick
        assert dur(tick) - covered <= max(0.05 * dur(tick), 3e-4), tick
        assert sum(self_time(r) for r in tree(tick)) == pytest.approx(
            dur(tick), rel=1e-9, abs=1e-9)
    assert covered_s >= 0.95 * sum(dur(t) for t in ticks)
    assert {"serve/tick", "serve/expire", "serve/admit", "serve/decode_tick",
            "serve/decode_build", "serve/decode_dispatch",
            "serve/token_read", "serve/commit", "serve/evict"} <= names
    decode = next(r for r in spans if r["name"] == "serve/decode_tick")
    assert decode["batch"] >= 1


def test_token_streams_identical_with_a_session_on_and_off(tmp_path):
    def run(trace_dir):
        eng, cfg = _tiny_engine(temperature=0.9, top_k=40)
        if trace_dir:
            _start(trace_dir)
        try:
            return eng.run(_requests(cfg), {i: i // 2 for i in range(5)})
        finally:
            if trace_dir:
                jax.profiler.stop_trace()

    off, on = run(None), run(tmp_path)
    assert {i: (c.tokens, c.reason) for i, c in on.items()} == \
        {i: (c.tokens, c.reason) for i, c in off.items()}
    assert any(r["name"] == "serve/prefill" for r in journal.traced())


# --------------------------------------------------------- compile ledger
def test_ledger_names_the_program_and_counts_specialisations():
    compile_cache.listen()
    compile_cache.listen()             # registering twice counts once

    @jax.jit
    def ledger_probe_fn(x):
        return jnp.tanh(x @ x.T).sum()

    ledger_probe_fn(jnp.ones((8, 8)))
    row = compile_cache.ledger()["ledger_probe_fn"]
    assert (row["traces"], row["lowerings"], row["compiles"]) == (1, 1, 1)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    ledger_probe_fn(jnp.ones((8, 8)))              # cached: nothing new
    assert compile_cache.compiles_of("ledger_probe_fn") == 1
    ledger_probe_fn(jnp.ones((4, 8)))              # a second specialisation
    row = compile_cache.ledger()["ledger_probe_fn"]
    assert (row["traces"], row["lowerings"], row["compiles"]) == (2, 2, 2)
    assert (row["cache_hits"], row["cache_misses"]) == (0, 0)  # CPU: no cache
    lines = compile_cache.ledger_lines(min_s=0.0, only={"ledger_probe_fn"})
    assert len(lines) == 1 and lines[0].startswith(
        "[compile] ledger_probe_fn: trace ") and "(2 built" in lines[0]
    totals = compile_cache.totals()
    assert totals["compiles"] >= 2 and totals["trace_lower_s"] >= \
        row["trace_s"] + row["lower_s"]
    assert compile_cache.new_lines(min_s=0.0)       # said once ...
    assert compile_cache.new_lines(min_s=0.0) == []  # ... and only once


def test_serve_retrace_guard_names_the_program():
    eng, cfg = _tiny_engine(retrace_guard="error")
    eng.run(_requests(cfg, 2))
    assert eng._program_of("decode") == "decode_tick"
    assert compile_cache.compiles_of("decode_tick") >= 1
    with pytest.raises(RuntimeError, match=r"program 'decode_tick', built "
                                           r"\d+ time\(s\) so far"):
        eng._guard("decode", (jnp.zeros((3, 5), jnp.int32),))


# ------------------------------------------------------ names on the device
def _scope_times():
    from tests.test_journal import _load_by_path

    return _load_by_path("scope_times", "scripts/scope_times.py")


def _scopes(text):
    """Named scopes in a lowered program's locations, through the
    ``jvp(...)`` / ``transpose(...)`` wrappers autodiff puts around them
    (the names are the ones the operator's reader groups device time by)."""
    return {s for s in _scope_times().SCOPES
            if re.search(r'[("/]%s[)/"]' % re.escape(s), text)}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/while/body/closed_call/jvp(xent)/jit(take_along_axis)"
     "/select_n:", "xent"),
    ("jit(train_step)/while/body/checkpoint/attn/jit(flash_attention)/"
     "pallas_call:", "attn"),
    ("jit(train_step)/transpose(jvp(mlp))/btd,dce->btce/dot_general:", "mlp"),
    ("jit(decode_tick)/attn/paged_attn/paged_gather/jit(_take)/gather:",
     "paged_gather"),
    ("jit(train_step)/shard_map/vote/wire/all_to_all:", "vote/wire"),
    ("jit(train_step)/lion_apply/pallas_call:", "lion_apply"),
    ("jit(decode_tick)/mla_attn/jit(mla_paged_attn)/mla_paged_attn/"
     "pallas_call:", "mla_paged_attn"),
    ("jit(decode_tick)/mla/kv_latent/paged_scatter/scatter:",
     "paged_scatter"),
    ("jit(prefill)/moe/experts/jit(moe_gmm)/moe_gmm/pallas_call:", "moe_gmm"),
    ("jit(prefill)/moe/sort/jit(argsort)/sort:", "moe/sort"),
    ("jit(prefill)/moe/shared/mlp/dot_general:", "mlp"),
    ("jit(prefill)/mhc/pre/jit(mhc_pre)/pallas_call:", "mhc_pre"),
    ("jit(decode_tick)/mhc/post/concatenate:", "mhc/post"),
    ("jit(prefill)/attn/jit(flash_gqa_fwd)/jit(_pad)/pad:", "flash_gqa_fwd"),
    ("jit(prefill)/attn/jit(flash_gqa_fwd)/flash_gqa_fwd/pallas_call:",
     "flash_gqa_fwd"),
    ("jit(decode_tick)/dsa/index/jit(dsa_index)/dsa_index/pallas_call:",
     "dsa_index"),
    ("jit(decode_tick)/dsa/select/while/body/reduce_sum:", "dsa/select"),
    ("jit(decode_tick)/dsa/attn/jit(mla_paged_attn)/dsa_attn/pallas_call:",
     "dsa_attn"),
    ("jit(decode_tick)/window_mla/jit(mla_paged_attn)/window_mla_attn/"
     "pallas_call:", "window_mla_attn"),
    ("jit(decode_tick)/window_mla/paged_scatter/scatter:", "paged_scatter"),
    ("jit(prefill)/while/body/closed_call/dsa/attn/while/body/closed_call/"
     "exp:", "dsa/attn"),
    ("jit(prefill)/while/body/closed_call/dsa/index/dot_general:",
     "dsa/index"),
    ("jit(prefill)/window_mla/mla_attn/dot_general:", "mla_attn"),
    ("pages[45]['k']:", "(no scope)"),
    ("jit(train_step)/headroom/attnx/add:", "(no scope)"),
    ("", "(no scope)"),
])
def test_scope_of_takes_the_innermost_region(op_name, scope):
    assert _scope_times().scope_of(op_name) == scope


def test_scope_times_sums_self_time_by_program_and_region():
    cols = [{"id": k} for k in ("rank", "program_id", "hlo_op_name",
                                "tf_op_name", "total_time",
                                "total_self_time")]

    def row(program, op, name, self_us):
        return {"c": [{"v": 0}, {"v": program}, {"v": op}, {"v": name},
                      {"v": 2 * self_us}, {"v": self_us}]}

    table = {"cols": cols, "rows": [
        row("7", "fusion.1", "jit(train_step)/jvp(xent)/reduce_max:", 300.0),
        row("7", "fusion.2", "jit(train_step)/transpose(jvp(xent))/sub:", 50.0),
        row("7", "lion_apply.3", "jit(train_step)/lion_apply/pallas_call:", 4.0),
        row("7", "copy.4", "", 6.0),
        row("9", "fusion.5", "jit(decode_tick)/attn/paged_attn/dot_general:",
            20.0),
        row("9", "copy.6", None, 80.0)]}
    assert _scope_times().by_scope(table) == {
        "jit(train_step) 7": {"xent": 350.0, "lion_apply": 4.0,
                              "(no scope)": 6.0},
        "jit(decode_tick) 9": {"paged_attn": 20.0, "(no scope)": 80.0}}
    assert _scope_times().by_scope({"cols": cols, "rows": []}) == {}


def test_lowered_train_step_holds_every_scope(capsys):
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    cfg = TrainConfig(lion=True, async_grad=True, wire="packed_a2a",
                      vote_buckets=1, kernel="pallas", learning_rate=1e-3,
                      warmup_steps=1, max_steps=3,
                      per_device_train_batch_size=1,
                      gradient_accumulation_steps=1, block_size=32,
                      logging_steps=1, output_dir=None, save_steps=10**6,
                      resume_from_checkpoint=False)
    tr = Trainer.for_gpt2(cfg, make_mesh(data=8), GPT2Config.tiny())
    try:
        batch = np.zeros((tr.global_train_batch(), 32), np.int32)
        text = tr._train_step.lower(
            tr.params, tr.state, tr.vote_health, tr._frozen_arg(), batch,
            jax.random.key(0)).as_text(debug_info=True)
    finally:
        tr.close()
    assert _scopes(text) >= {"embed", "attn", "mlp", "head", "xent",
                             "vote/pack", "vote/unpack", "vote/tally",
                             "vote/wire", "lion_ballot", "lion_apply"}
    assert "@jit_train_step" in text or "jit_train_step" in text
    assert re.search(r"\[setup\] trainer: mesh [\d.]+ s, init_params [\d.]+ s, "
                     r"init_state [\d.]+ s, build_step [\d.]+ s, resume "
                     r"[\d.]+ s\n", capsys.readouterr().out)


def test_lowered_decode_and_llama_hold_every_scope():
    eng, cfg = _tiny_engine()
    S, W = eng.cfg.max_seqs, eng.cfg.max_blocks_per_seq
    rest = (jnp.zeros((S, W), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool),
            jnp.zeros((S,), jnp.uint32), jnp.zeros((S,), jnp.int32))
    text = eng._decode_tick.lower(eng.params, eng.pages, *rest).as_text(
        debug_info=True)
    assert _scopes(text) >= {"embed", "attn", "mlp", "head", "paged_attn",
                             "paged_scatter", "paged_gather"}
    from distributed_lion_tpu.models.llama import (
        LlamaConfig, llama_apply, llama_init)

    lcfg = LlamaConfig.tiny()
    lparams = llama_init(jax.random.key(0), lcfg)
    text = jax.jit(lambda p, t: llama_apply(p, t, lcfg)).lower(
        lparams, jnp.zeros((2, 16), jnp.int32)).as_text(debug_info=True)
    assert _scopes(text) >= {"embed", "attn", "mlp", "head"}
