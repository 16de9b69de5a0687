"""Autotune subsystem (ISSUE 6): the device-keyed tuning cache round-trips,
fails LOUDLY (never silently) into defaults, ignores entries keyed to other
devices, kills wedged candidates under the per-candidate timeout guard,
agrees with ``parse_attn_spec`` about what a resolved spec means, and —
the invariant everything leans on — elections are BIT-identical tuned vs
default on both the XLA and Pallas optimizer paths: every knob the tuner
owns changes where/when work happens, never what is elected."""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.ops import autotune
from distributed_lion_tpu.optim import distributed_lion, init_global_state
from distributed_lion_tpu.optim.sharded import make_sharded_step, shard_state
from distributed_lion_tpu.parallel import make_mesh


@pytest.fixture(autouse=True)
def _fresh_cache_memo():
    autotune.invalidate_cache()
    yield
    autotune.invalidate_cache()


def _entry(value, ms=1.0):
    return {"value": value, "ms": ms}


# ------------------------------------------------------------- cache basics

def test_cache_round_trip(tmp_path):
    p = str(tmp_path / "cache.json")
    key = autotune.cache_key("TPU v5 lite", "flash_tiles", "T1024xD64",
                             "bfloat16")
    autotune.save_cache({key: _entry({"block_q": 512, "block_kv": 1024})},
                        path=p)
    got = autotune.lookup("flash_tiles", "T1024xD64", "bfloat16",
                          device_kind="TPU v5 lite", path=p)
    assert got == {"block_q": 512, "block_kv": 1024}
    # a different shape/dtype/knob misses
    assert autotune.lookup("flash_tiles", "T2048xD64", "bfloat16",
                           device_kind="TPU v5 lite", path=p) is None
    assert autotune.lookup("flash_tiles", "T1024xD64", "float32",
                           device_kind="TPU v5 lite", path=p) is None
    assert autotune.lookup("splash_tiles", "T1024xD64", "bfloat16",
                           device_kind="TPU v5 lite", path=p) is None


def test_device_key_mismatch_ignored(tmp_path):
    """An entry measured on a TPU must be INVISIBLE on any other device —
    the device kind is part of the key, not a filter someone must remember
    to apply."""
    p = str(tmp_path / "cache.json")
    key = autotune.cache_key("TPU v5 lite", "lion_row_block", "N100",
                             "float32")
    autotune.save_cache({key: _entry({"row_block": 2048})}, path=p)
    assert autotune.lookup("lion_row_block", "N100", "float32",
                           device_kind="cpu", path=p) is None
    assert autotune.lookup("lion_row_block", "N100", "float32",
                           device_kind="TPU v5 lite", path=p) == \
        {"row_block": 2048}


def test_wildcard_shape_is_operator_fallback(tmp_path):
    p = str(tmp_path / "cache.json")
    key = autotune.cache_key("cpu", "lion_row_block", "*", "float32")
    autotune.save_cache({key: _entry({"row_block": 256})}, path=p)
    assert autotune.lookup("lion_row_block", "N12345", "float32",
                           device_kind="cpu", path=p) == {"row_block": 256}
    # exact beats wildcard
    exact = autotune.cache_key("cpu", "lion_row_block", "N12345", "float32")
    autotune.save_cache({key: _entry({"row_block": 256}),
                         exact: _entry({"row_block": 1024})}, path=p)
    assert autotune.lookup("lion_row_block", "N12345", "float32",
                           device_kind="cpu", path=p) == {"row_block": 1024}


def test_corrupt_cache_falls_back_loudly(tmp_path, capsys):
    p = str(tmp_path / "cache.json")
    with open(p, "w") as f:
        f.write("{definitely not json")
    assert autotune.load_cache(p) == {}
    assert autotune.lookup("flash_tiles", "T1024xD64", "bfloat16",
                           device_kind="cpu", path=p) is None
    err = capsys.readouterr().err
    assert "FALLING BACK" in err and p in err


def test_schema_violation_falls_back_loudly(tmp_path, capsys):
    p = str(tmp_path / "cache.json")
    bad = {"format": autotune.CACHE_FORMAT, "entries": {
        "cpu|flash_tiles|T1024xD64|bfloat16":
            {"value": {"block_q": "big"}, "ms": 1.0}}}
    with open(p, "w") as f:
        json.dump(bad, f)
    assert autotune.validate_cache_doc(bad)
    assert autotune.load_cache(p) == {}
    assert "FALLING BACK" in capsys.readouterr().err


def test_validate_cache_doc_schema():
    good_key = autotune.cache_key("cpu", "vocab_chunks", "N256xV509",
                                  "float32")
    good = {"format": autotune.CACHE_FORMAT,
            "entries": {good_key: _entry({"vocab_chunks": 8})}}
    assert autotune.validate_cache_doc(good) == []
    assert autotune.validate_cache_doc([]) != []          # not an object
    assert autotune.validate_cache_doc({}) != []          # wrong format
    assert autotune.validate_cache_doc(
        {"format": autotune.CACHE_FORMAT, "entries": 3}) != []
    for entry in (
        {"value": {}, "ms": 1.0},                  # empty value
        {"value": {"x": 1.5}, "ms": 1.0},          # non-int knob value
        {"value": {"x": True}, "ms": 1.0},         # bool is not an int knob
        {"value": {"x": 1}, "ms": -1.0},           # negative ms
        {"value": {"x": 1}},                       # ms missing
        {"value": {"x": 1}, "ms": float("nan")},   # NaN ms
    ):
        doc = {"format": autotune.CACHE_FORMAT, "entries": {good_key: entry}}
        assert autotune.validate_cache_doc(doc), entry
    # bad keys: wrong arity, unknown knob
    for key in ("cpu|flash_tiles|T1", "cpu|warp_tiles|T1|f32", "a|b"):
        doc = {"format": autotune.CACHE_FORMAT,
               "entries": {key: _entry({"x": 1})}}
        assert autotune.validate_cache_doc(doc), key


def test_save_cache_refuses_invalid(tmp_path):
    with pytest.raises(ValueError, match="invalid cache"):
        autotune.save_cache({"busted": {"value": {}, "ms": 0.0}},
                            path=str(tmp_path / "c.json"))


def test_validate_metrics_covers_tuning_cache(tmp_path):
    """scripts/validate_metrics.py validates tuning_cache.json through the
    ONE schema authority (autotune.validate_cache_doc)."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "validate_metrics", os.path.join(repo, "scripts",
                                         "validate_metrics.py"))
    vm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(vm)
    good = tmp_path / "tuning_cache.json"
    autotune.save_cache(
        {autotune.cache_key("cpu", "vocab_chunks", "N1xV2", "float32"):
         _entry({"vocab_chunks": 2})}, path=str(good))
    assert vm.validate_json_doc(str(good)) == []
    bad = tmp_path / "b" / "tuning_cache.json"
    bad.parent.mkdir()
    bad.write_text(json.dumps({"format": "wrong", "entries": {}}))
    assert vm.validate_json_doc(str(bad))
    # dispatch rides the embedded format stamp too: a $DLT_TUNE_CACHE at
    # any filename still gets the STRICT schema, not the generic checks
    odd = tmp_path / "tc.json"
    odd.write_text(json.dumps({
        "format": autotune.CACHE_FORMAT,
        "entries": {"cpu|vocab_chunks|N1xV2|float32":
                    {"value": {"vocab_chunks": "nope"}, "ms": 1.0}}}))
    assert vm.validate_json_doc(str(odd))


# ------------------------------------------------- winner selection + guard

def test_select_winner_deterministic_tie_break():
    cands = [{"row_block": 128}, {"row_block": 256}, {"row_block": 512}]
    results = [{"candidate": c, "ms": ms}
               for c, ms in zip(cands, (2.0, 1.0, 1.0))]
    win = autotune.select_winner(results)
    # tie at 1.0ms → the EARLIER candidate (smaller tile) wins
    assert win["candidate"] == {"row_block": 256} and win["index"] == 1
    assert autotune.select_winner(
        [{"candidate": c, "ms": None, "error": "x"} for c in cands]) is None


def test_candidate_order_is_fixed_and_excludes_known_bad_tile():
    a = autotune.tile_candidates("flash_tiles", {"t": 1024})
    assert a == autotune.tile_candidates("flash_tiles", {"t": 1024})
    # ascending sizes (ties → smallest tile via select_winner's index rule)
    assert a[0] == {"block_q": 128, "block_kv": 128}
    # the tile that hung the compile >14 min in round 3 stays out
    assert {"block_q": 1024, "block_kv": 1024} not in a
    assert autotune.tile_candidates("lion_row_block", {}) == [
        {"row_block": rb} for rb in (128, 256, 512, 1024, 2048)]


def test_timeout_guard_kills_slow_candidate():
    """The per-candidate compile/run guard: a trial that wedges (here: the
    _test_sleep_s hook standing in for a pathological tile's compile) is
    SIGKILLed at the budget and reported as a timeout row — it can never
    eat more than timeout_s of a window."""
    payload = {"knob": "vocab_chunks", "candidate": {"vocab_chunks": 2},
               "info": {"n": 8, "d": 4, "v": 16, "dtype": "float32"},
               "iters": 1, "_test_sleep_s": 120}
    t0 = time.monotonic()
    r = autotune.run_trial_child(payload, timeout_s=3.0)
    elapsed = time.monotonic() - t0
    assert "timeout" in r.get("error", ""), r
    assert elapsed < 60, elapsed  # killed at the budget, not after 120s


# ---------------------------------------------- resolver ↔ dispatch bridge

def test_resolve_attn_spec_agrees_with_parse_attn_spec(tmp_path):
    """The cache resolver's output is a spec parse_attn_spec reads back to
    EXACTLY the cached tiles — the one grammar shared by bench/sweep and
    the attention dispatch can't drift from the cache."""
    from distributed_lion_tpu.ops.attention import parse_attn_spec

    p = str(tmp_path / "cache.json")
    key = autotune.cache_key("cpu", "flash_tiles",
                             autotune.attn_shape_key(1024, 64), "bfloat16")
    autotune.save_cache(
        {key: _entry({"block_q": 512, "block_kv": 1024,
                      "block_q_bwd": 256, "block_kv_bwd": 512})}, path=p)
    spec = autotune.resolve_attn_spec("auto", t=1024, head_dim=64,
                                      dtype="bfloat16", device_kind="cpu",
                                      path=p)
    assert spec == "flash@512x1024@256x512"
    assert parse_attn_spec(spec) == ("flash", 512, 1024, 256, 512)
    # fwd-only entry → fwd-only spec
    autotune.save_cache(
        {key: _entry({"block_q": 256, "block_kv": 256})}, path=p)
    spec = autotune.resolve_attn_spec("auto", t=1024, head_dim=64,
                                      dtype="bfloat16", device_kind="cpu",
                                      path=p)
    assert spec == "flash@256x256"
    assert parse_attn_spec(spec) == ("flash", 256, 256, 0, 0)
    # operator-written bwd-only entry (schema-valid; the dispatch honors
    # bwd-only pins) must resolve without crashing and round-trip: 0 means
    # "kernel default" in the grammar exactly as in the attention kwargs
    autotune.save_cache(
        {key: _entry({"block_q_bwd": 256, "block_kv_bwd": 512})}, path=p)
    spec = autotune.resolve_attn_spec("auto", t=1024, head_dim=64,
                                      dtype="bfloat16", device_kind="cpu",
                                      path=p)
    assert spec == "flash@0x0@256x512"
    assert parse_attn_spec(spec) == ("flash", 0, 0, 256, 512)
    # miss → unchanged; explicit specs pass through untouched
    assert autotune.resolve_attn_spec("auto", t=64, head_dim=64,
                                      dtype="bfloat16", device_kind="cpu",
                                      path=p) == "auto"
    assert autotune.resolve_attn_spec("xla", t=1024, head_dim=64,
                                      dtype="bfloat16", device_kind="cpu",
                                      path=p) == "xla"


def test_attention_auto_dispatch_consults_cache(tmp_path, monkeypatch):
    """`auto` on TPU with a cache hit dispatches flash with the MEASURED
    tiles (outranking the built-in heuristics); backend + kernel are
    monkeypatched — this pins DISPATCH, kernel math is pinned elsewhere."""
    from distributed_lion_tpu.ops import attention as A

    p = str(tmp_path / "cache.json")
    autotune.save_cache(
        {autotune.cache_key("cpu", "flash_tiles",
                            autotune.attn_shape_key(256, 32), "float32"):
         _entry({"block_q": 128, "block_kv": 256, "block_q_bwd": 64,
                 "block_kv_bwd": 128})}, path=p)
    monkeypatch.setenv("DLT_TUNE_CACHE", p)
    autotune.invalidate_cache()
    calls = []
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        A, "attention_flash",
        lambda q, k, v, causal=True, **kw: calls.append(kw) or q)
    q = jnp.zeros((1, 2, 256, 32), jnp.float32)
    A.attention(q, q, q, impl="auto")
    assert calls == [{"block_q": 128, "block_kv": 256,
                      "block_q_bwd": 64, "block_kv_bwd": 128}]
    # an unswept shape misses the cache and keeps the heuristic path (xla
    # at T=256 off the flagship shape → attention_flash NOT called)
    calls.clear()
    q2 = jnp.zeros((1, 2, 256, 16), jnp.float32)
    A.attention(q2, q2, q2, impl="auto")
    assert calls == []
    # caller-pinned tiles OUTRANK the cache (an explicit auto@BQxBKV spec
    # must stay sweepable even at a cached shape)
    calls.clear()
    A.attention(q, q, q, impl="auto", block_q=64, block_kv=64)
    assert calls == [{"block_q": 64, "block_kv": 64,
                      "block_q_bwd": 0, "block_kv_bwd": 0}]


def test_resolve_auto_comm_consults_vote_buckets_cache(tmp_path,
                                                       monkeypatch):
    from distributed_lion_tpu.train.loop import TrainConfig, resolve_auto_comm

    p = str(tmp_path / "cache.json")
    n = 17_000_000
    autotune.save_cache(
        {autotune.cache_key("cpu", "vote_buckets", f"N{n}", "int8"):
         _entry({"vote_buckets": 8})}, path=p)
    monkeypatch.setenv("DLT_TUNE_CACHE", p)
    autotune.invalidate_cache()
    mesh = make_mesh(data=8, devices=jax.devices()[:8])
    r = resolve_auto_comm(TrainConfig(wire="packed_a2a", vote_every=1),
                          mesh, n, params_replicated=True)
    assert r.vote_buckets == 8          # measured value outranks heuristic
    r = resolve_auto_comm(TrainConfig(wire="packed_a2a", vote_every=1),
                          mesh, n - 1, params_replicated=True)
    assert r.vote_buckets == 4          # miss → heuristic (≥16M → 4)
    cfg = TrainConfig(wire="packed_a2a", vote_every=1, vote_buckets=1)
    assert resolve_auto_comm(cfg, mesh, n, True) is cfg  # explicit wins


# ------------------------------------------ bit-identity: tuned vs default

@pytest.mark.parametrize("vote_buckets", [1, 4])
def test_elections_bit_identical_tuned_vs_default(vote_buckets):
    """The acceptance invariant: tuned row_block values (and the XLA path)
    produce BYTE-identical params/momenta across vote_buckets {1, 4} —
    tiling is never allowed to move an election or a weight."""
    mesh = make_mesh(data=8)
    rng = np.random.default_rng(11)
    params = {
        "w": jnp.asarray(rng.normal(size=(777, 13)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(259,)).astype(np.float32)),
    }
    grads = {
        "w": jnp.asarray(rng.normal(size=(8, 777, 13)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(8, 259)).astype(np.float32)),
    }
    results = []
    configs = [("xla", 0), ("pallas", 0), ("pallas", 128), ("pallas", 2048)]
    for kern, rb in configs:
        opt = distributed_lion(learning_rate=0.02, weight_decay=0.05,
                               wire="sign_psum", kernel=kern, row_block=rb,
                               vote_buckets=vote_buckets)
        state = shard_state(init_global_state(opt, params, 8), mesh)
        step = make_sharded_step(opt, mesh)
        p = params
        for _ in range(3):
            p, state = step(p, grads, state)
        results.append((kern, rb, p, state))
    _, _, p0, s0 = results[0]
    for kern, rb, p, s in results[1:]:
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(p0[k]), np.asarray(p[k]),
                err_msg=f"params diverged at kernel={kern} row_block={rb}")
            np.testing.assert_array_equal(
                np.asarray(s0.exp_avg[k]), np.asarray(s.exp_avg[k]),
                err_msg=f"momentum diverged at kernel={kern} row_block={rb}")


def test_bad_row_block_rejected_at_build():
    with pytest.raises(ValueError, match="multiple of 32"):
        distributed_lion(row_block=100)
    with pytest.raises(ValueError, match="multiple of 32"):
        distributed_lion(row_block=16)


# ------------------------------------------------- tuner CLI end to end

def test_run_tune_cpu_end_to_end(tmp_path, monkeypatch, capsys):
    """The tuner runs end-to-end on CPU (interpret/xla fallback):
    unsupported TPU-only knobs are skipped WITH a reason, a supported knob
    is measured, and the committed artifact round-trips through the strict
    loader and the resolver."""
    from distributed_lion_tpu.cli import run_tune

    p = str(tmp_path / "tuning_cache.json")
    monkeypatch.setenv("DLT_TUNE_CACHE", p)
    autotune.invalidate_cache()
    rc = run_tune.main(["--preset", "smoke", "--in-process", "--iters", "1",
                        "--knobs", "flash_tiles,vocab_chunks"])
    assert rc == 0
    out = capsys.readouterr().out
    summary = json.loads(out.strip().splitlines()[-1])
    assert "flash_tiles" in summary["skipped"]          # with a reason
    assert "unsupported" in summary["skipped"]["flash_tiles"]
    assert "vocab_chunks" in summary["tuned"]
    entries = autotune.load_cache(p)
    assert len(entries) == 1
    (key,) = entries
    assert key.startswith("cpu|vocab_chunks|")
    # and the resolver sees what the tuner wrote
    knob, shape, dtype = key.split("|")[1:]
    v = autotune.lookup(knob, shape, dtype, device_kind="cpu", path=p)
    assert v == entries[key]["value"]
    assert v["vocab_chunks"] in (1, 2, 4, 8, 16, 32)
