"""graft-check for the serving plane (analysis/serve_check, ISSUE 19):
the jaxpr contract holds on real matrix cells, an injected extra
collective / host callback / recompile each FAILS loudly, the tick-level
retrace guard warns/raises without perturbing token streams, and the
banked ``runs/static/serve_check.json`` artifact is schema-gated so a
corrupted (or forged-ok) report cannot pass ``check_evidence
static_serve``."""

import copy
import importlib.util
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.analysis import serve_check
from distributed_lion_tpu.serve.engine import (
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
    dispatch_signature,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "runs", "static", "serve_check.json")


def _load_validate_metrics():
    spec = importlib.util.spec_from_file_location(
        "dlt_vm_for_serve_check",
        os.path.join(REPO, "scripts", "validate_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the matrix
def test_matrix_covers_every_config_axis():
    """The committed matrix spans every serving lever the engine ships:
    tp {0,1,2}, ep {1,2}, ep_batch, both weight formats, speculation."""
    cells = serve_check.MATRIX
    assert {c.get("tp", 0) for c in cells} >= {0, 1, 2}
    assert {c.get("ep", 0) for c in cells} >= {0, 1, 2}
    assert any(c.get("ep_batch") for c in cells)
    assert any(c.get("ep_batch") and c.get("tp") for c in cells)
    assert any(c.get("quant") == "nf4" for c in cells)
    assert any(c.get("quant") == "nf4" and c.get("tp") for c in cells)
    assert any(c.get("quant") == "nf4" and c.get("ep") for c in cells)
    assert any(c.get("speculate") for c in cells)
    assert any(c.get("speculate") and c.get("moe") for c in cells)


def test_validator_cell_list_matches_live_matrix():
    """The stdlib validator's hardcoded cell list (it must stay
    importable without jax) cannot drift from the live matrix."""
    vm = _load_validate_metrics()
    assert sorted(vm._SERVE_CHECK_CELLS) == sorted(
        c["name"] for c in serve_check.MATRIX)


def test_dense_tp2_inventory_is_two_psums_per_layer():
    cell = {"name": "dense_tp2_bf16", "moe": False, "tp": 2}
    rep = serve_check.check_cell(cell)
    assert rep["ok"], rep
    decode = rep["dispatches"]["decode"]
    # 2 layers x (attention exit + MLP exit), operand [B=4, S=1, D=64]
    assert decode["observed"] == [["psum", ("tensor",), 256]] * 4
    assert decode["host_callbacks"] == []
    assert decode["donation_ok"] and decode["upcast_ok"]
    # every power-of-two bucket traced: 4, 8, 16
    assert {k for k in rep["dispatches"] if k.startswith("prefill:")} == \
        {"prefill:4", "prefill:8", "prefill:16"}
    assert rep["dispatches"]["cow"]["observed"] == []


def test_moe_ep2_batch_inventory_and_specs():
    cell = {"name": "moe_ep2_batch_bf16", "moe": True, "ep": 2,
            "ep_batch": True}
    rep = serve_check.check_cell(cell)
    assert rep["ok"], rep
    assert rep["ep_batch_specs_ok"]
    decode = rep["dispatches"]["decode"]
    # one MoE block (layer 1), two all_to_all hops of the [E=4, cap=2,
    # D=64] dispatch buffer (batch is sharded: B_local = 4/2)
    assert decode["observed"] == [["all_to_all", ("expert",), 512]] * 2


def test_moe_ep1_cell_puts_nothing_on_the_wire():
    """ep=1 binds the mesh but the static ``ep > 1`` gate keeps every
    all_to_all out of the program — zero fabric traffic, pinned."""
    rep = serve_check.check_cell({"name": "moe_ep1_bf16", "moe": True,
                                  "ep": 1})
    assert rep["ok"], rep
    for name, d in rep["dispatches"].items():
        assert d["observed"] == [], (name, d["observed"])


def test_speculate_cell_traces_the_verify_window():
    rep = serve_check.check_cell({"name": "dense_tp0_ngram", "moe": False,
                                  "speculate": "ngram:3"})
    assert rep["ok"], rep
    assert "verify" in rep["dispatches"]
    assert rep["dispatches"]["verify"]["host_callbacks"] == []


# ------------------------------------------------- injected violations
def test_injected_extra_psum_fails_naming_the_primitive():
    """An extra collective smuggled into the decode dispatch (the exact
    failure mode the inventory exists to catch: a sharding change that
    starts paying a hop the config doesn't buy) fails the cell and names
    the primitive."""
    from jax.sharding import PartitionSpec as P

    from _sharded import sharded
    from distributed_lion_tpu.parallel.mesh import EXPERT_AXIS

    cell = {"name": "moe_ep2_bf16", "moe": True, "ep": 2}
    eng, scfg = serve_check.build_engine(cell)
    mcfg = serve_check._model_cfg(True)
    reg = eng._dispatches["decode"]
    orig = reg["jitted"]
    leak_fn = sharded(
        lambda x: jax.lax.psum(x, EXPERT_AXIS), eng._mesh, (P(),), P(),
        check_vma=False)

    def bad(params, pages, *rest):
        (tok, st), pg = orig(params, pages, *rest)
        leak = leak_fn(jnp.zeros((128,), jnp.float32))
        return (tok + leak.sum().astype(tok.dtype), st), pg

    reg["jitted"] = bad
    rep = serve_check.check_dispatch(eng, mcfg, scfg, "decode")
    assert not rep["ok"] and not rep["inventory_ok"]
    assert any(u[0] == "psum" for u in rep["unexpected"]), rep["unexpected"]


def test_injected_host_callback_fails():
    cell = {"name": "dense_tp0_bf16", "moe": False}
    eng, scfg = serve_check.build_engine(cell)
    mcfg = serve_check._model_cfg(False)
    reg = eng._dispatches["decode"]
    orig = reg["jitted"]

    def bad(params, pages, *rest):
        (tok, st), pg = orig(params, pages, *rest)
        jax.debug.print("tick {}", tok.sum())
        return (tok, st), pg

    reg["jitted"] = bad
    rep = serve_check.check_dispatch(eng, mcfg, scfg, "decode")
    assert not rep["ok"] and rep["host_callbacks"]


# ------------------------------------------------------- compile budget
def test_compile_counts_hold_the_bucket_budget():
    rep = serve_check.check_compile_budget(
        {"name": "dense_tp0_bf16", "moe": False})
    assert rep["ok"], rep
    # ONE decode program; one prefill per power-of-two bucket {4, 8, 16}
    assert rep["counts"]["decode"] == 1
    assert rep["counts"]["prefill"] == 3 == rep["budget"]["prefill"]


# --------------------------------------------------------- retrace guard
def _tiny_engine(**kw):
    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init

    cfg = GPT2Config.tiny(vocab_size=128, n_ctx=64)
    params = gpt2_init(jax.random.key(0), cfg)
    scfg = ServeConfig(max_seqs=4, block_size=4, max_blocks_per_seq=4,
                       **kw)
    return ServingEngine(ServeModel.for_gpt2(params, cfg), scfg), cfg


def _workload(vocab, seed=0):
    return [Request(req_id=i, tokens=[1 + (i + j + seed) % (vocab - 1)
                                      for j in range(n)],
                    max_new_tokens=4, seed=i)
            for i, n in enumerate((1, 3, 7, 14))]


def test_retrace_guard_error_raises_on_injected_recompile():
    """A dispatch whose operand signature exceeds the compile budget (an
    injected shape drift — exactly what would silently retrace) raises
    BEFORE lowering under --serve_retrace_guard error."""
    eng, cfg = _tiny_engine(retrace_guard="error")
    eng.run(_workload(cfg.vocab_size))  # legit workload: within budget
    novel = (jnp.zeros((8, 4), jnp.int32),)  # decode budget (1) is spent
    with pytest.raises(RuntimeError, match="retrace"):
        eng._guard("decode", novel)


def test_retrace_guard_warn_counts_and_warns():
    eng, cfg = _tiny_engine(retrace_guard="warn")
    eng.run(_workload(cfg.vocab_size))
    assert eng.stats["serve_retraces"] == 0  # legit workload is silent
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng._guard("decode", (jnp.zeros((8, 4), jnp.int32),))
    assert eng.stats["serve_retraces"] == 1
    assert any("retrace" in str(w.message) for w in caught)


def test_retrace_guard_prefill_budget_is_per_bucket():
    """Three distinct prefill signatures (one per power-of-two bucket)
    are the budget, not a violation — the guard mirrors compile_budget,
    not dispatch count."""
    eng, cfg = _tiny_engine(retrace_guard="error")
    eng.run(_workload(cfg.vocab_size))  # hits buckets 4, 8 and 16
    assert eng.compile_counts()["prefill"] == 3
    assert eng.stats["serve_retraces"] == 0


def test_retrace_guard_off_is_bit_identical():
    eng_off, cfg = _tiny_engine(retrace_guard="off")
    eng_err, _ = _tiny_engine(retrace_guard="error")
    out_off = eng_off.run(_workload(cfg.vocab_size))
    out_err = eng_err.run(_workload(cfg.vocab_size))
    assert set(out_off) == set(out_err)
    for rid in out_off:
        assert out_off[rid].tokens == out_err[rid].tokens
        assert out_off[rid].reason == out_err[rid].reason
    assert "serve_retraces" not in eng_off.stats


def test_retrace_guard_rejects_unknown_mode():
    with pytest.raises(ValueError, match="retrace_guard"):
        _tiny_engine(retrace_guard="loud")


def test_dispatch_signature_is_shape_and_dtype():
    a = (jnp.zeros((4, 2), jnp.int32), jnp.uint32(0))
    b = (jnp.ones((4, 2), jnp.int32), jnp.uint32(9))  # values differ
    c = (jnp.zeros((4, 3), jnp.int32), jnp.uint32(0))  # shape differs
    assert dispatch_signature(a) == dispatch_signature(b)
    assert dispatch_signature(a) != dispatch_signature(c)


# ------------------------------------------------------ banked artifact
def _banked():
    with open(ARTIFACT) as f:
        return json.load(f)


def test_banked_artifact_validates_clean():
    vm = _load_validate_metrics()
    assert os.path.exists(ARTIFACT), "run `python -m " \
        "distributed_lion_tpu.analysis serve-check --json-out " \
        "runs/static/serve_check.json`"
    assert vm.validate_json_doc(ARTIFACT) == []


def _corrupt(doc, mode):
    """Five forgeries, every one leaving ``ok`` flags true — the schema
    re-derives the verdicts, so forged flags cannot pass."""
    cell = next(c for c in doc["cells"] if c["cell"] == "dense_tp2_bf16")
    if mode == "extra_collective":
        cell["dispatches"]["decode"]["observed"].append(
            ["psum", ["tensor"], 4096])
    elif mode == "missing_cell":
        doc["cells"] = [c for c in doc["cells"]
                        if c["cell"] != "moe_ep2_batch_tp2_bf16"]
    elif mode == "host_callback":
        cell["dispatches"]["decode"]["host_callbacks"] = ["pure_callback"]
    elif mode == "donation_lost":
        cell["dispatches"]["decode"]["donation"] = {
            "aliased_outputs": 0, "buffer_donors": 0}
    elif mode == "over_budget":
        doc["compile"][0]["counts"]["prefill"] = 9
    else:
        raise AssertionError(mode)
    return doc


@pytest.mark.parametrize("mode", ["extra_collective", "missing_cell",
                                  "host_callback", "donation_lost",
                                  "over_budget"])
def test_stage_rejects_corrupt_artifact(mode, tmp_path):
    vm = _load_validate_metrics()
    doc = _corrupt(copy.deepcopy(_banked()), mode)
    bad = tmp_path / "serve_check.json"
    bad.write_text(json.dumps(doc))
    assert vm.validate_json_doc(str(bad)), mode
    # and the evidence stage itself says MISSING for the same file
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_evidence.py"),
         "static_serve", str(bad)], capture_output=True).returncode
    assert rc != 0, mode


def test_evidence_stage_accepts_banked_artifact():
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_evidence.py"),
         "static_serve"], capture_output=True).returncode
    assert rc == 0
