"""graft-check tier 2 for the SERVING plane: jaxpr contracts on the
ACTUAL compiled serve dispatches.

The serving engine makes structural promises the benches only observe
indirectly (a slow tick, a surprise recompile, an HBM bump): one decode
program, O(log max) prefill buckets, collectives exactly where the
sharding says, no host round-trips inside a dispatch, page pools updated
in place. This module pins each of those STATICALLY, the same way
:mod:`analysis.trace_check` pins the trainer — build a real
:class:`~distributed_lion_tpu.serve.engine.ServingEngine` for every cell
of the serving config matrix (tp x ep x ep_batch x quant x speculate),
walk the jaxprs/lowered MLIR of the very callables the engine's ticks
dispatch (the ``engine._dispatches`` registry — not re-derived lookalike
programs), and assert per dispatch:

- **collective inventory** exactly matches the config-derived expectation
  (:func:`expected_serve_calls`): ``tp >= 1`` buys one row-parallel-exit
  psum per layer exit (attention out-proj + MLP/MoE out-proj — 2 per
  layer, operand ``[B, S, d_model]`` / the MoE dispatch buffer);
  ``ep > 1`` buys exactly TWO ``all_to_all`` hops per MoE block
  (``[E, cap, d_model]`` out and back); ``ep == 1`` buys ZERO fabric
  traffic (the ``ep > 1`` gate is static); the CoW page copy is
  collective-free on every mesh. Anything else fails naming the
  primitive, its axes/operand size, and the dispatch it appeared in.
- **zero host callbacks** in ANY dispatch — decode tick, every power-of-
  two prefill bucket, the speculative verify window, CoW.
- **donation survives lowering**: the page pool (2 buffers per layer)
  carries ``tf.aliasing_output`` / ``jax.buffer_donor`` in the lowered
  module. The engine turns ``donate_argnums`` off on the cpu backend, so
  the check re-jits the registered pre-jit body (``inner``) with
  donation forced — same program, donation provable on any backend.
- **no weight upcasts**: no ``convert_element_type`` takes a frozen
  bf16 / nf4-dequant weight matrix to f32. The ONLY legal large
  bf16->f32 converts in a serve dispatch are layer-norm's activation-
  stability upcasts, and those all have the activation shape
  ``[B_local, S, d_model]`` — any other large convert (in particular a
  weight-shaped one) fails. bf16 cells additionally run the positional
  param-leaf tracker (:func:`analysis.trace_check.param_upcasts`),
  filtered to matrix leaves (1-D ln/bias vectors upcast by design).
- **compile budget**: after a standard mixed workload (prompt lengths
  spanning every bucket + decode + speculative ticks), the engine's own
  jit caches (``engine.compile_counts()``) hold at most
  ``engine.compile_budget()`` distinct lowerings — ONE decode / verify /
  cow program, one prefill program per power-of-two page bucket. The
  runtime twin is ``ServeConfig.retrace_guard`` (``--serve_retrace_guard``).

Run it::

    python -m distributed_lion_tpu.analysis serve-check [--json-out F]
    python distributed_lion_tpu/analysis/serve_check.py   # file path, same

``runs/static/serve_check.json`` banks the report
(``scripts/validate_metrics.py`` schema, gated by
``scripts/check_evidence.py static_serve``).
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from distributed_lion_tpu.analysis.trace_check import (
    SCALAR_MAX,
    collective_calls,
    donation_report,
    iter_eqns,
    param_upcasts,
)
from distributed_lion_tpu.parallel.mesh import EXPERT_AXIS, TENSOR_AXIS

# engine geometry shared by every matrix cell: page cap = 16 tokens ->
# prefill buckets {4, 8, 16} (three compiles), 4 decode slots, and the
# smallest collective operand (batch-sharded decode attention exit,
# [2, 1, 64]) still clears SCALAR_MAX so inventory and scalar-probe
# classes cannot collide.
MAX_SEQS = 4
BLOCK_SIZE = 4
MAX_BLOCKS_PER_SEQ = 4
NGRAM_K = 3

# the serving config matrix: every tp degree {0 (no mesh), 1 (1-mesh,
# bit-identical pin), 2}, ep {1 (zero-traffic pin), 2}, ep_batch on/off,
# both weight formats, speculation off/on (ngram arms the verify-window
# dispatch). MoE cells use the tiny MoE checkpoint (moe_every=2,
# n_layer=2 -> exactly one MoE block).
MATRIX: List[Dict[str, Any]] = [
    {"name": "dense_tp0_bf16", "moe": False},
    {"name": "dense_tp0_nf4", "moe": False, "quant": "nf4"},
    {"name": "dense_tp1_bf16", "moe": False, "tp": 1},
    {"name": "dense_tp2_bf16", "moe": False, "tp": 2},
    {"name": "dense_tp2_nf4", "moe": False, "tp": 2, "quant": "nf4"},
    {"name": "dense_tp0_ngram", "moe": False,
     "speculate": f"ngram:{NGRAM_K}"},
    {"name": "moe_ep1_bf16", "moe": True, "ep": 1},
    {"name": "moe_ep2_bf16", "moe": True, "ep": 2},
    {"name": "moe_ep2_batch_bf16", "moe": True, "ep": 2, "ep_batch": True},
    {"name": "moe_ep2_batch_tp2_bf16", "moe": True, "ep": 2,
     "ep_batch": True, "tp": 2},
    {"name": "moe_ep2_nf4", "moe": True, "ep": 2, "quant": "nf4"},
    {"name": "moe_ep2_ngram", "moe": True, "ep": 2,
     "speculate": f"ngram:{NGRAM_K}"},
]

# cells that also run the REAL mixed workload for the compile-count
# budget (mesh-free: the budget law is geometry, not sharding — the
# jit caches count lowerings identically under shard_map)
COMPILE_CELLS = ("dense_tp0_bf16", "dense_tp0_ngram")


def _model_cfg(moe: bool):
    from distributed_lion_tpu.models.gpt2 import GPT2Config

    # bf16 params so the upcast leg has teeth; vocab/n_ctx trimmed to
    # keep 12 cells' worth of abstract traces cheap
    return GPT2Config.tiny(vocab_size=128, n_ctx=64,
                           param_dtype=jnp.bfloat16,
                           moe_experts=4 if moe else 0)


def build_engine(cell: Dict[str, Any]):
    """A live engine for one matrix cell — the SAME constructor path the
    server uses, so the registry holds the real dispatch callables."""
    from distributed_lion_tpu.models.gpt2 import gpt2_init
    from distributed_lion_tpu.serve.engine import (
        ServeConfig,
        ServeModel,
        ServingEngine,
    )

    cfg = _model_cfg(cell.get("moe", False))
    params = gpt2_init(jax.random.key(0), cfg)
    kw = {k: v for k, v in cell.items() if k not in ("name", "moe")}
    if kw.get("quant") == "nf4":
        kw.setdefault("quant_block", 16)  # d_model=64 must shard under tp
    scfg = ServeConfig(max_seqs=MAX_SEQS, block_size=BLOCK_SIZE,
                       max_blocks_per_seq=MAX_BLOCKS_PER_SEQ, **kw)
    return ServingEngine(ServeModel.for_gpt2(params, cfg), scfg), scfg


# ----------------------------------------------------- expected inventory
def expected_serve_calls(model_cfg, scfg, kind: str,
                         window: Optional[int] = None) -> List[tuple]:
    """The config-derived collective inventory for ONE serve dispatch, as
    a sorted ``(prim, axes, nelems)`` list — same key as
    ``trace_check.CollectiveCall`` and derived from the same single
    sources of truth the engine shards by (``models.gpt2.is_moe_block``
    for block placement, the Megatron row-parallel exits for psum count,
    ``moe_ffn``'s no-drop ``capacity_override = B*S`` for operand sizes).

    ``kind``: ``decode`` | ``prefill`` | ``verify`` | ``cow``;
    ``window`` is the padded token width (a prefill bucket, or the
    speculative ``k+1``) for the windowed kinds.
    """
    from distributed_lion_tpu.models.gpt2 import is_moe_block

    if kind == "cow":
        return []  # page copies are shard-local on every mesh
    groups = scfg.ep if (scfg.ep_batch and scfg.ep) else 1
    if kind == "decode":
        b_local, s = scfg.max_seqs // groups, 1
    elif kind == "prefill":
        # batch-1 window; under ep_batch the tokens are REPLICATED and
        # only table/length operands shard, so every shard traces B=1
        b_local, s = 1, int(window)
    elif kind == "verify":
        b_local, s = scfg.max_seqs // groups, int(window)
    else:
        raise ValueError(f"unknown dispatch kind {kind!r}")
    d = model_cfg.d_model
    e = model_cfg.moe_experts
    cap = b_local * s  # moe_ffn's no-drop capacity_override
    out: List[tuple] = []
    for i in range(model_cfg.n_layer):
        moe = is_moe_block(model_cfg, i)
        if scfg.tp >= 1:
            # attention out-proj exit (one per layer) ...
            out.append(("psum", (TENSOR_AXIS,), b_local * s * d))
            # ... and the FFN exit: dense MLP psums the activation, the
            # MoE expert FFN psums the [E, cap, D] dispatch buffer
            out.append(("psum", (TENSOR_AXIS,),
                        e * cap * d if moe else b_local * s * d))
        if moe and scfg.ep > 1:
            # expert dispatch out + combine back — exactly two hops
            out.append(("all_to_all", (EXPERT_AXIS,), e * cap * d))
            out.append(("all_to_all", (EXPERT_AXIS,), e * cap * d))
    return sorted(k for k in out if k[2] > SCALAR_MAX)


# ------------------------------------------------------- example operands
def _example_rest(eng, kind: str, window: Optional[int] = None) -> tuple:
    """Abstract-trace operands for one dispatch, shape/dtype-identical to
    what the engine's tick builds (engine.py `_decode` /
    `_dispatch_prefill` / `_flush_cow`, speculate.py `decode_tick`)."""
    cfg = eng.cfg
    s_, w_ = cfg.max_seqs, cfg.max_blocks_per_seq
    i32, u32 = jnp.int32, jnp.uint32
    if kind == "decode":
        return (jnp.zeros((s_, w_), i32), jnp.zeros((s_,), i32),
                eng._prev, jnp.zeros((s_,), bool),
                jnp.zeros((s_,), u32), jnp.zeros((s_,), i32))
    if kind == "prefill":
        toks = jnp.zeros((1, int(window)), i32)
        # the last two: the vector the decode tick reads its last tokens
        # from, and the slot whose entry the first token is written over
        if eng._ep_batch:
            g = eng.tables.groups
            return (jnp.zeros((g, w_), i32), toks, jnp.zeros((g,), i32),
                    jnp.zeros((g,), i32), u32(0), i32(0), eng._prev,
                    jnp.zeros((g,), i32))
        return (jnp.zeros((1, w_), i32), toks, jnp.zeros((1,), i32),
                i32(0), u32(0), i32(0), eng._prev, jnp.zeros((1,), i32))
    if kind == "verify":
        return (jnp.zeros((s_, w_), i32), jnp.zeros((s_,), i32),
                jnp.zeros((s_, int(window)), i32), jnp.zeros((s_,), i32),
                jnp.zeros((s_,), u32), jnp.zeros((s_,), i32))
    if kind == "cow":
        shape = ((eng.tables.groups, eng.tables.slots_per_group)
                 if eng._ep_batch else (s_,))
        return (jnp.zeros(shape, i32), jnp.zeros(shape, i32))
    raise ValueError(f"unknown dispatch kind {kind!r}")


def _dispatch_args(eng, kind: str, window: Optional[int] = None) -> tuple:
    rest = _example_rest(eng, kind, window)
    if kind == "cow":
        return (eng.pages,) + rest
    return (eng.params, eng.pages) + rest


def lowered_dispatch(eng, kind: str, window: Optional[int] = None):
    """The registered dispatch lowered on the engine's own example
    operands (``.as_text()`` names its Mosaic kernels, ``.compile()`` is
    what the backend runs) — ``chip_smoke.py`` reads both on the chip."""
    return eng._dispatches[kind]["jitted"].lower(
        *_dispatch_args(eng, kind, window))


def pool_leaf_copies(hlo_text: str, leaf) -> List[str]:
    """``copy`` instructions of optimized HLO whose result is as large as
    the pool leaf ``leaf`` (anything with ``dtype`` and ``size``): a
    re-layout of the pool inside a dispatch (serve/kv_cache's module
    note). One rule for ``chip_smoke.py`` and tests/test_chip_compile.py."""
    out = []
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\S* copy\(", hlo_text):
        n = 1
        for d in m[2].split(","):
            n *= int(d)
        if m[1] == jnp.dtype(leaf.dtype).name and n == leaf.size:
            out.append(m[0])
    return out


def _prefill_buckets(scfg) -> List[int]:
    cap = scfg.block_size * scfg.max_blocks_per_seq
    return sorted({scfg.bucket(n) for n in range(1, cap + 1)})


# ------------------------------------------------------------ the checks
def _upcast_scan(jaxpr, allowed_shape: tuple) -> List[dict]:
    """Every large ``convert_element_type -> f32`` whose operand is NOT
    the layer-norm activation shape — a weight-shaped convert means a
    frozen bf16 / nf4-dequant matrix is being read at double width."""
    bad: List[dict] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        if eqn.params.get("new_dtype") != jnp.float32:
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        nelems = 1
        for dim in aval.shape:
            nelems *= int(dim)
        if nelems <= SCALAR_MAX:
            continue
        if tuple(aval.shape) == tuple(allowed_shape):
            continue  # layer-norm stability upcast — by design
        bad.append({"shape": list(aval.shape), "dtype": str(aval.dtype),
                    "nelems": nelems})
    return bad


def check_dispatch(eng, model_cfg, scfg, kind: str,
                   window: Optional[int] = None) -> dict:
    """The whole per-dispatch contract: inventory + callbacks + donation
    + upcasts, against the REGISTERED callable (``engine._dispatches``)."""
    reg = eng._dispatches[kind.split(":")[0] if ":" in kind else kind]
    args = _dispatch_args(eng, kind, window)
    calls, callbacks = collective_calls(reg["jitted"], *args)
    observed = sorted(c.key for c in calls if c.nelems > SCALAR_MAX)
    scalar = [c for c in calls if c.nelems <= SCALAR_MAX]
    expected = expected_serve_calls(model_cfg, scfg, kind, window)
    obs_count: Dict[tuple, int] = {}
    for k in observed:
        obs_count[k] = obs_count.get(k, 0) + 1
    exp_count: Dict[tuple, int] = {}
    for k in expected:
        exp_count[k] = exp_count.get(k, 0) + 1
    unexpected = [list(k) for k in observed
                  if obs_count[k] > exp_count.get(k, 0)]
    missing = [list(k) for k in expected
               if exp_count[k] > obs_count.get(k, 0)]
    inventory_ok = observed == expected

    # donation: the engine disables donate_argnums on cpu (buffers are
    # host RAM), so prove it on the SAME program by re-jitting the
    # registered pre-jit body with donation forced. 2 pool buffers per
    # layer (k + v) must survive as aliases/donors.
    donate = (0,) if kind == "cow" else (1,)
    probe = jax.jit(reg["inner"], donate_argnums=donate)
    don = donation_report(probe, *args)
    need = 2 * model_cfg.n_layer
    donation_ok = (don["aliased_outputs"] + don["buffer_donors"]) >= need

    # upcasts: weight-shaped bf16->f32 converts (all cells) ...
    groups = scfg.ep if (scfg.ep_batch and scfg.ep) else 1
    if kind == "decode":
        act_shape = (scfg.max_seqs // groups, 1, model_cfg.d_model)
    elif kind == "prefill":
        act_shape = (1, int(window), model_cfg.d_model)
    elif kind == "verify":
        act_shape = (scfg.max_seqs // groups, int(window),
                     model_cfg.d_model)
    else:
        act_shape = ()
    jaxpr = jax.make_jaxpr(reg["jitted"])(*args)
    weight_upcasts = _upcast_scan(jaxpr, act_shape)
    # ... plus the positional bf16-param tracker on unquantized cells
    # (1-D ln/bias vectors upcast for stability by design — only matrix
    # leaves count)
    leaf_upcasts: List[list] = []
    if scfg.quant == "none" and kind != "cow":
        leaf_upcasts = [list(s) for s in
                        param_upcasts(reg["jitted"], args, param_argnum=0)
                        if len(s) >= 2]
    upcast_ok = not weight_upcasts and not leaf_upcasts

    ok = bool(inventory_ok and not callbacks and donation_ok and upcast_ok)
    return {
        "ok": ok,
        "inventory_ok": bool(inventory_ok),
        "observed": [list(k) for k in observed],
        "expected": [list(k) for k in expected],
        "unexpected": unexpected,
        "missing": missing,
        "scalar_reductions": len(scalar),
        "host_callbacks": list(callbacks),
        "donation": don,
        "donation_ok": bool(donation_ok),
        "weight_upcasts": weight_upcasts,
        "param_upcasts": leaf_upcasts,
        "upcast_ok": bool(upcast_ok),
    }


def check_cell(cell: Dict[str, Any]) -> dict:
    """Every dispatch of one matrix cell's engine: the decode tick, EVERY
    power-of-two prefill bucket, the verify window when armed, CoW."""
    eng, scfg = build_engine(cell)
    model_cfg = _model_cfg(cell.get("moe", False))
    dispatches: Dict[str, dict] = {}
    dispatches["decode"] = check_dispatch(eng, model_cfg, scfg, "decode")
    for bucket in _prefill_buckets(scfg):
        rep = check_dispatch(eng, model_cfg, scfg, "prefill", bucket)
        dispatches[f"prefill:{bucket}"] = rep
    if scfg.speculate:
        dispatches["verify"] = check_dispatch(eng, model_cfg, scfg,
                                              "verify", NGRAM_K + 1)
    dispatches["cow"] = check_dispatch(eng, model_cfg, scfg, "cow")
    report = {
        "cell": cell["name"],
        "tp": scfg.tp, "ep": scfg.ep, "ep_batch": bool(scfg.ep_batch),
        "quant": scfg.quant, "speculate": scfg.speculate,
        "ok": all(d["ok"] for d in dispatches.values()),
        "dispatches": dispatches,
    }
    if scfg.ep_batch:
        # the batch-sharded cells additionally pin the REGISTERED specs:
        # tables shard their slot-leading dim over the expert axis
        from jax.sharding import PartitionSpec as P

        specs = eng._dispatches["decode"]["rest_specs"]
        spec_ok = (specs is not None
                   and specs[0] == P(EXPERT_AXIS, None)
                   and all(sp == P(EXPERT_AXIS) for sp in specs[1:]))
        report["ep_batch_specs_ok"] = bool(spec_ok)
        report["ok"] = bool(report["ok"] and spec_ok)
    return report


# ------------------------------------------------------ engine counters
# ``mhc_res_defect_max`` (models/xing: the largest |rowsum - 1| or
# |colsum - 1| of a hyper-connection mixing matrix over a dispatch, x 1e6).
# 20 Sinkhorn steps leave up to 0.064 on a matrix with a dominant diagonal
# (the seeded weights' 4 I: the iteration's rate is the square of the
# limit's second singular value, some 0.9), 10 steps leave 0.15 and one
# step 1.8 (400,000 matrices drawn as the seeded weights draw them): 0.1
# stands between the configured iteration and one cut short or missing a
# normalisation.
MHC_DEFECT_LIMIT = 100_000


def check_counters(stats: Dict[str, Any]) -> List[str]:
    """Findings over an engine's counters after a workload (``[]`` = ok;
    an engine whose family keeps none of them has nothing to find)."""
    found = []
    defect = stats.get("mhc_res_defect_max")
    if defect is not None and defect > MHC_DEFECT_LIMIT:
        found.append(
            f"mhc_res_defect_max {defect} > {MHC_DEFECT_LIMIT}: a mixing "
            f"matrix is {defect / 1e6:.3f} from doubly stochastic (the "
            "Sinkhorn iteration was cut short or lost a normalisation)")
    return found


# ------------------------------------------------------- compile budget
def _mixed_workload(vocab: int) -> list:
    """Prompt lengths spanning every page bucket (1->4, 3->4, 7->8,
    14->16) plus decode ticks — the standard workload the compile-count
    budget is measured against."""
    from distributed_lion_tpu.serve.engine import Request

    return [Request(req_id=i, tokens=[1 + (i + j) % (vocab - 1)
                                      for j in range(n)],
                    max_new_tokens=4, seed=i)
            for i, n in enumerate((1, 3, 7, 14))]


def check_compile_budget(cell: Dict[str, Any]) -> dict:
    """Run the real mixed workload on one cell's engine and pin the live
    jit-cache sizes against ``engine.compile_budget()`` — the O(log max)
    prefill / ONE decode program claim, measured from jax's own caches."""
    eng, scfg = build_engine(cell)
    model_cfg = _model_cfg(cell.get("moe", False))
    eng.run(_mixed_workload(model_cfg.vocab_size))
    counts = eng.compile_counts()
    budget = eng.compile_budget()
    over = {k: [v, budget.get(k, 0)] for k, v in counts.items()
            if v > budget.get(k, 0)}
    findings = check_counters(eng.stats)
    ok = not over and counts.get("prefill", 0) > 0 and not findings
    return {"cell": cell["name"], "ok": bool(ok), "counts": counts,
            "budget": budget, "over_budget": over,
            "counter_findings": findings}


# --------------------------------------------------------------- driver
def run_matrix(cells: Optional[List[Dict[str, Any]]] = None,
               verbose: bool = True) -> dict:
    cells = MATRIX if cells is None else cells
    need = max(cell.get("ep", 0) * max(cell.get("tp", 0), 1) or
               max(cell.get("tp", 0), 1) for cell in cells)
    world = jax.local_device_count()
    if world < need:
        raise RuntimeError(
            f"serve-check needs {need} devices for the full matrix, "
            f"found {world} — run under DLION_PLATFORM=cpu8 (or a pod)")
    reports = [check_cell(cell) for cell in cells]
    compiles = [check_compile_budget(cell) for cell in cells
                if cell["name"] in COMPILE_CELLS]
    ok = all(r["ok"] for r in reports) and all(c["ok"] for c in compiles)
    if verbose:
        for r in reports:
            verdict = "ok" if r["ok"] else "CONTRACT VIOLATION"
            n_coll = sum(len(d["observed"])
                         for d in r["dispatches"].values())
            print(f"graft-check serve: {r['cell']}: {verdict} "
                  f"({len(r['dispatches'])} dispatches, "
                  f"{n_coll} collectives)")
            for dname, d in r["dispatches"].items():
                if d["ok"]:
                    continue
                if d["unexpected"]:
                    print(f"  {dname}: UNEXPECTED collectives "
                          f"{d['unexpected']}")
                if d["missing"]:
                    print(f"  {dname}: MISSING collectives "
                          f"{d['missing']}")
                if d["host_callbacks"]:
                    print(f"  {dname}: host callbacks "
                          f"{d['host_callbacks']}")
                if not d["donation_ok"]:
                    print(f"  {dname}: donation lost: {d['donation']}")
                if not d["upcast_ok"]:
                    print(f"  {dname}: weight upcasts "
                          f"{d['weight_upcasts'] or d['param_upcasts']}")
        for c in compiles:
            verdict = "ok" if c["ok"] else "OVER BUDGET"
            print(f"graft-check serve: compile[{c['cell']}]: {verdict} "
                  f"counts={c['counts']} budget={c['budget']}")
    return {
        "format": "dlt-serve-check-v1",
        "ok": bool(ok),
        "world": world,
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "cells": reports,
        "compile": compiles,
    }


def main(json_out: Optional[str] = None) -> int:
    report = run_matrix()
    if json_out:
        with open(json_out, "w") as f:
            json.dump(report, f, indent=1, allow_nan=False)
            f.write("\n")
        print(f"graft-check serve: report written to {json_out}")
    n = len(report["cells"])
    print(f"graft-check serve: {'PASS' if report['ok'] else 'FAIL'} "
          f"({n} cells)")
    return 0 if report["ok"] else 1


if __name__ == "__main__":  # file-path entry point, like lint.py
    import os
    import sys

    _root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if _root not in sys.path:
        sys.path.insert(0, _root)
    from distributed_lion_tpu.parallel.mesh import force_cpu_platform

    force_cpu_platform()
    json_arg = None
    argv = sys.argv[1:]
    if "--json-out" in argv:
        json_arg = argv[argv.index("--json-out") + 1]
    sys.exit(main(json_arg))
