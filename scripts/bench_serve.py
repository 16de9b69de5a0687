#!/usr/bin/env python
"""Decode benchmark for the serving engine.

Measures the continuous-batching engine (distributed_lion_tpu/serve/) the
way the training bench measures the train step, and writes ONE strict-JSON
evidence artifact under ``runs/serving/`` that check_evidence's ``serving``
stage judges (so serving regressions gate like training ones —
ROADMAP item 4):

- **decode rows** — tokens/s/chip at full-occupancy decode batch
  {32, 128, 256} (every slot active, K timed one-dispatch ticks), each row
  carrying the NF4-vs-bf16 weight-bytes column (ops/quant: the measured
  storage of the quantized tree vs the 2-byte/param bf16 dense serve).
- **prefill-share ablation** — the same staggered workload drained under
  different ``prefill_cap_tokens`` fairness caps: how much decode
  throughput a prefill burst is allowed to steal per tick.
- **bit-identity markers** — (a) greedy decode through the paged engine
  vs the dense-KV ``models/generate.generate`` on the same prompts with
  MATCHED attended length (bit-identical logits ⇒ identical tokens), and
  (b) a staggered continuous-batching run vs solo runs of each request.
  Both recomputed live at artifact-capture time; check_evidence requires
  them true.
- **speculative frontier** (ISSUE 11) — accept-rate × tokens/s/chip over
  drafter (``ngram`` prompt-lookup, ``draft`` self-draft smoke) × k on a
  repetitive and a random workload, plus the speculative identity
  markers recomputed live (greedy speculative == plain paged decode;
  sampled speculative == the same per-request PRNG stream). Judged by
  check_evidence's ``speculative`` stage (runbook stage 5j).
- **serve_resilience section** (ISSUE 14) — the replica plane's fault
  matrix through `serve/replica_plane.ServingFleet`: the crash-at-tick
  rows (tokens lost == 0 and migrated outputs token-identical at every
  cut, recovery-latency column), the one-slow-replica leg (per-replica
  p99 tick latency vs clean, detection + route-around facts), drain and
  rejoin legs, and identity markers recomputed live across
  greedy/sampled/speculative/prefix-cache engines. Judged by
  check_evidence's ``serve_resilience`` stage (runbook stage 5l).
- **tp_serving section** (ISSUE 13) — TP-degree rows (tokens/s/CHIP at
  each measured tp with p50/p99 tick latency: the per-chip number is the
  honest one — tp divides HBM per chip, not free throughput) and the
  shared-prefix memory leg: a 256-request shared-system-prompt workload
  drained through the prefix-cache engine vs the unshared engine,
  ``prefix_mem_ratio`` = physical pages allocated ÷ the unshared run's
  allocations (MEASURED, both runs, not derived). Identity markers
  recomputed live: tp=1 == unsharded, tp>1 == unsharded, and
  shared == unshared for greedy / sampled / speculative decode. Judged
  by check_evidence's ``tp_serving`` stage (runbook stage 5k). The tp>1
  markers/rows need ≥2 devices — on CPU run under
  ``DLION_PLATFORM=cpu8`` (the bench honors it via force_cpu_platform).
- **moe_serving section** (ISSUE 15) — the dense-vs-MoE-vs-MoE+ep decode
  matrix at the standard batches (tokens/s/CHIP, expert-capacity
  utilization and dropped-token-rate columns measured from the engine's
  on-device MoE routing stats against the capacity_factor budget), plus
  six live-recomputed identity markers on the tiny MoE config: paged MoE
  decode == dense-KV MoE generate, engine batched == solo, left-padded
  batched generate == solo, ep=1 bit-identical to the unsharded engine,
  ep>=2 and ep×tp token-identical. Judged by check_evidence's
  ``moe_serving`` stage (runbook stage 5m). The ep>=2 rows/markers need
  enough devices — on CPU run under ``DLION_PLATFORM=cpu8``.
- **fleet_resilience section** (ISSUE 20) — the process-isolated fleet's
  fault matrix over real OS processes and a live socket: the
  SIGKILL-at-tick rows (a replica CHILD PROCESS killed mid-decode under
  ``serve/net.drive_open_loop`` traffic — zero accepted-token loss,
  token-identical migrated responses, greedy and sampled), the
  full-stop restart leg (``serve/fleet_state`` shadow + chain index →
  fresh fleet, token-identical with prefill tokens saved by the
  warm-started pool), and the seeded workload soak through the socket
  front with its ``stream_sha256`` byte-determinism pin. Judged by
  check_evidence's ``fleet_resilience`` stage (runbook stage 5o).
- **slo section** (ISSUE 17) — the seeded scripts/workload_gen.py soak
  through the serve/metrics.py plane: TTFT and per-token decode latency
  p50/p95/p99 read from the LogHistogram sketches, goodput (in-SLO
  tokens/s), terminal status counts, token-loss accounting, breach
  count, and the ``metrics_inert`` marker (metrics-ON token streams
  byte-identical to metrics-OFF — the plane is observationally free).
  Judged by check_evidence's ``slo`` stage (runbook stage 5n).

CPU-produced artifacts are first-class smoke evidence (tiny model — the
engine mechanism, not chip throughput); ``meta.backend`` records what
measured it, and the runbook re-captures on chip at gpt2_124m.

    python scripts/bench_serve.py --out runs/serving
    python scripts/bench_serve.py --batches 32 --ticks 10   # quick look
    DLION_PLATFORM=cpu8 python scripts/bench_serve.py --out runs/serving
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

PROMPT_LEN = 16          # decode-row prompt length (uniform: the decode
#                          measurement wants full slots, not prompt variety)
DEFAULT_BATCHES = (32, 128, 256)


_MODEL_CACHE = {}


def _serve_model(model_name: str, family: str):
    # one init per (model, family) for the whole bench: the speculative
    # legs build many engines over the same weights, and a draft:<k> leg
    # needs the target twice (self-draft smoke — measures the mechanism)
    key = (model_name, family)
    if key not in _MODEL_CACHE:
        import jax

        from distributed_lion_tpu.serve.engine import ServeModel

        if family == "gpt2":
            from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init

            # "<base>_moe": the base architecture with a Switch-MoE FFN in
            # every other block (tiny: 4 experts, gpt2_124m: 8) — the
            # moe_serving matrix's MoE arm (ISSUE 15)
            base = (model_name[:-4] if model_name.endswith("_moe")
                    else model_name)
            moe = {}
            if model_name.endswith("_moe"):
                moe = dict(moe_experts=4 if base == "tiny" else 8)
            cfg = (GPT2Config.tiny(**moe) if base == "tiny"
                   else GPT2Config.gpt2_124m(**moe))
            params = gpt2_init(jax.random.key(0), cfg)
            model = ServeModel.for_gpt2(params, cfg)
        else:
            from distributed_lion_tpu.models.llama import LlamaConfig, llama_init

            cfg = LlamaConfig.named(model_name)
            params = llama_init(jax.random.key(0), cfg)
            model = ServeModel.for_llama(params, cfg)
        _MODEL_CACHE[key] = (model, params, cfg)
    return _MODEL_CACHE[key]


def _build(model_name: str, family: str, quant: str, max_seqs: int,
           block_size: int, max_blocks_per_seq: int,
           prefill_cap: int = 1 << 30, temperature: float = 0.0,
           top_k=None, speculate: str = "", tp: int = 0, ep: int = 0,
           ep_batch: bool = False, ep_overlap: bool = False,
           prefix_cache: bool = False, num_blocks: int = 0,
           moe_stats: bool = False, metrics: bool = False):
    from distributed_lion_tpu.serve.engine import ServeConfig, ServingEngine

    model, params, cfg = _serve_model(model_name, family)
    scfg = ServeConfig(max_seqs=max_seqs, block_size=block_size,
                       max_blocks_per_seq=max_blocks_per_seq,
                       num_blocks=num_blocks,
                       prefill_cap_tokens=prefill_cap,
                       temperature=temperature, top_k=top_k, quant=quant,
                       tp=tp, ep=ep, ep_batch=ep_batch,
                       ep_overlap=ep_overlap, prefix_cache=prefix_cache,
                       speculate=speculate, moe_stats=moe_stats,
                       metrics=metrics)
    draft = model if speculate.startswith("draft") else None
    return ServingEngine(model, scfg, draft_model=draft), params, cfg


def _prompts(n: int, vocab: int, length: int = PROMPT_LEN, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, vocab, length))) for _ in range(n)]


def bench_decode(batch: int, model_name: str, family: str, quant: str,
                 block_size: int, ticks: int, warmup: int) -> dict:
    """Fill every slot, then time ``ticks`` full-batch decode dispatches."""
    from distributed_lion_tpu.serve.engine import Request

    need = PROMPT_LEN + warmup + ticks + 2
    nblocks = -(-need // block_size)
    engine, params, cfg = _build(model_name, family, quant, batch,
                                 block_size, nblocks)
    for i, toks in enumerate(_prompts(batch, cfg.vocab_size)):
        engine.submit(Request(req_id=i, tokens=toks,
                              max_new_tokens=need, seed=i))
    while engine.pending:  # prefill phase (uncapped) until every slot runs
        engine.step()
    assert all(s is not None for s in engine.slots), "slots did not fill"
    for _ in range(warmup):
        engine.step()
    t0 = time.perf_counter()
    for _ in range(ticks):
        engine.step()  # each tick host-syncs its token batch — the
        #                dispatch is fully retired inside the window
    dt = time.perf_counter() - t0
    return {
        "batch": batch,
        "decode_ticks": ticks,
        "ms_per_tick": round(dt / ticks * 1e3, 4),
        "tokens_per_sec_per_chip": round(batch * ticks / dt, 2),
        "quant": quant,
    }


def bench_prefill_share(model_name: str, family: str, quant: str,
                        caps: list, block_size: int) -> list:
    """Drain one staggered mixed workload per fairness cap: the ablation
    showing what a prefill burst costs the decode batch."""
    from distributed_lion_tpu.serve.engine import Request

    rows = []
    for cap in caps:
        engine, params, cfg = _build(model_name, family, quant, 16,
                                     block_size, 8, prefill_cap=cap)
        prompts = _prompts(48, cfg.vocab_size, seed=7)
        reqs = [Request(req_id=i, tokens=t, max_new_tokens=24, seed=i)
                for i, t in enumerate(prompts)]
        arrivals = {i: (i // 8) * 2 for i in range(len(reqs))}
        t0 = time.perf_counter()
        done = engine.run(reqs, arrivals)
        dt = time.perf_counter() - t0
        total = sum(len(c.tokens) for c in done.values())
        st = engine.stats
        rows.append({
            "prefill_cap_tokens": cap,
            "ticks": st["ticks"],
            "tokens_per_sec": round(total / dt, 2),
            "prefill_token_share": round(
                st["padded_prefill_tokens"]
                / max(st["padded_prefill_tokens"] + st["decode_tokens"], 1),
                4),
        })
    return rows


def _spec_prompts(n: int, vocab: int, kind: str, seed: int = 21):
    """Frontier workloads: ``repetitive`` prompts are repeated short
    motifs (the traffic prompt-lookup drafting exists for — system
    prompts, templated requests), ``random`` prompts carry no n-gram
    signal (the drafter must cost nothing when it can't help)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    if kind == "repetitive":
        out = []
        for _ in range(n):
            motif = list(map(int, rng.integers(1, vocab, 4)))
            out.append(motif * 4)
        return out
    return _prompts(n, vocab, length=PROMPT_LEN, seed=seed)


def bench_speculative(model_name: str, family: str, quant: str,
                      block_size: int, ticks: int, warmup: int,
                      batch: int, ks=(2, 4)) -> dict:
    """The ISSUE 11 evidence: the accept-rate × tokens/s/chip frontier
    over drafter × k on two workloads, plus live-recomputed identity
    markers (greedy speculative == plain paged decode; sampled
    speculative == the same per-request PRNG stream). Speculation never
    changes an output — the frontier shows what each drafter's accept
    rate buys in committed tokens per second."""
    from distributed_lion_tpu.serve.engine import Request

    model, _, cfg = _serve_model(model_name, family)

    def timed_leg(speculate: str, kind: str) -> dict:
        # full-occupancy timed ticks, the decode-row recipe: budgets are
        # sized so no slot finishes inside the window (plain ticks commit
        # 1 token; a speculative tick commits up to k+1). The window is
        # capped by the model's position budget (tiny n_ctx=128 bounds
        # the CPU smoke; gpt2_124m's 1024 fits the full default window).
        k = int(speculate.split(":")[1]) if speculate else 0
        # the random leg decodes SAMPLED: greedy decode from a tiny model
        # degenerates into repeated motifs within a few tokens, handing
        # the self-drafter the very signal the leg exists to withhold —
        # a sampled stream keeps the workload genuinely n-gram-free
        # (identity markers below still pin sampled == the plain stream)
        samp = dict(temperature=0.9, top_k=40) if kind == "random" else {}
        # budget from the PAGE-ROUNDED position budget: pages quantize the
        # horizon, so a non-divisor --block_size must round DOWN here or
        # nblocks*block_size overshoots max_positions and the engine
        # refuses the geometry (e.g. n_ctx=128 at block_size 12)
        cap = model.max_positions or 1 << 30
        cap = (cap // block_size) * block_size
        assert cap > PROMPT_LEN + 2, \
            f"--block_size {block_size} leaves no room under the model's " \
            f"position budget {model.max_positions}"
        # admission steps ALSO run a decode tick (engine.step admits then
        # decodes), so budget FILL_TICKS extra ticks of commits — without
        # them slots exhaust max_new_tokens inside the timed window and
        # the speculative rows read biased-low vs the k=0 baseline
        FILL_TICKS = 2
        total = min(warmup + ticks,
                    (cap - PROMPT_LEN - 2) // (k + 1) - FILL_TICKS)
        w = min(warmup, max(total - 1, 0))
        t = total - w
        need = (total + FILL_TICKS) * (k + 1) + 2
        nblocks = -(-(PROMPT_LEN + need) // block_size)
        eng, _, _ = _build(model_name, family, quant, batch, block_size,
                           nblocks, speculate=speculate, **samp)
        for i, toks in enumerate(_spec_prompts(batch, cfg.vocab_size, kind)):
            eng.submit(Request(req_id=i, tokens=toks, max_new_tokens=need,
                               seed=i))
        while eng.pending:
            eng.step()
        assert all(s is not None for s in eng.slots), "slots did not fill"
        for _ in range(w):
            eng.step()
        t0 = time.perf_counter()
        tok0 = eng.stats["decode_tokens"]
        prop0 = eng.stats.get("spec_proposed", 0)
        acc0 = eng.stats.get("spec_accepted", 0)
        for _ in range(t):
            eng.step()
        dt = time.perf_counter() - t0
        assert all(s is not None for s in eng.slots), \
            "a slot finished inside the timed window — budget miscount"
        committed = eng.stats["decode_tokens"] - tok0
        proposed = eng.stats.get("spec_proposed", 0) - prop0
        accepted = eng.stats.get("spec_accepted", 0) - acc0
        name = speculate.split(":")[0] if speculate else "none"
        return {
            "drafter": name, "k": k, "workload": kind,
            "proposed": int(proposed), "accepted": int(accepted),
            "accept_rate": round(accepted / proposed, 4) if proposed
            else 0.0,
            "ticks": t,
            "ms_per_tick": round(dt / t * 1e3, 4),
            "tokens_per_tick": round(committed / t, 3),
            "tokens_per_sec_per_chip": round(committed / dt, 2),
        }

    frontier = []
    for kind in ("repetitive", "random"):
        legs = [""] + [f"{d}:{k}" for d in ("ngram", "draft") for k in ks]
        for leg in legs:
            frontier.append(timed_leg(leg, kind))
            print(json.dumps(frontier[-1], allow_nan=False), flush=True)

    # live-recomputed identity markers on the measured model: speculation
    # must EARN its "outputs unchanged" claim at capture time. Greedy:
    # both drafters; sampled: the per-request stream replay (ngram leg —
    # one drafter suffices, the acceptance rule is drafter-independent).
    def outputs(speculate: str, **samp):
        eng, _, _ = _build(model_name, family, quant, 8, block_size, 8,
                           speculate=speculate, **samp)
        reqs = [Request(req_id=i, tokens=toks, max_new_tokens=12, seed=i)
                for i, toks in enumerate(
                    _spec_prompts(4, cfg.vocab_size, "repetitive")
                    + _spec_prompts(4, cfg.vocab_size, "random"))]
        done = eng.run(reqs)
        return {r: c.tokens for r, c in done.items()}

    plain_greedy = outputs("")
    greedy_ok = all(outputs(s) == plain_greedy
                    for s in ("ngram:4", "draft:2"))
    sampled = dict(temperature=0.9, top_k=40)
    sampled_ok = outputs("ngram:4", **sampled) == outputs("", **sampled)
    return {
        "markers": {"greedy_vs_plain": bool(greedy_ok),
                    "sampled_vs_stream": bool(sampled_ok)},
        "frontier": frontier,
    }


def bit_identity_markers(family: str, model_name: str = "tiny") -> dict:
    """Live recompute of the two serving bit-identity claims on the tiny
    model (cheap on any backend) — the artifact must EARN its markers at
    capture time, not copy them from a test run. ``model_name``
    parameterizes the tiny architecture so the moe_serving section reuses
    the exact same recipe on the tiny MoE config (ISSUE 15)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.generate import generate
    from distributed_lion_tpu.serve.engine import Request

    block_size, nblk = 4, 8                  # paged horizon = 32 tokens
    new_tokens = 8
    engine, params, cfg = _build(model_name, family, "none", 4, block_size,
                                 nblk)
    if family == "gpt2":
        from distributed_lion_tpu.models.gpt2 import gpt2_decode, gpt2_init_cache

        def dec(p, t, c, pos, off=None):
            return gpt2_decode(p, t, cfg, c, pos, off)

        def ic(b, m):
            return gpt2_init_cache(cfg, b, m)
    else:
        from distributed_lion_tpu.models.llama import llama_decode, llama_init_cache

        def dec(p, t, c, pos, off=None):
            return llama_decode(p, t, cfg, c, pos, off)

        def ic(b, m):
            return llama_init_cache(cfg, b, m)

    # (a) paged engine vs dense generate, MATCHED attended length
    # (max_len == blocks*block_size), uniform prompts, greedy
    prompts = _prompts(4, cfg.vocab_size, length=7, seed=11)
    dense = np.asarray(generate(
        dec, ic, params, jnp.asarray(prompts, jnp.int32), new_tokens,
        max_len=block_size * nblk))
    done = engine.run([Request(req_id=i, tokens=t, max_new_tokens=new_tokens,
                               seed=0) for i, t in enumerate(prompts)])
    paged_vs_dense = all(
        list(dense[i]) == done[i].tokens for i in range(len(prompts)))

    # (b) staggered continuous batching vs solo runs, varied lengths
    varied = [p[: 3 + 2 * i] for i, p in enumerate(_prompts(4, cfg.vocab_size,
                                                            length=12, seed=13))]
    reqs = [Request(req_id=i, tokens=t, max_new_tokens=new_tokens, seed=i)
            for i, t in enumerate(varied)]
    eng2, _, _ = _build(model_name, family, "none", 4, block_size,
                        nblk)
    stag = eng2.run(reqs, arrivals={0: 0, 1: 1, 2: 1, 3: 4})
    ok = True
    for r in reqs:
        solo_eng, _, _ = _build(model_name, family, "none", 4,
                                block_size, nblk)
        solo = solo_eng.run([Request(r.req_id, list(r.tokens),
                                     r.max_new_tokens, r.seed)])
        ok = ok and solo[r.req_id].tokens == stag[r.req_id].tokens
    return {"paged_vs_dense": bool(paged_vs_dense),
            "batched_vs_solo": bool(ok)}


def _feasible_tps(family, cfg, requested) -> list:
    """Filter the requested TP degrees to ones this backend/model can
    actually run (enough devices, heads/kv-heads/d_ff divide) — dropped
    degrees are reported, never silently skipped (no-silent-caps)."""
    import jax

    from distributed_lion_tpu.parallel.tensor_parallel import validate_tp

    n_dev = len(jax.devices())
    out, dropped = [], []
    for t in requested:
        try:
            if t > n_dev:
                raise ValueError(f"{t} > {n_dev} devices")
            if t >= 1:
                validate_tp(cfg, t, family)
                kv = cfg.n_head if family == "gpt2" else cfg.n_kv_head
                if kv % t:
                    raise ValueError(f"kv heads {kv} % {t}")
            out.append(t)
        except ValueError as e:
            dropped.append((t, str(e)))
    for t, why in dropped:
        print(json.dumps({"dropped_tp_degree": t, "why": why},
                         allow_nan=False), flush=True)
    return out


def bench_tp_serving(model_name: str, family: str, quant: str,
                     block_size: int, ticks: int, warmup: int,
                     batch: int, tps, prefix_requests: int) -> dict:
    """The ISSUE 13 evidence: TP-degree decode rows (tokens/s/CHIP +
    p50/p99 tick latency), the shared-prefix memory leg (physical ÷
    logical pages, both MEASURED by draining the same workload through
    the shared and unshared engines), and the five live-recomputed
    identity markers (tiny model — identity is backend-independent)."""
    import numpy as np

    from distributed_lion_tpu.serve.engine import Request

    model, _, cfg = _serve_model(model_name, family)

    # ---- TP rows: full-occupancy timed decode ticks per degree
    rows = []
    for tp in _feasible_tps(family, cfg, tps):
        need = PROMPT_LEN + warmup + ticks + 2
        nblocks = -(-need // block_size)
        eng, _, _ = _build(model_name, family, quant, batch, block_size,
                           nblocks, tp=tp)
        for i, toks in enumerate(_prompts(batch, cfg.vocab_size)):
            eng.submit(Request(req_id=i, tokens=toks, max_new_tokens=need,
                               seed=i))
        while eng.pending:
            eng.step()
        assert all(s is not None for s in eng.slots), "slots did not fill"
        for _ in range(warmup):
            eng.step()
        tick_ms = []
        for _ in range(ticks):
            t0 = time.perf_counter()
            eng.step()  # host-syncs its token batch: fully retired
            tick_ms.append((time.perf_counter() - t0) * 1e3)
        total_s = sum(tick_ms) / 1e3
        chips = max(tp, 1)
        row = {
            "tp": tp, "batch": batch, "decode_ticks": ticks,
            "ms_per_tick_p50": round(float(np.percentile(tick_ms, 50)), 4),
            "ms_per_tick_p99": round(float(np.percentile(tick_ms, 99)), 4),
            "tokens_per_sec_per_chip": round(
                batch * ticks / total_s / chips, 2),
        }
        rows.append(row)
        print(json.dumps(row, allow_nan=False), flush=True)

    # ---- shared-prefix memory leg: 256 requests, one system prompt
    rng = np.random.default_rng(31)
    prompt_len = 132  # NOT page-aligned at the default block 16: the
    #                   partial boundary page exercises real CoW
    horizon = model.max_positions or 1 << 30
    prompt_len = min(prompt_len, (horizon // block_size) * block_size - 12)
    gen = 8
    sys_prompt = list(map(int, rng.integers(1, cfg.vocab_size, prompt_len)))
    reqs = [Request(req_id=i, tokens=list(sys_prompt), max_new_tokens=gen,
                    seed=i) for i in range(prefix_requests)]
    bps = -(-(prompt_len + gen + 1) // block_size)
    geom = dict(max_seqs=32, block_size=block_size, max_blocks_per_seq=bps)

    def drain(prefix_cache):
        eng, _, _ = _build(model_name, family, quant,
                           prefix_cache=prefix_cache, **geom)
        t0 = time.perf_counter()
        eng.run([Request(r.req_id, list(r.tokens), r.max_new_tokens,
                         r.seed) for r in reqs])
        dt = time.perf_counter() - t0
        return eng, dt

    unshared, dt_u = drain(False)
    shared, dt_s = drain(True)
    logical = unshared.tables.pages_allocated
    physical = shared.tables.pages_allocated
    prefix = {
        "requests": prefix_requests,
        "prompt_len": prompt_len,
        "max_new_tokens": gen,
        "logical_pages": int(logical),
        "physical_pages": int(physical),
        "prefix_mem_ratio": round(physical / logical, 4),
        "prefix_hits": int(shared.stats["prefix_hits"]),
        "cow_copies": int(shared.stats["cow_copies"]),
        "tokens_per_sec_shared": round(
            shared.stats["decode_tokens"] / dt_s, 2),
        "tokens_per_sec_unshared": round(
            unshared.stats["decode_tokens"] / dt_u, 2),
    }
    print(json.dumps(prefix, allow_nan=False), flush=True)

    # ---- identity markers, recomputed live on the tiny model (identity
    # is backend/scale-independent; the tiny model keeps capture cheap)
    def outputs(ident_kw, samp=None):
        eng, _, tcfg = _build("tiny", family, "none", 6, 4, 16,
                              num_blocks=128, **(ident_kw or {}),
                              **(samp or {}))
        trng = np.random.default_rng(17)
        sysp = list(map(int, trng.integers(1, tcfg.vocab_size, 13)))
        prompts = [sysp + list(map(int, trng.integers(1, tcfg.vocab_size,
                                                      3)))
                   for _ in range(4)] + [list(sysp)] * 2
        done = eng.run([Request(req_id=i, tokens=list(t), max_new_tokens=8,
                                seed=i) for i, t in enumerate(prompts)])
        return {r: c.tokens for r, c in done.items()}

    plain = outputs({})
    tiny_cfg = _serve_model("tiny", family)[2]
    tpn = max(_feasible_tps(family, tiny_cfg, [4, 2]) or [0])
    sampled = dict(temperature=0.9, top_k=40)
    markers = {
        "tp1_vs_unsharded": outputs({"tp": 1}) == plain,
        "tpN_vs_unsharded": (tpn >= 2
                             and outputs({"tp": tpn}) == plain),
        "shared_vs_unshared_greedy":
            outputs({"prefix_cache": True}) == plain,
        "shared_vs_unshared_sampled":
            outputs({"prefix_cache": True}, sampled)
            == outputs({}, sampled),
        "shared_vs_unshared_speculative":
            outputs({"prefix_cache": True, "speculate": "ngram:4"})
            == plain,
    }
    markers = {k: bool(v) for k, v in markers.items()}
    return {"markers": markers, "tp_degree_max_measured": int(tpn),
            "rows": rows, "prefix": prefix}


def bench_moe_serving(model_name: str, quant: str, block_size: int,
                      ticks: int, warmup: int, batches, eps) -> dict:
    """The ISSUE 15 evidence: the dense-vs-MoE-vs-MoE+ep decode matrix
    (tokens/s/CHIP at the standard batches, with expert-capacity
    utilization and dropped-token-rate columns measured from the engine's
    on-device MoE routing stats against the config's capacity_factor
    budget — serving itself never drops: inference routing is no-drop),
    plus the live-recomputed identity markers on the tiny MoE config:
    paged MoE == dense-KV MoE generate, batched == solo (engine AND
    left-padded batched generate), ep=1 bit-identical to the unsharded
    engine, ep>=2 and ep×tp token-identical on the measuring mesh. MoE
    is a gpt2 architecture; the section always measures the gpt2 family."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.serve.engine import Request

    family = "gpt2"
    moe_name = model_name + "_moe"
    _, _, mcfg = _serve_model(moe_name, family)
    E = mcfg.moe_experts
    n_dev = len(jax.devices())

    # feasible ep degrees — dropped degrees reported, never silently
    # skipped (no-silent-caps)
    feasible, dropped = [], []
    for e in eps:
        if e < 2:
            dropped.append((e, "matrix rows measure ep >= 2 (ep=1 is the "
                               "bit-identity marker)"))
        elif e > n_dev:
            dropped.append((e, f"{e} > {n_dev} devices"))
        elif E % e:
            dropped.append((e, f"moe_experts {E} % {e}"))
        else:
            feasible.append(e)
    for e, why in dropped:
        print(json.dumps({"dropped_ep_degree": e, "why": why},
                         allow_nan=False), flush=True)

    rows = []

    def routing_cols(batch: int) -> dict:
        """The capacity columns, measured in a SEPARATE UNTIMED pass with
        ``moe_stats`` armed — the per-tick stats host reads must never
        ride the timed throughput window (they would bias the
        dense-vs-MoE delta with instrumentation cost). Measured once per
        batch at ep=0: routing is pinned token-identical across
        ep/sharding, so one measurement honestly serves every MoE row of
        that batch."""
        stat_ticks = 8
        need = PROMPT_LEN + stat_ticks + 2
        nblocks = -(-need // block_size)
        eng, _, cfg = _build(moe_name, family, quant, batch, block_size,
                             nblocks, moe_stats=True)
        for i, toks in enumerate(_prompts(batch, cfg.vocab_size)):
            eng.submit(Request(req_id=i, tokens=toks, max_new_tokens=need,
                               seed=i))
        while eng.pending:
            eng.step()
        assert all(s is not None for s in eng.slots), "slots did not fill"
        v0, k0 = (eng.stats["moe_valid_tokens"],
                  eng.stats["moe_kept_tokens"])
        c0 = eng.stats["moe_capacity_slots"]
        for _ in range(stat_ticks):
            eng.step()
        vv = eng.stats["moe_valid_tokens"] - v0
        kk = eng.stats["moe_kept_tokens"] - k0
        cc = eng.stats["moe_capacity_slots"] - c0
        return {
            # routing load vs the capacity_factor budget (what-if columns:
            # the no-drop serving path drops nothing, these say how the
            # traffic would load the Switch training budget)
            "capacity_utilization": round(min(kk / cc, 1.0), 4) if cc
            else 0.0,
            "dropped_rate": round(max(vv - kk, 0.0) / vv, 4) if vv else 0.0,
        }

    dense_pc = {}  # batch -> dense tokens/s/chip (the per-chip yardstick)

    def timed(config: str, m_name: str, batch: int, ep: int,
              cols: dict, ep_batch: bool = False) -> None:
        need = PROMPT_LEN + warmup + ticks + 2
        nblocks = -(-need // block_size)
        is_moe = m_name == moe_name
        # moe_stats stays OFF here: every row (dense and MoE) times the
        # identical un-instrumented engine — apples to apples
        eng, _, cfg = _build(m_name, family, quant, batch, block_size,
                             nblocks, ep=ep, ep_batch=ep_batch)
        for i, toks in enumerate(_prompts(batch, cfg.vocab_size)):
            eng.submit(Request(req_id=i, tokens=toks, max_new_tokens=need,
                               seed=i))
        while eng.pending:
            eng.step()
        assert all(s is not None for s in eng.slots), "slots did not fill"
        for _ in range(warmup):
            eng.step()
        t0 = time.perf_counter()
        for _ in range(ticks):
            eng.step()  # host-syncs its token batch: fully retired
        dt = time.perf_counter() - t0
        pc = round(batch * ticks / dt / max(ep, 1), 2)
        if not is_moe:
            dense_pc[batch] = pc
        row = {
            "config": config, "experts": E if is_moe else 0, "ep": ep,
            # how the batch meets the expert axis: 'none' (no axis),
            # 'replicated' (every shard decodes the whole batch — ep is an
            # HBM lever only), 'batch' (rows sharded over the axis — each
            # shard decodes batch/ep rows, ISSUE 16's throughput lever)
            "sharding": ("batch" if ep_batch
                         else ("replicated" if ep else "none")),
            "batch": batch, "decode_ticks": ticks,
            "ms_per_tick": round(dt / ticks * 1e3, 4),
            "tokens_per_sec_per_chip": pc,
            "beats_dense_per_chip": bool(is_moe and batch in dense_pc
                                         and pc > dense_pc[batch]),
            "capacity_utilization": cols["capacity_utilization"] if is_moe
            else 0.0,
            "dropped_rate": cols["dropped_rate"] if is_moe else 0.0,
        }
        rows.append(row)
        print(json.dumps(row, allow_nan=False), flush=True)

    for batch in batches:
        cols = routing_cols(batch)
        timed("dense", model_name, batch, 0, cols)
        timed("moe", moe_name, batch, 0, cols)
        for e in feasible:
            timed(f"moe_ep{e}", moe_name, batch, e, cols)
            if batch % e == 0:
                timed(f"moe_ep{e}_batch", moe_name, batch, e, cols,
                      ep_batch=True)
            else:
                print(json.dumps(
                    {"dropped_row": f"moe_ep{e}_batch",
                     "why": f"batch {batch} % ep {e}"},
                    allow_nan=False), flush=True)

    # ---- identity markers, recomputed live on the tiny MoE config
    # (identity is backend/scale-independent; capture stays cheap)
    tiny = "tiny_moe"
    _, tparams, tcfg = _serve_model(tiny, family)
    bits = bit_identity_markers(family, model_name=tiny)

    # batched left-padded generate == solo (the lifted models/generate
    # refusal): greedy, varied prompt lengths
    from distributed_lion_tpu.models.generate import generate
    from distributed_lion_tpu.models.gpt2 import (
        gpt2_decode,
        gpt2_init_cache,
    )

    def dec(p, t, c, pos, off=None):
        return gpt2_decode(p, t, tcfg, c, pos, off)

    def ic(b, m):
        return gpt2_init_cache(tcfg, b, m)

    grng = np.random.default_rng(23)
    lens = [3, 7, 5, 9]
    prompts = [list(map(int, grng.integers(1, tcfg.vocab_size, L)))
               for L in lens]
    T = max(lens)
    padded = np.zeros((len(prompts), T), np.int32)
    for i, p in enumerate(prompts):
        padded[i, T - len(p):] = p
    batched = np.asarray(generate(
        dec, ic, tparams, jnp.asarray(padded), 8,
        prompt_lens=jnp.asarray(lens, jnp.int32)))
    gen_ok = True
    for i, p in enumerate(prompts):
        solo = np.asarray(generate(dec, ic, tparams,
                                   jnp.asarray([p], jnp.int32), 8))
        gen_ok = gen_ok and (batched[i] == solo[0]).all()

    # ep identity: engine outputs across sharding degrees
    def outputs(kw=None, samp=None):
        eng, _, _ = _build(tiny, family, "none", 4, 4, 8, **(kw or {}),
                           **(samp or {}))
        trng = np.random.default_rng(29)
        pr = [list(map(int, trng.integers(1, tcfg.vocab_size, 3 + 2 * i)))
              for i in range(4)]
        done = eng.run([Request(req_id=i, tokens=list(t), max_new_tokens=8,
                                seed=i) for i, t in enumerate(pr)])
        return {r: c.tokens for r, c in done.items()}

    plain = outputs()
    e_tiny = tcfg.moe_experts
    epn = max([e for e in (4, 2) if e <= n_dev and e_tiny % e == 0] or [0])
    can_ep_tp = n_dev >= 4 and tcfg.n_head % 2 == 0 and e_tiny % 2 == 0
    markers = {
        "paged_vs_dense": bits["paged_vs_dense"],
        "batched_vs_solo": bits["batched_vs_solo"],
        "batched_generate_vs_solo": bool(gen_ok),
        "ep1_vs_unsharded": outputs({"ep": 1}) == plain,
        "epN_vs_unsharded": epn >= 2 and outputs({"ep": epn}) == plain,
        "ep_tp_vs_unsharded": can_ep_tp
        and outputs({"ep": 2, "tp": 2}) == plain,
        # ISSUE 16: the batch-sharded rows are only admissible if the
        # sharding is a pure re-schedule — token-identical to the
        # unsharded engine, alone, with tp, and with the microbatch
        # overlap split
        "ep_batch1_vs_unsharded":
        outputs({"ep": 1, "ep_batch": True}) == plain,
        "ep_batchN_vs_unsharded": epn >= 2
        and outputs({"ep": epn, "ep_batch": True}) == plain,
        "ep_batch_tp_vs_unsharded": can_ep_tp
        and outputs({"ep": 2, "tp": 2, "ep_batch": True}) == plain,
        # overlap needs an even per-shard slot count: ep=2 on the 4-slot
        # identity engine (2 slots/shard, one per microbatch half)
        "ep_batch_overlap_vs_unsharded": epn >= 2 and e_tiny % 2 == 0
        and outputs({"ep": 2, "ep_batch": True,
                     "ep_overlap": True}) == plain,
    }
    markers = {k: bool(v) for k, v in markers.items()}
    return {"markers": markers, "ep_degree_max_measured": int(epn),
            "rows": rows}


def bench_serve_resilience(model_name: str, family: str, quant: str,
                           block_size: int) -> dict:
    """The ISSUE 14 evidence: the serve-side fault matrix through the
    replica plane. Crash-at-tick rows (tokens lost MUST be 0 and the
    migrated outputs token-identical — both measured against the
    single-engine baseline, with the recovery-latency column from the
    fleet's migration clock), the one-slow-replica leg (per-replica p99
    tick latency slow-vs-clean, detection + route-around facts), the
    drain and rejoin legs, and the identity markers recomputed live
    across greedy / sampled / speculative / prefix-cache engines."""
    from distributed_lion_tpu.serve.engine import Request
    from distributed_lion_tpu.serve.replica_plane import ServingFleet
    from distributed_lion_tpu.train import resilience

    model, _, cfg = _serve_model(model_name, family)
    gen = 16
    need = PROMPT_LEN + gen + 2
    nblocks = -(-need // block_size)
    n_req = 12
    prompts = _prompts(n_req, cfg.vocab_size, seed=5)
    arrivals = {i: (i // 2) for i in range(n_req)}

    def reqs():
        return [Request(req_id=i, tokens=list(t), max_new_tokens=gen,
                        seed=i) for i, t in enumerate(prompts)]

    def factory_for(**kw):
        def factory():
            eng, _, _ = _build(model_name, family, quant, 8, block_size,
                               nblocks, **kw)
            return eng
        return factory

    def fleet_run(specs, reqs_list=None, arr=None, record_latency=False,
                  **kw):
        resilience.inject_fault(
            "serve", resilience.parse_serve_specs(specs) if specs else [])
        fleet = ServingFleet(factory_for(**kw), replicas=2,
                             record_latency=record_latency)
        done = fleet.run(reqs_list if reqs_list is not None else reqs(),
                         dict(arr if arr is not None else arrivals))
        resilience.inject_fault("serve", [])
        return fleet, done

    def identical(done, base):
        return all(done[i].tokens == base[i].tokens
                   and done[i].reason == base[i].reason for i in base)

    def lost(done, base):
        return int(sum(max(len(base[i].tokens) - len(done[i].tokens), 0)
                       for i in base))

    base = factory_for()().run(reqs(), dict(arrivals))

    # ---- crash-at-tick matrix: zero accepted-token loss at every cut
    crash_matrix = []
    for crash_tick in (1, 3, 6):
        fleet, done = fleet_run(f"replica_crash:0:{crash_tick}")
        row = {
            "crash_tick": crash_tick,
            "migrated": int(fleet.stats["migrations"]),
            "tokens_lost": lost(done, base),
            "identical": bool(identical(done, base)),
            "recovery_latency_ticks": int(
                max(fleet.migration_latency_ticks, default=0)),
        }
        crash_matrix.append(row)
        print(json.dumps({"serve_resilience": "crash", **row},
                         allow_nan=False), flush=True)

    # ---- identity under sampling / speculation / prefix sharing: the
    # migrated stream must be the SAME stream, not just a plausible one
    samp = dict(temperature=0.9, top_k=40)
    base_samp = factory_for(**samp)().run(reqs(), dict(arrivals))
    _, done_samp = fleet_run("replica_crash:0:3", **samp)
    base_pc = factory_for(prefix_cache=True)().run(reqs(), dict(arrivals))
    _, done_spec = fleet_run("replica_crash:0:3", speculate="ngram:4")
    _, done_pc = fleet_run("replica_crash:0:3", prefix_cache=True)

    # ---- drain: admission stops, residents finish, nothing is lost
    fleet_d, done_d = fleet_run("replica_drain:0:2")
    drain = {
        "completed": int(len(done_d)),
        "identical": bool(identical(done_d, base)),
        "drained_departed": bool(fleet_d.lifecycle()[0] == "departed"),
        "migrated_pending": int(fleet_d.stats["migrations"]),
    }
    print(json.dumps({"serve_resilience": "drain", **drain},
                     allow_nan=False), flush=True)

    # ---- one slow replica: detected by the tick-latency watch, new
    # work routes around it, and the p99 story is measured per replica
    slow_ms = 20
    n_slow = 24
    slow_prompts = _prompts(n_slow, cfg.vocab_size, seed=6)
    slow_reqs = [Request(req_id=i, tokens=list(t), max_new_tokens=gen,
                         seed=i) for i, t in enumerate(slow_prompts)]
    slow_arr = {i: (i // 2) for i in range(n_slow)}
    fleet_c, done_c = fleet_run("", reqs_list=[
        Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)
        for r in slow_reqs], arr=slow_arr, record_latency=True)
    fleet_s, done_s = fleet_run(f"slow_tick:0:{slow_ms}", reqs_list=[
        Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)
        for r in slow_reqs], arr=slow_arr, record_latency=True)

    def p99(win):
        # TickLatencyWindow: exact percentile over the bounded recency
        # window — the first jit-compile tick ages out instead of
        # dominating p99 on BOTH replicas and masking the straggler
        return round(win.percentile(99), 3) if len(win) else 0.0

    slow_base = {i: c.tokens for i, c in done_c.items()}
    slow = {
        "slow_ms": slow_ms,
        "p99_ms_slow_replica": p99(fleet_s.tick_latency_log[0]),
        "p99_ms_clean_replica": p99(fleet_s.tick_latency_log[1]),
        "p99_ms_clean_run": max(p99(fleet_c.tick_latency_log[0]),
                                p99(fleet_c.tick_latency_log[1])),
        "detected": bool(fleet_s.stats["slow_detected"] >= 1),
        "admissions_slow": int(fleet_s.replicas[0].admissions),
        "admissions_fast": int(fleet_s.replicas[1].admissions),
        "identical": bool(all(done_s[i].tokens == slow_base[i]
                              for i in slow_base)),
    }
    print(json.dumps({"serve_resilience": "slow", **slow},
                     allow_nan=False), flush=True)

    # ---- crash then rejoin: the rejoiner re-enters the rotation with a
    # FRESH page pool and actually serves (its new engine's own stats
    # can only count post-rejoin work). Arrivals stretch PAST the rejoin
    # tick so there is new work to route to it — per-request outputs are
    # batch/arrival-independent (the engine's pinned streams), so the
    # same baseline still judges identity.
    fleet_r, done_r = fleet_run("replica_crash:0:2,replica_rejoin:0:6",
                                arr={i: i for i in range(n_req)})
    rep0 = fleet_r.replicas[0]
    rejoin = {
        "rejoined": bool(fleet_r.stats["replica_rejoins"] == 1),
        "served_after_rejoin": bool(
            rep0.engine is not None
            and rep0.engine.stats["prefill_dispatches"] > 0),
        "identical": bool(identical(done_r, base)),
        "final_lifecycle": list(fleet_r.lifecycle()),
    }
    print(json.dumps({"serve_resilience": "rejoin", **rejoin},
                     allow_nan=False), flush=True)

    markers = {
        "migrated_identity_greedy": all(r["identical"]
                                        for r in crash_matrix),
        "migrated_identity_sampled": identical(done_samp, base_samp),
        "migrated_identity_speculative": identical(done_spec, base),
        "migrated_identity_prefix_cache": identical(done_pc, base_pc),
        "zero_token_loss": all(r["tokens_lost"] == 0
                               for r in crash_matrix),
        "drain_completes_residents": drain["identical"]
        and drain["drained_departed"],
        "slow_detected_and_routed": slow["detected"]
        and slow["admissions_slow"] < slow["admissions_fast"],
        "rejoin_serves": rejoin["rejoined"]
        and rejoin["served_after_rejoin"] and rejoin["identical"],
    }
    markers = {k: bool(v) for k, v in markers.items()}
    return {"markers": markers, "crash_matrix": crash_matrix,
            "drain": drain, "slow": slow, "rejoin": rejoin}


def bench_fleet_resilience(block_size: int) -> dict:
    """The ISSUE 20 evidence: the process-isolated serving fleet's fault
    matrix, measured over real OS processes and a live socket.

    - **kill matrix** — a replica CHILD PROCESS is SIGKILLed for real at
      tick 1 / 3 / 6 (plus a sampled cut at tick 3) while
      ``serve/net.drive_open_loop`` streams the workload over a live
      socket connection; every response must come back token-identical
      to the never-killed single-engine run with zero accepted tokens
      lost, the cut registering as a process death (EOF on the pipe →
      ``replicas_declared_dead``), not a polite in-process exception.
    - **restart leg** — a fleet with a ``state_dir`` is stopped
      mid-decode (the persisted recovery shadow + prefix-chain index are
      all that survive) and a FRESH fleet resumes from disk:
      token-identical completions, with the warm-started page pool
      saving real prefill work (``shared_tokens`` > 0).
    - **socket soak** — a seeded workload_gen stream (imported by file
      path like the slo section) driven open-loop at a process fleet
      behind the socket front; banked with goodput and the
      byte-determinism ``stream_sha256`` pin (the digest every rerun of
      the same generator seed must reproduce).

    A CPU-produced artifact is first-class here for the same reason as
    the elasticity stage: process spawn, SIGKILL, pipe-EOF detection and
    the persistence manifest are host-plane mechanics on every backend.
    The section pins the tiny gpt2 model regardless of ``--model`` — the
    ``gpt2_tiny`` worker builder reconstructs those weights from the
    init seed alone, so parent baseline and child engines provably share
    weights with no checkpoint file in the loop."""
    import importlib.util
    import shutil
    import tempfile
    import threading
    import time

    import numpy as np

    from distributed_lion_tpu.serve import fleet_proc, fleet_state, net
    from distributed_lion_tpu.serve.engine import (
        Request,
        ServeConfig,
        ServingEngine,
    )
    from distributed_lion_tpu.serve.replica_plane import ServingFleet
    from distributed_lion_tpu.train import resilience

    model, _, cfg = _serve_model("tiny", "gpt2")
    gen = 10
    n_req = 8
    # worst prompt across the legs: 6-token shared prefix + 10-token
    # tail (kill matrix), or prefix_len+prompt_max = 22 (soak)
    serve_kw = dict(max_seqs=4, block_size=block_size,
                    max_blocks_per_seq=-(-(22 + 12 + 2) // block_size),
                    prefix_cache=True)
    builder = {"kind": "gpt2_tiny", "init_seed": 0, "serve": serve_kw}

    rng = np.random.default_rng(17)
    shared = [int(t) for t in rng.integers(1, cfg.vocab_size, 6)]
    wire = []
    for i in range(n_req):
        tail = [int(t) for t in rng.integers(1, cfg.vocab_size, 3 + i)]
        d = {"id": f"k{i}", "max_new_tokens": gen, "seed": i}
        if i % 2 == 0:
            d.update(tokens=shared + tail, prefix_group="sys")
        else:
            d["tokens"] = tail
        wire.append(d)

    def as_reqs():
        return [Request(req_id=d["id"], tokens=list(d["tokens"]),
                        max_new_tokens=d["max_new_tokens"], seed=d["seed"],
                        prefix_group=d.get("prefix_group"))
                for d in wire]

    def offline(**samp):
        eng = ServingEngine(model, ServeConfig(**{**serve_kw, **samp}))
        return eng.run(as_reqs())

    def kill_run(kill_tick, **samp):
        resilience.inject_fault("serve", resilience.parse_serve_specs(
            f"replica_kill:0:{kill_tick}"))
        fleet = ServingFleet(
            fleet_proc.process_replica_factory(
                {**builder, "serve": {**serve_kw, **samp}}),
            replicas=2)
        reps = [rep.engine for rep in fleet.replicas]
        pids = [r.pid for r in reps]
        srv = net.ServeServer(fleet, port=0)
        th = threading.Thread(target=srv.run,
                              kwargs={"max_wall_s": 300.0}, daemon=True)
        th.start()
        try:
            out = net.drive_open_loop(*srv.addr, records=wire,
                                      tick_s=0.0, max_wall_s=240.0)
        finally:
            srv.stop = True
            th.join(timeout=30)
            srv.close()
            fleet.close()
            resilience.inject_fault("serve", [])
        reaped = all(r.proc.poll() is not None for r in reps)
        isolated = (len(set(pids)) == 2 and os.getpid() not in pids
                    and all(p > 0 for p in pids) and reaped)
        return fleet, out, isolated

    # ---- SIGKILL matrix under live socket traffic
    kill_matrix = []
    for kill_tick, sampling in ((1, "greedy"), (3, "greedy"),
                                (6, "greedy"), (3, "stochastic")):
        samp = (dict(temperature=0.0) if sampling == "greedy"
                else dict(temperature=0.9, top_k=40))
        base = offline(**samp)
        fleet, out, isolated = kill_run(kill_tick, **samp)
        lost = sum(max(len(base[d["id"]].tokens)
                       - len(out["responses"][d["id"]]["tokens"]), 0)
                   for d in wire if d["id"] in out["responses"])
        row = {
            "kill_tick": kill_tick,
            "sampling": sampling,
            "migrated": int(fleet.stats["migrations"]),
            "declared_dead": int(fleet.stats["replicas_declared_dead"]),
            "tokens_lost": int(lost),
            "completed": int(len(out["responses"])),
            "identical": bool(
                len(out["responses"]) == n_req
                and all(out["responses"][d["id"]]["tokens"]
                        == base[d["id"]].tokens for d in wire)),
            "process_isolated": bool(isolated),
        }
        kill_matrix.append(row)
        print(json.dumps({"fleet_resilience": "kill", **row},
                         allow_nan=False), flush=True)

    # ---- full-stop restart from the persisted shadow + chain index
    base = offline()
    sdir = tempfile.mkdtemp(prefix="bench_fleet_state_")
    try:
        def factory():
            return ServingEngine(model, ServeConfig(**serve_kw))

        fleet_a = ServingFleet(factory, replicas=2, state_dir=sdir)
        done = {}
        for r in as_reqs():
            fleet_a.submit(r)
        for _ in range(4):              # mid-decode, nothing finished
            for c in fleet_a.step():
                done[c.req_id] = c
        fleet_a.save_state()
        inflight = len(fleet_a.export_records())
        # fleet_a is now abandoned — a kill -9 of the parent process
        fleet_b = ServingFleet(factory, replicas=2)
        state = fleet_state.load_fleet_state(sdir, now=time.monotonic())
        res = fleet_state.resume_into(fleet_b, state)
        while fleet_b.has_work():
            for c in fleet_b.step():
                done[c.req_id] = c
        saved = sum(rep.engine.stats["shared_tokens"]
                    for rep in fleet_b.replicas
                    if rep.engine is not None)
    finally:
        shutil.rmtree(sdir, ignore_errors=True)
    restart = {
        "inflight_at_stop": int(inflight),
        "restored": int(res["restored"]),
        "chains_primed": int(res["chains_primed"]),
        "resumed_from_tick": int(state["tick"]),
        "prefill_tokens_saved": int(saved),
        "identical": bool(all(
            done[d["id"]].tokens == base[d["id"]].tokens
            and done[d["id"]].reason == base[d["id"]].reason
            for d in wire)),
    }
    print(json.dumps({"fleet_resilience": "restart", **restart},
                     allow_nan=False), flush=True)

    # ---- seeded workload soak through the socket front
    spec = importlib.util.spec_from_file_location(
        "workload_gen_fleet", os.path.join(REPO, "scripts",
                                           "workload_gen.py"))
    wg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wg)
    records = wg.generate(requests=24, seed=3, vocab=cfg.vocab_size,
                          prompt_max=16, out_max=12, prefix_len=6,
                          deadline_frac=0.0)
    fleet = ServingFleet(fleet_proc.process_replica_factory(builder),
                         replicas=2)
    srv = net.ServeServer(fleet, port=0)
    th = threading.Thread(target=srv.run, kwargs={"max_wall_s": 300.0},
                          daemon=True)
    th.start()
    try:
        summary = wg.stream(records, "%s:%d" % srv.addr, tick_s=0.0,
                            max_wall_s=300.0)
    finally:
        srv.stop = True
        th.join(timeout=30)
        srv.close()
        fleet.close()
    soak = {
        "requests": int(len(records)),
        "completed": int(summary["completed"]),
        "rejects": int(summary["rejects"]),
        "retries": int(summary["retries"]),
        "wall_s": float(summary["wall_s"]),
        "tokens_out": int(summary["tokens_out"]),
        "goodput_tokens_per_s": round(
            summary["tokens_out"] / max(summary["wall_s"], 1e-9), 3),
        "stream_sha256": str(summary["stream_sha256"]),
    }
    print(json.dumps({"fleet_resilience": "soak", **soak},
                     allow_nan=False), flush=True)

    markers = {
        "sigkill_identity": all(r["identical"] for r in kill_matrix),
        "sigkill_zero_token_loss": all(r["tokens_lost"] == 0
                                       for r in kill_matrix),
        "process_isolated": all(r["process_isolated"]
                                and r["declared_dead"] == 1
                                for r in kill_matrix),
        "restart_identity": restart["identical"],
        "restart_prefill_saved": restart["prefill_tokens_saved"] > 0,
        "socket_soak_served": soak["completed"] == soak["requests"] > 0,
    }
    markers = {k: bool(v) for k, v in markers.items()}
    return {"markers": markers,
            "meta": {"model": "tiny", "replicas": 2,
                     "builder": "gpt2_tiny"},
            "kill_matrix": kill_matrix, "restart": restart,
            "socket_soak": soak}


def bench_slo(model_name: str, family: str, quant: str, block_size: int,
              requests: int = 48, seed: int = 0,
              slo_ttft_ms: float = 30_000.0, slo_tok_ms: float = 5_000.0,
              slo_p99: float = 0.99) -> dict:
    """The ISSUE 17 evidence: the seeded workload_gen soak through the
    metrics plane. One fixed open-loop workload (Poisson + bursts,
    heavy-tail lengths, shared-prefix populations — scripts/
    workload_gen.generate, imported by file path like the other script
    cross-imports) runs twice through identical engines: once with the
    metrics plane OFF (the baseline token streams) and once with
    metrics + SLO monitor ON (the measured soak). Banked:

    - TTFT and per-token decode latency p50/p95/p99 — read from the
      LogHistogram sketches, so the banked numbers exercise the same
      bounded path a fleet aggregates through;
    - goodput — tokens/s counted ONLY from requests that finished
      successfully (eos | length) with TTFT inside the target (the
      per-token side of the SLO is judged fleet-wide by the banked
      tok_ms quantiles and the breach counter — per-request wall decode
      clocks live inside the monitor and are not re-derivable here);
    - terminal status counts, token-loss accounting, breach count;
    - the ``metrics_inert`` marker: ON-run token streams byte-identical
      to the OFF run — the whole plane must be observationally free.

    The wide default targets are deliberate: a shared CI box can stall
    for seconds, and this leg's regression gate is token loss + schema +
    inertness, not wall-clock luck. Tight-target burn-rate behavior is
    pinned deterministically in tests/test_serve_metrics.py with an
    injected clock."""
    import importlib.util

    from distributed_lion_tpu.serve.engine import Request
    from distributed_lion_tpu.serve.metrics import ServeMetrics, SLOMonitor

    wg_path = os.path.join(REPO, "scripts", "workload_gen.py")
    spec_ = importlib.util.spec_from_file_location("dlt_workload_gen",
                                                   wg_path)
    wg = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(wg)

    _, _, cfg = _serve_model(model_name, family)
    prompt_max, out_max = 24, 24
    records = wg.generate(
        requests=requests, seed=seed, rate=1.0, burst_every=10,
        burst_size=3, vocab=cfg.vocab_size, prompt_median=8.0,
        prompt_max=prompt_max, out_median=8.0, out_max=out_max)
    reqs = [Request(req_id=r["id"], tokens=list(r["tokens"]),
                    max_new_tokens=r["max_new_tokens"], seed=r["seed"],
                    prefix_group=r.get("prefix_group"))
            for r in records]
    arrivals = {r["id"]: r["arrival_tick"] for r in records}
    nblocks = -(-(prompt_max + out_max + 2) // block_size)

    def fresh(**kw):
        eng, _, _ = _build(model_name, family, quant, 8, block_size,
                           nblocks, **kw)
        return eng

    def clone(rs):
        return [Request(r.req_id, list(r.tokens), r.max_new_tokens,
                        r.seed, prefix_group=r.prefix_group) for r in rs]

    base = fresh().run(clone(reqs), dict(arrivals))

    eng = fresh(metrics=True)
    eng.metrics = ServeMetrics(eng.times, slo=SLOMonitor(
        ttft_ms=slo_ttft_ms, tok_ms=slo_tok_ms, p99=slo_p99))
    t0 = time.perf_counter()
    done = eng.run(clone(reqs), dict(arrivals))
    wall_s = max(time.perf_counter() - t0, 1e-9)

    inert = (set(done) == set(base) and all(
        done[i].tokens == base[i].tokens
        and done[i].reason == base[i].reason for i in base))
    tokens_lost = int(sum(
        max(len(base[i].tokens) - len(done.get(i, base[i]).tokens), 0)
        for i in base))
    timed = all(
        isinstance(c.timing, dict)
        and isinstance(c.timing.get("queue_ticks"), int)
        and isinstance(c.timing.get("decode_ticks"), int)
        for c in done.values())

    counts = {k: 0 for k in ("eos", "length", "overflow", "timeout",
                             "failed")}
    for c in done.values():
        counts[c.reason] = counts.get(c.reason, 0) + 1
    good_tokens = sum(
        len(c.tokens) for c in done.values()
        if c.reason in ("eos", "length") and isinstance(c.timing, dict)
        and c.timing.get("ttft_ms") is not None
        and c.timing["ttft_ms"] <= slo_ttft_ms)

    snap = eng.metrics.snapshot()
    quantiles = {
        sec: {k: round(float(snap[sec][k]), 4)
              for k in ("p50", "p95", "p99")}
        for sec in ("ttft_ms", "tok_ms")}
    markers = {
        "metrics_inert": bool(inert),
        "zero_token_loss": bool(tokens_lost == 0),
        "responses_timed": bool(timed),
    }
    out = {
        "markers": markers,
        "targets": {"ttft_ms": float(slo_ttft_ms),
                    "tok_ms": float(slo_tok_ms), "p99": float(slo_p99)},
        "requests": int(len(done)),
        "tokens_out": int(sum(len(c.tokens) for c in done.values())),
        "tokens_lost": tokens_lost,
        "ticks": int(eng.stats["ticks"]),
        "breaches": int(eng.metrics.slo.breaches),
        "ttft_ms": quantiles["ttft_ms"],
        "tok_ms": quantiles["tok_ms"],
        "goodput_tokens_per_sec": round(float(good_tokens) / wall_s, 3),
        "status_counts": counts,
    }
    print(json.dumps({"slo": "soak", **{k: v for k, v in out.items()
                                        if k != "markers"}, **markers},
                     allow_nan=False), flush=True)
    return out


def main() -> int:
    from distributed_lion_tpu.parallel.mesh import force_cpu_platform

    force_cpu_platform()  # DLION_PLATFORM=cpu8 → 8 virtual devices for
    #                       the TP legs (must run before first device use)

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(REPO, "runs", "serving"))
    ap.add_argument("--model", default=None,
                    help="tiny (default off-TPU) | gpt2_124m (default on TPU)"
                         " | llama small/...")
    ap.add_argument("--family", default="gpt2", choices=("gpt2", "llama"))
    ap.add_argument("--quant", default="none",
                    choices=("none", "nf4", "int8"),
                    help="weight format of the MEASURED decode arm (the "
                         "bytes columns always report both)")
    ap.add_argument("--batches", default=",".join(map(str, DEFAULT_BATCHES)))
    ap.add_argument("--block_size", type=int, default=16)
    ap.add_argument("--ticks", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--spec_batch", type=int, default=8,
                    help="decode batch of the speculative frontier legs "
                         "(smaller than the decode rows: each leg runs "
                         "drafter x k x workload engines)")
    ap.add_argument("--spec_ks", default="2,4",
                    help="draft lengths measured per drafter")
    ap.add_argument("--tps", default="1,2,4",
                    help="TP degrees for the tp_serving rows (degrees the "
                         "backend/model can't run are dropped LOUDLY)")
    ap.add_argument("--tp_batch", type=int, default=32,
                    help="decode batch of the TP rows")
    ap.add_argument("--prefix_requests", type=int, default=256,
                    help="requests in the shared-system-prompt memory leg")
    ap.add_argument("--slo_requests", type=int, default=48,
                    help="requests in the seeded workload_gen soak of "
                         "the slo section")
    ap.add_argument("--slo_ttft_ms", type=float, default=30_000.0,
                    help="banked TTFT target of the slo soak (wide by "
                         "default: the gate is token loss + schema + "
                         "metrics inertness, not CI wall-clock luck)")
    ap.add_argument("--slo_tok_ms", type=float, default=5_000.0,
                    help="banked per-token latency target of the slo soak")
    ap.add_argument("--moe_eps", default="2,4",
                    help="expert-parallel degrees for the moe_serving "
                         "matrix rows (infeasible degrees dropped LOUDLY; "
                         "ep=1 is covered by the bit-identity marker)")
    args = ap.parse_args()

    import jax

    from distributed_lion_tpu.ops.quant import quantize_tree
    from distributed_lion_tpu.serve.engine import weight_bytes

    backend = jax.default_backend()
    model_name = args.model or ("gpt2_124m" if backend == "tpu" else "tiny")
    batches = [int(b) for b in args.batches.split(",") if b]

    # the NF4-vs-bf16 column: measured storage bytes of the same tree in
    # both formats (dense counted at 2 bytes/param — the bf16 serving
    # copy — so an f32 checkpoint doesn't inflate the comparison)
    _, params, cfg = _build(model_name, args.family, "none", 2,
                            args.block_size, 2)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    bytes_bf16 = 2 * n_params
    bytes_nf4 = weight_bytes(quantize_tree(params, "nf4"))
    del params

    decode_rows = []
    for b in batches:
        row = bench_decode(b, model_name, args.family, args.quant,
                           args.block_size, args.ticks, args.warmup)
        row["weight_bytes_bf16"] = int(bytes_bf16)
        row["weight_bytes_nf4"] = int(bytes_nf4)
        decode_rows.append(row)
        print(json.dumps(row, allow_nan=False), flush=True)

    share_rows = bench_prefill_share(model_name, args.family, args.quant,
                                     [args.block_size, 4 * args.block_size,
                                      1 << 30], args.block_size)
    bits = bit_identity_markers(args.family)
    spec = bench_speculative(model_name, args.family, args.quant,
                             args.block_size, args.ticks, args.warmup,
                             args.spec_batch,
                             tuple(int(k) for k in args.spec_ks.split(",")
                                   if k))
    tp_serving = bench_tp_serving(
        model_name, args.family, args.quant, args.block_size, args.ticks,
        args.warmup, args.tp_batch,
        [int(t) for t in args.tps.split(",") if t], args.prefix_requests)
    serve_resilience = bench_serve_resilience(
        model_name, args.family, args.quant, args.block_size)
    fleet_resilience = bench_fleet_resilience(args.block_size)
    # MoE is a gpt2 architecture; a llama bench still measures the MoE
    # matrix against the default gpt2 model at this scale
    moe_base = (model_name if args.family == "gpt2"
                else ("gpt2_124m" if backend == "tpu" else "tiny"))
    moe_serving = bench_moe_serving(
        moe_base, args.quant, args.block_size, args.ticks, args.warmup,
        batches, [int(e) for e in args.moe_eps.split(",") if e])
    slo = bench_slo(model_name, args.family, args.quant, args.block_size,
                    requests=args.slo_requests,
                    slo_ttft_ms=args.slo_ttft_ms,
                    slo_tok_ms=args.slo_tok_ms)

    doc = {
        "meta": {
            "backend": backend,
            "device_kind": jax.devices()[0].device_kind,
            "num_devices": 1,  # the engine is single-device today; rows
            #                    are per chip by construction
            "model": model_name,
            "family": args.family,
            "quant_measured": args.quant,
            "block_size": args.block_size,
            "prompt_len": PROMPT_LEN,
            "n_params": int(n_params),
        },
        "decode": decode_rows,
        "prefill_share": share_rows,
        "bit_identity": bits,
        "speculative": spec,
        "tp_serving": tp_serving,
        "serve_resilience": serve_resilience,
        "fleet_resilience": fleet_resilience,
        "moe_serving": moe_serving,
        "slo": slo,
    }
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "serving.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, allow_nan=False)
        f.write("\n")
    os.replace(tmp, path)
    print(json.dumps({"artifact": path, **bits,
                      **{f"spec_{k}": v
                         for k, v in spec["markers"].items()},
                      **{f"tp_{k}": v
                         for k, v in tp_serving["markers"].items()},
                      **{f"sr_{k}": v
                         for k, v in serve_resilience["markers"].items()},
                      **{f"fr_{k}": v
                         for k, v in fleet_resilience["markers"].items()},
                      **{f"moe_{k}": v
                         for k, v in moe_serving["markers"].items()},
                      **{f"slo_{k}": v
                         for k, v in slo["markers"].items()},
                      "prefix_mem_ratio":
                          tp_serving["prefix"]["prefix_mem_ratio"],
                      "best_tokens_per_sec_per_chip": max(
                          r["tokens_per_sec_per_chip"] for r in decode_rows)},
                     allow_nan=False), flush=True)
    return 0 if (all(bits.values()) and all(spec["markers"].values())
                 and all(tp_serving["markers"].values())
                 and all(serve_resilience["markers"].values())
                 and all(fleet_resilience["markers"].values())
                 and all(moe_serving["markers"].values())
                 and all(slo["markers"].values())) else 1


if __name__ == "__main__":
    sys.exit(main())
