"""Benchmark: GPT-2 124M vote-Lion training throughput + MFU on the local chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}
and exits 0 — on a TPU. The measurement runs in a child process under a hard
timeout (a hung backend init must not hang the orchestrator, which never
imports jax) with one short retry; a run that finds no TPU, or whose attempts
all fail, prints the errors and exits NON-ZERO. There is no CPU attempt and no
stale record attached: a number from this file is a chip number or there is
no number.

Anchor derivation (vs_baseline): the reference publishes no numbers
(BASELINE.md); its stated target is "GPT-2 124M on v5e-8 competitive with
8xA100". GPT-2 124M costs ~857 MFLOPs/token (6N = 744M for N=124M, plus
12*L*d*T = 113M of attention matmuls at L=12, d=768, T=1024). An A100 at 312
bf16 TFLOP/s would give ~145k tokens/s at a strong 40% MFU; under the
reference's stack (HF Trainer + DDP + a per-tensor Python-loop optimizer its
own README calls "currently slow") ~28% MFU is generous, giving the anchor
BASELINE_TOKENS_PER_SEC_PER_DEVICE = 100_000. vs_baseline > 1 therefore means
one TPU chip under this framework out-trains one A100 under the reference.

Measurement discipline: the K optimizer steps of each timed dispatch run as
ONE device program (Trainer._train_chunk, lax.scan over staged batches), and
the timer stops only after a device_get of the final chunk's loss — a value
data-dependent on every step — so queued-but-unexecuted work can't inflate
the number (dispatch returns long before execution finishes).
Config picked by scripts/bench_sweep.py on v5e (SWEEP_v5e.md): remat off
(124M activations fit HBM), bf16 params (the reference's canonical bf16
config), microbatch 4 with 16-step grad accumulation — small microbatches
keep attention-score traffic per pass low while accumulation amortizes the
optimizer's full-pytree ballot/vote/apply passes over 16x the tokens —
chunked-vocab CE (vocab_chunks 8: the streaming logsumexp kills the dense
[B,T,V] f32 logits round-trip), Pallas flash attention (round 3:
flash@512x1024 of jax's bundled kernel — its stock tiles LOSE to xla at
T=1024; since PR 27 `auto` takes the repo's own kernel, PERF.md),
and bf16 Lion momentum. The round-3 sweep measured the combination at
98,099 tokens/s/chip (~42.8% MFU) vs 82.8k for the round-2 xla/f32-momentum
config (scripts/SWEEP_r3_raw/sweep2.jsonl).

MFU = achieved model FLOP/s / chip peak bf16 FLOP/s, with model FLOPs/token =
6N + 12*L*d*T (fwd+bwd, PaLM appendix-B convention, attention included,
rematerialization not counted as useful work).
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import subprocess
import sys
import time

BASELINE_TOKENS_PER_SEC_PER_DEVICE = 100_000.0
STEPS_PER_CALL = 10
TIMED_CALLS = 4

# Recorded artifact holding the last measurement on real TPU hardware with
# THIS benchmark. bench.py WRITES it after every successful TPU run; a later
# bare run adopts its promoted config (run_inner). It is never attached to
# a run that did not reach a TPU.
LAST_TPU_ARTIFACT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "scripts", "last_tpu_measurement.json",
)


def _load_last_tpu_measurement() -> dict | None:
    try:
        with open(LAST_TPU_ARTIFACT) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def sweep_row_promotable(d: dict) -> bool:
    """The ONE eligibility rule for treating a bench_sweep row (or a
    recorded flagship config, run_inner's adoption probe) as flagship
    evidence. Promotable = a RESULT row of the canonical T=1024 anchor
    workload, TPU-attested: rows carry backend since round 4, and the
    default 'tpu' keeps the committed round-3 rows (captured on a TPU,
    scripts/SWEEP_r3_raw/log.txt) eligible while excluding any
    CPU-produced row. The block filter keeps T=2048 long-context
    rows (sweep3) out: a different workload, not anchor-comparable. The
    vote_buckets filter keeps the overlap-ablation rows out for the same
    reason in reverse: every banked flagship row measured the monolithic
    vote, so a pipelined-wire row (same tokens, less exposed wire time)
    must not displace the anchor it is being compared against."""
    return (bool(d.get("tokens_per_sec_per_chip"))
            and d.get("backend", "tpu") == "tpu"
            and d.get("block", 1024) == 1024
            and d.get("vote_buckets", 1) == 1)


def overlap_from_ablation() -> dict | None:
    """Measured vote-wire overlap from the committed buckets-ablation rows
    (scripts/SWEEP_r*_raw/overlap.jsonl, captured by the runbook's overlap
    stage: the flagship config at vote_buckets ∈ {1, 4, 16}).

    Groups TPU-attested result rows by config-minus-buckets; for a group
    holding a buckets=1 row and at least one buckets>1 row, the measured
    ``comm_overlap_frac`` is the step-time fraction the pipelined wire
    recovered: ``(ms[1] − min_B ms[B]) / ms[1]``, clipped at 0. This is a
    LOWER bound on the wire time hidden behind the fused apply (compute is
    unchanged between the rows — only when bytes move differs). Returns the
    best-covered group as {"comm_overlap_frac", "ms_per_step", "source"},
    or None when no ablation has been captured yet."""
    import glob as _glob

    pattern = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "scripts", "SWEEP_r*_raw", "overlap.jsonl")
    groups: dict = {}
    for path in sorted(_glob.glob(pattern)):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line.startswith("{"):
                        continue
                    try:
                        d = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if (not d.get("ms_per_step")
                            or not d.get("tokens_per_sec_per_chip")
                            or d.get("backend", "tpu") != "tpu"):
                        continue
                    key = (d.get("remat"), d.get("batch_per_dev"),
                           d.get("attn"), d.get("accum"), d.get("dtype"),
                           d.get("vocab_chunks", 0),
                           d.get("mom_dtype", "f32"), d.get("vocab_pad", 0),
                           d.get("block", 1024))
                    b = int(d.get("vote_buckets", 1))
                    # latest capture of a (config, buckets) cell wins
                    groups.setdefault(key, {})[b] = (float(d["ms_per_step"]),
                                                     path)
        except OSError:
            continue
    best = None
    for times in groups.values():
        if 1 not in times or len(times) < 2:
            continue
        ms1 = times[1][0]
        b_min = min((ms for b, (ms, _) in times.items() if b > 1))
        frac = max(0.0, (ms1 - b_min) / ms1) if ms1 > 0 else 0.0
        if best is None or len(times) > len(best["ms_per_step"]):
            best = {
                "comm_overlap_frac": round(frac, 4),
                "ms_per_step": {str(b): ms for b, (ms, _) in
                                sorted(times.items())},
                "source": os.path.relpath(
                    next(iter(times.values()))[1],
                    os.path.dirname(os.path.abspath(__file__))),
            }
    return best


def _record_tpu_measurement(result: dict) -> None:
    prev = _load_last_tpu_measurement()
    if prev and prev.get("promoted") and not result.get("promoted"):
        # an unpromoted capture (debug run with BENCH_* overrides) must not
        # clobber the promoted flagship artifact that future bare runs adopt
        # their config from (advisor r4, medium) — the run's own JSON line
        # still prints; only the adoption store is protected
        print("note: unpromoted TPU capture not recorded over the promoted "
              "flagship artifact", file=sys.stderr)
        return
    rec = dict(result)
    rec["measured"] = time.strftime("%Y-%m-%d %H:%M:%SZ", time.gmtime())
    try:
        with open(LAST_TPU_ARTIFACT, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    except OSError:
        pass

# Peak dense bf16 FLOP/s per chip by device_kind substring (ordered: first
# match wins). Public figures from cloud.google.com/tpu/docs/system-architecture.
_PEAK_FLOPS = (
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def _peak_flops_per_chip(device_kind: str) -> float | None:
    kind = device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def run_inner() -> None:
    """The actual measurement. Runs in a child process (see main)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_lion_tpu.data.sources import synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    devices = jax.devices()
    n_dev = len(devices)
    backend = devices[0].platform
    device_kind = devices[0].device_kind
    if os.environ.get("BENCH_REQUIRE_TPU") == "1" and backend != "tpu":
        # main()'s attempts are TPU measurements; on a host whose backend
        # resolves to CPU the flagship config would grind until the 900s
        # timeout (hours of work at 124M on a host core). Fail in seconds.
        print(f"BENCH_REQUIRE_TPU=1 but backend is {backend!r}; "
              "refusing the full-budget flagship config off-TPU",
              file=sys.stderr)
        raise SystemExit(REFUSED_NO_TPU)
    mesh = make_mesh()
    # BENCH_* env knobs parameterize the ONE timed-step implementation:
    # bench.py IS the sweep harness's measurement core (scripts/
    # bench_sweep.py spawns `bench.py --inner` per config), so a sweep row
    # and a bench capture can never disagree on methodology again.
    # Unset knobs default to the recorded PROMOTED flagship config (the
    # "config" block of scripts/last_tpu_measurement.json): when a run
    # with BENCH_PROMOTE=1 promotes a faster sweep config, a later bare
    # `python bench.py` —
    # the driver's own capture — measures THAT flagship, not a stale
    # built-in. Gated three ways (code-review r4): only promoted records
    # are adopted (a one-off debug run's knobs must not poison future
    # headline captures — adoption itself re-marks the new record promoted
    # so the chain survives bare re-runs); eligibility goes through the
    # ONE sweep_row_promotable rule (backend + anchor-workload block); and
    # every adopted value is validated below with a fallback to built-ins
    # (a corrupt committed artifact must not take down both TPU attempts).
    rec_cfg = {}
    if backend == "tpu":
        rec = _load_last_tpu_measurement() or {}
        if rec.get("promoted") and isinstance(rec.get("config"), dict):
            probe = {"tokens_per_sec_per_chip": rec.get("value"),
                     "backend": rec.get("backend"),
                     "block": rec["config"].get("block", 1024),
                     "vote_buckets": rec["config"].get("vote_buckets", 1)}
            if sweep_row_promotable(probe):
                rec_cfg = rec["config"]
    env_changed: list = []  # BENCH_* overrides that CHANGED an adopted value
    def _resolve_knobs(rc):
        env_changed.clear()
        def knob(env_key, rec_key, builtin):
            v = os.environ.get(env_key)
            adopted = rc.get(rec_key, builtin)
            if v is not None and str(v) != str(adopted):
                # a knob the environment moved off the adopted config: this
                # run is a one-off variant, not the flagship — it must not
                # re-mark itself promoted below (advisor r4, medium)
                env_changed.append(env_key)
            return v if v is not None else adopted

        k = {
            "remat": str(knob("BENCH_REMAT", "remat", "noremat")),
            "dtype": str(knob("BENCH_DTYPE", "dtype", "bf16")),
            "block": int(knob("BENCH_BLOCK", "block", 1024)),
            "batch_per_dev": int(knob("BENCH_BATCH", "batch_per_dev", 4)),
            "accum": int(knob("BENCH_ACCUM", "accum", 16)),
            "vocab_chunks": int(knob("BENCH_VOCAB_CHUNKS",
                                     "vocab_chunks", 8)),
            "mom_dtype": str(knob("BENCH_MOM_DTYPE", "mom_dtype",
                                  "bfloat16")),
            # 'auto' resolves from the shapes (ops/attention): at the
            # flagship shape on a TPU the repo's own token-major kernel
            # (ops/pallas_flash_attn, PR 27), which superseded round 3's
            # flash@512x1024 — the flagship bench needs no explicit spec
            "attn": str(knob("BENCH_ATTN", "attn", "auto")),
            "vocab_pad": int(knob("BENCH_VOCAB_PAD", "vocab_pad", 0)),
            # bucketed, overlapped vote wire (optim.distributed_lion):
            # B > 1 pipelines the ballot collective with the fused apply.
            # Default 1 keeps every banked row comparable (all committed
            # sweep rows measured the monolithic vote); the overlap
            # ablation (runbook stage → overlap.jsonl) sweeps {1, 4, 16}.
            "vote_buckets": int(knob("BENCH_VOTE_BUCKETS",
                                     "vote_buckets", 1)),
            # vote-health telemetry in the timed step (train/telemetry).
            # Default ON: the added device work is one extra ballot-width
            # pass per OPTIMIZER step (margin bincount + packed-election
            # XOR, ~0.5 GB of HBM traffic at 124M coords) amortized over
            # accum microbatches of fwd/bwd — well under 1% of step time —
            # and elections are pinned bit-identical. Recorded in the row's
            # config; BENCH_TELEMETRY=0 gives the exact pre-telemetry
            # methodology for an overhead A/B, and (like any env-moved
            # knob) marks the run unpromotable.
            "telemetry": int(knob("BENCH_TELEMETRY", "telemetry", 1)),
        }
        if k["remat"] not in ("noremat", "full", "dots"):
            raise ValueError(f"bad remat {k['remat']!r}")
        if k["vote_buckets"] < 1:
            raise ValueError(f"bad vote_buckets {k['vote_buckets']!r}")
        if k["telemetry"] not in (0, 1):
            raise ValueError(f"bad telemetry {k['telemetry']!r}")
        if k["dtype"] not in ("bf16", "f32"):
            raise ValueError(f"bad dtype {k['dtype']!r}")
        from distributed_lion_tpu.ops.attention import parse_attn_spec
        parse_attn_spec(k["attn"])  # raises on a malformed spec
        return k

    try:
        k = _resolve_knobs(rec_cfg)
    except Exception as e:
        if not rec_cfg:
            raise  # malformed ENV values keep their loud failure
        print(f"recorded flagship config unusable ({e}); using built-in "
              "defaults", file=sys.stderr)
        rec_cfg = {}
        k = _resolve_knobs({})
    remat_s, dtype_s, block = k["remat"], k["dtype"], k["block"]
    batch_per_dev = k["batch_per_dev"]
    accum, vocab_chunks = k["accum"], k["vocab_chunks"]
    mom_dtype, attn_spec, vocab_pad = (k["mom_dtype"], k["attn"],
                                       k["vocab_pad"])
    vote_buckets = k["vote_buckets"]
    bench_telemetry = bool(k["telemetry"])
    steps_per_call = int(os.environ.get("BENCH_STEPS", STEPS_PER_CALL))
    timed_calls = int(os.environ.get("BENCH_CALLS", TIMED_CALLS))
    if (steps_per_call, timed_calls) != (STEPS_PER_CALL, TIMED_CALLS):
        # a shortened measurement budget (smoke runs) is just as
        # disqualifying as a config knob: a 1-step compile-adjacent number
        # must not become the promoted flagship (code-review r5)
        env_changed.append("BENCH_STEPS/BENCH_CALLS")
    model_cfg = dataclasses.replace(
        GPT2Config.gpt2_124m(), attn_impl="xla",
        remat=remat_s != "noremat",
        remat_policy="dots" if remat_s == "dots" else "full",
        param_dtype=jnp.bfloat16 if dtype_s == "bf16" else jnp.float32,
    )
    if block != model_cfg.n_ctx:
        model_cfg = dataclasses.replace(model_cfg, n_ctx=block)
    if vocab_pad:
        model_cfg = dataclasses.replace(model_cfg,
                                        vocab_pad_multiple=vocab_pad)
    from distributed_lion_tpu.ops.attention import parse_attn_spec

    attn_impl, bq, bkv, bqb, bkvb = parse_attn_spec(attn_spec)
    if attn_spec != "xla":
        model_cfg = dataclasses.replace(
            model_cfg, attn_impl=attn_impl,
            flash_block_q=bq, flash_block_kv=bkv,
            flash_block_q_bwd=bqb, flash_block_kv_bwd=bkvb)
    # provenance of what 'auto' MEANS on this device: the autotune cache
    # resolver (ops/autotune — the same lookup ops.attention's auto
    # dispatch applies at trace time) maps an auto spec to its tuned
    # explicit form; "auto" back means cache miss → heuristic dispatch.
    # Recorded in the row so a sweep/bench log is self-describing; null
    # for explicit specs (nothing was resolved).
    attn_resolved = None
    if attn_impl == "auto":
        from distributed_lion_tpu.ops.autotune import resolve_attn_spec

        attn_resolved = resolve_attn_spec(
            "auto", t=model_cfg.n_ctx,
            head_dim=model_cfg.d_model // model_cfg.n_head,
            dtype=jnp.dtype(model_cfg.compute_dtype).name)
    cfg = TrainConfig(
        lion=True,
        async_grad=True,
        # vote-health telemetry rides the timed step so BENCH_*.json tracks
        # election dynamics (flip rate, margin, disagreement) alongside the
        # throughput number — see the BENCH_TELEMETRY knob above for the
        # overhead bound and the opt-out that reproduces the pre-telemetry
        # methodology exactly.
        telemetry=bench_telemetry,
        # pin the round-3 comm methodology: every committed sweep/bench row
        # measured every-step sign_psum voting. Left at the auto sentinels,
        # a W>1 backend would resolve to packed_a2a + vote_every=4 (less
        # comm per step) and rank incomparably against the banked rows.
        # W=1 short-circuits either way; this makes multi-chip explicit.
        wire="sign_psum",
        vote_every=1,
        vote_buckets=vote_buckets,
        learning_rate=1e-4,
        weight_decay=0.1,
        warmup_steps=10,
        max_steps=10_000,
        per_device_train_batch_size=batch_per_dev,
        gradient_accumulation_steps=accum,
        block_size=model_cfg.n_ctx,
        steps_per_call=steps_per_call,
        logging_steps=10_000,
        output_dir=None,
        vocab_chunks=vocab_chunks,
        mom_dtype=mom_dtype,
    )
    trainer = Trainer.for_gpt2(cfg, mesh, model_cfg)
    global_bs = trainer.global_train_batch()
    tokens_per_step = global_bs * cfg.block_size
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(trainer.params))
    # MFU honesty under a padded-vocab layout: the chip executes the pad
    # columns' FLOPs, but they are not useful model work — count only the
    # true-vocab parameters in the 6N model-FLOPs term
    n_pad = (model_cfg.padded_vocab - model_cfg.vocab_size) * model_cfg.d_model
    n_params -= n_pad

    blocks = synthetic_lm_dataset(
        global_bs * steps_per_call, cfg.block_size, model_cfg.vocab_size, seed=0
    )
    batches = jax.device_put(
        blocks.astype(np.int32).reshape(steps_per_call, global_bs, cfg.block_size),
        NamedSharding(mesh, P(None, "data")),
    )
    base_key = jax.random.key(0)

    # warmup/compile + honest sync
    trainer.params, trainer.state, trainer.vote_health, m = (
        trainer._train_chunk(trainer.params, trainer.state,
                             trainer.vote_health, trainer._frozen_arg(),
                             batches, base_key))
    _ = float(np.asarray(jax.device_get(m["loss"])))
    # drop the warmup window's vote stats: the recorded summary should
    # describe the TIMED steps only
    vote_health_summary = trainer.telemetry_summary(reset=True)

    # ring-only run journal (train/journal.py — no file sink) around the
    # timed window, attributed offline-style by the same analyzer the
    # runbook's journal stage uses (cli/run_analyze.attribute), so every
    # BENCH row says where its wall clock went: dispatch = host enqueue +
    # device backpressure across the timed calls, device = the final
    # drain. Host timestamps only — the timed loop is untouched beyond
    # two monotonic reads per dispatch.
    from distributed_lion_tpu.cli import run_analyze as _run_analyze
    from distributed_lion_tpu.train.journal import Journal as _Journal

    _jr = _Journal(None, ring=4096)
    _jr.event("train_start", step=0)
    t0 = time.perf_counter()
    for _i in range(timed_calls):
        with _jr.span("dispatch", step=_i * steps_per_call,
                      steps=steps_per_call):
            trainer.params, trainer.state, trainer.vote_health, m = (
                trainer._train_chunk(trainer.params, trainer.state,
                                     trainer.vote_health,
                                     trainer._frozen_arg(),
                                     batches, base_key))
    with _jr.span("device_wait", step=timed_calls * steps_per_call):
        final_loss = float(np.asarray(jax.device_get(m["loss"])))
    dt = time.perf_counter() - t0
    _jr.event("train_end", step=timed_calls * steps_per_call)
    journal_attribution = _run_analyze.attribute(_jr.records())
    vote_health_summary = trainer.telemetry_summary()

    steps = steps_per_call * timed_calls
    tokens_per_sec = tokens_per_step * steps / dt
    per_chip = tokens_per_sec / n_dev

    # Model FLOPs per token: 6N (fwd+bwd matmuls) + attention 12*L*d*T.
    flops_per_token = (
        6.0 * n_params
        + 12.0 * model_cfg.n_layer * model_cfg.d_model * cfg.block_size
    )
    peak = _peak_flops_per_chip(device_kind) if backend == "tpu" else None
    mfu = (per_chip * flops_per_token / peak) if peak else None

    on_tpu = backend == "tpu"
    mfu_str = f"MFU {mfu:.1%}, " if mfu is not None else ""
    print(
        json.dumps(
            {
                "metric": f"{mfu_str}tokens/sec/chip, GPT-2 124M vote-Lion "
                f"train step (microbatch {batch_per_dev}x{cfg.block_size}, "
                f"accum {accum}"
                + (f", vocab_chunks {vocab_chunks}" if vocab_chunks else "")
                + (f", mom_dtype {mom_dtype}" if mom_dtype else "")
                + (f", attn {attn_spec}" if attn_spec != "xla" else "")
                + (f", vocab_pad {vocab_pad}" if vocab_pad else "")
                + (f", vote_buckets {vote_buckets}"
                   if vote_buckets > 1 else "")
                + (f", remat {remat_s}" if remat_s != "noremat" else "")
                + (", f32 params" if dtype_s != "bf16" else "")
                + f", {n_dev} {device_kind} device(s), backend={backend})",
                "value": round(per_chip, 1),
                "unit": "tokens/s/chip",
                "ms_per_step": round(dt / steps * 1e3, 1),
                "loss": round(final_loss, 3),
                # the resolved knobs, persisted with the headline artifact
                # so future bare runs adopt the promoted flagship config.
                # promoted = blessed by the runbook's bench_best stage
                # (BENCH_PROMOTE=1) or itself adopted from a promoted
                # record — one-off env-tweaked runs stay unpromoted and
                # are never adopted as defaults
                "config": {
                    "attn": attn_spec, "vocab_chunks": vocab_chunks,
                    "mom_dtype": mom_dtype, "batch_per_dev": batch_per_dev,
                    "accum": accum, "vocab_pad": vocab_pad,
                    "remat": remat_s, "dtype": dtype_s, "block": block,
                    "vote_buckets": vote_buckets,
                    "telemetry": int(bench_telemetry),
                },
                "vote_buckets": vote_buckets,
                "attn_resolved": attn_resolved,
                # step-wall attribution of the timed window (run journal,
                # train/journal.py + cli/run_analyze): named buckets as
                # fractions of measured wall, so a sweep/bench row explains
                # its own ms_per_step — and run_analyze --baseline diffs a
                # later run against this row to NAME the regressing bucket
                "journal_attribution": journal_attribution,
                # election dynamics of the timed steps (train/telemetry):
                # margin histogram (fractions per voted coordinate),
                # elected-sign flip rate, worker disagreement — the
                # signals that say whether the 1-bit vote is healthy at
                # this config, now tracked per BENCH round
                "vote_health": vote_health_summary,
                # measured step-time fraction recovered by bucketing the
                # vote wire, from the committed overlap-ablation rows
                # (buckets ∈ {1,4,16}, scripts/SWEEP_r*_raw/overlap.jsonl);
                # null until a TPU run captures the ablation
                "comm_overlap_frac": (overlap_from_ablation() or {}).get(
                    "comm_overlap_frac") if on_tpu else None,
                "promoted": (os.environ.get("BENCH_PROMOTE") == "1"
                             or (bool(rec_cfg) and not env_changed)),
                # vs_baseline is defined against the derived A100 anchor and
                # only meaningful on TPU hardware; null (not 0.0) for a
                # `--inner` row a sweep took elsewhere.
                "vs_baseline": (
                    round(per_chip / BASELINE_TOKENS_PER_SEC_PER_DEVICE, 3)
                    if on_tpu
                    else None
                ),
                "mfu": round(mfu, 4) if mfu is not None else None,
                "flops_per_token": round(flops_per_token),
                "n_params": n_params,
                "backend": backend,
                "device_kind": device_kind,
                # comm budget (BASELINE.md §2: ≤0.5 bit/param): what the
                # flagship wire ships per step at the canonical W=4 world,
                # and the opt-in config that meets the budget outright
                "wire_bits_per_param": _wire_bits(n_params, accum),
            }
        ),
        flush=True,
    )


def _wire_bits(n_params: int, accum: int) -> dict:
    """Comm accounting extras for the bench record: the flagship wire's
    bits/param/step at the reference's canonical W=4 world, plus the
    budget-meeting opt-in (packed_a2a + vote_every 4, tested in
    tests/test_vote_every.py and run at scale by scripts/loss_parity.py
    --mode lazy)."""
    from distributed_lion_tpu.ops.codec import wire_bytes_per_param

    flagship = wire_bytes_per_param(n_params, 4, "sign_psum",
                                    accum_steps=accum)
    budget = wire_bytes_per_param(n_params, 4, "packed_a2a", vote_every=4,
                                  accum_steps=accum)
    return {
        "flagship(sign_psum,W=4)": round(flagship["bits_per_param"], 3),
        "budget_config(packed_a2a,vote_every=4,W=4)": round(
            budget["bits_per_param"], 3),
    }


def _extract_json_line(text: str) -> dict | None:
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "metric" in obj:
                return obj
    return None


# The measurement child holds the TPU (libtpu single-client lock). If an
# outer `timeout`/driver SIGTERMs the orchestrating parent mid-attempt, an
# orphaned child would keep the chip locked and hang every later user —
# children run in their own process group, torn down on signal/exit. This
# machinery is shared: scripts/bench_sweep.py imports run_child /
# install_child_teardown so the TPU-lock-release semantics can't drift
# between the two harnesses.
_child: subprocess.Popen | None = None


def _kill_child() -> None:
    if _child is not None and _child.poll() is None:
        try:
            os.killpg(_child.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def install_child_teardown() -> None:
    """Tear the current measurement child's process group down on SIGTERM
    and at interpreter exit. Call once from the orchestrating __main__."""
    signal.signal(signal.SIGTERM, lambda s, f: (_kill_child(),
                                                sys.exit(128 + s)))
    atexit.register(_kill_child)


def run_child(cmd: list, env: dict, budget: float,
              cwd: str) -> tuple[int, str, str]:
    """Run ``cmd`` in its own process group under a hard timeout; returns
    (rc, stdout, stderr). On timeout the whole group is SIGKILLed and
    TimeoutExpired re-raised — the child can never outlive the budget."""
    global _child
    _child = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=cwd, start_new_session=True,
    )
    try:
        out, err = _child.communicate(timeout=budget)
        rc = _child.returncode
    except subprocess.TimeoutExpired:
        _kill_child()
        _child.wait()
        _child = None
        raise
    _child = None
    return rc, out, err


def _run_attempt(env: dict, budget: float) -> tuple[int, str, str]:
    here = os.path.abspath(__file__)
    return run_child([sys.executable, here, "--inner"], env, budget,
                     os.path.dirname(here))


REFUSED_NO_TPU = 2  # run_inner's exit code under BENCH_REQUIRE_TPU=1


def main() -> int:
    """Orchestrator: run the measurement in a child process under a hard
    timeout, retry once on failure, print the result line and return 0 —
    or print the errors and return non-zero. Never imports jax itself
    (backend init can hang, and a parent that touched JAX would hold the
    chip its child needs)."""
    install_child_teardown()
    # a healthy TPU run needs ~2-4 min (compile + 50 fused steps); 900s is
    # ample headroom. If the backend hung once it rarely recovers seconds
    # later — the retry gets a short budget, not a second full one.
    timeout_s = float(os.environ.get("BENCH_TIMEOUT_S", "900"))
    errors: list[str] = []
    for budget in (timeout_s, min(timeout_s, 300.0)):
        env = dict(os.environ)
        env["BENCH_REQUIRE_TPU"] = "1"
        try:
            rc, stdout, stderr = _run_attempt(env, budget)
        except subprocess.TimeoutExpired:
            errors.append(f"timeout after {budget:.0f}s")
            continue
        result = _extract_json_line(stdout)
        if rc == 0 and result is not None:
            _record_tpu_measurement(result)
            print(json.dumps(result), flush=True)
            return 0
        tail = (stderr or stdout or "").strip().splitlines()[-8:]
        errors.append(f"rc={rc}: " + " | ".join(tail))
        if rc == REFUSED_NO_TPU:
            break  # no TPU here: a retry cannot find one
    print("bench.py: no TPU measurement — " + " || ".join(errors)[-2000:],
          file=sys.stderr, flush=True)
    return 1


if __name__ == "__main__":
    if "--inner" in sys.argv:
        run_inner()
    else:
        sys.exit(main())
