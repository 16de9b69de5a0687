"""Serving entry point: continuous batching over a paged KV cache.

Completes the train → export → SERVE cycle (ROADMAP item 4): loads the
same checkpoints ``run_generate`` does (model.npz, training output dirs,
HF save_pretrained dirs — family auto-detected), builds the serving
engine (serve/engine.py), and drains a request file:

    python -m distributed_lion_tpu.cli.run_serve \
        --model_path ./out --model_family gpt2 --model_name tiny \
        --requests requests.jsonl --out responses.jsonl \
        --quant nf4 --max_seqs 32 --block_size 16

With no --requests, --prompt strings (repeatable) become the workload —
a smoke mode mirroring run_generate (scripts/workload_gen.py emits
seeded open-loop request files in the same schema). ``--journal_dir``
records ``serve/*`` spans (train/journal) for ``cli/run_analyze
--serve``. ``--serve_metrics`` arms the request-lifecycle metrics plane
(serve/metrics.py: TTFT/per-token sketches, gauges, drain-cadence
journal events); ``--slo_ttft_ms``/``--slo_tok_ms``/``--slo_p99`` add
the SLO monitor with burn-rate ``slo_breach`` accounting. Both are
pinned inert — token streams are bit-identical with or without them.

``--serve_tp N`` shards the decode path (weights per the Megatron specs,
page pools over kv heads) across the first N local devices — how the
NF4 Llama-2-7B artifact serves on a v5e slice (ISSUE 13); ``--serve_ep N``
shards a MoE checkpoint's expert banks over the expert axis (composes
with --serve_tp, ISSUE 15); ``--prefix_cache`` shares prompt-prefix KV
pages across requests with copy-on-write semantics. All are pinned
output-identical to the plain engine.

``--replicas N`` serves through the elastic fleet
(serve/replica_plane.py, ISSUE 14): N engines over the one loaded
checkpoint, live replica crash/drain/slow/rejoin (``--inject_serve``
schedules the fault matrix), in-flight requests migrating
token-identically from their recovery records, per-request ``deadline_s``
honored with honest ``timeout``/``failed`` statuses.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional


@dataclasses.dataclass
class ServeArguments:
    requests: Optional[str] = None   # request JSONL (serve/api schema);
    # unset → --prompt strings (GenerateArguments) become the workload.
    # Sampling (--temperature/--top_k/--top_p/--seed) and --max_new_tokens
    # ride GenerateArguments — one knob surface across generate and serve
    out: Optional[str] = None        # response JSONL (default stdout)
    max_seqs: int = 8
    block_size: int = 16
    max_blocks_per_seq: int = 8
    num_blocks: int = 0              # 0 = auto (max_seqs * max_blocks_per_seq)
    prefill_cap_tokens: int = 512
    prefill_top_bucket: int = 0      # the largest prefill bucket, where the
    # longest prompt lies between two powers of two (ServeConfig's)
    quant: str = "none"              # none | nf4 | int8 (ops/quant)
    quant_block: Optional[int] = None  # quant block override; shrink so
    # every --serve_tp-sharded last dim splits (ops/quant.validate_quant_tp
    # names the offending leaf when it can't)
    serve_tp: int = 0                # tensor-parallel serving degree
    # (ISSUE 13): 0 = single-device (the pre-TP engine, bit for bit);
    # N >= 1 shards weights per the Megatron param specs and the page
    # pools over kv heads across the first N local devices, one
    # shard_map'd dispatch per tick. tp=1 is pinned bit-identical to the
    # single-device engine; heads/kv-heads/d_ff must divide N.
    serve_ep: int = 0                # expert-parallel serving degree
    # (ISSUE 15): 0 = no expert axis; N >= 1 needs a MoE checkpoint
    # (moe_experts % N == 0) and shards the expert FFN banks over the
    # expert axis of a (data=1, expert=N, tensor=max(tp,1)) mesh — two
    # all_to_all hops per MoE block per tick, page pools untouched.
    # Composes with --serve_tp (N x tp devices). ep=1 is pinned
    # bit-identical to the unsharded engine; ep>1 token-identical.
    serve_ep_batch: bool = False     # batch-shard the decode/prefill batch
    # over the expert axis (ISSUE 16): slots and page pools split into
    # --serve_ep groups (max_seqs and num_blocks must divide ep), per-chip
    # FLOPs scale with ep, tokens cross chips only inside the two MoE
    # all_to_all hops. Needs --serve_ep >= 1. ep=1 is pinned bit-identical
    # to the replicated engine; ep>1 token-identical. Composes with
    # --serve_tp, --prefix_cache (caches go group-local) and
    # --speculate ngram:<k>.
    serve_ep_overlap: bool = False   # split each decode tick into two
    # software-pipelined microbatches so one half's expert all_to_all is
    # in flight while the other half runs attention. Needs
    # --serve_ep_batch and an even per-group slot count >= 2. Pinned
    # bit-identical to the unoverlapped tick (attention is row-local and
    # no-drop routing is an exact per-token function).
    prefix_cache: bool = False       # share prompt-prefix KV pages across
    # requests (copy-on-write block tables, serve/kv_cache.PrefixCache):
    # N requests carrying the same system prompt hold ONE physical copy
    # of its pages. Outputs pinned identical to the unshared engine —
    # MoE checkpoints included (no-drop per-token inference routing means
    # shared pages cannot change any expert assignment).
    serve_retrace_guard: str = "warn"  # off | warn | error — the serve
    # twin of the trainer's --retrace_guard, at tick granularity: every
    # dispatch's operand signature (shapes + dtypes) is checked against
    # the compile budget (ONE decode/verify/cow program, one prefill per
    # power-of-two page bucket) BEFORE tracing. 'warn' counts
    # stats['serve_retraces'] and warns; 'error' raises before the extra
    # lowering compiles; both are bit-identical to 'off' on the token
    # streams (analysis/serve_check pins the budget statically).
    speculate: str = ""              # '<drafter>:<k>' — speculative decode
    # (serve/speculate.py): 'ngram:4' self-drafts from each request's own
    # history (zero extra device memory); 'draft:2' proposes with a small
    # draft model (--draft_model_path/--draft_model_name, same family and
    # vocab as the target). Outputs are pinned identical to non-speculative
    # serving; the knob only changes tokens per dispatch.
    draft_model_path: Optional[str] = None   # draft checkpoint for
    # --speculate draft:<k> (same loaders as --model_path)
    draft_model_name: Optional[str] = None   # draft architecture (default:
    # the target's model_name — self-drafting smoke mode)
    listen: str = ""                 # live socket mode (serve/net.py):
    # '<port>' or '<host>:<port>' ('0' = ephemeral, address printed as a
    # JSON line on stdout). Newline-delimited JSON requests in (the SAME
    # strict serve/api schema as --requests), per-token streaming frames
    # out at host tick boundaries, honest backpressure reject frames
    # when the admission queue or page pool is tight. Mutually exclusive
    # with --requests — one transport per run.
    listen_wall_s: float = 0.0       # stop the socket server after this
    # many wall seconds (0 = run until interrupted); the bounded mode
    # the soak bench and the runbook stage use
    replica_procs: bool = False      # process-isolated fleet
    # (serve/fleet_proc.py): each replica is its own
    # ``serve.replica_worker`` subprocess speaking the length-prefixed
    # pipe protocol — replica failure becomes a real OS event (the
    # replica_kill fault SIGKILLs the child mid-decode; migration stays
    # token-identical from the fleet's shadow). The parent loads the
    # tokenizer and validates the checkpoint WITHOUT touching JAX (the
    # children own the device); each child loads its own
    # copy — real isolation costs real memory. Implies the fleet path
    # even at --replicas 1.
    heartbeat_timeout_s: float = 60.0  # per-tick reply deadline for a
    # process replica; a miss journals replica_heartbeat_missed and the
    # tick stays outstanding (a late reply is consumed next round)
    heartbeat_max_misses: int = 3    # consecutive misses before the
    # replica is declared dead (replica_declared_dead), SIGKILLed, and
    # its requests migrate from the recovery shadow
    fleet_state_dir: Optional[str] = None  # fleet-restart persistence
    # (serve/fleet_state.py): recovery shadow + prefix chains persist
    # here (atomic tmp+rename, sha256 manifest) on the
    # --fleet_persist_every cadence and at drain. Implies the fleet path.
    fleet_persist_every: int = 0     # persistence cadence in fleet ticks
    # (0 = only at drain/exit)
    resume_fleet: bool = False       # restore the newest valid persisted
    # state from --fleet_state_dir before serving: in-flight requests
    # re-submit (re-prefill from committed — token-identical by
    # construction) and persisted shared-prefix chains re-prefill once
    # as priming requests so the page pool warm-starts
    replicas: int = 1                # elastic serving fleet width
    # (serve/replica_plane, ISSUE 14): N independent engines (weights
    # shared, page pools per-replica) behind one admission queue with
    # prefix_group-affine routing; replicas leave/drain/rejoin live and
    # in-flight requests migrate token-identically from their recovery
    # records. 1 (default) = the single engine, no fleet layer at all.
    inject_serve: str = ""           # serve-side fault schedule
    # (resilience.parse_serve_specs, comma-separated):
    # replica_crash:<r>:<tick> | replica_drain:<r>[:<tick>] |
    # slow_tick:<r>:<ms> | replica_rejoin:<r>:<tick> — consumed by the
    # fleet at tick boundaries. Needs --replicas >= 2 to mean anything
    # (a 1-replica fleet with a crash has nowhere to migrate).
    serve_metrics: bool = False      # arm the request-lifecycle metrics
    # plane (serve/metrics.ServeMetrics): TTFT/per-token latency
    # sketches, live gauges, drain-cadence serve_metrics/serve_stats
    # journal events. Pinned inert — token streams are bit-identical
    # with the plane on or off. Implied by any --slo_* flag.
    slo_ttft_ms: Optional[float] = None   # SLO: time-to-first-token
    # bound (wall ms). Setting it arms the metrics plane + SLO monitor;
    # violations count per request, rolling-window burn rate journals
    # edge-triggered slo_breach events (serve/metrics.SLOMonitor).
    slo_tok_ms: Optional[float] = None    # SLO: mean per-token decode
    # latency bound (wall ms per generated token)
    slo_p99: float = 0.99            # SLO quantile target: the error
    # budget is 1 - slo_p99 (the violation fraction the SLO tolerates);
    # burn rate = window violation fraction / budget
    journal_dir: Optional[str] = None


def build_engine_factory(gen_args, serve_args: "ServeArguments"):
    """(tokenizer, factory) from the run_generate model surface + serve
    knobs: checkpoints load ONCE, ``factory()`` builds a fresh
    :class:`ServingEngine` over the shared weights (its own page pool and
    block tables each call — what a rejoining fleet replica needs).
    Shared by :func:`build_engine`, the ``--replicas`` fleet path, and
    the bench."""
    from distributed_lion_tpu.cli.run_generate import build
    from distributed_lion_tpu.serve.engine import (
        ServeConfig,
        ServeModel,
        ServingEngine,
    )

    def as_serve_model(p, c):
        return {"gpt2": ServeModel.for_gpt2, "llama": ServeModel.for_llama,
                "joyai": ServeModel.for_joyai,
                "dots3": ServeModel.for_dots3,
                "laguna": ServeModel.for_laguna,
                "ling": ServeModel.for_ling,
                "minicpm_sala": ServeModel.for_minicpm_sala,
                "xing": ServeModel.for_xing,
                }[gen_args.model_family](p, c)

    if serve_args.speculate:
        # pure-config refusals BEFORE any checkpoint loads — a spec error
        # must cost milliseconds, not minutes of target-weight loading
        from distributed_lion_tpu.serve.speculate import parse_speculate

        name, _ = parse_speculate(serve_args.speculate)
        if name == "draft" and not serve_args.draft_model_path:
            raise ValueError(
                "--speculate draft:<k> needs --draft_model_path (a TRAINED "
                "draft checkpoint; without it the loader would random-init "
                "the drafter, whose proposals all reject — every tick then "
                "pays the draft dispatch plus the k+1-wide verify for "
                "nothing, silently slower than plain decode)")
    tok, cfg, params, _, _ = build(gen_args)
    model = as_serve_model(params, cfg)
    draft_model = None
    if serve_args.speculate.startswith("draft"):
        # the draft checkpoint rides the same loader surface as the target
        # (npz / training output dir / HF dir); family must match — the
        # vocab check in serve/speculate.build_speculator is the loud gate
        d_args = dataclasses.replace(
            gen_args, model_path=serve_args.draft_model_path,
            model_name=serve_args.draft_model_name or gen_args.model_name)
        _, dcfg, dparams, _, _ = build(d_args)
        draft_model = as_serve_model(dparams, dcfg)
    scfg = ServeConfig(
        max_seqs=serve_args.max_seqs, block_size=serve_args.block_size,
        max_blocks_per_seq=serve_args.max_blocks_per_seq,
        num_blocks=serve_args.num_blocks,
        prefill_cap_tokens=serve_args.prefill_cap_tokens,
        prefill_top_bucket=serve_args.prefill_top_bucket,
        max_new_tokens=gen_args.max_new_tokens,
        temperature=gen_args.temperature, top_k=gen_args.top_k,
        top_p=gen_args.top_p, quant=serve_args.quant,
        quant_block=serve_args.quant_block,
        tp=serve_args.serve_tp, ep=serve_args.serve_ep,
        ep_batch=serve_args.serve_ep_batch,
        ep_overlap=serve_args.serve_ep_overlap,
        prefix_cache=serve_args.prefix_cache,
        retrace_guard=serve_args.serve_retrace_guard,
        speculate=serve_args.speculate,
        metrics=(serve_args.serve_metrics
                 or serve_args.slo_ttft_ms is not None
                 or serve_args.slo_tok_ms is not None),
        eos_id=getattr(tok, "eos_id", None))
    slo_armed = (serve_args.slo_ttft_ms is not None
                 or serve_args.slo_tok_ms is not None)

    def factory() -> ServingEngine:
        engine = ServingEngine(model, scfg, draft_model=draft_model)
        if slo_armed:
            # each engine (each fleet replica) gets its own monitor —
            # burn rate is a per-replica signal; the fleet aggregate
            # rides metrics_snapshot()'s sketch merge
            from distributed_lion_tpu.serve.metrics import (
                ServeMetrics, SLOMonitor)

            engine.metrics = ServeMetrics(
                engine.times,
                slo=SLOMonitor(ttft_ms=serve_args.slo_ttft_ms,
                               tok_ms=serve_args.slo_tok_ms,
                               p99=serve_args.slo_p99))
        return engine

    return tok, factory


def build_engine(gen_args, serve_args: "ServeArguments"):
    """(tokenizer, engine) — the single-engine surface this CLI, the
    decode bench, and tests share."""
    tok, factory = build_engine_factory(gen_args, serve_args)
    return tok, factory()


def build_fleet(gen_args, serve_args: "ServeArguments"):
    """(tokenizer, fleet) for ``--replicas N`` — N engines over ONE
    loaded checkpoint behind the replica plane's admission queue
    (serve/replica_plane.ServingFleet). With ``--replica_procs`` each
    replica is instead a ``serve.replica_worker`` subprocess built from
    the SAME argument surface (the child re-runs this CLI's build), so
    replica death is a real OS event."""
    from distributed_lion_tpu.serve.replica_plane import ServingFleet

    if serve_args.replica_procs:
        from distributed_lion_tpu.cli.run_generate import check_checkpoint
        from distributed_lion_tpu.serve.fleet_proc import (
            process_replica_factory)

        # the parent stays OFF JAX: a chip belongs to one process, and a
        # parent that opened it would lock every child out. It loads the
        # tokenizer and validates the checkpoint without a device (to
        # fail fast on a bad path BEFORE spawning N children that would
        # each fail slower); children load their own weights — process
        # isolation is not free, it is the point
        tok = check_checkpoint(gen_args)
        builder = {"kind": "cli",
                   "gen": dataclasses.asdict(gen_args),
                   "serve": dataclasses.asdict(serve_args)}
        factory = process_replica_factory(
            builder,
            heartbeat_timeout_s=serve_args.heartbeat_timeout_s)
    else:
        tok, factory = build_engine_factory(gen_args, serve_args)
    return tok, ServingFleet(
        factory, replicas=serve_args.replicas,
        heartbeat_max_misses=serve_args.heartbeat_max_misses,
        state_dir=serve_args.fleet_state_dir,
        persist_every=serve_args.fleet_persist_every)


def main(argv=None):
    from distributed_lion_tpu.parallel.mesh import force_cpu_platform

    force_cpu_platform()

    from distributed_lion_tpu.cli.run_generate import GenerateArguments
    from distributed_lion_tpu.serve import api
    from distributed_lion_tpu.serve.engine import Request
    from distributed_lion_tpu.train import journal as journal_mod
    from distributed_lion_tpu.utils.argparsing import parse_dataclasses

    gen_args, args = parse_dataclasses((GenerateArguments, ServeArguments),
                                       argv)
    if not args.replica_procs:
        # (a --replica_procs parent must not initialize a backend; each
        # replica_worker child enables the cache for itself)
        from distributed_lion_tpu.utils.compile_cache import (
            enable_compilation_cache,
        )

        enable_compilation_cache()
    if args.replicas < 1:
        raise ValueError(f"--replicas must be >= 1, got {args.replicas}")
    if args.inject_serve and args.replicas < 2:
        raise ValueError(
            "--inject_serve needs --replicas >= 2: a one-replica fleet "
            "has no survivor to migrate in-flight requests to")
    if args.listen and args.requests:
        raise ValueError(
            "--listen and --requests are two transports over the same "
            "core — pick one per run (workload_gen --stream drives the "
            "socket side with the same request files)")
    if args.resume_fleet and not args.fleet_state_dir:
        raise ValueError(
            "--resume_fleet restores from --fleet_state_dir; set it to "
            "the directory the previous run persisted into")
    # the fleet path is implied by any fleet-plane knob: a 1-replica
    # process fleet or a persistence-armed single replica still needs
    # the fleet's shadow/heartbeat/persist machinery
    use_fleet = (args.replicas > 1 or args.replica_procs
                 or args.fleet_state_dir is not None)
    jrnl = None
    if args.journal_dir:
        jrnl = journal_mod.Journal(args.journal_dir)
        journal_mod.install(jrnl)
    try:
        if args.inject_serve:
            from distributed_lion_tpu.train import resilience

            resilience.inject_fault(
                "serve", resilience.parse_serve_specs(args.inject_serve))
        if use_fleet:
            tok, engine = build_fleet(gen_args, args)
        else:
            tok, engine = build_engine(gen_args, args)
        if args.resume_fleet:
            import time as _time

            from distributed_lion_tpu.serve import fleet_state

            state = fleet_state.load_fleet_state(args.fleet_state_dir,
                                                 now=_time.monotonic())
            info = fleet_state.resume_into(engine, state)
            print(json.dumps({"resumed": info["restored"],
                              "chains_primed": info["chains_primed"],
                              "from_tick": info["tick"]},
                             allow_nan=False), flush=True)
        if args.listen:
            from distributed_lion_tpu.serve.net import ServeServer

            spec = args.listen
            host, _, port = spec.rpartition(":")
            server = ServeServer(engine, host=host or "127.0.0.1",
                                 port=int(port), tokenizer=tok)
            print(json.dumps({"listening": list(server.addr)},
                             allow_nan=False), flush=True)
            try:
                server.run(max_wall_s=args.listen_wall_s or None)
            except KeyboardInterrupt:
                pass
            finally:
                server.close()
            records = []
        elif args.requests:
            records = api.serve_request_file(engine, args.requests,
                                             args.out or "/dev/stdout", tok)
        else:
            prompts = list(gen_args.prompt) or ["Hello"]  # smoke default
            reqs = [Request(req_id=f"req{i}",
                            tokens=tok.encode(p, add_bos=False) or [0],
                            max_new_tokens=gen_args.max_new_tokens,
                            seed=gen_args.seed)
                    for i, p in enumerate(prompts)]
            records = api.handle_requests(engine, reqs, tokenizer=tok)
            for p, rec in zip(prompts, records):
                print(json.dumps({"prompt": p, **rec}, allow_nan=False),
                      flush=True)
        journal_mod.active().event("serve_done", **{
            k: (float(v) if isinstance(v, float) else int(v))
            for k, v in engine.stats.items()})
        # final metrics drain: the end-of-run snapshot lands in the
        # journal even when the run was shorter than one drain cadence
        if use_fleet:
            snap = engine.metrics_snapshot()
            if snap is not None:
                journal_mod.active().event("serve_fleet_metrics", **{
                    f"{sec}_{k}": v for sec, d in snap.items()
                    if isinstance(d, dict) for k, v in d.items()})
            if args.fleet_state_dir:
                # the at-drain save: whatever is still in flight (a
                # --listen server interrupted mid-decode included)
                # survives into the next --resume_fleet
                engine.save_state()
            engine.close()
        elif engine.metrics is not None:
            engine.metrics.drain(engine.stats["ticks"])
        return records
    finally:
        if args.inject_serve:
            from distributed_lion_tpu.train import resilience

            resilience.inject_fault("serve", [])  # disarm leftovers — a
            # half-consumed schedule must not leak into the next engine
            # built in this process (tests drive main() in-process)
        if jrnl is not None:
            journal_mod.uninstall(jrnl)
            jrnl.close()


if __name__ == "__main__":
    main()
