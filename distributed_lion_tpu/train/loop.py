"""The training loop: jit-compiled train step with the vote inside.

This is the native replacement for the stack the reference borrows —
HF ``Trainer`` + ``accelerate``/DDP + the ``AsyncTrainer`` subclass
(/root/reference/async_trainer.py:8-34). The reference's one idea at this
layer is ``model.no_sync()``: gradients are NEVER all-reduced; the only
cross-worker traffic is the optimizer's 1-bit vote (async_trainer.py:15,
SURVEY §2.6). In JAX that contract is structural: the train step below is a
single ``shard_map`` over the data axis in which per-device gradients feed
per-device momentum, and the sole collective is the optimizer's majority
vote. With ``async_grad=False`` it degrades to classic data parallelism
(``lax.pmean`` of grads — DDP's all-reduce) for the reference's plain-Trainer
path.

Grad accumulation is a ``lax.scan`` over microbatches (the reference's
``gradient_accumulation_steps=8``, README.md:31), fwd/bwd via
``jax.value_and_grad``, loss/metrics pmean'd for logging only.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_lion_tpu.models.gpt2 import (
    GPT2Config,
    count_params,
    gpt2_apply,
    gpt2_hidden,
    gpt2_init,
)
from distributed_lion_tpu.models.loss import clm_loss_and_metrics
from distributed_lion_tpu.ops import xent as xent_ops
from distributed_lion_tpu.ops.codec import (
    bucket_bounds,
    vote_chunk_elems,
    wire_bytes_per_param,
    wire_codec,
)
from distributed_lion_tpu.optim import (
    distributed_lion,
    expand_worker_state,
    heal_worker_momentum,
    init_global_state,
    remap_worker_momentum,
    squeeze_worker_state,
)
from distributed_lion_tpu.optim.lion import FunctionalOptimizer, LionState
from distributed_lion_tpu.optim.optax_adapter import OptaxState, adamw
from distributed_lion_tpu.optim.zero import (
    Zero1State,
    adamw_zero1,
    expand_zero_state,
    squeeze_zero_state,
)
from distributed_lion_tpu.parallel.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    TENSOR_AXIS,
    data_axis_size,
)
from distributed_lion_tpu.train import (
    control_plane,
    journal,
    resilience,
    telemetry,
    vote_guard,
)
from distributed_lion_tpu.train.journal import emit
from distributed_lion_tpu.train.checkpoint import Checkpointer
from distributed_lion_tpu.train.metrics import MetricsLogger
from distributed_lion_tpu.train.profiling import (
    StepProfiler,
    comm_report,
    peak_hbm_gb,
    peak_hbm_per_device,
)
from distributed_lion_tpu.utils import compile_cache
from distributed_lion_tpu.train.schedule import (
    constant_schedule,
    cosine_schedule_with_warmup,
    linear_schedule_with_warmup,
)


@dataclasses.dataclass
class TrainConfig:
    """The reference's CLI surface (run_clm.py AsyncTrainingArguments +
    TrainingArguments subset actually exercised, README.md:18-38) as one
    dataclass. ``lion`` and ``async_grad`` are the two reference-specific
    flags (run_clm.py:73-86)."""

    lion: bool = True
    async_grad: bool = True
    zero1: bool = False  # AdamW path only: shard Adam m/v over the data axis
    # (ZeRO-1, optim/zero.py) — 2N/W floats of optimizer state per device
    # instead of 2N, updated chunks re-assembled with one all_gather.
    wire: str = "auto"  # vote wire format. 'auto' picks per mesh shape
    # (resolve_auto_comm): W=1 → sign_psum (no traffic); single-host W>1 →
    # packed_a2a (minimum received bytes AND fastest measured wire,
    # scripts/SWEEP_wires.md); multi-host → hier:<local_devices> (only the
    # 1-bit verdict chunks cross the DCN boundary). All wires elect
    # IDENTICAL signs (tests/test_collectives.py wire equivalence) — the
    # choice changes bytes moved, never the trajectory.
    vote_every: int = 0  # K > 1: lazy sign refresh — each step votes a 1/K
    # coordinate slice (wire volume ÷ K; packed_a2a at K=4 ≈ 0.375 bit/
    # param/step at W=4, the BASELINE.md ≤0.5-bit comm budget), stale
    # elected signs applied elsewhere (optim.distributed_lion). 0 = auto:
    # currently ALWAYS 1, the reference's strict every-step vote — lazy
    # voting is opt-in (--vote_every 4) until a full-scale parity:lazy leg
    # PASSES the pre-registered criterion (check_evidence parity:lazy;
    # runs/parity holds no lazy curve yet, so auto must not default to a
    # trajectory claim the evidence doesn't back — VERDICT weak #1).
    # Mechanism correctness at test scale IS pinned (tests/test_vote_every
    # convergence + replica consistency); the open question is trajectory
    # parity at 100M+ scale, which only the parity leg can answer.
    vote_buckets: int = 0  # B > 1: bucketed, overlapped vote wire — the
    # ballot splits into B contiguous wire-aligned chunks (codec.
    # bucket_bounds) voted as B independent collectives, software-pipelined
    # against the fused apply (bucket k rides the interconnect while bucket
    # k−1 updates in VMEM, optim.distributed_lion). Params/momentum and the
    # summed wire bytes are bit-identical to B=1 (tests/test_vote_buckets.py)
    # — bucketing changes WHEN bytes move, never what is elected. 0 = auto
    # (resolve_auto_comm): 4 when W > 1 and the per-step ballot slice is
    # ≥ AUTO_BUCKET_MIN_COORDS, else 1 (the monolithic vote).
    dcn_pipeline_depth: int = 0  # d > 0 (hier wire only): cross-step DCN
    # overlap — each step computes/combines its level-1 ICI tally
    # immediately and LAUNCHES the level-2 cross-group (DCN) ring for its
    # own ballot, but consumes the ring only d steps later (the in-flight
    # packed tallies ride LionState.dcn_ring, one slot per step), so the
    # slow fabric's round trip hides behind d steps of compute instead of
    # bounding every step. Elections applied at step t are the complete
    # two-level election of step t−d's ballots — uniformly stale, replicas
    # bit-identical; the first d steps apply no update (cold start, the
    # vote_every rule). Composes with vote_buckets/vote_every/the vote
    # guard; bytes per step are depth-invariant (comm_drift_bytes stays 0).
    # 0 = today's synchronous hier wire. Checkpoints carry the ring, so
    # crash-resume stays bit-identical at any depth; a depth toggle on
    # resume errors loudly. See ARCHITECTURE 'DCN overlap'.
    ep_dcn_pipeline: Optional[int] = None  # MoE balance-feedback staleness
    # when the EXPERT axis spans DCN (ISSUE 16). None (default) = today's
    # per-shard local aux, bit for bit. 0 = synchronous global balance:
    # each MoE block psums its routing tallies over the expert axis inside
    # the forward (a blocking DCN collective per MoE block — exact, and at
    # ep=1 bit-identical to unflagged). d > 0 = pipelined: the aux consumes
    # the globally-psummed tallies from d steps ago (LionState.moe_ring,
    # one slot per in-flight step, per-data-worker divergent — no DATA-axis
    # collective is added, so async_grad's only-collective-is-the-vote
    # contract holds), and this step's fresh tallies launch into the ring
    # after the backward — the slow fabric's round trip rides behind d
    # steps of compute. Token activations stay synchronous (the two MoE
    # all_to_all hops are exact); ONLY the non-differentiable load
    # estimate in the aux loss goes stale. First d steps fall back to the
    # local aux (cold start). Lion-only at d > 0 (the ring rides
    # LionState); needs MoE blocks; checkpoints carry the ring and a depth
    # toggle on resume errors loudly, like --dcn_pipeline_depth.
    kernel: str = "auto"  # auto | pallas | xla (ops/pallas_lion fused path)
    remat_policy: str = dataclasses.field(
        default="", metadata={"cli": False})  # '' = honor the model
    # config's own remat/remat_policy; 'auto' | 'full' | 'dots' overrides
    # it at Trainer build. Programmatic only (no CLI flag — run_clm's
    # model-level --remat_policy drives the model config directly; this
    # field is the override tests hand the Trainer builders).
    # 'auto' (the default of run_clm's, run_sft's and run_dpo's model
    # configs) is resolved once, by apply_remat_policy below, from the
    # shapes and the device's memory: the first of none (plain blocks) |
    # dots | full whose predicted peak fits (train/remat.py); an explicit
    # 'full' | 'dots' or remat=False always wins, and MoE blocks, a pipeline
    # or sequence axis and a backend without bytes_limit (the CPU) stay
    # 'full'. (models/gpt2._remat_policy: 'dots' keeps matmul outputs and
    # recomputes elementwise — the cheaper backward the sweep's dots leg
    # measures). A perf knob under the vote, not a semantics knob: at f32
    # compute the Lion trajectory AND the lazy elected-sign cache are
    # bit-identical across policies; at bf16 compute jax.checkpoint's
    # fusion barriers shift a few ULPs so elections may flip only on
    # near-tie coordinates (tests/test_train.py pins both halves, the
    # PR 6 remat-equivalence precedent).
    mom_dtype: str = ""  # Lion momentum dtype override ('bfloat16' halves
    # the per-worker optimizer state and its read/write traffic — at 7B
    # full-param scale that is ~14 GB of HBM; '' = the param dtype, the
    # reference's exp_avg = zeros_like(p) behavior)
    vocab_chunks: int = 0  # > 0: chunked-vocab cross entropy (ops/xent) —
    # the [B,T,V] f32 logits (the largest activation at GPT-2 124M: ~823MB
    # per microbatch) are never materialized; streaming logsumexp over V/N
    # chunks, chunk logits rematerialized in backward. Same math, less HBM.
    tp_vocab: bool = False  # Llama path, tensor_parallel > 1: shard the
    # lm_head's vocab columns over the tensor axis and compute the CLM loss
    # with Megatron vocab-parallel CE (ops/xent.tp_vocab_xent) — V/tp logit
    # columns per rank instead of every rank computing the full [B,T,V].
    tensor_parallel: int = 1  # tensor mesh axis size (consumed by the CLIs
                              # when building the mesh; net-new vs reference)
    seq_parallel: int = 1  # sequence/context mesh axis size: batches are
                           # sharded over tokens, attention rings over the
                           # 'seq' axis (parallel.ring_attention); net-new
    pipeline_parallel: int = 1  # pipeline stages over the 'pipe' mesh axis
    # (blocks stacked [pp, L/pp], GPipe microbatch schedule — models/gpt2_pipe
    # + parallel/pipeline); net-new
    pipeline_microbatches: int = 0  # GPipe microbatches per accum step
    # (0 → pipeline_parallel; bubble fraction = (S-1)/(M+S-1))
    expert_parallel: int = 1  # expert mesh axis size: MoE FFN banks sharded
    # over 'expert', tokens ride dispatch/return all_to_all; the axis doubles
    # as extra data parallelism for dense layers (parallel/expert); net-new
    max_grad_norm: Optional[float] = None  # set → stochastic binarization
    grad_clip_norm: Optional[float] = None  # global-norm gradient clipping
    # (HF Trainer, which the reference sits on, clips at 1.0 by default —
    # run_clm inherits it via TrainingArguments). When max_grad_norm is set
    # and this is not, grads are clipped at max_grad_norm: the stochastic
    # quantizer's unbiasedness needs |β₁m+(1−β₁)g| ≤ r (SURVEY §2.4).
    learning_rate: float = 1e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.99
    lr_scheduler_type: str = "cosine"  # cosine | linear | constant
    warmup_steps: int = 2000
    max_steps: int = 100_000
    per_device_train_batch_size: int = 20
    gradient_accumulation_steps: int = 8
    per_device_eval_batch_size: int = 20
    steps_per_call: int = 1  # optimizer steps fused into one device dispatch
    # (lax.scan over staged batches). >1 amortizes host→device dispatch
    # latency — the hot loop stays on device; logging granularity coarsens
    # to the chunk. Net-new vs the reference (HF Trainer dispatches per step).
    block_size: int = 1024
    seed: int = 42
    logging_steps: int = 50
    logging_first_step: bool = False  # also log after this loop's first
    # dispatch (HF TrainingArguments.logging_first_step): the interval's
    # first device drain, and the few scalar programs the first log
    # compiles (2.5 s on a TPU), then fall on step 1 and not inside the run
    eval_steps: int = 1000
    eval_iters: int = 20
    save_steps: int = 1000
    save_total_limit: Optional[int] = 2
    output_dir: Optional[str] = None
    resume_from_checkpoint: bool = True
    async_ckpt: bool = True  # async double-buffered checkpointing
    # (train/checkpoint.py): save() kicks off the Orbax async write and
    # returns after the device→host copy; the blocking drain moves to the
    # NEXT save boundary (and close()/anomaly paths), so serialization and
    # disk I/O hide behind the following train steps. The ckpt_stall_s
    # metric logs the loop's actual checkpoint tax; tests pin it below the
    # synchronous baseline. False = the old blocking save.
    ckpt_integrity: bool = True  # per-file sha256 manifest + COMMITTED
    # marker written last (atomic commit): resume autodetect verifies
    # newest-first and falls back to the newest GOOD checkpoint, so a torn
    # leaf file or corrupted manifest costs one save interval, not the run.
    on_preempt: str = "save_exit"  # save_exit | off. save_exit installs a
    # SIGTERM guard (train/resilience.PreemptionGuard) checked once per
    # dispatch: on trip the loop drains the in-flight async save, writes an
    # emergency checkpoint tagged 'preempt', and returns cleanly so the
    # process exits 0 and the watcher restarts into a normal resume.
    elastic_resume: bool = False  # allow resuming a checkpoint written at a
    # DIFFERENT data-parallel world size: the stacked [W, ...] Lion momenta
    # are remapped to [W', ...] by optim.distributed_lion.
    # remap_worker_momentum (shard-group re-averaging W'<W, replication
    # W'>W, mean broadcast otherwise — the cross-worker momentum mean, the
    # vote distribution's center, is preserved exactly in every case).
    # Off by default: a world-size mismatch is loud, not silently remapped.
    report_to_wandb: bool = False
    profile_dir: Optional[str] = None  # capture a jax.profiler trace window
    profile_start_step: int = 10
    profile_num_steps: int = 3
    telemetry: bool = False  # vote-health telemetry (train/telemetry.py):
    # an on-device VoteHealth accumulator rides the jitted step (margin
    # histogram, elected-sign flip rate, worker disagreement, stochastic
    # flip fraction, valid-update sparsity) and drains to the metrics log
    # at logging_steps cadence — zero added host transfers per step, and
    # elections stay bit-identical to telemetry-off (tests/test_telemetry).
    # Also arms measured wire counters (trace-time byte ledger at the vote-
    # collective call sites, cross-checked against the analytic comm_report
    # as comm_drift_bytes) and the multi-host step heartbeat. Lion-only:
    # the AdamW path has no election to observe.
    nan_sentinel: bool = False  # per-step isfinite watch over loss + grad
    # norm (checked one dispatch behind so the device pipeline stays full);
    # on trip, writes a crash bundle (step, config, per-leaf finite masks
    # naming the poisoned leaves, recent metrics window) to
    # output_dir/crash/step_<n>/ and raises FloatingPointError.
    retrace_guard: str = "warn"  # off | warn | error. The runtime leg of the
    # static-analysis subsystem (analysis/): hash the jitted train step's
    # abstract input signature (leaf shapes/dtypes) at each dispatch and
    # surface any UNSEEN signature after the first — a recompilation: new
    # batch shape/dtype, a drifted state structure; signatures jax already
    # compiled and cached re-dispatch freely — as a loud retraces metric +
    # warning, or a RuntimeError under 'error', instead of a silent 2x
    # step-time cliff. Checked BEFORE dispatch (host-side hash over leaf
    # avals, no device traffic), so 'error' refuses the recompile before
    # paying for it. Purely observational under 'warn': elections and
    # trajectories are bit-identical to 'off'.
    trace_on_anomaly: bool = False  # with nan_sentinel: instead of raising
    # immediately, arm a StepProfiler window at the tripping step (trace
    # written into the crash bundle), run profile_num_steps more steps to
    # capture the poisoned dataflow, then raise.
    vote_guard: str = "off"  # off | observe | enforce. The vote guard
    # (train/vote_guard.py + optim.distributed_lion guard mode): the jitted
    # step emits per-worker ballot-health signals (nonfinite local
    # grad/momentum before sign-encoding, frozen ballots via popcount(XOR
    # prev), outlier disagreement vs the healthy peers) and a host-side
    # quarantine machine — checked one dispatch behind, like the NaN
    # sentinel — strikes, quarantines and (after --guard_cooldown steps)
    # readmits workers. 'enforce' additionally masks quarantined ballots
    # out of the election (the majority threshold shrinks to the healthy
    # quorum), zeroes nonfinite gradients out of the momentum update, and
    # re-averages a readmitted worker's momentum from the healthy mean;
    # with an all-healthy mask it is bit-identical to 'off'
    # (tests/test_vote_guard.py). 'observe' reports what enforce would do
    # without touching the election. Lion-only: AdamW has no election.
    min_quorum: int = 0  # vote_guard enforce: refuse to continue (loud
    # RuntimeError) when the healthy quorum drops below this. 0 = auto:
    # a strict majority (W//2 + 1) — a vote with a sick majority is noise.
    guard_strikes: int = 3  # consecutive-ish bad observed steps before a
    # worker is quarantined (a clean dispatch resets its strikes, so
    # transient faults — one bad batch — never escalate)
    guard_cooldown: int = 50  # optimizer steps a quarantined worker sits
    # out before a readmission probe (healed momentum, mask cleared; a
    # still-sick worker re-strikes within guard_strikes steps)
    journal: bool = False  # run journal (train/journal.py): a host-side
    # span/event recorder around every loop region — trainer dispatch,
    # device wait (the log-cadence drain), data wait, logging drain,
    # checkpoint serialize/drain, preemption/quarantine transitions —
    # written as rank-stamped strict-JSON JSONL under --journal_dir and
    # analyzed offline by cli/run_analyze (step-time attribution, top
    # stall sources, cross-host skew, BENCH baseline diff). Host wall
    # clocks only: zero added device syncs per step, and elections are
    # pinned bit-identical journal-on vs journal-off
    # (tests/test_journal.py).
    journal_dir: str = ""  # journal sink directory ('' = output_dir/journal;
    # with neither set the journal runs ring-only: crash bundles still get
    # their journal_tail.jsonl, nothing else is written)
    inject_poison: str = ""  # fault injection for the guard's evidence and
    # tests: '<kind>:<worker>[:<start_step>]' with kind in
    # nan_grads | frozen_ballot | flipped_ballot
    # (train/resilience.parse_poison; baked into the step at trace time
    # through the resilience fault registry). Works with --vote_guard off
    # too — that is the degradation baseline the guard is measured against.
    control_plane: bool = False  # unified membership control plane
    # (train/control_plane.py): one host-side lifecycle per worker
    # (healthy → suspect → quarantined → departed → rejoining → healthy)
    # consuming the signals the NaN sentinel, the PreemptionGuard and the
    # vote guard each held a slice of, whose single output is the alive
    # mask the masked elections already accept. Live leave/join without a
    # restart: a departing worker (injected worker_drop, repeated guard
    # strikes, preemption) becomes a mask transition at the next dispatch
    # boundary — training continues at W−1 — and a rejoining worker is
    # re-absorbed in-run (momentum re-averaged from the healthy mean via
    # heal_worker_momentum, ballot history reset, probation window). Auto-
    # arms --vote_guard enforce when the guard is off (all-healthy enforce
    # is pinned bit-identical to off); refuses 'observe' (it never touches
    # the mask). Lion-only. In-run rejoin at --dcn_pipeline_depth > 0 is
    # refused loudly, mirroring the elastic-resume rule.
    rejoin_probe_steps: int = 0  # control plane: optimizer steps a
    # rejoined worker stays on probation ('rejoining'). A rejoiner that
    # re-strikes inside the window departs again (never the quarantine/
    # readmit cycle a dead host would loop forever); a clean window
    # promotes it to healthy. 0 = auto: --guard_cooldown.
    inject_membership: str = ""  # membership fault injection for the
    # control plane's evidence and tests: comma-separated
    # 'worker_drop:<w>[:<start_step>]' / 'worker_rejoin:<w>:<step>' specs
    # (train/resilience.parse_membership), consumed HOST-side at dispatch
    # boundaries through the resilience fault registry — a drop/rejoin is
    # a mask transition plus state surgery, never a trace change.
    # Requires --control_plane (the plane is the only consumer).

    def schedule(self) -> Callable:
        if self.lr_scheduler_type == "cosine":
            return cosine_schedule_with_warmup(self.learning_rate, self.warmup_steps, self.max_steps)
        if self.lr_scheduler_type == "linear":
            return linear_schedule_with_warmup(self.learning_rate, self.warmup_steps, self.max_steps)
        return constant_schedule(self.learning_rate)


def device_bytes_limit() -> Optional[int]:
    """One device's memory as its backend reports it (``memory_stats()``'s
    ``bytes_limit``: 16.91 GB on a v5e), or None (the CPU reports none)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def apply_remat_policy(cfg: "TrainConfig", model_cfg, mesh, params, *,
                       frozen=None, rows_per_sample: int = 1):
    """What the per-block checkpoint saves, decided once at Trainer build:
    ``(model_cfg, decision)``. ``TrainConfig.remat_policy`` ``''`` honors
    the model config's own setting; ``'auto' | 'full' | 'dots'`` replaces it
    (models/gpt2._remat_policy). An explicit ``full`` / ``dots`` and
    ``remat=False`` are obeyed as they stand (``decision`` None). ``auto``
    is resolved here and never reaches a model function: the first of
    none | dots | full whose predicted peak fits the device
    (train/remat.py), from the model config, the microbatch one device sees
    (``per_device_train_batch_size x rows_per_sample`` rows of
    ``block_size``), the bytes of ``params``, optimizer state and
    ``frozen`` trees on one device, the world size and the device's
    ``bytes_limit``. What the count does not model stays ``full``: MoE
    blocks, a pipeline or sequence axis, no ``bytes_limit`` (the CPU).
    Loud on an unknown policy and on an override with remat disabled — a
    policy that silently never applies is the kind of no-op a sweep leg
    would then measure."""
    from distributed_lion_tpu.train import remat

    if cfg.remat_policy not in ("", "auto", "full", "dots"):
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r} (auto | full | dots)")
    if cfg.remat_policy and not model_cfg.remat:
        raise ValueError(
            "TrainConfig.remat_policy set but the model config has "
            "remat=False — the policy would silently never apply; drop "
            "the override or enable remat")
    policy = cfg.remat_policy or model_cfg.remat_policy
    if not model_cfg.remat or policy != "auto":
        if cfg.remat_policy:
            model_cfg = dataclasses.replace(model_cfg, remat_policy=policy)
        return model_cfg, None
    decision = remat.resolve_for(
        cfg, model_cfg, mesh, params, frozen=frozen,
        rows_per_sample=rows_per_sample, bytes_limit=device_bytes_limit())
    return remat.with_rung(model_cfg, decision.rung), decision


@dataclasses.dataclass(frozen=True)
class LossSpec:
    """What a built loss tells the :class:`Trainer` beside the function:
    which of ``TrainConfig``'s head flags it honours (``parse_dataclasses``
    exposes every field on every CLI, so a loss that would ignore a set
    flag is refused, not run), how its batch is sharded (None: rows over
    ``data``), and the shape of one MoE balance tally where it feeds the
    ``--ep_dcn_pipeline`` ring (None: no ring), and which of its metrics
    are counts (``sum_metrics``: summed over a step's microbatches and
    workers where every other metric is averaged). The default is a bare
    callable's: it honours nothing."""

    vocab_chunks: bool = False
    tp_vocab: bool = False
    batch_spec: Optional[P] = None
    moe_tally_shape: Optional[tuple] = None
    sum_metrics: tuple = ()


def _clm_head_loss(cfg: "TrainConfig", mesh, model_cfg, hidden_fn: Callable,
                   head_fn: Callable, layout: str, *, head_cols: int,
                   valid_v: int = 0):
    """``(loss_fn, LossSpec)`` of a dense family's CLM training, from the
    two things a family hands over — ``hidden_fn(params, batch,
    dropout_key, tp_axis=, seq_axis=, vocab_axis=) -> [B, T, d]`` and where
    its head lies (``head_fn(params)``, ``layout``, its ``head_cols``
    vocabulary entries of which ``valid_v`` are real) — and from
    ``cfg.vocab_chunks``, ``cfg.tp_vocab`` and the mesh's axes. Which head
    runs is ``ops/xent.head_path``'s to say, asked here once so that what
    it refuses is refused at construction."""
    shape = dict(mesh.shape)
    tp, sp = shape.get(TENSOR_AXIS, 1), shape.get(SEQ_AXIS, 1)
    if cfg.tp_vocab and tp <= 1:
        raise ValueError("--tp_vocab needs --tensor_parallel > 1 (it shards "
                         "the head's vocabulary over the tensor axis)")
    tp_axis = TENSOR_AXIS if tp > 1 else None
    seq_axis = SEQ_AXIS if sp > 1 else None
    vocab_axis = TENSOR_AXIS if cfg.tp_vocab else None
    xent_ops.head_path(layout, model_cfg.d_model, model_cfg.compute_dtype,
                       chunks=cfg.vocab_chunks, vocab_axis=vocab_axis,
                       seq_axis=seq_axis)
    if cfg.tp_vocab and head_cols % tp:
        raise ValueError(
            f"--tp_vocab: the head's {head_cols} vocabulary entries are not "
            f"divisible by tensor axis {tp} (models/gpt2's "
            "vocab_pad_multiple pads a ragged vocab so it shards evenly)")
    if sp > 1:
        validate_seq_block(cfg, model_cfg, sp)

    def loss_fn(params, batch, dropout_key):
        hidden = hidden_fn(params, batch, dropout_key, tp_axis=tp_axis,
                           seq_axis=seq_axis, vocab_axis=vocab_axis)
        return xent_ops.clm_head_loss(
            hidden, head_fn(params), batch, layout=layout, valid_v=valid_v,
            chunks=cfg.vocab_chunks, vocab_axis=vocab_axis, seq_axis=seq_axis)

    return loss_fn, LossSpec(
        vocab_chunks=True, tp_vocab=True,
        # rows over data, tokens over seq
        batch_spec=P(DATA_AXIS, SEQ_AXIS) if sp > 1 else None)


def gpt2_clm_loss(cfg: "TrainConfig", mesh, model_cfg: GPT2Config):
    """:func:`_clm_head_loss` for dense GPT-2 (what ``Trainer.for_gpt2``
    steps at both training cells): ``gpt2_hidden`` and the tied embedding
    as it lies, ``[padded_vocab, d]``."""
    def hidden_fn(params, batch, dropout_key, **axes):
        # under a vocab axis params["wte"] is this rank's [V/tp, d]
        # vocab-row slice: VocabParallelEmbedding on the way in, its
        # transpose as the tied vocab-parallel head on the way out
        return gpt2_hidden(params, batch, model_cfg,
                           dropout_key=dropout_key, **axes)[0]

    return _clm_head_loss(
        cfg, mesh, model_cfg, hidden_fn, lambda params: params["wte"], "vd",
        head_cols=model_cfg.padded_vocab, valid_v=model_cfg.vocab_size)


def _resolve_comm(cfg: "TrainConfig", mesh, params):
    """``cfg`` with its ``auto`` comm fields resolved for a model of these
    ``params`` on ``mesh``, their count, and the vote wire's byte account
    (the factories' banner)."""
    n = count_params(params)
    shape = dict(mesh.shape)
    cfg = resolve_auto_comm(
        cfg, mesh, n,
        # tp/pp/expert all shard params; only dp(/sp) keeps them
        # replicated, the precondition for the lazy elected-sign cache
        params_replicated=all(
            shape.get(ax, 1) == 1
            for ax in (TENSOR_AXIS, PIPE_AXIS, EXPERT_AXIS)))
    return cfg, n, wire_bytes_per_param(
        n, data_axis_size(mesh), cfg.wire, vote_every=cfg.vote_every,
        accum_steps=cfg.gradient_accumulation_steps,
        vote_buckets=cfg.vote_buckets or 1)


def _pipelined_trainer(cfg: "TrainConfig", mesh, model_cfg, family: str,
                       remat_decision, make_loss, stage_params, stage_specs):
    """The :class:`Trainer` of either family under ``--pipeline_parallel``
    (models/gpt2_pipe, models/llama_pipe: ``make_loss`` and the stage
    layout are the family's). The pipelined loss streams
    ``cfg.vocab_chunks`` at the last stage and carries a replicated head:
    its ``LossSpec`` says so, and ``--tp_vocab`` is refused on it."""
    from distributed_lion_tpu.parallel.pipeline import validate_pipeline
    from distributed_lion_tpu.parallel.tensor_parallel import validate_tp

    shape = dict(mesh.shape)
    tp, sp, pp = (shape.get(ax, 1) for ax in (TENSOR_AXIS, SEQ_AXIS,
                                              PIPE_AXIS))
    if tp > 1:
        validate_tp(model_cfg, tp, family)
    if sp > 1:
        validate_seq_block(cfg, model_cfg, sp)
    n_micro = cfg.pipeline_microbatches or pp
    validate_pipeline(model_cfg, cfg, pp, n_micro)
    loss_fn = make_loss(model_cfg, n_micro,
                        tp_axis=TENSOR_AXIS if tp > 1 else None,
                        vocab_chunks=cfg.vocab_chunks,
                        seq_axis=SEQ_AXIS if sp > 1 else None)
    return Trainer(
        cfg, mesh, None, stage_params, loss_fn=loss_fn,
        loss_spec=LossSpec(
            vocab_chunks=True,
            batch_spec=P(DATA_AXIS, SEQ_AXIS) if sp > 1 else None),
        param_specs=stage_specs(tensor=tp > 1),
        remat_decision=remat_decision)


def validate_seq_block(cfg: "TrainConfig", model_cfg, sp: int) -> None:
    """Config-time guards shared by every sequence-parallel path (plain,
    pipelined, both families): tokens must split evenly over the seq axis,
    and the TOTAL sequence must fit the positional scheme — without the
    n_ctx check the wpe dynamic_slice clamps at the table end (later shards
    silently duplicate positional rows) and rope offsets extrapolate."""
    if cfg.block_size % sp:
        raise ValueError(f"block_size {cfg.block_size} not divisible by "
                         f"seq axis {sp}")
    if cfg.block_size > model_cfg.n_ctx:
        raise ValueError(
            f"seq-parallel block_size {cfg.block_size} (total tokens across "
            f"the {sp}-way seq axis) exceeds n_ctx {model_cfg.n_ctx}: the "
            f"positional scheme (wpe table / rope range) is too small"
        )


# the ballot size at which lazy vote refresh WOULD be worth auto-enabling
# (below it the full vote is cheap anyway). Auto currently resolves
# vote_every to 1 regardless — lazy is opt-in until a full-scale
# parity:lazy leg passes the pre-registered criterion (see
# resolve_auto_comm) — but the threshold is kept: it still gates the
# advisory trainer message, and it is the line the auto default re-arms at
# once the evidence lands.
AUTO_LAZY_MIN_PARAMS = 10_000_000

# bucketed-vote auto threshold: pipeline the wire only when the PER-STEP
# ballot slice (after vote_every's ÷K) is at least this many coordinates —
# 4 buckets of ≥4M coords each still amortize per-collective launch latency,
# while smaller ballots' wires are too cheap for overlap to matter and
# tiny/debug models keep the simplest single-collective graph
AUTO_BUCKET_MIN_COORDS = 16_000_000


def _spec_sharded_axes(param_specs) -> set:
    """Mesh axes any param PartitionSpec shards over (empty = replicated
    params). ``None`` specs (the default-replicated case) give the empty
    set."""
    if param_specs is None:
        return set()
    return {
        ax for s in jax.tree.leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P))
        for dim in s for ax in
        (dim if isinstance(dim, (tuple, list)) else (dim,))
        if ax is not None
    }


def resolve_auto_comm(cfg: TrainConfig, mesh, n_params: int,
                      params_replicated: bool) -> TrainConfig:
    """Resolve the comm sentinels (``wire='auto'``, ``vote_every=0``,
    ``vote_buckets=0``) into concrete values for this mesh + model — the one
    place the multi-chip default wire recipe lives (README 'wire recipe';
    BASELINE.md ≤0.5-bit budget vs the reference's always-sign_psum analog,
    /root/reference/distributed_lion.py:80-81). Idempotent: a cfg with all
    three fields explicit is returned unchanged, so factories can resolve
    early (for their byte-accounting print) and Trainer.__init__ resolves
    only what reaches it unresolved."""
    if (cfg.wire != "auto" and cfg.vote_every != 0
            and cfg.vote_buckets != 0):
        return cfg
    world = data_axis_size(mesh)
    wire, ve, vb = cfg.wire, cfg.vote_every, cfg.vote_buckets
    if wire == "auto":
        # hier's subgroups must be DATA-axis workers sharing a host. data is
        # the slowest-varying mesh axis (make_mesh), so consecutive data
        # indices sit `inner` devices apart (inner = product of the model
        # axes); a host of L local devices therefore holds L // inner whole
        # data rows. Grouping by local_device_count alone would straddle
        # hosts whenever inner > 1 and run the full ballot reduce-scatter
        # over DCN — the opposite of the wire's point.
        inner = 1
        for ax, sz in mesh.shape.items():
            if ax != DATA_AXIS:
                inner *= sz
        local = jax.local_device_count()
        hier_g = local // inner if inner and local % inner == 0 else 0
        if not cfg.lion or world == 1:
            wire = "sign_psum"  # W=1 short-circuits: no bytes move
        elif jax.process_count() > 1 and hier_g > 1 and world % hier_g == 0:
            # multi-host: only the 1-bit verdict chunks should cross DCN —
            # hier's DCN leg is 0.125 bits/param at g=4 vs packed_a2a's
            # cross-host phases (scripts/SWEEP_wires.md)
            wire = f"hier:{hier_g}"
        else:
            # minimum received bytes AND fastest measured wire at W=8
            # (scripts/SWEEP_wires.md: 1.75 bits/param, 1276 ms vs
            # sign_psum's 8.0 bits, 1885 ms); also the multi-host fallback
            # when the host layout gives no intact ICI data subgroup
            wire = "packed_a2a"
    if ve == 0:
        # The lazy default is OFF until evidenced: auto resolves to the
        # reference's strict every-step vote. Round 4 shipped ve=4 here for
        # big replicated ballots with a message claiming the trajectory
        # "overlays every-step voting at this scale (runs/parity)" — but
        # runs/parity holds NO lazy leg, so the default was asserting
        # evidence that does not exist (VERDICT weak #1). Until a
        # full-scale lazy leg PASSES the pre-registered criterion
        # (scripts/check_evidence.py parity:lazy + PARITY_EPS_NATS), lazy
        # voting stays an explicit opt-in; the candidate threshold it
        # would re-arm at is kept as AUTO_LAZY_MIN_PARAMS.
        ve = 1
        if (cfg.lion and world > 1 and params_replicated
                and n_params >= AUTO_LAZY_MIN_PARAMS):
            bits = wire_bytes_per_param(
                n_params, world, wire, vote_every=4)["bits_per_param"]
            emit(
                f"[trainer] auto comm: wire={wire} vote_every=1 (strict "
                f"every-step voting). Lazy --vote_every 4 would cut the "
                f"{n_params/1e6:.0f}M-coordinate ballot to {bits:.2f} "
                "bits/param/step, but it stays opt-in until the "
                "full-scale parity:lazy leg passes the pre-registered "
                "criterion (scripts/loss_parity.py; check_evidence "
                "parity:lazy)."
            )
    if vb == 0:
        # bucketed overlap: worth it only when there is a wire (W > 1) AND
        # the per-step ballot slice is big enough that each of 4 buckets
        # still amortizes collective launch latency. Elections are
        # bit-identical at any B, so auto never changes the trajectory —
        # only whether the wire can hide behind the fused apply.
        n_voted = (n_params if ve <= 1
                   else min(n_params, vote_chunk_elems(n_params, ve)))
        vb = (4 if (cfg.lion and world > 1
                    and n_voted >= AUTO_BUCKET_MIN_COORDS) else 1)
    return dataclasses.replace(cfg, wire=wire, vote_every=ve,
                               vote_buckets=vb)


def _lion_layout_line(cfg: TrainConfig, mesh, params, param_specs) -> str:
    """The ``[setup] lion:`` line: how the Lion kernels take this tree, from
    the shapes a worker holds (``ops/pallas_lion.leaf_layout``: leaves read
    where they lie, leaves pooled through the flat path, kernel calls a
    step). A fact of the shapes: the step runs those calls where
    ``optim.distributed_lion`` takes its Pallas path (a TPU, every
    coordinate voted every step); the XLA path votes in flat order and runs
    none."""
    from distributed_lion_tpu.ops import pallas_lion

    leaves, treedef = jax.tree.flatten(params)
    specs = ([P()] * len(leaves) if param_specs is None
             else treedef.flatten_up_to(param_specs))
    shapes = [NamedSharding(mesh, s).shard_shape(p.shape)
              for p, s in zip(leaves, specs)]
    bounds = bucket_bounds(sum(math.prod(s) for s in shapes),
                           cfg.vote_buckets or 1, data_axis_size(mesh),
                           cfg.wire)
    return pallas_lion.leaf_layout(shapes, bounds).line()


def make_optimizer(cfg: TrainConfig) -> FunctionalOptimizer:
    """The reference's optimizer wiring (run_clm.py:580-585): ``--lion`` →
    Lion(lr, wd) else AdamW(wd=0.1 hardcoded); both under a cosine-warmup
    schedule."""
    if cfg.zero1 and cfg.lion:
        raise ValueError(
            "--zero1 applies only to the AdamW path; with --lion the optimizer "
            "state is the per-worker vote momentum, which ZeRO-1 sharding "
            "would silently drop — drop one of the two flags"
        )
    if cfg.zero1 and cfg.async_grad:
        raise ValueError(
            "--zero1 requires synchronized gradients (async_grad=False): each "
            "worker updates the Adam-state chunk it owns, so all workers must "
            "see the same gradient for that chunk — with async_grad the "
            "all_gather would stitch together chunk-wise single-worker updates"
        )
    if cfg.telemetry and not cfg.lion:
        raise ValueError(
            "--telemetry instruments the majority-vote election; the AdamW "
            "path has no vote to observe — drop one of the two flags"
        )
    if cfg.vote_guard != "off" and not cfg.lion:
        raise ValueError(
            "--vote_guard protects the majority-vote election; the AdamW "
            "path has no vote to guard — drop one of the two flags"
        )
    if cfg.dcn_pipeline_depth > 0:
        from distributed_lion_tpu.ops.codec import parse_wire

        if not cfg.lion:
            raise ValueError(
                "--dcn_pipeline_depth pipelines the vote wire; the AdamW "
                "path has no vote collective — drop one of the two flags")
        if cfg.wire == "auto":
            # the Trainer resolves 'auto' before reaching here, so a
            # literal sentinel means a standalone caller skipped
            # resolve_auto_comm — and staleness must never ride an
            # implicit wire choice either way
            raise ValueError(
                f"--dcn_pipeline_depth {cfg.dcn_pipeline_depth} needs an "
                "explicitly named hier wire, but the wire is the "
                "unresolved 'auto' sentinel — pass --wire hier:<g>")
        if parse_wire(cfg.wire)[0] != "hier":
            raise ValueError(
                f"--dcn_pipeline_depth {cfg.dcn_pipeline_depth} pipelines "
                f"the hier wire's level-2 (DCN) leg, but the wire here is "
                f"{cfg.wire!r} — a wire without a DCN leg has nothing to "
                "overlap; pass --wire hier:<g>")
    if cfg.ep_dcn_pipeline is not None:
        if cfg.ep_dcn_pipeline < 0:
            raise ValueError(
                f"--ep_dcn_pipeline must be >= 0, got {cfg.ep_dcn_pipeline}")
        if cfg.ep_dcn_pipeline > 0 and not cfg.lion:
            raise ValueError(
                f"--ep_dcn_pipeline {cfg.ep_dcn_pipeline} stores the "
                "in-flight MoE balance tallies on LionState.moe_ring; the "
                "AdamW path has no per-worker optimizer state to carry "
                "them — use --lion, or --ep_dcn_pipeline 0 (the "
                "synchronous global balance needs no ring)")
    if cfg.lion:
        mom_dtype = jnp.dtype(cfg.mom_dtype) if cfg.mom_dtype else None
        return distributed_lion(
            cfg.schedule(),
            b1=cfg.beta1,
            b2=cfg.beta2,
            weight_decay=cfg.weight_decay,
            axis_name=DATA_AXIS,
            max_grad_norm=cfg.max_grad_norm,
            # standalone callers may pass an unresolved cfg (no mesh in this
            # signature): the sentinels degrade to the reference's strict
            # semantics; the Trainer always resolves via resolve_auto_comm
            # before reaching here
            wire="sign_psum" if cfg.wire == "auto" else cfg.wire,
            vote_every=cfg.vote_every or 1,
            vote_buckets=cfg.vote_buckets or 1,
            dcn_pipeline_depth=cfg.dcn_pipeline_depth,
            kernel=cfg.kernel,
            mom_dtype=mom_dtype,
            telemetry=cfg.telemetry,
            guard=cfg.vote_guard,
        )
    if cfg.async_grad:
        raise ValueError(
            "--async_grad without --lion would let replicas diverge (no grad "
            "sync and no vote); the reference silently permits this broken "
            "combination — we refuse it"
        )
    # default weight_decay=0.1 matches the reference's hardcoded AdamW value
    # (run_clm.py:583-585), but an explicit --weight_decay is honored here
    # rather than silently dropped as the reference does.
    if cfg.zero1:
        return adamw_zero1(cfg.schedule(), weight_decay=cfg.weight_decay,
                           axis_name=DATA_AXIS)
    return adamw(cfg.schedule(), weight_decay=cfg.weight_decay)


def _opt_state_specs(cfg: TrainConfig, exp_avg_specs):
    if cfg.lion:
        # stacked per-worker momentum: [world, ...] over 'data' (+ any
        # tensor-parallel dims the param itself carries); the elected-sign
        # cache (vote_every > 1) and the guard's health mask are replicated;
        # the guard's per-worker previous ballot and the DCN pipeline ring
        # (each member owns a different 1/g coordinate chunk) shard like
        # the momenta
        guard_on = cfg.vote_guard != "off"
        return LionState(count=P(), exp_avg=exp_avg_specs, rng=P(),
                         elected=P() if cfg.vote_every > 1 else None,
                         health=P() if guard_on else None,
                         prev_ballot=P(DATA_AXIS) if guard_on else None,
                         dcn_ring=(P(DATA_AXIS)
                                   if cfg.dcn_pipeline_depth > 0 else None),
                         moe_ring=(P(DATA_AXIS)
                                   if (cfg.ep_dcn_pipeline or 0) > 0
                                   else None))
    if cfg.zero1:
        # [world, chunk] m/v sharded over 'data': ZeRO-1 state partitioning
        return Zero1State(count=P(), m=P(DATA_AXIS), v=P(DATA_AXIS))
    return OptaxState(count=P(), inner=P(), rng=P())  # replicated


class Trainer:
    """Train/eval/checkpoint driver for the CLM workload.

    Model-agnostic: ``apply_fn(params, tokens, dropout_key) -> logits`` and an
    initial params pytree; GPT-2 helpers are provided by ``for_gpt2``.
    """

    def __init__(
        self,
        cfg: TrainConfig,
        mesh,
        apply_fn: Callable,
        params: Any,
        loss_fn: Optional[Callable] = None,
        loss_spec: Optional[LossSpec] = None,
        param_specs: Any = None,
        frozen_params: Any = None,
        frozen_specs: Any = None,
        remat_decision: Any = None,
    ):
        """``loss_fn(params, batch, dropout_key) -> (loss, metrics)`` may
        replace the default CLM loss; ``batch`` is then any pytree whose
        leaves carry a leading global-batch axis (e.g. DPO's
        chosen/rejected pairs); ``loss_spec`` is the :class:`LossSpec` its
        builder returned beside it (None: a bare callable's, which honours
        no flag). ``param_specs`` is an optional PartitionSpec
        pytree (parallel.tensor_parallel) for tensor-parallel params;
        default replicated.

        ``frozen_params`` is an optional NON-trained pytree (LoRA bases, DPO
        reference models) threaded through the train/eval shard_maps as a
        live sharded argument — required whenever the frozen tree must be
        sharded over a non-data mesh axis (a closure capture would be
        replicated). When set, ``loss_fn`` takes
        ``(params, frozen, batch, dropout_key)`` and ``frozen_specs`` gives
        its PartitionSpecs (default replicated). ``remat_decision`` is
        what :func:`apply_remat_policy` resolved ``auto`` to for the model
        inside ``loss_fn`` (None: nothing was resolved); said here, once the
        journal is up."""
        # the span gate's profiler, the collector's hook and the compile
        # ledger's listeners: all idempotent, needed before the first span,
        # the first long collection and the first compile
        journal.register_profiler(jax.profiler.TraceAnnotation)
        journal.watch_gc()
        compile_cache.listen()
        setup = journal.SetupLaps("trainer")
        n_params = count_params(params)
        cfg = resolve_auto_comm(
            cfg, mesh, n_params,
            params_replicated=not _spec_sharded_axes(param_specs),
        )
        cplane_auto_armed = False
        if cfg.control_plane:
            if not cfg.lion:
                raise ValueError(
                    "--control_plane drives the majority-vote election's "
                    "membership mask; the AdamW path has no election — "
                    "drop one of the two flags")
            if cfg.vote_guard == "observe":
                raise ValueError(
                    "--control_plane needs masked elections to act on its "
                    "membership decisions, but --vote_guard observe never "
                    "touches the mask — use 'enforce' (or leave the guard "
                    "off: the plane auto-arms enforce)")
            if cfg.vote_guard == "off":
                # all-healthy enforce is pinned bit-identical to off
                # (tests/test_vote_guard.py), so arming the mask machinery
                # never changes a healthy run's trajectory
                cfg = dataclasses.replace(cfg, vote_guard="enforce")
                cplane_auto_armed = True
        if cfg.inject_membership and not cfg.control_plane:
            raise ValueError(
                "--inject_membership schedules live worker leave/join, "
                "which only the control plane consumes — pass "
                "--control_plane (or drop the injection)")
        self.cfg = cfg
        self.mesh = mesh
        self.world = data_axis_size(mesh)
        # the run journal comes up FIRST so every construction/resume
        # message below already lands in the event stream; it is host-side
        # only — nothing it does can reach the traced step
        jdir = cfg.journal_dir or (os.path.join(cfg.output_dir, "journal")
                                   if cfg.output_dir else "")
        self.journal = (journal.Journal(jdir or None,
                                        rank=jax.process_index())
                        if cfg.journal else journal.NULL)
        if cfg.journal:
            journal.install(self.journal)
        if remat_decision is not None:
            emit(remat_decision.line())
            self.journal.event("remat_resolved", **remat_decision.fields())
        if cfg.lion:
            emit(f"[setup] vote: {cfg.wire} x{cfg.vote_buckets} buckets, "
                 f"{wire_codec(cfg.wire)}")
            emit(_lion_layout_line(cfg, mesh, params, param_specs))
        if cfg.zero1:
            shape = dict(mesh.shape)
            for ax in (TENSOR_AXIS, SEQ_AXIS):
                if shape.get(ax, 1) > 1:
                    raise ValueError(
                        f"--zero1 is incompatible with a '{ax}' mesh axis of "
                        f"size {shape[ax]}: inside shard_map each {ax} rank "
                        "ravels its own local param shard, so the m/v chunks "
                        "diverge across ranks while the out_specs assume "
                        f"{ax}-replication — one rank's moments would silently "
                        "win. Use pure data parallelism with ZeRO-1."
                    )
        if cfg.lion and cfg.learning_rate < 1e-3 and any(
            p.dtype == jnp.bfloat16 for p in jax.tree.leaves(params)
        ):
            # Lion applies a FIXED ±lr step; bf16's ULP at |p| ~ 0.1 is
            # ~8e-4, so at small lr the update rounds to a no-op on every
            # large-magnitude coordinate (silently frozen params). bf16
            # params are a throughput/memory opt-in for benching; real
            # training should keep f32 master params with bf16 COMPUTE
            # (the model configs' default split), like torch's f32 master
            # weights under autocast.
            emit(
                "[trainer] WARNING: bf16 param storage with Lion lr "
                f"{cfg.learning_rate:g} < 1e-3 — the fixed ±lr update is "
                "below bf16 ULP for |p| > ~lr*256, so those coordinates "
                "will NOT move. Use f32 param_dtype (bf16 compute_dtype "
                "keeps the matmul speed) unless this is a throughput bench."
            )
        spec = loss_spec or LossSpec()
        if cfg.vocab_chunks > 0 and not spec.vocab_chunks:
            # parse_dataclasses exposes every TrainConfig field on every
            # CLI: a loss whose builder did not say it honours the flag
            # (the MoE branch's, a bare callable, the default over
            # apply_fn) would silently ignore it
            raise NotImplementedError(
                "--vocab_chunks is not wired into this entry point's loss "
                "function (supported: run_clm's dp/tp/sp/pp paths, run_sft, "
                "run_dpo)"
            )
        if cfg.tp_vocab and not spec.tp_vocab:
            # the same trap: only the dense dp x tp losses of
            # for_gpt2/for_llama shard the head's vocabulary (the pipelined
            # and the MoE losses carry a replicated head)
            raise NotImplementedError(
                "--tp_vocab is wired for run_clm's dense dp x tp paths "
                "(gpt2 and llama families) only; this entry point's loss "
                "would silently ignore it"
            )
        self.batch_spec = (spec.batch_spec if spec.batch_spec is not None
                           else P(DATA_AXIS))
        # number of ways batch ROWS (dim 0) are sharded: data alone normally;
        # data x expert under expert parallelism (tokens ride both axes)
        dim0 = self.batch_spec[0] if len(self.batch_spec) else None
        dim0_axes = (tuple(dim0) if isinstance(dim0, (tuple, list))
                     else (dim0,) if dim0 else ())
        self.batch_shards = 1
        for a in dim0_axes:
            self.batch_shards *= dict(mesh.shape).get(a, 1)
        self.apply_fn = apply_fn
        self.opt = make_optimizer(cfg)
        if param_specs is None:
            param_specs = jax.tree.map(lambda _: P(), params)
        elif not cfg.lion:
            raise NotImplementedError("tensor-parallel param_specs require the Lion path")
        self.param_specs = param_specs
        if cfg.lion and cfg.vote_every > 1:
            sharded_axes = _spec_sharded_axes(param_specs)
            if sharded_axes:
                raise ValueError(
                    f"--vote_every > 1 is incompatible with params sharded "
                    f"over {sorted(sharded_axes)}: each rank's ballot covers "
                    "its own local param shards, so the elected-sign caches "
                    "differ across ranks while the P() spec declares them "
                    "replicated — one rank's cache would silently win and "
                    "stale signs would land on the wrong coordinates. Use "
                    "lazy vote refresh with replicated params (dp / dp x sp)."
                )
        if cfg.telemetry and _spec_sharded_axes(param_specs):
            raise ValueError(
                f"--telemetry is incompatible with params sharded over "
                f"{sorted(_spec_sharded_axes(param_specs))}: each rank's "
                "ballot covers its own local shards, so the packed election "
                "state the accumulator carries would differ across ranks "
                "while its P() spec declares it replicated. Use vote-health "
                "telemetry with replicated params (dp / dp x sp)."
            )
        vote_guard.parse_guard_mode(cfg.vote_guard)
        if cfg.vote_guard != "off" and _spec_sharded_axes(param_specs):
            raise ValueError(
                f"--vote_guard is incompatible with params sharded over "
                f"{sorted(_spec_sharded_axes(param_specs))}: the guard's "
                "per-worker ballot state covers each rank's LOCAL shards, "
                "so health decisions would mix different coordinate sets. "
                "Use the vote guard with replicated params (dp / dp x sp)."
            )
        self._guard = (vote_guard.make_guard(
            self.world, cfg.vote_guard, cfg.guard_strikes,
            cfg.guard_cooldown, cfg.min_quorum, journal=self.journal)
            if cfg.lion and cfg.vote_guard != "off" else None)
        self._guard_pending = None  # (step, obs-device-arrays, advanced)
        self._cplane = (control_plane.make_control_plane(
            self._guard, self.world, cfg.rejoin_probe_steps,
            cfg.dcn_pipeline_depth, journal=self.journal)
            if cfg.control_plane else None)
        if cplane_auto_armed:
            emit("[trainer] control plane: --vote_guard auto-armed to "
                 "'enforce' (the plane's membership mask rides the guard's "
                 "masked elections; all-healthy enforce is bit-identical "
                 "to off)")
        if cfg.inject_membership:
            sched = resilience.parse_membership_specs(cfg.inject_membership)
            bad = [(k, w) for k, w, _ in sched if w >= self.world]
            if bad:
                # fail at construction, not steps into the run
                raise ValueError(
                    f"--inject_membership names worker(s) "
                    f"{sorted(set(w for _, w in bad))} outside world "
                    f"{self.world}: {cfg.inject_membership!r}")
            if cfg.dcn_pipeline_depth > 0 and any(
                    k == "worker_rejoin" for k, _, _ in sched):
                # fail at construction, not steps into the run: the in-run
                # rejoin mirrors the elastic-resume depth rule (the DCN
                # ring's in-flight slots are functions of the membership)
                raise ValueError(
                    "--inject_membership schedules a worker_rejoin but "
                    f"--dcn_pipeline_depth {cfg.dcn_pipeline_depth} > 0: "
                    "the in-flight DCN tally ring cannot re-absorb a "
                    "worker mid-flight (the same reason --elastic_resume "
                    "refuses depth > 0). Run the rejoin at depth 0")
            resilience.inject_fault("membership", sched)
            emit(f"[trainer] FAULT INJECTION armed: membership "
                 f"{cfg.inject_membership!r}")
        if cfg.inject_poison:
            # route the spec through the resilience fault registry — the
            # same transport tests use directly; the step bakes it in at
            # trace time
            resilience.inject_fault(
                "ballot_poison", resilience.parse_poison(cfg.inject_poison))
            emit(f"[trainer] FAULT INJECTION armed: ballot poison "
                  f"{cfg.inject_poison!r}")

        setup.lap("setup/mesh")  # what the mesh decides: wire, buckets,
        # row block, the guard and the control plane
        self.params = jax.tree.map(
            lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, param_specs
        )
        self.frozen = None
        self.frozen_specs = None
        if frozen_params is not None:
            from distributed_lion_tpu.ops.quant import QuantizedTensor

            _is_qt = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
            if frozen_specs is None:
                frozen_specs = jax.tree.map(lambda _: P(), frozen_params,
                                            is_leaf=_is_qt)
            self.frozen_specs = frozen_specs

            def _put(p, s):
                # a QuantizedTensor node takes its dense leaf's spec: the
                # shaped layout keeps codes/absmax rank-aligned with the
                # dense weight, so the same P shards both children
                return jax.tree.map(
                    lambda c: jax.device_put(c, NamedSharding(mesh, s)), p)

            self.frozen = jax.tree.map(_put, frozen_params, frozen_specs,
                                       is_leaf=_is_qt)
        setup.lap("setup/init_params")
        rng = jax.random.key(cfg.seed)
        self._exp_avg_specs = jax.tree.map(
            lambda s: P(*((DATA_AXIS,) + tuple(s))), param_specs
        )
        if cfg.lion:
            state = init_global_state(
                self.opt, self.params, self.world,
                rng=rng if cfg.max_grad_norm is not None else None,
            )
            if (cfg.ep_dcn_pipeline or 0) > 0:
                # the MoE balance ring (--ep_dcn_pipeline d > 0): one
                # [n_moe_blocks, E+1] tally slot per in-flight step, stacked
                # per data worker like the momenta. Created HERE, not by
                # init_global_state — the tally shape is model config,
                # which the optimizer never sees; the MoE trainer's loss
                # says it in its LossSpec.
                if spec.moe_tally_shape is None:
                    raise ValueError(
                        f"--ep_dcn_pipeline {cfg.ep_dcn_pipeline} > 0 "
                        "needs the MoE trainer's loss (make_trainer with "
                        "--moe_experts), whose LossSpec gives the "
                        "balance-tally shape the ring is sized from; this "
                        "loss gives none")
                state = state._replace(moe_ring=jnp.zeros(
                    (self.world, cfg.ep_dcn_pipeline)
                    + tuple(spec.moe_tally_shape), jnp.float32))
            self.state = jax.device_put(
                state,
                LionState(
                    count=NamedSharding(mesh, P()),
                    exp_avg=jax.tree.map(
                        lambda s: NamedSharding(mesh, s), self._exp_avg_specs
                    ),
                    rng=None if state.rng is None else NamedSharding(mesh, P()),
                    elected=None if state.elected is None else NamedSharding(mesh, P()),
                    health=None if state.health is None
                    else NamedSharding(mesh, P()),
                    prev_ballot=None if state.prev_ballot is None
                    else NamedSharding(mesh, P(DATA_AXIS)),
                    dcn_ring=None if state.dcn_ring is None
                    else NamedSharding(mesh, P(DATA_AXIS)),
                    moe_ring=None if state.moe_ring is None
                    else NamedSharding(mesh, P(DATA_AXIS)),
                ),
            )
        elif cfg.zero1:
            state = self.opt.init(self.params, world=self.world)
            self.state = jax.device_put(
                state,
                Zero1State(
                    count=NamedSharding(mesh, P()),
                    m=NamedSharding(mesh, P(DATA_AXIS)),
                    v=NamedSharding(mesh, P(DATA_AXIS)),
                ),
            )
        else:
            self.state = jax.device_put(self.opt.init(self.params), NamedSharding(mesh, P()))

        # Vote-health telemetry state (train/telemetry.py): a small
        # replicated accumulator pytree threaded through the jitted step —
        # the ONLY signature change telemetry makes ({} when off keeps the
        # arity fixed, like the frozen arg). Drained + reset at log cadence.
        self._telemetry_on = bool(cfg.telemetry and cfg.lion)
        self._margin_exact = (self._telemetry_on
                              and telemetry.tally_wire(cfg.wire))
        if self._telemetry_on:
            n_tel = sum(int(np.prod(p.shape))
                        for p in jax.tree.leaves(self.params))
            self._n_ballot = n_tel
            self.vote_health = jax.device_put(
                telemetry.init_vote_health(n_tel, cfg.vote_every or 1),
                NamedSharding(mesh, P()),
            )
        else:
            self._n_ballot = 0
            self.vote_health = {}
        setup.lap("setup/init_state")
        self._wire_measured: Optional[dict] = None  # trace-time byte ledger
        self._metrics_window: collections.deque = collections.deque(maxlen=16)
        self._sentinel_pending = None   # (step, metrics) awaiting the check
        self._anomaly_deadline = None   # step to stop the anomaly trace at
        self._anomaly_reason = ""

        self.step_count = 0
        self._resume_skip_batches = 0
        # caller-provided data-provenance stamps (e.g. the native loader's
        # served shard list) merged into every checkpoint's manifest meta,
        # so resume can verify the deterministic replay will see the SAME
        # data the original run consumed (cli/run_clm's shard-fleet check)
        self.data_meta: dict = {}
        self._schedule = cfg.schedule()
        if loss_fn is None:
            def loss_fn(params, batch, dropout_key):
                logits = self.apply_fn(params, batch, dropout_key)
                return clm_loss_and_metrics(logits, batch)

        self.loss_fn = loss_fn
        self.loss_spec = spec
        self._train_step_core = self._build_train_step_core()
        # the accumulator (arg 2) is NOT donated: its zero-initialized
        # scalar counters can alias one device buffer, which XLA rejects as
        # a double donation — and its buffers are rebuilt every step anyway
        self._train_step = jax.jit(self._train_step_core,
                                   donate_argnums=(0, 1))
        self._train_chunk = jax.jit(self._build_train_chunk(),
                                    donate_argnums=(0, 1))
        self._eval_step = self._build_eval_step()
        setup.lap("setup/build_step")
        self.checkpointer = (
            Checkpointer(f"{cfg.output_dir}/checkpoints", cfg.save_total_limit,
                         async_save=cfg.async_ckpt,
                         integrity=cfg.ckpt_integrity,
                         journal=self.journal)
            if cfg.output_dir
            else None
        )
        if cfg.on_preempt not in ("save_exit", "off"):
            raise ValueError(
                f"--on_preempt {cfg.on_preempt!r}: expected 'save_exit' "
                "(drain + emergency checkpoint + clean return) or 'off'")
        if cfg.retrace_guard not in ("off", "warn", "error"):
            raise ValueError(
                f"--retrace_guard {cfg.retrace_guard!r}: expected 'off', "
                "'warn' (count + log recompilations) or 'error' (refuse "
                "them before compiling)")
        # retrace guard state: the abstract input signatures each jitted
        # entry point has ALREADY compiled ('step' and 'chunk' specialize
        # separately by design) — a set, because jax caches every
        # specialization: only an UNSEEN signature costs a compile
        self._retrace_sigs: dict = {}
        self.retrace_count = 0
        self.preempted = False
        self._preempt_guard = (
            resilience.PreemptionGuard(journal=self.journal)
            if cfg.on_preempt == "save_exit" else None)
        self.logger = MetricsLogger(cfg.output_dir, use_wandb=cfg.report_to_wandb)
        self.profiler = StepProfiler(cfg.profile_dir, cfg.profile_start_step,
                                     cfg.profile_num_steps)
        self.n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))
        self._maybe_resume()
        setup.lap("setup/resume")
        setup.emit()

    def _frozen_arg(self):
        """The frozen pytree as passed to the jitted steps ({} when unused —
        an empty pytree keeps the shard_map arity fixed)."""
        return self.frozen if self.frozen is not None else {}

    def comm_stats(self, steps_per_sec: Optional[float] = None) -> dict:
        """Analytic bytes-on-wire report for the vote collective (empty for
        the AdamW path, which has no optimizer collective)."""
        if not self.cfg.lion or self.world <= 1:
            # W=1: no vote collective executes at all — logging a comm
            # report (even a zeroed one) would dress a single-chip run in
            # multi-chip wire numbers
            return {}
        return comm_report(self.n_params, self.world, self.cfg.wire, steps_per_sec,
                           vote_every=self.cfg.vote_every,
                           accum_steps=self.cfg.gradient_accumulation_steps,
                           vote_buckets=self.cfg.vote_buckets or 1,
                           dcn_pipeline_depth=self.cfg.dcn_pipeline_depth)

    # -------------------------------------------------------------- telemetry
    def telemetry_summary(self, reset: bool = False) -> Optional[dict]:
        """Current vote-health summary as host floats (None when telemetry
        is off), for callers that drive the jitted steps directly instead
        of train()."""
        if not self._telemetry_on:
            return None
        out = telemetry.drain(self.vote_health, self._margin_exact)
        if reset:
            self.vote_health = telemetry.reset_counters(self.vote_health)
        return out

    def _measure_wire_once(self, batch_example) -> None:
        """Capture the measured per-step wire ledger (one abstract trace of
        the step with the collectives' tally recording — zero steady-state
        cost). Runs once, lazily, because the batch structure is only known
        when training starts."""
        if (self._wire_measured is not None or not self._telemetry_on
                or self.world <= 1):
            return
        try:
            self._wire_measured = telemetry.measure_step_wire(
                self._train_step_core, self.params, self.state,
                self.vote_health, self._frozen_arg(), batch_example,
                jax.random.key(0),
            )
        except Exception as e:  # measurement must never take down training
            emit(f"[telemetry] wire measurement unavailable: {e}")
            self._wire_measured = {}

    def _check_retrace(self, kind: str, *args) -> None:
        """The retrace guard (--retrace_guard): compare this dispatch's
        abstract input signature against the first dispatch's. A change
        means jax is about to compile a second specialization of the train
        step — a one-off multi-second stall plus a silently cached second
        program, which on a chip reads as a 2x step-time cliff with no
        error anywhere. Host-side hash over leaf shapes/dtypes, checked
        BEFORE dispatch so 'error' mode refuses the recompile before
        paying for it."""
        if self.cfg.retrace_guard == "off":
            return
        # the treedef is part of the signature: structure drift with an
        # identical leaf sequence (a renamed key, same-shaped leaves
        # swapped between containers) recompiles just the same
        sig = hash((jax.tree.structure(args), tuple(
            (getattr(leaf, "shape", None),
             str(getattr(leaf, "dtype", type(leaf).__name__)))
            for leaf in jax.tree.leaves(args))))
        seen = self._retrace_sigs.setdefault(kind, set())
        if not seen or sig in seen:
            # first dispatch, or a specialization jax already compiled and
            # cached (e.g. a short last-epoch batch alternating with the
            # full one) — re-dispatching a cached signature costs nothing
            # and must not re-warn forever
            seen.add(sig)
            return
        self.retrace_count += 1
        program = f"train_{kind}"  # its name in the compile ledger
        msg = (f"RETRACE: the jitted train {kind} (program {program!r}, "
               f"built {compile_cache.compiles_of(program)} time(s) so far) "
               "saw a new abstract input "
               f"signature at step {self.step_count} — jax will compile "
               "another specialization (multi-second stall now, a silent "
               "step-time cliff if it recurs). Usual causes: a batch "
               "shape/dtype change mid-run, or optimizer-state structure "
               "drift. --retrace_guard off silences; error refuses.")
        if self.cfg.retrace_guard == "error":
            # do NOT adopt the refused signature: a caller that catches and
            # re-dispatches the same shapes must be refused again, not
            # silently recompiled on the retry
            raise RuntimeError(msg)
        seen.add(sig)
        emit(f"[trainer] {msg}")

    def _enforce_events(self, step: int, heal: list, reset_ballot: list,
                        mask_changed: bool) -> None:
        """Act on guard/control-plane transitions against the device
        state: heal momenta from the healthy mean, zero rejoiners' ballot
        history, push the refreshed health mask, and enforce the quorum
        floor. The one place optimizer-state surgery happens — the guard
        and the plane only decide."""
        if heal:
            # healing: the healed worker's momentum restarts at the
            # HEALTHY mean (the vote distribution's center — the same
            # quantity the elastic-resume remap preserves) instead of
            # whatever it drifted or was poisoned to while away
            source = np.array(self._guard.healthy, dtype=bool)
            for w in heal:
                source[w] = False  # a healed worker is not its own source
            exp_avg = heal_worker_momentum(self.state.exp_avg, source, heal)
            exp_avg = jax.device_put(
                exp_avg, jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                      self._exp_avg_specs))
            self.state = self.state._replace(exp_avg=exp_avg)
        if reset_ballot and self.state.prev_ballot is not None:
            # a rejoiner's frozen-ballot XOR base must not reference a
            # vote it cast before it left; zeros read as 'no real previous
            # election' to the flip detector (flip_valid gates on it)
            prev = jnp.asarray(self.state.prev_ballot)
            for w in reset_ballot:
                prev = prev.at[w].set(0)
            self.state = self.state._replace(prev_ballot=jax.device_put(
                prev, NamedSharding(self.mesh, P(DATA_AXIS))))
        if mask_changed:
            # same shape/dtype as before — no retrace; the next dispatch's
            # elections exclude (or re-include) the flipped workers
            self.state = self.state._replace(health=jax.device_put(
                jnp.asarray(self._guard.healthy),
                NamedSharding(self.mesh, P())))
        if not self._guard.quorum_ok():
            if self.checkpointer:
                # the last good checkpoint must be durable before we refuse
                self.checkpointer.finalize()
            if self._cplane is not None:
                raise RuntimeError(self._cplane.quorum_error(step))
            raise RuntimeError(
                f"vote guard: healthy quorum {self._guard.healthy_count()}/"
                f"{self.world} fell below --min_quorum "
                f"{self._guard.min_quorum} at step {step} — a majority "
                "election with a sick majority is noise, refusing to "
                f"continue. Sick workers: {self._guard.sick_workers()} "
                f"(counters: {self._guard.sick_report()['sick_workers']})")

    def _apply_guard(self, step: int, obs: dict, advanced: int) -> None:
        """Drive the host quarantine machine — or, under --control_plane,
        the unified membership lifecycle — with one dispatch's guard
        observations (device arrays fetched HERE, one dispatch behind — the
        values finished computing long ago, so the get is a cheap copy),
        then act on the transitions via :meth:`_enforce_events`."""
        if not obs:
            return
        host = {k: np.asarray(jax.device_get(v)) for k, v in obs.items()}
        if self._cplane is not None:
            events = self._cplane.observe(step, host, advanced)
            heal, reset_ballot = events.heal, events.reset_ballot
            tag = "control plane"
        else:
            events = self._guard.update(step, host, advanced)
            heal, reset_ballot = events.readmitted, []
            tag = "vote guard"
        for line in events.logs:
            emit(f"[trainer] {tag}: {line}")
        if self.cfg.vote_guard != "enforce":
            return  # observe mode: bookkeeping + logs only
        self._enforce_events(step, heal, reset_ballot, events.mask_changed)

    def _apply_membership(self, step: int) -> None:
        """Consume due membership transitions (injected worker_drop /
        worker_rejoin) at a dispatch boundary, BEFORE the dispatch — so a
        drop scheduled for step s is already masked out of step s+1's
        election (and a step-0 drop out of the very first), and a
        rejoiner's healed momentum enters the very next vote."""
        events = self._cplane.membership_due(step)
        for line in events.logs:
            emit(f"[trainer] control plane: {line}")
        if events.left or events.rejoined or events.mask_changed:
            self._enforce_events(step, events.heal, events.reset_ballot,
                                 events.mask_changed)

    def _check_sentinel(self, step: int, metrics,
                        force_raise: bool = False) -> None:
        """The NaN sentinel's host half: isfinite over the step's loss (and
        pre-clip grad norm). On trip, writes the crash bundle and raises —
        or, under --trace_on_anomaly, arms a profiler window at the
        tripping step first so the poisoned dataflow lands in a trace."""
        if self._anomaly_deadline is not None and not force_raise:
            return  # already tripped; the armed trace window is draining
        vals = {}
        for k in ("loss", "grad_norm"):
            if k in metrics:
                vals[k] = float(np.asarray(jax.device_get(metrics[k])))
        bad = {k: v for k, v in vals.items() if not math.isfinite(v)}
        if not bad:
            return
        reason = ("non-finite " + ", ".join(f"{k}={v!r}"
                                            for k, v in bad.items())
                  + f" at step {step}")
        if self._guard is not None and self._guard.sick_workers():
            # the guard's per-worker health counters feed the sentinel: the
            # trip names the sick WORKER(s), not just the poisoned leaves —
            # a single worker's nonfinite local grad that loses every vote
            # never shows in the global loss, but it shows here
            reason += (" (vote guard sick workers: "
                       f"{self._guard.sick_workers()})")
        emit(f"[trainer] ANOMALY: {reason}")
        crash_dir = None
        if self.cfg.output_dir:
            window = list(self._metrics_window)
            window.append({"step": step, "tripped": True, **{
                k: float(np.asarray(jax.device_get(v)))
                for k, v in metrics.items()}})
            crash_dir = telemetry.write_crash_bundle(
                self.cfg.output_dir, step, reason,
                dataclasses.asdict(self.cfg), self.params, self.state,
                window,
                guard=(self._cplane.report() if self._cplane is not None
                       else self._guard.sick_report()
                       if self._guard is not None else None),
                journal_tail=self.journal.tail())
            emit(f"[trainer] crash bundle written to {crash_dir}")
        if self.cfg.trace_on_anomaly and not force_raise:
            trace_base = crash_dir or self.cfg.profile_dir
            if trace_base:
                # a --profile_dir window may be mid-capture: flush it before
                # swapping profilers, or the anomaly window's start_trace
                # would raise on the still-open jax profiler session
                self.profiler.close(sync=metrics)
                self.profiler = StepProfiler(
                    os.path.join(trace_base, "trace"),
                    start_step=self.step_count,
                    num_steps=self.cfg.profile_num_steps)
                self._anomaly_deadline = (self.step_count
                                          + self.cfg.profile_num_steps + 1)
                self._anomaly_reason = reason
                emit("[trainer] armed anomaly trace window for steps "
                      f"[{self.step_count}, {self._anomaly_deadline - 1})")
                return
        if self.checkpointer:
            # don't die with an async save half-committed: the last good
            # checkpoint must be durable before the anomaly unwinds us
            self.checkpointer.finalize()
        raise FloatingPointError(reason)

    # ------------------------------------------------------------------ steps
    def _build_train_step_core(self):
        cfg = self.cfg
        accum = cfg.gradient_accumulation_steps
        opt = self.opt
        loss_fn = self.loss_fn
        tp_axis = TENSOR_AXIS if dict(self.mesh.shape).get(TENSOR_AXIS, 1) > 1 else None
        param_specs = self.param_specs

        st_specs = _opt_state_specs(cfg, self._exp_avg_specs if cfg.lion else None)

        sp = dict(self.mesh.shape).get(SEQ_AXIS, 1)
        pp = dict(self.mesh.shape).get(PIPE_AXIS, 1)
        ep = dict(self.mesh.shape).get(EXPERT_AXIS, 1)
        has_frozen = self.frozen is not None
        frozen_specs = self.frozen_specs if has_frozen else {}
        telemetry_on = self._telemetry_on
        n_ballot = self._n_ballot
        world = self.world
        nan_sentinel = cfg.nan_sentinel
        guard_on = self._guard is not None
        guard_enforce = guard_on and cfg.vote_guard == "enforce"
        vh_specs = jax.tree.map(lambda _: P(), self.vote_health)
        # --ep_dcn_pipeline d > 0: the loss takes a stale global balance
        # tally (read from LionState.moe_ring pre-scan) and returns this
        # step's fresh local tallies on the metrics dict under the
        # reserved 'moe_tallies' key (popped in-trace below, never logged)
        ring_on = self.loss_spec.moe_tally_shape is not None
        counts = self.loss_spec.sum_metrics

        @partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(self.param_specs, st_specs, vh_specs, frozen_specs,
                      self.batch_spec, P()),
            out_specs=(self.param_specs, st_specs, vh_specs, P()),
            check_vma=False,
        )
        def train_step(params, state, vh, frozen, batch, base_key):
            call_loss = ((lambda p, b, k, *a: loss_fn(p, frozen, b, k, *a))
                         if has_frozen else loss_fn)
            stale_balance, ring, ring_slot = None, None, None
            if ring_on:
                # this data worker's ring of in-flight global tallies:
                # slot (count mod depth) was written at step count − depth
                # — read it now (the d-step-stale balance the aux
                # consumes), overwrite it with this step's fresh tally
                # after the backward. All-zero slots (cold start) make
                # moe_ffn fall back to the fresh local aux.
                ring = state.moe_ring[0]  # [depth, n_moe, E+1]
                ring_slot = lax.rem(_count_of(state),
                                    jnp.int32(ring.shape[0]))
                stale_balance = lax.dynamic_index_in_dim(
                    ring, ring_slot, 0, keepdims=False)
            # each batch leaf: [accum * local_bs, ...] → [accum, local_bs, ...]
            local = jax.tree.map(
                lambda b: b.reshape((accum, -1) + b.shape[1:]), batch
            )
            widx = lax.axis_index(DATA_AXIS)
            key = jax.random.fold_in(jax.random.fold_in(base_key, widx), _count_of(state))
            if ep > 1:
                # expert ranks hold different batch rows → distinct dropout keys
                key = jax.random.fold_in(key, lax.axis_index(EXPERT_AXIS))

            def micro(gsum, inp):
                microbatch, i = inp
                extra = (stale_balance,) if ring_on else ()
                (loss, metrics), g = jax.value_and_grad(
                    call_loss, has_aux=True
                )(params, microbatch, jax.random.fold_in(key, i), *extra)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return gsum, metrics

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            gsum, metrics = lax.scan(micro, zeros, (local, jnp.arange(accum)))
            grads = jax.tree.map(lambda g: g / accum, gsum)

            new_moe_ring = None
            if ring_on:
                # pop the reserved tally key BEFORE the scalarizing pmean
                # below; counts ADD across microbatches, then the expert
                # axis psum makes them global. No DATA-axis collective —
                # each data worker launches its own batch's tally into its
                # own ring row (async_grad's contract: the vote stays the
                # only optimizer collective).
                fresh = metrics.pop("moe_tallies").sum(axis=0)
                if ep > 1:
                    fresh = lax.psum(fresh, EXPERT_AXIS)
                new_moe_ring = ring.at[ring_slot].set(fresh)

            if sp > 1:
                # sequence parallelism: each seq shard computed the grad of
                # ITS tokens' loss term (normalized by the global token
                # count) — the full gradient is their sum.
                grads = lax.psum(grads, SEQ_AXIS)
            for ax, deg in ((PIPE_AXIS, pp), (EXPERT_AXIS, ep)):
                if deg <= 1:
                    continue
                # Leaves SHARDED over this axis carry complete local grads
                # (pipe: each stage owns its blocks; expert: the all_to_all
                # transpose already routed cross-shard cotangents home).
                # REPLICATED leaves carry per-shard partials — pipe: disjoint
                # stage contributions (stage-0 embedding, last-stage logits
                # tie); expert: per-row loss terms normalized by the global
                # token count — whose psum is the full gradient.
                from distributed_lion_tpu.parallel.tensor_parallel import (
                    spec_uses_axis,
                )

                flat_g, gdef = jax.tree.flatten(grads)
                flat_s = gdef.flatten_up_to(param_specs)
                flat_g = [
                    g if spec_uses_axis(s, ax) else lax.psum(g, ax)
                    for g, s in zip(flat_g, flat_s)
                ]
                grads = jax.tree.unflatten(gdef, flat_g)
            if not cfg.async_grad:
                # classic DDP all-reduce; the reference's non-async path.
                grads = lax.pmean(grads, DATA_AXIS)
            # else: no gradient sync — the AsyncTrainer contract
            # (async_trainer.py:15). The ONLY collective is the vote in
            # opt.step.
            poison = resilience.fault("ballot_poison")
            if poison is not None:
                # ballot-poisoning fault injection, baked in at trace time
                # (train/resilience registry): worker `pw` becomes a sick
                # voter from optimizer step `ps` on — NaN grads (poisons
                # momentum + votes −1 everywhere), zero grads (its ballot
                # freezes at sign(m)), or negated grads (its momentum and
                # ballot become the exact inverse — an adversarial voter)
                kind, pw, ps = poison
                hit = (widx == pw) & (_count_of(state) >= ps)
                if kind == "nan_grads":
                    grads = jax.tree.map(
                        lambda g: jnp.where(
                            hit, jnp.asarray(jnp.nan, g.dtype), g), grads)
                elif kind == "frozen_ballot":
                    grads = jax.tree.map(
                        lambda g: jnp.where(hit, jnp.zeros_like(g), g),
                        grads)
                else:  # flipped_ballot
                    grads = jax.tree.map(
                        lambda g: jnp.where(hit, -g, g), grads)
            shard_axes = tuple(a for a, flag in
                               ((TENSOR_AXIS, tp_axis is not None),
                                (PIPE_AXIS, pp > 1),
                                (EXPERT_AXIS, ep > 1)) if flag)
            gnorm = None
            if nan_sentinel:
                # pre-clip global norm (clipping would mask the explosion
                # the sentinel exists to catch); same exact cross-axis sum
                # the clipper uses, then meaned over workers for logging
                gsq = global_grad_sq(grads, specs=param_specs,
                                     shard_axes=shard_axes)
                if guard_enforce:
                    # degraded-mode training: one worker's nonfinite LOCAL
                    # grad must not poison the pmean'd metric and trip the
                    # sentinel on a run the guard is keeping healthy — the
                    # norm averages the finite workers, and the sick one is
                    # named through the guard's own counters instead
                    finite = jnp.isfinite(gsq)
                    gnorm = jnp.sqrt(
                        lax.psum(jnp.where(finite, gsq, 0.0), DATA_AXIS)
                        / jnp.maximum(
                            lax.psum(finite.astype(jnp.float32), DATA_AXIS),
                            1.0))
                else:
                    gnorm = jnp.sqrt(lax.pmean(gsq, DATA_AXIS))
            clip = (cfg.grad_clip_norm if cfg.grad_clip_norm is not None
                    else cfg.max_grad_norm)
            if clip:
                # per-worker clip (grads are local in async mode; in DDP mode
                # this runs on the already-averaged grads, matching HF Trainer
                # clipping after the all-reduce). Under TP/PP the grads of
                # sharded leaves get their norms psum'd across that axis so
                # every rank derives the same scale.
                grads = clip_by_global_norm(grads, clip, specs=param_specs,
                                            shard_axes=shard_axes)
            if cfg.lion:
                st = squeeze_worker_state(state)
            elif cfg.zero1:
                st = squeeze_zero_state(state)
            else:
                st = state
            outs = opt.step(params, grads, st)
            new_params, new_st = outs[0], outs[1]
            extra = list(outs[2:])
            if telemetry_on:
                # the optimizer emits the per-step vote-health frame; fold
                # it into the replicated accumulator on device (the only
                # additions are two scalar psums — no host traffic, and the
                # election itself is untouched)
                vh = telemetry.fold(vh, extra.pop(0), DATA_AXIS, world,
                                    n_ballot)
            gframe = extra.pop(0) if guard_on else None
            if cfg.lion:
                new_state = expand_worker_state(new_st)
            elif cfg.zero1:
                new_state = expand_zero_state(new_st)
            else:
                new_state = new_st
            if new_moe_ring is not None:
                # the optimizer's step passes the balance ring through
                # untouched (it constructs its result state without it) —
                # re-attach this step's launch here, re-stacked [1, ...]
                new_state = new_state._replace(moe_ring=new_moe_ring[None])

            mean_metrics = {
                k: (lax.psum(v.sum(), DATA_AXIS) if k in counts
                    else lax.pmean(v.mean(), DATA_AXIS))
                for k, v in metrics.items()}
            if gnorm is not None:
                mean_metrics["grad_norm"] = gnorm
            if gframe is not None:
                # the guard's per-dispatch observations ride the metrics
                # dict as replicated [W] vectors under the reserved
                # 'guard_*' names; the trainer pops them before logging and
                # feeds the host quarantine machine one dispatch behind
                # (vote_guard.OBS_KEYS). Frozen = a (re)vote with ZERO
                # ballot bit flips against a REAL previous election.
                frozen = ((gframe["flips"] == 0) & gframe["flip_valid"]
                          & (gframe["voted"] > 0))
                mean_metrics["guard_nonfinite"] = (
                    gframe["nonfinite"] > 0).astype(jnp.int32)
                mean_metrics["guard_frozen"] = frozen.astype(jnp.int32)
                mean_metrics["guard_disagree"] = gframe["disagree"]
                mean_metrics["guard_voted_steps"] = (
                    gframe["voted"] > 0).astype(jnp.int32)
            return new_params, new_state, vh, mean_metrics

        return train_step

    def _build_train_chunk(self):
        """K optimizer steps per device dispatch: ``lax.scan`` of the train
        step over a staged ``[K, global_batch, ...]`` batch stack. One
        host→device round trip per K steps instead of per step."""
        step = self._train_step_core

        def train_chunk(params, state, vh, frozen, batches, base_key):
            def body(carry, batch):
                p, s, v = carry
                p, s, v, m = step(p, s, v, frozen, batch, base_key)
                return (p, s, v), m

            (params, state, vh), ms = lax.scan(body, (params, state, vh),
                                               batches)
            # per-chunk mean for logging (loss of the last step alone would
            # alias a single microbatch draw); the guard's 'guard_*'
            # observations are bad-step COUNTS and summed fractions — they
            # sum over the chunk so the host strike counter sees every step
            return params, state, vh, {
                k: (v.sum(0) if k.startswith("guard_") else v.mean(0))
                for k, v in ms.items()}

        return train_chunk

    def _build_eval_step(self):
        loss_fn = self.loss_fn
        has_frozen = self.frozen is not None
        frozen_specs = self.frozen_specs if has_frozen else {}

        @partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(self.param_specs, frozen_specs, self.batch_spec),
            out_specs=P(),
            check_vma=False,
        )
        def eval_step(params, frozen, batch):
            loss, metrics = (loss_fn(params, frozen, batch, None) if has_frozen
                             else loss_fn(params, batch, None))
            return {k: lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}

        return jax.jit(eval_step)

    # ------------------------------------------------------------- train/eval
    def global_train_batch(self) -> int:
        return (self.batch_shards * self.cfg.per_device_train_batch_size
                * self.cfg.gradient_accumulation_steps)

    def train(
        self,
        train_iter: Iterator[np.ndarray],
        eval_blocks: Optional[np.ndarray] = None,
        max_steps: Optional[int] = None,
    ) -> list[dict]:
        """Run the step-based training loop (the reference trains by
        max_steps, README.md:25). ``train_iter`` yields
        [world*accum*per_device_bs, block] token batches."""
        cfg = self.cfg
        total = min(cfg.max_steps, self.step_count + max_steps if max_steps else cfg.max_steps)
        history = []
        data_spec = NamedSharding(self.mesh, self.batch_spec)
        base_key = jax.random.key(cfg.seed + 1)
        tokens_per_step = self.global_train_batch() * cfg.block_size
        # After resume, fast-forward the (deterministically seeded) data
        # iterator past the batches the checkpointed run consumed, so a
        # resumed run sees the same data a continuous run would. Seekable
        # iterators (data.sources.BatchIterator, the native loader) skip by
        # index arithmetic — no data reads; plain generators are replayed.
        if self._resume_skip_batches:
            if hasattr(train_iter, "skip"):
                train_iter.skip(self._resume_skip_batches)
            else:
                for _ in range(self._resume_skip_batches):
                    next(train_iter)
            self._resume_skip_batches = 0
        t_last, s_last = time.time(), self.step_count
        log_first = cfg.logging_first_step
        chunk_spec = NamedSharding(self.mesh, P(None, *self.batch_spec))
        jr = self.journal  # journal.NULL when --journal is off: its events
        # are no-ops. Spans go through journal.span, which is the shared
        # null span unless a profiler session or --journal listens
        span = journal.span
        jr.event("train_start", step=self.step_count, total=int(total))

        while self.step_count < total:
            if self._cplane is not None:
                # membership transitions land at dispatch boundaries: a
                # due drop is masked out of the NEXT election, a due
                # rejoin is healed before it votes again
                with span("membership", step=self.step_count):
                    self._apply_membership(self.step_count)
            self.profiler.maybe_start(self.step_count)
            k = min(self.cfg.steps_per_call, total - self.step_count)
            advanced = k
            if k == self.cfg.steps_per_call and k > 1:
                # fused K-step dispatch; the tail below K runs step-by-step
                # (avoids a second jit specialization for the remainder)
                with span("data_wait", step=self.step_count, steps=k):
                    stack = [next(train_iter) for _ in range(k)]
                    self._measure_wire_once(stack[0])
                    batches = jax.device_put(
                        jax.tree.map(lambda *xs: np.stack(xs), *stack),
                        chunk_spec)
                with span("retrace_check", step=self.step_count):
                    self._check_retrace("chunk", self.params, self.state,
                                        self.vote_health, self._frozen_arg(),
                                        batches)
                with self.profiler.annotate(self.step_count), \
                        span("dispatch", step=self.step_count, steps=k):
                    (self.params, self.state, self.vote_health,
                     metrics) = self._train_chunk(
                        self.params, self.state, self.vote_health,
                        self._frozen_arg(), batches, base_key
                    )
                self.step_count += k
            else:
                with span("data_wait", step=self.step_count, steps=1):
                    raw_batch = next(train_iter)
                    self._measure_wire_once(raw_batch)
                    batch = jax.device_put(raw_batch, data_spec)
                with span("retrace_check", step=self.step_count):
                    self._check_retrace("step", self.params, self.state,
                                        self.vote_health, self._frozen_arg(),
                                        batch)
                with self.profiler.annotate(self.step_count), \
                        span("dispatch", step=self.step_count, steps=1):
                    (self.params, self.state, self.vote_health,
                     metrics) = self._train_step(
                        self.params, self.state, self.vote_health,
                        self._frozen_arg(), batch, base_key
                    )
                self.step_count += 1
                advanced = 1
            for line in (journal.new_resolved_lines()
                         + compile_cache.new_lines()):
                # what attention `auto` and the loss head resolved to while
                # the program was traced; which program was traced, lowered,
                # compiled or loaded: said once, when it happens (the first
                # dispatch; a retrace later in the run)
                emit(line)
            self.profiler.maybe_stop(self.step_count, sync=metrics)
            if self._guard is not None:
                # pop the guard's [W]-vector observations before anything
                # host-floats the metrics dict; the machine runs one
                # dispatch behind (same pattern as the sentinel) so the
                # device pipeline never stalls on the host read
                obs = {k: metrics.pop(k) for k in vote_guard.OBS_KEYS
                       if k in metrics}
                if self._guard_pending is not None:
                    with span("guard_apply", step=self.step_count):
                        self._apply_guard(*self._guard_pending)
                self._guard_pending = (self.step_count, obs, advanced)
            if cfg.nan_sentinel:
                # trailing isfinite watch: the PREVIOUS dispatch's metrics
                # are checked after this one is in flight, so the device
                # pipeline stays full while anomalies are still caught one
                # dispatch late (the bundle names the tripping step)
                if self._sentinel_pending is not None:
                    with span("sentinel_check", step=self.step_count):
                        self._check_sentinel(*self._sentinel_pending)
                self._sentinel_pending = (self.step_count, metrics)
            if (self._anomaly_deadline is not None
                    and self.step_count >= self._anomaly_deadline):
                # trace_on_anomaly: the armed window has captured its steps
                self.profiler.maybe_stop(self.step_count, sync=metrics)
                if self.checkpointer:
                    self.checkpointer.finalize()
                raise FloatingPointError(self._anomaly_reason)

            # boundary tests are "crossed a multiple of N during this
            # dispatch" so chunked advances never skip a log/eval/save
            if (self.step_count % cfg.logging_steps < advanced or log_first
                    or self.step_count == total):
                log_first = False
                # the ONE device drain the loop already pays per log
                # interval (the host-float below blocks on it either way)
                # made explicit, so a listener sees device-bound time as a
                # span instead of smearing it into the logging bucket — no
                # sync is added that the float() conversions were not
                # about to perform
                with span("device_wait", step=self.step_count):
                    jax.block_until_ready(metrics)
                # everything since the device drain — metric assembly,
                # telemetry drain, the strict-JSON write — is the logging
                # tax
                with span("logging_drain", step=self.step_count):
                    m = {k: float(v) for k, v in metrics.items()}
                    now = time.time()
                    steps_per_sec = (self.step_count - s_last) / max(now - t_last, 1e-9)
                    m["tokens_per_sec"] = tokens_per_step * steps_per_sec
                    # the step just executed ran with optimizer count step_count-1
                    m["lr"] = float(self._schedule(jnp.asarray(self.step_count - 1, jnp.float32)))
                    comm = self.comm_stats(steps_per_sec)
                    if comm:
                        m["comm_bytes_per_step"] = comm["comm_bytes_per_step"]
                        m["comm_mbytes_per_sec"] = comm.get("comm_mbytes_per_sec", 0.0)
                        # analytic pipelineable wire share under vote_buckets
                        # (profiling.comm_report); the measured counterpart is
                        # the benchmark's vote_exposed_ms.train4
                        m["comm_overlap_frac"] = comm.get("comm_overlap_frac", 0.0)
                        if "dcn_overlap_frac" in comm:
                            # analytic share of the hier wire's level-2 latency
                            # off the critical path under --dcn_pipeline_depth;
                            # measured counterpart: bench_dcn's depth ablation
                            m["dcn_overlap_frac"] = comm["dcn_overlap_frac"]
                    from distributed_lion_tpu.parallel.collectives import (
                        DCN_WAIT,
                    )

                    dcn_waits = DCN_WAIT.pop()
                    if dcn_waits:
                        # the emulated DCN link's measured residual (unhidden)
                        # wait this interval — nonzero only under the dcn_delay
                        # fault (train/resilience registry); sub-delay values
                        # are the cross-step pipeline visibly hiding the leg
                        wait_s = sum(dcn_waits.values())
                        m["dcn_wait_s"] = wait_s
                        if self.cfg.journal:
                            # thread-tagged: the wait happened inside the
                            # device program (run_analyze excludes it from
                            # step-thread attribution — it overlaps dispatch)
                            jr.record({"kind": "span", "name": "dcn_wait",
                                       "dur": round(wait_s, 9),
                                       "step": self.step_count,
                                       "thread": "dcn-link"})
                    hbm = peak_hbm_gb()
                    if hbm is not None:
                        m["peak_hbm_gb"] = hbm
                    if self.checkpointer:
                        # seconds the loop spent blocked on checkpointing since
                        # the last log — async saves keep this near 0 while the
                        # sync path pays the full serialize+write here
                        m["ckpt_stall_s"] = self.checkpointer.pop_stall_s()
                    if self.retrace_count:
                        # recompilations the retrace guard observed (should stay
                        # 0 for the whole run; see --retrace_guard)
                        m["retraces"] = self.retrace_count
                    if self._telemetry_on:
                        # drain the on-device accumulator (the interval's ONLY
                        # telemetry host transfer) and reset its counters; the
                        # previous election carries over so flip rates stay
                        # continuous across intervals
                        vote = telemetry.drain(self.vote_health,
                                               self._margin_exact)
                        self.vote_health = telemetry.reset_counters(
                            self.vote_health)
                        m.update({f"vote/{k}": v for k, v in vote.items()})
                        if self._wire_measured:
                            mw = self._wire_measured
                            m["comm_measured_bytes_per_step"] = mw[
                                "bytes_per_step"]
                            m["comm_measured_calls_per_step"] = mw[
                                "calls_per_step"]
                            if mw.get("dcn_bytes_per_step"):
                                m["comm_measured_dcn_bytes_per_step"] = mw[
                                    "dcn_bytes_per_step"]
                            if comm:
                                # analytic-vs-measured drift, a first-class
                                # metric: 0 unless the accounting and the
                                # collectives have diverged
                                m["comm_drift_bytes"] = (
                                    mw["bytes_per_step"]
                                    - comm["comm_bytes_per_step"])
                        skew = telemetry.host_step_skew(self.step_count)
                        if skew is not None:
                            m["host_step_skew"] = skew
                        per_dev = peak_hbm_per_device()
                        if per_dev is not None and len(per_dev) > 1:
                            m["peak_hbm_per_device"] = per_dev
                    if self._guard is not None:
                        # scalar guard health for the record stream (the [W]
                        # observation vectors were popped above)
                        m.update(self._guard.summary())
                    if self._cplane is not None:
                        m.update(self._cplane.summary())
                    if hasattr(train_iter, "health_metrics"):
                        # input-pipeline health (e.g. the native loader's
                        # skipped_shards / shard_read_retries counters) rides
                        # the same strict-JSON metrics stream
                        m.update(train_iter.health_metrics())
                    t_last, s_last = now, self.step_count
                    self.logger.log(self.step_count, m, prefix="train")
                    self._metrics_window.append({"step": self.step_count, **m})
                    history.append({"step": self.step_count, **m})
                    if self.cfg.journal:
                        # the multi-host step-skew heartbeat becomes a journal
                        # event (PR 2 only PRINTED it, and only under
                        # --telemetry): run_analyze derives cross-host skew
                        # percentiles from these per-rank step_log records
                        jskew = (m.get("host_step_skew") if self._telemetry_on
                                 else telemetry.host_step_skew(self.step_count))
                        jr.event("step_log", step=self.step_count,
                                 steps_per_sec=round(steps_per_sec, 6),
                                 **({} if jskew is None
                                    else {"skew_steps": int(jskew)}))
                jr.flush()

            if eval_blocks is not None and self.step_count % cfg.eval_steps < advanced:
                with span("eval", step=self.step_count):
                    history.append({"step": self.step_count,
                                    **self.evaluate(eval_blocks)})

            if self.checkpointer and self.step_count % cfg.save_steps < advanced:
                self.save()

            if (self._preempt_guard is not None
                    and self._preempt_guard.should_stop()):
                # preemption drain: flag was set by SIGTERM/maintenance;
                # checked once per dispatch so we act at a consistent
                # boundary. Drain the in-flight async save, make the
                # emergency checkpoint durable, and return cleanly — the
                # caller exits 0 and the watcher restarts into a resume.
                if self._cplane is not None:
                    # the one membership stream records the departure too:
                    # a preempted process is every local worker leaving
                    self._cplane.note_preempt(self.step_count)
                if self.checkpointer:
                    emit(f"[trainer] preemption at step {self.step_count}:"
                          " draining in-flight save, writing emergency "
                          "checkpoint")
                    self.save(tag="preempt")
                    self.checkpointer.finalize()
                else:
                    emit(f"[trainer] preemption at step {self.step_count}:"
                          " no output_dir — NOTHING SAVED; a restart "
                          "begins from step 0")
                self.preempted = True
                break
        if self._guard is not None and self._guard_pending is not None:
            # the final dispatch's guard observations are still pending;
            # fold them so the machine's counters (and any quorum refusal)
            # cover the whole run — and so a sentinel bundle written just
            # below names the sick workers from complete evidence
            pending, self._guard_pending = self._guard_pending, None
            self._apply_guard(*pending)
        if cfg.nan_sentinel and self._sentinel_pending is not None:
            # the final dispatch's metrics were still awaiting their check
            pending, self._sentinel_pending = self._sentinel_pending, None
            self._check_sentinel(*pending, force_raise=True)
        jr.event("train_end", step=self.step_count,
                 preempted=bool(self.preempted))
        jr.flush()
        return history

    def evaluate(self, eval_blocks: np.ndarray) -> dict:
        """Eval loss / token accuracy / perplexity=exp(loss)
        (run_clm.py:630-636)."""
        cfg = self.cfg
        n_examples = len(jax.tree.leaves(eval_blocks)[0])
        per_dev = cfg.per_device_eval_batch_size
        # under pipelining the local batch must split into GPipe microbatches
        # (pp from the mesh, like the train step — cfg.pipeline_parallel is
        # only the CLI's mesh-building input)
        pp = dict(self.mesh.shape).get(PIPE_AXIS, 1)
        div = (cfg.pipeline_microbatches or pp) if pp > 1 else 1
        if n_examples < self.batch_shards * per_dev:
            # shrink rather than silently skipping eval on small validation
            # splits (jit re-specializes on the new shape)
            per_dev = max(div, n_examples // self.batch_shards // div * div)
        bs = self.batch_shards * per_dev
        if n_examples < bs:
            emit(f"[trainer] eval skipped: {n_examples} examples < "
                  f"{self.batch_shards} batch shards")
            return {"eval/loss": float("nan"), "eval/accuracy": float("nan"),
                    "eval/perplexity": float("nan")}
        data_spec = NamedSharding(self.mesh, self.batch_spec)
        per_key: dict = {}
        n_batches = min(cfg.eval_iters, n_examples // bs)
        for i in range(n_batches):
            batch = jax.device_put(
                jax.tree.map(
                    lambda x: np.ascontiguousarray(x[i * bs : (i + 1) * bs]), eval_blocks
                ),
                data_spec,
            )
            m = self._eval_step(self.params, self._frozen_arg(), batch)
            for k, v in m.items():
                per_key.setdefault(k, []).append(float(v))
        # aggregate EVERY metric the loss_fn reports (CLM: loss/accuracy/
        # n_tokens; DPO: loss/reward_accuracy/reward_margin; custom: anything)
        out = {f"eval/{k}": float(np.mean(v)) for k, v in per_key.items() if k != "n_tokens"}
        loss = out.get("eval/loss", float("nan"))
        if "n_tokens" in per_key:  # token-level LM loss → perplexity applies
            out["eval/perplexity"] = float(np.exp(min(loss, 80.0)))
        self.logger.log(self.step_count, out, prefix="")
        return out

    # ------------------------------------------------------------ checkpoints
    @staticmethod
    def _pack_state_rng(state):
        """Typed PRNG keys are not serializable (Orbax sees an opaque
        key dtype); store the raw key data and re-wrap on restore. A
        stochastic-binarization checkpoint without this loses its RNG —
        save simply failed before the resilience PR."""
        rng = getattr(state, "rng", None)
        if rng is None or not jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            return state
        return state._replace(rng=jax.random.key_data(rng))

    @staticmethod
    def _unpack_state_rng(state):
        rng = getattr(state, "rng", None)
        if rng is None or jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            return state
        return state._replace(rng=jax.random.wrap_key_data(rng))

    def _payload(self, world: Optional[int] = None):
        # 0-d ndarray, not np.int64 scalar: older orbax StandardCheckpointHandler
        # versions only accept ndarray/jax.Array leaves
        payload = {"params": self.params,
                   "opt_state": self._pack_state_rng(self.state),
                   "step": np.asarray(self.step_count, np.int64),
                   # data-iterator position (1 batch per step) and the world
                   # size the momenta were stacked at, explicit in the
                   # payload so resume doesn't have to infer either
                   "batches_consumed": np.asarray(self.step_count, np.int64),
                   "world": np.asarray(world or self.world, np.int64)}
        if self._telemetry_on:
            # the vote-health accumulator rides the checkpoint so flip
            # rates / histograms stay continuous across a restart
            payload["vote_health"] = self.vote_health
        return payload

    def save(self, tag: str = "periodic") -> None:
        assert self.checkpointer is not None
        if self.checkpointer.latest_step() == self.step_count:
            return  # already saved at this step (e.g. final save on a save_steps boundary)
        meta = {"world": self.world, "tag": tag,
                "step": self.step_count,
                "batches_consumed": self.step_count,
                "has_vote_health": self._telemetry_on,
                "has_guard": self._guard is not None,
                "wire": self.cfg.wire, "vote_every": self.cfg.vote_every,
                "dcn_pipeline_depth": self.cfg.dcn_pipeline_depth,
                "ep_dcn_pipeline": int(self.cfg.ep_dcn_pipeline or 0),
                "control_plane": self._cplane is not None,
                **self.data_meta}
        if self._cplane is not None:
            # mid-run membership survives the restart: the mask itself
            # rides LionState.health, but departed-vs-quarantined is plane
            # state — without this stamp a resume would auto-readmit a
            # worker the run knew was GONE
            meta["cp_departed"] = sorted(
                int(w) for w in self._cplane.departed)
            # the consumed-schedule watermark: a resume must not replay
            # drop/rejoin entries this run already acted on
            meta["cp_sched_through"] = int(self._cplane.sched_through)
            # probation windows + quarantine history: a crash mid-probation
            # must resume the probe-fail rule (a still-sick rejoiner
            # departs again), not fall back to the cooldown cycle
            meta["cp_rejoining_until"] = [
                int(x) for x in self._cplane.rejoining_until]
            meta["cp_quarantine_counts"] = [
                int(x) for x in self._cplane.quarantine_counts]
        self.checkpointer.save(self.step_count, self._payload(), meta=meta)

    def _with_guard_fields(self, tpl: dict, on: bool,
                           world: Optional[int] = None) -> dict:
        """Shape a restore template's opt_state for a checkpoint WITH or
        WITHOUT the vote-guard state (Orbax rejects templates missing — or
        mis-shaping — a saved key, so the manifest's has_guard stamp
        decides, not this run's flags). ``world`` sizes the stacked
        prev-ballot / mask for the elastic path."""
        w = world or self.world
        out = dict(tpl)
        if not on:
            out["opt_state"] = out["opt_state"]._replace(
                health=None, prev_ballot=None)
            return out
        from distributed_lion_tpu.optim.distributed_lion import (
            _guard_ballot_len,
        )

        blen = _guard_ballot_len(self.n_params, self.cfg.vote_every or 1)
        out["opt_state"] = out["opt_state"]._replace(
            health=jax.ShapeDtypeStruct(
                (w,), jnp.bool_,
                sharding=NamedSharding(self.mesh, P())),
            prev_ballot=jax.ShapeDtypeStruct(
                (w, blen), jnp.uint8,
                sharding=NamedSharding(
                    self.mesh,
                    P(DATA_AXIS) if w % self.world == 0 else P())),
        )
        return out

    def _fresh_guard_state(self):
        """(health, prev_ballot) reinitialized for THIS run's world — used
        when a checkpoint carries no guard state (or an incompatible one)
        but the guard is on."""
        from distributed_lion_tpu.optim.distributed_lion import (
            _guard_ballot_len,
        )

        blen = _guard_ballot_len(self.n_params, self.cfg.vote_every or 1)
        return (
            jax.device_put(jnp.ones((self.world,), jnp.bool_),
                           NamedSharding(self.mesh, P())),
            jax.device_put(jnp.zeros((self.world, blen), jnp.uint8),
                           NamedSharding(self.mesh, P(DATA_AXIS))),
        )

    def _vote_health_template(self, ckpt_vote_every: int):
        """A restore template for the checkpoint's vote_health accumulator,
        sized by the CHECKPOINT's vote_every (prev_elected's packed length
        depends on it) — the current config's value may differ, in which
        case the restored accumulator is discarded after restore. The
        template must still match what was saved: Orbax rejects templates
        missing (or mis-shaping) a saved key."""
        return jax.device_put(
            telemetry.init_vote_health(self.n_params, ckpt_vote_every),
            NamedSharding(self.mesh, P()))

    def _elastic_template(self, ckpt_world: int, meta: dict):
        """Restore template for a checkpoint stacked at a DIFFERENT world
        size: momentum leaves get a [ckpt_world, ...] leading dim. Params
        restore straight into their real shardings (same shapes at any
        world); the momentum stack shards its leading axis over 'data'
        whenever ckpt_world divides by the current world — only the
        non-divisible upscale case (e.g. 2→4) falls back to replicated
        restore, the one shape the mesh can't split evenly."""
        repl = NamedSharding(self.mesh, P())

        def _repl(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl)
            return x

        tpl = jax.tree.map(_repl, self._payload())
        tpl["params"] = jax.tree.map(
            lambda p, s: jax.ShapeDtypeStruct(
                p.shape, p.dtype, sharding=NamedSharding(self.mesh, s)),
            self.params, self.param_specs)
        # shape the vote_health slot to what the CHECKPOINT holds (it is
        # restored then discarded — its normalizations reference the old
        # world, so the telemetry window restarts fresh after remap)
        tpl.pop("vote_health", None)
        if meta.get("has_vote_health"):
            tpl["vote_health"] = self._vote_health_template(
                int(meta.get("vote_every", 1)) or 1)
        mom_shard = (NamedSharding(self.mesh, P(DATA_AXIS))
                     if ckpt_world % self.world == 0 else repl)
        tpl["opt_state"] = tpl["opt_state"]._replace(
            exp_avg=jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(
                    (ckpt_world,) + m.shape[1:], m.dtype,
                    sharding=mom_shard),
                tpl["opt_state"].exp_avg),
        )
        # guard fields sized by the CHECKPOINT's world (the meta stamp
        # decides presence, like vote_health); the restored mask drives the
        # healthy-only momentum heal below, then both reinit at W'
        tpl = self._with_guard_fields(tpl, bool(meta.get("has_guard")),
                                      world=ckpt_world)
        return tpl

    def _adopt_guard_state(self, step: int, meta: Optional[dict] = None) -> None:
        """Reconcile the restored state's guard fields with THIS run's
        guard flag: adopt a checkpointed health mask exactly (quarantined
        workers resume quarantined, cooldown restarting at the resumed
        step), attach fresh guard state when the checkpoint predates the
        guard, strip it when the guard is off now. Under --control_plane
        the manifest meta's ``cp_departed`` stamp restores the
        departed-vs-quarantined distinction (a control-plane toggle in
        either direction is tolerated like the guard toggle: a plane-off
        resume degrades departed workers to plain quarantine, a plane-on
        resume of a plane-off checkpoint starts with nobody departed)."""
        st = self.state
        if self._guard is not None:
            if st.health is None or st.prev_ballot is None:
                health, prev = self._fresh_guard_state()
                self.state = st._replace(health=health, prev_ballot=prev)
            else:
                mask = np.asarray(jax.device_get(st.health), dtype=bool)
                if self._cplane is not None:
                    m = meta or {}
                    self._cplane.adopt(
                        mask, step,
                        departed=m.get("cp_departed"),
                        sched_through=m.get("cp_sched_through"),
                        rejoining_until=m.get("cp_rejoining_until"),
                        quarantine_counts=m.get("cp_quarantine_counts"))
                    lc = self._cplane.lifecycle()
                    if not mask.all():
                        emit("[trainer] control plane: resumed with "
                             "lifecycle "
                             f"{dict((w, s) for w, s in enumerate(lc) if s != 'healthy')}"
                             f" at step {step}")
                else:
                    self._guard.adopt_mask(mask, step)
                    if not mask.all():
                        emit("[trainer] vote guard: resumed with "
                             "quarantined workers "
                             f"{[int(w) for w in np.nonzero(~mask)[0]]}"
                             f" (cooldown restarts at step {step})")
        elif st.health is not None or st.prev_ballot is not None:
            self.state = st._replace(health=None, prev_ballot=None)

    def _restore_step(self, step: int, meta: dict, ckpt_world: int) -> None:
        ckpt_ve = int(meta.get("vote_every", self.cfg.vote_every or 1)) or 1
        if ckpt_world == self.world:
            tpl = self._payload()
            # shape the template to what the checkpoint actually holds —
            # Orbax rejects templates missing (or mis-shaping) a saved key,
            # so the meta's has_vote_health/vote_every stamps decide the
            # vote_health slot, not the current run's flags
            has_vh = meta.get("has_vote_health")
            if has_vh is False:
                tpl.pop("vote_health", None)
            elif has_vh:
                tpl["vote_health"] = self._vote_health_template(ckpt_ve)
            tries = [tpl]
            if has_vh is None:
                # no manifest meta (--ckpt_integrity false / legacy dir):
                # the checkpoint's vote_health presence is unknown, so a
                # telemetry-flag toggle between save and resume would brick
                # the first template — also try the opposite shape
                alt = dict(tpl)
                if "vote_health" in alt:
                    alt.pop("vote_health")
                else:
                    alt["vote_health"] = self._vote_health_template(ckpt_ve)
                tries.append(alt)
            if self.cfg.lion:
                # guard-state presence follows the same stamp logic: the
                # manifest's has_guard decides the template's shape; with
                # no meta, try this run's shape first, then the opposite
                # (a --vote_guard toggle between save and resume)
                has_guard = meta.get("has_guard")
                cur_guard = self._guard is not None
                if has_guard is None:
                    tries = ([self._with_guard_fields(t, cur_guard)
                              for t in tries]
                             + [self._with_guard_fields(t, not cur_guard)
                                for t in tries])
                else:
                    tries = [self._with_guard_fields(t, bool(has_guard))
                             for t in tries]
            # pre-resilience checkpoints lack the world/batches_consumed/
            # vote_health keys entirely; the legacy payload shape last
            legacy_state = self._pack_state_rng(self.state)
            if self.cfg.lion:
                legacy_state = legacy_state._replace(health=None,
                                                     prev_ballot=None,
                                                     dcn_ring=None,
                                                     moe_ring=None)
            tries.append({"params": self.params,
                          "opt_state": legacy_state,
                          "step": np.asarray(self.step_count, np.int64)})
            restored = None
            for i, t in enumerate(tries):
                try:
                    restored = self.checkpointer.restore(step, t)
                    break
                except Exception:
                    if i == len(tries) - 1:
                        raise
            self.params = restored["params"]
            self.state = self._unpack_state_rng(restored["opt_state"])
            if self.cfg.lion:
                self._adopt_guard_state(step, meta)
            if ("vote_health" in restored and self._telemetry_on
                    and ckpt_ve == (self.cfg.vote_every or 1)):
                # adopt the accumulator only when its packing still matches
                # this run (vote_every sizes prev_elected); otherwise the
                # telemetry window restarts fresh
                self.vote_health = restored["vote_health"]
        else:
            restored = self.checkpointer.restore(
                step, self._elastic_template(ckpt_world, meta))
            self.params = jax.tree.map(
                lambda p, s: jax.device_put(p, NamedSharding(self.mesh, s)),
                restored["params"], self.param_specs)
            st = self._unpack_state_rng(restored["opt_state"])
            exp_avg = st.exp_avg
            if st.health is not None:
                # a checkpoint with quarantined workers: only HEALTHY
                # momenta may enter the remap — heal the quarantined rows
                # to the healthy mean first (mean-preserving, so the vote
                # center the remap promises to keep is the healthy one)
                mask = np.asarray(jax.device_get(st.health), dtype=bool)
                sick = [int(w) for w in np.nonzero(~mask)[0]]
                if sick:
                    exp_avg = heal_worker_momentum(exp_avg, mask, sick)
                    emit(f"[trainer] elastic resume: healed quarantined "
                          f"worker momenta {sick} from the healthy mean "
                          "before the world remap")
            st = st._replace(
                exp_avg=remap_worker_momentum(exp_avg, ckpt_world,
                                              self.world))
            if self._guard is not None:
                # worker identity does not survive a world change: the
                # guard restarts all-healthy at W' with a zero ballot
                # history (a still-sick HOST re-strikes within
                # --guard_strikes steps)
                health, prev = self._fresh_guard_state()
                st = st._replace(health=health, prev_ballot=prev)
            else:
                st = st._replace(health=None, prev_ballot=None)
            self.state = jax.device_put(
                st,
                LionState(
                    count=NamedSharding(self.mesh, P()),
                    exp_avg=jax.tree.map(
                        lambda s: NamedSharding(self.mesh, s),
                        self._exp_avg_specs),
                    rng=(None if st.rng is None
                         else NamedSharding(self.mesh, P())),
                    elected=(None if st.elected is None
                             else NamedSharding(self.mesh, P())),
                    health=(None if st.health is None
                            else NamedSharding(self.mesh, P())),
                    prev_ballot=(None if st.prev_ballot is None
                                 else NamedSharding(self.mesh,
                                                    P(DATA_AXIS))),
                ),
            )
            # the accumulator's normalizations reference the old world; a
            # fresh window is honest, stale continuity is not
            emit(f"[trainer] elastic resume: remapped [{ckpt_world}, ...] "
                  f"momenta to [{self.world}, ...] "
                  f"({'group mean' if ckpt_world > self.world else 'replicate'}"
                  f" policy, cross-worker mean preserved)")
        self.step_count = int(restored["step"])
        self._resume_skip_batches = int(
            restored.get("batches_consumed", restored["step"]))

    def _maybe_resume(self) -> None:
        if not (self.checkpointer and self.cfg.resume_from_checkpoint):
            return
        # verified autodetect, newest GOOD first: a torn leaf / corrupted
        # manifest / uncommitted save falls back one save interval instead
        # of poisoning the run (or killing the resume outright)
        candidates = (self.checkpointer.valid_steps()
                      if self.cfg.ckpt_integrity else
                      [s for s in [self.checkpointer.latest_step()]
                       if s is not None])
        for step in candidates:
            meta = (self.checkpointer.manifest_meta(step)
                    if self.cfg.ckpt_integrity else None) or {}
            ckpt_world = int(meta.get("world", self.world))
            if meta:
                # a depth toggle is an operator decision, never a silent
                # remap: the ring holds IN-FLIGHT level-2 tallies whose
                # slot count and staleness semantics are the depth — there
                # is no meaning-preserving reshape between depths (in a
                # stamped manifest, an absent key = pre-ring checkpoint =
                # depth 0). Checkpoints with NO manifest meta at all
                # (--ckpt_integrity false / legacy dirs) cannot be
                # depth-checked up front: a matching depth restores through
                # the normal templates, and a mismatch surfaces as the
                # all-templates-failed RuntimeError below, which names the
                # depth toggle as a candidate cause.
                ckpt_depth = int(meta.get("dcn_pipeline_depth", 0) or 0)
                if ckpt_depth != self.cfg.dcn_pipeline_depth:
                    raise ValueError(
                        f"checkpoint step {step} was written at "
                        f"--dcn_pipeline_depth {ckpt_depth} but this run "
                        f"uses {self.cfg.dcn_pipeline_depth}: the in-flight"
                        " DCN tally ring does not survive a depth change. "
                        "Resume with the matching depth (then change it at "
                        "the NEXT fresh start), or point --output_dir "
                        "elsewhere")
                # the MoE balance ring has the same no-remap property: its
                # slot count IS the staleness (None and 0 both mean no
                # ring, so toggling between those is fine)
                ckpt_ep = int(meta.get("ep_dcn_pipeline", 0) or 0)
                run_ep = int(self.cfg.ep_dcn_pipeline or 0)
                if ckpt_ep != run_ep:
                    raise ValueError(
                        f"checkpoint step {step} was written at "
                        f"--ep_dcn_pipeline {ckpt_ep} but this run uses "
                        f"{run_ep}: the in-flight MoE balance ring does "
                        "not survive a depth change. Resume with the "
                        "matching depth, or point --output_dir elsewhere")
            if ckpt_world != self.world:
                # a mismatched world is an operator decision, not a bad
                # checkpoint — never silently fall back past it
                if not self.cfg.elastic_resume:
                    raise ValueError(
                        f"checkpoint step {step} holds momenta for world="
                        f"{ckpt_world} but this mesh has world="
                        f"{self.world}; pass --elastic_resume to remap "
                        "them (or match the chip count)")
                if not self.cfg.lion:
                    raise NotImplementedError(
                        "--elastic_resume remaps the stacked per-worker "
                        "Lion momenta; the AdamW/ZeRO-1 states have no "
                        "defined remap")
                if self.cfg.dcn_pipeline_depth > 0:
                    raise NotImplementedError(
                        "--elastic_resume cannot remap the DCN pipeline "
                        "ring: its slots are in-flight level-2 tallies "
                        "whose chunk ownership and group count are "
                        "functions of the world size. Resume at the "
                        "original world (drain the pipeline), or restart "
                        "with --dcn_pipeline_depth 0")
                if (self.cfg.ep_dcn_pipeline or 0) > 0:
                    raise NotImplementedError(
                        "--elastic_resume cannot remap the MoE balance "
                        "ring: its rows are per-data-worker stale tallies "
                        "of batches the new world never routed. Resume at "
                        "the original world, or restart with "
                        "--ep_dcn_pipeline 0")
            try:
                self._restore_step(step, meta, ckpt_world)
            except Exception as e:
                emit(f"[trainer] checkpoint step {step} failed to restore "
                      f"({e}); falling back to the previous good checkpoint")
                continue
            purged = self.checkpointer.purge_steps_after(step)
            if purged:
                emit(f"[trainer] purged stale newer checkpoints {purged}: "
                      "left on disk they make Orbax silently drop every "
                      "post-resume save below them (the deterministic "
                      "replay re-creates them bit-identically)")
            emit(f"[trainer] resumed from checkpoint step {step}")
            return
        if candidates:
            # every verified checkpoint failed to restore — that's a
            # structural mismatch (model/optimizer config changed), not a
            # bad checkpoint. Restarting from step 0 underneath them would
            # also be unsaveable (Orbax drops saves below existing steps).
            raise RuntimeError(
                f"resume_from_checkpoint: all {len(candidates)} verified "
                f"checkpoint(s) (steps {candidates}) failed to restore "
                "into this run's state structure — likely a model/optimizer"
                " config change since they were written"
                + (" (this run's --dcn_pipeline_depth "
                   f"{self.cfg.dcn_pipeline_depth} is one candidate: a "
                   "checkpoint without manifest meta cannot be "
                   "depth-checked up front, and the DCN ring does not "
                   "survive a depth change)"
                   if self.cfg.dcn_pipeline_depth > 0 else "")
                + ". Refusing to "
                "silently restart from step 0; pass --resume_from_checkpoint"
                " false (or point --output_dir elsewhere) to start fresh")

    def close(self) -> None:
        self.profiler.close()
        if self.cfg.inject_poison:
            # disarm the poison this trainer injected so a later Trainer in
            # the same process does not inherit a sick worker
            resilience.inject_fault("ballot_poison", None)
        if self.cfg.inject_membership:
            # same hygiene for the membership schedule (unconsumed entries
            # must not fire inside a later Trainer's run)
            resilience.inject_fault("membership", None)
        if self._preempt_guard is not None:
            self._preempt_guard.close()
        try:
            if self.checkpointer:
                # may re-raise a committer-thread commit failure (the drain
                # boundary); the metrics log must still be flushed/closed
                self.checkpointer.close()
        finally:
            self.logger.close()
            # the journal closes LAST: the checkpointer drain above still
            # records its ckpt spans, and a commit failure propagating out
            # of this method leaves a flushed journal behind it
            journal.uninstall(self.journal)
            self.journal.close()

    # ------------------------------------------------------------- factories
    @staticmethod
    def for_gpt2(cfg: TrainConfig, mesh, model_cfg: GPT2Config, seed: Optional[int] = None,
                 initial_params: Any = None):
        """``initial_params`` (e.g. an HF checkpoint imported via
        models/hf_import) replaces the random init — the reference's
        finetune-from-pretrained path (run_clm.py:425-444)."""
        from distributed_lion_tpu.parallel.tensor_parallel import (
            gpt2_param_specs,
            validate_tp,
        )

        params = (initial_params if initial_params is not None else
                  gpt2_init(jax.random.key(seed if seed is not None else cfg.seed), model_cfg))
        model_cfg, remat_decision = apply_remat_policy(cfg, model_cfg, mesh,
                                                       params)
        cfg, n, acct = _resolve_comm(cfg, mesh, params)
        tp = mesh.shape[TENSOR_AXIS]
        emit(
            f"[trainer] GPT-2 {n/1e6:.1f}M params | world={data_axis_size(mesh)} "
            f"tp={tp} | vote wire={cfg.wire}"
            + (f" (vote_every={cfg.vote_every})" if cfg.vote_every > 1 else "")
            + (f" (vote_buckets={cfg.vote_buckets}, "
               f"{acct['overlappable_wire_frac']*100:.0f}% of the wire "
               "pipelineable)" if cfg.vote_buckets > 1 else "")
            + f": {acct['bits_per_param']:.2f} bits/param/step "
            f"({acct['vs_bf16_allreduce']*100:.1f}% of bf16 all-reduce; "
            f"{acct['bits_per_param_per_microbatch']:.2f} bits/param/microbatch)"
            + (f" | DCN leg {acct['dcn_bits_per_param']:.3f} bits/param"
               if "dcn_bits_per_param" in acct else "")
        )
        pp = dict(mesh.shape).get(PIPE_AXIS, 1)
        if pp > 1:
            from distributed_lion_tpu.models.gpt2_pipe import (
                make_pipeline_loss,
                pipeline_param_specs,
                pipeline_params,
            )

            if dict(mesh.shape).get(EXPERT_AXIS, 1) > 1:
                raise NotImplementedError(
                    "pipeline parallelism composes with data, tensor and "
                    "sequence parallelism (dp x tp x sp x pp); an expert "
                    "axis alongside pipe is not wired"
                )
            if model_cfg.moe_experts > 0:
                raise NotImplementedError(
                    "MoE blocks under pipeline parallelism are not wired "
                    "(mixed dense/MoE stage structures); drop one of the two"
                )
            return _pipelined_trainer(
                cfg, mesh, model_cfg, "gpt2", remat_decision,
                make_pipeline_loss, pipeline_params(params, pp),
                pipeline_param_specs)

        ep = dict(mesh.shape).get(EXPERT_AXIS, 1)
        if ep > 1 and model_cfg.moe_experts == 0:
            raise ValueError(
                f"an 'expert' mesh axis of size {ep} needs MoE blocks "
                "(--moe_experts); a dense model would silently duplicate all "
                "compute across the axis"
            )
        if cfg.ep_dcn_pipeline is not None and model_cfg.moe_experts == 0:
            raise ValueError(
                "--ep_dcn_pipeline schedules the MoE balance feedback; a "
                "dense model (--moe_experts 0) has no routing to balance. "
                "Drop the flag or add --moe_experts")
        if model_cfg.moe_experts > 0:
            from distributed_lion_tpu.models.gpt2 import gpt2_moe_param_specs
            from distributed_lion_tpu.models.loss import clm_loss_sharded_rows

            if dict(mesh.shape).get(SEQ_AXIS, 1) > 1:
                raise NotImplementedError(
                    "MoE composes with data, expert and tensor parallelism "
                    "(dp x ep x tp); a seq axis alongside MoE is not wired"
                )
            if model_cfg.moe_experts % ep:
                raise ValueError(
                    f"moe_experts {model_cfg.moe_experts} not divisible by "
                    f"expert axis {ep}"
                )
            if tp > 1:
                validate_tp(model_cfg, tp, "gpt2")
            expert_axis = EXPERT_AXIS if ep > 1 else None
            moe_tp_axis = TENSOR_AXIS if tp > 1 else None
            moe_specs = (gpt2_moe_param_specs(model_cfg, tensor=tp > 1)
                         if (ep > 1 or tp > 1) else None)

            ep_depth = cfg.ep_dcn_pipeline
            # depth 0 = synchronous fed balance: psum the routing tallies
            # over the expert axis INSIDE the forward (at ep=1 the axis
            # psum is the identity, so the aux stays bit-identical to the
            # unflagged local path — the depth-0 pin). depth > 0 feeds the
            # stale ring tally instead (4th loss arg, below).
            balance_axis = (EXPERT_AXIS
                            if (ep_depth == 0 and ep > 1) else None)

            def moe_apply(params, tokens, dropout_key, moe_balance=None,
                          return_tallies=False):
                return gpt2_apply(params, tokens, model_cfg,
                                  dropout_key=dropout_key,
                                  expert_axis=expert_axis,
                                  tp_axis=moe_tp_axis, return_aux=True,
                                  moe_balance=moe_balance,
                                  moe_balance_axis=balance_axis,
                                  return_moe_tallies=return_tallies)

            def moe_loss(params, batch, dropout_key, moe_balance=None):
                fed = moe_balance is not None
                logits, aux, *tallies = moe_apply(params, batch, dropout_key,
                                                  moe_balance, fed)
                if ep > 1:
                    loss, metrics = clm_loss_sharded_rows(
                        logits, batch, EXPERT_AXIS, aux=aux)
                else:
                    loss, metrics = clm_loss_and_metrics(logits, batch)
                    metrics["aux_loss"] = aux
                    loss = loss + 0.01 * aux
                if fed:
                    metrics["moe_tallies"] = tallies[0]
                return loss, metrics

            moe_batch_spec = P((DATA_AXIS, EXPERT_AXIS)) if ep > 1 else None
            tally_shape = None
            if (ep_depth or 0) > 0:
                from distributed_lion_tpu.models.gpt2 import is_moe_block
                n_moe = sum(1 for i in range(model_cfg.n_layer)
                            if is_moe_block(model_cfg, i))
                # read by Trainer.__init__ (ring sizing) and the step core
                # (ring read/feed/write); the tally row is per-expert token
                # counts + the lane count in the last entry
                tally_shape = (n_moe, model_cfg.moe_experts + 1)
            n_active = count_params(params) - sum(
                p.size for b in params["blocks"] if "moe" in b
                for p in jax.tree.leaves(b["moe"])
            )
            emit(f"[trainer] GPT-2-MoE: {count_params(params)/1e6:.1f}M total "
                  f"({n_active/1e6:.1f}M dense) | {model_cfg.moe_experts} "
                  f"experts every {model_cfg.moe_every} blocks | ep={ep}")
            return Trainer(cfg, mesh, apply_fn=None, params=params,
                           param_specs=moe_specs, loss_fn=moe_loss,
                           loss_spec=LossSpec(batch_spec=moe_batch_spec,
                                              moe_tally_shape=tally_shape),
                           remat_decision=remat_decision)

        param_specs = None
        if tp > 1:
            validate_tp(model_cfg, tp, "gpt2")
            param_specs = gpt2_param_specs(model_cfg,
                                           vocab_parallel=cfg.tp_vocab)
        if dict(mesh.shape).get(SEQ_AXIS, 1) > 1 and model_cfg.dropout > 0.0:
            emit(
                "[trainer] WARNING: attention-probability dropout is "
                "disabled under sequence parallelism (scores never exist "
                "in one place on the ring path); residual/embedding "
                "dropout still applies — semantics differ from "
                "replicated training at the same dropout rate"
            )

        loss_fn, loss_spec = gpt2_clm_loss(cfg, mesh, model_cfg)
        return Trainer(cfg, mesh, None, params, param_specs=param_specs,
                       loss_fn=loss_fn, loss_spec=loss_spec,
                       remat_decision=remat_decision)

    @staticmethod
    def for_llama(cfg: TrainConfig, mesh, model_cfg, seed: Optional[int] = None,
                  initial_params: Any = None):
        """Full-parameter CLM training of a Llama-family model — the
        reference's run_clm is architecture-agnostic (AutoModelForCausalLM,
        run_clm.py:425-444), so ours trains Llama from scratch or from an
        imported checkpoint too. Composes with dp, tensor (dp×tp), sequence
        (dp×sp) and pipeline (dp×pp, models/llama_pipe) parallelism; the
        expert axis is GPT-2-MoE-only."""
        from distributed_lion_tpu.models.llama import llama_hidden, llama_init
        from distributed_lion_tpu.ops.quant import maybe_dequant
        from distributed_lion_tpu.parallel.tensor_parallel import (
            llama_param_specs,
            validate_tp,
        )

        if dict(mesh.shape).get(EXPERT_AXIS, 1) > 1:
            raise NotImplementedError(
                "an 'expert' mesh axis is wired for GPT-2-MoE only; Llama "
                "composes with dp x tp x sp x pp"
            )
        params = (initial_params if initial_params is not None else
                  llama_init(jax.random.key(seed if seed is not None else cfg.seed),
                             model_cfg))
        model_cfg, remat_decision = apply_remat_policy(cfg, model_cfg, mesh,
                                                       params)
        cfg, n, acct = _resolve_comm(cfg, mesh, params)
        tp = mesh.shape[TENSOR_AXIS]
        pp = dict(mesh.shape).get(PIPE_AXIS, 1)
        emit(
            f"[trainer] Llama {n/1e6:.1f}M params | world={data_axis_size(mesh)} "
            f"tp={tp}" + (f" pp={pp}" if pp > 1 else "") + f" | vote wire={cfg.wire}"
            + (f" (vote_every={cfg.vote_every})" if cfg.vote_every > 1 else "")
            + (f" (vote_buckets={cfg.vote_buckets})"
               if cfg.vote_buckets > 1 else "")
            + f": {acct['bits_per_param']:.2f} bits/param/step"
            + (f" | DCN leg {acct['dcn_bits_per_param']:.3f} bits/param"
               if "dcn_bits_per_param" in acct else "")
        )
        if pp > 1:
            from distributed_lion_tpu.models.llama_pipe import (
                llama_pipeline_param_specs,
                llama_pipeline_params,
                make_llama_pipeline_loss,
            )

            return _pipelined_trainer(
                cfg, mesh, model_cfg, "llama", remat_decision,
                make_llama_pipeline_loss, llama_pipeline_params(params, pp),
                llama_pipeline_param_specs)
        param_specs = None
        if tp > 1:
            validate_tp(model_cfg, tp, "llama")
            param_specs = llama_param_specs(model_cfg,
                                            vocab_parallel=cfg.tp_vocab)

        def hidden_fn(params, batch, dropout_key, *, tp_axis, seq_axis,
                      vocab_axis):
            # our Llama (like HF's) has no dropout, and under a vocab axis
            # only the lm_head is sharded ([d, V/tp] column slices): the
            # embedding stays whole
            return llama_hidden(params, batch, model_cfg, tp_axis=tp_axis,
                                seq_axis=seq_axis)

        loss_fn, loss_spec = _clm_head_loss(
            cfg, mesh, model_cfg, hidden_fn,
            lambda params: maybe_dequant(params["lm_head"],
                                         model_cfg.compute_dtype),
            "dv", head_cols=model_cfg.vocab_size)
        return Trainer(cfg, mesh, None, params, param_specs=param_specs,
                       loss_fn=loss_fn, loss_spec=loss_spec,
                       remat_decision=remat_decision)


    @staticmethod
    def for_mellum(cfg: TrainConfig, mesh, model_cfg,
                   seed: Optional[int] = None, initial_params: Any = None):
        """Full-parameter CLM training of ``models/mellum`` (window and
        full GQA layers, a dropless top-k expert layer in every block,
        an untied head) over the data axis: every device holds the tree
        ``mellum_init`` makes, which with ``model_cfg.held`` is one chip's
        share of a layer spread over several. The exchange between those
        chips is not run, so tensor, sequence, pipeline and expert axes are
        refused. The step's metrics carry the expert layers' counters
        (``MELLUM_COUNTERS``), summed over layers, microbatches and
        workers."""
        from distributed_lion_tpu.models.mellum import (
            MELLUM_COUNTERS,
            mellum_hidden,
            mellum_init,
            mellum_param_specs,
        )

        wrong = [a for a in (TENSOR_AXIS, SEQ_AXIS, PIPE_AXIS, EXPERT_AXIS)
                 if dict(mesh.shape).get(a, 1) > 1]
        if wrong:
            raise NotImplementedError(
                f"--model_family mellum trains over the data axis; the mesh "
                f"has {wrong} (the exchange between the chips that share a "
                "layer's experts is not run: ROADMAP.md)")
        if cfg.tp_vocab or cfg.ep_dcn_pipeline is not None:
            raise ValueError("--tp_vocab and --ep_dcn_pipeline need axes "
                             "--model_family mellum does not train over")
        params = (initial_params if initial_params is not None else
                  mellum_init(jax.random.key(seed if seed is not None
                                             else cfg.seed), model_cfg))
        model_cfg, remat_decision = apply_remat_policy(cfg, model_cfg, mesh,
                                                       params)
        cfg, n, acct = _resolve_comm(cfg, mesh, params)
        held = model_cfg.held or (0, model_cfg.n_experts)
        emit(f"[trainer] Mellum {n/1e6:.1f}M params | "
             f"world={data_axis_size(mesh)} | experts {held[0]}-"
             f"{held[0] + held[1] - 1} of {model_cfg.n_experts} held, top "
             f"{model_cfg.top_k}, dropless | vote wire={cfg.wire}: "
             f"{acct['bits_per_param']:.2f} bits/param/step")
        xent_ops.head_path("vd", model_cfg.d_model, model_cfg.compute_dtype,
                           chunks=cfg.vocab_chunks)

        def loss_fn(params, batch, dropout_key):
            hidden, counters = mellum_hidden(params, batch, model_cfg)
            loss, metrics = xent_ops.clm_head_loss(
                hidden, params["lm_head"], batch, layout="vd",
                chunks=cfg.vocab_chunks)
            return loss, {**metrics, **counters}

        return Trainer(cfg, mesh, None, params,
                       param_specs=mellum_param_specs(model_cfg),
                       loss_fn=loss_fn,
                       loss_spec=LossSpec(vocab_chunks=True,
                                          sum_metrics=MELLUM_COUNTERS),
                       remat_decision=remat_decision)


def _count_of(state) -> jnp.ndarray:
    return state.count


def global_grad_sq(grads, specs=None, shard_axes: tuple = ()):
    """Exact squared global L2 norm of a gradient pytree inside shard_map.

    Under tensor/pipeline/expert parallelism (``shard_axes`` + ``specs``),
    the squared norm of each leaf SHARDED over one of those axes is psum'd
    across that axis (each rank holds one shard of that gradient) while
    replicated leaves — whose grads are complete and identical on every
    rank, via the copy_to_tp_region boundary / the pipe-axis grad psum —
    are counted once, so every rank derives the same value. The data axis
    is deliberately never summed: per-worker grads get per-worker norms
    (they are different gradients, not shards of one). Shared by the
    clipper and the NaN sentinel's grad-norm metric."""
    def _sq(g):
        return jnp.sum(jnp.square(g.astype(jnp.float32)))

    if not shard_axes:
        return sum(_sq(g) for g in jax.tree.leaves(grads))
    from distributed_lion_tpu.parallel.tensor_parallel import spec_uses_axis

    flat_g, gdef = jax.tree.flatten(grads)
    flat_s = gdef.flatten_up_to(specs)  # P leaves; same structure as grads
    # accumulate per axis-subset: a leaf sharded over axis A contributes
    # its local sq, psum'd over A; leaves sharded over several axes are
    # psum'd over each in turn
    sq = jnp.float32(0)
    by_axes: dict = {}
    for g, s in zip(flat_g, flat_s):
        axes = tuple(a for a in shard_axes if spec_uses_axis(s, a))
        by_axes[axes] = by_axes.get(axes, jnp.float32(0)) + _sq(g)
    for axes, part in by_axes.items():
        for a in axes:
            part = lax.psum(part, a)
        sq = sq + part
    return sq


def clip_by_global_norm(grads, clip: float, specs=None,
                        shard_axes: tuple = ()):
    """Scale the whole pytree so its global L2 norm is ≤ ``clip`` — the
    torch.nn.utils.clip_grad_norm_ semantics HF Trainer applies before every
    optimizer step (default max_grad_norm=1.0), which the reference inherits.
    Norm semantics under model parallelism: see :func:`global_grad_sq`."""
    sq = global_grad_sq(grads, specs=specs, shard_axes=shard_axes)
    scale = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
