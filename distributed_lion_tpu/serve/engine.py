"""Continuous-batching inference engine over the paged KV cache.

The serving counterpart of ``train/loop.py`` (ROADMAP item 4): requests
join a rolling batch on arrival, leave on EOS/length/overflow, and every
tick is ONE device dispatch — either a bucketed prefill or a decode step
over all active slots. The host's only per-tick work is table math
(serve/kv_cache.py) and reading back a dispatch's sampled tokens as one
array; there is no per-token host sync inside a tick (graft-check DLT001
pins the forbidden shape, tests/fixtures/analysis/serve/).

**The host runs one tick ahead of its reads.** A decode tick's last
tokens never come from the host: every dispatch returns one int32 vector
(the slots' tokens, counters behind them), the next dispatch takes that
vector as its operand ``prev`` while it is still on the device, and a
prefill writes its sampled first token over its slot's entry. So
``step()`` t enqueues the decode tick of t and only then reads tick t-1's
vector (and this tick's prefills'): the device runs tick t while the host
commits t-1 and builds t+1, and a tick costs max(device, host) where it
cost their sum. What the operands need besides is host arithmetic that
does not wait for a token: lengths and sample indices advance at
dispatch. An end the host can foresee (the budget) retires the slot AT
dispatch — row and pages freed, the replacement admitted next tick, the
Completion emitted when the last token is read, one ``step()`` later
(``timing["delivery_lag_ticks"]``); an end it cannot foresee (EOS) costs
the one row already enqueued, which is dropped (``run_ahead_discarded``).
A decision that needs unread tokens reads them first, a ``serve/drain``
span with its reason: an overflow eviction, a resident's deadline,
``export_records``. ``has_work()`` stays true until the last read is
made. A speculator's accept / reject shapes every next tick, so an engine
with one reads in order; nothing else does (``stats["run_ahead_ticks"]``
of ``decode_ticks``; the ``[setup] decode:`` line says which).

Scheduling (the vLLM recipe, simplified to two tick kinds):

- **admit** — pending requests take a free slot while pages fit, subject
  to a fairness cap on prefill tokens per engine tick
  (``prefill_cap_tokens``): a burst of long prompts cannot starve the
  decode batch for more than one tick.
- **prefill** — one dispatch per admitted request at a power-of-two
  bucketed length (a handful of compiles total, never per-prompt), tail
  masked via the scatter's ``valid`` lanes; samples the request's first
  token inside the same dispatch. The host knows at dispatch whether the
  prefill starts at position 0 (no shared prefix before it) and says so
  to a hook that takes it (``ServeModel.fresh_prefill``: GPT-2, Llama and
  the latent families) as the STATIC argument ``fresh``: in a bucket the
  family's tiled kernel takes (the rule ``fresh_prefill`` carries: on a
  TPU, whole blocks of 128 rows for ``ops/attention.fresh_kernel_applies``;
  ``LATENT_FRESH_MIN`` tokens or more for a latent family's
  ``latent_fresh_applies``)
  the program then attends over the keys it has just projected
  and only writes its pages; a prefill behind a shared prefix, the
  speculative verify and the drafter's mirror see pages they did not
  write and keep the gather path. ``stats["prefill_fresh_dispatches"]``
  of ``prefill_dispatches`` took the fresh path, and the ``[setup]
  prefill:`` line says which buckets can.
- **decode tick** — one dispatch advancing EVERY active slot one token:
  block-table decode (``*_decode_paged``) + per-slot sampling. Per-slot
  PRNG keys are ``fold_in(key(request.seed), generated_index)`` — a
  request's sample stream depends only on the request, NOT on which slot
  it rides or who shares the batch, which is what makes a staggered
  continuous-batching run produce outputs identical to solo runs
  (tests/test_serve.py pins it). On a TPU its attention is the
  ``paged_attn`` kernel reading each row's own pages where they lie
  (ops/attention's layout note): ``stats["decode_attn_kernel_ticks"]``
  counts those ticks and ``kv_pages_read`` / ``kv_pages_table`` is the
  share of the tables' width they read (both ride ``serve_stats``).
- **evict** — EOS / ``max_new_tokens`` / cache-overflow slots release
  their page refs; the block table row goes back to sentinel, so the next
  decode tick simply ignores the slot (no recompile, the shapes never
  changed).

**Tensor-parallel serving** (``ServeConfig.tp`` — ISSUE 13): the engine
composes with ``parallel/tensor_parallel`` exactly the way the trainer
does — attention/MLP weights sharded per the Megatron param specs, the
page pools sharded over their KV-HEAD axis across a ``(data=1,
tensor=tp)`` mesh, and every decode/prefill/verify dispatch shard_map'd
over the slice. The kv-head axis is embarrassingly parallel through the
whole paged chain (scatter/gather/attend are per-head), so each rank runs
the same program on its head shard and only the row-parallel output
projections cross the tensor axis (one psum per block). Host-side block
tables stay REPLICATED numpy — allocation is the same table math at any
tp and never recompiles. ``tp=0`` (default) is the single-device path,
bit-for-bit the pre-TP engine; ``tp=1`` runs the sharded program on a
1-mesh and is pinned bit-identical to it; ``tp>1`` divides weight + KV
HBM per chip and is pinned token-identical on CPU mesh emulation
(tests/test_tp_serve.py).

**Expert-parallel MoE serving** (``ServeConfig.ep`` — ISSUE 15, the PR 9
refusals lifted): MoE checkpoints serve through the paged engine. Pad and
sentinel lanes carry a ``valid`` mask into expert routing
(parallel/expert.moe_ffn) so they consume zero expert capacity, and
inference routing is NO-DROP (models/gpt2._decode_mlp) — an exact
per-token function, which is what makes paged MoE decode bit-identical to
the dense-KV MoE path, batched identical to solo, and the prefix-cache /
n-gram-speculation compositions hold unchanged. ``ep >= 1`` shards the
expert FFN banks over the expert axis of a ``(data=1, expert=ep,
tensor=max(tp,1))`` mesh via the SAME ``moe_param_specs`` trees the
trainer uses — two ``all_to_all`` hops per MoE block per tick, page pools
untouched (attention stays shard-local exactly as TP left it). NF4/int8
expert banks shard with the dense specs. ``ep=1`` is pinned bit-identical
to the unsharded program; ``ep in {2,4}`` and ep×tp are pinned
token-identical on CPU mesh emulation (tests/test_moe_serve.py).
``draft:<k>`` speculation keeps its loud MoE refusal (the mirror-pool
residual, serve/speculate.py).

**Prefix sharing** (``ServeConfig.prefix_cache``): a prompt-prefix →
page-run cache with per-page refcounts (serve/kv_cache.PrefixCache). An
admitted request shares the cached pages covering its prompt prefix (one
physical copy for N requests carrying the same system prompt), prefills
only the uncovered suffix (the shared pages already hold its k/v —
computed once, by the first request, from the same tokens and weights,
hence bit-identical), and copy-on-write kicks in at the first divergent
write: a write landing in a ref>1 page first copies that page
(``ops.attention.paged_copy_pages``) so ``paged_scatter_kv`` targets a
private clone for the written suffix only. ``grow``/``shrink``/free are
refcount ops — speculative rollback over a shared table row releases
refs without freeing pages a neighbor still reads. Outputs are pinned
identical to the unshared engine (greedy, sampled, and speculative —
tests/test_serve.py / test_speculate.py).

With ``ServeConfig.speculate`` set, the decode tick is replaced by the
speculative draft/verify/commit round (serve/speculate.py): up to k
drafted tokens per slot ride ONE batched verify dispatch and the accepted
prefix commits to the block tables — outputs pinned identical to this
one-token tick (greedy bit-identical, sampled token-identical to the same
per-request stream), only the tokens-per-dispatch ratio changes.

NF4/int8 frozen-weight serving: ``quant='nf4'`` re-packs the dense
checkpoint through ``ops.quant.quantize_tree`` once at engine build; the
decode paths dequantize inside each matmul's producer fusion
(``maybe_dequant``), so a 7B checkpoint serves from ~0.5 byte/param of
HBM plus the page pool. Under TP the quantized leaves shard with the SAME
specs as their dense twins (the shaped layout's last-dim blocks never
straddle a shard boundary — ops/quant.validate_quant_tp fails fast when a
block size can't split).

**Elastic serving** (ISSUE 14): every unfinished request is exportable as
a :class:`RecoveryRecord` — prompt + committed tokens + seed (+ budget and
deadline) — and a request carrying ``committed`` tokens re-admits by
prefilling its whole history and RESUMING the pinned per-request sample
stream at ``token_index = len(committed)``. Because every draw's key is
``fold_in(key(seed), token_index)`` and prefill-computed k/v are
bit-identical to decode-written k/v for the same tokens at the same
positions, a migrated request's continued stream is token-identical to
the uninterrupted one by construction — the property
``serve/replica_plane.ServingFleet`` builds replica crash/drain/rejoin on
(tests/test_replica_plane.py pins it, greedy/sampled/speculative,
prefix_cache on and off). Requests may also carry a wall-clock
``deadline_s``; expiry evicts with the honest ``timeout`` status at the
next tick boundary, partial output attached.

**Spans** (``train/journal.span`` — the one span primitive: the shared
null span unless a profiler session or an installed ``--journal_dir``
journal listens; with a listener each span is a
``jax.profiler.TraceAnnotation`` on the device trace's clock, a record in
``journal.traced()`` and a journal line, all carrying ``id``/``parent``).
Every tick is one tree::

    serve/tick            tick=<n>                       the whole step()
      serve/expire                                       deadline sweep
        serve/drain       reason=deadline                a resident is late
      serve/admit         pending, prefills              admission + table math
        serve/cow         copies
        serve/prefill     req_id, prompt_len, padded..   one per admitted request: enqueue only
        serve/evict       req_id, slot, reason=length    budget of 1: retired at dispatch
      serve/decode_tick   batch
        serve/decode_build                               grow, CoW, operands, tables
          serve/cow
          serve/drain     reason=overflow                then serve/evict
        serve/decode_dispatch                            the jitted call: enqueue only
          serve/evict     reason=length                  budget ends with this token
        serve/token_read  of=decode, tick=<n-1>          the blocking np.asarray(vec)
        serve/commit      batch                          per-row bookkeeping, completions
          serve/evict     reason=eos
        serve/token_read  of=prefill, tick=<n>           one pair per prefill admitted
        serve/commit
      serve/metrics                                      only with ServeConfig.metrics

(under ``speculate`` the decode tick is ``serve/draft``, ``serve/verify``
with its own ``serve/token_read``, and ``serve/commit``, and a prefill's
``serve/token_read`` and ``serve/commit`` follow it under ``serve/admit``).
A tick's span less the ``serve/token_read`` under it is what the host did
itself; a ``serve/token_read`` is where it waits for the device, and with
the next tick already enqueued the device does not wait for it.

**Always-on accounts** (``train/journal.account``: kept whether or not
anything listens, because the run that stalls is never the traced one).
Construction is timed by ``setup/place_weights``, ``setup/init_pages`` and
``setup/build_dispatches`` (``setup_lap`` accounts and one ``[setup]``
line); the compile ledger (utils/compile_cache) names each dispatch's
program (``decode_tick``, ``prefill``, ``cow_copy``, ``verify``) with its
trace, lower and compile-or-load seconds when it is built, and the retrace
guard names it when it counts a retrace. Every tick is stamped by host
clock reads alone (eight in a decode-only tick, through the engine's
``time_fn``; no device sync, the token path untouched):
``stats["read_wait_s"]`` sums what the host waited in its blocking reads
(its slack under the device's tick: it falls to zero where the host
binds), ``stats["gc_pause_s"]`` / ``["gc_collections"]`` copy the
collector's totals once a tick (``journal.watch_gc``), and a decode-only
tick (no prefill admitted in it or the tick before) whose wall is over
``SLOW_TICK_FACTOR`` times the median of the last ``SLOW_TICK_HISTORY``
such ticks' and over ``SLOW_TICK_MIN_S`` counts in ``stats["slow_ticks"]``
and ``["slow_tick_excess_s"]`` (wall less that median) and leaves one
``slow_tick`` account: ``tick``, ``wall_ms``, ``median_ms``,
``read_wait_ms`` with the longest read's ``read_of`` / ``read_tick``,
``gc_ms``, ``admit_ms``, ``build_ms``, ``commit_ms``, ``prefills`` and
``next_read_wait_ms``. The ``[serve] slow tick`` line is printed at once
(stderr), with those fields; the two ticks after it fill
``next_read_wait_ms`` in and a second line says them: near zero and then a
whole device tick means the device had finished its queue and sat idle
while the host was blocked, so the transfer or the runtime held the host; a
usual wait means the device itself was late. The counters ride
``serve_stats``.
"""

from __future__ import annotations

import dataclasses
import functools
import statistics
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from distributed_lion_tpu.parallel.mesh import EXPERT_AXIS, TENSOR_AXIS
from distributed_lion_tpu.serve.kv_cache import (
    BlockTables,
    PrefixCache,
    bucket_tokens,
    init_page_leaves,
)
from distributed_lion_tpu.serve.metrics import RequestTimes, ServeMetrics
from distributed_lion_tpu.train import journal
from distributed_lion_tpu.utils import compile_cache


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seqs: int = 8            # rolling-batch width (decode slots)
    block_size: int = 16         # tokens per KV page
    max_blocks_per_seq: int = 8  # block-table width; per-seq cap =
    #                              block_size * max_blocks_per_seq tokens
    num_blocks: int = 0          # page-pool size; 0 = auto
    #                              (max_seqs * max_blocks_per_seq: no slot
    #                              can starve another at full occupancy)
    prefill_cap_tokens: int = 512  # fairness cap: max PADDED prefill
    #                              tokens admitted per engine tick (a
    #                              single over-cap prompt still admits
    #                              when the tick has admitted nothing —
    #                              caps must not livelock)
    prefill_top_bucket: int = 0  # the largest prefill bucket where the
    #                              longest prompt served lies between two
    #                              powers of two (whole pages; 0 = none:
    #                              serve/kv_cache.bucket_tokens ``top``)
    max_new_tokens: int = 64     # per-request default budget
    temperature: float = 0.0     # 0 = greedy; sampling knobs are engine-
    top_k: Optional[int] = None  # static (one compiled tick), seeds are
    top_p: Optional[float] = None  # per-request
    quant: str = "none"          # none | nf4 | int8 frozen-weight serving
    quant_block: Optional[int] = None  # quant block override (elements;
    # None = the format default). Under --serve_tp every sharded last dim
    # needs last/2 (nf4 packing) and last/block divisible by tp
    # (ops/quant.validate_quant_tp fails fast with the leaf path) — small
    # models need a smaller block than the 64-element default.
    eos_id: Optional[int] = None
    tp: int = 0                  # tensor-parallel degree. 0 = the
    # single-device engine (no mesh, no collectives — the pre-TP program
    # bit for bit); tp >= 1 builds a (data=1, tensor=tp) mesh over the
    # first tp local devices, shards weights per the Megatron param specs
    # and the page pools over kv heads, and shard_maps every dispatch.
    # tp=1 is pinned BIT-identical to tp=0; tp>1 divides weight+KV HBM
    # per chip and is pinned token-identical (tests/test_tp_serve.py).
    # kv_heads/n_head/d_ff must divide (parallel.tensor_parallel.
    # validate_tp — the same rule the trainer enforces).
    ep: int = 0                  # expert-parallel serving degree
    # (ISSUE 15): 0 = no expert axis. N >= 1 requires a MoE checkpoint
    # (moe_experts % N == 0) and shards the expert FFN banks over the
    # expert axis of a (data=1, expert=N, tensor=max(tp,1)) mesh — two
    # all_to_all hops per MoE block per tick, page pools untouched
    # (attention stays shard-local exactly as TP left it; under ep-only
    # the pools are replicated). Composes with tp (ep x tp devices).
    # ep=1 is pinned bit-identical to the unsharded engine; ep in {2,4}
    # (and ep x tp) pinned token-identical on CPU mesh emulation
    # (tests/test_moe_serve.py).
    ep_batch: bool = False       # batch-sharded expert-parallel decode
    # (ISSUE 16): shard the decode/prefill/verify BATCH over the expert
    # axis too — slot s lives on shard s // (max_seqs/ep), the page pools
    # shard over their block dim (P(expert, None, tensor, None)) and each
    # shard's tokens reach their experts through moe_ffn's two all_to_all
    # hops, so per-chip attention+FFN FLOPs divide by ep (a THROUGHPUT
    # lever, where plain --serve_ep only bought HBM). Host BlockTables
    # stay replicated numpy partitioned into ep page groups; allocation
    # never recompiles. Requires --serve_ep >= 1 with max_seqs and
    # num_blocks divisible by ep. ep_batch at ep=1 is pinned bit-identical
    # to the replicated-batch program; ep in {2,4} and ep x tp pinned
    # token-identical on CPU mesh emulation (tests/test_ep_batch_serve.py).
    # Prefix sharing composes group-locally (a cached page is only
    # physically present on its group's shard).
    ep_overlap: bool = False     # two-microbatch software pipelining of
    # the decode tick (ISSUE 16): the tick splits its slots into two
    # halves traced back-to-back in ONE dispatch, so microbatch B's
    # attention (page-local) has no data dependency on microbatch A's
    # expert-dispatch all_to_all and XLA's async collective scheduler can
    # overlap the two — the fabric hop hides behind compute. Outputs are
    # pinned bit-identical to the unsplit tick (attention is row-local,
    # inference MoE routing is no-drop per-token). Requires an even
    # per-shard slot count. Works with or without a mesh (off-mesh it is
    # a scheduling no-op but stays pinned, which is what the CPU tests
    # drive).
    moe_stats: bool = False      # accumulate MoE routing-load scalars
    # (valid/kept tokens vs the capacity_factor budget) into engine.stats
    # after every dispatch — the bench's capacity-utilization and
    # dropped-rate columns. Off by default: it adds per-tick host reads.
    prefix_cache: bool = False   # share prompt-prefix KV pages across
    # requests (serve/kv_cache.PrefixCache): refcounted page runs, CoW on
    # the first divergent write, LRU reclaim under pool pressure. Outputs
    # pinned identical to the unshared engine; only the physical page
    # count (and the prefill work for cache hits) changes. Composes with
    # MoE checkpoints: inference routing is no-drop per-token, so shared
    # prefix pages cannot change any expert assignment.
    speculate: str = ""          # '' = one token per decode tick;
    # '<drafter>:<k>' (ngram:4 | draft:2 ...) arms speculative decode
    # (serve/speculate.py): the drafter proposes up to k tokens per slot,
    # one batched verify dispatch scores them against this engine's model
    # on the paged cache, and the accepted prefix commits to the block
    # tables (rejected-tail pages roll back exactly). Outputs are pinned
    # identical to the non-speculative engine — greedy bit-identical,
    # sampled token-identical to the same per-request PRNG stream — the
    # knob only changes tokens per dispatch. 'draft:<k>' additionally
    # needs ServingEngine(draft_model=...).
    metrics: bool = False        # arm the request-lifecycle metrics plane
    # (serve/metrics.ServeMetrics): wall-clock TTFT / per-token sketches,
    # live gauges, drain-cadence journal events. Pinned INERT — token
    # streams are bit-identical with metrics on or off (the hooks ride
    # host work the tick already does; tests/test_serve_metrics.py).
    # Tick-domain request clocks (RequestTimes) run unconditionally —
    # they are integer bookkeeping and feed the response-record timing
    # columns even when the plane is off.
    retrace_guard: str = "warn"  # off | warn | error — the serve twin of
    # the trainer's --retrace_guard (ISSUE 19): every dispatch kind
    # (decode tick, prefill, verify, cow) hashes its operand signature
    # (rest-operand shapes/dtypes — params/pages are engine-owned stable
    # buffers) and carries a compile budget: 1 program each for
    # decode/verify/cow, one per power-of-two bucket for prefill. A
    # signature past the budget is a recompile about to happen — counted
    # as stats['serve_retraces'] + a warning, or a RuntimeError under
    # 'error' BEFORE jax pays for the lowering. Purely observational:
    # token streams are bit-identical to 'off' (the guard reads shapes,
    # never values; pinned by tests/test_serve_check.py).

    def resolved_num_blocks(self) -> int:
        return self.num_blocks or self.max_seqs * self.max_blocks_per_seq

    def bucket(self, n: int) -> int:
        """The padded length of an ``n``-token prefill: THE bucketing rule
        of this configuration (the engine's prefill, the draft mirror's and
        ``analysis/serve_check``'s compile budget all ask here)."""
        return bucket_tokens(n, self.block_size, self.max_blocks_per_seq,
                             self.prefill_top_bucket)


@dataclasses.dataclass
class Request:
    req_id: Any
    tokens: List[int]                    # prompt token ids (non-empty)
    max_new_tokens: Optional[int] = None  # None = engine default
    seed: int = 0
    prefix_group: Optional[str] = None   # optional routing/accounting tag
    # for requests sharing a prompt prefix (serve/api validates it
    # strictly and echoes it on the response); the prefix cache itself
    # matches by TOKENS, so the tag never changes what is shared
    committed: List[int] = dataclasses.field(default_factory=list)
    # tokens this request already generated on ANOTHER replica (the
    # migration path, serve/replica_plane): the engine prefills
    # tokens + committed as one history and resumes the request's pinned
    # sample stream at index len(committed) — the per-request PRNG keys
    # are fold_in(key(seed), token_index), so the continued stream is
    # token-identical to never having migrated, by construction
    deadline_s: Optional[float] = None   # wall-clock budget from submit;
    # an expired request is evicted with the honest 'timeout' status
    # (partial output attached), never silently dropped


@dataclasses.dataclass
class Completion:
    req_id: Any
    prompt_len: int
    tokens: List[int]    # generated ids (EOS included when emitted)
    reason: str          # eos | length | overflow | rejected | timeout
    #                      (| failed — replica_plane's retry-budget status)
    timing: Optional[Dict[str, Any]] = None  # tick-domain request clocks
    # (serve/metrics.RequestTimes): queue_ticks always, ttft_ticks /
    # decode_ticks once a first token existed, wall ttft_ms when the
    # metrics plane is on. Echoed on the serve/api response record for
    # EVERY terminal status — a timeout with no timing would be a
    # request whose queue wait silently vanished from the books.


@dataclasses.dataclass
class RecoveryRecord:
    """The minimal per-request state a survivor needs to continue a
    request token-identically after its replica dies: prompt + committed
    tokens + seed (+ the resolved budget and deadline). The pinned
    per-request PRNG stream (``_sample_rows``: fold_in(key(seed),
    token_index)) carries the rest — re-prefilling the committed history
    and resuming at token_index = len(committed) reproduces the exact
    stream the dead replica was emitting. Exported every tick by
    :meth:`ServingEngine.export_records`; the fleet
    (serve/replica_plane.ServingFleet) shadows these OUTSIDE the replica,
    so a crash never needs to ask the dead engine anything."""

    req_id: Any
    tokens: List[int]                    # the ORIGINAL prompt
    committed: List[int]                 # tokens generated so far
    seed: int
    budget: Optional[int]                # total max_new_tokens (resolved
    #                                      for resident slots)
    prefix_group: Optional[str] = None
    deadline_at: Optional[float] = None  # absolute time.monotonic() stamp
    #                                      — survives migration unmoved

    def to_request(self) -> "Request":
        return Request(req_id=self.req_id, tokens=list(self.tokens),
                       max_new_tokens=self.budget, seed=int(self.seed),
                       prefix_group=self.prefix_group,
                       committed=list(self.committed))

    @staticmethod
    def from_request(req: "Request", committed, budget,
                     deadline_at: Optional[float]) -> "RecoveryRecord":
        """The ONE construction site (engine slot/pending exports and the
        fleet's routing-time shadow all build records here, so a future
        field cannot silently miss one of them). ``req.tokens`` is shared,
        not copied: the prompt list is immutable after submit (nothing in
        the engine or fleet writes to it) and it dominates the per-tick
        shadow-refresh cost on long prompts; ``committed`` mutates every
        tick and is always copied."""
        return RecoveryRecord(
            req_id=req.req_id, tokens=req.tokens,
            committed=list(committed), seed=int(req.seed), budget=budget,
            prefix_group=req.prefix_group, deadline_at=deadline_at)


@dataclasses.dataclass
class _Slot:
    req: Request
    budget: int          # max new tokens for this request
    cache_len: int       # tokens whose k/v are in the pages once every
    #                      dispatch enqueued so far has run
    last_tok: int = 0    # newest token the host has READ: the speculative
    #                      tick's window starts from it; the plain tick
    #                      takes its last token on the device (``prev``)
    gen: List[int] = dataclasses.field(default_factory=list)  # tokens read
    unread: int = 0      # tokens dispatched whose read is outstanding (a
    #                      prefill's first, one decode tick's)
    done: bool = False   # its Completion is out; a row of it still
    #                      unread is dropped (an EOS the host could not
    #                      foresee)


# A decode-only tick (no prefill admitted in it or in the tick before: a
# prefill's device time lands in the read of the tick after it) is a SLOW
# tick when its wall is over SLOW_TICK_FACTOR times the median of the last
# SLOW_TICK_HISTORY such ticks' and over SLOW_TICK_MIN_S; judged once
# SLOW_TICK_MIN_TICKS of them are known.
SLOW_TICK_FACTOR, SLOW_TICK_MIN_S = 8.0, 0.05
SLOW_TICK_HISTORY, SLOW_TICK_MIN_TICKS = 256, 8


def _new_lap() -> dict:
    """One tick's host seconds by part, from clock reads alone: building
    the decode operands, waiting in reads, committing; how many reads, the
    longest of them (seconds, ``of``, ``tick``) and when the last ended."""
    return {"build": 0.0, "read": 0.0, "commit": 0.0, "reads": 0,
            "worst": (0.0, "", 0), "at": 0.0}


@dataclasses.dataclass
class _Unread:
    """A dispatch whose output vector the host has not read yet: the
    tokens of ``rows`` (``(slot, _Slot)``: token ``vec[slot]``) and the
    counters that ride behind them. It holds the ``_Slot`` objects
    themselves, because a slot whose end the host foresaw at dispatch has
    left ``engine.slots`` (and its pages) by the time its last token is
    read."""

    kind: str            # "prefill" | "decode"
    tick: int            # the tick whose dispatch made these tokens
    vec: Any             # device int32 [max_seqs + counters]
    st: Any              # capacity-routed MoE load scalars, or {}
    rows: List[tuple]
    fresh: bool = False  # a prefill that attended over its own fresh keys


def dispatch_signature(operands) -> tuple:
    """The retrace guard's operand signature: (shape, dtype) per rest
    operand — pure attribute reads (never values, never a device sync),
    so observing a dispatch costs nanoseconds on the common tick. Python
    scalars hash by type name (a scalar operand's jnp conversion always
    lands the same weak dtype for the same Python type); a bool is a
    STATIC argument of its dispatch (the prefill's ``fresh``) and its value
    picks the program, so it counts by value."""
    return tuple(
        ((), f"static {a}") if isinstance(a, bool) else
        (tuple(getattr(a, "shape", ())),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in operands)


class _RetraceGuard:
    """Tick-level recompile sentinel (``ServeConfig.retrace_guard`` —
    the serving twin of train/loop's --retrace_guard, ISSUE 19). Each
    dispatch kind carries a compile BUDGET (decode/verify/cow: one
    program; prefill: one per power-of-two bucket — the engine's own
    O(log max) compile claim). The first ``budget`` distinct operand
    signatures are the legal specializations; any later NEW signature is
    a recompile the design forbids — counted into
    ``stats['serve_retraces']`` and warned once per signature, or raised
    under ``error`` BEFORE jax pays for the lowering."""

    def __init__(self, mode: str, budgets: Dict[str, int],
                 stats: Dict[str, Any],
                 program_of: Callable[[str], str] = str):
        self.mode = mode
        self.budgets = budgets
        self.stats = stats
        self.program_of = program_of  # dispatch kind -> compile-ledger name
        self.seen: Dict[str, set] = {}

    def observe(self, kind: str, operands) -> None:
        sig = dispatch_signature(operands)
        seen = self.seen.setdefault(kind, set())
        if sig in seen:
            return
        budget = self.budgets.get(kind, 1)
        if len(seen) < budget:
            seen.add(sig)
            return
        program = self.program_of(kind)
        msg = (f"serve retrace guard: dispatch {kind!r} (program "
               f"{program!r}, built {compile_cache.compiles_of(program)} "
               f"time(s) so far) saw a new operand "
               f"signature past its compile budget ({budget}) — a "
               f"recompile the serving design forbids; new signature: "
               f"{sig}")
        if self.mode == "error":
            raise RuntimeError(msg)
        seen.add(sig)
        self.stats["serve_retraces"] = self.stats.get("serve_retraces", 0) + 1
        import warnings

        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _dense_fresh(cfg, kv_heads: int) -> tuple:
    """``ServeModel.fresh_prefill`` of GPT-2 and Llama: the tiled forward
    kernel's name and its rule for one shard's heads."""
    from distributed_lion_tpu.ops.attention import fresh_kernel_applies

    return "flash_gqa_fwd", lambda bucket, shards: fresh_kernel_applies(
        bucket, cfg.n_head // shards, kv_heads // shards, cfg.head_dim,
        cfg.compute_dtype)


def _latent_fresh(cfg) -> tuple:
    """``ServeModel.fresh_prefill`` of a family whose layers are
    ``models/joyai._mla_block`` with no ``keys`` of their own: the kernel's
    name and the block's own rule, from the head widths alone (the engine
    shards no such family)."""
    from distributed_lion_tpu.ops.attention import latent_fresh_applies

    return "latent_prefill", lambda bucket, shards: latent_fresh_applies(
        bucket, cfg.qk_nope_head_dim, cfg.v_head_dim)


class ServeModel:
    """Family adapter: the paged decode hook + cache geometry the engine
    needs, built from a (params, config) pair. ``decode_paged(params,
    tokens, pages, tables, pos, valid, tp_axis, ep_axis,
    return_moe_stats)`` must return ``(logits [B,S,V] f32, pages')``
    (plus a MoE routing-stats dict when requested) —
    models/gpt2.gpt2_decode_paged and models/llama.llama_decode_paged
    are the two implementations; with ``tp_axis``/``ep_axis`` the call
    runs inside the engine's shard_map and the hook threads the axes
    into the model's Megatron-split blocks / expert banks. A family with
    ``window_layers`` is also handed ``slots`` [B] int32, the slot each row
    owns: its window layers' rings are found from that alone; so is a
    family with ``state_layers``."""

    def __init__(self, family: str, cfg: Any, params: Any,
                 decode_paged: Callable, n_layer: int, kv_heads: int,
                 head_dim: int, cache_dtype: Any,
                 max_positions: Optional[int] = None,
                 page_leaves: Optional[Dict[str, tuple]] = None,
                 kernel_stat: str = "decode_attn_kernel_ticks",
                 moe_counters: tuple = (), last_logit: bool = False,
                 shardable: bool = True, window: int = 0,
                 window_layers: tuple = (), state_layers: tuple = (),
                 state_leaves: Optional[Dict[str, tuple]] = None,
                 setup_note: str = "",
                 fresh_prefill: Optional[tuple] = None,
                 page_run: int = 0,
                 window_leaves: Optional[Dict[str, tuple]] = None,
                 index_leaves: tuple = ()):
        self.family = family
        self.cfg = cfg
        self.params = params
        self.decode_paged = decode_paged
        self.n_layer = n_layer
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.cache_dtype = cache_dtype
        # what one layer of the page pool holds, ``{leaf: (heads, width)}``
        # (serve/kv_cache.init_page_leaves): keys and values a kv head
        # unless the family says otherwise (a latent cache: one leaf, one
        # row a token)
        self.page_leaves = page_leaves or {"k": (kv_heads, head_dim),
                                           "v": (kv_heads, head_dim)}
        # the engine.stats counter of decode ticks that ran the family's
        # in-place attention kernel
        self.kernel_stat = kernel_stat
        # names of the int32 routing counters the hook returns (a dict)
        # under ``return_moe_stats``; the engine packs them behind the
        # sampled tokens so that they ride the one transfer a dispatch
        # already makes
        self.moe_counters = tuple(moe_counters)
        # the hook takes ``logit_index`` and returns logits of that one
        # position ``[B, 1, V]``: a 2,048-token prefill over a 129,280-row
        # head would otherwise hold 1 GB of float32 logits to read one row
        self.last_logit = last_logit
        # the hook takes ``fresh`` (static): the engine says at dispatch
        # that a prefill starts at position 0, and the hook may then attend
        # over the keys it has just projected instead of gathering them back
        # out of the pool (ops/attention.fresh_causal_attention). A family
        # whose hook already reads S > 1 as "from 0" has no use for it
        # (None). Else ``(the kernel's name, rule(bucket, tensor shards) ->
        # bool)``: which buckets' prefills from 0 take it
        self.fresh_prefill = fresh_prefill
        # False: no tensor / expert sharding and no quantized weights here
        self.shardable = shardable
        # layers that see only the last ``window`` positions keep a bounded
        # ring a slot beside the growing pages (ops/attention.ring_pages);
        # the hook then takes ``slots`` [B], the slot each row owns, which
        # is all a ring's page ids are made of
        self.window = window
        self.window_layers = tuple(window_layers)
        # what a window layer's ring holds where that differs from
        # ``page_leaves`` (latent rows of the window layers' own width)
        self.window_leaves = dict(window_leaves or {})
        # the page leaves that hold a learned indexer's keys, a row a
        # position beside the rows they index (ops/dsa)
        self.index_leaves = tuple(index_leaves)
        # layers that carry a recurrent state a slot instead of a cache by
        # position: ``{leaf: (shape, dtype)}`` a slot
        # (serve/kv_cache.init_state_leaves); the hook takes ``slots`` for
        # them too
        self.state_layers = tuple(state_layers)
        self.state_leaves = dict(state_leaves or {})
        # positions the family's reader copies as one (a selection block):
        # the engine mints the pages that hold them as an aligned run of
        # the pool, ``page_run // block_size`` pages
        # (serve/kv_cache.BlockTables ``run_pages``), which is what makes
        # them one contiguous slab of every leaf. 0: by the page. From the
        # family's own config, never a user's knob
        self.page_run = page_run
        # the family's own ``[setup]`` line, printed at engine build with
        # ``{slots}`` and ``{state_gb}`` filled in
        self.setup_note = setup_note
        # the model's position budget (gpt2: learned wpe rows; llama's
        # rope extrapolates but n_ctx is still the trained horizon) — the
        # engine refuses a page geometry that would silently alias/exceed
        self.max_positions = max_positions

    def param_specs(self, tensor: bool = True) -> dict:
        """The Megatron PartitionSpec tree for this family — ONE source of
        truth with the trainer (parallel/tensor_parallel and, for MoE
        checkpoints, models/gpt2.gpt2_moe_param_specs which reuses it), so
        serving and training can never shard the same checkpoint
        differently. ``tensor=False`` (an expert-only serving mesh) keeps
        attention/dense-MLP leaves replicated and shards only the expert
        banks over the expert axis."""
        if self.family == "gpt2" and getattr(self.cfg, "moe_experts", 0) > 0:
            from distributed_lion_tpu.models.gpt2 import gpt2_moe_param_specs

            return gpt2_moe_param_specs(self.cfg, tensor=tensor)
        from distributed_lion_tpu.parallel.tensor_parallel import (
            gpt2_param_specs,
            llama_param_specs,
        )

        fn = gpt2_param_specs if self.family == "gpt2" else llama_param_specs
        return fn(self.cfg)

    @staticmethod
    def for_gpt2(params: Any, cfg: Any) -> "ServeModel":
        from distributed_lion_tpu.models.gpt2 import gpt2_decode_paged

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, fresh=False):
            return gpt2_decode_paged(p, toks, cfg, pages, tables, pos,
                                     valid, tp_axis, ep_axis,
                                     return_moe_stats, stats_axis,
                                     stats_lanes, fresh)

        return ServeModel("gpt2", cfg, params, decode, cfg.n_layer,
                          cfg.n_head, cfg.head_dim, cfg.compute_dtype,
                          max_positions=cfg.n_ctx,
                          fresh_prefill=_dense_fresh(cfg, cfg.n_head))

    @staticmethod
    def for_llama(params: Any, cfg: Any) -> "ServeModel":
        from distributed_lion_tpu.models.llama import llama_decode_paged

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, fresh=False):
            # llama has no MoE blocks; the engine refuses --serve_ep for
            # it at build, so these can never be set here
            assert ep_axis is None and not return_moe_stats
            assert stats_axis is None and stats_lanes is None
            return llama_decode_paged(p, toks, cfg, pages, tables, pos,
                                      valid, tp_axis, fresh)

        return ServeModel("llama", cfg, params, decode, cfg.n_layer,
                          cfg.n_kv_head, cfg.head_dim, cfg.compute_dtype,
                          max_positions=cfg.n_ctx,
                          fresh_prefill=_dense_fresh(cfg, cfg.n_kv_head))

    @staticmethod
    def for_joyai(params: Any, cfg: Any) -> "ServeModel":
        """JoyAI-LLM-Flash (models/joyai): latent attention over ONE page
        leaf a layer (``[c_kv | k_rope]``, 576 values in 640 lanes at the
        published widths) and top-k dropless experts with a shared one."""
        from distributed_lion_tpu.models.joyai import (
            MOE_COUNTERS,
            joyai_decode_paged,
        )

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, logit_index=None, fresh=False):
            # the engine refuses tp / ep for this family at build
            assert tp_axis is None and ep_axis is None and stats_axis is None
            return joyai_decode_paged(p, toks, cfg, pages, tables, pos,
                                      valid, return_moe_stats, logit_index,
                                      fresh)

        return ServeModel(
            "joyai", cfg, params, decode, cfg.n_layer, 1, cfg.latent_dim,
            cfg.compute_dtype, max_positions=cfg.n_ctx,
            page_leaves={"kv": (1, cfg.latent_dim)},
            kernel_stat="mla_kernel_ticks", moe_counters=MOE_COUNTERS,
            last_logit=True, shardable=False,
            fresh_prefill=_latent_fresh(cfg))

    @staticmethod
    def for_xing(params: Any, cfg: Any) -> "ServeModel":
        """Xing4.0 (models/xing): JoyAI's latent page leaf and expert
        layer round a residual of ``hc_mult`` mixed streams, which never
        reaches the cache (``ops/mhc``); the mix's counters ride behind the
        expert layer's."""
        from distributed_lion_tpu.models.xing import (
            XING_COUNTERS,
            xing_decode_paged,
        )

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, logit_index=None, fresh=False):
            # the engine refuses tp / ep for this family at build
            assert tp_axis is None and ep_axis is None and stats_axis is None
            return xing_decode_paged(p, toks, cfg, pages, tables, pos,
                                     valid, return_moe_stats, logit_index,
                                     fresh)

        return ServeModel(
            "xing", cfg, params, decode, cfg.n_layer, 1, cfg.latent_dim,
            cfg.compute_dtype, max_positions=cfg.n_ctx,
            page_leaves={"kv": (1, cfg.latent_dim)},
            kernel_stat="mla_kernel_ticks", moe_counters=XING_COUNTERS,
            last_logit=True, shardable=False,
            fresh_prefill=_latent_fresh(cfg),
            setup_note=(
                f"residual: {cfg.hc_mult} mixed streams of {cfg.d_model} "
                f"(mHC, {cfg.hc_sinkhorn_iters} Sinkhorn steps a token a "
                f"sublayer), {2 * cfg.n_layer} sublayers"))

    @staticmethod
    def for_laguna(params: Any, cfg: Any) -> "ServeModel":
        """Laguna (models/laguna): window and full GQA layers with their
        own head counts over one pool list, a bounded ring a slot for the
        window layers beside the full layers' growing pages, and dropless
        experts told which they hold."""
        from distributed_lion_tpu.models.laguna import (
            LAGUNA_COUNTERS,
            laguna_decode_paged,
        )

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, logit_index=None, slots=None):
            # the engine refuses tp / ep for this family at build
            assert tp_axis is None and ep_axis is None and stats_axis is None
            return laguna_decode_paged(
                p, toks, cfg, pages, tables, slots, pos, valid,
                return_moe_stats, logit_index)

        return ServeModel(
            "laguna", cfg, params, decode, cfg.n_layer, cfg.n_kv_head,
            cfg.head_dim, cfg.compute_dtype, max_positions=cfg.n_ctx,
            moe_counters=LAGUNA_COUNTERS, last_logit=True, shardable=False,
            window=cfg.window, window_layers=cfg.window_layers)

    @staticmethod
    def for_dots3(params: Any, cfg: Any) -> "ServeModel":
        """dots3-note (models/dots3): latent attention of two geometries in
        one pool list. A full layer's latent rows live in pages with an
        index key a position beside them (leaf ``ik``: the learned
        indexer's, ``ops/dsa``), minted in aligned runs of the decode
        walk's copy; a sliding layer's latent rows, of another width, in a
        bounded ring a slot; dropless experts told which they hold."""
        from distributed_lion_tpu.models.dots3 import (
            DOTS3_COUNTERS,
            dots3_decode_paged,
        )

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, logit_index=None, slots=None):
            # the engine refuses tp / ep for this family at build
            assert tp_axis is None and ep_axis is None and stats_axis is None
            return dots3_decode_paged(
                p, toks, cfg, pages, tables, slots, pos, valid,
                return_moe_stats, logit_index)

        return ServeModel(
            "dots3", cfg, params, decode, cfg.n_layer, 1,
            cfg.full.latent_dim, cfg.compute_dtype, max_positions=cfg.n_ctx,
            page_leaves={"kv": (1, cfg.full.latent_dim),
                         "ik": (1, cfg.index_head_dim)},
            kernel_stat="mla_kernel_ticks", moe_counters=DOTS3_COUNTERS,
            last_logit=True, shardable=False, window=cfg.window,
            window_layers=cfg.window_layers,
            window_leaves={"kv": (1, cfg.swa.latent_dim)},
            index_leaves=("ik",), page_run=cfg.page_run,
            setup_note=(
                f"indexer: top {cfg.index_topk} positions a query, "
                f"{cfg.index_n_heads} heads of {cfg.index_head_dim}, ik a "
                f"position in {len(cfg.full_layers)} full layers; latent "
                f"rings of {cfg.window} in {len(cfg.window_layers)} sliding "
                "layers x {slots} slots"))

    @staticmethod
    def for_ling(params: Any, cfg: Any) -> "ServeModel":
        """Ling 3.0 flash (models/ling): five KDA layers to one MLA layer.
        The MLA layers' latent rows live in pages (JoyAI's leaf), a KDA
        layer's recurrent state and convolution tail in slot-indexed leaves
        beside them; group-limited dropless experts told which they hold."""
        import jax.numpy as jnp

        from distributed_lion_tpu.models.ling import (
            LING_COUNTERS,
            ling_decode_paged,
        )

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, logit_index=None, slots=None,
                   fresh=False):
            # the engine refuses tp / ep for this family at build
            assert tp_axis is None and ep_axis is None and stats_axis is None
            return ling_decode_paged(
                p, toks, cfg, pages, tables, slots, pos, valid,
                return_moe_stats, logit_index, fresh)

        H, hd = cfg.n_head, cfg.head_dim
        return ServeModel(
            "ling", cfg, params, decode, cfg.n_layer, 1, cfg.latent_dim,
            cfg.compute_dtype, max_positions=cfg.n_ctx,
            page_leaves={"kv": (1, cfg.latent_dim)},
            kernel_stat="mla_kernel_ticks", moe_counters=LING_COUNTERS,
            last_logit=True, shardable=False, state_layers=cfg.kda_layers,
            fresh_prefill=_latent_fresh(cfg),
            state_leaves={
                "state": ((H, hd, hd), jnp.float32),
                "conv": ((cfg.conv_width - 1, cfg.conv_channels),
                         cfg.compute_dtype)})


    @staticmethod
    def for_minicpm_sala(params: Any, cfg: Any) -> "ServeModel":
        """MiniCPM-SALA (models/minicpm_sala): block-sparse attention
        layers whose keys and values live in pages with one compressed key a
        page beside them (``ck``: a page's id and lifetime, one row a stride), among
        Lightning linear-attention layers that keep a float32 state a
        slot."""
        import jax.numpy as jnp

        from distributed_lion_tpu.models.minicpm_sala import (
            SALA_COUNTERS,
            minicpm_sala_decode_paged,
        )

        def decode(p, toks, pages, tables, pos, valid=None, tp_axis=None,
                   ep_axis=None, return_moe_stats=False, stats_axis=None,
                   stats_lanes=None, logit_index=None, slots=None):
            # the engine refuses tp / ep for this family at build
            assert tp_axis is None and ep_axis is None and stats_axis is None
            return minicpm_sala_decode_paged(
                p, toks, cfg, pages, tables, slots, pos, valid,
                return_moe_stats, logit_index)

        H, KV, hd, sp = cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.sparse
        return ServeModel(
            "minicpm_sala", cfg, params, decode, cfg.n_layer, KV, hd,
            cfg.compute_dtype, max_positions=cfg.n_ctx,
            page_leaves={"k": (KV, hd), "v": (KV, hd), "ck": (KV, hd, sp.kernel_stride)},
            moe_counters=SALA_COUNTERS, last_logit=True, shardable=False,
            state_layers=cfg.lightning_layers,
            state_leaves={"state": ((H, hd, hd), jnp.float32)},
            page_run=sp.block_size,
            setup_note=(
                f"sparse: top {sp.topk} of blocks of {sp.block_size}, dense "
                f"to {sp.dense_len}, ck a page; lightning: "
                f"{len(cfg.lightning_layers)} state layers x {{slots}} "
                "slots, {state_gb:.2f} GB"))


def weight_bytes(params: Any) -> int:
    """Actual storage bytes of a (possibly quantized) weight tree —
    QuantizedTensor leaves count packed codes + absmax scales, dense
    leaves their array bytes. The bench's NF4-vs-bf16 column."""
    import jax

    from distributed_lion_tpu.ops.quant import QuantizedTensor

    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            total += leaf.codes.size * leaf.codes.dtype.itemsize
            total += leaf.absmax.size * leaf.absmax.dtype.itemsize
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


def _sample_rows(logits, seeds, counts, temperature: float,
                 top_k: Optional[int], top_p: Optional[float]):
    """[B, V] logits → [B] tokens with PER-ROW keys derived from
    (request seed, generated-token index) — slot- and batch-independent
    draws (see module doc). Greedy when ``temperature == 0``."""
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.models.generate import filter_logits

    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    filtered = filter_logits(logits, temperature, top_k, top_p)
    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.key(s), c))(seeds, counts)
    return jax.vmap(jax.random.categorical)(keys, filtered)


def _flat_leaves(tree, is_leaf=None):
    import jax

    return jax.tree.flatten(tree, is_leaf=is_leaf)


def _shard_params(params: Any, specs: Any, mesh) -> Any:
    """Place a (possibly NF4/int8-quantized) weight tree onto the TP mesh
    per its Megatron PartitionSpec tree. Quantized leaves shard with the
    SAME spec as their dense twin: the shaped layout keeps every leading
    dim 1:1 with the dense weight and blocks run along the last dim only
    (ops/quant), so codes and absmax both slice cleanly."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from distributed_lion_tpu.ops.quant import QuantizedTensor

    leaves, treedef = _flat_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    spec_leaves, _ = _flat_leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    assert len(leaves) == len(spec_leaves), \
        "param tree and spec tree disagree"

    def place(w, spec):
        s = NamedSharding(mesh, spec)
        if isinstance(w, QuantizedTensor):
            return QuantizedTensor(jax.device_put(w.codes, s),
                                   jax.device_put(w.absmax, s),
                                   w.shape, w.fmt, w.block, w.layout)
        return jax.device_put(w, s)

    return jax.tree.unflatten(
        treedef, [place(w, s) for w, s in zip(leaves, spec_leaves)])


class ServingEngine:
    """See module doc. Host-side driver: ``submit`` requests, call
    ``step()`` per tick (or ``run()`` to drain a workload), collect
    :class:`Completion`s."""

    def __init__(self, model: ServeModel, cfg: ServeConfig,
                 draft_model: Optional[ServeModel] = None,
                 time_fn: Callable[[], float] = time.monotonic):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.model = model
        self.cfg = cfg
        # the injectable clock (graft-check DLT011): every wall-clock
        # read in the engine goes through ``self._now`` so deadline /
        # latency behavior is testable without real sleeps; the metrics
        # plane (when armed) shares the same clock
        self._now = time_fn
        # the span gate's profiler, the collector's hook and the compile
        # ledger's listeners (all idempotent)
        journal.register_profiler(jax.profiler.TraceAnnotation)
        journal.watch_gc()
        compile_cache.listen()
        setup = journal.SetupLaps("engine")
        params = model.params
        if cfg.quant not in ("none", "nf4", "int8"):
            raise ValueError(f"unknown quant mode {cfg.quant!r}")
        if cfg.prefill_top_bucket % cfg.block_size:
            raise ValueError(
                f"prefill_top_bucket {cfg.prefill_top_bucket} is not whole "
                f"pages of {cfg.block_size}")
        if cfg.retrace_guard not in ("off", "warn", "error"):
            raise ValueError(
                f"unknown retrace_guard mode {cfg.retrace_guard!r} "
                "(off | warn | error)")
        # what a cache that is found by the slot cannot serve under, by its
        # kind, each with its own sentence
        flags = ((cfg.prefix_cache, "--prefix_cache"),
                 (cfg.speculate, "--speculate"), (cfg.tp, "--serve_tp"),
                 (cfg.ep, "--serve_ep"))
        no_exchange = ("its expert layer is told the one range it holds; "
                       "the exchange between ranges is not built")
        for layers, keeps, whys in (
                (model.index_leaves,
                 f"keeps an index key a position (leaf "
                 f"{'/'.join(model.index_leaves)!r}) beside its latent pages "
                 f"(leaf 'kv') and a ring of {model.window} latent rows a "
                 "slot for its window layers",
                 ("a shared page's index keys would be shared with it and "
                  "nothing copies them on write yet, and a shared prefix's "
                  "pages say nothing of the window layers' rings",
                  "a rejected draft cannot be rolled back out of a ring that "
                  "has overwritten its oldest page",
                  "neither the index-key leaf nor the ring leaves have a "
                  "sharding spec", no_exchange)),
                (model.window_layers,
                 f"keeps a ring of {model.window} positions a slot for its "
                 "window layers",
                 ("a shared prefix's pages say nothing of the window layers' "
                  "rings, which would have to be rebuilt for every sharer",
                  "a rejected draft cannot be rolled back out of a ring that "
                  "has overwritten its oldest page",
                  "its query heads differ by layer and the ring leaves have "
                  "no sharding spec", no_exchange)),
                (model.state_layers,
                 f"carries a recurrent state a slot in "
                 f"{len(model.state_layers)} of its layers",
                 ("a shared prefix's pages say nothing of the state the "
                  "prefix leaves behind, and a state has no pages to share",
                  "a rejected draft cannot be rolled back out of a state that "
                  "has already decayed and been written",
                  "the state leaves have no sharding spec", no_exchange)),
                (tuple(n for n, hw in model.page_leaves.items()
                       if len(hw) > 2),
                 "keeps a compressed key a page beside its keys and values",
                 ("a shared page's compressed key would be shared with it, "
                  "and nothing copies it on write yet",
                  "a rejected draft's position may have closed a window "
                  "whose compressed key is already written",
                  "the compressed-key leaf has no sharding spec",
                  no_exchange))):
            for (on, flag), why in zip(flags, whys):
                if on and layers:
                    raise ValueError(
                        f"family {model.family!r} {keeps} and does not serve "
                        f"under {flag}: {why} (ROADMAP Reach)")
        if not model.shardable and (cfg.tp or cfg.ep or cfg.ep_overlap
                                    or cfg.quant != "none"):
            raise ValueError(
                f"family {model.family!r} serves on one device, unquantized "
                f"(got --serve_tp {cfg.tp}, --serve_ep {cfg.ep}, "
                f"--serve_ep_overlap {cfg.ep_overlap}, --quant {cfg.quant}): "
                "its latent page leaf has no kv-head axis to shard and its "
                "expert layer holds every expert (ROADMAP Reach)")
        if cfg.quant != "none":
            from distributed_lion_tpu.ops.quant import quantize_tree

            params = quantize_tree(params, cfg.quant,
                                   block=cfg.quant_block)
        horizon = cfg.block_size * cfg.max_blocks_per_seq
        if model.max_positions is not None and horizon > model.max_positions:
            raise ValueError(
                f"page geometry allows {horizon} tokens/seq but the model's "
                f"position budget is {model.max_positions} (n_ctx); shrink "
                "--block_size/--max_blocks_per_seq — positions past the "
                "trained horizon would silently alias")

        # ---- tensor/expert-parallel mesh (tp=0, ep=0: the single-device
        # program, bit for bit)
        self._mesh = None
        self._param_specs = None
        self._pages_spec = None
        self._tp_axis = TENSOR_AXIS if cfg.tp else None
        self._ep_axis = EXPERT_AXIS if cfg.ep else None
        self._ep_batch = bool(cfg.ep_batch)
        self._ep_overlap = bool(cfg.ep_overlap)
        self._moe_stats = bool(cfg.moe_stats
                               and getattr(model.cfg, "moe_experts", 0) > 0)
        if cfg.ep_batch:
            if not cfg.ep:
                raise ValueError(
                    "--serve_ep_batch shards the decode batch over the "
                    "expert axis — it needs --serve_ep >= 1")
            if cfg.max_seqs % cfg.ep:
                raise ValueError(
                    f"--serve_ep_batch needs max_seqs ({cfg.max_seqs}) "
                    f"divisible by --serve_ep {cfg.ep}: slots partition "
                    "evenly over the expert shards")
            if cfg.resolved_num_blocks() % cfg.ep:
                raise ValueError(
                    f"--serve_ep_batch needs num_blocks "
                    f"({cfg.resolved_num_blocks()}) divisible by "
                    f"--serve_ep {cfg.ep}: the page pool shards over its "
                    "block dim")
        groups = cfg.ep if cfg.ep_batch else 1
        if cfg.ep_overlap:
            local_slots = cfg.max_seqs // groups
            if local_slots % 2 or local_slots < 2:
                raise ValueError(
                    f"--serve_ep_overlap splits each shard's "
                    f"{local_slots} decode slots into two microbatches — "
                    "the per-shard slot count must be even (and >= 2)")
        pages_sharding = None
        if cfg.ep:
            n_experts = getattr(model.cfg, "moe_experts", 0)
            if n_experts <= 0:
                raise ValueError(
                    f"--serve_ep {cfg.ep} needs a MoE checkpoint "
                    "(moe_experts > 0): the expert axis shards expert FFN "
                    "banks — dense checkpoints shard with --serve_tp")
            if n_experts % cfg.ep:
                raise ValueError(
                    f"moe_experts ({n_experts}) not divisible by "
                    f"--serve_ep {cfg.ep}: the expert banks shard over "
                    "the expert axis")
        if cfg.tp or cfg.ep:
            from distributed_lion_tpu.parallel.mesh import make_mesh

            if cfg.tp:
                from distributed_lion_tpu.parallel.tensor_parallel import (
                    validate_tp,
                )

                validate_tp(model.cfg, cfg.tp, model.family)
                if model.kv_heads % cfg.tp:
                    raise ValueError(
                        f"kv heads ({model.kv_heads}) not divisible by "
                        f"--serve_tp {cfg.tp}: the page pool shards over "
                        "the kv-head axis")
            devices = jax.devices()
            need = max(cfg.tp, 1) * max(cfg.ep, 1)
            if len(devices) < need:
                raise ValueError(
                    f"--serve_tp {cfg.tp} x --serve_ep {cfg.ep} needs "
                    f"{need} devices, backend has {len(devices)}")
            self._mesh = make_mesh(data=1, tensor=max(cfg.tp, 1),
                                   expert=max(cfg.ep, 1),
                                   devices=devices[:need])
            specs = model.param_specs(tensor=bool(cfg.tp))
            if cfg.quant != "none":
                from distributed_lion_tpu.ops.quant import validate_quant_tp

                if cfg.tp:
                    validate_quant_tp(params, specs, cfg.tp, TENSOR_AXIS)
                if cfg.ep > 1:
                    # expert banks shard their LEADING dim — the shaped
                    # quant layout keeps leading dims 1:1 with the dense
                    # weight, so the same validator covers the expert axis
                    validate_quant_tp(params, specs, cfg.ep, EXPERT_AXIS)
            params = _shard_params(params, specs, self._mesh)
            self._param_specs = specs
            # batch-sharded ep additionally shards the pool over its
            # BLOCK dim (each shard holds its slot group's pages); the
            # kv-head axis stays tensor-sharded either way
            pool_spec = (P(EXPERT_AXIS, None, TENSOR_AXIS, None)
                         if cfg.ep_batch
                         else P(None, None, TENSOR_AXIS, None))
            self._pages_spec = [{name: pool_spec
                                 for name in model.page_leaves}
                                for _ in range(model.n_layer)]
            pages_sharding = NamedSharding(self._mesh, pool_spec)
        self.params = params
        setup.lap("setup/place_weights")  # quantize, mesh, shard

        # the family's run in pages (a page that does not divide it is the
        # family's reader's to refuse, in its own words)
        run, rest = divmod(model.page_run, cfg.block_size)
        self.tables = BlockTables(
            cfg.resolved_num_blocks(), cfg.block_size, cfg.max_seqs,
            cfg.max_blocks_per_seq, groups=groups,
            run_pages=1 if rest else max(run, 1))
        # window layers: a ring of fixed pages a slot, owned for good and
        # never counted against num_blocks (admission sees full-layer
        # pages only); the dispatches name a row's ring by its slot id
        from distributed_lion_tpu.ops.attention import ring_pages

        self._windowed = bool(model.window_layers)
        # the dispatches name a row's ring or state by its slot id
        self._slotted = self._windowed or bool(model.state_layers)
        self.pages = init_page_leaves(
            model.n_layer, cfg.resolved_num_blocks(), cfg.block_size,
            model.page_leaves, model.cache_dtype, groups=max(cfg.tp, 1),
            # (a family whose rings hold leaves of their own says which;
            # every other family's call is the one it was)
            ring=(model.window_layers, cfg.max_seqs * ring_pages(
                model.window, cfg.block_size) if self._windowed else 0)
            + ((model.window_leaves,) if model.window_leaves else ()),
            # state layers: slot-indexed leaves in that layer's place,
            # never counted against num_blocks either
            state=(model.state_layers, cfg.max_seqs, model.state_leaves))
        if pages_sharding is not None:
            self.pages = [
                {k: jax.device_put(v, pages_sharding)
                 for k, v in layer.items()} for layer in self.pages]
        # one PrefixCache per pool group (sharing is group-local under
        # batch-sharded ep: a cached page is physically present only on
        # its group's shard); ``self.prefix`` stays the groups==1 alias
        # the existing tests/bench read
        if cfg.prefix_cache:
            if self.tables.groups == 1:
                self._prefix_caches = [PrefixCache(self.tables)]
            else:
                self._prefix_caches = [PrefixCache(self.tables, g)
                                       for g in range(self.tables.groups)]
            self.prefix = self._prefix_caches[0]
        else:
            self._prefix_caches = None
            self.prefix = None
        setup.lap("setup/init_pages")  # tables, the page pool, prefix caches
        self.slots: List[Optional[_Slot]] = [None] * cfg.max_seqs
        self.pending: deque = deque()
        # req_id -> absolute time.monotonic() deadline (requests with a
        # deadline_s, or an inherited stamp from a pre-migration submit)
        self._deadline_at: Dict[Any, float] = {}
        self.stats = {"ticks": 0, "decode_ticks": 0, "prefill_dispatches": 0,
                      # of them, those that started at position 0 in a
                      # bucket the tiled forward kernel takes: they attended
                      # over their own fresh keys and gathered no page
                      # (every such prefill on a TPU, none on the CPU)
                      "prefill_fresh_dispatches": 0,
                      "decode_tokens": 0, "prefill_tokens": 0,
                      "padded_prefill_tokens": 0, "evictions": 0,
                      "freed_pages": 0, "timeouts": 0, "resumed_requests": 0,
                      "resumed_tokens": 0,
                      # ticks whose decode program holds the Mosaic kernel
                      # (every decode tick on a TPU, none on the CPU), and
                      # the pages their rows' lengths need against the
                      # tables' whole width, which the gather path reads
                      model.kernel_stat: 0, "kv_pages_read": 0,
                      "kv_pages_table": 0,
                      # decode ticks enqueued while a read was outstanding,
                      # reads forced before their turn (``serve/drain``
                      # names why), and rows dropped because their slot had
                      # sampled EOS in the tick before
                      "run_ahead_ticks": 0, "run_ahead_drains": 0,
                      "run_ahead_discarded": 0,
                      # always on, from host clock reads alone (module
                      # doc, "Always-on accounts"): seconds the host was
                      # blocked in its reads, the process's collections
                      # as of the last tick (``journal.gc_totals``), and
                      # the decode-only ticks that stalled
                      "read_wait_s": 0.0, "gc_pause_s": 0.0,
                      "gc_collections": 0, "slow_ticks": 0,
                      "slow_tick_excess_s": 0.0}
        self._lap = _new_lap()         # this tick's seconds by part
        self._walls: deque = deque(maxlen=SLOW_TICK_HISTORY)
        self._prev_prefills = 0        # prefills the tick before admitted
        self._slow_open: List[dict] = []   # slow ticks whose next reads
        # are still to come
        from distributed_lion_tpu.ops.attention import paged_kernel_applies

        paged = next(i for i in range(model.n_layer)
                     if i not in model.state_layers)
        nb, bs, _, width = next(iter(self.pages[paged].values())).shape
        self._decode_kernel = paged_kernel_applies(
            1, (nb // groups, bs, 1, width),  # one shard's share of the pool
            model.cache_dtype)
        # the prefill buckets whose program attends over fresh keys when
        # the host says the prefill starts at 0 (one shard's heads)
        self._fresh_buckets = frozenset(
            b for b in self._buckets() if model.fresh_prefill
            and model.fresh_prefill[1](b, max(cfg.tp, 1)))
        if self._windowed:
            # ticks whose walk over the ring ran the kernel. The pages
            # those walks were handed, ``kv_window_pages_read`` (ONE window
            # layer's; at most ring_pages a row, where a full layer reads
            # them all), is counted in the program from the kernel's own
            # operands and rides with ``model.moe_counters``
            self.stats["window_kernel_ticks"] = 0
        if model.state_layers:
            # rows the decode ticks stepped (live slots x state layers),
            # slots whose state an admission's prefill started afresh, and
            # what the state leaves hold
            self.stats.update(
                state_rows_stepped=0, state_resets=0,
                state_bytes=sum(
                    int(v.size) * v.dtype.itemsize
                    for i in model.state_layers
                    for v in self.pages[i].values()))
        if self.prefix is not None:
            self.stats.update(prefix_hits=0, shared_tokens=0, cow_copies=0,
                              reclaimed_pages=0)
        if self._moe_stats:
            # routing load vs the capacity_factor budget (moe_ffn stats;
            # serving itself never drops — inference routing is no-drop)
            self.stats.update(moe_valid_tokens=0.0, moe_kept_tokens=0.0,
                              moe_capacity_slots=0.0)
        # a dropless expert layer has no capacity to report against: its
        # int32 counters (assignments, distinct experts hit, the largest
        # load at one expert) are packed behind the sampled tokens, decode
        # and prefill dispatches apart
        self._moe_counters = (model.moe_counters if cfg.moe_stats else ())
        for prefix in ("moe_", "moe_prefill_"):
            self._absorb_counters([0] * len(self._moe_counters), prefix)
        # tick-domain request clocks: always on (integer bookkeeping on
        # events the loop already handles); the wall-clock/sketch plane
        # only when armed. ``self.metrics`` may be replaced before the
        # first submit with a ServeMetrics carrying an SLOMonitor
        # (cli/run_serve wires --slo_* that way).
        self.times = RequestTimes()
        self.metrics: Optional[ServeMetrics] = (
            ServeMetrics(self.times, time_fn=time_fn)
            if cfg.metrics else None)
        # dispatch registry (ISSUE 19): name -> the jitted callable plus
        # the pre-jit body and jit options, so analysis/serve_check can
        # walk the ACTUAL compiled programs (jaxprs + lowered MLIR) and
        # compile_counts() can enumerate the live jit caches
        self._dispatches: Dict[str, Dict[str, Any]] = {}
        self._retrace_guard: Optional[_RetraceGuard] = None
        if cfg.retrace_guard != "off":
            self.stats["serve_retraces"] = 0
            self._retrace_guard = _RetraceGuard(
                cfg.retrace_guard, self.compile_budget(), self.stats,
                program_of=self._program_of)

        samp = (cfg.temperature, cfg.top_k, cfg.top_p)
        tp_axis, ep_axis = self._tp_axis, self._ep_axis
        counters = self._moe_counters
        moe_stats = self._moe_stats or bool(counters)

        def ride(toks, st):
            """The dropless layer's counters behind the tokens, one int32
            vector: no transfer of their own."""
            if not counters:
                return toks, st
            return jnp.concatenate(
                [toks.astype(jnp.int32),
                 jnp.stack([st[k] for k in counters]).astype(jnp.int32)]), {}
        # batch-sharded ep: each shard routes only its batch slice, so
        # the routing-load counters must psum over the expert axis to
        # stay global (parallel/expert.moe_ffn stats_axis)
        stats_axis = ep_axis if cfg.ep_batch else None
        overlap = self._ep_overlap
        slotted = self._slotted

        def decode_tick(params, pages, tables, lens, prev, act, seeds,
                        counts):
            # prev: the output vector of the dispatch before this one,
            # still on the device (the last decode tick's tokens, with the
            # first token of every prefill since written over its slot's
            # entry, and counters behind them). A row's last token is read
            # from it here, so the host can enqueue this tick before it
            # has read that one.
            last = prev[:lens.shape[0]]
            # act [S] bool: the engine's valid-lane mask for the tick —
            # inactive (sentinel) slots are dead lanes for expert routing
            # and for the scatter (which their sentinel rows drop anyway).
            # Under ep_batch every operand here is this shard's LOCAL
            # slot slice and ``tables`` carries group-local page ids.
            def run(pages, sl):
                out = model.decode_paged(
                    params, last[sl][:, None], pages, tables[sl], lens[sl],
                    act[sl][:, None], tp_axis=tp_axis, ep_axis=ep_axis,
                    return_moe_stats=moe_stats, stats_axis=stats_axis,
                    # row i of a decode tick is slot i
                    **({"slots": jnp.arange(lens.shape[0])[sl]}
                       if slotted else {}))
                return out[0], (out[2] if moe_stats else {}), out[1]

            if not overlap:
                logits, st, pages = run(pages, slice(None))
            else:
                # two microbatches traced back-to-back in ONE program:
                # B's attention depends on A only through the page
                # buffers (disjoint rows), NOT on A's expert all_to_all —
                # XLA's async collective scheduling overlaps the two.
                # Bit-identical to the unsplit tick: attention is
                # row-local and inference MoE routing is no-drop
                # per-token (capacity_override = the microbatch size
                # still never drops).
                n = lens.shape[0]
                la, sa, pages = run(pages, slice(0, n // 2))
                lb, sb, pages = run(pages, slice(n // 2, None))
                logits = jnp.concatenate([la, lb], axis=0)
                st = {k: sa[k] + sb[k] for k in sa} if moe_stats else {}
            return ride(_sample_rows(logits[:, -1], seeds, counts, *samp),
                        st), pages

        def prefill(params, pages, tables, toks, start, length, seed, count,
                    prev, where, fresh=False):
            # ``fresh`` (static, so a bucket may compile two programs under
            # ``prefix_cache``): ``start`` is 0 and the bucket is one of
            # ``_fresh_buckets``, which the host knows at dispatch; the
            # hook then attends over the keys it has just projected.
            # ``where`` ([1] int32): the slot being admitted, which is also
            # what a window or state family's layers find their ring or
            # state by. The sampled first token is written over that entry
            # of ``prev`` (see decode_tick) and the prefill's own counters
            # over the tail, so what comes back is the decode tick's next
            # operand and the host's one read both.
            # toks [1, P] — the prompt SUFFIX not covered by shared prefix
            # pages, scattered at absolute positions start..start+P-1
            # (start == 0 without prefix sharing: the whole prompt).
            # Under ep_batch the batch-1 prefill stays one dispatch: every
            # shard traces the same program, but only the OWNER group's
            # shard receives the slot's table row and the true length —
            # the others see an all-sentinel row and length 0 (all lanes
            # invalid), so their scatters drop, their lanes consume zero
            # expert capacity, and their sampled lane is garbage the host
            # never reads (the token output is expert-sharded [ep]; the
            # host picks the owner's entry).
            L = jnp.reshape(length, (-1,))[0]
            valid = jnp.arange(toks.shape[1])[None, :] < L
            # stats_lanes: non-owner groups replay the width with every
            # lane invalid — fake lanes that must not inflate the stats
            # capacity budget past the unsharded prefill's (ceil is
            # nonlinear, so the budget can't be corrected after the fact)
            at = jnp.maximum(L - 1, 0)
            out = model.decode_paged(params, toks, pages, tables,
                                     start, valid, tp_axis=tp_axis,
                                     ep_axis=ep_axis,
                                     return_moe_stats=moe_stats,
                                     stats_axis=stats_axis,
                                     stats_lanes=(toks.shape[1]
                                                  if stats_axis else None),
                                     **({"logit_index": at}
                                        if model.last_logit else {}),
                                     **({"slots": where}
                                        if slotted else {}),
                                     **({"fresh": True} if fresh else {}))
            logits, pages = out[0], out[1]
            st = out[2] if moe_stats else {}
            last = logits[0, 0] if model.last_logit else \
                jax.lax.dynamic_index_in_dim(logits[0], at, 0, keepdims=False)
            tok = _sample_rows(last[None], seed[None], count[None], *samp)
            vec, st = ride(tok, st)
            # under ep_batch ``prev`` is this shard's slots and ``where``
            # lies past them on every shard but the owner's: dropped
            tail = prev.shape[0] - vec.shape[0] + 1 + jnp.arange(
                vec.shape[0] - 1)
            at = jnp.concatenate([where, tail.astype(where.dtype)])
            return (prev.at[at].set(vec.astype(prev.dtype), mode="drop"),
                    st), pages

        def cow_copy(pages, src, dst):
            from distributed_lion_tpu.ops.attention import paged_copy_pages

            # src/dst arrive [width] (replicated) or [1, width] (this
            # shard's row of the grouped layout) — flatten either way
            return paged_copy_pages(pages, src.reshape(-1), dst.reshape(-1))

        if cfg.ep_batch:
            from jax.sharding import PartitionSpec as P

            bsp, rep = P(EXPERT_AXIS), P()
            tab = P(EXPERT_AXIS, None)
            self._decode_tick = self._jit_paged(
                decode_tick, n_rest=6,
                rest_specs=(tab, bsp, bsp, bsp, bsp, bsp),
                out_spec=(bsp, rep), name="decode")
            self._prefill = self._jit_paged(
                prefill, n_rest=8,
                rest_specs=(tab, rep, bsp, bsp, rep, rep, bsp, bsp),
                out_spec=(bsp, rep), name="prefill", static=("fresh",))
        else:
            self._decode_tick = self._jit_paged(decode_tick, n_rest=6,
                                                name="decode")
            self._prefill = self._jit_paged(prefill, n_rest=8,
                                            name="prefill",
                                            static=("fresh",))
        self._cow = self._jit_cow(cow_copy)

        self._speculator = None
        if cfg.speculate:
            from distributed_lion_tpu.serve.speculate import build_speculator

            self._speculator = build_speculator(self, cfg.speculate,
                                                draft_model)
        self._unread: deque = deque()  # dispatches not yet read, in order
        self._carry: List[Completion] = []  # completed outside a step()
        n_prev = cfg.max_seqs + len(self._moe_counters)
        if self._mesh is None:
            self._prev = jnp.zeros((n_prev,), jnp.int32)
        else:   # laid out as the dispatches return it
            self._prev = jax.device_put(
                np.zeros((n_prev,), np.int32), NamedSharding(
                    self._mesh, P(EXPERT_AXIS) if cfg.ep_batch else P()))
        setup.lap("setup/build_dispatches")  # jit wrappers; each program
        # compiles at its first tick, where the compile ledger names it
        setup.emit(stderr=True)  # stdout is run_serve's response stream
        if model.setup_note:
            journal.emit("[setup] " + model.setup_note.format(
                slots=cfg.max_seqs,
                state_gb=self.stats.get("state_bytes", 0) / 1e9),
                stderr=True)
        if self.tables.run_pages > 1:
            journal.emit(
                f"[setup] pages: minted in aligned runs of "
                f"{self.tables.run_pages} ({model.page_run} positions, one "
                f"copy of the decode walk); {self.tables.unused_blocks} of "
                f"{self.tables.num_blocks} pages lie in no whole run and "
                "stay unused", stderr=True)
        journal.emit(
            "[setup] decode: " + (
                "run-ahead 1 tick (device-fed last token)"
                if self._run_ahead else "in order (speculation)"),
            stderr=True)
        if model.fresh_prefill:
            fb = sorted(self._fresh_buckets)
            paged = model.n_layer - len(model.state_layers)
            journal.emit("[setup] prefill: " + (
                f"fresh keys, {model.fresh_prefill[0]} x{paged} (from position "
                f"0 in buckets {fb[0]}-{fb[-1]}; every other prefill gathers)"
                if fb else "gather"), stderr=True)

    # ------------------------------------------------------- TP dispatch
    def _register_dispatch(self, name: Optional[str], jitted, inner,
                           donate, rest_specs, out_spec) -> None:
        """Record a jitted serve dispatch for the observability hooks:
        ``compile_counts()`` reads the live jit caches,
        analysis/serve_check walks the jaxprs/MLIR of the same callables
        the ticks run (``inner`` is the pre-jit body — the shard_map'd
        program under a mesh — so the check can re-jit it with donation
        forced on backends where the engine turns donation off)."""
        if name is None:
            return
        self._dispatches[name] = {
            "jitted": jitted, "inner": inner, "donate": tuple(donate),
            "rest_specs": rest_specs, "out_spec": out_spec,
        }

    def compile_counts(self) -> Dict[str, int]:
        """Distinct compiled programs per registered dispatch, from jax's
        own jit caches — the measurable side of "O(log max) prefill
        compiles, ONE decode program". The compile-budget contract
        (analysis/serve_check and the retrace guard) pins these against
        :meth:`compile_budget` after a mixed workload."""
        out: Dict[str, int] = {}
        for name, d in self._dispatches.items():
            size = getattr(d["jitted"], "_cache_size", None)
            out[name] = int(size()) if callable(size) else -1
        return out

    def compile_budget(self) -> Dict[str, int]:
        """Max legal distinct lowerings per dispatch kind: decode /
        verify / cow are ONE fixed-shape program each; prefill gets one
        per power-of-two page bucket (serve/kv_cache.bucket_tokens — the
        O(log max) claim made countable), and under ``prefix_cache`` one
        more for each bucket whose prefill from position 0 takes the
        fresh-keys path (``_fresh_buckets``). The draft-model mirror's own
        prefill buckets identically."""
        buckets = self._buckets()
        # a bucket whose prefill from position 0 attends over fresh keys
        # compiles a second program where a prefill can also start behind
        # a shared prefix
        both = len(self._fresh_buckets) if self.cfg.prefix_cache else 0
        budget = {"decode": 1, "cow": 1, "prefill": len(buckets) + both}
        if self.cfg.speculate:
            budget["verify"] = 1
            budget["draft_prefill"] = len(buckets)
            budget["draft_step"] = 1
        return budget

    def _buckets(self) -> set:
        """Every padded length a prefill can have."""
        cap = self.cfg.block_size * self.cfg.max_blocks_per_seq
        return {self.cfg.bucket(n) for n in range(1, cap + 1)}

    def _program_of(self, kind: str) -> str:
        """A dispatch kind's name in the compile ledger: the name of the
        function that was jitted."""
        d = self._dispatches.get(kind)
        return getattr(d and d["inner"], "__name__", kind)

    def _guard(self, kind: str, operands) -> None:
        """Retrace-guard hook, called immediately before each dispatch
        with its rest operands (params/pages are engine-owned stable
        buffers and never change signature)."""
        if self._retrace_guard is not None:
            self._retrace_guard.observe(kind, operands)

    def _jit_paged(self, fn, n_rest: int, rest_specs=None, out_spec=None,
                   name: Optional[str] = None, static: tuple = ()):
        """jit a dispatch ``fn(params, pages, *rest) -> (out, pages)``;
        under TP the body is shard_map'd over the serving mesh — params
        and pages sharded per their spec trees, every host-built operand
        (tables, lens, tokens, seeds) replicated, the sampled tokens
        replicated back out (each rank computes identical logits: see the
        model hooks). ``check_vma=False`` mirrors the trainer's usage.

        Batch-sharded ep (ISSUE 16) passes ``rest_specs`` (one
        PartitionSpec per rest operand — slot-leading arrays shard
        ``P(EXPERT_AXIS)``) and ``out_spec`` (the spec-prefix for the
        first output, e.g. ``(P(EXPERT_AXIS), P())`` for
        expert-sharded sampled tokens + replicated psummed stats);
        speculative verify reuses the same hooks (serve/speculate.py).
        ``static`` names keyword arguments of ``fn`` that pick the program
        (the prefill's ``fresh``); the registered ``inner`` is the program
        of their defaults."""
        import jax

        donate = (1,) if jax.default_backend() != "cpu" else ()
        if self._mesh is None:
            jitted = jax.jit(fn, donate_argnums=donate,
                             static_argnames=static)
            self._register_dispatch(name, jitted, fn, donate, None, None)
            return jitted
        from jax.sharding import PartitionSpec as P

        rep = P()
        if rest_specs is None:
            rest_specs = (rep,) * n_rest
        if out_spec is None:
            out_spec = rep

        def sharded(fn):
            return jax.shard_map(
                fn, mesh=self._mesh,
                in_specs=(self._param_specs, self._pages_spec)
                + tuple(rest_specs),
                out_specs=(out_spec, self._pages_spec), check_vma=False)

        body = sharded(fn)
        if static:
            @functools.wraps(fn)
            def program(*operands, **chosen):
                return sharded(functools.partial(fn, **chosen))(*operands)
        else:
            program = body
        jitted = jax.jit(program, donate_argnums=donate,
                         static_argnames=static)
        self._register_dispatch(name, jitted, body, donate,
                                tuple(rest_specs), out_spec)
        return jitted

    def _jit_cow(self, fn):
        """jit the CoW page-copy ``fn(pages, src, dst) -> pages`` (pages
        donated; shard-local under TP — page ids are replicated host
        math, the kv-head axis stays put). Under batch-sharded ep the
        src/dst ids arrive as the grouped ``[ep, width]`` layout, each
        shard copying only its own group's rows with LOCAL ids."""
        import jax

        donate = (0,) if jax.default_backend() != "cpu" else ()
        if self._mesh is None:
            jitted = jax.jit(fn, donate_argnums=donate)
            self._register_dispatch("cow", jitted, fn, donate, None, None)
            return jitted
        from jax.sharding import PartitionSpec as P

        rep = P()
        idx = P(EXPERT_AXIS) if self._ep_batch else rep
        body = jax.shard_map(
            fn, mesh=self._mesh,
            in_specs=(self._pages_spec, idx, idx),
            out_specs=self._pages_spec, check_vma=False)
        jitted = jax.jit(body, donate_argnums=donate)
        self._register_dispatch("cow", jitted, body, donate,
                                (idx, idx), None)
        return jitted

    def _absorb_counters(self, tail, prefix: str = "moe_") -> None:
        """Fold the dropless layer's counters, read from behind a
        dispatch's tokens, into engine.stats: sums, and the largest load
        as a maximum. ``prefix`` keeps prefill dispatches apart."""
        for name, value in zip(self._moe_counters, tail):
            key = name.replace("moe_", prefix, 1)
            join = max if name.endswith("_max") else int.__add__
            self.stats[key] = join(self.stats.get(key, 0), int(value))

    def _absorb_moe_stats(self, st) -> None:
        """Fold a dispatch's MoE routing-load scalars into engine.stats —
        a no-op ({}) unless ``ServeConfig.moe_stats`` is armed on a MoE
        checkpoint, so the common tick pays zero extra host reads."""
        if not st:
            return
        self.stats["moe_valid_tokens"] += float(np.asarray(st["valid"]))
        self.stats["moe_kept_tokens"] += float(np.asarray(st["kept"]))
        self.stats["moe_capacity_slots"] += float(
            np.asarray(st["capacity_slots"]))

    # ------------------------------------------------------------- intake
    def submit(self, req: Request, deadline_at: Optional[float] = None
               ) -> None:
        """Queue a request. ``deadline_at`` (absolute ``time.monotonic()``)
        overrides the fresh ``deadline_s`` stamp — the migration path: a
        request's wall-clock budget started at its ORIGINAL submission and
        must not reset when a survivor re-admits it."""
        if deadline_at is None and req.deadline_s is not None:
            deadline_at = self._now() + float(req.deadline_s)
        if deadline_at is not None:
            self._deadline_at[req.req_id] = float(deadline_at)
        self.times.submitted(req.req_id, self.stats["ticks"])
        if self.metrics is not None:
            self.metrics.on_submit(req.req_id)
        self.pending.append(req)

    def _finish_timing(self, req_id, status: str,
                       tick: Optional[int] = None) -> Dict[str, Any]:
        """Retire the request's clocks into a timing dict (fed through
        the metrics plane when armed, which adds wall ``ttft_ms``) and
        journal the terminal ``serve_finish`` event — the per-request
        record run_analyze --serve builds waterfalls from. ``tick`` is the
        tick whose dispatch made the last token; the host may have read it
        a tick later, and ``delivery_lag_ticks`` (0 or 1) says so."""
        now = self.stats["ticks"]
        tick = now if tick is None else tick
        timing = self.times.finished(req_id, tick)
        timing["delivery_lag_ticks"] = now - tick
        if self.metrics is not None:
            timing = self.metrics.on_finish(req_id, timing, status,
                                            tick=now)
        journal.active().event("serve_finish", req_id=str(req_id),
                               reason=status, **timing)
        return timing

    @property
    def _run_ahead(self) -> bool:
        """Tick t+1's decode dispatch is enqueued before the host reads
        tick t's tokens, unless each tick's outcome shapes the next
        tick's operands (a speculator's accept / reject)."""
        return self._speculator is None

    def has_work(self) -> bool:
        """Also true while a dispatch is unread or a completion is held
        for the next ``step()``: every driver loop drains through it."""
        return bool(self.pending or self._unread or self._carry) \
            or any(s is not None for s in self.slots)

    def export_records(self) -> List[RecoveryRecord]:
        """Snapshot every unfinished request (resident slots + the pending
        queue) as :class:`RecoveryRecord`s. The fleet copies these OUT of
        the replica each tick so a crash recovers from the shadow, never
        from the dead engine. A record holds every token made: an unread
        dispatch is read first (a ``serve/drain``), and what that read
        completes is returned by the next ``step()``."""
        self._drain("export_records", self._carry)
        recs = []
        for s in self.slots:
            if s is None:
                continue
            recs.append(RecoveryRecord.from_request(
                s.req, s.gen, int(s.budget),
                self._deadline_at.get(s.req.req_id)))
        for req in self.pending:
            recs.append(RecoveryRecord.from_request(
                req, req.committed, req.max_new_tokens,
                self._deadline_at.get(req.req_id)))
        return recs

    def export_prefix_chains(self) -> List[List[int]]:
        """The prefix cache's maximal cached token chains (all pool
        groups merged, deduped) — what fleet-restart persistence banks so
        a NEW engine can warm-start its page pool by re-prefilling each
        shared chain once instead of cold prefilling it per request.
        Empty without ``prefix_cache`` (nothing shared, nothing to save).
        Host-side dict walks only — no device sync, and no drain: a chain
        is registered at admission from the request's history (prompt and
        committed tokens), never from a token a dispatch has yet to
        deliver."""
        if not self._prefix_caches:
            return []
        seen = set()
        for cache in self._prefix_caches:
            for chain in cache.chains():
                seen.add(tuple(chain))
        return [list(k) for k in sorted(seen, key=lambda k: (len(k), k))]

    def _bucket(self, n: int) -> int:
        return self.cfg.bucket(n)

    def _prefix_for(self, slot: int) -> PrefixCache:
        """The prefix cache serving ``slot``'s pool group (the one cache
        when the batch is not expert-sharded)."""
        return self._prefix_caches[self.tables.group_of(slot)]

    def _device_tables(self):
        """The decode tick's device view of the block tables: the global
        numpy table as-is, or — under batch-sharded ep — group-LOCAL page
        ids (sentinel == the local pool size, inert on every shard's
        scatter/gather exactly like the global sentinel is globally)."""
        import jax.numpy as jnp

        bt = self.tables
        if not self._ep_batch:
            # a copy: the host goes on to free and grow rows while the
            # dispatch that reads this is still in flight
            return jnp.asarray(bt.tables.copy())
        base = (np.arange(bt.max_seqs, dtype=np.int32)
                // bt.slots_per_group) * bt.blocks_per_group
        local = np.where(bt.tables == bt.sentinel, bt.blocks_per_group,
                         bt.tables - base[:, None]).astype(np.int32)
        return jnp.asarray(local)

    # ------------------------------------------------- page bookkeeping
    def _grow(self, slot: int, n_tokens: int) -> bool:
        """``tables.grow`` with prefix-cache reclaim as the fallback: a
        pool exhausted by CACHED pages (refs held only by the cache) is
        not full — LRU chains are dropped until the grow fits or the
        cache is empty. Overflow semantics beyond that are the caller's
        (unchanged from the unshared engine)."""
        if self.tables.grow(slot, n_tokens):
            return True
        if self.prefix is None:
            return False
        if self.tables.blocks_for(n_tokens) > self.tables.max_blocks_per_seq:
            return False  # width cap, not pool pressure: no reclaim helps
        need = (self.tables.blocks_for(n_tokens)
                - int(self.tables.owned[slot]))
        self.stats["reclaimed_pages"] += self._prefix_for(slot).reclaim(need)
        return self.tables.grow(slot, n_tokens)

    def _cow_if_shared(self, slot: int, pos: int, pairs: List[tuple]) -> bool:
        """Queue a copy-on-write for the page holding ``pos`` when it is
        shared (refs > 1) — the caller flushes ``pairs`` as ONE device
        dispatch before any write lands. Returns False only when no page
        can be found even after cache reclaim (caller overflow-evicts)."""
        if self.prefix is None or not self.tables.shared_at(slot, pos):
            return True
        pair = self.tables.cow(slot, pos)
        if pair is None:
            self.stats["reclaimed_pages"] += self._prefix_for(slot).reclaim(1)
            if not self.tables.shared_at(slot, pos):
                # the reclaim dropped the cache's own ref on this page —
                # it is private now, no copy needed (retrying cow here
                # would trip its shared-page precondition)
                return True
            pair = self.tables.cow(slot, pos)
            if pair is None:
                return False
        pairs.append(pair)
        self.stats["cow_copies"] += 1
        return True

    def _flush_cow(self, pairs: List[tuple]) -> None:
        """Dispatch the tick's queued page copies (one fixed-width jitted
        program, sentinel-padded — no recompiles as the copy count
        varies). A no-op on an empty queue: the common tick pays zero."""
        if not pairs:
            return
        import jax.numpy as jnp

        bt = self.tables
        if self._ep_batch:
            # grouped layout [ep, width]: each shard receives its group's
            # row with LOCAL page ids (a CoW pair is always intra-group —
            # cow() mints from the slot's own group), padded with the
            # LOCAL sentinel so unused lanes drop on device
            width = bt.slots_per_group
            lsent = bt.blocks_per_group
            src = np.full((bt.groups, width), lsent, np.int32)
            dst = np.full((bt.groups, width), lsent, np.int32)
            fill = np.zeros((bt.groups,), np.int32)
            for s, d in pairs:
                g = s // bt.blocks_per_group
                base = g * bt.blocks_per_group
                i = int(fill[g])
                fill[g] += 1
                src[g, i] = s - base
                dst[g, i] = d - base
            assert fill.max() <= width, "more CoW copies than group slots"
        else:
            width = self.cfg.max_seqs
            assert len(pairs) <= width, "more CoW copies than slots"
            sentinel = bt.sentinel
            src = np.full((width,), sentinel, np.int32)
            dst = np.full((width,), sentinel, np.int32)
            for i, (s, d) in enumerate(pairs):
                src[i], dst[i] = s, d
        with journal.span("serve/cow", copies=len(pairs)):
            src_dev, dst_dev = jnp.asarray(src), jnp.asarray(dst)
            self._guard("cow", (src_dev, dst_dev))
            self.pages = self._cow(self.pages, src_dev, dst_dev)

    # -------------------------------------------------------------- ticks
    def _dispatch_prefill(self, req: Request, slot: int, covered: int,
                          suffix: List[int], padded: int):
        """Enqueue ONE admitted request's prefill and return its output
        vector (not read), its MoE load scalars and whether it was
        dispatched as ``fresh``. The program writes the
        sampled first token over ``prev[slot]``, so the decode tick
        enqueued next reads it on the device; the host reads the vector
        when its turn comes (:meth:`_read`). All device-array construction
        for the dispatch happens here, at the dispatch boundary — the
        admission loop's body stays numpy/table math (graft-check DLT010
        pins that shape)."""
        import jax.numpy as jnp

        toks = np.zeros((1, padded), np.int32)
        toks[0, :len(suffix)] = suffix
        bt = self.tables
        g = bt.group_of(slot)
        if self._ep_batch:
            # only the OWNER group's shard gets the real table row
            # (LOCAL ids), the true length and a place in its share of
            # ``prev`` — the other shards see all-sentinel + length 0
            # (every lane invalid) and a place past their slots: their
            # scatters drop, their lanes consume zero expert capacity,
            # their sampled lane is written nowhere
            tab = np.full((bt.groups, bt.max_blocks_per_seq),
                          bt.blocks_per_group, np.int32)
            row = bt.tables[slot]
            tab[g] = np.where(row == bt.sentinel,
                              bt.blocks_per_group,
                              row - bt.group_base(g))
            start_h = np.zeros((bt.groups,), np.int32)
            start_h[g] = covered
            len_h = np.zeros((bt.groups,), np.int32)
            len_h[g] = len(suffix)
            where_h = np.full((bt.groups,), bt.slots_per_group, np.int32)
            where_h[g] = slot - g * bt.slots_per_group
            tab_dev = jnp.asarray(tab)
            start_dev = jnp.asarray(start_h)
            len_dev = jnp.asarray(len_h)
            where_dev = jnp.asarray(where_h)
        else:
            # a copy: the row grows and is freed while this is in flight
            tab_dev = jnp.asarray(bt.tables[slot:slot + 1].copy())
            start_dev = jnp.full((1,), covered, jnp.int32)
            len_dev = jnp.int32(len(suffix))
            where_dev = jnp.full((1,), slot, jnp.int32)
        # the sample index resumes at len(committed): the key for this
        # draw is fold_in(key(seed), len(committed)) — the exact key the
        # pre-migration engine would use next
        rest = (tab_dev, jnp.asarray(toks), start_dev, len_dev,
                jnp.uint32(req.seed), jnp.int32(len(req.committed)),
                self._prev, where_dev)
        # from position 0 no query sees a page this dispatch did not write
        fresh = covered == 0 and padded in self._fresh_buckets
        self._guard("prefill", rest + (fresh,))
        (vec, st), self.pages = self._prefill(self.params, self.pages,
                                              *rest, fresh=fresh)
        return vec, st, fresh

    def _enqueued(self, kind: str, vec, st, rows: List[tuple],
                  fresh: bool = False) -> None:
        """A dispatch is on the device's queue: its output vector is the
        next dispatch's ``prev``, its copy to the host starts now, and its
        read waits its turn (:meth:`_read`)."""
        self._prev = vec
        vec.copy_to_host_async()
        self._unread.append(_Unread(kind, self.stats["ticks"], vec, st, rows,
                                    fresh))

    def _admit(self, completions: List[Completion]) -> int:
        budget = self.cfg.prefill_cap_tokens
        admitted = 0
        while self.pending:
            req = self.pending[0]
            # a migrated request prefills its WHOLE history — prompt plus
            # the tokens it already generated elsewhere — and resumes the
            # pinned sample stream at index len(committed) (see Request)
            hist = list(req.tokens) + list(req.committed)
            L = len(hist)
            cap = self.tables.max_tokens_per_seq
            if not req.tokens or len(req.tokens) > cap - 1:
                # -1: a prompt must leave room for one decode write
                self.pending.popleft()
                self._deadline_at.pop(req.req_id, None)
                completions.append(Completion(
                    req.req_id, len(req.tokens), list(req.committed),
                    "rejected", timing=self._finish_timing(
                        req.req_id, "rejected")))
                continue
            if L > cap:
                # a resumption already past the horizon: the uninterrupted
                # run overflow-evicted at exactly this point, delivering
                # these committed tokens — same status, same tokens, no
                # pointless prefill (L == cap still admits: the history
                # fills the table, one token samples, and the NEXT tick's
                # failed grow overflow-evicts like the uninterrupted run)
                self.pending.popleft()
                self._deadline_at.pop(req.req_id, None)
                completions.append(Completion(
                    req.req_id, len(req.tokens), list(req.committed),
                    "overflow", timing=self._finish_timing(
                        req.req_id, "overflow")))
                continue
            slot = self.tables.find_free_slot()
            if slot is None:
                break  # no slot: wait for evictions — checked BEFORE the
                # prefix match so a stalled queue costs O(1) per tick,
                # not a full match walk (which would also touch LRU
                # recency for a request that cannot admit)
            run, covered = ([], 0)
            if self.prefix is not None:
                run, covered = self._prefix_for(slot).match(hist)
            P = self._bucket(L - covered)
            if admitted and P > budget:
                break  # fairness cap — but never starve an empty tick
            if run:
                self.tables.share(slot, run)
            cow_pairs: List[tuple] = []
            if not (self._grow(slot, min(L + 1, cap))
                    and self._cow_if_shared(slot, covered, cow_pairs)):
                # no pages even after reclaim: roll the slot back EMPTY
                # (all-or-nothing — a half-reserved slot strands refs)
                self.stats["freed_pages"] += self.tables.free_slot(slot)
                break
            self.pending.popleft()
            self._flush_cow(cow_pairs)
            suffix = hist[covered:]
            with journal.span("serve/prefill", req_id=str(req.req_id),
                           prompt_len=L, padded=P, slot=slot,
                           shared=covered, resumed=len(req.committed)):
                vec, st, fresh = self._dispatch_prefill(req, slot, covered,
                                                        suffix, P)
            budget -= P
            admitted += 1
            self.stats["prefill_tokens"] += len(suffix)
            self.stats["padded_prefill_tokens"] += P
            if self.model.state_layers:
                # the prefill started the slot's state from zero and
                # overwrote what its last tenant left
                self.stats["state_resets"] += 1
            if req.committed:
                self.stats["resumed_requests"] += 1
                self.stats["resumed_tokens"] += len(req.committed)
            if self.prefix is not None:
                if covered:
                    self.stats["prefix_hits"] += 1
                    self.stats["shared_tokens"] += covered
                self._prefix_for(slot).register(slot, hist)
            s = _Slot(req=req, cache_len=L, gen=list(req.committed),
                      unread=1, budget=(req.max_new_tokens
                                        or self.cfg.max_new_tokens))
            self.slots[slot] = s
            self._enqueued("prefill", vec, st, [(slot, s)], fresh)
            if self._speculator is not None:
                self._speculator.on_admit(slot, hist, len(req.committed))
            if not self._run_ahead:
                # in order: the speculative tick drafts from the token
                self._read_unread(completions)
            else:
                self._retire_if_spent(slot, s)
        return admitted

    def _release(self, slot: int, s: _Slot, reason: str) -> None:
        """Hand ``slot``'s table row and page refs back and empty the
        slot (``serve/evict``). Dispatches enqueued so far still see the
        row as it was (each took its own copy), and the device runs them
        in order before any prefill that reuses the pages."""
        with journal.span(
                "serve/evict", req_id=str(s.req.req_id), slot=slot,
                reason=reason, n_generated=(
                    min(len(s.gen) + s.unread, s.budget)
                    if reason == "length" else len(s.gen))):
            # refcount-honest accounting: evicting a sharer whose pages
            # all outlive it (prefix cache / other slots) frees ZERO
            # physical pages — freed_pages records what really returned
            freed = self.tables.free_slot(slot)
            self.stats["freed_pages"] += freed
            self.slots[slot] = None
            self.stats["evictions"] += 1
            if reason == "timeout":
                self.stats["timeouts"] += 1
            if self._speculator is not None:
                self._speculator.on_evict(slot)

    def _retire_if_spent(self, slot: int, s: _Slot) -> None:
        """An end the host can foresee: the tokens dispatched for ``s``
        reach its budget, so it is not dispatched again and its slot and
        pages are free for the next admission. Its Completion waits for
        the read of its last token."""
        if len(s.gen) + s.unread >= s.budget:
            self._release(slot, s, "length")

    def _maybe_finish(self, slot: int, completions: List[Completion],
                      overflow: bool = False, timeout: bool = False,
                      s: Optional[_Slot] = None,
                      tick: Optional[int] = None) -> None:
        """End ``s`` (default: the slot's resident) if it has a reason to
        end, on the tokens the host has read: release the slot unless it
        was retired at dispatch, and emit the Completion. ``tick``: the
        tick whose dispatch made its last token (default: this one)."""
        s = self.slots[slot] if s is None else s
        reason = None
        if overflow:
            reason = "overflow"
        elif timeout:
            reason = "timeout"
        elif self.cfg.eos_id is not None and s.gen and \
                s.gen[-1] == self.cfg.eos_id:
            reason = "eos"
        elif len(s.gen) >= s.budget:
            reason = "length"
        if reason is None:
            return
        # overflow and timeout are decided on a drained engine; an EOS may
        # leave one row in flight, which its read drops
        assert not s.unread or reason == "eos", (reason, s.unread)
        if self.slots[slot] is s:
            self._release(slot, s, reason)
        s.done = True
        self._deadline_at.pop(s.req.req_id, None)
        completions.append(Completion(
            s.req.req_id, len(s.req.tokens), list(s.gen), reason,
            timing=self._finish_timing(s.req.req_id, reason, tick)))

    def _read(self, u: _Unread, completions: List[Completion]) -> None:
        """Read one dispatch's output vector — the host's ONE sync with
        the device for that dispatch, blocking until it has run — and
        commit its rows: the one routine through which a token reaches
        ``gen``, ``decode_tokens`` / ``prefill_dispatches`` count it and a
        request can end by EOS or length."""
        vec = self._host_read(u.vec, u.kind, u.tick)
        with journal.span("serve/commit", batch=len(u.rows)):
            first = u.kind == "prefill"
            self._absorb_moe_stats(u.st)
            self._absorb_counters(vec[self.cfg.max_seqs:],
                                  "moe_prefill_" if first else "moe_")
            for i, s in u.rows:
                s.unread -= 1
                if s.done:
                    # it sampled EOS in the tick before; this row was
                    # already enqueued. Never appended, never counted.
                    self.stats["run_ahead_discarded"] += 1
                    continue
                s.last_tok = int(vec[i])
                s.gen.append(s.last_tok)
                if first:
                    self.stats["prefill_dispatches"] += 1
                    self.stats["prefill_fresh_dispatches"] += u.fresh
                    self.times.first_token(s.req.req_id, u.tick)
                    if self.metrics is not None:
                        self.metrics.on_first_token(s.req.req_id)
                else:
                    self.stats["decode_tokens"] += 1
                self._maybe_finish(i, completions, s=s, tick=u.tick)
        lap = self._lap
        lap["commit"] += self._now() - lap["at"]

    def _host_read(self, vec, of: str, tick: int) -> np.ndarray:
        """The blocking read of one dispatch's output (``serve/token_read``),
        with the host's wait in it stamped always: ``stats["read_wait_s"]``
        and this tick's lap (clock reads only; the value is untouched)."""
        with journal.span("serve/token_read", of=of, tick=tick):
            t0 = self._now()
            out = np.asarray(vec)
            t1 = self._now()
        wait = t1 - t0
        self.stats["read_wait_s"] += wait
        lap = self._lap
        lap["read"] += wait
        lap["reads"] += 1
        lap["at"] = t1
        if wait >= lap["worst"][0]:
            lap["worst"] = (wait, of, tick)
        return out

    def _read_unread(self, completions: List[Completion],
                     keep: int = 0) -> None:
        """Read the unread dispatches, oldest first, down to the newest
        ``keep``."""
        while len(self._unread) > keep:
            self._read(self._unread.popleft(), completions)

    def _drain(self, why: str, completions: List[Completion]) -> None:
        """Read every unread dispatch NOW, before its turn: a decision
        that needs the tokens is about to be made (``why``: overflow,
        deadline, export_records). No-op with nothing unread."""
        if not self._unread:
            return
        self.stats["run_ahead_drains"] += 1
        with journal.span("serve/drain", reason=why,
                          dispatches=len(self._unread)):
            self._read_unread(completions)

    def _reserve_write(self, slot: int, s: _Slot,
                       cow_pairs: List[tuple]) -> bool:
        """Pages for the tick's ONE write of ``s`` (CoW'ing a shared
        boundary page first — the first decode write after a cache-hit
        admit is the canonical divergent write)."""
        return (self._grow(slot, s.cache_len + 1)
                and self._cow_if_shared(slot, s.cache_len, cow_pairs))

    def _decode_operands(self, completions: List[Completion]):
        """The decode tick's host half (``serve/decode_build``): reserve
        every resident's write, then build the operands. None of it
        waits for a token: ``lens`` and ``counts`` advance at dispatch,
        and the rows' last tokens are ``prev``, on the device. A slot the
        pool can't serve even after reclaim is evicted as overflow — after
        a drain, since its truncated output holds every token made and the
        unread tick may free a slot's pages by EOS — and the rest of the
        batch keeps moving. Returns ``(operands, rows)``, or None when no
        slot is left."""
        import jax.numpy as jnp

        cow_pairs: List[tuple] = []
        for i, s in enumerate(self.slots):
            if s is None or self._reserve_write(i, s, cow_pairs):
                continue
            # (the copies queued so far first: the drain may free their
            # pages, and a page minted twice must not be copied into twice
            # by one dispatch)
            self._flush_cow(cow_pairs)
            del cow_pairs[:]
            self._drain("overflow", completions)
            if self.slots[i] is s and not self._reserve_write(
                    i, s, cow_pairs):
                self._maybe_finish(i, completions, overflow=True)
        rows = [(i, s) for i, s in enumerate(self.slots) if s is not None]
        if not rows:
            return None
        self._flush_cow(cow_pairs)
        S = self.cfg.max_seqs
        lens = np.zeros((S,), np.int32)
        act = np.zeros((S,), bool)
        seeds = np.zeros((S,), np.uint32)
        counts = np.zeros((S,), np.int32)
        for i, s in rows:
            lens[i] = s.cache_len
            act[i] = True
            seeds[i] = s.req.seed
            # index of the token being sampled
            counts[i] = len(s.gen) + s.unread
        return (self._device_tables(), jnp.asarray(lens), self._prev,
                jnp.asarray(act), jnp.asarray(seeds),
                jnp.asarray(counts)), rows

    def _decode(self, completions: List[Completion]) -> None:
        """Enqueue this tick's decode dispatch, THEN read what was
        enqueued before it (the last tick's tokens, this tick's prefills):
        the device runs tick t while the host commits tick t-1 and builds
        tick t+1."""
        residents = sum(s is not None for s in self.slots)
        if not (residents or self._unread):
            return
        span = journal.span
        with span("serve/decode_tick", batch=residents):
            with span("serve/decode_build"):
                t0 = self._now()
                built = self._decode_operands(completions)
                self._lap["build"] += self._now() - t0
            if built is not None:
                rest, rows = built
                with span("serve/decode_dispatch"):  # enqueue only
                    self._guard("decode", rest)
                    self.stats["run_ahead_ticks"] += bool(self._unread)
                    (vec, st), self.pages = self._decode_tick(
                        self.params, self.pages, *rest)
                    self._enqueued("decode", vec, st, rows)
                    self._count_dispatched(rows)
            # all but the dispatch just enqueued (a speculator's tick is
            # not this method: it reads its own dispatch in order)
            self._read_unread(completions, keep=int(built is not None))

    def _count_dispatched(self, rows: List[tuple]) -> None:
        """What the enqueued decode tick does on the device, counted as it
        is enqueued (the rooflines divide device time by these), and the
        rows' own clocks: one more cached position, one more unread
        token, and the slot's end if the host can foresee it."""
        self.stats["decode_ticks"] += 1
        self.stats[self.model.kernel_stat] += self._decode_kernel
        self.stats["kv_pages_table"] += (
            self.cfg.max_seqs * self.cfg.max_blocks_per_seq)
        if self._windowed:
            self.stats["window_kernel_ticks"] += self._decode_kernel
        if self.model.state_layers:
            self.stats["state_rows_stepped"] += (
                len(rows) * len(self.model.state_layers))
        for i, s in rows:
            s.cache_len += 1
            s.unread += 1
            self.stats["kv_pages_read"] += self.tables.blocks_for(
                s.cache_len)
            self._retire_if_spent(i, s)

    def _expire_deadlines(self, completions: List[Completion]) -> None:
        """Evict every request past its wall-clock deadline with the
        honest ``timeout`` status (partial output attached) — checked at
        the tick boundary BEFORE admit/decode, so an expired pending
        request never pays a prefill and an expired resident never pays
        another dispatch. A resident's partial output holds every token
        made: an unread dispatch is read first (which may end it some
        other way). Host-side clock reads only."""
        if not self._deadline_at:
            return
        now = self._now()
        jrnl = journal.active()  # events only: no-ops with none installed
        keep: deque = deque()
        while self.pending:
            req = self.pending.popleft()
            at = self._deadline_at.get(req.req_id)
            if at is not None and now >= at:
                self._deadline_at.pop(req.req_id, None)
                self.stats["timeouts"] += 1
                jrnl.event("serve/timeout", req_id=str(req.req_id),
                           where="pending",
                           n_generated=len(req.committed))
                completions.append(Completion(
                    req.req_id, len(req.tokens), list(req.committed),
                    "timeout", timing=self._finish_timing(
                        req.req_id, "timeout")))
            else:
                keep.append(req)
        self.pending = keep
        late = [i for i, s in enumerate(self.slots) if s is not None
                and now >= self._deadline_at.get(s.req.req_id, np.inf)]
        if late:
            self._drain("deadline", completions)
        for i in late:
            if self.slots[i] is not None:
                self._maybe_finish(i, completions, timeout=True)

    def step(self) -> List[Completion]:
        """One engine tick: expire deadlines, admit/prefill under the
        fairness cap, enqueue one decode dispatch over the rolling batch,
        then read and commit what was enqueued before it. Returns the
        requests whose last token the host read this tick: one made by
        tick t's decode dispatch is read, and its request returned, by
        ``step()`` t+1 (``timing["delivery_lag_ticks"]``), unless a
        speculator keeps the engine in order."""
        completions, self._carry = self._carry, []
        self.stats["ticks"] += 1
        span = journal.span
        self._lap = _new_lap()
        gc0 = journal.gc_totals()[1]
        t_start = self._now()
        with span("serve/tick", tick=self.stats["ticks"]):
            with span("serve/expire"):
                self._expire_deadlines(completions)
            with span("serve/admit", pending=len(self.pending)) as admit:
                prefills = self._admit(completions)
                admit.set(prefills=prefills)
            t_admitted = self._now()
            if self.metrics is not None:
                # per-token decode interval = the tick's wall time from
                # here over however many tokens it committed (1/slot
                # plain, up to k+1/slot speculative) — host clock reads
                # only, the dispatch itself is untouched
                t0 = self._now()
                tok0 = self.stats["decode_tokens"]
            if self._speculator is not None:
                self._speculator.decode_tick(completions)
            else:
                self._decode(completions)
            if self.metrics is not None:
                with span("serve/metrics"):
                    self._tick_metrics(t0, tok0)
        self._judge_tick(t_start, t_admitted, self._now(), prefills, gc0)
        for line in compile_cache.new_lines():
            # a dispatch's program, named when it is built (its first
            # tick; a retrace later): trace, lower, compile or load
            journal.emit(line, stderr=True)
        return completions

    def _judge_tick(self, t0: float, t_admitted: float, t1: float,
                    prefills: int, gc0: float) -> None:
        """The tick's always-on account (module doc): copy the
        collector's totals into ``stats``, hand this tick's read wait to
        the slow ticks still waiting for their next reads, and judge the
        tick itself if it was decode-only. Host arithmetic on stamps
        already taken; the median is worked out only for a tick over
        ``SLOW_TICK_MIN_S``."""
        lap, stats = self._lap, self.stats
        stats["gc_collections"], stats["gc_pause_s"] = journal.gc_totals()
        for rec in self._slow_open:
            rec["next_read_wait_ms"].append(round(lap["read"] * 1e3, 3))
        while self._slow_open and \
                len(self._slow_open[0]["next_read_wait_ms"]) == 2:
            rec = self._slow_open.pop(0)
            journal.emit(
                f"[serve] slow tick {rec['tick']}: next reads waited "
                + ", ".join(f"{ms:.1f}" for ms in rec["next_read_wait_ms"])
                + " ms", stderr=True)
        quiet = not (prefills or self._prev_prefills)
        self._prev_prefills = prefills
        if not (quiet and lap["reads"]):
            return
        wall = t1 - t0
        if wall > SLOW_TICK_MIN_S and len(self._walls) >= SLOW_TICK_MIN_TICKS:
            median = statistics.median(self._walls)
            if wall > SLOW_TICK_FACTOR * median:
                stats["slow_ticks"] += 1
                stats["slow_tick_excess_s"] += wall - median
                wait, of, tick = lap["worst"]
                rec = journal.account(
                    "slow_tick", "serve/slow_tick", t0, t1,
                    tick=stats["ticks"], wall_ms=round(wall * 1e3, 3),
                    median_ms=round(median * 1e3, 3),
                    read_wait_ms=round(lap["read"] * 1e3, 3),
                    read_of=of, read_tick=tick,
                    gc_ms=round((stats["gc_pause_s"] - gc0) * 1e3, 3),
                    admit_ms=round((t_admitted - t0) * 1e3, 3),
                    build_ms=round(lap["build"] * 1e3, 3),
                    commit_ms=round(lap["commit"] * 1e3, 3),
                    prefills=prefills, next_read_wait_ms=[])
                self._slow_open.append(rec)
                # said at once, with what is known: a stall in a run's last
                # two ticks is on stderr all the same
                journal.emit(
                    f"[serve] slow tick {rec['tick']}: {rec['wall_ms']:.1f} "
                    f"ms (median {rec['median_ms']:.1f}): read of {of} tick "
                    f"{tick} waited {rec['read_wait_ms']:.1f}, gc "
                    f"{rec['gc_ms']:.1f}, admit {rec['admit_ms']:.1f}, build "
                    f"{rec['build_ms']:.1f}, commit {rec['commit_ms']:.1f}",
                    stderr=True)
        # a stalled wall enters the history too: one in 256 does not move a
        # median, and a regime that stays slow becomes the median instead
        # of a stall a tick for ever
        self._walls.append(wall)

    def _tick_metrics(self, t0: float, tok0: int) -> None:
        made = self.stats["decode_tokens"] - tok0
        if made > 0:
            self.metrics.on_decode_tick(
                (self._now() - t0) * 1e3 / made, made)
        self.metrics.set_gauges(**self._gauge_snapshot())
        if self.metrics.maybe_drain(self.stats["ticks"]) is not None:
            # the SAME counters the bench banks, at the same cadence
            # the sketches drain — crash bundles and run_analyze
            # --serve read these, not a private in-memory dict
            journal.active().event("serve_stats", **self.stats)

    def _gauge_snapshot(self) -> Dict[str, float]:
        """Live gauges for the metrics drain — every value is already a
        host scalar (queue/slot/table bookkeeping and stats counters);
        nothing here may touch a device buffer (the DLT001 rule)."""
        g = {"queue_depth": len(self.pending),
             "active_slots": sum(s is not None for s in self.slots),
             "pages_allocated": self.tables.pages_allocated,
             "free_blocks": self.tables.free_blocks,
             "evictions": self.stats["evictions"],
             "timeouts": self.stats["timeouts"]}
        if self.prefix is not None:
            hits, disp = self.stats["prefix_hits"], max(
                self.stats["prefill_dispatches"], 1)
            g["prefix_hit_rate"] = hits / disp
            g["cow_copies"] = self.stats["cow_copies"]
        if "mhc_rows" in self.stats:
            # models/xing's residual mix: rows x sublayers mixed so far, and
            # how far the worst mixing matrix was from doubly stochastic
            g["mhc_rows"] = self.stats["mhc_rows"]
            g["mhc_res_defect"] = self.stats["mhc_res_defect_max"] / 1e6
        if "spec_proposed" in self.stats:
            g["spec_accept_rate"] = (
                self.stats["spec_accepted"]
                / max(self.stats["spec_proposed"], 1))
        return g

    # ---------------------------------------------------------- the driver
    def run(self, requests: List[Request],
            arrivals: Optional[Dict[Any, int]] = None,
            max_ticks: int = 100_000) -> Dict[Any, Completion]:
        """Drain a workload: ``arrivals`` maps req_id → engine tick at
        which the request becomes visible (default: all at tick 0) — the
        staggered-arrival harness the continuous-batching tests drive."""
        arrivals = arrivals or {}
        todo = sorted(requests, key=lambda r: arrivals.get(r.req_id, 0))
        out: Dict[Any, Completion] = {}
        tick = 0
        while todo or self.has_work():
            while todo and arrivals.get(todo[0].req_id, 0) <= tick:
                self.submit(todo.pop(0))
            for c in self.step():
                out[c.req_id] = c
            tick += 1
            if tick > max_ticks:
                raise RuntimeError(
                    f"serving engine did not drain within {max_ticks} ticks "
                    f"({len(self.pending)} pending, "
                    f"{sum(s is not None for s in self.slots)} active)")
        return out
