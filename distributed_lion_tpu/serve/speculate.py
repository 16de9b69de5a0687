"""Speculative decode on the paged KV cache: draft / verify / commit.

ROADMAP item 3 (Leviathan et al., "Fast Inference from Transformers via
Speculative Decoding", 2023): at batch 32-256 the decode tick is
memory-bandwidth-bound on weights it reads once per token, so a cheap
drafter proposes k tokens per slot, ONE batched verify dispatch scores all
of them against the target model, and the accepted prefix commits to the
block tables — the classic 2-3x decode lever, built so the repo's
bit-identity discipline survives intact.

**The acceptance rule is the pinned PRNG stream itself.** The serving
engine already draws every token of request r from
``fold_in(key(r.seed), token_index)`` (serve/engine._sample_rows) — a
stream that depends only on the request, never on batching. The verify
dispatch therefore computes, for every window position, the EXACT token
the non-speculative engine would have produced there (argmax when greedy;
the per-index categorical draw when sampling) and accepts a draft token
iff it equals that pinned draw. The committed tokens ARE the
non-speculative run's tokens by construction — greedy speculative output
is bit-identical to non-speculative paged decode and sampled output is
token-identical to the same per-request stream (tests/test_speculate.py
pins both, across both drafters x k in {2,4}) — and the drafter only ever
changes HOW FAST the stream is emitted, never what it says. (Classic
p/q rejection sampling preserves the output *distribution*; replaying the
pinned stream preserves the output *sequence*, which is the stronger
guarantee this repo's evidence artifacts are built on.)

One speculative tick (replaces the engine's decode tick when
``ServeConfig.speculate`` is set):

- **draft** — the drafter proposes up to k tokens per active slot
  (``serve/draft`` span). Host-side n-gram drafting is pure table math;
  the draft-model drafter is ONE jitted scan dispatch (its per-token
  draws never touch the host — graft-check DLT001 pins the forbidden
  shape, tests/fixtures/analysis/serve/dlt001_verify_host_read.py).
- **verify** — ONE jitted dispatch scores the whole batch's windows
  ``[last_tok, d_1 .. d_k]`` ([B, k+1] with per-row valid counts) against
  the target on the paged cache: speculative k/v land in the already-owned
  or freshly-grown pages (``ops.attention.paged_scatter_kv`` masks the
  invalid tail), attention is causal inside the window, and all k+1 pinned
  draws come back as ONE [B, k+1] array — one host sync per tick, exactly
  like the non-speculative engine (``serve/verify`` span).
- **commit** — per slot: accept the longest draft prefix matching the
  pinned draws, append ``accepted + 1`` tokens (the first mismatch
  position yields the CORRECTED token; a full match yields the bonus
  draw), and roll the block table back over the rejected tail with
  ``BlockTables.shrink`` — the exact inverse of the optimistic grow, so
  len/last/table/free-list state after a partial accept equals what a
  token-by-token run would hold (``serve/commit`` span).

Drafters (one :class:`Drafter` protocol):

- ``ngram:<k>`` — host-side self-drafting suffix-cache lookup (prompt
  lookup decoding): propose the k tokens that followed the most recent
  earlier occurrence of the sequence's own suffix. Zero extra device
  memory or dispatches; great on repetitive / system-prompt traffic,
  proposes nothing (v=0, plain decode) when the history has no signal.
- ``draft:<k>`` — a tiny draft model (its own :class:`ServeModel` with
  its own page pool and block tables, same geometry as the target's)
  greedily proposes k tokens in one scan dispatch. The draft cache mirrors
  the target's committed history exactly: accepted drafts' k/v were
  written during drafting, the corrected/bonus token is ingested as the
  first scan step of the NEXT round, and the rejected tail rolls back
  with the same ``shrink`` math.

MoE checkpoints (ISSUE 15): ``ngram:<k>`` composes — the verify window is
just a wider decode dispatch, MoE inference routing is no-drop per-token
with draft lanes valid-masked (models/gpt2._decode_mlp), and rollback
over MoE pages is attention-side only, so speculative == plain holds
unchanged (tests/test_moe_serve.py pins it). ``draft:<k>`` keeps a loud
refusal: the draft mirror holds its OWN page pool and block tables, and
an expert-parallel target would leave that mirror pool unsharded on the
mesh — the mirror-pool residual (ROADMAP item 3/4) has no honest sharded
budget yet.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from distributed_lion_tpu.serve.kv_cache import BlockTables, init_page_leaves
from distributed_lion_tpu.train import journal


def parse_speculate(spec: str) -> Tuple[str, int]:
    """``"<drafter>:<k>"`` → ``(drafter, k)`` with loud validation — the
    one grammar shared by ServeConfig.speculate, cli/run_serve and
    scripts/bench_serve.py."""
    name, _, ks = spec.partition(":")
    if name not in ("ngram", "draft"):
        raise ValueError(
            f"unknown drafter {name!r} in --speculate {spec!r} "
            "(ngram:<k> | draft:<k>)")
    try:
        k = int(ks)
    except ValueError:
        raise ValueError(
            f"--speculate {spec!r} needs an integer draft length "
            "(e.g. ngram:4)") from None
    if not 1 <= k <= 16:
        raise ValueError(f"--speculate draft length must be in [1, 16], "
                         f"got {k}")
    return name, k


def ngram_propose(seq: List[int], k: int, max_n: int = 3) -> List[int]:
    """Suffix-cache proposal: find the most recent EARLIER occurrence of
    the sequence's longest suffix (n down from ``max_n``) and return up to
    ``k`` of the tokens that followed it. [] = no signal (the caller runs
    a plain decode for that slot). Pure list math — the host-side half of
    prompt-lookup decoding."""
    L = len(seq)
    if k <= 0 or L < 2:
        return []
    for n in range(min(max_n, L - 1), 0, -1):
        pat = seq[L - n:]
        for j in range(L - n - 1, -1, -1):
            if seq[j:j + n] == pat:
                # j + n <= L - 1, so the continuation always has at
                # least seq[j + n] — a match never comes back empty
                return [int(t) for t in seq[j + n:j + n + k]]
    return []


class NGramDrafter:
    """Self-drafting from the request's own token history (prompt + the
    generated stream) — no device state, no extra dispatches.

    The suffix index is INCREMENTAL: each appended token records the
    n-grams it completes (n ≤ max_n) with their two most recent start
    positions, so a propose is max_n dict probes instead of the reference
    scan's full-history walk (O(L) per tick → O(L²) per request — the
    review-flagged shape; :func:`ngram_propose` stays as the reference
    the index is fuzz-pinned against). The current suffix is always its
    own most recent indexed occurrence, so the SECOND-most-recent start
    is exactly the "most recent earlier occurrence" the reference finds.
    Histories sync lazily from the slot's ``gen`` at propose time via a
    consumed-count cursor — no assumptions about which engine path
    (prefill first-token, speculative commit) appended the tokens."""

    name = "ngram"

    def __init__(self, k: int, max_n: int = 3):
        self.k = int(k)
        self.max_n = int(max_n)
        self._hist = {}   # slot -> [token, ...] == req.tokens + gen
        self._index = {}  # slot -> {ngram: (latest_start, prev_start)}
        self._ngen = {}   # slot -> how many of gen are already indexed

    def _append(self, slot: int, tokens) -> None:
        hist, index = self._hist[slot], self._index[slot]
        for t in tokens:
            hist.append(int(t))
            p = len(hist) - 1
            for n in range(1, min(self.max_n, p + 1) + 1):
                gram = tuple(hist[p - n + 1:p + 1])
                prev = index.get(gram)
                index[gram] = (p - n + 1, None if prev is None else prev[0])

    def admit(self, slot: int, tokens: List[int],
              n_committed: int = 0) -> None:
        # ``tokens`` is the slot's full prefilled history; its last
        # ``n_committed`` entries are ALSO the head of the slot's ``gen``
        # (a migrated request resumes mid-stream, serve/replica_plane) —
        # start the gen cursor past them or the propose-time sync would
        # index the committed tokens twice
        self._hist[slot] = []
        self._index[slot] = {}
        self._ngen[slot] = int(n_committed)
        self._append(slot, tokens)

    def evict(self, slot: int) -> None:
        self._hist.pop(slot, None)
        self._index.pop(slot, None)
        self._ngen.pop(slot, None)

    def commit(self, slot: int, cache_len: int) -> None:
        pass  # propose syncs from gen itself — nothing extra to do here

    def _lookup(self, slot: int, k: int) -> List[int]:
        hist, index = self._hist[slot], self._index[slot]
        L = len(hist)
        if k <= 0 or L < 2:
            return []
        for n in range(min(self.max_n, L - 1), 0, -1):
            # the suffix indexed itself when its last token appended, so
            # entry[0] == L - n; entry[1] is the most recent EARLIER start
            j = index[tuple(hist[L - n:])][1]
            if j is not None:
                return hist[j + n:j + n + k]
        return []

    def propose(self, active: List[int], slots, desired: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        drafts = np.zeros((len(slots), self.k), np.int32)
        counts = np.zeros((len(slots),), np.int32)
        for i in active:
            s = slots[i]
            new = s.gen[self._ngen[i]:]
            if new:
                self._append(i, new)
                self._ngen[i] = len(s.gen)
            if len(self._hist[i]) != len(s.req.tokens) + len(s.gen):
                raise RuntimeError(
                    f"ngram history desynced on slot {i}: index holds "
                    f"{len(self._hist[i])} tokens, slot "
                    f"{len(s.req.tokens) + len(s.gen)} — a drafter "
                    "bookkeeping bug")
            cont = self._lookup(i, int(desired[i]))
            counts[i] = len(cont)
            drafts[i, :len(cont)] = cont
        return drafts, counts


class DraftModelDrafter:
    """A small draft model proposing greedily on its OWN paged cache.

    The draft cache mirrors the target's committed history position for
    position (``self.len[slot] == slot.cache_len`` at every tick start):
    one scan dispatch per round ingests the newest committed token
    (``last_tok``) and drafts k more, writing their k/v as it goes, so an
    accepted draft's cache entry is already in place and a rejected tail
    rolls back with the same :meth:`BlockTables.shrink` math as the
    target. A slot whose draft pool can't fit even the ingest goes
    draft-dead (plain decode, counted in ``draft_dead``) rather than
    corrupting the mirror — loud in stats, silent in outputs."""

    name = "draft"

    def __init__(self, model, k: int, cfg):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.k = int(k)
        self.cfg = cfg
        nb = cfg.resolved_num_blocks()
        horizon = cfg.block_size * cfg.max_blocks_per_seq
        if model.max_positions is not None and horizon > model.max_positions:
            raise ValueError(
                f"draft model's position budget {model.max_positions} is "
                f"smaller than the page horizon {horizon}; a draft window "
                "past it would silently alias — use a draft model trained "
                "to at least the serving horizon")
        self.tables = BlockTables(nb, cfg.block_size, cfg.max_seqs,
                                  cfg.max_blocks_per_seq)
        self.pages = init_page_leaves(model.n_layer, nb, cfg.block_size,
                                      model.page_leaves, model.cache_dtype)
        self.len = np.zeros((cfg.max_seqs,), np.int32)
        self.dead = np.zeros((cfg.max_seqs,), bool)
        self.draft_dead = 0
        donate = (1,) if jax.default_backend() != "cpu" else ()

        def prefill(params, pages, tables, toks, length):
            valid = jnp.arange(toks.shape[1])[None, :] < length
            _, pages = model.decode_paged(params, toks, pages, tables,
                                          jnp.zeros((1,), jnp.int32), valid)
            return pages

        def draft(params, pages, tables, lens, last, dcount):
            # scan step i ingests window token i (i=0: last_tok, i>=1: the
            # (i)th draft) at position lens+i and emits the NEXT greedy
            # token; rows write only steps 0..dcount[row] (masked beyond),
            # draft-dead rows (dcount=-1) write nothing. The final step's
            # emitted token is discarded — it only exists to write d_k's
            # k/v so a fully-accepted round leaves the mirror complete.
            def body(carry, i):
                tok, pos, pages = carry
                valid = (i <= dcount)[:, None]
                logits, pages = model.decode_paged(params, tok[:, None],
                                                   pages, tables, pos, valid)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(tok.dtype)
                return (nxt, pos + 1, pages), nxt

            (_, _, pages), toks = jax.lax.scan(
                body, (last, lens, pages),
                jnp.arange(self.k + 1, dtype=lens.dtype))
            return toks[: self.k].T, pages  # [B, k] proposals

        self._prefill = jax.jit(prefill, donate_argnums=donate)
        self._draft = jax.jit(draft, donate_argnums=donate)
        # pre-jit bodies + donation, kept for the engine's dispatch
        # registry (build_speculator registers the mirror's dispatches so
        # analysis/serve_check and compile_counts() see EVERY serve
        # dispatch, the draft mirror's included)
        self._prefill_fn, self._draft_fn = prefill, draft
        self._donate = donate

    def _bucket(self, n: int) -> int:
        # the engine's exact bucketing rule — the mirror must pad like
        # the target or the two prefills land k/v at different positions
        return self.cfg.bucket(n)

    def _go_dead(self, slot: int) -> None:
        # a dead slot decodes plain until evicted — hand its mirror pages
        # back NOW, or under a tight draft pool one dead slot's stranded
        # history cascades every other slot into draft-dead too
        self.dead[slot] = True
        self.draft_dead += 1
        self.tables.free_slot(slot)
        self.len[slot] = 0

    def admit(self, slot: int, tokens: List[int],
              n_committed: int = 0) -> None:
        # the mirror prefills the slot's FULL history (a migrated
        # request's committed tokens included — they are cache content
        # like any other); n_committed only matters to gen-cursor
        # drafters, so it is accepted and unused here
        import jax.numpy as jnp

        L = len(tokens)
        if not self.tables.grow(slot, L):
            self._go_dead(slot)
            return
        P = self._bucket(L)
        toks = np.zeros((1, P), np.int32)
        toks[0, :L] = tokens
        self.pages = self._prefill(
            self.model.params, self.pages,
            jnp.asarray(self.tables.tables[slot:slot + 1]),
            jnp.asarray(toks), jnp.int32(L))
        self.len[slot] = L
        self.dead[slot] = False

    def evict(self, slot: int) -> None:
        self.tables.free_slot(slot)
        self.len[slot] = 0
        self.dead[slot] = False

    def commit(self, slot: int, cache_len: int) -> None:
        if self.dead[slot]:
            return
        # accepted drafts' k/v were written during drafting; the rejected
        # tail rolls back exactly like the target's
        self.len[slot] = cache_len
        self.tables.shrink(slot, cache_len)

    def propose(self, active: List[int], slots, desired: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp

        S = len(slots)
        dcount = np.full((S,), -1, np.int32)
        lens = np.zeros((S,), np.int32)
        last = np.zeros((S,), np.int32)
        for i in active:
            if self.dead[i]:
                continue
            if int(self.len[i]) != int(slots[i].cache_len):
                raise RuntimeError(
                    f"draft cache desynced on slot {i}: draft holds "
                    f"{int(self.len[i])} positions, target "
                    f"{int(slots[i].cache_len)} — a drafter bookkeeping bug")
            d = int(desired[i])
            while d >= 0 and not self.tables.grow(
                    i, int(self.len[i]) + d + 1):
                d -= 1
            if d < 0:
                self._go_dead(i)
                continue
            dcount[i] = d
            lens[i] = self.len[i]
            last[i] = slots[i].last_tok
        drafts, self.pages = self._draft(
            self.model.params, self.pages, jnp.asarray(self.tables.tables),
            jnp.asarray(lens), jnp.asarray(last), jnp.asarray(dcount))
        drafts = np.asarray(drafts)  # ONE host sync per draft dispatch
        return drafts, np.maximum(dcount, 0)


class Speculator:
    """The engine-side driver: owns the drafter and the jitted verify
    dispatch, and runs the speculative decode tick in place of the
    engine's one-token tick (serve/engine.ServingEngine._decode)."""

    def __init__(self, engine, drafter, k: int):
        import jax
        import jax.numpy as jnp

        from distributed_lion_tpu.serve.engine import _sample_rows

        self.engine = engine
        self.drafter = drafter
        self.k = int(k)
        for key in ("spec_rounds", "spec_proposed", "spec_accepted"):
            engine.stats.setdefault(key, 0)
        samp = (engine.cfg.temperature, engine.cfg.top_k, engine.cfg.top_p)
        model = engine.model
        # the engine's resolved mesh axes (None off-mesh): an ep-only mesh
        # must NOT bind the tensor axis here — the verify window shards
        # exactly like the engine's own decode tick
        tp_axis, ep_axis = engine._tp_axis, engine._ep_axis

        moe_stats = engine._moe_stats
        stats_axis = ep_axis if engine._ep_batch else None

        def verify(params, pages, tables, lens, window, vcounts, seeds,
                   counts):
            # window [B, k+1] = [last_tok, d_1 .. d_k]; row b's first
            # vcounts[b] entries are real (0 = inactive slot: every write
            # drops, the draws are garbage the host never reads). Under
            # batch-sharded ep every operand is this shard's local slot
            # slice, tables carry group-local page ids.
            W = window.shape[1]
            valid = jnp.arange(W)[None, :] < vcounts[:, None]
            out = model.decode_paged(params, window, pages, tables, lens,
                                     valid, tp_axis=tp_axis,
                                     ep_axis=ep_axis,
                                     return_moe_stats=moe_stats,
                                     stats_axis=stats_axis)
            logits, pages = out[0], out[1]
            st = out[2] if moe_stats else {}
            B, _, V = logits.shape
            # the pinned per-request stream: position s of row b draws
            # with fold_in(key(seed_b), counts_b + s) — exactly the key
            # the non-speculative tick would use for that token index
            seeds_r = jnp.repeat(seeds, W)
            counts_r = (counts[:, None]
                        + jnp.arange(W, dtype=counts.dtype)[None, :])
            draws = _sample_rows(logits.reshape(B * W, V), seeds_r,
                                 counts_r.reshape(-1), *samp)
            return (draws.reshape(B, W), st), pages

        # the engine's dispatch wrapper: plain jit at tp=0, shard_map'd
        # over the serving mesh under TP (ISSUE 13) — the verify window
        # is just a wider decode tick, so it shards identically; under
        # batch-sharded ep (ISSUE 16) every slot-leading operand and the
        # [B, k+1] draws shard over the expert axis like the decode tick
        if engine._ep_batch:
            from jax.sharding import PartitionSpec as P

            from distributed_lion_tpu.parallel.mesh import EXPERT_AXIS

            bsp, rep = P(EXPERT_AXIS), P()
            self._verify = engine._jit_paged(
                verify, n_rest=6,
                rest_specs=(P(EXPERT_AXIS, None), bsp, bsp, bsp, bsp, bsp),
                out_spec=(bsp, rep), name="verify")
        else:
            self._verify = engine._jit_paged(verify, n_rest=6,
                                             name="verify")

    # lifecycle relays from the engine
    def on_admit(self, slot: int, tokens: List[int],
                 n_committed: int = 0) -> None:
        self.drafter.admit(slot, tokens, n_committed)

    def on_evict(self, slot: int) -> None:
        self.drafter.evict(slot)

    def decode_tick(self, completions: List) -> None:
        import jax.numpy as jnp

        eng = self.engine
        tables = eng.tables
        active = [i for i, s in enumerate(eng.slots) if s is not None]
        if not active:
            return
        S = eng.cfg.max_seqs
        span = journal.span

        # two-phase grow. Phase 1 reserves every active slot's ONE
        # mandatory write (last_tok) first — the exact loop the plain
        # tick runs — so WITHIN a tick drafting never costs a LATER slot
        # its mandatory page because an earlier slot optimistically took
        # k extra (the single-phase grow had that bug; regression-pinned
        # on a symmetric workload). ACROSS ticks no such pin is possible:
        # speculation advances high-accept slots more tokens per tick, so
        # when the pool exhausts under an ASYMMETRIC workload the
        # overflow eviction can land on a different request than plain —
        # a race against exhaustion whose racers changed speed, not
        # words. The unconditional invariant (pinned): each request's
        # output is a prefix of the other run's, completed requests
        # identical.
        cow_pairs = []
        for i in list(active):
            s = eng.slots[i]
            if not (eng._grow(i, s.cache_len + 1)
                    and eng._cow_if_shared(i, s.cache_len, cow_pairs)):
                eng._maybe_finish(i, completions, overflow=True)
                active.remove(i)
        if not active:
            return
        # Phase 2: drafts claim only the LEFTOVER pool — the token budget
        # caps the window (a slot one token from its budget needs no
        # drafts), then degrade to fewer drafts as grows fail; rejected
        # tails hand their pages back at commit. Only the FIRST write
        # position can sit in a shared page (pages past the prompt are
        # always private), so phase 1's CoW covers the whole window.
        desired = np.zeros((S,), np.int32)
        for i in active:
            s = eng.slots[i]
            v = max(min(self.k, s.budget - len(s.gen) - 1), 0)
            # plain tables.grow, NOT eng._grow: a draft page is optional
            # and rolls back at commit — it must degrade to fewer drafts
            # under pressure, never evict prefix-cache chains to exist
            while v > 0 and not tables.grow(i, s.cache_len + v + 1):
                v -= 1
            desired[i] = v
        eng._flush_cow(cow_pairs)

        with span("serve/draft", drafter=self.drafter.name,
                       batch=len(active), k=self.k):
            drafts, counts = self.drafter.propose(active, eng.slots, desired)

        window = np.zeros((S, self.k + 1), np.int32)
        vcounts = np.zeros((S,), np.int32)
        lens = np.zeros((S,), np.int32)
        seeds = np.zeros((S,), np.uint32)
        gcounts = np.zeros((S,), np.int32)
        for i in active:
            s = eng.slots[i]
            v = int(min(desired[i], counts[i]))
            desired[i] = v
            window[i, 0] = s.last_tok
            if v:
                window[i, 1:1 + v] = drafts[i, :v]
            vcounts[i] = v + 1
            lens[i] = s.cache_len
            seeds[i] = s.req.seed
            gcounts[i] = len(s.gen)

        with span("serve/verify", batch=len(active),
                       proposed=int(sum(desired[i] for i in active))):
            rest = (eng._device_tables(), jnp.asarray(lens),
                    jnp.asarray(window), jnp.asarray(vcounts),
                    jnp.asarray(seeds), jnp.asarray(gcounts))
            eng._guard("verify", rest)
            (draws, st), eng.pages = self._verify(
                eng.params, eng.pages, *rest)
            # ONE host sync for the batch
            draws = eng._host_read(draws, "verify", eng.stats["ticks"])
            eng._absorb_moe_stats(st)

        accepted_total = committed_total = 0
        with span("serve/commit", batch=len(active)) as commit_span:
            for i in active:
                s = eng.slots[i]
                v = int(desired[i])
                m = 0
                while m < v and draws[i, m] == window[i, m + 1]:
                    m += 1
                eng.stats["spec_proposed"] += v
                eng.stats["spec_accepted"] += m
                accepted_total += m
                # commit draws[0..m] one at a time with the plain tick's
                # finish rules — EOS inside the accepted prefix truncates
                # there, exactly where the token-by-token run would stop
                finished = False
                n_taken = 0
                for t in (int(t) for t in draws[i, :m + 1]):
                    s.gen.append(t)
                    n_taken += 1
                    if (eng.cfg.eos_id is not None
                            and t == eng.cfg.eos_id) \
                            or len(s.gen) >= s.budget:
                        finished = True
                        break
                s.cache_len += n_taken
                s.last_tok = s.gen[-1]
                eng.stats["decode_tokens"] += n_taken
                committed_total += n_taken
                if finished:
                    eng._maybe_finish(i, completions)
                    continue
                # roll the rejected tail's pages back: post-commit state
                # == the state a token-by-token run would hold
                tables.shrink(i, s.cache_len)
                self.drafter.commit(i, s.cache_len)
            commit_span.set(accepted=accepted_total,
                            committed=committed_total)
        eng.stats["decode_ticks"] += 1
        eng.stats["spec_rounds"] += 1


def build_speculator(engine, spec: str,
                     draft_model: Optional[object] = None) -> Speculator:
    """Construct the Speculator for ``ServeConfig.speculate`` — called by
    ServingEngine at build. ``draft_model`` (a ServeModel) is required for
    ``draft:<k>`` and must share the target's vocabulary."""
    name, k = parse_speculate(spec)
    if name == "ngram":
        drafter = NGramDrafter(k)
    else:
        if getattr(engine.model.cfg, "moe_experts", 0) > 0 or (
                draft_model is not None
                and getattr(draft_model.cfg, "moe_experts", 0) > 0):
            raise ValueError(
                "--speculate draft:<k> does not support MoE checkpoints "
                "yet: the draft MIRROR keeps its own page pool and block "
                "tables, and that mirror pool has no sharded budget under "
                "expert parallelism — the mirror-pool residual (ROADMAP "
                "items 3/4); use ngram:<k> (pinned speculative==plain for "
                "MoE) or serve without speculation")
        if engine._mesh is not None:
            raise ValueError(
                "--speculate draft:<k> does not compose with --serve_tp "
                "yet: the draft mirror would keep its own unsharded page "
                "pool on rank 0 and steal page-pool HBM from the sharded "
                "target (ROADMAP item 3 residual); use ngram:<k> — the "
                "host-side drafter needs no device state — or serve "
                "without TP")
        if draft_model is None:
            raise ValueError(
                "--speculate draft:<k> needs a draft model "
                "(ServingEngine(draft_model=...) / cli --draft_model_path)")
        tv = getattr(engine.model.cfg, "vocab_size", None)
        dv = getattr(draft_model.cfg, "vocab_size", None)
        if tv != dv:
            raise ValueError(
                f"draft model vocab {dv} != target vocab {tv}; the drafted "
                "token ids would be meaningless to the target")
        drafter = DraftModelDrafter(draft_model, k, engine.cfg)
        engine._register_dispatch("draft_prefill", drafter._prefill,
                                  drafter._prefill_fn, drafter._donate,
                                  None, None)
        engine._register_dispatch("draft_step", drafter._draft,
                                  drafter._draft_fn, drafter._donate,
                                  None, None)
    return Speculator(engine, drafter, k)
