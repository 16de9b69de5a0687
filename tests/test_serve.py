"""Serving subsystem (ISSUE 9): paged-KV decode bit-identical to the dense
cache, continuous batching identical to solo runs, host-side page
allocator invariants, NF4 frozen-weight serving, fairness cap, the
request-file API, and the banked serving evidence artifact."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.models.gpt2 import (
    GPT2Config, gpt2_decode, gpt2_decode_paged, gpt2_init, gpt2_init_cache,
)
from distributed_lion_tpu.models.llama import (
    LlamaConfig, llama_decode, llama_decode_paged, llama_init,
    llama_init_cache,
)
from distributed_lion_tpu.serve.engine import (
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
    weight_bytes,
)
from distributed_lion_tpu.serve.kv_cache import BlockTables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# Model hooks called by hand run COMPILED, one program a shape (ISSUE 35):
# eagerly a forward pass is a few hundred one-op programs. ``cfg`` is static.
_compiled = {fn: jax.jit(fn, static_argnums=2) for fn in (
    gpt2_decode, gpt2_decode_paged, llama_decode, llama_decode_paged)}


def _tokens(vocab, b, t, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(1, vocab, (b, t)), jnp.int32)


# ------------------------------------------------------- paged == dense
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_paged_decode_bit_identical_to_dense(family):
    """Prefill + per-token decode through SHUFFLED block tables produces
    bit-identical logits to the dense KV cache at the same attended
    length — the paged layout is pure indirection, never arithmetic."""
    if family == "gpt2":
        cfg = GPT2Config.tiny()
        params = gpt2_init(jax.random.key(0), cfg)
        dec, icache, decp, kv = _compiled[gpt2_decode], gpt2_init_cache, \
            _compiled[gpt2_decode_paged], cfg.n_head
    else:
        cfg = LlamaConfig.tiny()  # GQA: pages hold kv heads un-repeated
        params = llama_init(jax.random.key(0), cfg)
        dec, icache, decp, kv = _compiled[llama_decode], llama_init_cache, \
            _compiled[llama_decode_paged], cfg.n_kv_head
    B, L, bs, nb_seq = 2, 7, 4, 4          # both caches attend 16 slots
    toks = _tokens(cfg.vocab_size, B, L)
    cache = icache(cfg, B, bs * nb_seq)
    dl, cache = dec(params, toks, cfg, cache, 0)
    pages = [{k: jnp.zeros((B * nb_seq, bs, kv, cfg.head_dim),
                           cfg.compute_dtype) for k in ("k", "v")}
             for _ in range(cfg.n_layer)]
    # interleaved/shuffled page ownership: the gather must reassemble
    # purely via the table, not via any layout assumption
    tables = jnp.asarray([[2, 0, 1, 3], [5, 7, 4, 6]], jnp.int32)
    pl, pages = decp(params, toks, cfg, pages, tables,
                     jnp.zeros((B,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(dl), np.asarray(pl))
    t_cur = jnp.argmax(dl[:, -1], -1)
    lens = jnp.full((B,), L, jnp.int32)
    for i in range(5):
        dl, cache = dec(params, t_cur[:, None], cfg, cache, L + i)
        pl, pages = decp(params, t_cur[:, None], cfg, pages, tables, lens)
        np.testing.assert_array_equal(np.asarray(dl), np.asarray(pl))
        t_cur = jnp.argmax(dl[:, -1], -1)
        lens = lens + 1


def test_paged_prefill_valid_mask_drops_pad_tail():
    """A right-padded prefill (the engine's bucketed shape) must write
    exactly the real tokens' pages: logits at real positions match an
    unpadded prefill, and a later decode step agrees too. Two dispatches
    of DIFFERENT shapes ([1, 5] vs [1, 8] tokens) are different XLA
    programs whose reductions may round differently in the last place, so
    those are held to a few ulps of f32 with the argmax exact; where two
    dispatches share a shape, equality stays exact."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(1), cfg)
    L, P, bs = 5, 8, 4
    toks = _tokens(cfg.vocab_size, 1, L, seed=2)
    padded = jnp.concatenate(
        [toks, jnp.zeros((1, P - L), jnp.int32)], axis=1)

    def pages():
        return [{k: jnp.zeros((4, bs, cfg.n_head, cfg.head_dim),
                              cfg.compute_dtype) for k in ("k", "v")}
                for _ in range(cfg.n_layer)]

    tables = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    decp = _compiled[gpt2_decode_paged]
    ref, ref_pages = decp(params, toks, cfg, pages(), tables, zero)
    valid = (jnp.arange(P) < L)[None, :]
    got, got_pages = decp(params, padded, cfg, pages(), tables, zero, valid)
    ref, got = np.asarray(ref), np.asarray(got[:, :L])
    assert ref.dtype == np.float32
    few_ulps = 4 * np.finfo(np.float32).eps * np.abs(ref).max()
    np.testing.assert_allclose(got, ref, rtol=0, atol=few_ulps)
    np.testing.assert_array_equal(ref.argmax(-1), got.argmax(-1))
    # the pad tail wrote NOTHING: pages past the real tokens stay zero in
    # both pools (exact — this is indirection, not arithmetic)
    for rp, gp in zip(ref_pages, got_pages):
        for key in ("k", "v"):
            flat = np.asarray(gp[key], np.float32).reshape(-1, *gp[key].shape[2:])
            assert not flat[L:].any()
            page_ulps = 4 * float(jnp.finfo(gp[key].dtype).eps) * max(
                1.0, float(np.abs(flat).max()))
            np.testing.assert_allclose(
                flat[:L], np.asarray(rp[key], np.float32).reshape(
                    flat.shape)[:L], rtol=0, atol=page_ulps)
    nxt = jnp.argmax(jnp.asarray(ref)[:, L - 1], -1)[:, None]
    lens = jnp.full((1,), L, jnp.int32)
    # same shape, same program, SAME pages -> exact; and the decode step
    # over the padded prefill's pages agrees with the unpadded one's
    a, _ = decp(params, nxt, cfg, ref_pages, tables, lens)
    a2, _ = decp(params, nxt, cfg, ref_pages, tables, lens)
    b, _ = decp(params, nxt, cfg, got_pages, tables, lens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                               atol=few_ulps)
    np.testing.assert_array_equal(np.asarray(a).argmax(-1),
                                  np.asarray(b).argmax(-1))


# --------------------------------------------- multi-token window commit
def test_multi_token_scatter_matches_sequential():
    """The speculative-verify window commit (ISSUE 11): one [B, S] window
    scatter through ``paged_scatter_kv`` is bit-identical to S sequential
    single-token scatters — same (page, offset) cells, same values,
    masked tails and sentinel rows dropping identically — including
    shuffled tables and rows whose windows straddle a page boundary."""
    from distributed_lion_tpu.ops.attention import paged_scatter_kv

    rng = np.random.default_rng(0)
    NB, bs, KV, hd, B, S = 6, 4, 2, 8, 3, 5
    pool = jnp.asarray(rng.standard_normal((NB, bs, KV, hd)), jnp.float32)
    # row 0: shuffled pages mid-sequence; row 1: window crosses into a
    # fresh page; row 2: SENTINEL table row (inactive slot — every write
    # must drop)
    tables = jnp.asarray([[4, 1, 3], [2, 0, 5], [NB, NB, NB]], jnp.int32)
    pos = jnp.asarray([1, 6, 0], jnp.int32)
    new = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    # per-row valid COUNTS, the verify-window shape: arange(S) < counts
    counts = jnp.asarray([5, 3, 4], jnp.int32)
    valid = jnp.arange(S)[None, :] < counts[:, None]

    window = paged_scatter_kv(pool, tables, pos, new, valid)

    seq = pool
    for s in range(S):
        seq = paged_scatter_kv(seq, tables, pos + s, new[:, s:s + 1],
                               valid[:, s:s + 1])
    np.testing.assert_array_equal(np.asarray(window), np.asarray(seq))
    # the sentinel row and the masked tails never touched the pool:
    # replaying only the valid in-range writes reproduces it too
    redo = pool
    for b in range(B - 1):          # row 2 is all-sentinel: contributes 0
        for s in range(int(counts[b])):
            redo = paged_scatter_kv(redo, tables[b:b + 1], pos[b:b + 1] + s,
                                    new[b:b + 1, s:s + 1])
    np.testing.assert_array_equal(np.asarray(window), np.asarray(redo))


def test_block_tables_shrink_is_exact_inverse_of_grow():
    """``BlockTables.shrink`` — the speculative rollback primitive — is
    the exact inverse of ``grow``: after an optimistic grow for k draft
    tokens and a rollback to the accepted length, the tables, owned
    counts AND the LIFO free-list order are bit-identical to having grown
    to the accepted length directly (what a token-by-token run holds)."""
    import copy

    def state(bt):
        return (bt.tables.copy(), bt.owned.copy(), list(bt._free))

    ref = BlockTables(num_blocks=12, block_size=4, max_seqs=3,
                      max_blocks_per_seq=4)
    # interleaved multi-slot history so page ownership is shuffled
    assert ref.grow(0, 6) and ref.grow(1, 3) and ref.grow(2, 9)
    spec = copy.deepcopy(ref)

    # token-by-token: slot 0 advances to 9 total entries (one new page)
    assert ref.grow(0, 9)
    # speculative: slot 0 optimistically grows for a k=7 window (to 13 →
    # two extra pages), then a partial accept rolls back to 9
    assert spec.grow(0, 13)
    assert spec.owned[0] > ref.owned[0]
    freed = spec.shrink(0, 9)
    assert freed == 1
    for a, b in zip(state(spec), state(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # shrink to a length needing all owned pages (or more) is a no-op
    assert spec.shrink(0, 9) == 0 and spec.shrink(0, 100) == 0
    for a, b in zip(state(spec), state(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # full-round-trip: rollback to the pre-speculation state frees in
    # reverse allocation order, so a subsequent grow reuses the SAME pages
    before = state(spec)
    assert spec.grow(0, 16)
    spec.shrink(0, 9)
    for a, b in zip(state(spec), before):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- refcounts / CoW / prefix cache
def test_block_tables_share_refcounts_and_cow():
    """ISSUE 13: grow mints ref-1 pages; share bumps refs; shrink/free
    over shared pages release refs without freeing; cow swaps in a fresh
    private page and the original survives for its other holders."""
    bt = BlockTables(num_blocks=8, block_size=4, max_seqs=3,
                     max_blocks_per_seq=4)
    assert bt.grow(0, 8)                       # slot 0: 2 pages
    run = [int(bt.tables[0, 0]), int(bt.tables[0, 1])]
    assert all(bt.refs[p] == 1 for p in run)
    bt.share(1, run)                           # slot 1 shares both
    assert all(bt.refs[p] == 2 for p in run)
    assert bt.grow(1, 12)                      # + 1 private page
    free_before = bt.free_blocks
    assert bt.shrink(1, 8) == 1                # private page freed...
    assert bt.free_blocks == free_before + 1
    assert bt.shrink(1, 4) == 0                # ...shared page only deref'd
    assert bt.refs[run[1]] == 1 and bt.free_blocks == free_before + 1
    # cow: slot 1's remaining shared page becomes private
    bt.share(2, [run[0]])
    assert bt.refs[run[0]] == 3
    pair = bt.cow(2, 0)
    assert pair is not None and pair[0] == run[0]
    assert bt.refs[run[0]] == 2 and bt.refs[pair[1]] == 1
    assert int(bt.tables[2, 0]) == pair[1]
    # evicting the sharer frees only what nobody else holds
    assert bt.free_slot(2) == 1                # the cow'd private page
    assert bt.free_slot(1) == 0                # run[0] still owned by slot 0
    assert bt.free_slot(0) == 2                # now both physically free
    assert bt.free_blocks == bt.num_blocks


def test_block_tables_refcount_fuzz_vs_reference():
    """Property fuzz: random grow/shrink/share/cow/free sequences against
    a dict-based reference counter — refcounts agree exactly, the free
    list never holds a live page or a duplicate, and pages are conserved
    (free + live == pool) at every step."""
    rng = np.random.default_rng(42)
    bt = BlockTables(num_blocks=24, block_size=4, max_seqs=4,
                     max_blocks_per_seq=6)
    refs = {}          # page -> count (the reference counter)
    slot_pages = {s: [] for s in range(4)}
    cache_refs = []    # pages the "cache" holds a ref on

    def check():
        live = {p for p, c in refs.items() if c > 0}
        flat = [p for grp in bt._free for p in grp]
        free = set(flat)
        assert len(flat) == len(free), "duplicate page on free list"
        assert not (live & free), "live page on the free list"
        assert live | free == set(range(bt.num_blocks)), "page leaked"
        for p in range(bt.num_blocks):
            assert bt.refs[p] == refs.get(p, 0), f"refcount drift page {p}"

    for _ in range(600):
        op = rng.choice(["grow", "shrink", "free", "share", "cow",
                         "cache_ref", "cache_drop", "crash"])
        s = int(rng.integers(0, 4))
        if op == "crash":
            # mid-fuzz replica crash (ISSUE 14): a random subset of slots
            # — the dead replica's residents — mass-free at once, the way
            # a migration releases them. Pages the SURVIVORS still hold
            # (other slots' shared runs, the cache's refs) must survive
            # the mass free; the post-op check pins exact refcounts, no
            # live page on the free list, and page conservation.
            victims = [v for v in range(4) if rng.integers(0, 2)]
            for v in victims:
                for p in slot_pages[v]:
                    refs[p] -= 1
                bt.free_slot(v)
                slot_pages[v] = []
            check()
            continue
        if op == "grow":
            n = int(rng.integers(1, bt.max_blocks_per_seq * bt.block_size))
            before = [int(p) for p in bt.tables[s, :bt.owned[s]]]
            if bt.grow(s, n):
                now = [int(p) for p in bt.tables[s, :bt.owned[s]]]
                for p in now[len(before):]:
                    refs[p] = refs.get(p, 0) + 1
                slot_pages[s] = now
        elif op == "shrink":
            n = int(rng.integers(0, bt.max_blocks_per_seq * bt.block_size))
            keep = bt.blocks_for(n)
            dropped = slot_pages[s][keep:] if keep < len(slot_pages[s]) \
                else []
            bt.shrink(s, n)
            for p in dropped:
                refs[p] -= 1
            slot_pages[s] = slot_pages[s][:min(keep, len(slot_pages[s]))]
        elif op == "free":
            for p in slot_pages[s]:
                refs[p] -= 1
            bt.free_slot(s)
            slot_pages[s] = []
        elif op == "share":
            donor = int(rng.integers(0, 4))
            if slot_pages[s] or not slot_pages[donor]:
                continue
            k = int(rng.integers(1, len(slot_pages[donor]) + 1))
            run = slot_pages[donor][:k]
            bt.share(s, run)
            for p in run:
                refs[p] += 1
            slot_pages[s] = list(run)
        elif op == "cow":
            shared = [i for i, p in enumerate(slot_pages[s])
                      if refs.get(p, 0) > 1]
            if not shared:
                continue
            i = shared[0]
            pair = bt.cow(s, i * bt.block_size)
            if pair is None:
                continue
            old, new = pair
            refs[old] -= 1
            refs[new] = refs.get(new, 0) + 1
            slot_pages[s][i] = new
        elif op == "cache_ref":
            if not slot_pages[s]:
                continue
            p = slot_pages[s][0]
            bt.add_ref(p)
            refs[p] += 1
            cache_refs.append(p)
        elif op == "cache_drop":
            if not cache_refs:
                continue
            p = cache_refs.pop()
            bt.release_page(p)
            refs[p] -= 1
        check()
    # drain everything: the pool must come back whole
    for s in range(4):
        for p in slot_pages[s]:
            refs[p] -= 1
        bt.free_slot(s)
    for p in cache_refs:
        refs[p] -= 1
        bt.release_page(p)
    check()
    assert bt.free_blocks == bt.num_blocks


# (run_pages, num_blocks, groups, seed): 98 and 100 pages leave a tail that
# no whole aligned run covers; two groups split the pool into spans whose
# edges need not be run edges; runs of 1 are the historical allocator
RUN_CASES = [(4, 96, 1, 0), (4, 96, 1, 1), (4, 98, 1, 2), (2, 64, 2, 3),
             (4, 100, 2, 4), (8, 128, 1, 5), (1, 40, 1, 6), (1, 40, 2, 7)]


@pytest.mark.parametrize("r,num_blocks,groups,seed", RUN_CASES)
def test_block_tables_mint_aligned_runs(r, num_blocks, groups, seed):
    """``BlockTables(run_pages=r)``: a seeded fuzz of ``grow`` (a page or
    two at a time, the slots in turn, as decoding slots grow) and
    ``free_slot`` (in scrambled order, the freed slots admitted again into
    the descending free lists) over 8 slots. After EVERY call: the owned
    entries among ``tables[slot, r j : r j + r]`` are consecutive ids from a
    multiple of ``r``, inside the slot's group; ``can_grow`` says what
    ``grow`` then does and a refused ``grow`` changes nothing; ``owned``,
    ``refs``, the free lists and ``unused_blocks`` add up to the pool; and
    the tables are, pop for pop, those of a LIFO list of run heads written
    out here apart from the class (at ``r`` 1: the allocator as it always
    was). Sharing, copy-on-write and rollback work by the page and raise."""
    rng = np.random.default_rng(seed)
    n_slots, per = 8, 11                    # a table no multiple of 4 or 8
    bt = BlockTables(num_blocks, 4, n_slots, per, groups=groups,
                     run_pages=r)
    bpg, spg = num_blocks // groups, n_slots // groups
    model_free = [[h for h in range(num_blocks - 1, -1, -1)
                   if h % r == 0 and g * bpg <= h and h + r <= (g + 1) * bpg]
                  for g in range(groups)]
    model = [[] for _ in range(n_slots)]
    whole = sum(len(f) for f in model_free) * r
    assert bt.unused_blocks == num_blocks - whole
    assert bt.unused_blocks == {98: 2, 100: 4}.get(num_blocks, 0)
    assert bt.free_blocks == whole

    def check():
        held = 0
        for s in range(n_slots):
            n, row = int(bt.owned[s]), bt.tables[s]
            assert row[:n].tolist() == model[s]
            assert (row[n:] == bt.sentinel).all()
            for j in range(0, n, r):
                run = row[j:min(j + r, n)]
                assert run[0] % r == 0, (s, j, run)
                assert (np.diff(run) == 1).all(), (s, j, run)
                assert run[0] // bpg == s // spg == (run[0] + r - 1) // bpg
            held += -(-n // r) * r
        assert [list(f) for f in bt._free] == model_free
        heads = [h for f in bt._free for h in f]
        assert len(set(heads)) == len(heads)
        assert all(not bt.refs[h:h + r].any() for h in heads)
        owned = sorted(p for m in model for p in m)
        assert np.flatnonzero(bt.refs).tolist() == owned
        assert int(bt.refs.sum()) == int(bt.owned.sum()) == len(owned)
        assert bt.free_blocks + held + bt.unused_blocks == num_blocks
        assert bt.free_blocks == sum(bt.free_blocks_in(g)
                                     for g in range(groups))

    minted = 0
    for step in range(900):
        s = int(rng.integers(0, n_slots))
        if rng.random() < 0.12 or bt.owned[s] == per:
            freed = bt.free_slot(s)                 # frees in any order
            assert freed == len(model[s])
            model_free[s // spg].extend(model[s][::r])
            model[s] = []
        else:
            tokens = int(bt.owned[s]) * 4 + int(rng.integers(1, 9))
            need = min(-(-tokens // 4), per + 1)
            take = -(-need // r) - -(-len(model[s]) // r)
            can = need <= per and take <= len(model_free[s // spg])
            assert bt.can_grow(s, tokens) == can
            before = (bt.tables.copy(), bt.owned.copy(), bt.refs.copy())
            assert bt.grow(s, tokens) == can
            if can:
                for i in range(len(model[s]), need):
                    model[s].append(model_free[s // spg].pop() if i % r == 0
                                    else model[s][-1] + 1)
                    minted += 1
            else:
                for a, b in zip(before, (bt.tables, bt.owned, bt.refs)):
                    np.testing.assert_array_equal(a, b)
        check()
    assert bt.pages_allocated == minted > 300
    for s in rng.permutation(n_slots):
        bt.free_slot(int(s))
    assert bt.free_blocks == whole and not bt.refs.any()

    assert bt.grow(0, 6)
    calls = {"share": lambda: bt.share(1, [int(bt.tables[0, 0])]),
             "cow": lambda: bt.cow(0, 0), "shrink": lambda: bt.shrink(0, 1),
             "add_ref": lambda: bt.add_ref(int(bt.tables[0, 0]))}
    for name, call in calls.items():
        if r > 1:
            with pytest.raises(NotImplementedError, match=f"{name}.*runs of"):
                call()
    if r == 1:
        calls["share"](), calls["add_ref"]()
        assert bt.refs[bt.tables[0, 0]] == 3 and bt.shrink(0, 1) == 1
    with pytest.raises(ValueError, match="run_pages=0"):
        BlockTables(num_blocks, 4, n_slots, per, run_pages=0)


def test_paged_copy_then_scatter_matches_scatter_after_deep_copy():
    """The CoW device primitive: copying a page with paged_copy_pages and
    then multi-token-scattering into the copy is bit-identical to a host
    deep copy followed by the same scatter — including sentinel-padded
    copy rows (dropped) and a window straddling the copied page."""
    from distributed_lion_tpu.ops.attention import (
        paged_copy_pages,
        paged_scatter_kv,
    )

    rng = np.random.default_rng(8)
    NB, bs, KV, hd = 6, 4, 2, 8
    pool = jnp.asarray(rng.standard_normal((NB, bs, KV, hd)), jnp.float32)
    layers = [{"k": pool, "v": pool * 2.0}]
    # copy page 1 -> 4, sentinel-pad the rest of the copy list
    src = jnp.asarray([1, NB, NB], jnp.int32)
    dst = jnp.asarray([4, NB, NB], jnp.int32)
    copied = paged_copy_pages(layers, src, dst)
    ref = {k: np.asarray(layers[0][k]).copy() for k in ("k", "v")}
    for k in ref:
        ref[k][4] = ref[k][1]
    for k in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(copied[0][k]), ref[k])
    # scatter a 3-token window into the COPIED page (table points at 4)
    tables = jnp.asarray([[0, 4, 2]], jnp.int32)
    pos = jnp.asarray([5], jnp.int32)  # straddles pages 1->2 of the row
    new = jnp.asarray(rng.standard_normal((1, 3, KV, hd)), jnp.float32)
    got = paged_scatter_kv(copied[0]["k"], tables, pos, new)
    want = paged_scatter_kv(jnp.asarray(ref["k"]), tables, pos, new)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefix_cache_match_register_reclaim():
    from distributed_lion_tpu.serve.kv_cache import PrefixCache

    bt = BlockTables(num_blocks=16, block_size=4, max_seqs=4,
                     max_blocks_per_seq=4)
    pc = PrefixCache(bt)
    prompt = list(range(100, 110))             # 10 tokens: 2 full + 2 tail
    assert bt.grow(0, len(prompt) + 1)
    assert pc.register(0, prompt) == 3         # 2 full + 1 partial page
    row = [int(p) for p in bt.tables[0, :3]]
    assert all(bt.refs[p] == 2 for p in row)   # slot + cache
    # identical prompt: shares both full pages AND the partial's prefix
    pages, covered = pc.match(list(prompt))
    assert pages == row and covered == 9       # capped at L-1
    # shared-prefix-different-tail: full pages only
    pages, covered = pc.match(prompt[:8] + [999, 998])
    assert pages == row[:2] and covered == 8
    # divergence inside the first page: no hit
    assert pc.match([1, 2, 3, 4, 5, 6, 7, 8]) == ([], 0)
    # eviction of the chain root drops the descendants too — no leaks
    bt.free_slot(0)
    freed = pc.reclaim(bt.num_blocks)
    assert freed == 3 and bt.free_blocks == bt.num_blocks
    assert pc.match(list(prompt)) == ([], 0)


def _shared_workload(cfg, n=8, seed=11, max_new=6):
    rng = np.random.default_rng(seed)
    sys_p = list(map(int, rng.integers(1, cfg.vocab_size, 13)))
    prompts = [sys_p + list(map(int, rng.integers(1, cfg.vocab_size, 3)))
               for _ in range(n - 3)]
    prompts += [list(sys_p) for _ in range(3)]  # fully identical prompts
    return [Request(req_id=i, tokens=list(t), max_new_tokens=max_new,
                    seed=i) for i, t in enumerate(prompts)]


@pytest.mark.parametrize("sampling", ["greedy", "stochastic"])
def test_shared_prefix_engine_matches_unshared(sampling):
    """THE prefix-sharing pin (ISSUE 13): a shared-system-prompt workload
    through the prefix-cache engine produces outputs identical to the
    unshared engine — greedy and sampled — while allocating strictly
    fewer physical pages and actually hitting the cache."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    samp = (dict(temperature=0.0) if sampling == "greedy"
            else dict(temperature=0.9, top_k=40))
    reqs = _shared_workload(cfg)
    plain = _engine(params, cfg, num_blocks=64, **samp)
    shared = _engine(params, cfg, num_blocks=64, prefix_cache=True, **samp)
    base = plain.run([Request(r.req_id, list(r.tokens), r.max_new_tokens,
                              r.seed) for r in reqs])
    got = shared.run([Request(r.req_id, list(r.tokens), r.max_new_tokens,
                              r.seed) for r in reqs])
    for r in reqs:
        assert got[r.req_id].tokens == base[r.req_id].tokens, r.req_id
        assert got[r.req_id].reason == base[r.req_id].reason
    assert shared.stats["prefix_hits"] > 0
    assert shared.stats["cow_copies"] > 0
    assert shared.tables.pages_allocated < plain.tables.pages_allocated
    # pool accounting after drain: only cache-held pages remain physical,
    # and every live ref belongs to the cache
    assert all(s is None for s in shared.slots)
    assert (shared.tables.physical_pages + shared.tables.free_blocks
            == shared.tables.num_blocks)
    assert int(shared.tables.refs.sum()) == shared.tables.physical_pages


def test_shared_prefix_staggered_matches_solo():
    """Continuous batching × prefix sharing: staggered arrivals through
    the shared engine still equal solo runs of each request (the cache
    only changes which PHYSICAL pages hold the same bytes)."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    reqs = _shared_workload(cfg, n=5)
    shared = _engine(params, cfg, num_blocks=64, prefix_cache=True)
    got = shared.run(
        [Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)
         for r in reqs],
        arrivals={0: 0, 1: 1, 2: 1, 3: 3, 4: 5})
    for r in reqs:
        solo = _engine(params, cfg, num_blocks=64, prefix_cache=True).run(
            [Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)])
        assert got[r.req_id].tokens == solo[r.req_id].tokens, r.req_id


def test_evicting_sharer_frees_zero_physical_pages():
    """Overflow-evicting a request whose pages are all shared hands back
    refs, not pages — the engine's freed_pages ledger records what
    physically returned (the satellite's accounting pin)."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    eng = _engine(params, cfg, num_blocks=64, prefix_cache=True)
    prompt = list(range(1, 14))                # 13 tokens: 3 full + tail
    first = eng.run([Request("a", list(prompt), 4, 0)])
    assert first["a"].reason == "length"
    freed_before = eng.stats["freed_pages"]
    phys_before = eng.tables.physical_pages
    # the second identical request shares the cached run; evict it right
    # after admit by giving it a 1-token budget (finishes at prefill)
    out = eng.run([Request("b", list(prompt), 1, 0)])
    assert out["b"].reason == "length"
    # b's only private page was its CoW'd boundary page (cache keeps the
    # original), so at most ONE physical page came back — and none of the
    # shared run did
    freed_b = eng.stats["freed_pages"] - freed_before
    assert freed_b <= 1, freed_b
    assert eng.tables.physical_pages == phys_before
    assert eng.stats["prefix_hits"] >= 1


def test_prefix_cache_reclaims_under_pool_pressure():
    """A pool exhausted by CACHED pages is not full: admission reclaims
    LRU chains instead of rejecting/overflowing, and the request that
    triggered the reclaim completes normally."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    # pool of 8 pages, block 4: one 13-token prompt + gen occupies ~4,
    # all cache-registered after it drains; a second DISJOINT prompt then
    # needs more pages than remain un-cached
    eng = _engine(params, cfg, max_seqs=1, block_size=4,
                  max_blocks_per_seq=8, num_blocks=8, prefix_cache=True)
    rng = np.random.default_rng(2)
    p1 = list(map(int, rng.integers(1, cfg.vocab_size, 13)))
    p2 = list(map(int, rng.integers(1, cfg.vocab_size, 14)))
    out1 = eng.run([Request("a", p1, 4, 0)])
    assert out1["a"].reason == "length"
    assert eng.tables.physical_pages > 0       # the cache holds a's pages
    out2 = eng.run([Request("b", p2, 4, 0)])
    assert out2["b"].reason == "length"        # not overflow/rejected
    assert eng.stats["reclaimed_pages"] > 0
    # outputs unaffected by the eviction dance
    plain = _engine(params, cfg, max_seqs=1, block_size=4,
                    max_blocks_per_seq=8, num_blocks=8)
    assert plain.run([Request("b", list(p2), 4, 0)])["b"].tokens \
        == out2["b"].tokens


def test_cow_under_pool_pressure_after_reclaim_unshares():
    """Regression (review round): when the CoW fallback's reclaim drops
    the cache's own ref on the page being CoW'd, the page is PRIVATE now
    and needs no copy — the old unconditional cow retry tripped its
    shared-page precondition and crashed the engine on exactly the
    pool-pressure path the fallback exists to handle."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    # pool of 3 pages, page-aligned 8-token prompt: request a registers 2
    # cached pages; the identical request b shares both, takes the last
    # free page, and its boundary CoW finds the pool dry
    eng = _engine(params, cfg, max_seqs=1, block_size=4,
                  max_blocks_per_seq=4, num_blocks=3, prefix_cache=True)
    prompt = list(range(1, 9))
    out_a = eng.run([Request("a", list(prompt), 2, 0)])
    out_b = eng.run([Request("b", list(prompt), 2, 0)])  # crashed before
    assert out_b["b"].reason == out_a["a"].reason == "length"
    assert out_b["b"].tokens == out_a["a"].tokens  # same seed, greedy
    # outputs still match the unshared engine on the same pool geometry
    plain = _engine(params, cfg, max_seqs=1, block_size=4,
                    max_blocks_per_seq=4, num_blocks=3)
    assert plain.run([Request("b", list(prompt), 2, 0)])["b"].tokens \
        == out_b["b"].tokens


def test_request_file_prefix_group_roundtrip(tmp_path):
    """serve/api: the optional prefix_group tag is validated strictly and
    echoed on the response record."""
    from distributed_lion_tpu.serve import api

    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    inp = tmp_path / "requests.jsonl"
    inp.write_text(
        '{"id": "a", "tokens": [1, 2, 3], "max_new_tokens": 2, '
        '"prefix_group": "sys-v1"}\n'
        '{"id": "b", "tokens": [4, 5], "max_new_tokens": 2}\n')
    out = tmp_path / "responses.jsonl"
    records = api.serve_request_file(
        _engine(params, cfg, prefix_cache=True), str(inp), str(out))
    assert records[0]["prefix_group"] == "sys-v1"
    assert "prefix_group" not in records[1]
    # strict validation: wrong type and empty string both refuse loudly
    for bad in ('{"id": "x", "tokens": [1], "prefix_group": 7}\n',
                '{"id": "x", "tokens": [1], "prefix_group": ""}\n'):
        p = tmp_path / "bad.jsonl"
        p.write_text(bad)
        with pytest.raises(ValueError, match="prefix_group"):
            api.load_request_file(str(p))


def test_run_serve_builds_prefix_cache_and_ep_with_moe(monkeypatch):
    """cli satellite (ISSUE 15): --prefix_cache AND --serve_ep now build
    for MoE checkpoints — the old loud refusals are replaced by the
    pinned equivalences (tests/test_moe_serve.py); this pins the CLI
    surface actually reaches the composed engine."""
    import distributed_lion_tpu.cli.run_generate as rg
    from distributed_lion_tpu.cli.run_serve import (
        ServeArguments,
        build_engine,
    )

    cfg = GPT2Config.tiny(moe_experts=2)
    params = gpt2_init(jax.random.key(0), cfg)
    monkeypatch.setattr(rg, "build",
                        lambda a: (None, cfg, params, None, None))
    eng = build_engine(rg.GenerateArguments(),
                       ServeArguments(prefix_cache=True, serve_ep=2))[1]
    assert eng.prefix is not None and eng.cfg.ep == 2


# ------------------------------------------------------- host allocator
def test_block_tables_alloc_free_invariants():
    bt = BlockTables(num_blocks=8, block_size=4, max_seqs=3,
                     max_blocks_per_seq=4)
    assert bt.free_blocks == 8 and bt.max_tokens_per_seq == 16
    assert bt.grow(0, 5)            # 2 pages
    assert bt.owned[0] == 2 and bt.free_blocks == 6
    assert bt.grow(0, 5)            # idempotent: no new pages
    assert bt.free_blocks == 6
    assert bt.grow(1, 16)           # 4 pages — slot 1 maxes its table
    assert not bt.grow(1, 17)       # beyond the table width
    assert bt.free_blocks == 2
    # all-or-nothing: slot 2 wants 3 pages, pool has 2 — NOTHING allocates
    assert not bt.grow(2, 12)
    assert bt.owned[2] == 0 and bt.free_blocks == 2
    assert bt.find_free_slot() == 2
    freed = bt.free_slot(1)
    assert freed == 4 and bt.free_blocks == 6
    assert (bt.tables[1] == bt.sentinel).all()
    assert bt.grow(2, 12)           # now it fits


# ------------------------------------------- continuous batching == solo
def _tiny_requests(cfg, n=5, seed=3, max_new=8):
    rng = np.random.default_rng(seed)
    lens = (3, 9, 5, 14, 2, 7, 11)[:n]
    return [Request(req_id=f"r{i}",
                    tokens=list(map(int, rng.integers(1, cfg.vocab_size, L))),
                    max_new_tokens=max_new, seed=i)
            for i, L in enumerate(lens)]


def _engine(params, cfg, **kw):
    base = dict(max_seqs=4, block_size=4, max_blocks_per_seq=8)
    base.update(kw)
    return ServingEngine(ServeModel.for_gpt2(params, cfg), ServeConfig(**base))


@pytest.mark.parametrize("sampling", ["greedy", "stochastic"])
def test_staggered_continuous_batching_matches_solo(sampling):
    """The acceptance pin: a continuous-batching run with staggered
    arrivals produces per-request outputs identical to solo runs — slots,
    neighbors, and arrival order must not leak into any request (per-slot
    PRNG keys are (request seed, token index), batch-independent)."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    samp = (dict(temperature=0.0) if sampling == "greedy"
            else dict(temperature=0.9, top_k=40))
    reqs = _tiny_requests(cfg)
    batched = _engine(params, cfg, **samp).run(
        [Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)
         for r in reqs],
        arrivals={"r0": 0, "r1": 1, "r2": 1, "r3": 3, "r4": 5})
    alone = _engine(params, cfg, **samp)  # one engine: programs compiled once
    for r in reqs:
        solo = alone.run(
            [Request(r.req_id, list(r.tokens), r.max_new_tokens, r.seed)])
        assert batched[r.req_id].tokens == solo[r.req_id].tokens, r.req_id
        assert batched[r.req_id].reason == solo[r.req_id].reason


def test_engine_greedy_matches_dense_generate():
    """Greedy decode through the paged engine == the dense-KV generate at
    MATCHED attended length (max_len == pages-per-seq * block_size):
    bit-identical logits imply identical tokens."""
    from functools import partial

    from distributed_lion_tpu.models.generate import generate

    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(4), cfg)
    prompts = [list(map(int, r)) for r in np.asarray(
        _tokens(cfg.vocab_size, 3, 6, seed=9))]
    new = 8
    dense = np.asarray(generate(
        partial(lambda c, p, t, k, pos, off=None:
                gpt2_decode(p, t, c, k, pos, off), cfg),
        partial(gpt2_init_cache, cfg), params,
        jnp.asarray(prompts, jnp.int32), new, max_len=4 * 8))
    eng = _engine(params, cfg, block_size=4, max_blocks_per_seq=8)
    done = eng.run([Request(req_id=i, tokens=t, max_new_tokens=new, seed=0)
                    for i, t in enumerate(prompts)])
    for i in range(len(prompts)):
        assert list(dense[i]) == done[i].tokens, i


def test_engine_eos_evicts_and_frees_pages():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    reqs = _tiny_requests(cfg, n=2, max_new=16)
    # learn each request's first greedy token, then declare it EOS
    first = {r.req_id: _engine(params, cfg).run(
        [Request(r.req_id, list(r.tokens), 1, 0)])[r.req_id].tokens[0]
        for r in reqs}
    eos = first[reqs[0].req_id]
    eng = _engine(params, cfg, eos_id=eos)
    done = eng.run([Request(r.req_id, list(r.tokens), 16, 0) for r in reqs])
    assert done[reqs[0].req_id].reason == "eos"
    assert done[reqs[0].req_id].tokens[-1] == eos
    # every page returned to the pool after the workload drains
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()
    assert all(s is None for s in eng.slots)


def test_engine_overflow_truncates_loudly():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    eng = _engine(params, cfg, max_seqs=2, block_size=4, max_blocks_per_seq=2)
    toks = list(map(int, np.asarray(_tokens(cfg.vocab_size, 1, 5, seed=1))[0]))
    done = eng.run([Request("big", toks, 64, 0)])
    assert done["big"].reason == "overflow"
    # the cache holds 8 slots: 5 prompt + 3 decode writes → 4 generated
    # tokens (the overflowing write is the one that could not fit)
    assert len(done["big"].tokens) == 4
    assert eng.tables.free_blocks == eng.cfg.resolved_num_blocks()


def test_engine_refuses_geometry_past_position_budget():
    """A page horizon beyond the model's trained position budget (gpt2's
    learned wpe rows) must fail at build, not alias silently at slot 129."""
    cfg = GPT2Config.tiny()  # n_ctx = 128
    params = gpt2_init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="position budget"):
        _engine(params, cfg, block_size=16, max_blocks_per_seq=16)


def test_moe_checkpoints_serve_through_the_paged_engine():
    """ISSUE 15: the PR 9 refusals are LIFTED — valid-lane masked,
    no-drop MoE routing makes pad lanes consume zero expert capacity, so
    ServeModel build, gpt2_decode_paged and the left-padded gpt2_decode
    offset path all serve MoE checkpoints (the equivalence pins live in
    tests/test_moe_serve.py; this pins that no refusal remains)."""
    cfg = GPT2Config.tiny(moe_experts=2)
    params = gpt2_init(jax.random.key(0), cfg)
    model = ServeModel.for_gpt2(params, cfg)
    eng = ServingEngine(model, ServeConfig(max_seqs=2, block_size=4,
                                           max_blocks_per_seq=4))
    done = eng.run([Request("m", [1, 2, 3], 4, 0)])
    assert len(done["m"].tokens) == 4
    pages = [{k: jnp.zeros((4, 4, cfg.n_head, cfg.head_dim),
                           cfg.compute_dtype) for k in ("k", "v")}
             for _ in range(cfg.n_layer)]
    logits, _ = _compiled[gpt2_decode_paged](
        params, jnp.ones((1, 4), jnp.int32), cfg, pages,
        jnp.asarray([[0, 1, 2, 3]], jnp.int32), jnp.zeros((1,), jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()
    logits, _ = _compiled[gpt2_decode](
        params, jnp.ones((2, 4), jnp.int32), cfg, gpt2_init_cache(cfg, 2, 8),
        0, jnp.asarray([0, 1], jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()


def test_engine_rejects_impossible_prompt():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    eng = _engine(params, cfg, max_seqs=2, block_size=4, max_blocks_per_seq=2)
    toks = list(map(int, np.asarray(_tokens(cfg.vocab_size, 1, 8, seed=1))[0]))
    done = eng.run([Request("toolong", toks, 4, 0)])  # 8 == cap, no room
    assert done["toolong"].reason == "rejected"
    assert done["toolong"].tokens == []


def test_prefill_fairness_cap():
    """A small cap admits one prompt per tick (the decode batch keeps
    moving); an uncapped engine admits the whole burst at tick 0 — and
    the cap never changes WHAT is generated, only when."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    reqs = _tiny_requests(cfg, n=4, max_new=4)

    def run(cap):
        eng = _engine(params, cfg, max_seqs=4, prefill_cap_tokens=cap)
        out = eng.run([Request(r.req_id, list(r.tokens), 4, r.seed)
                       for r in reqs])
        return eng.stats, out

    s_small, out_small = run(4)        # one 4/8/16-token bucket per tick
    s_big, out_big = run(1 << 30)
    assert s_big["ticks"] < s_small["ticks"]
    for r in reqs:
        assert out_small[r.req_id].tokens == out_big[r.req_id].tokens


def test_nf4_engine_serves_and_shrinks_weights():
    """quant='nf4' serves from packed codes (ops/quant) — outputs stay
    plausible (right count, in-vocab) and the weight tree actually
    shrinks below a third of the bf16 bytes."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    eng = _engine(params, cfg, quant="nf4")
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert weight_bytes(eng.params) * 3 < 2 * n_params
    done = eng.run([Request("q", [1, 2, 3, 4], 6, 0)])
    assert len(done["q"].tokens) == 6
    assert all(0 <= t < cfg.vocab_size for t in done["q"].tokens)


def test_engine_journal_spans(tmp_path):
    """serve/admit, serve/prefill, serve/decode_tick, serve/evict ride
    the installed run journal (PR 7), schema-valid."""
    import importlib.util

    from distributed_lion_tpu.train import journal as journal_mod

    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    jrnl = journal_mod.Journal(str(tmp_path))
    journal_mod.install(jrnl)
    try:
        _engine(params, cfg).run(
            [Request("a", [1, 2, 3], 3, 0), Request("b", [4, 5], 3, 0)])
    finally:
        journal_mod.uninstall(jrnl)
        jrnl.close()
    names = {r["name"] for r in jrnl.tail() if r["kind"] == "span"}
    assert {"serve/admit", "serve/prefill", "serve/decode_tick",
            "serve/evict"} <= names, names
    spec = importlib.util.spec_from_file_location(
        "vm_serve", os.path.join(REPO, "scripts", "validate_metrics.py"))
    vm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(vm)
    assert vm.validate_journal_file(
        str(tmp_path / "journal_rank0.jsonl")) == []


# ------------------------------------------------------------------ api
def test_request_file_roundtrip(tmp_path):
    from distributed_lion_tpu.serve import api

    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    reqs = [{"id": "a", "tokens": [1, 2, 3], "max_new_tokens": 4},
            {"id": "b", "tokens": [9, 8], "max_new_tokens": 4,
             "arrival_tick": 2, "seed": 5}]
    inp = tmp_path / "requests.jsonl"
    inp.write_text("".join(json.dumps(r) + "\n" for r in reqs))
    out = tmp_path / "responses.jsonl"
    records = api.serve_request_file(_engine(params, cfg), str(inp), str(out))
    got = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert got == records
    assert [r["id"] for r in got] == ["a", "b"]
    assert all(r["n_generated"] == 4 for r in got)
    # a request with neither tokens nor prompt+tokenizer fails LOUDLY
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"id": "x", "max_new_tokens": 2}\n')
    with pytest.raises(ValueError, match="tokens"):
        api.load_request_file(str(bad))


def test_run_serve_cli_smoke(tmp_path, capsys):
    from distributed_lion_tpu.cli.run_serve import main

    reqs = tmp_path / "requests.jsonl"
    reqs.write_text('{"id": "r1", "prompt": "ab", "max_new_tokens": 3}\n')
    out = tmp_path / "responses.jsonl"
    records = main(["--model_family", "gpt2", "--model_name", "tiny",
                    "--requests", str(reqs), "--out", str(out),
                    "--temperature", "0", "--max_seqs", "2",
                    "--block_size", "4"])
    assert len(records) == 1 and records[0]["n_generated"] == 3
    assert json.loads(out.read_text())["id"] == "r1"


# ------------------------------------------------- the evidence artifact
def test_banked_serving_artifact_passes_stage():
    """The committed CPU smoke artifact satisfies the serving evidence
    stage (schema + bit-identity markers + tokens/s floor at every
    required batch + the NF4 byte story) — the same gate the runbook's
    on-chip recapture must clear."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_serve", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    assert os.path.exists(ce.SERVE_ARTIFACT), "banked artifact missing"
    assert ce.serving_ok()
    with open(ce.SERVE_ARTIFACT) as f:
        doc = json.load(f)
    assert {r["batch"] for r in doc["decode"]} >= set(ce.SERVE_BATCHES)


def test_serving_stage_rejects_bad_artifacts(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_serve2", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    with open(ce.SERVE_ARTIFACT) as f:
        good = json.load(f)
    # flipped bit-identity marker
    doc = json.loads(json.dumps(good))
    doc["bit_identity"]["paged_vs_dense"] = False
    p = tmp_path / "serving.json"
    p.write_text(json.dumps(doc))
    assert not ce.serving_ok(str(p))
    # missing required batch row
    doc = json.loads(json.dumps(good))
    doc["decode"] = [r for r in doc["decode"] if r["batch"] != 128]
    p.write_text(json.dumps(doc))
    assert not ce.serving_ok(str(p))
    # throughput floor
    doc = json.loads(json.dumps(good))
    doc["decode"][0]["tokens_per_sec_per_chip"] = 1.0
    p.write_text(json.dumps(doc))
    assert not ce.serving_ok(str(p))
    # quantization story: nf4 bytes not actually small
    doc = json.loads(json.dumps(good))
    for r in doc["decode"]:
        r["weight_bytes_nf4"] = r["weight_bytes_bf16"]
    p.write_text(json.dumps(doc))
    assert not ce.serving_ok(str(p))
    # schema violation (NaN token) caught via validate_metrics delegation
    p.write_text(json.dumps(good).replace(
        str(good["decode"][0]["ms_per_tick"]), "NaN", 1))
    assert not ce.serving_ok(str(p))


def test_banked_artifact_passes_tp_serving_stage():
    """The committed CPU artifact (captured under DLION_PLATFORM=cpu8 so
    the tp>1 legs exist) satisfies the ISSUE 13 tp_serving stage: strict
    schema, all five identity markers, a tp>=2 row above the tokens/s
    floor, and prefix_mem_ratio <= 0.15 on the 256-request
    shared-system-prompt workload — the gate runbook stage 5k re-judges
    after the on-chip recapture."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_tp", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    assert ce.tp_serving_ok()
    with open(ce.SERVE_ARTIFACT) as f:
        doc = json.load(f)
    sec = doc["tp_serving"]
    assert any(r["tp"] >= 2 for r in sec["rows"])
    assert sec["prefix"]["requests"] >= 256
    assert sec["prefix"]["prefix_mem_ratio"] <= 0.15


def test_tp_serving_stage_rejects_bad_artifacts(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_tp2", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    with open(ce.SERVE_ARTIFACT) as f:
        good = json.load(f)
    p = tmp_path / "serving.json"

    def reject(mutate):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        p.write_text(json.dumps(doc))
        assert not ce.tp_serving_ok(str(p))

    # artifact predates ISSUE 13 entirely (also a schema violation now)
    reject(lambda d: d.pop("tp_serving"))
    # each identity marker flips the stage
    for k in ("tp1_vs_unsharded", "tpN_vs_unsharded",
              "shared_vs_unshared_greedy", "shared_vs_unshared_sampled",
              "shared_vs_unshared_speculative"):
        reject(lambda d, k=k: d["tp_serving"]["markers"].update({k: False}))
    # no multi-chip row / throughput floor / memory story
    reject(lambda d: d["tp_serving"].update(
        rows=[r for r in d["tp_serving"]["rows"] if r["tp"] < 2]))
    reject(lambda d: d["tp_serving"]["rows"][0].update(
        tokens_per_sec_per_chip=1.0))
    reject(lambda d: d["tp_serving"]["prefix"].update(
        prefix_mem_ratio=0.5))
    reject(lambda d: d["tp_serving"]["prefix"].update(requests=8))
    # strict schema: a non-int page count (validate_metrics delegation)
    reject(lambda d: d["tp_serving"]["prefix"].update(
        physical_pages="many"))
    # the untouched artifact still passes from the tmp copy
    p.write_text(json.dumps(good))
    assert ce.tp_serving_ok(str(p))


def test_banked_artifact_passes_speculative_stage():
    """The committed CPU artifact also satisfies the ISSUE 11 speculative
    stage (strict frontier schema, both live-recomputed identity markers,
    a baseline + both drafters on both workloads, ngram accept_rate > 0
    on the repetitive traffic) — the gate runbook stage 5j re-judges after
    the on-chip recapture."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_spec", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    assert ce.speculative_ok()


def test_speculative_stage_rejects_bad_artifacts(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ce_spec2", os.path.join(REPO, "scripts", "check_evidence.py"))
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)
    with open(ce.SERVE_ARTIFACT) as f:
        good = json.load(f)
    p = tmp_path / "serving.json"

    def reject(mutate):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        p.write_text(json.dumps(doc))
        assert not ce.speculative_ok(str(p))

    # artifact predates ISSUE 11 entirely
    reject(lambda d: d.pop("speculative"))
    # a flipped live-recomputed identity marker
    reject(lambda d: d["speculative"]["markers"].update(
        greedy_vs_plain=False))
    reject(lambda d: d["speculative"]["markers"].update(
        sampled_vs_stream=False))
    # schema: accept_rate outside [0, 1] (validate_metrics delegation)
    reject(lambda d: d["speculative"]["frontier"][1].update(
        accept_rate=1.5))
    # frontier coverage: no non-speculative baseline to read against /
    # a drafter missing on one workload
    reject(lambda d: d["speculative"].update(frontier=[
        r for r in d["speculative"]["frontier"] if r["drafter"] != "none"]))
    reject(lambda d: d["speculative"].update(frontier=[
        r for r in d["speculative"]["frontier"]
        if not (r["drafter"] == "ngram" and r["workload"] == "random")]))
    # the n-gram drafter must EARN accept_rate > 0 on repetitive traffic
    def zero_ngram(d):
        for r in d["speculative"]["frontier"]:
            if r["drafter"] == "ngram" and r["workload"] == "repetitive":
                r["accept_rate"] = 0.0
    reject(zero_ngram)
    # the untouched artifact still passes from the tmp copy
    p.write_text(json.dumps(good))
    assert ce.speculative_ok(str(p))

# ------------------------------- a prefill over its own fresh keys (PR 40)
def _fresh_model(family):
    """Heads the tiled forward kernel takes: three of 64 (an odd count:
    a head of zero lanes beside them) and two of 128 over one kv head."""
    if family == "gpt2":
        cfg = GPT2Config.tiny(n_head=3, d_model=192, n_ctx=256,
                              compute_dtype=jnp.float32)
        return ServeModel.for_gpt2(gpt2_init(jax.random.key(0), cfg), cfg)
    cfg = LlamaConfig.tiny(n_head=2, n_kv_head=1, d_model=256, n_ctx=256,
                           compute_dtype=jnp.float32)
    return ServeModel.for_llama(llama_init(jax.random.key(0), cfg), cfg)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The TPU's choice for a prefill on the CPU: ``flash_gqa_fwd`` in
    interpret mode, its calls recorded (one a layer a traced program). The
    decode tick keeps the gather path: only the prefill is under test."""
    from distributed_lion_tpu.ops import attention as attn_ops
    from distributed_lion_tpu.ops import pallas_flash_attn

    calls = []
    real = pallas_flash_attn.flash_gqa_fwd

    def kernel(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_flash_attn, "flash_gqa_fwd", kernel)
    monkeypatch.setattr(attn_ops, "paged_kernel_applies",
                        lambda *a, **k: False)
    return calls


def _prompts(vocab, lens, seed=5):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, vocab, n))) for n in lens]


@pytest.mark.parametrize("shape", ["engine_pool", "head_major_pool",
                                   "window_off_whole_pages"])
def test_fresh_scatter_writes_the_cells_the_row_scatter_writes(shape):
    """``paged_scatter_fresh`` (whole pages, then the page a prompt ends in
    row by row) against ``paged_scatter_kv`` from position 0, bytewise,
    over a pool full of stale rows: prompts that end inside a page, on a
    page's edge, inside the first page, at the window's end, an empty lane,
    and a row whose table is all sentinel."""
    from distributed_lion_tpu.ops.attention import (
        paged_scatter_fresh, paged_scatter_kv,
    )

    rng = np.random.default_rng(3)
    KV, hd, nb, per = 3, 8, 40, 6
    bs, S = (5, 32) if shape == "window_off_whole_pages" else (8, 32)
    G, W = (KV, hd) if shape == "head_major_pool" else (1, 128)
    pages = jnp.asarray(rng.standard_normal((nb, bs, G, W)), jnp.float32)
    tables = rng.permutation(nb)[:6 * per].reshape(6, per).astype(np.int32)
    tables[5] = nb                                   # never allocated
    tables[1, 3:] = nb                               # pages for 24 tokens
    lengths = np.asarray([13, 24, 3, 32, 0, 9])
    new = jnp.asarray(rng.standard_normal((6, S, KV, hd)), jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
    tables = jnp.asarray(tables)
    for mask in (valid, None):
        want = paged_scatter_kv(pages, tables, jnp.zeros((6,), jnp.int32),
                                new, mask)
        got = jax.jit(paged_scatter_fresh)(pages, tables, new, mask)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(want), np.asarray(pages))


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_prefill_from_0_attends_over_its_fresh_keys(family, request):
    """A prefill that starts at position 0, in a bucket the kernel takes,
    never gathers: the tokens are the gather path's, and so are the pages
    (the first layer's bit for bit: the same projections scattered to the
    same cells; a deeper layer's within the kernel's rounding of the
    layer before)."""
    model = _fresh_model(family)
    scfg = ServeConfig(max_seqs=2, block_size=64, max_blocks_per_seq=2,
                       temperature=0.0)

    def run():
        eng = ServingEngine(model, scfg)
        out = eng.run([Request(f"r{i}", toks, 5, seed=i) for i, toks in
                       enumerate(_prompts(model.cfg.vocab_size, (100, 70)))])
        return eng, out

    gather, want = run()
    assert gather.stats["prefill_fresh_dispatches"] == 0     # the CPU
    assert gather._fresh_buckets == frozenset()
    calls = request.getfixturevalue("on_a_tpu")
    fresh, got = run()
    assert calls == [(1, 128, model.cfg.n_head * model.head_dim)] \
        * model.n_layer                    # ONE program, the 128 bucket's
    assert fresh._fresh_buckets == {128}
    assert fresh.stats["prefill_fresh_dispatches"] == 2 \
        == fresh.stats["prefill_dispatches"]
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, rid
    for layer, (a, b) in enumerate(zip(fresh.pages, gather.pages)):
        for leaf in ("k", "v"):
            if layer == 0:
                np.testing.assert_array_equal(a[leaf], b[leaf])
            else:
                np.testing.assert_allclose(a[leaf], b[leaf], atol=1e-5)


def test_only_a_prefill_from_0_takes_the_fresh_path(on_a_tpu, capsys):
    """A prefill behind a shared prefix sees pages it did not write and
    keeps the gather path, so under ``prefix_cache`` a bucket the kernel
    takes may compile two programs: the budget says so, the run reaches it
    and the retrace guard stays silent. The counter counts the prefills
    that attended over fresh keys: from 0, in a bucket the kernel takes."""
    model = _fresh_model("gpt2")
    eng = ServingEngine(model, ServeConfig(
        max_seqs=1, block_size=64, max_blocks_per_seq=4, num_blocks=16,
        temperature=0.0, prefix_cache=True, retrace_guard="error"))
    assert "[setup] prefill: fresh keys, flash_gqa_fwd x2 (from position 0 " \
        "in buckets 128-256; every other prefill gathers)" \
        in capsys.readouterr().err
    assert eng._fresh_buckets == {128, 256}
    assert eng.compile_budget()["prefill"] == 3 + 2
    a, b, c, d, e = _prompts(model.cfg.vocab_size, (100, 200, 60, 100, 136))
    prompts = [c[:40],        # from 0, bucket 64: off a multiple of 128
               a, b,          # from 0, buckets 128 and 256: fresh
               a[:64] + c,    # behind a's first page, bucket 64
               a[:64] + d,    # ... bucket 128
               b[:64] + e]    # behind b's first page, bucket 256
    for i, toks in enumerate(prompts):     # one at a time: a's pages are
        eng.run([Request(f"r{i}", toks, 2, seed=i)])      # cached by then
    st = eng.stats
    assert st["prefix_hits"] == 3 and st["shared_tokens"] == 3 * 64
    assert st["prefill_dispatches"] == 6
    assert st["prefill_fresh_dispatches"] == 2
    assert eng.compile_counts()["prefill"] == eng.compile_budget()["prefill"]
    assert st["serve_retraces"] == 0
    assert sorted(s[1] for s in on_a_tpu) == [128, 128, 256, 256]


def test_a_speculative_verify_keeps_the_gather_path(on_a_tpu):
    model = _fresh_model("gpt2")
    eng = ServingEngine(model, ServeConfig(
        max_seqs=1, block_size=64, max_blocks_per_seq=4, temperature=0.0,
        speculate="ngram:2"))
    phrase = _prompts(model.cfg.vocab_size, (10,))[0]
    eng.run([Request("r", phrase * 10, 8, seed=0)])
    assert eng.stats["spec_rounds"] > 0
    assert eng.stats["prefill_fresh_dispatches"] == 1
    # the prefill's two layers, and nothing from the verify window
    assert on_a_tpu == [(1, 128, 192)] * 2


def test_a_gather_engine_says_so(capsys):
    ServingEngine(_fresh_model("llama"), ServeConfig(
        max_seqs=1, block_size=64, max_blocks_per_seq=2))
    assert "[setup] prefill: gather\n" in capsys.readouterr().err
