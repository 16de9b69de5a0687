"""MiniCPM-SALA (models/minicpm_sala) against its plain reference at TINY on
the CPU in float32: prefill then decode through pages, compressed keys and
slot-indexed states on both sides of ``dense_len``, the Lightning
recurrence's three forms, selection by hand, the compressed-key leaf's
lifetime, the faults each of these is there to catch, what the engine
refuses, and the other families' pools left as they were.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import minicpm_sala as family  # noqa: E402
from distributed_lion_tpu.models import minicpm_sala as sala  # noqa: E402
from distributed_lion_tpu.models.minicpm_sala import (  # noqa: E402
    MiniCPMSalaConfig,
    minicpm_sala_decode_paged,
)
from distributed_lion_tpu.ops import (  # noqa: E402
    lightning,
    pallas_lightning,
    sparse_select,
)
from distributed_lion_tpu.ops.sparse_select import SparseConfig  # noqa: E402
from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.kv_cache import init_page_leaves  # noqa: E402

ref = family.reference
TINY = family.TINY
TOL = 1e-4
BLOCK, PER_SEQ, T = 2, 80, 160       # rows of up to 160 tokens
PLENS = np.asarray([128, 100, 40])   # fills its bucket past dense_len 64,
#                                      does not, lies under it
STEPS = 30                           # row 2 crosses dense_len at step 24


@pytest.fixture(scope="module")
def model():
    """(reference weights, program params, config) at TINY, float32: the
    same values in both layouts."""
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 37), TINY, jnp.float32)
    cfg = MiniCPMSalaConfig.from_hf(TINY, param_dtype=jnp.float32,
                                    compute_dtype=jnp.float32)
    return weights, family.to_program(weights), cfg


def run_tables(n_seq, per_seq, run, order=None):
    """Tables as the engine mints them for this family: a block's ``run``
    pages an aligned run of the pool, the runs themselves in ``order``
    (None: descending)."""
    runs = n_seq * per_seq // run
    heads = (np.arange(runs)[::-1] if order is None else order) * run
    return jnp.asarray((heads[:, None] + np.arange(run)).reshape(
        n_seq, per_seq), jnp.int32)


def pool(cfg, n_seq, block=BLOCK, per_seq=PER_SEQ):
    m = ServeModel.for_minicpm_sala(None, cfg)
    pages = init_page_leaves(
        cfg.n_layer, n_seq * per_seq, block, m.page_leaves, jnp.float32,
        state=(cfg.lightning_layers, n_seq, m.state_leaves))
    return pages, run_tables(n_seq, per_seq, cfg.sparse.block_size // block)


def hooks_of(model):
    _, params, cfg = model

    @jax.jit
    def prefill(toks, pages, tables, slots, valid):
        return minicpm_sala_decode_paged(
            params, toks, cfg, pages, tables, slots,
            jnp.zeros((toks.shape[0],), jnp.int32), valid, True)

    @jax.jit
    def step(toks, pages, tables, pos):
        return minicpm_sala_decode_paged(
            params, toks, cfg, pages, tables, None, pos,
            jnp.ones((toks.shape[0], 1), bool), True)

    return prefill, step


@pytest.fixture(scope="module")
def served(model):
    """Three rows through the hook, prefill (a bucket of 128) then ``STEPS``
    decode ticks: (rows, the reference's logits, the hook's logits a tick,
    the counters a tick, the pages after the prefill, the pages at the
    end)."""
    weights, _, cfg = model
    prefill, step = hooks_of(model)
    rows = np.random.default_rng(2).integers(0, 256, (3, T)).astype(np.int32)
    want = jax.jit(lambda r: ref.forward(weights, r, TINY))(rows)
    fresh, tables = pool(cfg, 3)
    slots = jnp.asarray([2, 0, 1])          # a state is found by the slot
    valid = jnp.arange(128)[None, :] < PLENS[:, None]
    logits, filled, count = prefill(rows[:, :128], fresh, tables, slots,
                                    valid)
    # decode rows ARE slots: put each row's state where its row is
    pages = [{k: (v[slots] if k == "state" else v) for k, v in c.items()}
             for c in filled]
    ticks, counts = [], []
    for j in range(STEPS):
        at = PLENS + j
        out, pages, c = step(rows[np.arange(3), at][:, None], pages, tables,
                             jnp.asarray(at, jnp.int32))
        ticks.append(out[:, 0])
        counts.append({k: int(v) for k, v in c.items()})
    return dict(rows=rows, want=want, prefill=logits, prefill_count=count,
                ticks=ticks, counts=counts, filled=filled, pages=pages,
                tables=tables, slots=slots, valid=valid, fresh=fresh)


# ----------------------------- (a), (e) prefill, then decode, by reference
def test_prefill_then_decode_on_both_sides_of_dense_len(served):
    """Prompts that fill their bucket past ``dense_len`` (128), that do not
    (100) and that lie under it (40), then one token a tick, the third row
    crossing ``dense_len`` on the way: every logit against the reference's
    full pass (selection query by query, the recurrence token by token). The
    largest gap at TINY in float32 is at rounding level (2.3e-6 measured,
    PR 37, where the logits reach 0.18); ``TOL`` is 1e-4."""
    want = served["want"]
    worst = max(float(jnp.abs(served["prefill"][i, :n] - want[i, :n]).max())
                for i, n in enumerate(PLENS))
    for j, out in enumerate(served["ticks"]):
        worst = max(worst, float(jnp.abs(
            out - want[np.arange(3), PLENS + j]).max()))
    assert worst < TOL, worst


def test_a_tick_holds_rows_on_both_sides_of_dense_len(served):
    """Row 2 is at or under ``dense_len`` 64 until its position 64 (tick
    24): before it a tick counts two sparse rows and one dense a layer,
    after it three sparse; a dense row's list is its whole table."""
    first, last = served["counts"][0], served["counts"][-1]
    assert (first["sparse_rows"], first["dense_rows"]) == (4, 2)
    assert (last["sparse_rows"], last["dense_rows"]) == (6, 0)
    assert served["counts"][23]["dense_rows"] == 2
    assert served["counts"][24]["dense_rows"] == 0
    # 4 blocks of 8 are 16 pages a (row, kv head), the query's own partly
    # filled; the dense row walks ceil(41 / 2) = 21 pages a kv head
    sparse_pages = 2 * 2 * (3 * 4 + 1) + 2 * 2 * (3 * 4 + 3)
    assert first["kv_pages_selected"] == sparse_pages + 2 * 2 * 21
    # a copy a block a leaf: 4 blocks a sparse list, ceil(41 / 8) = 6 the
    # dense row's; by the page it would be 2 x kv_pages_selected = 392
    assert first["kv_copies"] == 2 * (2 * 2 * (4 + 4) + 2 * 2 * 6)


def test_selection_matters_to_the_logits(model, served):
    """The control ``nosel`` (every query sees every block) is another
    function past ``dense_len`` and the same one under it."""
    weights = model[0]
    nosel = jax.jit(lambda r: ref.forward(weights, r, TINY, "nosel"))(
        served["rows"])
    gap = jnp.abs(nosel - served["want"]).max(-1)
    assert float(gap[:, :64].max()) < 1e-6
    assert float(gap[:, 80:].max()) > 10 * TOL
    state16 = jax.jit(lambda r: ref.forward(weights, r, TINY, "state16"))(
        served["rows"])
    assert float(jnp.abs(state16 - served["want"]).max()) > 10 * TOL


@pytest.mark.parametrize("fault", ["early_window", "one_list", "padding"])
def test_faults_the_comparison_catches(model, served, fault, monkeypatch):
    """A compressed key read before its window is complete; one list shared
    by both kv heads; a prefill that runs the state and the windows over its
    padding: each moves the logits past ``TOL`` (or, the last, the leaves a
    decode would read)."""
    cfg = model[2]
    if fault == "padding":
        prefill, _ = hooks_of(model)
        _, over, count = prefill(served["rows"][:, :128], served["fresh"],
                                 served["tables"], served["slots"],
                                 jnp.ones((3, 128), bool))
        a, b = served["filled"], over
        for layer in cfg.lightning_layers:
            assert bool(jnp.array_equal(a[layer]["state"][2],
                                        b[layer]["state"][2]))  # row of 128
            assert float(jnp.abs(a[layer]["state"][0]
                                 - b[layer]["state"][0]).max()) > 1e-3
        assert int(count["ck_rows_written"]) \
            > int(served["prefill_count"]["ck_rows_written"]) == 262
        return
    if fault == "early_window":
        # one stride early can only reach the query's own block, which is
        # forced: the by-hand case has the window that would move a set
        assert by_hand(32, [(7, 15), (15,)])[0] == [[0, 1, 4], [0, 1, 4]]
        monkeypatch.setattr(
            sparse_select, "visible", lambda p, j, sp:
            sp.kernel_stride * j + sp.kernel_size - 1 <= p + sp.kernel_stride)
        assert by_hand(32, [(7, 15), (15,)])[0] == [[0, 1, 4], [0, 3, 4]]
        return
    else:
        lists_of = sparse_select.decode_page_lists

        def shared(*a):
            lists, held, sparse = lists_of(*a)
            return (jnp.broadcast_to(lists[:, :1], lists.shape),
                    jnp.broadcast_to(held[:, :1], held.shape), sparse)

        monkeypatch.setattr(sala, "decode_page_lists", shared)
    _, step = hooks_of(model)               # a new trace under the fault
    pages = [{k: (v[served["slots"]] if k == "state" else v)
              for k, v in c.items()} for c in served["filled"]]
    worst = 0.0
    for j in range(6):
        at = PLENS + j
        out, pages, _ = step(served["rows"][np.arange(3), at][:, None], pages,
                             served["tables"], jnp.asarray(at, jnp.int32))
        worst = max(worst, float(jnp.abs(
            out[:, 0] - served["want"][np.arange(3), at]).max()))
    assert worst > 3 * TOL, worst


@pytest.mark.parametrize("block", [8])
def test_pages_of_several_strides(model, block):
    """Pages of 8 positions hold 4 compressed keys each (the engine's
    ``--block_size`` need not be the stride): the same logits."""
    weights, params, cfg = model
    rows = np.random.default_rng(3).integers(0, 256, (1, 96)).astype(np.int32)
    want = jax.jit(lambda r: ref.forward(weights, r, TINY))(rows)
    pages, tables = pool(cfg, 1, block, 96 // block)
    assert pages[0]["ck"].shape[:2] == (96 // block, 4)
    logits, pages = jax.jit(lambda t, p: minicpm_sala_decode_paged(
        params, t, cfg, p, tables, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.arange(64)[None, :] < 50))(
            rows[:, :64], pages)
    worst = float(jnp.abs(logits[0, :50] - want[0, :50]).max())
    step = jax.jit(lambda t, p, pos: minicpm_sala_decode_paged(
        params, t, cfg, p, tables, None, pos, jnp.ones((1, 1), bool)))
    for at in range(50, 90):
        out, pages = step(rows[:, at:at + 1], pages,
                          jnp.asarray([at], jnp.int32))
        worst = max(worst, float(jnp.abs(out[0, 0] - want[0, at]).max()))
    assert worst < TOL, worst


# ------------------------------------------- (b) the recurrence's three forms
def rule_inputs(T_, B=2, H=2, d=128, seed=1):
    ks = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(ks[i], (B, T_, H, d)) * 0.5
               for i in range(3))
    # the fastest head's slope (h = 0 of 32) and the slowest's (h = 31)
    slope = jnp.asarray([2.0 ** -0.25, 2.0 ** -8], jnp.float32) if H == 2 \
        else sala.lightning_slopes(H)
    return q, k, v, slope, jax.random.normal(ks[3], (B, H, d, d))


def scanned(q, k, v, slope, lengths, state):
    """The reference's step (``reference/minicpm_sala._lightning``), token
    by token; a position past a row's length leaves the state alone."""
    lam = jnp.exp(-slope)[None, :, None, None]

    def one(S, xs):
        q, k, v, t = xs
        new = lam * S + jnp.einsum("rhk,rhv->rhkv", k, v, precision="highest")
        S = jnp.where((t < lengths)[:, None, None, None], new, S)
        return S, jnp.einsum("rhkv,rhk->rhv", S, q, precision="highest")

    S, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v)) + (jnp.arange(q.shape[1]),))
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_chunked_form_is_the_step_repeated_is_the_scan(form):
    """200 positions (no multiple of either chunk), one row cut at 137, from
    a state that is not zero, the fastest and the slowest head's decay: the
    chunked form (XLA; the kernel in interpret mode) and the step repeated
    against the reference's scan. Relative to outputs of 70: 1e-6 and, for
    the kernel's three-pass matmuls, 6e-6."""
    q, k, v, slope, state = rule_inputs(200)
    lengths = jnp.asarray([200, 137])
    o_ref, S_ref = jax.jit(scanned)(q, k, v, slope, lengths, state)
    keep = (jnp.arange(200)[None, :] < lengths[:, None])[..., None, None]
    if form == "xla":
        o, S = jax.jit(lightning.lightning_chunked_xla)(
            q, k, v, slope, lengths, state)

        def one(S, xs):
            q, k, v, t = xs
            o, S = lightning.lightning_step_xla(S, q, k, v, jnp.exp(-slope),
                                                t < lengths)
            return S, o
        S2, o2 = jax.jit(lambda: jax.lax.scan(one, state, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v)) + (jnp.arange(200),)))()
        assert float(jnp.abs((jnp.moveaxis(o2, 0, 1) - o_ref) * keep).max()) \
            < 1e-4
        assert float(jnp.abs(S2 - S_ref).max()) < 1e-4
        tol = 2e-4
    else:
        o, S = pallas_lightning.lightning_chunk(q, k, v, slope, lengths,
                                                state, interpret=True)
        tol = 1e-3
    assert bool(jnp.isfinite(o).all())
    assert float(jnp.abs((o - o_ref) * keep).max()) < tol
    assert float(jnp.abs(S - S_ref).max()) < tol


@pytest.mark.parametrize("live", [(True, False, True, False),
                                  (False, False, False, False)])
def test_step_kernel_is_the_plain_step_and_skips_dead_slots(live):
    """The kernel (interpret mode) against the plain step, dead slots among
    the live and none live at all: a dead slot's state comes back bit for
    bit and its output row is zero."""
    q, k, v, slope, state = rule_inputs(1, B=4, H=32)
    live = jnp.asarray(live)
    slope = sala.lightning_slopes(32)
    args = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(-slope), live)
    o1, S1 = lightning.lightning_step_xla(state, *args)
    o2, S2 = pallas_lightning.lightning_step(state, *args, interpret=True)
    assert float(jnp.abs(o1 - o2).max()) < 1e-4
    assert float(jnp.abs(S1 - S2).max()) < 1e-5
    dead = ~np.asarray(live)
    assert bool(jnp.array_equal(S2[dead], state[dead]))
    assert not bool(o2[dead].any())


def test_kernels_are_taken_on_a_tpu_at_whole_tiles(monkeypatch):
    calls = []
    monkeypatch.setattr(pallas_lightning, "lightning_chunk",
                        lambda *a: calls.append("chunk") or (a[2], a[5]))
    monkeypatch.setattr(pallas_lightning, "lightning_step",
                        lambda *a: calls.append("step") or (a[3], a[0]))
    q, k, v, slope, state = rule_inputs(8, B=1, H=32)
    small = rule_inputs(8, B=1, H=2, d=16)

    def trace(a):
        jax.eval_shape(lambda *x: lightning.lightning_chunked(*x), *a[:4],
                       jnp.asarray([8]), a[4])
        jax.eval_shape(lambda *x: lightning.lightning_step(*x), a[4],
                       a[0][:, 0], a[1][:, 0], a[2][:, 0], a[3],
                       jnp.asarray([True]))
        return len(calls)

    assert trace((q, k, v, slope, state)) == 0              # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace((q, k, v, slope, state)) == 2
    assert trace(small) == 2                                # 16 lanes: XLA


# ------------------------------------------------- (c) selection, by hand
SP = SparseConfig(kernel_size=4, kernel_stride=2, block_size=8,
                  window_size=8, topk=3, init_blocks=1, dense_len=16)


def loud(J, at):
    """Compressed keys ``[J, 2, 4]`` whose first value is 8 at the windows
    ``at[g]`` of kv head g and 0 elsewhere: a query ``e_0`` puts nearly all
    its softmax there."""
    ck = np.zeros((J, 2, 4), np.float32)
    for g, js in enumerate(at):
        ck[list(js), g, 0] = 8.0
    return jnp.asarray(ck)


def by_hand(p, at, sp=SP):
    q = jnp.zeros((1, 2, 1, 4)).at[..., 0].set(1.0)
    idx, count = sparse_select.selected_blocks(
        q, loud(24, at), jnp.asarray([p]), sp, 6)
    return idx[0].tolist(), count[0].tolist()


def test_selection_by_hand():
    """A query in block 4 of blocks of 8 (4 windows of 4 keys at stride 2 a
    block; window j covers keys 2j..2j+3, so window 4b - 1 straddles blocks
    b - 1 and b). Block 0 (``init_blocks``) and block 4 (the local window of
    one block) are forced; ``topk`` 3 keeps one more. kv head 0 is loud at
    window 7, which touches blocks 1 AND 2: a tie, to the lower index. kv
    head 1 is loud at window 13 (block 3 alone): a different set a kv head.
    At position 32 window 15 (keys 30-33, last key past the query) is not
    visible and must not count for block 3, however loud."""
    assert by_hand(39, [(7,), (13,)]) == ([[0, 1, 4], [0, 3, 4]], [3, 3])
    assert by_hand(32, [(7, 15), (15,)])[0] == [[0, 1, 4], [0, 1, 4]]
    assert by_hand(34, [(7, 15), (15,)])[0] == [[0, 1, 4], [0, 3, 4]]
    # with room for two more, the window before a block's own four (4b - 1)
    # counts for it: both blocks window 11 touches are kept
    four = SparseConfig(4, 2, 8, 8, 4, 1, 16)
    assert by_hand(39, [(11,), (3,)], four)[0] == [[0, 2, 3, 4], [0, 1, 2, 4]]
    # without a forced first block, block 0 competes by its own windows
    # 0..3 (there is no window -1) and loses to a loud block
    free = SparseConfig(4, 2, 8, 8, 2, 0, 16)
    assert by_hand(39, [(9,), (2,)], free)[0] == [[2, 4], [0, 4]]
    # fewer blocks at or before the query's own than topk: the rest are
    # the sentinel n_blocks and do not count
    assert by_hand(12, [(1,), (1,)]) == ([[0, 1, 6], [0, 1, 6]], [2, 2])
    # the reference's own rule, written apart from the program's
    q = jnp.zeros((2, 1, 1, 4)).at[..., 0].set(1.0)
    kept = ref.kept_blocks(
        q, loud(15, [(7,), (13,)]).transpose(1, 0, 2), jnp.asarray([32]),
        dict(kernel_size=4, kernel_stride=2, block_size=8, window_size=8,
             topk=3, init_blocks=1, dense_len=16), 6)
    assert np.flatnonzero(kept[0, 0]).tolist() == [0, 1, 4]
    assert np.flatnonzero(kept[1, 0]).tolist() == [0, 3, 4]


def test_the_compacted_lists_attention_is_masked_dense_attention():
    """Rows at positions 39 (sparse), 12 (dense: ``dense_len`` 16) and a dead
    lane over pages of 2, a block's four pages an aligned run and the runs
    scattered: ``paged_decode_attention`` over each (row, kv head)'s list of
    runs against a softmax over the kept blocks' positions taken by hand, a
    different set a kv head."""
    rng = np.random.default_rng(5)
    B, H, KV, hd, nb = 3, 4, 2, 4, 24
    k_pages = jnp.asarray(rng.normal(size=(B * nb, 2, 1, 128)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(B * nb, 2, 1, 128)), jnp.float32)
    tables = run_tables(B, nb, 4, rng.permutation(B * nb // 4))
    pos = jnp.asarray([39, 12, 0])
    live = jnp.asarray([True, True, False])
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    ck_rows = np.zeros((B * nb, 1, 1, 128), np.float32)
    row0 = np.asarray(tables[0])
    ck_rows[row0[7], 0, 0, :hd] = 3 * np.asarray(q[0, 0])      # kv head 0
    ck_rows[row0[13], 0, 0, hd:2 * hd] = 3 * np.asarray(q[0, 2])
    lists, held, sparse = sparse_select.decode_page_lists(
        q, jnp.asarray(ck_rows), tables, pos, live, SP, KV, 2)
    assert sparse.tolist() == [True, False, False]
    assert held.tolist() == [[24, 24], [13, 13], [0, 0]]
    assert lists.shape == (B, KV, 3)           # max(3 blocks, 16 / 8)
    assert lists[0, 0].tolist() == (row0[[0, 4, 16]] // 4).tolist()
    assert lists[0, 1].tolist() == (row0[[0, 12, 16]] // 4).tolist()
    assert lists[1, 0].tolist() == (np.asarray(tables[1])[[0, 4, 8]]
                                    // 4).tolist()
    out = sparse_select.sparse_decode_attention(q, k_pages, v_pages, lists,
                                                held, KV, 4)
    for b, blocks in ((0, ([0, 1, 4], [0, 3, 4])), (1, ([0, 1], [0, 1]))):
        p = int(pos[b])
        k_row = np.asarray(k_pages)[np.asarray(tables[b])].reshape(-1, 128)
        v_row = np.asarray(v_pages)[np.asarray(tables[b])].reshape(-1, 128)
        for h in range(H):
            g = h // 2
            at = [t for t in range(p + 1) if t // 8 in blocks[g]]
            kk = k_row[at, g * hd:(g + 1) * hd]
            s = kk @ np.asarray(q[b, h]) / 2.0
            w = np.exp(s - s.max())
            want = (w / w.sum()) @ v_row[at, g * hd:(g + 1) * hd]
            assert np.allclose(out[b, h], want, atol=1e-5), (b, h)


# --------------------------------------- (d) the compressed-key leaf's life
def test_a_window_is_written_exactly_when_its_last_key_is(served):
    """Row 1 (prompt 100, pages of 2 = the stride): the prefill wrote windows
    0..48 (window 49 needs key 101) at the TRUE length, not the bucket's;
    the tick at position 100 closes none, the tick at 101 closes window 49,
    into the row of page 49."""
    ck = served["filled"][0]["ck"]
    table = np.asarray(served["tables"][1])
    assert bool(ck[table[48]].any()) and not bool(ck[table[49]].any())
    assert not bool(ck[table[60]].any())       # inside the bucket of 128
    counts = served["counts"]
    # both layers: rows 0 and 1 at even lengths, row 2 (40) too
    assert counts[0]["ck_rows_written"] == 0
    assert counts[1]["ck_rows_written"] == 6
    assert bool(served["pages"][0]["ck"][table[49]].any())
    total = sum(c["ck_rows_written"] for c in counts)
    assert total == 2 * 3 * (STEPS // 2)


def requests(first_id=0):
    """Prompts past ``dense_len`` 64 that fill their bucket (128) and do not
    (70, 100), more requests than slots, outputs that cross windows."""
    rng = np.random.default_rng(37)
    return [Request(req_id=first_id + i,
                    tokens=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=m, seed=0)
            for i, (n, m) in enumerate([(128, 9), (70, 12), (100, 7)])]


def engine_of(model, **kw):
    _, params, cfg = model
    base = dict(max_seqs=2, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                prefill_cap_tokens=128, moe_stats=True)
    base.update(kw)
    return ServingEngine(ServeModel.for_minicpm_sala(params, cfg),
                         ServeConfig(**base))


@pytest.fixture(scope="module")
def batched(model):
    """(engine, its first run's completions, the same requests run AGAIN
    through the engine as the first run left it, every compressed-key row of
    the pool made loud in between)."""
    eng = engine_of(model)
    first = eng.run(requests())
    stats = dict(eng.stats)
    eng.pages = [{k: (jnp.full_like(v, 40.0) if k == "ck" else v)
                  for k, v in c.items()} for c in eng.pages]
    return eng, first, eng.run(requests(10)), stats


def test_engine_tokens_are_the_references_first_choices(model, batched):
    weights = model[0]
    _, out, _, _ = batched
    rows = np.zeros((3, T), np.int32)
    for req in requests():
        assert out[req.req_id].reason == "length"
        seq = list(req.tokens) + out[req.req_id].tokens
        rows[req.req_id, :len(seq)] = seq
    first = np.asarray(jax.jit(
        lambda r: ref.forward(weights, r, TINY).argmax(-1))(rows))
    for req in requests():
        n, m = len(req.tokens), req.max_new_tokens
        assert first[req.req_id, n - 1:n + m - 1].tolist() \
            == out[req.req_id].tokens, req.req_id


def test_a_page_admitted_twice_reads_nothing_of_its_last_owner(model, batched):
    """The same requests through the engine as its first run left it, with a
    loud stale compressed key planted in EVERY page: the tokens a fresh
    engine gave (a row reads only windows it has rewritten itself), and the
    states reset. The engine's counters: a reset an admission, a compressed
    key a closed window a ``minicpm4`` layer."""
    eng, first, again, stats = batched
    for req in requests():
        assert again[req.req_id + 10].tokens == first[req.req_id].tokens
    assert eng.stats["state_resets"] == eng.stats["prefill_dispatches"] == 6
    # prompts of 128, 70, 100: 63 + 34 + 49 windows; decode ticks close one
    # at every even length reached: 4 in 129..136, 5 in 71..81, 3 in 101..106
    assert stats["ck_rows_written"] == 2 * (63 + 34 + 49 + 4 + 5 + 3)
    assert stats["sparse_rows"] == 2 * (8 + 11 + 6) and not stats["dense_rows"]
    assert stats["state_rows_stepped"] == 2 * (8 + 11 + 6)
    assert stats["state_bytes"] == 2 * 2 * 4 * 16 * 16 * 4


def page_lists_of_pr40(q, ck, tables, pos, live, sp, kv_heads, page):
    """``ops/sparse_select.decode_page_lists`` of the parent commit
    (4baec4a): a list names PAGES, four table entries a kept block, read
    wherever they point."""
    NB = ck.shape[0]
    B, H, hd = q.shape
    nb = tables.shape[1]
    r = sp.block_size // page
    n_blocks = nb // r
    rows = sparse_select.gather_compressed(ck, tables)[..., :kv_heads * hd]
    idx, count = jax.vmap(
        lambda qb, cb, pb: sparse_select.selected_blocks(
            qb.reshape(1, kv_heads, H // kv_heads, hd),
            cb.reshape(-1, kv_heads, hd), pb[None], sp, n_blocks)
    )(q, rows, pos)
    idx, count = idx[:, 0], count[:, 0]
    K = idx.shape[-1]
    width = min(nb, max(K * r, sp.dense_len // page))
    at = (idx[..., None] * r + jnp.arange(r)).reshape(B, kv_heads, K * r)
    picked = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None], (B, kv_heads, nb)),
        jnp.minimum(at, nb - 1), axis=2)
    picked = jnp.where(at < n_blocks * r, picked, NB)
    picked = jnp.pad(picked, ((0, 0), (0, 0), (0, width - K * r)),
                     constant_values=NB)
    sparse = pos + 1 > sp.dense_len
    held = (count - 1) * sp.block_size + (pos % sp.block_size)[:, None] + 1
    lists = jnp.where(sparse[:, None, None], picked,
                      tables[:, None, :width])
    lengths = jnp.where(sparse[:, None], held, (pos + 1)[:, None])
    return lists, jnp.where(live[:, None], lengths, 0), sparse


@pytest.mark.parametrize("n_slots", [2, 3])
def test_slots_that_leave_out_of_order_and_return_read_whole_blocks(
        model, n_slots, monkeypatch):
    """Seven requests through two or three slots, outputs of 5 to 40 tokens
    over prompts on both sides of ``dense_len``: slots grow a page every
    second tick in turn, leave in an order that is not their admission's and
    are admitted again into what the leavers gave back. The engine mints this
    family's pages in aligned runs of four (``ServeModel.page_run``: every
    table holds whole runs whenever a slot leaves), so the walk by blocks
    over the pool viewed by runs serves the tokens of the walk by pages (the
    parent commit's lists: four table entries a block, read wherever they
    point); ``kv_pages_selected`` counts what it counted, and ``kv_copies``
    a copy a block a leaf where the walk by pages starts two a page."""
    rng = np.random.default_rng(41)
    shapes = [(100, 30), (40, 9), (70, 5), (128, 12), (50, 40), (90, 7),
              (66, 21)]
    reqs = [Request(req_id=i, tokens=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=m, seed=0)
            for i, (n, m) in enumerate(shapes)]

    def serve(page_run=8):
        served = ServeModel.for_minicpm_sala(model[1], model[2])
        assert served.page_run == 8          # the family's block, its own
        served.page_run = page_run
        eng = ServingEngine(served, ServeConfig(
            max_seqs=n_slots, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
            prefill_cap_tokens=128, moe_stats=True))
        bt, left = eng.tables, []
        assert bt.run_pages == max(page_run // BLOCK, 1)
        assert bt.unused_blocks == 0
        if not page_run:
            out = eng.run(reqs)
            return [out[r.req_id].tokens for r in reqs]
        free_slot = bt.free_slot

        def leaving(slot):
            for s in range(n_slots):
                n = int(bt.owned[s])
                for j in range(0, n, 4):
                    run = bt.tables[s, j:min(j + 4, n)]
                    assert run[0] % 4 == 0 and (np.diff(run) == 1).all()
            left.append((slot, int(bt.tables[slot, 0])))
            return free_slot(slot)

        bt.free_slot = leaving
        out = eng.run(reqs)
        return [out[r.req_id].tokens for r in reqs], dict(eng.stats), left

    by_runs, stats, left = serve()
    assert len(left) == len(reqs)
    # a slot's second tenant starts in a run its first did not start in,
    # and slots do not leave in the order they came
    assert len({head for _, head in left}) > n_slots
    assert [s for s, _ in left[:n_slots]] != list(range(n_slots))
    with monkeypatch.context() as m:
        m.setattr(sala, "decode_page_lists", page_lists_of_pr40)
        m.setattr(sala, "sparse_decode_attention", lambda *a:
                  sparse_select.sparse_decode_attention(*a[:6]))
        by_pages, stats_pages, left_pages = serve()
    assert by_runs == by_pages and left == left_pages
    assert [len(t) for t in by_runs] == [m for _, m in shapes]
    assert stats["kv_pages_selected"] == stats_pages["kv_pages_selected"] > 0
    # whole blocks but each list's last: between a half and 0.6
    assert 0.5 <= stats["kv_copies"] / stats["kv_pages_selected"] < 0.6
    assert stats["sparse_rows"] > 0 and stats["dense_rows"] > 0
    # neither half alone: blocks read as runs out of pages minted one by one
    if n_slots == 3:
        assert serve(page_run=0) != by_runs


def test_compressed_keys_are_not_counted_against_the_pool(model):
    eng = engine_of(model, num_blocks=100)
    assert eng.tables.num_blocks == 100
    assert eng.pages[0]["ck"].shape == (100, 1, 1, 128)
    assert eng.pages[0]["k"].shape == (100, 2, 1, 128)
    assert eng.pages[1]["state"].shape == (2, 4, 16, 16)
    with pytest.raises(ValueError, match="whole strides"):
        engine_of(model, block_size=3)


# ----------------------- (f) the other families' pools, as they were
def leaves_as_they_were(n_layer, num_blocks, block_size, leaves, dtype,
                        groups=1, ring=((), 0), state=((), 0, {})):
    """``serve/kv_cache.init_page_leaves`` of the parent commit (314d2df),
    word for word."""
    from distributed_lion_tpu.serve.kv_cache import (
        init_state_leaves,
        pool_row_width,
    )

    def leaf(blocks, heads, width):
        return jnp.zeros((blocks, block_size, groups,
                          pool_row_width(heads // groups, width)), dtype)

    ring_layers, ring_blocks = ring
    state_layers, max_seqs, state_leaves = state
    return [init_state_leaves(max_seqs, state_leaves) if i in state_layers
            else {name: leaf(ring_blocks if i in ring_layers else num_blocks,
                             *hw) for name, hw in leaves.items()}
            for i in range(n_layer)]


@pytest.mark.parametrize("name", ["gpt2", "laguna", "ling"])
def test_other_families_pools_and_programs_are_as_they_were(name):
    """The page list is built by one function for every family: with the
    parent commit's in its place a GPT-2, a Laguna and a Ling TINY engine
    hold the same leaves, and the decode program (lowered for the family
    with all three kinds of leaf) is the same text over either (so the same
    bits)."""
    from distributed_lion_tpu.serve import engine as engine_mod

    if name == "gpt2":
        from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
        cfg = GPT2Config.tiny(vocab_size=256)
        m = ServeModel.for_gpt2(gpt2_init(jax.random.key(0), cfg), cfg)
    elif name == "laguna":
        from distributed_lion_tpu.models import laguna
        cfg = laguna.LagunaConfig.tiny(param_dtype=jnp.float32,
                                       compute_dtype=jnp.float32)
        m = ServeModel.for_laguna(laguna.laguna_init(jax.random.key(0), cfg),
                                  cfg)
    else:
        from distributed_lion_tpu.models import ling
        cfg = ling.LingConfig.tiny(param_dtype=jnp.float32,
                                   compute_dtype=jnp.float32)
        m = ServeModel.for_ling(ling.ling_init(jax.random.key(0), cfg), cfg)
    sc = ServeConfig(max_seqs=2, block_size=8, max_blocks_per_seq=4)

    def decode_text(make):
        old = engine_mod.init_page_leaves
        engine_mod.init_page_leaves = make
        try:
            eng = ServingEngine(m, sc)
        finally:
            engine_mod.init_page_leaves = old
        shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)), eng.pages)
        if name != "ling":      # one lowering is enough to show the seam
            return shapes, ""
        i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
        rest = (i32((2, 4)), i32((2,)), eng._prev, jnp.ones((2,), bool),
                jnp.zeros((2,), jnp.uint32), i32((2,)))
        text = jax.jit(eng._dispatches["decode"]["inner"]).lower(
            eng.params, eng.pages, *rest).as_text()
        return shapes, text

    assert decode_text(engine_mod.init_page_leaves) \
        == decode_text(leaves_as_they_were)


# ------------------------------------------------------- (g) the refusals
@pytest.mark.parametrize("kw,flag,sentence", [
    ({"prefix_cache": True}, "--prefix_cache", "no pages to share"),
    ({"speculate": "ngram:2"}, "--speculate", "cannot be rolled back"),
    ({"tp": 2}, "--serve_tp", "no sharding spec"),
    ({"ep": 2}, "--serve_ep", "exchange between ranges is not built")])
def test_engine_refuses_what_a_state_cannot_serve(model, kw, flag, sentence):
    with pytest.raises(ValueError,
                       match=f"recurrent state.*{flag}.*{sentence}"):
        engine_of(model, **kw)


@pytest.mark.parametrize("kw,flag,sentence", [
    ({"prefix_cache": True}, "--prefix_cache",
     "compressed key would be shared with it"),
    ({"speculate": "ngram:2"}, "--speculate", "already written")])
def test_engine_refuses_what_a_compressed_key_cannot_serve(model, kw, flag,
                                                           sentence):
    """The compressed-key leaf's own sentences, for a family that had it
    without a state."""
    _, params, cfg = model
    m = ServeModel.for_minicpm_sala(params, cfg)
    m.state_layers = ()
    with pytest.raises(ValueError,
                       match=f"compressed key a page.*{flag}.*{sentence}"):
        ServingEngine(m, ServeConfig(max_seqs=2, block_size=2,
                                     max_blocks_per_seq=8, **kw))


def test_engine_refuses_to_quantize_this_family(model):
    with pytest.raises(ValueError, match="one device, unquantized"):
        engine_of(model, quant="int8")


# ------------------------------------------------------ the configuration
def test_config_from_the_published_keys():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala.json")) as f:
        body = json.load(f)
    cfg = MiniCPMSalaConfig.from_hf(body)
    assert cfg == MiniCPMSalaConfig.named(os.path.join(
        ROOT, "benchmark", "configs", "minicpm-sala.json"))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (8, 4096, 32, 2, 128, 16384, 73448)
    assert cfg.sparse_layers == (0, 7) and len(cfg.lightning_layers) == 6
    assert cfg.sparse == SparseConfig(32, 16, 64, 2048, 64, 1, 8192)
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert float(sala.lightning_slopes(32)[0]) == pytest.approx(2 ** -0.25)
    assert float(sala.lightning_slopes(32)[31]) == pytest.approx(2 ** -8)
    for key, wrong in (("attn_use_rope", True), ("qk_norm", False),
                       ("lightning_nkv", 8), ("use_output_gate", False)):
        with pytest.raises(ValueError, match="not implemented"):
            MiniCPMSalaConfig.from_hf(dict(body, **{key: wrong}))
    with pytest.raises(ValueError, match="sparse_config"):
        SparseConfig(kernel_size=24)


def test_tiny_is_the_families_tiny():
    assert MiniCPMSalaConfig.tiny() == MiniCPMSalaConfig.from_hf(TINY)
    assert MiniCPMSalaConfig.named("tiny") == MiniCPMSalaConfig.tiny()


def test_run_serve_names_the_family():
    from distributed_lion_tpu.cli import run_generate, run_serve
    import inspect

    assert run_generate.PAGED_ONLY["minicpm_sala"] == "MiniCPMSalaConfig"
    assert "for_minicpm_sala" in inspect.getsource(run_serve)
