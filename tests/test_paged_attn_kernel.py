"""The decode kernel that reads the KV pool in place
(ops/pallas_paged_attn), through Pallas interpret mode at tiny sizes,
against the gather path it replaces on the chip; and the rule that sends
every other call down the gather path, bit for bit."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.ops import attention as A
from distributed_lion_tpu.ops.pallas_paged_attn import (
    PAGES_PER_BLOCK,
    kernel_takes,
    paged_attn,
)
from distributed_lion_tpu.serve.kv_cache import init_pages, pool_row_width

BS = 16          # block_size: whole sublane tiles of bf16 and float32
NB_SEQ = 2 * PAGES_PER_BLOCK + 1  # table width: two whole blocks and a bit


def _pool(rng, nb, kv, hd, dtype):
    """A k and a v leaf as the engine lays them out, every page written
    (pad lanes zero, as the scatter leaves them) and the LAST page, which no
    table names, poisoned: a read clamped from past the pool would show."""
    W = pool_row_width(kv, hd)
    leaves = []
    for _ in range(2):
        x = np.zeros((nb, BS, 1, W), np.float32)
        x[..., :kv * hd] = rng.standard_normal((nb, BS, 1, kv * hd))
        x[-1] = np.nan
        leaves.append(jnp.asarray(x, dtype))
    return leaves


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("H,KV,hd", [(4, 4, 64), (8, 2, 128)],
                         ids=["mha64", "gqa128"])
def test_kernel_matches_gather_path(H, KV, hd, dtype, tol):
    """Rows of length 1, bs-1, bs, bs+1, a whole block +1 and the full
    table, over a permuted table, among inactive slots (all-sentinel rows,
    where no row's first block is copied ahead): the kernel equals the
    gather path within the dtype's tolerance, the inactive rows are exactly
    zero and nothing past the pool is read."""
    rng = np.random.default_rng(H * 1000 + hd)
    lens = [1, BS - 1, 0, BS, BS + 1, PAGES_PER_BLOCK * BS + 1, 0, 0,
            NB_SEQ * BS, 0]
    B = len(lens)
    nb = B * NB_SEQ + 1                       # + the poisoned page
    k_pages, v_pages = _pool(rng, nb, KV, hd, dtype)
    assert kernel_takes(k_pages.shape, dtype)
    tables = rng.permutation(nb - 1)[:B * NB_SEQ].reshape(B, NB_SEQ)
    for b, n in enumerate(lens):              # unowned entries: sentinel
        tables[b, math.ceil(n / BS):] = nb
    tables = jnp.asarray(tables, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, 1, hd)), dtype)
    pos = jnp.asarray([max(n - 1, 0) for n in lens], jnp.int32)

    want = A.paged_decode_attention(q, k_pages, v_pages, tables, pos,
                                    kv_heads=KV)  # CPU: the gather path
    lengths = jnp.minimum(pos + 1, jnp.sum(tables < nb, axis=1) * BS)
    np.testing.assert_array_equal(np.asarray(lengths), lens)
    got = paged_attn(q[:, :, 0], k_pages, v_pages, tables, lengths,
                     kv_heads=KV, interpret=True)

    assert got.shape == (B, H, hd) and got.dtype == dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want[:, :, 0]))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[[n == 0 for n in lens]], 0.0)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def _gather_path_of_pr23(q, k_pages, v_pages, tables, pos, start=None):
    """``paged_decode_attention`` as it stood before the kernel, over a
    ``[num_blocks, bs, KV, hd]`` pool: the bit-for-bit reference."""
    B, H, S, hd = q.shape
    KV = k_pages.shape[2]

    def full(pages):
        got = jnp.take(pages, tables, axis=0, mode="fill", fill_value=0)
        return got.reshape((B, -1) + pages.shape[2:]).transpose(0, 2, 1, 3)

    k_full, v_full = full(k_pages), full(v_pages)
    if KV != H:
        k_full = jnp.repeat(k_full, H // KV, axis=1)
        v_full = jnp.repeat(v_full, H // KV, axis=1)
    T = k_full.shape[2]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k_full,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    t_idx = jnp.arange(T)[None, None, :]
    valid = t_idx <= (pos[:, None] + jnp.arange(S)[None, :])[:, :, None]
    if start is not None:
        valid &= t_idx >= start[:, None, None]
    scores = jnp.where(valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v_full,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@pytest.mark.parametrize("S,with_start", [(1, False), (3, False), (1, True)],
                         ids=["cpu", "window", "start"])
def test_other_calls_keep_the_gather_path_bit_for_bit(S, with_start,
                                                      monkeypatch):
    """The CPU backend, S > 1 and ``start`` all run the gather path, over
    the engine's padded pool exactly as over ``[NB, bs, KV, hd]``; and the
    dispatch rule says so even where the backend is a TPU."""
    rng = np.random.default_rng(7)
    B, H, KV, hd, nb, per = 3, 5, 5, 64, 12, 4   # 5 x 64: a head of pad
    heads = rng.standard_normal((2, nb, 8, KV, hd)).astype(np.float32)
    old = [jnp.asarray(x, jnp.bfloat16) for x in heads]
    new = []
    for leaf, x in zip(init_pages(2, nb, 8, KV, hd, jnp.bfloat16)[0].values(),
                       heads):
        assert leaf.shape == (nb, 8, 1, 384)
        new.append(leaf.at[..., :KV * hd].set(
            x.reshape(nb, 8, 1, KV * hd).astype(jnp.bfloat16)))
    tables = jnp.asarray(rng.permutation(nb)[:B * per].reshape(B, per),
                         jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, S, hd)), jnp.bfloat16)
    pos = jnp.asarray([3, 17, 28], jnp.int32)
    start = jnp.asarray([0, 2, 9], jnp.int32) if with_start else None

    want = _gather_path_of_pr23(q, *old, tables, pos, start)
    for pool, kv in ((old, None), (new, KV)):
        got = A.paged_decode_attention(q, *pool, tables, pos, start,
                                       kv_heads=kv)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    pool_shape = (2048, 16, 1, 1664)
    assert not A.paged_kernel_applies(S, pool_shape, jnp.bfloat16,
                                      start)  # the CPU never takes it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    takes = A.paged_kernel_applies(S, pool_shape, jnp.bfloat16, start)
    assert takes == (S == 1 and not with_start)
    # ... and a pool the chip would have to re-lay out stays with the gather
    assert not A.paged_kernel_applies(1, (2048, 16, 25, 64),
                                      jnp.bfloat16)
    assert not A.paged_kernel_applies(1, (2048, 8, 1, 1664),
                                      jnp.bfloat16)


def test_engine_counts_pages_read_and_no_kernel_ticks_on_the_cpu():
    """``kv_pages_read`` is the pages the rows' lengths need, tick by
    tick; ``kv_pages_table`` what the tables' whole width holds; and the
    CPU's decode program holds no kernel."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServeModel, ServingEngine,
    )

    cfg = GPT2Config.tiny()
    eng = ServingEngine(
        ServeModel.for_gpt2(gpt2_init(jax.random.key(0), cfg), cfg),
        ServeConfig(max_seqs=2, block_size=4, max_blocks_per_seq=8,
                    temperature=0.0))
    assert eng.pages[0]["k"].shape == (16, 4, 1, pool_row_width(
        cfg.n_head, cfg.head_dim))
    L, new = 6, 5
    eng.run([Request("a", list(range(1, L + 1)), new)])
    st = eng.stats
    assert st["decode_ticks"] == new - 1      # the prefill gave the first
    assert st["decode_attn_kernel_ticks"] == 0
    assert st["kv_pages_table"] == st["decode_ticks"] * 2 * 8
    # tick j attends the prompt, the j tokens before it and its own
    assert st["kv_pages_read"] == sum(
        math.ceil((L + j + 1) / 4) for j in range(new - 1))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_block_lists_over_the_run_view_are_the_page_walk_bit_for_bit(dtype):
    """A family whose reader walks blocks of 64 positions gets its pages in
    aligned runs of four (``BlockTables(run_pages=4)``) and hands the kernel
    the pool viewed ``[NB / 4, 64, 1, W]`` with a run id a block: the output
    is BIT FOR BIT the walk over the same blocks' four table entries each by
    pages of 16 (16 runs are copied ahead where 16 pages were, but the
    softmax takes 256 positions at a time either way), with a quarter of
    the list. Rows: blocks kept out of a long row, more than one block of 16
    runs, the last run partly filled; a dead row; a row under ``dense_len``
    (its table's own blocks, a partly filled page last); a row every page of
    which was minted a page at a time between its neighbours', after a slot
    had left and its runs had been taken again."""
    from distributed_lion_tpu.ops.pallas_paged_attn import (
        pages_per_block, parts_per_block,
    )
    from distributed_lion_tpu.serve.kv_cache import BlockTables

    assert (pages_per_block(BS), parts_per_block(BS)) == (PAGES_PER_BLOCK, 1)
    assert (pages_per_block(64), parts_per_block(64)) == (PAGES_PER_BLOCK, 4)
    assert (pages_per_block(512), parts_per_block(512)) == (2, 2)
    assert (pages_per_block(48), parts_per_block(48)) == (16, 1)
    rng = np.random.default_rng(41)
    r, H, KV, hd, per = 4, 8, 2, 128, 80
    bt = BlockTables(num_blocks=4 * per + 8, block_size=BS, max_seqs=4,
                     max_blocks_per_seq=per, run_pages=r)
    assert bt.grow(3, 9 * BS) and bt.grow(0, 79 * BS)
    bt.free_slot(3)                    # its runs come back first, descending
    for tokens in range(BS, 23 * BS + 1, BS):      # a page at a time, in turn
        for slot, upto in ((1, 6), (3, 23)):
            if tokens <= upto * BS:
                assert bt.grow(slot, tokens)
    tables = np.asarray(bt.tables)
    assert (np.diff(tables[3, :8]) == 1).sum() == 6      # runs, scattered:
    assert len(set(tables[3, :23:r] // r)) == 6          # not one long run
    # the slot that left gave back runs 0, 4, 8: taken again last first
    assert (tables[1, 0], tables[3, 0], tables[1, 4]) == (8, 4, 0)
    k_pages, v_pages = _pool(rng, bt.num_blocks, KV, hd, dtype)
    assert not (tables[:, :per] == bt.num_blocks - 1).any()   # the poison

    # (row's slot, blocks kept ascending, positions the list holds)
    rows = [(0, [0, 2] + list(range(4, 20)), 17 * 64 + 37),
            (2, [], 0),
            (1, [0, 1], 5 * BS + 3),
            (3, [0, 1, 3, 4, 5], 4 * 64 + 2 * BS + 9)]
    width = 20
    runs = np.full((len(rows), width), bt.num_blocks // r, np.int32)
    pages = np.full((len(rows), width * r), bt.num_blocks, np.int32)
    for i, (slot, kept, _) in enumerate(rows):
        for j, b in enumerate(kept):
            runs[i, j] = tables[slot, r * b] // r
            pages[i, r * j:r * j + r] = tables[slot, r * b:r * b + r]
    lengths = jnp.asarray([n for _, _, n in rows], jnp.int32)
    q = jnp.asarray(rng.standard_normal((len(rows), H, hd)), dtype)

    def view(x):
        return x.reshape(x.shape[0] // r, r * BS, 1, x.shape[-1])

    by_page = paged_attn(q, k_pages, v_pages, jnp.asarray(pages), lengths,
                         kv_heads=KV, interpret=True)
    by_run = paged_attn(q, view(k_pages), view(v_pages), jnp.asarray(runs),
                        lengths, kv_heads=KV, interpret=True)
    assert np.isfinite(np.asarray(by_run, np.float32)).all()
    assert np.abs(np.asarray(by_run, np.float32)[[0, 2, 3]]).min() > 0
    np.testing.assert_array_equal(np.asarray(by_run, np.float32)[1], 0.0)
    np.testing.assert_array_equal(np.asarray(by_run, np.float32),
                                  np.asarray(by_page, np.float32))
    # and both are the gather path's attention over those positions
    want = A.paged_decode_attention(q[:, :, None], view(k_pages),
                                    view(v_pages), jnp.asarray(runs),
                                    lengths - 1, kv_heads=KV)[:, :, 0]
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(by_run, np.float32)[[0, 2, 3]],
                               np.asarray(want, np.float32)[[0, 2, 3]],
                               atol=tol, rtol=tol)
