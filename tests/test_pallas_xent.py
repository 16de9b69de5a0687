"""The loss head's kernel pair (ops/pallas_xent), through Pallas interpret
mode at small sizes, against ``clm_loss_and_metrics`` on dense
float32-accumulated logits: loss, accuracy, n_tokens and both gradients;
and the rule (``ops/xent.head_path``) by which the entry
(``ops/xent.clm_head_loss``) takes the kernels or another head, from what a
call shows (never an option), which ``train/remat`` asks and does not copy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.models.loss import clm_loss_and_metrics
from distributed_lion_tpu.ops import pallas_xent as PX
from distributed_lion_tpu.ops import xent as X

TILES = (128, 128)


def _dense(hidden, head, tokens, mask, valid_v):
    logits = jnp.einsum("btd,vd->btv", hidden, head.astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return clm_loss_and_metrics(logits[..., :valid_v or None], tokens, mask)


def _fused(hidden, head, tokens, mask, valid_v, tiles=TILES):
    return X._fused_clm_loss_and_metrics(hidden, head, tokens, mask, valid_v,
                                         tiles, True)


def _case(B, T, V, d, dtype, *, valid_v=0, mask=None, seed=0):
    kh, kw, kt = jax.random.split(jax.random.key(seed), 3)
    hidden = jax.random.normal(kh, (B, T, d), dtype)
    head = jax.random.normal(kw, (V, d), jnp.float32) * 0.3
    tokens = jax.random.randint(kt, (B, T), 0, valid_v or V)
    return hidden, head, tokens, mask, valid_v


def _whole_rows_off(B, T):
    m = np.ones((B, T), np.float32)
    m[1] = 0.0                      # a whole sequence off
    m[0, : T // 2] = 0.0            # and half of another
    return jnp.asarray(m)


def _close(got, want, f32):
    """float32: element by element. bfloat16: both sides round their
    float32 sums to 8 bits, in another order: the whole gradient to 1%, an
    element to a hundredth of the largest."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    if f32:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
        return
    assert np.linalg.norm(got - want) <= 1e-2 * np.linalg.norm(want)
    assert np.abs(got - want).max() <= 1e-2 * np.abs(want).max()


CASES = {
    # vocabulary neither a multiple of the tile nor of 128; rows a multiple
    "ragged_vocab": dict(B=2, T=128, V=333, d=128),
    # rows (2 x 75 = 150) not a multiple of the 128-row tile
    "ragged_rows": dict(B=2, T=75, V=256, d=128),
    "both_ragged": dict(B=3, T=50, V=300, d=256),
    "one_tile": dict(B=1, T=64, V=100, d=128),
    "masked_rows": dict(B=3, T=64, V=333, d=128, mask=True),
    # a padded head: rows 321.. of 384 are alignment padding
    "padded_head": dict(B=2, T=64, V=384, d=128, valid_v=321),
    # padding that fills whole tiles (they are never visited)
    "padded_tiles": dict(B=2, T=64, V=512, d=128, valid_v=200),
}


def _build(name, dtype):
    kw = dict(CASES[name])
    if kw.pop("mask", False):
        kw["mask"] = _whole_rows_off(kw["B"], kw["T"])
    return _case(dtype=dtype, **kw)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_pair_matches_the_dense_loss(name, dtype):
    args = _build(name, dtype)
    hidden, head = args[:2]

    def grads(fn):
        (loss, metrics), g = jax.value_and_grad(
            lambda h, w: fn(h, w, *args[2:]), argnums=(0, 1),
            has_aux=True)(hidden, head)
        return loss, metrics, g

    loss, m, (dh, dw) = grads(_fused)
    loss0, m0, (dh0, dw0) = grads(_dense)
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(loss, loss0, rtol=2e-6 if f32 else 2e-5)
    assert m["n_tokens"] == m0["n_tokens"]
    # bf16 logits tie more often and a tile's sum order can move one; the
    # float32 argmax is the dense one exactly
    np.testing.assert_allclose(m["accuracy"], m0["accuracy"],
                               atol=0 if f32 else 2.0 / m0["n_tokens"])
    assert dh.dtype == hidden.dtype and dw.dtype == head.dtype
    _close(dh, dh0, f32)
    _close(dw, dw0, f32)
    valid_v = args[4]
    if valid_v:                     # a padded head's pad rows: no gradient
        assert not np.asarray(dw[valid_v:]).any()


def test_masked_rows_give_no_loss_and_no_gradient():
    hidden, head, tokens, mask, _ = _build("masked_rows", jnp.float32)
    dh = jax.grad(lambda h: _fused(h, head, tokens, mask, 0)[0])(hidden)
    assert not np.asarray(dh[1]).any()                 # the sequence off
    assert not np.asarray(dh[0, : 64 // 2 - 1]).any()  # labels masked
    assert not np.asarray(dh[:, -1]).any()             # no label at all
    assert np.asarray(dh[2, :-1]).any(axis=-1).all()
    all_off = jnp.zeros_like(mask)
    loss, m = _fused(hidden, head, tokens, all_off, 0)
    assert loss == 0.0 and m["n_tokens"] == 0.0


def test_label_in_the_last_partial_tile():
    """V = 333 with tiles of 128: the last tile holds rows 256..332 and 51
    rows of whatever the buffer held. Every label lies in it."""
    hidden, head, tokens, _, _ = _build("ragged_vocab", jnp.float32)
    tokens = 256 + tokens % (333 - 256)
    loss, m = _fused(hidden, head, tokens, None, 0)
    loss0, m0 = _dense(hidden, head, tokens, None, 0)
    np.testing.assert_allclose(loss, loss0, rtol=2e-6)
    assert m["accuracy"] == m0["accuracy"]
    dw = jax.grad(lambda w: _fused(hidden, w, tokens, None, 0)[0])(head)
    dw0 = jax.grad(lambda w: _dense(hidden, w, tokens, None, 0)[0])(head)
    np.testing.assert_allclose(dw, dw0, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("pair", [(5, 200), (130, 131), (3, 300)],
                         ids=["across_tiles", "within_a_tile", "last_tile"])
def test_a_tie_between_two_logits_takes_the_first_index(pair):
    """Two equal rows of the head give equal logits, and larger than any
    other: ``argmax`` is the lower index, in one tile or across two."""
    lo, hi = pair
    kh, kw = jax.random.split(jax.random.key(7))
    hidden = jax.random.normal(kh, (40, 128), jnp.float32)
    head = jax.random.normal(kw, (333, 128), jnp.float32) * 0.01
    top = hidden.mean(axis=0) * 50.0
    head = head.at[lo].set(top).at[hi].set(top)
    labels = jnp.zeros((40,), jnp.int32)
    _, idx = PX.fused_xent(hidden, head, labels, 0, TILES, True)
    dense = jnp.einsum("nd,vd->nv", hidden, head).argmax(-1)
    np.testing.assert_array_equal(idx, dense)
    assert (np.asarray(idx) == lo).sum() > 20      # the tie decided rows


def _computed_blocks(n, d, tn):
    """Row blocks the backward's grid computes: a short last group's
    missing steps are skipped, so never more than the blocks there are."""
    groups, per_group = PX.row_groups(n, d, tn)
    blocks = -(-n // tn)
    assert (groups - 1) * per_group < blocks <= groups * per_group
    return blocks


def test_tiles_and_row_groups():
    assert PX.tiles_for(20 * 1024, 50257, 768) == (1024, 512)
    assert PX.tiles_for(4 * 1024, 50257, 768) == (1024, 512)
    assert PX.tiles_for(150, 100, 768) == (256, 128)
    # 20 x 1,024 rows of 768 float32: 63 MB of dh in two groups of ten
    # blocks; 4 x 1,024 in one
    assert PX.row_groups(20 * 1024, 768, 1024) == (2, 10)
    assert PX.row_groups(4 * 1024, 768, 1024) == (1, 4)
    assert PX.row_groups(150, 128, 128) == (1, 2)
    # cell 10: 2 x 8,192 rows of 2,304 against V 24,576. A 1,024 x 512 step
    # is three times cell 1's: the row block halves, 32 blocks of 4.7 MB of
    # dh fill four groups of eight, and no row is padding
    tn, tv = PX.tiles_for(2 * 8192, 24576, 2304)
    assert (tn, tv) == (512, 512) and tn * tv * 2304 <= PX.STEP_MACS
    groups, per_group = PX.row_groups(2 * 8192, 2304, tn)
    assert (groups, per_group) == (4, 8) and groups <= 6
    assert groups * per_group * tn == 2 * 8192 and -(2 * 8192) % tn == 0
    assert per_group * tn * 2304 * 4 <= PX.DH_VMEM_BYTES
    # a prime row count at that width: 33 blocks in five groups of seven,
    # the last of five; 33 computed, the 35 slots' last two skipped, and
    # the rows padded to their own last block alone
    n = 16411
    tn, _ = PX.tiles_for(n, 24576, 2304)
    assert PX.row_groups(n, 2304, tn) == (5, 7)
    assert _computed_blocks(n, 2304, tn) == 33 and 33 * tn - n == 485
    # the rule halves the larger tile, and only down to whole lanes
    assert PX.tiles_for(2 * 8192, 24576, 4096) == (256, 512)
    assert PX.tiles_for(384, 24576, 8192) == (384, 128)


@pytest.mark.parametrize("rows,cut", [(512, (2, 2)), (640, (3, 2)),
                                      (700, (2, 3))],
                         ids=["even_split", "short_last_group",
                              "ragged_rows"])
def test_more_rows_than_one_group_holds(monkeypatch, rows, cut):
    """More row groups than one (the float32 dh of two or three 128-row
    blocks each): four blocks in two full groups; five in three groups, the
    last one block short (its missing step skipped); 700 rows, no multiple
    of the row block, padded to six blocks and no further. Partial head
    gradients a group, summed; a masked row's gradient is zero."""
    monkeypatch.setattr(PX, "DH_VMEM_BYTES", cut[1] * 128 * 128 * 4)
    hidden, head, tokens, _, _ = _case(1, rows, 200, 128, jnp.float32)
    assert PX.row_groups(rows, 128, 128) == cut
    assert _computed_blocks(rows, 128, 128) == -(-rows // 128)
    mask = np.ones((1, rows), np.float32)
    mask[0, rows - 130:rows - 3] = 0.0      # across the last two blocks
    mask = jnp.asarray(mask)

    def both(fn):
        (loss, m), g = jax.value_and_grad(
            lambda h, w: fn(h, w, tokens, mask, 0), (0, 1),
            has_aux=True)(hidden, head)
        return loss, m["accuracy"], m["n_tokens"], *g

    got, want = both(_fused), both(_dense)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    assert got[1:3] == want[1:3]
    for a, b in zip(got[3:], want[3:]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    dh = np.asarray(got[3])[0]
    assert not dh[rows - 131:rows - 4].any()     # their labels are masked
    assert dh[:rows - 131].any(axis=-1).all()


# ------------------------------------------------------------ the entry's rule
@pytest.mark.parametrize("backend,d,dtype,takes", [
    ("tpu", 768, jnp.bfloat16, True),       # both training cells
    ("tpu", 1600, jnp.bfloat16, False),     # GPT-2 XL: 12.5 lane blocks
    ("tpu", 768, jnp.float32, False),
    ("tpu", 64, jnp.bfloat16, False),       # the tiny preset
    ("cpu", 768, jnp.bfloat16, False),
    ("gpu", 768, jnp.bfloat16, False),
])
def test_kernel_applies_from_what_a_call_shows(monkeypatch, backend, d, dtype,
                                               takes):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert X.head_path("vd", d, dtype) == ("fused" if takes else "dense")


_CELL = ("vd", 768, jnp.bfloat16)           # both training cells' head
_REFUSED = {"chunks": "--tp_vocab and --vocab_chunks are alternative head "
                      "strategies",
            "seq_axis": "--tp_vocab under --seq_parallel is not wired"}


@pytest.mark.parametrize("head,kw,path", [
    (_CELL, {}, "fused"),
    (("dv", 768, jnp.bfloat16), {}, "dense"),      # an lm_head lies [d, V]
    (_CELL, dict(chunks=4), "chunked"),
    (("dv", 4096, jnp.bfloat16), dict(chunks=8), "chunked"),
    (_CELL, dict(vocab_axis="tensor"), "tp_vocab"),
    (("dv", 64, jnp.float32), dict(vocab_axis="tensor"), "tp_vocab"),
    (_CELL, dict(seq_axis="seq"), "seq"),
    (("dv", 64, jnp.float32), dict(seq_axis="seq"), "seq"),
    (_CELL, dict(seq_axis="seq", chunks=4), "seq_chunked"),
    (_CELL, dict(vocab_axis="tensor", chunks=4), _REFUSED["chunks"]),
    (("dv", 64, jnp.float32), dict(vocab_axis="tensor", seq_axis="seq"),
     _REFUSED["seq_axis"]),
], ids=lambda v: v if isinstance(v, str) and " " not in v else None)
def test_the_rule_from_what_a_call_shows(monkeypatch, head, kw, path):
    """Every outcome of ``head_path`` on a backend that says "tpu" (the
    kernels are the last thing asked: an axis or chunks come first), and
    the two combinations it refuses, by the words the CLIs' tests match."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if path in _REFUSED.values():
        with pytest.raises(NotImplementedError, match=path):
            X.head_path(*head, **kw)
    else:
        assert X.head_path(*head, **kw) == path
    with pytest.raises(ValueError, match="layout"):
        X.head_path("dd", *head[1:], **kw)


def test_the_checkpoint_resolver_asks_the_rule(monkeypatch):
    """``remat.resolve_for`` sizes the loss head from ``head_path``'s
    answer: make the rule say ``dense`` at cell 1's shapes and every
    rung's predicted peak grows by the float32 logits and their cotangent
    less the kernels' two partial head gradients."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train import remat
    from distributed_lion_tpu.train.loop import TrainConfig

    model = GPT2Config.gpt2_124m(dropout=0.0)
    cfg = TrainConfig(lion=True, per_device_train_batch_size=20,
                      block_size=1024)
    shapes = jax.eval_shape(lambda: gpt2_init(jax.random.key(0), model))
    mesh = make_mesh(data=1, devices=jax.devices()[:1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def peaks():
        return remat.resolve_for(cfg, model, mesh, shapes,
                                 bytes_limit=16 * 2 ** 30).predicted

    asked = []
    real = X.head_path
    monkeypatch.setattr(
        X, "head_path", lambda *a, **k: asked.append((a, k)) or real(*a, **k))
    fused = peaks()
    assert asked == [(_CELL, dict(chunks=0, vocab_axis=None))]
    monkeypatch.setattr(X, "head_path", lambda *a, **k: "dense")
    dense = peaks()
    grown = (remat.head_bytes(model, 20, 1024, fused=False)
             - remat.head_bytes(model, 20, 1024, fused=True))
    assert grown > 3 * 2 ** 30                  # two [20, 1024, 50304] f32
    assert {r: dense[r] - fused[r] for r in fused} == dict.fromkeys(fused,
                                                                    grown)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("valid_v", [0, 321])
def test_on_the_cpu_the_entry_is_the_dense_path_bit_for_bit(dtype, valid_v):
    hidden, head, tokens, _, _ = _case(2, 32, 384, 128, dtype,
                                       valid_v=valid_v)
    mask = _whole_rows_off(2, 32)

    def both(fn):
        (loss, m), g = jax.jit(jax.value_and_grad(
            lambda h, w: fn(h, w, tokens, mask, valid_v), argnums=(0, 1),
            has_aux=True))(hidden, head)
        return [loss, m["accuracy"], m["n_tokens"], *g]

    def entry(h, w, t, m, v):
        return X.clm_head_loss(h, w, t, layout="vd", loss_mask=m, valid_v=v)

    for a, b in zip(both(entry), both(_dense)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_on_a_tpu_backend_the_entry_takes_the_kernels_and_says_so(monkeypatch,
                                                                  tmp_path):
    from distributed_lion_tpu.train import journal

    seen, real = {}, PX.fused_xent

    def fake(h, w, labels, valid_v, tiles, interpret):
        seen.update(rows=h.shape[0], valid_v=valid_v, w=w.dtype)
        return real(h, w, labels, valid_v, TILES, True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PX, "fused_xent", fake)
    hidden, head, tokens, _, _ = _case(2, 64, 333, 128, jnp.bfloat16)
    j = journal.Journal(str(tmp_path))
    journal.install(j)
    try:
        loss, m = X.clm_head_loss(hidden, head, tokens, layout="vd")
        X.clm_head_loss(hidden, head, tokens, layout="vd")  # said once
        events = [r for r in j.records() if r.get("name") == "xent_resolved"]
    finally:
        journal.uninstall(j)
        j.close()
    assert seen == {"rows": 128, "valid_v": 0, "w": jnp.bfloat16}
    loss0, m0 = _dense(hidden, head, tokens, None, 0)
    np.testing.assert_allclose(loss, loss0, rtol=2e-5)
    assert m["n_tokens"] == m0["n_tokens"] == 2 * 63
    assert [(e["impl"], e["rows"], e["vocab"], e["d"], e["dtype"])
            for e in events] == [("pallas_fused_xent", 128, 333, 128,
                                  "bfloat16")]
    # which cut it took: one group of one 128-row block, no row padding
    assert [(e["groups"], e["blocks_per_group"], e["pad_rows"])
            for e in events] == [(1, 1, 0)]
    lines = journal.new_resolved_lines()
    assert any(line.startswith(
        "[setup] cross-entropy: tied head auto -> pallas_fused_xent "
        "(rows 128, vocab 333, d 128, bfloat16, tiles ") for line in lines)
    assert any(line.endswith("1 groups of 1 row blocks, pad rows 0)")
               for line in lines)
    assert journal.new_resolved_lines() == []    # said once


def _trainer(monkeypatch, mesh_kw, **cfg_kw):
    """``Trainer.for_gpt2`` at the tiny preset with the rule's answers
    recorded and the kernel path replaced by a failure."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train import loop

    calls = []
    real = X.head_path
    monkeypatch.setattr(
        X, "head_path",
        lambda *a, **k: calls.append(real(*a, **k)) or calls[-1])
    monkeypatch.setattr(
        X, "_fused_clm_loss_and_metrics",
        lambda *a, **k: pytest.fail("the kernel path was reached"))
    cfg = loop.TrainConfig(lion=True, per_device_train_batch_size=2,
                           gradient_accumulation_steps=1, block_size=32,
                           max_steps=1, **cfg_kw)
    n = int(np.prod(list(mesh_kw.values())))
    t = loop.Trainer.for_gpt2(cfg, make_mesh(devices=jax.devices()[:n],
                                             **mesh_kw), GPT2Config.tiny())
    return t, calls


@pytest.mark.parametrize("mesh_kw,cfg_kw,path", [
    (dict(data=2, tensor=2), dict(tp_vocab=True), "tp_vocab"),
    (dict(data=2, seq=2), dict(), "seq"),
    (dict(data=2), dict(vocab_chunks=4), "chunked"),
    (dict(data=2, seq=2), dict(vocab_chunks=4), "seq_chunked"),
], ids=["tp_vocab", "seq_axis", "vocab_chunks", "seq_axis+vocab_chunks"])
def test_other_head_strategies_never_reach_the_kernels(monkeypatch, mesh_kw,
                                                       cfg_kw, path):
    """``tp_vocab``, a sequence axis and ``vocab_chunks > 0`` keep their own
    heads behind the entry: the rule names them at construction, and one
    train step on a backend that says "tpu" never enters the kernel path."""
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )

    t, calls = _trainer(monkeypatch, mesh_kw, **cfg_kw)
    assert calls == [path]
    blocks = synthetic_lm_dataset(64, 32, 256)
    monkeypatch.setattr(X, "fused_kernel_applies", lambda *a: True)
    t.train(batch_iterator(blocks, t.global_train_batch(), seed=1),
            max_steps=1)
    assert set(calls) == {path}          # and at every trace of the step
    t.close()


def test_the_dense_branch_builds_its_loss_from_the_entry(monkeypatch):
    t, calls = _trainer(monkeypatch, dict(data=2))
    assert calls == ["dense"]                   # the CPU's answer
    t.close()
