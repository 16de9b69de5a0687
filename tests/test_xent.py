"""Chunked-vocab cross entropy (ops/xent): exact parity with the dense
log_softmax path — values, accuracy metric, AND gradients — plus the
Trainer integration (`--vocab_chunks`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_hidden, gpt2_init
from distributed_lion_tpu.models.loss import clm_loss_and_metrics
from distributed_lion_tpu.ops.xent import chunked_softmax_xent, clm_head_loss


@pytest.mark.parametrize("n_chunks,v", [
    (1, 101), (3, 101), (8, 101),
    (7, 10),   # padding spills across several chunks; some chunks all-pad
    (16, 17),  # nearly every chunk is padding
])
def test_xent_matches_dense(n_chunks, v):
    rng = np.random.default_rng(0)
    n, d = 17, 16
    hidden = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, n), jnp.int32)

    nll, correct = chunked_softmax_xent(hidden, emb, labels, n_chunks)
    logits = hidden @ emb.T
    ref_nll = -jax.nn.log_softmax(logits)[jnp.arange(n), labels]
    np.testing.assert_allclose(np.asarray(nll), np.asarray(ref_nll),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(correct),
                                  np.asarray(logits.argmax(-1) == labels))


def test_xent_grads_match_dense():
    rng = np.random.default_rng(1)
    n, d, v = 11, 8, 37
    hidden = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, n), jnp.int32)

    def chunked(h, e):
        return chunked_softmax_xent(h, e, labels, 4)[0].mean()

    def dense(h, e):
        return (-jax.nn.log_softmax(h @ e.T)[jnp.arange(n), labels]).mean()

    gh1, ge1 = jax.grad(chunked, argnums=(0, 1))(hidden, emb)
    gh2, ge2 = jax.grad(dense, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ge1), np.asarray(ge2), rtol=1e-4, atol=1e-5)


def test_chunked_clm_matches_dense_loss():
    model = GPT2Config.tiny(compute_dtype=jnp.float32)
    params = gpt2_init(jax.random.key(0), model)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, model.vocab_size, (2, 24)), jnp.int32)
    hidden, _ = gpt2_hidden(params, tokens, model)
    loss_c, m_c = clm_head_loss(hidden, params["wte"], tokens, layout="vd",
                                chunks=4)
    loss_d, m_d = clm_loss_and_metrics(gpt2_apply(params, tokens, model), tokens)
    np.testing.assert_allclose(float(loss_c), float(loss_d), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(m_c["accuracy"]), float(m_d["accuracy"]),
                               rtol=1e-6, atol=1e-6)


def test_trainer_vocab_chunks_matches_dense():
    """5 training steps with --vocab_chunks ≡ the dense-loss run (f32)."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    model = GPT2Config.tiny(compute_dtype=jnp.float32)
    mesh = make_mesh(data=8)

    def run(vocab_chunks):
        cfg = TrainConfig(
            lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
            max_steps=5, per_device_train_batch_size=2,
            gradient_accumulation_steps=1, block_size=32, logging_steps=1,
            output_dir=None, vocab_chunks=vocab_chunks,
        )
        t = Trainer.for_gpt2(cfg, mesh, model, seed=3)
        blocks = synthetic_lm_dataset(max(64, t.global_train_batch() * 2), 32,
                                      model.vocab_size, seed=7)
        hist = t.train(batch_iterator(blocks, t.global_train_batch(), seed=0))
        losses = [h["loss"] for h in hist if "loss" in h]
        params = jax.tree.map(np.asarray, jax.device_get(t.params))
        t.close()
        return losses, params

    losses_d, params_d = run(0)
    losses_c, params_c = run(4)
    np.testing.assert_allclose(losses_c, losses_d, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(params_d), jax.tree.leaves(params_c)):
        assert np.abs(a - b).max() <= 2 * 1e-3 * 5 + 1e-6  # ballot-flip envelope


def test_llama_chunked_matches_dense():
    """llama_hidden + chunked xent == llama_apply + dense loss (untied head,
    lm_head [d, V] transposed into the emb contract)."""
    from distributed_lion_tpu.models.llama import (
        LlamaConfig, llama_apply, llama_hidden, llama_init,
    )

    model = LlamaConfig.tiny(compute_dtype=jnp.float32)
    params = llama_init(jax.random.key(0), model)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, model.vocab_size, (2, 24)), jnp.int32)
    hidden = llama_hidden(params, tokens, model)
    loss_c, m_c = clm_head_loss(hidden, params["lm_head"], tokens,
                                layout="dv", chunks=4)
    loss_d, m_d = clm_loss_and_metrics(llama_apply(params, tokens, model), tokens)
    np.testing.assert_allclose(float(loss_c), float(loss_d), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(m_c["accuracy"]), float(m_d["accuracy"]),
                               rtol=1e-6, atol=1e-6)


def test_chunked_seq_parallel_matches_dense_seq_loss():
    """chunked_clm_loss_seq_parallel == clm_loss_seq_parallel (values,
    metrics, AND grads) under a 4-way seq mesh — the long-context x
    huge-vocab composition (round 3)."""
    from _sharded import run_sharded
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_lion_tpu.models.llama import (
        LlamaConfig, llama_apply, llama_hidden, llama_init,
    )
    from distributed_lion_tpu.models.loss import clm_loss_seq_parallel
    from distributed_lion_tpu.ops.xent import chunked_clm_loss_seq_parallel

    model = LlamaConfig.tiny(compute_dtype=jnp.float32)
    params = llama_init(jax.random.key(0), model)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, model.vocab_size, (2, 64)),
        jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))

    def dense(params, tokens):
        logits = llama_apply(params, tokens, model, seq_axis="seq")
        loss, m = clm_loss_seq_parallel(logits, tokens, "seq")
        return loss, m

    def chunked(params, tokens):
        hidden = llama_hidden(params, tokens, model, seq_axis="seq")
        loss, m = chunked_clm_loss_seq_parallel(
            hidden, params["lm_head"], tokens, 4, "seq", emb_layout="dv")
        return loss, m

    def run(fn):
        def body(params, tokens):
            (loss, m), g = jax.value_and_grad(
                lambda p, t: fn(p, t), has_aux=True)(params, tokens)
            # the train loop's seq-axis grad reduction
            g = jax.lax.psum(g, "seq")
            return m["loss"], m["accuracy"], g

        out = run_sharded(
            body, mesh, (P(), P(None, "seq")), (P(), P(), P()),
            params, tokens, check_vma=False)
        return jax.tree.map(np.asarray, jax.device_get(out))

    loss_d, acc_d, g_d = run(dense)
    loss_c, acc_c, g_c = run(chunked)
    np.testing.assert_allclose(loss_c, loss_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(acc_c, acc_d, rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_c)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# ------------------------------------------------- the one entry, bit for bit
def _gpt2_family():
    """A ``vd`` head with padding rows: vocab 250 in a table of 256."""
    model = GPT2Config.tiny(n_layer=1, d_model=32, vocab_size=250,
                            vocab_pad_multiple=64, dropout=0.0,
                            compute_dtype=jnp.float32)
    # the head is what is compared: no block between embedding and ln_f
    params = dict(gpt2_init(jax.random.key(0), model), blocks=[])
    return dict(
        params=params, vocab=model.vocab_size,
        head_key="wte", layout="vd", valid_v=model.vocab_size,
        hidden=lambda p, t, **ax: gpt2_hidden(p, t, model, **ax)[0],
        logits=lambda p, t, **ax: gpt2_apply(p, t, model, **ax),
        head=lambda p: p["wte"], head_dv=lambda p: p["wte"].T,
        shard=lambda ax: jax.sharding.PartitionSpec(ax))


def _llama_family():
    from distributed_lion_tpu.models.llama import (
        LlamaConfig, llama_apply, llama_hidden, llama_init,
    )

    model = LlamaConfig.tiny(n_layer=1, d_model=32, d_ff=64,
                             compute_dtype=jnp.float32)
    params = dict(llama_init(jax.random.key(0), model), blocks=[])
    return dict(
        params=params, vocab=model.vocab_size,
        head_key="lm_head", layout="dv", valid_v=0,
        # only the lm_head is sharded under a vocab axis, not the embedding
        hidden=lambda p, t, vocab_axis=None, **ax: llama_hidden(
            p, t, model, **ax),
        logits=lambda p, t, **ax: llama_apply(p, t, model, **ax),
        head=lambda p: p["lm_head"], head_dv=lambda p: p["lm_head"],
        shard=lambda ax: jax.sharding.PartitionSpec(None, ax))


@pytest.mark.parametrize("family", [_gpt2_family, _llama_family],
                         ids=["vd_padded", "dv"])
@pytest.mark.parametrize("path", ["dense", "chunked", "tp_vocab", "seq",
                                  "seq_chunked"])
def test_the_entry_is_each_head_as_it_was_composed(path, family):
    """``clm_head_loss`` against the composition each caller wrote out
    before there was one entry (``*_apply`` and models/loss for the dense
    and the sequence head; the family's hidden states, the ops/xent
    implementation and the shift-by-one masked mean for chunks and a vocab
    shard): loss, metrics and gradients equal bit for bit, a ``vd`` head
    with padding rows and a ``dv`` head, each side compiled as its own
    program."""
    from _sharded import run_sharded
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_lion_tpu.models.loss import clm_loss_seq_parallel
    from distributed_lion_tpu.ops import xent as X

    f = family()
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, f["vocab"], (2, 32)), jnp.int32)
    chunks = 4 if "chunked" in path else 0
    axis = {"tp_vocab": "tensor", "seq": "seq", "seq_chunked": "seq"}.get(path)
    vocab_axis = axis if path == "tp_vocab" else None
    seq_axis = axis if path.startswith("seq") else None
    kw = dict(valid_v=f["valid_v"])

    def entry(p, t):
        assert X.head_path(f["layout"], 32, jnp.float32, chunks=chunks,
                           vocab_axis=vocab_axis, seq_axis=seq_axis) == path
        ax = {k: v for k, v in (("vocab_axis", vocab_axis),
                                ("seq_axis", seq_axis)) if v}
        return X.clm_head_loss(
            f["hidden"](p, t, **ax), f["head"](p), t, layout=f["layout"],
            chunks=chunks, vocab_axis=vocab_axis, seq_axis=seq_axis, **kw)

    def shifted(xent_fn, hidden, t):
        b, n, d = hidden.shape
        labels = t[:, 1:].reshape(-1).astype(jnp.int32)
        nll, correct = xent_fn(hidden[:, :-1].reshape(b * (n - 1), d), labels)
        mask = jnp.ones_like(nll)
        count = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / count
        acc = (correct.astype(jnp.float32) * mask).sum() / count
        return loss, {"loss": loss, "accuracy": acc, "n_tokens": mask.sum()}

    def composed(p, t):
        if path == "dense":
            return clm_loss_and_metrics(f["logits"](p, t), t)
        if path == "seq":
            return clm_loss_seq_parallel(
                f["logits"](p, t, seq_axis="seq"), t, "seq")
        if path == "chunked":
            return shifted(lambda h, lab: chunked_softmax_xent(
                h, f["head"](p), lab, 4, f["layout"], f["valid_v"]),
                f["hidden"](p, t), t)
        if path == "seq_chunked":
            return X.chunked_clm_loss_seq_parallel(
                f["hidden"](p, t, seq_axis="seq"), f["head"](p), t, 4, "seq",
                emb_layout=f["layout"], **kw)
        return shifted(lambda h, lab: X.tp_vocab_xent(
            h, f["head_dv"](p), lab, "tensor", f["valid_v"]),
            f["hidden"](p, t, vocab_axis="tensor"), t)

    def run(fn):
        def body(p, t):
            (loss, m), g = jax.value_and_grad(fn, has_aux=True)(p, t)
            return loss, m, g

        if axis is None:
            return jax.device_get(jax.jit(body)(f["params"], tokens))
        # a vocab axis shards the head alone; a sequence axis the tokens,
        # and the loss is then each shard's own (as are its gradients)
        pspec = jax.tree.map(lambda _: P(), f["params"])
        if vocab_axis:
            pspec[f["head_key"]] = f["shard"]("tensor")
        each = P(axis) if seq_axis else P()
        out = run_sharded(
            lambda p, t: jax.tree.map(
                lambda x: x[None] if seq_axis else x, body(p, t)),
            Mesh(np.array(jax.devices()[:2]), (axis,)),
            (pspec, P(None, seq_axis)),
            (each, each, jax.tree.map(lambda s: P(axis, *s), pspec)
             if seq_axis else pspec),
            f["params"], tokens, check_vma=False)
        return jax.device_get(out)

    got, want = run(entry), run(composed)
    assert float(np.ravel(want[0])[0]) > 1.0           # a loss was computed
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(a, b)
