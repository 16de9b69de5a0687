"""MXU-aligned vocab padding (models/gpt2 ``vocab_pad_multiple``).

Padding the embedding table is a pure LAYOUT choice: the pad rows/columns
must be invisible to every consumer — dense loss, chunked CE, tp_vocab CE,
generation — and must receive zero loss gradient so local Lion leaves them
at exactly their zero init. These tests pin that equivalence against the
unpadded model bit-for-bit where the math allows it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.models.gpt2 import (
    GPT2Config,
    gpt2_apply,
    gpt2_decode,
    gpt2_init,
    gpt2_init_cache,
)
from distributed_lion_tpu.models.loss import clm_loss_and_metrics
from distributed_lion_tpu.ops.xent import chunked_softmax_xent, clm_head_loss

V, PAD_M = 250, 64  # padded_vocab = 256

# Forward passes run COMPILED, one program a shape (ISSUE 35): eagerly each is
# a few hundred one-op programs.
apply = jax.jit(gpt2_apply, static_argnums=2)
decode = jax.jit(gpt2_decode, static_argnums=2)


def _cfgs():
    plain = GPT2Config.tiny(vocab_size=V)
    padded = GPT2Config.tiny(vocab_size=V, vocab_pad_multiple=PAD_M)
    return plain, padded


def test_padded_vocab_property():
    plain, padded = _cfgs()
    assert plain.padded_vocab == V
    assert padded.padded_vocab == 256
    assert GPT2Config.tiny(vocab_size=256,
                           vocab_pad_multiple=64).padded_vocab == 256
    with pytest.raises(ValueError):
        GPT2Config.tiny(vocab_pad_multiple=-1)


def test_init_pads_with_zero_rows_same_draw():
    plain, padded = _cfgs()
    key = jax.random.key(7)
    p0, p1 = gpt2_init(key, plain), gpt2_init(key, padded)
    assert p1["wte"].shape == (256, plain.d_model)
    np.testing.assert_array_equal(p0["wte"], p1["wte"][:V])
    np.testing.assert_array_equal(p1["wte"][V:], 0.0)


def test_apply_logits_exact_vs_unpadded():
    plain, padded = _cfgs()
    key = jax.random.key(7)
    p0, p1 = gpt2_init(key, plain), gpt2_init(key, padded)
    tok = jax.random.randint(jax.random.key(1), (2, 16), 0, V)
    l0 = apply(p0, tok, plain)
    l1 = apply(p1, tok, padded)
    assert l1.shape == l0.shape == (2, 16, V)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=1e-5)


def test_pad_rows_do_not_leak_even_when_nonzero():
    """Vote-Lion's tie→−1 walks zero-grad rows; junk pad values must stay
    invisible to logits/loss (they are sliced/masked, not trusted-zero)."""
    _, padded = _cfgs()
    p = gpt2_init(jax.random.key(7), padded)
    junk = p["wte"].at[V:].set(37.0)
    tok = jax.random.randint(jax.random.key(1), (2, 16), 0, V)
    l_clean = apply(p, tok, padded)
    l_junk = apply({**p, "wte": junk}, tok, padded)
    np.testing.assert_array_equal(np.asarray(l_clean), np.asarray(l_junk))
    loss_c, _ = clm_head_loss(
        jax.random.normal(jax.random.key(2), (2, 16, padded.d_model)),
        junk, tok, layout="vd", chunks=4, valid_v=V)
    loss_u, _ = clm_head_loss(
        jax.random.normal(jax.random.key(2), (2, 16, padded.d_model)),
        junk[:V], tok, layout="vd", chunks=4)
    np.testing.assert_allclose(float(loss_c), float(loss_u), atol=1e-6)


def test_chunked_xent_valid_v_matches_dense_and_zero_pad_grad():
    d, n = 32, 12
    key = jax.random.key(3)
    hidden = jax.random.normal(key, (n, d))
    emb = jax.random.normal(jax.random.key(4), (256, d))
    emb = emb.at[V:].set(0.0)
    labels = jax.random.randint(jax.random.key(5), (n,), 0, V)

    def loss_pad(e):
        nll, _ = chunked_softmax_xent(hidden, e, labels, n_chunks=4, valid_v=V)
        return nll.mean()

    def loss_dense(e):
        logp = jax.nn.log_softmax(hidden @ e[:V].T, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

    np.testing.assert_allclose(float(loss_pad(emb)), float(loss_dense(emb)),
                               rtol=1e-6)
    g_pad = jax.jit(jax.grad(loss_pad))(emb)
    g_dense = jax.jit(jax.grad(loss_dense))(emb)
    np.testing.assert_array_equal(np.asarray(g_pad[V:]), 0.0)
    np.testing.assert_allclose(np.asarray(g_pad[:V]), np.asarray(g_dense[:V]),
                               atol=1e-5)


def test_chunked_xent_whole_chunk_masked():
    # pad spans entire chunks: v=256 over 8 chunks of 32, valid 100 → chunks
    # 4..7 fully masked; the -inf carry guards must hold
    d, n = 16, 6
    hidden = jax.random.normal(jax.random.key(0), (n, d))
    emb = jax.random.normal(jax.random.key(1), (256, d))
    labels = jnp.arange(n, dtype=jnp.int32)
    nll, correct = chunked_softmax_xent(hidden, emb, labels, n_chunks=8,
                                        valid_v=100)
    logp = jax.nn.log_softmax(hidden @ emb[:100].T, axis=-1)
    ref = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
    np.testing.assert_allclose(np.asarray(nll), np.asarray(ref), rtol=1e-5)
    assert np.isfinite(np.asarray(nll)).all()


def test_decode_matches_apply_with_padding():
    _, padded = _cfgs()
    p = gpt2_init(jax.random.key(7), padded)
    tok = jax.random.randint(jax.random.key(1), (1, 12), 0, V)
    full = apply(p, tok, padded)
    cache = gpt2_init_cache(padded, 1, 12)
    dec, _ = decode(p, tok, padded, cache, 0)
    assert dec.shape[-1] == V
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), atol=2e-4)


def test_trainer_padded_equals_unpadded_trajectory():
    """Full vote-Lion training on the dp mesh: padded and unpadded configs
    produce the same loss stream (chunked CE path, the flagship's)."""
    import dataclasses

    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.parallel import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    plain, padded = _cfgs()
    plain = dataclasses.replace(plain, remat=False, n_ctx=32)
    padded = dataclasses.replace(padded, remat=False, n_ctx=32)
    mesh = make_mesh(data=8)
    losses = {}
    for name, mc in (("plain", plain), ("padded", padded)):
        cfg = TrainConfig(
            lion=True, async_grad=True, learning_rate=1e-3, weight_decay=0.1,
            warmup_steps=0, max_steps=8, per_device_train_batch_size=1,
            gradient_accumulation_steps=1, block_size=32,
            logging_steps=1, eval_steps=1000, save_steps=1000,
            output_dir=None, vocab_chunks=4, seed=11,
        )
        trainer = Trainer.for_gpt2(cfg, mesh, mc, seed=11)
        blocks = synthetic_lm_dataset(128, 32, V, seed=0)
        it = batch_iterator(blocks, trainer.global_train_batch(), seed=0)
        history = trainer.train(it, max_steps=8)
        losses[name] = [h["loss"] for h in history if "loss" in h]
        trainer.close()
    # step 1 (pre-update) pins exact masking: an unmasked pad column would
    # shift the lse by ~log(256/250) ≈ 0.024. Later steps tolerate the fp
    # noise Lion's sign amplifies (chunk boundaries differ: ceil(250/4) vs
    # 256/4) but stay well under that bug-sized shift.
    np.testing.assert_allclose(losses["plain"][0], losses["padded"][0],
                               atol=1e-5)
    np.testing.assert_allclose(losses["plain"], losses["padded"], atol=8e-3)


def test_hf_export_slices_pad_rows(tmp_path):
    """gpt2_to_hf writes the TRUE-vocab table: the MXU pad rows never leak
    into the HF checkpoint (which must round-trip into transformers)."""
    from distributed_lion_tpu.models.hf_export import gpt2_to_hf

    _, padded = _cfgs()
    p = gpt2_init(jax.random.key(7), padded)
    out = str(tmp_path / "export")
    gpt2_to_hf(p, padded, out)
    import json
    import os

    import numpy as _np

    from safetensors.numpy import load_file

    sd = load_file(os.path.join(out, "model.safetensors"))
    assert sd["transformer.wte.weight"].shape[0] == V
    with open(os.path.join(out, "config.json")) as f:
        assert json.load(f)["vocab_size"] == V
    _np.testing.assert_array_equal(sd["transformer.wte.weight"],
                                   _np.asarray(p["wte"][:V], _np.float32))
