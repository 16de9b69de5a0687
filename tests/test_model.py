"""GPT-2 model tests: shapes, causality, dtype discipline, param count."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_lion_tpu.models.gpt2 import GPT2Config, count_params, gpt2_apply, gpt2_init
from distributed_lion_tpu.models.loss import clm_loss_and_metrics

# the forward pass as ONE compiled program a shape (ISSUE 35): eagerly it is
# a few hundred one-op programs
apply = jax.jit(gpt2_apply, static_argnums=2)


def test_forward_shapes_and_dtype():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = apply(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32  # f32 logits out of bf16 compute


def test_causality():
    """Changing a future token must not affect past logits."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 12)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % cfg.vocab_size
    l1 = apply(params, jnp.asarray(toks), cfg)
    l2 = apply(params, jnp.asarray(toks2), cfg)
    np.testing.assert_array_equal(np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]))
    assert not np.array_equal(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


def test_param_count_124m():
    cfg = GPT2Config.gpt2_124m()
    shapes = jax.eval_shape(lambda k: gpt2_init(k, cfg), jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 124_000_000 < n < 125_000_000  # GPT-2 small, tied embeddings


def test_loss_and_accuracy():
    logits = jnp.zeros((1, 4, 10))
    # make position 0 predict the label at position 1 perfectly
    logits = logits.at[0, 0, 7].set(100.0)
    tokens = jnp.asarray([[1, 7, 2, 3]], jnp.int32)
    loss, m = clm_loss_and_metrics(logits, tokens)
    assert float(m["accuracy"]) >= 1 / 3  # 1 of 3 shifted positions correct
    assert float(m["n_tokens"]) == 3.0
    # uniform logits → loss ≈ ln(10) on the other positions
    assert 0.0 < float(loss) < np.log(10) + 0.1


def test_loss_mask():
    logits = jnp.zeros((1, 4, 10))
    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    mask = jnp.asarray([[0, 0, 1, 1]], jnp.float32)  # only last two labels count
    _, m = clm_loss_and_metrics(logits, tokens, mask)
    assert float(m["n_tokens"]) == 2.0


def test_dropout_changes_output_only_with_key():
    cfg = GPT2Config.tiny(dropout=0.5)
    params = gpt2_init(jax.random.key(0), cfg)
    toks = jnp.ones((1, 8), jnp.int32)
    a = apply(params, toks, cfg, dropout_key=jax.random.key(1))
    b = apply(params, toks, cfg, dropout_key=jax.random.key(2))
    c = apply(params, toks, cfg)  # deterministic (eval) path
    d = apply(params, toks, cfg)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(d))


def test_remat_policy_dots_matches_full():
    """remat_policy is a perf knob, not a numerics knob: same loss, same
    grads as the full-recompute policy."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init

    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)), jnp.int32)

    def loss_for(policy):
        cfg = GPT2Config.tiny(remat=True, remat_policy=policy)
        params = gpt2_init(jax.random.key(0), cfg)

        def loss(p):
            logits = gpt2_apply(p, toks, cfg)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

        # Eager on purpose (ISSUE 35): compiled whole, the two policies fuse
        # differently around bf16 roundings and 357 of 4096 entries of one
        # grad leaf leave the tolerance below (max abs 3.8e-6).
        return jax.value_and_grad(loss)(params)

    l_full, g_full = loss_for("full")
    l_dots, g_dots = loss_for("dots")
    np.testing.assert_allclose(float(l_full), float(l_dots), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_dots)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
