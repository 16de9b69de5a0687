"""The plain reference against the program at a tiny size on the CPU: it
shares no code with the package, so agreement here is what lets the chip
runs use it as the yardstick."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import gpt2 as gpt2_program
from benchmark.reference import gpt2 as ref

CFG = {"vocab_size": 256, "n_positions": 128, "n_embd": 64, "n_layer": 2,
       "n_head": 4, "layer_norm_epsilon": 1e-5}
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda k: ref.init_weights(k, CFG, jnp.float32))(ref.seed_key(SEED))


def test_forward_matches_gpt2_apply_in_float32(weights):
    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply

    cfg = GPT2Config(**gpt2_program.config_kwargs(CFG),
                     compute_dtype=jnp.float32, remat=False)
    tokens = np.random.default_rng(0).integers(0, 256, (3, 48), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = gpt2_apply(gpt2_program.to_program(weights), tokens, cfg)
    want = ref.forward(weights, tokens, CFG)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # tolerance: both are float32 at 'highest'; the two differ only in
    # summation order. A bfloat16 forward pass is 100x further away:
    low = ref.forward(weights, tokens, CFG, quant="bf16")
    assert float(jnp.abs(low - want).max()) > 2e-4


def test_program_layout_roundtrip(weights):
    tree = gpt2_program.to_program(weights)
    leaves = gpt2_program.program_leaves(tree)
    assert len(leaves) == 4 + 12 * CFG["n_layer"]
    assert leaves[("c_attn_w", 1)].shape == (64, 3, 64)
    norms = jax.device_get(gpt2_program.reference_leaf_norms(weights))
    for key, leaf in leaves.items():
        assert float(jnp.linalg.norm(leaf.reshape(-1))) == pytest.approx(
            float(norms[key]), rel=1e-5)


def test_seeded_init_is_gpt2s(weights):
    again = jax.jit(lambda k: ref.init_weights(k, CFG, jnp.float32))(ref.seed_key(SEED))
    assert all(np.array_equal(weights[k], again[k]) for k in weights)
    other = jax.jit(lambda k: ref.init_weights(k, CFG, jnp.float32))(ref.seed_key(SEED + 1))
    assert not np.array_equal(weights["wte"], other["wte"])
    assert float(weights["wte"].std()) == pytest.approx(0.02, rel=0.05)
    assert float(weights["mlp_proj_w"].std()) == pytest.approx(
        0.02 / 2.0, rel=0.05)                     # 1/sqrt(2 * 2 layers)
    assert np.all(weights["ln_1_g"] == 1) and np.all(weights["c_fc_b"] == 0)


def test_vote_lion_majority_with_ties_electing_minus_one():
    w = {"p": jnp.zeros((4,))}
    zeros = {"p": jnp.zeros((4,))}
    # four workers' gradients per coordinate: 4-0, 3-1, 2-2 (a tie), 0-4
    g = [jnp.array(x, jnp.float32) for x in
         ([1, 1, 1, -1], [1, 1, 1, -1], [1, 1, -1, -1], [1, -1, -1, -1])]
    new_w, new_m = ref.vote_lion_step(w, [zeros] * 4, [{"p": x} for x in g],
                                      lr=0.5, wd=0.0, b1=0.9, b2=0.99)
    np.testing.assert_allclose(new_w["p"], [-0.5, -0.5, 0.5, 0.5])
    np.testing.assert_allclose(new_m[3]["p"], 0.01 * np.array([1, -1, -1, -1]),
                               rtol=1e-5)


def test_lr_schedule_is_the_warmup_cosine():
    lr = [float(ref.cosine_warmup_lr(s, 1e-4, 2000, 100000))
          for s in (0, 1, 2000, 100000)]
    assert lr[0] == 0.0 and lr[1] == pytest.approx(5e-8)
    assert lr[2] == pytest.approx(1e-4) and lr[3] == pytest.approx(0, abs=1e-9)


def test_loss_and_grad_blocks_give_the_whole_batch_mean(weights):
    rows = np.random.default_rng(1).integers(0, 256, (4, 32), dtype=np.int32)
    whole = ref.clm_loss(weights, rows, CFG)
    loss, grads = ref.loss_and_grad(weights, rows, CFG, micro=2)
    assert float(loss) == pytest.approx(float(whole), rel=1e-5)
    direct = jax.grad(ref.clm_loss)(weights, rows, CFG)
    np.testing.assert_allclose(grads["c_fc_w"], direct["c_fc_w"], atol=1e-6)
