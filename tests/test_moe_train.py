"""Trainable MoE (VERDICT r1 item 5): GPT-2 with Switch-MoE FFN blocks,
vote-Lion training over dp and dp x ep meshes.

Pins: loss decreases on the 8-device mesh with --moe_experts; expert
parallelism (dispatch/return all_to_all + expert-sharded grads + the
expert-axis grad psum for dense leaves) trains and keeps replicas
consistent; ep=1 and ep=4 agree on the forward loss.
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init
from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train.loop import TrainConfig, Trainer

MODEL = GPT2Config.tiny(n_layer=4, moe_experts=4)


def _cfg(**kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=3e-3, warmup_steps=2,
        max_steps=30, per_device_train_batch_size=2,
        gradient_accumulation_steps=1, block_size=32, logging_steps=5,
        output_dir=None, seed=7,
    )
    base.update(kw)
    return TrainConfig(**base)


def test_moe_init_structure():
    params = gpt2_init(jax.random.key(0), MODEL)
    moe_blocks = [i for i, b in enumerate(params["blocks"]) if "moe" in b]
    assert moe_blocks == [1, 3]  # every 2nd block (moe_every=2)
    assert params["blocks"][1]["moe"]["w_in"].shape == (4, 64, 256)


def test_moe_loss_decreases_dp():
    """run_clm semantics: --moe_experts 4 on a pure-dp 8-device mesh."""
    mesh = make_mesh(data=8)
    trainer = Trainer.for_gpt2(_cfg(), mesh, MODEL, seed=1)
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  MODEL.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(), seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.3, losses
    assert all(np.isfinite(h.get("aux_loss", 1.0)) for h in hist)
    trainer.close()


def test_moe_expert_parallel_trains():
    """dp=2 x ep=4: expert banks sharded, tokens over both axes."""
    mesh = make_mesh(data=2, expert=4)
    trainer = Trainer.for_gpt2(_cfg(max_steps=20), mesh, MODEL, seed=1)
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  MODEL.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(), seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.2, losses
    # dense params replicated across ALL devices must agree bit-for-bit
    wte = trainer.params["wte"]
    shards = [np.asarray(s.data) for s in wte.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    trainer.close()


def test_moe_ep_forward_matches_ep1():
    """Same params, same rows: the ep=4 sharded forward loss must equal the
    single-device forward (routing/drops are identical — capacity is computed
    per LOCAL token count, so use equal local counts)."""
    from jax import shard_map

    from distributed_lion_tpu.models.loss import clm_loss_sharded_rows

    mesh = make_mesh(data=2, expert=4)
    params = gpt2_init(jax.random.key(0), MODEL)
    specs = None
    from distributed_lion_tpu.models.gpt2 import gpt2_moe_param_specs

    specs = gpt2_moe_param_specs(MODEL)
    rows = 16  # 2 per (data, expert) shard
    tokens = np.random.default_rng(0).integers(
        0, MODEL.vocab_size, size=(rows, 32)).astype(np.int32)

    @jax.jit
    def sharded_loss(params, tokens):
        def body(p, t):
            loss_local, m = clm_loss_sharded_rows(
                gpt2_apply(p, t, MODEL, expert_axis="expert", return_aux=True)[0],
                t, "expert")
            return jax.lax.pmean(m["loss"], "data")

        return shard_map(
            body, mesh=mesh, in_specs=(specs, P(("data", "expert"))),
            out_specs=P(), check_vma=False,
        )(params, tokens)

    got = float(sharded_loss(params, tokens))

    # reference: per-2-row groups through the single-device moe (same local
    # capacity as each (data, expert) shard saw), loss = token-weighted mean
    from distributed_lion_tpu.models.loss import clm_loss_and_metrics

    @jax.jit
    def group_loss(params, pair):
        logits = gpt2_apply(params, pair, MODEL, return_aux=True)[0]
        return clm_loss_and_metrics(logits, pair)[0]

    losses = [float(group_loss(params, tokens[i:i + 2]))
              for i in range(0, rows, 2)]
    ref = float(np.mean(losses))
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_moe_guards():
    mesh = make_mesh(data=2, expert=4)
    with pytest.raises(ValueError, match="divisible"):
        Trainer.for_gpt2(_cfg(), mesh, GPT2Config.tiny(n_layer=4, moe_experts=6))
    with pytest.raises(ValueError, match="expert"):
        Trainer.for_gpt2(_cfg(), mesh, GPT2Config.tiny(n_layer=4))  # dense + ep>1


def test_moe_decode_matches_apply():
    """The export->generate cycle works for MoE checkpoints: cached decode
    logits match the full forward position-for-position. Decode never drops
    tokens (capacity_override = per-call token count), so compare against a
    capacity_factor high enough that the full forward doesn't drop either —
    where both paths keep every token, they must agree."""
    from distributed_lion_tpu.models.gpt2 import gpt2_decode, gpt2_init_cache

    model = GPT2Config.tiny(n_layer=4, moe_experts=4, moe_capacity_factor=4.0)
    params = gpt2_init(jax.random.key(2), model)
    tokens = np.random.default_rng(1).integers(
        0, model.vocab_size, size=(2, 12)).astype(np.int32)
    # each as ONE compiled program (ISSUE 35), not op by op
    full = jax.jit(lambda p, t: gpt2_apply(p, t, model, return_aux=True)[0])(
        params, tokens)
    cache = gpt2_init_cache(model, 2, 16)
    dec, _ = jax.jit(lambda p, t, c: gpt2_decode(p, t, model, c, 0))(
        params, tokens, cache)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


def test_moe_ep_tp_trajectory_matches_ep():
    """dp=2 x ep=2 x tp=2 ≡ dp=2 x ep=2: adding the tensor axis —
    Megatron-split attention AND per-expert FFNs (w_in column / w_out row,
    b_out added after the row psum) — is a pure re-schedule on top of the
    ep mesh: identical routing groups, identical voters. (ep itself is NOT
    trajectory-equal to pure dp: row sharding changes the voter grouping —
    its semantics are pinned by the forward-equality and convergence tests
    above.) f32 compute so the vote's sign threshold sees no reordering
    noise."""
    import dataclasses

    model_f32 = dataclasses.replace(MODEL, compute_dtype=np.float32,
                                    moe_experts=2)

    def run(mesh, **cfg_kw):
        cfg = _cfg(learning_rate=1e-3, max_steps=5, logging_steps=1, **cfg_kw)
        trainer = Trainer.for_gpt2(cfg, mesh, model_f32, seed=123)
        blocks = synthetic_lm_dataset(
            max(64, trainer.global_train_batch() * 2), 32,
            model_f32.vocab_size, seed=11)
        hist = trainer.train(
            batch_iterator(blocks, trainer.global_train_batch(), seed=0),
            max_steps=5)
        params = jax.tree.map(np.asarray, jax.device_get(trainer.params))
        trainer.close()
        return [h["loss"] for h in hist if "loss" in h], params

    losses_ep, params_ep = run(
        make_mesh(data=2, expert=2, devices=jax.devices()[:4]),
        expert_parallel=2)
    losses_x, params_x = run(make_mesh(data=2, expert=2, tensor=2),
                             expert_parallel=2, tensor_parallel=2)
    np.testing.assert_allclose(losses_x, losses_ep, rtol=1e-4, atol=1e-4)
    envelope = 2 * 1e-3 * 5
    for a, b in zip(jax.tree.leaves(params_ep), jax.tree.leaves(params_x)):
        assert np.abs(a.astype(np.float64) - b.astype(np.float64)).max() \
            <= envelope


def test_moe_tp_only_trains():
    """ep=1 with tp=2: the tensor split applies without an expert axis."""
    mesh = make_mesh(data=4, tensor=2)
    trainer = Trainer.for_gpt2(_cfg(max_steps=20, tensor_parallel=2),
                               mesh, MODEL, seed=1)
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  MODEL.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(),
                                        seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.3, losses
    trainer.close()
