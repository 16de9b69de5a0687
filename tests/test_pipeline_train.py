"""Trainable pipeline parallelism (VERDICT r1 item 4): real GPT-2 blocks as
stages, full vote-Lion training over a dp x pp mesh.

The load-bearing invariant: pipelining is a pure re-schedule — a dp=2 x pp=4
run must produce the same losses/params as the dp=2 run with the same global
batch, because every microbatch passes through the same blocks in the same
order; only the device placement changes.
"""

import dataclasses

import jax
import numpy as np
import pytest

from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init
from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train.loop import TrainConfig, Trainer


def _cfg(**kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
        max_steps=5, per_device_train_batch_size=4,
        gradient_accumulation_steps=1, block_size=32, logging_steps=1,
        output_dir=None, seed=7,
    )
    base.update(kw)
    return TrainConfig(**base)


MODEL = GPT2Config.tiny(n_layer=4)


def _train(mesh, cfg, n_steps=5, model=None):
    model = model or MODEL
    trainer = Trainer.for_gpt2(cfg, mesh, model, seed=123)
    blocks = synthetic_lm_dataset(
        max(64, trainer.global_train_batch() * 2), cfg.block_size,
        model.vocab_size, seed=11,
    )
    hist = trainer.train(
        batch_iterator(blocks, trainer.global_train_batch(), seed=0),
        max_steps=n_steps,
    )
    params = jax.tree.map(np.asarray, jax.device_get(trainer.params))
    trainer.close()
    return [h["loss"] for h in hist if "loss" in h], params


def test_pp_forward_matches_sequential():
    """Pipeline forward loss == plain forward loss on identical params."""
    from distributed_lion_tpu.models.gpt2_pipe import (
        make_pipeline_loss,
        pipeline_param_specs,
        pipeline_params,
    )
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    pp = 4
    mesh = make_mesh(data=2, pipe=pp)
    params = gpt2_init(jax.random.key(0), MODEL)
    tokens = np.random.default_rng(0).integers(
        0, MODEL.vocab_size, size=(8, 32)).astype(np.int32)

    from distributed_lion_tpu.models.loss import clm_loss_and_metrics

    logits = jax.jit(gpt2_apply, static_argnums=2)(params, tokens, MODEL)
    ref_loss, _ = clm_loss_and_metrics(logits, tokens)

    loss_fn = make_pipeline_loss(MODEL, n_micro=2)
    pparams = pipeline_params(params, pp)
    pspecs = pipeline_param_specs()

    @jax.jit
    def run(pparams, tokens):
        def body(p, t):
            loss, _ = loss_fn(p, t, None)
            # per-data-shard loss over equal token counts → pmean = global
            return jax.lax.pmean(loss, "data")
        return shard_map(
            body, mesh=mesh, in_specs=(pspecs, P("data")), out_specs=P(),
            check_vma=False,
        )(pparams, tokens)

    got = float(run(pparams, tokens))
    np.testing.assert_allclose(got, float(ref_loss), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "mesh_kw,cfg_kw",
    [
        pytest.param(dict(data=2, pipe=4),
                     dict(pipeline_parallel=4, pipeline_microbatches=2),
                     id="dp2xpp4"),
        pytest.param(dict(data=2, tensor=2, pipe=2),
                     dict(tensor_parallel=2, pipeline_parallel=2,
                          pipeline_microbatches=2),
                     id="dp2xtp2xpp2"),
    ],
)
def test_pipelined_mesh_matches_pure_dp(mesh_kw, cfg_kw):
    """dp×pp — and the classic large-model mesh dp×tp×pp (Megatron
    sharding INSIDE each GPipe stage) — must train identically to pure
    dp=2 at the same global batch/data/seed: both are pure re-schedules.

    Run in f32 compute: pipelining/tp-psum reorder bf16 matmul tiles, and
    the vote's sign threshold amplifies that noise into ±2·lr param flips
    on near-zero ballots — in f32 the reordering noise is below any ballot
    margin, so the schedules must agree to tight tolerance."""
    devs = jax.devices()
    mesh_dp = make_mesh(data=2, devices=devs[:2])
    mesh_x = make_mesh(**mesh_kw)

    model_f32 = dataclasses.replace(MODEL, compute_dtype=jax.numpy.float32)
    losses_dp, params_dp = _train(mesh_dp, _cfg(), n_steps=5, model=model_f32)
    losses_x, params_x = _train(mesh_x, _cfg(**cfg_kw), n_steps=5,
                                model=model_f32)

    np.testing.assert_allclose(losses_x, losses_dp, rtol=1e-4, atol=1e-4)
    # Param comparison, modulo sign-of-zero ballots: coordinates whose
    # gradient is EXACTLY zero by symmetry (e.g. k-bias under softmax shift
    # invariance) vote on the sign of fp noise, which any schedule change
    # may flip — each flip moves a param by ±2·lr. So: every coordinate must
    # be within the 5-step ballot-flip envelope, and the flipped fraction
    # must be small (the informative coordinates agree exactly).
    from distributed_lion_tpu.models.gpt2_pipe import unpipeline_params

    restored = unpipeline_params(params_x, MODEL.n_layer)
    total = mismatched = 0
    envelope = 2 * 1e-3 * 5  # 2·lr·n_steps
    for a, b in zip(jax.tree.leaves(params_dp), jax.tree.leaves(restored)):
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        assert d.max() <= envelope, d.max()
        mismatched += int((d > 1e-6).sum())
        total += d.size
    assert mismatched / total < 0.02, f"{mismatched}/{total} params flipped"


def test_pp_loss_decreases():
    mesh = make_mesh(data=2, pipe=4)
    cfg = _cfg(pipeline_parallel=4, pipeline_microbatches=4,
               learning_rate=3e-3, max_steps=30)
    trainer = Trainer.for_gpt2(cfg, mesh, MODEL, seed=1)
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  MODEL.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(), seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.3, losses
    trainer.close()


def test_pp_guards():
    mesh = make_mesh(data=2, pipe=4)
    with pytest.raises(ValueError, match="divisible"):
        Trainer.for_gpt2(_cfg(pipeline_parallel=4), mesh,
                         GPT2Config.tiny(n_layer=3))
    with pytest.raises(ValueError, match="dropout"):
        Trainer.for_gpt2(_cfg(pipeline_parallel=4), mesh,
                         dataclasses.replace(MODEL, dropout=0.1))
    with pytest.raises(ValueError, match="not divisible by pipeline_microbatches"):
        Trainer.for_gpt2(_cfg(pipeline_parallel=4, per_device_train_batch_size=3,
                              pipeline_microbatches=2), mesh, MODEL)


def test_tp_pp_loss_decreases():
    mesh = make_mesh(data=2, tensor=2, pipe=2)
    cfg = _cfg(tensor_parallel=2, pipeline_parallel=2,
               pipeline_microbatches=4, learning_rate=3e-3, max_steps=30)
    trainer = Trainer.for_gpt2(cfg, mesh, MODEL, seed=1)
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  MODEL.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(), seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.3, losses
    trainer.close()


def test_pp_chunked_head_matches_dense():
    """pp × vocab_chunks: the chunked last-stage head computes the same
    loss as the dense pipelined head and the sequential model."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.models.gpt2_pipe import (
        make_pipeline_loss,
        pipeline_param_specs,
        pipeline_params,
    )
    from distributed_lion_tpu.models.loss import clm_loss_and_metrics

    pp = 4
    mesh = make_mesh(data=2, pipe=pp)
    params = gpt2_init(jax.random.key(0), MODEL)
    tokens = np.random.default_rng(0).integers(
        0, MODEL.vocab_size, size=(8, 32)).astype(np.int32)
    ref_loss, _ = clm_loss_and_metrics(
        jax.jit(gpt2_apply, static_argnums=2)(params, tokens, MODEL), tokens)

    loss_fn = make_pipeline_loss(MODEL, n_micro=2, vocab_chunks=4)
    pparams = pipeline_params(params, pp)

    @jax.jit
    def run(pparams, tokens):
        def body(p, t):
            loss, _ = loss_fn(p, t, None)
            return jax.lax.pmean(loss, "data")
        return shard_map(
            body, mesh=mesh, in_specs=(pipeline_param_specs(), P("data")),
            out_specs=P(), check_vma=False,
        )(pparams, tokens)

    got = float(run(pparams, tokens))
    np.testing.assert_allclose(got, float(ref_loss), rtol=2e-5, atol=2e-5)


def test_tp_pp_chunked_trains():
    """The full composition dp×tp×pp×vocab_chunks runs and learns."""
    mesh = make_mesh(data=2, tensor=2, pipe=2)
    cfg = _cfg(tensor_parallel=2, pipeline_parallel=2,
               pipeline_microbatches=2, vocab_chunks=4,
               learning_rate=3e-3, max_steps=30)
    trainer = Trainer.for_gpt2(cfg, mesh, MODEL, seed=1)
    blocks = synthetic_lm_dataset(trainer.global_train_batch() * 2, 32,
                                  MODEL.vocab_size, seed=3)
    hist = trainer.train(batch_iterator(blocks, trainer.global_train_batch(),
                                        seed=0))
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0] - 0.3, losses
    trainer.close()


@pytest.mark.parametrize("chunks", [0, 4], ids=["dense", "chunked"])
def test_sp_pp_chunked_trajectory_matches_dp(chunks):
    """dp=2 x sp=2 x pp=2 (dense AND chunked seq-parallel heads) ≡ dp=2:
    long-context pipelined training — ring attention inside every pipeline
    tick, wpe offset per seq shard, boundary labels via ppermute feeding
    the CE at the last stage."""
    from distributed_lion_tpu.models.gpt2_pipe import unpipeline_params

    model_f32 = dataclasses.replace(MODEL, compute_dtype=jax.numpy.float32)
    losses_dp, params_dp = _train(
        make_mesh(data=2, devices=jax.devices()[:2]),
        _cfg(vocab_chunks=chunks), n_steps=5, model=model_f32)
    losses_sp, params_sp = _train(
        make_mesh(data=2, seq=2, pipe=2),
        _cfg(seq_parallel=2, pipeline_parallel=2, pipeline_microbatches=2,
             vocab_chunks=chunks),
        n_steps=5, model=model_f32)
    np.testing.assert_allclose(losses_sp, losses_dp, rtol=1e-4, atol=1e-4)
    restored = unpipeline_params(params_sp, MODEL.n_layer)
    envelope = 2 * 1e-3 * 5
    total = mismatched = 0
    for a, b in zip(jax.tree.leaves(params_dp), jax.tree.leaves(restored)):
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        assert d.max() <= envelope, d.max()
        mismatched += int((d > 1e-6).sum())
        total += d.size
    assert mismatched / total < 0.02, f"{mismatched}/{total} params flipped"


def test_tp_sp_pp_full_composition_matches_dp():
    """The whole mesh at once — tp=2 x sp=2 x pp=2 (+ chunked CE) ≡ plain
    single-device training: Megatron sharding inside GPipe stages whose
    attention rings over the seq axis, streamed CE at the last stage."""
    from distributed_lion_tpu.models.gpt2_pipe import unpipeline_params

    model_f32 = dataclasses.replace(MODEL, compute_dtype=jax.numpy.float32)
    losses_dp, params_dp = _train(
        make_mesh(data=1, devices=jax.devices()[:1]),
        _cfg(vocab_chunks=4, per_device_train_batch_size=8),
        n_steps=5, model=model_f32)
    losses_x, params_x = _train(
        make_mesh(data=1, tensor=2, seq=2, pipe=2),
        _cfg(tensor_parallel=2, seq_parallel=2, pipeline_parallel=2,
             pipeline_microbatches=2, vocab_chunks=4,
             per_device_train_batch_size=8),
        n_steps=5, model=model_f32)
    np.testing.assert_allclose(losses_x, losses_dp, rtol=1e-4, atol=1e-4)
    restored = unpipeline_params(params_x, MODEL.n_layer)
    envelope = 2 * 1e-3 * 5
    total = mismatched = 0
    for a, b in zip(jax.tree.leaves(params_dp), jax.tree.leaves(restored)):
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        assert d.max() <= envelope, d.max()
        mismatched += int((d > 1e-6).sum())
        total += d.size
    assert mismatched / total < 0.02, f"{mismatched}/{total} params flipped"


def test_checkpoint_resume_exact_under_tp_pp(tmp_path):
    """Train, checkpoint, resume under the dp×tp×pp mesh → params and
    per-worker momentum match a continuous run exactly (Orbax round-trips
    the stacked tp/pipe-sharded stage leaves)."""
    mesh = make_mesh(data=2, tensor=2, pipe=2)
    model_f32 = dataclasses.replace(MODEL, compute_dtype=jax.numpy.float32)
    kw = dict(tensor_parallel=2, pipeline_parallel=2, pipeline_microbatches=2)
    blocks = synthetic_lm_dataset(256, 32, MODEL.vocab_size, seed=0)

    cfg_c = _cfg(max_steps=10, **kw)
    t_cont = Trainer.for_gpt2(cfg_c, mesh, model_f32, seed=5)
    t_cont.train(batch_iterator(blocks, t_cont.global_train_batch(), seed=9),
                 max_steps=10)

    cfg_a = _cfg(max_steps=10, output_dir=str(tmp_path / "run"),
                 save_steps=10**9, **kw)
    t1 = Trainer.for_gpt2(cfg_a, mesh, model_f32, seed=5)
    t1.train(batch_iterator(blocks, t1.global_train_batch(), seed=9),
             max_steps=5)
    t1.save()
    t1.close()

    t2 = Trainer.for_gpt2(cfg_a, mesh, model_f32, seed=5)
    assert t2.step_count == 5, "did not resume from checkpoint"
    t2.train(batch_iterator(blocks, t2.global_train_batch(), seed=9),
             max_steps=5)
    for a, b in zip(jax.tree.leaves(t_cont.params), jax.tree.leaves(t2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t_cont.state.exp_avg),
                    jax.tree.leaves(t2.state.exp_avg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    t2.close()
    t_cont.close()


def test_sp_pipeline_oversized_total_sequence_fails_loudly():
    """ADVICE r3: calling make_pipeline_loss directly with seq_axis and a
    TOTAL sequence (T_local x seq shards) past n_ctx must raise at trace
    time — without the guard the wpe dynamic_slice clamps silently and
    later seq shards duplicate positional rows. (The Trainer path already
    refuses this at config time via validate_seq_block; this pins the
    model-level guard for callers that bypass the Trainer.)"""
    from distributed_lion_tpu.models.gpt2_pipe import (
        make_pipeline_loss,
        pipeline_param_specs,
        pipeline_params,
    )
    from _sharded import run_sharded
    from jax.sharding import PartitionSpec as P

    pp, sp = 2, 2
    mesh = make_mesh(data=2, seq=sp, pipe=pp)
    model = GPT2Config.tiny(n_layer=pp)  # n_ctx=128
    params = gpt2_init(jax.random.key(0), model)
    # the shard_map in_spec splits dim 1 over the 2-way seq axis, so
    # T_local = n_ctx: fits per shard, but total = 2*n_ctx overflows wpe
    tokens = np.zeros((8, 2 * model.n_ctx), np.int32)

    loss_fn = make_pipeline_loss(model, n_micro=2, seq_axis="seq",
                                 vocab_chunks=0, axis_name="pipe")
    pparams = pipeline_params(params, pp)
    pspecs = pipeline_param_specs()

    def body(p, t):
        loss, _ = loss_fn(p, t, None)
        return jax.lax.pmean(loss, "data")

    with pytest.raises(ValueError, match="exceeds n_ctx"):
        run_sharded(body, mesh, (pspecs, P("data", "seq")), P(),
                    pparams, tokens, check_vma=False)
