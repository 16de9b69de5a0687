"""Trainable pipeline parallelism for the Llama family (round-3 unlock):
real Llama blocks (RMSNorm/RoPE/SwiGLU/GQA) as GPipe stages, full vote-Lion
training over a dp x pp mesh.

Same load-bearing invariant as tests/test_pipeline_train.py: pipelining is a
pure re-schedule — dp=2 x pp=4 must reproduce the dp=2 trajectory at equal
global batch (only device placement changes)."""

import jax
import numpy as np
import pytest

from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_init
from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train.loop import TrainConfig, Trainer

MODEL = LlamaConfig.tiny(n_layer=4, compute_dtype=np.float32)


def _cfg(**kw):
    base = dict(
        lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
        max_steps=5, per_device_train_batch_size=4,
        gradient_accumulation_steps=1, block_size=32, logging_steps=1,
        output_dir=None, seed=7,
    )
    base.update(kw)
    return TrainConfig(**base)


def _train(mesh, cfg, n_steps=5):
    trainer = Trainer.for_llama(cfg, mesh, MODEL, seed=123)
    blocks = synthetic_lm_dataset(
        max(64, trainer.global_train_batch() * 2), cfg.block_size,
        MODEL.vocab_size, seed=11,
    )
    hist = trainer.train(
        batch_iterator(blocks, trainer.global_train_batch(), seed=0),
        max_steps=n_steps,
    )
    params = jax.tree.map(np.asarray, jax.device_get(trainer.params))
    trainer.close()
    return [h["loss"] for h in hist if "loss" in h], params


def test_llama_pp_forward_matches_sequential():
    """Pipeline forward loss == plain forward loss on identical params."""
    from _sharded import run_sharded
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.models.llama_pipe import (
        llama_pipeline_param_specs,
        llama_pipeline_params,
        make_llama_pipeline_loss,
    )
    from distributed_lion_tpu.models.loss import clm_loss_and_metrics

    pp = 4
    params = llama_init(jax.random.key(0), MODEL)
    tokens = np.random.default_rng(0).integers(
        0, MODEL.vocab_size, size=(4, 32)).astype(np.int32)

    mesh = make_mesh(data=1, pipe=pp, devices=jax.devices()[:pp])
    loss_fn = make_llama_pipeline_loss(MODEL, n_micro=2)
    pparams = llama_pipeline_params(params, pp)

    def body(pp_params, toks):
        loss, m = loss_fn(pp_params, toks, None)
        return m["loss"]

    loss_pp = run_sharded(
        body, mesh, (llama_pipeline_param_specs(), P()), P(),
        pparams, tokens, check_vma=False)

    loss_seq, _ = clm_loss_and_metrics(
        jax.jit(llama_apply, static_argnums=2)(params, tokens, MODEL), tokens)
    np.testing.assert_allclose(float(loss_pp), float(loss_seq),
                               rtol=2e-4, atol=2e-4)


def test_llama_pp_roundtrip_params():
    from distributed_lion_tpu.models.llama_pipe import (
        llama_pipeline_params, llama_unpipeline_params)

    params = llama_init(jax.random.key(1), MODEL)
    back = llama_unpipeline_params(
        llama_pipeline_params(params, 4), MODEL.n_layer)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
def test_llama_pipelined_mesh_trajectory_matches_dp(mesh_kw, cfg_kw):
    """dp×pp — and dp×tp×pp, Megatron sharding inside the Llama stages —
    ≡ dp=2 at equal global batch."""
    from distributed_lion_tpu.models.llama_pipe import llama_unpipeline_params

    losses_dp, params_dp = _train(
        make_mesh(data=2, devices=jax.devices()[:2]), _cfg())
    losses_pp, params_pp = _train(make_mesh(**mesh_kw), _cfg(**cfg_kw))
    np.testing.assert_allclose(losses_pp, losses_dp, rtol=1e-4, atol=1e-4)
    restored = llama_unpipeline_params(params_pp, MODEL.n_layer)
    envelope = 2 * 1e-3 * 5  # 2·lr·n_steps ballot-flip envelope
    for a, b in zip(jax.tree.leaves(params_dp), jax.tree.leaves(restored)):
        assert np.abs(a.astype(np.float64) - b.astype(np.float64)).max() \
            <= envelope


def test_llama_pp_guards():
    mesh = make_mesh(data=2, pipe=4)
    with pytest.raises(ValueError, match="divisible"):
        Trainer.for_llama(_cfg(pipeline_parallel=4), mesh,
                          LlamaConfig.tiny(n_layer=3))
    with pytest.raises(NotImplementedError, match="tp_vocab"):
        Trainer.for_llama(_cfg(pipeline_parallel=2, tensor_parallel=2,
                               tp_vocab=True),
                          make_mesh(data=2, tensor=2, pipe=2), MODEL)


@pytest.mark.parametrize("chunks", [0, 4], ids=["dense", "chunked"])
def test_llama_sp_pp_trajectory_matches_dp(chunks):
    """dp=2 x sp=2 x pp=2 ≡ dp=2: ring attention inside every pipeline
    tick, rope offsets per seq shard, seq-parallel CE at the last stage —
    dense AND chunked (dv-layout) heads."""
    from distributed_lion_tpu.models.llama_pipe import llama_unpipeline_params

    losses_dp, params_dp = _train(
        make_mesh(data=2, devices=jax.devices()[:2]), _cfg(vocab_chunks=chunks))
    losses_sp, params_sp = _train(
        make_mesh(data=2, seq=2, pipe=2),
        _cfg(seq_parallel=2, pipeline_parallel=2, pipeline_microbatches=2,
             vocab_chunks=chunks))
    np.testing.assert_allclose(losses_sp, losses_dp, rtol=1e-4, atol=1e-4)
    restored = llama_unpipeline_params(params_sp, MODEL.n_layer)
    envelope = 2 * 1e-3 * 5
    for a, b in zip(jax.tree.leaves(params_dp), jax.tree.leaves(restored)):
        assert np.abs(a.astype(np.float64) - b.astype(np.float64)).max() \
            <= envelope


def test_run_clm_cli_llama_pp_smoke():
    from distributed_lion_tpu.cli.run_clm import main

    main([
        "--model_family", "llama", "--model_name", "tiny", "--lion",
        "--async_grad", "--dataset", "synthetic", "--max_steps", "2",
        "--per_device_train_batch_size", "2",
        "--gradient_accumulation_steps", "1", "--block_size", "32",
        "--pipeline_parallel", "2", "--pipeline_microbatches", "2",
        "--logging_steps", "10", "--eval_steps", "1000", "--save_steps",
        "1000",
    ])


def test_llama_pp_chunked_head_matches_dense():
    """pp × vocab_chunks on the untied lm_head (dv layout)."""
    from _sharded import run_sharded
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.models.llama_pipe import (
        llama_pipeline_param_specs,
        llama_pipeline_params,
        make_llama_pipeline_loss,
    )
    from distributed_lion_tpu.models.loss import clm_loss_and_metrics

    pp = 4
    params = llama_init(jax.random.key(0), MODEL)
    tokens = np.random.default_rng(0).integers(
        0, MODEL.vocab_size, size=(4, 32)).astype(np.int32)
    mesh = make_mesh(data=1, pipe=pp, devices=jax.devices()[:pp])
    loss_fn = make_llama_pipeline_loss(MODEL, n_micro=2, vocab_chunks=4)
    pparams = llama_pipeline_params(params, pp)

    def body(pp_params, toks):
        loss, m = loss_fn(pp_params, toks, None)
        return m["loss"]

    loss_pp = run_sharded(
        body, mesh, (llama_pipeline_param_specs(), P()), P(),
        pparams, tokens, check_vma=False)
    loss_seq, _ = clm_loss_and_metrics(
        jax.jit(llama_apply, static_argnums=2)(params, tokens, MODEL), tokens)
    np.testing.assert_allclose(float(loss_pp), float(loss_seq),
                               rtol=2e-4, atol=2e-4)


def test_llama_tp_sp_pp_full_composition_matches_dp():
    """The Llama twin of the full-mesh pin: tp=2 x sp=2 x pp=2 + chunked
    dv-head CE ≡ plain single-device training (rotary offsets composing
    with Megatron sharding inside ring-attention GPipe ticks)."""
    from distributed_lion_tpu.models.llama_pipe import llama_unpipeline_params

    losses_dp, params_dp = _train(
        make_mesh(data=1, devices=jax.devices()[:1]),
        _cfg(vocab_chunks=4, per_device_train_batch_size=8))
    losses_x, params_x = _train(
        make_mesh(data=1, tensor=2, seq=2, pipe=2),
        _cfg(tensor_parallel=2, seq_parallel=2, pipeline_parallel=2,
             pipeline_microbatches=2, vocab_chunks=4,
             per_device_train_batch_size=8))
    np.testing.assert_allclose(losses_x, losses_dp, rtol=1e-4, atol=1e-4)
    restored = llama_unpipeline_params(params_x, MODEL.n_layer)
    envelope = 2 * 1e-3 * 5
    for a, b in zip(jax.tree.leaves(params_dp), jax.tree.leaves(restored)):
        assert np.abs(a.astype(np.float64) - b.astype(np.float64)).max() \
            <= envelope
