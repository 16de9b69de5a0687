"""Pipeline-parallel Llama: blocks as GPipe stages, trainable end-to-end.

The Llama twin of models/gpt2_pipe.py (same generic schedule —
parallel/pipeline.py's stacked stage params over the ``pipe`` axis,
activations rotating via ``ppermute``, one ``lax.scan``), so ``run_clm
--model_family llama --pipeline_parallel N`` trains with the reference's
second architecture family split into N stages. Differences from the GPT-2
wiring, all boundary-layer: rotary tables (cos/sin, computed once per step
from T and closed over — identical on every stage) replace the learned
positional embedding, RMSNorm replaces LayerNorm, and the head is the
untied ``lm_head`` rather than the tied embedding.

Gradient contract matches gpt2_pipe: stage leaves carry complete local
grads; replicated leaves (wte / lm_head / ln_f) carry disjoint per-stage
partials (stage 0: embedding; last stage: head + final norm) that the train
loop psums over the pipe axis.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.models.llama import (
    LlamaConfig,
    _block,
    _block_remat_for,
    _rms_norm,
    rope_angles,
)
from distributed_lion_tpu.models.loss import (
    pipelined_loss,
    pipelined_seq_parallel_loss,
)
from distributed_lion_tpu.ops import xent as xent_ops
from distributed_lion_tpu.parallel.mesh import PIPE_AXIS
from distributed_lion_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
    unstack_stage_params,
)


def llama_pipeline_params(params: dict, pp: int) -> dict:
    """Standard llama_init layout → pipeline layout with stacked stages."""
    return {
        "wte": params["wte"],
        "lm_head": params["lm_head"],
        "ln_f": params["ln_f"],
        "stages": stack_stage_params(params["blocks"], pp),
    }


def llama_unpipeline_params(pparams: dict, n_layer: int) -> dict:
    """Inverse of :func:`llama_pipeline_params` (export / generation)."""
    return {
        "wte": pparams["wte"],
        "lm_head": pparams["lm_head"],
        "ln_f": pparams["ln_f"],
        "blocks": unstack_stage_params(pparams["stages"], n_layer),
    }


def llama_pipeline_param_specs(tensor: bool = False) -> dict:
    """Replicated embeddings/head/final-norm; stage leaves sharded over
    ``pipe`` (their stacked leading dim).

    ``tensor=True`` ADDITIONALLY shards each stage's weights over the
    tensor axis (tp × pp): parallel/tensor_parallel.llama_param_specs'
    per-layer Megatron specs shifted past the two stacked-stage dims.
    wte / lm_head / ln_f stay replicated over tensor (replicated-head TP);
    the per-stage RMSNorm scales stay pipe-sharded only, their tensor-axis
    grads arriving complete through the Megatron copy boundary (same
    argument as gpt2_pipe)."""
    rep = P()
    stage_rms = {"scale": P(PIPE_AXIS)}
    if not tensor:
        att = {k: P(PIPE_AXIS) for k in ("wq", "wk", "wv", "wo")}
        mlp = {k: P(PIPE_AXIS) for k in ("w_gate", "w_up", "w_down")}
    else:
        from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS

        col = P(PIPE_AXIS, None, None, TENSOR_AXIS)   # [pp, L/pp, d, k]
        row = P(PIPE_AXIS, None, TENSOR_AXIS, None)   # [pp, L/pp, k, d]
        att = {"wq": col, "wk": col, "wv": col, "wo": row}
        mlp = {"w_gate": col, "w_up": col, "w_down": row}
    stages = {"ln_attn": stage_rms, "attn": att, "ln_mlp": stage_rms,
              "mlp": mlp}
    return {"wte": rep, "lm_head": rep, "ln_f": {"scale": rep},
            "stages": stages}


def make_llama_pipeline_loss(model_cfg: LlamaConfig, n_micro: int,
                             axis_name: str = PIPE_AXIS,
                             tp_axis=None, vocab_chunks: int = 0,
                             seq_axis=None):
    """Build ``loss_fn(params, tokens, dropout_key) -> (loss, metrics)`` for
    the Trainer. Must run inside ``shard_map`` with ``axis_name`` bound;
    ``tokens`` [B_local, T] with B_local divisible by ``n_micro``.
    ``tp_axis`` runs each stage's blocks tensor-parallel (tp × pp) — see
    gpt2_pipe.make_pipeline_loss. ``vocab_chunks`` streams the last stage's
    untied lm_head through the chunked CE (the win that matters most at
    Llama-3's 128k vocab: [B, T, 128k] f32 logits never materialize).
    ``seq_axis`` shards tokens over a sequence axis on top of the pipeline
    (sp × pp): rotary angles offset by the seq shard index, ring attention
    over ``seq_axis`` inside every pipeline tick, seq-parallel CE at the
    last stage — see gpt2_pipe.make_pipeline_loss for the cond/collective
    argument."""

    def loss_fn(params, tokens, dropout_key):
        del dropout_key  # Llama (like HF's) has no dropout
        B, T = tokens.shape
        if seq_axis is None:
            if T > model_cfg.n_ctx:
                raise ValueError(f"sequence length {T} exceeds n_ctx "
                                 f"{model_cfg.n_ctx}")
            offset = 0
        else:
            # static guard (axis sizes are static under shard_map): an
            # oversized total sequence would silently RoPE-extrapolate past
            # n_ctx instead of failing; mirror gpt2_pipe's loud check
            total_t = T * lax.axis_size(seq_axis)
            if total_t > model_cfg.n_ctx:
                raise ValueError(
                    f"total sequence length {total_t} (T_local {T} x "
                    f"{lax.axis_size(seq_axis)} seq shards) exceeds n_ctx "
                    f"{model_cfg.n_ctx}")
            offset = lax.axis_index(seq_axis) * T
        cos, sin = rope_angles(T, model_cfg.head_dim, model_cfg.rope_theta,
                               offset=offset)
        # same remat wrapper as the non-pipelined path (honors remat_policy)
        block = _block_remat_for(model_cfg) if model_cfg.remat else _block

        def layer_fn(p_layer, h):
            return block(h, p_layer, model_cfg, cos, sin, tp_axis, seq_axis)

        x = params["wte"][tokens].astype(model_cfg.compute_dtype)
        xm = x.reshape((n_micro, B // n_micro, T, x.shape[-1]))
        # local stage view inside shard_map keeps a leading [1] shard axis
        stage_local = jax.tree.map(lambda a: a[0], params["stages"])
        acc = pipeline_apply(layer_fn, stage_local, xm, axis_name=axis_name)

        if seq_axis is not None:
            # sp × pp scaffold (collective hoisting + grad contract) shared
            # with gpt2_pipe: models/loss.pipelined_seq_parallel_loss, and
            # masked_local_nll under its lax.cond for gpt2_pipe's reason.
            def head_partials(acc, labels, mask):
                h = _rms_norm(acc.reshape((B, T, x.shape[-1])),
                              params["ln_f"], model_cfg.rms_eps)
                return xent_ops.masked_local_nll(
                    h, params["lm_head"], labels, mask, vocab_chunks,
                    emb_layout="dv")

            return pipelined_seq_parallel_loss(
                head_partials, acc, tokens, seq_axis, axis_name)

        def head_loss(acc):
            h = _rms_norm(acc.reshape((B, T, x.shape[-1])), params["ln_f"],
                          model_cfg.rms_eps)
            return xent_ops.clm_head_loss(h, params["lm_head"], tokens,
                                          layout="dv", chunks=vocab_chunks)

        return pipelined_loss(head_loss, acc, axis_name)

    return loss_fn
