"""Pipeline-parallel GPT-2: blocks as stages, trainable end-to-end.

Net-new vs the reference (data-parallel only, SURVEY §2.7). This wires the
generic GPipe schedule (parallel/pipeline.py: stacked stage params sharded
over the ``pipe`` mesh axis, activations rotating via ``ppermute``, one
``lax.scan``) to the real GPT-2 of models/gpt2.py so ``run_clm
--pipeline_parallel N`` trains with blocks split into N stages.

SPMD layout inside the train-step ``shard_map`` (axes data × pipe):

- params = {wte, wpe, ln_f, stages} — ``stages`` leaves are
  ``[pp, n_layer/pp, ...]`` sharded ``P('pipe', ...)``; the embedding/final
  norm stay replicated.
- every stage runs the same program: embed (only stage 0's result is
  ingested), pipeline over the stages, ln_f + tied-logits + CLM loss (only
  the LAST stage's is real — selected with a masked ``psum``); the backward
  through the other stages' garbage compute receives zero cotangent.
- replicated-leaf gradients (wte/wpe/ln_f) are per-stage partials over
  disjoint contributions (stage 0: embedding; last stage: logits tie) —
  the train loop ``psum``s them over the pipe axis (train/loop.py), exactly
  like the seq-parallel gradient reduction.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.models.gpt2 import (
    GPT2Config,
    _block,
    _block_remat_for,
    _layer_norm,
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


def pipeline_params(params: dict, pp: int) -> dict:
    """Standard gpt2_init layout → pipeline layout with stacked stages."""
    return {
        "wte": params["wte"],
        "wpe": params["wpe"],
        "ln_f": params["ln_f"],
        "stages": stack_stage_params(params["blocks"], pp),
    }


def unpipeline_params(pparams: dict, n_layer: int) -> dict:
    """Inverse of :func:`pipeline_params` (export / generation)."""
    return {
        "wte": pparams["wte"],
        "wpe": pparams["wpe"],
        "ln_f": pparams["ln_f"],
        "blocks": unstack_stage_params(pparams["stages"], n_layer),
    }


def pipeline_param_specs(tensor: bool = False) -> dict:
    """Replicated embeddings/norm; stage leaves sharded over ``pipe`` (the
    stacked-stage leading dim is implied by ``P(PIPE_AXIS)`` alone — no
    config dependence).

    ``tensor=True`` ADDITIONALLY shards each stage's weights over the
    tensor axis (tp × pp, the classic large-model mesh): the per-layer
    Megatron specs of parallel/tensor_parallel.gpt2_param_specs shift right
    by the two stacked-stage dims ``[pp, layers/stage, ...]``. Embeddings,
    final norm, and the tied head stay replicated over tensor (the
    replicated-head TP layout) — the per-stage LayerNorms stay sharded over
    pipe only, and their tensor-axis gradients arrive complete through the
    Megatron copy boundary inside each block, so no extra reduction is
    needed (same argument as the non-pipelined TP path)."""
    rep = P()
    ln = {"scale": rep, "bias": rep}
    stage_ln = {"scale": P(PIPE_AXIS), "bias": P(PIPE_AXIS)}
    if not tensor:
        att = {k: P(PIPE_AXIS) for k in ("qkv", "qkv_b", "proj", "proj_b")}
        mlp = {k: P(PIPE_AXIS) for k in ("fc", "fc_b", "proj", "proj_b")}
    else:
        from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS

        def stage_spec(*tensor_dims):
            return P(PIPE_AXIS, None, *tensor_dims)

        att = {
            "qkv": stage_spec(None, None, TENSOR_AXIS),   # [d, 3, d/tp]
            "qkv_b": stage_spec(None, TENSOR_AXIS),
            "proj": stage_spec(TENSOR_AXIS, None),        # row-parallel
            "proj_b": stage_spec(),
        }
        mlp = {
            "fc": stage_spec(None, TENSOR_AXIS),          # column-parallel
            "fc_b": stage_spec(TENSOR_AXIS),
            "proj": stage_spec(TENSOR_AXIS, None),        # row-parallel
            "proj_b": stage_spec(),
        }
    stages = {"ln_1": stage_ln, "attn": att, "ln_2": stage_ln, "mlp": mlp}
    return {"wte": rep, "wpe": rep, "ln_f": ln, "stages": stages}


def make_pipeline_loss(model_cfg: GPT2Config, n_micro: int,
                       axis_name: str = PIPE_AXIS,
                       tp_axis: Optional[str] = None,
                       vocab_chunks: int = 0,
                       seq_axis: Optional[str] = None):
    """Build ``loss_fn(params, tokens, dropout_key) -> (loss, metrics)`` for
    the Trainer. Must run inside ``shard_map`` with ``axis_name`` bound;
    ``tokens`` [B_local, T] with B_local divisible by ``n_micro``. Dropout is
    unsupported under pipelining (guarded at config time).

    ``tp_axis`` runs each stage's blocks tensor-parallel (tp × pp):
    activations enter every stage replicated over the tensor axis, each
    block's column/row-parallel matmuls psum over it (models/gpt2._block),
    and they exit replicated again — so the ppermute pipeline rotation and
    the last-stage replicated head are untouched by tensor sharding.

    ``vocab_chunks`` streams the last stage's tied head through the chunked
    CE (ops/xent) — the [B, T, V] logits never materialize even on the one
    stage that computes the loss (and ONLY there: the cond still skips the
    head on every other stage).

    ``seq_axis`` shards TOKENS over a sequence axis on top of the pipeline
    (sp × pp, long-context pipelined training): each stage's blocks ring
    their attention k/v over ``seq_axis`` inside every pipeline tick, the
    positional rows are offset by the seq shard index, and the last stage's
    loss runs the seq-parallel CE. Its collectives (boundary-label
    ppermute, count/metric psums) are hoisted OUTSIDE the lax.cond — XLA
    aborts on collectives under conditional control flow — so the cond
    computes only collective-free masked NLL partials
    (ops/xent.masked_local_nll)."""

    # _block_remat_for honors cfg.remat_policy ('dots' keeps matmul
    # outputs) — the same wrapper the non-pipelined path uses
    block = _block_remat_for(model_cfg) if model_cfg.remat else _block

    def layer_fn(p_layer, h):
        return block(h, p_layer, None, model_cfg, tp_axis, seq_axis)

    def loss_fn(params, tokens, dropout_key):
        del dropout_key  # dropout unsupported under pipelining
        B, T = tokens.shape
        if seq_axis is None:
            if T > model_cfg.n_ctx:
                raise ValueError(
                    f"sequence length {T} exceeds n_ctx {model_cfg.n_ctx}")
            pos_start = 0
        else:
            # axis sizes are static under shard_map, so this guard is
            # shape-static too: without it an oversized TOTAL sequence
            # (T_local × seq shards > n_ctx) would make the wpe
            # dynamic_slice below clamp silently and hand later seq shards
            # duplicated positional rows — callers bypassing the Trainer's
            # config-time validate_seq_block must still fail loudly here
            total_t = T * lax.axis_size(seq_axis)
            if total_t > model_cfg.n_ctx:
                raise ValueError(
                    f"total sequence length {total_t} (T_local {T} x "
                    f"{lax.axis_size(seq_axis)} seq shards) exceeds n_ctx "
                    f"{model_cfg.n_ctx}")
            pos_start = lax.axis_index(seq_axis) * T
        x = params["wte"][tokens].astype(model_cfg.compute_dtype)
        x = x + lax.dynamic_slice_in_dim(
            params["wpe"], pos_start, T, axis=0
        ).astype(model_cfg.compute_dtype)
        xm = x.reshape((n_micro, B // n_micro, T, x.shape[-1]))
        # local stage view inside shard_map keeps a leading [1] shard axis
        stage_local = jax.tree.map(lambda a: a[0], params["stages"])
        acc = pipeline_apply(layer_fn, stage_local, xm, axis_name=axis_name)

        if seq_axis is not None:
            # sp × pp scaffold (collective hoisting + grad contract) lives
            # in models/loss.pipelined_seq_parallel_loss, shared with
            # llama_pipe; only the family head is defined here. It is
            # ops/xent.masked_local_nll and not the entry (clm_head_loss),
            # whose sequence arms psum and ppermute: this runs under
            # lax.cond, where XLA aborts on a collective.
            def head_partials(acc, labels, mask):
                h = _layer_norm(acc.reshape((B, T, x.shape[-1])),
                                params["ln_f"])
                return xent_ops.masked_local_nll(
                    h, params["wte"], labels, mask, vocab_chunks,
                    valid_v=model_cfg.vocab_size)

            return pipelined_seq_parallel_loss(
                head_partials, acc, tokens, seq_axis, axis_name)

        def head_loss(acc):
            h = _layer_norm(acc.reshape((B, T, x.shape[-1])), params["ln_f"])
            # padded-vocab layout (models/gpt2 vocab_pad_multiple): valid_v
            # drops the alignment columns, same as gpt2_apply
            return xent_ops.clm_head_loss(
                h, params["wte"], tokens, layout="vd",
                valid_v=model_cfg.vocab_size, chunks=vocab_chunks)

        return pipelined_loss(head_loss, acc, axis_name)

    return loss_fn
