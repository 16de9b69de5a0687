"""Causal-LM loss and eval metrics.

Parity targets: HF's shift-by-one CLM cross entropy (the loss the reference's
run_clm optimizes via AutoModelForCausalLM) and its eval metrics — argmax
token accuracy computed on shifted labels (/root/reference/run_clm.py:562-577)
and perplexity = exp(eval_loss) (:630-636, computed in train.eval).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("xent")
def clm_loss_and_metrics(
    logits: jnp.ndarray,
    tokens: jnp.ndarray,
    loss_mask: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Next-token cross entropy with shift-by-one labels.

    Args:
        logits: [B, T, V] float32.
        tokens: [B, T] int32 — inputs; labels are ``tokens[:, 1:]``.
        loss_mask: optional [B, T] bool/float; positions where the LABEL
            (i.e. mask index 1..T-1) should count. Used by SFT completion-only
            training and padding exclusion.

    Returns:
        (mean_loss, {"loss", "accuracy", "n_tokens"}) — accuracy is argmax
        token accuracy on the shifted labels (run_clm.py:569-577 semantics).
    """
    shift_logits = logits[:, :-1]
    shift_labels = tokens[:, 1:]
    if loss_mask is None:
        mask = jnp.ones(shift_labels.shape, jnp.float32)
    else:
        mask = loss_mask[:, 1:].astype(jnp.float32)

    logp = jax.nn.log_softmax(shift_logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, shift_labels[..., None], axis=-1)[..., 0]
    n = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / n

    pred = shift_logits.argmax(-1)
    acc = ((pred == shift_labels) * mask).sum() / n
    return loss, {"loss": loss, "accuracy": acc, "n_tokens": mask.sum()}


@jax.named_scope("xent")
def clm_loss_sharded_rows(
    logits: jnp.ndarray,
    tokens: jnp.ndarray,
    axis_name: str,
    aux: jnp.ndarray | None = None,
    aux_weight: float = 0.01,
) -> tuple[jnp.ndarray, dict]:
    """CLM loss when batch ROWS are sharded over ``axis_name`` but params are
    replicated along it (expert parallelism's token sharding — the 'expert'
    axis doubles as extra data parallelism for the dense layers).

    Returns ``local_row_nll_sum / global_token_count`` (+ the MoE aux loss,
    averaged over shards) so that a ``psum`` of its GRADIENT over
    ``axis_name`` equals the full-batch gradient — the train loop reduces
    replicated-leaf grads exactly that way (train/loop.py). Expert-SHARDED
    leaves need no such reduction: every path from them to any shard's loss
    crosses the dispatch/return all_to_all, whose transpose routes the
    cross-shard cotangents home. Metrics are globally reduced.
    """
    shift_logits = logits[:, :-1]
    shift_labels = tokens[:, 1:]
    logp = jax.nn.log_softmax(shift_logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, shift_labels[..., None], axis=-1)[..., 0]
    n_local = jnp.float32(nll.size)
    shards = jax.lax.psum(1, axis_name)
    n_global = jnp.maximum(jax.lax.psum(n_local, axis_name), 1.0)
    ce_local = nll.sum() / n_global
    loss_local = ce_local
    pred = shift_logits.argmax(-1)
    acc = jax.lax.psum((pred == shift_labels).sum().astype(jnp.float32),
                       axis_name) / n_global
    metrics = {
        "loss": jax.lax.psum(ce_local, axis_name),  # CE only, aux reported apart
        "accuracy": acc,
        "n_tokens": n_global / shards,  # per-shard average (logging parity)
    }
    if aux is not None:
        loss_local = loss_local + aux_weight * aux / shards
        metrics["aux_loss"] = jax.lax.psum(aux / shards, axis_name)
    return loss_local, metrics


def shift_in_next_shard(
    x: jnp.ndarray, axis_name: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The seq-parallel shard-boundary protocol, in one place: shift a
    [B, T_local] array left by one column, filling the last column with the
    NEXT shard's first column via a single [B, 1] ``ppermute``. Returns
    ``(shifted, is_last_shard)`` — the final shard's fill is garbage (wraps
    to shard 0) and must be masked by the caller using the flag. Shared by
    :func:`clm_loss_seq_parallel` and train/dpo's seq-parallel logprob so
    the perm direction and boundary masking can't drift apart."""
    S = jax.lax.psum(1, axis_name)
    sidx = jax.lax.axis_index(axis_name)
    nxt = jax.lax.ppermute(
        x[:, :1], axis_name, [(i, (i - 1) % S) for i in range(S)]
    )
    return jnp.concatenate([x[:, 1:], nxt], axis=1), sidx == S - 1


def shifted_labels_and_mask(
    tokens: jnp.ndarray, axis_name: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`shift_in_next_shard` plus the boundary MASK — the other half
    of the shard-boundary protocol (the final shard's last position has no
    next token and must not count), in one place so no caller hand-rolls
    it. Returns ``(labels [B, T_local], mask [B, T_local] f32)``."""
    labels, is_last = shift_in_next_shard(tokens, axis_name)
    mask = jnp.ones(labels.shape, jnp.float32)
    mask = mask.at[:, -1].set(jnp.where(is_last, 0.0, 1.0))
    return labels, mask


def clm_loss_seq_parallel(
    logits: jnp.ndarray,
    tokens: jnp.ndarray,
    axis_name: str,
) -> tuple[jnp.ndarray, dict]:
    """CLM loss under sequence parallelism (inside shard_map).

    Each device holds a contiguous chunk ``tokens`` [B, T_local] of the full
    sequence and that chunk's ``logits``. The label of a chunk's LAST
    position is the NEXT chunk's first token — fetched with one tiny
    ``ppermute`` ([B, 1] per hop) — so no token's loss signal is dropped at
    shard boundaries; only the final position of the final chunk (which has
    no next token, exactly like the last position in the non-parallel loss)
    is masked.

    Returns a loss whose value is ``local_nll_sum / global_token_count`` —
    psum of its GRADIENT over ``axis_name`` equals the full-sequence
    gradient, which is how the train loop reduces it. The reported metrics
    are globally reduced (identical on every shard).
    """
    S = jax.lax.psum(1, axis_name)
    # my last position's label = next shard's first token (shard i gets it
    # from shard i+1; shard S-1 receives garbage from shard 0 and masks it)
    labels, mask = shifted_labels_and_mask(tokens, axis_name)  # [B, T_local]

    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    n_global = jnp.maximum(jax.lax.psum(mask.sum(), axis_name), 1.0)
    loss_local = (nll * mask).sum() / n_global  # grad psums to the full grad

    pred = logits.argmax(-1)
    acc = jax.lax.psum(((pred == labels) * mask).sum(), axis_name) / n_global
    loss_global = jax.lax.psum(loss_local, axis_name)
    return loss_local, {
        "loss": loss_global,
        "accuracy": acc,
        "n_tokens": n_global / jnp.maximum(S, 1),  # per-shard average, matches
        # the replicated path's per-device count convention for logging
    }


def pipelined_loss(head_loss, acc, pipe_axis: str):
    """The pp loss tail of gpt2_pipe and llama_pipe: only the last stage
    saw real activations, so ``lax.cond`` runs ``head_loss(acc) -> (loss,
    metrics)`` there alone — XLA executes just the taken branch, and the
    (expensive) vocab projection is skipped on every other stage — and
    the psum then both broadcasts the value and routes zero cotangent
    into the skip branch."""
    def skip_loss(acc):
        z = jnp.float32(0)
        return z, {"loss": z, "accuracy": z, "n_tokens": z}

    stage = jax.lax.axis_index(pipe_axis)
    last = jax.lax.psum(1, pipe_axis) - 1
    loss_local, metrics = jax.lax.cond(stage == last, head_loss, skip_loss,
                                       acc)
    return jax.lax.psum(loss_local, pipe_axis), {
        k: jax.lax.psum(v, pipe_axis) for k, v in metrics.items()}


def pipelined_seq_parallel_loss(head_partials, acc, tokens, seq_axis: str,
                                pipe_axis: str):
    """The sp × pp loss scaffold, shared by gpt2_pipe and llama_pipe so the
    trickiest contracts live in ONE place:

    - collective hoisting: XLA aborts on collectives under conditional
      control flow, so the boundary-label ``ppermute`` (tokens-only — free
      to hoist) and every psum run OUT here while the ``lax.cond`` over
      pipeline stages wraps only ``head_partials(acc, labels, mask) ->
      (masked nll sum, masked correct sum)``, which must be
      collective-free (ops/xent.masked_local_nll);
    - grad contract: the returned loss differentiates as
      ``local_nll_sum / global_token_count`` per (seq, pipe) rank — the
      train loop psums grads over the seq axis and (for replicated leaves)
      the pipe axis, completing the sum.

    Returns ``(loss, metrics)`` in the Trainer's contract; metrics are
    globally reduced, ``n_tokens`` is the per-seq-shard average (the seq
    loss's logging convention, uniform across pipe)."""
    labels, mask = shifted_labels_and_mask(tokens, seq_axis)
    S = jax.lax.psum(1, seq_axis)
    n_global = jnp.maximum(jax.lax.psum(mask.sum(), seq_axis), 1.0)

    stage = jax.lax.axis_index(pipe_axis)
    last = jax.lax.psum(1, pipe_axis) - 1
    nll_sum, correct_sum = jax.lax.cond(
        stage == last,
        lambda a: head_partials(a, labels, mask),
        lambda a: (jnp.float32(0), jnp.float32(0)),
        acc,
    )
    loss_local = nll_sum / n_global
    loss = jax.lax.psum(loss_local, pipe_axis)
    metrics = {
        "loss": jax.lax.psum(jax.lax.psum(loss_local, seq_axis), pipe_axis),
        "accuracy": jax.lax.psum(
            jax.lax.psum(correct_sum, seq_axis), pipe_axis) / n_global,
        "n_tokens": n_global / S,
    }
    return loss, metrics
