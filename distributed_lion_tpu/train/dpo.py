"""DPO: direct preference optimization loss over policy + frozen reference.

The reference's DPO entry point is broken as shipped (syntax error at
dpo_llama2.py:81, undefined ``base_model`` at :210-213 — SURVEY §2.10); this
implements the INTENDED workload: policy and frozen reference model score
(prompt, chosen) and (prompt, rejected); the loss is

    -log σ(β · [(logπ_c − logπ_r) − (logref_c − logref_r)])

with β=0.1 (dpo_llama2.py:25, :223). Batches are pytrees
{"chosen", "rejected", "chosen_mask", "rejected_mask"} of [B, T] arrays,
masks selecting completion tokens only (prompt excluded, padding excluded),
produced by data/dpo.py.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.parallel.mesh import DATA_AXIS
from distributed_lion_tpu.train.loop import LossSpec


def sequence_logprob(logits: jnp.ndarray, tokens: jnp.ndarray,
                     mask: jnp.ndarray) -> jnp.ndarray:
    """Sum of label log-probs over masked (completion) positions, [B]."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return (ll * mask[:, 1:].astype(jnp.float32)).sum(-1)


def sequence_logprob_seq_parallel(
    logits: jnp.ndarray, tokens: jnp.ndarray, mask: jnp.ndarray,
    axis_name: str,
) -> jnp.ndarray:
    """Seq-parallel :func:`sequence_logprob` (inside shard_map): each device
    holds a contiguous [B, T/S] chunk of tokens/mask and ITS chunk's logits.
    Boundary labels (and their mask bits — a label counts iff the mask at
    the LABEL position is set, exactly like the dense path's
    ``mask[:, 1:]``) arrive from the next shard via one [B, 1] ppermute;
    per-shard partial sums are psum'd so every shard returns the full-
    sequence [B] logprob — the nonlinear pairwise DPO loss downstream then
    computes identically on every shard, and the train loop's seq-axis grad
    psum stitches the shard-local cotangent paths into the full gradient."""
    from distributed_lion_tpu.models.loss import shift_in_next_shard

    labels, is_last = shift_in_next_shard(tokens, axis_name)
    lmask, _ = shift_in_next_shard(mask, axis_name)
    lmask = lmask.astype(jnp.float32)
    # the final shard's last position has no next token (dense path drops it
    # via logits[:, :-1])
    lmask = lmask.at[:, -1].set(jnp.where(is_last, 0.0, lmask[:, -1]))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # the reduced [B] logprob is consumed replicated (every shard computes
    # the same pairwise loss), so the exit reduce is the Megatron g operator
    # — identity backward; a raw psum's transpose would scale every
    # adapter gradient by S (uniform, so sign-Lion hid it, but exact is
    # exact). The train loop's seq-axis grad psum then sums the per-shard
    # partial cotangent paths into the full gradient.
    from distributed_lion_tpu.parallel.tensor_parallel import reduce_from_tp_region

    return reduce_from_tp_region((ll * lmask).sum(-1), axis_name)


def sequence_logprob_chunked(
    hidden: jnp.ndarray, head: jnp.ndarray, tokens: jnp.ndarray,
    mask: jnp.ndarray, n_chunks: int, emb_layout: str = "dv",
) -> jnp.ndarray:
    """:func:`sequence_logprob` from HIDDEN STATES via the streaming
    chunked-vocab logsumexp (ops/xent.chunked_softmax_xent): per-position
    label logprob is −nll, so the [B, T, V] f32 ``log_softmax`` — ~1.3 GB
    per microbatch pass at Llama vocab 32k, and DPO runs FOUR such passes
    (policy/ref × chosen/rejected) — is never materialized. Exact same
    math (pinned by tests/test_dpo_chunked.py)."""
    from distributed_lion_tpu.ops.xent import chunked_softmax_xent

    b, t, d = hidden.shape
    h = hidden[:, :-1].reshape(b * (t - 1), d)
    labels = tokens[:, 1:].reshape(-1).astype(jnp.int32)
    nll, _ = chunked_softmax_xent(h, head, labels, n_chunks, emb_layout)
    ll = -nll.reshape(b, t - 1)
    return (ll * mask[:, 1:].astype(jnp.float32)).sum(-1)


def sequence_logprob_chunked_seq_parallel(
    hidden: jnp.ndarray, head: jnp.ndarray, tokens: jnp.ndarray,
    mask: jnp.ndarray, axis_name: str, n_chunks: int,
    emb_layout: str = "dv",
) -> jnp.ndarray:
    """Chunked × sequence-parallel :func:`sequence_logprob`: the boundary
    protocol of :func:`sequence_logprob_seq_parallel` (labels and their
    mask bits ppermute in from the next shard; final shard's last position
    dropped) with the local shard's label logprobs computed by the
    streaming chunked logsumexp instead of a materialized log_softmax."""
    from distributed_lion_tpu.models.loss import shift_in_next_shard
    from distributed_lion_tpu.ops.xent import chunked_softmax_xent
    from distributed_lion_tpu.parallel.tensor_parallel import reduce_from_tp_region

    labels, is_last = shift_in_next_shard(tokens, axis_name)
    lmask, _ = shift_in_next_shard(mask, axis_name)
    lmask = lmask.astype(jnp.float32)
    lmask = lmask.at[:, -1].set(jnp.where(is_last, 0.0, lmask[:, -1]))
    b, t, d = hidden.shape
    nll, _ = chunked_softmax_xent(
        hidden.reshape(b * t, d), head,
        labels.reshape(-1).astype(jnp.int32), n_chunks, emb_layout)
    ll = -nll.reshape(b, t)
    # replicated consumer ⇒ Megatron g-operator exit (identity backward),
    # same rationale as sequence_logprob_seq_parallel
    return reduce_from_tp_region((ll * lmask).sum(-1), axis_name)


def _accepts_dropout_key(fn: Callable) -> bool:
    """True when ``fn`` can take a ``dropout_key`` keyword (LoRA adapter
    dropout); plain ``(params, tokens)`` callables keep their signature."""
    import inspect

    try:
        return any(
            p.name == "dropout_key" or p.kind is inspect.Parameter.VAR_KEYWORD
            for p in inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):  # builtins/partials without signatures
        return False


def make_dpo_loss_fn(
    policy_apply: Callable,
    ref_apply: Callable,
    beta: float = 0.1,
    seq_axis: str | None = None,
    vocab_chunks: int = 0,
    emb_layout: str = "dv",
) -> tuple[Callable, LossSpec]:
    """Build ``loss_fn(params, batch, dropout_key) -> (loss, metrics)`` and
    its ``LossSpec`` (``vocab_chunks`` is honoured when given here; under
    ``seq_axis`` every [B, T] leaf of the batch is token-sharded) for the
    Trainer.
    ``policy_apply(params, tokens)`` and ``ref_apply(tokens)``
    (ref params are frozen/closed-over, mirroring the reference's separate
    4-bit ref model, dpo_llama2.py:146-152). With ``seq_axis``, the batch
    leaves are token-sharded chunks and the apply fns are expected to run
    the model with the same seq axis (ring attention). With
    ``vocab_chunks > 0``, the apply fns must return ``(hidden, head)``
    instead of logits and the logprobs stream through the chunked-vocab
    logsumexp (no [B, T, V] materialization — DPO's four scoring passes
    make this the biggest activation saving of any workload)."""

    def seqlp(out, tokens, mask):
        if vocab_chunks > 0:
            if not (isinstance(out, tuple) and len(out) == 2):
                # a [B,T,V] logits array would silently unpack along batch
                raise TypeError(
                    "vocab_chunks > 0 requires apply fns returning "
                    "(hidden, head); got a single array — wire the hidden/"
                    "head forward (see cli/run_dpo._hidden_and_head)")
            hidden, head = out
            if seq_axis is None:
                return sequence_logprob_chunked(
                    hidden, head, tokens, mask, vocab_chunks, emb_layout)
            return sequence_logprob_chunked_seq_parallel(
                hidden, head, tokens, mask, seq_axis, vocab_chunks,
                emb_layout)
        if seq_axis is None:
            return sequence_logprob(out, tokens, mask)
        return sequence_logprob_seq_parallel(out, tokens, mask, seq_axis)

    _accepts_key = _accepts_dropout_key(policy_apply)

    def _policy(params, tokens, key):
        if _accepts_key:
            return policy_apply(params, tokens, dropout_key=key)
        return policy_apply(params, tokens)

    def loss_fn(params, batch, dropout_key):
        # adapter (lora_dropout) keys: one per policy pass, None in eval —
        # the reference's PEFT dropout is train-time only (sft_llama2.py:48)
        kc = kr = None
        if dropout_key is not None:
            kc, kr = jax.random.split(dropout_key)
        pol_c = seqlp(_policy(params, batch["chosen"], kc),
                      batch["chosen"], batch["chosen_mask"])
        pol_r = seqlp(_policy(params, batch["rejected"], kr),
                      batch["rejected"], batch["rejected_mask"])
        ref_c = seqlp(ref_apply(batch["chosen"]),
                      batch["chosen"], batch["chosen_mask"])
        ref_r = seqlp(ref_apply(batch["rejected"]),
                      batch["rejected"], batch["rejected_mask"])
        # stop_gradient is belt-and-braces: ref_apply takes no params arg.
        ref_c = jax.lax.stop_gradient(ref_c)
        ref_r = jax.lax.stop_gradient(ref_r)

        logits = beta * ((pol_c - pol_r) - (ref_c - ref_r))
        loss = -jax.nn.log_sigmoid(logits).mean()
        reward_c = beta * (pol_c - ref_c)
        reward_r = beta * (pol_r - ref_r)
        metrics = {
            "loss": loss,
            "reward_accuracy": (reward_c > reward_r).mean(),
            "reward_margin": (reward_c - reward_r).mean(),
        }
        return loss, metrics

    return loss_fn, LossSpec(
        vocab_chunks=vocab_chunks > 0,
        batch_spec=P(DATA_AXIS, seq_axis) if seq_axis else None)


def make_dpo_loss_fn_frozen(
    policy_apply: Callable,
    ref_apply: Callable,
    beta: float = 0.1,
) -> Callable:
    """Frozen-as-argument variant for the Trainer's ``frozen_params`` path
    (tensor parallelism: the base/ref trees arrive as live sharded args, not
    closures). ``policy_apply(params, frozen, tokens)``,
    ``ref_apply(frozen, tokens)``; returns
    ``loss_fn(params, frozen, batch, dropout_key)``."""

    _accepts_key = _accepts_dropout_key(policy_apply)

    def loss_fn(params, frozen, batch, dropout_key):
        if _accepts_key:
            pol = (lambda p, t, dropout_key=None:
                   policy_apply(p, frozen, t, dropout_key=dropout_key))
        else:
            pol = lambda p, t: policy_apply(p, frozen, t)  # noqa: E731
        inner, _ = make_dpo_loss_fn(pol, lambda t: ref_apply(frozen, t), beta)
        return inner(params, batch, dropout_key)

    return loss_fn
