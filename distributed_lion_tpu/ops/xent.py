"""Chunked-vocab softmax cross entropy: CLM loss without materializing the
full [B, T, V] f32 logits.

At GPT-2 124M flagship shapes the logits tensor is the single largest
activation — microbatch 4 × T 1024 × V 50257 in f32 is ~823 MB, written to
and re-read from HBM around the softmax (and again in backward). Here the
tied-embedding projection, the streaming logsumexp, the label gather, and
the argmax (for the accuracy metric) run per vocab CHUNK inside one
``lax.scan`` whose body is ``jax.checkpoint``-ed: forward keeps only the
running (max, sumexp, label-logit, argmax) carries — peak logits memory
drops to [N, V/chunks] — and backward recomputes each chunk's logits from
(hidden, emb_chunk) instead of loading stored ones.

Exact same math as ``log_softmax`` + gather (pinned to the dense path by
tests/test_xent.py, gradients included); only the schedule differs.

Where the trainer's loss head is decided: :func:`clm_head_loss` is the one
entry from final hidden states and a head matrix to a CLM loss, and
:func:`head_path` the one rule, a function of what a call shows (never an
option beyond the ``vocab_chunks`` / ``tp_vocab`` a user already sets):

- ``tp_vocab``: a vocab axis. Each rank's shard of the head, Megatron's
  vocab-parallel cross entropy (:func:`tp_vocab_xent`).
- ``seq``: a sequence axis. The shard's logits and
  ``models/loss.clm_loss_seq_parallel``.
- ``seq_chunked``: a sequence axis and chunks
  (:func:`chunked_clm_loss_seq_parallel`).
- ``chunked``: chunks. :func:`chunked_softmax_xent`, a vocab chunk at a time.
- ``fused``: a ``[V, d]`` head whole on a TPU under bfloat16 hidden states
  with ``d`` a multiple of 128. The Mosaic kernel pair of
  ``ops/pallas_xent``: no ``[B, T, V]`` logits in HBM, forward or backward.
- ``dense``: everything else. The einsum the family's ``*_apply`` forms and
  ``models/loss.clm_loss_and_metrics``, bit for bit.

Both trainers' builder (``train/loop``), both pipelined losses and SFT call
the entry; ``train/remat`` asks the rule which head a step will hold. What
``fused`` resolved to is recorded once a shape at trace time: an
``xent_resolved`` event in the run journal and a ``[setup] cross-entropy:
...`` line after the trainer's first dispatch (``train/journal.resolved``),
as ``ops/attention`` does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


@jax.named_scope("xent")
def chunked_softmax_xent(
    hidden: jnp.ndarray,
    emb: jnp.ndarray,
    labels: jnp.ndarray,
    n_chunks: int = 8,
    emb_layout: str = "vd",
    valid_v: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming cross entropy against a tied embedding / LM head.

    Each chunk is carved out of the ORIGINAL head array with a
    ``dynamic_slice`` — no padded or transposed copy of the (possibly
    V=128k × d) head is ever materialized; when V doesn't divide evenly the
    final chunk overlaps the previous one and the already-counted columns
    are masked out of the lse/gather/argmax.

    Args:
        hidden: [N, d] final hidden states (any float dtype; matmul f32-acc).
        emb: the head — [V, d] with ``emb_layout="vd"`` (tied embedding,
            rows are vocab entries) or [d, V] with ``"dv"`` (untied lm_head
            in matmul orientation, e.g. Llama).
        labels: [N] int32 target ids (< V by contract).
        n_chunks: number of vocab chunks.
        valid_v: when > 0, only head columns < ``valid_v`` are real vocab —
            the rest are MXU-alignment padding (models/gpt2
            ``vocab_pad_multiple``) masked out of the lse/gather/argmax
            exactly like tail-chunk overlap columns, so the padded head
            computes the identical loss and its pad rows get zero gradient.

    Returns:
        (nll [N] f32, correct [N] bool) — per-position negative log
        likelihood and argmax-equals-label (for the accuracy metric).
    """
    if emb_layout not in ("vd", "dv"):
        raise ValueError(f"emb_layout must be 'vd' or 'dv', got {emb_layout!r}")
    n, d = hidden.shape
    v = emb.shape[0] if emb_layout == "vd" else emb.shape[1]
    v_real = valid_v if valid_v > 0 else v
    if v_real > v:
        raise ValueError(f"valid_v {v_real} > head columns {v}")
    vc = -(-v // n_chunks)  # ceil; vc <= v always

    @partial(jax.checkpoint, prevent_cse=False)
    def body(carry, cidx):
        m, s, lab, best, besti = carry
        # the tail chunk starts early enough to stay in-bounds; columns it
        # shares with the previous chunk are masked as already-counted
        start = jnp.minimum(cidx * vc, v - vc)
        if emb_layout == "vd":
            ec = lax.dynamic_slice_in_dim(emb, start, vc, axis=0)
            logits = jnp.einsum("nd,vd->nv", hidden, ec.astype(hidden.dtype),
                                preferred_element_type=jnp.float32)
        else:
            ec = lax.dynamic_slice_in_dim(emb, start, vc, axis=1)
            logits = jnp.einsum("nd,dv->nv", hidden, ec.astype(hidden.dtype),
                                preferred_element_type=jnp.float32)
        cols = start + jnp.arange(vc)
        fresh = (cols >= cidx * vc) & (cols < v_real)
        logits = jnp.where(fresh[None, :], logits, -jnp.inf)

        cm = logits.max(-1)
        new_m = jnp.maximum(m, cm)
        # exp(-inf - finite) == 0 handles the all-masked-column case; the
        # m carry starts at -inf so guard its rescale with where:
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - new_m), 0.0)
        add = jnp.where(jnp.isfinite(cm),
                        jnp.exp(logits - new_m[:, None]).sum(-1), 0.0)
        s = s * scale + add

        local = labels - start
        in_range = (labels >= cidx * vc) & (local < vc)
        gathered = jnp.take_along_axis(
            logits, jnp.clip(local, 0, vc - 1)[:, None], axis=-1
        )[:, 0]
        lab = lab + jnp.where(in_range, gathered, 0.0)

        upd = cm > best
        best = jnp.where(upd, cm, best)
        besti = jnp.where(upd, logits.argmax(-1) + start, besti)
        return (new_m, s, lab, best, besti), None

    init = (
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.int32),
    )
    (m, s, lab, _, besti), _ = lax.scan(
        body, init, jnp.arange(n_chunks, dtype=jnp.int32)
    )
    lse = m + jnp.log(s)
    nll = lse - lab
    return nll, besti == labels


@jax.named_scope("xent")
def tp_vocab_xent(
    hidden: jnp.ndarray,
    head_shard: jnp.ndarray,
    labels: jnp.ndarray,
    axis_name: str,
    valid_v: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Megatron-style vocab-parallel cross entropy (inside shard_map).

    Each tensor rank holds ``head_shard`` [d, V/tp] — its contiguous slice
    of the lm_head's vocab columns — and computes only those logits: the
    full [N, V] logits never exist on any device, and the head matmul's
    FLOPs split tp ways (the replicated-head TP path computes identical
    full-vocab logits on every rank). The softmax normalizer assembles from
    per-rank (max, sumexp) via ``pmax``/``psum``; the label logit via a
    masked gather on the one rank whose slice contains it; argmax (for the
    accuracy metric) via pmax-then-pmin, matching dense argmax's
    lowest-index tie rule.

    ``hidden`` [N, d] must be replicated over ``axis_name``; it is passed
    through the Megatron copy boundary here, so backward psums d(hidden)
    across ranks — callers get complete backbone gradients without extra
    plumbing. Returns (nll [N] f32, correct [N] bool), identical on every
    rank.

    ``valid_v`` (> 0) marks global columns >= it as MXU-alignment padding
    (models/gpt2 ``vocab_pad_multiple``) and masks them out of the
    normalizer/argmax — shard_map needs the vocab axis to divide evenly, so
    padding is what makes a ragged vocab (GPT-2's 50257) shardable at all;
    the mask keeps the padded math exactly equal to the dense loss.
    """
    from distributed_lion_tpu.parallel.tensor_parallel import (
        copy_to_tp_region,
        reduce_from_tp_region,
    )

    vshard = head_shard.shape[1]
    start = lax.axis_index(axis_name) * vshard
    hidden = copy_to_tp_region(hidden, axis_name)
    logits = jnp.einsum("nd,dv->nv", hidden,
                        head_shard.astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    if valid_v > 0:
        # pad columns: -inf drops them from the normalizer with zero
        # gradient (m below is a GLOBAL pmax, so even an all-pad rank's
        # exp(-inf - m) underflows cleanly to 0)
        real = (start + jnp.arange(vshard)) < valid_v
        logits = jnp.where(real[None, :], logits, -jnp.inf)
    # the max shift is a constant offset that cancels analytically in the
    # softmax gradient, so detaching it is exact — and the stop_gradient
    # must sit UPSTREAM of the pmax (which defines no differentiation rule)
    # so no tangent ever reaches the collective
    m = lax.pmax(lax.stop_gradient(logits).max(-1), axis_name)
    se = reduce_from_tp_region(jnp.exp(logits - m[:, None]).sum(-1), axis_name)
    lse = jnp.log(se) + m

    in_range = (labels >= start) & (labels < start + vshard)
    idx = jnp.clip(labels - start, 0, vshard - 1)
    lab = jnp.take_along_axis(logits, idx[:, None], axis=-1)[..., 0]
    label_logit = reduce_from_tp_region(jnp.where(in_range, lab, 0.0), axis_name)
    nll = lse - label_logit

    stopped = lax.stop_gradient(logits)  # accuracy metric: no grad path
    # m IS the global max — ranks whose local max reaches it are the argmax
    # candidates; pmin picks the lowest global id (dense argmax's tie rule)
    cand = jnp.where(stopped.max(-1) == m, stopped.argmax(-1) + start,
                     jnp.int32(2**30))
    best_id = lax.pmin(cand, axis_name)
    return nll, best_id == labels


def _shifted_clm_metrics(xent_fn, hidden, tokens, loss_mask):
    """Shared shift-by-one CLM tail: ``xent_fn(h [N,d], labels [N]) ->
    (nll, correct)`` over positions 0..T-2 predicting tokens 1..T-1, masked
    mean loss/accuracy — the one place the contract of
    models/loss.clm_loss_and_metrics is reproduced from hidden states."""
    b, t, d = hidden.shape
    h = hidden[:, :-1].reshape(b * (t - 1), d)
    labels = tokens[:, 1:].reshape(-1).astype(jnp.int32)
    nll, correct = xent_fn(h, labels)
    if loss_mask is None:
        mask = jnp.ones_like(nll)
    else:
        mask = loss_mask[:, 1:].reshape(-1).astype(jnp.float32)
    return _masked_mean_metrics(nll, correct, mask)


def _masked_mean_metrics(nll, correct, mask):
    """Per-row ``nll`` / ``correct`` under a float ``mask`` -> the contract
    of models/loss.clm_loss_and_metrics."""
    nmask = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / nmask
    acc = (correct.astype(jnp.float32) * mask).sum() / nmask
    return loss, {"loss": loss, "accuracy": acc, "n_tokens": mask.sum()}


def chunked_clm_loss_seq_parallel(
    hidden: jnp.ndarray,
    emb: jnp.ndarray,
    tokens: jnp.ndarray,
    n_chunks: int,
    axis_name: str,
    emb_layout: str = "vd",
    valid_v: int = 0,
) -> tuple[jnp.ndarray, dict]:
    """Chunked-vocab CE under sequence parallelism (inside shard_map) —
    the composition of :func:`chunked_softmax_xent` (no [B, T, V] logits
    materialized) with models/loss.clm_loss_seq_parallel's
    shard-boundary protocol (each device holds a contiguous [B, T_local]
    token chunk; its last position's label arrives from the next shard via
    one [B, 1] ppermute; only the final shard's final position is masked).

    Long-context × huge-vocab is exactly where both tricks matter at once:
    at T=128k sharded 8 ways with a 128k vocab, a single shard's dense
    logits would still be [B, 16k, 128k] f32. Same gradient contract as
    clm_loss_seq_parallel: returns ``local_nll_sum / global_token_count``
    whose seq-axis grad psum (done by the train loop) is the full gradient.
    """
    from distributed_lion_tpu.models.loss import shifted_labels_and_mask

    S = jax.lax.psum(1, axis_name)
    labels, mask = shifted_labels_and_mask(tokens, axis_name)  # [B, T_local]

    nll_sum, correct_sum = masked_local_nll(
        hidden, emb, labels, mask, n_chunks, emb_layout, valid_v)
    n_global = jnp.maximum(jax.lax.psum(mask.sum(), axis_name), 1.0)
    loss_local = nll_sum / n_global
    return loss_local, {
        "loss": jax.lax.psum(loss_local, axis_name),
        "accuracy": jax.lax.psum(correct_sum, axis_name) / n_global,
        "n_tokens": n_global / jnp.maximum(S, 1),
    }


@jax.named_scope("xent")
def masked_local_nll(
    hidden: jnp.ndarray,
    head: jnp.ndarray,
    labels: jnp.ndarray,
    mask: jnp.ndarray,
    n_chunks: int = 0,
    emb_layout: str = "vd",
    valid_v: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """COLLECTIVE-FREE masked NLL partials: ``hidden`` [B, T, d] with
    per-position ``labels``/``mask`` [B, T] → (masked nll sum, masked
    correct sum), both f32 scalars. ``n_chunks > 0`` streams the head
    through :func:`chunked_softmax_xent`; otherwise a dense log_softmax
    (``valid_v`` slices a padded head's columns first).

    Exists for losses that must run inside ``lax.cond`` — the pipelined
    seq-parallel head computes only these local partials on the last stage
    and leaves every psum/ppermute OUTSIDE the cond (XLA aborts on
    collectives under conditional control flow even when all participants
    agree on the branch)."""
    b, t, d = hidden.shape
    flat_labels = labels.reshape(-1).astype(jnp.int32)
    if n_chunks > 0:
        nll, correct = chunked_softmax_xent(
            hidden.reshape(b * t, d), head, flat_labels, n_chunks,
            emb_layout, valid_v)
    else:
        logits = _head_logits(hidden, head, emb_layout, valid_v)
        logp = jax.nn.log_softmax(logits.reshape(b * t, -1), axis=-1)
        nll = -jnp.take_along_axis(logp, flat_labels[:, None], 1)[:, 0]
        correct = logp.argmax(-1) == flat_labels
    fm = mask.reshape(-1).astype(jnp.float32)
    return (nll * fm).sum(), (correct.astype(jnp.float32) * fm).sum()


def fused_kernel_applies(d: int, dtype) -> bool:
    """True when the kernel pair takes such hidden states (``fused`` in the
    module doc's table); :func:`head_path` is its one caller."""
    from distributed_lion_tpu.ops.pallas_xent import kernel_takes

    return jax.default_backend() == "tpu" and kernel_takes(d, dtype)


@jax.named_scope("xent")
def _fused_clm_loss_and_metrics(hidden, head, tokens, loss_mask, valid_v,
                                tiles=None, interpret=False):
    """The kernel path: every position is a row (a free reshape where
    ``hidden[:, :-1]`` is a copy); a sequence's last position has no label
    and weighs nothing. ``tiles`` and ``interpret`` are the tests' (small
    tiles through the Pallas interpreter on the CPU)."""
    from distributed_lion_tpu.ops.pallas_xent import (
        fused_xent,
        row_groups,
        tiles_for,
    )
    from distributed_lion_tpu.train import journal

    b, t, d = hidden.shape
    v = valid_v if valid_v > 0 else head.shape[0]
    tiles = tiles or tiles_for(b * t, v, d)
    groups, per_group = row_groups(b * t, d, tiles[0])
    pad_rows = -(b * t) % tiles[0]
    name = jnp.dtype(hidden.dtype).name
    journal.resolved(
        "xent_resolved",
        f"[setup] cross-entropy: tied head auto -> pallas_fused_xent (rows "
        f"{b * t}, vocab {v}, d {d}, {name}, tiles %dx%d, {groups} groups of "
        f"{per_group} row blocks, pad rows {pad_rows})" % tiles,
        impl="pallas_fused_xent", rows=b * t, vocab=v, d=d, dtype=name,
        tiles="%dx%d" % tiles, groups=groups, blocks_per_group=per_group,
        pad_rows=pad_rows)
    last = jnp.zeros((b, 1), jnp.float32)
    labels = jnp.concatenate(
        [tokens[:, 1:], last.astype(tokens.dtype)], axis=1).reshape(-1)
    mask = (jnp.ones((b, t - 1), jnp.float32) if loss_mask is None
            else loss_mask[:, 1:].astype(jnp.float32))
    mask = jnp.concatenate([mask, last], axis=1).reshape(-1)
    nll, pred = fused_xent(hidden.reshape(b * t, d),
                           head.astype(hidden.dtype), labels, valid_v, tiles,
                           interpret)
    return _masked_mean_metrics(nll, pred == labels, mask)


def _head_logits(hidden, head, layout, valid_v):
    """The float32 logits as the family's ``*_apply`` forms them: the head
    as it lies (``vd``: ``[V, d]``, ``dv``: ``[d, V]``), a padded head's
    alignment columns sliced off."""
    eq = "btd,vd->btv" if layout == "vd" else "btd,dv->btv"
    with jax.named_scope("head"):
        logits = jnp.einsum(eq, hidden, head.astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
    return logits[..., :valid_v] if valid_v > 0 else logits


def head_path(layout: str, d: int, dtype, *, chunks: int = 0,
              vocab_axis: str | None = None,
              seq_axis: str | None = None) -> str:
    """Which loss head :func:`clm_head_loss` runs for a head lying as
    ``layout`` under hidden states of width ``d`` and ``dtype``: one of
    ``fused | dense | chunked | tp_vocab | seq | seq_chunked`` (the module
    doc's table). Loud on the two combinations that are not wired."""
    if layout not in ("vd", "dv"):
        raise ValueError(f"layout must be 'vd' or 'dv', got {layout!r}")
    if vocab_axis and chunks > 0:
        raise NotImplementedError(
            "--tp_vocab and --vocab_chunks are alternative head strategies "
            "(vocab sharded across ranks vs streamed in chunks); pick one")
    if vocab_axis and seq_axis:
        raise NotImplementedError(
            "--tp_vocab under --seq_parallel is not wired; pick one")
    if vocab_axis:
        return "tp_vocab"
    if seq_axis:
        return "seq_chunked" if chunks > 0 else "seq"
    if chunks > 0:
        return "chunked"
    if layout == "vd" and fused_kernel_applies(d, dtype):
        return "fused"
    return "dense"


def clm_head_loss(
    hidden: jnp.ndarray,
    head: jnp.ndarray,
    tokens: jnp.ndarray,
    *,
    layout: str,
    loss_mask: jnp.ndarray | None = None,
    valid_v: int = 0,
    chunks: int = 0,
    vocab_axis: str | None = None,
    seq_axis: str | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Shift-by-one CLM loss from FINAL HIDDEN STATES ``[B, T, d]`` and the
    head as it lies (``layout`` ``vd``: ``[V, d]``, a tied embedding;
    ``dv``: ``[d, V]``, an lm_head), with the return contract of
    models/loss.clm_loss_and_metrics (a masked position gives no loss and
    no gradient). ``valid_v`` marks a padded head's alignment columns,
    ``chunks`` streams the vocabulary, ``vocab_axis`` names the mesh axis
    the head's vocabulary is sharded over (``head`` is then this rank's
    shard) and ``seq_axis`` the one the tokens are (``tokens`` this shard's
    chunk; the loss then differentiates as ``local_nll_sum /
    global_token_count``, as models/loss.clm_loss_seq_parallel says).
    :func:`head_path` picks the implementation."""
    from distributed_lion_tpu.models.loss import (
        clm_loss_and_metrics,
        clm_loss_seq_parallel,
    )

    path = head_path(layout, hidden.shape[-1], hidden.dtype, chunks=chunks,
                     vocab_axis=vocab_axis, seq_axis=seq_axis)
    if path in ("seq", "seq_chunked") and loss_mask is not None:
        raise NotImplementedError(
            "a loss mask under a sequence axis is not wired (the shard "
            "boundary's label protocol carries none)")
    if path == "tp_vocab":
        # a [V/tp, d] shard of a tied embedding is the head's [d, V/tp]
        # column slice transposed
        shard = head.T if layout == "vd" else head
        return _shifted_clm_metrics(
            lambda h, lab: tp_vocab_xent(h, shard, lab, vocab_axis, valid_v),
            hidden, tokens, loss_mask)
    if path == "seq_chunked":
        return chunked_clm_loss_seq_parallel(
            hidden, head, tokens, chunks, seq_axis, layout, valid_v)
    if path == "seq":
        return clm_loss_seq_parallel(
            _head_logits(hidden, head, layout, valid_v), tokens, seq_axis)
    if path == "chunked":
        return _shifted_clm_metrics(
            lambda h, lab: chunked_softmax_xent(h, head, lab, chunks, layout,
                                                valid_v),
            hidden, tokens, loss_mask)
    if path == "fused":
        return _fused_clm_loss_and_metrics(hidden, head, tokens, loss_mask,
                                           valid_v)
    return clm_loss_and_metrics(
        _head_logits(hidden, head, layout, valid_v), tokens, loss_mask)
