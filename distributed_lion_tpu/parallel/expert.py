"""Expert parallelism: Switch-style MoE FFN with all_to_all dispatch.

Net-new vs the reference (data-parallel only, SURVEY §2.7), designed for the
TPU fabric rather than ported: experts live sharded over the ``expert`` mesh
axis, and each device's tokens reach their experts through exactly two
``lax.all_to_all`` collectives (dispatch + return) riding ICI — the standard
TPU MoE layout (tokens stay in fixed-capacity buffers, every shape static,
no host-side routing).

Routing is top-1 ("Switch Transformer"): per-token argmax over a learned
gate, fixed per-expert capacity ``ceil(cf * N / E)`` with overflow dropped
(the residual path carries dropped tokens unchanged), and the usual
load-balancing auxiliary loss. All arithmetic is batched einsums over
[tokens, experts, capacity] one-hot masks — MXU-friendly, autodiff-clean.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from distributed_lion_tpu.parallel.mesh import EXPERT_AXIS


def moe_init(key, n_experts: int, d_model: int, d_ff: int, dtype=jnp.float32):
    """Gate + per-expert FFN params. Shard the ``w_/b_`` leaves over the
    expert axis with :func:`moe_param_specs`; the gate stays replicated."""
    kg, ki, ko = jax.random.split(key, 3)
    init = jax.nn.initializers.normal(0.02)
    return {
        "gate": init(kg, (d_model, n_experts), dtype),
        "w_in": init(ki, (n_experts, d_model, d_ff), dtype),
        "b_in": jnp.zeros((n_experts, d_ff), dtype),
        "w_out": init(ko, (n_experts, d_ff, d_model), dtype),
        "b_out": jnp.zeros((n_experts, d_model), dtype),
    }


def moe_param_specs(tensor: bool = False):
    """Expert banks over the 'expert' axis; ``tensor=True`` ADDITIONALLY
    Megatron-splits each expert's FFN over the tensor axis (w_in column-
    parallel on d_ff, w_out row-parallel — the same split as a dense MLP,
    batched over the expert dim). The gate and b_out stay replicated over
    tensor (b_out is added AFTER the row-parallel psum in moe_ffn)."""
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS

    e = EXPERT_AXIS
    if not tensor:
        return {
            "gate": P(),
            "w_in": P(e), "b_in": P(e),
            "w_out": P(e), "b_out": P(e),
        }
    t = TENSOR_AXIS
    return {
        "gate": P(),
        "w_in": P(e, None, t), "b_in": P(e, t),   # [E, d, f/tp], [E, f/tp]
        "w_out": P(e, t, None), "b_out": P(e),    # [E, f/tp, d]
    }


def capacity(n_tokens: int, n_experts: int, capacity_factor: float) -> int:
    return max(1, math.ceil(capacity_factor * n_tokens / n_experts))


def moe_ffn(
    params,
    x: jnp.ndarray,
    *,
    capacity_factor: float = 1.25,
    axis_name: Optional[str] = EXPERT_AXIS,
    capacity_override: Optional[int] = None,
    tp_axis: Optional[str] = None,
    valid: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
    stats_axis: Optional[str] = None,
    stats_lanes: Optional[int] = None,
    balance_tokens: Optional[jnp.ndarray] = None,
    balance_axis: Optional[str] = None,
    return_tallies: bool = False,
):
    """Apply the MoE FFN to local tokens ``x [N, D]``.

    Under ``shard_map`` with ``axis_name`` bound, ``params['w_in']`` etc.
    hold only this shard's experts ``[E_local, ...]`` while the gate scores
    ALL ``E = E_local * shards`` experts; tokens travel over the fabric.
    With ``axis_name=None`` (or axis size 1) it is the single-device
    reference semantics — same routing, same drops, no collectives.

    ``tp_axis`` (ep × tp): each expert's FFN is ADDITIONALLY Megatron-split
    over the tensor axis — w_in column-parallel on d_ff, w_out row-parallel
    with one psum (moe_param_specs(tensor=True) layout). Routing/dispatch
    see the full D on every tensor rank (x is replicated over tensor), so
    the gate decisions and the expert all_to_all are identical across tp.

    ``valid`` (optional ``[N]`` bool) marks the lanes that carry real
    tokens — the serving engine's pad/sentinel lanes (right-padded bucketed
    prefill tails, inactive decode slots, left-pad offsets in batched
    generate) pass False. Invalid lanes are masked out of the gate
    assignment BEFORE the capacity one-hot, so a dead lane never occupies
    an expert-capacity slot and never perturbs which real tokens get
    dropped: a padded batch's routed assignment for its real tokens equals
    the unpadded batch's assignment at the same capacity (pinned by
    tests/test_moe_serve.py), and invalid lanes produce exact-zero output
    rows. ``valid=None`` (training) keeps every lane, bit-identical to the
    pre-mask code path.

    ``return_stats`` additionally returns a dict of routing-load scalars
    measured over the VALID lanes against the ``capacity_factor`` budget
    ``capacity(n, E, capacity_factor)`` — regardless of any
    ``capacity_override`` in effect, so the serving engine's no-drop
    override still reports how its traffic loads the Switch capacity
    budget: ``valid`` (real lanes routed), ``kept`` (of those, how many
    fit the per-expert budget), ``capacity_slots`` (E × budget). All f32
    scalars computable on-device with zero host syncs.

    ``stats_axis`` (batch-sharded serving, ISSUE 16): when the TOKEN batch
    is sharded over a mesh axis, each shard sees only its slice of the
    tick's lanes — the stats psum the per-expert counts over that axis and
    size the budget from the GLOBAL lane count, so capacity utilization /
    dropped rate stay global quantities, bit-equal to the unsharded run.
    Naively psumming the per-shard scalars is WRONG: ``capacity`` is a
    ceil, so per-shard budgets don't sum to the global budget.
    ``stats_lanes`` (static int) overrides that global lane count for
    dispatches whose shards carry FAKE lanes the unsharded run never had
    — the batch-sharded batch-1 prefill replays the prompt width on every
    group with non-owners all-invalid, so its budget must come from the
    true width, not ``n × shards``. Counts still psum (invalid lanes
    contribute zero), keeping stats bit-equal to the unsharded engine.

    ``balance_tokens`` (training ``--ep_dcn_pipeline``, ISSUE 16): an
    ``[E+1]`` f32 vector — per-expert routed-token counts plus the total
    lane count — substituted for the LOCAL token-load fraction in the aux
    loss. The differentiable gate-probability factor stays fresh and
    local; only the non-differentiable load estimate is replaced, which
    is what lets the trainer feed a globally-psummed (and, at depth > 0,
    ring-stale) load through the aux without adding a blocking collective
    to the backward pass. ``return_tallies`` additionally returns this
    step's fresh local ``[E+1]`` tally (stop-gradient) for the caller to
    aggregate. ``balance_tokens=None`` is bit-identical to the historical
    local-fraction aux; an all-zero tally (lane-count entry 0) is the
    ring's cold-start sentinel and falls back to the local fraction.
    ``balance_axis`` is the SYNCHRONOUS alternative (``--ep_dcn_pipeline
    0``): psum the raw tallies over that axis inside the forward before
    forming the load fraction — blocking, but exactly global-fresh; at
    axis size 1 it is the local aux bit for bit. Mutually exclusive with
    ``balance_tokens``.

    Returns ``(y [N, D], aux_loss scalar)`` (plus the stats dict when
    requested); add ``aux`=0.01*aux_loss`` to the train loss to balance
    expert load (Switch Transformer recipe).
    """
    # NF4/int8 frozen-weight serving (ops/quant): QuantizedTensor expert
    # banks dequantize into their einsum's producer fusion; dense leaves
    # (and every training call) pass through maybe_dequant untouched.
    # Dequant FIRST: under shard_map a quantized leaf's static .shape is
    # the GLOBAL shape, while the dequantized array has this shard's
    # local expert count — the only honest source for e_local.
    from distributed_lion_tpu.ops.quant import maybe_dequant

    w_in = maybe_dequant(params["w_in"], x.dtype)
    w_out = maybe_dequant(params["w_out"], x.dtype)
    b_in = maybe_dequant(params["b_in"], x.dtype)
    b_out = maybe_dequant(params["b_out"], x.dtype)

    n, d = x.shape
    ep = 1 if axis_name is None else lax.psum(1, axis_name)
    e_local = w_in.shape[0]
    n_experts = e_local * ep
    # capacity_override: incremental decode calls with tiny per-step token
    # counts (n = batch) would otherwise compute cap ≈ 1 and systematically
    # drop colliding tokens that training/prefill (n = B*T) never drops —
    # the decode paths pass cap = n so no token is ever dropped at
    # generation time (models/gpt2._decode_mlp documents the trade).
    cap = (capacity_override if capacity_override is not None
           else capacity(n, n_experts, capacity_factor))

    # --- route (every device scores the full expert set) ---
    logits = x @ maybe_dequant(params["gate"], x.dtype)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [N]
    gate_p = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    # Routing arithmetic stays in int32/float32 regardless of x.dtype:
    # bf16 can't represent integers > 256, so a bf16 cumsum would collide
    # ranks once an expert sees > 256 local tokens (tokens silently summed
    # into one dispatch slot). Only the final masks are cast to x.dtype.
    one_hot_i = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [N, E]
    if valid is not None:
        # dead lanes leave the assignment BEFORE the capacity cumsum: they
        # take no queue position, so real tokens' slots (and therefore
        # which real tokens overflow) are exactly the unpadded batch's
        one_hot_i = one_hot_i * valid.astype(jnp.int32)[:, None]
    pos = jnp.cumsum(one_hot_i, axis=0) * one_hot_i - 1  # slot in expert queue
    keep = (pos >= 0) & (pos < cap)
    slot = jax.nn.one_hot(pos.max(axis=-1), cap, dtype=x.dtype)  # [N, C]
    one_hot = one_hot_i.astype(x.dtype)
    mask = one_hot[:, :, None] * slot[:, None, :] * keep.max(-1)[:, None, None].astype(x.dtype)

    # --- load-balance aux loss (computed on pre-drop assignments) ---
    counts_f = one_hot_i.astype(jnp.float32).sum(axis=0)  # [E] real lanes
    if valid is None:
        n_lanes = jnp.float32(n)
        frac_probs = probs.mean(axis=0)
    else:
        # averages over the REAL lanes only — pads must not dilute the
        # load estimate (inference-only today, but the mask must not make
        # the auxiliary silently wrong if it is ever consumed)
        v32 = valid.astype(jnp.float32)
        n_lanes = v32.sum()
        frac_probs = (probs * v32[:, None]).sum(axis=0) \
            / jnp.maximum(n_lanes, 1.0)
    local_frac = counts_f / jnp.maximum(n_lanes, 1.0)
    if balance_tokens is not None:
        # the fed-in (global, possibly stale) load estimate replaces the
        # local one; gradients still flow through frac_probs only — the
        # token-count factor was never differentiable to begin with. An
        # all-zero tally (lane count 0) is the ring's cold-start sentinel:
        # until depth steps have launched there is no stale global load
        # yet, so the aux falls back to the fresh local fraction (every
        # real tally has lane count > 0 — a training batch is never empty)
        fed_frac = balance_tokens[:n_experts] \
            / jnp.maximum(balance_tokens[n_experts], 1.0)
        frac_tokens = jnp.where(balance_tokens[n_experts] > 0.0,
                                fed_frac, local_frac)
    elif balance_axis is not None:
        # synchronous global balance (--ep_dcn_pipeline 0): psum the raw
        # token tallies over the expert axis BEFORE forming the fraction —
        # a blocking collective in the forward, which is exactly what
        # depth 0 means. At axis size 1 the psums are identity, so this is
        # the local fraction bit for bit.
        frac_tokens = lax.psum(counts_f, balance_axis) \
            / jnp.maximum(lax.psum(n_lanes, balance_axis), 1.0)
    else:
        frac_tokens = local_frac
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)

    tallies = None
    if return_tallies:
        tallies = lax.stop_gradient(jnp.concatenate(
            [counts_f, jnp.reshape(jnp.asarray(n_lanes, jnp.float32), (1,))]))

    stats = None
    if return_stats:
        counts = counts_f
        n_stats = n
        if stats_axis is not None:
            counts = lax.psum(counts, stats_axis)
            n_stats = n * lax.psum(1, stats_axis)
        if stats_lanes is not None:
            n_stats = stats_lanes
        budget = capacity(n_stats, n_experts, capacity_factor)
        kept = jnp.minimum(counts, jnp.float32(budget)).sum()
        stats = {
            "valid": counts.sum(),
            "kept": kept,
            "capacity_slots": jnp.float32(n_experts * budget),
        }

    # --- dispatch: [E, C, D] buffers, tokens in their expert's slots ---
    dispatch = jnp.einsum("nec,nd->ecd", mask, x)
    if axis_name is not None and ep > 1:
        # split the expert axis across shards, concat arrivals along
        # capacity: [E, C, D] -> [E_local, S*C, D] in ONE all_to_all
        dispatch = lax.all_to_all(
            dispatch, axis_name, split_axis=0, concat_axis=1, tiled=True
        )

    # --- expert FFN (batched over this shard's experts) ---
    if tp_axis is not None:
        # Megatron f-operator: identity forward, psum backward — each
        # tensor rank's partial input-cotangent (from its w_in shard)
        # completes here, so upstream sees the full gradient
        from distributed_lion_tpu.parallel.tensor_parallel import (
            copy_to_tp_region,
            reduce_from_tp_region,
        )

        dispatch = copy_to_tp_region(dispatch, tp_axis)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", dispatch, w_in) + b_in[:, None, :]
    )
    out = jnp.einsum("ecf,efd->ecd", h, w_out)
    if tp_axis is not None:
        # g-operator: row-parallel partials psum to the full output; b_out
        # is replicated over tensor and must be added exactly once — AFTER
        # the psum (adding per rank would scale it by tp)
        out = reduce_from_tp_region(out, tp_axis)
    out = out + b_out[:, None, :]

    if axis_name is not None and ep > 1:
        # inverse: [E_local, S*C, D] -> [E, C, D] back on the token's shard
        out = lax.all_to_all(
            out, axis_name, split_axis=1, concat_axis=0, tiled=True
        )

    # --- combine: weight each token's slot by its gate probability ---
    y = jnp.einsum("nec,ecd->nd", mask * gate_p[:, None, None], out)
    if return_stats and return_tallies:
        return y, aux, stats, tallies
    if return_stats:
        return y, aux, stats
    if return_tallies:
        return y, aux, tallies
    return y, aux


# ------------------------------------------------- top-k dropless experts
# Beside the Switch layer above (top-1, capacity, one-hot dispatch; its
# tests stay): the routing of the DeepSeek-V3 line of models, as served.
# Every token goes to its ``top_k`` experts whatever the imbalance: the
# assignments are sorted by expert and the experts run as ONE grouped matmul
# over the sorted rows (O(tokens x top_k), no ``[E, tokens, D]`` buffer, no
# one-hot mask, no capacity), then the rows go back to their tokens and are
# summed with the routing weights; a shared expert sees every token. Single
# device: the layer holds every expert it routes over, or the range it is
# told it holds (``held``: one chip's share of an expert-parallel
# deployment; the exchange with the chips that hold the rest is not here).

MOE_COUNTERS = ("moe_assignments", "moe_experts_hit", "moe_load_max")


def sigmoid_topk_route(x, router, bias, top_k: int, scale: float,
                       groups: tuple = (1, 1)):
    """``s = sigmoid(float32(x) router^T)``; the ``top_k`` experts by
    ``s + bias`` (the correction bias moves the choice, never the weight);
    weights ``s[idx] / (sum s[idx] + 1e-20) * scale``. The matmul and the
    scores are float32 (``Precision.HIGHEST``: a TPU's default would round
    float32 operands to bfloat16). x [N, D], router [E, D], bias [E] ->
    idx [N, k] int32, w [N, k] float32.

    ``groups = (n_group, topk_group)``: group-limited selection. The experts
    are ``n_group`` runs of ``E / n_group``; a group's score is the sum of
    its two largest ``s + bias``; only experts of the ``topk_group`` best
    groups can be picked (the others' selection scores go to ``-inf``; the
    weights are still the picks' own ``s``). ``(1, 1)``, every other
    family's, is no limit and adds nothing to the program."""
    n_group, topk_group = groups
    with jax.named_scope("moe/route"):
        s = jax.nn.sigmoid(jnp.einsum(
            "nd,ed->ne", x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        choice = s + bias.astype(jnp.float32)
        if n_group > 1:
            with jax.named_scope("moe/groups"):
                n, e = choice.shape
                by_group = choice.reshape(n, n_group, e // n_group)
                score = lax.top_k(by_group, 2)[0].sum(-1)    # [N, n_group]
                _, best = lax.top_k(score, topk_group)
                kept = jnp.zeros((n, n_group), bool).at[
                    jnp.arange(n)[:, None], best].set(True)
                choice = jnp.where(kept[:, :, None], by_group,
                                   -jnp.inf).reshape(n, e)
        _, idx = lax.top_k(choice, top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scale


def softmax_topk_route(x, router, top_k: int):
    """``p = softmax(float32(x) router^T)`` over all ``E`` outputs; the
    ``top_k`` largest; weights ``p[idx] / sum p[idx]`` (``norm_topk_prob``).
    Float32 throughout, like :func:`sigmoid_topk_route`; no bias and no
    scale. x [N, D], router [E, D] -> idx [N, k] int32, w [N, k] float32
    (differentiable in x and router through the weights)."""
    with jax.named_scope("moe/route"):
        p = jax.nn.softmax(jnp.einsum(
            "nd,ed->ne", x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST), axis=-1)
        w, idx = lax.top_k(p, top_k)
        return idx, w / w.sum(-1, keepdims=True)


def _gmm_kernels(k: int, n: int):
    """``ops/pallas_moe_gmm`` where its kernels take these widths on this
    backend, else None."""
    from distributed_lion_tpu.ops import pallas_moe_gmm

    if jax.default_backend() == "tpu" and pallas_moe_gmm.kernel_takes(k, n):
        return pallas_moe_gmm
    return None


def _grouped_product(lhs, rhs, group_sizes, tail):
    kernels = _gmm_kernels(lhs.shape[1], rhs.shape[2])
    if kernels:
        return kernels.moe_gmm(lhs, rhs, group_sizes, tail=tail)
    return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, group_sizes, tail: bool = False):
    """Rows of ``lhs [M, K]`` sorted by group against ``rhs [E, K, N]``:
    the Mosaic kernel ``moe_gmm`` on a TPU where the widths are whole lane
    tiles, ``jax.lax.ragged_dot`` elsewhere (the CPU; the tests hold the
    two together). Chosen from what the call shows, like
    ``ops/attention.paged_kernel_applies``: no flag. Rows past the last
    group are undefined on the kernel's path.

    Its gradient is two more grouped products, on the same path as the
    forward: ``dlhs = dy rhs^T`` (this product against the banks
    transposed; zero in the rows past the last group, whatever ``dy``
    holds there) and ``drhs[g] = lhs_g^T dy_g`` accumulated in float32
    (``moe_gmm_drhs`` on a TPU; off it a ``ragged_dot`` whose ragged
    dimension is the contraction)."""
    return _grouped_product(lhs, rhs, group_sizes, tail)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, tail):
    return _grouped_product(lhs, rhs, group_sizes, tail), \
        (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(tail, res, dy):
    lhs, rhs, group_sizes = res
    dy = dy.astype(lhs.dtype)
    dlhs = _grouped_product(dy, jnp.swapaxes(rhs, 1, 2), group_sizes, tail)
    grouped = jnp.arange(lhs.shape[0]) < group_sizes.sum()
    dlhs = jnp.where(grouped[:, None], dlhs, 0)
    kernels = _gmm_kernels(lhs.shape[1], dy.shape[1])
    if kernels:
        drhs = kernels.moe_gmm_drhs(lhs, dy, group_sizes)
    else:
        drhs = lax.ragged_dot_general(
            lhs, dy, group_sizes.astype(jnp.int32),
            lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            preferred_element_type=jnp.float32)
    return dlhs, drhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.custom_vjp
def _sorted_rows(x, order):
    """``x[order // k]``: every token's row once a pick, in the sorted
    order (``k = len(order) / len(x)``). Its transpose as autodiff writes
    it is a scatter-add over rows that repeat; ``order`` is a permutation,
    so it is a gather by the inverse and a sum of ``k`` rows."""
    return x[order // (order.shape[0] // x.shape[0])]


def _sorted_rows_fwd(x, order):
    return _sorted_rows(x, order), (order, x.shape[0])


def _sorted_rows_bwd(res, g):
    order, n = res
    dx = g[jnp.argsort(order)].reshape(n, -1, g.shape[1])
    return dx.astype(jnp.float32).sum(1).astype(g.dtype), None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


@jax.custom_vjp
def _unsorted_rows(y, order, back):
    """``y[back]``: the sorted rows back in pick order; the transpose of a
    permutation is the gather by its inverse."""
    return y[back]


def _unsorted_rows_fwd(y, order, back):
    return y[back], order


def _unsorted_rows_bwd(order, g):
    return g[order], None, None


_unsorted_rows.defvjp(_unsorted_rows_fwd, _unsorted_rows_bwd)


# Rows a trip of the combine's bounded transpose takes. A multiple of the
# grouped kernels' deepest tile of rows (``pallas_moe_gmm.TILE_M_DRHS``), so
# every tile a kernel visits lies inside the chunks that were written; above
# the decode ticks' picks (640 and 1,024 a tick at the serving cells).
ROW_CHUNK = 2048


def _chunks_below(count):
    """Whole chunks of ``ROW_CHUNK`` sorted rows that hold a row below
    ``count``, and never none: a grouped kernel visits a tile even for an
    empty group, and what it reads there must be finite."""
    return jnp.maximum((count + ROW_CHUNK - 1) // ROW_CHUNK, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine_held(y, w, order, back, flat, groups: int):
    """The combine of a layer that holds a range of its experts: the sorted
    rows ``y [N k, D]`` back in pick order (``y[back]``), the picks held
    elsewhere and the lanes with no token masked (``flat [N k]``, a pick's
    group, is ``groups`` for those: their rows are undefined), and summed a
    token with the routing weights ``w [N, k]`` in float32. As the plain
    program writes it, so a forward pass is the plain program's.

    Its transpose is bounded by the rows in groups. Autodiff would build the
    ``[N, k, D]`` product of the cotangent and the weights, mask it, gather
    all ``N k`` rows of it into sorted order, and gather ``y`` by ``back``
    once more for the weights' gradient: two of the layer's five row moves a
    step, three quarters of it rows nothing reads where a quarter of the
    experts is held. Here a loop takes the chunks of ``ROW_CHUNK`` sorted
    rows that hold a row of a group (its trip count is the count of live
    picks, read on the device) and no other: ``dy[j] = dout[order[j] // k]
    w_j`` gathered from the ``[N, D]`` cotangent, cast as the product was,
    and written over ``y``'s chunk, whose rows the same trip has just read
    for ``dw_j = sum_d dout[.., d] y[j, d]``. No second ``[N k, D]`` buffer
    is held. Rows of ``dy`` past the chunks taken are ``y``'s, undefined
    there; ``grouped_matmul``'s gradient reads none of them."""
    n, k = w.shape
    rows = _unsorted_rows(y, order, back).reshape(n, k, -1)
    rows = jnp.where((flat < groups).reshape(n, k, 1), rows, 0)
    return jnp.einsum("nkd,nk->nd", rows.astype(jnp.float32), w)


def _combine_held_fwd(y, w, order, back, flat, groups):
    return _combine_held(y, w, order, back, flat, groups), \
        (y, w, order, back, flat < groups)


def _combine_held_bwd(groups, res, dout):
    y, w, order, back, live = res
    k = w.shape[1]
    m = y.shape[0]
    flat_w = w.reshape(-1)

    def trip(i, carry):
        # ``rows`` holds y where no trip has been and dy where one has (the
        # rows before ``first`` of a last, part chunk were the trip's
        # before and are left as they are)
        rows, dscale = carry
        first = i * ROW_CHUNK
        start = jnp.minimum(first, m - ROW_CHUNK)
        fresh = start + jnp.arange(ROW_CHUNK) >= first
        picks = lax.dynamic_slice_in_dim(order, start, ROW_CHUNK)
        g = dout[picks // k]                              # [chunk, D] float32
        was = lax.dynamic_slice_in_dim(rows, start, ROW_CHUNK)
        dy = (g * flat_w[picks][:, None]).astype(y.dtype)
        rows = lax.dynamic_update_slice_in_dim(
            rows, jnp.where(fresh[:, None], dy, was), start, 0)
        ds = jnp.where(fresh, (g * was.astype(jnp.float32)).sum(-1),
                       lax.dynamic_slice_in_dim(dscale, start, ROW_CHUNK))
        return rows, lax.dynamic_update_slice_in_dim(dscale, ds, start, 0)

    dy, dscale = lax.fori_loop(
        0, _chunks_below(live.sum().astype(jnp.int32)), trip,
        (y, jnp.zeros((m,), jnp.float32)))
    dw = jnp.where(live, dscale[back], 0).reshape(w.shape)
    return dy, dw.astype(w.dtype), None, None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def _swiglu_limited(gate, up, limit: float):
    """``silu(gate) * up``; with a ``limit`` > 0 the gate is clamped from
    above and the up-projection to ``[-limit, limit]`` first (a per-layer
    SwiGLU clamp some configurations list; 0 is no clamp and no op)."""
    if limit > 0:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def moe_dropless_ffn(params, x, *, top_k: int, scale: float, valid=None,
                     return_counters: bool = False, held=None,
                     route_groups: tuple = (1, 1),
                     limits: tuple = (0.0, 0.0)):
    """``sum_i w_i E_idx_i(x) + E_shared(x)`` for local tokens ``x [N, D]``,
    every ``E`` a SwiGLU ``(silu(x W_g) * (x W_u)) W_d``.

    ``params``: ``router [E, D]``, ``bias [E]`` (float32), the banks
    ``w_gate`` / ``w_up`` ``[E, D, F]`` and ``w_down [E, F, D]``, and
    ``shared`` (``w_gate``, ``w_up``, ``w_down`` of one expert every token
    takes). What the tree holds says which layer it is: with a ``bias`` the
    route is :func:`sigmoid_topk_route`, without one
    :func:`softmax_topk_route` (``scale`` and ``route_groups`` are the
    sigmoid route's); without ``shared`` no shared expert is added. The
    layer is differentiable (the trainer's expert layer): the sort and its
    inverse transpose as gathers, the grouped products through
    :func:`grouped_matmul`'s own gradient. ``valid`` (optional ``[N]`` bool): pad and inactive lanes sort
    behind every group, so no expert runs them, and give zero rows.

    ``held = (first, count)``: the layer is told which experts it holds
    (one chip's share of an expert-parallel deployment). The router still
    scores all ``E`` and a token still picks ``top_k`` of them, weighted
    over all its picks; the banks are the ``count`` experts from ``first``
    on, a pick of any other expert sorts behind every group like an invalid
    lane (no bank read, no row computed) and adds nothing: the result is
    this share's part of the layer, the shared expert included. Default:
    every expert is held.

    The layer takes all of ``x`` in one pass, so the banks are read once
    a call whatever its length. What bounds a call's memory is the sorted
    rows and the rows gathered back (bfloat16 ``[N top_k, D]``: 0.5 GB each
    for an 8,192-token prefill at top 10 of 3,072); the combine's float32
    ``[N, top_k, D]`` lives inside one fusion and is never held
    (tests/test_chip_compile.py looks for it among the buffers).

    Row traffic: a forward pass moves every pick's row twice (the sort's
    gather, the combine's), held or not; so does the sort's transpose. The
    combine's transpose is bounded by the rows in groups where the call
    says rows lie past the last group (``held``) and has a chunk of picks
    or more (``N top_k >= ROW_CHUNK``, a static shape): :func:`_combine_held`
    takes the whole chunks of sorted rows that hold a row of a group and no
    other, and holds no second ``[N top_k, D]`` buffer. Every other call's
    transpose is autodiff's, and every forward program is the one it was.

    ``return_counters``: also a dict of int32 scalars over the valid lanes
    (``MOE_COUNTERS``, ``moe_routed`` and ``moe_rows_moved``): rows
    computed here (tokens x top_k where every expert is held: none is ever
    dropped), distinct experts hit, the most rows at one expert, the picks
    made, held or not (tokens x top_k), and the sorted rows the combine's
    transpose takes for this call (the whole chunks that hold a row of a
    group; ``N top_k``, pad lanes included, where it is not bounded).

    ``route_groups``: group-limited selection
    (:func:`sigmoid_topk_route`'s ``groups``).
    ``limits = (routed, shared)``: the SwiGLU clamps of the routed experts
    and of the shared one (:func:`_swiglu_limited`; 0 = none)."""
    n, d = x.shape
    n_experts = params["router"].shape[0]
    groups = n_experts if held is None else held[1]
    if "bias" in params:
        idx, w = sigmoid_topk_route(x, params["router"], params["bias"],
                                    top_k, scale, route_groups)
    else:
        idx, w = softmax_topk_route(x, params["router"], top_k)
    # rows lie past the last group and the call has a chunk of picks or more:
    # the combine's transpose takes the rows in groups alone
    bounded = held is not None and n * top_k >= ROW_CHUNK
    with jax.named_scope("moe/sort"):
        flat = idx.reshape(-1)
        if held is not None:
            # a pick of an expert held elsewhere sorts past the last group
            local = flat - held[0]
            flat = jnp.where((local >= 0) & (local < groups), local, groups)
        if valid is not None:
            # a lane with no token sorts past the last expert's rows
            flat = jnp.where(jnp.repeat(valid, top_k), flat, groups)
        order = jnp.argsort(flat)            # stable: ties keep token order
        ends = jnp.searchsorted(flat[order], jnp.arange(groups + 1))
        sizes = jnp.diff(ends).astype(jnp.int32)       # [E] rows an expert
        rows = _sorted_rows(x, order)                  # [N k, D], by expert
    with jax.named_scope("moe/experts"):
        # a held range leaves the picks held elsewhere past the last group
        tail = held is not None
        h = _swiglu_limited(
            grouped_matmul(rows, params["w_gate"], sizes, tail),
            grouped_matmul(rows, params["w_up"], sizes, tail), limits[0])
        y = grouped_matmul(h, params["w_down"], sizes, tail)
    with jax.named_scope("moe/combine"):
        back = jnp.argsort(order)            # each token's k rows, in order
        if bounded:
            # rows past the last group are undefined: picks held elsewhere
            # and lanes with no token alike
            out = _combine_held(y, w, order, back, flat, groups)
        else:
            y = _unsorted_rows(y, order, back).reshape(n, top_k, d)
            if held is not None:
                y = jnp.where((flat < groups).reshape(n, top_k, 1), y, 0)
            elif valid is not None:
                y = jnp.where(valid[:, None, None], y, 0)
            out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), w)
    if "shared" in params:
        with jax.named_scope("moe/shared"):
            from distributed_lion_tpu.models.llama import _matmul, _mlp

            sh = params["shared"]
            out = out + (_mlp(x, sh) if limits[1] <= 0 else _matmul(
                _swiglu_limited(_matmul(x, sh["w_gate"]),
                                _matmul(x, sh["w_up"]), limits[1]),
                sh["w_down"]))
    out = out.astype(x.dtype)
    if valid is not None:
        out = jnp.where(valid[:, None], out, 0)
    if not return_counters:
        return out
    lanes = n if valid is None else valid.sum()
    moved = jnp.minimum(_chunks_below(sizes.sum()) * ROW_CHUNK, n * top_k) \
        if bounded else n * top_k
    return out, {"moe_assignments": sizes.sum(),
                 "moe_experts_hit": (sizes > 0).sum().astype(jnp.int32),
                 "moe_load_max": sizes.max(),
                 "moe_routed": jnp.asarray(lanes * top_k, jnp.int32),
                 "moe_rows_moved": jnp.asarray(moved, jnp.int32)}
