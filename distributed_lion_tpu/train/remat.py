"""What the per-block checkpoint saves, picked from the shapes and the
chip's memory (``remat_policy='auto'``).

A block is wrapped one of three ways (models/gpt2.py, models/llama.py), the
rungs of this module, cheapest backward first:

- ``none``: the plain ``_block``. The backward reads every residual the
  forward produced; nothing is computed twice.
- ``dots``: ``jax.checkpoint`` keeping the matmul outputs. The backward runs
  the attention kernel and the elementwise work again.
- ``full``: ``jax.checkpoint`` keeping nothing but the block's input. The
  backward runs the block's whole forward again (a quarter of the blocks'
  time at GPT-2 124M, PERF.md section 5).

Three blocks are counted, told apart by what their configuration holds: a
fused-qkv GELU block (no ``d_ff``), a GQA SwiGLU block (``d_ff``) and a GQA
block whose FFN is a dropless top-k expert layer (``top_k``,
:func:`expert_block_saved_bytes`).

:func:`resolve` takes the first rung whose predicted peak is at most
``MEMORY_SHARE`` of the device's ``bytes_limit``. The prediction is a
closed-form count, no compile and no trial run: what one block keeps for
its backward (:func:`block_saved_bytes`, from ``_block``'s own tensors),
times the layers, plus the recompute's working set and the fixed part
(:func:`fixed_bytes`: parameters, optimizer state, the gradient
accumulator, the loss head's workspace, the vote's buffers). The counts are
held to the chip's compiler by ``tests/test_chip_compile.py`` (a described
v5e's ``memory_analysis()`` of the blocks under each rung) and to the chip
by ``peak_hbm_gb.train`` (PERF.md section 6, PR 31).

What the count does not model resolves to ``full``, the behaviour before
there was an ``auto``: Switch-MoE blocks, a pipeline or sequence axis, and
a backend that reports no ``bytes_limit`` (the CPU): :func:`resolve_for`
reads each from the config, the mesh and the device, and the decision's
``unmodelled`` says which. No model is known here by name.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

RUNGS = ("none", "dots", "full")

# The share of ``bytes_limit`` a predicted peak may take. The rest is the
# allocator's head-room (a program's temporaries are one contiguous
# reservation: fragments of freed buffers do not serve it), the inputs of
# the two steps the host runs ahead, whatever else the process keeps on the
# device (an eval program, a checkpoint's staging copy of the parameters:
# 0.5 GB at 124M), and the count's own error: the described v5e's compiler
# reads within 8% of it at both training cells' shapes and the chip read 3%
# above it (cell 1) and 4% below (cell 4; PERF.md section 6, PR 31), and
# 0.72 x 1.15 still leaves a sixth of the device free.
MEMORY_SHARE = 0.72

# Bytes a parameter that the majority vote holds on a worker while it runs
# (sign ballots, the packed wire's send and receive buffers, the unpacked
# tallies, the elected signs and the previous ballot): they exist only when
# world > 1. Measured, not derived: cell 4's peak under `full`, 5.45 GB
# (ledger, PR 30), less its 1.5 GB of live state and the 1.39 GB of
# temporaries the same step compiles to without a vote (described v5e,
# PR 31), over 124.4 M parameters: 21. Since PR 38 the Lion kernels take
# every leaf where it lies and the step no longer stages flat float32
# copies of parameters, gradients and momentum beside the ballots: cell 4's
# peak fell 5.627 -> 5.447 GB at the same rung (my chip runs, PR 38), 1.45
# bytes a parameter, so 19.6, held at 20 (what is left: a bucket's int8
# ballots and verdict, the packed wire's buffers, the unpacked tallies).
VOTE_BYTES_PER_PARAM = 20


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def block_saved_bytes(model_cfg, rows: int, seq: int, *, tp: int = 1,
                      attn: str = "kernel") -> dict:
    """Bytes ONE block keeps for its backward under each rung, on one
    device, at a microbatch of ``rows`` sequences of ``seq`` tokens. Counted
    from the tensors ``_block`` produces, in units of one activation
    ``U = rows x seq x d_model`` in the compute dtype; ``tp`` divides the
    tensors that live head- or column-parallel. What the compiler
    recomputes inside a fusion for free is not counted (the layer norms'
    outputs, the activation function's output, grouped keys and values
    repeated to the query heads: the described v5e's ``memory_analysis()``
    shows none of them).

    GPT-2 (``models/gpt2._block``), ``none``: the block's input, the fused
    q/k/v projection (3), the attention's output and its softmax
    statistics, the second residual, the MLP's pre-activation (``d_ff / d``
    = 4). ``dots``: the input and the matmul outputs the backward reads
    (q/k/v, the attention projection's output, the pre-activation).
    ``full``: the input. Llama (``models/llama._block``) keeps q, the
    grouped k and v, and the gate and up projections.

    ``attn`` says which attention the shapes reach (``ops/attention``):
    ``kernel``, the repo's (one float32 statistic a row and head);
    ``library``, jax's bundled kernel on the head-major path (two
    statistics, each spread over 128 lanes); ``xla``, materialized scores
    (the float32 probabilities ``[rows, heads, seq, seq]`` and their copy
    in the compute dtype)."""
    size = _itemsize(model_cfg.compute_dtype)
    u = rows * seq * model_cfg.d_model * size
    rows_heads = rows * (model_cfg.n_head // tp) * seq
    stat = {"kernel": 4, "library": 2 * 128 * 4,
            "xla": seq * (4 + size)}[attn] * rows_heads
    d_ff = getattr(model_cfg, "d_ff", None)
    if d_ff is None:                       # GPT-2: fused qkv, GELU MLP of 4 d
        qkv, mlp = 3.0, 4.0
    else:                                  # Llama: GQA, SwiGLU (gate and up)
        qkv = 1.0 + 2.0 * model_cfg.n_kv_head / model_cfg.n_head
        mlp = 2.0 * d_ff / model_cfg.d_model
    none = u * (2.0 + (qkv + 1.0 + mlp) / tp) + stat
    dots = u * (2.0 + (qkv + mlp) / tp)
    return {"none": int(none), "dots": int(dots), "full": int(u)}


def expert_block_saved_bytes(model_cfg, rows: int, seq: int) -> dict:
    """:func:`block_saved_bytes` for a block of grouped-query attention
    (separate q, k, v of ``head_dim`` lanes a head, q and the attention's
    output ``n_head x head_dim`` wide, the repo's kernel: one float32
    statistic a row and head) and a dropless ``top_k`` expert layer
    (``parallel/expert.moe_dropless_ffn``), in the same unit ``U``. The
    expert layer keeps, a pick and not a token: the sorted rows
    ``[tokens x top_k, d]``, the gate and up products ``[tokens x top_k,
    moe_d_ff]`` each and the expert outputs (back in pick order, or as they
    lie sorted where the combine's transpose is bounded by the rows in
    groups: the combine's gradient in the routing weights reads them); the
    grouped products are kernels with their own gradient, so ``dots`` keeps
    of the expert layer nothing but the router's float32 logits. A held
    range changes none of the buffers: the picks held elsewhere still have
    their rows in each. What it changes is the traffic of the combine's
    transpose, which takes the rows in groups alone
    (``parallel/expert._combine_held``)."""
    size = _itemsize(model_cfg.compute_dtype)
    d, k = model_cfg.d_model, model_cfg.top_k
    u = rows * seq * d * size
    q = model_cfg.n_head * model_cfg.head_dim / d
    kv = 2.0 * model_cfg.n_kv_head * model_cfg.head_dim / d
    stat = 4 * rows * model_cfg.n_head * seq
    logits = 4 * rows * seq * model_cfg.n_experts
    picks = k * (2.0 + 2.0 * model_cfg.moe_d_ff / d)
    none = u * (2.0 + 2.0 * q + kv + picks) + stat + logits
    dots = u * (2.0 + q + kv) + logits
    return {"none": int(none), "dots": int(dots), "full": int(u)}


def expert_backward_bytes(model_cfg, rows: int, seq: int) -> int:
    """What the expert layer's backward holds at once beside what its
    forward kept, under every rung: the cotangents of the outputs in pick
    order and sorted, and of the sorted rows, ``[tokens x top_k, d]``
    each. The plain program's count: a combine whose transpose is bounded
    by the rows in groups gathers it from the ``[tokens, d]`` cotangent,
    never builds the one in pick order and writes the sorted one over the
    outputs, so a held range of a chunk of picks or more runs under this."""
    return 3 * model_cfg.top_k * rows * seq * model_cfg.d_model \
        * _itemsize(model_cfg.compute_dtype)


def head_bytes(model_cfg, rows: int, seq: int, *, fused: bool,
               vocab_shards: int = 1, vocab_chunks: int = 0) -> int:
    """The loss head's workspace, from ``ops/xent``'s shapes. The fused
    kernel pair (``fused=True``: ``ops/xent.head_path`` said ``fused``) keeps
    the logits in VMEM and writes two float32 partial gradients of the head
    ``[V, d]`` beside the hidden states and their cotangent; every other
    head holds the float32 logits and their cotangent ``[rows, seq, V]``,
    a chunk of them under ``vocab_chunks``, a shard under ``tp_vocab``."""
    v = getattr(model_cfg, "padded_vocab", model_cfg.vocab_size)
    u = rows * seq * model_cfg.d_model * _itemsize(model_cfg.compute_dtype)
    if fused:
        return 2 * v * model_cfg.d_model * 4 + 2 * u
    cols = v // max(1, vocab_shards) // max(1, vocab_chunks)
    return 2 * rows * seq * cols * 4 + 2 * u


def fixed_bytes(*, n_params: int, compute_dtype, param_dtype,
                state_bytes: int, head: int, world: int,
                frozen_bytes: int = 0) -> int:
    """Everything on one device that does not grow with the layers' saved
    activations: the parameters and the optimizer's state (``state_bytes``:
    Lion's momentum, AdamW's two moments), the frozen trees, the float32
    gradient accumulator and one microbatch's float32 gradient, the
    parameters' copy in the compute dtype (hoisted out of the accumulation
    loop), the loss head's workspace and, across workers, the vote's
    buffers."""
    cast = (_itemsize(compute_dtype)
            if jnp.dtype(compute_dtype) != jnp.dtype(param_dtype) else 0)
    vote = VOTE_BYTES_PER_PARAM if world > 1 else 0
    return (n_params * (_itemsize(param_dtype) + 2 * 4 + cast + vote)
            + state_bytes + frozen_bytes + head)


def predicted_peaks(saved: dict, n_layer: int, fixed: int) -> dict:
    """Peak bytes under each rung: the fixed part, every layer's saved
    tensors, and under ``dots`` and ``full`` the one block being recomputed
    (its backward holds what ``none`` would have saved for it)."""
    return {rung: fixed + n_layer * saved[rung]
            + (saved["none"] - saved[rung]) for rung in RUNGS}


@dataclasses.dataclass(frozen=True)
class RematDecision:
    """What ``auto`` resolved to and from what: the ``remat_resolved``
    journal event's fields and the ``[setup] remat:`` line."""

    rung: str
    predicted: dict                       # rung -> bytes; {} when unmodelled
    bytes_limit: Optional[int]
    unmodelled: str = ""                  # why the count does not apply

    def line(self) -> str:
        if self.unmodelled:
            return (f"[setup] remat: {self.rung} (auto does not model "
                    f"{self.unmodelled})")
        gb = {r: b / 1e9 for r, b in self.predicted.items()}
        others = ", ".join(f"{r} {gb[r]:.1f}" for r in RUNGS
                           if r != self.rung)
        return (f"[setup] remat: {self.rung} (predicted {gb[self.rung]:.1f} "
                f"of {self.bytes_limit / 1e9:.2f} GB; {others})")

    def fields(self) -> dict:
        return {"rung": self.rung, "bytes_limit": self.bytes_limit,
                "unmodelled": self.unmodelled,
                **{f"predicted_{r}": b for r, b in self.predicted.items()}}


def resolve(saved: dict, n_layer: int, fixed: int,
            bytes_limit: Optional[int], unmodelled: str = "") -> RematDecision:
    """The first of ``none``, ``dots``, ``full`` whose predicted peak is at
    most ``MEMORY_SHARE`` of ``bytes_limit``; ``full`` when none is (the
    smallest there is) and when the count does not apply."""
    if not unmodelled and not bytes_limit:
        unmodelled = "a backend that reports no bytes_limit"
    if unmodelled:
        return RematDecision("full", {}, bytes_limit, unmodelled)
    peaks = predicted_peaks(saved, n_layer, fixed)
    rung = next((r for r in RUNGS if peaks[r] <= MEMORY_SHARE * bytes_limit),
                "full")
    return RematDecision(rung, peaks, bytes_limit)


def resolve_for(cfg, model_cfg, mesh, params, *, bytes_limit: Optional[int],
                frozen=None, rows_per_sample: int = 1) -> RematDecision:
    """:func:`resolve` for a trainer about to be built: ``cfg`` its
    ``TrainConfig``, ``mesh`` its mesh, ``params`` the trained tree and
    ``frozen`` the trees held beside it (arrays or shapes); a sample is
    ``rows_per_sample`` rows of the microbatch."""
    from distributed_lion_tpu.ops import attention as attn_ops
    from distributed_lion_tpu.ops import xent as xent_ops
    from distributed_lion_tpu.parallel.mesh import (
        PIPE_AXIS,
        SEQ_AXIS,
        TENSOR_AXIS,
        data_axis_size,
    )

    shape = dict(mesh.shape)
    unmodelled = next((why for why, hit in (
        ("moe_experts > 0", getattr(model_cfg, "moe_experts", 0) > 0),
        ("pipeline_parallel > 1", shape.get(PIPE_AXIS, 1) > 1),
        ("seq_parallel > 1", shape.get(SEQ_AXIS, 1) > 1)) if hit), "")
    if unmodelled or not bytes_limit:
        return resolve({}, 0, 0, bytes_limit, unmodelled)
    tp = shape.get(TENSOR_AXIS, 1)
    rows = cfg.per_device_train_batch_size * rows_per_sample
    seq = cfg.block_size
    experts = hasattr(model_cfg, "top_k")
    gpt2 = not experts and not hasattr(model_cfg, "d_ff")
    # which attention and which loss head a TPU's `auto` takes at these
    # shapes (ops/attention, ops/xent: their own rules, asked, not copied):
    # GPT-2's fused projection reaches the repo's kernel, the head-major
    # entry the library's; attention dropout and `attn_impl='xla'`
    # materialize the scores
    if experts:
        from distributed_lion_tpu.ops import pallas_flash_attn

        if not pallas_flash_attn.gqa_train_kernel_takes(
                seq, model_cfg.head_dim, model_cfg.compute_dtype):
            return resolve({}, 0, 0, bytes_limit,
                           "grouped-query attention off the kernel pair")
        attn = "kernel"
    elif model_cfg.attn_impl != "auto" \
            or getattr(model_cfg, "dropout", 0.0) > 0:
        attn = "xla"
    elif gpt2 and attn_ops.qkv_kernel_applies(
            seq, model_cfg.n_head // tp, model_cfg.head_dim,
            model_cfg.compute_dtype):
        attn = "kernel"
    else:
        attn = "library" if attn_ops.library_kernel_applies(seq) else "xla"
    fused = xent_ops.head_path(
        "vd" if gpt2 or experts else "dv", model_cfg.d_model,
        model_cfg.compute_dtype,
        chunks=cfg.vocab_chunks,
        vocab_axis=TENSOR_AXIS if cfg.tp_vocab else None) == "fused"
    # a tensor axis splits the blocks' matrices; what stays whole
    # (embeddings, norms) is small beside them, and counting it split too
    # errs by less than the share's room
    n_local = sum(int(x.size) for x in jax.tree.leaves(params)) // tp
    if cfg.lion:
        state_bytes = n_local * _itemsize(cfg.mom_dtype
                                          or model_cfg.param_dtype)
    else:
        state_bytes = 2 * 4 * n_local // (
            data_axis_size(mesh) if cfg.zero1 else 1)
    frozen_bytes = sum(int(x.size) * _itemsize(x.dtype)
                       for x in jax.tree.leaves(frozen)) // tp
    fixed = fixed_bytes(
        n_params=n_local, compute_dtype=model_cfg.compute_dtype,
        param_dtype=model_cfg.param_dtype, state_bytes=state_bytes,
        head=head_bytes(model_cfg, rows, seq, fused=fused,
                        vocab_shards=tp if cfg.tp_vocab else 1,
                        vocab_chunks=cfg.vocab_chunks),
        world=data_axis_size(mesh) if cfg.lion else 1,
        frozen_bytes=frozen_bytes)
    if experts:
        saved = expert_block_saved_bytes(model_cfg, rows, seq)
        fixed += expert_backward_bytes(model_cfg, rows, seq)
    else:
        saved = block_saved_bytes(model_cfg, rows, seq, tp=tp, attn=attn)
    return resolve(saved, model_cfg.n_layer, fixed, bytes_limit)


def with_rung(model_cfg, rung: str):
    """``model_cfg`` with its blocks wrapped as ``rung`` says."""
    if rung == "none":
        return dataclasses.replace(model_cfg, remat=False, remat_policy="full")
    return dataclasses.replace(model_cfg, remat=True, remat_policy=rung)
