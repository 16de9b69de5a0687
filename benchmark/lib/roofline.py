"""Operations and bytes computed from shapes, for MFU and roofline shares.
Each function counts what the algorithm needs, not what a particular
program does: recomputation (remat) is not counted."""

from __future__ import annotations


def gpt2_train_flops_per_token(n_layer: int, d: int, vocab: int,
                               seq: int) -> int:
    """Forward + backward FLOPs per token of a GPT-2 decoder with a tied
    head: 6 FLOPs per matmul parameter (the 12 d^2 per layer and the
    vocab x d head) plus the attention scores and values, 12 * seq * d per
    layer (QK^T and PV, forward 4 seq d, backward twice that; causal
    masking not discounted). ``bench.py``'s arithmetic, except that it
    multiplies ALL parameters by 6 (positions, biases, LayerNorms too:
    859,885,056 for GPT-2 124M at seq 1024); only matmul parameters do
    matmul work, which gives 854,438,400, 0.6% less."""
    matmul_params = 12 * n_layer * d * d + vocab * d
    return 6 * matmul_params + 12 * n_layer * seq * d


def flash_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                          causal: bool = True, backward: bool = True) -> int:
    """FLOPs the causal attention of ``batch x heads`` sequences needs:
    forward two matmuls (QK^T, PV) of 2 seq^2 head_dim each; backward four
    (dV, dP, dQ, dK) = 2x forward. A tiled kernel's recomputation of the
    scores in its backward pass, and a remat policy's second forward pass,
    are NOT counted: they are what the program chose, not what attention
    needs. A causal mask halves all of it."""
    fwd = 4 * batch * heads * seq * seq * head_dim
    total = fwd * (1 + (2 if backward else 0))
    return int(total // 2 if causal else total)


def lion_kernel_bytes(n_params: int, world: int, mom_bytes: int = 4,
                      param_bytes: int = 4, grad_bytes: int = 4) -> int:
    """Least HBM bytes of one vote-Lion update over ``n_params``
    coordinates: the ballot kernel reads gradient and momentum and writes
    one int8 ballot; the apply kernel reads parameter, gradient, momentum
    and the int32 vote total (one byte a coordinate when W = 1 would do, but
    the tally is int32 on the wire's far side) and writes parameter and
    momentum."""
    ballot = grad_bytes + mom_bytes + 1
    apply = param_bytes + grad_bytes + mom_bytes + 4 + param_bytes + mom_bytes
    return n_params * (ballot + apply)


def paged_attn_bytes(pages: int, block_size: int, row_width: int,
                     layers: int, itemsize: int = 2) -> int:
    """Least HBM bytes of decode attention over a paged KV pool read in
    place: every page that a live row's length needs (the engine's
    ``kv_pages_read``), ``block_size`` rows of ``row_width`` values (all the
    kv heads of a token, lane padding included: a page is one DMA), keys and
    values, in every layer. The queries in and the outputs back (one row a
    sequence) are some thousandth of it and are not counted."""
    return pages * block_size * row_width * 2 * itemsize * layers
