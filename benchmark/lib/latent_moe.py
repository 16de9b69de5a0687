"""Operations and bytes of the latent-attention decode kernel and of the
experts' grouped matmul, computed from shapes and from the engine's own
counters (what the algorithm needs, as ``lib/roofline`` counts: not what a
particular program does)."""

from __future__ import annotations

# the kernels by name in the trace (ops/pallas_mla_attn, ops/pallas_moe_gmm)
MLA_KERNEL = r"mla_paged_attn"
GMM_KERNEL = r"moe_gmm"


def mla_attn_bytes(pages: int, block_size: int, row_width: int,
                   layers: int, itemsize: int = 2) -> int:
    """Least HBM bytes of absorbed latent decode attention: every page a
    live row's length needs (the engine's ``kv_pages_read``), ``block_size``
    rows of ``row_width`` values (lane padding included: a page is one DMA),
    read ONCE for scores and values, in every layer's one leaf."""
    return pages * block_size * row_width * itemsize * layers


def mla_attn_flops(pages: int, block_size: int, heads: int, row_values: int,
                   value_width: int, layers: int) -> int:
    """Multiply-adds x 2 of the same: every head's query against every
    cached row (``row_values`` = latent rank + rope width: 576) and every
    head's probabilities against the row's latent part (``value_width``:
    512). Rows are counted by whole pages, as the bytes are."""
    return pages * block_size * heads * (row_values + value_width) * 2 * layers


def moe_gmm_flops(assignments: int, d_model: int, d_expert: int) -> int:
    """A routed SwiGLU expert is three matmuls of ``d_model x d_expert`` a
    token it was given: ``assignments`` counts tokens x experts per token,
    summed over the expert layers."""
    return assignments * 3 * 2 * d_model * d_expert


def moe_gmm_bytes(assignments: int, experts_hit: int, d_model: int,
                  d_expert: int, itemsize: int = 2) -> int:
    """Least HBM bytes of the same: the three banks of every expert a
    dispatch touches, once a dispatch (``experts_hit`` is summed over layers
    and dispatches), and a row's activations in and out of each matmul
    (gate and up read ``d_model`` and write ``d_expert`` each, down reads
    ``d_expert`` and writes ``d_model``)."""
    banks = experts_hit * 3 * d_model * d_expert
    rows = assignments * (3 * d_model + 3 * d_expert)
    return (banks + rows) * itemsize


def counter_delta(ctx, *names):
    """Sum of the engine's counters ``names`` between the traced window's
    edges (``facts["engine_stats"]``), or None where the program keeps none
    of them or no trace closed."""
    stats = ctx["facts"].get("engine_stats") or {}
    if "trace_open" not in stats or "trace_close" not in stats:
        return None
    if not any(n in stats["trace_close"] for n in names):
        return None
    return sum(stats["trace_close"].get(n, 0) - stats["trace_open"].get(n, 0)
               for n in names)
