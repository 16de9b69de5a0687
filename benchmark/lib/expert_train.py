"""Operations and bytes of a trained block of grouped-query attention (a
window or every earlier key) and a dropless top-k expert layer, and the
expert layers' counters as the trainer drains them. Computed from shapes and
from the program's own counters (what the algorithm needs, as
``lib/roofline`` counts: recompute under a remat rung is not counted). Kept
here, not in ``roofline`` / ``latent_moe``: a PR adds to the benchmark by new
files alone."""

from __future__ import annotations

# the kernels by name in the trace: ops/pallas_moe_gmm (`moe_gmm` forward
# and dlhs, `moe_gmm_drhs`) and ops/pallas_flash_attn (`flash_gqa_lse`,
# `flash_gqa_dq`, `flash_gqa_dkv`)
GMM_TRAIN_KERNELS = r"moe_gmm"
GQA_TRAIN_KERNELS = r"flash_gqa_(lse|dq|dkv)"
COUNTERS = ("moe_routed", "moe_assignments", "moe_experts_hit",
            "moe_load_max")


def routed_experts(cfg: dict) -> int:
    """The router's outputs: the published number where the file was
    ``reduced`` in ``num_experts`` (then ``num_experts`` are held)."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def step_counters(ctx):
    """The expert layers' counters of ONE optimizer step (summed over
    layers and microbatches on the device), as the trainer last drained
    them at ``logging_steps`` (``train/metrics.last_logged``), or None
    where the program keeps none (a commit before the one that added them)
    or no log interval closed."""
    try:
        from distributed_lion_tpu.train import metrics
    except ImportError:
        return None
    read = getattr(metrics, "last_logged", None)
    last = read("train") if read else None
    if not last or any(name not in last for name in COUNTERS):
        return None
    return {name: float(last[name]) for name in COUNTERS}


def pairs_seen(seq: int, window) -> int:
    """(query, key) pairs of one causal sequence: position i sees
    ``min(i + 1, window)`` keys."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(cfg: dict, seqs: int, seq: int, backward: bool = True):
    """FLOPs the attention of ``seqs`` sequences needs over the layers of
    the cut: forward two matmuls (QK^T, PV) of 2 x head_dim a pair and
    query head, backward four; the band's and the causal half's pairs
    only."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    pairs = sum(pairs_seen(seq, cfg["sliding_window"]
                           if kind == "sliding_attention" else None)
                for kind in kinds)
    fwd = 4 * cfg["head_dim"] * cfg["num_attention_heads"] * pairs * seqs
    return fwd * (3 if backward else 1)


def gmm_train_flops(rows: float, d: int, f: int) -> float:
    """The three grouped products of a SwiGLU expert forward and their six
    gradient products, 2 x d x f a row each; ``rows`` counts the picks
    held, summed over layers and microbatches."""
    return rows * 9 * 2 * d * f


def gmm_train_bytes(rows: float, calls: int, held: int, d: int, f: int,
                    itemsize: int = 2) -> float:
    """Least HBM bytes of the same: every product reads or writes a row's
    ``d`` and ``f`` values once (nine products: 9 (d + f) a row), and each
    of a call's nine products moves the held experts' bank once (read
    forward and for dlhs, written for drhs); ``calls`` = layers x
    microbatches."""
    return (rows * 9 * (d + f) + calls * 9 * held * d * f) * itemsize


def train_flops_per_token(cfg: dict, seq: int, held_share: float) -> float:
    """Forward + backward FLOPs a token of THIS cut: the four projections,
    the router, the picks held (``held_share`` of ``num_experts_per_tok``),
    the band's and the causal half's scores, the head over the rows held; 6
    FLOPs a matmul parameter a token."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    attn = 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])
    expert = 3 * d * cfg["moe_intermediate_size"]
    layer = attn + d * routed_experts(cfg) \
        + cfg["num_experts_per_tok"] * held_share * expert
    matmul = cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d
    return 6 * matmul + attention_flops(cfg, 1, seq) / seq
