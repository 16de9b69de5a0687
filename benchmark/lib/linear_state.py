"""Bytes of one gated-delta-rule step over the slots' recurrent states, bytes
and operations of the same rule over a prompt, and the counts the state's
readers share, computed from shapes and from the engine's own counters (what
the algorithm needs, as ``lib/roofline`` counts: not what a particular
program does)."""

from __future__ import annotations

# the kernels by name in the trace (ops/pallas_kda): the decode tick's
# step, the prefill's chunked form
KDA_KERNEL = r"kda_step"
KDA_CHUNK_KERNEL = r"kda_chunk"


def state_layers(cfg: dict) -> int:
    """Layers that carry a recurrent state among the layers that are run:
    all but every ``layer_group_size``-th."""
    depth, period = cfg["num_hidden_layers"], cfg["layer_group_size"]
    return depth - depth // period


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def state_row_bytes(cfg: dict) -> int:
    """One slot's state in one layer: heads x d_k x d_v float32."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 4


def vector_bytes(cfg: dict) -> int:
    """One position's vectors in one layer, float32: q, k, v and the decay a
    key channel in and o out (5 x head_dim a head), a write strength a
    head."""
    return cfg["num_attention_heads"] * (5 * cfg["head_dim"] + 1) * 4


def kda_step_bytes(rows: int, cfg: dict) -> int:
    """Least HBM bytes of ``rows`` steps (live slots x state layers, the
    engine's ``state_rows_stepped``): a row's state read once and written
    once, and its vectors (:func:`vector_bytes`)."""
    return rows * (2 * state_row_bytes(cfg) + vector_bytes(cfg))


def kda_chunk_bytes(tokens: int, prompts: int, cfg: dict) -> int:
    """Least HBM bytes of the rule over ``prompts`` prompts of ``tokens``
    positions in all (as padded: the engine's ``padded_prefill_tokens`` and
    ``prefill_dispatches``) in every state layer: a position's vectors
    (:func:`vector_bytes`) and a prompt's state written once (it starts from
    zero: nothing to read)."""
    return state_layers(cfg) * (tokens * vector_bytes(cfg)
                                + prompts * state_row_bytes(cfg))


def kda_chunk_flops(tokens: int, cfg: dict) -> int:
    """Operations of the recurrence itself, a position a head: the decay
    (d_k d_v), ``S'^T k``, the rank-one write and ``S^T q`` (2 d_k d_v
    each). What a chunked form spends on its products and its triangular
    solve is the program's choice and is not counted."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return state_layers(cfg) * tokens * heads * 7 * hd * hd
