"""Bytes and operations of the Lightning recurrence (one step over the
slots' states, and a prompt in chunks) and of decode attention over
selected pages, computed from shapes and from the engine's own counters
(what the algorithm needs, as ``lib/roofline`` counts: not what a particular
program does)."""

from __future__ import annotations

# the kernels by name in the trace (ops/pallas_lightning): the decode tick's
# step, the prefill's chunked form
LIGHTNING_KERNEL = r"lightning_step"
LIGHTNING_CHUNK_KERNEL = r"lightning_chunk"

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layer_kinds(cfg: dict) -> tuple:
    """(``minicpm4`` layers, ``lightning-attn`` layers) among the layers that
    are run."""
    kinds = cfg["mixer_types"][:cfg["num_hidden_layers"]]
    sparse = sum(k == SPARSE for k in kinds)
    return sparse, len(kinds) - sparse


def state_row_bytes(cfg: dict) -> int:
    """One slot's state in one layer: heads x d x d float32."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 4


def vector_bytes(cfg: dict, itemsize: int) -> int:
    """One position's vectors in one layer: q, k, v in and o out, ``head_dim``
    a head each, at ``itemsize`` bytes a value."""
    return cfg["num_attention_heads"] * 4 * cfg["head_dim"] * itemsize


def lightning_step_bytes(rows: int, cfg: dict) -> int:
    """Least HBM bytes of ``rows`` steps (live slots x Lightning layers, the
    engine's ``state_rows_stepped``): a row's state read once and written
    once, and its vectors in float32 (what the step kernel is handed)."""
    return rows * (2 * state_row_bytes(cfg) + vector_bytes(cfg, 4))


def lightning_chunk_bytes(tokens: int, prompts: int, cfg: dict,
                          itemsize: int = 2) -> int:
    """Least HBM bytes of the recurrence over ``prompts`` prompts of
    ``tokens`` positions in all (as padded: the engine's
    ``padded_prefill_tokens`` and ``prefill_dispatches``) in every Lightning
    layer: a position's q, k, v in at the dtype the program holds them
    (``itemsize``) and o out in float32, and a prompt's state written once
    (it starts from zero: nothing to read)."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    position = heads * hd * (3 * itemsize + 4)
    return layer_kinds(cfg)[1] * (tokens * position
                                  + prompts * state_row_bytes(cfg))


def lightning_chunk_flops(tokens: int, cfg: dict) -> int:
    """Operations of the recurrence itself, a position a head: the decay (d
    d), the rank-one write and ``S^T q`` (2 d d each): 5 d d. A chunk's extra
    products are the program's choice and are not counted."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return layer_kinds(cfg)[1] * tokens * heads * 5 * hd * hd


def selected_page_bytes(cfg: dict, block_size: int, itemsize: int = 2) -> int:
    """One kv head's keys AND values of one page: ``block_size`` rows of
    ``head_dim`` values, twice (8,192 B at pages of 16 x 128 bfloat16)."""
    return 2 * block_size * cfg["head_dim"] * itemsize


def sparse_attn_bytes(pages_selected: int, cfg: dict, block_size: int,
                      itemsize: int = 2) -> int:
    """Least HBM bytes of decode attention over the lists the program
    builds: ``pages_selected`` counts one for every (page, kv head) pair a
    list holds, over both kinds of row and every ``minicpm4`` layer (the
    engine's ``kv_pages_selected``, counted in the decode program). The
    queries in, the outputs back and the compressed keys selection reads are
    some hundredth of it and are not counted."""
    return pages_selected * selected_page_bytes(cfg, block_size, itemsize)


def pages_walked(pages_read: int, cfg: dict) -> int:
    """The (page, kv head) pairs a program that walks every page of every
    live row would hand attention: the host's ``kv_pages_read`` (one layer,
    all kv heads together) x kv heads x ``minicpm4`` layers."""
    return pages_read * cfg["num_key_value_heads"] * layer_kinds(cfg)[0]
