"""Bytes of the learned indexer's decode scoring, of latent attention over
the set it kept and of the sliding layers' walk over their rings of latent
rows, computed from shapes and from the engine's own counters (what the
mathematics needs, as ``lib/roofline`` counts: not what a particular program
reads), and the three kernels by name in the trace (``ops/pallas_dsa``,
``ops/pallas_mla_attn``)."""

from __future__ import annotations

INDEX_KERNEL = r"dsa_index"
ATTN_KERNEL = r"dsa_attn"
WINDOW_KERNEL = r"window_mla_attn"
# the engine's decode program among the trace's executed programs
DECODE_MODULE = r"^jit_decode_tick\b"

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_kinds(cfg: dict) -> tuple:
    """(full layers, sliding layers) among the layers that are run."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(k == FULL for k in kinds)
    return full, len(kinds) - full


def lanes(values: int) -> int:
    """A cache row's lanes: its values rounded up to whole 128-lane tiles
    (``serve/kv_cache.pool_row_width``)."""
    return -(-values // 128) * 128


def index_key_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One cached index key: ``index_head_dim`` values (256 B)."""
    return cfg["index_head_dim"] * itemsize


def latent_row_bytes(cfg: dict, kind: str = FULL, itemsize: int = 2) -> int:
    """One cached latent row of a layer kind as the pool holds it, pad lanes
    included (a page is one DMA): 576 values in 640 lanes, 1,280 B, on a full
    layer; 1,088 in 1,152, 2,304 B, on a sliding one."""
    pre = "swa_" if kind == SLIDING else ""
    return lanes(cfg[pre + "kv_lora_rank"]
                 + cfg[pre + "qk_rope_head_dim"]) * itemsize


def index_bytes(keys_visible: int, cfg: dict, itemsize: int = 2) -> int:
    """Least HBM bytes of the decode ticks' scoring: every index key a live
    row can see, once (the engine's ``dsa_keys_visible``, summed over rows,
    ticks and full layers). The queries (64 x 128 a row) and the scores
    written (4 B a key: a sixty-fourth) are not counted."""
    return keys_visible * index_key_bytes(cfg, itemsize)


def kept_attn_bytes(keys_kept: int, rows: int, cfg: dict,
                    itemsize: int = 2) -> int:
    """Least HBM bytes of latent attention over the kept set: the latent
    rows the mathematics needs, ``dsa_keys_kept`` of them, read once for
    scores and values, and a (row, layer)'s absorbed queries in and outputs
    back (heads x a latent row each way), ``dsa_rows`` of them: WHATEVER the
    kernel reads (a masked walk over every visible row reads low; no
    implementation can read over 100%)."""
    row = latent_row_bytes(cfg, FULL, itemsize)
    return keys_kept * row + rows * 2 * cfg["num_attention_heads"] * row


def window_bytes(window_pages: int, block_size: int, cfg: dict,
                 itemsize: int = 2) -> int:
    """Least HBM bytes of the sliding layers' decode walks: the pages ONE
    sliding layer's walks were handed (the engine's ``kv_window_pages_read``:
    at most ``ring_pages`` a row), ``block_size`` latent rows each, in every
    sliding layer."""
    return window_pages * block_size * layer_kinds(cfg)[1] \
        * latent_row_bytes(cfg, SLIDING, itemsize)
