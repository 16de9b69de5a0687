"""Bytes of decode attention over a cache of two lifetimes (full layers'
growing pages, window layers' bounded rings), and the expert layer's
counters as shares, computed from shapes and from the engine's own counters
(what the algorithm needs, as ``lib/roofline`` counts: not what a particular
program does)."""

from __future__ import annotations


def layer_kinds(cfg: dict) -> tuple:
    """(full layers, window layers) among the layers that are run."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    window = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - window, window


def page_bytes(cfg: dict, block_size: int, itemsize: int = 2) -> int:
    """One page of keys AND values of one layer: ``block_size`` rows of all
    the kv heads side by side, lanes rounded up to 128 (a page is one DMA
    a leaf)."""
    lanes = -(-cfg["num_key_value_heads"] * cfg["head_dim"] // 128) * 128
    return block_size * lanes * itemsize * 2


def hybrid_attn_bytes(pages_full: int, pages_window: int, cfg: dict,
                      block_size: int, itemsize: int = 2) -> int:
    """Least HBM bytes of the decode attention: every page a live row's
    length needs in each full layer (the engine's ``kv_pages_read``) and
    every page of the walk the kernel is handed in each window layer
    (``kv_window_pages_read``, counted in the decode program: at most the
    ring's 33 a row), keys and values. The
    queries in and the outputs back are some thousandth of it and are not
    counted."""
    full, window = layer_kinds(cfg)
    return (pages_full * full + pages_window * window) \
        * page_bytes(cfg, block_size, itemsize)


def expert_layers(cfg: dict) -> int:
    kinds = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]]
    return sum(k == "sparse" for k in kinds)
