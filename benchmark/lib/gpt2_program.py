"""The seam between the benchmark's seeded GPT-2 weights (reference layout,
stacked ``[L, ...]``, HF names) and the program's parameter tree
(``models/gpt2.gpt2_init``: a list of per-layer dicts, qkv as ``[d, 3, d]``).
Pure re-labelling: no value is changed."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2 as ref

# program path inside one block -> reference name
_BLOCK = {
    ("ln_1", "scale"): "ln_1_g", ("ln_1", "bias"): "ln_1_b",
    ("ln_2", "scale"): "ln_2_g", ("ln_2", "bias"): "ln_2_b",
    ("attn", "qkv"): "c_attn_w", ("attn", "qkv_b"): "c_attn_b",
    ("attn", "proj"): "attn_proj_w", ("attn", "proj_b"): "attn_proj_b",
    ("mlp", "fc"): "c_fc_w", ("mlp", "fc_b"): "c_fc_b",
    ("mlp", "proj"): "mlp_proj_w", ("mlp", "proj_b"): "mlp_proj_b",
}
_TOP = {("wte",): "wte", ("wpe",): "wpe", ("ln_f", "scale"): "ln_f_g",
        ("ln_f", "bias"): "ln_f_b"}


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    d = w["wte"].shape[1]
    n_layer = w["c_attn_w"].shape[0]

    def shaped(name, x):
        if name == "c_attn_w":
            return x.reshape(d, 3, d)
        if name == "c_attn_b":
            return x.reshape(3, d)
        return x

    blocks = []
    for i in range(n_layer):
        block: dict = {}
        for (group, leaf), name in _BLOCK.items():
            block.setdefault(group, {})[leaf] = shaped(name, w[name][i])
        blocks.append(block)
    tree: dict = {"blocks": blocks}
    for path, name in _TOP.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = w[name]
    return tree


def program_leaves(tree: dict) -> dict:
    """The program tree's leaves keyed ``(reference name, layer | None)``."""
    out = {}
    for path, name in _TOP.items():
        node = tree
        for part in path:
            node = node[part]
        out[(name, None)] = node
    for i, block in enumerate(tree["blocks"]):
        for (group, leaf), name in _BLOCK.items():
            out[(name, i)] = block[group][leaf]
    return out


def reference_leaf_norms(tree: dict) -> dict:
    """L2 norm of every program-sized leaf of a reference-layout tree,
    keyed like :func:`program_leaves` (stacked arrays give one per layer)."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name in ref._PER_LAYER:
            norms = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[(name, i)] = norms[i]
        else:
            out[(name, None)] = jnp.sqrt(jnp.sum(x * x))
    return out


def make_program_weights(seed: int, cfg: dict, dtype, shardings=None):
    """The program's weights, made on the device from the seed in ONE
    jitted call, in the dtype they are used in."""
    fn = jax.jit(lambda key: to_program(ref.init_weights(key, cfg, dtype)),
                 out_shardings=shardings)
    return fn(ref.seed_key(seed))


def gpt2_config_kwargs(cfg: dict) -> dict:
    """The published config.json keys as ``GPT2Config`` keywords."""
    return dict(vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"],
                n_head=cfg["n_head"], d_model=cfg["n_embd"],
                n_ctx=cfg["n_positions"])
