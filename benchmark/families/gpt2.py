"""Family ``gpt2``: everything the drivers take from a GPT-2 configuration,
found by the ``model_type`` its file carries (``harness.load_family``).
What a family gives is set out in ``families/__init__.py``. Here the seam
lies between the benchmark's seeded GPT-2 weights (reference layout, stacked
``[L, ...]``, HF names) and the program's parameter tree
(``models/gpt2.gpt2_init``: a list of per-layer dicts, qkv as ``[d, 3, d]``);
the re-labelling changes no value.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference import gpt2 as reference

TINY = {"model_type": "gpt2", "vocab_size": 256, "n_positions": 128,
        "n_embd": 64, "n_layer": 2, "n_head": 4, "layer_norm_epsilon": 1e-5}

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
        if name in reference._PER_LAYER:
            norms = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[(name, i)] = norms[i]
        else:
            out[(name, None)] = jnp.sqrt(jnp.sum(x * x))
    return out


def leaf_keys(cfg: dict) -> list:
    """Every key of :func:`program_leaves` for this configuration."""
    keys = [(name, None) for name in _TOP.values()]
    keys += [(name, i) for i in range(cfg["n_layer"])
             for name in reference._PER_LAYER]
    return keys


def program_weights(key, cfg: dict, dtype) -> dict:
    """The seeded weights as the program's tree (traceable: the drivers
    call it inside one ``jax.jit`` with the key as an argument)."""
    return to_program(reference.init_weights(key, cfg, dtype))


def config_kwargs(cfg: dict) -> dict:
    """The published config.json keys as ``GPT2Config`` keywords."""
    return dict(vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"],
                n_head=cfg["n_head"], d_model=cfg["n_embd"],
                n_ctx=cfg["n_positions"])


def serve_model(params, cfg: dict, dtype):
    """``GPT2Config -> ServeModel.for_gpt2``, the constructors
    ``run_serve.build_engine_factory`` calls (the checkpoint loader is
    bypassed: the weights are the benchmark's, and the CLI cannot name
    GPT-2 XL today)."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.serve.engine import ServeModel

    model_cfg = GPT2Config(**config_kwargs(cfg), param_dtype=dtype,
                           compute_dtype=jnp.bfloat16)
    return ServeModel.for_gpt2(params, model_cfg)


def train_flags(cfg: dict) -> dict:
    """``run_clm``'s flags that name this model: the CLI knows GPT-2 by
    preset, not by sizes."""
    sizes = {k: cfg[k] for k in ("n_layer", "n_embd", "n_head")}
    if sizes == {"n_layer": 12, "n_embd": 768, "n_head": 12}:
        return {"model_name": "gpt2_124m"}
    if sizes == {k: TINY[k] for k in sizes}:
        return {"model_name": "tiny"}
    raise ValueError(f"run_clm has no preset for GPT-2 at {sizes}")


def vocab(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """GPT-2's learned positions are few: a reference row is all of them."""
    return int(cell["config"]["n_positions"])


def check_config(body: dict) -> None:
    assert body["n_embd"] % body["n_head"] == 0
    assert body["n_embd"] // body["n_head"] == 64     # published head size
