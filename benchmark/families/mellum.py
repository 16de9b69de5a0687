"""Family ``mellum``: everything the training driver takes from a Mellum 2
configuration (what a family gives is set out in ``families/__init__.py``;
this one trains and is not served, so it gives no ``serve_model``). The seam
lies between the benchmark's seeded weights (``reference/mellum``: per-layer
dicts under the published names' short forms) and the program's tree
(``models/mellum.mellum_init``); the re-labelling changes no value and
copies none.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import tempfile

import jax.numpy as jnp

import distributed_lion_tpu.models.mellum  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import mellum as reference

# the published widths (config.json): check_config holds a file to them
PUBLISHED = {
    "hidden_size": 2304, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 7168,
    "moe_intermediate_size": 896, "num_experts_per_tok": 8,
    "sliding_window": 1024, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 131072, "norm_topk_prob": True,
    "attention_bias": False, "tie_word_embeddings": False,
    "hidden_act": "silu",
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
}
ROUTER_OUTPUTS = 64
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CELL_PARAMETERS = 595_154_176

TINY = {
    "model_type": "mellum", "vocab_size": 256, "num_hidden_layers": 4,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "sliding_window": 8, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 4096, "attention_bias": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "max_window_layers": 0, "use_sliding_window": True,
    "layer_types": PERIOD, "mlp_layer_types": ["sparse"] * 4,
    # the published RoPE blocks, YaRN's original context shrunk with the rest
    "rope_parameters": dict(
        PUBLISHED["rope_parameters"],
        full_attention=dict(PUBLISHED["rope_parameters"]["full_attention"],
                            original_max_position_embeddings=64)),
    # experts 0-3 of a router of 8 are held, as the cell's cut holds 16 of 64
    "reduced": ["num_experts"], "published": {"num_experts": 8},
}

_ATTN = {"wq": "q", "wk": "k", "wv": "v", "wo": "o"}
_MOE = {"router": "router", "w_gate": "exp_gate", "w_up": "exp_up",
        "w_down": "exp_down"}
# program path inside one block -> reference name
_BLOCK = {("ln_attn", "scale"): "input_norm", ("ln_mlp", "scale"): "post_norm",
          ("attn", "q_norm", "scale"): "q_norm",
          ("attn", "k_norm", "scale"): "k_norm",
          **{("attn", mine): theirs for mine, theirs in _ATTN.items()},
          **{("moe", mine): theirs for mine, theirs in _MOE.items()}}
_TOP = {("wte",): "embed", ("lm_head",): "head", ("ln_f", "scale"): "final_norm"}


def _put(tree: dict, path: tuple, leaf) -> None:
    for part in path[:-1]:
        tree = tree.setdefault(part, {})
    tree[path[-1]] = leaf


def _get(tree: dict, path: tuple):
    for part in path:
        tree = tree[part]
    return tree


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    tree: dict = {"blocks": []}
    for path, name in _TOP.items():
        _put(tree, path, w[name])
    for layer in w["layers"]:
        block: dict = {}
        for path, name in _BLOCK.items():
            _put(block, path, layer[name])
        tree["blocks"].append(block)
    return tree


def program_leaves(tree: dict) -> dict:
    """The program tree's leaves keyed ``(reference name, layer | None)``."""
    out = {(name, None): _get(tree, path) for path, name in _TOP.items()}
    for i, block in enumerate(tree["blocks"]):
        out.update({(name, i): _get(block, path)
                    for path, name in _BLOCK.items()})
    return out


def reference_leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf of a reference-layout tree, keyed like
    :func:`program_leaves`."""
    def norm(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x))

    out = {(name, None): norm(tree[name]) for name in reference.TOP}
    for i, layer in enumerate(tree["layers"]):
        out.update({(name, i): norm(layer[name])
                    for name in reference.PER_LAYER})
    return out


def leaf_keys(cfg: dict) -> list:
    """Every key of :func:`program_leaves` for this configuration."""
    return [(name, None) for name in reference.TOP] + [
        (name, i) for i in range(cfg["num_hidden_layers"])
        for name in reference.PER_LAYER]


def program_weights(key, cfg: dict, dtype) -> dict:
    """The seeded weights as the program's tree (traceable: the driver
    calls it inside one ``jax.jit`` with the key as an argument)."""
    return to_program(reference.init_weights(key, cfg, dtype))


def train_flags(cfg: dict) -> dict:
    """``run_clm``'s flags that name this model. ``run_clm --model_family
    mellum`` takes a path to a ``config.json``; the driver hands this
    function the file's body. So the body goes to a file of this family's
    own under the run's temporary directory (named by its content: the
    driver asks more than once), and the flags name that."""
    body = json.dumps(cfg, sort_keys=True)
    path = os.path.join(
        tempfile.gettempdir(),
        f"mellum-{hashlib.sha256(body.encode()).hexdigest()[:16]}.json")
    if not os.path.exists(path):
        with open(path + f".{os.getpid()}", "w") as f:
            f.write(body)
        os.replace(path + f".{os.getpid()}", path)
    return {"model_family": "mellum", "model_name": path}


def vocab(cfg: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids from
    them, and logits and loss are over them."""
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """131,072 declared positions are never a row: the traffic's block."""
    return int(cell["traffic"]["block_size"])


def parameters(body: dict) -> int:
    """Parameters of the configuration as cut: the arithmetic of the
    file's ``assumed.sizes``."""
    shapes = reference.layer_shapes(body)
    layer = sum(math.prod(s) for s in shapes.values())
    d, v = body["hidden_size"], body["vocab_size"]
    return body["num_hidden_layers"] * layer + 2 * v * d + d


def check_config(body: dict) -> None:
    """Every published width, the router's 64 outputs and 8 a token, the
    window, both RoPE blocks; a depth of whole periods (window x 3, full);
    at least 8 experts and an eighth of the vocabulary held; and, at the
    cell's cut (4 layers, 16 experts, 24,576 rows), 595,154,176 parameters
    of which a layer's share is 120,476,416."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert reference.routed_experts(body) == ROUTER_OUTPUTS, body["num_experts"]
    assert 8 <= body["num_experts"] <= ROUTER_OUTPUTS
    assert ROUTER_OUTPUTS % body["num_experts"] == 0
    depth = body["num_hidden_layers"]
    assert depth >= 4 and depth % len(PERIOD) == 0, depth
    assert body["layer_types"][:depth] == PERIOD * (depth // 4)
    assert set(body["mlp_layer_types"]) == {"sparse"}
    assert body["vocab_size"] * 8 >= 98304 and 98304 % body["vocab_size"] == 0
    shapes = reference.layer_shapes(body)
    assert math.prod(shapes["exp_gate"]) // body["num_experts"] * 3 == 6_193_152
    if (depth, body["num_experts"], body["vocab_size"]) == (4, 16, 24576):
        assert sum(math.prod(s) for s in shapes.values()) == 120_476_416
        assert parameters(body) == CELL_PARAMETERS, parameters(body)
