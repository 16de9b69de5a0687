"""Family ``laguna``: everything the serving driver takes from a Laguna
configuration (what a family gives is set out in ``families/__init__.py``;
this one serves and does not train, so it gives no ``train_flags`` and no
leaf re-labelling for gradients). The seam lies between the benchmark's
seeded weights (``reference/laguna``: per-layer dicts under the published
names' short forms) and the program's tree (``models/laguna.laguna_init``);
the re-labelling changes no value and copies none.
"""

from __future__ import annotations

import distributed_lion_tpu.models.laguna  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import laguna as reference

# the published widths (config.json): check_config holds a file to them
PUBLISHED = {
    "hidden_size": 3072, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 12288,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "num_experts_per_tok": 10, "sliding_window": 512,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 1048576,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1}},
}
ROUTER_OUTPUTS = 256
HEADS = {"full_attention": 48, "sliding_attention": 72}
PERIOD = ["full_attention"] + ["sliding_attention"] * 3

TINY = {
    "model_type": "laguna", "vocab_size": 256, "num_hidden_layers": 5,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 4,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "sliding_window": 8,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 4096,
    "gating": "per-head", "mlp_only_layers": [0],
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    # the published RoPE blocks, YaRN's original context shrunk with the rest
    "rope_parameters": dict(
        PUBLISHED["rope_parameters"],
        full_attention=dict(PUBLISHED["rope_parameters"]["full_attention"],
                            original_max_position_embeddings=64)),
    # experts 0-3 of a router of 8 are held, as the cell's cut holds 128 of
    # 256
    "reduced": ["num_experts"], "published": {"num_experts": 8},
}

_ATTN = {"wq": "q", "wk": "k", "wv": "v", "wg": "g", "wo": "o"}
_MLP = {"w_gate": "gate", "w_up": "up", "w_down": "down"}
_MOE = {"router": "router", "bias": "router_bias", "w_gate": "exp_gate",
        "w_up": "exp_up", "w_down": "exp_down"}
_SHARED = {"w_gate": "sh_gate", "w_up": "sh_up", "w_down": "sh_down"}


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    blocks = []
    for layer in w["layers"]:
        block = {"ln_attn": {"scale": layer["input_norm"]},
                 "attn": {mine: layer[theirs]
                          for mine, theirs in _ATTN.items()},
                 "ln_mlp": {"scale": layer["post_norm"]}}
        if "router" in layer:
            block["moe"] = {mine: layer[theirs]
                            for mine, theirs in _MOE.items()}
            block["moe"]["shared"] = {mine: layer[theirs]
                                      for mine, theirs in _SHARED.items()}
        else:
            block["mlp"] = {mine: layer[theirs]
                            for mine, theirs in _MLP.items()}
        blocks.append(block)
    return {"wte": w["embed"], "lm_head": w["head"],
            "ln_f": {"scale": w["final_norm"]}, "blocks": blocks}


def program_weights(key, cfg: dict, dtype) -> dict:
    """The seeded weights as the program's tree (traceable: the driver
    calls it inside one ``jax.jit`` with the key as an argument)."""
    return to_program(reference.init_weights(key, cfg, dtype))


def serve_model(params, cfg: dict, dtype):
    """``LagunaConfig.from_hf -> ServeModel.for_laguna``: the constructors
    ``run_serve --model_family laguna --model_name <this file>`` calls (the
    checkpoint loader is bypassed: the weights are the benchmark's)."""
    from distributed_lion_tpu.models.laguna import LagunaConfig
    from distributed_lion_tpu.serve.engine import ServeModel

    model_cfg = LagunaConfig.from_hf(cfg, param_dtype=dtype,
                                     compute_dtype=dtype)
    return ServeModel.for_laguna(params, model_cfg)


def vocab(cfg: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids from
    them, and logits and argmax are over them."""
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """1,048,576 declared positions are never a row: the traffic's longest
    prompt plus longest output, in whole pages."""
    t, block = cell["traffic"], cell["program"]["serve_config"]["block_size"]
    longest = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    return -(-longest // block) * block


def check_config(body: dict) -> None:
    """Every published width, the router's 256 outputs and 10 a token, the
    window, both RoPE blocks; a depth that covers the leading dense layer
    and whole periods of one full and three window layers behind it, each
    with its own head count."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert reference.routed_experts(body) == ROUTER_OUTPUTS, body["num_experts"]
    assert body["num_experts"] <= ROUTER_OUTPUTS
    depth = body["num_hidden_layers"]
    assert depth > 1 and (depth - 1) % len(PERIOD) == 0, depth
    kinds = body["layer_types"][:depth]
    # layer 0 opens a period; the cut keeps whole ones behind it, closing
    # on the next period's full layer: window x 3, full
    assert kinds == (PERIOD * depth)[:depth], kinds
    assert body["num_attention_heads_per_layer"][:depth] == \
        [HEADS[k] for k in kinds]
    assert body["mlp_layer_types"][:depth] == \
        ["dense"] + ["sparse"] * (depth - 1)
    assert body["mlp_only_layers"] == [0] and body["gating"] == "per-head"
