"""Family ``ling_3_flash``: everything the serving driver takes from a
Ling-3.0-flash configuration (what a family gives is set out in
``families/__init__.py``; this one serves and does not train, so it gives no
``train_flags`` and no leaf re-labelling for gradients). The seam lies
between the benchmark's seeded weights (``reference/ling_3_flash``: per-layer
dicts under the published names' short forms) and the program's tree
(``models/ling.ling_init``); the re-labelling changes no value and copies
none.
"""

from __future__ import annotations

import distributed_lion_tpu.models.ling  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import ling_3_flash as reference

# the published widths and settings (the catalog row's config): check_config
# holds a file to them
PUBLISHED = {
    "hidden_size": 2560, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "moe_shared_expert_intermediate_size": 768,
    "num_experts_per_tok": 8, "num_attention_heads": 32,
    "num_key_value_heads": 32, "head_dim": 128, "q_lora_rank": None,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 131072, "partial_rotary_factor": 0.5,
    "rotary_dim": 64, "routed_scaling_factor": 2.5, "n_group": 8,
    "topk_group": 4, "layer_group_size": 6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "group_norm_size": 1,
    "num_kv_heads_for_linear_attn": 0, "score_function": "sigmoid",
    "gated_attention_proj_granularity_type": "head_wise",
}
ROUTER_OUTPUTS = 512
# what the cut's sizes add to, in millions of parameters (the file's
# ``assumed.sizes`` gives the parts)
CUT_PARAMETERS_M = 5169

TINY = {
    "model_type": "ling_3_flash", "vocab_size": 256, "num_hidden_layers": 3,
    "hidden_size": 64, "intermediate_size": 128, "first_k_dense_replace": 1,
    "max_position_embeddings": 4096, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_experts_per_tok": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "routed_scaling_factor": 2.5, "n_group": 4,
    "topk_group": 2, "layer_group_size": 2, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "norm_topk_prob": True,
    "score_function": "sigmoid", "use_qk_norm": True,
    "gated_attention_proj_granularity_type": "head_wise",
    "expert_swiglu_limit_list": [0, 0, 0],
    "share_expert_swiglu_limit_list": [0, 0, 0],
    # experts 0-7 (groups 0 and 1 of 4) of a router of 16 are held, as the
    # cell's cut holds 128 of 512; a KDA layer with the dense FFN, then an
    # MLA and a KDA layer with experts: a period of 2 for the published 6
    # (every kind of layer, the fewest the CPU tests must compile)
    "num_experts": 8, "reduced": ["num_experts"],
    "published": {"num_experts": 16},
}

_KDA = {"wq": "q", "wk": "k", "wv": "v", "conv": "conv", "wf": "f",
        "A_log": "A_log", "dt_bias": "dt_bias", "wb": "b", "wg": "g",
        "wo": "o"}
_MLA = {"wq": "q", "wkv_a": "kv_a", "wkv_b": "kv_b", "wg": "g", "wo": "o"}
_MLP = {"w_gate": "gate", "w_up": "up", "w_down": "down"}
_MOE = {"router": "router", "bias": "router_bias", "w_gate": "exp_gate",
        "w_up": "exp_up", "w_down": "exp_down"}
_SHARED = {"w_gate": "sh_gate", "w_up": "sh_up", "w_down": "sh_down"}


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    blocks = []
    for layer in w["layers"]:
        block = {"ln_attn": {"scale": layer["input_norm"]},
                 "ln_mlp": {"scale": layer["post_norm"]}}
        if "kv_a" in layer:
            block["attn"] = {mine: layer[theirs]
                             for mine, theirs in _MLA.items()}
            block["attn"]["kv_norm"] = {"scale": layer["kv_a_norm"]}
        else:
            block["kda"] = {mine: layer[theirs]
                            for mine, theirs in _KDA.items()}
            block["kda"]["o_norm"] = {"scale": layer["o_norm"]}
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
    """``LingConfig.from_hf -> ServeModel.for_ling``: the constructors
    ``run_serve --model_family ling --model_name <this file>`` calls (the
    checkpoint loader is bypassed: the weights are the benchmark's)."""
    from distributed_lion_tpu.models.ling import LingConfig
    from distributed_lion_tpu.serve.engine import ServeModel

    model_cfg = LingConfig.from_hf(cfg, param_dtype=dtype,
                                   compute_dtype=dtype)
    return ServeModel.for_ling(params, model_cfg)


def vocab(cfg: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids from
    them, and logits and argmax are over them."""
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """131,072 declared positions are never a row: the traffic's longest
    prompt plus longest output, in whole pages."""
    t, block = cell["traffic"], cell["program"]["serve_config"]["block_size"]
    longest = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    return -(-longest // block) * block


def cut_parameters(body: dict) -> int:
    """Parameters of the configuration as cut, from its own keys (the
    reference's shapes)."""
    n = 2 * body["vocab_size"] * body["hidden_size"] + body["hidden_size"]
    for layer in range(body["num_hidden_layers"]):
        for shape in reference.layer_shapes(body, layer).values():
            size = 1
            for dim in shape:
                size *= dim
            n += size
    return n


def check_config(body: dict) -> None:
    """Every published width and setting, the router's 512 outputs in 8
    groups and 8 a token; a depth that covers the leading dense layer and
    at least one whole period of five KDA layers and one MLA layer; a held
    range that is whole routing groups; sizes that add to 5,169 M."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert reference.routed_experts(body) == ROUTER_OUTPUTS, body["num_experts"]
    per_group = ROUTER_OUTPUTS // body["n_group"]
    assert body["num_experts"] <= ROUTER_OUTPUTS
    assert body["num_experts"] % per_group == 0, body["num_experts"]
    depth, dense = body["num_hidden_layers"], body["first_k_dense_replace"]
    assert dense >= 1 and depth >= dense + body["layer_group_size"], depth
    kinds = [reference.is_mla(body, i) for i in range(depth)]
    assert sum(kinds) >= 1 and not kinds[0], kinds
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(body[key]) == body["published"]["num_hidden_layers"]
        assert not any(body[key][:depth]), (key, body[key][:depth])
    assert round(cut_parameters(body) / 1e6) == CUT_PARAMETERS_M, \
        cut_parameters(body)
