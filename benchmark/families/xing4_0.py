"""Family ``xing4_0``: everything the serving driver takes from a Xing4.0
configuration (what a family gives is set out in ``families/__init__.py``;
this one serves and does not train, so it gives no ``train_flags`` and no
leaf re-labelling for gradients). The seam lies between the benchmark's
seeded weights (``reference/xing4_0``: per-layer dicts under the published
names' short forms) and the program's tree (``models/xing.xing_init``). The
re-labelling changes no value; one leaf a sublayer changes its FORM: the
mix's float32 ``phi`` is handed over packed (``ops/mhc.pack_phi``: its three
bfloat16 parts side by side, whose sum is the float32 matrix to 24 bits).
"""

from __future__ import annotations

import distributed_lion_tpu.models.xing  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import xing4_0 as reference

# the published widths and knobs (config.json): check_config holds a file
# to them
PUBLISHED = {
    "hidden_size": 3584, "num_attention_heads": 32,
    "num_key_value_heads": 32, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 9216, "moe_intermediate_size": 1024,
    "n_routed_experts": 64, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "vocab_size": 131072, "routed_scaling_factor": 2, "rope_theta": 10000,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 262144,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}
CUT_PARAMETERS_M = 4788.5    # as the configuration file is cut (its note)

TINY = {
    "model_type": "xing4_0", "vocab_size": 256,
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 4096,
}

_ATTN = {"wq_a": "q_a", "wq_b": "q_b", "wkv_a": "kv_a", "wkv_b": "kv_b",
         "wo": "o"}
_MLP = {"w_gate": "gate", "w_up": "up", "w_down": "down"}
_MOE = {"router": "router", "bias": "router_bias", "w_gate": "exp_gate",
        "w_up": "exp_up", "w_down": "exp_down"}
_SHARED = {"w_gate": "sh_gate", "w_up": "sh_up", "w_down": "sh_down"}


def model_config(cfg: dict, dtype):
    from distributed_lion_tpu.models.xing import XingConfig

    return XingConfig.from_hf(cfg, param_dtype=dtype, compute_dtype=dtype)


def to_program(w: dict, mix) -> dict:
    """Reference-layout weights as the program's tree (``mix``: the
    program's ``MixConfig``, for the packing of ``phi``)."""
    from distributed_lion_tpu.ops.mhc import pack_phi

    def hyper(layer, which):
        return {"phi": pack_phi(layer[which + "_hc_phi"], mix),
                "a": layer[which + "_hc_a"], "b": layer[which + "_hc_b"]}

    blocks = []
    for layer in w["layers"]:
        attn = {mine: layer[theirs] for mine, theirs in _ATTN.items()}
        attn["q_norm"] = {"scale": layer["q_a_norm"]}
        attn["kv_norm"] = {"scale": layer["kv_a_norm"]}
        block = {"hc_attn": hyper(layer, "attn"),
                 "ln_attn": {"scale": layer["input_norm"]}, "attn": attn,
                 "hc_mlp": hyper(layer, "ffn"),
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
    return to_program(reference.init_weights(key, cfg, dtype),
                      model_config(cfg, dtype).mix)


def serve_model(params, cfg: dict, dtype):
    """``XingConfig.from_hf -> ServeModel.for_xing``: the constructors
    ``run_serve --model_family xing --model_name <this file>`` calls (the
    checkpoint loader is bypassed: the weights are the benchmark's)."""
    from distributed_lion_tpu.serve.engine import ServeModel

    return ServeModel.for_xing(params, model_config(cfg, dtype))


def vocab(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """262,144 declared positions are never a row: the traffic's longest
    prompt plus longest output, in whole pages (4,608 in the cell)."""
    t, block = cell["traffic"], cell["program"]["serve_config"]["block_size"]
    longest = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    return -(-longest // block) * block


def cut_parameters(body: dict) -> tuple:
    """Parameters of the configuration as cut, from its own keys (the
    reference's shapes): (the matrices of attention, FFNs, experts, routers,
    embedding and head; the mix's ``phi``, ``a``, ``b``). Norm gains and the
    routers' biases (some 30 thousand) are in neither."""
    matrices, mix = 2 * body["vocab_size"] * body["hidden_size"], 0
    for layer in range(body["num_hidden_layers"]):
        for name, shape in reference.layer_shapes(body, layer).items():
            size = 1
            for dim in shape:
                size *= dim
            if "_hc_" in name:
                mix += size
            elif not name.endswith(("norm", "router_bias")):
                matrices += size
    return matrices, mix


def check_config(body: dict) -> None:
    """Every published width and knob, all the experts and the whole
    vocabulary; a leading dense layer and four expert layers or more;
    matrices that add to 4,788.5 M."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert 1 <= body["first_k_dense_replace"] \
        <= body["num_hidden_layers"] - 4, body["num_hidden_layers"]
    matrices, _ = cut_parameters(body)
    assert round(matrices / 1e5) == round(CUT_PARAMETERS_M * 10), matrices
