"""Family ``joyai_llm_flash``: everything the serving driver takes from a
JoyAI-LLM-Flash configuration (what a family gives is set out in
``families/__init__.py``; this one serves and does not train, so it gives
no ``train_flags`` and no leaf re-labelling for gradients). The seam lies
between the benchmark's seeded weights (``reference/joyai_llm_flash``:
per-layer dicts under the published names' short forms) and the program's
tree (``models/joyai.joyai_init``); the re-labelling changes no value and
copies none.
"""

from __future__ import annotations

import distributed_lion_tpu.models.joyai  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import joyai_llm_flash as reference

# the published widths (config.json): check_config holds a file to them
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "intermediate_size": 7168,
    "moe_intermediate_size": 768, "n_routed_experts": 256,
    "num_experts_per_tok": 8, "n_shared_experts": 1, "vocab_size": 129280,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "rope_theta": 32000000, "rms_norm_eps": 1e-06,
}

TINY = {
    "model_type": "joyai_llm_flash", "vocab_size": 256,
    "num_hidden_layers": 2, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 32000000,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 4096,
}

_ATTN = {"wq_a": "q_a", "wq_b": "q_b", "wkv_a": "kv_a", "wkv_b": "kv_b",
         "wo": "o"}
_MLP = {"w_gate": "gate", "w_up": "up", "w_down": "down"}
_MOE = {"router": "router", "bias": "router_bias", "w_gate": "exp_gate",
        "w_up": "exp_up", "w_down": "exp_down"}
_SHARED = {"w_gate": "sh_gate", "w_up": "sh_up", "w_down": "sh_down"}


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    blocks = []
    for layer in w["layers"]:
        attn = {mine: layer[theirs] for mine, theirs in _ATTN.items()}
        attn["q_norm"] = {"scale": layer["q_a_norm"]}
        attn["kv_norm"] = {"scale": layer["kv_a_norm"]}
        block = {"ln_attn": {"scale": layer["input_norm"]}, "attn": attn,
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
    """``JoyAIConfig.from_hf -> ServeModel.for_joyai``: the constructors
    ``run_serve --model_family joyai --model_name <this file>`` calls (the
    checkpoint loader is bypassed: the weights are the benchmark's)."""
    from distributed_lion_tpu.models.joyai import JoyAIConfig
    from distributed_lion_tpu.serve.engine import ServeModel

    model_cfg = JoyAIConfig.from_hf(cfg, param_dtype=dtype,
                                    compute_dtype=dtype)
    return ServeModel.for_joyai(params, model_cfg)


def vocab(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """131,072 declared positions are never a row: the traffic's longest
    prompt plus longest output, in whole pages."""
    t, block = cell["traffic"], cell["program"]["serve_config"]["block_size"]
    longest = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    return -(-longest // block) * block


def check_config(body: dict) -> None:
    """Every published width, all the experts and the whole vocabulary."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert body["qk_head_dim"] == (body["qk_nope_head_dim"]
                                   + body["qk_rope_head_dim"])
    assert body["num_hidden_layers"] > body["first_k_dense_replace"]
