"""Family ``dots3_note``: everything the serving driver takes from a
dots3-note configuration (what a family gives is set out in
``families/__init__.py``; this one serves and does not train, so it gives no
``train_flags`` and no leaf re-labelling for gradients). The seam lies between
the benchmark's seeded weights (``reference/dots3_note``: per-layer dicts
under short names) and the program's tree (``models/dots3.dots3_init``); the
re-labelling changes no value and copies none.

The indexer's seeded weights (``reference.init_weights``): ``W_qI``, ``W_kI``
and ``W_wI`` are N(0, 0.02) like every other matrix, the LayerNorm's gain 1
and bias 0. That spread was chosen because it already gives what the cell
needs of a selection: the head weights ``w`` take both signs and a roped
query against a normed key is as large at position 0 as at the query's own,
so a row's 2,048 kept positions lie scattered over its whole context (not
its last 2,048) and most of them change from one token to the next (the
query latent is another one); the cell's note gives both shares as measured
on the chip (``scripts/dots3_selection_agreement.py``).
"""

from __future__ import annotations

import distributed_lion_tpu.models.dots3  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import dots3_note as reference

# the catalog row's config, every key but those a cut changes: check_config
# holds a file to them
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "max_position_embeddings": 524288,
    "model_type": "dots3_note", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 128, "q_lora_rank": 1024,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128,
}
ROUTER_OUTPUTS = 256
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# what the cut's sizes add to, in millions of parameters (the file's
# ``assumed.sizes`` gives the parts)
CUT_PARAMETERS_M = 4087.0

TINY = {
    "model_type": "dots3_note", "vocab_size": 256, "num_hidden_layers": 5,
    "hidden_size": 64, "intermediate_size": 128,
    "apply_mla_qkv_lora_rescale": True,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 80000000, "rope_scaling": None,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 48, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    # a window of 9 over pages of 8 wraps its ring of 3 pages inside 30
    # tokens; the 12 best of 4 index heads of 16 (8 of them roped)
    "sliding_window_size": 9, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 12,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "rms_norm_eps": 1e-05,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    # experts 0-3 of a router of 8 are held, as the cell's cut holds 32 of
    # 256
    "n_routed_experts": 4, "reduced": ["n_routed_experts"],
    "published": {"n_routed_experts": 8},
}

_ATTN = {"wq_a": "q_a", "wq_b": "q_b", "wkv_a": "kv_a", "wkv_b": "kv_b",
         "wg": "g", "wo": "o"}
_INDEX = {"wq": "idx_q", "wk": "idx_k", "ww": "idx_w"}
_MLP = {"w_gate": "gate", "w_up": "up", "w_down": "down"}
_MOE = {"router": "router", "bias": "router_bias", "w_gate": "exp_gate",
        "w_up": "exp_up", "w_down": "exp_down"}
_SHARED = {"w_gate": "sh_gate", "w_up": "sh_up", "w_down": "sh_down"}


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    blocks = []
    for layer in w["layers"]:
        attn = {mine: layer[theirs] for mine, theirs in _ATTN.items()}
        attn.update(q_norm={"scale": layer["q_a_norm"]},
                    kv_norm={"scale": layer["kv_a_norm"]})
        block = {"ln_attn": {"scale": layer["input_norm"]}, "attn": attn,
                 "ln_mlp": {"scale": layer["post_norm"]}}
        if "idx_q" in layer:
            block["index"] = {mine: layer[theirs]
                              for mine, theirs in _INDEX.items()}
            block["index"]["k_norm"] = {"scale": layer["idx_k_norm"],
                                        "bias": layer["idx_k_bias"]}
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
    """``Dots3Config.from_hf -> ServeModel.for_dots3``: the constructors
    ``run_serve --model_family dots3 --model_name <this file>`` calls (the
    checkpoint loader is bypassed: the weights are the benchmark's)."""
    from distributed_lion_tpu.models.dots3 import Dots3Config
    from distributed_lion_tpu.serve.engine import ServeModel

    model_cfg = Dots3Config.from_hf(cfg, param_dtype=dtype,
                                    compute_dtype=dtype)
    return ServeModel.for_dots3(params, model_cfg)


def vocab(cfg: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids from
    them, and logits and argmax are over them."""
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """524,288 declared positions are never a row: the traffic's longest
    prompt plus longest output, in whole pages (16,384 in the cell)."""
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
    """Every key of the catalog's config unchanged but the four a cut
    changes; the router's 256 outputs and 8 a token; a depth that covers
    the leading dense full layer and whole periods of three sliding layers
    to one full layer behind it; sizes that add to 4,087 M."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert reference.routed_experts(body) == ROUTER_OUTPUTS
    assert body["n_routed_experts"] <= ROUTER_OUTPUTS
    depth, kinds = body["num_hidden_layers"], body["layer_types"]
    assert len(kinds) == depth and depth > 1 \
        and (depth - 1) % len(PERIOD) == 0, (depth, kinds)
    assert kinds == ["full_attention"] + PERIOD * ((depth - 1) // len(PERIOD))
    full = body["published"]["layer_types"]
    # the period behind the leading layer is a run of the published list
    assert any(full[i:i + depth - 1] == kinds[1:]
               for i in range(1, len(full))), kinds
    assert round(cut_parameters(body) / 1e6) == round(CUT_PARAMETERS_M), \
        cut_parameters(body)
