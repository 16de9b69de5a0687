"""Family ``minicpm_sala``: everything the serving driver takes from a
MiniCPM-SALA configuration (what a family gives is set out in
``families/__init__.py``; this one serves and does not train). The seam lies
between the benchmark's seeded weights (``reference/minicpm_sala``: per-layer
dicts under the published names' short forms) and the program's tree
(``models/minicpm_sala.minicpm_sala_init``); the re-labelling changes no
value and copies none.
"""

from __future__ import annotations

import distributed_lion_tpu.models.minicpm_sala  # noqa: F401  (a program without this family fails here, at once)

from benchmark.reference import minicpm_sala as reference

# the catalog row's config (every key but the two a cut in depth changes):
# check_config holds a file to them
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "num_attention_heads": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}
SPARSE_CONFIG = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                 "window_size": 2048, "topk": 64, "init_blocks": 1,
                 "dense_len": 8192}
# what the cut's sizes add to, in millions of parameters (the file's
# ``assumed.sizes`` gives the parts): 2,820,545,280. ISSUE 37 writes 2,820.6
# from a Lightning layer rounded up to 285.23 M (it is 285.22)
CUT_PARAMETERS_M = 2820.5

TINY = {
    "model_type": "minicpm_sala", "vocab_size": 256, "num_hidden_layers": 4,
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_head_dim": 16,
    "max_position_embeddings": 4096, "rope_theta": 10000,
    "rms_norm_eps": 1e-06, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 4, "dim_model_base": 16,
    # sparse, Lightning, Lightning, sparse; pages of 2 (the stride), windows
    # of 4, blocks of 8, the 4 best past 64 positions with a local window of
    # 2 blocks: selection really drops blocks at 200 positions
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                      "window_size": 16, "topk": 4, "init_blocks": 1,
                      "dense_len": 64},
}

_SHARED = {"wq": "q", "wk": "k", "wv": "v", "wz": "z", "wo": "o"}
_MLP = {"w_gate": "gate", "w_up": "up", "w_down": "down"}


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's tree."""
    blocks = []
    for layer in w["layers"]:
        mixer = {mine: layer[theirs] for mine, theirs in _SHARED.items()}
        mixer.update(q_norm={"scale": layer["q_norm"]},
                     k_norm={"scale": layer["k_norm"]})
        if "slope" in layer:
            mixer.update(o_norm={"scale": layer["o_norm"]},
                         slope=layer["slope"])
        blocks.append({
            "ln_attn": {"scale": layer["input_norm"]},
            "ln_mlp": {"scale": layer["post_norm"]},
            "mlp": {mine: layer[theirs] for mine, theirs in _MLP.items()},
            "lightning" if "slope" in layer else "attn": mixer})
    return {"wte": w["embed"], "lm_head": w["head"],
            "ln_f": {"scale": w["final_norm"]}, "blocks": blocks}


def program_weights(key, cfg: dict, dtype) -> dict:
    """The seeded weights as the program's tree (traceable: the driver
    calls it inside one ``jax.jit`` with the key as an argument)."""
    return to_program(reference.init_weights(key, cfg, dtype))


def serve_model(params, cfg: dict, dtype):
    """``MiniCPMSalaConfig.from_hf -> ServeModel.for_minicpm_sala``: the
    constructors ``run_serve --model_family minicpm_sala --model_name <this
    file>`` calls (the checkpoint loader is bypassed: the weights are the
    benchmark's)."""
    from distributed_lion_tpu.models.minicpm_sala import MiniCPMSalaConfig
    from distributed_lion_tpu.serve.engine import ServeModel

    model_cfg = MiniCPMSalaConfig.from_hf(cfg, param_dtype=dtype,
                                          compute_dtype=dtype)
    return ServeModel.for_minicpm_sala(params, model_cfg)


def vocab(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """524,288 declared positions are never a row: the traffic's longest
    prompt plus longest output, in whole pages (20,480 in the cell)."""
    t, block = cell["traffic"], cell["program"]["serve_config"]["block_size"]
    longest = int(t["prompt_len"]["hi"]) + int(t["output_len"]["hi"])
    return -(-longest // block) * block


def decay(cfg: dict):
    """``lambda [layers kept, heads]`` of the Lightning layers (zeros on a
    ``minicpm4`` layer's row): one function of (layer, head), the
    reference's :func:`reference.slopes`."""
    import jax.numpy as jnp

    return jnp.stack([
        jnp.exp(-reference.slopes(cfg, i)) if kind == reference.LIGHTNING
        else jnp.zeros((cfg["num_attention_heads"],), jnp.float32)
        for i, kind in enumerate(reference.kinds(cfg))])


def cut_parameters(body: dict) -> int:
    """Parameters of the configuration as cut, from its own keys (the
    reference's shapes; the decay slopes are constants, not parameters)."""
    n = 2 * body["vocab_size"] * body["hidden_size"] + body["hidden_size"]
    for layer in range(body["num_hidden_layers"]):
        for name, shape in reference.layer_shapes(body, layer).items():
            size = 1
            for dim in shape:
                size *= dim
            n += size if name != "slope" else 0
    return n


def check_config(body: dict) -> None:
    """Every key of the catalog's config unchanged but the depth and the
    list of mixers, which is a contiguous run of the published list in whole
    periods of one ``minicpm4`` layer to three Lightning layers; the
    ``sparse_config`` assumed; sizes that add to 2,820.5 M."""
    for key, value in PUBLISHED.items():
        assert body[key] == value, (key, body[key], value)
    assert body["sparse_config"] == SPARSE_CONFIG, body["sparse_config"]
    depth, kinds = body["num_hidden_layers"], body["mixer_types"]
    full = body["published"]["mixer_types"]
    assert len(kinds) == depth and depth < len(full), (depth, len(kinds))
    first = body["deployment_layers"][0]
    assert full[first:first + depth] == kinds, (first, kinds)
    sparse = sum(k == reference.SPARSE for k in kinds)
    assert sparse >= 1 and 3 * sparse == depth - sparse, kinds
    assert round(cut_parameters(body) / 1e5) == round(CUT_PARAMETERS_M * 10), \
        cut_parameters(body)
