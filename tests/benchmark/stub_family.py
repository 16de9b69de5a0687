"""A model family that is not GPT-2, for ``test_bm_family_seam.py``: the
program's ``models/llama.py`` at a tiny size (RMSNorm, rotary positions,
grouped-query attention, a gated MLP, an untied head), with its own plain
reference. Its configuration (``stub_config.json``) has none of GPT-2's
keys, is cut in depth with the cut in ``reduced`` and the published value
beside it, and declares far more positions than a reference row is long.
No file under ``benchmark/`` names it: the test points the manifest and the
family lookup here. What a family gives: ``benchmark/families/__init__.py``.
"""

from __future__ import annotations

import json
import math
import os
import types

import jax
import jax.numpy as jnp
from jax import lax

# the precision helper and the seed's key are not GPT-2's in any way
from benchmark.reference.gpt2 import matmul, seed_key

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "stub_config.json")) as _f:
    TINY = json.load(_f)

_PER_LAYER = ("ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "w_gate", "w_up",
              "w_down")


def _sizes(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, heads, cfg["num_key_value_heads"], d // heads


# ----------------------------------------------------------- the reference
def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    d, heads, kv, hd = _sizes(cfg)
    L, V, ff = (cfg["num_hidden_layers"], cfg["vocab_size"],
                cfg["intermediate_size"])
    resid = 0.02 / math.sqrt(2 * L)
    shapes = {"embed": ((V, d), 0.02), "lm_head": ((d, V), 0.02),
              "wq": ((L, d, heads * hd), 0.02), "wk": ((L, d, kv * hd), 0.02),
              "wv": ((L, d, kv * hd), 0.02), "wo": ((L, heads * hd, d), resid),
              "w_gate": ((L, d, ff), 0.02), "w_up": ((L, d, ff), 0.02),
              "w_down": ((L, ff, d), resid)}
    keys = jax.random.split(key, len(shapes))
    out = {name: (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
           for k, (name, (shape, std)) in zip(keys, sorted(shapes.items()))}
    out.update(ln_f=jnp.ones((d,), dtype), ln_attn=jnp.ones((L, d), dtype),
               ln_mlp=jnp.ones((L, d), dtype))
    return out


def _rms_norm(x, g, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def forward(weights: dict, tokens, cfg: dict, quant=None):
    """tokens ``[B, T]`` -> logits ``[B, T, V]`` float32."""
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    d, heads, kv, hd = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    B, T = tokens.shape
    inv_freq = 1.0 / (cfg["rope_theta"]
                      ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = jnp.outer(jnp.arange(T, dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def rope(x):                       # [B, h, T, hd], interleaved pairs
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)

    def split(x, n):
        return x.reshape(B, T, n, hd).transpose(0, 2, 1, 3)

    def body(x, l):
        h = _rms_norm(x, l["ln_attn"], eps)
        q = rope(split(matmul(h, l["wq"], quant), heads))
        k = rope(split(matmul(h, l["wk"], quant), kv))
        v = split(matmul(h, l["wv"], quant), kv)
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision=lax.Precision.HIGHEST) / math.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                       precision=lax.Precision.HIGHEST)
        x = x + matmul(a.transpose(0, 2, 1, 3).reshape(B, T, d), l["wo"],
                       quant)
        h = _rms_norm(x, l["ln_mlp"], eps)
        gate = jax.nn.silu(matmul(h, l["w_gate"], quant))
        return x + matmul(gate * matmul(h, l["w_up"], quant), l["w_down"],
                          quant), None

    x, _ = lax.scan(body, w["embed"][tokens], {k: w[k] for k in _PER_LAYER})
    return matmul(_rms_norm(x, w["ln_f"], eps), w["lm_head"], quant)


reference = types.SimpleNamespace(seed_key=seed_key, init_weights=init_weights,
                                  forward=forward)


# ------------------------------------------------------------- the program
def to_program(w: dict) -> dict:
    """Reference-layout weights as ``models/llama.llama_init``'s tree."""
    blocks = [{"ln_attn": {"scale": w["ln_attn"][i]},
               "attn": {k: w[k][i] for k in ("wq", "wk", "wv", "wo")},
               "ln_mlp": {"scale": w["ln_mlp"][i]},
               "mlp": {k: w[k][i] for k in ("w_gate", "w_up", "w_down")}}
              for i in range(w["wq"].shape[0])]
    return {"wte": w["embed"], "lm_head": w["lm_head"],
            "ln_f": {"scale": w["ln_f"]}, "blocks": blocks}


def program_weights(key, cfg: dict, dtype) -> dict:
    return to_program(init_weights(key, cfg, dtype))


def serve_model(params, cfg: dict, dtype):
    from distributed_lion_tpu.models.llama import LlamaConfig
    from distributed_lion_tpu.serve.engine import ServeModel

    d, heads, kv, _ = _sizes(cfg)
    return ServeModel.for_llama(params, LlamaConfig(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=heads, n_kv_head=kv, d_model=d, d_ff=cfg["intermediate_size"],
        n_ctx=cfg["max_position_embeddings"], rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"], param_dtype=dtype,
        compute_dtype=jnp.bfloat16))


def vocab(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def reference_row_len(cell: dict) -> int:
    """What the traffic can produce, in whole pages: the longest prompt and
    the longest output, not the 4,096 positions the file declares."""
    longest = int(cell["traffic"]["prompt_len"]["hi"]) \
        + int(cell["traffic"]["output_len"]["hi"])
    block = int(cell["program"]["serve_config"]["block_size"])
    return -(-longest // block) * block


def check_config(body: dict) -> None:
    d, heads, kv, hd = _sizes(body)
    assert d % heads == 0 and heads % kv == 0 and hd % 2 == 0
