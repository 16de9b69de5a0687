"""Plain JoyAI-LLM-Flash reference: the forward pass.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no cache, no absorbed attention, no sorted dispatch. It imports
nothing of the program and takes nothing the program made: weights come
from :func:`init_weights` (the benchmark's own seeded init, which the family
file also hands to the program, relabelled and unchanged).

Follows the ``jdopensource/JoyAI-LLM-Flash`` ``config.json`` (a
DeepSeek-V3-line decoder). Per layer, pre-norm residual, RMSNorm eps
``rms_norm_eps``: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

- Attention (MLA): ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``. ``x W_kva = [c_kv | k_rope]``; ``c_kv =
  RMSNorm(c_kv)``; RoPE (theta ``rope_theta``, ``rope_interleave``: pairs
  ``(2i, 2i+1)`` of the stored layout, no scaling) on ``q_rope`` of every head
  and on the one shared ``k_rope``. ``c_kv W_kvb`` -> heads of
  ``[k_nope | v]``; ``k = [k_nope | k_rope]``;
  ``softmax(q k^T / sqrt(nope + rope))``, causal; heads' values through
  ``W_o``. No biases. Keys and values are expanded for every position and
  attention is full: one head at a time (``lax.map``), so a 2,816-token row
  holds 32 MB of scores and not 1 GB. That is the only departure in form;
  the arithmetic is the plain one.
- Layers below ``first_k_dense_replace``: a SwiGLU MLP of
  ``intermediate_size``. The others: ``s = sigmoid(float32(x) W_r^T)``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  (``n_group`` 1, ``topk_group`` 1: no group limit); ``w = s[idx] /
  (sum s[idx] + 1e-20) * routed_scaling_factor``; ``FFN(x) = sum_i w_i
  E_idx_i(x) + E_shared(x)``, every ``E`` a SwiGLU of
  ``moe_intermediate_size``. Written as a ``lax.scan`` over ALL the routed
  experts with the routing weight (0 where not chosen) as a mask: every
  expert sees every token, no token can be dropped, nothing is sorted.
- Final RMSNorm, untied head. The multi-token-prediction block is not part
  of the main model's logits and is not here (``num_nextn_predict_layers``
  is 0 in the configuration that is run).

Weights are kept in the dtype they are made in (bfloat16 in the cell: 11 GB;
float32 copies of all of them would not fit the chip) and each is raised to
float32 where it is used.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales). Accumulation
stays float32. The router's matmul stays float32 in all of them, as a
deployment in a lower precision keeps it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
BIAS_STD = 0.01
HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def layer_shapes(cfg: dict, layer: int) -> dict:
    """Name -> shape of one layer's weights (``x @ W``: ``[in, out]``)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    sh = {
        "input_norm": (d,), "post_norm": (d,),
        "q_a": (d, rq), "q_a_norm": (rq,), "q_b": (rq, H * qk),
        "kv_a": (d, r + cfg["qk_rope_head_dim"]), "kv_a_norm": (r,),
        "kv_b": (r, H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
        "o": (H * cfg["v_head_dim"], d),
    }
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        sh.update(gate=(d, f), up=(d, f), down=(f, d))
    else:
        E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = cfg["n_shared_experts"] * f
        sh.update(router=(E, d), router_bias=(E,),
                  exp_gate=(E, d, f), exp_up=(E, d, f), exp_down=(E, f, d),
                  sh_gate=(d, fs), sh_up=(d, fs), sh_down=(fs, d))
    return sh


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Seeded weights: every matrix N(0, 0.02), norm gains 1,
    ``e_score_correction_bias`` N(0, 0.01) in float32 (so that the choice
    and the weighting differ). One key a leaf, folded from ``key`` by the
    leaf's number. Call it inside one ``jax.jit`` WITH THE KEY AS AN
    ARGUMENT (see ``reference/gpt2.init_weights``)."""
    count = iter(range(1 << 20))

    def leaf(name, shape):
        k = jax.random.fold_in(key, next(count))
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        if name == "router_bias":
            return jax.random.normal(k, shape, jnp.float32) * BIAS_STD
        return (jax.random.normal(k, shape, jnp.float32) * STD).astype(dtype)

    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed": leaf("embed", (V, d)), "head": leaf("head", (d, V)),
        "final_norm": leaf("final_norm", (d,)),
        "layers": [{name: leaf(name, shape)
                    for name, shape in layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"])],
    }


# ------------------------------------------------------------- precision
def _q_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _q_fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x, w, quant):
    """``x [..., k] @ w [k, n]`` in float32, both operands put through
    ``quant`` first."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "int8":
        x, w = _q_int8(x, -1), _q_int8(w, 0)   # per token, per out channel
    elif quant == "fp8":
        x, w = _q_fp8(x), _q_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.matmul(x, w, precision=HI)


# ---------------------------------------------------------------- forward
def _rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """x [..., T, dim]: rotate the pairs ``(2i, 2i+1)`` by ``t * theta **
    (-2i / dim)``, t the position along the axis before last."""
    T, dim = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def _swiglu(x, gate, up, down, quant):
    return matmul(jax.nn.silu(matmul(x, gate, quant)) * matmul(x, up, quant),
                  down, quant)


def _attention(x, w, cfg, quant):
    R, T, _ = x.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    c_q = _rms_norm(matmul(x, w["q_a"], quant), w["q_a_norm"], eps)
    q = matmul(c_q, w["q_b"], quant).reshape(R, T, H, dn + dr)
    q = q.transpose(2, 0, 1, 3)                               # [H, R, T, .]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kv = matmul(x, w["kv_a"], quant)
    c_kv = _rms_norm(kv[..., :r], w["kv_a_norm"], eps)
    k_rope = _rope(kv[..., r:], theta)                        # [R, T, dr]
    kvx = matmul(c_kv, w["kv_b"], quant).reshape(R, T, H, dn + dv)
    kvx = kvx.transpose(2, 0, 1, 3)                           # [H, R, T, .]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(args):
        qh, kvh = args                                        # [R, T, .]
        kh = jnp.concatenate([kvh[..., :dn], k_rope], -1)
        s = jnp.einsum("rtd,rsd->rts", qh, kh, precision=HI) \
            / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("rts,rsd->rtd", p, kvh[..., dn:], precision=HI)

    o = lax.map(head, (q, kvx))                               # [H, R, T, dv]
    return matmul(o.transpose(1, 2, 0, 3).reshape(R, T, H * dv), w["o"],
                  quant)


def route(x, w, cfg):
    """The experts of every token and their weights: ``idx [N, k]``,
    ``weight [N, k]`` (float32 throughout)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w["router"].astype(jnp.float32).T,
        precision=HI))
    _, idx = lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, -1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    return idx, weight


def _experts(x, w, cfg, quant):
    R, T, d = x.shape
    flat = x.reshape(R * T, d)
    idx, weight = route(flat, w, cfg)

    def one(acc, args):
        e, gate, up, down = args
        mask = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)   # [N]
        return acc + _swiglu(flat, gate, up, down, quant) * mask[:, None], None

    n_experts = w["router"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(flat),
                         (jnp.arange(n_experts), w["exp_gate"], w["exp_up"],
                          w["exp_down"]))
    shared = _swiglu(flat, w["sh_gate"], w["sh_up"], w["sh_down"], quant)
    return (routed + shared).reshape(R, T, d)


def forward(weights, rows, cfg: dict, quant=None):
    """``rows [R, T]`` int32 token ids -> logits ``[R, T, vocab]`` float32
    (positions 0 .. T-1, causal)."""
    eps = cfg["rms_norm_eps"]
    x = weights["embed"][rows].astype(jnp.float32)
    for w in weights["layers"]:
        x = x + _attention(_rms_norm(x, w["input_norm"], eps), w, cfg, quant)
        h = _rms_norm(x, w["post_norm"], eps)
        x = x + (_experts(h, w, cfg, quant) if "router" in w
                 else _swiglu(h, w["gate"], w["up"], w["down"], quant))
    return matmul(_rms_norm(x, weights["final_norm"], eps), weights["head"],
                  quant)
