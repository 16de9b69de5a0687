"""Plain Ling-3.0-flash reference (inclusionAI Ling-3.0-flash-VL's language
model): the forward pass.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no cache, no chunks, no sorted dispatch, no batching beyond the
rows it is given. It imports nothing of the program and takes nothing the
program made: weights come from :func:`init_weights` (the benchmark's own
seeded init, which the family file also hands to the program, relabelled and
unchanged).

Follows the catalog row's ``config`` (``inclusionAI/Ling-3.0-flash-VL``
``config.json``); what that config does not settle is listed under
``assumed`` in the configuration file, in the same words. Per layer ``l``,
pre-norm residual, RMSNorm eps ``rms_norm_eps``, no biases:
``h = x + Mix_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; ``Mix_l`` is
MLA where ``(l + 1) % layer_group_size == 0``, else KDA.

- **KDA** (``u`` the normed input, H heads of ``head_dim`` = d_k = d_v):
  ``q~, k~, v~ = u W_q, u W_k, u W_v``; a causal depthwise convolution of
  ``short_conv_kernel_size`` over each (``y_t = sum_j c_j x_{t-3+j}``, zeros
  before position 0), then SiLU; q and k divided by their L2 norm a head, q
  times ``d_k^-0.5``; ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) (u W_f
  + dt_bias))`` a key channel, ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(u
  W_beta)`` a head; the state ``S [d_k, d_v]`` a head, zero at position 0,
  **token by token under** ``lax.scan``:
  ``S' = diag(alpha_t) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``;
  ``o_t = S^T q_t``; RMSNorm of ``o_t`` over the head's values with a gain;
  times the head's gate ``sigmoid(u W_g)``; ``W_o``.
- **MLA**: ``q = u W_q`` (no low-rank step) -> heads of ``[q_nope |
  q_rope]``; ``u W_kva = [c | k_rope]``, ``c`` normed; RoPE (pairs ``(2i,
  2i+1)``, theta ``rope_theta``) on ``q_rope`` a head and on the one
  ``k_rope`` all heads share; ``c W_kvb`` -> heads of ``[k_nope | v]``;
  ``softmax(q k^T / sqrt(nope + rope))``, causal; the same head-wise gate;
  ``W_o``.
- **FFN**: layers before ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``. The others: ``s = sigmoid(float32(u) W_r^T)``;
  selection by ``s + bias``: the experts are ``n_group`` runs, a run's score
  is the sum of its two largest ``s + bias``, only the ``topk_group`` best
  runs stay, the ``num_experts_per_tok`` best experts among them are picked;
  ``w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor``;
  ``FFN(u) = sum_i w_i E_idx_i(u) + E_shared(u)``, every ``E`` a SwiGLU
  (clamped where the layer's entry of ``expert_swiglu_limit_list`` /
  ``share_expert_swiglu_limit_list`` is over 0: gate from above, up to
  ``[-limit, limit]``). A ``lax.scan`` over the experts HELD with the routing
  weight (0 where not chosen) as a mask.
- **The share.** A configuration ``reduced`` in ``num_experts`` holds that
  many experts, from index 0, of the ``published`` number the router scores
  (one chip of an expert-parallel group): the router has all its outputs and
  all its groups, a token picks among all of them and its weights are
  normalised over all its picks; a pick of an expert that is not held adds
  nothing. The partial sum, shared expert included, goes on to the next
  layer: what this chip computes, not the whole model's output.
- Final RMSNorm, untied head over the ``vocab_size`` rows held.

Weights are kept in the dtype they are made in (bfloat16 in the cell) and
each is raised to float32 where it is used.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales). Accumulation
stays float32, and so do the router's matmul and the recurrence, as a
deployment in a lower precision keeps them. ``"state16"`` is the control
of the recurrent state's own precision: the float32 pass with every KDA
state rounded to bfloat16 after every step. ``"slip"`` is no precision but
the planted fault that ``served_logit_gap_max`` is held against: the float32
pass with the logits of one position in ``SLIP_EVERY`` rolled half the
vocabulary round.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
BIAS_STD = 0.01
A_LOG_STD = 0.3
DT_BIAS = (-4.0, 1.0)      # mean, std
SLIP_EVERY = 251      # a request of a thousand tokens holds a few
HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def routed_experts(cfg: dict) -> int:
    """The router's outputs: the published number where the file holds a
    share of the experts, else ``num_experts``."""
    if "num_experts" in cfg.get("reduced", ()):
        return int(cfg["published"]["num_experts"])
    return int(cfg["num_experts"])


def is_mla(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["layer_group_size"] == 0


def layer_shapes(cfg: dict, layer: int) -> dict:
    """Name -> shape of one layer's weights (``x @ W``: ``[in, out]``)."""
    d, H, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    sh = {"input_norm": (d,), "post_norm": (d,), "g": (d, H)}
    if is_mla(cfg, layer):
        r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        sh.update(q=(d, H * (dn + dr)), kv_a=(d, r + dr), kv_a_norm=(r,),
                  kv_b=(r, H * (dn + dv)), o=(H * dv, d))
    else:
        ch = H * hd
        sh.update(q=(d, ch), k=(d, ch), v=(d, ch),
                  conv=(cfg["short_conv_kernel_size"], 3 * ch), f=(d, ch),
                  A_log=(H,), dt_bias=(ch,), b=(d, H), o_norm=(hd,),
                  o=(ch, d))
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        sh.update(gate=(d, f), up=(d, f), down=(f, d))
    else:
        E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        fs, R = cfg["moe_shared_expert_intermediate_size"], routed_experts(cfg)
        sh.update(router=(R, d), router_bias=(R,),
                  exp_gate=(E, d, f), exp_up=(E, d, f), exp_down=(E, f, d),
                  sh_gate=(d, fs), sh_up=(d, fs), sh_down=(fs, d))
    return sh


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Seeded weights: every matrix and the convolution kernel N(0, 0.02),
    norm gains 1; in float32 the expert bias N(0, 0.01) (so that the choice
    and the weighting differ), ``A_log`` N(0, 0.3) and ``dt_bias`` N(-4, 1)
    (decays a key channel from a token or two to hundreds of tokens). One
    key a leaf, folded from ``key`` by the leaf's number. Call it inside one
    ``jax.jit`` WITH THE KEY AS AN ARGUMENT (see
    ``reference/gpt2.init_weights``)."""
    count = iter(range(1 << 20))

    def leaf(name, shape):
        k = jax.random.fold_in(key, next(count))
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        noise = jax.random.normal(k, shape, jnp.float32)
        if name == "router_bias":
            return noise * BIAS_STD
        if name == "A_log":
            return noise * A_LOG_STD
        if name == "dt_bias":
            return noise * DT_BIAS[1] + DT_BIAS[0]
        return (noise * STD).astype(dtype)

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


def _round_bf16(x):
    """float32 rounded to bfloat16's 8 exponent and 7 mantissa bits, kept in
    float32. ``lax.reduce_precision`` and not a cast there and back: a chip's
    compiler may keep the excess precision of a cast pair (on the TPU the
    pair moved NOTHING, to the last bit of every logit: PERF.md section 6),
    and may not drop this."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def matmul(x, w, quant):
    """``x [..., k] @ w [k, n]`` in float32, both operands put through
    ``quant`` first."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "bf16":
        x, w = _round_bf16(x), _round_bf16(w)
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


def _swiglu(x, gate, up, down, quant, limit=0.0):
    g, u = matmul(x, gate, quant), matmul(x, up, quant)
    if limit > 0:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return matmul(jax.nn.silu(g) * u, down, quant)


def _conv_silu(x, kernel):
    """x [R, T, C], kernel [W, C]: ``silu(sum_j kernel[j] x[t - W + 1 +
    j])``, zeros before position 0."""
    W, T = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + T] * kernel[j].astype(jnp.float32)
                           for j in range(W)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(x, w, cfg, quant, state16):
    R, T, _ = x.shape
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kernels = jnp.split(w["conv"], 3, axis=1)
    q, k, v = (_conv_silu(matmul(x, w[n], quant), c).reshape(R, T, H, hd)
               for n, c in zip("qkv", kernels))
    q, k = _unit(q) * hd ** -0.5, _unit(k)
    a = matmul(x, w["f"], quant).reshape(R, T, H, hd)
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(w["A_log"])[:, None] * (a + w["dt_bias"].reshape(H, hd)))
    beta = jax.nn.sigmoid(matmul(x, w["b"], quant))            # [R, T, H]

    def step(S, xs):
        q, k, v, g, beta = xs                        # [R, H, hd], beta [R, H]
        S = S * jnp.exp(g)[..., None]
        read = jnp.einsum("rhkv,rhk->rhv", S, k, precision=HI)
        S = S + jnp.einsum("rhk,rhv->rhkv", k * beta[..., None], v - read,
                           precision=HI)
        o = jnp.einsum("rhkv,rhk->rhv", S, q, precision=HI)
        if state16:
            S = _round_bf16(S)
        return S, o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((R, H, hd, hd), jnp.float32), xs)
    o = _rms_norm(jnp.moveaxis(o, 0, 1), w["o_norm"], cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(matmul(x, w["g"], quant))[..., None]
    return matmul(o.reshape(R, T, H * hd), w["o"], quant)


def _mla(x, w, cfg, quant):
    R, T, _ = x.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    q = matmul(x, w["q"], quant).reshape(R, T, H, dn + dr)
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

    o = lax.map(head, (q, kvx)).transpose(1, 2, 0, 3)         # [R, T, H, dv]
    o = o * jax.nn.sigmoid(matmul(x, w["g"], quant))[..., None]
    return matmul(o.reshape(R, T, H * dv), w["o"], quant)


def route(x, w, cfg):
    """The experts of every token, among the router scores of the groups
    that stay, and their weights: ``idx [N, k]``, ``weight [N, k]`` (float32
    throughout)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w["router"].astype(jnp.float32).T,
        precision=HI))
    choice = s + w["router_bias"]
    n, E = choice.shape
    G = int(cfg["n_group"])
    if G > 1:
        two = lax.top_k(choice.reshape(n, G, E // G), 2)[0].sum(-1)
        _, best = lax.top_k(two, int(cfg["topk_group"]))      # [N, topk]
        stays = (best[:, :, None] == jnp.arange(G)).any(1)    # [N, G]
        choice = jnp.where(jnp.repeat(stays, E // G, axis=1), choice,
                           -jnp.inf)
    _, idx = lax.top_k(choice, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, -1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    return idx, weight


def _limit(cfg, key, layer):
    xs = cfg.get(key, ())
    return float(xs[layer]) if layer < len(xs) else 0.0


def _experts(x, w, cfg, layer, quant):
    R, T, d = x.shape
    flat = x.reshape(R * T, d)
    idx, weight = route(flat, w, cfg)
    limit = _limit(cfg, "expert_swiglu_limit_list", layer)

    def one(acc, args):
        e, gate, up, down = args
        mask = jnp.sum(jnp.where(idx == e, weight, 0.0), -1)   # [N]
        return acc + _swiglu(flat, gate, up, down, quant, limit) \
            * mask[:, None], None

    held = w["exp_gate"].shape[0]      # experts 0 .. held - 1 are here
    routed, _ = lax.scan(one, jnp.zeros_like(flat),
                         (jnp.arange(held), w["exp_gate"], w["exp_up"],
                          w["exp_down"]))
    shared = _swiglu(flat, w["sh_gate"], w["sh_up"], w["sh_down"], quant,
                     _limit(cfg, "share_expert_swiglu_limit_list", layer))
    return (routed + shared).reshape(R, T, d)


def forward(weights, rows, cfg: dict, quant=None):
    """``rows [R, T]`` int32 token ids -> logits ``[R, T, vocab]`` float32
    (positions 0 .. T-1, causal)."""
    slip, state16 = quant == "slip", quant == "state16"
    quant = None if slip or state16 else quant
    eps = cfg["rms_norm_eps"]
    x = weights["embed"][rows].astype(jnp.float32)
    for layer, w in enumerate(weights["layers"]):
        u = _rms_norm(x, w["input_norm"], eps)
        x = x + (_mla(u, w, cfg, quant) if "kv_a" in w
                 else _kda(u, w, cfg, quant, state16))
        h = _rms_norm(x, w["post_norm"], eps)
        x = x + (_experts(h, w, cfg, layer, quant) if "router" in w
                 else _swiglu(h, w["gate"], w["up"], w["down"], quant))
    logits = matmul(_rms_norm(x, weights["final_norm"], eps),
                    weights["head"], quant)
    if slip:
        at = jnp.arange(SLIP_EVERY - 1, rows.shape[1], SLIP_EVERY)
        logits = logits.at[:, at].set(
            jnp.roll(logits[:, at], logits.shape[-1] // 2, -1))
    return logits
