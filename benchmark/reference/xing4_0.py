"""Plain Xing4.0 (``model_type`` ``xing4_0``) reference: the forward pass.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no cache, no absorbed attention, no sorted dispatch, one row a call
where the caller says so. It imports nothing of the program and takes
nothing the program made: weights come from :func:`init_weights` (the
benchmark's own seeded init, which the family file also hands to the
program, relabelled and unchanged).

Follows the ``XingChen-AGI/Xing4.0-29B-A4B`` ``config.json``: a
DeepSeek-V3-line decoder (latent attention, sigmoid top-k experts with a
shared one) whose residual path is **manifold-constrained hyper-connections**
(mHC, arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606): the
residual is ``n = hc_mult`` streams a token, ``X [R, T, n, d]``, from the
embedding to the head. With ``F`` a sublayer (attention or FFN) and ``g`` its
pre-norm gain, all of the mix in float32:

1. ``r = rsqrt(mean(vec(X)^2) + rms_norm_eps)`` over all ``n d`` values of a
   token; ``m = r * (vec(X) phi)``, ``phi [n d, n n + 2 n]``, columns
   ``[pre (n) | post (n) | res (n n)]`` (:func:`mix_coeffs`, steps 1-2).
2. ``Hpre = sigmoid(a_pre m_pre + b_pre)``; ``Hpost = 2 sigmoid(a_post m_post
   + b_post)``; ``Z = clip(a_res mat(m_res) + b_res, clamp_min, clamp_max)``;
   ``M_0 = exp(Z)``; ``M_t = T_c(T_r(M_{t-1}))`` for ``hc_sinkhorn_iters``
   steps, ``T_r(M) = M / (rowsum(M) + hc_eps)``, ``T_c(M) = M / (colsum(M) +
   hc_eps)``; ``Hres = M_last`` (doubly stochastic to the steps' residue).
3. ``u = sum_i Hpre[i] X[i]``; ``y = F(RMSNorm_g(u))``.
4. ``X'[i] = Hpost[i] y + sum_j Hres[i, j] X[j]``.

``X_0[i] = E[token]`` for every ``i`` (:func:`expand`); the head reads
``RMSNorm(sum_i X_L[i])`` (:func:`read_out`). **Assumed** (the published
``config.json`` names the knobs ``hc_mult``, ``hc_sinkhorn_iters``,
``hc_eps``, ``mhc_h_res_clamp_min/max`` and not these; each is one function
here, so a correction against the published code is one function): that the
knobs mean mHC as published; the weightless RMS over the flattened stream
with ``rms_norm_eps``; the order row-then-column with ``hc_eps`` in the
denominators; ``Hres`` applied as ``X'[i] = sum_j Hres[i, j] X[j]``; the
clamp on ``Z`` before ``exp``; ``Hpost``'s factor 2; the sublayer's own
gained RMSNorm after the pre-mix; the embedding repeated ``n`` times and the
streams summed before the final norm (Hyper-Connections' convention).

The sublayers:

- Attention (MLA): ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``. ``x W_kva = [c_kv | k_rope]``; ``c_kv =
  RMSNorm(c_kv)``; RoPE on ``q_rope`` of every head and on the one shared
  ``k_rope``: pairs ``(2i, 2i+1)`` (assumed: interleaved, as the
  DeepSeek-V3 line stores them), YaRN frequencies (:func:`yarn_inv_freq`:
  dims that turn more than ``beta_fast`` times over
  ``original_max_position_embeddings`` keep their frequency, dims that turn
  fewer than ``beta_slow`` times have it divided by ``factor``, a linear
  ramp between), cos and sin scaled by ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)`` (1 here). ``c_kv W_kvb`` -> heads of
  ``[k_nope | v]``; ``softmax(q k^T * scale)``, causal, ``scale =
  mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)``, ``mscale(f, m) =
  0.1 m ln f + 1`` (:func:`softmax_scale`: 0.14468 as published). One head
  at a time (``lax.map``): a 4,608-token row holds 85 MB of scores.
- Layers below ``first_k_dense_replace``: a SwiGLU MLP of
  ``intermediate_size``. The others: ``s = sigmoid(float32(x) W_r^T)``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  (``n_group`` 1: no group limit); ``w = s[idx] / (sum s[idx] + 1e-20) *
  routed_scaling_factor``; ``FFN(x) = sum_i w_i E_idx_i(x) + E_shared(x)``.
  A ``lax.scan`` over ALL the routed experts with the routing weight (0
  where not chosen) as a mask: every expert sees every token, no token can
  be dropped, nothing is sorted, and one expert's float32 weights are live
  at a time.
- The multi-token-prediction block is not part of the main model's logits
  and is not here (``num_nextn_predict_layers`` is 0 in the configuration
  that is run).

Weights are kept in the dtype they are made in (bfloat16 in the cell: 9.6
GB; the mix's ``phi``, ``a``, ``b`` and the router's bias always float32)
and each is raised to float32 where it is used.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales). Accumulation
stays float32. The router's matmul and the mix (its projection ``vec(X)
phi`` too) stay float32 in all of them, as a deployment in a lower
precision keeps them. Two controls are no precision: ``"sink1"`` is the
float32 pass with ONE Sinkhorn step in place of ``hc_sinkhorn_iters`` (a
program that cuts the iteration short), ``"slip"`` the planted fault
``served_logit_gap_max`` is held against, the float32 pass with the logits
of one position in ``SLIP_EVERY`` rolled half the vocabulary round.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
BIAS_STD = 0.01
MIX_BIAS_STD = 0.5    # b_pre, b_post, and b_res about 4 I
MIX_RES_DIAG = 4.0
SLIP_EVERY = 251
HEAD_BLOCK = 512      # positions a step of the head in served_logits
HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def mix_width(cfg: dict) -> int:
    """Coefficients a token a sublayer: ``[pre (n) | post (n) | res (n n)]``."""
    n = cfg["hc_mult"]
    return n * n + 2 * n


def layer_shapes(cfg: dict, layer: int) -> dict:
    """Name -> shape of one layer's weights (``x @ W``: ``[in, out]``)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    n, c = cfg["hc_mult"], mix_width(cfg)
    sh = {
        "input_norm": (d,), "post_norm": (d,),
        "q_a": (d, rq), "q_a_norm": (rq,), "q_b": (rq, H * qk),
        "kv_a": (d, r + cfg["qk_rope_head_dim"]), "kv_a_norm": (r,),
        "kv_b": (r, H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
        "o": (H * cfg["v_head_dim"], d),
        "attn_hc_phi": (n * d, c), "attn_hc_a": (3,), "attn_hc_b": (c,),
        "ffn_hc_phi": (n * d, c), "ffn_hc_a": (3,), "ffn_hc_b": (c,),
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
    ``e_score_correction_bias`` N(0, 0.01) in float32. The mix, float32
    whatever ``dtype``: ``phi`` N(0, 0.02), ``a`` = 1, ``b_pre`` and
    ``b_post`` N(0, 0.5), ``b_res`` = 4 I + N(0, 0.5): on a unit-RMS stream
    ``m`` has a standard deviation of ``0.02 sqrt(n d)`` (2.4 at the
    published width), so the coefficients move by token and a program that
    drops the dynamic part, the clamp or the Sinkhorn steps fails the
    comparison. One key a leaf, folded from ``key`` by the leaf's number.
    Call it inside one ``jax.jit`` WITH THE KEY AS AN ARGUMENT (see
    ``reference/gpt2.init_weights``)."""
    count = iter(range(1 << 20))
    n = cfg["hc_mult"]
    eye = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                           MIX_RES_DIAG * jnp.eye(n).reshape(-1)])

    def leaf(name, shape):
        k = jax.random.fold_in(key, next(count))
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        if name.endswith("_hc_a"):
            return jnp.ones(shape, jnp.float32)
        if name.endswith("_hc_b"):
            return eye + jax.random.normal(k, shape, jnp.float32) \
                * MIX_BIAS_STD
        if name.endswith("_hc_phi"):
            return jax.random.normal(k, shape, jnp.float32) * STD
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


def _matmul_quant(quant):
    """What ``quant`` puts in the matmuls: the controls that are no
    precision leave them float32."""
    return None if quant in ("slip", "sink1") else quant


# ------------------------------------------------------------ the mix
def mix_coeffs(X, phi, a, b, cfg: dict, iters=None):
    """Steps 1-2: ``X [..., n, d]`` float32 -> (``Hpre [..., n]``, ``Hpost
    [..., n]``, ``Hres [..., n, n]``), float32. ``iters``: Sinkhorn steps
    (default ``hc_sinkhorn_iters``; the ``sink1`` control passes 1)."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    iters = cfg["hc_sinkhorn_iters"] if iters is None else iters
    flat = X.reshape(X.shape[:-2] + (-1,))
    r = lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                  + cfg["rms_norm_eps"])
    m = r * jnp.matmul(flat, phi, precision=HI)
    pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
    Z = a[2] * m[..., 2 * n:] + b[2 * n:]
    Z = jnp.clip(Z, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    M = jnp.exp(Z).reshape(Z.shape[:-1] + (n, n))
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)      # rows
        M = M / (M.sum(-2, keepdims=True) + eps)      # then columns
    return pre, post, M


def expand(x, cfg: dict):
    """The embedding as the first stream: ``[..., d] -> [..., n, d]``, every
    stream a copy."""
    return jnp.repeat(x[..., None, :], cfg["hc_mult"], -2)


def read_out(X):
    """What the final norm and the head see: the streams summed."""
    return X.sum(-2)


def _mixed(X, w, which, norm, F, cfg, iters):
    """One sublayer round its mix (steps 1-4)."""
    pre, post, res = mix_coeffs(X, w[which + "_hc_phi"], w[which + "_hc_a"],
                                w[which + "_hc_b"], cfg, iters)
    u = jnp.einsum("...n,...nd->...d", pre, X, precision=HI)
    y = F(_rms_norm(u, w[norm], cfg["rms_norm_eps"]))
    return post[..., None] * y[..., None, :] \
        + jnp.einsum("...ij,...jd->...id", res, X, precision=HI)


# ---------------------------------------------------------------- forward
def _rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: dict) -> float:
    """``mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)``."""
    rs = cfg["rope_scaling"]
    return _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2 \
        / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def yarn_inv_freq(cfg: dict):
    """``[rope / 2]`` float32 YaRN frequencies (the module note)."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    freq = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def turns_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0, 1)
    return freq / rs["factor"] * ramp + freq * (1 - ramp)


def _rope(x, cfg):
    """x [..., T, dim]: rotate the pairs ``(2i, 2i+1)`` by ``t`` times the
    YaRN frequency ``i``, t the position along the axis before last."""
    rs = cfg["rope_scaling"]
    T = x.shape[-2]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)
    amp = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    c, s = jnp.cos(ang) * amp, jnp.sin(ang) * amp
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
    scale = softmax_scale(cfg)
    c_q = _rms_norm(matmul(x, w["q_a"], quant), w["q_a_norm"], eps)
    q = matmul(c_q, w["q_b"], quant).reshape(R, T, H, dn + dr)
    q = q.transpose(2, 0, 1, 3)                               # [H, R, T, .]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], -1)
    kv = matmul(x, w["kv_a"], quant)
    c_kv = _rms_norm(kv[..., :r], w["kv_a_norm"], eps)
    k_rope = _rope(kv[..., r:], cfg)                          # [R, T, dr]
    kvx = matmul(c_kv, w["kv_b"], quant).reshape(R, T, H, dn + dv)
    kvx = kvx.transpose(2, 0, 1, 3)                           # [H, R, T, .]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(args):
        qh, kvh = args                                        # [R, T, .]
        kh = jnp.concatenate([kvh[..., :dn], k_rope], -1)
        s = jnp.einsum("rtd,rsd->rts", qh, kh, precision=HI) * scale
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


def hidden(weights, rows, cfg: dict, quant=None):
    """``rows [R, T]`` int32 token ids -> the final normed hidden states
    ``[R, T, hidden]`` float32, a layer after another."""
    iters = 1 if quant == "sink1" else None
    quant = _matmul_quant(quant)
    X = expand(weights["embed"][rows].astype(jnp.float32), cfg)
    for w in weights["layers"]:
        X = _mixed(X, w, "attn", "input_norm",
                   lambda h: _attention(h, w, cfg, quant), cfg, iters)
        ffn = (lambda h: _experts(h, w, cfg, quant)) if "router" in w else \
            (lambda h: _swiglu(h, w["gate"], w["up"], w["down"], quant))
        X = _mixed(X, w, "ffn", "post_norm", ffn, cfg, iters)
    return _rms_norm(read_out(X), weights["final_norm"], cfg["rms_norm_eps"])


def _slip(logits, first):
    """Roll the logits of every position ``SLIP_EVERY - 1 (mod SLIP_EVERY)``
    half the vocabulary round; ``first`` (may be traced) the position of
    row 0."""
    at = (first + jnp.arange(logits.shape[1])) % SLIP_EVERY == SLIP_EVERY - 1
    return jnp.where(at[None, :, None],
                     jnp.roll(logits, logits.shape[-1] // 2, -1), logits)


def forward(weights, rows, cfg: dict, quant=None):
    """``rows [R, T]`` int32 token ids -> logits ``[R, T, vocab]`` float32
    (positions 0 .. T-1, causal)."""
    logits = matmul(hidden(weights, rows, cfg, quant), weights["head"],
                    _matmul_quant(quant))
    return _slip(logits, 0) if quant == "slip" else logits


def served_logits(weights, rows, cfg: dict, lo, n: int, quant=None):
    """The logits of positions ``lo .. lo + n - 1`` only (``lo`` may be
    traced; the span is clipped to the row), ``[R, n, vocab]`` float32: the
    hidden states of the whole row, the head over the span, ``HEAD_BLOCK``
    positions at a time (4,608 x 131,072 float32 logits would be 2.4 GB
    beside 9.6 GB of weights). What :func:`forward` gives there."""
    h = hidden(weights, rows, cfg, quant)
    lo = jnp.clip(lo, 0, h.shape[1] - n)
    h = lax.dynamic_slice_in_dim(h, lo, n, axis=1)
    step = min(HEAD_BLOCK, n)
    assert n % step == 0, (n, step)
    parts = lax.map(lambda x: matmul(x, weights["head"], _matmul_quant(quant)),
                    jnp.moveaxis(h.reshape(h.shape[0], n // step, step, -1),
                                 1, 0))
    logits = jnp.moveaxis(parts, 0, 1).reshape(h.shape[0], n, -1)
    return _slip(logits, lo) if quant == "slip" else logits
