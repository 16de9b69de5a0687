"""Plain dots3-note reference (dots-studio ``dots3-note-prev``): the forward
pass of the language model.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no cache, no pages, no ring, no absorbed attention, no sorted
dispatch, no batching beyond the rows it is given. It imports nothing of the
program and takes nothing the program made: weights come from
:func:`init_weights` (the benchmark's own seeded init, which the family file
also hands to the program, relabelled and unchanged).

Follows the catalog row's ``config``; what that config names without settling
is listed under ``assumed`` in the configuration file, in the same words, and
**each is ONE function here** (:func:`lora_rescale`, :func:`index_scores`,
:func:`index_rope`, :func:`kept`, :func:`head_gate`, :func:`in_window`,
:func:`_rope`, :func:`route`), so that a correction against the published
code is one line. ``d`` = ``hidden_size``, eps ``rms_norm_eps`` everywhere,
no biases but the indexer's LayerNorm, ``u = RMSNorm(x)``:

- Block: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; logits
  ``= RMSNorm(x_L) W_head``.
- **Latent attention** of either kind, with ``(H, dn, dr, dv, rq, rkv,
  theta)`` the kind's own: ``c_q = s_q RMSNorm(u W_qa)``, ``q = c_q W_qb`` ->
  heads of ``[q_nope | q_rope]``, RoPE(theta, pairs ``(2i, 2i + 1)``) on
  ``q_rope``; ``u W_kva = [c | k_r]``, ``c_kv = s_kv RMSNorm(c)``, ``k_r``
  roped, one for all heads; ``c_kv W_kvb`` -> heads of ``[k_nope | v]``;
  ``softmax`` over the layer kind's key set of ``(q_nope . k_nope + q_rope .
  k_r) / sqrt(dn + dr)``; ``g = sigmoid(u W_g)`` a head; ``out = concat_h(g_h
  o_h) W_o``. ``s_q, s_kv`` = :func:`lora_rescale`.
- **Full** layers (no ``swa_`` prefix): the key set is what the indexer
  keeps. ``qI = c_q W_qI`` -> ``index_n_heads`` heads of ``index_head_dim``,
  ``kI = LayerNorm(u W_kI)`` (gain and bias), both through
  :func:`index_rope`; ``w = (u W_wI) / sqrt(heads x dim)``; ``I[t, s] = sum_j
  w[t, j] ReLU(qI[t, j] . kI[s])`` for ``s <= t``; a query with ``t + 1 <=
  index_topk`` attends every ``s <= t``, else the ``index_topk`` positions of
  largest ``I[t, s]``, ties to the lower ``s`` (:func:`kept`: ``lax.top_k``'s
  threshold, ties counted from the lowest position up).
- **Sliding** layers (the ``swa_`` keys): ``s`` in ``t - window + 1 .. t``
  (``sliding_window_size`` counts the query's own position).
- FFN: layers below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; the others ``s = sigmoid(float32(u) W_r^T)``, the
  ``num_experts_per_tok`` largest of ``s + bias`` picked, weights ``s_e /
  sum_picked s * routed_scaling_factor``, ``sum_e w_e E_e(u) + Shared(u)``,
  written as a ``lax.scan`` over the experts HELD (banks ``0 .. n - 1`` where
  the file holds a share: a pick of any other expert adds nothing here) with
  the routing weight as a mask: nothing is sorted, no token can be dropped.

Attention is computed a block of ``QUERY_BLOCK`` queries at a time over all
the row's keys, one head at a time inside a block, so that a 16,384-token row
holds 67 MB of scores and not 137 GB; that is the only departure in form.

Weights are kept in the dtype they are made in (bfloat16 in the cell) and
each is raised to float32 where it is used.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales); accumulation, the
attention products, the router, the indexer's scores and the selection stay
float32. Controls that are no precision: ``"nosel"`` (every full layer
attends every visible position: a program that never selects), ``"noresc"``
(``s_q = s_kv = 1``: a program without the rescale), ``"slip"`` (the planted
fault ``served_logit_gap_max`` is held against: the logits of one position
in ``SLIP_EVERY`` rolled half the vocabulary round).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
BIAS_STD = 0.01
SLIP_EVERY = 251
QUERY_BLOCK = 1024    # queries a step of attention: [1024, T] scores a head
HEAD_BLOCK = 1024     # positions a step of the head in served_logits
HI = lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"
NOT_PRECISIONS = ("slip", "nosel", "noresc")


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def routed_experts(cfg: dict) -> int:
    """The router's outputs: the published number where the file holds a
    share of the experts, else ``n_routed_experts``."""
    if "n_routed_experts" in cfg.get("reduced", ()):
        return int(cfg["published"]["n_routed_experts"])
    return int(cfg["n_routed_experts"])


def geometry(cfg: dict, kind: str) -> dict:
    """The layer kind's own latent attention: ``(H, dn, dr, dv, rq, rkv,
    theta)`` under short names."""
    pre = "swa_" if kind == SLIDING else ""
    return {"H": cfg[pre + "num_attention_heads"],
            "dn": cfg[pre + "qk_nope_head_dim"],
            "dr": cfg[pre + "qk_rope_head_dim"],
            "dv": cfg[pre + "v_head_dim"], "rq": cfg[pre + "q_lora_rank"],
            "rkv": cfg[pre + "kv_lora_rank"],
            "theta": float(cfg[pre + "rope_theta"])}


def layer_shapes(cfg: dict, layer: int) -> dict:
    """Name -> shape of one layer's weights (``x @ W``: ``[in, out]``)."""
    d, kind = cfg["hidden_size"], kinds(cfg)[layer]
    g = geometry(cfg, kind)
    H, qk = g["H"], g["dn"] + g["dr"]
    sh = {
        "input_norm": (d,), "post_norm": (d,),
        "q_a": (d, g["rq"]), "q_a_norm": (g["rq"],),
        "q_b": (g["rq"], H * qk),
        "kv_a": (d, g["rkv"] + g["dr"]), "kv_a_norm": (g["rkv"],),
        "kv_b": (g["rkv"], H * (g["dn"] + g["dv"])),
        "g": (d, H), "o": (H * g["dv"], d),
    }
    if kind == FULL:
        Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        sh.update(idx_q=(g["rq"], Hi * di), idx_k=(d, di),
                  idx_k_norm=(di,), idx_k_bias=(di,), idx_w=(d, Hi))
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        sh.update(gate=(d, f), up=(d, f), down=(f, d))
    else:
        E, held = routed_experts(cfg), cfg["n_routed_experts"]
        f = cfg["moe_intermediate_size"]
        fs = cfg["n_shared_experts"] * f
        sh.update(router=(E, d), router_bias=(E,),
                  exp_gate=(held, d, f), exp_up=(held, d, f),
                  exp_down=(held, f, d),
                  sh_gate=(d, fs), sh_up=(d, fs), sh_down=(fs, d))
    return sh


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Seeded weights: every matrix N(0, 0.02) (the indexer's three too:
    with them a query's kept positions lie scattered over the whole context
    and change from token to token; the cell's note gives the measured
    shares), norm gains 1, the indexer's LayerNorm bias 0, the selection
    bias N(0, 0.01) in float32 (so that the choice and the weighting
    differ). One key a leaf, folded from ``key`` by the leaf's number. Call
    it inside one ``jax.jit`` WITH THE KEY AS AN ARGUMENT (see
    ``reference/gpt2.init_weights``)."""
    count = iter(range(1 << 20))

    def leaf(name, shape):
        k = jax.random.fold_in(key, next(count))
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        if name == "idx_k_bias":
            return jnp.zeros(shape, dtype)
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


def _round_bf16(x):
    """float32 rounded to bfloat16's bits, kept in float32
    (``lax.reduce_precision``: a cast pair may be optimised away)."""
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


def _matmul_quant(quant):
    """What ``quant`` puts in the matmuls: the controls that are no
    precision leave them float32."""
    return None if quant in NOT_PRECISIONS else quant


# ------------------------------------------------- the assumed, one each
def lora_rescale(cfg: dict, rank: int) -> float:
    """``apply_mla_qkv_lora_rescale``: a latent of ``rank`` is multiplied
    after its norm by ``sqrt(hidden_size / rank)``, in both kinds of layer;
    1 where the key is false."""
    if not cfg.get("apply_mla_qkv_lora_rescale", False):
        return 1.0
    return math.sqrt(cfg["hidden_size"] / rank)


def _rope(x, theta):
    """The attention's RoPE. x [..., T, dim]: rotate the pairs ``(2i, 2i +
    1)`` by ``t * theta ** (-2i / dim)``, t the position along the axis
    before last."""
    T, dim = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def index_rope(x, cfg: dict):
    """The indexer's RoPE. x [..., T, index_head_dim]: rotate a head's first
    ``qk_rope_head_dim`` values in the pairs ``(i, i + rot / 2)`` by ``t *
    rope_theta ** (-2i / rot)``, pass the rest."""
    T, rot = x.shape[-2], cfg["qk_rope_head_dim"]
    inv = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


def index_scores(qi, wi, ki):
    """``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])``: qi ``[Hi, Q,
    di]``, wi ``[Q, Hi]``, ki ``[T, di]`` -> ``[Q, T]`` float32, one index
    head at a time."""
    def one(acc, args):
        q, w = args                                    # [Q, di], [Q]
        s = jnp.matmul(q, ki.T, precision=HI)
        return acc + w[:, None] * jnp.maximum(s, 0.0), None

    acc, _ = lax.scan(one, jnp.zeros((qi.shape[1], ki.shape[0]), jnp.float32),
                      (qi, wi.T))
    return acc


def kept(scores, visible, topk: int):
    """The positions each query attends in a full layer: ``scores [Q, T]``,
    ``visible [Q, T]`` (``s <= t``) -> bool ``[Q, T]``. Every visible
    position where no more than ``topk`` are visible; else the ``topk`` of
    largest score, ties to the lower position: above the ``topk``-th
    largest value (``lax.top_k``) all, at it the lowest positions that fill
    the count."""
    masked = jnp.where(visible, scores, -jnp.inf)
    k = min(topk, scores.shape[-1])
    tau = lax.top_k(masked, k)[0][..., -1:]    # -inf where fewer are visible
    above, tied = masked > tau, (masked == tau) & visible
    need = k - above.sum(-1, keepdims=True)
    return visible & (above | (tied & (jnp.cumsum(tied, -1) <= need)))


def in_window(q_pos, k_pos, window: int):
    """A sliding layer's key set: ``sliding_window_size`` positions, the
    query's own counted."""
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


def head_gate(u, w, quant):
    """``headwise``: one sigmoid gate a head from the block's normed input,
    ``[R, T, H]``, applied to the head's output before ``W_o``."""
    return jax.nn.sigmoid(matmul(u, w["g"], quant))


# ---------------------------------------------------------------- forward
def _rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _swiglu(x, gate, up, down, quant):
    return matmul(jax.nn.silu(matmul(x, gate, quant)) * matmul(x, up, quant),
                  down, quant)


def _attention(u, w, cfg, kind, quant, control, tap=None):
    """One row at a time (``lax.map`` over R), a block of queries at a time,
    a head at a time."""
    T = u.shape[1]
    g, eps = geometry(cfg, kind), cfg["rms_norm_eps"]
    H, dn, dr, dv = g["H"], g["dn"], g["dr"], g["dv"]
    s_q = s_kv = 1.0
    if control != "noresc":
        s_q, s_kv = lora_rescale(cfg, g["rq"]), lora_rescale(cfg, g["rkv"])
    Qb = min(QUERY_BLOCK, T)
    pad = -T % Qb
    t_all = jnp.arange(T)
    tapped = tap is not None and kind == FULL

    def row(ur):                                            # [T, d]
        c_q = s_q * _rms_norm(matmul(ur, w["q_a"], quant), w["q_a_norm"], eps)
        q = matmul(c_q, w["q_b"], quant).reshape(T, H, dn + dr)
        q = q.transpose(1, 0, 2)                            # [H, T, .]
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], g["theta"])], -1)
        kv = matmul(ur, w["kv_a"], quant)
        c_kv = s_kv * _rms_norm(kv[:, :g["rkv"]], w["kv_a_norm"], eps)
        k_r = _rope(kv[:, g["rkv"]:], g["theta"])           # [T, dr]
        kvx = matmul(c_kv, w["kv_b"], quant).reshape(T, H, dn + dv)
        kvx = kvx.transpose(1, 0, 2)                        # [H, T, .]
        if kind == FULL:
            Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
            qi = matmul(c_q, w["idx_q"], quant).reshape(T, Hi, di)
            qi = index_rope(qi.transpose(1, 0, 2), cfg)     # [Hi, T, di]
            ki = index_rope(_layer_norm(
                matmul(ur, w["idx_k"], quant), w["idx_k_norm"],
                w["idx_k_bias"], eps), cfg)                 # [T, di]
            wi = matmul(ur, w["idx_w"], quant) / math.sqrt(Hi * di)
            qi = jnp.pad(qi, ((0, 0), (0, pad), (0, 0)))
            wi = jnp.pad(wi, ((0, pad), (0, 0)))

        def block(b0):
            p = b0 + jnp.arange(Qb)
            causal = t_all[None, :] <= p[:, None]           # [Qb, T]
            if kind == SLIDING:
                seen = in_window(p[:, None], t_all[None, :],
                                 cfg["sliding_window_size"])
            elif control == "nosel":
                seen = causal
            else:
                seen = kept(index_scores(
                    lax.dynamic_slice_in_dim(qi, b0, Qb, 1),
                    lax.dynamic_slice_in_dim(wi, b0, Qb, 0), ki),
                    causal, cfg["index_topk"])

            def head(args):
                qh, kvh = args                    # [T + pad, .], [T, .]
                qb = lax.dynamic_slice_in_dim(qh, b0, Qb, 0)
                kh = jnp.concatenate([kvh[:, :dn], k_r], -1)
                s = jnp.matmul(qb, kh.T, precision=HI) / math.sqrt(dn + dr)
                pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
                return jnp.matmul(pr, kvh[:, dn:], precision=HI)

            o = lax.map(head, (qp, kvx))                    # [H, Qb, dv]
            return o.transpose(1, 0, 2).reshape(Qb, H * dv), \
                seen if tapped else jnp.zeros((), bool)

        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        o, seen = lax.map(block, jnp.arange(0, T + pad, Qb))
        o = o.reshape(-1, H * dv)[:T]
        o = (o.reshape(T, H, dv)
             * head_gate(ur[None], w, quant)[0][..., None]).reshape(T, H * dv)
        return matmul(o, w["o"], quant), \
            seen.reshape(-1, T)[:T] if tapped else seen

    out, seen = lax.map(row, u)
    if tapped:
        tap.append(seen)
    return out


def route(x, w, cfg):
    """The experts of every token and their weights: ``idx [N, k]``,
    ``weight [N, k]`` (float32 throughout; one routing group)."""
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

    held = w["exp_gate"].shape[0]      # experts 0 .. held - 1 are here
    routed, _ = lax.scan(one, jnp.zeros_like(flat),
                         (jnp.arange(held), w["exp_gate"], w["exp_up"],
                          w["exp_down"]))
    shared = _swiglu(flat, w["sh_gate"], w["sh_up"], w["sh_down"], quant)
    return (routed + shared).reshape(R, T, d)


def hidden(weights, rows, cfg: dict, quant=None, tap=None):
    """``rows [R, T]`` int32 token ids -> the final normed hidden states
    ``[R, T, hidden]`` float32. ``tap`` (a list): each full layer appends
    the positions every query attended, bool ``[R, T, T]``."""
    control, quant = quant, _matmul_quant(quant)
    eps = cfg["rms_norm_eps"]
    x = weights["embed"][rows].astype(jnp.float32)
    for w, kind in zip(weights["layers"], kinds(cfg)):
        u = _rms_norm(x, w["input_norm"], eps)
        x = x + _attention(u, w, cfg, kind, quant, control, tap)
        h = _rms_norm(x, w["post_norm"], eps)
        x = x + (_experts(h, w, cfg, quant) if "router" in w
                 else _swiglu(h, w["gate"], w["up"], w["down"], quant))
    return _rms_norm(x, weights["final_norm"], eps)


def selection_sets(weights, rows, cfg: dict, quant=None) -> list:
    """The positions every query attended in every full layer under
    ``quant`` (:func:`hidden`'s ``tap``): what
    ``scripts/dots3_selection_agreement.py`` compares between two
    precisions and between consecutive positions."""
    tap: list = []
    hidden(weights, rows, cfg, quant, tap)
    return tap


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
    positions at a time. What :func:`forward` gives there, without the
    whole row's logits."""
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
