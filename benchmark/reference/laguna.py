"""Plain Laguna reference (poolside Laguna-S-2.1): the forward pass.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no cache, no ring, no sorted dispatch. It imports nothing of the
program and takes nothing the program made: weights come from
:func:`init_weights` (the benchmark's own seeded init, which the family file
also hands to the program, relabelled and unchanged).

Follows the ``poolside/Laguna-S-2.1`` ``config.json``. Per layer ``l``,
pre-norm residual, RMSNorm eps ``rms_norm_eps``, no biases:
``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``.

- Attention with ``H_l = num_attention_heads_per_layer[l]`` query heads (48
  on a ``full_attention`` layer, 72 on a ``sliding_attention`` layer),
  ``num_key_value_heads`` kv heads of ``head_dim``, ``u = RMSNorm(x)``:
  ``q = u W_q``, ``k = u W_k``, ``v = u W_v``, ``g = sigmoid(u W_g)`` (one
  scalar a head: ``gating`` ``per-head``). RoPE on q and k by the layer's
  kind (``rope_parameters``): a full layer rotates the first
  ``partial_rotary_factor * head_dim`` dims with YaRN frequencies (theta,
  ``factor``, ``original_max_position_embeddings``, ``beta_fast`` /
  ``beta_slow``: transformers' ``_compute_yarn_parameters``) and multiplies
  cos and sin by ``attention_factor``; a window layer rotates every dim
  plainly. Head ``h`` reads kv head ``h // (H_l / kv)``.
  ``softmax(q k^T / sqrt(head_dim))`` over a causal mask and, on a window
  layer, the band ``i - sliding_window + 1 .. i``. ``o_h <- g_h o_h``, then
  ``W_o``. Attention is over the whole row, a block of queries at a time
  (``lax.map``), so that a row of 8,960 holds 72 heads x 128 queries x 8,960
  keys of float32 scores (330 MB) and not 23 GB. That is the only departure
  in form; the arithmetic is the plain one.
- Layers of ``mlp_layer_types`` ``dense``: a SwiGLU of ``intermediate_size``.
  The others: ``s = sigmoid(float32(u) W_r^T)``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  ``w = s[idx] / (sum s[idx] + 1e-20) * moe_routed_scaling_factor``;
  ``FFN(u) = sum_i w_i E_idx_i(u) + E_shared(u)``, every ``E`` a SwiGLU.
  Written as a ``lax.scan`` over the experts HELD with the routing weight (0
  where not chosen) as a mask: every held expert sees every token, nothing
  is sorted, no token can be dropped.
- **The share.** A configuration ``reduced`` in ``num_experts`` holds that
  many experts, from index 0, of the ``published`` number the router scores
  (one chip of an expert-parallel pair): the router has all its outputs, a
  token picks its experts among all of them and its weights are normalised
  over all its picks; a pick of an expert that is not held adds nothing. The
  partial sum, shared expert included, goes on to the next layer: what this
  chip computes, not the whole model's output.
- Final RMSNorm, untied head over the ``vocab_size`` rows held.

**Assumed, because the config does not say** (the same list is in the
configuration file): sigmoid router scores with a selection-only correction
bias (the config gives ``norm_topk_prob`` and a routed scaling factor, the
pairing of that router; it names no score function); the gate is a sigmoid
of a linear map of the block's normed input, applied to each head's output
before ``W_o``; RoPE pairs dim ``i`` with ``i + rot/2`` (``rotate_half``),
the rotated dims first; no q/k norm (no key names one); the window counts
the query's own position.

Weights are kept in the dtype they are made in (bfloat16 in the cell) and
each is raised to float32 where it is used.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales). Accumulation
stays float32, and so does the router's matmul, as a deployment in a lower
precision keeps it. ``"slip"`` is no precision but the planted fault that
``served_logit_gap_max`` is held against: the float32 pass with the logits of
one position in ``SLIP_EVERY`` rolled half the vocabulary round, so that the
token put first there is one the model did not choose (a row read from
another slot, a token lost in a transfer); the mean over a request's tokens
hardly moves.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
BIAS_STD = 0.01
SLIP_EVERY = 251      # a request of a few hundred tokens holds one or two
HI = lax.Precision.HIGHEST
QUERY_BLOCK = 128


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


def layer_shapes(cfg: dict, layer: int) -> dict:
    """Name -> shape of one layer's weights (``x @ W``: ``[in, out]``)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H = cfg["num_attention_heads_per_layer"][layer]
    kv = cfg["num_key_value_heads"] * hd
    sh = {"input_norm": (d,), "post_norm": (d,), "q": (d, H * hd),
          "k": (d, kv), "v": (d, kv), "g": (d, H), "o": (H * hd, d)}
    if cfg["mlp_layer_types"][layer] == "dense":
        f = cfg["intermediate_size"]
        sh.update(gate=(d, f), up=(d, f), down=(f, d))
    else:
        E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        fs = cfg["shared_expert_intermediate_size"]
        sh.update(router=(routed_experts(cfg), d),
                  router_bias=(routed_experts(cfg),),
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


def rope_inv_freq(spec: dict, head_dim: int):
    """One layer kind's rotary frequencies ``[rot / 2]`` (float32) and the
    factor on cos and sin, from its ``rope_parameters`` entry."""
    rot = int(head_dim * spec.get("partial_rotary_factor", 1))
    theta = float(spec["rope_theta"])
    freq = [theta ** (-2.0 * i / rot) for i in range(rot // 2)]
    if spec.get("rope_type", "default") == "default":
        return jnp.asarray(freq, jnp.float32), 1.0
    assert spec["rope_type"] == "yarn", spec["rope_type"]
    factor = float(spec["factor"])
    orig = float(spec["original_max_position_embeddings"])

    def dim_of(turns):   # the dim whose wave turns this often over ``orig``
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(spec.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(spec.get("beta_slow", 1)))), rot - 1)
    if high == low:
        high += 0.001
    out = []
    for i, f in enumerate(freq):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    scale = spec.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return jnp.asarray(out, jnp.float32), float(scale)


def _rope(x, spec, head_dim):
    """x [..., T, head_dim]: rotate the pairs ``(i, i + rot/2)`` of the
    first ``rot`` dims by ``t * inv_freq[i]``, t the position along the axis
    before last; the other dims pass."""
    inv, scale = rope_inv_freq(spec, head_dim)
    half = inv.shape[0]
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


def _swiglu(x, gate, up, down, quant):
    return matmul(jax.nn.silu(matmul(x, gate, quant)) * matmul(x, up, quant),
                  down, quant)


def _attention(x, w, cfg, layer, quant):
    R, T, _ = x.shape
    hd, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    H = cfg["num_attention_heads_per_layer"][layer]
    kind = cfg["layer_types"][layer]
    spec = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    q = matmul(x, w["q"], quant).reshape(R, T, H, hd).transpose(0, 2, 1, 3)
    k = matmul(x, w["k"], quant).reshape(R, T, KV, hd).transpose(0, 2, 1, 3)
    v = matmul(x, w["v"], quant).reshape(R, T, KV, hd).transpose(0, 2, 1, 3)
    g = jax.nn.sigmoid(matmul(x, w["g"], quant))               # [R, T, H]
    q, k = _rope(q, spec, hd), _rope(k, spec, hd)
    k = jnp.repeat(k, H // KV, axis=1)       # head h reads kv h // (H / KV)
    v = jnp.repeat(v, H // KV, axis=1)
    blk = math.gcd(T, QUERY_BLOCK)
    keys = jnp.arange(T)[None, :]

    def block(args):
        qb, first = args                                     # [R, H, blk, hd]
        at = (first + jnp.arange(blk))[:, None]
        seen = keys <= at
        if window is not None:
            seen &= keys > at - window
        s = jnp.einsum("rhqd,rhkd->rhqk", qb, k, precision=HI) \
            / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("rhqk,rhkd->rhqd", p, v, precision=HI)

    qs = q.reshape(R, H, T // blk, blk, hd).transpose(2, 0, 1, 3, 4)
    o = lax.map(block, (qs, jnp.arange(T // blk) * blk))   # [n,R,H,blk,hd]
    o = o.transpose(1, 0, 3, 2, 4).reshape(R, T, H, hd) * g[..., None]
    return matmul(o.reshape(R, T, H * hd), w["o"], quant)


def route(x, w, cfg):
    """The experts of every token, among ALL the router scores, and their
    weights: ``idx [N, k]``, ``weight [N, k]`` (float32 throughout)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w["router"].astype(jnp.float32).T,
        precision=HI))
    _, idx = lax.top_k(s + w["router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, -1)
    weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * cfg["moe_routed_scaling_factor"]
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


def forward(weights, rows, cfg: dict, quant=None):
    """``rows [R, T]`` int32 token ids -> logits ``[R, T, vocab]`` float32
    (positions 0 .. T-1, causal)."""
    slip, quant = quant == "slip", None if quant == "slip" else quant
    eps = cfg["rms_norm_eps"]
    x = weights["embed"][rows].astype(jnp.float32)
    for layer, w in enumerate(weights["layers"]):
        x = x + _attention(_rms_norm(x, w["input_norm"], eps), w, cfg, layer,
                           quant)
        h = _rms_norm(x, w["post_norm"], eps)
        x = x + (_experts(h, w, cfg, quant) if "router" in w
                 else _swiglu(h, w["gate"], w["up"], w["down"], quant))
    logits = matmul(_rms_norm(x, weights["final_norm"], eps),
                    weights["head"], quant)
    if slip:
        at = jnp.arange(SLIP_EVERY - 1, rows.shape[1], SLIP_EVERY)
        logits = logits.at[:, at].set(
            jnp.roll(logits[:, at], logits.shape[-1] // 2, -1))
    return logits
