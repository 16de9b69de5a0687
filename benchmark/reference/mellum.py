"""Plain Mellum 2 reference (JetBrains Mellum2-12B-A2.5B-Instruct): forward,
loss, gradients and majority-vote Lion.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no sort, no grouped product. It imports nothing of the program and
takes nothing the program made: weights come from :func:`init_weights` (the
benchmark's own seeded init, which the family file also hands to the
program, relabelled and unchanged; :func:`init_weights` says what they
are).

Follows the ``JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json``
(``model_type`` ``mellum``). Per layer, pre-norm residual, RMSNorm eps
``rms_norm_eps``, no biases; ``x`` is ``[S, hidden]``:

1. ``u = rmsnorm(x; g1)``; ``q = u Wq`` as ``num_attention_heads`` heads of
   ``head_dim``, ``k = u Wk``, ``v = u Wv`` as ``num_key_value_heads``
   heads; q and k each RMS-normed over a head's lanes with a learned weight;
   RoPE by ``rotate_half`` over the whole head: on a ``sliding_attention``
   layer ``inv_freq_j = theta^(-2j/head_dim)``, on a ``full_attention``
   layer YaRN's blend (transformers' ``_compute_yarn_parameters``: a linear
   ramp between the dims that turn ``beta_fast`` and ``beta_slow`` times in
   ``original_max_position_embeddings`` positions; below the ramp
   ``inv_freq``, above it ``inv_freq / factor``) with cos and sin times
   ``attention_factor``. Query head ``h`` reads kv head ``h // (heads /
   kv heads)``. Scores ``q k^T / sqrt(head_dim)``, causal; on a window layer
   position ``i`` sees keys ``i - sliding_window + 1 .. i``. Softmax in
   float32, ``x = x + (softmax(.) v) Wo``. Attention is over the whole row,
   a block of queries at a time under ``jax.checkpoint``, so that a row of
   8,192 holds 32 heads x 256 queries x 8,192 keys of float32 scores (268
   MB a row) and its backward keeps none of them. That is the only
   departure in form; the arithmetic is the plain one.
2. ``u = rmsnorm(x; g2)``; ``p = softmax(float32(u) R^T)`` over all the
   router's outputs; the ``num_experts_per_tok`` largest; ``w = p[idx] /
   sum p[idx]`` (``norm_topk_prob``); ``y = sum_i w_i (silu(u Wg_i) * (u
   Wu_i)) Wd_i`` over the picks HELD, written as a ``lax.scan`` over the
   held experts with the routing weight (0 where not chosen) as a mask:
   every held expert sees every token, nothing is sorted, no token can be
   dropped. ``x = x + y``. No shared expert.
3. After the layers ``rmsnorm(x; g_f)``, logits over the ``vocab_size``
   rows held of the untied head; the loss is the mean next-token
   cross-entropy, float32. No auxiliary loss (the config names no
   coefficient), no multi-token-prediction head (the config has no key for
   one).

**The share.** A configuration ``reduced`` in ``num_experts`` holds that many
experts, from index 0, of the ``published`` number the router scores (one
chip of four that share a layer): the router has all its outputs, a token
picks its experts among all of them and its weights are normalised over all
its picks; a pick of an expert that is not held adds nothing. What this chip
computes, not the whole model's output.

**Assumed, because the config does not say** (the same list is in the
configuration file): the q/k head norms and the float32 softmax router with
renormalised top-k weights (the convention of the family whose key names
this config uses); ``rotate_half`` pairs; the window counts the query's own
position; no auxiliary loss; dropout 0.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales), each with a
straight-through gradient. Accumulation stays float32, and so does the
router's matmul, as training in a lower precision keeps it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
QUERY_BLOCK = 256

_MATRICES = ("q", "k", "v", "o", "router", "exp_gate", "exp_up", "exp_down")
_GAINS = ("input_norm", "post_norm", "q_norm", "k_norm")
PER_LAYER = _GAINS + _MATRICES
TOP = ("embed", "head", "final_norm")


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def routed_experts(cfg: dict) -> int:
    """The router's outputs: the published number where the file was
    ``reduced`` in ``num_experts`` (then ``num_experts`` are held)."""
    if "num_experts" in cfg.get("reduced", ()):
        return int(cfg["published"]["num_experts"])
    return int(cfg["num_experts"])


def layer_shapes(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {"input_norm": (d,), "post_norm": (d,), "q_norm": (hd,),
            "k_norm": (hd,), "q": (d, H * hd), "k": (d, KV * hd),
            "v": (d, KV * hd), "o": (H * hd, d),
            "router": (routed_experts(cfg), d), "exp_gate": (E, d, f),
            "exp_up": (E, d, f), "exp_down": (E, f, d)}


def published_depth(cfg: dict) -> int:
    """Layers of the whole model: the published number where the file was
    ``reduced`` in ``num_hidden_layers``."""
    if "num_hidden_layers" in cfg.get("reduced", ()):
        return int(cfg["published"]["num_hidden_layers"])
    return int(cfg["num_hidden_layers"])


def init_weights(key, cfg: dict, dtype) -> dict:
    """Every matrix N(0, 0.02) but the two that write the residual stream
    (``o``, ``exp_down``), which are scaled by ``1 / sqrt(2 x depth)`` of the
    WHOLE model (as the GPT-2 cells' seeded weights are); every norm gain 1.
    Without the scaling the attention's output, nearly one vector for every
    late position of a row of uniform tokens, is as large as a token's own
    embedding: every token then prefers the same experts, and how many of
    them this chip holds moves with the seed. Traceable, so that the
    harness makes them on the device in one jitted call, the key an
    argument."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = layer_shapes(cfg)
    L = cfg["num_hidden_layers"]
    resid = 1.0 / math.sqrt(2 * published_depth(cfg))
    keys = iter(jax.random.split(key, 2 + L * len(_MATRICES)))

    def normal(shape, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (STD * scale)).astype(dtype)

    w = {"embed": normal((V, d)), "head": normal((V, d)),
         "final_norm": jnp.ones((d,), dtype), "layers": []}
    for _ in range(L):
        layer = {name: jnp.ones(shapes[name], dtype) for name in _GAINS}
        layer.update({name: normal(shapes[name], resid if name in (
            "o", "exp_down") else 1.0) for name in _MATRICES})
        w["layers"].append(layer)
    return w


# ------------------------------------------------------------- precision
def _q_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _q_fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ste(fn, x):
    """Quantise in the forward pass, pass the gradient straight through."""
    return x + lax.stop_gradient(fn(x) - x)


def matmul(x, w, quant):
    """``x [..., k] @ w [k, n]`` with both operands put through ``quant``."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "bf16":
        x = _ste(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), x)
        w = _ste(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), w)
    elif quant == "int8":
        x = _ste(lambda a: _q_int8(a, -1), x)   # per token
        w = _ste(lambda a: _q_int8(a, 0), w)    # per output channel
    elif quant == "fp8":
        x, w = _ste(_q_fp8, x), _ste(_q_fp8, w)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.matmul(x, w, precision=lax.Precision.HIGHEST)


# ---------------------------------------------------------------- forward
def rmsnorm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def inv_freq(spec: dict, dim: int) -> jnp.ndarray:
    """``[dim / 2]`` float32 rotary frequencies of one layer kind
    (``rope_parameters[kind]``): plain, or YaRN's blend."""
    theta = float(spec["rope_theta"])
    j = jnp.arange(0, dim, 2, dtype=jnp.float32)
    freq = 1.0 / theta ** (j / dim)
    if spec.get("rope_type", "default") == "default":
        return freq
    factor, orig = float(spec["factor"]), spec[
        "original_max_position_embeddings"]

    def dim_of(turns):       # the dim that turns `turns` times in `orig`
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(spec.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(spec.get("beta_slow", 1)))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high != low else 0.001), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def rope_table(spec: dict, dim: int, length: int):
    """cos, sin ``[length, dim / 2]``, scaled by the kind's
    ``attention_factor`` (YaRN: ``0.1 ln(factor) + 1`` unless given)."""
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq(spec, dim)
    scale = 1.0
    if spec.get("rope_type", "default") == "yarn":
        scale = float(spec.get("attention_factor")
                      or 0.1 * math.log(float(spec["factor"])) + 1.0)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, cos, sin):
    """x [B, S, heads, dim]: ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, window):
    """q [B, S, H, hd]; k, v [B, S, KV, hd] -> [B, S, H, hd]; a block of
    queries at a time, each block's scores recomputed in its backward."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    block = min(QUERY_BLOCK, S)
    while S % block:
        block //= 2
    qg = q.reshape(B, S // block, block, KV, H // KV, hd)
    key_pos = jnp.arange(S)[None, :]

    @jax.checkpoint
    def one(qb, start):
        scores = jnp.einsum("bsgrd,btgd->bgrst", qb, k,
                            precision=lax.Precision.HIGHEST) / math.sqrt(hd)
        pos = (start + jnp.arange(block))[:, None]
        seen = key_pos <= pos
        if window is not None:
            seen &= key_pos > pos - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgrst,btgd->bsgrd", probs, v,
                          precision=lax.Precision.HIGHEST)

    out = lax.map(lambda a: one(*a), (jnp.moveaxis(qg, 1, 0),
                                      jnp.arange(0, S, block)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def experts(u, layer, cfg: dict, quant):
    """``sum_i w_i E_idx_i(u)`` over the picks held; u [N, d]."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.matmul(
        u, layer["router"].astype(jnp.float32).T,
        precision=lax.Precision.HIGHEST), axis=-1)
    top, idx = lax.top_k(probs, k)
    top = top / top.sum(-1, keepdims=True)

    @jax.checkpoint
    def one(acc, inp):
        e, gate, up, down = inp
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        h = jax.nn.silu(matmul(u, gate, quant)) * matmul(u, up, quant)
        return acc + weight[:, None] * matmul(h, down, quant), None

    held = layer["exp_gate"].shape[0]
    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (jnp.arange(held), layer["exp_gate"], layer["exp_up"],
                       layer["exp_down"]))
    return out


def block(x, layer, cfg: dict, kind: str, quant):
    B, S, d = x.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    u = rmsnorm(x, layer["input_norm"], eps)
    q = rmsnorm(matmul(u, layer["q"], quant).reshape(B, S, H, hd),
                layer["q_norm"], eps)
    k = rmsnorm(matmul(u, layer["k"], quant).reshape(B, S, KV, hd),
                layer["k_norm"], eps)
    v = matmul(u, layer["v"], quant).reshape(B, S, KV, hd)
    cos, sin = rope_table(cfg["rope_parameters"][kind], hd, S)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    a = attention(rotate(q, cos, sin), rotate(k, cos, sin), v, window)
    x = x + matmul(a.reshape(B, S, H * hd), layer["o"], quant)
    u = rmsnorm(x, layer["post_norm"], eps)
    return x + experts(u.reshape(B * S, d), layer, cfg, quant).reshape(
        B, S, d)


def forward(weights: dict, tokens, cfg: dict, quant=None):
    """tokens [B, S] -> float32 logits [B, S, vocab held]."""
    x = weights["embed"].astype(jnp.float32)[tokens]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for layer, kind in zip(weights["layers"], kinds):
        x = jax.checkpoint(block, static_argnums=(2, 3, 4))(
            x, layer, cfg, kind, quant)
    x = rmsnorm(x, weights["final_norm"], cfg["rms_norm_eps"])
    return matmul(x, weights["head"].T, quant)


def clm_loss(weights: dict, tokens, cfg: dict, quant=None):
    """Mean next-token cross-entropy over ``tokens [B, S]``, float32."""
    logits = forward(weights, tokens, _hashable(cfg), quant)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return nll.mean()


class _Frozen(dict):
    """A configuration as a static argument of ``jax.checkpoint``."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def _hashable(cfg: dict) -> dict:
    return cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)


def loss_and_grad(weights: dict, rows, cfg: dict, micro: int, quant=None):
    """Mean loss and gradient over ``rows [N, T]``, ``micro`` rows at a time
    (equal blocks, so the mean of block means is the mean). One block needs
    no accumulator beside its gradient."""
    n = rows.shape[0]
    if n % micro:
        raise ValueError(f"{n} rows do not split into blocks of {micro}")
    cfg = _hashable(cfg)
    if n == micro:
        return jax.value_and_grad(clm_loss)(weights, rows, cfg, quant)
    blocks = rows.reshape(n // micro, micro, rows.shape[1])
    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), weights)

    def body(carry, rows_k):
        loss_sum, gsum = carry
        loss, g = jax.value_and_grad(clm_loss)(weights, rows_k, cfg, quant)
        return (loss_sum + loss, jax.tree.map(jnp.add, gsum, g)), None

    (loss_sum, gsum), _ = lax.scan(body, (jnp.float32(0), zero), blocks)
    k = n // micro
    return loss_sum / k, jax.tree.map(lambda g: g / k, gsum)


# ------------------------------------------------------ majority-vote Lion
def cosine_warmup_lr(count, peak, warmup, total):
    """transformers' ``get_cosine_schedule_with_warmup`` at step ``count``."""
    count = jnp.asarray(count, jnp.float32)
    warm = count / max(1.0, warmup)
    prog = (count - warmup) / max(1.0, total - warmup)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return peak * jnp.where(count < warmup, warm, jnp.maximum(0.0, cos))


def vote_lion_step(weights, momenta, grads, lr, wd, b1, b2):
    """One step of 1-bit majority-vote Lion over W workers.

    ``momenta`` and ``grads`` are lists (one per worker) of trees like
    ``weights``. Every worker votes ``sign(b1 m + (1-b1) g)`` with zero
    voting -1; the elected sign is +1 where the votes sum above zero and -1
    otherwise (a tie elects -1); ``p <- p (1 - lr wd) - lr elected``;
    each worker's ``m <- b2 m + (1-b2) g`` with its own gradient.
    """
    def ballot(m, g):
        return jnp.where(b1 * m + (1.0 - b1) * g > 0, 1, -1)

    total = jax.tree.map(lambda *x: sum(x),
                         *[jax.tree.map(ballot, m, g)
                           for m, g in zip(momenta, grads)])
    new_w = jax.tree.map(
        lambda p, t: p * (1.0 - lr * wd) - lr * jnp.where(t > 0, 1.0, -1.0),
        weights, total)
    new_m = [jax.tree.map(lambda m, g: b2 * m + (1.0 - b2) * g, m, g)
             for m, g in zip(momenta, grads)]
    return new_w, new_m
