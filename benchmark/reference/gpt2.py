"""Plain GPT-2 reference: forward, loss, gradients and majority-vote Lion.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program made: weights come from :func:`init_weights` (the benchmark's own
seeded init, which the drivers also hand to the program). Layers are kept
stacked ``[L, ...]`` and walked with ``lax.scan`` so a 48-layer model
compiles as fast as a 12-layer one.

Follows Radford et al. 2019 / the ``openai-community/gpt2`` ``config.json``:
pre-LN decoder, learned positions, ``gelu_new`` (tanh) MLP, tied output
head, LayerNorm eps 1e-5. ``c_attn_w`` is HF's ``[d, 3d]`` (q | k | v).

``quant`` puts a lower precision in the matmuls (the control of
``correct``): ``"bf16"`` rounds matmul operands to bfloat16, ``"int8"`` is
W8A8 with per-token / per-output-channel absmax scales, ``"fp8"`` is e4m3
with per-tensor absmax scales. Accumulation stays float32 in all of them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def shapes(cfg: dict) -> dict:
    d, L, V, P = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    return {
        "wte": (V, d), "wpe": (P, d), "ln_f_g": (d,), "ln_f_b": (d,),
        "ln_1_g": (L, d), "ln_1_b": (L, d), "ln_2_g": (L, d), "ln_2_b": (L, d),
        "c_attn_w": (L, d, 3 * d), "c_attn_b": (L, 3 * d),
        "attn_proj_w": (L, d, d), "attn_proj_b": (L, d),
        "c_fc_w": (L, d, 4 * d), "c_fc_b": (L, 4 * d),
        "mlp_proj_w": (L, 4 * d, d), "mlp_proj_b": (L, d),
    }


def shapes_names() -> tuple:
    """The names of the weight arrays (the keys of :func:`shapes`)."""
    return ("wte", "wpe", "ln_f_g", "ln_f_b") + _PER_LAYER


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """GPT-2's init from ``key`` (:func:`seed_key` of the seed): N(0, 0.02)
    matrices, residual projections scaled by 1/sqrt(2 L), LayerNorm gain 1,
    biases 0. Call it inside one ``jax.jit`` WITH THE KEY AS AN ARGUMENT,
    so that the weights are made on the device and the compiled program is
    the same for every seed (a seed traced in as a constant would miss the
    compilation cache in every run)."""
    sh = shapes(cfg)
    resid = STD / math.sqrt(2 * cfg["n_layer"])
    std = {"wte": STD, "wpe": STD, "c_attn_w": STD, "c_fc_w": STD,
           "attn_proj_w": resid, "mlp_proj_w": resid}
    keys = jax.random.split(key, len(std))
    out = {}
    for key, name in zip(keys, sorted(std)):
        out[name] = (jax.random.normal(key, sh[name], jnp.float32)
                     * std[name]).astype(dtype)
    for name, shape in sh.items():
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, dtype)
        elif name not in out:
            out[name] = jnp.zeros(shape, dtype)
    return out


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
def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, n_head, eps, quant):
    B, T, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, w["ln_1_g"], w["ln_1_b"], eps)
    qkv = matmul(h, w["c_attn_w"], quant) + w["c_attn_b"]
    q, k, v = (a.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                   precision=lax.Precision.HIGHEST)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + matmul(a, w["attn_proj_w"], quant) + w["attn_proj_b"]
    h = _layer_norm(x, w["ln_2_g"], w["ln_2_b"], eps)
    h = _gelu_new(matmul(h, w["c_fc_w"], quant) + w["c_fc_b"])
    return x + matmul(h, w["mlp_proj_w"], quant) + w["mlp_proj_b"]


_PER_LAYER = ("ln_1_g", "ln_1_b", "ln_2_g", "ln_2_b", "c_attn_w", "c_attn_b",
              "attn_proj_w", "attn_proj_b", "c_fc_w", "c_fc_b", "mlp_proj_w",
              "mlp_proj_b")


def forward(weights: dict, tokens, cfg: dict, quant=None):
    """tokens ``[B, T]`` -> logits ``[B, T, V]`` float32."""
    w = {k: v.astype(jnp.float32) for k, v in weights.items()}
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    T = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:T]

    def body(x, layer):
        return _block(x, layer, cfg["n_head"], eps, quant), None

    x, _ = lax.scan(body, x, {k: w[k] for k in _PER_LAYER})
    x = _layer_norm(x, w["ln_f_g"], w["ln_f_b"], eps)
    return matmul(x, w["wte"].T, quant)


def clm_loss(weights: dict, tokens, cfg: dict, quant=None):
    """Mean next-token cross entropy over ``[B, T-1]``."""
    logits = forward(weights, tokens, cfg, quant)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()


def loss_and_grad(weights: dict, rows, cfg: dict, micro: int, quant=None):
    """Mean loss and gradient over ``rows [N, T]``, ``micro`` rows at a time
    (equal blocks, so the mean of block means is the mean)."""
    n = rows.shape[0]
    if n % micro:
        raise ValueError(f"{n} rows do not split into blocks of {micro}")
    blocks = rows.reshape(n // micro, micro, rows.shape[1])
    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), weights)

    def body(carry, block):
        loss_sum, gsum = carry
        loss, g = jax.value_and_grad(clm_loss)(weights, block, cfg, quant)
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
