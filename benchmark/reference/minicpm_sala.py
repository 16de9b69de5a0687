"""Plain MiniCPM-SALA reference (openbmb ``MiniCPM-SALA``): the forward pass.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST``: no
kernels, no cache, no pages, no chunks, no batching beyond the rows it is
given. It imports nothing of the program and takes nothing the program made:
weights come from :func:`init_weights` (the benchmark's own seeded init, which
the family file also hands to the program, relabelled and unchanged).

Follows the catalog row's ``config``; what that config does not settle (the
whole ``sparse_config``, the score aggregation, the decay, the norms' and
gates' forms, the residual's denominator) is listed under ``assumed`` in the
configuration file, in the same words. eps ``rms_norm_eps`` everywhere, no
biases, ``u = RMSNorm(x)``:

- Block: ``x <- x + c Mix(RMSNorm(x))``, ``x <- x + c SwiGLU(RMSNorm(x))``,
  ``c = scale_depth / sqrt(mup_denominator)``; ``x_0 = scale_emb E[token]``;
  logits ``= W_head (RMSNorm(x_L) / (hidden_size / dim_model_base))``.
- **Lightning** (``lightning-attn``; H heads of d): ``q, k, v = u W_q, u W_k,
  u W_v``; q and k RMS-normed over a head's d values with a gain, then RoPE
  (``rotate_half`` pairs ``(i, i + d/2)``, theta ``rope_theta``) over the
  whole head; the state ``S [d, d]`` a head, zero at position 0, **token by
  token under** ``lax.scan``: ``S_t = lambda_h S_{t-1} + k_t v_t^T``, ``o_t
  = d^-0.5 S_t^T q_t``, ``lambda_h = exp(-slope_h)`` (the layer's ``slope``
  leaf); ``o_t`` RMS-normed over the head with a gain; ``y = W_o
  (sigmoid(u W_z) * o)``.
- **Sparse** (``minicpm4``; H query heads in ``num_key_value_heads`` groups,
  no RoPE): q and k RMS-normed a head with a gain; compressed keys a kv
  head ``c_j = mean(k_{s j} .. k_{s j + w - 1})`` (``w = kernel_size``, ``s
  = kernel_stride``), visible to a query at ``p`` once ``s j + w - 1 <= p``.
  A query with ``p + 1 <= dense_len`` attends every position ``<= p``. Past
  it, **query by query**, for kv head g: ``a_{h,j} = softmax_j(d^-0.5 q_h .
  c_j)`` over the visible j; ``A_{g,j} = sum_{h in g} a_{h,j}``; block b
  (``block_size`` positions) scores the largest ``A_{g,j}`` among the
  visible windows that touch it; the first ``init_blocks`` blocks and the
  ``window_size / block_size`` last up to the query's own score +inf; the
  ``topk`` best are kept (ties to the lower index); softmax attention over
  the kept blocks' positions ``<= p``; ``y = W_o (sigmoid(u W_z) * o)``.
- SwiGLU: ``W_d (SiLU(u W_g) * u W_u)``.

Weights are kept in the dtype they are made in (bfloat16 in the cell) and
each is raised to float32 where it is used.

``quant`` puts a lower precision in the matmuls' operands (the control of
``correct``): ``"bf16"``, ``"int8"`` (W8A8, per-token / per-output-channel
absmax scales), ``"fp8"`` (e4m3, per-tensor absmax scales); accumulation,
the attention products, the selection and the recurrence stay float32.
``"state16"``: the float32 pass with every Lightning state rounded to
bfloat16 after every step. ``"nosel"``: the float32 pass in which every
query sees every block (a program that never selects). ``"slip"``: the
planted fault ``served_logit_gap_max`` is held against, the float32 pass
with the logits of one position in ``SLIP_EVERY`` rolled half the vocabulary
round.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STD = 0.02
SLIP_EVERY = 251
QUERY_BLOCK = 512     # queries a step of the sparse layer: [H, 512, T] scores
HEAD_BLOCK = 1024     # positions a step of the head in served_logits
HI = lax.Precision.HIGHEST
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def kinds(cfg: dict) -> list:
    return list(cfg["mixer_types"][:cfg["num_hidden_layers"]])


def slopes(cfg: dict, layer: int):
    """The Lightning heads' decay slopes, ``lambda = exp(-slope)``: ``2^(-8
    (h + 1) / H)``, the same in every layer (``layer`` is here so that a
    correction by layer is one line)."""
    del layer
    H = cfg["num_attention_heads"]
    return 2.0 ** (-8.0 * (jnp.arange(H, dtype=jnp.float32) + 1) / H)


def layer_shapes(cfg: dict, layer: int) -> dict:
    """Name -> shape of one layer's weights (``x @ W``: ``[in, out]``)."""
    d, H, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    f, ch = cfg["intermediate_size"], H * hd
    sh = {"input_norm": (d,), "post_norm": (d,), "gate": (d, f),
          "up": (d, f), "down": (f, d), "q": (d, ch), "z": (d, ch),
          "o": (ch, d), "q_norm": (hd,), "k_norm": (hd,)}
    if kinds(cfg)[layer] == SPARSE:
        kv = cfg["num_key_value_heads"] * hd
        sh.update(k=(d, kv), v=(d, kv))
    else:
        sh.update(k=(d, ch), v=(d, ch), o_norm=(hd,), slope=(H,))
    return sh


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Seeded weights: every matrix N(0, 0.02), norm gains 1, the Lightning
    slopes :func:`slopes` (float32). One key a leaf, folded from ``key`` by
    the leaf's number. Call it inside one ``jax.jit`` WITH THE KEY AS AN
    ARGUMENT (see ``reference/gpt2.init_weights``)."""
    count = iter(range(1 << 20))

    def leaf(name, shape, layer=0):
        k = jax.random.fold_in(key, next(count))
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        if name == "slope":
            return slopes(cfg, layer)
        return (jax.random.normal(k, shape, jnp.float32) * STD).astype(dtype)

    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed": leaf("embed", (V, d)), "head": leaf("head", (d, V)),
        "final_norm": leaf("final_norm", (d,)),
        "layers": [{name: leaf(name, shape, i)
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


# ---------------------------------------------------------------- forward
def _rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope_half(x, theta):
    """x [R, T, H, d]: rotate the pairs ``(i, i + d/2)`` by ``t * theta **
    (-2i / d)``, t the position along axis 1."""
    T, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _swiglu(x, w, quant):
    g, u = matmul(x, w["gate"], quant), matmul(x, w["up"], quant)
    return matmul(jax.nn.silu(g) * u, w["down"], quant)


def _head_norm(x, w, name, n, cfg, quant):
    """``x W`` as heads ``[R, T, n, d]``, RMS-normed a head."""
    R, T, _ = x.shape
    y = matmul(x, w[name], quant).reshape(R, T, n, cfg["head_dim"])
    return _rms_norm(y, w[name + "_norm"], cfg["rms_norm_eps"])


def _lightning(x, w, cfg, quant, state16):
    R, T, _ = x.shape
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    theta = float(cfg["rope_theta"])
    q = _rope_half(_head_norm(x, w, "q", H, cfg, quant), theta)
    k = _rope_half(_head_norm(x, w, "k", H, cfg, quant), theta)
    v = matmul(x, w["v"], quant).reshape(R, T, H, d)
    lam = jnp.exp(-w["slope"].astype(jnp.float32))[None, :, None, None]

    def step(S, xs):
        q, k, v = xs                                         # [R, H, d]
        S = lam * S + jnp.einsum("rhk,rhv->rhkv", k, v, precision=HI)
        o = jnp.einsum("rhkv,rhk->rhv", S, q, precision=HI) * d ** -0.5
        if state16:
            S = _round_bf16(S)
        return S, o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v))
    _, o = lax.scan(step, jnp.zeros((R, H, d, d), jnp.float32), xs)
    o = _rms_norm(jnp.moveaxis(o, 0, 1), w["o_norm"], cfg["rms_norm_eps"])
    o = o.reshape(R, T, H * d) * jax.nn.sigmoid(matmul(x, w["z"], quant))
    return matmul(o, w["o"], quant)


def kept_blocks(q, ck, p, sc: dict, n_blocks: int):
    """One row, a block of queries: which blocks each query keeps, a kv
    head. ``q [G, rep, Q, d]``; ``ck [G, J, d]``; ``p [Q]`` the queries'
    positions. Returns bool ``[G, Q, n_blocks]`` (a block past the query's
    own may be kept where fewer than ``topk`` lie before it: the causal
    mask drops it)."""
    s, w, bsz = sc["kernel_stride"], sc["kernel_size"], sc["block_size"]
    J = ck.shape[1]
    last = s * jnp.arange(J) + w - 1                  # window j's last key
    seen = last[None, :] <= p[:, None]                           # [Q, J]
    sim = jnp.einsum("grqd,gjd->grqj", q, ck, precision=HI) \
        / math.sqrt(q.shape[-1])
    a = jax.nn.softmax(jnp.where(seen, sim, -jnp.inf), -1)
    A = jnp.where(seen, jnp.where(seen, a, 0.0).sum(1), -jnp.inf)  # [G, Q, J]
    # the windows that touch block b: j with s j <= bsz b + bsz - 1 and
    # s j + w - 1 >= bsz b
    first_j = -((w - 1 - bsz * jnp.arange(n_blocks)) // s)
    touch = first_j[:, None] + jnp.arange((bsz + w - 2) // s + 1)[None, :]
    inside = (touch >= 0) & (touch < J) \
        & (s * touch <= (bsz * jnp.arange(n_blocks) + bsz - 1)[:, None])
    got = jnp.where(inside, A[..., jnp.clip(touch, 0, J - 1)], -jnp.inf)
    score = got.max(-1)                                  # [G, Q, n_blocks]
    b = jnp.arange(n_blocks)[None, :]
    own = (p // bsz)[:, None]
    forced = (b < sc["init_blocks"]) \
        | ((b > own - sc["window_size"] // bsz) & (b <= own))
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b <= own, score, -jnp.inf)
    _, idx = lax.top_k(score, min(sc["topk"], n_blocks))
    return (idx[..., None] == jnp.arange(n_blocks)).any(-2)


def _sparse(x, w, cfg, quant, nosel, tap=None):
    R, T, _ = x.shape
    H, G, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    sc = cfg["sparse_config"]
    s, wk, bsz = sc["kernel_stride"], sc["kernel_size"], sc["block_size"]
    q = _head_norm(x, w, "q", H, cfg, quant)
    k = _head_norm(x, w, "k", G, cfg, quant)
    v = matmul(x, w["v"], quant).reshape(R, T, G, d)
    J = max((T - wk) // s + 1, 0)
    n_blocks = -(-T // bsz)
    Qb = min(QUERY_BLOCK, T)
    pad = -T % Qb
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q = q.reshape(R, (T + pad) // Qb, Qb, G, H // G, d)
    t = jnp.arange(T)

    def row(args):
        qr, kr, vr = args               # [n, Qb, G, rep, d], [T, G, d] x 2
        kg, vg = kr.transpose(1, 0, 2), vr.transpose(1, 0, 2)    # [G, T, d]
        keys_of = s * jnp.arange(J)[:, None] + jnp.arange(wk)[None, :]
        ck = kg[:, keys_of].mean(2)                              # [G, J, d]

        def block(args):
            qb, p0 = args                                # [Qb, G, rep, d]
            p = p0 + jnp.arange(Qb)
            qg = qb.transpose(1, 2, 0, 3)                # [G, rep, Qb, d]
            causal = t[None, :] <= p[:, None]            # [Qb, T]
            every = (p + 1 <= sc["dense_len"])[:, None]
            kept = jnp.ones((G, Qb, n_blocks), bool) if nosel or not J \
                else kept_blocks(qg, ck, p, sc, n_blocks)
            mask = causal & (every | jnp.repeat(kept, bsz, -1)[..., :T])
            sim = jnp.einsum("grqd,gtd->grqt", qg, kg, precision=HI) \
                / math.sqrt(d)
            prob = jax.nn.softmax(
                jnp.where(mask[:, None], sim, -jnp.inf), -1)
            o = jnp.einsum("grqt,gtd->grqd", prob, vg, precision=HI)
            return o.transpose(2, 0, 1, 3).reshape(Qb, H * d), \
                kept.transpose(1, 0, 2)

        o, kept = lax.map(block, (qr, jnp.arange(qr.shape[0]) * Qb))
        return o.reshape(-1, H * d)[:T], kept.reshape(-1, G, n_blocks)[:T]

    o, kept = lax.map(row, (q, k, v))          # [R, T, H d], [R, T, G, nb]
    if tap is not None:
        tap.append(kept)
    o = o * jax.nn.sigmoid(matmul(x, w["z"], quant))
    return matmul(o, w["o"], quant)


def hidden(weights, rows, cfg: dict, quant=None, tap=None):
    """``rows [R, T]`` int32 token ids -> the final normed hidden states
    ``[R, T, hidden]`` float32, already divided for the head. ``tap`` (a
    list): each sparse layer appends the blocks every query kept, bool ``[R,
    T, kv heads, blocks]`` (only the entries of a query past ``dense_len``
    and of blocks at or before its own were used)."""
    state16, nosel = quant == "state16", quant == "nosel"
    quant = _matmul_quant(quant)
    eps = cfg["rms_norm_eps"]
    c = cfg["scale_depth"] / math.sqrt(cfg["mup_denominator"])
    x = weights["embed"][rows].astype(jnp.float32) * cfg["scale_emb"]
    for w in weights["layers"]:
        u = _rms_norm(x, w["input_norm"], eps)
        x = x + c * (_lightning(u, w, cfg, quant, state16) if "slope" in w
                     else _sparse(u, w, cfg, quant, nosel, tap))
        x = x + c * _swiglu(_rms_norm(x, w["post_norm"], eps), w, quant)
    return _rms_norm(x, weights["final_norm"], eps) \
        / (cfg["hidden_size"] / cfg["dim_model_base"])


def selection_sets(weights, rows, cfg: dict, quant=None) -> list:
    """The blocks every query kept in every sparse layer under ``quant``
    (:func:`hidden`'s ``tap``): what ``scripts/sala_selection_agreement.py``
    compares between two precisions."""
    tap: list = []
    hidden(weights, rows, cfg, quant, tap)
    return tap


def _matmul_quant(quant):
    """What ``quant`` puts in the matmuls: the controls that are no
    precision leave them float32."""
    return None if quant in ("slip", "state16", "nosel") else quant


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
