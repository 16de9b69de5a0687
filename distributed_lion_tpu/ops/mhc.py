"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the residual as ``n`` streams a token,
mixed a token a sublayer. **The stream is held flat, ``X [..., n d]``**, stream
``i`` the lanes ``i d .. (i + 1) d``: a ``[..., n, d]`` array would put the
``n`` streams on a TPU's sublanes (4 rows padded to a tile of 16 in bfloat16,
and a relayout at every call); ``vec(X)`` below is then the array itself.

A sublayer ``F`` with its own mix ``(phi, a, b)`` (all of the mix float32,
the stored stream the model's compute dtype):

1. ``r = rsqrt(mean(vec(X)^2) + rms_eps)`` over all ``n d`` values of a
   token; ``m = r * (vec(X) phi)``, ``phi [n d, n n + 2 n]``, columns
   ``[pre (n) | post (n) | res (n n)]``.
2. ``Hpre = sigmoid(a[0] m_pre + b_pre)``; ``Hpost = 2 sigmoid(a[1] m_post +
   b_post)``; ``Z = clip(a[2] mat(m_res) + b_res, clamp)``; ``M_0 = exp(Z)``;
   ``M_t = T_c(T_r(M_{t-1}))``, ``iters`` times, ``T_r(M) = M / (rowsum(M) +
   eps)``, ``T_c(M) = M / (colsum(M) + eps)``; ``Hres = M_iters``: doubly
   stochastic to the iteration's residue (``:func:mhc_defect``).
3. ``u = sum_i Hpre[i] X[i]`` (:func:`mhc_pre`); the caller computes ``y =
   F(RMSNorm(u))``.
4. ``X'[i] = Hpost[i] y + sum_j Hres[i, j] X[j]`` (:func:`mhc_post`).

Steps 1-2 are ONE function, :func:`mhc_coeffs`; what a family assumes about
them (``benchmark/reference/xing4_0``'s note) is corrected there.

**``phi`` is kept packed** (:func:`pack_phi`): three bfloat16 parts of the
float32 matrix side by side on the lanes, ``[n d, 128]`` for ``n`` = 4 (72
lanes used). A bfloat16 stream times the three parts, summed in float32, is
the float32 product to the accumulator's rounding, in ONE pass of the matrix
unit: the kernel (``ops/pallas_mhc.mhc_pre``) and this file's ``jax.numpy``
form compute the same sums.

The coefficients travel as one float32 row a token, ``[..., COEF_LANES]``:
``[Hpre (n) | Hpost (n) | Hres (n n, row-major) | zeros]``, a whole lane tile
so that the kernels write and read it unpadded.

On a TPU, for a bfloat16 stream of whole lane tiles, :func:`mhc_pre` and
:func:`mhc_post` are the Mosaic kernels of ``ops/pallas_mhc`` (one pass over
the stream each, ``post`` in place); everything else (the CPU, float32
streams, a ``d`` that is no multiple of 128) runs the ``jax.numpy`` forms
below. No flag chooses.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

COEF_LANES = 128


@dataclasses.dataclass(frozen=True)
class MixConfig:
    """The mix's knobs (``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min/max`` and the model's ``rms_norm_eps``)."""
    n: int = 4
    iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)
    rms_eps: float = 1e-6

    @property
    def width(self) -> int:
        """Coefficients a token: ``[pre | post | res]``."""
        return self.n * self.n + 2 * self.n

    @property
    def packed_lanes(self) -> int:
        return -(-3 * self.width // 128) * 128


def pack_phi(phi, cfg: MixConfig):
    """float32 ``phi [n d, width]`` -> bfloat16 ``[n d, packed_lanes]``:
    ``[hi | mid | lo | zeros]``, ``hi + mid + lo = phi`` to 24 bits."""
    f32, bf = jnp.float32, jnp.bfloat16
    phi = phi.astype(f32)
    hi = phi.astype(bf)
    rest = phi - hi.astype(f32)
    mid = rest.astype(bf)
    lo = (rest - mid.astype(f32)).astype(bf)
    packed = jnp.concatenate([hi, mid, lo], -1)
    return jnp.pad(packed, ((0, 0), (0, cfg.packed_lanes - 3 * cfg.width)))


def project(flat, packed):
    """``vec(X) [N, n d]`` against the packed ``phi``: float32 ``[N,
    packed_lanes]``, the three parts' products still apart. A bfloat16 stream
    is one plain bfloat16 product (exact products, float32 sums); a float32
    stream (the CPU tests) multiplies in full precision."""
    if flat.dtype == jnp.bfloat16:
        return jax.lax.dot_general(
            flat, packed, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
    return jnp.dot(flat.astype(jnp.float32), packed.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def coeff_rows(m, a, b, cfg: MixConfig):
    """Step 2 on ``width`` same-shaped float32 arrays ``m`` (one a column of
    ``phi``; any shape: a kernel's ``[1, rows]`` lanes, this file's ``[N]``):
    the ``width`` coefficient arrays ``[pre | post | res]``. ``a``: three
    scalars; ``b``: ``width`` scalars. Element-wise throughout, so a kernel
    and the ``jax.numpy`` form that call it agree to the bit."""
    n = cfg.n

    def sigmoid(z):
        return 1.0 / (1.0 + jnp.exp(-z))

    pre = [sigmoid(a[0] * m[i] + b[i]) for i in range(n)]
    post = [2.0 * sigmoid(a[1] * m[n + i] + b[n + i]) for i in range(n)]
    lo, hi = cfg.clamp
    M = tuple(jnp.exp(jnp.clip(a[2] * m[2 * n + k] + b[2 * n + k], lo, hi))
              for k in range(n * n))

    def step(_, M):
        M = list(M)
        for i in range(n):                       # rows
            s = M[i * n]
            for j in range(1, n):
                s = s + M[i * n + j]
            inv = 1.0 / (s + cfg.eps)
            for j in range(n):
                M[i * n + j] = M[i * n + j] * inv
        for j in range(n):                       # then columns
            s = M[j]
            for i in range(1, n):
                s = s + M[i * n + j]
            inv = 1.0 / (s + cfg.eps)
            for i in range(n):
                M[i * n + j] = M[i * n + j] * inv
        return tuple(M)

    M = jax.lax.fori_loop(0, cfg.iters, step, M)
    return pre + post + list(M)


def lane_chunk(d: int) -> int:
    """Lanes of the stream a step of the kernels' (and this file's) sums
    takes: 512 where ``d`` divides, else 128, else all of ``d``."""
    return 512 if d % 512 == 0 else 128 if d % 128 == 0 else d


def row_sumsq(flat, chunk: int):
    """Sum of squares a row of ``flat [N, W]`` (any dtype) in float32
    ``[N, 1]``: ``chunk`` lanes at a time into ``[N, chunk]`` partial sums,
    those summed last (the kernel's order)."""
    f32 = jnp.float32
    s = None
    for k in range(flat.shape[-1] // chunk):
        x = flat[:, k * chunk:(k + 1) * chunk].astype(f32)
        s = x * x if s is None else s + x * x
    return jnp.sum(s, axis=-1, keepdims=True)


def mhc_coeffs(X, phi, a, b, cfg: MixConfig):
    """Steps 1-2: the stream ``X [..., n d]``, the packed ``phi``, ``a
    [3]``, ``b [width]`` -> the coefficient rows ``[..., COEF_LANES]``
    float32."""
    lead, n = X.shape[:-1], cfg.n
    d = X.shape[-1] // n
    flat = X.reshape(-1, n * d)
    w = cfg.width
    acc = project(flat, phi)
    r = jax.lax.rsqrt(row_sumsq(flat, lane_chunk(d)) / (n * d) + cfg.rms_eps)
    m = [(acc[:, k] + acc[:, w + k] + acc[:, 2 * w + k]) * r[:, 0]
         for k in range(w)]
    rows = coeff_rows(m, a, b, cfg)
    coef = jnp.stack(rows, -1)
    coef = jnp.pad(coef, ((0, 0), (0, COEF_LANES - w)))
    return coef.reshape(lead + (COEF_LANES,))


def _kernels_take(X, cfg) -> bool:
    from distributed_lion_tpu.ops import pallas_mhc

    return jax.default_backend() == "tpu" and pallas_mhc.kernel_takes(
        X.shape, X.dtype, cfg)


def mhc_pre(X, phi, a, b, cfg: MixConfig):
    """Steps 1-3: (``u [..., d]`` in the stream's dtype, the coefficient
    rows ``[..., COEF_LANES]`` float32)."""
    with jax.named_scope("mhc/pre"):
        if _kernels_take(X, cfg):
            from distributed_lion_tpu.ops import pallas_mhc

            return pallas_mhc.mhc_pre(X, phi, a, b, cfg)
        return mhc_pre_xla(X, phi, a, b, cfg)


def mhc_pre_xla(X, phi, a, b, cfg: MixConfig):
    coef = mhc_coeffs(X, phi, a, b, cfg)
    d = X.shape[-1] // cfg.n
    u = None
    for i in range(cfg.n):
        term = coef[..., i:i + 1] \
            * X[..., i * d:(i + 1) * d].astype(jnp.float32)
        u = term if u is None else u + term
    return u.astype(X.dtype), coef


def mhc_post(X, y, coef, valid, cfg: MixConfig):
    """Step 4: the stream rewritten, ``X'[i] = Hpost[i] y + sum_j Hres[i, j]
    X[j]``, in the stream's dtype. ``valid [...]`` bool: a row without a
    token keeps its streams as they were. The kernel rewrites the stream in
    place."""
    with jax.named_scope("mhc/post"):
        if _kernels_take(X, cfg):
            from distributed_lion_tpu.ops import pallas_mhc

            return pallas_mhc.mhc_post(X, y, coef, valid, cfg)
        return mhc_post_xla(X, y, coef, valid, cfg)


def mhc_post_xla(X, y, coef, valid, cfg: MixConfig):
    f32, n = jnp.float32, cfg.n
    d = X.shape[-1] // n
    xs = [X[..., j * d:(j + 1) * d].astype(f32) for j in range(n)]
    y = y.astype(f32)
    out = []
    for i in range(n):
        new = coef[..., n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            new = new + coef[..., k:k + 1] * xs[j]
        out.append(jnp.where(valid[..., None], new, xs[i]).astype(X.dtype))
    return jnp.concatenate(out, -1)


def expand(x, cfg: MixConfig):
    """The embedding as the first stream: ``[..., d] -> [..., n d]``, every
    stream a copy."""
    return jnp.concatenate([x] * cfg.n, -1)


def read_out(X, cfg: MixConfig):
    """What the final norm and the head see: the streams summed (float32
    sum, the stream's dtype back)."""
    d = X.shape[-1] // cfg.n
    total = X[..., :d].astype(jnp.float32)
    for i in range(1, cfg.n):
        total = total + X[..., i * d:(i + 1) * d].astype(jnp.float32)
    return total.astype(X.dtype)


def mhc_defect(coef, valid, cfg: MixConfig):
    """How far ``Hres`` is from doubly stochastic: the largest ``|rowsum -
    1|`` or ``|colsum - 1|`` over the ``valid`` rows, float32 scalar (0 with
    no valid row)."""
    n = cfg.n
    res = coef[..., 2 * n:2 * n + n * n].reshape(coef.shape[:-1] + (n, n))
    off = jnp.maximum(jnp.abs(res.sum(-1) - 1.0).max(-1),
                      jnp.abs(res.sum(-2) - 1.0).max(-1))
    return jnp.max(jnp.where(valid, off, 0.0))
