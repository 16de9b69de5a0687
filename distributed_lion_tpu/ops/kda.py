"""The gated delta rule with a decay a key channel (Kimi delta attention,
KDA): one recurrent step, and the same recurrence over a whole prompt in
chunks.

A head keeps a float32 state ``S [d_k, d_v]``. Position ``t`` brings a
query and a key ``q, k [d_k]`` (both already normalised), a value
``v [d_v]``, a log-decay ``g [d_k] <= 0`` a key channel and a write
strength ``beta`` in (0, 1):

    S' = diag(exp g) S;   S = S' + beta k (v - S'^T k)^T;   o = S^T q.

- :func:`kda_step`: that, once, for a batch of slots (the decode tick). On a
  TPU at whole lane tiles it is the Mosaic kernel ``ops/pallas_kda.kda_step``
  (the state aliased in and out, dead slots skipped); elsewhere
  :func:`kda_step_xla`. Chosen from what the call shows, like
  ``ops/attention.paged_kernel_applies``: no flag.
- :func:`kda_chunked`: ``T`` positions from a given state, a chunk at a
  time. On a TPU at whole lane tiles it is the Mosaic kernel
  ``ops/pallas_kda.kda_chunk`` (the same equations with every product a
  matmul and nothing of a chunk in HBM but what comes in and goes out);
  elsewhere :func:`kda_chunked_xla`, ``CHUNK`` at a time. With ``G_t`` the
  running sum of ``g`` inside a chunk and ``S_0`` the state it starts from,
  the chunk's corrected values ``u_t = v_t - S'_t^T k_t`` solve the unit
  lower-triangular system

      (I + A) U = V - (K . exp G) S_0,
      A[t, j] = beta_j  sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])   (j < t),

  the outputs are ``O = (Q . exp G) S_0 + B U`` with ``B`` the same sum with
  ``q_t`` for ``k_t`` and ``j <= t``, and the chunk leaves ``S_C = diag(exp
  G_C) S_0 + (K . beta . exp(G_C - G))^T U``. Everything that does not need
  ``S_0`` (``A``, ``B``, the solve of ``V`` and of ``K . exp G``) is done for
  all chunks at once; a ``lax.scan`` then carries the state through three
  small matmuls a chunk.

  **Exact at the gate's bound.** A gate bounded below at -5 sums to -320
  over a chunk of 64, and ``exp(320)`` is no float32: the textbook form that
  divides keys by their cumulative decay (``k_j exp(-G_j)``) overflows. In
  both paths every exponent is a DIFFERENCE of cumulative log-decays that is
  never positive. The kernel's note says how it takes them; the XLA form
  (:func:`decayed_products`) takes them pair by pair inside blocks of ``SUB``
  rows (pairs with ``j > t`` are masked to ``-inf`` before the exponential,
  not multiplied by zero after), and between blocks through the value at
  the edge that separates them. The price is a pairwise ``[SUB, SUB, d_k]``
  product a block inside one fused reduction where the textbook form has a
  matmul (5.9 ms of a layer's 15 at 4,096 positions when it was taken over
  whole chunks of 64, PERF.md section 6).
  The triangular system is solved in blocks of ``SUB`` rows: the diagonal
  blocks by substitution, row by row, the rest by block substitution. (The
  product form ``(I - A)(I + A^2)(I + A^4)...`` is all matmuls but cancels
  catastrophically on repeated keys, where the powers of ``A`` grow like
  binomials; a prompt that repeats a token has them.)

  A position past a row's length must neither decay nor write: the caller
  hands it ``g = 0`` and ``beta = 0`` and the state comes out as position
  ``length - 1`` left it.

All arithmetic is float32; the matmuls ask for ``Precision.HIGHEST`` (a
TPU's default would round float32 operands to bfloat16, sixty-four times a
chunk into the state).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
SUB = 16
HI = lax.Precision.HIGHEST


def kda_step_xla(state, q, k, v, g, beta, live=None):
    """One step in plain XLA. ``state [B, H, d_k, d_v]`` float32; ``q``,
    ``k``, ``g`` ``[B, H, d_k]``; ``v [B, H, d_v]``; ``beta [B, H]``;
    ``live [B]`` bool (None: all): a dead row keeps its state and gives
    zeros. Returns (``o [B, H, d_v]`` float32, the new state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., None]
    read = (decayed * k[..., None]).sum(-2)                  # S'^T k
    write = (v - read) * beta[..., None]
    new = decayed + k[..., None] * write[..., None, :]
    out = (new * q[..., None]).sum(-2)
    if live is None:
        return out, new
    keep = live[:, None, None]
    return jnp.where(keep, out, 0.0), jnp.where(keep[..., None], new, state)


def kda_step(state, q, k, v, g, beta, live):
    """The decode tick's step (the module note says which path). Shapes as
    :func:`kda_step_xla`; ``live`` is required: the kernel is told which
    slots to skip."""
    from distributed_lion_tpu.ops import pallas_kda

    with jax.named_scope("kda/step"):
        if jax.default_backend() == "tpu" and pallas_kda.kernel_takes(
                state.shape, state.dtype):
            return pallas_kda.kda_step(state, q, k, v, g, beta, live)
        return kda_step_xla(state, q, k, v, g, beta, live)


def unit_lower_solve(a, w, sub: int = SUB):
    """``X`` of ``(I + a) X = w`` for strictly lower-triangular ``a [..., C,
    C]`` and ``w [..., C, R]``: diagonal blocks of ``sub`` rows inverted by
    substitution (``sub - 1`` unrolled steps over all blocks at once), then
    block forward substitution (``C / sub`` steps of two matmuls)."""
    C = a.shape[-1]
    s = min(sub, C)
    nb = C // s
    assert nb * s == C, (C, s)
    lead = a.shape[:-2]
    blocks = a.reshape(lead + (nb, s, nb, s))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], -3)
    eye = jnp.eye(s, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], lead + (nb, s))]
    for i in range(1, s):
        done = jnp.stack(rows, -2)                           # [..., nb, i, s]
        rows.append(eye[i] - jnp.einsum("...j,...jk->...k",
                                        diag[..., i, :i], done, precision=HI))
    inv = jnp.stack(rows, -2)                                # [..., nb, s, s]
    wb = w.reshape(lead + (nb, s, w.shape[-1]))
    out = []
    for i in range(nb):
        rhs = wb[..., i, :, :]
        if i:
            rhs = rhs - jnp.einsum(
                "...tj,...jr->...tr",
                blocks[..., i, :, :i, :].reshape(lead + (s, i * s)),
                jnp.concatenate(out, -2), precision=HI)
        out.append(jnp.einsum("...tj,...jr->...tr", inv[..., i, :, :], rhs,
                              precision=HI))
    return jnp.concatenate(out, -2)


def decayed_products(q, k, G, sub: int = SUB):
    """``a[t, j] = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])`` for ``j < t``
    and ``b[t, j]`` the same with ``q_t`` for ``k_t`` and ``j <= t`` (zero
    elsewhere), ``[..., C, C]`` from ``q``, ``k``, ``G`` ``[..., C, d_k]``
    with ``G`` falling along ``C``. No exponent is ever positive (the module
    note): inside a diagonal block of ``sub`` rows the difference is taken
    pair by pair before the exponential (``[sub, sub, d_k]`` in one fused
    reduction); a block's rows against the earlier blocks' columns go through
    the value ``G`` has at the edge between them, ``exp(G_t - edge) exp(edge
    - G_j)``, two factors at most 1, and so through a matmul."""
    C, dk = G.shape[-2:]
    s = min(sub, C)
    nb = C // s
    lead = G.shape[:-2]
    qb, kb, Gb = (x.reshape(lead + (nb, s, dk)) for x in (q, k, G))
    at = jnp.arange(s)
    near = jnp.exp(jnp.where(
        (at[:, None] >= at[None, :])[:, :, None],
        Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))  # [., s, s, dk]
    kj = kb[..., None, :, :] * near
    a_near = jnp.where(at[:, None] > at[None, :],
                       (kb[..., :, None, :] * kj).sum(-1), 0.0)
    b_near = (qb[..., :, None, :] * kj).sum(-1)                # [., nb, s, s]
    rows_a, rows_b = [], []
    for i in range(nb):
        after = jnp.zeros(lead + (s, C - (i + 1) * s), G.dtype)
        parts_a, parts_b = [a_near[..., i, :, :], after], \
            [b_near[..., i, :, :], after]
        if i:
            edge = Gb[..., i - 1, -1:, :]          # G where block i - 1 ends
            fall = jnp.exp(Gb[..., i, :, :] - edge)
            lhs = jnp.concatenate([kb[..., i, :, :] * fall,
                                   qb[..., i, :, :] * fall], -2)
            rhs = k[..., :i * s, :] * jnp.exp(edge - G[..., :i * s, :])
            far = jnp.einsum("...tc,...jc->...tj", lhs, rhs, precision=HI)
            parts_a.insert(0, far[..., :s, :])
            parts_b.insert(0, far[..., s:, :])
        rows_a.append(jnp.concatenate(parts_a, -1))
        rows_b.append(jnp.concatenate(parts_b, -1))
    return jnp.concatenate(rows_a, -2), jnp.concatenate(rows_b, -2)


def kda_chunked(q, k, v, g, beta, state):
    """The prefill's chunked form (the module note says which path). Shapes
    as :func:`kda_chunked_xla`."""
    from distributed_lion_tpu.ops import pallas_kda

    with jax.named_scope("kda/chunk"):
        if jax.default_backend() == "tpu" and pallas_kda.chunk_kernel_takes(
                state.shape, state.dtype):
            return pallas_kda.kda_chunk(q, k, v, g, beta, state)
        return kda_chunked_xla(q, k, v, g, beta, state)


def kda_chunked_xla(q, k, v, g, beta, state, chunk: int = CHUNK):
    """``T`` positions from ``state``, in chunks (the module note). ``q``,
    ``k``, ``g`` ``[B, T, H, d_k]``; ``v [B, T, H, d_v]``; ``beta [B, T,
    H]``; ``state [B, H, d_k, d_v]`` float32. Returns (``o [B, T, H, d_v]``
    float32, the state after position ``T - 1``). A ``T`` that is no
    multiple of the chunk (a short one: of ``SUB``) is padded with inert
    positions (``g = 0``, ``beta = 0``)."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, -(-T // SUB) * SUB)      # whole sub-blocks
    pad = -T % C
    N = (T + pad) // C

    def heads(x):      # [B, T, H, d] -> [B, H, N, C, d], inert tail
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(B, H, N, C, x.shape[-1])

    q, k, v, g = heads(q), heads(k), heads(v), heads(g)
    beta = heads(beta[..., None])[..., 0]                # [B, H, N, C]
    G = jnp.cumsum(g, axis=-2)                           # inclusive
    a, b = decayed_products(q, k, G)
    a, b = a * beta[..., None, :], b * beta[..., None, :]
    eG = jnp.exp(G)
    solved = unit_lower_solve(a, jnp.concatenate([v, k * eG], -1))
    u0, k_in = solved[..., :dv], solved[..., dv:]
    q_in = q * eG
    k_out = k * beta[..., None] * jnp.exp(G[..., -1:, :] - G)
    keep = eG[..., -1, :]                                # [B, H, N, dk]

    def one(S, xs):
        u0, k_in, q_in, b, k_out, keep = xs
        u = u0 - jnp.einsum("bhtk,bhkv->bhtv", k_in, S, precision=HI)
        o = jnp.einsum("bhtk,bhkv->bhtv", q_in, S, precision=HI) \
            + jnp.einsum("bhtj,bhjv->bhtv", b, u, precision=HI)
        S = S * keep[..., None] \
            + jnp.einsum("bhtk,bhtv->bhkv", k_out, u, precision=HI)
        return S, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (u0, k_in, q_in, b, k_out, keep))
    state, o = lax.scan(one, state.astype(f32), xs)      # o [N, B, H, C, dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, N * C, H, dv)[:, :T]
    return o, state
