"""The decayed outer-product recurrence of Lightning attention: one
recurrent step, and the same recurrence over a whole prompt in chunks.

A head keeps a float32 state ``S [d_k, d_v]``. Position ``t`` brings a query
and a key ``q, k [d_k]`` and a value ``v [d_v]``; the head has one constant
decay ``lambda = exp(-slope)`` in (0, 1]:

    S_t = lambda S_{t-1} + k_t v_t^T;   o_t = S_t^T q_t.

Linear attention with a decay, no write correction (``ops/kda`` has the
delta rule, whose ``S'^T k`` term and per-channel gate this has not). The
caller scales ``o`` (``models/minicpm_sala``: ``head_dim^-0.5``).

- :func:`lightning_step`: that, once, for a batch of slots (the decode
  tick). On a TPU at whole lane tiles the Mosaic kernel
  ``ops/pallas_lightning.lightning_step`` (the state aliased in and out,
  dead slots skipped); elsewhere :func:`lightning_step_xla`. Chosen from
  what the call shows, like ``ops/kda.kda_step``: no flag.
- :func:`lightning_chunked`: ``T`` positions from a given state, a chunk at
  a time. On a TPU at whole lane tiles the kernel ``lightning_chunk``;
  elsewhere :func:`lightning_chunked_xla`, the same equations (the kernel's
  module note has them) under ``lax.scan``. A position at or past a row's
  ``lengths`` neither decays nor writes: the state comes out as position
  ``lengths - 1`` left it, and every decay factor is ``exp(-slope n)`` of a
  count ``n >= 0``, so none passes 1.

All arithmetic is float32; the XLA matmuls ask for ``Precision.HIGHEST``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
HI = lax.Precision.HIGHEST


def lightning_step_xla(state, q, k, v, decay, live=None):
    """One step in plain XLA. ``state [B, H, d_k, d_v]`` float32; ``q``,
    ``k`` ``[B, H, d_k]``; ``v [B, H, d_v]``; ``decay [H]``; ``live [B]``
    bool (None: all): a dead row keeps its state and gives zeros. Returns
    (``o [B, H, d_v]`` float32, the new state)."""
    f32 = jnp.float32
    q, k, v = (x.astype(f32) for x in (q, k, v))
    new = state * decay.astype(f32)[:, None, None] \
        + k[..., None] * v[..., None, :]
    out = (new * q[..., None]).sum(-2)
    if live is None:
        return out, new
    keep = live[:, None, None]
    return jnp.where(keep, out, 0.0), jnp.where(keep[..., None], new, state)


def lightning_step(state, q, k, v, decay, live):
    """The decode tick's step (the module note says which path). Shapes as
    :func:`lightning_step_xla`; ``live`` is required: the kernel is told
    which slots to skip."""
    from distributed_lion_tpu.ops import pallas_lightning as pk

    with jax.named_scope("lightning/step"):
        if jax.default_backend() == "tpu" and pk.kernel_takes(
                state.shape, state.dtype):
            return pk.lightning_step(state, q, k, v, decay, live)
        return lightning_step_xla(state, q, k, v, decay, live)


def lightning_chunked(q, k, v, slope, lengths, state):
    """The prefill's chunked form (the module note says which path). Shapes
    as :func:`lightning_chunked_xla`."""
    from distributed_lion_tpu.ops import pallas_lightning as pk

    with jax.named_scope("lightning/chunk"):
        if jax.default_backend() == "tpu" and pk.chunk_kernel_takes(
                state.shape, state.dtype):
            return pk.lightning_chunk(q, k, v, slope, lengths, state)
        return lightning_chunked_xla(q, k, v, slope, lengths, state)


def lightning_chunked_xla(q, k, v, slope, lengths, state, chunk: int = CHUNK):
    """``T`` positions from ``state``, in chunks. ``q``, ``k`` ``[B, T, H,
    d_k]``; ``v [B, T, H, d_v]``; ``slope [H]`` (``lambda = exp(-slope)``);
    ``lengths [B]`` int32; ``state [B, H, d_k, d_v]`` float32. Returns
    (``o [B, T, H, d_v]`` float32, the state after position ``lengths -
    1``)."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    C = min(chunk, T)
    pad = -T % C
    N = (T + pad) // C

    def heads(x):      # [B, T, H, d] -> [N, B, H, C, d]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        x = x.reshape(B, N, C, H, x.shape[-1])
        return x.transpose(1, 0, 3, 2, 4)

    at = jnp.arange(T + pad).reshape(N, 1, C)
    held = jnp.minimum(at + 1, lengths[None, :, None]) \
        - jnp.minimum(at - at % C, lengths[None, :, None])   # [N, B, C]
    writes = at < lengths[None, :, None]
    s = slope.astype(f32)[None, :, None]                     # [1, H, 1]

    def one(S, xs):
        q, k, v, n, w = xs                   # [B, H, C, d]; n, w [B, C]
        n = n.astype(f32)[:, None, :]                         # [B, 1, C]
        k = jnp.where(w[:, None, :, None], k, 0.0)
        d = jnp.where(jnp.tril(jnp.ones((C, C), bool)),
                      jnp.exp(-s[..., None] * (n[..., :, None]
                                               - n[..., None, :])), 0.0)
        p = jnp.einsum("bhik,bhjk->bhij", q, k, precision=HI) * d
        o = jnp.einsum("bhij,bhjv->bhiv", p, v, precision=HI) \
            + jnp.einsum("bhik,bhkv->bhiv", q * jnp.exp(-s * n)[..., None],
                         S, precision=HI)
        end = n[..., -1:]
        S = S * jnp.exp(-s * end)[..., None] + jnp.einsum(
            "bhjk,bhjv->bhkv", k * jnp.exp(-s * (end - n))[..., None], v,
            precision=HI)
        return S, o

    state, o = lax.scan(one, state.astype(f32),
                        (heads(q), heads(k), heads(v), held, writes))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, N * C, H, -1)[:, :T]
    return o, state
