"""Llama-class decoder transformer in pure JAX.

The reference's SFT/DPO workloads run Llama-2-7B from HF hub
(/root/reference/sft_llama2.py:141-154, dpo_llama2.py:133-152); here the
architecture is our own implementation — RMSNorm, rotary position embeddings,
SwiGLU MLP, grouped-query attention, no biases, separate (untied) LM head —
covering Llama-2/-3-style configs. TPU-first like gpt2.py: bf16 compute with
f32 accumulation/softmax, static shapes, per-block rematerialization.

Frozen-base quantization (the reference's QLoRA 4-bit path) plugs in via
``ops.quant``: any weight leaf may be a QuantizedTensor and ``_matmul``
dequantizes on the fly.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from distributed_lion_tpu.ops.attention import attention as shared_attention
from distributed_lion_tpu.ops.quant import maybe_dequant
from distributed_lion_tpu.parallel.tensor_parallel import (
    copy_to_tp_region,
    reduce_from_tp_region,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32          # < n_head → grouped-query attention
    d_model: int = 4096
    d_ff: int = 11008
    n_ctx: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    attn_impl: str = "auto"  # ops.attention: auto | xla
    seq_impl: str = "ring"   # sequence-parallel attention: ring | ulysses
    remat: bool = True  # per-block jax.checkpoint; off when activations fit
    remat_policy: str = "full"  # 'full' | 'dots' (keep matmul outputs,
    # recompute elementwise — models/gpt2._remat_policy); 'auto' asks the
    # trainer to pick from the shapes and the device's memory
    # (train/loop.apply_remat_policy) and never reaches llama_apply
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=256, n_layer=2, n_head=4, n_kv_head=2,
                    d_model=64, d_ff=128, n_ctx=128)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def small(**kw) -> "LlamaConfig":
        """A ~25M-param preset (at byte-level vocab): large enough for the
        auto comm defaults and meaningful CPU-mesh evidence runs (DPO
        step-rate rows taken without a chip), small enough that a
        1-core host steps it in seconds."""
        base = dict(vocab_size=256, n_layer=8, n_head=8, n_kv_head=4,
                    d_model=512, d_ff=1376, n_ctx=1024)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        base = dict(vocab_size=128256, n_layer=32, n_head=32, n_kv_head=8,
                    d_model=4096, d_ff=14336, n_ctx=8192, rope_theta=500000.0)
        base.update(kw)
        return LlamaConfig(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "LlamaConfig":
        """Resolve a CLI model name — single source for every entry point
        (run_clm / run_sft / run_dpo / run_generate)."""
        ctors = {"tiny": cls.tiny, "small": cls.small,
                 "llama2_7b": cls.llama2_7b, "llama3_8b": cls.llama3_8b}
        if name not in ctors:
            raise ValueError(
                f"unknown llama model_name {name!r}; pick one of "
                f"{sorted(ctors)}"
            )
        return ctors[name](**kw)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape) * std).astype(dtype)


def llama_init(key: jax.Array, cfg: LlamaConfig) -> dict:
    d, dt = cfg.d_model, cfg.param_dtype
    hd, nh, nkv = cfg.head_dim, cfg.n_head, cfg.n_kv_head
    std = 0.02
    keys = iter(jax.random.split(key, 2 + 7 * cfg.n_layer))
    params: dict = {
        "wte": _normal(next(keys), (cfg.vocab_size, d), std, dt),
        "lm_head": _normal(next(keys), (d, cfg.vocab_size), std, dt),
        "ln_f": {"scale": jnp.ones((d,), dt)},
        "blocks": [],
    }
    for _ in range(cfg.n_layer):
        params["blocks"].append({
            "ln_attn": {"scale": jnp.ones((d,), dt)},
            "attn": {
                "wq": _normal(next(keys), (d, nh * hd), std, dt),
                "wk": _normal(next(keys), (d, nkv * hd), std, dt),
                "wv": _normal(next(keys), (d, nkv * hd), std, dt),
                "wo": _normal(next(keys), (nh * hd, d), std / math.sqrt(2 * cfg.n_layer), dt),
            },
            "ln_mlp": {"scale": jnp.ones((d,), dt)},
            "mlp": {
                "w_gate": _normal(next(keys), (d, cfg.d_ff), std, dt),
                "w_up": _normal(next(keys), (d, cfg.d_ff), std, dt),
                "w_down": _normal(next(keys), (cfg.d_ff, d), std / math.sqrt(2 * cfg.n_layer), dt),
            },
        })
    return params


def _rms_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope_angles(t: int, head_dim: int, theta: float, offset=0) -> tuple:
    """cos/sin tables [T, head_dim/2] (f32). ``offset`` may be a traced
    scalar (sequence-parallel shard start), so the arange is static-length
    with the offset added."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    pos = jnp.arange(t, dtype=jnp.float32) + offset
    ang = jnp.outer(pos, inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, H, T, hd]; rotate pairs (even, odd) — the interleaved
    formulation. cos/sin are [T, hd/2] (one position track shared by the
    batch) or [B, T, hd/2] (per-row position tracks: the paged decode tick
    and left-padded batched generation gather each row its own angles)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    if cos.ndim == 3:
        c = cos[:, None, :, :].astype(x.dtype)
        s = sin[:, None, :, :].astype(x.dtype)
    else:
        c = cos[None, None, :, :].astype(x.dtype)
        s = sin[None, None, :, :].astype(x.dtype)
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape)


def _matmul(x, w):
    # dense / QuantizedTensor / LoraTensor (factored x@W + s·(x@A)@B) —
    # models.lora.lora_matmul is the single dispatch point
    from distributed_lion_tpu.models.lora import lora_matmul

    return lora_matmul(x, w)


@jax.named_scope("attn")
def _attention(x, p, cfg: LlamaConfig, cos, sin, tp_axis=None, seq_axis=None):
    """GQA attention; with ``tp_axis``, wq/wk/wv are column-parallel (this
    device holds n_head/tp query and n_kv_head/tp kv heads) and wo is
    row-parallel with a psum over the tensor axis (Megatron pattern). With
    ``seq_axis``, x is this device's contiguous token chunk and attention
    rings over the sequence axis (cos/sin already offset by the caller)."""
    B, T, D = x.shape
    tp = 1 if tp_axis is None else jax.lax.psum(1, tp_axis)
    if tp_axis is not None:
        # Megatron f: identity fwd, psum bwd (see parallel.tensor_parallel)
        x = copy_to_tp_region(x, tp_axis)
    H, KV, hd = cfg.n_head // tp, cfg.n_kv_head // tp, cfg.head_dim
    q = _matmul(x, p["wq"]).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = _matmul(x, p["wk"]).reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
    v = _matmul(x, p["wv"]).reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if KV != H:  # GQA: repeat kv heads
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if seq_axis is not None:
        from distributed_lion_tpu.parallel.ring_attention import (
            ring_attention,
            ulysses_attention,
        )

        seq_attn = (ulysses_attention if cfg.seq_impl == "ulysses"
                    else ring_attention)
        out = seq_attn(q, k, v, axis_name=seq_axis)
    else:
        out = shared_attention(q, k, v, causal=True, impl=cfg.attn_impl)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    out = _matmul(out, p["wo"])
    if tp_axis is not None:
        out = reduce_from_tp_region(out, tp_axis)
    return out


@jax.named_scope("mlp")
def _mlp(x, p, tp_axis=None):
    if tp_axis is not None:
        x = copy_to_tp_region(x, tp_axis)
    gate = jax.nn.silu(_matmul(x, p["w_gate"]))
    out = _matmul(gate * _matmul(x, p["w_up"]), p["w_down"])
    if tp_axis is not None:
        out = reduce_from_tp_region(out, tp_axis)
    return out


def _block(x, p, cfg: LlamaConfig, cos, sin, tp_axis=None, seq_axis=None):
    x = x + _attention(_rms_norm(x, p["ln_attn"], cfg.rms_eps), p["attn"], cfg,
                       cos, sin, tp_axis, seq_axis)
    x = x + _mlp(_rms_norm(x, p["ln_mlp"], cfg.rms_eps), p["mlp"], tp_axis)
    return x


def _block_remat_for(cfg):
    from distributed_lion_tpu.models.gpt2 import _remat_policy

    return partial(jax.checkpoint, static_argnums=(2, 5, 6),
                   policy=_remat_policy(cfg.remat_policy))(_block)


def llama_init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> list:
    """Per-layer KV cache [B, n_kv_head, max_len, hd] — stored UN-repeated
    (GQA): repeat-to-query-heads happens at attend time, so cache memory
    scales with kv heads, the GQA payoff."""
    shape = (batch, cfg.n_kv_head, max_len, cfg.head_dim)
    return [
        {"k": jnp.zeros(shape, cfg.compute_dtype), "v": jnp.zeros(shape, cfg.compute_dtype)}
        for _ in range(cfg.n_layer)
    ]


@jax.named_scope("attn")
def _decode_attention(x, p, cfg: LlamaConfig, c, pos, cos, sin, offset=None):
    """``offset`` (optional [B] int32): per-row left-pad width in a
    batched variable-length prompt — cache slots below it are masked out
    of that row's attention (cli/run_generate multi-prompt mode; the rope
    angles are already per-row-shifted by the caller)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = _matmul(x, p["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = _matmul(x, p["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    v = _matmul(x, p["wv"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k_cache = jax.lax.dynamic_update_slice_in_dim(c["k"], k.astype(c["k"].dtype), pos, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(c["v"], v.astype(c["v"].dtype), pos, axis=2)
    rep = H // KV
    k_full = jnp.repeat(k_cache, rep, axis=1) if rep > 1 else k_cache
    v_full = jnp.repeat(v_cache, rep, axis=1) if rep > 1 else v_cache
    T = k_cache.shape[2]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k_full,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = jnp.arange(T)[None, :] <= (pos + jnp.arange(S))[:, None]
    if offset is None:
        scores = jnp.where(valid[None, None], scores, -1e30)
    else:
        row_valid = valid[None] & (jnp.arange(T)[None, None, :]
                                   >= offset[:, None, None])
        scores = jnp.where(row_valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v_full,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return _matmul(out, p["wo"]), {"k": k_cache, "v": v_cache}


@jax.named_scope("head")
def _head_logits(x, params):
    return jnp.einsum("btd,dv->btv", x,
                      maybe_dequant(params["lm_head"], x.dtype).astype(x.dtype),
                      preferred_element_type=jnp.float32)


def llama_decode(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig, cache: list,
                 pos, offset=None):
    """Incremental forward with rotary offset: prefill with the prompt at
    pos=0, then one token at a time. Matches ``llama_apply`` logits
    position-for-position (tests/test_generate.py). ``offset`` [B]: per-row
    left-pad width for batched variable-length prompts — row b's tokens at
    cache slot t get rotary position ``t - offset[b]`` and never attend
    below slot ``offset[b]`` (solo semantics, shifted into the batch)."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    # rope tables at the absolute positions of these S tokens: build a
    # max-length table once and slice at pos (pos is traced under jit)
    cos_all, sin_all = rope_angles(cache[0]["k"].shape[2], cfg.head_dim, cfg.rope_theta)
    if offset is None:
        cos = jax.lax.dynamic_slice_in_dim(cos_all, pos, S, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin_all, pos, S, axis=0)
    else:
        pos_ids = jnp.clip(pos + jnp.arange(S)[None, :] - offset[:, None],
                           0, cos_all.shape[0] - 1)
        cos, sin = cos_all[pos_ids], sin_all[pos_ids]  # [B, S, hd/2]
    new_cache = []
    for p, c in zip(params["blocks"], cache):
        a, c = _decode_attention(_rms_norm(x, p["ln_attn"], cfg.rms_eps), p["attn"],
                                 cfg, c, pos, cos, sin, offset)
        x = x + a
        x = x + _mlp(_rms_norm(x, p["ln_mlp"], cfg.rms_eps), p["mlp"])
        new_cache.append(c)
    x = _rms_norm(x, params["ln_f"], cfg.rms_eps)
    return _head_logits(x, params), new_cache


@jax.named_scope("attn")
def _paged_attention_block(x, p, cfg: LlamaConfig, c, tables, pos, cos, sin,
                           valid, tp_axis=None, fresh=False):
    """The paged twin of :func:`_decode_attention` (serve/kv_cache layout):
    scatter the roped new k (and v) into block-table pages, attend over
    the gathered history via ops.attention.paged_decode_attention — the
    same masked-softmax chain, so greedy decode is bit-identical to the
    dense cache whenever the attended length matches. ``fresh`` (static):
    the window starts at position 0 and, where
    ``ops.attention.fresh_kernel_applies`` (heads of 128), attends over its
    own roped q, k and v through the tiled forward kernel; the pages are
    written and never gathered. With ``tp_axis``
    (the TP serving engine) wq/wk/wv are column-parallel — this rank holds
    n_head/tp query and n_kv_head/tp kv heads and the page pool's matching
    kv-head shard — the scatter/gather/attend chain is shard-local (GQA
    repeat preserved: H/tp over KV/tp), and wo is row-parallel with one
    psum over the tensor axis."""
    from distributed_lion_tpu.ops.attention import (
        fresh_causal_attention,
        fresh_kernel_applies,
        paged_decode_attention,
        paged_scatter_fresh,
        paged_scatter_kv,
    )

    B, S, _ = x.shape
    tp = 1 if tp_axis is None else jax.lax.psum(1, tp_axis)
    H, KV, hd = cfg.n_head // tp, cfg.n_kv_head // tp, cfg.head_dim
    q = _matmul(x, p["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = _matmul(x, p["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
    v = _matmul(x, p["wv"]).reshape(B, S, KV, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin).transpose(0, 2, 1, 3)  # back to [B, S, KV, hd]
    if fresh and fresh_kernel_applies(S, H, KV, hd, q.dtype):
        k_pages = paged_scatter_fresh(c["k"], tables, k.astype(c["k"].dtype),
                                      valid)
        v_pages = paged_scatter_fresh(c["v"], tables, v.astype(c["v"].dtype),
                                      valid)
        out = fresh_causal_attention(q.transpose(0, 2, 1, 3), k, v)
    else:
        k_pages = paged_scatter_kv(c["k"], tables, pos,
                                   k.astype(c["k"].dtype), valid)
        v_pages = paged_scatter_kv(c["v"], tables, pos,
                                   v.astype(c["v"].dtype), valid)
        out = paged_decode_attention(q, k_pages, v_pages, tables, pos,
                                     kv_heads=KV)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = _matmul(out, p["wo"])
    if tp_axis is not None:
        out = reduce_from_tp_region(out, tp_axis)
    return out, {"k": k_pages, "v": v_pages}


def llama_decode_paged(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
                       pages: list, tables: jnp.ndarray, pos: jnp.ndarray,
                       valid=None, tp_axis=None, fresh=False):
    """Block-table decode (the serving engine's model hook): row b's
    ``tokens`` [B, S] sit at positions ``pos[b] .. pos[b]+S-1`` of its own
    sequence (rotary angles gathered per row); ``pages`` is the per-layer
    {"k","v"} pool laid out by serve/kv_cache.init_pages
    ([num_blocks, block_size, n_kv_head, hd] reads too; GQA: pages store
    kv heads un-repeated, like the dense cache). Returns (logits
    [B, S, vocab] f32, updated pages). One jitted program serves both the
    bucketed prefill (S = padded prompt, ``valid`` masks the tail) and the
    rolling decode tick (S = 1, pos = per-slot lengths). ``fresh`` (static)
    says every row's ``pos`` is 0 (``models/gpt2.gpt2_decode_paged``). With
    ``tp_axis`` (inside shard_map — the TP serving engine, ISSUE 13)
    attention/MLP weights and the pool's kv-head axis are pre-sharded per
    ``parallel.tensor_parallel.llama_param_specs``; wte/lm_head stay
    replicated, so logits are identical on every tensor rank."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    max_pos = tables.shape[1] * pages[0]["k"].shape[1]
    cos_all, sin_all = rope_angles(max_pos, cfg.head_dim, cfg.rope_theta)
    pos_ids = jnp.clip(pos[:, None] + jnp.arange(S)[None, :], 0, max_pos - 1)
    cos, sin = cos_all[pos_ids], sin_all[pos_ids]  # [B, S, hd/2]
    new_pages = []
    for p, c in zip(params["blocks"], pages):
        a, c = _paged_attention_block(_rms_norm(x, p["ln_attn"], cfg.rms_eps),
                                      p["attn"], cfg, c, tables, pos, cos, sin,
                                      valid, tp_axis, fresh)
        x = x + a
        x = x + _mlp(_rms_norm(x, p["ln_mlp"], cfg.rms_eps), p["mlp"], tp_axis)
        new_pages.append(c)
    x = _rms_norm(x, params["ln_f"], cfg.rms_eps)
    return _head_logits(x, params), new_pages


def llama_hidden(
    params: dict,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    *,
    tp_axis: Optional[str] = None,
    seq_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Backbone forward: tokens [B, T] → final hidden [B, T, d] after the
    last RMSNorm. The lm_head is applied by :func:`llama_apply`, or streamed
    chunk-wise by ops/xent (vocab 32k/128k logits never materialized)."""
    B, T = tokens.shape
    if seq_axis is None:
        if T > cfg.n_ctx:
            raise ValueError(f"sequence length {T} exceeds n_ctx {cfg.n_ctx}")
        offset = 0
    else:
        offset = jax.lax.axis_index(seq_axis) * T
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    cos, sin = rope_angles(T, cfg.head_dim, cfg.rope_theta, offset=offset)
    block = _block_remat_for(cfg) if cfg.remat else _block
    for p in params["blocks"]:
        x = block(x, p, cfg, cos, sin, tp_axis, seq_axis)
    return _rms_norm(x, params["ln_f"], cfg.rms_eps)


def llama_apply(
    params: dict,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    *,
    dropout_key: Optional[jax.Array] = None,  # parity arg; Llama uses none
    tp_axis: Optional[str] = None,
    seq_axis: Optional[str] = None,
) -> jnp.ndarray:
    """int32 tokens [B, T] → f32 logits [B, T, vocab].

    With ``tp_axis`` (inside shard_map), weights are expected pre-sharded per
    ``parallel.tensor_parallel.llama_param_specs``. With ``seq_axis``,
    ``tokens`` is this device's contiguous chunk: rotary angles are offset by
    the shard index and attention rings over the axis.
    """
    x = llama_hidden(params, tokens, cfg, tp_axis=tp_axis, seq_axis=seq_axis)
    with jax.named_scope("head"):
        return jnp.einsum(
            "btd,dv->btv", x,
            maybe_dequant(params["lm_head"], x.dtype).astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
