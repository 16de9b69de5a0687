"""Laguna (``model_type`` ``laguna``, poolside's Laguna-S-2.1 / XS.2) in
pure JAX: the serving path.

A decoder of pre-norm residual blocks (published ``config.json``:
https://huggingface.co/poolside/Laguna-S-2.1), ``h = x + Attn_l(RMSNorm(x))``,
``y = h + FFN_l(RMSNorm(h))``; RMSNorm, SwiGLU and the head are
``models/llama``'s, the expert layer ``parallel/expert.moe_dropless_ffn``.
No biases, untied head. What differs by layer:

- **Attention, by the layer's kind** (``layer_types``). Grouped queries
  over ``n_kv_head`` kv heads of ``head_dim``; the number of QUERY heads is
  the layer's own (``num_attention_heads_per_layer``: 48 on a full layer,
  72 on a window layer), so ``wq``, ``wg`` and ``wo`` differ in shape from
  layer to layer and ``wk`` / ``wv`` do not. A *full* layer sees every
  earlier position; a *window* layer sees the last ``window`` (the query's
  own counted). RoPE is the kind's own too (:class:`Rope`): full layers
  rotate the first half of a head with YaRN frequencies and scale cos and
  sin by the attention factor, window layers rotate all of it plainly.
  Pairs are ``(i, i + rot/2)`` (``rotate_half``), the rotated dims first.
- **Per-head output gate**: ``g = sigmoid(u W_g)``, one scalar a head,
  multiplies the head's attention output before ``W_o``.
- **Two cache lifetimes.** A full layer's keys and values live in pages
  under the engine's block tables and grow with the sequence. A window
  layer's live in a bounded ring a slot (``ops/attention``'s ring note
  and ``ring_pages``): 33 pages of 16 for a window of 512, whatever the
  length, found from the row's slot id alone. The decode tick (S = 1)
  runs the ``paged_attn`` kernel over both on a TPU; a prefill (S > 1)
  attends over its own fresh keys (``ops/attention
  .banded_causal_attention``: a full layer through the tiled kernel
  ``flash_gqa_fwd`` on a TPU, a window layer banded, a chunk of queries
  at a time), and writes pages and ring behind it. **A call with S > 1 is a prefill from position
  0**: the engine refuses the prefix cache and speculation for this
  family, the two callers that would start elsewhere.
- **FFN**: the layers in ``dense_layers`` a SwiGLU of ``d_ff``; the others
  sigmoid top-k dropless experts with a shared expert, and, where the
  configuration says so, told which experts they hold (``held``: one chip's
  share of an expert-parallel pair; the router keeps all its outputs).

Training this family (loss, remat, specs) is not here.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_lion_tpu.models.llama import (
    _head_logits,
    _matmul,
    _mlp,
    _normal,
    _rms_norm,
)
from distributed_lion_tpu.parallel.expert import (
    MOE_COUNTERS,
    moe_dropless_ffn,
)

# what a dispatch counts under ``return_moe_stats``: the expert layers' rows
# computed here, experts hit, largest load and picks made, held or not; and
# the pages ONE window layer's decode walk was handed (0 from a prefill,
# which reads its own fresh keys)
LAGUNA_COUNTERS = MOE_COUNTERS + ("moe_routed", "kv_window_pages_read")


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotary embedding (``rope_parameters[kind]``)."""
    theta: float
    rotary_dim: int                  # leading dims of a head that rotate
    factor: float = 1.0              # YaRN: > 1 interpolates the slow dims
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0    # scales cos and sin

    @staticmethod
    def from_hf(spec: dict, head_dim: int) -> "Rope":
        kind = spec.get("rope_type", "default")
        if kind not in ("default", "yarn"):
            raise ValueError(f"laguna: rope_type {kind!r} is not implemented")
        rot = int(head_dim * spec.get("partial_rotary_factor", 1))
        if kind == "default":
            return Rope(float(spec["rope_theta"]), rot)
        factor = float(spec["factor"])
        return Rope(float(spec["rope_theta"]), rot, factor,
                    int(spec["original_max_position_embeddings"]),
                    float(spec.get("beta_fast", 32)),
                    float(spec.get("beta_slow", 1)),
                    float(spec.get("attention_factor")
                          or 0.1 * math.log(factor) + 1.0))

    def inv_freq(self) -> np.ndarray:
        """``[rotary_dim / 2]`` float32. YaRN (transformers'
        ``_compute_yarn_parameters``): dims that turn more than
        ``beta_fast`` times over the original context keep their frequency,
        dims that turn fewer than ``beta_slow`` times have it divided by
        ``factor``, a linear ramp between."""
        dim = self.rotary_dim
        freq = self.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        if self.factor == 1.0:
            return freq.astype(np.float32)

        def turns_dim(turns):
            return dim * math.log(self.original_max / (turns * 2 * math.pi)) \
                / (2 * math.log(self.theta))

        low = max(math.floor(turns_dim(self.beta_fast)), 0)
        high = min(math.ceil(turns_dim(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / ((high - low) or 0.001), 0, 1)
        return (freq / self.factor * ramp + freq * (1 - ramp)
                ).astype(np.float32)

    def angles(self, positions):
        """cos, sin ``[..., rotary_dim / 2]`` float32 of ``positions``."""
        ang = positions[..., None].astype(jnp.float32) * self.inv_freq()
        return (jnp.cos(ang) * self.attention_factor,
                jnp.sin(ang) * self.attention_factor)


def apply_rope_half(x, cos, sin):
    """x [B, H, S, hd]; cos, sin [B, S, rot / 2]: rotate the pairs
    ``(i, i + rot/2)`` of the leading ``rot`` dims, pass the rest."""
    half = cos.shape[-1]
    c, s = (t[:, None].astype(x.dtype) for t in (cos, sin))
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    n_layer: int = 48
    d_model: int = 3072
    n_kv_head: int = 8
    head_dim: int = 128
    heads: tuple = (48, 72, 72, 72) * 12      # query heads, a layer
    windowed: tuple = (False, True, True, True) * 12   # the layer's kind
    window: int = 512
    rope_full: Rope = Rope(5e5, 64, 128.0, 8192, 32.0, 1.0,
                           1.4852030263919618)
    rope_window: Rope = Rope(1e4, 128)
    d_ff: int = 12288                # the dense layers' SwiGLU
    dense_layers: tuple = (0,)       # mlp_only_layers
    n_experts: int = 256             # the router's outputs
    top_k: int = 10
    moe_d_ff: int = 1024
    shared_d_ff: int = 1024
    routed_scale: float = 2.5
    held: Optional[tuple] = None     # (first, count): the experts whose
    #                                  banks are here; None = all of them
    rms_eps: float = 1e-6
    n_ctx: int = 1048576
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def banks(self) -> int:
        """Experts whose weights a layer holds."""
        return self.held[1] if self.held else self.n_experts

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, w in enumerate(self.windowed) if w)

    @staticmethod
    def from_hf(hf: dict, **kw) -> "LagunaConfig":
        """From the published ``config.json`` keys (a benchmark
        configuration file carries them under the same names). The
        per-layer lists may be longer than ``num_hidden_layers``: a cut in
        depth reads their head. Where the file says it was ``reduced`` in
        ``num_experts``, that number is the experts HELD (from 0) and the
        router keeps the ``published`` number of outputs."""
        only = {"attention_bias": False, "tie_word_embeddings": False,
                "norm_topk_prob": True, "decoder_sparse_step": 1,
                "moe_apply_router_weight_on_input": False,
                "moe_router_logit_softcapping": 0, "gating": "per-head"}
        for key, want in only.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"laguna: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
        L, hd = hf["num_hidden_layers"], hf["head_dim"]
        kinds = hf["layer_types"][:L]
        if set(kinds) - {"full_attention", "sliding_attention"}:
            raise ValueError(f"laguna: layer_types {sorted(set(kinds))}")
        mlp = hf.get("mlp_layer_types", [])[:L]
        dense = tuple(hf.get("mlp_only_layers", ())) if not mlp else \
            tuple(i for i, t in enumerate(mlp) if t == "dense")
        held_n = hf["num_experts"]
        routed = hf.get("published", {}).get("num_experts", held_n) \
            if "num_experts" in hf.get("reduced", ()) else held_n
        rope = hf["rope_parameters"]
        base = dict(
            vocab_size=hf["vocab_size"], n_layer=L, d_model=hf["hidden_size"],
            n_kv_head=hf["num_key_value_heads"], head_dim=hd,
            heads=tuple(hf["num_attention_heads_per_layer"][:L]),
            windowed=tuple(k == "sliding_attention" for k in kinds),
            window=hf["sliding_window"],
            rope_full=Rope.from_hf(rope["full_attention"], hd),
            rope_window=Rope.from_hf(rope["sliding_attention"], hd),
            d_ff=hf["intermediate_size"],
            dense_layers=tuple(i for i in dense if i < L),
            n_experts=routed, top_k=hf["num_experts_per_tok"],
            moe_d_ff=hf["moe_intermediate_size"],
            shared_d_ff=hf["shared_expert_intermediate_size"],
            routed_scale=hf["moe_routed_scaling_factor"],
            held=None if held_n == routed else (0, held_n),
            rms_eps=hf["rms_norm_eps"], n_ctx=hf["max_position_embeddings"])
        base.update(kw)
        return LagunaConfig(**base)

    @staticmethod
    def tiny(**kw) -> "LagunaConfig":
        """Dense + window, window, window, full: one period behind the
        leading dense layer, as the benchmark's cut has it."""
        base = dict(vocab_size=256, n_layer=5, d_model=64, n_kv_head=2,
                    head_dim=16, heads=(4, 6, 6, 6, 4),
                    windowed=(False, True, True, True, False), window=8,
                    rope_full=Rope(5e5, 8, 128.0, 64, 32.0, 1.0,
                                   1.4852030263919618),
                    rope_window=Rope(1e4, 16), d_ff=128, dense_layers=(0,),
                    n_experts=8, top_k=2, moe_d_ff=32, shared_d_ff=32,
                    n_ctx=4096)
        base.update(kw)
        return LagunaConfig(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "LagunaConfig":
        """A CLI model name: ``tiny``, or the path of a JSON file holding
        the published ``config.json`` keys (further keys, as a benchmark
        configuration file has, are read as :meth:`from_hf` says)."""
        if name == "tiny":
            return cls.tiny(**kw)
        if name.endswith(".json"):
            with open(name) as f:
                return cls.from_hf(json.load(f), **kw)
        raise ValueError(
            f"unknown laguna model_name {name!r}: 'tiny' or the path of a "
            "config.json")


def laguna_init(key: jax.Array, cfg: LagunaConfig) -> dict:
    """Seeded N(0, 0.02) weights in the program's tree (norm gains 1; the
    router's correction bias N(0, 0.01), float32). An expert layer's banks
    are the ``cfg.banks`` experts held; its router has all its outputs."""
    d, dt, hd = cfg.d_model, cfg.param_dtype, cfg.head_dim
    kv, f, fs = cfg.n_kv_head * hd, cfg.moe_d_ff, cfg.shared_d_ff
    keys = iter(jax.random.split(key, 2 + 13 * cfg.n_layer))

    def w(*shape):
        return _normal(next(keys), shape, 0.02, dt)

    def gain(n):
        return {"scale": jnp.ones((n,), dt)}

    params: dict = {"wte": w(cfg.vocab_size, d),
                    "lm_head": w(d, cfg.vocab_size), "ln_f": gain(d),
                    "blocks": []}
    for layer, H in enumerate(cfg.heads):
        block = {"ln_attn": gain(d), "ln_mlp": gain(d),
                 "attn": {"wq": w(d, H * hd), "wk": w(d, kv), "wv": w(d, kv),
                          "wg": w(d, H), "wo": w(H * hd, d)}}
        if layer in cfg.dense_layers:
            block["mlp"] = {"w_gate": w(d, cfg.d_ff), "w_up": w(d, cfg.d_ff),
                            "w_down": w(cfg.d_ff, d)}
        else:
            block["moe"] = {
                "router": w(cfg.n_experts, d),
                "bias": _normal(next(keys), (cfg.n_experts,), 0.01,
                                jnp.float32),
                "w_gate": w(cfg.banks, d, f), "w_up": w(cfg.banks, d, f),
                "w_down": w(cfg.banks, f, d),
                "shared": {"w_gate": w(d, fs), "w_up": w(d, fs),
                           "w_down": w(fs, d)},
            }
        params["blocks"].append(block)
    return params


def head_gate(u, wg):
    """The per-head output gate's scalars, ``sigmoid(u W_g)`` ``[B, S, H]``
    float32, ``u`` the block's normed input."""
    with jax.named_scope("attn/gate"):
        return jax.nn.sigmoid(_matmul(u, wg).astype(jnp.float32))


def gate_heads(out, gate, dtype):
    """``out [B, S, H, hd]`` (a mixer's output a head, before ``W_o``) times
    its head's scalar, as ``[B, S, H * hd]`` of ``dtype``."""
    with jax.named_scope("attn/gate"):
        B, S, H, hd = out.shape
        return (out * gate[..., None]).astype(dtype).reshape(B, S, H * hd)


def _attention_block(u, p, cfg: LagunaConfig, layer: int, c, tables, slots,
                     pos, lengths, valid, cos, sin):
    """One layer's gated attention over its cache (the module note says
    which cache and which path). Returns (output ``[B, S, d]``, the layer's
    updated ``{"k", "v"}`` leaves, the pages a window layer's decode walk
    was handed ``[B]`` int32 or None)."""
    from distributed_lion_tpu.ops.attention import (
        banded_causal_attention,
        paged_decode_attention,
        paged_scatter_kv,
        ring_decode_attention,
        ring_scatter_kv,
    )

    B, S, _ = u.shape
    H, KV, hd = cfg.heads[layer], cfg.n_kv_head, cfg.head_dim
    windowed = cfg.windowed[layer]
    with jax.named_scope("attn/qkv"):
        q = _matmul(u, p["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = _matmul(u, p["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
        v = _matmul(u, p["wv"]).reshape(B, S, KV, hd)
    gate = head_gate(u, p["wg"])
    with jax.named_scope("attn/rope"):
        q, k = apply_rope_half(q, cos, sin), apply_rope_half(k, cos, sin)
    with jax.named_scope("window_attn" if windowed else "full_attn"):
        k_new = k.transpose(0, 2, 1, 3).astype(c["k"].dtype)
        v_new = v.astype(c["v"].dtype)
        walked = None
        if windowed:
            k_pages = ring_scatter_kv(c["k"], slots, pos, k_new, lengths,
                                      window=cfg.window)
            v_pages = ring_scatter_kv(c["v"], slots, pos, v_new, lengths,
                                      window=cfg.window)
        else:
            k_pages = paged_scatter_kv(c["k"], tables, pos, k_new, valid)
            v_pages = paged_scatter_kv(c["v"], tables, pos, v_new, valid)
        if S > 1:      # a prefill from position 0: its own fresh keys
            out = banded_causal_attention(
                q, k, v.transpose(0, 2, 1, 3),
                window=cfg.window if windowed else None)
        elif windowed:
            out, walked = ring_decode_attention(
                q, k_pages, v_pages, slots, pos, window=cfg.window,
                active=lengths > 0, kv_heads=KV)
        else:
            out = paged_decode_attention(q, k_pages, v_pages, tables, pos,
                                         kv_heads=KV)
    out = gate_heads(out.transpose(0, 2, 1, 3), gate, u.dtype)
    return _matmul(out, p["wo"]), {"k": k_pages, "v": v_pages}, walked


def laguna_decode_paged(params: dict, tokens: jnp.ndarray, cfg: LagunaConfig,
                        pages: list, tables: jnp.ndarray,
                        slots: jnp.ndarray, pos: jnp.ndarray, valid=None,
                        return_moe_stats: bool = False, logit_index=None):
    """Block-table decode (the serving engine's model hook, as
    ``llama_decode_paged``): row b's ``tokens`` [B, S] sit at positions
    ``pos[b] .. pos[b]+S-1``; ``pages`` is the per-layer ``{"k", "v"}``
    pool list, a full layer's leaves under ``tables`` [B, nb] and a window
    layer's in the ring of slot ``slots[b]`` (``ops/attention``'s ring
    note). S = 1 is the decode tick; S > 1 a prefill from
    position 0 (the module note). Returns (logits float32, updated
    pages[, counters]): logits ``[B, S, vocab]``, or ``[B, 1, vocab]`` of
    position ``logit_index`` when given. ``return_moe_stats``: the expert
    layers' int32 counters over the ``valid`` lanes, summed over the
    layers, the load as their maximum, and the pages the first window
    layer's decode walk was handed (``LAGUNA_COUNTERS``)."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    lanes = None if valid is None else jnp.broadcast_to(valid, (B, S))
    lengths = jnp.full((B,), S, jnp.int32) if lanes is None \
        else lanes.sum(1).astype(jnp.int32)
    pos_ids = pos[:, None] + jnp.arange(S)[None, :]
    angles = {True: cfg.rope_window.angles(pos_ids),
              False: cfg.rope_full.angles(pos_ids)}
    counters = dict.fromkeys(LAGUNA_COUNTERS, jnp.int32(0))
    new_pages = []
    for layer, (p, c) in enumerate(zip(params["blocks"], pages)):
        a, c, walked = _attention_block(
            _rms_norm(x, p["ln_attn"], cfg.rms_eps), p["attn"], cfg, layer,
            c, tables, slots, pos, lengths, valid,
            *angles[cfg.windowed[layer]])
        new_pages.append(c)
        if walked is not None and layer == cfg.window_layers[0]:
            counters["kv_window_pages_read"] = walked.sum()
        x = x + a
        h = _rms_norm(x, p["ln_mlp"], cfg.rms_eps)
        if "moe" not in p:
            x = x + _mlp(h, p["mlp"])
            continue
        y = moe_dropless_ffn(
            p["moe"], h.reshape(B * S, -1), top_k=cfg.top_k,
            scale=cfg.routed_scale,
            valid=None if lanes is None else lanes.reshape(-1),
            return_counters=return_moe_stats, held=cfg.held)
        if return_moe_stats:
            y, st = y
            for name in st:
                if name not in counters:     # one this family does not keep
                    continue
                join = jnp.maximum if name.endswith("_max") else jnp.add
                counters[name] = join(counters[name],
                                      st[name].astype(jnp.int32))
        x = x + y.reshape(B, S, -1)
    x = _rms_norm(x, params["ln_f"], cfg.rms_eps)
    if logit_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    logits = _head_logits(x, params)
    return (logits, new_pages, counters) if return_moe_stats \
        else (logits, new_pages)
