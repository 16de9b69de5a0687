"""Mellum 2 (``model_type`` ``mellum``, JetBrains' Mellum2-12B-A2.5B) in pure
JAX: the TRAINING path.

A decoder of pre-norm residual blocks (published ``config.json``:
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct),
``h = x + Attn_l(RMSNorm(x))``, ``y = h + Experts_l(RMSNorm(h))``; no biases,
an untied head. RMSNorm is ``models/llama``'s, the rotary embedding
``models/laguna``'s (:class:`Rope`, ``apply_rope_half``: ``rotate_half``
pairs over the whole head), the expert layer
``parallel/expert.moe_dropless_ffn``; none is written again here.

- **Attention, by the layer's kind** (``layer_types``: three
  ``sliding_attention`` layers to one ``full_attention`` layer). Grouped
  queries, 32 heads over 4 kv heads of 128; q and k are RMS-normed over a
  head's 128 lanes with a learned weight before they rotate. A window layer
  sees the last ``window`` positions (the query's own counted) under plain
  RoPE; a full layer sees every earlier position under YaRN, cos and sin
  scaled by the attention factor. On a TPU both go through the kernel pair
  ``ops/pallas_flash_attn.flash_gqa`` (token-major, kv heads never repeated,
  tiles outside the band never visited); everywhere else, and for shapes the
  kernels do not take, through ``ops/attention.banded_causal_attention``.
  No flag chooses.
- **Experts in every layer**: a float32 softmax router over all
  ``n_experts`` outputs, the ``top_k`` largest renormalised
  (``norm_topk_prob``), dropless, no shared expert; ``held = (first,
  count)`` says which experts' banks this chip has (one chip's share of a
  layer spread over several: a pick held elsewhere adds nothing here and
  costs no product, forward or backward). The gradient of the grouped
  products runs in the ``moe_gmm`` kernels (``parallel/expert
  .grouped_matmul``'s ``custom_vjp``).

:func:`mellum_hidden` returns the final hidden states and the expert
layers' counters (``MELLUM_COUNTERS``, int32 scalars summed over layers);
the loss head is ``ops/xent.clm_head_loss`` over ``params["lm_head"]``
``[vocab, d]`` (``train/loop.Trainer.for_mellum``). Serving this family
(pages, rings, a decode tick) is not here.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.models.laguna import Rope, apply_rope_half
from distributed_lion_tpu.models.llama import _matmul, _normal, _rms_norm
from distributed_lion_tpu.models.lora import lora_embed
from distributed_lion_tpu.parallel.expert import (
    MOE_COUNTERS,
    moe_dropless_ffn,
)

MELLUM_COUNTERS = MOE_COUNTERS + ("moe_routed", "moe_rows_moved")


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    n_layer: int = 28
    d_model: int = 2304
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    windowed: tuple = (True, True, True, False) * 7   # the layer's kind
    window: int = 1024
    rope_full: Rope = Rope(5e5, 128, 16.0, 8192, 32.0, 1.0,
                           1.2772588722239782)
    rope_window: Rope = Rope(5e5, 128)
    n_experts: int = 64              # the router's outputs
    top_k: int = 8
    moe_d_ff: int = 896
    held: Optional[tuple] = None     # (first, count): the experts whose
    #                                  banks are here; None = all of them
    rms_eps: float = 1e-6
    n_ctx: int = 131072
    remat: bool = True
    remat_policy: str = "full"       # as models/llama.LlamaConfig's
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def banks(self) -> int:
        """Experts whose weights a layer holds."""
        return self.held[1] if self.held else self.n_experts

    @staticmethod
    def from_hf(hf: dict, **kw) -> "MellumConfig":
        """From the published ``config.json`` keys (a benchmark
        configuration file carries them under the same names).
        ``layer_types`` may be longer than ``num_hidden_layers``: a cut in
        depth reads its head. Where the file says it was ``reduced`` in
        ``num_experts``, that number is the experts HELD (from 0) and the
        router keeps the ``published`` number of outputs."""
        only = {"attention_bias": False, "tie_word_embeddings": False,
                "norm_topk_prob": True, "hidden_act": "silu",
                "use_sliding_window": True, "max_window_layers": 0}
        for key, want in only.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"mellum: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
        L, hd = hf["num_hidden_layers"], hf["head_dim"]
        kinds = hf["layer_types"][:L]
        if set(kinds) - {"full_attention", "sliding_attention"}:
            raise ValueError(f"mellum: layer_types {sorted(set(kinds))}")
        if set(hf.get("mlp_layer_types", ["sparse"])[:L]) != {"sparse"}:
            raise ValueError("mellum: only sparse layers are implemented")
        held_n = hf["num_experts"]
        routed = hf.get("published", {}).get("num_experts", held_n) \
            if "num_experts" in hf.get("reduced", ()) else held_n
        rope = hf["rope_parameters"]
        return MellumConfig(
            vocab_size=hf["vocab_size"], n_layer=L, d_model=hf["hidden_size"],
            n_head=hf["num_attention_heads"],
            n_kv_head=hf["num_key_value_heads"], head_dim=hd,
            windowed=tuple(k == "sliding_attention" for k in kinds),
            window=hf["sliding_window"],
            rope_full=Rope.from_hf(rope["full_attention"], hd),
            rope_window=Rope.from_hf(rope["sliding_attention"], hd),
            n_experts=routed, top_k=hf["num_experts_per_tok"],
            moe_d_ff=hf["moe_intermediate_size"],
            held=(0, held_n) if held_n < routed else None,
            rms_eps=hf["rms_norm_eps"], n_ctx=hf["max_position_embeddings"],
            **kw)

    @staticmethod
    def from_file(path: str, **kw) -> "MellumConfig":
        with open(path) as f:
            return MellumConfig.from_hf(json.load(f), **kw)

    @staticmethod
    def tiny(**kw) -> "MellumConfig":
        """Two periods' worth of kinds in four layers at head_dim 16; half
        the router's experts held."""
        base = dict(vocab_size=256, n_layer=4, d_model=64, n_head=4,
                    n_kv_head=2, head_dim=16,
                    windowed=(True, True, True, False), window=8,
                    rope_full=Rope(5e5, 16, 16.0, 64, 32.0, 1.0,
                                   1.2772588722239782),
                    rope_window=Rope(5e5, 16), n_experts=8, top_k=2,
                    moe_d_ff=32, held=(0, 4), n_ctx=4096)
        base.update(kw)
        return MellumConfig(**base)


def mellum_init(key: jax.Array, cfg: MellumConfig) -> dict:
    d, hd, dt = cfg.d_model, cfg.head_dim, cfg.param_dtype
    E, f, std = cfg.banks, cfg.moe_d_ff, 0.02
    resid = std / math.sqrt(2 * cfg.n_layer)     # as models/llama.llama_init
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layer))
    ones = lambda n: {"scale": jnp.ones((n,), dt)}  # noqa: E731
    params: dict = {
        "wte": _normal(next(keys), (cfg.vocab_size, d), std, dt),
        "lm_head": _normal(next(keys), (cfg.vocab_size, d), std, dt),
        "ln_f": ones(d), "blocks": []}
    for _ in range(cfg.n_layer):
        params["blocks"].append({
            "ln_attn": ones(d),
            "attn": {
                "wq": _normal(next(keys), (d, cfg.n_head * hd), std, dt),
                "wk": _normal(next(keys), (d, cfg.n_kv_head * hd), std, dt),
                "wv": _normal(next(keys), (d, cfg.n_kv_head * hd), std, dt),
                "wo": _normal(next(keys), (cfg.n_head * hd, d), resid, dt),
                "q_norm": ones(hd), "k_norm": ones(hd)},
            "ln_mlp": ones(d),
            "moe": {
                "router": _normal(next(keys), (cfg.n_experts, d), std, dt),
                "w_gate": _normal(next(keys), (E, d, f), std, dt),
                "w_up": _normal(next(keys), (E, d, f), std, dt),
                "w_down": _normal(next(keys), (E, f, d), resid, dt)},
        })
    return params


def mellum_param_specs(cfg: MellumConfig) -> dict:
    """Every leaf whole on every device of the data axis: the one layout
    the trainer steps today (the exchange between the chips that share a
    layer's experts is not run; ROADMAP.md)."""
    shapes = jax.eval_shape(lambda: mellum_init(jax.random.key(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def head_norm_rope(y, scale, cos, sin, eps):
    """Each head of ``y [B, T, n * hd]`` RMS-normed over its ``hd`` lanes
    with the weight ``scale [hd]`` and rotated by ``cos``, ``sin`` ``[T, rot /
    2]``: the plain expression, which ``ops/pallas_qk_rope.qk_norm_rope``
    stands for on a TPU (same arguments) and is held to."""
    B, T, width = y.shape
    hd = scale.shape[0]
    c, s = (jnp.broadcast_to(t, (B,) + t.shape).reshape(B * T, 1, -1)
            for t in (cos, sin))
    y = _rms_norm(y.reshape(B * T, width // hd, 1, hd), {"scale": scale}, eps)
    return apply_rope_half(y, c, s).reshape(B, T, width)


def _attention(x, p, cfg: MellumConfig, windowed: bool):
    """x [B, T, d] -> [B, T, d]; token-major throughout on the kernels'
    path (no head-major copy of q, k, v or the output, and q and k normed
    and rotated where the projection wrote them)."""
    from distributed_lion_tpu.ops import pallas_flash_attn as flash
    from distributed_lion_tpu.ops import pallas_qk_rope
    from distributed_lion_tpu.ops.attention import banded_causal_attention

    B, T, _ = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    rope = cfg.rope_window if windowed else cfg.rope_full
    window = cfg.window if windowed and cfg.window < T else 0
    cos, sin = rope.angles(jnp.arange(T))
    on_tpu = jax.default_backend() == "tpu"
    fused = on_tpu and pallas_qk_rope.qk_rope_takes(
        T, hd, rope.rotary_dim, x.dtype)
    norm_rope = pallas_qk_rope.qk_norm_rope if fused else head_norm_rope

    def heads(w, norm):
        """Project, RMS-norm each head's lanes, rotate: [B, T, n * hd]."""
        return norm_rope(_matmul(x, w), norm["scale"], cos, sin, cfg.rms_eps)

    q = heads(p["wq"], p["q_norm"])
    k = heads(p["wk"], p["k_norm"])
    v = _matmul(x, p["wv"])
    if on_tpu and flash.gqa_train_kernel_takes(T, hd, q.dtype):
        out = flash.flash_gqa(q, k, v, H, window)
    else:
        q, k, v = (t.reshape(B, T, -1, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        out = banded_causal_attention(q, k, v, window=window or None)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    return _matmul(out, p["wo"])


def _block(x, p, cfg: MellumConfig, windowed: bool):
    """One layer: ``(x, counters)``."""
    B, T, d = x.shape
    with jax.named_scope("attn/window" if windowed else "attn/full"):
        x = x + _attention(_rms_norm(x, p["ln_attn"], cfg.rms_eps),
                           p["attn"], cfg, windowed)
    with jax.named_scope("moe"):
        moe = {"router": p["moe"]["router"],
               **{n: p["moe"][n].astype(x.dtype)
                  for n in ("w_gate", "w_up", "w_down")}}
        u = _rms_norm(x, p["ln_mlp"], cfg.rms_eps).reshape(B * T, d)
        y, counters = moe_dropless_ffn(moe, u, top_k=cfg.top_k, scale=1.0,
                                       held=cfg.held, return_counters=True)
    return x + y.reshape(B, T, d), counters


def _block_for(cfg: MellumConfig):
    if not cfg.remat:
        return _block
    from distributed_lion_tpu.models.gpt2 import _remat_policy

    return jax.checkpoint(_block, static_argnums=(2, 3),
                          policy=_remat_policy(cfg.remat_policy))


def mellum_hidden(params: dict, tokens: jnp.ndarray, cfg: MellumConfig):
    """tokens [B, T] -> (final hidden [B, T, d] after the last RMSNorm,
    counters: ``MELLUM_COUNTERS`` as int32 scalars summed over layers)."""
    if tokens.shape[1] > cfg.n_ctx:
        raise ValueError(
            f"sequence length {tokens.shape[1]} exceeds n_ctx {cfg.n_ctx}")
    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    block = _block_for(cfg)
    total = {name: jnp.int32(0) for name in MELLUM_COUNTERS}
    for p, windowed in zip(params["blocks"], cfg.windowed):
        x, counters = block(x, p, cfg, windowed)
        total = {name: total[name] + counters[name] for name in total}
    return _rms_norm(x, params["ln_f"], cfg.rms_eps), total


def mellum_apply(params: dict, tokens: jnp.ndarray, cfg: MellumConfig):
    """int32 tokens [B, T] -> float32 logits [B, T, vocab] (the tests'
    entry; the trainer's loss never holds them: ``ops/xent``)."""
    x, _ = mellum_hidden(params, tokens, cfg)
    with jax.named_scope("head"):
        return jnp.einsum("btd,vd->btv", x,
                          params["lm_head"].astype(x.dtype),
                          preferred_element_type=jnp.float32)
