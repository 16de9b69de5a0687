"""Xing4.0 (``model_type`` ``xing4_0``, XingChen-AGI's Xing4.0-29B-A4B) in
pure JAX: the serving path.

A DeepSeek-V3-line decoder (published ``config.json``:
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B) whose residual path is
**manifold-constrained hyper-connections** (``ops/mhc``'s note has the
equations): the residual is ``n = hc_mult`` streams a token from the
embedding to the head, and every sublayer reads its input as a learned
per-token mix of the streams (``mhc_pre``) and writes its output back
through a second one while the streams are re-mixed by a per-token ``n x n``
matrix that 20 Sinkhorn steps bring near doubly stochastic (``mhc_post``).
Where every other family of this repo writes ``x = x + F(norm(x))``, a block
here is

    u, H = mhc_pre(X);  y = F(RMSNorm_g(u));  X = mhc_post(X, y, H)

twice: ``F`` = latent attention (``models/joyai._mla_block``: the same cache
row, the same two paths), then a SwiGLU of ``d_ff`` (the ``first_dense``
leading layers) or sigmoid top-k dropless experts with a shared one
(``parallel/expert.moe_dropless_ffn``). ``X_0`` is the embedding repeated
``n`` times; the head reads ``RMSNorm(sum_i X_L[i])``.

- **The stream is ``[B S, n d]``**, flat and two-dimensional, in the compute
  dtype, from the embedding to the read-out: stream ``i`` is the lanes ``i d
  .. (i + 1) d`` (``ops/mhc``'s note says why not ``[B, S, n, d]``). It never
  reaches the cache: only ``[c_kv | k_rope]`` rows do, so the page leaves,
  the block tables and the prefix cache are JoyAI's. The mix's parameters
  (``phi`` packed, ``a``, ``b``), its coefficients and every sum over
  streams are float32.
- **YaRN** on the rope half of a head (``rope_scaling``: factor 64 over
  4,096 positions): the angles are ``models/laguna.Rope``'s blended
  frequencies, cos and sin scaled by ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)`` (1 as published), pairs ``(2i, 2i+1)`` as
  the DeepSeek-V3 line stores them; the softmax scale is ``mscale(factor,
  mscale_all_dim)^2 / sqrt(nope + rope)`` (0.14468 as published), handed to
  both of ``_mla_block``'s paths.

Counters under ``return_moe_stats``: the expert layers' (``MOE_COUNTERS``),
``mhc_rows`` (rows with a token x sublayers through the mix) and
``mhc_res_defect_max`` (the largest ``|rowsum - 1|`` or ``|colsum - 1|`` of
a mixing matrix over the dispatch, x 1e6).

Multi-token prediction (``num_nextn_predict_layers``) is not instantiated,
for ``models/joyai``'s reason; a next-token block over the four streams is
ROADMAP Reach, M6. Training this family is not here either.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any

import jax
import jax.numpy as jnp

from distributed_lion_tpu.models.joyai import _mla_block, joyai_init
from distributed_lion_tpu.models.laguna import Rope
from distributed_lion_tpu.models.llama import (
    _head_logits,
    _mlp,
    _normal,
    _rms_norm,
)
from distributed_lion_tpu.ops.mhc import (
    MixConfig,
    expand,
    mhc_defect,
    mhc_post,
    mhc_pre,
    pack_phi,
    read_out,
)
from distributed_lion_tpu.parallel.expert import (
    MOE_COUNTERS,
    moe_dropless_ffn,
)

XING_COUNTERS = MOE_COUNTERS + ("mhc_rows", "mhc_res_defect_max")


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class XingConfig:
    vocab_size: int = 131072
    n_layer: int = 40
    d_model: int = 3584
    n_head: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 9216                 # the leading dense layers' SwiGLU
    first_dense: int = 2             # first_k_dense_replace
    n_experts: int = 64
    top_k: int = 4
    moe_d_ff: int = 1024
    n_shared: int = 1                # shared experts, as one of n x moe_d_ff
    routed_scale: float = 2.0
    rope_theta: float = 10000.0
    rope_factor: float = 64.0        # YaRN; 1 = plain RoPE
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    hc_mult: int = 4                 # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)  # mhc_h_res_clamp_min / _max
    rms_eps: float = 1e-6
    n_ctx: int = 262144
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        """Values of one cached row: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mix(self) -> MixConfig:
        return MixConfig(self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps,
                         tuple(float(c) for c in self.hc_clamp), self.rms_eps)

    @property
    def rope(self) -> Rope:
        return Rope(self.rope_theta, self.qk_rope_head_dim, self.rope_factor,
                    self.rope_original_max, self.rope_beta_fast,
                    self.rope_beta_slow,
                    yarn_mscale(self.rope_factor, self.rope_mscale)
                    / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        return yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2 \
            / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    @staticmethod
    def from_hf(hf: dict, **kw) -> "XingConfig":
        """From the published ``config.json`` keys (a benchmark
        configuration file carries them under the same names)."""
        unsupported = {
            "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
            "norm_topk_prob": True, "attention_bias": False,
            "moe_layer_freq": 1, "tie_word_embeddings": False}
        for key, want in unsupported.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"xing: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
        if hf["hc_mult"] < 1:
            raise ValueError(
                f"xing: hc_mult={hf['hc_mult']!r} is not implemented (one "
                "residual stream or more)")
        rs = hf.get("rope_scaling") or {"type": "yarn", "factor": 1.0}
        kind = rs.get("type", rs.get("rope_type"))
        if kind != "yarn":
            raise ValueError(
                f"xing: rope_scaling.type={kind!r} is not implemented (only "
                "'yarn')")
        base = dict(
            vocab_size=hf["vocab_size"], n_layer=hf["num_hidden_layers"],
            d_model=hf["hidden_size"], n_head=hf["num_attention_heads"],
            q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"], d_ff=hf["intermediate_size"],
            first_dense=hf["first_k_dense_replace"],
            n_experts=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
            moe_d_ff=hf["moe_intermediate_size"],
            n_shared=hf["n_shared_experts"],
            routed_scale=hf["routed_scaling_factor"],
            rope_theta=float(hf["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_original_max=int(
                rs.get("original_max_position_embeddings", 0)),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            hc_mult=hf["hc_mult"],
            hc_sinkhorn_iters=hf["hc_sinkhorn_iters"], hc_eps=hf["hc_eps"],
            hc_clamp=(hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"]),
            rms_eps=hf["rms_norm_eps"], n_ctx=hf["max_position_embeddings"])
        base.update(kw)
        return XingConfig(**base)

    @staticmethod
    def tiny(**kw) -> "XingConfig":
        base = dict(vocab_size=256, n_layer=2, d_model=64, n_head=4,
                    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, d_ff=128,
                    first_dense=1, n_experts=8, top_k=2, moe_d_ff=32,
                    rope_factor=8.0, rope_original_max=16, n_ctx=4096)
        base.update(kw)
        return XingConfig(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "XingConfig":
        """A CLI model name: ``tiny``, or the path of a JSON file holding
        the published ``config.json`` keys (further keys, as a benchmark
        configuration file has, are ignored)."""
        if name == "tiny":
            return cls.tiny(**kw)
        if name.endswith(".json"):
            with open(name) as f:
                return cls.from_hf(json.load(f), **kw)
        raise ValueError(
            f"unknown xing model_name {name!r}: 'tiny' or the path of a "
            "config.json")


def xing_init(key: jax.Array, cfg: XingConfig) -> dict:
    """Seeded weights in the program's tree: ``models/joyai.joyai_init``'s
    (the same attention, SwiGLU, experts, norms and head under the same
    names: matrices N(0, 0.02), gains 1, the router's correction bias N(0,
    0.01) float32) with a mix a sublayer beside them, ``hc_attn`` and
    ``hc_mlp``: ``phi`` N(0, 0.02) packed (``ops/mhc.pack_phi``), ``a`` = 1,
    ``b`` N(0, 0.5) with 4 added on the mixing matrix's diagonal."""
    mix, n = cfg.mix, cfg.hc_mult
    k_model, k_mix = jax.random.split(key)
    params = joyai_init(k_model, cfg)
    keys = iter(jax.random.split(k_mix, 4 * cfg.n_layer))

    def hyper():
        phi = _normal(next(keys), (n * cfg.d_model, mix.width), 0.02,
                      jnp.float32)
        b = _normal(next(keys), (mix.width,), 0.5, jnp.float32)
        return {"phi": pack_phi(phi, mix), "a": jnp.ones((3,), jnp.float32),
                "b": b.at[2 * n:].add(4.0 * jnp.eye(n).reshape(-1))}

    for block in params["blocks"]:
        block.update(hc_attn=hyper(), hc_mlp=hyper())
    return params


def xing_decode_paged(params: dict, tokens: jnp.ndarray, cfg: XingConfig,
                      pages: list, tables: jnp.ndarray, pos: jnp.ndarray,
                      valid=None, return_moe_stats: bool = False,
                      logit_index=None, fresh: bool = False):
    """Block-table decode (the serving engine's model hook, as
    ``joyai_decode_paged``, ``fresh`` included): row b's ``tokens`` [B, S] sit at positions
    ``pos[b] .. pos[b]+S-1``; ``pages`` is the per-layer ``{"kv"}`` latent
    pool. Returns (logits float32, updated pages[, counters]): logits ``[B,
    S, vocab]``, or ``[B, 1, vocab]`` of position ``logit_index`` when given.
    ``return_moe_stats``: int32 counters over the ``valid`` lanes
    (``XING_COUNTERS``), sums over the layers but the two maxima."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    mix, d = cfg.mix, cfg.d_model
    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
        X = expand(x.reshape(B * S, d), mix)                  # [B S, n d]
    max_pos = tables.shape[1] * pages[0]["kv"].shape[1]
    pos_ids = jnp.clip(pos[:, None] + jnp.arange(S)[None, :], 0, max_pos - 1)
    cos, sin = cfg.rope.angles(pos_ids)                       # [B, S, dr/2]
    lanes = jnp.ones((B * S,), bool) if valid is None else \
        jnp.broadcast_to(valid, (B, S)).reshape(-1)
    counters = dict.fromkeys(XING_COUNTERS, jnp.int32(0))
    defects = []
    new_pages = []

    def read(X, hc, gain):
        """A sublayer's input ``[B, S, d]`` (pre-mix, then its own gained
        norm) and the coefficient rows its write-back takes."""
        u, coef = mhc_pre(X, hc["phi"], hc["a"], hc["b"], mix)
        if return_moe_stats:
            defects.append(mhc_defect(coef, lanes, mix))
        return _rms_norm(u, gain, cfg.rms_eps).reshape(B, S, d), coef

    for p, c in zip(params["blocks"], pages):
        h, coef = read(X, p["hc_attn"], p["ln_attn"])
        a, c = _mla_block(h, p["attn"], cfg, c, tables, pos, cos, sin, valid,
                          scale=cfg.softmax_scale, fresh=fresh)
        new_pages.append(c)
        X = mhc_post(X, a.reshape(B * S, d), coef, lanes, mix)
        h, coef = read(X, p["hc_mlp"], p["ln_mlp"])
        if "moe" not in p:
            y = _mlp(h, p["mlp"])
        else:
            y = moe_dropless_ffn(
                p["moe"], h.reshape(B * S, d), top_k=cfg.top_k,
                scale=cfg.routed_scale,
                valid=None if valid is None else lanes,
                return_counters=return_moe_stats)
            if return_moe_stats:
                y, st = y
                for name in MOE_COUNTERS:
                    join = jnp.maximum if name.endswith("_max") else jnp.add
                    counters[name] = join(counters[name],
                                          st[name].astype(jnp.int32))
        X = mhc_post(X, y.reshape(B * S, d), coef, lanes, mix)
    with jax.named_scope("mhc/read_out"):
        X = X.reshape(B, S, -1)
        if logit_index is not None:
            X = jax.lax.dynamic_slice_in_dim(X, logit_index, 1, axis=1)
        x = read_out(X, mix)
    logits = _head_logits(_rms_norm(x, params["ln_f"], cfg.rms_eps), params)
    if not return_moe_stats:
        return logits, new_pages
    counters["mhc_rows"] = lanes.sum().astype(jnp.int32) \
        * (2 * len(params["blocks"]))
    counters["mhc_res_defect_max"] = jnp.ceil(
        jnp.max(jnp.stack(defects)) * 1e6).astype(jnp.int32)
    return logits, new_pages, counters
