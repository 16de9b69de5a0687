"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``) in pure JAX: the
serving path.

A DeepSeek-V3-line decoder (published ``config.json``:
https://huggingface.co/jdopensource/JoyAI-LLM-Flash): pre-norm residual
blocks of latent attention (MLA) and, after ``first_k_dense_replace``
leading dense SwiGLU layers, sigmoid top-k dropless experts with a shared
expert (``parallel/expert.moe_dropless_ffn``); RMSNorm, interleaved RoPE and
SwiGLU are ``models/llama``'s. No biases, untied head.

Per layer, ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``:

- ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``; ``x W_kva = [c_kv | k_rope]``, ``c_kv`` normed,
  RoPE (pairs ``(2i, 2i+1)``) on ``q_rope`` a head and on the ONE ``k_rope``
  all heads share; ``c_kv W_kvb`` -> heads of ``[k_nope | v]``;
  ``softmax(q k^T / sqrt(nope + rope))``, causal.
- **The cache holds ``[c_kv (normed) | k_rope (roped)]``**: one row a token
  a layer (``serve/kv_cache.init_page_leaves``, leaf ``"kv"``), 17.8 times
  fewer bytes than 32 heads' keys and values.
- Two attention paths over that cache (``ops/attention``'s latent note).
  The decode tick on a TPU *absorbs* the up-projections:
  ``q_lat = q_nope W_kvb^K[h]``, ``score = q_lat . c_kv + q_rope . k_rope``,
  ``o = (sum p c_kv) W_kvb^V[h]``, the same mathematics with every head
  reading the same latent row, once (kernel ``mla_paged_attn``). Every
  other call (S > 1: prefill, verify, prefix hits; the CPU) gathers the
  rows, expands keys (width nope + rope) and values (width v) and attends
  a chunk of queries at a time. A prefill whose rows all start at 0 (no
  shared prefix) gathers only the pages its own tokens fill; told so at
  dispatch (the engine's static ``fresh``) and in a bucket of 2,048 tokens
  or more on a TPU, it gathers nothing and attends the rows it has just
  computed through the tiled kernel ``latent_prefill``
  (``ops/attention.latent_fresh_attention``).

``_mla_block`` also serves a family whose layers differ in geometry and in
WHERE THEIR KEYS COME FROM (``models/dots3``): it takes the layer kind's own
view of the configuration, a ``rescale`` of the two latents (1 here) and a
``keys`` function that writes the new rows where its cache keeps them and
attends its own key set: the last ``window`` positions out of a ring of
latent rows a slot, or the set a learned indexer kept, whose keys lie in a
page leaf of their own (``ik``) beside the latent rows. ``None``, every
family but that one, is the two paths above.

Multi-token prediction (``num_nextn_predict_layers``) is not instantiated:
the main model's logits do not depend on it, and the public inference code
drops it unless a speculative decoder asks (ROADMAP Reach, M6). Training
this family (loss, remat, specs) is not here either.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any

import jax
import jax.numpy as jnp

from distributed_lion_tpu.models.llama import (
    _head_logits,
    _matmul,
    _mlp,
    _normal,
    _rms_norm,
    apply_rope,
    rope_angles,
)
from distributed_lion_tpu.parallel.expert import (
    MOE_COUNTERS,
    moe_dropless_ffn,
)


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    n_layer: int = 40
    d_model: int = 2048
    n_head: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168                 # the leading dense layers' SwiGLU
    first_dense: int = 1             # first_k_dense_replace
    n_experts: int = 256
    top_k: int = 8
    moe_d_ff: int = 768
    n_shared: int = 1                # shared experts, as one of n x moe_d_ff
    routed_scale: float = 2.5
    rope_theta: float = 32e6
    rms_eps: float = 1e-6
    n_ctx: int = 131072
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        """Values of one cached row: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def from_hf(hf: dict, **kw) -> "JoyAIConfig":
        """From the published ``config.json`` keys (a benchmark
        configuration file carries them under the same names)."""
        unsupported = {
            "n_group": 1, "topk_group": 1, "rope_scaling": None,
            "scoring_func": "sigmoid", "norm_topk_prob": True,
            "rope_interleave": True, "attention_bias": False,
            "moe_layer_freq": 1, "tie_word_embeddings": False}
        for key, want in unsupported.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"joyai: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
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
            rope_theta=float(hf["rope_theta"]), rms_eps=hf["rms_norm_eps"],
            n_ctx=hf["max_position_embeddings"])
        base.update(kw)
        return JoyAIConfig(**base)

    @staticmethod
    def tiny(**kw) -> "JoyAIConfig":
        base = dict(vocab_size=256, n_layer=2, d_model=64, n_head=4,
                    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, d_ff=128,
                    n_experts=8, top_k=2, moe_d_ff=32, n_ctx=4096)
        base.update(kw)
        return JoyAIConfig(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "JoyAIConfig":
        """A CLI model name: ``tiny``, or the path of a JSON file holding
        the published ``config.json`` keys (further keys, as a benchmark
        configuration file has, are ignored)."""
        if name == "tiny":
            return cls.tiny(**kw)
        if name.endswith(".json"):
            with open(name) as f:
                return cls.from_hf(json.load(f), **kw)
        raise ValueError(
            f"unknown joyai model_name {name!r}: 'tiny' or the path of a "
            "config.json")


def joyai_init(key: jax.Array, cfg: JoyAIConfig) -> dict:
    """Seeded N(0, 0.02) weights in the program's tree (norm gains 1; the
    router's correction bias N(0, 0.01), float32)."""
    d, dt, H = cfg.d_model, cfg.param_dtype, cfg.n_head
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f, fs = cfg.moe_d_ff, cfg.n_shared * cfg.moe_d_ff
    std = 0.02
    keys = iter(jax.random.split(key, 2 + 13 * cfg.n_layer))

    def w(*shape):
        return _normal(next(keys), shape, std, dt)

    def gain(n):
        return {"scale": jnp.ones((n,), dt)}

    params: dict = {"wte": w(cfg.vocab_size, d), "lm_head": w(d, cfg.vocab_size),
                    "ln_f": gain(d), "blocks": []}
    for layer in range(cfg.n_layer):
        block = {
            "ln_attn": gain(d),
            "attn": {
                "wq_a": w(d, cfg.q_lora_rank), "q_norm": gain(cfg.q_lora_rank),
                "wq_b": w(cfg.q_lora_rank, H * qk),
                "wkv_a": w(d, cfg.latent_dim),
                "kv_norm": gain(cfg.kv_lora_rank),
                "wkv_b": w(cfg.kv_lora_rank,
                           H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "wo": w(H * cfg.v_head_dim, d),
            },
            "ln_mlp": gain(d),
        }
        if layer < cfg.first_dense:
            block["mlp"] = {"w_gate": w(d, cfg.d_ff), "w_up": w(d, cfg.d_ff),
                            "w_down": w(cfg.d_ff, d)}
        else:
            block["moe"] = {
                "router": w(cfg.n_experts, d),
                "bias": _normal(next(keys), (cfg.n_experts,), 0.01,
                                jnp.float32),
                "w_gate": w(cfg.n_experts, d, f),
                "w_up": w(cfg.n_experts, d, f),
                "w_down": w(cfg.n_experts, f, d),
                "shared": {"w_gate": w(d, fs), "w_up": w(d, fs),
                           "w_down": w(fs, d)},
            }
        params["blocks"].append(block)
    return params


def absorb_query(q_nope, q_rope, w_kvb, width: int):
    """One query a row in the latent row's own layout: ``[q_nope W_kvb^K[h]
    | q_rope | 0]`` ``[B, H, width]`` (q_nope, q_rope ``[B, H, 1, .]``;
    ``w_kvb [r, H, dn + dv]``), so that every head attends the cached row
    itself."""
    dn = q_nope.shape[-1]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0], w_kvb[..., :dn],
                       preferred_element_type=jnp.float32)
    q_abs = jnp.concatenate([q_lat.astype(q_nope.dtype), q_rope[:, :, 0]], -1)
    return jnp.pad(q_abs, ((0, 0), (0, 0), (0, width - q_abs.shape[-1])))


def expand_output(o_lat, w_kvb, dn: int):
    """The absorbed kernel's ``sum p * row`` ``[B, H, width]`` through the
    value up-projection: ``[B, H, dv]`` in its dtype."""
    out = jnp.einsum("bhr,rhv->bhv", o_lat[..., :w_kvb.shape[0]],
                     w_kvb[..., dn:], preferred_element_type=jnp.float32)
    return out.astype(o_lat.dtype)


def expand_rows(rows, w_kvb, dn: int):
    """Latent rows ``[B, T, r + dr]`` as every head's keys ``[B, H, T, dn +
    dr]`` (``[k_nope | the one k_rope]``) and values ``[B, H, T, dv]``."""
    r = w_kvb.shape[0]
    kvx = jnp.einsum("btr,rhm->bhtm", rows[..., :r], w_kvb,
                     preferred_element_type=jnp.float32
                     ).astype(rows.dtype)                     # [B, H, T, .]
    k_r = jnp.broadcast_to(rows[:, None, :, r:],
                           kvx.shape[:3] + (rows.shape[-1] - r,))
    return jnp.concatenate([kvx[..., :dn], k_r], -1), kvx[..., dn:]


def _mla_block(x, p, cfg: JoyAIConfig, c, tables, pos, cos, sin, valid,
               scale=None, rescale=(1.0, 1.0), keys=None, fresh=False):
    """Latent attention over the paged cache: scatter the new tokens'
    latent rows, then attend (the module note says which path). Returns
    (output ``[B, S, d]``, the layer's updated page leaf). ``cfg`` is read
    for its head count, widths and ``rms_eps`` alone, so another family's
    configuration with the same names serves (``models/ling``,
    ``models/xing``). ``scale``: the softmax scale, both paths' (default ``1 /
    sqrt(nope + rope)``; a YaRN family's carries its ``mscale``). Two leaves of
    ``p`` are optional: ``wq`` in place of ``wq_a`` / ``q_norm`` / ``wq_b``
    (a query with no low-rank step), and ``wg``, a per-head output gate
    before ``wo`` (``models/laguna.head_gate``).

    ``rescale = (s_q, s_kv)``: factors on the query latent and on the
    key-value latent after their norms (the cached row holds the scaled
    ``c_kv``); 1 for every family but ``models/dots3``.

    ``keys``: WHERE THE KEYS COME FROM. None: every position up to the
    query's, from the pages under ``tables`` (the two paths of the module
    note). Else a function ``keys(q_nope, q_rope, row, c_q, w_kvb, scale)
    -> (out [B, S, H * dv], the layer's updated leaves)`` that writes the
    new rows ``row [B, S, 1, r + dr]`` where its cache keeps them and
    attends its own key set: the last ``window`` positions out of a ring a
    slot, or the set a learned indexer kept (``models/dots3``'s two; ``c_q``
    is the scaled query latent an indexer reads).

    ``fresh`` (static): the dispatch says every row starts at position 0
    (the engine's ``fresh``). In a bucket ``ops/attention.
    latent_fresh_applies`` takes, the block then attends the rows it has
    just computed through the tiled kernel and gathers nothing."""
    from distributed_lion_tpu.ops.attention import (
        chunked_causal_attention,
        latent_fresh_applies,
        latent_fresh_attention,
        mla_decode_attention,
        paged_gather_kv,
        paged_kernel_applies,
        paged_scatter_kv,
    )

    B, S, _ = x.shape
    H, r = cfg.n_head, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if scale is None:
        scale = 1.0 / math.sqrt(dn + dr)
    s_q, s_kv = rescale
    c_q = None
    with jax.named_scope("mla/q"):
        if "wq" in p:
            q = _matmul(x, p["wq"])
        else:
            c_q = _rms_norm(_matmul(x, p["wq_a"]), p["q_norm"], cfg.rms_eps)
            if s_q != 1.0:
                c_q = (c_q * s_q).astype(x.dtype)
            q = _matmul(c_q, p["wq_b"])
        q = q.reshape(B, S, H, dn + dr)
        q = q.transpose(0, 2, 1, 3)                       # [B, H, S, dn+dr]
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
    with jax.named_scope("mla/kv_latent"):
        kv = _matmul(x, p["wkv_a"])                       # [B, S, r + dr]
        c_kv = _rms_norm(kv[..., :r], p["kv_norm"], cfg.rms_eps)
        if s_kv != 1.0:
            c_kv = (c_kv * s_kv).astype(x.dtype)
        k_rope = apply_rope(kv[:, None, :, r:], cos, sin)[:, 0]
        row = jnp.concatenate([c_kv, k_rope], -1)[:, :, None, :]
        if keys is None:
            pool = paged_scatter_kv(c["kv"], tables, pos,
                                    row.astype(c["kv"].dtype), valid)
    w_kvb = p["wkv_b"].reshape(r, H, dn + dv)             # heads of [k | v]
    if keys is not None:
        out, leaves = keys(q_nope, q_rope, row, c_q, w_kvb, scale)
    elif paged_kernel_applies(S, pool.shape, pool.dtype):
        # absorbed: every head's query against the latent row itself
        q_abs = absorb_query(q_nope, q_rope, w_kvb, pool.shape[-1])
        o_lat = mla_decode_attention(q_abs, pool, tables, pos, scale=scale)
        out = expand_output(o_lat, w_kvb, dn).reshape(B, 1, H * dv)
    elif fresh and latent_fresh_applies(S, dn, dv):
        out = latent_fresh_attention(
            jnp.concatenate([q_nope, q_rope], -1), row[:, :, 0], w_kvb,
            valid, scale=scale)
    else:
        def attend(tab):
            rows = paged_gather_kv(pool, tab)[:, :, 0, :r + dr]  # [B, T, .]
            k, v = expand_rows(rows, w_kvb, dn)
            q_full = jnp.concatenate([q_nope, q_rope], -1)
            return chunked_causal_attention(q_full, k, v, pos, scale=scale)

        bs, own = pool.shape[1], S // pool.shape[1]
        if S > 1 and S % bs == 0 and own < tables.shape[1]:
            # rows that all start at 0 (no shared prefix) fill only the
            # pages of their own S tokens: gather and expand those alone
            out = jax.lax.cond(jnp.all(pos == 0),
                               lambda: attend(tables[:, :own]),
                               lambda: attend(tables))
        else:
            out = attend(tables)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    if keys is None:
        leaves = {"kv": pool}
    if "wg" in p:
        from distributed_lion_tpu.models.laguna import gate_heads, head_gate

        out = gate_heads(out.reshape(B, S, H, dv), head_gate(x, p["wg"]),
                         x.dtype)
    return _matmul(out, p["wo"]), leaves


def joyai_decode_paged(params: dict, tokens: jnp.ndarray, cfg: JoyAIConfig,
                       pages: list, tables: jnp.ndarray, pos: jnp.ndarray,
                       valid=None, return_moe_stats: bool = False,
                       logit_index=None, fresh: bool = False):
    """Block-table decode (the serving engine's model hook, as
    ``llama_decode_paged``, ``fresh`` included: ``_mla_block``'s): row b's
    ``tokens`` [B, S] sit at positions
    ``pos[b] .. pos[b]+S-1``; ``pages`` is the per-layer ``{"kv"}`` latent
    pool. Returns (logits float32, updated pages[, counters]): logits
    ``[B, S, vocab]``, or ``[B, 1, vocab]`` of position ``logit_index``
    when given (the prefill reads one row: 2,048 x 129,280 float32 logits
    would be 1 GB). ``return_moe_stats``: the expert layers' int32 counters
    over the ``valid`` lanes (``parallel/expert.MOE_COUNTERS``), assignments
    and experts hit summed over the layers, the load as their maximum."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    max_pos = tables.shape[1] * pages[0]["kv"].shape[1]
    cos_all, sin_all = rope_angles(max_pos, cfg.qk_rope_head_dim,
                                   cfg.rope_theta)
    pos_ids = jnp.clip(pos[:, None] + jnp.arange(S)[None, :], 0, max_pos - 1)
    cos, sin = cos_all[pos_ids], sin_all[pos_ids]         # [B, S, dr/2]
    lanes = None if valid is None else \
        jnp.broadcast_to(valid, (B, S)).reshape(-1)
    counters = dict.fromkeys(MOE_COUNTERS, jnp.int32(0))
    new_pages = []
    for p, c in zip(params["blocks"], pages):
        a, c = _mla_block(_rms_norm(x, p["ln_attn"], cfg.rms_eps), p["attn"],
                          cfg, c, tables, pos, cos, sin, valid, fresh=fresh)
        new_pages.append(c)
        x = x + a
        h = _rms_norm(x, p["ln_mlp"], cfg.rms_eps)
        if "moe" not in p:
            x = x + _mlp(h, p["mlp"])
            continue
        y = moe_dropless_ffn(
            p["moe"], h.reshape(B * S, -1), top_k=cfg.top_k,
            scale=cfg.routed_scale, valid=lanes,
            return_counters=return_moe_stats)
        if not return_moe_stats:
            x = x + y.reshape(B, S, -1)
            continue
        y, st = y
        x = x + y.reshape(B, S, -1)
        for name in MOE_COUNTERS:
            join = jnp.maximum if name.endswith("_max") else jnp.add
            counters[name] = join(counters[name], st[name].astype(jnp.int32))
    x = _rms_norm(x, params["ln_f"], cfg.rms_eps)
    if logit_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    logits = _head_logits(x, params)
    return (logits, new_pages, counters) if return_moe_stats \
        else (logits, new_pages)
