"""dots3-note (``model_type`` ``dots3_note``, dots-studio's dots3-note-prev)
in pure JAX: the serving path of its language model.

A decoder of pre-norm residual blocks (published ``config.json``:
https://huggingface.co/dots-studio/dots3-note-prev), ``h = x +
Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; RMSNorm, SwiGLU and the
head are ``models/llama``'s, the expert layer ``parallel/expert
.moe_dropless_ffn`` (sigmoid scores with a selection bias, one shared expert,
told which experts it holds). No biases but the indexer's LayerNorm, untied
head. Every layer's attention is LATENT attention, ``models/joyai
._mla_block``: the projections, the rope, the per-head output gate and
``W_o`` are that function's, handed the layer kind's own view of the
configuration (:class:`Latent`: head count, ranks and widths differ by kind)
and two things besides, its latents' rescale and **where its keys come
from** (``layer_types``):

- **full**: 128 heads of 128 nope + 64 rope over a latent of 512, theta 8e7.
  The cache row ``[c_kv | k_rope]`` (576 values in 640 lanes) lives in pages
  under the engine's block tables, leaf ``kv``, and beside it, under the
  same tables, **an index key a token**, leaf ``ik`` (128 lanes): the
  learned indexer's (``ops/dsa``). A query attends the ``index_topk``
  positions its indexer scores highest, every position while no more are
  visible. The decode tick scores the row's index keys in place (kernel
  ``dsa_index``), takes the exact mask and walks the latent pages under it
  in the absorbed form (kernel ``dsa_attn``); a prefill takes its masks a
  chunk of queries at a time and attends its own fresh keys in the expanded
  form (``ops/dsa.dsa_prefill_attention``: the tiled kernel ``dsa_prefill``
  on a TPU).
- **sliding**: latent attention of ANOTHER geometry (64 heads of 192 nope +
  64 rope over a latent of 1,024, theta 5e4) over the last ``window``
  positions, the query's own counted. Its rows (1,088 values in 1,152
  lanes) live in a bounded RING a slot (``ops/attention.ring_pages``: 34
  pages of 16 for a window of 513), found from the slot id alone. The decode
  tick runs the absorbed kernel over the ring in logical order from the
  window's first row on (``window_mla_attn``); a prefill attends its own
  fresh keys banded (``ops/attention.banded_causal_attention``) and writes
  the ring behind it.

Both kinds multiply ``c_q`` and ``c_kv`` after their norms by
:func:`lora_rescale` (the scaled ``c_kv`` is what is cached, the indexer's
query reads the scaled ``c_q``), and both gate each head's output
(``models/laguna.head_gate``). **A call with S > 1 is a prefill from position
0**, as ``models/laguna``'s: the engine refuses the prefix cache and
speculation for this family.

The indexer (:func:`_indexer`): ``qI = c_q W_qI`` -> ``index_n_heads`` heads
of ``index_head_dim``; ``kI = LayerNorm(u W_kI)`` (gain and bias); RoPE on
the first ``qk_rope_head_dim`` values of each in rotate-half pairs
(``models/laguna.apply_rope_half``); ``w = (u W_wI) / sqrt(heads x dim)``
float32. The published inference code's Hadamard rotation of ``qI`` and
``kI`` (orthogonal: no dot product changes) and its fp8 index cache are not
here.

The vision tower, the audio encoder and the multi-token-prediction block are
not built (text ids only; ROADMAP M13, M6). Training this family is not here.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from distributed_lion_tpu.models.joyai import (
    _mla_block,
    absorb_query,
    expand_output,
    expand_rows,
)
from distributed_lion_tpu.models.laguna import apply_rope_half
from distributed_lion_tpu.models.llama import (
    _head_logits,
    _matmul,
    _mlp,
    _normal,
    _rms_norm,
    rope_angles,
)
from distributed_lion_tpu.ops import dsa
from distributed_lion_tpu.parallel.expert import (
    MOE_COUNTERS,
    moe_dropless_ffn,
)

# what a dispatch counts under ``return_moe_stats``: the expert layers' rows
# computed here, experts hit, largest load, and picks made, held or not; the
# indexer's rows, visible and kept keys over the full layers' decode ticks
# (``ops/dsa.DSA_COUNTERS``) and the pages ONE sliding layer's decode walk
# was handed (both 0 from a prefill, which reads its own fresh keys: a name
# without ``moe_`` is one sum in ``engine.stats``, not two)
DOTS3_COUNTERS = MOE_COUNTERS + ("moe_routed",) + dsa.DSA_COUNTERS \
    + ("kv_window_pages_read",)


def lora_rescale(hidden: int, rank: int) -> float:
    """``apply_mla_qkv_lora_rescale``: the factor on a latent of ``rank``
    after its norm, ``sqrt(hidden_size / rank)``."""
    return math.sqrt(hidden / rank)


@dataclasses.dataclass(frozen=True)
class Latent:
    """One layer kind's latent attention, under the names
    ``models/joyai._mla_block`` reads."""
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float
    rescale: tuple = (1.0, 1.0)      # (s_q, s_kv)

    @property
    def latent_dim(self) -> int:
        """Values of one cached row: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064
    n_layer: int = 46
    d_model: int = 5120
    windowed: tuple = (False, False) + (True, True, True, False) * 11
    full: Latent = Latent(128, 1024, 512, 128, 64, 128, 8e7, 1e-5,
                          (math.sqrt(5.0), math.sqrt(10.0)))
    swa: Latent = Latent(64, 1024, 1024, 192, 64, 128, 5e4, 1e-5,
                         (math.sqrt(5.0), math.sqrt(5.0)))
    window: int = 513                # the query's own position counted
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    d_ff: int = 13824                # the leading dense layers' SwiGLU
    first_dense: int = 1             # first_k_dense_replace
    n_experts: int = 256             # the router's outputs
    top_k: int = 8
    moe_d_ff: int = 1536
    n_shared: int = 1
    routed_scale: float = 1.0
    held: Optional[tuple] = None     # (first, count): the experts whose
    #                                  banks are here; None = all of them
    rms_eps: float = 1e-5
    n_ctx: int = 524288
    # positions one copy of the full layers' decode walks brings: the engine
    # mints their pages in aligned runs of it (``ServeModel.page_run``)
    page_run: int = 64
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def banks(self) -> int:
        """Experts whose weights a layer holds."""
        return self.held[1] if self.held else self.n_experts

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, w in enumerate(self.windowed) if w)

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, w in enumerate(self.windowed) if not w)

    def latent(self, layer: int) -> Latent:
        return self.swa if self.windowed[layer] else self.full

    @staticmethod
    def from_hf(hf: dict, **kw) -> "Dots3Config":
        """From the published ``config.json`` keys (a benchmark
        configuration file carries them under the same names). Where the
        file says it was ``reduced`` in ``n_routed_experts``, that number is
        the experts HELD (from 0) and the router keeps the ``published``
        number of outputs, as ``models/laguna``'s."""
        only = {"rope_scaling": None, "n_group": 1, "topk_group": 1,
                "attention_gate_type": "headwise",
                "swa_attention_gate_type": "headwise",
                "scoring_func": "sigmoid", "topk_method": "noaux_tc",
                "norm_topk_prob": True, "attention_bias": False,
                "moe_layer_freq": 1, "tie_word_embeddings": False,
                "hidden_act": "silu"}
        for key, want in only.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"dots3: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
        L, d, eps = hf["num_hidden_layers"], hf["hidden_size"], \
            hf["rms_norm_eps"]
        kinds = hf["layer_types"][:L]
        if len(kinds) != L or \
                set(kinds) - {"full_attention", "sliding_attention"}:
            raise ValueError(f"dots3: layer_types {kinds} for {L} layers")
        on = bool(hf.get("apply_mla_qkv_lora_rescale", False))

        def latent(pre, theta):
            rq, rkv = hf[pre + "q_lora_rank"], hf[pre + "kv_lora_rank"]
            return Latent(
                hf[pre + "num_attention_heads"], rq, rkv,
                hf[pre + "qk_nope_head_dim"], hf[pre + "qk_rope_head_dim"],
                hf[pre + "v_head_dim"], float(theta), eps,
                (lora_rescale(d, rq), lora_rescale(d, rkv)) if on
                else (1.0, 1.0))

        held_n = hf["n_routed_experts"]
        routed = hf.get("published", {}).get("n_routed_experts", held_n) \
            if "n_routed_experts" in hf.get("reduced", ()) else held_n
        base = dict(
            vocab_size=hf["vocab_size"], n_layer=L, d_model=d,
            windowed=tuple(k == "sliding_attention" for k in kinds),
            full=latent("", hf["rope_theta"]),
            swa=latent("swa_", hf["swa_rope_theta"]),
            window=hf["sliding_window_size"],
            index_n_heads=hf["index_n_heads"],
            index_head_dim=hf["index_head_dim"],
            index_topk=hf["index_topk"], d_ff=hf["intermediate_size"],
            first_dense=hf["first_k_dense_replace"], n_experts=routed,
            top_k=hf["num_experts_per_tok"],
            moe_d_ff=hf["moe_intermediate_size"],
            n_shared=hf["n_shared_experts"],
            routed_scale=hf["routed_scaling_factor"],
            held=None if held_n == routed else (0, held_n),
            rms_eps=eps, n_ctx=hf["max_position_embeddings"])
        base.update(kw)
        return Dots3Config(**base)

    @staticmethod
    def tiny(**kw) -> "Dots3Config":
        """Dense + sliding, sliding, sliding, full behind a leading full
        layer: the benchmark's cut, with a window of 9 and the 12 best
        positions kept."""
        base = dict(
            vocab_size=256, n_layer=5, d_model=64,
            windowed=(False, True, True, True, False),
            full=Latent(4, 32, 32, 16, 8, 16, 8e7, 1e-5,
                        (lora_rescale(64, 32), lora_rescale(64, 32))),
            swa=Latent(2, 32, 48, 24, 8, 16, 5e4, 1e-5,
                       (lora_rescale(64, 32), lora_rescale(64, 48))),
            window=9, index_n_heads=4, index_head_dim=16, index_topk=12,
            d_ff=128, n_experts=8, top_k=2, moe_d_ff=32, n_ctx=4096,
            page_run=0)
        base.update(kw)
        return Dots3Config(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "Dots3Config":
        """A CLI model name: ``tiny``, or the path of a JSON file holding
        the published ``config.json`` keys (further keys, as a benchmark
        configuration file has, are read as :meth:`from_hf` says)."""
        if name == "tiny":
            return cls.tiny(**kw)
        if name.endswith(".json"):
            with open(name) as f:
                return cls.from_hf(json.load(f), **kw)
        raise ValueError(
            f"unknown dots3 model_name {name!r}: 'tiny' or the path of a "
            "config.json")


def dots3_init(key: jax.Array, cfg: Dots3Config) -> dict:
    """Seeded N(0, 0.02) weights in the program's tree (norm gains 1, the
    indexer's LayerNorm bias 0; the router's selection bias N(0, 0.01),
    float32). An expert layer's banks are the ``cfg.banks`` experts held;
    its router has all its outputs."""
    d, dt = cfg.d_model, cfg.param_dtype
    f, fs = cfg.moe_d_ff, cfg.n_shared * cfg.moe_d_ff
    keys = iter(jax.random.split(key, 2 + 20 * cfg.n_layer))

    def w(*shape):
        return _normal(next(keys), shape, 0.02, dt)

    def gain(n):
        return {"scale": jnp.ones((n,), dt)}

    params: dict = {"wte": w(cfg.vocab_size, d),
                    "lm_head": w(d, cfg.vocab_size), "ln_f": gain(d),
                    "blocks": []}
    for layer in range(cfg.n_layer):
        g = cfg.latent(layer)
        H, qk = g.n_head, g.qk_nope_head_dim + g.qk_rope_head_dim
        attn = {"wq_a": w(d, g.q_lora_rank), "q_norm": gain(g.q_lora_rank),
                "wq_b": w(g.q_lora_rank, H * qk),
                "wkv_a": w(d, g.latent_dim), "kv_norm": gain(g.kv_lora_rank),
                "wkv_b": w(g.kv_lora_rank,
                           H * (g.qk_nope_head_dim + g.v_head_dim)),
                "wg": w(d, H), "wo": w(H * g.v_head_dim, d)}
        block = {"ln_attn": gain(d), "ln_mlp": gain(d), "attn": attn}
        if not cfg.windowed[layer]:
            Hi, di = cfg.index_n_heads, cfg.index_head_dim
            block["index"] = {
                "wq": w(g.q_lora_rank, Hi * di), "wk": w(d, di),
                "k_norm": {"scale": jnp.ones((di,), dt),
                           "bias": jnp.zeros((di,), dt)},
                "ww": w(d, Hi)}
        if layer < cfg.first_dense:
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


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _indexer(u, c_q, p, cfg: Dots3Config, cos, sin):
    """The indexer's side of a full layer (the module note): (qI ``[B, S,
    Hi, di]`` roped, w ``[B, S, Hi]`` float32, kI ``[B, S, di]`` roped: the
    index-key cache row)."""
    B, S, _ = u.shape
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    q = _matmul(c_q, p["wq"]).reshape(B, S, Hi, di).transpose(0, 2, 1, 3)
    q = apply_rope_half(q, cos, sin).transpose(0, 2, 1, 3)
    k = _layer_norm(_matmul(u, p["wk"]), p["k_norm"], cfg.rms_eps)
    k = apply_rope_half(k[:, None], cos, sin)[:, 0]
    w = _matmul(u, p["ww"]).astype(jnp.float32) / math.sqrt(Hi * di)
    return q, w, k


def _seen_attention(q_nope, q_rope, rows, w_kvb, seen, scale):
    """The gather path of either key set: every head's query over the
    latent rows ``rows [B, T, r + dr]`` expanded, under ``seen [B, S, T]``.
    Returns ``[B, S, H * dv]``."""
    k, v = expand_rows(rows, w_kvb, q_nope.shape[-1])
    q = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    pr = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30),
                        axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", pr, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    B, H, S, dv = out.shape
    return out.transpose(0, 2, 1, 3).reshape(B, S, H * dv)


def kept_keys(u, p_index, cfg: Dots3Config, c, tables, pos, lengths, valid,
              cos, sin, tally: dict):
    """A full layer's key source for ``_mla_block``: the set the indexer
    kept, out of the latent pages ``c["kv"]`` and the index-key pages
    ``c["ik"]``. ``tally`` receives the layer's ``DSA_COUNTERS``."""
    from distributed_lion_tpu.ops.attention import (
        paged_gather_kv,
        paged_kernel_applies,
        paged_scatter_kv,
    )

    g = cfg.full
    r, dr, dn = g.kv_lora_rank, g.qk_rope_head_dim, g.qk_nope_head_dim
    topk = cfg.index_topk

    def keys(q_nope, q_rope, row, c_q, w_kvb, scale):
        B, H, S, _ = q_nope.shape
        qi, wi, ki = _indexer(u, c_q, p_index, cfg, cos, sin)
        pool = paged_scatter_kv(c["kv"], tables, pos,
                                row.astype(c["kv"].dtype), valid)
        ik = paged_scatter_kv(c["ik"], tables, pos,
                              ki[:, :, None, :].astype(c["ik"].dtype), valid)
        leaves = {"kv": pool, "ik": ik}
        if S > 1:      # a prefill from position 0: its own fresh keys
            k, v = expand_rows(row[:, :, 0], w_kvb, dn)
            # (its counts are dropped: the engine keeps one sum a counter,
            # and these are the decode ticks')
            out, _ = dsa.dsa_prefill_attention(
                jnp.concatenate([q_nope, q_rope], -1), k, v, qi, wi, ki,
                lengths, topk=topk, scale=scale)
            return out.transpose(0, 2, 1, 3).reshape(B, S, -1), leaves
        T = tables.shape[1] * pool.shape[1]
        scores = dsa.decode_index_scores(qi[:, 0], wi[:, 0], ik, tables, pos,
                                         page_run=cfg.page_run)
        live = lengths > 0
        with jax.named_scope("dsa/select"):
            visible = (jnp.arange(T)[None, :] <= pos[:, None]) & live[:, None]
            keep = dsa.kept_positions(scores, visible, topk)
        tally.update(dsa_rows=live.sum().astype(jnp.int32),
                     dsa_keys_visible=visible.sum().astype(jnp.int32),
                     dsa_keys_kept=keep.sum().astype(jnp.int32))
        if paged_kernel_applies(S, pool.shape, pool.dtype):
            q_abs = absorb_query(q_nope, q_rope, w_kvb, pool.shape[-1])
            o_lat = dsa.kept_decode_attention(
                q_abs, pool, tables, pos, keep, scale=scale,
                page_run=cfg.page_run)
            return expand_output(o_lat, w_kvb, dn).reshape(B, 1, -1), leaves
        with jax.named_scope("dsa/attn"):
            rows = paged_gather_kv(pool, tables)[:, :, 0, :r + dr]
            return _seen_attention(q_nope, q_rope, rows, w_kvb,
                                   keep[:, None], scale), leaves

    return keys


def ring_keys(cfg: Dots3Config, c, slots, pos, lengths, tally: dict):
    """A sliding layer's key source for ``_mla_block``: the last
    ``cfg.window`` positions, out of slot ``slots[b]``'s ring of latent rows
    ``c["kv"]``. ``tally`` receives the pages the decode walk was handed."""
    from distributed_lion_tpu.ops.attention import (
        banded_causal_attention,
        paged_gather_kv,
        paged_kernel_applies,
        ring_mla_decode_attention,
        ring_scatter_kv,
        ring_walk,
    )

    g = cfg.swa
    r, dr, dn = g.kv_lora_rank, g.qk_rope_head_dim, g.qk_nope_head_dim

    def keys(q_nope, q_rope, row, c_q, w_kvb, scale):
        B, H, S, _ = q_nope.shape
        with jax.named_scope("window_mla"):
            pool = ring_scatter_kv(c["kv"], slots, pos,
                                   row.astype(c["kv"].dtype), lengths,
                                   window=cfg.window)
            if S > 1:  # a prefill from position 0: its own fresh keys
                k, v = expand_rows(row[:, :, 0], w_kvb, dn)
                out = banded_causal_attention(
                    jnp.concatenate([q_nope, q_rope], -1), k, v,
                    window=cfg.window)
                return out.transpose(0, 2, 1, 3).reshape(B, S, -1), \
                    {"kv": pool}
            if paged_kernel_applies(S, pool.shape, pool.dtype):
                q_abs = absorb_query(q_nope, q_rope, w_kvb, pool.shape[-1])
                o_lat, read = ring_mla_decode_attention(
                    q_abs, pool, slots, pos, window=cfg.window, scale=scale,
                    active=lengths > 0)
                out = expand_output(o_lat, w_kvb, dn).reshape(B, 1, -1)
            else:
                walk, rel_len, rel_start, read = ring_walk(
                    slots, pos, pool.shape[1], window=cfg.window,
                    active=lengths > 0)
                rows = paged_gather_kv(pool, walk)[:, :, 0, :r + dr]
                t = jnp.arange(rows.shape[1])[None, :]
                seen = (t >= rel_start[:, None]) & (t < rel_len[:, None])
                out = _seen_attention(q_nope, q_rope, rows, w_kvb,
                                      seen[:, None], scale)
            tally["kv_window_pages_read"] = read.sum().astype(jnp.int32)
            return out, {"kv": pool}

    return keys


def dots3_decode_paged(params: dict, tokens: jnp.ndarray, cfg: Dots3Config,
                       pages: list, tables: jnp.ndarray, slots: jnp.ndarray,
                       pos: jnp.ndarray, valid=None,
                       return_moe_stats: bool = False, logit_index=None):
    """Block-table decode (the serving engine's model hook, as
    ``laguna_decode_paged``): row b's ``tokens`` [B, S] sit at positions
    ``pos[b] .. pos[b]+S-1``; ``pages`` is the per-layer pool list, a full
    layer's ``{"kv", "ik"}`` under ``tables`` [B, nb] and a sliding layer's
    ``{"kv"}`` in the ring of slot ``slots[b]``. S = 1 is the decode tick;
    S > 1 a prefill from position 0 (the module note). Returns (logits
    float32, updated pages[, counters]): logits ``[B, S, vocab]``, or ``[B,
    1, vocab]`` of position ``logit_index`` when given.
    ``return_moe_stats``: ``DOTS3_COUNTERS`` over the ``valid`` lanes, summed
    over the layers (the expert load as their maximum; the window pages of
    the first sliding layer alone)."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    lanes = None if valid is None else jnp.broadcast_to(valid, (B, S))
    lengths = jnp.full((B,), S, jnp.int32) if lanes is None \
        else lanes.sum(1).astype(jnp.int32)
    max_pos = tables.shape[1] * pages[cfg.full_layers[0]]["kv"].shape[1]
    pos_ids = jnp.clip(pos[:, None] + jnp.arange(S)[None, :], 0, max_pos - 1)
    angles = {}
    for windowed, g in ((False, cfg.full), (True, cfg.swa)):
        cos, sin = rope_angles(max_pos, g.qk_rope_head_dim, g.rope_theta)
        angles[windowed] = (cos[pos_ids], sin[pos_ids])   # [B, S, dr / 2]
    counters = dict.fromkeys(DOTS3_COUNTERS, jnp.int32(0))
    new_pages = []
    for layer, (p, c) in enumerate(zip(params["blocks"], pages)):
        windowed, g = cfg.windowed[layer], cfg.latent(layer)
        cos, sin = angles[windowed]
        u = _rms_norm(x, p["ln_attn"], cfg.rms_eps)
        tally: dict = {}
        keys = ring_keys(cfg, c, slots, pos, lengths, tally) if windowed \
            else kept_keys(u, p["index"], cfg, c, tables, pos, lengths,
                           valid, cos, sin, tally)
        a, c = _mla_block(u, p["attn"], g, c, tables, pos, cos, sin, valid,
                          scale=g.scale, rescale=g.rescale, keys=keys)
        new_pages.append(c)
        if not windowed or layer == cfg.window_layers[0]:
            for name, n in tally.items():
                counters[name] = counters[name] + n
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
