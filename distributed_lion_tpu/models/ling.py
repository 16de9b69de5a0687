"""Ling 3.0 flash (inclusionAI's ``Ling-3.0-flash`` / ``-VL`` language
model) in pure JAX: the serving path.

A decoder of pre-norm residual blocks (published ``config.json``:
https://huggingface.co/inclusionAI/Ling-3.0-flash-VL), ``h = x +
Mix_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; RMSNorm, SwiGLU and the
head are ``models/llama``'s, the latent attention ``models/joyai``'s, the
per-head output gate ``models/laguna``'s, the expert layer
``parallel/expert.moe_dropless_ffn``. No biases, untied head. Layers come in
periods of ``group`` (6): five that mix the sequence through a recurrent
state, then one of latent attention.

- **KDA layers** (``(layer + 1) % group != 0``; ``ops/kda``'s module note has
  the recurrence). From the normed input ``u``: ``q~, k~, v~ = u W_q, u W_k,
  u W_v``; a causal depthwise convolution of ``conv_width`` (4) over each,
  then SiLU; q and k L2-normalised a head (q scaled by ``d_k^-0.5``; no
  RoPE); a log-decay a key channel ``g = gate_floor * sigmoid(exp(A_log_h)
  (u W_f + dt_bias))`` in ``(gate_floor, 0)`` (-5: the "safe" gate); a write
  strength ``beta = sigmoid(u W_beta)`` a head; the gated delta rule over a
  float32 state ``[d_k, d_v]`` a head; RMSNorm of each head's output with a
  gain; the per-head gate ``sigmoid(u W_g)``; ``W_o``.
- **MLA layers**: ``models/joyai._mla_block`` with a full-rank query
  (``wq``: the published ``q_lora_rank`` is null) and the same per-head gate
  (``wg``), RoPE (pairs ``(2i, 2i+1)``) on the 64 rope dims.
- **FFN**: the first ``first_dense`` layers a SwiGLU of ``d_ff``; the others
  sigmoid top-k dropless experts chosen under a group limit (``n_group``
  groups, the best ``topk_group`` of them by the sum of their two largest
  scores) with a shared expert, told which experts they hold (``held``: one
  chip's share of an expert-parallel deployment; the router keeps all its
  outputs). A layer's SwiGLU clamps (``expert_limits`` / ``shared_limits``)
  are 0, no clamp, in every layer the benchmark's cut keeps.
- **Two kinds of cache** (``serve/kv_cache``). An MLA layer's latent rows
  live in pages under the engine's block tables and grow with the sequence.
  A KDA layer keeps, a slot, ``state`` float32 ``[heads, d_k, d_v]`` and
  ``conv``, the last ``conv_width - 1`` rows of the three convolutions'
  inputs: no pages, no growth, found from the slot id alone. The decode tick
  (S = 1; **row b is slot b**: the leaves are stepped whole, in place, dead
  slots skipped: kernel ``kda_step`` on a TPU) reads and writes a live slot's
  state once. **A call with S > 1 is a prefill from position 0**: it starts
  from a zero state and a zero tail whatever the slot held (the reset at
  admission is the prefill itself: nothing of the slot's last tenant is
  read), runs the chunked form over the prompt, and overwrites the slot's
  leaves with what position ``length - 1`` leaves: a position past the
  row's length neither decays nor writes (``g = 0``, ``beta = 0``) and the
  tail is cut at the length, not at the bucket. The engine refuses the
  prefix cache and speculation for this family, the two callers that would
  start elsewhere or take a step back.

The vision tower is not here (the published config gives its four patch-token
ids and no width): the model serves text ids. Multi-token prediction and
training this family (loss, remat, specs, the chunked scan's backward) are
not here either.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional

import jax
import jax.numpy as jnp

from distributed_lion_tpu.models.joyai import _mla_block
from distributed_lion_tpu.models.laguna import gate_heads, head_gate
from distributed_lion_tpu.models.llama import (
    _head_logits,
    _matmul,
    _mlp,
    _normal,
    _rms_norm,
    rope_angles,
)
from distributed_lion_tpu.ops.kda import kda_chunked, kda_step
from distributed_lion_tpu.parallel.expert import (
    MOE_COUNTERS,
    moe_dropless_ffn,
)

# what a dispatch counts under ``return_moe_stats``: the expert layers' rows
# computed here, experts hit, largest load and picks made, held or not
LING_COUNTERS = MOE_COUNTERS + ("moe_routed",)


@dataclasses.dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157184
    n_layer: int = 42
    d_model: int = 2560
    n_head: int = 32
    head_dim: int = 128              # a KDA head's d_k = d_v
    group: int = 6                   # layer_group_size: the last is MLA
    conv_width: int = 4
    gate_floor: float = -5.0         # kda_lower_bound
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 6144                 # the leading dense layers' SwiGLU
    first_dense: int = 2             # first_k_dense_replace
    n_experts: int = 512             # the router's outputs
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    moe_d_ff: int = 768
    shared_d_ff: int = 768
    routed_scale: float = 2.5
    held: Optional[tuple] = None     # (first, count): the experts whose
    #                                  banks are here; None = all of them
    expert_limits: tuple = ()        # SwiGLU clamps a layer, () = none
    shared_limits: tuple = ()
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    n_ctx: int = 131072
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        """Values of one cached MLA row: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def banks(self) -> int:
        """Experts whose weights a layer holds."""
        return self.held[1] if self.held else self.n_experts

    @property
    def mla_layers(self) -> tuple:
        return tuple(i for i in range(self.n_layer)
                     if (i + 1) % self.group == 0)

    @property
    def kda_layers(self) -> tuple:
        return tuple(i for i in range(self.n_layer)
                     if (i + 1) % self.group)

    @property
    def conv_channels(self) -> int:
        """q~, k~ and v~ side by side: what one ``conv`` row holds."""
        return 3 * self.n_head * self.head_dim

    def limits(self, layer: int) -> tuple:
        """(routed, shared) SwiGLU clamps of ``layer``; 0 = none."""
        def at(xs):
            return float(xs[layer]) if layer < len(xs) else 0.0
        return at(self.expert_limits), at(self.shared_limits)

    @staticmethod
    def from_hf(hf: dict, **kw) -> "LingConfig":
        """From the published ``config.json`` keys (a benchmark
        configuration file carries them under the same names). Where the
        file says it was ``reduced`` in ``num_experts``, that number is the
        experts HELD (from 0) and the router keeps the ``published`` number
        of outputs. The per-layer lists may be longer than
        ``num_hidden_layers``: a cut in depth reads their head."""
        only = {"q_lora_rank": None, "use_qk_norm": True,
                "score_function": "sigmoid", "linear_silu": True,
                "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
                "use_mla_nope": False, "use_nGPT": False,
                "scale_router_input": False, "value_norm": False,
                "up_proj_norm": False, "no_kda_lora": True,
                "use_kda_lora": False, "kda_safe_gate": True,
                "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
                "gated_attention_proj_granularity_type": "head_wise",
                "tie_word_embeddings": False, "rope_scaling": None}
        for key, want in only.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"ling: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
        L = hf["num_hidden_layers"]
        held_n = hf["num_experts"]
        routed = hf.get("published", {}).get("num_experts", held_n) \
            if "num_experts" in hf.get("reduced", ()) else held_n
        base = dict(
            vocab_size=hf["vocab_size"], n_layer=L, d_model=hf["hidden_size"],
            n_head=hf["num_attention_heads"], head_dim=hf["head_dim"],
            group=hf["layer_group_size"],
            conv_width=hf["short_conv_kernel_size"],
            gate_floor=float(hf["kda_lower_bound"]),
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"], d_ff=hf["intermediate_size"],
            first_dense=hf["first_k_dense_replace"], n_experts=routed,
            top_k=hf["num_experts_per_tok"], n_group=hf["n_group"],
            topk_group=hf["topk_group"],
            moe_d_ff=hf["moe_intermediate_size"],
            shared_d_ff=hf["moe_shared_expert_intermediate_size"],
            routed_scale=hf["routed_scaling_factor"],
            held=None if held_n == routed else (0, held_n),
            expert_limits=tuple(hf.get("expert_swiglu_limit_list", ())[:L]),
            shared_limits=tuple(
                hf.get("share_expert_swiglu_limit_list", ())[:L]),
            rope_theta=float(hf["rope_theta"]), rms_eps=hf["rms_norm_eps"],
            n_ctx=hf["max_position_embeddings"])
        base.update(kw)
        return LingConfig(**base)

    @staticmethod
    def tiny(**kw) -> "LingConfig":
        """KDA + dense, then MLA and KDA with experts: every kind of layer
        with a period of 2 for the published 6."""
        base = dict(vocab_size=256, n_layer=3, d_model=64, n_head=4,
                    head_dim=16, group=2, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    d_ff=128, first_dense=1, n_experts=16, top_k=2, n_group=4,
                    topk_group=2, moe_d_ff=32, shared_d_ff=32, n_ctx=4096)
        base.update(kw)
        return LingConfig(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "LingConfig":
        """A CLI model name: ``tiny``, or the path of a JSON file holding
        the published ``config.json`` keys (further keys, as a benchmark
        configuration file has, are read as :meth:`from_hf` says)."""
        if name == "tiny":
            return cls.tiny(**kw)
        if name.endswith(".json"):
            with open(name) as f:
                return cls.from_hf(json.load(f), **kw)
        raise ValueError(
            f"unknown ling model_name {name!r}: 'tiny' or the path of a "
            "config.json")


def ling_init(key: jax.Array, cfg: LingConfig) -> dict:
    """Seeded N(0, 0.02) weights in the program's tree (norm gains 1; the
    router's correction bias N(0, 0.01), ``A_log`` N(0, 0.3) and ``dt_bias``
    N(-4, 1), float32: decays from a token or two to hundreds). An expert
    layer's banks are the ``cfg.banks`` experts held; its router has all
    its outputs."""
    d, dt, H = cfg.d_model, cfg.param_dtype, cfg.n_head
    ch, f, fs = H * cfg.head_dim, cfg.moe_d_ff, cfg.shared_d_ff
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = iter(jax.random.split(key, 2 + 20 * cfg.n_layer))

    def w(*shape):
        return _normal(next(keys), shape, 0.02, dt)

    def gain(n):
        return {"scale": jnp.ones((n,), dt)}

    params: dict = {"wte": w(cfg.vocab_size, d),
                    "lm_head": w(d, cfg.vocab_size), "ln_f": gain(d),
                    "blocks": []}
    for layer in range(cfg.n_layer):
        block = {"ln_attn": gain(d), "ln_mlp": gain(d)}
        if layer in cfg.mla_layers:
            block["attn"] = {
                "wq": w(d, H * qk), "wkv_a": w(d, cfg.latent_dim),
                "kv_norm": gain(cfg.kv_lora_rank),
                "wkv_b": w(cfg.kv_lora_rank,
                           H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "wg": w(d, H), "wo": w(H * cfg.v_head_dim, d)}
        else:
            block["kda"] = {
                "wq": w(d, ch), "wk": w(d, ch), "wv": w(d, ch),
                "conv": w(cfg.conv_width, 3 * ch), "wf": w(d, ch),
                "A_log": _normal(next(keys), (H,), 0.3, jnp.float32),
                "dt_bias": _normal(next(keys), (ch,), 1.0, jnp.float32) - 4.0,
                "wb": w(d, H), "wg": w(d, H), "o_norm": gain(cfg.head_dim),
                "wo": w(ch, d)}
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


def _unit(x):
    """x over its last axis' L2 norm (float32)."""
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _kda_block(u, p, cfg: LingConfig, c, slots, lengths, lanes):
    """One KDA layer over its slot-indexed leaves (the module note says
    which path). ``lanes [B, S]`` bool, the positions that hold a token.
    Returns (output ``[B, S, d]``, the layer's updated ``{"state",
    "conv"}``)."""
    f32 = jnp.float32
    B, S, _ = u.shape
    H, dk, tail = cfg.n_head, cfg.head_dim, cfg.conv_width - 1
    prefill = S > 1
    with jax.named_scope("kda/conv"):
        x = jnp.concatenate([_matmul(u, p[n]) for n in ("wq", "wk", "wv")],
                            -1).astype(c["conv"].dtype)       # [B, S, 3 H dk]
        # a prefill starts from position 0 whatever the slot held
        before = jnp.zeros((B, tail, x.shape[-1]), x.dtype) if prefill \
            else c["conv"]
        seen = jnp.concatenate([before, x], 1)                # [B, S + 3, .]
        kernel = p["conv"].astype(f32)
        y = sum(seen[:, j:j + S].astype(f32) * kernel[j]
                for j in range(cfg.conv_width))
        q, k, v = (t.reshape(B, S, H, dk)
                   for t in jnp.split(jax.nn.silu(y), 3, -1))
        q, k = _unit(q) * dk ** -0.5, _unit(k)
        if prefill:     # the rows at positions length - 3 .. length - 1
            last = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
                row, n, tail, 0))(seen, lengths)
            conv = c["conv"].at[slots].set(last)
        else:
            conv = jnp.where(lanes[:, :, None], seen[:, 1:], c["conv"])
    with jax.named_scope("kda/gate"):
        a = _matmul(u, p["wf"]).astype(f32).reshape(B, S, H, dk)
        g = cfg.gate_floor * jax.nn.sigmoid(
            jnp.exp(p["A_log"].astype(f32))[:, None]
            * (a + p["dt_bias"].astype(f32).reshape(H, dk)))
        beta = jax.nn.sigmoid(_matmul(u, p["wb"]).astype(f32))    # [B, S, H]
        # a position with no token neither decays nor writes
        g = jnp.where(lanes[..., None, None], g, 0.0)
        beta = jnp.where(lanes[..., None], beta, 0.0)
    gate = head_gate(u, p["wg"])
    if prefill:
        o, last = kda_chunked(q, k, v, g, beta,
                              jnp.zeros((B, H, dk, dk), f32))
        state = c["state"].at[slots].set(last)
    else:
        o, state = kda_step(c["state"], q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0], lanes[:, 0])
        o = o[:, None]
    with jax.named_scope("kda/out_norm"):
        o = _rms_norm(o, p["o_norm"], cfg.rms_eps)     # float32, a head
    out = gate_heads(o, gate, u.dtype)
    return _matmul(out, p["wo"]), {"state": state, "conv": conv}


def ling_decode_paged(params: dict, tokens: jnp.ndarray, cfg: LingConfig,
                      pages: list, tables: jnp.ndarray, slots: jnp.ndarray,
                      pos: jnp.ndarray, valid=None,
                      return_moe_stats: bool = False, logit_index=None,
                      fresh: bool = False):
    """Block-table decode (the serving engine's model hook, as
    ``laguna_decode_paged``; ``fresh`` is ``models/joyai._mla_block``'s):
    row b's ``tokens`` [B, S] sit at positions
    ``pos[b] .. pos[b]+S-1``; ``pages`` is the per-layer cache list, an MLA
    layer's ``{"kv"}`` latent pool under ``tables`` [B, nb] and a KDA
    layer's ``{"state", "conv"}`` a slot (the module note). S = 1 is the
    decode tick, row b being slot b; S > 1 a prefill from position 0 into
    slot ``slots[b]``. Returns (logits float32, updated pages[, counters]):
    logits ``[B, S, vocab]``, or ``[B, 1, vocab]`` of position
    ``logit_index`` when given. ``return_moe_stats``: the expert layers'
    int32 counters over the ``valid`` lanes (``LING_COUNTERS``), summed over
    the layers, the load as their maximum."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    lanes = jnp.ones((B, S), bool) if valid is None \
        else jnp.broadcast_to(valid, (B, S))
    lengths = lanes.sum(1).astype(jnp.int32)
    pool = pages[cfg.mla_layers[0]]["kv"]
    max_pos = tables.shape[1] * pool.shape[1]
    cos_all, sin_all = rope_angles(max_pos, cfg.qk_rope_head_dim,
                                   cfg.rope_theta)
    pos_ids = jnp.clip(pos[:, None] + jnp.arange(S)[None, :], 0, max_pos - 1)
    cos, sin = cos_all[pos_ids], sin_all[pos_ids]             # [B, S, dr/2]
    counters = dict.fromkeys(LING_COUNTERS, jnp.int32(0))
    new_pages = []
    for layer, (p, c) in enumerate(zip(params["blocks"], pages)):
        u = _rms_norm(x, p["ln_attn"], cfg.rms_eps)
        if "kda" in p:
            a, c = _kda_block(u, p["kda"], cfg, c, slots, lengths, lanes)
        else:
            a, c = _mla_block(u, p["attn"], cfg, c, tables, pos, cos, sin,
                              valid, fresh=fresh)
        new_pages.append(c)
        x = x + a
        h = _rms_norm(x, p["ln_mlp"], cfg.rms_eps)
        if "moe" not in p:
            x = x + _mlp(h, p["mlp"])
            continue
        y = moe_dropless_ffn(
            p["moe"], h.reshape(B * S, -1), top_k=cfg.top_k,
            scale=cfg.routed_scale,
            valid=None if valid is None else lanes.reshape(-1),
            return_counters=return_moe_stats, held=cfg.held,
            route_groups=(cfg.n_group, cfg.topk_group),
            limits=cfg.limits(layer))
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
