"""MiniCPM-SALA (``model_type`` ``minicpm_sala``, openbmb) in pure JAX: the
serving path.

A dense decoder of pre-norm residual blocks under muP scalings (published
``config.json``: https://huggingface.co/openbmb/MiniCPM-SALA): ``x_0 =
scale_emb E[token]``; ``x <- x + c Mix_l(RMSNorm(x))``, ``x <- x + c
SwiGLU(RMSNorm(x))`` with ``c = scale_depth / sqrt(mup_denominator)`` (the
published number under the root whatever depth is kept); logits ``= W_head
(RMSNorm(x_L) / (hidden_size / dim_model_base))``, untied. RMSNorm, SwiGLU and
the head are ``models/llama``'s, RoPE ``models/laguna``'s (``rotate_half``
pairs). No biases. Two mixers (``mixer_types``):

- **``minicpm4``: block-sparse softmax attention** (InfLLM-V2;
  ``ops/sparse_select``'s module note has the rule). Grouped queries over
  ``n_kv_head`` kv heads, no RoPE, q and k RMS-normed a head with a gain, a
  full-width output gate ``sigmoid(u W_z)``. Keys and values live in pages
  under the engine's block tables, and beside them one compressed key a
  page (leaf ``ck``): a prefill writes every window that lies inside the
  prompt's TRUE length, a decode tick the one its position closes. A query
  past ``dense_len`` attends the ``topk`` blocks it selects, a set a kv
  head; one at or under it, every position. Decode (S = 1) runs
  ``paged_attn`` over each (row, kv head)'s compacted list of blocks, a
  block being an aligned run of pages (the engine's tables are minted so:
  ``ServeModel.page_run``).
- **``lightning-attn``: linear attention with a constant decay a head**
  (``ops/lightning``): q, k, v of ``n_head`` heads, q and k RMS-normed a
  head with a gain, RoPE over the whole head, ``S <- lambda_h S + k v^T``,
  ``o = head_dim^-0.5 S^T q`` over a float32 state ``[head_dim, head_dim]``
  a head a slot, RMSNorm of each head's output with a gain, the same
  full-width gate. ``lambda_h = exp(-slope_h)``; the slopes are a float32
  leaf of the layer's weights (:func:`lightning_slopes` at init: the head
  slopes of Lightning Attention-2).

**Three kinds of cache leaf** (``serve/kv_cache``): a ``minicpm4`` layer's
``{"k", "v"}`` pages and ``ck``, one row a page, under the block tables; a
Lightning layer's ``{"state"}`` a slot, found from the slot id alone. The
decode tick (S = 1; row b is slot b) steps the live slots' states in place
(kernel ``lightning_step`` on a TPU). **A call with S > 1 is a prefill from
position 0**: it starts every state from zero whatever the slot held, runs
the chunked form over the prompt (a position past the row's length neither
decays nor writes), and the engine refuses the prefix cache and speculation
for this family.

Training this family is not here.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any

import jax
import jax.numpy as jnp

from distributed_lion_tpu.models.laguna import Rope, apply_rope_half
from distributed_lion_tpu.models.llama import (
    _head_logits,
    _matmul,
    _mlp,
    _normal,
    _rms_norm,
)
from distributed_lion_tpu.ops.lightning import (
    lightning_chunked,
    lightning_step,
)
from distributed_lion_tpu.ops.sparse_select import (
    SparseConfig,
    decode_compressed,
    decode_page_lists,
    prefill_compressed,
    scatter_compressed,
    sparse_decode_attention,
    sparse_prefill_attention,
)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# what a dispatch counts under ``return_moe_stats`` (int32, summed over the
# ``minicpm4`` layers; the name is the engine's, there are no experts): the
# (page, kv head) pairs the decode lists hand attention, the copies the walk
# over them starts (a list's entries x the k and the v leaf: 2 a pair where
# an entry is a page, 0.5 where it is a block of four), the live decode rows
# past and at or under ``dense_len``, and the compressed keys written
SALA_COUNTERS = ("kv_pages_selected", "kv_copies", "sparse_rows",
                 "dense_rows", "ck_rows_written")


def lightning_slopes(n_head: int) -> jnp.ndarray:
    """``s_h = 2^(-8 (h + 1) / H)`` float32: the fastest head forgets in a
    token or two, the slowest keeps hundreds."""
    return 2.0 ** (-8.0 * (jnp.arange(n_head, dtype=jnp.float32) + 1)
                   / n_head)


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    n_layer: int = 32
    d_model: int = 4096
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    d_ff: int = 16384
    mixers: tuple = (SPARSE,) + (LIGHTNING,) * 3     # a kind a layer
    sparse: SparseConfig = SparseConfig()
    rope_theta: float = 1e4                # the Lightning layers' RoPE
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32
    dim_model_base: int = 256
    rms_eps: float = 1e-6
    n_ctx: int = 524288
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def sparse_layers(self) -> tuple:
        return tuple(i for i, m in enumerate(self.mixers) if m == SPARSE)

    @property
    def lightning_layers(self) -> tuple:
        return tuple(i for i, m in enumerate(self.mixers) if m == LIGHTNING)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @staticmethod
    def from_hf(hf: dict, **kw) -> "MiniCPMSalaConfig":
        """From the published ``config.json`` keys plus ``sparse_config``
        (a benchmark configuration file carries them under the same names).
        ``mixer_types`` may be longer than ``num_hidden_layers``: a cut in
        depth reads its head."""
        only = {"attention_bias": False, "attn_use_rope": False,
                "hidden_act": "silu", "lightning_use_rope": True,
                "lightning_scale": "1/sqrt(d)", "qk_norm": True,
                "tie_word_embeddings": False, "use_output_gate": True,
                "use_output_norm": True, "attn_use_output_gate": True}
        for key, want in only.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"minicpm_sala: {key}={hf[key]!r} is not implemented "
                    f"(only {want!r})")
        L, H, hd = hf["num_hidden_layers"], hf["num_attention_heads"], \
            hf["head_dim"]
        if (hf.get("lightning_nh", H), hf.get("lightning_nkv", H),
                hf.get("lightning_head_dim", hd)) != (H, H, hd):
            raise ValueError(
                "minicpm_sala: Lightning heads other than the attention's "
                "own count and size are not implemented")
        kinds = tuple(hf["mixer_types"][:L])
        if len(kinds) != L or set(kinds) - {SPARSE, LIGHTNING}:
            raise ValueError(f"minicpm_sala: mixer_types {kinds}")
        base = dict(
            vocab_size=hf["vocab_size"], n_layer=L, d_model=hf["hidden_size"],
            n_head=H, n_kv_head=hf["num_key_value_heads"], head_dim=hd,
            d_ff=hf["intermediate_size"], mixers=kinds,
            sparse=SparseConfig.from_hf(hf["sparse_config"]),
            rope_theta=float(hf["rope_theta"]),
            scale_emb=float(hf["scale_emb"]),
            scale_depth=float(hf["scale_depth"]),
            mup_denominator=int(hf["mup_denominator"]),
            dim_model_base=int(hf["dim_model_base"]),
            rms_eps=hf["rms_norm_eps"], n_ctx=hf["max_position_embeddings"])
        base.update(kw)
        return MiniCPMSalaConfig(**base)

    @staticmethod
    def tiny(**kw) -> "MiniCPMSalaConfig":
        """Sparse, Lightning, Lightning, sparse; pages of 2, blocks of 8,
        the 4 best of them past 64 positions with a local window of 2: the
        rule really drops blocks at a hundred positions."""
        base = dict(vocab_size=256, n_layer=4, d_model=64, n_head=4,
                    n_kv_head=2, head_dim=16, d_ff=128,
                    mixers=(SPARSE, LIGHTNING, LIGHTNING, SPARSE),
                    sparse=SparseConfig(4, 2, 8, 16, 4, 1, 64),
                    mup_denominator=4, dim_model_base=16, n_ctx=4096)
        base.update(kw)
        return MiniCPMSalaConfig(**base)

    @classmethod
    def named(cls, name: str, **kw) -> "MiniCPMSalaConfig":
        """A CLI model name: ``tiny``, or the path of a JSON file holding
        the published ``config.json`` keys and ``sparse_config``."""
        if name == "tiny":
            return cls.tiny(**kw)
        if name.endswith(".json"):
            with open(name) as f:
                return cls.from_hf(json.load(f), **kw)
        raise ValueError(
            f"unknown minicpm_sala model_name {name!r}: 'tiny' or the path "
            "of a config.json")


def minicpm_sala_init(key: jax.Array, cfg: MiniCPMSalaConfig) -> dict:
    """Seeded N(0, 0.02) weights in the program's tree (norm gains 1, the
    Lightning slopes :func:`lightning_slopes`, float32)."""
    d, dt, hd = cfg.d_model, cfg.param_dtype, cfg.head_dim
    ch, kv = cfg.n_head * hd, cfg.n_kv_head * hd
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layer))

    def w(*shape):
        return _normal(next(keys), shape, 0.02, dt)

    def gain(n):
        return {"scale": jnp.ones((n,), dt)}

    params: dict = {"wte": w(cfg.vocab_size, d),
                    "lm_head": w(d, cfg.vocab_size), "ln_f": gain(d),
                    "blocks": []}
    for kind in cfg.mixers:
        block = {"ln_attn": gain(d), "ln_mlp": gain(d),
                 "mlp": {"w_gate": w(d, cfg.d_ff), "w_up": w(d, cfg.d_ff),
                         "w_down": w(cfg.d_ff, d)}}
        if kind == SPARSE:
            block["attn"] = {"wq": w(d, ch), "wk": w(d, kv), "wv": w(d, kv),
                             "wz": w(d, ch), "wo": w(ch, d),
                             "q_norm": gain(hd), "k_norm": gain(hd)}
        else:
            block["lightning"] = {
                "wq": w(d, ch), "wk": w(d, ch), "wv": w(d, ch),
                "wz": w(d, ch), "wo": w(ch, d), "q_norm": gain(hd),
                "k_norm": gain(hd), "o_norm": gain(hd),
                "slope": lightning_slopes(cfg.n_head)}
        params["blocks"].append(block)
    return params


def _gated(out, u, wz):
    """``sigmoid(u W_z) * out``, full width, in ``u``'s dtype. out [B, S,
    H * hd] float32 or the compute dtype."""
    with jax.named_scope("attn/gate"):
        gate = jax.nn.sigmoid(_matmul(u, wz).astype(jnp.float32))
        return (out.astype(jnp.float32) * gate).astype(u.dtype)


def _heads(u, w, norm, n, hd, eps):
    """``u W`` as ``[B, S, n, hd]``, RMS-normed a head where ``norm``."""
    B, S, _ = u.shape
    x = _matmul(u, w).reshape(B, S, n, hd)
    return x if norm is None else _rms_norm(x, norm, eps)


def _sparse_block(u, p, cfg: MiniCPMSalaConfig, c, tables, pos, lengths,
                  valid):
    """One ``minicpm4`` layer over its pages and compressed keys. Returns
    (output ``[B, S, d]``, the layer's updated ``{"k", "v", "ck"}``, its
    counters)."""
    from distributed_lion_tpu.ops.attention import paged_scatter_kv

    B, S, _ = u.shape
    H, KV, hd, sp = cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.sparse
    bs = c["k"].shape[1]
    with jax.named_scope("attn/qkv"):
        q = _heads(u, p["wq"], p["q_norm"], H, hd, cfg.rms_eps)
        k = _heads(u, p["wk"], p["k_norm"], KV, hd, cfg.rms_eps)
        v = _heads(u, p["wv"], None, KV, hd, cfg.rms_eps)
    k_new, v_new = k.astype(c["k"].dtype), v.astype(c["v"].dtype)
    k_pages = paged_scatter_kv(c["k"], tables, pos, k_new, valid)
    v_pages = paged_scatter_kv(c["v"], tables, pos, v_new, valid)
    zero = jnp.int32(0)
    count = dict.fromkeys(SALA_COUNTERS, zero)
    if S > 1:      # a prefill from position 0: its own fresh keys
        rows, whole = prefill_compressed(k_new, lengths, sp)
        ck = scatter_compressed(c["ck"], tables, rows, whole)
        out = sparse_prefill_attention(
            q.transpose(0, 2, 1, 3), k_new.transpose(0, 2, 1, 3),
            v_new.transpose(0, 2, 1, 3), rows, sp).transpose(0, 2, 1, 3)
        count["ck_rows_written"] = whole.sum().astype(jnp.int32)
    else:
        live = lengths > 0
        ck, closed = decode_compressed(c["ck"], k_pages, tables, pos, live,
                                       sp)
        lists, held, sparse = decode_page_lists(
            q[:, 0], ck, tables, pos, live, sp, KV, bs)
        # one program holds rows on both sides of dense_len; the kernel
        # inside either region reads ``paged_attn``
        with jax.named_scope("sparse_attn"):
            out = sparse_decode_attention(
                q[:, 0], k_pages, v_pages, lists, held, KV,
                sp.block_size // bs)[:, None]
        count.update(
            kv_pages_selected=((held + bs - 1) // bs).sum().astype(jnp.int32),
            kv_copies=2 * (-(-held // sp.block_size)).sum().astype(jnp.int32),
            sparse_rows=(live & sparse).sum().astype(jnp.int32),
            dense_rows=(live & ~sparse).sum().astype(jnp.int32),
            ck_rows_written=closed.sum().astype(jnp.int32))
    out = _gated(out.reshape(B, S, H * hd), u, p["wz"])
    return _matmul(out, p["wo"]), {"k": k_pages, "v": v_pages, "ck": ck}, \
        count


def _lightning_block(u, p, cfg: MiniCPMSalaConfig, c, slots, lengths, lanes,
                     cos, sin):
    """One Lightning layer over its slot-indexed state. Returns (output
    ``[B, S, d]``, the layer's updated ``{"state"}``)."""
    f32 = jnp.float32
    B, S, _ = u.shape
    H, hd = cfg.n_head, cfg.head_dim
    with jax.named_scope("attn/qkv"):
        q = _heads(u, p["wq"], p["q_norm"], H, hd, cfg.rms_eps)
        k = _heads(u, p["wk"], p["k_norm"], H, hd, cfg.rms_eps)
        v = _heads(u, p["wv"], None, H, hd, cfg.rms_eps)
    with jax.named_scope("attn/rope"):
        q = apply_rope_half(q.transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope_half(k.transpose(0, 2, 1, 3), cos, sin)
        q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    slope = p["slope"].astype(f32)
    if S > 1:      # from position 0, whatever the slot held
        o, last = lightning_chunked(q, k, v, slope, lengths,
                                    jnp.zeros((B, H, hd, hd), f32))
        state = c["state"].at[slots].set(last)
    else:
        o, state = lightning_step(c["state"], q[:, 0], k[:, 0], v[:, 0],
                                  jnp.exp(-slope), lanes[:, 0])
        o = o[:, None]
    with jax.named_scope("lightning/out_norm"):
        o = _rms_norm(o * hd ** -0.5, p["o_norm"], cfg.rms_eps)  # float32
    out = _gated(o.reshape(B, S, H * hd), u, p["wz"])
    return _matmul(out, p["wo"]), {"state": state}


def minicpm_sala_decode_paged(params: dict, tokens: jnp.ndarray,
                              cfg: MiniCPMSalaConfig, pages: list,
                              tables: jnp.ndarray, slots: jnp.ndarray,
                              pos: jnp.ndarray, valid=None,
                              return_moe_stats: bool = False,
                              logit_index=None):
    """Block-table decode (the serving engine's model hook, as
    ``ling_decode_paged``): row b's ``tokens`` [B, S] sit at positions
    ``pos[b] .. pos[b]+S-1``; ``pages`` is the per-layer cache list, a
    ``minicpm4`` layer's ``{"k", "v", "ck"}`` under ``tables`` [B, nb] and a
    Lightning layer's ``{"state"}`` a slot (the module note). S = 1 is the
    decode tick, row b being slot b; S > 1 a prefill from position 0 into
    slot ``slots[b]``. Returns (logits float32, updated pages[, counters]):
    logits ``[B, S, vocab]``, or ``[B, 1, vocab]`` of position
    ``logit_index`` when given. ``return_moe_stats``: ``SALA_COUNTERS``,
    summed over the ``minicpm4`` layers."""
    B, S = tokens.shape
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
        x = (x * cfg.scale_emb).astype(cfg.compute_dtype)
    lanes = jnp.ones((B, S), bool) if valid is None \
        else jnp.broadcast_to(valid, (B, S))
    lengths = lanes.sum(1).astype(jnp.int32)
    cos, sin = Rope(cfg.rope_theta, cfg.head_dim).angles(
        pos[:, None] + jnp.arange(S)[None, :])
    c_res = cfg.residual_scale
    counters = dict.fromkeys(SALA_COUNTERS, jnp.int32(0))
    new_pages = []
    for p, c in zip(params["blocks"], pages):
        u = _rms_norm(x, p["ln_attn"], cfg.rms_eps)
        if "attn" in p:
            a, c, count = _sparse_block(u, p["attn"], cfg, c, tables, pos,
                                        lengths, valid)
            counters = {n: counters[n] + count[n] for n in counters}
        else:
            a, c = _lightning_block(u, p["lightning"], cfg, c, slots,
                                    lengths, lanes, cos, sin)
        new_pages.append(c)
        x = x + (a * c_res).astype(x.dtype)
        h = _rms_norm(x, p["ln_mlp"], cfg.rms_eps)
        x = x + (_mlp(h, p["mlp"]) * c_res).astype(x.dtype)
    x = _rms_norm(x, params["ln_f"], cfg.rms_eps)
    if logit_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    x = (x / (cfg.d_model / cfg.dim_model_base)).astype(x.dtype)
    logits = _head_logits(x, params)
    return (logits, new_pages, counters) if return_moe_stats \
        else (logits, new_pages)
