"""GPT-2-class decoder transformer in pure JAX.

The reference's pretraining workload is GPT-2 124M from HF hub
(/root/reference/run_clm.py:425-444, README.md:21-23); here the model is our
own implementation — pre-LN residual decoder with learned positional
embeddings, GELU MLP, tied input/output embedding — designed for the MXU:

- all matmuls batched and expressed as einsums XLA tiles onto the systolic
  array; compute in bf16 with f32 accumulation (``preferred_element_type``);
- static shapes everywhere (fixed block size, as the reference's fixed-block
  ``group_texts`` packing guarantees, run_clm.py:509-522);
- params as a plain nested dict pytree → optimizer/sharding/checkpoint code
  stays generic.

124M default config matches GPT-2 small: vocab 50257, 12 layers, 12 heads,
d_model 768, context 1024.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from distributed_lion_tpu.ops.attention import attention_qkv
from distributed_lion_tpu.parallel.tensor_parallel import (
    copy_to_tp_region,
    reduce_from_tp_region,
)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    n_ctx: int = 1024
    dropout: float = 0.0
    attn_impl: str = "auto"  # ops.attention: auto | xla
    seq_impl: str = "ring"   # sequence-parallel attention: 'ring' (k/v
    # blocks rotate over the seq axis — O(T/S) memory, any head count) or
    # 'ulysses' (all_to_all to head sharding — needs n_head % sp == 0,
    # two collective hops but full-T local attention)
    remat: bool = True  # rematerialize blocks (HBM for FLOPs); turn off when
                        # activations fit — backward skips the fwd recompute
    remat_policy: str = "full"  # what the per-block checkpoint SAVES:
    # 'full' (nothing — recompute everything), 'dots' (keep matmul outputs,
    # recompute elementwise/softmax — the usual best trade on TPU: matmuls
    # are the expensive recompute, elementwise is free next to HBM).
    # A config handed to gpt2_apply means what it says. 'auto' is a
    # request to the TRAINER (the default of run_clm's model arguments):
    # train/loop.apply_remat_policy resolves it once, from the shapes and
    # the device's memory, to remat=False | 'dots' | 'full'; explicit
    # values win, MoE / pipeline / sequence-parallel / CPU runs stay
    # 'full', and a model function that still sees 'auto' raises.
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    moe_experts: int = 0  # > 0: Switch-MoE FFN (parallel/expert.py) replaces
                          # the dense MLP in every ``moe_every``-th block;
                          # net-new vs the reference (data-parallel only)
    moe_every: int = 2    # MoE in blocks with index % moe_every == moe_every-1
    moe_capacity_factor: float = 1.25
    vocab_pad_multiple: int = 0  # > 0: round the EMBEDDING TABLE rows up to
    # a multiple (wte becomes [padded_vocab, d]) so the tied-head matmul and
    # the chunked-CE slices land on MXU-aligned tile boundaries — GPT-2's
    # 50257 is ragged (Llama vocabs are already 128-multiples). A pure
    # LAYOUT choice, not a semantics change: logits are sliced back to
    # vocab_size in gpt2_apply and the chunked loss masks the pad columns,
    # so loss/generation are exact and the pad rows get zero loss gradient.
    # (Under vote-Lion the tie→−1 rule still walks zero-gradient pad rows;
    # they stay out of every consumer and hf_export slices them off.)

    def __post_init__(self):
        if self.moe_experts > 0 and self.moe_every < 1:
            raise ValueError(
                f"moe_every must be >= 1 when moe_experts is set, got "
                f"{self.moe_every}"
            )
        if self.vocab_pad_multiple < 0:
            raise ValueError(
                f"vocab_pad_multiple must be >= 0, got {self.vocab_pad_multiple}"
            )

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows: ``vocab_size`` rounded up to
        ``vocab_pad_multiple`` (== ``vocab_size`` when padding is off)."""
        m = self.vocab_pad_multiple
        if m <= 0:
            return self.vocab_size
        return -(-self.vocab_size // m) * m

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        """A test-sized config (for unit tests and the dryrun path)."""
        base = dict(vocab_size=256, n_layer=2, n_head=4, d_model=64, n_ctx=128)
        base.update(kw)
        return GPT2Config(**base)

    @staticmethod
    def small(**kw) -> "GPT2Config":
        """The reduced evidence-scale preset (~12.7M params at a 16k
        vocab): the smallest architecture the ≥10M auto comm defaults
        apply to — shared by the reduced CPU parity legs
        (scripts/loss_parity.py --reduced) and the reduced convergence
        run, so the two reduced CPU legs evidence the same model."""
        base = dict(vocab_size=16384, n_layer=6, n_head=5, d_model=320,
                    n_ctx=256)
        base.update(kw)
        return GPT2Config(**base)

    @staticmethod
    def gpt2_124m(**kw) -> "GPT2Config":
        return GPT2Config(**kw)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape) * std).astype(dtype)


def pad_wte(wte: jnp.ndarray, cfg: "GPT2Config") -> jnp.ndarray:
    """Append the zero MXU-alignment rows of ``cfg.vocab_pad_multiple`` to a
    true-vocab embedding table (no-op when padding is off). The single
    source of the pad layout — used by :func:`gpt2_init` and by CLI
    checkpoint import, so fresh inits and imported tables can't drift."""
    extra = cfg.padded_vocab - wte.shape[0]
    if extra <= 0:
        return wte
    return jnp.concatenate(
        [wte, jnp.zeros((extra, wte.shape[1]), wte.dtype)]
    )


def is_moe_block(cfg: GPT2Config, i: int) -> bool:
    return cfg.moe_experts > 0 and i % cfg.moe_every == cfg.moe_every - 1


def gpt2_init(key: jax.Array, cfg: GPT2Config) -> dict:
    """Initialize parameters (GPT-2 init: N(0, 0.02), residual projections
    scaled by 1/sqrt(2*n_layer) as in the original OpenAI scheme). With
    ``cfg.moe_experts``, every ``moe_every``-th block carries a Switch-MoE
    FFN (``"moe"`` entry, parallel/expert.moe_init) instead of the dense
    ``"mlp"``."""
    d, dt = cfg.d_model, cfg.param_dtype
    std = 0.02
    resid_std = std / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 4 + 7 * cfg.n_layer))

    # pad rows are ZEROS appended after the draw, so the true-vocab rows are
    # bit-identical to the unpadded init under the same key (pinned by
    # tests/test_vocab_pad.py) and exports can slice the pad back off
    params: dict = {
        "wte": pad_wte(_normal(next(keys), (cfg.vocab_size, d), std, dt), cfg),
        "wpe": _normal(next(keys), (cfg.n_ctx, d), std, dt),
        "ln_f": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        "blocks": [],
    }
    for i in range(cfg.n_layer):
        block = {
            "ln_1": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
            "attn": {
                # [d, 3, d]: q/k/v stacked on axis 1 so tensor parallelism
                # shards the last (head) dim without cutting across q|k|v
                "qkv": _normal(next(keys), (d, 3, d), std, dt),
                "qkv_b": jnp.zeros((3, d), dt),
                "proj": _normal(next(keys), (d, d), resid_std, dt),
                "proj_b": jnp.zeros((d,), dt),
            },
            "ln_2": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        }
        if is_moe_block(cfg, i):
            from distributed_lion_tpu.parallel.expert import moe_init

            block["moe"] = moe_init(next(keys), cfg.moe_experts, d, 4 * d, dt)
        else:
            block["mlp"] = {
                "fc": _normal(next(keys), (d, 4 * d), std, dt),
                "fc_b": jnp.zeros((4 * d,), dt),
                "proj": _normal(next(keys), (4 * d, d), resid_std, dt),
                "proj_b": jnp.zeros((d,), dt),
            }
        params["blocks"].append(block)
    return params


def _layer_norm(x, p, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _dropout(x, rate, key):
    if rate == 0.0 or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _qkv_project(x, w, flat: bool = False):
    """[B,T,d] @ [d,3,d] stacked qkv — dense or LoRA-adapted (factored).
    ``flat`` gives ``[B, T, 3 * d]`` (a token's q, k, v side by side) and
    contracts against the weight as ``[d, 3 * d]``: the training path's
    form. Born 3-D, the activation is row-major, which is how the
    token-major attention kernel reads it; born ``[B, T, 3, d]`` the TPU
    compiler lays it out token-minor and re-lays 94 MB a layer on each side
    of the kernel (tests/test_chip_compile.py pins that no such copy
    exists). The serving programs keep the 4-D contraction: re-laying a
    bf16 weight ``[d, 3, d]`` out as ``[d, 3 * d]`` is free beside the
    trainer's float32 -> bf16 convert and costs GPT-2 XL's decode tick
    0.9 ms where the weights are the only thing it reads (my chip run,
    PR 27)."""
    from distributed_lion_tpu.models.lora import LoraTensor
    from distributed_lion_tpu.ops.quant import maybe_dequant

    if isinstance(w, LoraTensor):
        base = jnp.einsum("btd,dce->btce", x,
                          maybe_dequant(w.base, x.dtype).astype(x.dtype),
                          preferred_element_type=jnp.float32).astype(x.dtype)
        xa = x @ w.A.astype(x.dtype)
        delta = jnp.einsum("btr,rce->btce", xa, w.B.astype(x.dtype),
                           preferred_element_type=jnp.float32).astype(x.dtype)
        out = base + w.scaling * delta
        return out.reshape(x.shape[:2] + (-1,)) if flat else out
    # maybe_dequant: NF4/int8 frozen-weight serving (ops/quant) — a
    # QuantizedTensor in the qkv slot dequantizes into the matmul's
    # producer fusion; dense weights pass through untouched
    w = maybe_dequant(w, x.dtype).astype(x.dtype)
    if flat:
        return jnp.einsum("btd,dn->btn", x, w.reshape(w.shape[0], -1),
                          preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.einsum("btd,dce->btce", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


@jax.named_scope("attn")
def _attention(x, p, cfg: GPT2Config, key, tp_axis=None, seq_axis=None):
    """Causal multi-head attention; f32 softmax for stability.

    With ``tp_axis`` (Megatron tensor parallelism): qkv is column-parallel
    (this device holds H/tp heads), proj is row-parallel (partial sums are
    psum-reduced over the tensor axis; bias added after the reduction).
    With ``seq_axis`` (sequence/context parallelism): x holds this device's
    contiguous token chunk and attention runs as ring attention — (k, v)
    blocks rotate over the seq axis (parallel.ring_attention).
    """
    B, T, D = x.shape
    tp = 1 if tp_axis is None else jax.lax.psum(1, tp_axis)
    if tp_axis is not None:
        # Megatron f: identity fwd, psum bwd — dx re-assembled across tensor
        # ranks so upstream (LN/embedding) grads are complete, not partials
        x = copy_to_tp_region(x, tp_axis)
    H, hd = cfg.n_head // tp, cfg.head_dim
    qkv = (_qkv_project(x, p["qkv"], flat=True)
           + p["qkv_b"].reshape(-1).astype(x.dtype))          # [B, T, 3 H hd]
    attn_dropout = cfg.dropout > 0.0 and key is not None and seq_axis is None
    if not attn_dropout and seq_axis is None:
        # the projection's output as it lies: on a TPU `auto` hands it to
        # the token-major kernel (ops/pallas_flash_attn) and gets [B, T, D]
        # back; no head-major copy exists on that path
        out = attention_qkv(qkv, H, impl=cfg.attn_impl)
    else:
        q, k, v = (x.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
                   for x in jnp.split(qkv, 3, axis=2))
        if attn_dropout:
            # attention-prob dropout needs materialized scores; training
            # with dropout keeps the XLA path. Under sequence parallelism
            # the scores never exist in one place, so attention-prob
            # dropout is skipped (residual/embedding dropout still applies).
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores / math.sqrt(hd)
            causal = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(causal, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            probs = _dropout(probs, cfg.dropout, key)
            out = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                             preferred_element_type=jnp.float32)
            out = out.astype(x.dtype)
        else:
            from distributed_lion_tpu.parallel.ring_attention import (
                ring_attention,
                ulysses_attention,
            )

            seq_attn = (ulysses_attention if cfg.seq_impl == "ulysses"
                        else ring_attention)
            out = seq_attn(q, k, v, axis_name=seq_axis)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    out = _proj(out, p["proj"])
    if tp_axis is not None:
        out = reduce_from_tp_region(out, tp_axis)  # row-parallel exit (g op)
    return out + p["proj_b"].astype(x.dtype)


def _proj(x, w):
    """2-D projection through the dense/quant/LoRA dispatch."""
    from distributed_lion_tpu.models.lora import lora_matmul

    return lora_matmul(x, w)


@jax.named_scope("mlp")
def _mlp(x, p, tp_axis=None):
    if tp_axis is not None:
        x = copy_to_tp_region(x, tp_axis)
    h = _proj(x, p["fc"]) + p["fc_b"].astype(x.dtype)
    h = jax.nn.gelu(h, approximate=True)
    out = _proj(h, p["proj"])
    if tp_axis is not None:
        out = reduce_from_tp_region(out, tp_axis)
    return out + p["proj_b"].astype(x.dtype)


def _block(x, p, key, cfg: GPT2Config, tp_axis=None, seq_axis=None):
    """One pre-LN transformer block. When ``cfg.remat`` the block is wrapped
    in ``jax.checkpoint`` so activations are recomputed in backward — HBM for
    FLOPs, the standard TPU trade for big models/long context; small models
    whose activations fit HBM set ``remat=False`` and skip the ~⅓ extra
    forward FLOPs in backward."""
    k1, k2, k3 = (None, None, None) if key is None else jax.random.split(key, 3)
    x = x + _dropout(
        _attention(_layer_norm(x, p["ln_1"]), p["attn"], cfg, k1, tp_axis, seq_axis),
        cfg.dropout, k2,
    )
    x = x + _dropout(_mlp(_layer_norm(x, p["ln_2"]), p["mlp"], tp_axis), cfg.dropout, k3)
    return x


def _remat_policy(name: str):
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "full":
        return None  # save nothing: recompute the whole block in backward
    if name == "auto":
        raise ValueError(
            "remat_policy 'auto' is the trainer's to resolve "
            "(train/loop.apply_remat_policy, at Trainer build); a model "
            "function takes full | dots, or remat=False")
    raise ValueError(f"unknown remat_policy {name!r} (full | dots)")


def _block_remat_for(cfg):
    return partial(jax.checkpoint, static_argnums=(3, 4, 5),
                   policy=_remat_policy(cfg.remat_policy))(_block)


def _moe_block(x, p, key, cfg: GPT2Config, expert_axis=None, tp_axis=None,
               balance_tokens=None, return_tallies=False,
               balance_axis=None):
    """Pre-LN block whose FFN is the Switch-MoE layer: tokens flattened to
    [B*T, D], routed/dispatched by parallel/expert.moe_ffn (two all_to_all
    hops when ``expert_axis`` is bound), combined back. ``tp_axis`` runs
    the attention half column/row-parallel and Megatron-splits each
    expert's FFN (ep × tp). Returns ``(x, aux_loss)`` — the load-balance
    auxiliary to add to the train loss. ``balance_tokens`` ([E+1] f32,
    optional) substitutes a fed-in (global / ring-stale) token-load tally
    for the local one in the aux (the ``--ep_dcn_pipeline`` wire, see
    parallel/expert.moe_ffn); ``return_tallies`` additionally returns
    this block's fresh local tally; ``balance_axis`` is the synchronous
    depth-0 alternative (psum the tallies in the forward)."""
    from distributed_lion_tpu.parallel.expert import moe_ffn

    k1, k2, k3 = (None, None, None) if key is None else jax.random.split(key, 3)
    x = x + _dropout(
        _attention(_layer_norm(x, p["ln_1"]), p["attn"], cfg, k1, tp_axis, None),
        cfg.dropout, k2,
    )
    B, T, D = x.shape
    h = _layer_norm(x, p["ln_2"]).reshape(B * T, D)
    with jax.named_scope("mlp"):
        out = moe_ffn(p["moe"], h, capacity_factor=cfg.moe_capacity_factor,
                      axis_name=expert_axis, tp_axis=tp_axis,
                      balance_tokens=balance_tokens,
                      balance_axis=balance_axis,
                      return_tallies=return_tallies)
    if return_tallies:
        y, aux, tally = out
    else:
        (y, aux), tally = out, None
    x = x + _dropout(y.reshape(B, T, D), cfg.dropout, k3)
    if return_tallies:
        return x, aux, tally
    return x, aux


def _moe_block_remat_for(cfg):
    # balance_tokens (argnum 6) is a traced array; return_tallies (7) and
    # balance_axis (8) are static python values like the axis names
    return partial(jax.checkpoint, static_argnums=(3, 4, 5, 7, 8),
                   policy=_remat_policy(cfg.remat_policy))(_moe_block)


@jax.named_scope("embed")
def vocab_parallel_embed(wte_shard: jnp.ndarray, tokens: jnp.ndarray,
                         vocab_axis: str, out_dtype=None) -> jnp.ndarray:
    """Megatron VocabParallelEmbedding: ``wte_shard`` [V/tp, d] is this
    rank's contiguous vocab-row slice; out-of-range tokens contribute zero
    and the partial embeddings reduce over the tensor axis (the *g*
    operator — exact identity backward). Pairs with the vocab-parallel tied
    head (ops/xent.tp_vocab_xent on ``wte_shard.T``) so the full [V, d]
    table never exists on one device. ``out_dtype`` casts BEFORE the
    collective: exactly one rank contributes a nonzero row per token, so
    reducing in the (usually narrower) compute dtype is bit-identical at
    half the wire bytes."""
    vshard = wte_shard.shape[0]
    start = lax.axis_index(vocab_axis) * vshard
    in_range = (tokens >= start) & (tokens < start + vshard)
    idx = jnp.clip(tokens - start, 0, vshard - 1)
    part = wte_shard[idx] * in_range[..., None].astype(wte_shard.dtype)
    if out_dtype is not None:
        part = part.astype(out_dtype)
    return reduce_from_tp_region(part, vocab_axis)


def gpt2_hidden(
    params: dict,
    tokens: jnp.ndarray,
    cfg: GPT2Config,
    *,
    dropout_key: Optional[jax.Array] = None,
    tp_axis: Optional[str] = None,
    seq_axis: Optional[str] = None,
    expert_axis: Optional[str] = None,
    vocab_axis: Optional[str] = None,
    moe_balance: Optional[jnp.ndarray] = None,
    moe_balance_axis: Optional[str] = None,
    return_moe_tallies: bool = False,
) -> tuple:
    """Backbone forward: tokens [B, T] → (final hidden [B, T, d] after ln_f,
    MoE aux loss scalar). The tied-logits head is applied by
    :func:`gpt2_apply`, or streamed chunk-wise by ops/xent for the
    memory-lean loss path. With ``vocab_axis``, ``params["wte"]`` is this
    rank's vocab-row shard (:func:`vocab_parallel_embed`).

    ``moe_balance`` ([n_moe_blocks, E+1] f32, optional) feeds each MoE
    block's aux loss a substituted token-load tally — PER BLOCK, so a
    size-1 psum of the fresh tallies reproduces the unfed aux bit-for-bit
    (the ``--ep_dcn_pipeline`` depth-0 pin, train/loop.py).
    ``moe_balance_axis`` is the synchronous depth-0 form: each MoE block
    psums its fresh tallies over that axis inside the forward.
    ``return_moe_tallies`` appends a third output: the stacked fresh local
    tallies [n_moe_blocks, E+1] (stop-gradient)."""
    B, T = tokens.shape
    if seq_axis is None:
        if T > cfg.n_ctx:
            raise ValueError(f"sequence length {T} exceeds n_ctx {cfg.n_ctx}")
        pos_start = 0
    else:
        sidx = lax.axis_index(seq_axis)
        pos_start = sidx * T
        if dropout_key is not None:
            dropout_key = jax.random.fold_in(dropout_key, sidx)
    with jax.named_scope("embed"):
        if vocab_axis is not None:
            x = vocab_parallel_embed(params["wte"], tokens, vocab_axis,
                                     out_dtype=cfg.compute_dtype)
        else:
            x = params["wte"][tokens]
        x = x.astype(cfg.compute_dtype)
        x = x + lax.dynamic_slice_in_dim(
            params["wpe"], pos_start, T, axis=0).astype(cfg.compute_dtype)
    keys = (
        [None] * (cfg.n_layer + 1)
        if dropout_key is None
        else list(jax.random.split(dropout_key, cfg.n_layer + 1))
    )
    x = _dropout(x, cfg.dropout, keys[-1])
    block = _block_remat_for(cfg) if cfg.remat else _block
    moe_block = _moe_block_remat_for(cfg) if cfg.remat else _moe_block
    aux_total = jnp.float32(0)
    tallies = []
    moe_i = 0
    for p, k in zip(params["blocks"], keys[: cfg.n_layer]):
        if "moe" in p:  # static pytree-structure branch, resolved at trace
            bt = None if moe_balance is None else moe_balance[moe_i]
            out = moe_block(x, p, k, cfg, expert_axis, tp_axis, bt,
                            return_moe_tallies, moe_balance_axis)
            if return_moe_tallies:
                x, aux, tally = out
                tallies.append(tally)
            else:
                x, aux = out
            aux_total = aux_total + aux
            moe_i += 1
        else:
            x = block(x, p, k, cfg, tp_axis, seq_axis)
    hidden = _layer_norm(x, params["ln_f"])
    if return_moe_tallies:
        stacked = (jnp.stack(tallies) if tallies
                   else jnp.zeros((0, 1), jnp.float32))
        return hidden, aux_total, stacked
    return hidden, aux_total


def gpt2_apply(
    params: dict,
    tokens: jnp.ndarray,
    cfg: GPT2Config,
    *,
    dropout_key: Optional[jax.Array] = None,
    tp_axis: Optional[str] = None,
    seq_axis: Optional[str] = None,
    expert_axis: Optional[str] = None,
    return_aux: bool = False,
    moe_balance: Optional[jnp.ndarray] = None,
    moe_balance_axis: Optional[str] = None,
    return_moe_tallies: bool = False,
) -> jnp.ndarray:
    """Forward pass: int32 tokens [B, T] → logits [B, T, vocab] (f32).

    Output projection is tied to the input embedding (GPT-2 weight tying).
    With ``tp_axis`` (inside shard_map), attention/MLP weights are expected
    pre-sharded per ``parallel.tensor_parallel.gpt2_param_specs``. With
    ``seq_axis`` (sequence parallelism), ``tokens`` is this device's
    contiguous chunk of the full sequence: positions offset by the shard
    index, attention rings over the axis, per-shard dropout keys.
    """
    out = gpt2_hidden(
        params, tokens, cfg, dropout_key=dropout_key, tp_axis=tp_axis,
        seq_axis=seq_axis, expert_axis=expert_axis,
        moe_balance=moe_balance, moe_balance_axis=moe_balance_axis,
        return_moe_tallies=return_moe_tallies,
    )
    x, aux_total = out[0], out[1]
    with jax.named_scope("head"):
        logits = jnp.einsum(
            "btd,vd->btv", x, params["wte"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
    # padded-vocab layout: the matmul ran MXU-aligned over padded_vocab
    # columns; slicing back to vocab_size here keeps every downstream
    # consumer (losses, generation, eval) on exact true-vocab semantics
    logits = logits[..., : cfg.vocab_size]
    if return_moe_tallies:
        if return_aux:
            return logits, aux_total, out[2]
        return logits, out[2]
    if return_aux:
        return logits, aux_total
    return logits


def count_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


def gpt2_moe_param_specs(cfg: GPT2Config, tensor: bool = False) -> dict:
    """PartitionSpec tree for a MoE config: expert FFN banks sharded over the
    'expert' mesh axis (parallel/expert.moe_param_specs); everything else
    replicated. Valid for ep == 1 too (a P('expert') dim over a size-1 axis
    is replication). ``tensor=True`` (ep × tp) additionally applies the
    Megatron split to attention, the dense MLP blocks, and each expert's
    FFN (the same layouts as gpt2_param_specs / moe_param_specs(tensor))."""
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.parallel.expert import moe_param_specs

    rep = P()
    ln = {"scale": rep, "bias": rep}
    if tensor:
        # ONE source of truth for the Megatron attn/mlp layouts: reuse the
        # dense-TP spec tree rather than hand-copying it (a layout change
        # there must not silently diverge the MoE-TP sharding)
        from distributed_lion_tpu.parallel.tensor_parallel import (
            gpt2_param_specs,
        )

        dense_block = gpt2_param_specs(cfg)["blocks"][0]
        att, mlp = dense_block["attn"], dense_block["mlp"]
    else:
        att = {k: rep for k in ("qkv", "qkv_b", "proj", "proj_b")}
        mlp = {k: rep for k in ("fc", "fc_b", "proj", "proj_b")}
    blocks = []
    for i in range(cfg.n_layer):
        block = {"ln_1": ln, "attn": att, "ln_2": ln}
        if is_moe_block(cfg, i):
            block["moe"] = moe_param_specs(tensor=tensor)
        else:
            block["mlp"] = mlp
        blocks.append(block)
    return {"wte": rep, "wpe": rep, "ln_f": ln, "blocks": blocks}


# ------------------------------------------------------------------ decoding
def gpt2_init_cache(cfg: GPT2Config, batch: int, max_len: int) -> list:
    """Per-layer KV cache [B, H, max_len, hd] (static shape: decode writes
    into a fixed-size buffer with a position index — no dynamic shapes under
    jit). Net-new vs the reference, which has no inference path at all."""
    shape = (batch, cfg.n_head, max_len, cfg.head_dim)
    return [
        {"k": jnp.zeros(shape, cfg.compute_dtype), "v": jnp.zeros(shape, cfg.compute_dtype)}
        for _ in range(cfg.n_layer)
    ]


@jax.named_scope("attn")
def _decode_attention(x, p, cfg: GPT2Config, c, pos, offset=None):
    """Cache-aware attention for S new tokens at absolute position ``pos``:
    project qkv for the new tokens, write k/v into the cache, attend q over
    the whole (masked) cache. ``offset`` (optional [B] int32) is the
    per-row count of left-pad slots in a batched, variable-length prompt
    (cli/run_generate's multi-prompt mode): slots below it are masked out
    of every row's attention, so the pad prefix never leaks into scores."""
    B, S, _ = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    qkv = _qkv_project(x, p["qkv"]) + p["qkv_b"].astype(x.dtype)
    q, k, v = (qkv[:, :, i].reshape(B, S, H, hd).transpose(0, 2, 1, 3) for i in range(3))
    k_cache = lax.dynamic_update_slice_in_dim(c["k"], k.astype(c["k"].dtype), pos, axis=2)
    v_cache = lax.dynamic_update_slice_in_dim(c["v"], v.astype(c["v"].dtype), pos, axis=2)
    T = k_cache.shape[2]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = jnp.arange(T)[None, :] <= (pos + jnp.arange(S))[:, None]  # causal + unwritten
    if offset is None:
        scores = jnp.where(valid[None, None], scores, -1e30)
    else:
        row_valid = valid[None] & (jnp.arange(T)[None, None, :]
                                   >= offset[:, None, None])
        scores = jnp.where(row_valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v_cache,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = _proj(out, p["proj"]) + p["proj_b"].astype(x.dtype)
    return out, {"k": k_cache, "v": v_cache}


@jax.named_scope("mlp")
def _decode_mlp(x, p, cfg: GPT2Config, tp_axis=None, valid=None,
                ep_axis=None, moe_stats=None, stats_axis=None,
                stats_lanes=None):
    """The post-attention half of a decode block (dense MLP or the MoE
    FFN with decode-friendly capacity) — shared by the dense-cache and
    paged decode paths so their numerics cannot drift. ``tp_axis`` runs
    the dense MLP (and, with ``ep_axis``×tp, each expert's FFN)
    Megatron-split — the TP serving engine's path.

    MoE at inference is NO-DROP: ``capacity_override = B*S`` for every
    decode-path call (single-token ticks AND prefill/verify windows), so
    routing is an exact per-token function — no batchmate, padding bucket
    or speculation window can displace another token's expert slot. That
    is what makes paged==dense, batched==solo and speculative==plain hold
    bit-for-bit for MoE (training keeps the Switch capacity bound; the
    inference trade is a [E, B*S, D] dispatch buffer — bounded by the
    page-geometry bucket, ephemeral, and tiny next to the KV pages).
    ``valid`` ([B, S] bool) masks pad/sentinel lanes out of routing
    (parallel/expert.moe_ffn) so dead lanes consume zero expert capacity;
    ``ep_axis`` shards the expert banks over the serving mesh's expert
    axis (two all_to_all hops); ``moe_stats`` (a list) collects this
    block's routing-load scalars when the engine benchmarks capacity
    utilization; ``stats_axis`` (batch-sharded ep serving, ISSUE 16)
    psums the routing-load counters over the expert axis so the stats
    stay GLOBAL when each shard routes only its batch slice, and
    ``stats_lanes`` (static) overrides the budget's lane count for
    dispatches whose non-owner shards carry fake all-invalid lanes (the
    batch-sharded batch-1 prefill)."""
    if "moe" in p:
        from distributed_lion_tpu.parallel.expert import moe_ffn

        B2, S2, D2 = x.shape
        h = _layer_norm(x, p["ln_2"]).reshape(B2 * S2, D2)
        v = None if valid is None else valid.reshape(B2 * S2)
        out = moe_ffn(p["moe"], h, capacity_factor=cfg.moe_capacity_factor,
                      axis_name=ep_axis, capacity_override=B2 * S2,
                      tp_axis=tp_axis, valid=v,
                      return_stats=moe_stats is not None,
                      stats_axis=stats_axis, stats_lanes=stats_lanes)
        if moe_stats is not None:
            y, _, st = out
            moe_stats.append(st)
        else:
            y, _ = out
        return x + y.reshape(B2, S2, D2)
    return x + _mlp(_layer_norm(x, p["ln_2"]), p["mlp"], tp_axis)


@jax.named_scope("embed")
def _decode_embed(params, tokens, cfg: GPT2Config, pos, offset):
    """Token + position embeddings for a decode chunk. Scalar ``pos``
    slices wpe uniformly; with per-row ``offset`` (left-padded batch) each
    row gathers its own shifted position ids (clipped at 0 — pad slots
    reuse position 0, masked out of attention anyway). Both lookups route
    through lora_embed/maybe_dequant so NF4-quantized tables serve."""
    from distributed_lion_tpu.models.lora import lora_embed
    from distributed_lion_tpu.ops.quant import maybe_dequant

    B, S = tokens.shape
    x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
    if offset is None:
        wpe = maybe_dequant(params["wpe"], cfg.compute_dtype)
        return x + lax.dynamic_slice_in_dim(wpe, pos, S, axis=0).astype(
            cfg.compute_dtype)
    pos_ids = jnp.clip(pos + jnp.arange(S)[None, :] - offset[:, None],
                       0, cfg.n_ctx - 1)
    return x + lora_embed(params["wpe"], pos_ids, cfg.compute_dtype)


@jax.named_scope("head")
def _tied_logits(x, params, cfg: GPT2Config):
    from distributed_lion_tpu.ops.quant import maybe_dequant

    logits = jnp.einsum("btd,vd->btv", x,
                        maybe_dequant(params["wte"], x.dtype).astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits[..., : cfg.vocab_size]


def gpt2_decode(params: dict, tokens: jnp.ndarray, cfg: GPT2Config, cache: list,
                pos, offset=None):
    """Incremental forward: ``tokens`` [B, S] are the next S tokens at
    absolute cache slots [pos, pos+S). Returns (logits [B, S, vocab] f32,
    updated cache). ``gpt2_decode(params, prompt, cfg, cache, 0)`` is the
    prefill; single-token calls are the decode loop. Matches ``gpt2_apply``
    logits position-for-position (pinned by tests/test_generate.py).
    ``offset`` [B]: per-row left-pad width for batched variable-length
    prompts — row b's real tokens sit at slots >= offset[b] and get
    position ids ``slot - offset[b]`` (solo semantics, shifted). MoE
    checkpoints compose with the offset path: the left-pad lanes are
    masked out of expert routing (``valid`` below) and inference routing
    is no-drop per-token (see _decode_mlp), so batched greedy output
    equals solo runs for MoE exactly as it does for dense models."""
    valid = None
    if offset is not None:
        # lane (b, s) sits at absolute cache slot pos + s; slots below the
        # row's left-pad width are dead lanes for expert routing
        valid = (pos + jnp.arange(tokens.shape[1]))[None, :] >= offset[:, None]
    x = _decode_embed(params, tokens, cfg, pos, offset)
    new_cache = []
    for p, c in zip(params["blocks"], cache):
        a, c = _decode_attention(_layer_norm(x, p["ln_1"]), p["attn"], cfg, c,
                                 pos, offset)
        x = _decode_mlp(x + a, p, cfg, valid=valid)
        new_cache.append(c)
    x = _layer_norm(x, params["ln_f"])
    return _tied_logits(x, params, cfg), new_cache


@jax.named_scope("attn")
def _paged_attention_block(x, p, cfg: GPT2Config, c, tables, pos, valid,
                           tp_axis=None, fresh=False):
    """The paged twin of :func:`_decode_attention`: scatter the new k/v
    into block-table pages, attend over the gathered history
    (ops.attention.paged_decode_attention — same masked-softmax chain as
    the dense path, so greedy decode is bit-identical when T matches).
    ``fresh`` (static): the window's first token is at position 0, so the
    keys it attends to are the ones it has just projected; where
    ``ops.attention.fresh_kernel_applies`` it attends over q, k, v as they
    lie, token-major, through the tiled forward kernel, and the pages are
    written and never gathered.
    With ``tp_axis`` (inside shard_map — the TP serving engine): qkv is
    column-parallel (this rank holds H/tp heads and the page pool's
    matching kv-head shard), the scatter/gather/attend chain is entirely
    shard-local, and only the row-parallel output projection crosses the
    tensor axis (one psum; bias added after the reduction, once)."""
    from distributed_lion_tpu.ops.attention import (
        fresh_causal_attention,
        fresh_kernel_applies,
        paged_decode_attention,
        paged_scatter_fresh,
        paged_scatter_kv,
    )

    B, S, _ = x.shape
    tp = 1 if tp_axis is None else jax.lax.psum(1, tp_axis)
    H, hd = cfg.n_head // tp, cfg.head_dim
    qkv = _qkv_project(x, p["qkv"]) + p["qkv_b"].astype(x.dtype)
    q, k, v = (qkv[:, :, i].reshape(B, S, H, hd) for i in range(3))
    if fresh and fresh_kernel_applies(S, H, H, hd, q.dtype):
        k_pages = paged_scatter_fresh(c["k"], tables, k.astype(c["k"].dtype),
                                      valid)
        v_pages = paged_scatter_fresh(c["v"], tables, v.astype(c["v"].dtype),
                                      valid)
        out = fresh_causal_attention(q, k, v)
    else:
        k_pages = paged_scatter_kv(c["k"], tables, pos,
                                   k.astype(c["k"].dtype), valid)
        v_pages = paged_scatter_kv(c["v"], tables, pos,
                                   v.astype(c["v"].dtype), valid)
        out = paged_decode_attention(q.transpose(0, 2, 1, 3), k_pages,
                                     v_pages, tables, pos, kv_heads=H)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = _proj(out, p["proj"])
    if tp_axis is not None:
        out = reduce_from_tp_region(out, tp_axis)
    out = out + p["proj_b"].astype(x.dtype)
    return out, {"k": k_pages, "v": v_pages}


def gpt2_decode_paged(params: dict, tokens: jnp.ndarray, cfg: GPT2Config,
                      pages: list, tables: jnp.ndarray, pos: jnp.ndarray,
                      valid=None, tp_axis=None, ep_axis=None,
                      return_moe_stats=False, stats_axis=None,
                      stats_lanes=None, fresh=False):
    """Block-table decode (the serving engine's model hook): ``tokens``
    [B, S] where row b's tokens sit at absolute positions
    ``pos[b] .. pos[b]+S-1`` of its own sequence; ``pages`` is the
    per-layer page pool ({"k","v"} leaves laid out by
    serve/kv_cache.init_pages; [num_blocks, block_size, H, hd] reads too),
    ``tables`` [B, blocks_per_seq] the per-row block tables, ``valid``
    optional [B, S] (False = right-pad tail of a bucketed prefill — no
    page write, logits discarded by the caller). Returns (logits
    [B, S, vocab] f32, updated pages). Positions are PER ROW, so one call
    serves prefill (S = padded prompt, pos = 0) and the rolling decode
    tick (S = 1, pos = per-slot lengths) — one jitted program each.
    ``fresh`` (static; the engine knows it at dispatch): every row's
    ``pos`` is 0, so no query sees a page this call did not write, and the
    blocks may attend over their own fresh keys
    (:func:`_paged_attention_block`). A window behind a shared prefix, a
    speculative verify and a drafter's mirror leave it False.
    With ``tp_axis`` (inside shard_map — the TP serving engine, ISSUE 13)
    attention/MLP weights and the page pool's kv-head axis are expected
    pre-sharded per ``parallel.tensor_parallel.gpt2_param_specs``;
    embeddings and the tied head stay replicated, so the returned logits
    are identical on every tensor rank.

    MoE checkpoints serve through this path (ISSUE 15 — the PR 9 refusal
    lifted): ``valid`` masks pad/sentinel lanes out of expert routing and
    inference routing is no-drop (see _decode_mlp), so paged MoE decode
    is bit-identical to the dense-KV MoE path at matched attended length.
    ``ep_axis`` (inside the serving engine's shard_map) shards the expert
    banks over the mesh's expert axis — two all_to_all hops per MoE block,
    the page pools untouched. ``return_moe_stats`` additionally returns a
    dict of routing-load scalars summed over the MoE blocks (the bench's
    capacity-utilization columns; {} for a dense checkpoint); under
    batch-sharded ep (ISSUE 16) ``stats_axis`` makes those counters
    global (see _decode_mlp).

    Batch-sharded expert-parallel decode (ISSUE 16): when the engine
    shards the decode batch over the expert axis, every operand here is
    this shard's LOCAL slice — B local slots, the page pool's local block
    span, tables carrying LOCAL page ids (sentinel == local pool size).
    Attention is row-local so nothing changes; the MoE dispatch
    all_to_all hops are exactly the training-style layout moe_ffn was
    written for, and no-drop routing keeps per-token outputs bit-equal
    to the replicated program."""
    pos_ids = jnp.clip(pos[:, None] + jnp.arange(tokens.shape[1])[None, :],
                       0, cfg.n_ctx - 1)
    from distributed_lion_tpu.models.lora import lora_embed

    with jax.named_scope("embed"):
        x = lora_embed(params["wte"], tokens, cfg.compute_dtype)
        x = x + lora_embed(params["wpe"], pos_ids, cfg.compute_dtype)
    stats = [] if return_moe_stats else None
    new_pages = []
    for p, c in zip(params["blocks"], pages):
        a, c = _paged_attention_block(_layer_norm(x, p["ln_1"]), p["attn"],
                                      cfg, c, tables, pos, valid, tp_axis,
                                      fresh)
        x = _decode_mlp(x + a, p, cfg, tp_axis, valid, ep_axis, stats,
                        stats_axis, stats_lanes)
        new_pages.append(c)
    x = _layer_norm(x, params["ln_f"])
    logits = _tied_logits(x, params, cfg)
    if return_moe_stats:
        agg = ({k: sum(s[k] for s in stats)
                for k in ("valid", "kept", "capacity_slots")}
               if stats else {})
        return logits, new_pages, agg
    return logits, new_pages
