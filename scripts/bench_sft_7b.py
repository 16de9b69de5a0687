"""SFT throughput/memory smoke at Llama-2-7B shapes on the local chip.

The reference's flagship finetune is Llama-2-7B QLoRA SFT
(/root/reference/sft_llama2.py:141-153: 4-bit NF4 base, bf16 compute, LoRA
q/v r=8). This script runs that workload's train step at FULL 7B shapes
(32 layers, d=4096, random-init base — throughput and memory don't care
about weight values) and reports tokens/s/chip plus peak HBM, the number
VERDICT r1 asked to have recorded.

Methodology: fused K-step dispatches via
Trainer._train_chunk, timer stopped on a device_get of the final loss so
queued-but-unexecuted work can't inflate the number.

    python scripts/bench_sft_7b.py             # nf4, bs1, accum 4, chunks 8
    python scripts/bench_sft_7b.py bf16:2:4:0  # quant:bs:accum:vocab_chunks
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 4           # steps per device dispatch
TIMED_CALLS = 2
# SFT7B_VALIDATE=1: pipeline-validation mode (VERDICT r4 #5 — "first
# validate the full spec list end to end on CPU host-RAM so the window is
# spent measuring, not debugging"). Each spec runs the REAL pipeline (host
# init at full d_model/vocab, NF4/int8 quantize, LoRA, chunked loss,
# trainer step) but at n_layer=2 / bs=1 / accum=1 / one dispatch — full
# 7B depth is days of work on the 1-core host. Rows are stamped
# "validate": true and never create skip keys, so a later real TPU window
# still measures every spec.
VALIDATE = os.environ.get("SFT7B_VALIDATE") == "1"


def run(quant: str = "nf4", batch_per_dev: int = 1, accum: int = 4,
        vocab_chunks: int = 8, n_layer: int | None = None,
        seq_len: int = 1024, model: str = "llama2_7b",
        remat_policy: str = "full") -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_lion_tpu.models.llama import LlamaConfig, llama_init
    from distributed_lion_tpu.models.lora import LoraConfig, apply_adapters, lora_init
    from distributed_lion_tpu.ops.quant import quantize_tree
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    n_dev = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    mesh = make_mesh()
    kw = {} if n_layer is None else {"n_layer": n_layer}
    ctor = {"llama2_7b": LlamaConfig.llama2_7b, "tiny": LlamaConfig.tiny}[model]
    model_cfg = ctor(remat_policy=remat_policy, **kw)
    cfg = TrainConfig(
        lion=True, async_grad=True, learning_rate=1e-4, weight_decay=0.0,
        warmup_steps=10, max_steps=10_000,
        per_device_train_batch_size=batch_per_dev,
        gradient_accumulation_steps=accum, block_size=seq_len,
        steps_per_call=K, logging_steps=10_000, output_dir=None,
        vocab_chunks=vocab_chunks,
        # pin the banked-row methodology: the auto sentinels would resolve
        # to packed_a2a (+ lazy votes) on a W>1 mesh and rank incomparably
        # against rows measured under every-step sign_psum
        wire="sign_psum", vote_every=1,
    )

    # Init + quantize the frozen base ON HOST CPU, then ship only the packed
    # codes: a 7B f32 base is 26 GB — bigger than the whole v5e chip — so
    # on-device init OOMs before quantization
    # can shrink it. Host RAM holds it easily; the device only ever sees the
    # ~3.5 GB NF4 codes (+ small dense leaves). Throughput/memory don't
    # care about weight values (random init either way).
    import contextlib
    import dataclasses as _dc

    import jax.numpy as jnp

    try:
        # needs the host backend exposed next to the chip
        # (JAX_PLATFORMS="tpu,cpu", or unset)
        cpu = jax.local_devices(backend="cpu")[0]
        ctx = jax.default_device(cpu)
    except RuntimeError:
        # no host backend exposed: init on device — bf16 keeps the dense
        # tree at 13 GB (fits one v5e chip; the per-leaf quantize peak adds
        # only the largest single leaf's codes)
        ctx = contextlib.nullcontext()
    with ctx:
        # quant "nf4"/"int8" → packed codes from a bf16 host init (absmax
        # at bf16 precision is irrelevant for a random-init throughput
        # bench); "bf16" → DENSE bf16 base (13 GB at 7B — fits the chip);
        # "none" → dense base in the config's own param_dtype (f32: 26 GB,
        # only viable with an n_layer override on one chip)
        dense = quant in ("none", "bf16")
        base_dtype = model_cfg.param_dtype if quant == "none" else jnp.bfloat16
        host_cfg = _dc.replace(model_cfg, param_dtype=base_dtype)
        base = llama_init(jax.random.key(0), host_cfg)
        n_base = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(base))
        if not dense:
            base = quantize_tree(base, quant)
    lora_cfg = LoraConfig(r=8, alpha=16)
    adapters = lora_init(jax.random.key(1), base, lora_cfg)
    n_adapter = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(adapters))

    from distributed_lion_tpu.models.llama import llama_hidden
    from distributed_lion_tpu.ops.quant import maybe_dequant
    from distributed_lion_tpu.ops.xent import clm_head_loss
    from distributed_lion_tpu.train.loop import LossSpec

    # the frozen base rides the Trainer's frozen_params slot (replicated
    # device_put + a (params, frozen, batch, key) loss) instead of a
    # Python closure: a closed-over jax.Array is baked into the jaxpr as a
    # CONSTANT, so XLA constant-folds over the multi-GB packed codes at
    # compile time (observed: minutes of u8[4096,2048] folding on the
    # validation run) and the executable carries them — as an argument the
    # codes ship once and compile stays shape-only
    def loss_fn(params, frozen, batch, dropout_key):
        effective = apply_adapters(frozen, params, lora_cfg)
        hidden = llama_hidden(effective, batch, model_cfg)
        return clm_head_loss(
            hidden, maybe_dequant(effective["lm_head"], hidden.dtype), batch,
            layout="dv", chunks=vocab_chunks)

    trainer = Trainer(cfg, mesh, apply_fn=None, params=adapters, loss_fn=loss_fn,
                      loss_spec=LossSpec(vocab_chunks=True), frozen_params=base)
    gb = trainer.global_train_batch()
    tokens_per_step = gb * seq_len

    rng = np.random.default_rng(0)
    batches = jax.device_put(
        rng.integers(0, model_cfg.vocab_size,
                     size=(K, gb, seq_len)).astype(np.int32),
        NamedSharding(mesh, P(None, "data")),
    )
    key = jax.random.key(0)
    t0 = time.perf_counter()
    trainer.params, trainer.state, trainer.vote_health, m = (
        trainer._train_chunk(trainer.params, trainer.state,
                             trainer.vote_health, trainer._frozen_arg(),
                             batches, key))
    _ = float(np.asarray(jax.device_get(m["loss"])))
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(TIMED_CALLS):
        trainer.params, trainer.state, trainer.vote_health, m = (
            trainer._train_chunk(trainer.params, trainer.state,
                                 trainer.vote_health, trainer._frozen_arg(),
                                 batches, key))
    loss = float(np.asarray(jax.device_get(m["loss"])))
    dt = time.perf_counter() - t0
    steps = K * TIMED_CALLS
    tps = tokens_per_step * steps / dt / n_dev

    stats = {}
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        stats = {"peak_hbm_gb": round(ms.get("peak_bytes_in_use", 0) / 2**30, 2),
                 "hbm_limit_gb": round(ms.get("bytes_limit", 0) / 2**30, 2)}
    except Exception:
        pass
    print(json.dumps({
        "workload": f"{model} QLoRA SFT vote-Lion train step",
        **({"validate": True} if VALIDATE else {}),
        "quant": quant, "n_layer": model_cfg.n_layer,
        "base_params": n_base, "adapter_params": n_adapter,
        "batch_per_dev": batch_per_dev, "accum": accum, "seq_len": seq_len,
        "remat_policy": remat_policy,
        "vocab_chunks": vocab_chunks, "device_kind": device_kind,
        "compile_s": round(compile_s, 1), "loss": round(loss, 3),
        "ms_per_step": round(dt / steps * 1e3, 1),
        "tokens_per_sec_per_chip": round(tps, 1), **stats,
    }), flush=True)
    trainer.close()


def _captured_keys() -> set:
    """Specs already holding a RESULT row in $SFT7B_SKIP_FILE (the jsonl
    the runbook appends to): a watcher-re-fired window resumes at the
    first unmeasured spec instead of re-burning minutes of 7B quantize +
    compile per captured one. Error rows don't count — failed specs get
    retried. Key = the spec-derived config fields (n_layer is resolved
    model-side, so it's not part of the key)."""
    path = os.environ.get("SFT7B_SKIP_FILE", "")
    keys: set = set()
    if not path:
        return keys
    try:
        with open(path) as f:
            for line in f:
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if d.get("validate"):
                    continue  # pipeline-validation rows are not captures
                if d.get("tokens_per_sec_per_chip"):
                    keys.add((d.get("quant"), d.get("batch_per_dev"),
                              d.get("accum"), d.get("seq_len"),
                              d.get("remat_policy"), d.get("vocab_chunks")))
    except OSError:
        pass
    return keys


def _validate_full_init() -> None:
    """Full-DEPTH host init + quantize only (no train step): the one part
    of the real 7B pipeline the reduced-depth validation runs don't cover
    — 13 GB of host-RAM init and the per-leaf NF4 packing at true leaf
    shapes. Catches OOM/shape/dtype bugs before a TPU window pays for
    them."""
    import jax
    import numpy as np

    from distributed_lion_tpu.models.llama import LlamaConfig, llama_init
    from distributed_lion_tpu.ops.quant import quantize_tree

    cfg = LlamaConfig.llama2_7b()
    import dataclasses as _dc

    import jax.numpy as jnp
    t0 = time.time()
    base = llama_init(jax.random.key(0),
                      _dc.replace(cfg, param_dtype=jnp.bfloat16))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(base))
    init_s = time.time() - t0
    t0 = time.time()
    q = quantize_tree(base, "nf4")
    q_bytes = sum(x.nbytes for x in jax.tree.leaves(q))
    print(json.dumps({
        "validate": True, "full_init": True, "n_layer": cfg.n_layer,
        "base_params": n, "init_s": round(init_s, 1),
        "quantize_s": round(time.time() - t0, 1),
        "nf4_gb": round(q_bytes / 2**30, 2),
    }), flush=True)


if __name__ == "__main__":
    from distributed_lion_tpu.parallel.mesh import force_cpu_platform

    force_cpu_platform()
    specs = sys.argv[1:] or ["nf4:1:4:8"]
    DEFAULTS = ["nf4", "1", "4", "8", "", "1024", "full"]
    captured = _captured_keys()
    if VALIDATE:
        K, TIMED_CALLS = 1, 1
    for spec in specs:
        parts = spec.split(":")
        # pad with the defaults for the MISSING tail fields only (a plain
        # `parts + DEFAULTS` would splice the default list in positionally:
        # "nf4:1:4:8" must mean full-depth T=1024, not n_layer=1 seq=4)
        parts = (parts + DEFAULTS[len(parts):])[:7]
        quant, bs, accum, vc, nl, sl, pol = parts
        if VALIDATE:
            # exercise the spec's quant/seq_len/remat/chunks through the
            # real pipeline at a depth/budget the host core can afford
            bs, accum, nl = "1", "1", nl or "2"
        if not VALIDATE and (quant, int(bs), int(accum), int(sl),
                             pol or "full", int(vc or 0)) in captured:
            print(f"[7b] skip (already captured): {spec}", file=sys.stderr,
                  flush=True)
            continue
        try:
            run(quant, int(bs), int(accum), int(vc or 0),
                None if not nl else int(nl), int(sl), remat_policy=pol or "full")
        except Exception as e:
            print(json.dumps({"spec": spec,
                              "error": str(e).split("\n")[0][:200]}), flush=True)
    if VALIDATE:
        try:
            _validate_full_init()
        except Exception as e:
            print(json.dumps({"validate": True, "full_init": True,
                              "error": str(e).split("\n")[0][:200]}),
                  flush=True)
