"""SFT entry point — the reference's ``sft_llama2.py`` workload (Llama +
QLoRA + packed SFT, /root/reference/sft_llama2.py, README.md:41-63) rebuilt
TPU-native.

Maps the reference's pieces:
- 4-bit NF4 base + bf16 compute (:141-153) → ``--quant nf4`` (ops/quant);
- LoRA q/v r=8 α=16 (:44-51)            → ``--lora_r/--lora_alpha``;
- ConstantLengthDataset packing (:122-137) → data/sft.constant_length_batches;
- chars_token_ratio estimation (:62-75)  → logged before training;
- guards (:53-59): packing×group_by_length mutually exclusive, gradient
  checkpointing rejected with PEFT (we remat per-block regardless — the
  guard is kept for CLI parity and prints why it's moot here);
- --lion/--async_grad optimizer wiring (:163-181);
- post-train merge_and_unload + save merged (:183-199) → models/lora.merge_lora
  → utils/serialization.save_pytree.

Data: ``--dataset jsonl:<path>`` with stack-exchange-paired-style records
({"question", "response_j"}), or ``synthetic`` Q/A pairs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class SFTArguments:
    """sft_llama2.py ScriptArguments (:20-40) equivalents."""

    model_name: str = "llama2_7b"  # llama2_7b | llama3_8b | tiny
    model_path: Optional[str] = None  # local HF Llama checkpoint → finetune a
    # PRETRAINED base, the reference's from_pretrained path
    # (sft_llama2.py:141-154); overrides model_name's architecture
    dataset: str = "synthetic"     # synthetic | jsonl:<path>
    seq_length: int = 1024
    size_valid_set: int = 64
    num_train_samples: int = 512   # synthetic corpus size
    quant: str = "none"            # none | int8 | nf4  (reference: nf4)
    quant_block: Optional[int] = None  # quant block size override (elements;
    # defaults: nf4 64, int8 256). Shrink when a small model's projections
    # must shard under --tensor_parallel (last dim / block % tp == 0).
    lora_r: int = 8
    lora_alpha: int = 16
    lora_dropout: float = 0.05  # adapter-branch dropout (sft_llama2.py:48)
    packing: bool = True
    group_by_length: bool = False
    gradient_checkpointing: bool = False
    attn_impl: str = "auto"  # ops.attention: auto | xla
    seq_impl: str = "ring"   # under --seq_parallel: ring | ulysses
    tokenizer_name: Optional[str] = None
    adapter_path: Optional[str] = None  # start from a PEFT adapter
    # checkpoint (adapter_config.json + adapter_model.safetensors) instead
    # of fresh lora_init — models/hf_import.peft_to_lora
    adapter_output: Optional[str] = None  # save the trained LoRA adapters
    # as a HF PEFT checkpoint directory (adapter_model.safetensors +
    # adapter_config.json — PeftModel.from_pretrained-loadable; the
    # reference's pre-merge save_model artifact, sft_llama2.py:183-190)
    merged_output: Optional[str] = None  # save the LoRA-merged model here:
    # a *.npz path → flat save_pytree archive (cli/run_generate's format);
    # any other path → an HF save_pretrained directory
    # (LlamaForCausalLM.from_pretrained-loadable, models/hf_export)


def main(argv=None):
    from distributed_lion_tpu.utils.argparsing import parse_dataclasses

    script_args, train_cfg = parse_dataclasses((SFTArguments, _train_cfg_cls()), argv)

    # Reference guards (sft_llama2.py:53-59).
    if script_args.packing and script_args.group_by_length:
        raise ValueError("Cannot use both packing and group by length")
    if script_args.gradient_checkpointing:
        raise ValueError(
            "gradient_checkpointing with LoRA is rejected for parity with the "
            "reference (sft_llama2.py:56-59); note this framework picks what "
            "its per-block checkpoint saves from the device's memory "
            "(remat_policy auto), so the memory benefit is already in place"
        )

    import jax
    from jax.sharding import PartitionSpec as P

    from distributed_lion_tpu.cli.run_clm import build_mesh
    from distributed_lion_tpu.data.sft import (
        chars_token_ratio,
        constant_length_batches,
        load_pairs_jsonl,
        synthetic_qa_pairs,
    )
    from distributed_lion_tpu.data.tokenizer import load_tokenizer
    from distributed_lion_tpu.models.llama import (
        LlamaConfig,
        llama_hidden,
        llama_init,
    )
    from distributed_lion_tpu.models.lora import (
        LoraConfig,
        apply_adapters,
        lora_init,
        merge_lora,
    )
    from distributed_lion_tpu.ops import xent as xent_ops
    from distributed_lion_tpu.ops.quant import maybe_dequant, quantize_tree
    from distributed_lion_tpu.parallel.mesh import (
        DATA_AXIS,
        SEQ_AXIS,
        TENSOR_AXIS,
    )
    from distributed_lion_tpu.train.loop import (
        LossSpec,
        Trainer,
        apply_remat_policy,
    )
    from distributed_lion_tpu.utils.serialization import save_pytree

    sp = train_cfg.seq_parallel
    if sp > 1:
        # long-context SFT: packed rows sharded over tokens, ring attention
        # over the 'seq' axis; boundary labels ride a ppermute
        # (models/loss.clm_loss_seq_parallel)
        if not script_args.packing:
            raise NotImplementedError(
                "--seq_parallel needs --packing: padded/masked per-example "
                "rows are not wired across sequence shards"
            )
    mesh = build_mesh(train_cfg.tensor_parallel, sp)
    tok = load_tokenizer(script_args.tokenizer_name)

    if script_args.dataset == "synthetic":
        records = synthetic_qa_pairs(script_args.num_train_samples + script_args.size_valid_set)
        valid = records[: script_args.size_valid_set]
        train = records[script_args.size_valid_set:]
    elif script_args.dataset.startswith("jsonl:"):
        train, valid = load_pairs_jsonl(
            script_args.dataset[len("jsonl:"):], size_valid_set=script_args.size_valid_set
        )
    else:
        raise ValueError(f"unknown dataset spec {script_args.dataset!r}")

    ratio = chars_token_ratio(train, tok)
    print(f"[run_sft] chars/token ratio: {ratio:.2f} over {min(len(train), 400)} samples")

    if script_args.model_path:
        from distributed_lion_tpu.models.hf_import import llama_from_hf

        base_params, model_cfg = llama_from_hf(script_args.model_path)
        print(f"[run_sft] loaded pretrained Llama from {script_args.model_path}: "
              f"{model_cfg.n_layer}L d={model_cfg.d_model} vocab={model_cfg.vocab_size}")
        if tok.vocab_size > model_cfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab {tok.vocab_size} exceeds the checkpoint's "
                f"{model_cfg.vocab_size}; pass the checkpoint's own tokenizer"
            )
    else:
        model_cfg = LlamaConfig.named(script_args.model_name,
                                      vocab_size=max(tok.vocab_size, 259))
    # remat_policy 'auto': the trainer's pick from the shapes and the
    # device's memory, resolved below once the trees to count exist
    model_cfg = dataclasses.replace(model_cfg, attn_impl=script_args.attn_impl,
                                    seq_impl=script_args.seq_impl,
                                    remat_policy="auto")
    if script_args.seq_length > model_cfg.n_ctx:
        script_args.seq_length = model_cfg.n_ctx
    if sp > 1 and script_args.seq_length % sp:
        # checked AFTER the n_ctx clamp so the validated value is the one
        # the packed rows actually use
        raise ValueError(
            f"--seq_length {script_args.seq_length} (after the n_ctx clamp) "
            f"must divide evenly over the {sp}-way seq axis"
        )
    train_cfg.block_size = script_args.seq_length

    if not script_args.model_path:
        base_params = llama_init(jax.random.key(train_cfg.seed), model_cfg)
    if script_args.quant != "none":
        print(f"[run_sft] quantizing frozen base to {script_args.quant}")
        base_params = quantize_tree(base_params, script_args.quant,
                                    block=script_args.quant_block)

    if script_args.adapter_path:
        # continue training a PEFT checkpoint (ours via --adapter_output, or
        # one trained by the torch/peft stack) — r/alpha/targets come from
        # its adapter_config.json, overriding --lora_r/--lora_alpha
        from distributed_lion_tpu.models.hf_import import peft_to_lora

        adapters, lora_cfg = peft_to_lora(script_args.adapter_path, model_cfg)
        print(f"[run_sft] resumed PEFT adapter from {script_args.adapter_path} "
              f"(r={lora_cfg.r} alpha={lora_cfg.alpha})")
    else:
        lora_cfg = LoraConfig(r=script_args.lora_r, alpha=script_args.lora_alpha,
                              dropout=script_args.lora_dropout)
        adapters = lora_init(jax.random.key(train_cfg.seed + 1), base_params, lora_cfg)
    n_adapter = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(adapters))
    print(f"[run_sft] LoRA adapters: {len(adapters)} sites, {n_adapter/1e3:.1f}k trainable params")

    model_cfg, remat_decision = apply_remat_policy(
        train_cfg, model_cfg, mesh, adapters, frozen=base_params)
    tp = train_cfg.tensor_parallel
    tp_axis = TENSOR_AXIS if tp > 1 else None
    seq_axis = SEQ_AXIS if sp > 1 else None

    def _loss(effective, batch):
        """CLM loss over the (possibly adapted/quantized) effective params.
        ``--vocab_chunks`` streams the lm_head, left in its [d, V] matmul
        layout, so the [B, T, V] logits never materialize (V is 32k for
        Llama-2, 128k for Llama-3-class configs); under ``--seq_parallel``
        the batch is this shard's contiguous token chunk [B, T/sp] and the
        boundary labels ride a ppermute. Which head runs is
        ops/xent.head_path's to say."""
        # packed: plain [B, T] token array; non-packed: {"tokens", "mask"}
        tokens, mask = ((batch["tokens"], batch["mask"])
                        if isinstance(batch, dict) else (batch, None))
        hidden = llama_hidden(effective, tokens, model_cfg, tp_axis=tp_axis,
                              seq_axis=seq_axis)
        return xent_ops.clm_head_loss(
            hidden, maybe_dequant(effective["lm_head"], hidden.dtype), tokens,
            layout="dv", loss_mask=mask, chunks=train_cfg.vocab_chunks,
            seq_axis=seq_axis)

    # tp x sp is long-context QLoRA SFT: base weights sharded over 'tensor',
    # packed rows' tokens over 'seq' (ring attention), one vote world over
    # 'data'; the train loop psums grads over the seq axis
    loss_spec = LossSpec(
        vocab_chunks=True,
        batch_spec=P(DATA_AXIS, SEQ_AXIS) if sp > 1 else None)
    if tp > 1:
        # frozen base sharded over the tensor axis, threaded through the
        # train step as a live argument; adapters shard with their targets
        # (models/lora.lora_adapter_specs), replicated factors get the
        # copy_to_tp_region gradient boundary inside apply_adapters (the
        # f/g custom-vjp pair keeps per-tensor-rank adapter grads exact).
        from distributed_lion_tpu.models.lora import lora_adapter_specs
        from distributed_lion_tpu.parallel.tensor_parallel import (
            llama_param_specs,
            validate_tp,
        )

        validate_tp(model_cfg, tp, "llama")
        base_specs = llama_param_specs(model_cfg)
        if script_args.quant != "none":
            # the shaped QuantizedTensor layout shards with the dense specs;
            # fail fast with the leaf path if block alignment doesn't allow it
            from distributed_lion_tpu.ops.quant import validate_quant_tp

            validate_quant_tp(base_params, base_specs, tp, TENSOR_AXIS)

        def loss_fn(params, frozen, batch, dropout_key):
            return _loss(apply_adapters(frozen, params, lora_cfg,
                                        tp_axis=TENSOR_AXIS,
                                        base_specs=base_specs,
                                        dropout_key=dropout_key), batch)

        trainer = Trainer(
            train_cfg, mesh, apply_fn=None, params=adapters,
            remat_decision=remat_decision, loss_fn=loss_fn,
            loss_spec=loss_spec,
            param_specs=lora_adapter_specs(adapters, base_specs, TENSOR_AXIS),
            frozen_params=base_params, frozen_specs=base_specs)
    else:
        def loss_fn(params, batch, dropout_key):
            return _loss(apply_adapters(base_params, params, lora_cfg,
                                        dropout_key=dropout_key), batch)

        trainer = Trainer(train_cfg, mesh, apply_fn=None, params=adapters,
                          remat_decision=remat_decision, loss_fn=loss_fn,
                          loss_spec=loss_spec)

    if script_args.packing:
        def batches():
            gen = constant_length_batches(
                train, tok, script_args.seq_length, infinite=True,
                chars_per_token=ratio,
            )
            gb = trainer.global_train_batch()
            while True:
                yield np.stack([next(gen) for _ in range(gb)])

        train_iter = batches()
        eval_blocks = None
        if valid:
            rows = list(constant_length_batches(
                valid, tok, script_args.seq_length, infinite=False,
                chars_per_token=ratio,
            ))
            if rows:
                eval_blocks = np.stack(rows)
    else:
        # non-packed: one example per row, padded + loss-masked, optionally
        # length-grouped (the reference base trainer's alternative to
        # ConstantLengthDataset, sft_llama2.py:53-54)
        from distributed_lion_tpu.data.sft import padded_batch_iterator, padded_examples

        tr_tokens, tr_mask = padded_examples(
            train, tok, script_args.seq_length,
            group_by_length=script_args.group_by_length,
        )
        train_iter = padded_batch_iterator(
            tr_tokens, tr_mask, trainer.global_train_batch(),
            seed=train_cfg.seed,
            length_grouped=script_args.group_by_length,
        )
        eval_blocks = None
        if valid:
            ev_tokens, ev_mask = padded_examples(valid, tok, script_args.seq_length)
            eval_blocks = {"tokens": ev_tokens, "mask": ev_mask}

    try:
        trainer.train(train_iter, eval_blocks=eval_blocks)
        if trainer.preempted:
            print("[run_sft] preempted: "
                  + ("checkpoint durable, " if trainer.checkpointer
                     else "NO checkpointer (no --output_dir) — nothing "
                          "saved, ")
                  + "exiting cleanly")
            return
        if eval_blocks is not None:
            trainer.evaluate(eval_blocks)
        if trainer.checkpointer:
            trainer.save()
        if script_args.adapter_output:
            from distributed_lion_tpu.models.hf_export import lora_to_peft

            lora_to_peft(jax.device_get(trainer.params), model_cfg, lora_cfg,
                         script_args.adapter_output,
                         base_model_name=script_args.model_path or "")
            print(f"[run_sft] PEFT adapter saved to {script_args.adapter_output}")
        # merge_and_unload parity (sft_llama2.py:183-199)
        if script_args.merged_output:
            from distributed_lion_tpu.ops.quant import dequantize_tree

            merged = dequantize_tree(merge_lora(base_params, trainer.params, lora_cfg))
            if script_args.merged_output.endswith(".npz"):
                save_pytree(script_args.merged_output, merged)
            else:
                # HF save_pretrained layout — loadable by
                # LlamaForCausalLM.from_pretrained, the format the
                # reference's merge flow emits (sft_llama2.py:196-199)
                from distributed_lion_tpu.models.hf_export import (
                    copy_tokenizer_files, llama_to_hf)

                llama_to_hf(jax.device_get(merged), model_cfg,
                            script_args.merged_output)
                copy_tokenizer_files(script_args.tokenizer_name
                                     or script_args.model_path,
                                     script_args.merged_output)
            print(f"[run_sft] merged model saved to {script_args.merged_output}")
    finally:
        trainer.close()


def _train_cfg_cls():
    from distributed_lion_tpu.train.loop import TrainConfig

    return TrainConfig


if __name__ == "__main__":
    main()
