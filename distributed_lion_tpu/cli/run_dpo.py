"""DPO entry point — the INTENDED workload of the reference's broken
``dpo_llama2.py`` (/root/reference/dpo_llama2.py; syntax error at :81 and
undefined ``base_model`` at :210-213 make it unrunnable — SURVEY §2.10).

Pieces mapped:
- policy + frozen reference model, both from the SFT checkpoint (:133-152)
  → ``--sft_checkpoint`` loads a merged .npz (from run_sft --merged_output);
  both start identical, the ref stays frozen (optionally quantized);
- β=0.1 pairwise loss (:25, :223) → train/dpo.make_dpo_loss_fn;
- prompt/chosen/rejected prep + length filter (:84-125, :158-168)
  → data/dpo.prepare_dpo_batch (max_length 1024, max_prompt_length 512);
- --sanity_check (:62) truncates to 1000 pairs;
- LoRA on the policy (:192-207) with the reference's wider target set;
- --lion/--async_grad optimizer wiring (:209-231).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class DPOArguments:
    """dpo_llama2.py ScriptArguments (:18-81), repaired."""

    model_name: str = "llama2_7b"  # llama2_7b | llama3_8b | small | tiny
    model_path: Optional[str] = None  # local HF Llama checkpoint: policy+ref
    # both start from the pretrained base (dpo_llama2.py:133-152); an
    # --sft_checkpoint takes precedence (the reference's canonical flow runs
    # DPO on the SFT-merged model)
    dataset: str = "synthetic"     # synthetic | jsonl:<path>
    sft_checkpoint: Optional[str] = None  # merged .npz from run_sft
    beta: float = 0.1
    max_length: int = 1024
    max_prompt_length: int = 512
    num_train_samples: int = 512
    size_valid_set: int = 64
    sanity_check: bool = False
    attn_impl: str = "auto"  # ops.attention: auto | xla
    seq_impl: str = "ring"   # under --seq_parallel: ring | ulysses
    quant_ref: str = "none"        # none | int8 | nf4 — frozen ref model
    quant_block: Optional[int] = None  # quant block size override; shrink so
    # a small model's projections shard under --tensor_parallel
    lora_r: int = 8
    lora_alpha: int = 16
    lora_dropout: float = 0.05  # adapter-branch dropout (PEFT semantics)
    tokenizer_name: Optional[str] = None
    adapter_path: Optional[str] = None  # start the policy from a PEFT
    # adapter checkpoint (models/hf_import.peft_to_lora) instead of fresh init
    adapter_output: Optional[str] = None  # save the trained policy LoRA
    # adapters as a HF PEFT checkpoint directory (models/hf_export.lora_to_peft)
    merged_output: Optional[str] = None  # save the LoRA-merged policy here:
    # *.npz → flat save_pytree archive; any other path → HF save_pretrained
    # directory (models/hf_export)


def main(argv=None):
    from distributed_lion_tpu.utils.argparsing import parse_dataclasses

    script_args, train_cfg = parse_dataclasses((DPOArguments, _train_cfg_cls()), argv)

    import jax
    import numpy as np

    from distributed_lion_tpu.cli.run_clm import build_mesh
    from distributed_lion_tpu.data.dpo import dpo_batch_iterator, prepare_dpo_batch
    from distributed_lion_tpu.data.sft import load_pairs_jsonl, synthetic_qa_pairs
    from distributed_lion_tpu.data.tokenizer import load_tokenizer
    from distributed_lion_tpu.models.llama import LlamaConfig, llama_apply, llama_init
    from distributed_lion_tpu.models.lora import LoraConfig, lora_apply_fn, lora_init, merge_lora
    from distributed_lion_tpu.ops.quant import dequantize_tree, quantize_tree
    from distributed_lion_tpu.train.dpo import make_dpo_loss_fn
    from distributed_lion_tpu.train.loop import Trainer
    from distributed_lion_tpu.utils.serialization import load_pytree, save_pytree

    sp = train_cfg.seq_parallel
    if sp > 1 and train_cfg.tensor_parallel > 1:
        raise NotImplementedError(
            "--tensor_parallel x --seq_parallel on the DPO path is not "
            "wired; pick one"
        )
    mesh = build_mesh(train_cfg.tensor_parallel, sp)
    tok = load_tokenizer(script_args.tokenizer_name)

    pretrained_params = None
    if script_args.model_path:
        from distributed_lion_tpu.models.hf_import import llama_from_hf

        pretrained_params, model_cfg = llama_from_hf(script_args.model_path)
        print(f"[run_dpo] loaded pretrained Llama from {script_args.model_path}: "
              f"{model_cfg.n_layer}L d={model_cfg.d_model} vocab={model_cfg.vocab_size}")
    else:
        model_cfg = LlamaConfig.named(script_args.model_name,
                                      vocab_size=max(tok.vocab_size, 259))
    # remat_policy 'auto': the trainer's pick from the shapes and the
    # device's memory, resolved below once the trees to count exist
    model_cfg = dataclasses.replace(model_cfg, attn_impl=script_args.attn_impl,
                                    seq_impl=script_args.seq_impl,
                                    remat_policy="auto")
    if script_args.max_length > model_cfg.n_ctx:
        script_args.max_length = model_cfg.n_ctx
    if sp > 1 and script_args.max_length % sp:
        # checked after the n_ctx clamp: the padded rows use this value
        raise ValueError(
            f"--max_length {script_args.max_length} (after the n_ctx clamp) "
            f"must divide evenly over the {sp}-way seq axis"
        )
    train_cfg.block_size = script_args.max_length

    # Policy and reference both start from the SFT model (dpo_llama2.py:133-152).
    if script_args.sft_checkpoint:
        import jax.numpy as jnp

        base_params = load_pytree(script_args.sft_checkpoint)
        # npz leaves are numpy; move to device arrays (traced indexing needs
        # jax arrays) and normalize float dtypes to the model's param dtype
        base_params = jax.tree.map(
            lambda x: jnp.asarray(
                x, model_cfg.param_dtype
                if np.issubdtype(np.asarray(x).dtype, np.floating) else None
            ),
            base_params,
        )
        print(f"[run_dpo] loaded SFT model from {script_args.sft_checkpoint}")
    elif pretrained_params is not None:
        base_params = pretrained_params
    else:
        print("[run_dpo] no --sft_checkpoint/--model_path given; starting from fresh init")
        base_params = llama_init(jax.random.key(train_cfg.seed), model_cfg)

    ref_params = base_params
    if script_args.quant_ref != "none":
        ref_params = quantize_tree(base_params, script_args.quant_ref,
                                   block=script_args.quant_block)

    # LoRA on the policy, the reference's wider DPO target set (:192-207).
    if script_args.adapter_path:
        from distributed_lion_tpu.models.hf_import import peft_to_lora

        adapters, lora_cfg = peft_to_lora(script_args.adapter_path, model_cfg)
        print(f"[run_dpo] resumed PEFT adapter from {script_args.adapter_path} "
              f"(r={lora_cfg.r} alpha={lora_cfg.alpha})")
    else:
        # the reference's full DPO target set (dpo_llama2.py:192-207):
        # q/k/v/out projections + the MLP (fc_in/fc_out class) + the token
        # embedding (wte — gather-side adapter, models/lora.lora_embed)
        from distributed_lion_tpu.models.lora import DPO_TARGET_PATTERNS

        lora_cfg = LoraConfig(
            r=script_args.lora_r, alpha=script_args.lora_alpha,
            dropout=script_args.lora_dropout,
            target_patterns=DPO_TARGET_PATTERNS,
        )
        adapters = lora_init(jax.random.key(train_cfg.seed + 1), base_params, lora_cfg)

    from distributed_lion_tpu.train.loop import apply_remat_policy

    # the policy runs chosen and rejected rows: two forwards' residuals a
    # pair; a quantized reference is a second frozen tree beside the base
    model_cfg, remat_decision = apply_remat_policy(
        train_cfg, model_cfg, mesh, adapters, rows_per_sample=2,
        frozen=(base_params if ref_params is base_params
                else (base_params, ref_params)))

    vc = train_cfg.vocab_chunks
    if vc > 0 and train_cfg.tensor_parallel > 1:
        raise NotImplementedError(
            "--vocab_chunks x --tensor_parallel on the DPO path is not "
            "wired (the TP head is already vocab-sharded; chunking it "
            "again buys nothing) — drop one"
        )

    def _hidden_and_head(params, tokens, **kw):
        # chunked-vocab scoring contract: (hidden, head) instead of logits;
        # train/dpo streams the label logprobs through ops/xent
        from distributed_lion_tpu.models.llama import llama_hidden
        from distributed_lion_tpu.ops.quant import maybe_dequant

        return (llama_hidden(params, tokens, model_cfg, **kw),
                maybe_dequant(params["lm_head"], model_cfg.compute_dtype))

    tp = train_cfg.tensor_parallel
    frozen_params = frozen_specs = None
    if tp > 1:
        from distributed_lion_tpu.models.lora import apply_adapters, lora_adapter_specs
        from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS
        from distributed_lion_tpu.parallel.tensor_parallel import (
            llama_param_specs,
            validate_tp,
        )
        from distributed_lion_tpu.train.dpo import make_dpo_loss_fn_frozen

        validate_tp(model_cfg, tp, "llama")
        base_specs = llama_param_specs(model_cfg)
        if script_args.quant_ref != "none":
            # the shaped QuantizedTensor layout shards with the dense specs
            # — multi-chip DPO holds TWO 7B models, exactly where sharding
            # the NF4 ref matters
            from distributed_lion_tpu.ops.quant import validate_quant_tp

            validate_quant_tp(ref_params, base_specs, tp, TENSOR_AXIS)
        frozen_params = {"base": base_params, "ref": ref_params}
        frozen_specs = {"base": base_specs, "ref": base_specs}

        def policy_apply(params, frozen, tokens, dropout_key=None):
            effective = apply_adapters(frozen["base"], params, lora_cfg,
                                       tp_axis=TENSOR_AXIS, base_specs=base_specs,
                                       dropout_key=dropout_key)
            return llama_apply(effective, tokens, model_cfg, tp_axis=TENSOR_AXIS)

        loss_spec = None  # the frozen variant honours no head flag
        loss_fn = make_dpo_loss_fn_frozen(
            policy_apply=policy_apply,
            ref_apply=lambda frozen, t: llama_apply(frozen["ref"], t, model_cfg,
                                                    tp_axis=TENSOR_AXIS),
            beta=script_args.beta,
        )
        adapter_specs = lora_adapter_specs(adapters, base_specs, TENSOR_AXIS)
    elif sp > 1:
        # long-context DPO: chosen/rejected rows sharded over tokens — ring
        # attention through policy and frozen ref, per-shard logprob partials
        # psum'd before the pairwise sigmoid (train/dpo.py)
        from distributed_lion_tpu.parallel.mesh import SEQ_AXIS

        if vc > 0:
            base_fwd = lambda p, t: _hidden_and_head(p, t, seq_axis=SEQ_AXIS)  # noqa: E731
            ref_fwd = lambda t: _hidden_and_head(ref_params, t, seq_axis=SEQ_AXIS)  # noqa: E731
        else:
            base_fwd = lambda p, t: llama_apply(p, t, model_cfg, seq_axis=SEQ_AXIS)  # noqa: E731
            ref_fwd = lambda t: llama_apply(ref_params, t, model_cfg,
                                            seq_axis=SEQ_AXIS)  # noqa: E731
        policy_apply_lora = lora_apply_fn(base_fwd, base_params, lora_cfg)
        loss_fn, loss_spec = make_dpo_loss_fn(
            policy_apply=policy_apply_lora,
            ref_apply=ref_fwd,
            beta=script_args.beta,
            seq_axis=SEQ_AXIS,
            vocab_chunks=vc,
        )
        adapter_specs = None
    else:
        if vc > 0:
            base_fwd = _hidden_and_head
            ref_fwd = lambda t: _hidden_and_head(ref_params, t)  # noqa: E731
        else:
            base_fwd = lambda p, t: llama_apply(p, t, model_cfg)  # noqa: E731
            ref_fwd = lambda t: llama_apply(ref_params, t, model_cfg)  # noqa: E731
        policy_apply_lora = lora_apply_fn(base_fwd, base_params, lora_cfg)
        loss_fn, loss_spec = make_dpo_loss_fn(
            policy_apply=policy_apply_lora,
            ref_apply=ref_fwd,
            beta=script_args.beta,
            vocab_chunks=vc,
        )
        adapter_specs = None

    if script_args.dataset == "synthetic":
        records = synthetic_qa_pairs(script_args.num_train_samples + script_args.size_valid_set)
    elif script_args.dataset.startswith("jsonl:"):
        train_recs, _ = load_pairs_jsonl(script_args.dataset[len("jsonl:"):])
        records = train_recs
    else:
        raise ValueError(f"unknown dataset spec {script_args.dataset!r}")

    data = prepare_dpo_batch(
        records, tok,
        max_length=script_args.max_length,
        max_prompt_length=script_args.max_prompt_length,
        sanity_check=script_args.sanity_check,
    )
    n = len(data["chosen"])
    n_valid = min(script_args.size_valid_set, n // 4)
    eval_data = {k: v[:n_valid] for k, v in data.items()} if n_valid else None
    train_data = {k: v[n_valid:] for k, v in data.items()}
    print(f"[run_dpo] {len(train_data['chosen'])} train / {n_valid} eval pairs "
          f"(after length filtering)")

    trainer = Trainer(train_cfg, mesh, apply_fn=None, params=adapters,
                      loss_fn=loss_fn, loss_spec=loss_spec,
                      param_specs=adapter_specs,
                      frozen_params=frozen_params, frozen_specs=frozen_specs,
                      remat_decision=remat_decision)
    it = dpo_batch_iterator(train_data, trainer.global_train_batch(), seed=train_cfg.seed)
    try:
        trainer.train(it, eval_blocks=eval_data)
        if trainer.preempted:
            print("[run_dpo] preempted: "
                  + ("checkpoint durable, " if trainer.checkpointer
                     else "NO checkpointer (no --output_dir) — nothing "
                          "saved, ")
                  + "exiting cleanly")
            return
        if eval_data is not None:
            trainer.evaluate(eval_data)
        if trainer.checkpointer:
            trainer.save()
        if script_args.adapter_output:
            from distributed_lion_tpu.models.hf_export import lora_to_peft

            lora_to_peft(jax.device_get(trainer.params), model_cfg, lora_cfg,
                         script_args.adapter_output,
                         base_model_name=script_args.model_path or "")
            print(f"[run_dpo] PEFT adapter saved to {script_args.adapter_output}")
        if script_args.merged_output:
            merged = dequantize_tree(merge_lora(base_params, trainer.params, lora_cfg))
            if script_args.merged_output.endswith(".npz"):
                save_pytree(script_args.merged_output, merged)
            else:
                # HF save_pretrained layout, like run_sft's merge flow
                import jax

                from distributed_lion_tpu.models.hf_export import (
                    copy_tokenizer_files, llama_to_hf)

                llama_to_hf(jax.device_get(merged), model_cfg,
                            script_args.merged_output)
                copy_tokenizer_files(script_args.tokenizer_name
                                     or script_args.model_path,
                                     script_args.merged_output)
            print(f"[run_dpo] merged policy saved to {script_args.merged_output}")
    finally:
        trainer.close()


def _train_cfg_cls():
    from distributed_lion_tpu.train.loop import TrainConfig

    return TrainConfig


if __name__ == "__main__":
    main()
