"""Causal-LM pretraining entry point — the reference's ``run_clm.py``
workload (GPT-2 on openwebtext, /root/reference/run_clm.py, README.md:18-38)
rebuilt TPU-native.

Canonical launch (maps the reference's ``torchrun --nproc_per_node 4
run_clm.py --lion --async_grad ...``, README.md:19-38):

    python -m distributed_lion_tpu.cli.run_clm \
        --lion --async_grad --model_name gpt2_124m \
        --dataset synthetic --per_device_train_batch_size 20 \
        --gradient_accumulation_steps 8 --learning_rate 1e-4 \
        --weight_decay 0.1 --warmup_steps 2000 --max_steps 100000 \
        --block_size 1024 --output_dir ./out

There is no torchrun: the device mesh comes from ``jax.devices()`` (all
local chips → the ``data`` axis) or multi-host ``jax.distributed``. Data
sources (zero-egress substitutes for HF-hub streaming): ``synthetic``,
``text:<glob>`` (local files via the byte/HF-cache tokenizer), or
``bin:<path>`` (pre-tokenized uint16 memmap, e.g. an openwebtext dump).
Set env ``DLION_PLATFORM=cpu8`` to force an 8-virtual-device CPU mesh.

What the per-block checkpoint saves (``--remat_policy``, default ``auto``)
is picked once at Trainer build from the shapes and the device's memory:
plain blocks where every residual fits (the README command on a 16 GB
chip), else ``dots``, else ``full``; the ``[setup] remat:`` line says which
and the peak it predicted. ``--remat_policy full|dots`` and ``--remat
false`` are obeyed as given; MoE blocks, ``--pipeline_parallel``,
``--seq_parallel`` and the CPU keep ``full``.

Observability flags (train/telemetry.py; README "Observability"):
``--telemetry`` arms vote-health telemetry (on-device margin histogram /
flip rate / disagreement, measured-vs-analytic wire drift, multi-host
heartbeat), ``--nan_sentinel`` the per-step isfinite watch with crash
bundles under ``output_dir/crash/``, ``--trace_on_anomaly`` a profiler
window at the tripping step.
"""

from __future__ import annotations

import dataclasses
import glob
from typing import Optional


@dataclasses.dataclass
class ModelArguments:
    """run_clm.py ModelArguments (:89-166) — the subset that configures a
    from-scratch model rather than an HF hub download."""

    model_family: str = "gpt2"  # gpt2 | llama | mellum — the reference's
    # run_clm is architecture-agnostic (AutoModelForCausalLM,
    # run_clm.py:425-444); llama composes with dp x tp x sp (pipe/expert/MoE
    # are GPT-2-only), mellum (models/mellum) trains over the data axis
    model_name: str = "gpt2_124m"  # gpt2: gpt2_124m | gpt2_small | tiny;
    # llama: llama2_7b | llama3_8b | tiny; mellum: tiny | a config.json of
    # the published keys (benchmark/configs/mellum2-12b-a2.5b.json)
    model_path: Optional[str] = None  # local HF checkpoint (save_pretrained
    # dir / .safetensors / .bin / .npz) → finetune from pretrained weights,
    # the reference's from_pretrained path (run_clm.py:425-444). Overrides
    # model_name's architecture with the checkpoint's.
    hf_export: Optional[str] = None  # also write the final model as an HF
    # save_pretrained directory (models/hf_export) — the reference's
    # save_model output format (run_clm.py:611-622)
    vocab_size: Optional[int] = None  # default: tokenizer/model default
    n_ctx: Optional[int] = None
    dropout: Optional[float] = None  # None = family default: 0.1 for GPT-2
    # (the reference trains from the HF GPT-2 config, whose every pdrop knob
    # defaults to 0.1 — /root/reference/run_clm.py:425-444), 0.0 for Llama
    # (no dropout), under --pipeline_parallel (unsupported there; explicit
    # --dropout with pp still fails loudly in validate_pipeline), and under
    # --seq_parallel (attention-prob dropout is skipped there; explicit
    # --dropout opts into the partial semantics — see resolve_dropout)
    seq_impl: str = "ring"  # sequence-parallel attention under
    # --seq_parallel: 'ring' (kv rotation) | 'ulysses' (all_to_all to head
    # sharding; needs n_head % seq_parallel == 0)
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True  # per-block activation remat; --remat false is
    # obeyed as it stands: plain blocks, nothing recomputed
    remat_policy: str = "auto"  # what the per-block checkpoint saves:
    # 'auto' (the trainer picks, once, from the shapes and the device's
    # memory: plain blocks where every residual fits, else 'dots', else
    # 'full'; its `[setup] remat:` line says which and what it predicted;
    # MoE blocks, --pipeline_parallel, --seq_parallel and the CPU stay
    # 'full': train/loop.apply_remat_policy) | 'full' (recompute the whole
    # block) | 'dots' (keep matmul outputs, recompute elementwise —
    # models/gpt2._remat_policy). An explicit value always wins.
    moe_experts: int = 0  # > 0: Switch-MoE FFN every moe_every-th block
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    vocab_pad_multiple: int = 0  # gpt2 only: round the embedding-table rows
    # up to this multiple (e.g. 1024 → 50257 becomes 51200) so the tied
    # head / chunked-CE slices are MXU-tile-aligned and --tp_vocab shards
    # evenly; loss/generation semantics are exact (models/gpt2)


def resolve_dropout(dropout: Optional[float], family: str, pp: int,
                    sp: int = 1) -> float:
    """Family-default dropout (None = unset): 0.1 for GPT-2 pretraining —
    the reference instantiates the HF GPT-2 config, whose every pdrop knob
    defaults to 0.1 (/root/reference/run_clm.py:425-444). 0.0 for Llama
    (no dropout), under pipeline parallelism (unsupported there; an
    EXPLICIT nonzero value still fails loudly in validate_pipeline / the
    Llama guard rather than being silently zeroed here), and under
    sequence parallelism — sp skips attention-prob dropout (the scores
    never exist in one place, models/gpt2), so 0.1 would be a DIFFERENT
    regularizer than the reference default this function promises; an
    explicit --dropout under sp opts into that partial semantics (the
    trainer prints the semantics warning)."""
    if dropout is not None:
        return dropout
    return 0.1 if family == "gpt2" and pp <= 1 and sp <= 1 else 0.0


@dataclasses.dataclass
class DataArguments:
    """run_clm.py DataTrainingArguments (:169-244), zero-egress edition."""

    dataset: str = "synthetic"  # synthetic | text:<glob> | bin:<path>
    tokenizer_name: Optional[str] = None
    validation_split_percentage: int = 5  # run_clm.py:181-184
    max_train_samples: Optional[int] = None  # debug truncation (:186-203)
    max_eval_samples: Optional[int] = None
    synthetic_blocks: int = 4096
    native_loader: bool = True  # C++ mmap+prefetch loader for bin: datasets
    bin_dtype: str = "uint16"  # token width of bin: shards (uint16 | uint32)


def build_mesh(tensor_parallel: int = 1, seq_parallel: int = 1,
               pipeline_parallel: int = 1, expert_parallel: int = 1):
    from distributed_lion_tpu.parallel.mesh import (
        force_cpu_platform,
        make_mesh,
        multihost_initialize,
    )
    from distributed_lion_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    force_cpu_platform()
    # distributed init FIRST: the cache gate probes jax.default_backend(),
    # which initializes XLA backends — with backends up,
    # jax.distributed.initialize() raises and multihost_initialize
    # re-raises it loudly (parallel/mesh.py), failing the launch instead of
    # training N silently-disconnected replicas. The order is correctness,
    # not optimization.
    multihost_initialize()
    enable_compilation_cache()
    return make_mesh(tensor=tensor_parallel, seq=seq_parallel,
                     pipe=pipeline_parallel, expert=expert_parallel)


VOCAB_PROBE_TOKENS = 4_000_000  # sample budget for the token-id range check


def _check_vocab(max_token_id: int, vocab_size: int) -> None:
    # token ids must fit the model's embedding table — XLA gather would
    # silently clamp out-of-range ids into wrong-but-running training.
    if max_token_id >= vocab_size:
        raise ValueError(
            f"dataset contains token id {max_token_id} >= model vocab_size "
            f"{vocab_size}; set --vocab_size (or use a matching tokenizer)"
        )


def _bin_paths(spec: str) -> list:
    paths = sorted(glob.glob(spec[len("bin:"):]))
    if not paths:
        raise FileNotFoundError(f"no files match {spec!r}")
    return paths


def load_blocks(data_args: DataArguments, block_size: int, vocab_size: int):
    import numpy as np

    from distributed_lion_tpu.data.sources import (
        TokenDataset,
        synthetic_lm_dataset,
        tokens_from_text_files,
    )

    if data_args.dataset == "synthetic":
        blocks = synthetic_lm_dataset(data_args.synthetic_blocks, block_size, vocab_size)
    elif data_args.dataset.startswith("text:"):
        paths = sorted(glob.glob(data_args.dataset[len("text:"):]))
        if not paths:
            raise FileNotFoundError(f"no files match {data_args.dataset!r}")
        blocks = tokens_from_text_files(paths, block_size, data_args.tokenizer_name)
    elif data_args.dataset.startswith("bin:"):
        # glob + per-shard block cut (tail below one block dropped per shard),
        # matching the native loader's layout exactly
        dtype = np.dtype(data_args.bin_dtype)
        shards = [
            TokenDataset.from_bin(p, block_size, dtype).blocks
            for p in _bin_paths(data_args.dataset)
        ]
        blocks = np.concatenate([s for s in shards if len(s)]) if shards else shards
    else:
        raise ValueError(f"unknown dataset spec {data_args.dataset!r}")

    if len(blocks):
        sample = np.asarray(blocks[: max(1, VOCAB_PROBE_TOKENS // blocks.shape[1])])
        _check_vocab(int(sample.max()), vocab_size)

    # validation split + debug truncation (run_clm.py:181-203, 355-381)
    n_val = max(1, len(blocks) * data_args.validation_split_percentage // 100)
    train, val = blocks[n_val:], blocks[:n_val]
    if data_args.max_train_samples:
        train = train[: data_args.max_train_samples]
    if data_args.max_eval_samples:
        val = val[: data_args.max_eval_samples]
    return np.asarray(train), np.asarray(val)


def make_native_pipeline(
    data_args: DataArguments, block_size: int, vocab_size: int,
    global_batch: int, seed: int,
):
    """C++ mmap+prefetch input pipeline for ``bin:<glob>`` datasets. Returns
    (train_iter, eval_blocks, loader) or None to fall back to Python."""
    if not (data_args.dataset.startswith("bin:") and data_args.native_loader):
        return None
    import numpy as np

    from distributed_lion_tpu.data.native_loader import (
        NativeTokenLoader,
        native_available,
    )

    if not native_available():
        print("[run_clm] no C++ toolchain; falling back to Python loader")
        return None
    paths = _bin_paths(data_args.dataset)
    loader = NativeTokenLoader(
        paths, block_size, dtype=np.dtype(data_args.bin_dtype)
    )
    n = len(loader)
    # hold-out range is ALWAYS the full split percentage so the training set
    # is identical whether or not --max_eval_samples caps the blocks actually
    # evaluated (and identical to the Python load_blocks path).
    n_val = max(1, n * data_args.validation_split_percentage // 100)
    hi = n
    if data_args.max_train_samples:
        hi = min(n, n_val + data_args.max_train_samples)
    # an explicit --max_eval_samples is honored in full; the 4096 default cap
    # only bounds the eager read on huge unconfigured splits (noted below)
    if data_args.max_eval_samples:
        n_eval_read = min(n_val, data_args.max_eval_samples)
    else:
        n_eval_read = min(n_val, 4096)
        if n_eval_read < n_val:
            print(f"[run_clm] eval uses the first {n_eval_read} of {n_val} "
                  "held-out blocks (set --max_eval_samples to override)")
    eval_blocks = loader.read_blocks(0, n_eval_read)
    # vocab check must also sample the TRAIN range — eval-only coverage would
    # let out-of-range train ids reach XLA gather's silent clamp.
    n_probe = max(1, min(hi - n_val, VOCAB_PROBE_TOKENS // block_size))
    probe_idx = np.linspace(n_val, hi - 1, n_probe, dtype=np.int64)
    mx = max(
        int(eval_blocks.max()) if n_eval_read else 0,
        max(int(loader.read_block(int(i)).max()) for i in probe_idx),
    )
    _check_vocab(mx, vocab_size)
    it = loader.batches(global_batch, seed=seed, block_range=(n_val, hi))
    print(f"[run_clm] native loader: {len(paths)} shard(s), {n} blocks "
          f"({n_val} held out for eval)")
    return it, eval_blocks, loader


def main(argv=None):
    from distributed_lion_tpu.utils.argparsing import parse_dataclasses

    model_args, data_args, train_cfg = parse_dataclasses(
        (ModelArguments, DataArguments, _train_config_cls()), argv
    )

    import jax.numpy as jnp

    from distributed_lion_tpu.data.sources import batch_iterator
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    mesh = build_mesh(train_cfg.tensor_parallel, train_cfg.seq_parallel,
                      train_cfg.pipeline_parallel, train_cfg.expert_parallel)
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    family = model_args.model_family
    if model_args.model_path:
        # the checkpoint's architecture wins; resolve BEFORE the family
        # guards so they judge what will actually run
        from distributed_lion_tpu.models import hf_import

        family = hf_import.detect_family(model_args.model_path)
        if family != model_args.model_family:
            print(f"[run_clm] --model_family {model_args.model_family} -> "
                  f"{family} (detected from --model_path)")
    dropout = resolve_dropout(model_args.dropout, family,
                              train_cfg.pipeline_parallel,
                              train_cfg.seq_parallel)
    common = dict(
        dropout=dropout,
        param_dtype=dtypes[model_args.param_dtype],
        compute_dtype=dtypes[model_args.compute_dtype],
        remat=model_args.remat,
        remat_policy=model_args.remat_policy,
        seq_impl=model_args.seq_impl,
        moe_experts=model_args.moe_experts,
        moe_every=model_args.moe_every,
        moe_capacity_factor=model_args.moe_capacity_factor,
        vocab_pad_multiple=model_args.vocab_pad_multiple,
    )
    if family not in ("gpt2", "llama", "mellum"):
        raise ValueError(f"unknown model family {family!r}")
    if family == "mellum":
        ignored = [flag for flag, on in (
            ("--model_path", model_args.model_path),
            ("--hf_export", model_args.hf_export),
            ("--moe_experts", model_args.moe_experts > 0),
            ("--vocab_pad_multiple", model_args.vocab_pad_multiple),
            ("--vocab_size", model_args.vocab_size),
            ("--dropout > 0", (model_args.dropout or 0.0) > 0.0)) if on]
        if ignored:
            raise ValueError(
                f"--model_family mellum takes its architecture from "
                f"--model_name (tiny | a config.json) and has no dropout; "
                f"it does not take {ignored}")
    if family == "llama" and (
        model_args.moe_experts > 0 or train_cfg.expert_parallel > 1
    ):
        raise NotImplementedError(
            "--model_family llama composes with dp x tp x sp x pp; MoE and "
            "the expert axis are wired for GPT-2 only"
        )
    if family == "llama" and (model_args.dropout or 0.0) > 0.0:
        raise ValueError("our Llama (like HF's) has no dropout; set --dropout 0")
    if family == "llama" and model_args.vocab_pad_multiple:
        raise ValueError(
            "--vocab_pad_multiple is a GPT-2 layout option; Llama vocabs "
            "(32000/128256) are already 128-multiples"
        )
    initial_params = None
    if model_args.model_path:
        if family == "llama":
            initial_params, model_cfg = hf_import.llama_from_hf(
                model_args.model_path,
                param_dtype=dtypes[model_args.param_dtype],
                compute_dtype=dtypes[model_args.compute_dtype],
                remat=model_args.remat,
                remat_policy=model_args.remat_policy,
                seq_impl=model_args.seq_impl,
            )
        else:
            initial_params, model_cfg = hf_import.gpt2_from_hf(
                model_args.model_path,
                dropout=dropout,
                param_dtype=dtypes[model_args.param_dtype],
                compute_dtype=dtypes[model_args.compute_dtype],
                remat=model_args.remat,
                remat_policy=model_args.remat_policy,
                seq_impl=model_args.seq_impl,
            )
        print(f"[run_clm] loaded pretrained {family} from {model_args.model_path}: "
              f"{model_cfg.n_layer}L d={model_cfg.d_model} vocab={model_cfg.vocab_size}")
        if model_args.vocab_pad_multiple:
            # pad the imported table with zero rows to the aligned layout;
            # hf_export slices them back off (models/gpt2 vocab_pad_multiple)
            from distributed_lion_tpu.models.gpt2 import pad_wte

            model_cfg = dataclasses.replace(
                model_cfg, vocab_pad_multiple=model_args.vocab_pad_multiple)
            initial_params["wte"] = pad_wte(initial_params["wte"], model_cfg)
    elif family == "mellum":
        from distributed_lion_tpu.models.mellum import MellumConfig

        kw = {k: common[k] for k in ("param_dtype", "compute_dtype", "remat",
                                     "remat_policy")}
        model_cfg = (MellumConfig.tiny(**kw)
                     if model_args.model_name == "tiny" else
                     MellumConfig.from_file(model_args.model_name, **kw))
    elif family == "llama":
        from distributed_lion_tpu.models.llama import LlamaConfig

        # the gpt2 `common` kwargs minus the fields LlamaConfig doesn't have
        # (dropout, moe_*)
        llama_common = {k: common[k] for k in
                        ("param_dtype", "compute_dtype", "remat",
                         "remat_policy", "seq_impl")}
        model_cfg = LlamaConfig.named(model_args.model_name, **llama_common)
    elif model_args.model_name == "tiny":
        model_cfg = GPT2Config.tiny(**common)
    elif model_args.model_name == "gpt2_small":
        model_cfg = GPT2Config.small(**common)
    else:
        model_cfg = GPT2Config.gpt2_124m(**common)
    if model_args.model_path and (model_args.vocab_size or model_args.n_ctx):
        raise ValueError("--vocab_size/--n_ctx cannot override a loaded checkpoint's architecture")
    if model_args.vocab_size:
        model_cfg = dataclasses.replace(model_cfg, vocab_size=model_args.vocab_size)
    elif data_args.dataset.startswith("text:") and initial_params is None:
        # (with a loaded checkpoint the embedding is fixed; out-of-range
        # tokenizer ids are caught by the _check_vocab probe instead)
        # size the embedding to the tokenizer when the user didn't pin it
        from distributed_lion_tpu.data.tokenizer import load_tokenizer

        tok_vocab = load_tokenizer(data_args.tokenizer_name).vocab_size
        if tok_vocab > model_cfg.vocab_size:
            print(f"[run_clm] growing vocab_size {model_cfg.vocab_size} -> tokenizer {tok_vocab}")
            model_cfg = dataclasses.replace(model_cfg, vocab_size=tok_vocab)
    if model_args.n_ctx:
        model_cfg = dataclasses.replace(model_cfg, n_ctx=model_args.n_ctx)
    if model_args.hf_export and getattr(model_cfg, "moe_experts", 0) > 0:
        # fail BEFORE spending the training budget: MoE blocks have no HF
        # GPT-2 equivalent (models/hf_export raises the same at save time)
        raise ValueError("--hf_export is incompatible with --moe_experts: "
                         "MoE blocks have no HF GPT-2 equivalent")
    if train_cfg.block_size > model_cfg.n_ctx:
        # run_clm.py:491-506 caps block_size at the model context length.
        print(f"[run_clm] capping block_size {train_cfg.block_size} -> n_ctx {model_cfg.n_ctx}")
        train_cfg.block_size = model_cfg.n_ctx

    factory = {"llama": Trainer.for_llama, "mellum": Trainer.for_mellum,
               "gpt2": Trainer.for_gpt2}[family]
    trainer = factory(train_cfg, mesh, model_cfg, initial_params=initial_params)
    if train_cfg.telemetry:
        # name the regime the vote-health records will be in: only the
        # tally wires carry exact margins; the ±1-proxy wires zero the
        # histogram by design (train/telemetry.tally_wire)
        from distributed_lion_tpu.train.telemetry import tally_wire

        print("[run_clm] vote-health telemetry on: margin histogram "
              + ("EXACT (tally wire "
                 if tally_wire(trainer.cfg.wire) else "UNAVAILABLE (proxy wire ")
              + f"{trainer.cfg.wire}); drained every "
              f"{train_cfg.logging_steps} steps"
              + (", NaN sentinel armed" if train_cfg.nan_sentinel else ""))
    if train_cfg.vote_guard != "off":
        world = trainer.world
        # the guard's OWN resolved quorum — never re-derive the auto rule
        quorum = trainer._guard.min_quorum
        print(f"[run_clm] vote guard {train_cfg.vote_guard.upper()}: "
              f"per-worker ballot health inside the step (nonfinite / "
              f"frozen / outlier), quarantine after {train_cfg.guard_strikes}"
              f" strikes, readmission probe after {train_cfg.guard_cooldown} "
              f"steps, refusing below quorum {quorum}/{world}"
              + ("" if train_cfg.vote_guard == "enforce"
                 else " (observe: elections untouched)"))
    native = make_native_pipeline(
        data_args, train_cfg.block_size, model_cfg.vocab_size,
        trainer.global_train_batch(), train_cfg.seed,
    )
    if native is not None:
        it, eval_blocks, _loader = native
        # stamp the SERVED shard fleet into every checkpoint's manifest
        # meta: block indexing is a pure function of this list, so a
        # resumed run must see the identical fleet or its deterministic
        # replay (the batches_consumed fast-forward) silently streams
        # different data than the original run consumed
        trainer.data_meta["data_shards"] = _loader.shards
        if trainer.step_count > 0:
            meta = (trainer.checkpointer.manifest_meta(trainer.step_count)
                    if trainer.checkpointer and train_cfg.ckpt_integrity
                    else None) or {}
            old = meta.get("data_shards")
            if old is not None and list(old) != list(_loader.shards):
                raise RuntimeError(
                    f"resuming from step {trainer.step_count} but the "
                    f"served shard fleet changed: checkpoint recorded "
                    f"{old}, this run would serve {_loader.shards} "
                    f"(skipped: {_loader.skipped_shards}); the "
                    "deterministic data replay would diverge from the "
                    "original run. Restore the original shards (or start "
                    "fresh with --resume_from_checkpoint false / a new "
                    "--output_dir)")
            if old is None and _loader.skipped_shards:
                # pre-stamp checkpoint (or integrity off): the original
                # fleet is unknown and THIS run's fleet just shrank —
                # refuse conservatively rather than risk a divergent replay
                raise RuntimeError(
                    f"resuming from step {trainer.step_count} but "
                    f"{len(_loader.skipped_shards)} shard(s) failed to "
                    f"load ({_loader.skipped_shards}) and the checkpoint "
                    "predates shard-fleet stamping — cannot prove the "
                    "deterministic replay matches. Restore the shard(s) "
                    "(or start fresh with --resume_from_checkpoint false "
                    "/ a new --output_dir)")
    else:
        train_blocks, eval_blocks = load_blocks(
            data_args, train_cfg.block_size, model_cfg.vocab_size
        )
        it = batch_iterator(train_blocks, trainer.global_train_batch(), seed=train_cfg.seed)
    try:
        trainer.train(it, eval_blocks=eval_blocks)
        if trainer.preempted:
            # drained + emergency checkpoint already durable; exit 0 so the
            # watcher restarts this command into a normal resume
            print("[run_clm] preempted: "
                  + ("checkpoint durable, " if trainer.checkpointer
                     else "NO checkpointer (no --output_dir) — nothing "
                          "saved, ")
                  + "exiting cleanly")
            return
        if eval_blocks is not None and len(eval_blocks):
            trainer.evaluate(eval_blocks)
        if trainer.checkpointer:
            trainer.save()
        if train_cfg.output_dir or model_args.hf_export:
            export = trainer.params
            if train_cfg.pipeline_parallel > 1:
                if family == "gpt2":
                    from distributed_lion_tpu.models.gpt2_pipe import (
                        unpipeline_params)

                    export = unpipeline_params(export, model_cfg.n_layer)
                else:
                    from distributed_lion_tpu.models.llama_pipe import (
                        llama_unpipeline_params)

                    export = llama_unpipeline_params(export, model_cfg.n_layer)
        if train_cfg.output_dir:
            # portable single-file export (HF save_pretrained role) —
            # consumed by cli/run_generate
            from distributed_lion_tpu.utils.serialization import save_pytree

            save_pytree(f"{train_cfg.output_dir}/model.npz", export)
        if model_args.hf_export:
            # HF save_pretrained layout (run_clm.py:611-622's save_model;
            # loadable by GPT2LMHeadModel.from_pretrained) — dense
            # architectures only (guarded before training starts)
            import jax

            from distributed_lion_tpu.models.hf_export import (
                copy_tokenizer_files,
                gpt2_to_hf,
                llama_to_hf,
                write_model_card,
            )

            to_hf = llama_to_hf if family == "llama" else gpt2_to_hf
            to_hf(jax.device_get(export), model_cfg, model_args.hf_export)
            copy_tokenizer_files(data_args.tokenizer_name, model_args.hf_export)
            write_model_card(
                model_args.hf_export, model_type=family,
                train_summary={
                    "optimizer": "distributed-lion" if train_cfg.lion else "adamw",
                    "async_grad": train_cfg.async_grad,
                    # trainer.cfg, not train_cfg: the card must record the
                    # wire that actually ran, not the 'auto' sentinel
                    "wire": trainer.cfg.wire,
                    "vote_every": trainer.cfg.vote_every,
                    "steps": train_cfg.max_steps,
                    "learning_rate": train_cfg.learning_rate,
                    "weight_decay": train_cfg.weight_decay,
                    "global_batch": trainer.global_train_batch(),
                    "block_size": train_cfg.block_size,
                    "n_params": trainer.n_params,
                },
            )
            print(f"[run_clm] HF-format checkpoint at {model_args.hf_export}")
    finally:
        trainer.close()


def _train_config_cls():
    from distributed_lion_tpu.train.loop import TrainConfig

    return TrainConfig


if __name__ == "__main__":
    main()
