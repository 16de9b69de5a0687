"""Text-generation entry point: load an exported model, decode with KV cache.

Net-new vs the reference (it has no inference path). Completes the train →
export → use cycle: ``run_clm``/``run_sft`` export ``model.npz`` via
utils.serialization; this CLI loads it and generates.

    python -m distributed_lion_tpu.cli.run_generate \
        --model_path ./out/model.npz --model_family gpt2 --model_name tiny \
        --prompt "Question: " --max_new_tokens 64 --temperature 0.8 --top_k 40

With no --model_path, random-init weights are used (smoke mode).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional


@dataclasses.dataclass
class GenerateArguments:
    model_path: Optional[str] = None  # .npz from utils.serialization, or an
    # HF save_pretrained directory (hf_export/--merged_output output, family
    # auto-detected); unset → random init (smoke mode)
    model_family: str = "gpt2"  # gpt2 | llama | dots3, joyai, laguna, ling, minicpm_sala, xing (run_serve only)
    model_name: str = "tiny"    # gpt2: gpt2_124m | tiny; llama: llama2_7b | llama3_8b | tiny;
    # joyai, laguna, ling: tiny | the path of a JSON file with the published
    # config.json keys
    tokenizer_name: Optional[str] = None  # HF cache name; byte tokenizer otherwise
    prompt: List[str] = dataclasses.field(default_factory=list)
    # one or more prompts (--prompt "a" "b" "c"); several prompts batch into
    # ONE left-padded generate call with per-row position offsets — each
    # row attends/positions exactly as its solo run would (greedy outputs
    # are identical to solo runs; see main() on sampling). With neither
    # --prompt nor --prompt_file, "Hello" is the smoke default
    prompt_file: Optional[str] = None  # one prompt per line; appended to
    # --prompt (blank lines skipped)
    max_new_tokens: int = 64
    temperature: float = 0.8
    top_k: Optional[int] = 40
    top_p: Optional[float] = None  # nucleus sampling mass (e.g. 0.95)
    seed: int = 0
    vocab_size: Optional[int] = None
    moe_experts: int = 0  # > 0: the checkpoint is Switch-MoE (gpt2 only;
    # must match the training --moe_experts/--moe_every — model.npz holds
    # no config stamp, and the serve engine's expert-parallel and
    # capacity-aware paths key off the declared config). HF-dir
    # checkpoints ignore it (no MoE export format).
    moe_every: int = 2


def _is_hf_dir(path: Optional[str]) -> bool:
    import os

    # A training --output_dir holds model.npz but no config.json; only route
    # directories that look like save_pretrained output to the HF importer.
    return bool(path) and os.path.isdir(path) and os.path.isfile(
        os.path.join(path, "config.json"))


def resolve_model_path(args: GenerateArguments) -> None:
    """Point ``args.model_path`` at the weights file when it names a
    training ``--output_dir`` (the weights live at ``<dir>/model.npz``)."""
    import os

    if (args.model_path and os.path.isdir(args.model_path)
            and not _is_hf_dir(args.model_path)):
        npz = os.path.join(args.model_path, "model.npz")
        if os.path.isfile(npz):
            args.model_path = npz
        else:
            raise FileNotFoundError(
                f"{args.model_path!r} is a directory with neither config.json "
                "(HF checkpoint) nor model.npz (training output)"
            )


def check_checkpoint(args: GenerateArguments):
    """Tokenizer + a device-free look at the checkpoint: what a parent that
    must stay off JAX (``run_serve --replica_procs`` — the children own the
    chip) can do to fail fast on a bad ``--model_path`` before spawning
    workers that would each fail slower. Returns the tokenizer."""
    import numpy as np

    from distributed_lion_tpu.data.tokenizer import load_tokenizer

    tok = load_tokenizer(args.tokenizer_name)
    resolve_model_path(args)
    if _is_hf_dir(args.model_path):
        from distributed_lion_tpu.models import hf_import

        hf_import.detect_family(args.model_path)  # numpy/json only
    elif args.model_path:
        with np.load(args.model_path) as data:  # reads the zip directory
            if not data.files:
                raise ValueError(
                    f"checkpoint {args.model_path!r} holds no arrays")
    return tok


# families with no dense-cache decode (module and ``<family>_init`` by the
# family's name): their configuration class
PAGED_ONLY = {"dots3": "Dots3Config", "joyai": "JoyAIConfig",
              "laguna": "LagunaConfig",
              "ling": "LingConfig", "minicpm_sala": "MiniCPMSalaConfig",
              "xing": "XingConfig"}


def build(args: GenerateArguments):
    import jax

    from distributed_lion_tpu.data.tokenizer import load_tokenizer
    from distributed_lion_tpu.utils.serialization import load_pytree

    tok = load_tokenizer(args.tokenizer_name)
    vocab = args.vocab_size or tok.vocab_size
    resolve_model_path(args)

    hf_params = hf_cfg = None
    if _is_hf_dir(args.model_path):
        # an HF save_pretrained directory (e.g. run_clm --hf_export or
        # run_sft --merged_output <dir>): import it, family auto-detected
        from distributed_lion_tpu.models import hf_import

        family = hf_import.detect_family(args.model_path)
        if family != args.model_family:
            print(f"[run_generate] --model_family {args.model_family} -> "
                  f"{family} (detected from checkpoint)")
            args.model_family = family
        loader = (hf_import.gpt2_from_hf if family == "gpt2"
                  else hf_import.llama_from_hf)
        hf_params, hf_cfg = loader(args.model_path)

    if args.model_family == "gpt2":
        from distributed_lion_tpu.models.gpt2 import (
            GPT2Config, gpt2_decode, gpt2_init, gpt2_init_cache,
        )

        moe_kw = ({"moe_experts": args.moe_experts,
                   "moe_every": args.moe_every}
                  if args.moe_experts > 0 else {})
        cfg = hf_cfg or (
            GPT2Config.tiny if args.model_name == "tiny" else GPT2Config.gpt2_124m
        )(vocab_size=vocab, **moe_kw)
        params = (hf_params if hf_params is not None
                  else load_pytree(args.model_path) if args.model_path
                  else gpt2_init(jax.random.key(args.seed), cfg))
        decode = partial(
            lambda c, p, t, k, pos, off=None: gpt2_decode(p, t, c, k, pos, off),
            cfg)
        init_cache = partial(gpt2_init_cache, cfg)
    elif args.model_family == "llama":
        from distributed_lion_tpu.models.llama import (
            LlamaConfig, llama_decode, llama_init, llama_init_cache,
        )

        cfg = hf_cfg or LlamaConfig.named(args.model_name, vocab_size=vocab)
        params = (hf_params if hf_params is not None
                  else load_pytree(args.model_path) if args.model_path
                  else llama_init(jax.random.key(args.seed), cfg))
        decode = partial(
            lambda c, p, t, k, pos, off=None: llama_decode(p, t, c, k, pos, off),
            cfg)
        init_cache = partial(llama_init_cache, cfg)
    elif args.model_family in PAGED_ONLY:
        import importlib

        # --model_name: 'tiny', or the path of a JSON file with the
        # published config.json keys (benchmark/configs/<configuration>.json)
        family = args.model_family
        module = importlib.import_module(
            f"distributed_lion_tpu.models.{family}")
        cfg = getattr(module, PAGED_ONLY[family]).named(
            args.model_name,
            **({"vocab_size": vocab} if args.model_name == "tiny" else {}))
        params = (load_pytree(args.model_path) if args.model_path
                  else getattr(module, f"{family}_init")(
                      jax.random.key(args.seed), cfg))
        # no dense-cache decode: these families serve through the paged
        # engine alone (latent pages, rings, slot-indexed states: run_serve)
        decode = init_cache = None
    else:
        raise ValueError(f"unknown model family {args.model_family!r}")
    return tok, cfg, params, decode, init_cache


def main(argv=None):
    import os

    import jax

    from distributed_lion_tpu.parallel.mesh import force_cpu_platform
    from distributed_lion_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    force_cpu_platform()
    enable_compilation_cache()
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.generate import generate
    from distributed_lion_tpu.utils.argparsing import parse_dataclasses

    (args,) = parse_dataclasses((GenerateArguments,), argv)
    tok, cfg, params, decode, init_cache = build(args)
    if decode is None:
        raise ValueError(
            f"run_generate has no dense-cache decode for --model_family "
            f"{args.model_family}: serve it with run_serve")
    prompts = list(args.prompt)
    if args.prompt_file:
        with open(args.prompt_file) as f:
            prompts += [ln.rstrip("\n") for ln in f if ln.strip()]
        if not prompts:
            raise ValueError(
                f"no prompts: --prompt_file {args.prompt_file!r} holds no "
                "non-blank lines and no --prompt was given")
    elif not prompts:
        prompts = ["Hello"]  # the historical smoke default
    # NOTE: at temperature > 0 the batched draws share one PRNG stream
    # over the [B, V] batch, so SAMPLED continuations differ from solo
    # invocations (greedy rows are identical to solo runs — pinned by
    # test); per-request streams live in the serving engine (run_serve)
    ids = [tok.encode(p, add_bos=False) or [0] for p in prompts]
    T = max(len(i) for i in ids)
    # LEFT-pad to the longest prompt: every row's last prompt token sits at
    # slot T-1 (so one shared sampling position), and the pad widths flow
    # to the model as per-row position offsets + attention masks — each
    # row attends and positions exactly as its solo run would
    batch = np.zeros((len(ids), T), np.int32)
    for r, seq in enumerate(ids):
        batch[r, T - len(seq):] = seq
    lens = jnp.asarray([len(seq) for seq in ids], jnp.int32)
    out = generate(
        decode, init_cache, params, jnp.asarray(batch), args.max_new_tokens,
        key=jax.random.key(args.seed), temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p,
        eos_id=getattr(tok, "eos_id", None),
        prompt_lens=None if len(ids) == 1 else lens,
    )
    texts = [tok.decode([int(t) for t in row]) for row in out]
    for p, t in zip(prompts, texts):
        print(p + t)
    return texts[0] if len(texts) == 1 else texts


if __name__ == "__main__":
    main()
