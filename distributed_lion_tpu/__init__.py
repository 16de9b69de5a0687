"""distributed_lion_tpu — a TPU-native framework with the capabilities of
kyleliang919/distributed-lion-pytorch (arXiv:2404.00438).

Brand-new JAX/XLA/Pallas design, not a port:

- ``ops.codec``      — 1-bit sign codec (real uint8 wire format; fixes the
                       reference's accidental int64, distributed_lion.py:75-77).
- ``optim.lion``     — local Lion as a pure optax-style transform
                       (semantics of reference distributed_lion.py:47-59).
- ``optim.distributed_lion`` — majority-vote Distributed Lion: sign votes are
                       psum-reduced on the interconnect (or bit-packed and
                       all-gathered) inside the jit'd update, replacing the
                       reference's per-tensor NCCL all_gather + torch.mode
                       (distributed_lion.py:61-136).
- ``parallel``       — mesh construction, vote collectives, byte accounting,
                       ring attention / sequence parallelism.
- ``models``         — GPT-2- and Llama-class decoders in pure JAX, LoRA.
- ``data``           — fixed-block packing (group_texts), SFT/DPO pipelines.
- ``train``          — jit train loop with NO gradient sync (the reference's
                       AsyncTrainer no_sync contract, async_trainer.py:15),
                       schedules, eval, Orbax checkpointing, metrics.
- ``cli``            — run_clm / run_sft / run_dpo entry points with the
                       reference's ``--lion`` / ``--async_grad`` surface.
"""

__version__ = "0.1.0"
