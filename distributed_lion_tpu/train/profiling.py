"""Tracing / profiling hooks for the train loop.

The reference has no profiling at all (SURVEY §5: no profiler imports; its
only perf statement is README.md:2's "currently slow" admission). Here
profiling is a first-class trainer subsystem:

- :class:`StepProfiler` — captures a ``jax.profiler`` device trace (viewable
  in TensorBoard / Perfetto) for a configurable window of steps, and tags
  every step with ``StepTraceAnnotation`` so the trace viewer groups ops by
  step. Capturing a bounded window (not the whole run) keeps trace files
  small and the steady-state steps representative.
  While its window is open the run journal's span gate is on too
  (train/journal.span reads ``TraceAnnotation.is_enabled()``), so the
  loop's spans land in the same trace.
- :func:`comm_report` — analytic bytes-on-the-wire accounting for the vote
  collective (ops/codec.wire_bytes_per_param), the number BASELINE.md's
  ≤1/32-of-bf16-all-reduce budget is judged against.
"""

from __future__ import annotations

from typing import Optional

from distributed_lion_tpu.ops.codec import wire_bytes_per_param
from distributed_lion_tpu.train.journal import emit


class StepProfiler:
    """Trace steps [start_step, start_step + num_steps) to ``trace_dir``.

    Inactive when ``trace_dir`` is None — zero overhead beyond an int
    compare per step. ``annotate()`` returns a ``StepTraceAnnotation``
    context while tracing (so ops group per-step in the viewer) and a
    null context otherwise.
    """

    def __init__(self, trace_dir: Optional[str], start_step: int = 10,
                 num_steps: int = 3):
        self.trace_dir = trace_dir
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self.stop_step = self.start_step + self.num_steps
        self._active = False
        self._done = False

    def maybe_start(self, step: int) -> None:
        # >= (not ==) so a checkpoint-resumed run that re-enters past the
        # configured start still captures a window (anchored at the first
        # step it actually sees)
        if (self.trace_dir and not self._active and not self._done
                and step >= self.start_step):
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self.stop_step = step + self.num_steps
            self._active = True

    def annotate(self, step: int):
        if self._active:
            import jax

            return jax.profiler.StepTraceAnnotation("train", step_num=step)
        import contextlib

        return contextlib.nullcontext()

    def maybe_stop(self, step: int, sync=None) -> None:
        """Stop at the window end. ``sync`` (e.g. the last metrics pytree) is
        block_until_ready'd first so in-flight device work lands in the
        trace."""
        if self._active and step >= self.stop_step:
            import jax

            if sync is not None:
                jax.block_until_ready(sync)
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            emit(f"[profiler] trace for steps [{self.start_step}, "
                 f"{self.stop_step}) written to {self.trace_dir}")

    def close(self, sync=None) -> None:
        if self._active:
            self.maybe_stop(self.stop_step, sync)


def peak_hbm_per_device() -> Optional[list[float]]:
    """Peak device-memory high-water mark in GiB for EVERY local device (in
    ``jax.local_devices()`` order), or None where the backend exposes no
    memory_stats (host CPU). Per-device values matter because sharded
    workloads are limited by the WORST device — an imbalanced shard or a
    stray buffer on one chip is invisible in a device-0-only reading."""
    try:
        import jax

        out = []
        for d in jax.local_devices():
            ms = d.memory_stats()
            if not ms or "peak_bytes_in_use" not in ms:
                return None
            out.append(round(ms["peak_bytes_in_use"] / 2**30, 3))
        return out or None
    except Exception:  # graft: disable=DLT006
        return None  # metric probe, not a code path: any backend without
        # (or with quirky) memory_stats must read as "no HBM metric", never
        # take down the training loop that polls this at log cadence


def peak_hbm_gb() -> Optional[float]:
    """The high-water mark across ALL local devices (the number an OOM is
    actually decided by), not device 0's alone."""
    per = peak_hbm_per_device()
    return max(per) if per else None


def comm_report(num_params: int, world: int, wire: str,
                steps_per_sec: Optional[float] = None,
                vote_every: int = 1, accum_steps: int = 1,
                vote_buckets: int = 1, dcn_pipeline_depth: int = 0) -> dict:
    """Vote-collective wire accounting (+ bandwidth when a rate is known).

    ``comm_overlap_frac`` is the ANALYTIC pipelineable share of the wire
    under ``vote_buckets`` bucketing: the optimizer overlaps bucket k's
    collective with bucket k−1's fused apply, so every bucket after the
    first can ride behind compute — 0.0 for the monolithic vote, ≈(B−1)/B
    for B equal buckets. The measured counterpart is the benchmark's
    ``vote_exposed_ms.train4``: the wire time no compute covers.

    ``dcn_overlap_frac`` (hier wire only) is the analytic share of the
    level-2 (DCN) leg's LATENCY eligible to leave the critical path under
    ``--dcn_pipeline_depth``: 1.0 once the leg rides the cross-step ring
    (depth ≥ 1 — the whole round trip hides behind d steps of compute),
    0.0 for the synchronous wire. Bytes are depth-invariant. The measured
    counterpart comes from the bench_dcn ablation (scripts/bench_dcn.py).
    """
    acct = wire_bytes_per_param(num_params, world, wire,
                                vote_every=vote_every, accum_steps=accum_steps,
                                vote_buckets=vote_buckets,
                                dcn_pipeline_depth=dcn_pipeline_depth)
    out = {
        "wire": acct["wire"],
        "comm_bytes_per_step": acct["bytes_per_step"],
        "comm_bits_per_param": acct["bits_per_param"],
        "comm_bits_per_param_per_microbatch": acct["bits_per_param_per_microbatch"],
        "vote_buckets": acct["vote_buckets"],
        "comm_overlap_frac": acct["overlappable_wire_frac"],
        "vs_bf16_allreduce": acct["vs_bf16_allreduce"],
        "vs_reference_wire": acct["bytes_per_step"]
        / max(acct["reference_bytes_per_step"], 1),
    }
    if "dcn_bytes_per_step" in acct:  # hier wire: the slow-fabric leg alone
        out["comm_dcn_bytes_per_step"] = acct["dcn_bytes_per_step"]
        out["comm_dcn_bits_per_param"] = acct["dcn_bits_per_param"]
        out["dcn_pipeline_depth"] = acct["dcn_pipeline_depth"]
        out["dcn_overlap_frac"] = acct["dcn_overlap_frac"]
    if steps_per_sec:
        out["comm_mbytes_per_sec"] = acct["bytes_per_step"] * steps_per_sec / 1e6
    return out
