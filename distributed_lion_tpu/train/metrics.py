"""Metrics logging: stdout + JSONL + optional wandb.

Replaces the reference's HF `trainer.log_metrics`/wandb reporting
(/root/reference/run_clm.py:620-621, README.md:28). The reference calls
``wandb.login`` with a hardcoded API credential (run_clm.py:58-59 — a leaked
secret); here wandb activates ONLY when ``WANDB_API_KEY`` is present in the
environment (env-var/netrc auth, never a literal key in code).
"""

from __future__ import annotations

import json
import math
import os
import pathlib
import time
from typing import Optional

from distributed_lion_tpu.train.journal import emit


# The newest record each prefix logged, in this process: what a reader that
# holds no Trainer (the benchmark's per-layer readers, after the run) takes
# the step's drained counters from. One dict assignment a log interval.
_LAST: dict = {}


def last_logged(prefix: str = "train") -> Optional[dict]:
    """The metrics of the newest ``MetricsLogger.log(step, metrics,
    prefix)`` of this process, under their own names (``step`` added), or
    None before the first."""
    return _LAST.get(prefix)


class MetricsLogger:
    def __init__(self, output_dir: Optional[str] = None, run_name: str = "run",
                 use_wandb: bool = False):
        self.jsonl = None
        if output_dir:
            path = pathlib.Path(output_dir)
            path.mkdir(parents=True, exist_ok=True)
            self.jsonl = open(path / "metrics.jsonl", "a", buffering=1)
        self.wandb = None
        if use_wandb and os.environ.get("WANDB_API_KEY"):
            try:
                import wandb

                wandb.init(project=os.environ.get("WANDB_PROJECT", "distributed-lion-tpu"),
                           name=run_name)
                self.wandb = wandb
            except Exception as e:  # offline / not installed: degrade to local logs
                emit(f"[metrics] wandb unavailable ({e}); logging locally",
                     stderr=True)
        self._t0 = time.time()

    def log(self, step: int, metrics: dict, prefix: str = "train") -> None:
        _LAST[prefix] = {"step": step, **metrics}
        record = {"step": step, "elapsed_s": round(time.time() - self._t0, 3)}
        sep = "/" if prefix else ""
        record.update({f"{prefix}{sep}{k}": _scalar(v) for k, v in metrics.items()})
        line = " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                        for k, v in record.items())
        # record=False: the metrics stream's durable form IS metrics.jsonl
        # below — duplicating every row into the run journal would bloat it
        # with data the analyzer reads from the metrics file anyway
        emit(line, record=False)
        if self.jsonl:
            # allow_nan=False: json.dumps(nan) silently emits the bare token
            # `NaN`, which is NOT JSON — every strict consumer downstream
            # (jq, pandas read_json, check_evidence) chokes on the whole
            # line. Non-finite floats become null with the raw value
            # preserved under "<k>_repr" (jsonable_record), and the flag
            # turns any future regression into a loud error instead of a
            # corrupt log. scripts/validate_metrics.py is the CI check.
            self.jsonl.write(
                json.dumps(jsonable_record(record), allow_nan=False) + "\n")
        if self.wandb:
            self.wandb.log(record, step=step)

    def close(self) -> None:
        if self.jsonl:
            self.jsonl.close()
        if self.wandb:
            self.wandb.finish()


def _scalar(v):
    try:
        return float(v)
    except (TypeError, ValueError):
        return v


def jsonable_record(record: dict) -> dict:
    """Strict-JSON view of a flat metrics record: NaN/±Inf floats become
    ``null`` with the raw value preserved as a string under ``"<k>_repr"``
    (so a diverged loss is still visible in the log, in valid JSON). Lists
    (e.g. the vote-margin histogram, per-device HBM) are sanitized
    elementwise — a non-finite element inside one would corrupt the line
    just the same."""
    out: dict = {}
    for k, v in record.items():
        if isinstance(v, float) and not math.isfinite(v):
            out[k] = None
            out[f"{k}_repr"] = repr(v)
        elif isinstance(v, (list, tuple)):
            out[k] = [None if isinstance(x, float) and not math.isfinite(x)
                      else x for x in v]
            if any(isinstance(x, float) and not math.isfinite(x) for x in v):
                out[f"{k}_repr"] = repr(list(v))
        else:
            out[k] = v
    return out
