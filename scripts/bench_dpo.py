"""DPO evidence row: step rate + comm bytes for the last reference
workload without numbers (VERDICT r4 #7).

Drives the REAL CLI (`distributed_lion_tpu.cli.run_dpo` — the repaired
semantics of the reference's broken ``dpo_llama2.py``; intended loop at
/root/reference/dpo_llama2.py:216-231) end to end on synthetic preference
pairs, then distills the trainer's own metrics.jsonl into one appended row
of $DPO_BENCH_OUT (default scripts/SWEEP_r3_raw/dpo.jsonl). Honest
provenance: the row carries backend/device_kind, so a CPU-mesh row
(DLION_PLATFORM=cpu8, the caller's explicit choice) can never be mistaken
for a chip capture.

    DLION_PLATFORM=cpu8 python scripts/bench_dpo.py small:none:1:1:512:0
    python scripts/bench_dpo.py small:nf4:2:1:512:0      # on the chip

Spec grammar: model:quant_ref:batch_per_dev:accum:max_length:vocab_chunks.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEFAULTS = ["small", "none", "1", "1", "512", "0"]
STEPS = int(os.environ.get("DPO_BENCH_STEPS", "30"))
LOG_EVERY = 5


def main() -> None:
    from distributed_lion_tpu.parallel.mesh import force_cpu_platform

    force_cpu_platform()
    spec = sys.argv[1] if len(sys.argv) > 1 else ":".join(DEFAULTS)
    parts = spec.split(":")
    model, quant_ref, bs, accum, max_len, vc = (
        parts + DEFAULTS[len(parts):])[:6]

    out_dir = os.environ.get("DPO_BENCH_DIR",
                             os.path.join(REPO, "runs", "dpo_bench"))
    shutil.rmtree(out_dir, ignore_errors=True)
    argv = [
        "--model_name", model, "--dataset", "synthetic",
        "--quant_ref", quant_ref,
        "--max_length", max_len, "--max_prompt_length",
        str(max(int(max_len) // 2, 8)),
        "--num_train_samples", "512", "--size_valid_set", "32",
        "--lion", "--async_grad",
        # pin the banked-row comm methodology (same pin as
        # bench_sft_7b.py): every-step sign_psum voting, so rows rank
        # comparably across backends
        "--wire", "sign_psum", "--vote_every", "1",
        "--per_device_train_batch_size", bs,
        "--gradient_accumulation_steps", accum,
        "--vocab_chunks", vc,
        "--max_steps", str(STEPS), "--warmup_steps", "5",
        "--logging_steps", str(LOG_EVERY),
        # no mid-run eval/checkpoint pauses inside the timed window
        "--eval_steps", str(STEPS * 10), "--save_steps", str(STEPS * 10),
        "--learning_rate", "1e-4",
        "--output_dir", out_dir,
    ]
    from distributed_lion_tpu.cli.run_dpo import main as dpo_main

    t0 = time.time()
    dpo_main(argv)
    wall = time.time() - t0

    import jax

    dev = jax.devices()[0]
    rows = []
    with open(os.path.join(out_dir, "metrics.jsonl")) as f:
        for line in f:
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "train/tokens_per_sec" in d:
                rows.append(d)
    if not rows:
        raise SystemExit("[bench_dpo] no train metrics rows were logged")
    # the FIRST logged row includes compile; steady state = the rest
    steady = rows[1:] or rows
    tps = sum(r["train/tokens_per_sec"] for r in steady) / len(steady)
    row = {
        "workload": "DPO train step (policy+frozen ref, LoRA, vote-Lion)",
        "spec": spec, "model": model, "quant_ref": quant_ref,
        "batch_per_dev": int(bs), "accum": int(accum),
        "max_length": int(max_len), "vocab_chunks": int(vc),
        "steps": STEPS, "n_dev": len(jax.devices()),
        "backend": dev.platform, "device_kind": dev.device_kind,
        "tokens_per_sec_per_chip": round(tps / len(jax.devices()), 1),
        "comm_bytes_per_step": steady[-1].get("train/comm_bytes_per_step"),
        "final_loss": round(rows[-1].get("train/loss", 0.0), 4),
        "wall_s": round(wall, 1),
    }
    out_path = os.environ.get(
        "DPO_BENCH_OUT", os.path.join(REPO, "scripts", "SWEEP_r3_raw",
                                      "dpo.jsonl"))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
