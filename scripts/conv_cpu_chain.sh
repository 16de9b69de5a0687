#!/bin/bash
# Reduced-scale convergence run on the CPU — for the round-4 review's item
# #6 (real-corpus convergence with eval accuracy/perplexity + mid-run
# checkpoint resume). Waits for the CPU parity legs to finish (one host
# core: running both at once just slows the critical path), then trains
# gpt2_small (the shared 12.7M reduced evidence preset) on the parity
# corpus through the native BPE for 2000 steps, writing eval acc/ppl to
# runs/convergence_cpu/metrics.jsonl. The first segment is deliberately
# killed by a timeout so the second segment EXERCISES run_clm's Orbax
# resume-autodetect — resume is part of the evidence, not an accident.
#
#   nohup bash scripts/conv_cpu_chain.sh > /tmp/conv_cpu_chain.log 2>&1 &
set -u
cd "$(dirname "$0")/.."
stamp() { date -u +%FT%TZ; }

# ---- wait for the parity driver chain to release the core
while pgrep -f 'loss_parity.py --phase run' > /dev/null \
   || pgrep -f 'parity_cpu_driver.sh' > /dev/null; do
  sleep 120
done
echo "$(stamp) parity chain done; starting reduced convergence run"

if python scripts/check_evidence.py conv; then
  echo "$(stamp) convergence already captured; nothing to do"
  exit 0
fi

mkdir -p runs/convergence_cpu
if [ ! -s runs/convergence_cpu/tokens.bin ]; then
  python - <<'EOF'
import numpy as np
a = np.load("runs/parity/tokens.npy", mmap_mode="r")
assert int(np.asarray(a[:1_000_000]).max()) < 65536
np.asarray(a, dtype=np.uint16).tofile("runs/convergence_cpu/tokens.bin")
EOF
fi

run_segment() { # $1 = timeout seconds (0 = none)
  local t="$1"; shift
  local pre=(env DLION_PLATFORM=cpu)
  [ "$t" != 0 ] && pre=(timeout "$t" env DLION_PLATFORM=cpu)
  nice -n 15 "${pre[@]}" python -m distributed_lion_tpu.cli.run_clm \
    --model_name gpt2_small --dataset bin:runs/convergence_cpu/tokens.bin \
    --vocab_size 16384 --lion --async_grad \
    --wire sign_psum --vote_every 1 \
    --per_device_train_batch_size 4 --gradient_accumulation_steps 1 \
    --block_size 256 --max_steps 2000 --warmup_steps 100 \
    --learning_rate 1e-4 --weight_decay 0.1 \
    --eval_steps 250 --eval_iters 10 --logging_steps 25 \
    --save_steps 250 --save_total_limit 2 \
    --param_dtype float32 --compute_dtype bfloat16 \
    --vocab_chunks 0 --remat false \
    --output_dir runs/convergence_cpu
}

# segment 1: capped so segment 2 must resume from the Orbax checkpoint
run_segment 2700
echo "$(stamp) segment 1 done (rc=$?); resuming to completion"
for attempt in 1 2 3; do
  if run_segment 0; then
    break
  fi
  echo "$(stamp) segment attempt $attempt failed; retrying"
  sleep 60
done

if python scripts/check_evidence.py conv; then
  for p in runs/convergence_cpu/metrics.jsonl; do
    [ -e "$p" ] && git add "$p"
  done
  git commit -q -m "Capture reduced CPU convergence run (eval acc/ppl, mid-run resume)" \
    && echo "$(stamp) convergence run committed"
else
  echo "$(stamp) convergence run FAILED the evidence check"
fi
echo "$(stamp) conv chain done"
