#!/bin/bash
# Sequential reduced-scale CPU parity legs — for the round-4 review's
# next-steps #1/#3: capture parity:local/vote/lazy as 2000-step
# curves at >=10M params on the CPU backend (runs/parity_cpu), so the
# round's scientific core claim (vote-Lion trajectory == local Lion,
# /root/reference/README.md:75-83) has committed data without any chip
# time. Full-scale TPU legs in runs/parity supersede these:
# the whole driver stands down only when runs/parity holds the COMPLETE
# qualifying set (all three modes) — a partial full-scale capture must not
# split the leg set across directories, because the parity:PASS criterion
# (check_evidence.parity_mad) only compares legs within one directory.
#
#   nohup bash scripts/parity_cpu_driver.sh > /tmp/parity_cpu_driver.log 2>&1 &
#
# Idempotent: per-mode skip defers to check_evidence's _leg_ok (the ONE
# leg-qualification rule: f32-stamped meta + >=1900 steps), and
# loss_parity's own mid-leg checkpoint makes a killed leg resume rather
# than restart. nice'd so a concurrently-firing TPU runbook window wins
# the single host core.
set -u
cd "$(dirname "$0")/.."
stamp() { date -u +%FT%TZ; }

full_set_captured() { # all three FULL-SCALE legs qualify => stand down
  python - <<'EOF'
import sys
sys.path.insert(0, "scripts")
import check_evidence as ce
ok = all(ce._leg_ok(ce._load_leg("parity", m))
         for m in ("local", "vote", "lazy"))
sys.exit(0 if ok else 1)
EOF
}

captured() { # $1 = mode; qualification delegated to check_evidence._leg_ok
  # on the CPU directory only (presence-based, not the numeric-PASS gate: a
  # deterministic failing leg would re-run forever producing identical data)
  python - "$1" <<'EOF'
import sys
sys.path.insert(0, "scripts")
import check_evidence as ce
sys.exit(0 if ce._leg_ok(ce._load_leg("parity_cpu", sys.argv[1])) else 1)
EOF
}

if full_set_captured; then
  echo "$(stamp) full-scale runs/parity leg set already captured; no CPU legs needed"
  exit 0
fi

for mode in local vote lazy; do
  if captured "$mode"; then
    echo "$(stamp) parity_cpu:$mode leg already qualifies; skipping"
    continue
  fi
  # retry transient failures (loss_parity's mid-leg checkpoint makes a
  # retry resume, not restart); after 3 strikes move on to the next mode
  # rather than hard-exiting — one stuck leg must not stall the whole
  # fallback program (code-review r5)
  ok=0
  for attempt in 1 2 3; do
    echo "$(stamp) running reduced parity leg: $mode (attempt $attempt)"
    if nice -n 15 python scripts/loss_parity.py --phase run --mode "$mode" \
        --reduced --steps 2000; then
      ok=1; break
    fi
    echo "$(stamp) leg $mode attempt $attempt failed"
    sleep 60
  done
  if [ "$ok" = 1 ]; then
    git add runs/parity_cpu && git commit -q \
      -m "Capture reduced CPU parity leg: $mode" && \
      echo "$(stamp) committed $mode leg"
  else
    echo "$(stamp) leg $mode FAILED after 3 attempts; continuing"
  fi
done
python scripts/loss_parity.py --phase report --out runs/parity_cpu \
  && git add runs/parity_cpu && git commit -q -m "Parity report for reduced CPU legs" \
  && echo "$(stamp) report committed"
echo "$(stamp) parity driver done"
