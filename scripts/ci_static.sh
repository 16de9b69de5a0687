#!/bin/bash
# Static-analysis gate (ISSUE 4): ruff baseline + graft-check tier 1 +
# shellcheck over the runbook scripts. Invoked by check_evidence's
# `static` stage (so it runs on every runbook pass / watcher poll) and
# runnable standalone. Exit 0 = clean.
#
# Tool availability is gated, not assumed: the gate must be meaningful on
# a bare box (no ruff/shellcheck wheels, no jax) — graft-check tier 1 is
# pure stdlib and ALWAYS runs (by file path, so even the package's jax
# import is not required); ruff/shellcheck join in when installed, using
# the pyproject.toml / default configs. The jaxpr tier (tier 2) is NOT
# here: it needs a traceable step, so the runbook captures it separately
# via `python -m distributed_lion_tpu.analysis --tier2 --json-out ...`.
set -u
cd "$(dirname "$0")/.."
rc=0

if command -v ruff >/dev/null 2>&1; then
  ruff check distributed_lion_tpu scripts || rc=1
else
  echo "ci_static: ruff not installed — skipped (baseline lives in pyproject.toml)"
fi

# graft-check tier 1 over the package (pure stdlib, loaded by file path)
python distributed_lion_tpu/analysis/lint.py distributed_lion_tpu || rc=1

# serve-plane graft-check (ISSUE 19): like tier 2, the traced matrix runs
# in the runbook (`python -m distributed_lion_tpu.analysis serve-check
# --json-out runs/static/serve_check.json`, stage 0b) — here the BANKED
# artifact is held to the strict schema (stdlib validate_metrics: every
# matrix cell present and ok, inventories re-derived equal, zero host
# callbacks, donation present, compile counts within budget)
if [ -f runs/static/serve_check.json ]; then
  python scripts/validate_metrics.py runs/static/serve_check.json || rc=1
else
  echo "ci_static: runs/static/serve_check.json not captured yet — run" \
       "python -m distributed_lion_tpu.analysis serve-check --json-out it"
  rc=1
fi

if command -v shellcheck >/dev/null 2>&1; then
  shellcheck scripts/*.sh || rc=1
else
  echo "ci_static: shellcheck not installed — skipped"
fi

exit $rc
