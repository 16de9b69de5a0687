"""Single source of truth for "is this evidence stage captured?" — one
definition for whatever drives a capture (per-stage skip guards) and
whatever decides it is finished (the `automation` exit condition), so the
two can never disagree about what "captured" means. (The round-3/4 shell
runbook and watcher that called it were deleted in PR 21; the stage
judgments stay until ROADMAP D2 replaces them.)

    python scripts/check_evidence.py parity local   # exit 0 = captured
    python scripts/check_evidence.py sweep2
    python scripts/check_evidence.py sft7b
    python scripts/check_evidence.py bench_best
    python scripts/check_evidence.py overlap        # buckets {1,4,16} rows
    python scripts/check_evidence.py telemetry      # vote-health JSONL
    python scripts/check_evidence.py static         # graft-check both tiers
    python scripts/check_evidence.py vote_guard     # poisoned-run rescue
    python scripts/check_evidence.py journal        # run-journal attribution
    python scripts/check_evidence.py dcn_overlap    # pipelined hier DCN leg
    python scripts/check_evidence.py serving        # paged-KV decode bench
    python scripts/check_evidence.py speculative    # draft/verify/commit
    python scripts/check_evidence.py tp_serving     # TP decode + prefix share
    python scripts/check_evidence.py serve_resilience  # replica fault matrix
    python scripts/check_evidence.py fleet_resilience  # SIGKILLed processes
    python scripts/check_evidence.py moe_serving    # MoE paged decode + ep
    python scripts/check_evidence.py elasticity     # live worker leave/join
    python scripts/check_evidence.py all

parity:vote / parity:lazy are STRICT since ISSUE 6: a leg counts as
captured only when the pre-registered numeric criterion PASSES (mean
|Δloss| vs local over the tail ≤ PARITY_EPS_NATS), not on mere presence.
The watcher exit condition (`automation`) still judges presence — see
_AUTOMATION_OVERRIDES.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "scripts", "SWEEP_r3_raw")
PARITY_MIN_STEP = 1900
# full-scale TPU legs take precedence; runs/parity_cpu holds the reduced
# (>=10M-param, short-seq) CPU legs captured without a chip —
# legs are only ever COMPARED within one directory (same scale/config)
PARITY_DIRS = ("parity", "parity_cpu")
# ---- the pre-registered numeric parity criterion (VERDICT r4 #4), pinned
# BEFORE the data lands: over the last quarter of training, the mean
# per-logged-step |loss(vote) - loss(local)| must be within EPS nats (legs
# share seed => identical per-step batches, so the gap is optimizer
# trajectory, not data noise). Same bound for the lazy (vote_every=4) leg.
# loss_parity.py --phase report imports these and prints PASS/FAIL.
PARITY_EPS_NATS = 0.05
PARITY_TAIL_FRAC = 0.75


def _load_leg(dirname: str, mode: str):
    """(meta, {step: loss}) from runs/<dirname>/<mode>.jsonl, or None.
    ``dirname`` may also be an absolute directory (loss_parity's report
    phase reuses this loader on an arbitrary --out dir)."""
    base = (dirname if os.path.isabs(dirname)
            else os.path.join(REPO, "runs", dirname))
    meta, curve = None, {}
    try:
        with open(os.path.join(base, f"{mode}.jsonl")) as f:
            for line in f:
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn last line from a mid-write crash
                if d.get("meta"):
                    meta = d
                elif "loss" in d and "step" in d:
                    curve[d["step"]] = d["loss"]
    except OSError:
        return None
    return (meta, curve) if meta is not None else None


def _leg_ok(leg) -> bool:
    """Captured = enough steps AND stamped as an f32-master-params run —
    bf16-era curves had frozen large-magnitude params (Lion's ±lr is below
    bf16 ULP there) and must not satisfy the evidence check."""
    if leg is None:
        return False
    meta, curve = leg
    return (meta.get("param_dtype") == "float32"
            and curve and max(curve) >= PARITY_MIN_STEP)


def _metas_comparable(a: dict, b: dict) -> bool:
    """Two legs may only be numerically compared when every config stamp
    they BOTH carry (scale, seed, batch, precision, step budget — all but
    the mode itself) agrees; intersection semantics keep older metas
    without the round-5 scale stamps comparable."""
    keys = (set(a) & set(b)) - {"mode", "meta", "backend"}
    return all(a[k] == b[k] for k in keys)


def parity(mode: str) -> bool:
    """Presence check (the watcher/automation exit condition): a
    qualifying leg exists in either parity directory. The evidence-facing
    ``parity:*`` stages use :func:`parity_strict` — presence alone is NOT
    capture for the vote/lazy legs anymore (ISSUE 6: a present-but-
    diverged curve must not read 'captured'); presence stays the
    AUTOMATION semantics because a failing numeric criterion is
    deterministic in the seed and needs a human, not an infinite watcher
    loop re-burning identical 2000-step legs."""
    return any(_leg_ok(_load_leg(d, mode)) for d in PARITY_DIRS)


def parity_strict(mode: str) -> bool:
    """The ``parity:<mode>`` stage: a qualifying leg exists AND — for the
    vote/lazy comparison legs — the pre-registered numeric criterion
    PASSES in the directory providing it (mean |Δloss| vs the same-dir
    local leg over the last (1 − PARITY_TAIL_FRAC) of steps ≤
    PARITY_EPS_NATS — with 10-step logging over 2000 steps that tail is
    the last 500 steps). ``local`` is the baseline leg: presence only."""
    if mode == "local":
        return parity("local")
    for d in PARITY_DIRS:
        if not _leg_ok(_load_leg(d, mode)):
            continue
        m = parity_mad(d, mode)
        if m is not None and m <= PARITY_EPS_NATS:
            return True
    return False


def parity_full(mode: str) -> bool:
    """Full-scale (runs/parity) presence only — the TPU runbook's stage-6
    skip guard. Reduced CPU legs satisfy parity()/the watcher, but must
    NOT stop a live TPU window from capturing the flagship-scale legs the
    docs say take precedence (code-review r5)."""
    return _leg_ok(_load_leg("parity", mode))


def parity_mad(dirname: str, mode: str):
    """Mean |loss(mode) - loss(local)| over the common logged steps in the
    last (1 - PARITY_TAIL_FRAC) of training, or None when either leg in
    that directory is missing/unqualified/config-mismatched."""
    leg_l, leg_m = _load_leg(dirname, "local"), _load_leg(dirname, mode)
    if not (_leg_ok(leg_l) and _leg_ok(leg_m)):
        return None
    if not _metas_comparable(leg_l[0], leg_m[0]):
        return None
    steps = leg_l[0].get("steps", PARITY_MIN_STEP)
    tail = [s for s in sorted(set(leg_l[1]) & set(leg_m[1]))
            if s >= PARITY_TAIL_FRAC * steps]
    if not tail:
        return None
    return sum(abs(leg_m[1][s] - leg_l[1][s]) for s in tail) / len(tail)


def parity_pass() -> bool:
    """The parity:PASS stage: some directory holds a complete local leg
    plus vote AND lazy legs whose tail curves are within PARITY_EPS_NATS
    of it. This is what makes check_evidence able to FAIL on bad parity
    data, not only on absent data (VERDICT r4 #4)."""
    for d in PARITY_DIRS:
        mads = [parity_mad(d, m) for m in ("vote", "lazy")]
        if all(m is not None and m <= PARITY_EPS_NATS for m in mads):
            return True
    return False


def _window_captured(path: str, marker: dict, result_key: str) -> bool:
    """Captured = the LAST window config has a RESULT row (stages run
    sequentially, so it implies every earlier config executed). Rows are
    parsed as JSON and the marker compared field-by-field — substring
    needles were coupled to dict insertion order and separator spacing
    (advisor r4). An ERROR row for the marker config does NOT count: a
    window where every config failed fast (the backend died mid-stage but
    each config still emitted an error row) must not mark the stage captured —
    and because the files are append-mode across watcher re-fires, a
    file-global "any result row" check would be satisfied by a PREVIOUS
    window's banked rows. This is the watcher's EXIT condition only —
    earlier configs that errored transiently are retried regardless: the
    runbook's sweep stages ran UNCONDITIONALLY on every recovery and the
    sweep skipped result-row configs only, so retries cost seconds, not
    chip time."""
    try:
        with open(path) as f:
            for line in f:
                try:
                    d = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if not isinstance(d, dict) or not d.get(result_key):
                    continue
                if d.get("validate"):
                    # pipeline-validation rows (bench_sft_7b SFT7B_VALIDATE)
                    # exercise the code path, not the measurement — they
                    # must never mark a capture stage done
                    continue
                if all(d.get(k, _MARKER_DEFAULTS.get(k)) == v
                       for k, v in marker.items()):
                    return True
        return False
    except OSError:
        return False


# absent row fields fall back to the emitting script's defaults before the
# marker compare (round-3 sweep2 rows omit block when it is 1024;
# pre-buckets rows omit vote_buckets when it is 1)
_MARKER_DEFAULTS = {"block": 1024, "vote_buckets": 1}

# the LAST config of each runbook window's spec list, as structural field
# markers (stages run sequentially, so the last config's result row
# implies the whole window executed):
#   sweep2 — noremat:4:flash@512x1024@512x512:...:1024 (bwd-tile leg)
#   sweep3 — noremat:2:flash@512x1024@512x512:...:2048 (T=2048 bwd-tile
#            leg; batch+block disambiguate it from the same attn at T=1024)
#   sft7b  — nf4:1:2:8::2048:dots (the only spec with seq_len 2048)
SWEEP2_MARKER = {"attn": "flash@512x1024@512x512", "block": 1024}
SWEEP3_MARKER = {"attn": "flash@512x1024@512x512", "batch_per_dev": 2,
                 "block": 2048}
SFT7B_MARKER = {"seq_len": 2048}


def sweep2() -> bool:
    return _window_captured(os.path.join(OUT, "sweep2.jsonl"),
                            SWEEP2_MARKER, "tokens_per_sec_per_chip")


def sweep3() -> bool:
    return _window_captured(os.path.join(OUT, "sweep3.jsonl"),
                            SWEEP3_MARKER, "tokens_per_sec_per_chip")


def sft7b() -> bool:
    return _window_captured(os.path.join(OUT, "sft7b2.jsonl"),
                            SFT7B_MARKER, "tokens_per_sec_per_chip")


def bench_best() -> bool:
    return os.path.exists(os.path.join(OUT, "bench_best.done"))


# the vote-wire overlap ablation (ISSUE 1): the flagship anchor config at
# vote_buckets ∈ {1, 4, 16} — every cell must hold a RESULT row, because the
# measured comm_overlap_frac (bench.overlap_from_ablation) needs the B=1
# anchor AND at least one pipelined row, and the {4, 16} pair shows whether
# more buckets keep buying overlap or launch latency wins
OVERLAP_BUCKETS = (1, 4, 16)


def overlap() -> bool:
    path = os.path.join(OUT, "overlap.jsonl")
    return all(
        _window_captured(path, {"vote_buckets": b}, "tokens_per_sec_per_chip")
        for b in OVERLAP_BUCKETS
    )


def dpo(tpu_only: bool = False) -> bool:
    """A DPO step-rate + comm-bytes result row exists (VERDICT r4 #7 —
    the last workload without numbers). Any backend counts for the
    evidence stage (rows carry backend honestly; the CPU-mesh fallback is
    explicitly allowed); ``tpu_only`` is the runbook's stage guard, so a
    live window still captures a chip row once."""
    return _window_captured(os.path.join(OUT, "dpo.jsonl"),
                            {"backend": "tpu"} if tpu_only else {},
                            "tokens_per_sec_per_chip")


def conv(dirname: str | None = None) -> bool:
    """Real-corpus convergence artifact (VERDICT r3 stretch, r4 #6):
    ≥1900 steps of run_clm with the reference's convergence signals (eval
    accuracy/perplexity, /root/reference/run_clm.py:562-577, 630-636)
    logged in metrics.jsonl. Canonical-config TPU run in
    runs/convergence; the reduced CPU run (gpt2_small on the
    same corpus/BPE, scripts/conv_cpu_chain.sh) in runs/convergence_cpu —
    mirror of the parity-leg directory split."""
    dirs = (dirname,) if dirname else ("convergence", "convergence_cpu")
    for d in dirs:
        try:
            last, has_eval = 0, False
            with open(os.path.join(REPO, "runs", d, "metrics.jsonl")) as f:
                for line in f:
                    try:
                        r = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    last = max(last, r.get("step", 0))
                    if any(k.startswith("eval/") for k in r):
                        has_eval = True
            if has_eval and last >= 1900:
                return True
        except OSError:
            continue
    return False


# vote-health telemetry artifact (ISSUE 2): the runbook's telemetry stage
# runs a short --telemetry --nan_sentinel training (runs/telemetry) whose
# metrics.jsonl must hold vote-health rows with a CONSERVED margin
# histogram: the histogram is normalized per voted coordinate, so its mass
# times the voted-coordinate count must equal the voted-coordinate count
# (mass == 1 ⇔ every voted coordinate landed in a bin — the invariant that
# catches binning/masking bugs in the on-device accumulator). Only rows
# from tally wires are judged (margin_exact == 1; the two-phase wires ship
# a ±1 proxy and zero the histogram by design).
TELEMETRY_MASS_RTOL = 0.01


def telemetry_ok(dirname: str = "telemetry") -> bool:
    path = os.path.join(REPO, "runs", dirname, "metrics.jsonl")
    found = False
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                hist = r.get("train/vote/margin_hist")
                if hist is None or r.get("train/vote/margin_exact") != 1:
                    continue
                voted = r.get("train/vote/voted_per_step", 0)
                if not voted or None in hist:
                    return False
                mass = sum(hist)
                if abs(mass * voted - voted) > TELEMETRY_MASS_RTOL * voted:
                    return False  # histogram lost/invented coordinates
                found = True
    except OSError:
        return False
    return found


# resilience artifact (ISSUE 3): the runbook's resilience stage runs a short
# async-checkpoint training (runs/resilience) plus a synchronous baseline
# (runs/resilience_sync). Captured = the async run's newest checkpoint
# VERIFIES (per-file sha256 manifest + COMMITTED marker, via the pure-stdlib
# reader in distributed_lion_tpu.train.resilience — no jax import) AND the
# async run's logged ckpt_stall_s peak is below the sync baseline's (the
# overlap actually keeps the step loop unblocked at save boundaries).

def _peak_metric(path: str, key: str):
    peak = None
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                v = r.get(key)
                if isinstance(v, (int, float)):
                    peak = v if peak is None else max(peak, v)
    except OSError:
        return None
    return peak


def resilience_ok(dirname: str = "resilience") -> bool:
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    try:
        from distributed_lion_tpu.train.resilience import latest_valid_step_in
    except ImportError:
        return False
    base = os.path.join(REPO, "runs", dirname)
    if latest_valid_step_in(os.path.join(base, "checkpoints")) is None:
        return False  # no committed+verified checkpoint — the stage's point
    a = _peak_metric(os.path.join(base, "metrics.jsonl"),
                     "train/ckpt_stall_s")
    s = _peak_metric(os.path.join(REPO, "runs", f"{dirname}_sync",
                                  "metrics.jsonl"), "train/ckpt_stall_s")
    # the sync leg must have actually paid a visible save (>0) for the
    # comparison to mean anything
    return a is not None and s is not None and s > 0 and a < s


# vote-guard artifact (ISSUE 5): the runbook's vote_guard stage runs four
# short same-seed trainings under runs/vote_guard/ —
#   clean          (no poison, --vote_guard off)
#   clean_enforce  (no poison, --vote_guard enforce)
#   poison_enforce (one flipped-ballot worker, enforce)
#   poison_off     (same poison, guard off)
# Captured = (a) ALL-HEALTHY BIT-IDENTITY: clean and clean_enforce log
# byte-identical loss curves (enforce with an all-True mask must not move
# one election), and (b) the DEGRADED-MODE claim: poison_enforce's tail
# loss stays within GUARD_ENFORCE_EPS of clean while poison_off sits at
# least GUARD_MIN_GAP further out — the guard demonstrably rescues the run
# the adversary demonstrably degrades. (The stricter clean-W−1 comparison
# is pinned by tests/test_vote_guard.py, where the mesh can be carved.)
GUARD_ENFORCE_EPS = 0.35
GUARD_MIN_GAP = 0.1
GUARD_TAIL_FRAC = 0.75
GUARD_MIN_STEPS = 30


def _loss_curve(dirname: str):
    path = os.path.join(REPO, "runs", dirname, "metrics.jsonl")
    curve = {}
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(r.get("train/loss"), (int, float)) \
                        and isinstance(r.get("step"), int):
                    curve[r["step"]] = r["train/loss"]
    except OSError:
        return None
    return curve or None


def _tail_mean(curve: dict):
    last = max(curve)
    tail = [v for s, v in curve.items() if s >= GUARD_TAIL_FRAC * last]
    return sum(tail) / len(tail)


def vote_guard_ok(base: str = "vote_guard") -> bool:
    legs = {leg: _loss_curve(os.path.join(base, leg))
            for leg in ("clean", "clean_enforce", "poison_enforce",
                        "poison_off")}
    if any(c is None or max(c) < GUARD_MIN_STEPS for c in legs.values()):
        return False
    clean, clean_enf = legs["clean"], legs["clean_enforce"]
    common = sorted(set(clean) & set(clean_enf))
    if not common or any(clean[s] != clean_enf[s] for s in common):
        return False  # all-healthy enforce moved an election
    gap_enf = abs(_tail_mean(legs["poison_enforce"]) - _tail_mean(clean))
    gap_off = abs(_tail_mean(legs["poison_off"]) - _tail_mean(clean))
    return gap_enf <= GUARD_ENFORCE_EPS and gap_off >= gap_enf + GUARD_MIN_GAP


# static-analysis gate (ISSUE 4): the stage is green when (a) the
# ci_static.sh gate passes RIGHT NOW — ruff baseline + graft-check tier-1
# AST lint + shellcheck, each skipped gracefully where not installed — and
# (b) the jaxpr contract tier's report (written by the runbook's static
# stage via `python -m distributed_lion_tpu.analysis --tier2 --json-out`)
# exists with ok=true. Tier 1 re-runs on every poll (sub-second, no jax);
# tier 2 traces the real train step, so it is captured once per runbook
# pass like every other evidence artifact.
STATIC_TIER2_REPORT = os.path.join(OUT, "static_tier2.json")

# serve-plane graft-check gate (ISSUE 19): the committed
# runs/static/serve_check.json (written by `python -m
# distributed_lion_tpu.analysis serve-check --json-out`, re-captured by
# the runbook's stage 0b) passes validate_metrics' strict schema — every
# matrix cell present and ok, inventories re-derived equal, zero host
# callbacks, donation present, compile counts within budget.
SERVE_CHECK_REPORT = os.path.join(REPO, "runs", "static",
                                  "serve_check.json")


def static_serve_ok(path: str | None = None) -> bool:
    path = path or SERVE_CHECK_REPORT
    if not os.path.exists(path):
        return False
    vm = _validate_metrics_module()
    return not vm.validate_json_doc(path)


def static_ok() -> bool:
    try:
        gate = subprocess.run(
            ["bash", os.path.join(REPO, "scripts", "ci_static.sh")],
            capture_output=True, timeout=600)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if gate.returncode != 0:
        return False
    try:
        with open(STATIC_TIER2_REPORT) as f:
            report = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    return report.get("ok") is True


# the run-journal stage (ISSUE 7): the runbook's journal leg records a
# --journal training (runs/journal) whose journal must (a) exist and parse
# under the strict schema (run_analyze counts schema errors), (b) close —
# named buckets + other + unattributed == measured wall — and (c) attribute
# at least JOURNAL_MIN_COVERAGE of the measured step wall to the NAMED
# buckets (device / dispatch / data / ckpt / logging): the acceptance
# criterion that makes the next MFU push start from a named stall budget
# instead of a guess. The analyzer is cli/run_analyze — stdlib-only,
# loaded by FILE PATH, so this script stays
# jax-free.
JOURNAL_MIN_COVERAGE = 0.95


def _run_analyze_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "dlt_run_analyze_standalone",
        os.path.join(REPO, "distributed_lion_tpu", "cli", "run_analyze.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the DCN-overlap stage (ISSUE 8): scripts/bench_dcn.py's artifact under
# runs/dcn_overlap — (a) passes the strict dcn_overlap.json schema
# (validate_metrics, loaded by FILE PATH so this script stays jax-free),
# (b) the depth-0 bit-identity legs hold (the dcn_delay fault is
# timing-only and the synchronous wire deterministic), (c) the depth-1
# pipeline recovered >= DCN_OVERLAP_MIN of the injected per-step latency,
# (d) the bits-per-param × steps-to-loss frontier is present and
# row-valid, and (e) the pre-registered depth {1,2} loss-parity bound
# held. A CPU-produced artifact is first-class here: the DCN link is
# emulated on every backend (the point is the pipeline mechanism, not
# chip throughput); meta.backend records what measured it.
DCN_OVERLAP_MIN = 0.8
DCN_ARTIFACT = os.path.join(REPO, "runs", "dcn_overlap", "dcn_overlap.json")


def _validate_metrics_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "dlt_validate_metrics_standalone",
        os.path.join(REPO, "scripts", "validate_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dcn_overlap_ok(path: str = DCN_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    bit = doc.get("bit_identity", {})
    if not (bit.get("depth0_deterministic") is True
            and bit.get("depth0_fault_inert") is True):
        return False
    overlap = doc.get("overlap", {})
    frac = overlap.get("recovered_frac_depth1")
    if not isinstance(frac, (int, float)) or frac < DCN_OVERLAP_MIN:
        return False
    if not doc.get("frontier"):
        return False
    return doc.get("parity", {}).get("pass") is True


# the serving stage (ISSUE 9): scripts/bench_serve.py's artifact under
# runs/serving — (a) passes the strict serving.json schema
# (validate_metrics, loaded by FILE PATH so this script stays jax-free),
# (b) both live-recomputed bit-identity markers hold (paged-engine greedy
# == dense-KV generate at matched attended length; staggered continuous
# batching == solo runs per request), (c) a decode row exists at every
# required batch size {32, 128, 256} with tokens/s/chip above the floor —
# SERVE_MIN_TOKS is calibrated to the banked CPU smoke artifact (tiny
# model on a 2-core box measures >1k; a TPU gpt2_124m run is orders of
# magnitude above), so any regression that stalls the tick loop trips it
# on every backend — and (d) the NF4 weight-bytes column actually shows
# the 4-bit story (nf4 < bf16/3, i.e. < ~0.67 byte/param incl. scales).
SERVE_ARTIFACT = os.path.join(REPO, "runs", "serving", "serving.json")
SERVE_BATCHES = (32, 128, 256)
SERVE_MIN_TOKS = 50.0


def serving_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    bits = doc.get("bit_identity", {})
    if not (bits.get("paged_vs_dense") is True
            and bits.get("batched_vs_solo") is True):
        return False
    rows = {r.get("batch"): r for r in doc.get("decode", [])}
    for b in SERVE_BATCHES:
        row = rows.get(b)
        if row is None or not isinstance(
                row.get("tokens_per_sec_per_chip"), (int, float)):
            return False
        if row["tokens_per_sec_per_chip"] < SERVE_MIN_TOKS:
            return False
        if not (isinstance(row.get("weight_bytes_nf4"), int)
                and isinstance(row.get("weight_bytes_bf16"), int)
                and row["weight_bytes_nf4"] * 3 < row["weight_bytes_bf16"]):
            return False
    return True


# the speculative stage (ISSUE 11): the speculative-decode section of
# the SAME serving.json artifact (bench_serve writes both; stage 5j
# re-captures on chip) — (a) the whole artifact passes the strict schema
# (which pins accept_rate ∈ [0,1], drafter/k/tokens-per-sec columns on
# every frontier row), (b) both live-recomputed speculative identity
# markers hold (greedy speculative == plain paged decode; sampled
# speculative == the same per-request PRNG stream — speculation may only
# change SPEED, never an output), (c) the frontier actually covers the
# claim: a non-speculative baseline row plus both drafters measured on
# the repetitive AND random workloads, and (d) the n-gram drafter EARNS
# accept_rate > 0 on the repetitive workload (prompt-lookup drafting
# must work where its traffic exists, not just ride the schema).
def speculative_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations (incl. accept_rate range)
    except Exception:
        return False
    spec = doc.get("speculative")
    if not isinstance(spec, dict):
        return False
    marks = spec.get("markers", {})
    if not (marks.get("greedy_vs_plain") is True
            and marks.get("sampled_vs_stream") is True):
        return False
    rows = spec.get("frontier", [])
    for workload in ("repetitive", "random"):
        here = [r for r in rows if r.get("workload") == workload]
        if not any(r.get("drafter") == "none" for r in here):
            return False  # no baseline to read the frontier against
        for drafter in ("ngram", "draft"):
            if not any(r.get("drafter") == drafter for r in here):
                return False
    return any(r.get("drafter") == "ngram"
               and r.get("workload") == "repetitive"
               and r.get("accept_rate", 0) > 0 for r in rows)


# the tp_serving stage (ISSUE 13): the TP-sharded + prefix-sharing
# section of the SAME serving.json artifact (bench_serve writes it;
# runbook stage 5k re-captures on chip) — (a) the whole artifact passes
# the strict schema (validate_metrics: TP rows + prefix leg per-row
# validated), (b) ALL FIVE live-recomputed identity markers hold (tp=1
# sharded == unsharded, tp>1 == unsharded on the measuring mesh, and
# shared-prefix == unshared for greedy/sampled/speculative decode —
# sharding and sharing may only change HBM and speed, never an output),
# (c) a TP row at degree >= 2 exists (the section is about multi-chip
# serving; on CPU the bench runs under DLION_PLATFORM=cpu8) with
# tokens/s/chip above the same floor the serving stage uses at every
# measured degree, and (d) the shared-system-prompt workload actually
# demonstrates the memory story: >= 256 requests and
# prefix_mem_ratio <= TP_SERVE_MEM_RATIO (physical ÷ logical pages,
# both MEASURED by draining the workload through both engines).
TP_SERVE_MEM_RATIO = 0.15
TP_SERVE_MIN_REQUESTS = 256


def tp_serving_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    sec = doc.get("tp_serving")
    if not isinstance(sec, dict):
        return False
    marks = sec.get("markers", {})
    for k in ("tp1_vs_unsharded", "tpN_vs_unsharded",
              "shared_vs_unshared_greedy", "shared_vs_unshared_sampled",
              "shared_vs_unshared_speculative"):
        if marks.get(k) is not True:
            return False
    rows = sec.get("rows", [])
    if not any(r.get("tp", 0) >= 2 for r in rows):
        return False  # no multi-chip measurement: the section's point
    for r in rows:
        if not isinstance(r.get("tokens_per_sec_per_chip"), (int, float)):
            return False
        if r["tokens_per_sec_per_chip"] < SERVE_MIN_TOKS:
            return False
    pref = sec.get("prefix", {})
    if pref.get("requests", 0) < TP_SERVE_MIN_REQUESTS:
        return False
    ratio = pref.get("prefix_mem_ratio")
    if not isinstance(ratio, (int, float)) or ratio > TP_SERVE_MEM_RATIO:
        return False
    return True


# the moe_serving stage (ISSUE 15): the MoE-serving section of the SAME
# serving.json artifact (bench_serve writes it; runbook stage 5m
# re-captures on chip) — (a) the whole artifact passes the strict schema
# (validate_metrics: matrix rows per-row validated incl.
# capacity_utilization/dropped_rate ∈ [0,1] and the ISSUE 16
# sharding/beats_dense_per_chip columns), (b) ALL TEN live-recomputed
# identity markers hold (paged MoE decode == dense-KV MoE generate,
# engine batched == solo, left-padded batched generate == solo — the
# lifted PR 9 refusals — plus ep=1 bit-identical to the unsharded engine,
# ep>=2 / ep×tp token-identical on the measuring mesh, and the four
# batch-sharded markers: ep_batch at ep=1 bit-identical, ep>=2 / ep×tp /
# microbatch-overlap token-identical), and (c) the matrix actually
# covers the claim: a dense baseline row, a MoE row, a replicated MoE+ep
# row at ep >= 2, AND a batch-sharded row at the same (batch, ep) whose
# per-chip tokens/s is STRICTLY above the replicated row's — ep as a
# throughput lever, not just an HBM lever — with every MoE row carrying
# a measured tokens/s/chip above the serving floor and its
# capacity-utilization and dropped-rate columns.
MOE_SERVE_MARKERS = ("paged_vs_dense", "batched_vs_solo",
                     "batched_generate_vs_solo", "ep1_vs_unsharded",
                     "epN_vs_unsharded", "ep_tp_vs_unsharded",
                     "ep_batch1_vs_unsharded", "ep_batchN_vs_unsharded",
                     "ep_batch_tp_vs_unsharded",
                     "ep_batch_overlap_vs_unsharded")


def moe_serving_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    sec = doc.get("moe_serving")
    if not isinstance(sec, dict):
        return False
    marks = sec.get("markers", {})
    for k in MOE_SERVE_MARKERS:
        if marks.get(k) is not True:
            return False
    rows = sec.get("rows", [])
    configs = {r.get("config") for r in rows}
    if "dense" not in configs or "moe" not in configs:
        return False  # no baseline (or no MoE arm) to read the matrix
    if not any(r.get("ep", 0) >= 2 and r.get("experts", 0) > 0
               for r in rows):
        return False  # no expert-parallel measurement: the section's point
    # ISSUE 16: at least one (batch, ep>=2) pair must carry BOTH a
    # replicated and a batch-sharded row, and the batch-sharded row's
    # per-chip throughput must be STRICTLY above the replicated one —
    # otherwise 'ep is a throughput lever' is an unmeasured claim
    lever = False
    for r in rows:
        if r.get("sharding") != "batch" or r.get("ep", 0) < 2:
            continue
        rep = [x for x in rows
               if x.get("sharding") == "replicated"
               and x.get("ep") == r.get("ep")
               and x.get("batch") == r.get("batch")]
        if rep and all(r.get("tokens_per_sec_per_chip", 0)
                       > x.get("tokens_per_sec_per_chip", 0) for x in rep):
            lever = True
    if not lever:
        return False
    for r in rows:
        if r.get("experts", 0) <= 0:
            continue  # dense baseline rows judge only by presence
        if not isinstance(r.get("tokens_per_sec_per_chip"), (int, float)):
            return False
        if r["tokens_per_sec_per_chip"] < SERVE_MIN_TOKS:
            return False
        for k in ("capacity_utilization", "dropped_rate"):
            v = r.get(k)
            if not isinstance(v, (int, float)) or not 0.0 <= v <= 1.0:
                return False
    return True


# the serve_resilience stage (ISSUE 14): the replica-plane section of
# the SAME serving.json artifact (bench_serve writes it; runbook stage
# 5l re-captures on chip) — (a) the whole artifact passes the strict
# schema (validate_metrics: crash-matrix/slow/drain/rejoin rows per-row
# validated), (b) ALL EIGHT live-recomputed markers hold (crash-migrated
# outputs token-identical greedy/sampled/speculative/prefix-cache, zero
# accepted-token loss, drain finishes residents and departs, the slow
# replica is detected AND routed around, a rejoiner serves from a fresh
# pool), (c) the crash matrix covers >= SERVE_RES_MIN_CRASH_TICKS cut
# points, every row with tokens_lost == 0, identical, and at least one
# actual migration, and (d) the slow leg's measured story holds: the
# slow replica's p99 tick latency strictly above its clean peer's in the
# same run (the latency watch had a real signal to act on).
SERVE_RES_MIN_CRASH_TICKS = 3


def serve_resilience_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    sec = doc.get("serve_resilience")
    if not isinstance(sec, dict):
        return False
    marks = sec.get("markers", {})
    for k in ("migrated_identity_greedy", "migrated_identity_sampled",
              "migrated_identity_speculative",
              "migrated_identity_prefix_cache", "zero_token_loss",
              "drain_completes_residents", "slow_detected_and_routed",
              "rejoin_serves"):
        if marks.get(k) is not True:
            return False
    rows = sec.get("crash_matrix", [])
    if len({r.get("crash_tick") for r in rows}) < SERVE_RES_MIN_CRASH_TICKS:
        return False  # 'crash at any tick' needs more than one cut point
    for r in rows:
        if r.get("tokens_lost") != 0 or r.get("identical") is not True:
            return False
    if not any(r.get("migrated", 0) > 0 for r in rows):
        return False  # a matrix where nothing migrated proved nothing
    slow = sec.get("slow", {})
    if not (isinstance(slow.get("p99_ms_slow_replica"), (int, float))
            and isinstance(slow.get("p99_ms_clean_replica"), (int, float))
            and slow["p99_ms_slow_replica"] > slow["p99_ms_clean_replica"]):
        return False
    return True


# the process-isolated fleet stage (ISSUE 20): the fleet_resilience
# section of the same serving artifact — (a) the whole document passes
# the strict serving.json schema, (b) all six markers recomputed true at
# capture time (SIGKILL identity + zero token loss, real-process
# isolation, restart identity + prefill-tokens-saved, socket soak
# served), (c) the kill matrix covers >= FLEET_RES_MIN_KILL_TICKS
# distinct cut points and includes a sampled cut, every row with
# tokens_lost == 0, identical, the dead process actually declared and at
# least one real migration somewhere in the matrix, (d) the restart leg
# restored in-flight work (the stop really interrupted a fleet) with
# prefill_tokens_saved > 0 (the persisted chains did real work), and
# (e) the soak completed every request and pinned its byte stream. A
# CPU-produced artifact is first-class here for the same reason as the
# elasticity stage: process spawn, SIGKILL, pipe-EOF detection and the
# persistence manifest are host-plane mechanics on every backend.
FLEET_RES_MIN_KILL_TICKS = 3


def fleet_resilience_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    sec = doc.get("fleet_resilience")
    if not isinstance(sec, dict):
        return False
    marks = sec.get("markers", {})
    for k in ("sigkill_identity", "sigkill_zero_token_loss",
              "process_isolated", "restart_identity",
              "restart_prefill_saved", "socket_soak_served"):
        if marks.get(k) is not True:
            return False
    rows = sec.get("kill_matrix", [])
    if len({r.get("kill_tick") for r in rows}) < FLEET_RES_MIN_KILL_TICKS:
        return False  # 'SIGKILL at any tick' needs more than one cut
    if not any(r.get("sampling") == "stochastic" for r in rows):
        return False  # greedy-only identity is the easy half
    for r in rows:
        if (r.get("tokens_lost") != 0 or r.get("identical") is not True
                or r.get("declared_dead") != 1
                or r.get("process_isolated") is not True):
            return False
    if not any(r.get("migrated", 0) > 0 for r in rows):
        return False  # a matrix where nothing migrated proved nothing
    restart = sec.get("restart", {})
    if not (restart.get("inflight_at_stop", 0) > 0
            and restart.get("restored", 0) > 0
            and restart.get("prefill_tokens_saved", 0) > 0):
        return False
    soak = sec.get("socket_soak", {})
    if not (soak.get("requests", 0) > 0
            and soak.get("completed") == soak.get("requests")):
        return False
    return True


# the live-elasticity stage (ISSUE 10): scripts/bench_elasticity.py's
# artifact under runs/elasticity — (a) passes the strict elasticity.json
# schema (validate_metrics, loaded by FILE PATH so this script stays
# jax-free), (b) the headline drop/rejoin scenario SURVIVED: every step
# completed without restart, losses/momenta finite, exactly one leave and
# one rejoin, ending all-healthy at full W, (c) both degraded-phase
# bit-identity markers hold (departed-from-step-0 == masked-from-scratch
# W−1; the drop/rejoin schedule is deterministic), (d) the journal-read
# membership timeline carries the worker_left AND worker_rejoined events
# (the run_analyze leg actually closed), and (e) the pre-registered
# post-rejoin parity bound PASSED. A CPU-produced artifact is first-class
# here: membership transitions are host-side mask flips on every backend
# (the point is the control-plane mechanism, not chip throughput);
# meta.backend records what measured it and the runbook re-captures on
# chip (stage 5i).
ELASTICITY_ARTIFACT = os.path.join(REPO, "runs", "elasticity",
                                   "elasticity.json")


def elasticity_ok(path: str = ELASTICITY_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    sv = doc.get("survive", {})
    world = doc.get("meta", {}).get("world")
    if not (sv.get("completed") is True and sv.get("finite") is True
            and sv.get("left_events") == 1 and sv.get("rejoin_events") == 1
            and sv.get("final_alive") == world):
        return False
    bits = doc.get("bit_identity", {})
    if not (bits.get("degraded_vs_masked") is True
            and bits.get("drop_deterministic") is True):
        return False
    names = [r.get("event") for r in doc.get("timeline", [])]
    if not ("worker_left" in names and "worker_rejoined" in names):
        return False
    return doc.get("parity", {}).get("pass") is True


# the serve-SLO stage (ISSUE 17): serving.json's ``slo`` section — the
# seeded workload_gen soak through the serve/metrics.py plane. Captured
# means (a) the document passes the strict serving.json schema
# (including the slo section's ordered non-negative quantiles and
# required status counts), (b) all three markers hold — metrics_inert
# (metrics-ON token streams byte-identical to metrics-OFF),
# zero_token_loss, responses_timed (every terminal status carried its
# timing columns), (c) the soak actually ran (requests > 0 with
# tokens_out > 0) and lost NOTHING (tokens_lost == 0 — the token-loss
# regression gate), and (d) the banked TTFT p99 sits inside the banked
# target (the SLO regression gate: the target rides the artifact, so a
# re-bank that quietly widened it is visible in review, not laundered
# through this check).
def slo_ok(path: str = SERVE_ARTIFACT) -> bool:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return False
    try:
        vm = _validate_metrics_module()
        if vm.validate_json_doc(path):
            return False  # schema violations
    except Exception:
        return False
    sec = doc.get("slo")
    if not isinstance(sec, dict):
        return False
    marks = sec.get("markers", {})
    for k in ("metrics_inert", "zero_token_loss", "responses_timed"):
        if marks.get(k) is not True:
            return False
    if not (sec.get("requests", 0) > 0 and sec.get("tokens_out", 0) > 0):
        return False  # an empty soak proved nothing
    if sec.get("tokens_lost") != 0:
        return False
    targets = sec.get("targets", {})
    ttft = sec.get("ttft_ms", {})
    tok = sec.get("tok_ms", {})
    if not (isinstance(ttft.get("p99"), (int, float))
            and isinstance(targets.get("ttft_ms"), (int, float))
            and ttft["p99"] <= targets["ttft_ms"]):
        return False
    return (isinstance(tok.get("p99"), (int, float))
            and isinstance(targets.get("tok_ms"), (int, float))
            and tok["p99"] <= targets["tok_ms"])


def journal_ok(dirname: str = "journal") -> bool:
    base = (dirname if os.path.isabs(dirname)
            else os.path.join(REPO, "runs", dirname))
    try:
        ra = _run_analyze_module()
        report = ra.analyze_dir(base)
    except Exception:
        return False
    if report is None or report.get("schema_errors"):
        return False
    att = report.get("attribution")
    return bool(att and att["closes"] and att.get("steps", 0) > 0
                and att["coverage"] >= JOURNAL_MIN_COVERAGE)


# the ONE stage list both check("all") and the CLI printout derive from —
# adding a stage here updates the watcher exit condition and the operator
# status display together
STAGES = [
    ("sweep2", sweep2),
    ("sweep3", sweep3),
    ("bench_best", bench_best),
    ("overlap", overlap),
    ("sft7b", sft7b),
    ("parity:local", lambda: parity_strict("local")),
    ("parity:vote", lambda: parity_strict("vote")),
    ("parity:lazy", lambda: parity_strict("lazy")),
    ("parity:PASS", parity_pass),
    ("conv", conv),
    ("dpo", dpo),
    ("telemetry", telemetry_ok),
    ("resilience", resilience_ok),
    ("static", static_ok),
    ("static_serve", static_serve_ok),
    ("vote_guard", vote_guard_ok),
    ("journal", journal_ok),
    ("dcn_overlap", dcn_overlap_ok),
    ("serving", serving_ok),
    ("speculative", speculative_ok),
    ("tp_serving", tp_serving_ok),
    ("serve_resilience", serve_resilience_ok),
    ("fleet_resilience", fleet_resilience_ok),
    ("moe_serving", moe_serving_ok),
    ("elasticity", elasticity_ok),
    ("slo", slo_ok),
]

# automation (the watcher exit condition) judges the parity legs on
# PRESENCE, not the numeric criterion: the criterion is a deterministic
# function of already-captured legs (same seed reproduces the same curve),
# so once a leg exists no amount of re-fired windows can flip its verdict —
# a failing criterion needs a human, not an infinite watcher loop
# (code-review r5). The evidence-facing STAGES entries above stay strict.
_AUTOMATION_OVERRIDES = {
    "parity:vote": lambda: parity("vote"),
    "parity:lazy": lambda: parity("lazy"),
}


def automation_complete() -> bool:
    """The watcher's exit condition: every stage automation can still
    affect is captured (parity legs by presence — see
    _AUTOMATION_OVERRIDES; parity:PASS excluded entirely). `all` keeps
    the full strict list for operators/judges."""
    return all(_AUTOMATION_OVERRIDES.get(name, fn)()
               for name, fn in STAGES if name != "parity:PASS")


def check(what: str, arg: str | None = None) -> bool:
    if what == "parity":
        # the CLI parity check is the STRICT one (presence + numeric PASS
        # for vote/lazy); the watcher's presence semantics ride
        # `automation`, and the runbook's skip guards use parity_full
        return parity_strict(arg or "local")
    if what == "sweep2":
        return sweep2()
    if what == "sweep3":
        return sweep3()
    if what == "sft7b":
        return sft7b()
    if what == "bench_best":
        return bench_best()
    if what == "overlap":
        return overlap()
    if what == "conv":
        return conv()
    if what == "conv_full":
        # canonical-scale artifact only — the TPU runbook's stage guard
        # (mirrors parity_full: a reduced CPU fallback must not stop a
        # live window from capturing the canonical run)
        return conv("convergence")
    if what == "parity_pass":
        return parity_pass()
    if what == "parity_full":
        return parity_full(arg or "local")
    if what == "dpo":
        return dpo(tpu_only=arg == "tpu")
    if what == "telemetry":
        return telemetry_ok(arg or "telemetry")
    if what == "resilience":
        return resilience_ok(arg or "resilience")
    if what == "static":
        return static_ok()
    if what == "static_serve":
        return static_serve_ok(arg)
    if what == "vote_guard":
        return vote_guard_ok(arg or "vote_guard")
    if what == "journal":
        return journal_ok(arg or "journal")
    if what == "dcn_overlap":
        return dcn_overlap_ok(arg or DCN_ARTIFACT)
    if what == "serving":
        return serving_ok(arg or SERVE_ARTIFACT)
    if what == "speculative":
        return speculative_ok(arg or SERVE_ARTIFACT)
    if what == "tp_serving":
        return tp_serving_ok(arg or SERVE_ARTIFACT)
    if what == "serve_resilience":
        return serve_resilience_ok(arg or SERVE_ARTIFACT)
    if what == "fleet_resilience":
        return fleet_resilience_ok(arg or SERVE_ARTIFACT)
    if what == "moe_serving":
        return moe_serving_ok(arg or SERVE_ARTIFACT)
    if what == "elasticity":
        return elasticity_ok(arg or ELASTICITY_ARTIFACT)
    if what == "slo":
        return slo_ok(arg or SERVE_ARTIFACT)
    if what == "all":
        return all(fn() for _, fn in STAGES)
    if what == "automation":
        return automation_complete()
    raise SystemExit(f"unknown evidence check {what!r}")


if __name__ == "__main__":
    what = sys.argv[1]
    if what == "all":
        # per-stage status printout for operators; exit 0 only when complete
        status = [(name, fn()) for name, fn in STAGES]
        for name, ok in status:
            print(f"{name}: {'captured' if ok else 'MISSING'}")
        sys.exit(0 if all(ok for _, ok in status) else 1)
    ok = check(what, sys.argv[2] if len(sys.argv) > 2 else None)
    sys.exit(0 if ok else 1)
