"""Evidence-window capture semantics (ADVICE r3): a window where every
config failed fast still writes the last config's ERROR row — that must
NOT mark the stage captured, or whatever polls the `automation` exit
condition stops with no real data for it."""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "check_evidence", os.path.join(REPO, "scripts", "check_evidence.py"))
ce = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ce)


def _write(tmp_path, lines):
    p = tmp_path / "w.jsonl"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


# structural marker (advisor r4: substring needles were coupled to dict
# insertion order / separator spacing)
MARKER = {"attn": "flash@512x1024@512x512"}


def test_all_error_window_is_not_captured(tmp_path):
    path = _write(tmp_path, [
        '{"attn": "flash@512x1024", "error": "rc=1: backend died"}',
        '{"attn": "flash@512x1024@512x512", "error": "rc=1: backend died"}',
    ])
    assert not ce._window_captured(path, MARKER, "tokens_per_sec_per_chip")




def test_marker_error_row_is_not_captured_even_with_banked_results(tmp_path):
    """The files are append-mode across watcher re-fires: a PREVIOUS
    window's banked result rows must not combine with THIS window's error
    marker to fake a capture (code-review r4 finding on the file-global
    any-result check)."""
    path = _write(tmp_path, [
        '{"attn": "flash@512x1024", "tokens_per_sec_per_chip": 98099.3}',
        '{"attn": "flash@512x1024@512x512", "error": "OOM"}',
    ])
    assert not ce._window_captured(path, MARKER, "tokens_per_sec_per_chip")


def test_marker_result_row_is_captured(tmp_path):
    path = _write(tmp_path, [
        '{"attn": "flash@512x1024", "error": "transient"}',
        '{"attn": "flash@512x1024@512x512", "tokens_per_sec_per_chip": 97000.0}',
    ])
    assert ce._window_captured(path, MARKER, "tokens_per_sec_per_chip")


def test_missing_marker_is_not_captured(tmp_path):
    path = _write(tmp_path, [
        '{"attn": "flash@512x1024", "tokens_per_sec_per_chip": 98099.3}',
    ])
    assert not ce._window_captured(path, MARKER, "tokens_per_sec_per_chip")


def test_missing_file_is_not_captured(tmp_path):
    assert not ce._window_captured(str(tmp_path / "nope.jsonl"), MARKER,
                                   "tokens_per_sec_per_chip")


def test_marker_matches_any_field_order(tmp_path):
    """The structural compare must be immune to key order and spacing —
    the exact failure mode of the old substring needles."""
    path = _write(tmp_path, [
        '{"tokens_per_sec_per_chip": 97000.0,   '
        '"attn":"flash@512x1024@512x512"}',
    ])
    assert ce._window_captured(path, MARKER, "tokens_per_sec_per_chip")


def test_marker_default_fill(tmp_path):
    """Round-3 rows omit block=1024; the sweep2 marker must still match
    them via _MARKER_DEFAULTS, while block=2048 rows must not."""
    path = _write(tmp_path, [
        '{"attn": "flash@512x1024@512x512", "tokens_per_sec_per_chip": 1.0}',
    ])
    assert ce._window_captured(path, ce.SWEEP2_MARKER,
                               "tokens_per_sec_per_chip")
    path2 = _write(tmp_path, [
        '{"attn": "flash@512x1024@512x512", "block": 2048, '
        '"batch_per_dev": 2, "tokens_per_sec_per_chip": 1.0}',
    ])
    assert not ce._window_captured(path2, ce.SWEEP2_MARKER,
                                   "tokens_per_sec_per_chip")
    assert ce._window_captured(path2, ce.SWEEP3_MARKER,
                               "tokens_per_sec_per_chip")


def _leg_lines(mode, steps=2000, dtype="float32", loss=5.0, seed=0,
               n_params=12_700_000):
    import json as _json
    rows = [_json.dumps({"meta": True, "mode": mode, "param_dtype": dtype,
                         "steps": steps, "workers": 8, "seed": seed,
                         "n_params": n_params})]
    for s in range(0, steps, 10):
        rows.append(_json.dumps({"step": s, "loss": loss}))
    rows.append(_json.dumps({"step": steps - 1, "loss": loss}))
    return rows


def test_parity_numeric_criterion(tmp_path):
    """parity_mad/parity_pass: identical curves PASS, curves offset by more
    than PARITY_EPS_NATS FAIL, and a config mismatch is UNCOMPUTABLE."""
    d = tmp_path / "legs"
    d.mkdir()
    (d / "local.jsonl").write_text("\n".join(_leg_lines("local")) + "\n")
    (d / "vote.jsonl").write_text(
        "\n".join(_leg_lines("vote", loss=5.0 + 0.01)) + "\n")
    assert abs(ce.parity_mad(str(d), "vote") - 0.01) < 1e-9
    (d / "lazy.jsonl").write_text(
        "\n".join(_leg_lines("lazy", loss=5.0 + ce.PARITY_EPS_NATS * 2))
        + "\n")
    assert ce.parity_mad(str(d), "lazy") > ce.PARITY_EPS_NATS
    # config mismatch (different seed) → UNCOMPUTABLE, not a bogus number
    (d / "vote.jsonl").write_text(
        "\n".join(_leg_lines("vote", seed=1)) + "\n")
    assert ce.parity_mad(str(d), "vote") is None
    # bf16-stamped leg is unqualified regardless of curve
    (d / "vote.jsonl").write_text(
        "\n".join(_leg_lines("vote", dtype="bfloat16")) + "\n")
    assert ce.parity_mad(str(d), "vote") is None


def test_parity_strict_requires_numeric_pass(tmp_path, monkeypatch):
    """ISSUE 6 satellite: the parity:vote / parity:lazy stages require the
    pre-registered criterion to PASS — a present-but-diverged leg reads
    MISSING. The watcher's automation check still judges presence (a
    deterministic FAIL needs a human, not an infinite re-fire loop)."""
    monkeypatch.setattr(ce, "REPO", str(tmp_path))
    d = tmp_path / "runs" / "parity"
    d.mkdir(parents=True)
    (d / "local.jsonl").write_text("\n".join(_leg_lines("local")) + "\n")
    # within EPS → strict stage captured
    (d / "vote.jsonl").write_text(
        "\n".join(_leg_lines("vote", loss=5.0 + ce.PARITY_EPS_NATS / 2))
        + "\n")
    assert ce.parity("vote") and ce.parity_strict("vote")
    # present but diverged → presence yes, strict NO
    (d / "vote.jsonl").write_text(
        "\n".join(_leg_lines("vote", loss=5.0 + ce.PARITY_EPS_NATS * 3))
        + "\n")
    assert ce.parity("vote")
    assert not ce.parity_strict("vote")
    # local is the baseline leg: presence-only semantics
    assert ce.parity_strict("local")
    # absent lazy leg: both read missing
    assert not ce.parity("lazy") and not ce.parity_strict("lazy")


def test_parity_short_leg_unqualified(tmp_path):
    d = tmp_path / "legs"
    d.mkdir()
    (d / "local.jsonl").write_text(
        "\n".join(_leg_lines("local", steps=500)) + "\n")
    assert ce._load_leg(str(d), "local") is not None
    assert not ce._leg_ok(ce._load_leg(str(d), "local"))


def test_validate_rows_never_mark_capture(tmp_path):
    """SFT7B_VALIDATE pipeline rows carry the real result key but must
    satisfy neither the capture marker nor the skip-key resume."""
    import importlib.util
    import json as _json

    row = {"seq_len": 2048, "tokens_per_sec_per_chip": 5.0,
           "validate": True, "quant": "nf4", "batch_per_dev": 1,
           "accum": 1, "remat_policy": "dots", "vocab_chunks": 8}
    path = _write(tmp_path, [_json.dumps(row)])
    assert not ce._window_captured(path, ce.SFT7B_MARKER,
                                   "tokens_per_sec_per_chip")
    import os as _os
    _os.environ["SFT7B_SKIP_FILE"] = path
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_sft_7b", os.path.join(REPO, "scripts", "bench_sft_7b.py"))
        b7 = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(b7)
        assert b7._captured_keys() == set()
    finally:
        del _os.environ["SFT7B_SKIP_FILE"]


def test_dpo_stage_and_tpu_guard(tmp_path, monkeypatch):
    import json as _json

    monkeypatch.setattr(ce, "OUT", str(tmp_path))
    assert not ce.dpo()
    p = tmp_path / "dpo.jsonl"
    p.write_text(_json.dumps({"backend": "cpu",
                              "tokens_per_sec_per_chip": 7.6}) + "\n")
    assert ce.dpo()                  # evidence stage: any backend
    assert not ce.dpo(tpu_only=True)  # runbook guard: chip rows only
    p.write_text(p.read_text() + _json.dumps(
        {"backend": "tpu", "tokens_per_sec_per_chip": 900.0}) + "\n")
    assert ce.dpo(tpu_only=True)


def test_conv_dual_directory(tmp_path, monkeypatch):
    import json as _json

    monkeypatch.setattr(ce, "REPO", str(tmp_path))
    rows = [_json.dumps({"step": s, "train/loss": 5.0})
            for s in range(0, 2000, 25)]
    rows.append(_json.dumps({"step": 1999, "eval/loss": 5.0,
                             "eval/accuracy": 0.3}))
    d = tmp_path / "runs" / "convergence_cpu"
    d.mkdir(parents=True)
    (d / "metrics.jsonl").write_text("\n".join(rows) + "\n")
    assert ce.conv()                       # fallback dir satisfies conv
    assert not ce.conv("convergence")      # the runbook's conv_full doesn't
    # eval-less curve must not count
    (d / "metrics.jsonl").write_text("\n".join(rows[:-1]) + "\n")
    assert not ce.conv()


def test_overlap_stage_needs_all_three_bucket_rows(tmp_path, monkeypatch):
    """The vote-wire overlap ablation is captured only when buckets
    {1, 4, 16} ALL hold result rows — a lone B=1 anchor (or a window that
    errored on the pipelined legs) must not mark the stage done."""
    import json as _json

    monkeypatch.setattr(ce, "OUT", str(tmp_path))
    assert not ce.overlap()
    base = {"remat": "noremat", "batch_per_dev": 4, "attn": "flash@512x1024",
            "accum": 16, "dtype": "bf16", "vocab_chunks": 8,
            "mom_dtype": "bfloat16", "vocab_pad": 0,
            "tokens_per_sec_per_chip": 98000.0, "ms_per_step": 668.0,
            "backend": "tpu"}
    p = tmp_path / "overlap.jsonl"
    # B=1 rows omit the field (the sweep elided defaults) — the marker's
    # _MARKER_DEFAULTS fill must still match them
    rows = [_json.dumps(base),
            _json.dumps({**base, "vote_buckets": 4, "ms_per_step": 640.0})]
    p.write_text("\n".join(rows) + "\n")
    assert not ce.overlap()  # 16 missing
    rows.append(_json.dumps({**base, "vote_buckets": 16,
                             "ms_per_step": 645.0, "error": "x"}))
    p.write_text("\n".join(rows) + "\n")
    assert ce.overlap()


def test_telemetry_stage_mass_conservation(tmp_path, monkeypatch):
    """The 'telemetry' stage (ISSUE 2): a vote-health row passes only when
    its margin histogram conserves the voted-coordinate count (mass ~= 1 of
    per-voted-coordinate fractions), comes from a tally wire
    (margin_exact == 1), and parses as strict JSON. A lossy histogram, a
    proxy-wire row alone, or an absent artifact must all read MISSING."""
    import json as _json

    monkeypatch.setattr(ce, "REPO", str(tmp_path))
    d = tmp_path / "runs" / "telemetry"
    d.mkdir(parents=True)
    path = d / "metrics.jsonl"

    def row(hist, exact=1, voted=124672.0):
        return _json.dumps({
            "step": 10, "train/vote/margin_hist": hist,
            "train/vote/margin_exact": exact,
            "train/vote/voted_per_step": voted,
        })

    good = row([0.25, 0.0, 0.4, 0.0, 0.2, 0.0, 0.1, 0.05])
    assert not ce.telemetry_ok()            # absent artifact
    path.write_text(row([0.1] * 8, exact=0) + "\n")
    assert not ce.telemetry_ok()            # proxy-wire rows alone: no
    path.write_text(good + "\n")
    assert ce.telemetry_ok()                # conserved mass: captured
    path.write_text(good + "\n" + row([0.2] * 8) + "\n")
    assert not ce.telemetry_ok()            # any lossy row fails the stage
    path.write_text(row([0.5, None] + [0.1] * 6) + "\n")
    assert not ce.telemetry_ok()            # null bin (NaN leaked): fail


def test_static_stage(tmp_path, monkeypatch):
    """The 'static' stage (ISSUE 4): green only when the ci_static gate
    passes AND the tier-2 jaxpr-contract report exists with ok=true — an
    absent, corrupt, or failing report reads MISSING, so the runbook
    re-captures it. The gate subprocess is stubbed (like the report path)
    so this stays a stage-logic test, independent of which ruff/shellcheck
    versions the host happens to have; the REAL gate passing over the repo
    is pinned by tests/test_analysis_lint.py."""
    import json as _json
    import subprocess as _sp

    gate_rc = {"rc": 0}
    monkeypatch.setattr(ce.subprocess, "run", lambda *a, **k: _sp.
                        CompletedProcess(a, gate_rc["rc"]))
    monkeypatch.setattr(ce, "STATIC_TIER2_REPORT",
                        str(tmp_path / "static_tier2.json"))
    assert not ce.static_ok()  # gate passes but the report is absent
    (tmp_path / "static_tier2.json").write_text(
        _json.dumps({"ok": False, "configs": []}))
    assert not ce.static_ok()  # a failing contract must not read captured
    (tmp_path / "static_tier2.json").write_text("{not json")
    assert not ce.static_ok()
    (tmp_path / "static_tier2.json").write_text(
        _json.dumps({"ok": True, "world": 8, "configs": []}))
    assert ce.static_ok()
    gate_rc["rc"] = 1
    assert not ce.static_ok()  # a red gate must not read captured either


def test_vote_guard_stage(tmp_path, monkeypatch):
    """The 'vote_guard' stage (ISSUE 5): captured only when (a) the clean
    and clean_enforce legs log BYTE-identical loss curves (all-healthy
    bit-identity) and (b) the poisoned enforce leg's tail tracks clean
    within GUARD_ENFORCE_EPS while guard-off sits GUARD_MIN_GAP further
    out. A missing leg, a bit-identity breach, a non-degrading adversary,
    or a non-rescuing guard must all read MISSING."""
    import json as _json

    monkeypatch.setattr(ce, "REPO", str(tmp_path))

    def write(leg, losses):
        d = tmp_path / "runs" / "vote_guard" / leg
        d.mkdir(parents=True, exist_ok=True)
        rows = [_json.dumps({"step": s + 1, "train/loss": v})
                for s, v in enumerate(losses)]
        (d / "metrics.jsonl").write_text("\n".join(rows) + "\n")

    clean = [5.0 - 0.05 * i for i in range(40)]
    assert not ce.vote_guard_ok()           # nothing captured
    write("clean", clean)
    write("clean_enforce", clean)
    write("poison_enforce", [v + 0.2 for v in clean])
    assert not ce.vote_guard_ok()           # poison_off leg missing
    write("poison_off", [v + 0.5 for v in clean])
    assert ce.vote_guard_ok()               # the full claim holds
    write("clean_enforce", [v + 1e-6 for v in clean])
    assert not ce.vote_guard_ok()           # bit-identity breach fails
    write("clean_enforce", clean)
    write("poison_enforce", [v + 0.6 for v in clean])
    assert not ce.vote_guard_ok()           # guard failed to rescue
    write("poison_enforce", [v + 0.2 for v in clean])
    write("poison_off", [v + 0.22 for v in clean])
    assert not ce.vote_guard_ok()           # adversary didn't degrade
    write("poison_off", [v + 0.5 for v in clean[:20]])
    assert not ce.vote_guard_ok()           # short leg (< GUARD_MIN_STEPS)


def test_journal_stage(tmp_path):
    """The 'journal' stage (ISSUE 7): captured only when a journal exists,
    parses under the strict schema, the attribution CLOSES, and >=95% of
    measured step wall lands in named buckets. Absent journals, schema
    errors, and poor coverage must all read MISSING."""
    import json as _json

    def rec(**kw):
        return _json.dumps(kw)

    def write(d, cover_frac):
        d.mkdir(parents=True, exist_ok=True)
        # a 10s window with `cover_frac` of it tiled by dispatch spans
        rows = [rec(kind="meta", name="journal_start", t=0.0, rank=0,
                    wall=100.0, version=1),
                rec(kind="event", name="train_start", t=0.0, rank=0, step=0),
                rec(kind="span", name="dispatch", t=10.0 * cover_frac,
                    rank=0, dur=10.0 * cover_frac, step=0),
                rec(kind="event", name="step_log", t=9.9, rank=0, step=9),
                rec(kind="event", name="train_end", t=10.0, rank=0, step=10)]
        (d / "journal_rank0.jsonl").write_text("\n".join(rows) + "\n")

    assert not ce.journal_ok(str(tmp_path / "missing"))   # no journal at all
    good = tmp_path / "good"
    write(good, 0.98)
    assert ce.journal_ok(str(good))
    sparse = tmp_path / "sparse"
    write(sparse, 0.5)                                    # coverage 50%
    assert not ce.journal_ok(str(sparse))
    bad = tmp_path / "bad"
    write(bad, 0.98)
    p = bad / "journal_rank0.jsonl"
    p.write_text('{"kind": "span"}\n' + p.read_text())    # schema error
    assert not ce.journal_ok(str(bad))
