#!/usr/bin/env python
"""JSONL schema smoke check for metrics logs — the CI guard behind
MetricsLogger's strict-JSON contract.

``json.dumps(float('nan'))`` emits the bare token ``NaN``, which is not
JSON: one diverged loss used to corrupt the whole line for every strict
consumer (jq, pandas, check_evidence). MetricsLogger now serializes
non-finite floats as ``null`` with the raw value under ``"<k>_repr"``; this
script asserts a metrics file actually honors that contract:

- every non-empty line parses as STRICT JSON (the NaN/Infinity/-Infinity
  tokens Python's json module happily reads back are rejected);
- every record is an object carrying an integer ``step``;
- every value is a JSON scalar or a flat list of JSON scalars (the shapes
  downstream tooling indexes by key).

A torn final line (a run killed mid-write) is tolerated once, at EOF —
append-mode logs legitimately end that way.

JSONL arguments whose basename starts with ``journal`` (the run journal's
``journal_rank<r>.jsonl`` files and their rotations, plus crash bundles'
``journal_tail.jsonl`` — train/journal.py) get the journal record schema
instead: every line a strict-JSON object carrying ``kind`` (meta | span |
event | log), a string ``name``, a finite number ``t`` and an integer
``rank``; span records additionally carry a finite non-negative ``dur``.
The torn-final-line tolerance applies the same way (a crash mid-write
tears at most the last record — the journal's documented durability unit).

JSONL basenames starting with ``requests``/``workload`` (the
scripts/workload_gen.py output) get the serve request line schema —
tokens-or-prompt plus typed optionals — and basenames starting with
``responses`` (``run_serve --out``) get the serve response schema:
id/reason/token accounting plus the ISSUE-17 timing columns, with
``queue_ticks``/``decode_ticks`` REQUIRED on every terminal status
including timeout/failed/overflow.

Non-JSONL arguments (``*.json``) are validated as strict single-document
JSON artifacts, so EVERY JSON artifact the repo writes passes one
validator: crash bundles (``crash/step_*/bundle.json`` — must carry
step/reason/config, telemetry.write_crash_bundle), checkpoint
manifests (``manifest.json`` — must carry format/step/files with
sha256+bytes per file, checkpoint.write_manifest),
the DCN-overlap evidence artifact (``dcn_overlap.json`` —
scripts/bench_dcn.py's ablation/frontier/parity document; the frontier
rows are strict-validated per row), the serving-bench artifact
(``serving.json`` — scripts/bench_serve.py's decode/prefill-share/
bit-identity/speculative-frontier/tp_serving/serve_resilience/
fleet_resilience/moe_serving document, per-row validated the same way
incl. accept_rate ∈ [0,1] on every frontier row, the TP-degree +
shared-prefix rows of the ISSUE 13 section, the
crash-matrix/slow/drain/rejoin rows of the ISSUE 14 replica-plane
section, the SIGKILL-kill-matrix/restart/socket-soak rows of the
ISSUE 20 process-isolated fleet section (incl. the 64-hex
``stream_sha256`` byte-determinism pin), capacity_utilization/
dropped_rate ∈ [0,1] on every dense-vs-MoE-vs-MoE+ep matrix row of the
ISSUE 15 section, and the ISSUE 17 ``slo`` section — ordered p50 <= p95 <= p99 non-negative
latency quantiles, finite goodput, required status counts), and the
live-elasticity artifact (``elasticity.json`` —
scripts/bench_elasticity.py's survive/bit-identity/timeline/parity
document; timeline rows are strict-validated per row).
The same NaN-token rejection applies: all the writers pass
``allow_nan=False`` and this script is the CI check that they keep
doing so.

    python scripts/validate_metrics.py runs/telemetry/metrics.jsonl \
        runs/telemetry/crash/step_*/bundle.json \
        runs/resilience/checkpoints/*/manifest.json

Exit 0 = every file valid. Used by tests/test_telemetry.py,
tests/test_validate_artifacts.py and the telemetry evidence stage
(scripts/check_evidence.py telemetry).
"""

from __future__ import annotations

import json
import os
import re
import sys


def _reject_constant(name: str):
    raise ValueError(f"non-finite JSON constant {name!r} (invalid JSON; "
                     "MetricsLogger must serialize it as null + _repr)")


def _scalar_ok(v) -> bool:
    return v is None or isinstance(v, (str, int, float, bool))


def validate_file(path: str) -> list[str]:
    """Return a list of violation strings (empty = valid)."""
    errors: list[str] = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    n_records = 0
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line, parse_constant=_reject_constant)
        except ValueError as e:
            if i == len(lines) and "constant" not in str(e):
                continue  # torn last line from a mid-write kill: tolerated
            errors.append(f"{path}:{i}: {e}")
            continue
        if not isinstance(rec, dict):
            errors.append(f"{path}:{i}: record is {type(rec).__name__}, "
                          "not an object")
            continue
        n_records += 1
        if not isinstance(rec.get("step"), int):
            errors.append(f"{path}:{i}: missing integer 'step'")
        for k, v in rec.items():
            if _scalar_ok(v):
                continue
            if isinstance(v, list) and all(_scalar_ok(x) for x in v):
                continue
            errors.append(f"{path}:{i}: key {k!r} holds a "
                          f"{type(v).__name__} (want scalar or flat list)")
    if n_records == 0:
        errors.append(f"{path}: no metrics records")
    return errors


_JOURNAL_KINDS = ("meta", "span", "event", "log")  # == train/journal.KINDS


def _finite_number(v) -> bool:
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and v == v and v not in (float("inf"), float("-inf")))


def validate_request_file(path: str) -> list[str]:
    """Strict-schema check for serve request JSONL (the serve/api input
    schema; scripts/workload_gen.py is the canonical writer): each line a
    strict-JSON object carrying ``tokens`` (non-empty flat int list) or
    ``prompt`` (non-empty string), with typed optionals —
    ``max_new_tokens`` positive int, ``seed`` int, ``arrival_tick``
    non-negative int, ``prefix_group`` non-empty string, ``deadline_s``
    positive finite. The same refusals serve/api.load_request_file makes
    at serve time, made BEFORE a soak burns minutes on a bad file."""
    errors: list[str] = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    n_records = 0
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line, parse_constant=_reject_constant)
        except ValueError as e:
            if i == len(lines) and "constant" not in str(e):
                continue
            errors.append(f"{path}:{i}: {e}")
            continue
        if not isinstance(rec, dict):
            errors.append(f"{path}:{i}: record is {type(rec).__name__}, "
                          "not an object")
            continue
        n_records += 1
        toks = rec.get("tokens")
        prompt = rec.get("prompt")
        if toks is not None:
            if (not isinstance(toks, list) or not toks or not all(
                    isinstance(t, int) and not isinstance(t, bool)
                    and t >= 0 for t in toks)):
                errors.append(f"{path}:{i}: 'tokens' must be a non-empty "
                              "flat list of non-negative ints")
        elif not (isinstance(prompt, str) and prompt):
            errors.append(f"{path}:{i}: request needs 'tokens' or a "
                          "non-empty 'prompt'")
        mnt = rec.get("max_new_tokens")
        if mnt is not None and not (isinstance(mnt, int)
                                    and not isinstance(mnt, bool)
                                    and mnt > 0):
            errors.append(f"{path}:{i}: 'max_new_tokens' must be a "
                          "positive int when present")
        seed = rec.get("seed")
        if seed is not None and (isinstance(seed, bool)
                                 or not isinstance(seed, int)):
            errors.append(f"{path}:{i}: 'seed' must be an int when "
                          "present")
        at = rec.get("arrival_tick")
        if at is not None and not (isinstance(at, int)
                                   and not isinstance(at, bool)
                                   and at >= 0):
            errors.append(f"{path}:{i}: 'arrival_tick' must be a "
                          "non-negative int when present")
        group = rec.get("prefix_group")
        if group is not None and (not isinstance(group, str) or not group):
            errors.append(f"{path}:{i}: 'prefix_group' must be a "
                          "non-empty string when present")
        dl = rec.get("deadline_s")
        if dl is not None and not (_finite_number(dl) and dl > 0):
            errors.append(f"{path}:{i}: 'deadline_s' must be a positive "
                          "finite number when present")
    if n_records == 0:
        errors.append(f"{path}: no request records")
    return errors


_RESPONSE_REASONS = ("eos", "length", "overflow", "rejected", "timeout",
                     "failed")


def validate_response_file(path: str) -> list[str]:
    """Strict-schema check for serve response JSONL
    (serve/api.serve_request_file / cli/run_serve --out): id + reason +
    token accounting on every line, and the ISSUE-17 timing columns —
    ``queue_ticks``/``decode_ticks`` REQUIRED on every terminal status
    (timeout/failed/overflow included: a queue-side death whose wait
    vanished from the books is the failure mode these columns exist to
    prevent), ``ttft_ticks``/``ttft_ms`` typed strictly when present
    (same discipline as ``prefix_group``)."""
    errors: list[str] = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    n_records = 0
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line, parse_constant=_reject_constant)
        except ValueError as e:
            if i == len(lines) and "constant" not in str(e):
                continue
            errors.append(f"{path}:{i}: {e}")
            continue
        if not isinstance(rec, dict):
            errors.append(f"{path}:{i}: record is {type(rec).__name__}, "
                          "not an object")
            continue
        n_records += 1
        if "id" not in rec:
            errors.append(f"{path}:{i}: missing 'id'")
        if rec.get("reason") not in _RESPONSE_REASONS:
            errors.append(f"{path}:{i}: 'reason' must be one of "
                          f"{'|'.join(_RESPONSE_REASONS)}, got "
                          f"{rec.get('reason')!r}")
        toks = rec.get("tokens")
        if not (isinstance(toks, list) and all(
                isinstance(t, int) and not isinstance(t, bool)
                for t in toks)):
            errors.append(f"{path}:{i}: 'tokens' must be a flat int list")
        for k in ("prompt_len", "n_generated"):
            v = rec.get(k)
            if not (isinstance(v, int) and not isinstance(v, bool)
                    and v >= 0):
                errors.append(f"{path}:{i}: {k!r} must be a non-negative "
                              "int")
        for k in ("queue_ticks", "decode_ticks"):
            v = rec.get(k)
            if not (isinstance(v, int) and not isinstance(v, bool)
                    and v >= 0):
                errors.append(f"{path}:{i}: missing non-negative int "
                              f"{k!r} (timing columns are required on "
                              "every terminal status)")
        tt = rec.get("ttft_ticks")
        if tt is not None and not (isinstance(tt, int)
                                   and not isinstance(tt, bool)
                                   and tt >= 0):
            errors.append(f"{path}:{i}: 'ttft_ticks' must be a "
                          "non-negative int when present")
        tms = rec.get("ttft_ms")
        if tms is not None and not (_finite_number(tms) and tms >= 0):
            errors.append(f"{path}:{i}: 'ttft_ms' must be a non-negative "
                          "finite number when present")
        group = rec.get("prefix_group")
        if group is not None and (not isinstance(group, str) or not group):
            errors.append(f"{path}:{i}: 'prefix_group' must be a "
                          "non-empty string when present")
    if n_records == 0:
        errors.append(f"{path}: no response records")
    return errors


def validate_journal_file(path: str) -> list[str]:
    """Strict-schema check for run-journal JSONL (train/journal.py): the
    per-line single-doc + allow_nan=False discipline of validate_file, plus
    the journal record contract — kind/name/t/rank on every record, a
    finite non-negative dur on spans (whose optional id/parent are an
    integer and an integer or null), scalar-or-flat-list values
    throughout. Returns violation strings (empty = valid)."""
    errors: list[str] = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    n_records = 0
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line, parse_constant=_reject_constant)
        except ValueError as e:
            if i == len(lines) and "constant" not in str(e):
                continue  # torn last line (crash mid-write): tolerated
            errors.append(f"{path}:{i}: {e}")
            continue
        if not isinstance(rec, dict):
            errors.append(f"{path}:{i}: record is {type(rec).__name__}, "
                          "not an object")
            continue
        n_records += 1
        if rec.get("kind") not in _JOURNAL_KINDS:
            errors.append(f"{path}:{i}: 'kind' must be one of "
                          f"{_JOURNAL_KINDS}, got {rec.get('kind')!r}")
        if not isinstance(rec.get("name"), str):
            errors.append(f"{path}:{i}: missing string 'name'")
        if not _finite_number(rec.get("t")):
            errors.append(f"{path}:{i}: missing finite number 't'")
        if not isinstance(rec.get("rank"), int) \
                or isinstance(rec.get("rank"), bool):
            errors.append(f"{path}:{i}: missing integer 'rank'")
        if rec.get("kind") == "span" and not (
                _finite_number(rec.get("dur")) and rec["dur"] >= 0):
            errors.append(f"{path}:{i}: span without a finite non-negative "
                          "'dur'")
        # the span tree (journal.span): 'id' an integer, 'parent' an
        # integer or null; both optional (synthetic spans carry neither)
        for k, may_be_null in (("id", False), ("parent", True)):
            if k in rec and not ((rec[k] is None and may_be_null) or (
                    isinstance(rec[k], int)
                    and not isinstance(rec[k], bool))):
                errors.append(f"{path}:{i}: {k!r} must be an integer"
                              + (" or null" if may_be_null else ""))
        for k, v in rec.items():
            if _scalar_ok(v):
                continue
            if isinstance(v, list) and all(_scalar_ok(x) for x in v):
                continue
            errors.append(f"{path}:{i}: key {k!r} holds a "
                          f"{type(v).__name__} (want scalar or flat list)")
    if n_records == 0:
        errors.append(f"{path}: no journal records")
    return errors


# required top-level keys per known single-document artifact name.
# (dcn_overlap.json, serving.json and elasticity.json have their own
# branches: their rows carry per-row schemas the generic required-keys
# check can't express.)
_DOC_SCHEMAS = {
    "bundle.json": ("step", "reason", "config"),
    "manifest.json": ("format", "step", "files"),
}


def _serving_errors(path: str, doc: dict) -> list[str]:
    """Strict schema of the serving-bench evidence artifact
    (scripts/bench_serve.py; judged by check_evidence's ``serving`` and
    ``speculative`` stages): decode rows each a tokens/s/chip measurement
    at one batch size carrying the NF4-vs-bf16 weight-bytes column, the
    prefill-share ablation rows, the two live-recomputed bit-identity
    markers, and the speculative-decode section (ISSUE 11) — an
    accept-rate × tokens/s/chip frontier over drafter × k plus its own
    live-recomputed identity markers (greedy speculative == plain paged
    decode; sampled speculative == the same per-request PRNG stream)."""
    errors = []
    for key in ("meta", "decode", "prefill_share", "bit_identity",
                "speculative", "tp_serving", "serve_resilience",
                "fleet_resilience", "moe_serving", "slo"):
        if key not in doc:
            errors.append(f"{path}: missing required key {key!r}")
    meta = doc.get("meta")
    if isinstance(meta, dict):
        for k in ("backend", "model", "family"):
            if not isinstance(meta.get(k), str):
                errors.append(f"{path}: meta.{k} must be a string")
    for name, row_keys in (
            ("decode", ("batch", "decode_ticks", "ms_per_tick",
                        "tokens_per_sec_per_chip", "quant",
                        "weight_bytes_bf16", "weight_bytes_nf4")),
            ("prefill_share", ("prefill_cap_tokens", "ticks",
                               "tokens_per_sec", "prefill_token_share"))):
        rows = doc.get(name)
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: {name!r} must be a non-empty list")
            continue
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                errors.append(f"{path}: {name}[{i}] is not an object")
                continue
            for k in row_keys:
                if k not in row:
                    errors.append(f"{path}: {name}[{i}] missing {k!r}")
                elif k == "quant":
                    if not isinstance(row[k], str):
                        errors.append(f"{path}: {name}[{i}].quant is not "
                                      "a string")
                elif not _finite_number(row[k]):
                    errors.append(f"{path}: {name}[{i}].{k} is not finite")
    bits = doc.get("bit_identity")
    if isinstance(bits, dict):
        for k in ("paged_vs_dense", "batched_vs_solo"):
            if not isinstance(bits.get(k), bool):
                errors.append(f"{path}: bit_identity.{k} must be a bool")
    spec = doc.get("speculative")
    if spec is not None and not isinstance(spec, dict):
        errors.append(f"{path}: 'speculative' must be an object")
    elif isinstance(spec, dict):
        marks = spec.get("markers")
        if not isinstance(marks, dict):
            errors.append(f"{path}: speculative.markers must be an object")
        else:
            for k in ("greedy_vs_plain", "sampled_vs_stream"):
                if not isinstance(marks.get(k), bool):
                    errors.append(
                        f"{path}: speculative.markers.{k} must be a bool")
        rows = spec.get("frontier")
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: speculative.frontier must be a "
                          "non-empty list")
            rows = []
        for i, row in enumerate(rows):
            where = f"{path}: speculative.frontier[{i}]"
            if not isinstance(row, dict):
                errors.append(f"{where} is not an object")
                continue
            for k in ("drafter", "workload"):
                if not isinstance(row.get(k), str):
                    errors.append(f"{where}.{k} must be a string")
            if not (isinstance(row.get("k"), int)
                    and not isinstance(row.get("k"), bool)
                    and row["k"] >= 0):
                errors.append(f"{where}.k must be a non-negative int")
            for k in ("ms_per_tick", "tokens_per_sec_per_chip",
                      "proposed", "accepted"):
                if not _finite_number(row.get(k)):
                    errors.append(f"{where}.{k} is not finite")
            ar = row.get("accept_rate")
            if not (_finite_number(ar) and 0.0 <= ar <= 1.0):
                errors.append(f"{where}.accept_rate must be a finite "
                              "number in [0, 1]")
    tps = doc.get("tp_serving")
    if tps is not None and not isinstance(tps, dict):
        errors.append(f"{path}: 'tp_serving' must be an object")
    elif isinstance(tps, dict):
        marks = tps.get("markers")
        if not isinstance(marks, dict):
            errors.append(f"{path}: tp_serving.markers must be an object")
        else:
            for k in ("tp1_vs_unsharded", "tpN_vs_unsharded",
                      "shared_vs_unshared_greedy",
                      "shared_vs_unshared_sampled",
                      "shared_vs_unshared_speculative"):
                if not isinstance(marks.get(k), bool):
                    errors.append(
                        f"{path}: tp_serving.markers.{k} must be a bool")
        rows = tps.get("rows")
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: tp_serving.rows must be a non-empty "
                          "list")
            rows = []
        for i, row in enumerate(rows):
            where = f"{path}: tp_serving.rows[{i}]"
            if not isinstance(row, dict):
                errors.append(f"{where} is not an object")
                continue
            for k in ("tp", "batch", "decode_ticks"):
                if not (isinstance(row.get(k), int)
                        and not isinstance(row.get(k), bool)
                        and row[k] >= 0):
                    errors.append(f"{where}.{k} must be a non-negative int")
            for k in ("ms_per_tick_p50", "ms_per_tick_p99",
                      "tokens_per_sec_per_chip"):
                if not _finite_number(row.get(k)):
                    errors.append(f"{where}.{k} is not finite")
        pref = tps.get("prefix")
        if not isinstance(pref, dict):
            errors.append(f"{path}: tp_serving.prefix must be an object")
        else:
            for k in ("requests", "prompt_len", "logical_pages",
                      "physical_pages", "prefix_hits", "cow_copies"):
                if not (isinstance(pref.get(k), int)
                        and not isinstance(pref.get(k), bool)
                        and pref[k] >= 0):
                    errors.append(f"{path}: tp_serving.prefix.{k} must be "
                                  "a non-negative int")
            ratio = pref.get("prefix_mem_ratio")
            if not (_finite_number(ratio) and ratio > 0):
                errors.append(f"{path}: tp_serving.prefix.prefix_mem_ratio "
                              "must be a finite positive number")
    sr = doc.get("serve_resilience")
    if sr is not None and not isinstance(sr, dict):
        errors.append(f"{path}: 'serve_resilience' must be an object")
    elif isinstance(sr, dict):
        marks = sr.get("markers")
        if not isinstance(marks, dict):
            errors.append(f"{path}: serve_resilience.markers must be an "
                          "object")
        else:
            for k in ("migrated_identity_greedy",
                      "migrated_identity_sampled",
                      "migrated_identity_speculative",
                      "migrated_identity_prefix_cache",
                      "zero_token_loss", "drain_completes_residents",
                      "slow_detected_and_routed", "rejoin_serves"):
                if not isinstance(marks.get(k), bool):
                    errors.append(
                        f"{path}: serve_resilience.markers.{k} must be a "
                        "bool")
        rows = sr.get("crash_matrix")
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: serve_resilience.crash_matrix must be "
                          "a non-empty list")
            rows = []
        for i, row in enumerate(rows):
            where = f"{path}: serve_resilience.crash_matrix[{i}]"
            if not isinstance(row, dict):
                errors.append(f"{where} is not an object")
                continue
            for k in ("crash_tick", "migrated", "tokens_lost",
                      "recovery_latency_ticks"):
                if not (isinstance(row.get(k), int)
                        and not isinstance(row.get(k), bool)
                        and row[k] >= 0):
                    errors.append(f"{where}.{k} must be a non-negative int")
            if not isinstance(row.get("identical"), bool):
                errors.append(f"{where}.identical must be a bool")
        slow = sr.get("slow")
        if not isinstance(slow, dict):
            errors.append(f"{path}: serve_resilience.slow must be an "
                          "object")
        else:
            for k in ("p99_ms_slow_replica", "p99_ms_clean_replica",
                      "p99_ms_clean_run"):
                if not _finite_number(slow.get(k)):
                    errors.append(f"{path}: serve_resilience.slow.{k} is "
                                  "not finite")
            for k in ("slow_ms", "admissions_slow", "admissions_fast"):
                if not (isinstance(slow.get(k), int)
                        and not isinstance(slow.get(k), bool)
                        and slow[k] >= 0):
                    errors.append(f"{path}: serve_resilience.slow.{k} must "
                                  "be a non-negative int")
            for k in ("detected", "identical"):
                if not isinstance(slow.get(k), bool):
                    errors.append(f"{path}: serve_resilience.slow.{k} must "
                                  "be a bool")
        for section, bool_keys in (
                ("drain", ("identical", "drained_departed")),
                ("rejoin", ("rejoined", "served_after_rejoin",
                            "identical"))):
            sec = sr.get(section)
            if not isinstance(sec, dict):
                errors.append(f"{path}: serve_resilience.{section} must be "
                              "an object")
                continue
            for k in bool_keys:
                if not isinstance(sec.get(k), bool):
                    errors.append(f"{path}: serve_resilience.{section}.{k} "
                                  "must be a bool")
    fr = doc.get("fleet_resilience")
    if fr is not None and not isinstance(fr, dict):
        errors.append(f"{path}: 'fleet_resilience' must be an object")
    elif isinstance(fr, dict):
        marks = fr.get("markers")
        if not isinstance(marks, dict):
            errors.append(f"{path}: fleet_resilience.markers must be an "
                          "object")
        else:
            for k in ("sigkill_identity", "sigkill_zero_token_loss",
                      "process_isolated", "restart_identity",
                      "restart_prefill_saved", "socket_soak_served"):
                if not isinstance(marks.get(k), bool):
                    errors.append(
                        f"{path}: fleet_resilience.markers.{k} must be a "
                        "bool")
        rows = fr.get("kill_matrix")
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: fleet_resilience.kill_matrix must be "
                          "a non-empty list")
            rows = []
        for i, row in enumerate(rows):
            where = f"{path}: fleet_resilience.kill_matrix[{i}]"
            if not isinstance(row, dict):
                errors.append(f"{where} is not an object")
                continue
            for k in ("kill_tick", "migrated", "declared_dead",
                      "tokens_lost", "completed"):
                if not (isinstance(row.get(k), int)
                        and not isinstance(row.get(k), bool)
                        and row[k] >= 0):
                    errors.append(f"{where}.{k} must be a non-negative int")
            if row.get("sampling") not in ("greedy", "stochastic"):
                errors.append(f"{where}.sampling must be "
                              "'greedy'|'stochastic'")
            for k in ("identical", "process_isolated"):
                if not isinstance(row.get(k), bool):
                    errors.append(f"{where}.{k} must be a bool")
        restart = fr.get("restart")
        if not isinstance(restart, dict):
            errors.append(f"{path}: fleet_resilience.restart must be an "
                          "object")
        else:
            for k in ("inflight_at_stop", "restored", "chains_primed",
                      "resumed_from_tick", "prefill_tokens_saved"):
                if not (isinstance(restart.get(k), int)
                        and not isinstance(restart.get(k), bool)
                        and restart[k] >= 0):
                    errors.append(f"{path}: fleet_resilience.restart.{k} "
                                  "must be a non-negative int")
            if not isinstance(restart.get("identical"), bool):
                errors.append(f"{path}: fleet_resilience.restart."
                              "identical must be a bool")
        soak = fr.get("socket_soak")
        if not isinstance(soak, dict):
            errors.append(f"{path}: fleet_resilience.socket_soak must be "
                          "an object")
        else:
            for k in ("requests", "completed", "rejects", "retries",
                      "tokens_out"):
                if not (isinstance(soak.get(k), int)
                        and not isinstance(soak.get(k), bool)
                        and soak[k] >= 0):
                    errors.append(f"{path}: fleet_resilience.socket_soak."
                                  f"{k} must be a non-negative int")
            for k in ("wall_s", "goodput_tokens_per_s"):
                if not _finite_number(soak.get(k)):
                    errors.append(f"{path}: fleet_resilience.socket_soak."
                                  f"{k} is not finite")
            sha = soak.get("stream_sha256")
            if not (isinstance(sha, str)
                    and re.fullmatch(r"[0-9a-f]{64}", sha)):
                errors.append(f"{path}: fleet_resilience.socket_soak."
                              "stream_sha256 must be a 64-hex-char "
                              "sha256 digest")
    moe = doc.get("moe_serving")
    if moe is not None and not isinstance(moe, dict):
        errors.append(f"{path}: 'moe_serving' must be an object")
    elif isinstance(moe, dict):
        marks = moe.get("markers")
        if not isinstance(marks, dict):
            errors.append(f"{path}: moe_serving.markers must be an object")
        else:
            for k in ("paged_vs_dense", "batched_vs_solo",
                      "batched_generate_vs_solo", "ep1_vs_unsharded",
                      "epN_vs_unsharded", "ep_tp_vs_unsharded",
                      "ep_batch1_vs_unsharded", "ep_batchN_vs_unsharded",
                      "ep_batch_tp_vs_unsharded",
                      "ep_batch_overlap_vs_unsharded"):
                if not isinstance(marks.get(k), bool):
                    errors.append(
                        f"{path}: moe_serving.markers.{k} must be a bool")
        rows = moe.get("rows")
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: moe_serving.rows must be a non-empty "
                          "list")
            rows = []
        for i, row in enumerate(rows):
            where = f"{path}: moe_serving.rows[{i}]"
            if not isinstance(row, dict):
                errors.append(f"{where} is not an object")
                continue
            if not isinstance(row.get("config"), str):
                errors.append(f"{where}.config must be a string")
            for k in ("experts", "ep", "batch", "decode_ticks"):
                if not (isinstance(row.get(k), int)
                        and not isinstance(row.get(k), bool)
                        and row[k] >= 0):
                    errors.append(f"{where}.{k} must be a non-negative int")
            for k in ("ms_per_tick", "tokens_per_sec_per_chip"):
                if not _finite_number(row.get(k)):
                    errors.append(f"{where}.{k} is not finite")
            if row.get("sharding") not in ("none", "replicated", "batch"):
                errors.append(f"{where}.sharding must be one of "
                              "'none' | 'replicated' | 'batch'")
            if not isinstance(row.get("beats_dense_per_chip"), bool):
                errors.append(f"{where}.beats_dense_per_chip must be a "
                              "bool")
            for k in ("capacity_utilization", "dropped_rate"):
                v = row.get(k)
                if not (_finite_number(v) and 0.0 <= v <= 1.0):
                    errors.append(f"{where}.{k} must be a finite number "
                                  "in [0, 1]")
    slo = doc.get("slo")
    if slo is not None and not isinstance(slo, dict):
        errors.append(f"{path}: 'slo' must be an object")
    elif isinstance(slo, dict):
        marks = slo.get("markers")
        if not isinstance(marks, dict):
            errors.append(f"{path}: slo.markers must be an object")
        else:
            for k in ("metrics_inert", "zero_token_loss",
                      "responses_timed"):
                if not isinstance(marks.get(k), bool):
                    errors.append(f"{path}: slo.markers.{k} must be a bool")
        for k in ("requests", "tokens_out", "tokens_lost", "ticks",
                  "breaches"):
            if not (isinstance(slo.get(k), int)
                    and not isinstance(slo.get(k), bool)
                    and slo[k] >= 0):
                errors.append(f"{path}: slo.{k} must be a non-negative int")
        targets = slo.get("targets")
        if not isinstance(targets, dict):
            errors.append(f"{path}: slo.targets must be an object")
        else:
            for k in ("ttft_ms", "tok_ms"):
                if not (_finite_number(targets.get(k)) and targets[k] > 0):
                    errors.append(f"{path}: slo.targets.{k} must be a "
                                  "finite positive number")
            p = targets.get("p99")
            if not (_finite_number(p) and 0.0 < p < 1.0):
                errors.append(f"{path}: slo.targets.p99 must be a finite "
                              "number in (0, 1)")
        # percentile sketches must be non-negative AND ordered: a banked
        # p50 > p99 means the sketch (or the banking code) is lying, and
        # a latency can never be negative — both shapes the slo stage
        # must refuse, not average over
        for sec in ("ttft_ms", "tok_ms"):
            q = slo.get(sec)
            if not isinstance(q, dict):
                errors.append(f"{path}: slo.{sec} must be an object")
                continue
            bad = False
            for k in ("p50", "p95", "p99"):
                v = q.get(k)
                if not (_finite_number(v) and v >= 0):
                    errors.append(f"{path}: slo.{sec}.{k} must be a "
                                  "non-negative finite number")
                    bad = True
            if not bad and not (q["p50"] <= q["p95"] <= q["p99"]):
                errors.append(f"{path}: slo.{sec} percentiles must be "
                              "ordered p50 <= p95 <= p99")
        gp = slo.get("goodput_tokens_per_sec")
        if not (_finite_number(gp) and gp >= 0):
            errors.append(f"{path}: slo.goodput_tokens_per_sec must be a "
                          "non-negative finite number")
        counts = slo.get("status_counts")
        if not isinstance(counts, dict):
            errors.append(f"{path}: slo.status_counts must be an object")
        else:
            for k in ("eos", "length", "overflow", "timeout", "failed"):
                if k not in counts:
                    errors.append(f"{path}: slo.status_counts missing "
                                  f"{k!r}")
            for k, v in counts.items():
                if not (isinstance(v, int) and not isinstance(v, bool)
                        and v >= 0):
                    errors.append(f"{path}: slo.status_counts.{k} must be "
                                  "a non-negative int")
    return errors


def _dcn_overlap_errors(path: str, doc: dict) -> list[str]:
    """Strict schema of the DCN-overlap evidence artifact
    (scripts/bench_dcn.py; judged by check_evidence's ``dcn_overlap``
    stage): the four evidence sections present, ablation rows carrying
    finite timings, and every frontier row a
    bits-per-param × steps-to-loss point (``steps_to_loss`` null = the
    target was never reached within the leg's budget — allowed, but the
    key must exist so a silently-dropped measurement can't masquerade as
    a complete table)."""
    errors = []
    for key in ("meta", "bit_identity", "ablation", "overlap", "frontier",
                "parity"):
        if key not in doc:
            errors.append(f"{path}: missing required key {key!r}")
    for name, row_keys in (("ablation",
                            ("depth", "ms_per_step",
                             "dcn_wait_ms_per_step")),
                           ("frontier",
                            ("wire", "bits_per_param", "steps_to_loss",
                             "target_loss", "final_loss"))):
        rows = doc.get(name)
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: {name!r} must be a non-empty list")
            continue
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                errors.append(f"{path}: {name}[{i}] is not an object")
                continue
            for k in row_keys:
                if k not in row:
                    errors.append(f"{path}: {name}[{i}] missing {k!r}")
                elif k != "steps_to_loss" and not (
                        isinstance(row[k], str) if k == "wire"
                        else _finite_number(row[k])):
                    errors.append(f"{path}: {name}[{i}].{k} is not "
                                  f"{'a string' if k == 'wire' else 'finite'}")
    for section, key in (("overlap", "pass"), ("parity", "pass")):
        sec = doc.get(section)
        if isinstance(sec, dict) and not isinstance(sec.get(key), bool):
            errors.append(f"{path}: {section}.{key} must be a bool")
    return errors


def _elasticity_errors(path: str, doc: dict) -> list[str]:
    """Strict schema of the live-elasticity evidence artifact
    (scripts/bench_elasticity.py; judged by check_evidence's
    ``elasticity`` stage): the headline drop/rejoin scenario's survival
    facts, the two degraded-phase bit-identity markers, the journal-read
    membership timeline (per-row validated — every row one control-plane
    event with step/cause/quorum), and the pre-registered post-rejoin
    parity judgement."""
    errors = []
    for key in ("meta", "survive", "bit_identity", "timeline", "parity"):
        if key not in doc:
            errors.append(f"{path}: missing required key {key!r}")
        elif key != "timeline" and not isinstance(doc[key], dict):
            # a present-but-wrong-type section must fail the strict
            # schema, not slip past the per-field checks (which would let
            # check_evidence's judgement crash on it downstream)
            errors.append(f"{path}: {key!r} must be an object")
    meta = doc.get("meta")
    if isinstance(meta, dict):
        if not isinstance(meta.get("backend"), str):
            errors.append(f"{path}: meta.backend must be a string")
        for k in ("world", "steps", "drop_worker", "drop_step",
                  "rejoin_step"):
            if not isinstance(meta.get(k), int):
                errors.append(f"{path}: meta.{k} must be an integer")
    sv = doc.get("survive")
    if isinstance(sv, dict):
        for k in ("completed", "finite"):
            if not isinstance(sv.get(k), bool):
                errors.append(f"{path}: survive.{k} must be a bool")
        for k in ("steps", "left_events", "rejoin_events", "final_alive"):
            if not isinstance(sv.get(k), int):
                errors.append(f"{path}: survive.{k} must be an integer")
        lc = sv.get("final_lifecycle")
        if not (isinstance(lc, list) and lc
                and all(isinstance(s, str) for s in lc)):
            errors.append(f"{path}: survive.final_lifecycle must be a "
                          "non-empty list of state names")
    bits = doc.get("bit_identity")
    if isinstance(bits, dict):
        for k in ("degraded_vs_masked", "drop_deterministic"):
            if not isinstance(bits.get(k), bool):
                errors.append(f"{path}: bit_identity.{k} must be a bool")
    rows = doc.get("timeline")
    if not isinstance(rows, list) or not rows:
        errors.append(f"{path}: 'timeline' must be a non-empty list")
    else:
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                errors.append(f"{path}: timeline[{i}] is not an object")
                continue
            if not isinstance(row.get("event"), str):
                errors.append(f"{path}: timeline[{i}].event must be a "
                              "string")
            for k in ("step", "alive", "world"):
                if not isinstance(row.get(k), int):
                    errors.append(f"{path}: timeline[{i}].{k} must be an "
                                  "integer")
    par = doc.get("parity")
    if isinstance(par, dict):
        if not isinstance(par.get("pass"), bool):
            errors.append(f"{path}: parity.pass must be a bool")
        if not isinstance(par.get("scale"), str):
            errors.append(f"{path}: parity.scale must be a string")
        for k in ("bound_nats", "rejoin_gap_nats", "tail_frac"):
            if not _finite_number(par.get(k)):
                errors.append(f"{path}: parity.{k} is not finite")
    return errors


_SHA256 = re.compile(r"^[0-9a-f]{64}$")


# the serve-plane graft-check matrix (analysis/serve_check.MATRIX): the
# banked artifact must carry every cell — a missing cell means a config
# axis silently dropped out of the contract. Kept as a literal so this
# validator stays importable on boxes without jax
# (tests/test_serve_check.py pins it against the live MATRIX).
_SERVE_CHECK_FORMAT = "dlt-serve-check-v1"
_SERVE_CHECK_CELLS = (
    "dense_tp0_bf16", "dense_tp0_nf4", "dense_tp1_bf16", "dense_tp2_bf16",
    "dense_tp2_nf4", "dense_tp0_ngram", "moe_ep1_bf16", "moe_ep2_bf16",
    "moe_ep2_batch_bf16", "moe_ep2_batch_tp2_bf16", "moe_ep2_nf4",
    "moe_ep2_ngram",
)


def _serve_check_errors(path: str, doc: dict) -> list[str]:
    """Strict schema of the serve-plane graft-check artifact
    (``python -m distributed_lion_tpu.analysis serve-check --json-out``;
    gated by check_evidence's ``static_serve`` stage). The deep fields
    are RE-DERIVED, not trusted: a forged ``ok: true`` over a mismatched
    inventory, a present host callback, lost donation, or an over-budget
    compile count is rejected from the document alone."""
    errors = []
    if doc.get("format") != _SERVE_CHECK_FORMAT:
        errors.append(f"{path}: format must be {_SERVE_CHECK_FORMAT!r}")
    if doc.get("ok") is not True:
        errors.append(f"{path}: top-level ok must be true")
    if not isinstance(doc.get("world"), int) or doc.get("world", 0) < 4:
        errors.append(f"{path}: world must be an int >= 4 (full matrix)")
    for k in ("backend", "jax"):
        if not isinstance(doc.get(k), str):
            errors.append(f"{path}: {k!r} must be a string")
    cells = doc.get("cells")
    if not isinstance(cells, list) or not cells:
        errors.append(f"{path}: 'cells' must be a non-empty list")
        cells = []
    names = [c.get("cell") for c in cells if isinstance(c, dict)]
    for want in _SERVE_CHECK_CELLS:
        if want not in names:
            errors.append(f"{path}: matrix cell {want!r} missing")
    for cell in cells:
        if not isinstance(cell, dict):
            errors.append(f"{path}: cell entry is not an object")
            continue
        cname = cell.get("cell", "?")
        if cell.get("ok") is not True:
            errors.append(f"{path}: cells[{cname}].ok must be true")
        disp = cell.get("dispatches")
        if not isinstance(disp, dict) or not disp:
            errors.append(f"{path}: cells[{cname}].dispatches must be a "
                          "non-empty object")
            continue
        need = {"decode", "cow"}
        if cell.get("speculate"):
            need.add("verify")
        if not any(d.startswith("prefill:") for d in disp):
            errors.append(f"{path}: cells[{cname}] has no prefill bucket "
                          "dispatch")
        for d in sorted(need - set(disp)):
            errors.append(f"{path}: cells[{cname}] missing dispatch "
                          f"{d!r}")
        for dname, rep in disp.items():
            if not isinstance(rep, dict):
                errors.append(f"{path}: cells[{cname}].{dname} is not an "
                              "object")
                continue
            where = f"cells[{cname}].{dname}"
            if rep.get("ok") is not True:
                errors.append(f"{path}: {where}.ok must be true")
            obs, exp = rep.get("observed"), rep.get("expected")
            if not isinstance(obs, list) or not isinstance(exp, list):
                errors.append(f"{path}: {where} observed/expected must be "
                              "lists")
            elif obs != exp:  # re-derived, not trusted from ok flags
                errors.append(f"{path}: {where} collective inventory "
                              f"mismatch: observed {obs} != expected "
                              f"{exp}")
            if rep.get("host_callbacks") != []:
                errors.append(f"{path}: {where} has host callbacks "
                              f"{rep.get('host_callbacks')}")
            don = rep.get("donation")
            if not isinstance(don, dict) or (
                    don.get("aliased_outputs", 0)
                    + don.get("buffer_donors", 0)) <= 0:
                errors.append(f"{path}: {where} page-pool donation absent "
                              f"({don})")
            if rep.get("weight_upcasts") or rep.get("param_upcasts"):
                errors.append(f"{path}: {where} carries weight upcasts")
    compiles = doc.get("compile")
    if not isinstance(compiles, list) or not compiles:
        errors.append(f"{path}: 'compile' must be a non-empty list")
        compiles = []
    for comp in compiles:
        if not isinstance(comp, dict):
            errors.append(f"{path}: compile entry is not an object")
            continue
        cname = comp.get("cell", "?")
        counts, budget = comp.get("counts"), comp.get("budget")
        if not isinstance(counts, dict) or not isinstance(budget, dict):
            errors.append(f"{path}: compile[{cname}] counts/budget must "
                          "be objects")
            continue
        if counts.get("prefill", 0) <= 0:
            errors.append(f"{path}: compile[{cname}] measured no prefill "
                          "compiles — workload did not run")
        for k, v in counts.items():  # re-derived over-budget check
            # v == -1 is the "cache size unreadable" sentinel — rejected:
            # an unmeasurable count cannot evidence the budget
            if not isinstance(v, int) or v < 0 or v > budget.get(k, 0):
                errors.append(f"{path}: compile[{cname}] {k}={v} exceeds "
                              f"budget {budget.get(k, 0)}")
    return errors


def validate_json_doc(path: str) -> list[str]:
    """Strict single-document JSON artifact check (crash bundles,
    checkpoint manifests, and any other ``*.json`` the repo writes):
    strict parse (NaN/Infinity tokens rejected), a top-level object, and —
    for the known artifact names — the writer's required keys with sane
    shapes. Returns violation strings (empty = valid)."""
    errors: list[str] = []
    try:
        with open(path) as f:
            raw = f.read()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    try:
        doc = json.loads(raw, parse_constant=_reject_constant)
    except ValueError as e:
        return [f"{path}: {e}"]
    if not isinstance(doc, dict):
        return [f"{path}: document is {type(doc).__name__}, not an object"]
    name = os.path.basename(path)
    if name == "dcn_overlap.json":
        return _dcn_overlap_errors(path, doc)
    if name == "serving.json":
        return _serving_errors(path, doc)
    if name == "serve_check.json" or doc.get("format") == _SERVE_CHECK_FORMAT:
        return _serve_check_errors(path, doc)
    if name == "elasticity.json":
        return _elasticity_errors(path, doc)
    for key in _DOC_SCHEMAS.get(name, ()):
        if key not in doc:
            errors.append(f"{path}: missing required key {key!r}")
    if name in _DOC_SCHEMAS and not isinstance(doc.get("step"), int):
        errors.append(f"{path}: 'step' must be an integer")
    if name == "manifest.json" and isinstance(doc.get("files"), dict):
        for rel, info in doc["files"].items():
            if not isinstance(info, dict):
                errors.append(f"{path}: files[{rel!r}] is not an object")
                continue
            if not _SHA256.match(str(info.get("sha256", ""))):
                errors.append(f"{path}: files[{rel!r}] has no valid sha256")
            if not isinstance(info.get("bytes"), int):
                errors.append(f"{path}: files[{rel!r}] has no integer bytes")
    elif name == "manifest.json" and "files" in doc:
        errors.append(f"{path}: 'files' must be an object")
    if name == "bundle.json" and "config" in doc and not isinstance(
            doc["config"], dict):
        errors.append(f"{path}: 'config' must be an object")
    return errors


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    failed = False
    for path in argv:
        if path.endswith(".jsonl"):
            # run-journal files (journal_rank<r>.jsonl + rotations,
            # journal_tail.jsonl in crash bundles) carry the journal
            # record schema; serve workloads (requests*.jsonl /
            # workload*.jsonl, the workload_gen output) and serve
            # responses (responses*.jsonl, the run_serve --out) carry
            # the serve/api line schemas; every other .jsonl is a
            # metrics log
            base = os.path.basename(path)
            if base.startswith("journal"):
                errors = validate_journal_file(path)
            elif base.startswith(("requests", "workload")):
                errors = validate_request_file(path)
            elif base.startswith("responses"):
                errors = validate_response_file(path)
            else:
                errors = validate_file(path)
        else:
            errors = validate_json_doc(path)
        if errors:
            failed = True
            for e in errors:
                print(f"INVALID {e}")
        else:
            print(f"ok {path}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
