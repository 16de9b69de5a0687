"""Kernel autotuner CLI: measure tile candidates, commit winners to the cache.

    python -m distributed_lion_tpu.cli.run_tune --preset flagship
    python -m distributed_lion_tpu.cli.run_tune --preset smoke --in-process
    python -m distributed_lion_tpu.cli.run_tune --knobs lion_row_block

Each candidate runs as a CHILD process under a hard per-candidate timeout
(``ops/autotune.run_trial_child``) covering compile AND run — round 3 lost
>14 min of chip time to one hand-picked flash tile (1024x1024) hanging the
compile; under the tuner the worst a pathological tile can cost is
``--timeout_s``. Winners (minimum ms, ties to the smallest tile —
``autotune.select_winner``) are merged into the device-keyed tuning cache
(``scripts/tuning_cache.json`` by default, ``$DLT_TUNE_CACHE`` override),
which ``ops/attention`` ``auto`` dispatch, the Trainer's ``kernel='auto'``
path and ``resolve_auto_comm``'s ``vote_buckets`` sentinel then consult.

``--in-process`` skips the child processes (no hang protection — a wedged
compile wedges the tuner) and exists for CPU CI, where the interpret/xla
fallbacks cannot hang and child-spawn latency would dominate. The knob set
degrades honestly off-TPU: flash/splash trials report
``unsupported`` (there is no tile to tune in the xla fallback) while
lion_row_block / vocab_chunks / vote_buckets still run, so a CPU pass
produces a valid — cpu-keyed, therefore TPU-inert — cache artifact that
exercises the full search/commit path end to end.

Prints one JSON summary line (runbook-parseable):
``{"tuned": {...}, "skipped": {...}, "backend": ..., "device_kind": ...,
"cache": path}``. Exit 0 when every requested knob either tuned or was
skipped-with-reason; exit 1 when a supported knob's candidates ALL failed
(that is a bug or a sick backend, not a tuning outcome).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from distributed_lion_tpu.ops import autotune

# Shape presets. 'flagship' is the ROADMAP-1 anchor workload — GPT-2 124M
# at the promoted bench config (microbatch 4 × T=1024, head_dim 64, bf16
# compute, 50257-vocab chunked CE, the 124,439,808-coordinate ballot).
# 'smoke' is the CPU CI scale: same structure, minutes not hours. The odd
# smoke coordinate counts are deliberate — they can never collide with a
# shape some test resolves through the committed cache.
PRESETS = {
    "flagship": {
        "attn": {"b": 4, "h": 12, "t": 1024, "d": 64, "dtype": "bfloat16"},
        # the flagship bench config runs bf16 momenta (mom_dtype bfloat16)
        "lion": {"n": 124_439_808, "dtype": "bfloat16"},
        "xent": {"n": 4096, "d": 768, "v": 50257, "dtype": "bfloat16"},
    },
    "smoke": {
        "attn": {"b": 1, "h": 2, "t": 128, "d": 64, "dtype": "float32"},
        "lion": {"n": 1_048_581, "dtype": "float32"},
        "xent": {"n": 256, "d": 64, "v": 509, "dtype": "float32"},
    },
}
# the knob whitelist is the schema's (ops/autotune.KNOBS) — one authority,
# so the CLI's validation and the cache validator cannot drift
DEFAULT_KNOBS = autotune.KNOBS


def _knob_info(knob: str, preset: dict) -> dict:
    if knob in ("flash_tiles", "splash_tiles"):
        return dict(preset["attn"])
    if knob in ("lion_row_block", "vote_buckets"):
        return dict(preset["lion"])
    if knob == "vocab_chunks":
        return dict(preset["xent"])
    raise ValueError(f"unknown knob {knob!r}")


def _shape_key(knob: str, info: dict) -> str:
    if knob in ("flash_tiles", "splash_tiles"):
        return autotune.attn_shape_key(info["t"], info["d"])
    if knob in ("lion_row_block", "vote_buckets"):
        return f"N{info['n']}"
    return f"N{info['n']}xV{info['v']}"


def _key_dtype(knob: str, info: dict) -> str:
    """The dtype component of the cache key: the dtype the knob's tiling
    actually varies over — qkv dtype for attention tiles, momentum dtype
    for the lion kernels, hidden dtype for chunked CE, and the constant
    int8 wire payload for vote_buckets (its resolver,
    train.loop.resolve_auto_comm, has no float dtype in scope)."""
    if knob == "vote_buckets":
        return "int8"
    return str(info.get("dtype", "float32"))


def _measure(knob: str, candidates: list, info: dict, args,
             base: dict | None = None, journal=None) -> list:
    """Candidate-ordered result rows for one knob; every row is printed as
    it lands so a killed tuner still leaves a legible trail."""
    results = []
    for cand in candidates:
        payload = {"knob": knob, "candidate": cand, "info": info,
                   "iters": args.iters}
        if base:
            payload["info"] = {**info, "base": base}
        if args.test_sleep_s:  # timeout-guard test hook (see autotune)
            payload["_test_sleep_s"] = args.test_sleep_s
        if args.in_process:
            t0 = time.monotonic()
            r = autotune.execute_trial(payload)
            # same span writer as the child path: one record shape, same
            # per-trial flush, same never-break-the-search guard
            autotune.journal_trial(journal, knob, cand, r, t0)
        else:
            r = autotune.run_trial_child(payload, args.timeout_s,
                                         journal=journal)
        row = {"knob": knob, "candidate": cand,
               "ms": r.get("ms"), "error": r.get("error")}
        print(json.dumps({k: v for k, v in row.items() if v is not None},
                         allow_nan=False), file=sys.stderr, flush=True)
        results.append(row)
        if r.get("error", "").startswith("unsupported"):
            break  # one unsupported row describes the whole knob
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--knobs", default=",".join(DEFAULT_KNOBS),
                    help="comma-separated subset of: " + ", ".join(DEFAULT_KNOBS))
    ap.add_argument("--preset", choices=sorted(PRESETS), default="flagship")
    ap.add_argument("--cache", default=None,
                    help="cache path (default scripts/tuning_cache.json "
                         "or $DLT_TUNE_CACHE)")
    ap.add_argument("--timeout_s", type=float, default=600.0,
                    help="per-candidate compile+run budget; on expiry the "
                         "candidate's process group is SIGKILLed")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--in-process", action="store_true",
                    help="run trials in this process (NO hang protection; "
                         "CPU CI only)")
    ap.add_argument("--skip_cached", action="store_true",
                    help="skip knobs that already hold a cache entry for "
                         "this device/shape/dtype — the runbook's re-fire "
                         "resume: a dropped window re-tunes only the "
                         "missing knobs")
    ap.add_argument("--journal_dir", default=None,
                    help="record a run journal (train/journal.py) of the "
                         "tuning session — one autotune/trial span per "
                         "candidate with knob, candidate, ms/error and "
                         "child wall time; analyze with cli/run_analyze")
    ap.add_argument("--test_sleep_s", type=float, default=0.0,
                    help=argparse.SUPPRESS)  # timeout-guard test hook
    ap.add_argument("--trial", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.trial is not None:
        # child mode: one guarded candidate — print the result JSON and out
        print(json.dumps(autotune.execute_trial(json.loads(args.trial)),
                         allow_nan=False), flush=True)
        return 0

    autotune.install_trial_teardown()
    # Backend discovery WITHOUT initializing jax in this process when
    # trials run as children: libtpu is single-client, so a parent that
    # opens the chip starves every trial child of it (bench.py's
    # orchestrator "never imports jax itself" for exactly this reason —
    # the CPU smoke path can't catch the mistake because CPUs have no
    # device lock). The probe is itself a guarded child; --in-process mode
    # runs trials here anyway, so there the direct import is correct.
    if args.in_process:
        import jax

        backend = jax.default_backend()
        device_kind = autotune.current_device_kind()
    else:
        probe = autotune.run_trial_child({"knob": "_probe"}, args.timeout_s)
        if "backend" not in probe:
            print(f"backend probe failed: {probe.get('error')}",
                  file=sys.stderr)
            return 1
        backend, device_kind = probe["backend"], probe["device_kind"]
    preset = PRESETS[args.preset]
    knobs = [k.strip() for k in args.knobs.split(",") if k.strip()]
    unknown = [k for k in knobs if k not in DEFAULT_KNOBS]
    if unknown:
        ap.error(f"unknown knob(s) {unknown}; pick from {DEFAULT_KNOBS}")

    jr = None
    if args.journal_dir:
        from distributed_lion_tpu.train.journal import Journal

        jr = Journal(args.journal_dir)
        jr.event("tune_start", preset=args.preset, backend=backend,
                 device_kind=device_kind)
    entries = dict(autotune.load_cache(args.cache))
    tuned: dict = {}
    skipped: dict = {}
    failed: dict = {}
    cache_file = None
    cached: dict = {}
    try:
        for knob in knobs:
            info = _knob_info(knob, preset)
            key = autotune.cache_key(device_kind, knob,
                                     _shape_key(knob, info),
                                     _key_dtype(knob, info))
            if args.skip_cached and key in entries:
                cached[knob] = key
                continue
            results = _measure(knob, autotune.tile_candidates(knob, info),
                               info, args, journal=jr)
            if results and str(results[-1].get("error", "")).startswith(
                    "unsupported"):
                skipped[knob] = results[-1]["error"]
                continue
            win = autotune.select_winner(results)
            if win is None:
                failed[knob] = [r.get("error") for r in results][:3]
                continue
            value = dict(win["candidate"])
            if knob == "flash_tiles":
                # phase 2: backward tiles, with the winning forward tiles
                # pinned (the bwd passes are ~2× the fwd FLOPs with
                # different operand shapes — VERDICT's named lever).
                # Deterministic: the phase-2 grid and tie-break are as
                # fixed as phase 1's.
                bwd = _measure(
                    "flash_tiles_bwd",
                    autotune.tile_candidates("flash_tiles_bwd", info),
                    info, args, base=value, journal=jr)
                bwin = autotune.select_winner(bwd)
                if bwin is not None:
                    value.update(bwin["candidate"])
                    win["ms"] = bwin["ms"]
            entries[key] = {
                "value": value,
                "ms": round(float(win["ms"]), 4),
                "backend": backend,
                "candidates": len(results),
                "measured": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime()),
            }
            tuned[knob] = {"key": key, "value": value,
                           "ms": entries[key]["ms"]}
            # commit after EVERY knob (atomic tmp+rename): a dropped TPU
            # window keeps the knobs it finished — the same at-most-one-
            # interval loss discipline as the parity legs' checkpoints
            cache_file = autotune.save_cache(entries, args.cache)
    finally:
        # flush/close even when a knob raises: a crashed or killed tuner
        # must still leave a legible journal (journal_trial flushed after
        # every candidate; this seals the file)
        if jr is not None:
            jr.event("tune_end", tuned=len(tuned), skipped=len(skipped),
                     failed=len(failed))
            jr.close()
    print(json.dumps({
        "tuned": tuned, "cached": cached, "skipped": skipped,
        "failed": failed, "backend": backend, "device_kind": device_kind,
        "cache": cache_file,
    }, allow_nan=False), flush=True)
    # exit contract: a knob whose trials ALL errored (not 'unsupported')
    # signals a sick backend or a tuner bug — loud, so the runbook stage
    # logs it red instead of quietly committing a partial cache
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
