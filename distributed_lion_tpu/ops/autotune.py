"""Kernel autotune subsystem: device-keyed tile search with a persistent cache.

ROADMAP item 1's remaining levers are all TILE choices — flash-attention
fwd/bwd blocking, splash blocking, the Pallas lion ``row_block``, the vocab
chunk count, the vote-bucket count — and until now every one of them was a
hand-enumerated shell config. One bad hand pick (``flash@1024x1024``) hung
the compile for >14 minutes of chip time. This module makes tile choice a
MEASUREMENT with
three hard properties:

1. **Per-candidate timeout guards.** Every timed trial runs in a child
   process (its own session) under a hard wall-clock budget covering BOTH
   compile and run; on expiry the whole process group is SIGKILLed and the
   candidate is recorded as a timeout row. A pathological tile can cost one
   budget, never a window (:func:`run_trial_child`, the same process-group
   teardown discipline as ``bench.run_child``).
2. **Deterministic winner selection.** Candidates are generated in a fixed
   order (ascending block sizes — the smaller-VMEM-footprint tile first);
   the winner is the minimum measured ms with ties broken by generation
   order (:func:`select_winner`). Re-running the tuner over identical
   measurements reproduces the identical cache.
3. **A persistent, device-keyed cache.** Winners land in a strict-schema
   JSON document (``scripts/tuning_cache.json``) keyed by
   ``device_kind × knob × shape × dtype``. A cache produced on one device
   kind can never leak onto another (the key embeds
   ``jax.devices()[0].device_kind``); a corrupt or schema-violating cache
   is reported LOUDLY on stderr and treated as absent — defaults win, the
   run proceeds (:func:`load_cache`, :func:`validate_cache_doc` — the same
   strictness contract as ``scripts/validate_metrics.py``, which also
   validates the artifact in CI).

Resolution (the ONE resolver consulted by ``ops/attention`` ``auto``
dispatch, ``train/loop``'s ``kernel='auto'``/``vote_buckets`` auto, and
``bench.py``/``scripts/bench_sweep.py`` row provenance) is
:func:`lookup` — exact key first, then the ``"*"`` wildcard shape (written
by operators, never by the tuner). Elections are pinned bit-identical
tuned-vs-default (tests/test_autotune.py): every knob here changes WHERE
and WHEN work happens, never what is elected.

This module imports nothing heavier than the stdlib at import time, so
``scripts/check_evidence.py`` can validate the cache artifact without jax
(the same loadable-by-file-path discipline as ``train/resilience`` and
``analysis/lint``). jax is imported lazily inside trial execution and
device-kind discovery only.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Optional

CACHE_FORMAT = "dlt-tune-cache-v1"
# repo-layout default (this file lives at distributed_lion_tpu/ops/):
# <repo>/scripts/tuning_cache.json — override with $DLT_TUNE_CACHE
DEFAULT_CACHE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "scripts", "tuning_cache.json",
)

# the tunable surfaces. Each knob's cache value is a flat {str: int} dict
# consumed by exactly one resolver site:
#   flash_tiles    → ops.attention auto dispatch (block_q/kv + bwd tiles)
#   splash_tiles   → ops.attention (explicit splash with no caller tiles)
#   lion_row_block → optim.distributed_lion Pallas kernels (row_block)
#   vocab_chunks   → chunked-CE chunk count (bench/sweep provenance)
#   vote_buckets   → train.loop.resolve_auto_comm (vote_buckets sentinel)
KNOBS = ("flash_tiles", "splash_tiles", "lion_row_block", "vocab_chunks",
         "vote_buckets")

_SEP = "|"
_warned_paths: set = set()
_loaded: dict = {}  # path → entries memo (see load_cache / invalidate_cache)


def cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get("DLT_TUNE_CACHE") or DEFAULT_CACHE_PATH


def cache_key(device_kind: str, knob: str, shape: str, dtype: str) -> str:
    """``device_kind|knob|shape|dtype`` — the device kind is PART OF the
    key, so entries measured on one accelerator can never resolve on
    another (the device-key-mismatch-ignored contract)."""
    for part in (device_kind, knob, shape, dtype):
        if _SEP in part:
            raise ValueError(f"cache key part {part!r} contains {_SEP!r}")
    return _SEP.join((device_kind, knob, shape, dtype))


# ------------------------------------------------------------ strict schema

def validate_cache_doc(doc) -> list:
    """Violation strings (empty = valid) — the validate_metrics.py-style
    strict contract for the tuning-cache artifact. Checked by the loader
    (violations → loud fallback to defaults), by run_tune before every
    write, and by scripts/validate_metrics.py in CI."""
    errors: list = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, not an object"]
    if doc.get("format") != CACHE_FORMAT:
        errors.append(f"format is {doc.get('format')!r}, want {CACHE_FORMAT!r}")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        return errors + ["'entries' must be an object"]
    for key, e in entries.items():
        parts = key.split(_SEP)
        if len(parts) != 4 or not all(parts):
            errors.append(f"entries[{key!r}]: key is not "
                          "device_kind|knob|shape|dtype")
            continue
        if parts[1] not in KNOBS:
            errors.append(f"entries[{key!r}]: unknown knob {parts[1]!r}")
        if not isinstance(e, dict):
            errors.append(f"entries[{key!r}]: entry is not an object")
            continue
        val = e.get("value")
        if not isinstance(val, dict) or not val or not all(
                isinstance(k, str) and isinstance(v, int)
                and not isinstance(v, bool) for k, v in val.items()):
            errors.append(f"entries[{key!r}]: 'value' must be a non-empty "
                          "{str: int} object")
        ms = e.get("ms")
        if not isinstance(ms, (int, float)) or isinstance(ms, bool) \
                or not ms == ms or ms < 0:
            errors.append(f"entries[{key!r}]: 'ms' must be a finite "
                          "non-negative number")
    return errors


def load_cache(path: Optional[str] = None) -> dict:
    """entries dict from the cache artifact, or {} when absent. A corrupt
    or schema-violating cache is LOUD (stderr, once per path per process)
    and treated as absent: tuning is an optimization, so every failure
    mode degrades to the built-in defaults rather than blocking a run —
    but never silently."""
    p = cache_path(path)
    if p in _loaded:
        # memoized per process: the resolver runs at trace time (attention
        # auto dispatch), and a re-read per trace would be both wasteful
        # and a trace-determinism hazard if the file changed mid-run.
        # run_tune/tests call invalidate_cache() after writing.
        return _loaded[p]
    try:
        with open(p) as f:
            doc = json.load(f, parse_constant=lambda name: (_ for _ in ()).throw(
                ValueError(f"non-finite JSON constant {name!r}")))
    except FileNotFoundError:
        _loaded[p] = {}
        return {}
    except (OSError, ValueError) as e:
        if p not in _warned_paths:
            _warned_paths.add(p)
            print(f"[autotune] tuning cache {p} unreadable ({e}); "
                  "FALLING BACK to built-in defaults", file=sys.stderr)
        _loaded[p] = {}
        return {}
    errors = validate_cache_doc(doc)
    if errors:
        if p not in _warned_paths:
            _warned_paths.add(p)
            print(f"[autotune] tuning cache {p} fails schema validation "
                  f"({errors[0]}{' ...' if len(errors) > 1 else ''}); "
                  "FALLING BACK to built-in defaults", file=sys.stderr)
        _loaded[p] = {}
        return {}
    _loaded[p] = doc["entries"]
    return doc["entries"]


def invalidate_cache(path: Optional[str] = None) -> None:
    """Drop the load memo (and the warn-once latch) for ``path`` — or for
    every path when None. Call after writing the cache file."""
    if path is None:
        _loaded.clear()
        _warned_paths.clear()
    else:
        _loaded.pop(cache_path(path), None)
        _warned_paths.discard(cache_path(path))


def save_cache(entries: dict, path: Optional[str] = None) -> str:
    """Write {format, entries} atomically (tmp+rename, sorted keys, strict
    JSON) after re-validating — a tuner bug can never commit an artifact
    the loader would then loudly reject."""
    doc = {"format": CACHE_FORMAT, "entries": dict(sorted(entries.items()))}
    errors = validate_cache_doc(doc)
    if errors:
        raise ValueError(f"refusing to write invalid cache: {errors}")
    p = cache_path(path)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    tmp = p + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, allow_nan=False)
        f.write("\n")
    os.replace(tmp, p)
    invalidate_cache(p)  # the next lookup must see what was just written
    return p


# --------------------------------------------------------------- resolution

_device_kind_cache: Optional[str] = None


def current_device_kind() -> str:
    """``jax.devices()[0].device_kind`` (e.g. ``"TPU v5 lite"``, ``"cpu"``),
    memoized — the lazy jax import keeps this module stdlib-only for
    non-jax consumers (check_evidence, validate_metrics)."""
    global _device_kind_cache
    if _device_kind_cache is None:
        import jax

        _device_kind_cache = jax.devices()[0].device_kind
    return _device_kind_cache


def lookup(knob: str, shape: str, dtype: str, *,
           device_kind: Optional[str] = None,
           path: Optional[str] = None) -> Optional[dict]:
    """THE resolver: the tuned value dict for (device, knob, shape, dtype)
    or None. Exact shape key first, then the ``"*"`` wildcard shape (an
    operator escape hatch — the tuner itself only writes exact shapes,
    keeping every cached number a per-shape measurement, this repo's
    standing rule for tile generalization). Entries keyed to a different
    device kind are invisible by construction."""
    entries = load_cache(path)
    if not entries:
        return None
    dk = device_kind if device_kind is not None else current_device_kind()
    for s in (shape, "*"):
        e = entries.get(cache_key(dk, knob, s, dtype))
        if e is not None:
            return e["value"]
    return None


def attn_shape_key(t: int, head_dim: int) -> str:
    """Flash/splash tile keys vary over the tile-relevant dims only:
    sequence length and head_dim (batch×heads just scale the grid)."""
    return f"T{t}xD{head_dim}"


def resolve_attn_spec(spec: str, *, t: int, head_dim: int, dtype: str,
                      device_kind: Optional[str] = None,
                      path: Optional[str] = None) -> str:
    """``"auto"`` → the cache-tuned explicit spec (``flash@BQxBKV[@BQBxBKVB]``)
    when a flash_tiles entry exists for this device/shape/dtype, else
    ``spec`` unchanged. The provenance form of the same resolution
    ``ops.attention.attention`` applies at dispatch — bench.py records it
    in its row so a sweep log says what ``auto`` MEANT on that device."""
    if spec != "auto":
        return spec
    v = lookup("flash_tiles", attn_shape_key(t, head_dim), dtype,
               device_kind=device_kind, path=path)
    if not v:
        return spec
    # .get with 0-defaults, not [..]: the schema admits partial entries —
    # an operator-written bwd-only pin ({"block_q_bwd": …}) is a supported
    # dispatch case (ops/attention honors it the same way), and the two
    # consumers of the one resolver must agree on every cache entry.
    # 0 means "kernel default" in the spec grammar exactly as in the
    # attention kwargs, so flash@0x0@256x512 round-trips through
    # parse_attn_spec to the identical tile tuple.
    out = f"flash@{v.get('block_q', 0)}x{v.get('block_kv', 0)}"
    if v.get("block_q_bwd") or v.get("block_kv_bwd"):
        out += f"@{v.get('block_q_bwd', 0)}x{v.get('block_kv_bwd', 0)}"
    return out


# ------------------------------------------------------ candidate generation

def tile_candidates(knob: str, info: dict) -> list:
    """The fixed, ordered candidate list for one knob at one shape.
    Ordering is load-bearing: ascending sizes, and :func:`select_winner`
    breaks ms ties by list position — so ties resolve to the SMALLEST
    tile (least VMEM pressure), deterministically."""
    if knob in ("flash_tiles", "splash_tiles"):
        t = int(info["t"])
        sizes = [s for s in (128, 256, 512, 1024) if s <= max(t, 128)]
        cands = [{"block_q": bq, "block_kv": bkv}
                 for bq in sizes for bkv in sizes]
        # flash@1024x1024 hung the compile >14 min in round 3; keep it
        # OUT of the default grid — the timeout guard would absorb it, but
        # a known-bad tile should not burn a budget on every device
        return [c for c in cands
                if not (c["block_q"] == 1024 and c["block_kv"] == 1024)]
    if knob == "flash_tiles_bwd":  # phase 2 of the flash search (run_tune)
        t = int(info["t"])
        sizes = [s for s in (128, 256, 512, 1024) if s <= max(t, 128)]
        return [{"block_q_bwd": bq, "block_kv_bwd": bkv}
                for bq in sizes for bkv in sizes]
    if knob == "lion_row_block":
        return [{"row_block": rb} for rb in (128, 256, 512, 1024, 2048)]
    if knob == "vocab_chunks":
        v = int(info["v"])
        return [{"vocab_chunks": c} for c in (1, 2, 4, 8, 16, 32) if c <= v]
    if knob == "vote_buckets":
        return [{"vote_buckets": b} for b in (1, 2, 4, 8, 16)]
    raise ValueError(f"unknown knob {knob!r}")


def select_winner(results: list) -> Optional[dict]:
    """Deterministic winner from trial results
    (``[{"candidate", "ms"|None, "error"|None}, ...]`` in candidate order):
    minimum ms, ties broken by candidate order (earlier = smaller tile
    wins). None when no candidate produced a measurement."""
    best = None
    for idx, r in enumerate(results):
        ms = r.get("ms")
        if ms is None:
            continue
        if best is None or ms < best[0]:
            best = (ms, idx, r)
    if best is None:
        return None
    return {"candidate": best[2]["candidate"], "ms": best[0],
            "index": best[1]}


# ------------------------------------------------------------- timed trials

def _time_jitted(fn, args, iters: int) -> float:
    """min wall ms over ``iters`` calls after one warmup (compile) call.
    The warmup's block_until_ready keeps compile out of the timed window;
    min (not mean) because scheduler noise only ever ADDS time."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def execute_trial(payload: dict) -> dict:
    """Run ONE candidate measurement in-process and return
    ``{"ms": float}`` or ``{"error": str}``. Called inside the
    timeout-guarded child (``run_tune --trial``) on hardware, or directly
    via ``run_tune --in-process`` where child-spawn latency dominates
    (CPU CI). ``_test_sleep_s`` is the timeout-guard test hook: it stalls
    the trial exactly like a wedged compile would, so tests can prove the
    guard kills a slow candidate without needing a real pathological tile.
    """
    if payload.get("_test_sleep_s"):
        time.sleep(float(payload["_test_sleep_s"]))
    if payload.get("knob") == "_probe":
        # backend discovery for the ORCHESTRATOR, run as a guarded child:
        # the parent must never initialize jax itself in child mode — on
        # TPU it would take the libtpu single-client lock and every trial
        # child would then fail to open the chip (the bench.py orchestrator
        # lesson, bench.py:590-596). "ms" 0.0 satisfies the child-result
        # shape contract of run_trial_child.
        import jax

        return {"ms": 0.0, "backend": jax.default_backend(),
                "device_kind": jax.devices()[0].device_kind}
    knob, cand, info = payload["knob"], payload["candidate"], payload["info"]
    iters = int(payload.get("iters", 5))
    import jax
    import jax.numpy as jnp

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.dtype(info.get("dtype", "float32"))
    try:
        if knob in ("flash_tiles", "flash_tiles_bwd", "splash_tiles"):
            if not on_tpu:
                return {"error": "unsupported: Pallas attention kernels "
                                 "need a TPU backend (xla fallback has no "
                                 "tiles to tune)"}
            from distributed_lion_tpu.ops.attention import (
                attention_flash,
                attention_splash,
            )

            b, h, t, d = (int(info[k]) for k in ("b", "h", "t", "d"))
            ks = jax.random.split(jax.random.key(0), 3)
            q, k, v = (jax.random.normal(kk, (b, h, t, d), dtype) for kk in ks)
            if knob == "splash_tiles":
                def fwd(q, k, v):
                    return attention_splash(q, k, v, **cand)
            else:
                tiles = dict(info.get("base", {}))
                tiles.update(cand)

                def fwd(q, k, v):
                    return attention_flash(q, k, v, **tiles)

            step = jax.jit(jax.grad(
                lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)))
            return {"ms": _time_jitted(step, (q, k, v), iters)}

        if knob == "lion_row_block":
            from distributed_lion_tpu.ops.pallas_lion import (
                fused_apply,
                fused_ballots,
                pallas_available,
            )

            n = int(info["n"])
            interpret = not pallas_available()
            key = jax.random.key(0)
            g = jax.random.normal(key, (n,), dtype)
            m = jnp.zeros((n,), dtype)
            p = jax.random.normal(jax.random.fold_in(key, 1), (n,), dtype)

            @jax.jit
            def step(p, g, m):
                ballots = fused_ballots(g, m, 0.9, interpret=interpret,
                                        row_block=cand["row_block"])
                return fused_apply(p, g, m, ballots.astype(jnp.int32),
                                   1e-4, 0.1, 0.99, interpret=interpret,
                                   row_block=cand["row_block"])

            return {"ms": _time_jitted(step, (p, g, m), iters)}

        if knob == "vocab_chunks":
            from distributed_lion_tpu.ops.xent import chunked_softmax_xent

            n, d, v = (int(info[k]) for k in ("n", "d", "v"))
            key = jax.random.key(0)
            hidden = jax.random.normal(key, (n, d), dtype)
            emb = jax.random.normal(jax.random.fold_in(key, 1), (v, d), dtype)
            labels = jnp.arange(n, dtype=jnp.int32) % v

            @jax.jit
            def step(hidden, emb):
                nll, _ = chunked_softmax_xent(
                    hidden, emb, labels, n_chunks=cand["vocab_chunks"])
                return jax.grad(
                    lambda h, e: chunked_softmax_xent(
                        h, e, labels,
                        n_chunks=cand["vocab_chunks"])[0].sum(),
                    argnums=(0, 1))(hidden, emb)

            return {"ms": _time_jitted(step, (hidden, emb), iters)}

        if knob == "vote_buckets":
            # single-host proxy: the bucket pipeline's per-bucket kernel
            # launches + window slicing at B buckets over an n-coordinate
            # ballot. The WIRE overlap itself is only measurable multi-chip
            # (the runbook's overlap ablation owns that number); this trial
            # ranks the launch-amortization side, which is what auto's B
            # controls on a given ballot size.
            from distributed_lion_tpu.ops.codec import bucket_bounds
            from distributed_lion_tpu.ops.pallas_lion import (
                fused_apply_window,
                fused_ballots_window,
                pallas_available,
            )

            n = int(info["n"])
            interpret = not pallas_available()
            bounds = bucket_bounds(n, cand["vote_buckets"], 1, "sign_psum")
            key = jax.random.key(0)
            g = jax.random.normal(key, (n,), dtype)
            m = jnp.zeros((n,), dtype)
            p = jax.random.normal(jax.random.fold_in(key, 1), (n,), dtype)

            @jax.jit
            def step(p, g, m):
                outs = []
                for start, ln in bounds:
                    ballots = fused_ballots_window(
                        g, m, 0.9, start=start, length=ln,
                        interpret=interpret)
                    outs.append(fused_apply_window(
                        p, g, m, ballots.astype(jnp.int32), 1e-4, 0.1, 0.99,
                        start=start, length=ln, interpret=interpret))
                return outs

            return {"ms": _time_jitted(step, (p, g, m), iters)}
    except Exception as e:  # a failed candidate is a ROW, not a crash:
        # the search must survive OOM/unsupported-tile errors per candidate
        return {"error": f"{type(e).__name__}: {e}"}
    return {"error": f"unknown knob {knob!r}"}


# ------------------------------------------------- the per-candidate guard

_trial_child: Optional[subprocess.Popen] = None


def _kill_trial_child() -> None:
    if _trial_child is not None and _trial_child.poll() is None:
        try:
            os.killpg(_trial_child.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def run_trial_child(payload: dict, timeout_s: float,
                    python: Optional[str] = None, journal=None) -> dict:
    """Run one trial in a child process under a HARD wall-clock budget
    covering compile AND run — the guard that makes a pathological tile
    cost one ``timeout_s``, never a window. The child runs in its own
    session; on expiry the whole process group is SIGKILLed (a wedged XLA
    compile ignores SIGTERM). Returns the child's JSON result, an
    ``{"error": "timeout ..."}`` row, or an ``{"error": "rc=..."}`` row —
    the search always continues.

    ``journal`` (train/journal.py's recorder, duck-typed so this module
    stays stdlib-only at import) gets one ``autotune/trial`` span per
    candidate — knob, candidate, measured ms or error, and the child's
    wall time including compile — so a tuning session's time budget is
    attributable candidate by candidate."""
    t_trial = time.monotonic()
    result = _run_trial_child(payload, timeout_s, python)
    journal_trial(journal, str(payload.get("knob")),
                  payload.get("candidate", {}), result, t_trial)
    return result


def journal_trial(journal, knob: str, candidate: dict, result: dict,
                  t0: float) -> None:
    """THE one autotune/trial span writer (run_trial_child and run_tune's
    in-process branch share it, so the record shape cannot drift). Flushes
    after every trial: a killed tuner must still leave a legible journal,
    the same discipline as the per-row stdout printing. Journaling errors
    warn and never break the search."""
    if journal is None:
        return
    try:
        journal.record({
            "kind": "span", "name": "autotune/trial",
            "dur": round(time.monotonic() - t0, 6),
            "knob": knob,
            "candidate": json.dumps(candidate, sort_keys=True,
                                    allow_nan=False),
            "ms": result.get("ms"), "error": result.get("error"),
        })
        flush = getattr(journal, "flush", None)
        if flush is not None:
            flush()
    except Exception as e:  # journaling must never break the search
        print(f"[autotune] journal record failed: {e}", file=sys.stderr)


def _run_trial_child(payload: dict, timeout_s: float,
                     python: Optional[str] = None) -> dict:
    global _trial_child
    cmd = [python or sys.executable, "-m",
           "distributed_lion_tpu.cli.run_tune", "--trial",
           json.dumps(payload, allow_nan=False)]
    _trial_child = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, err = _trial_child.communicate(timeout=timeout_s)
        rc = _trial_child.returncode
    except subprocess.TimeoutExpired:
        _kill_trial_child()
        _trial_child.wait()
        _trial_child = None
        return {"error": f"timeout after {timeout_s:.0f}s "
                         "(compile/run guard killed the candidate)"}
    _trial_child = None
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(d, dict) and ("ms" in d or "error" in d):
                return d
    tail = (err or out or "").strip().splitlines()[-3:]
    return {"error": (f"rc={rc}: " + " | ".join(tail))[:300]}


def install_trial_teardown() -> None:
    """SIGTERM/exit teardown for the current trial child — an outer driver
    timeout must never orphan a child holding the TPU lock (the bench.py
    lesson, applied to the tuner)."""
    import atexit

    signal.signal(signal.SIGTERM,
                  lambda s, f: (_kill_trial_child(), sys.exit(128 + s)))
    atexit.register(_kill_trial_child)
