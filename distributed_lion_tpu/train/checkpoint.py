"""Checkpoint save/restore with Orbax: async, atomic, self-verifying.

Replaces what the reference borrows from HF Trainer: last-checkpoint
autodetect (/root/reference/run_clm.py:289-302), ``resume_from_checkpoint``
(:604-610), ``save_total_limit`` rotation (README.md:34). One deliberate fix
over the reference: with ``--async_grad`` the Lion momenta are
per-worker-distinct, and HF Trainer saves only rank-0's optimizer state —
silent corruption on resume (SURVEY §5). Here the stacked ``[world, ...]``
momentum pytree is saved shard-by-shard via Orbax, so resume restores every
worker's momentum exactly.

Resilience layer (train/resilience.py is the companion module):

- **Async double-buffered saves** (``async_save=True``): ``save()`` kicks off
  the Orbax async write and returns after the device→host copy; the blocking
  ``wait_until_finished`` moves to the NEXT save boundary (and to
  ``close()``/anomaly paths), so serialization and disk I/O overlap the
  following train steps instead of stalling them. ``pop_stall_s()`` reports
  exactly how long the loop was blocked — the ``ckpt_stall_s`` metric that
  proves the overlap (tests pin async < sync).
- **Atomic commit + integrity manifest** (``integrity=True``): once Orbax
  finalizes a step, a background commit writes ``manifest.json`` (per-file
  sha256 + sizes + caller metadata like the world size) and then a
  ``COMMITTED`` marker — marker last, both via tmp+rename. A checkpoint
  without its marker was torn mid-commit and is never resumed from.
- **Verified autodetect**: ``latest_valid_step()`` re-hashes candidates
  newest-first and falls back to the newest GOOD checkpoint, so a torn leaf
  file or a bit-flipped manifest costs one save interval, not the run.
  Directories written before this layer existed (no ``MANIFESTS_ENABLED``
  stamp) are grandfathered as valid.
- **Retry/backoff** around the save call: transient I/O failures (flaky
  NFS/GCS) retry with exponential backoff before surfacing.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from distributed_lion_tpu.train import journal as run_journal
from distributed_lion_tpu.train import resilience
from distributed_lion_tpu.train.journal import emit
# the read side (verify, autodetect) lives in resilience.py so the
# dependency-light evidence checker can import it without jax/orbax;
# re-exported here because this module is the checkpoint API surface
from distributed_lion_tpu.train.resilience import (  # noqa: F401
    MANIFEST,
    MANIFEST_FORMAT,
    MANIFESTS_STAMP,
    MARKER,
    latest_valid_step_in,
    read_manifest,
    sha256_file as _sha256_file,
    verify_step_dir,
)


def _atomic_write(path: pathlib.Path, data: bytes) -> None:
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_bytes(data)
    os.replace(tmp, path)


def write_manifest(sdir: pathlib.Path, step: int,
                   meta: Optional[dict] = None) -> str:
    """Digest every data file under a finalized step directory into
    ``manifest.json``; returns the manifest's own sha256 (recorded in the
    commit marker so a corrupted manifest is caught without re-hashing)."""
    files = {}
    for p in sorted(sdir.rglob("*")):
        if p.is_file() and p.name not in (MANIFEST, MARKER):
            files[str(p.relative_to(sdir))] = {
                "sha256": _sha256_file(p), "bytes": p.stat().st_size}
    raw = json.dumps(
        {"format": MANIFEST_FORMAT, "step": int(step), "files": files,
         "meta": meta or {}},
        sort_keys=True, allow_nan=False).encode()
    _atomic_write(sdir / MANIFEST, raw)
    return hashlib.sha256(raw).hexdigest()


def _read_marker(sdir: pathlib.Path) -> Optional[dict]:
    return resilience.read_json(sdir / MARKER)


class Checkpointer:
    def __init__(self, directory: str | pathlib.Path,
                 save_total_limit: Optional[int] = None, *,
                 async_save: bool = False, integrity: bool = True,
                 max_retries: int = 3, retry_backoff_s: float = 0.1,
                 journal=None):
        # the run journal (train/journal.py; NULL no-op when the trainer
        # runs without --journal): caller-thread spans (ckpt/serialize,
        # ckpt/drain, opened through the gated journal.span like the rest
        # of the step loop's) are the step loop's checkpoint tax — the
        # same wall time the ckpt_stall_s ledger counts, cross-checked by
        # tests/test_journal.py — while the committer-thread spans
        # (thread="committer", recorded to this journal only) show where
        # the BACKGROUND commit spends its time without counting against
        # the step wall
        self._journal = journal if journal is not None else run_journal.NULL
        self.directory = pathlib.Path(directory).absolute()
        self.directory.mkdir(parents=True, exist_ok=True)
        self.integrity = integrity
        self.async_save = async_save
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.manager = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=save_total_limit,
                create=True,
                enable_async_checkpointing=async_save,
            ),
        )
        if integrity and jax.process_index() == 0:
            stamp = self.directory / MANIFESTS_STAMP
            if not stamp.exists():
                # don't retroactively invalidate a sync-era directory:
                # stamping flips 'no marker' from legacy-good to
                # torn-commit-reject, so it only happens when every
                # existing step already carries a marker (or none exist)
                legacy = any(
                    p.is_dir() and p.name.isdigit()
                    and not (p / MARKER).exists()
                    for p in self.directory.iterdir())
                if not legacy:
                    _atomic_write(stamp, b"1\n")
        self._executor = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="ckpt-commit")
            if async_save else None
        )
        self._inflight: Optional[Future] = None
        self._inflight_step: Optional[int] = None
        # stall ledger: wall time the CALLING thread spent blocked inside
        # save()/finalize() — the step loop's checkpoint tax. _unread is
        # drained by pop_stall_s() at the metrics-log cadence.
        self.total_stall_s = 0.0
        self.last_stall_s = 0.0
        self._unread_stall_s = 0.0

    # ----------------------------------------------------------------- save
    def save(self, step: int, payload: Any,
             meta: Optional[dict] = None) -> None:
        """Save a pytree (params / optimizer state / counters); sharded
        arrays are written distributed, one shard per host. With
        ``async_save`` this blocks only for the previous save's drain (the
        double-buffer wait, usually 0 once steps outlast serialization)
        plus the device→host copy; the write + digest + commit run behind
        the following train steps."""
        t0 = time.monotonic()
        drained = 0.0
        try:
            try:
                drained = self.finalize()  # accounts its own stall;
                # subtracted below so the drain isn't double-counted in
                # this save's ledger
            except Exception:
                # finalize's finally already ledgered the drain seconds;
                # mark them consumed so this save's finally doesn't add
                # the same wall time again on the way out
                drained = time.monotonic() - t0
                raise
            # caller-thread serialize span: the D2H copy + Orbax enqueue
            # (async) or the full serialize+write+commit (sync) — with the
            # drain above, the whole of save()'s step-loop tax
            with run_journal.span("ckpt/serialize", step=int(step)):
                delay = self.retry_backoff_s
                for attempt in range(self.max_retries + 1):
                    try:
                        if resilience.consume_fault_count("ckpt_save_raise"):
                            raise OSError("injected save fault")
                        self.manager.save(step,
                                          args=ocp.args.StandardSave(payload))
                        break
                    except Exception as e:
                        if attempt == self.max_retries:
                            # out of retries: re-raise with step/path context
                            # attached, same exception class so callers (and
                            # tests) matching on the original type still do
                            try:
                                wrapped = type(e)(
                                    f"checkpoint save(step={step}) under "
                                    f"{self.directory} failed after "
                                    f"{attempt + 1} attempts: {e}")
                            except Exception:
                                raise e  # exotic ctor signature: original as-is
                            raise wrapped from e
                        emit(f"[ckpt] save({step}) attempt {attempt + 1} "
                             f"failed ({e}); retrying in {delay:.2f}s")
                        time.sleep(delay)
                        delay *= 2
                if self._executor is not None:
                    self._inflight = self._executor.submit(self._commit, step,
                                                           meta)
                    self._inflight_step = step
                else:
                    self._commit(step, meta)
        finally:
            self._add_stall(max(time.monotonic() - t0 - drained, 0.0))

    def _commit(self, step: int, meta: Optional[dict]) -> Optional[int]:
        """Wait for Orbax to finalize the step, then write manifest + commit
        marker (marker LAST — its presence is the atomic commit point).
        Runs on the committer thread under async_save, inline otherwise."""
        with self._journal.span("ckpt/orbax_finalize", step=int(step),
                                thread="committer"):
            self.manager.wait_until_finished()
            slow = resilience.fault("ckpt_slow_commit")
            if slow:
                time.sleep(float(slow))
        if not self.integrity or jax.process_index() != 0:
            return step
        if resilience.fault("ckpt_crash_before_manifest"):
            return None  # simulated death after Orbax finalize, before commit
        sdir = self._step_dir(step)
        with self._journal.span("ckpt/digest", step=int(step),
                                thread="committer"):
            digest = write_manifest(sdir, step, meta)
        if resilience.fault("ckpt_crash_before_marker"):
            return None
        with self._journal.span("ckpt/commit_marker", step=int(step),
                                thread="committer"):
            _atomic_write(
                sdir / MARKER,
                json.dumps({"manifest_sha256": digest, "step": int(step),
                            "committed_at_unix": time.time()},
                           allow_nan=False).encode())
        return step

    def finalize(self) -> float:
        """Drain the in-flight async save, if any; returns the seconds this
        call blocked. An exception the committer thread hit (Orbax
        finalization, manifest I/O) is re-raised HERE — the drain boundary
        — with step/path context attached: swallowing it left the run
        believing in checkpoints that were never committed. (Injected
        crash-faults simulate death by returning early, not by raising, so
        the fault matrix still exercises the fall-back-past-it path.)"""
        if self._inflight is None:
            return 0.0
        t0 = time.monotonic()
        fut, step = self._inflight, self._inflight_step
        self._inflight, self._inflight_step = None, None
        try:
            with run_journal.span("ckpt/drain", step=int(step)):
                fut.result()
        except Exception as e:
            raise RuntimeError(
                f"checkpoint commit for step {step} under "
                f"{self._step_dir(step)} failed on the committer thread; "
                "that checkpoint was never committed and will not be "
                "resumed from") from e
        finally:
            dt = time.monotonic() - t0
            self._add_stall(dt)
        return dt

    def _add_stall(self, dt: float) -> None:
        self.total_stall_s += dt
        self.last_stall_s = dt
        self._unread_stall_s += dt

    def pop_stall_s(self) -> float:
        """Checkpoint-blocked seconds accrued since the last pop — the
        ``ckpt_stall_s`` metric."""
        out, self._unread_stall_s = self._unread_stall_s, 0.0
        return out

    # ------------------------------------------------------------- discovery
    def _step_dir(self, step: int) -> pathlib.Path:
        return self.directory / str(step)

    def latest_step(self) -> Optional[int]:
        """The reference's get_last_checkpoint autodetect (run_clm.py:289-302)
        — Orbax's view, integrity-unverified. Used only to dedupe saves;
        resume goes through :meth:`latest_valid_step`."""
        return self.manager.latest_step()

    def valid_steps(self) -> list[int]:
        """Committed-and-verified steps, newest first. In a pre-manifest
        (unstamped) directory, steps without markers are grandfathered."""
        steps = sorted((int(s) for s in self.manager.all_steps()),
                       reverse=True)
        if not self.integrity:
            return steps
        stamped = (self.directory / MANIFESTS_STAMP).exists()
        out = []
        for s in steps:
            sdir = self._step_dir(s)
            if verify_step_dir(sdir):
                out.append(s)
            elif not stamped and _read_marker(sdir) is None:
                out.append(s)  # legacy checkpoint from the sync-only era
        return out

    def latest_valid_step(self) -> Optional[int]:
        steps = self.valid_steps()
        return steps[0] if steps else None

    def purge_steps_after(self, step: int) -> list[int]:
        """Delete EVERY step newer than the resumed one. Left in place they
        poison Orbax's step ordering: with a step 1488 still on disk, a
        post-resume save at 1460 is silently dropped/rotated away, so the
        run makes progress it can never checkpoint again — and the
        ``latest_step()`` save dedupe would skip re-saving 1488 when the
        run re-reaches it. This applies to hash-VALID newer steps too (a
        step that verified but failed to restore): once the run resumed
        below them they are an abandoned future, and the deterministic
        replay re-creates them bit-identically anyway."""
        purged: list[int] = []
        failures: list[tuple[int, Exception]] = []
        for s in sorted(int(x) for x in self.manager.all_steps()):
            if s > step:
                try:
                    self.manager.delete(s)
                except Exception as e:
                    # keep purging the rest, then raise with full context:
                    # a stale step left on disk silently eats every future
                    # save below it — "could not purge" is not a warning
                    failures.append((s, e))
                    continue
                purged.append(s)
        if failures:
            detail = "; ".join(f"step {s} ({self._step_dir(s)}): {e}"
                               for s, e in failures)
            raise RuntimeError(
                f"could not purge stale checkpoint step(s) "
                f"{[s for s, _ in failures]} newer than the resumed step "
                f"{step} — left on disk they make Orbax silently drop every "
                f"post-resume save below them: {detail}") from failures[0][1]
        return purged

    def manifest_meta(self, step: int) -> Optional[dict]:
        """The caller metadata recorded at commit (world size, tag, data
        counters) — read before restore so elastic resume can size the
        template without guessing."""
        manifest = read_manifest(self._step_dir(step))
        return manifest.get("meta") if manifest else None

    # --------------------------------------------------------------- restore
    def restore(self, step: int, like: Any) -> Any:
        """Restore into the shardings/dtypes of ``like`` (an abstract or
        concrete pytree template)."""
        template = jax.tree.map(_as_abstract, like)
        return self.manager.restore(step, args=ocp.args.StandardRestore(template))

    def close(self) -> None:
        # the drain may re-raise a committer-thread failure; the executor
        # and Orbax manager must still be torn down before it propagates
        try:
            self.finalize()
        finally:
            if self._executor is not None:
                self._executor.shutdown(wait=True)
                self._executor = None
            self.manager.close()


def _as_abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    if isinstance(x, (np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
    return x
