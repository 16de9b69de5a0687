"""The off-chip side of the chip contract, CPU only: the compile cache is
placed from outside, nothing that measures pretends a CPU is a chip, and a
``--replica_procs`` parent leaves the device to its children."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}, **extra)
    return env


@pytest.fixture
def tpu_backend(monkeypatch):
    """enable_compilation_cache as a TPU process would see it, with every
    jax.config write recorded instead of applied."""
    import jax

    from distributed_lion_tpu.utils import compile_cache

    writes = {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: writes.__setitem__(k, v))
    monkeypatch.delenv("DLION_COMPILE_CACHE", raising=False)
    return compile_cache, writes


def test_cache_dir_left_alone_when_placed_from_outside(tpu_backend,
                                                       monkeypatch, tmp_path):
    compile_cache, writes = tpu_backend
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in writes


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(tpu_backend,
                                                              monkeypatch):
    compile_cache, writes = tpu_backend
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compilation_cache()
    assert got == writes["jax_compilation_cache_dir"] \
        == os.path.join(REPO, ".jax_compile_cache")
    # nothing about the process or the host may enter the path (the path
    # is part of the cache key: a directory that moves never hits)
    monkeypatch.setattr(os, "getpid", lambda: 4242)
    monkeypatch.setenv("HOSTNAME", "elsewhere")
    monkeypatch.setenv("HOME", "/nonexistent")
    monkeypatch.setenv("DLION_COMPILE_CACHE_DIR", "/ignored")
    assert compile_cache.enable_compilation_cache() == got
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_cache_stays_off_on_cpu_and_on_opt_out(tpu_backend, monkeypatch):
    import jax

    compile_cache, writes = tpu_backend
    monkeypatch.setenv("DLION_COMPILE_CACHE", "0")
    assert compile_cache.enable_compilation_cache() is None
    monkeypatch.delenv("DLION_COMPILE_CACHE")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert compile_cache.enable_compilation_cache() is None
    assert not writes


@pytest.mark.parametrize("script", ["chip_smoke.py",
                                    "scripts/flash_microbench.py"])
def test_chip_entry_points_refuse_the_cpu(script):
    """No accelerator -> non-zero exit within seconds and no result line."""
    proc = subprocess.run([sys.executable, os.path.join(REPO, script)],
                          capture_output=True, text=True, timeout=240,
                          env=_cpu_env(), cwd=REPO)
    assert proc.returncode != 0, proc.stdout[-500:]
    assert "TPU" in proc.stderr, proc.stderr[-500:]   # refused, not crashed
    # neither script's result line (`"ok": true` / a `B=... block` row)
    assert '"ok": true' not in proc.stdout and "B=" not in proc.stdout


def test_replica_procs_parent_stays_off_jax(tmp_path):
    """`run_serve --replica_procs`: the children own the device, so the
    parent must finish a whole serve without initializing a backend."""
    reqs = tmp_path / "requests.jsonl"
    reqs.write_text("".join(
        json.dumps({"id": f"c{i}", "tokens": [7 + i, 3, 5 + i],
                    "max_new_tokens": 3, "seed": i}) + "\n"
        for i in range(2)))
    code = (
        "import sys\n"
        "from distributed_lion_tpu.cli.run_serve import main\n"
        "recs = main(sys.argv[1:])\n"
        "from jax._src import xla_bridge\n"
        "assert len(recs) == 2 and all(r['n_generated'] == 3 for r in recs)\n"
        "assert not xla_bridge.backends_are_initialized(), 'parent on JAX'\n"
        "print('PARENT_OFF_JAX')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code, "--model_family", "gpt2",
         "--model_name", "tiny", "--requests", str(reqs), "--out",
         str(tmp_path / "responses.jsonl"), "--temperature", "0",
         "--max_seqs", "2", "--block_size", "4", "--replica_procs"],
        capture_output=True, text=True, timeout=300, env=_cpu_env(),
        cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PARENT_OFF_JAX" in proc.stdout


def test_replica_worker_reports_a_failed_build_in_its_hello():
    """A worker that cannot build its engine (or open its device) says so
    in the hello frame instead of dying silently."""
    from distributed_lion_tpu.serve.fleet_proc import (
        ProcessReplica, ReplicaGone)

    with pytest.raises(ReplicaGone, match="unknown replica builder kind"):
        ProcessReplica({"kind": "no-such-builder"}, spawn_timeout_s=120)
