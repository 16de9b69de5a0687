"""What every run of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the chip gate, compile accounting, the peaks
table and the result line. Nothing here knows a cell, a configuration, a
model family or a metric by name."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest() -> dict:
    return read_json(ROOT, "BENCHMARK.json")


def merge(base: dict, patch: dict) -> dict:
    """``patch`` laid over ``base``, dict by dict (for trial runs such as
    the rate sweep: ``run.py --override``)."""
    out = dict(base)
    for key, value in patch.items():
        out[key] = merge(out[key], value) if isinstance(value, dict) \
            and isinstance(out.get(key), dict) else value
    return out


def load_cell(name: str, manifest: dict | None = None) -> dict:
    """Everything that defines one cell, gathered from its own files:
    the ``workloads`` entry, the configuration file, the traffic mix and
    ``benchmark/workloads/<cell>.json`` (driver, program settings,
    limits of ``correct``)."""
    manifest = manifest or load_manifest()
    cell = dict(read_json(BENCH_DIR, "workloads", name + ".json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 cell.get("candidate"))
    if entry is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    config = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    cell.update(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=read_json(ROOT, config["file"]),
                traffic=read_json(BENCH_DIR, "traffic",
                                  entry["traffic"] + ".json"))

    def mine(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if mine(m)]
    return cell


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(cfg: dict):
    """The model family of a configuration: ``benchmark/families/
    <model_type>.py``, by the key its file carries (what a family gives is
    set out in ``families/__init__.py``)."""
    return load_module("families", cfg["model_type"])


def check_config_file(entry: dict, body: dict) -> None:
    """Hold a configuration file (``body``) to its ``configs`` entry and to
    what a cut configuration must state (the model-configs guide, section
    4): the source, every key changed from it, each size assumed and the
    deployment it stands for; then to its family's own assertions."""
    def need(ok, what):
        if not ok:
            raise ValueError(f"configuration {entry.get('name')!r}: {what}")

    need(body.get("source") == entry["source"],
         "source differs between BENCHMARK.json and the file")
    need(body.get("reduced") == entry["reduced"],
         "reduced differs between BENCHMARK.json and the file")
    missing = {"assumed", "deployment", "model_type"} - set(body)
    need(not missing, f"the file lacks {sorted(missing)}")
    published = body.get("published", {})
    for key in entry["reduced"]:
        need(key in body, f"reduced names {key!r}, which the file lacks")
        need(key in published and published[key] != body[key],
             f"reduced key {key!r} needs its published value, another than "
             "the file's own, under 'published'")
    try:
        family = load_family(body)
    except FileNotFoundError:
        need(False, f"no family file for model_type {body['model_type']!r}")
    family.check_config(body)


def seeded_weights(family, cfg: dict, seed: int, dtype, shardings=None):
    """The program's weights, made on the device from the seed in ONE
    jitted call, in the dtype they are used in. The key is an argument: a
    seed traced in as a constant would miss the compile cache every run."""
    import jax

    fn = jax.jit(lambda key: family.program_weights(key, cfg, dtype),
                 out_shardings=shardings)
    return fn(family.reference.seed_key(seed))


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero, printing no result,
    unless JAX found a TPU with exactly ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) != chips:
        print(f"benchmark: the cell asks for {chips} chip(s), JAX sees "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(device_kind: str) -> dict:
    table = read_json(BENCH_DIR, "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         "benchmark/peaks.json (a device that is not in the "
                         "table is an error, not a default)")
    return table["devices"][device_kind]


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip, as the backend reports it: the
    allocator's peak of live buffers (``peak_bytes_in_use``: weights,
    state, caches, inputs) plus the peak it set aside for the compiled
    programs' temporaries (``peak_bytes_reserved``), which the first
    number leaves out on a TPU (a 124M training step that needs 7.8 GB of
    activations read 1.5 GB without it)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


class CompileMeter:
    """Backend compile seconds and persistent-cache hits / misses, from
    jax.monitoring events (copied from ``chip_smoke.CompileMeter``)."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses}


class Check:
    """The numbers ``correct`` compares, each beside its limit."""

    def __init__(self):
        self.rows = []
        self.controls = {}   # precision -> Check, filled by control.py runs

    def add(self, name: str, value, limit, note: str = "") -> bool:
        value = float(value)
        ok = value == value and value <= float(limit)  # NaN fails
        self.rows.append((name, value, float(limit), ok, note))
        return ok

    def fail(self, name: str, note: str) -> None:
        self.rows.append((name, float("nan"), 0.0, False, note))

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)

    def print(self) -> None:
        for name, value, limit, ok, note in self.rows:
            print(f"[check] {name} = {value!r} (limit {limit!r}) "
                  f"{'ok' if ok else 'FAILED'}{' — ' + note if note else ''}",
                  flush=True)
