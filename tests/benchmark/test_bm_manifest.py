"""BENCHMARK.json against the contract's form, and against the files the
harness will look for by name."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.lib import harness

MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in MANIFEST["end_to_end"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if "bound" in metric:                       # end to end
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
    else:                                       # per layer
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = next(m for m in MANIFEST["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(moved.get("workloads", CELLS))
        assert os.path.isfile(os.path.join(
            harness.BENCH_DIR, "layer_metrics", metric["name"] + ".py"))


def test_names_are_unique():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = harness.load_cell(name, MANIFEST)
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    assert NAME.match(name) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200 and entry["chips"] in (1, 4)
    assert os.path.isfile(os.path.join(
        harness.BENCH_DIR, "drivers", cell["driver"] + ".py"))
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell["per_layer"], "every cell reports a per-layer metric"
    assert cell["correct"]["limits"]


# configurations whose cut is pinned here: both fit one chip whole
UNCUT = ("gpt2-124m", "gpt2-xl")


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_file_states_source_and_widths(config):
    body = harness.read_json(harness.ROOT, config["file"])
    harness.check_config_file(config, body)   # the family's widths among it
    if config["name"] in UNCUT:
        assert body["reduced"] == config["reduced"] == []
        assert body["n_embd"] // body["n_head"] == 64   # published head size


def cut_config():
    """GPT-2 124M cut in depth, as a later configuration would be: the cut
    in ``reduced`` in both places, the published value beside it."""
    entry = dict(MANIFEST["configs"][0], reduced=["n_layer"])
    body = dict(harness.read_json(harness.ROOT, entry["file"]), n_layer=4,
                reduced=["n_layer"], published={"n_layer": 12})
    return entry, body


def test_a_configuration_cut_in_depth_passes():
    harness.check_config_file(*cut_config())


@pytest.mark.parametrize("fault", [
    "reduced_differs", "no_published_value", "published_value_is_the_same",
    "reduced_key_not_in_file", "no_family", "no_deployment",
    "family_refuses_widths"])
def test_config_check_refuses(fault):
    entry, body = cut_config()
    if fault == "reduced_differs":
        entry["reduced"] = []
    elif fault == "no_published_value":
        body["published"] = {}
    elif fault == "published_value_is_the_same":
        body["published"] = {"n_layer": 4}
    elif fault == "reduced_key_not_in_file":
        del body["n_layer"]
    elif fault == "no_family":
        body["model_type"] = "no-such-family"
    elif fault == "no_deployment":
        del body["deployment"]
    elif fault == "family_refuses_widths":
        body["n_head"] = 16                 # 768 / 16 = 48, not GPT-2's 64
    with pytest.raises((ValueError, AssertionError)):
        harness.check_config_file(entry, body)


def test_command_on_the_cpu_exits_nonzero_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert '"metrics"' not in line
        try:
            assert "correct" not in json.loads(line)
        except (ValueError, TypeError):
            pass
