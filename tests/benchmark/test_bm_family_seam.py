"""The family seam: a configuration that is not GPT-2 and is cut in depth
passes through the harness with no file of ``benchmark/`` naming it. The
stub (``stub_family.py``, ``stub_config.json``) takes cell 2's driver,
workload file and traffic at a tiny size on the CPU; the manifest and the
family lookup are pointed at it here, as a later PR's new files would be
found by name."""

import importlib.util
import os
import re
import time

import pytest

from benchmark import rehearse
from benchmark.lib import harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve.gpt2-xl.decode-backlog"
SEED = 2 ** 31 + 9
GPT2_KEYS = {"n_embd", "n_head", "n_layer", "n_positions", "n_ctx"}
# the files that may name no family and read no key of a configuration
SEAM_FILES = ("drivers/serve_engine.py", "drivers/train_clm.py",
              "control.py", "rehearse.py")


def load_stub():
    spec = importlib.util.spec_from_file_location(
        "stub_family", os.path.join(HERE, "stub_family.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def stub(monkeypatch):
    """Cell 2's entry with the stub's configuration in GPT-2 XL's place."""
    family = load_stub()
    manifest = harness.load_manifest()
    body = harness.read_json(HERE, "stub_config.json")
    entry = {"name": "stub-llama", "source": body["source"],
             "file": "tests/benchmark/stub_config.json",
             "reduced": ["num_hidden_layers"], "why": "a foreign family"}
    manifest["configs"] = manifest["configs"] + [entry]
    manifest["workloads"] = [
        dict(w, config="stub-llama") if w["name"] == CELL else w
        for w in manifest["workloads"]]
    real_family = harness.load_family
    monkeypatch.setattr(harness, "load_manifest", lambda: manifest)
    monkeypatch.setattr(
        harness, "load_family", lambda cfg: family
        if cfg["model_type"] == "stub-llama" else real_family(cfg))
    return {"family": family, "entry": entry, "body": body}


def test_the_stub_configuration_passes_the_configuration_check(stub):
    body = stub["body"]
    assert not GPT2_KEYS & set(body) and body["reduced"]
    assert body["published"]["num_hidden_layers"] \
        != body["num_hidden_layers"]
    harness.check_config_file(stub["entry"], body)
    assert harness.load_cell(CELL)["config"] == body


@pytest.mark.parametrize("trace", [False, True],
                         ids=["untraced", "traced"])
def test_a_foreign_family_passes_through_the_serving_driver(stub, trace,
                                                            monkeypatch):
    from benchmark import run
    from benchmark.drivers import serve_engine

    cell = rehearse.tiny_cell(CELL)
    assert cell["config"] == stub["body"] == stub["family"].TINY
    # the limits stay cell 2's: at this size every served token is the
    # reference's own choice (both gaps read 0.0 on eight seeds)
    assert stub["family"].reference_row_len(cell) == 88 \
        < cell["config"]["max_position_embeddings"]
    kept = {}
    real_run = serve_engine.run

    def keep_facts(*args):
        kept["out"] = real_run(*args)
        return kept["out"]

    monkeypatch.setattr(serve_engine, "run", keep_facts)
    result = run.run_cell(cell, SEED, 2.0 if trace else 1.0, trace,
                          {"platform": "cpu", "kind": "cpu", "count": 1},
                          t_process=time.monotonic())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    facts = kept["out"]["facts"]
    stats = facts["engine_stats"]
    opened, closed = stats["open"], stats["close"]
    assert closed["decode_ticks"] - opened["decode_ticks"] == \
        sum(1 for t in facts["ticks"] if t["decode_tokens"])
    assert closed["kv_pages_read"] > opened["kv_pages_read"] > 0
    if trace:
        # the cell's per-layer metrics that find something to read on the
        # CPU; no kernel ran here, so its time and its share of a roofline
        # are left out of the line, not reported as 0
        assert set(result) == keys | {"breakdown"}
        assert {"tick_ms.decode", "slots_busy_pct.decode"} \
            <= set(result["metrics"])
        assert not {"paged_attn_ms.decode", "paged_attn_roofline"} \
            & set(result["metrics"])
        inside = [stats[k]["kv_pages_read"] for k in (
            "open", "trace_open", "trace_close", "close")]
        assert inside == sorted(inside) and inside[1] < inside[2]
    else:
        assert set(result) == keys
        assert set(result["metrics"]) == {"serve_out_tokens_per_s",
                                          "setup_s"}
        assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0
        assert "trace_open" not in stats
    # the pool as the engine holds it: 2 kv heads of 16 in a page row
    # padded to 128 lanes, keys and values of 2 layers
    assert facts["kv_pool"] == {"leaf_shape": [64, 8, 1, 128], "leaves": 4,
                                "itemsize": 2}


@pytest.mark.parametrize("path", SEAM_FILES)
def test_the_drivers_name_no_family(path):
    with open(os.path.join(harness.BENCH_DIR, path)) as f:
        text = f.read()
    assert "gpt2" not in text.lower()
    # no key of a configuration is read outside a family file
    keys = re.findall(r'(?:cfg|\["config"\])\["(\w+)"\]', text)
    assert set(keys) <= {"vocab_size", "model_type"}, keys
