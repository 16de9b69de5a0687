"""The ``mellum`` family, its plain reference and the readers of
``train.mellum2-12b-a2.5b.share-8k`` on the CPU at the family's tiny size:
the configuration file against the catalog row's every key, the cut's
arithmetic, the cell and its readers in the manifest (membership, not
position), the program against the reference through the training driver
(``correct`` true) with the fp8 control not, and each new reader against hand
counts (a program without the kernels or the counters reports nothing)."""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import expert_train, harness

CELL = "train.mellum2-12b-a2.5b.share-8k"
CONFIG = "mellum2-12b-a2.5b"
SEED = 2 ** 31 + 43
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["mfu.moe", "moe_gmm_ms.train", "moe_gmm_train_roofline",
       "flash_gqa_ms.train", "flash_gqa_roofline", "moe_held_pct.train",
       "moe_load_max_pct.train"]
# the tiny size's own limits, read as the cells' are (PERF.md section 2):
# three seeds' sound runs gave grad_norm_gap 0.0092 and grad_sketch_gap 0.188
# at most, the fp8 control 0.0156 and 0.303 at least
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.012,
               "grad_sketch_gap": 0.24, "update_norm_gap": 0.2}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def tiny_cell(family, chips: int) -> dict:
    """The cell with its family's tiny model and ``rehearse.tiny_cell``'s
    tiny training traffic (which knows a training cell by the driver name
    ``train_clm`` alone: PERF.md section 7)."""
    cell = harness.load_cell(CELL)
    cell["config"] = dict(family.TINY)
    cell["chips"] = chips
    cell["traffic"].update(block_size=64, per_device_train_batch_size=2,
                           gradient_accumulation_steps=2)
    cell["correct"]["limits"] = dict(TINY_LIMITS)
    return cell


def test_the_cell_is_found_by_name_and_states_its_cut(family):
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "train_clm_lean" and cell["chips"] == 1
    assert cell["traffic_name"] == "share-2x2-8k"
    assert cell["config_name"] == CONFIG
    assert body["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == (4, 16, 24576)
    assert {"qk_norm", "router", "rope_layout", "window", "aux_loss",
            "mtp_head", "dropout", "weights", "sizes"} <= set(body["assumed"])
    assert "four chips share each layer" in body["deployment"]
    reported = {m["name"] for m in cell["per_layer"]}
    # `lion_ms.train`, `dispatch_ms.train` and `xent_ms.train` read no
    # GPT-2 key and would find their kernels and span here; tests the
    # benchmark already had pin their lists to the two GPT-2 cells
    assert set(NEW) | {"compile_s", "host_gap_ms.train",
                       "step_device_ms.train",
                       "peak_hbm_gb.train"} == reported
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"train_tokens_per_s_per_chip", "setup_s"}
    t = cell["traffic"]
    assert (t["block_size"], t["per_device_train_batch_size"],
            t["gradient_accumulation_steps"]) == (8192, 2, 2)
    flags = cell["program"]["flags"]
    assert flags["lion"] and flags["async_grad"]
    assert (flags["learning_rate"], flags["weight_decay"],
            flags["warmup_steps"], flags["max_steps"]) == (
                1e-4, 0.1, 2000, 100000)
    assert family.vocab(body) == 24576
    assert family.reference_row_len(cell) == 8192
    assert set(cell["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "update_norm_gap"}


def test_the_configuration_holds_the_catalogs_keys_and_its_arithmetic(family):
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    body = harness.read_json(harness.ROOT, entry["file"])
    harness.check_config_file(entry, body)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    for text in (entry["why"], entry["source"], cell["why"]):
        # the manifest's rule for every line of words: the driver refuses
        # the file before any run over one character more
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    # 4 x (21,238,528 + 147,456 + 16 x 6,193,152) + 2 x 24,576 x 2,304 + 2,304
    assert family.parameters(body) == 595_154_176 \
        == 4 * 120_476_416 + 113_246_208 + 2304
    whole = dict(body, **body["published"])
    assert family.parameters(whole) == 28 * 417_747_712 + 452_984_832 + 2304
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert entry["source"] == body["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in body, key
            if key in entry["reduced"]:
                assert body["published"][key] == value != body[key], key
            else:
                assert body[key] == value, key
                if key in family.PUBLISHED:
                    assert family.PUBLISHED[key] == value, key
    for key, wrong in (("hidden_size", 2048), ("moe_intermediate_size", 768),
                       ("num_experts_per_tok", 4), ("num_experts", 4),
                       ("vocab_size", 8192), ("num_hidden_layers", 6),
                       ("sliding_window", 512), ("head_dim", 64),
                       ("rope_parameters", dict(
                           body["rope_parameters"], full_attention=dict(
                               body["rope_parameters"]["full_attention"],
                               factor=4)))):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))


def test_program_layout_shares_the_reference_arrays(family):
    w = jax.jit(lambda key: family.reference.init_weights(
        key, family.TINY, jnp.float32))(family.reference.seed_key(1))
    tree = family.to_program(w)
    assert tree["blocks"][0]["attn"]["wq"] is w["layers"][0]["q"]
    assert tree["blocks"][3]["moe"]["w_down"] is w["layers"][3]["exp_down"]
    assert tree["lm_head"] is w["head"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))
    leaves = family.program_leaves(tree)
    assert set(leaves) == set(family.leaf_keys(family.TINY))
    assert set(jax.jit(family.reference_leaf_norms)(w)) == set(leaves)
    assert leaves[("router", 2)].shape == (8, 64)       # all 8 outputs
    assert leaves[("exp_gate", 2)].shape == (4, 64, 32)  # 4 banks held


def test_sound_training_run_is_correct_and_the_fp8_control_is_not(family):
    from benchmark.drivers import train_clm_lean

    # the trainer takes every device it sees as a worker (conftest: 8); the
    # control rides the same drive, as `benchmark/control.py` runs it
    cell = tiny_cell(family, chips=jax.device_count())
    cell["control_quants"] = ["fp8"]
    check = harness.Check()
    out = train_clm_lean.run(cell, SEED, 1.0, None, time.monotonic,
                             time.monotonic(), check)
    assert check.ok and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    failed = {r[0] for r in check.controls["fp8"].rows if not r[3]}
    assert {"grad_norm_gap", "grad_sketch_gap"} <= failed
    # the counters of the last drained step, as the readers take them
    counters = expert_train.step_counters({})
    workers, accum, rows, block, layers, k = 8, 2, 2, 64, 4, 2
    assert counters["moe_routed"] == workers * accum * rows * block * layers * k
    assert 0 < counters["moe_assignments"] < counters["moe_routed"]


def test_the_lean_reference_gives_the_drivers_numbers(family):
    """``train_clm_lean.reference_numbers`` holds fewer trees at a time and
    changes no number: every loss, norm, sketch and update norm is
    ``train_clm.reference_numbers``' own, bit for bit."""
    import numpy as np

    from benchmark.drivers import train_clm, train_clm_lean

    cell = tiny_cell(family, chips=1)
    lean = train_clm_lean.reference_numbers(cell, SEED)
    plain = train_clm.reference_numbers(cell, SEED)
    assert lean["loss"] == plain["loss"]
    assert lean["update_norms"] == plain["update_norms"]
    for name in ("grad_norms", "grad_sketch"):
        assert set(lean[name]) == set(plain[name])
        for key, value in plain[name].items():
            np.testing.assert_array_equal(lean[name][key], value)
    assert train_clm.reference_numbers.__module__ == train_clm.__name__


def _ctx(cfg, counters=None):
    return {"cell": {"config": cfg}, "peaks": PEAKS, "trace": {"planes": []},
            "facts": {"world": 1, "tokens_per_step": 32768,
                      "job": {"block": 8192, "micro": 2, "accum": 2},
                      "trace": {"steps": 4, "window_s": 2.0}}}


def test_readers_against_hand_counts(family, monkeypatch):
    from distributed_lion_tpu.train import metrics

    cfg = harness.load_cell(CELL)["config"]
    ctx = _ctx(cfg)
    read = {n: harness.load_module("layer_metrics", n) for n in NEW}
    # a program that drained no counter: the counter readers say nothing
    monkeypatch.setattr(metrics, "_LAST", {})
    assert read["moe_held_pct.train"].read(ctx) is None
    assert read["moe_load_max_pct.train"].read(ctx) is None
    # no kernel in the trace: the time readers say nothing
    for name in ("moe_gmm_ms.train", "moe_gmm_train_roofline",
                 "flash_gqa_ms.train", "flash_gqa_roofline"):
        assert read[name].read(ctx) is None
    # a flat router: 8 picks x 32,768 tokens x 4 layers, a quarter held
    routed = 8 * 32768 * 4
    monkeypatch.setattr(metrics, "_LAST", {"train": {
        "step": 10, "moe_routed": routed, "moe_assignments": routed / 4,
        "moe_experts_hit": 16 * 8, "moe_load_max": 8 * 2300}})
    assert read["moe_held_pct.train"].read(ctx) == pytest.approx(25.0)
    assert read["moe_load_max_pct.train"].read(ctx) == pytest.approx(
        100 * 2300 / 2048)
    # FLOPs a token, by hand: 42.47 M of projections, 0.29 of router and
    # 24.77 of held picks a layer, 113.25 of head, the band's and the causal
    # half's pairs
    pairs = 3 * (1024 * 1025 // 2 + 7168 * 1024) + 8192 * 8193 // 2
    assert 3 * expert_train.pairs_seen(8192, 1024) \
        + expert_train.pairs_seen(8192, None) == pairs
    fwd = 4 * (2 * 2304 * 128 * 72 + 2 * 2304 * 64
               + 2 * 2 * 3 * 2304 * 896) + 2 * 24576 * 2304 \
        + 4 * 128 * 32 * pairs / 8192
    assert expert_train.train_flops_per_token(cfg, 8192, 0.25) \
        == pytest.approx(3 * fwd)
    assert 3 * fwd == pytest.approx(1.492e9, rel=2e-3)
    assert read["mfu.moe"].read(ctx) == pytest.approx(
        100 * 3 * fwd * (4 * 32768 / 2.0) / 197e12)
    # the kernels' time, handed in: 60 ms of grouped products, 100 ms of
    # attention a step
    for name, ms in (("moe_gmm_train_roofline", 60.0),
                     ("flash_gqa_roofline", 100.0)):
        monkeypatch.setattr(read[name], "kernel_ms_per_unit",
                            lambda ctx, pattern, ms=ms: ms)
    rows = routed / 4
    flops = rows * 9 * 2 * 2304 * 896
    assert read["moe_gmm_train_roofline"].read(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.060)
    assert expert_train.gmm_train_bytes(rows, 8, 16, 2304, 896) \
        == (rows * 9 * 3200 + 8 * 9 * 16 * 2304 * 896) * 2
    assert read["flash_gqa_roofline"].read(ctx) == pytest.approx(
        100 * 3 * 4 * 128 * 32 * pairs * 4 / 197e12 / 0.100)
    assert read["flash_gqa_roofline"].read(ctx) < 105
