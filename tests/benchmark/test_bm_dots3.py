"""The ``dots3_note`` family, its plain reference and the readers of
``serve.dots3-note-prev.backlog-12k`` on the CPU at the family's tiny size:
the reference against the program through the serving driver (``correct``
true), the configuration's arithmetic against the catalog, the controls
``nosel`` and ``noresc`` through the blocks driver, and each new reader
against hand counts on made-up traces and counters and against what a TINY
engine really did (a program without the kernels or the counters reports
nothing)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import dsa_layers, harness

CELL = "serve.dots3-note-prev.backlog-12k"
SEED = 2 ** 31 + 46
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["dsa_index_ms.decode", "dsa_index_roofline", "dsa_attn_ms.decode",
       "dsa_attn_roofline", "dsa_kept_pct.decode", "window_mla_roofline"]


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def tiny_cell():
    """``rehearse.tiny_cell`` with rows long enough to pass TINY's
    ``index_topk`` 12 and to lap its rings of 3 pages of 8."""
    cell = rehearse.tiny_cell(CELL)
    cell["program"]["serve_config"].update(
        max_blocks_per_seq=16, prefill_cap_tokens=64, prefill_top_bucket=48)
    cell["traffic"].update(
        prompt_len={"median": 30, "sigma": 0.3, "lo": 14, "hi": 48},
        output_len={"median": 16, "sigma": 0.4, "lo": 6, "hi": 32})
    return cell


def test_the_cell_is_found_by_name_and_states_its_cut(family):
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "serve_engine_blocks" and cell["chips"] == 1
    assert cell["traffic_name"] == "backlog-12k"
    assert body["reduced"] == ["num_hidden_layers", "layer_types",
                               "n_routed_experts", "vocab_size"]
    assert body["published"]["num_hidden_layers"] == 46
    assert body["published"]["n_routed_experts"] == 256
    assert body["published"]["vocab_size"] == 152064 == 8 * body["vocab_size"]
    assert len(body["published"]["layer_types"]) == 46
    assert body["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert body["published"]["layer_types"][2:6] == body["layer_types"][1:]
    assert {"lora_rescale", "indexer", "index_rope", "output_gate", "window",
            "rope_layout", "router", "serving_dtypes", "not_built",
            "weights", "sizes"} <= set(body["assumed"])
    assert "eight chips share each layer" in body["deployment"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {
        "compile_s", "tick_ms.decode", "decode_device_ms.decode",
        "host_gap_ms.decode", "slots_busy_pct.decode", "peak_hbm_gb.decode",
        "prefill_ms.decode", "moe_gmm_ms.decode", "moe_gmm_roofline",
        "moe_held_pct.decode"} == reported
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"serve_out_tokens_per_s", "setup_s"}
    sc, t = cell["program"]["serve_config"], cell["traffic"]
    assert sc["max_blocks_per_seq"] * sc["block_size"] \
        == t["prompt_len"]["hi"] + t["output_len"]["hi"] == 16384 \
        == family.reference_row_len(cell)
    assert sc["max_seqs"] == t["deck"] == 64 and sc["moe_stats"] is True
    assert sc["num_blocks"] == sc["max_seqs"] * sc["max_blocks_per_seq"]
    assert (t["mix_seed"], t["arrivals"]) == (4601, {"kind": "backlog",
                                                     "count": 256})
    assert t["prompt_len"] == {"median": 10240, "sigma": 0.15, "lo": 8192,
                               "hi": 12288}
    assert t["output_len"] == {"median": 1536, "sigma": 0.6, "lo": 512,
                               "hi": 4096}
    # every prompt past index_topk four times over, and the top bucket the
    # longest prompt (the driver warms it with a prompt of that length)
    assert t["prompt_len"]["lo"] >= 4 * body["index_topk"]
    assert sc["prefill_top_bucket"] == t["prompt_len"]["hi"] \
        == sc["prefill_cap_tokens"]
    from distributed_lion_tpu.serve.engine import ServeConfig
    cfg = ServeConfig(**sc)
    assert {cfg.bucket(n) for n in range(8192, 12289)} == {8192, 12288}
    window = cell["program"]["window"]
    assert (window["ticks"], window["trace_after_s"], window["trace_s"]) \
        == (64, 7.5, 10.0)


def test_the_configuration_holds_the_catalogs_keys_and_its_arithmetic(family):
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "dots3-note-prev")
    body = harness.read_json(harness.ROOT, entry["file"])
    harness.check_config_file(entry, body)
    assert family.cut_parameters(body) == 4_087_154_176
    # full attention and its indexer, sliding attention, one expert
    assert 134_676_480 + 9_371_648 == 144_048_128
    assert 3 * 5120 * 1536 == 23_592_960
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert body[key] == value == family.PUBLISHED[key], key
        assert body["published"]["layer_types"] == row["config"]["layer_types"]
        for key in ("num_hidden_layers", "n_routed_experts", "vocab_size"):
            assert body["published"][key] == row["config"][key]
    for key, wrong in (("hidden_size", 4096), ("index_topk", 1024),
                       ("swa_kv_lora_rank", 512), ("kv_lora_rank", 1024),
                       ("sliding_window_size", 512),
                       ("num_experts_per_tok", 4), ("num_hidden_layers", 6),
                       ("layer_types", ["full_attention"] * 5),
                       ("vocab_size", 38016), ("n_routed_experts", 64)):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))


def test_program_layout_shares_the_reference_arrays(family):
    w = family.reference.init_weights(family.reference.seed_key(1),
                                      family.TINY, jnp.float32)
    tree = family.to_program(w)
    assert tree["blocks"][0]["index"]["wk"] is w["layers"][0]["idx_k"]
    assert tree["blocks"][0]["index"]["k_norm"]["bias"] \
        is w["layers"][0]["idx_k_bias"]
    assert tree["blocks"][1]["attn"]["wg"] is w["layers"][1]["g"]
    assert "index" not in tree["blocks"][1]
    assert tree["blocks"][3]["moe"]["w_down"] is w["layers"][3]["exp_down"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))
    # the tree the program's own init makes, leaf for leaf
    from distributed_lion_tpu.models.dots3 import Dots3Config, dots3_init
    mine = jax.eval_shape(lambda: dots3_init(
        jax.random.key(0), Dots3Config.from_hf(
            family.TINY, param_dtype=jnp.float32)))
    assert jax.tree.map(lambda x: x.shape, mine) \
        == jax.tree.map(lambda x: x.shape, tree)


def test_the_reference_imports_nothing_of_the_package():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(
        harness.load_family({"model_type": "dots3_note"}).reference))
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and (m.startswith("distributed_lion_tpu")
                          or m.startswith("benchmark")) for m in mods), mods


# ------------------------------------------------------------ the driver
def test_sound_tiny_run_is_correct_through_the_driver():
    """The driver end to end at TINY (the same code path as the cell:
    prefill, index keys, rings and pages in ``engine.pages``), rows past
    ``index_topk``: the served tokens are the reference's own choices."""
    from benchmark import run

    result = run.run_cell(tiny_cell(), SEED, 0.5, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0


def test_controls_through_the_blocks_driver(family):
    """A request whose tokens are the reference's own greedy choices reads
    a gap of 0; the controls that plant this PR's two faults (``nosel``: no
    selection; ``noresc``: no rescale) and ``fp8`` read more; ``slip`` moves
    the one position in 251 it is planted at."""
    from benchmark.drivers import serve_engine_blocks

    ref = family.reference
    cell = tiny_cell()
    cell["config"] = dict(cell["config"], vocab_size=8192)
    cell["traffic"]["output_len"]["hi"] = 16
    cfg = cell["config"]
    rng = np.random.default_rng(3)
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(SEED))
    step = jax.jit(lambda rows: ref.forward(weights, rows, cfg).argmax(-1))
    sample = []
    for i in range(1):
        seq = rng.integers(0, 8192, 40).tolist()
        n = len(seq)
        for _ in range(8):
            pad = np.zeros((1, 64), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(step(pad)[0, len(seq) - 1]))
        sample.append({"id": i, "prompt": seq[:n], "tokens": seq[n:]})
    quants = ("fp8", "nosel", "noresc")
    got = serve_engine_blocks.served_token_gaps(cell, SEED, sample, quants)
    assert max(float(g.max()) for g in got["program"]) == 0.0
    for q in quants:
        assert all(g.shape == (8,) for g in got[q])
        assert max(float(g.max()) for g in got[q]) > 0, q
    rows = np.zeros((1, 512), np.int32)
    plain = ref.served_logits(weights, rows, cfg, 240, 16)
    slip = ref.served_logits(weights, rows, cfg, 240, 16, "slip")
    moved = np.asarray(jnp.abs(plain - slip).max(-1))[0]
    assert moved[250 - 240] > 0 and not np.delete(moved, 10).any()
    with pytest.raises(ValueError, match="unknown precision"):
        ref.matmul(jnp.ones((2, 2)), jnp.ones((2, 2)), "int4")


def test_selection_sets_tap(family):
    """``selection_sets``: the positions every query attended in each full
    layer, 12 a query past ``index_topk``, every visible one before."""
    ref, cfg = family.reference, family.TINY
    weights = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    rows = np.random.default_rng(0).integers(0, 256, (1, 40)).astype(np.int32)
    taps = jax.jit(lambda r: ref.selection_sets(weights, r, cfg))(rows)
    assert len(taps) == 2 and taps[0].shape == (1, 40, 40)
    counts = np.asarray(taps[0][0].sum(-1))
    assert counts.tolist() == [min(t + 1, 12) for t in range(40)]
    assert not bool(jnp.triu(taps[1][0], 1).any())


# ---------------------------------------------------------------- readers
def read(ctx, name):
    return harness.load_module("layer_metrics", name).read(ctx)


KERNEL = 'custom-call( custom_call_target="tpu_custom_call" | s32[64] %x)'
MODULES = [["jit_decode_tick(1)", 0, 20e6], ["jit_prefill(2)", 30e6, 50e6],
           ["jit_decode_tick(1)", 90e6, 20e6]]
OPS = [["dsa_index.3", 1e6, 2e6, "dsa_index.3 " + KERNEL],
       ["dsa_attn.3", 4e6, 5e6, "dsa_attn.3 " + KERNEL],
       ["window_mla_attn.2", 10e6, 1e6, "window_mla_attn.2 " + KERNEL],
       ["dsa_index.3", 91e6, 1e6, "dsa_index.3 " + KERNEL],
       ["dsa_attn.3", 93e6, 3e6, "dsa_attn.3 " + KERNEL],
       ["window_mla_attn.2", 97e6, 1e6, "window_mla_attn.2 " + KERNEL],
       ["mla_paged_attn.9", 40e6, 7e6, "mla_paged_attn.9 " + KERNEL]]


def ctx_of(ops, stats=None, cell=CELL, modules=MODULES):
    facts = {"trace": {"t0": 100.0, "t1": 102.0}, "ticks": [], "max_seqs": 64}
    if stats is not None:
        facts["engine_stats"] = stats
    lines = [{"name": "XLA Ops", "events": ops}]
    if modules:
        lines.append({"name": "XLA Modules", "events": modules})
    return {"cell": harness.load_cell(cell),
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "facts": facts,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": lines}]}}


def edges(**delta):
    zero = {k: 1000 for k in delta}
    return {"trace_open": zero,
            "trace_close": {k: 1000 + v for k, v in delta.items()}}


def test_index_and_attention_rooflines_against_a_hand_count():
    """Two ticks of 64 rows at 12,000 positions in 2 full layers: 3,072,000
    visible keys of 256 B = 0.786 GB, 0.96 ms at 819 GB/s, over 3 ms of
    ``dsa_index``; 524,288 kept rows of 1,280 B and 256 (row, layer) pairs'
    queries and outputs (2 x 128 x 1,280 B) = 0.755 GB, 0.92 ms, over 8 ms
    of ``dsa_attn``: 17.07% kept."""
    cfg = harness.load_cell(CELL)["config"]
    visible, kept, rows = 2 * 64 * 2 * 12000, 2 * 64 * 2 * 2048, 2 * 64 * 2
    st = edges(dsa_keys_visible=visible, dsa_keys_kept=kept, dsa_rows=rows)
    assert dsa_layers.index_bytes(visible, cfg) == visible * 256
    assert dsa_layers.latent_row_bytes(cfg) == 1280
    assert dsa_layers.latent_row_bytes(cfg, dsa_layers.SLIDING) == 2304
    least = kept * 1280 + rows * 2 * 128 * 1280
    assert dsa_layers.kept_attn_bytes(kept, rows, cfg) == least
    got = read(ctx_of(OPS, st), "dsa_index_roofline")
    assert got == pytest.approx(100 * visible * 256 / 819e9 / 3e-3)
    assert 31 < got < 33
    got = read(ctx_of(OPS, st), "dsa_attn_roofline")
    assert got == pytest.approx(100 * least / 819e9 / 8e-3)
    assert 11 < got < 12
    assert read(ctx_of(OPS, st), "dsa_kept_pct.decode") \
        == pytest.approx(100 * 2048 / 12000)
    # a program that attends every visible position reads 100%
    st = edges(dsa_keys_visible=visible, dsa_keys_kept=visible, dsa_rows=rows)
    assert read(ctx_of(OPS, st), "dsa_kept_pct.decode") \
        == pytest.approx(100.0)
    # per decode program: two executions; the prefill's kernel is not theirs
    assert read(ctx_of(OPS), "dsa_index_ms.decode") == pytest.approx(1.5)
    assert read(ctx_of(OPS), "dsa_attn_ms.decode") == pytest.approx(4.0)


def test_window_roofline_against_a_hand_count():
    """Two ticks of 64 rows whose walk is 33 pages in ONE sliding layer:
    4,224 pages x 16 rows x 2,304 B x 3 layers = 0.467 GB, 0.57 ms at 819
    GB/s, over 2 ms of ``window_mla_attn``."""
    cfg = harness.load_cell(CELL)["config"]
    assert dsa_layers.layer_kinds(cfg) == (2, 3)
    st = edges(kv_window_pages_read=2 * 64 * 33)
    least = 2 * 64 * 33 * 16 * 2304 * 3
    assert dsa_layers.window_bytes(2 * 64 * 33, 16, cfg) == least
    got = read(ctx_of(OPS, st), "window_mla_roofline")
    assert got == pytest.approx(100 * least / 819e9 / 2e-3)
    assert 28 < got < 29


def test_readers_at_tiny_from_an_engine_run(family):
    """The readers' counts against what a TINY engine really did: one
    request of 20 prompt tokens and 7 outputs alone in 2 slots over pages
    of 8 (6 decode ticks at positions 20..25, all past ``index_topk`` 12;
    2 full layers; a window of 9 over a ring of 3 pages)."""
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServingEngine,
    )

    cfg = dict(family.TINY)
    params = family.program_weights(family.reference.seed_key(SEED), cfg,
                                    jnp.float32)
    eng = ServingEngine(family.serve_model(params, cfg, jnp.float32),
                        ServeConfig(max_seqs=2, block_size=8,
                                    max_blocks_per_seq=8,
                                    prefill_cap_tokens=32, moe_stats=True))
    before = dict(eng.stats)
    eng.run([Request(req_id=0, tokens=list(range(20)), max_new_tokens=7)])
    assert eng.stats["dsa_rows"] == 6 * 2
    assert eng.stats["dsa_keys_kept"] == 6 * 2 * 12
    visible = 2 * sum(p + 1 for p in range(20, 26))
    assert eng.stats["dsa_keys_visible"] == visible
    walk = [p // 8 - (p - 8) // 8 + 1 for p in range(20, 26)]
    assert eng.stats["kv_window_pages_read"] == sum(walk)
    ops = [["dsa_index.1", 0, 1e3, "dsa_index.1 " + KERNEL],
           ["dsa_attn.1", 2e3, 1e3, "dsa_attn.1 " + KERNEL],
           ["window_mla_attn.1", 4e3, 1e3, "window_mla_attn.1 " + KERNEL]]
    ctx = ctx_of(ops, {"trace_open": before, "trace_close": dict(eng.stats)},
                 modules=[["jit_decode_tick(3)", 0, 6e3]])
    ctx["cell"] = dict(ctx["cell"], config=cfg)
    ctx["cell"]["program"] = dict(
        ctx["cell"]["program"], weights_dtype="float32",
        serve_config=dict(ctx["cell"]["program"]["serve_config"],
                          block_size=8))
    assert read(ctx, "dsa_kept_pct.decode") == pytest.approx(
        100 * 6 * 2 * 12 / visible)
    assert read(ctx, "dsa_index_roofline") == pytest.approx(
        100 * visible * 16 * 2 / 819e9 / 1e-6)
    # TINY's latent rows are 40 and 56 values: one tile of 128 lanes each
    assert read(ctx, "dsa_attn_roofline") == pytest.approx(
        100 * (6 * 2 * 12 * 256 + 6 * 2 * 2 * 4 * 256) / 819e9 / 1e-6)
    assert read(ctx, "window_mla_roofline") == pytest.approx(
        100 * sum(walk) * 8 * 256 * 3 / 819e9 / 1e-6)
    assert read(ctx, "dsa_index_ms.decode") == pytest.approx(1e-3)


@pytest.mark.parametrize("name", NEW)
def test_readers_with_nothing_to_read_return_nothing(name):
    """What the parent gives a new reader: no kernel of these names, no
    counter of these names, or no trace at all: nothing, and no raise."""
    bare = {"trace_open": {"ticks": 1}, "trace_close": {"ticks": 9}}
    st = edges(dsa_keys_visible=9, dsa_keys_kept=3, dsa_rows=1,
               kv_window_pages_read=4)
    other = [["mla_paged_attn.9", 40e6, 7e6, "mla_paged_attn.9 " + KERNEL],
             ["paged_attn.2", 50e6, 7e6, "paged_attn.2 " + KERNEL]]
    if name.endswith("_ms.decode"):
        assert read(ctx_of(other), name) is None
        assert read(ctx_of(OPS, modules=None), name) is None
        assert read(dict(ctx_of(OPS), trace={"planes": []}), name) is None
        return
    assert read(ctx_of(OPS), name) is None             # an older driver
    assert read(ctx_of(OPS, {"open": {}, "close": {}}), name) is None
    assert read(ctx_of(OPS, bare), name) is None       # no such counter
    if name == "dsa_kept_pct.decode":
        assert read(ctx_of(OPS, edges(dsa_keys_kept=0, dsa_keys_visible=0)),
                    name) is None                      # no decode tick
        return
    assert read(ctx_of(other, st), name) is None       # no such kernel
    assert read(dict(ctx_of(OPS, st), trace={"planes": []}), name) is None
    # another family's cell, whose configuration has not these keys
    assert read(ctx_of(OPS, st, "serve.laguna-s-2.1.backlog-8k"),
                name) is None


def test_new_readers_are_listed_for_this_cell_alone():
    manifest = harness.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "serve_out_tokens_per_s"
        assert listed[name]["layer"] \
            == listed["mla_attn_ms.decode"]["layer"]
    assert {listed[n]["unit"] for n in NEW} == {"ms", "%"}
    for name in ("mla_attn_roofline", "mla_attn_ms.decode",
                 "paged_attn_ms.decode", "window_pages_pct.decode",
                 "tick_host_ms.decode", "admit_ms.decode", "lower_s",
                 "compile_misses", "moe_experts_hit_pct.decode"):
        assert CELL not in listed[name]["workloads"], name
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    # nine cells before this one (PERF.md numbers them 1-10; cell 3 was
    # built and left out), one of them on four chips
    assert len(manifest["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the new kernels' names hold no other reader's pattern, nor theirs one
    # of these
    for kernel in (dsa_layers.INDEX_KERNEL, dsa_layers.ATTN_KERNEL,
                   dsa_layers.WINDOW_KERNEL):
        for other in ("paged_attn", "mla_paged_attn", "kda_", "lightning_",
                      "flash_", "moe_gmm", "lion_", "mhc_"):
            assert other not in kernel and kernel not in other
