"""The ``minicpm_sala`` family, its plain reference and the readers of
``serve.minicpm-sala.backlog-16k`` on the CPU at the family's tiny size: the
reference against the program through the serving driver (``correct`` true;
the driver that takes the served positions' logits alone against the one
that takes every position's, a control included), the configuration's
arithmetic against the catalog,
and each new reader against hand counts on made-up traces and counters and
against what a TINY engine really did (a program without the kernel or the
counters reports nothing)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import harness, sparse_linear

CELL = "serve.minicpm-sala.backlog-16k"
SEED = 2 ** 31 + 37
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def tiny_cell():
    """``rehearse.tiny_cell`` with pages of the TINY stride and rows long
    enough to pass TINY's ``dense_len`` 64 (its own pages of 8 and prompts of
    8-64 would leave every row under it)."""
    cell = rehearse.tiny_cell(CELL)
    cell["program"]["serve_config"].update(
        block_size=2, max_blocks_per_seq=96, prefill_cap_tokens=128)
    cell["traffic"].update(
        prompt_len={"median": 80, "sigma": 0.3, "lo": 40, "hi": 128},
        output_len={"median": 16, "sigma": 0.4, "lo": 6, "hi": 32})
    return cell


def test_the_cell_is_found_by_name_and_states_its_cut(family):
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "serve_engine_blocks" and cell["chips"] == 1
    assert cell["traffic_name"] == "backlog-16k"
    assert body["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert body["published"]["num_hidden_layers"] == 32
    assert len(body["published"]["mixer_types"]) == 32
    assert body["num_hidden_layers"] == 8 and body["mixer_types"] == \
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert body["published"]["mixer_types"][9:17] == body["mixer_types"]
    assert {"sparse_config", "compressed_keys", "score_aggregation",
            "dense_len_by_query", "qk_norm", "rope", "lightning_rule",
            "decay", "output_norm", "output_gate", "residual", "ffn",
            "weights", "serving_dtypes", "sizes"} <= set(body["assumed"])
    assert "four pipeline stages of 8 layers" in body["deployment"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"lightning_step_ms.decode", "lightning_state_roofline",
            "lightning_chunk_roofline", "sparse_pages_pct.decode",
            "sparse_attn_roofline", "paged_attn_ms.decode",
            "prefill_ms.decode", "tick_ms.decode", "peak_hbm_gb.decode",
            "slots_busy_pct.decode", "host_gap_ms.decode",
            "decode_device_ms.decode", "compile_s"} == reported
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"serve_out_tokens_per_s", "setup_s"}
    sc, t = cell["program"]["serve_config"], cell["traffic"]
    assert sc["max_blocks_per_seq"] * sc["block_size"] \
        == t["prompt_len"]["hi"] + t["output_len"]["hi"] == 20480 \
        == family.reference_row_len(cell)
    assert sc["block_size"] == body["sparse_config"]["kernel_stride"]
    assert sc["max_seqs"] == t["deck"] and sc["moe_stats"] is True
    assert sc["num_blocks"] == sc["max_seqs"] * sc["max_blocks_per_seq"]
    assert (t["mix_seed"], t["arrivals"]) == (3701, {"kind": "backlog",
                                                     "count": 192})
    assert t["prompt_len"] == {"median": 12288, "sigma": 0.2, "lo": 9216,
                               "hi": 16384}
    assert t["output_len"] == {"median": 2560, "sigma": 0.6, "lo": 1024,
                               "hi": 4096}
    # every prompt past dense_len, every prompt in the one bucket
    assert t["prompt_len"]["lo"] > body["sparse_config"]["dense_len"]
    from benchmark.drivers import serve_engine
    assert serve_engine.buckets_of(cell) == [16384]


def test_the_configuration_holds_the_catalogs_keys_and_its_arithmetic(family):
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "minicpm-sala")
    body = harness.read_json(harness.ROOT, entry["file"])
    harness.check_config_file(entry, body)
    # 2 x 253.76 + 6 x 285.22 + 601.69 M (ISSUE 37 writes 2,820.6: it
    # rounds a Lightning layer up)
    assert family.cut_parameters(body) == 2_820_545_280
    assert 2 * 253_763_840 + 6 * 285_221_248 + 601_686_016 + 4096 \
        == 2_820_545_280
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert body[key] == value == family.PUBLISHED[key], key
        assert body["published"]["mixer_types"] == row["config"]["mixer_types"]
    for key, wrong in (("hidden_size", 2048), ("num_key_value_heads", 8),
                       ("vocab_size", 36724), ("scale_depth", 1.0),
                       ("num_hidden_layers", 6),
                       ("mixer_types", ["lightning-attn"] * 8),
                       ("sparse_config", dict(body["sparse_config"],
                                              topk=32)),
                       ("deployment_layers", [8, 15])):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))
    lam = family.decay(body)
    assert lam.shape == (8, 32) and not bool(lam[0].any())
    assert float(lam[1, 0]) == pytest.approx(np.exp(-2 ** -0.25))
    assert float(lam[6, 31]) == pytest.approx(np.exp(-2 ** -8))


def test_program_layout_shares_the_reference_arrays(family):
    w = family.reference.init_weights(family.reference.seed_key(1),
                                      family.TINY, jnp.float32)
    tree = family.to_program(w)
    assert tree["blocks"][0]["attn"]["wk"] is w["layers"][0]["k"]
    assert tree["blocks"][1]["lightning"]["slope"] is w["layers"][1]["slope"]
    assert tree["blocks"][3]["mlp"]["w_down"] is w["layers"][3]["down"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))


def test_the_reference_imports_nothing_of_the_package():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(
        harness.load_family({"model_type": "minicpm_sala"}).reference))
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and (m.startswith("distributed_lion_tpu")
                          or m.startswith("benchmark")) for m in mods), mods


# ------------------------------------------------------------ the driver
def test_sound_tiny_run_is_correct_through_the_driver():
    """The driver end to end at TINY (the same code path as the cell:
    prefill, compressed keys, lists and states in ``engine.pages``), rows on
    both sides of ``dense_len``: the served tokens are the reference's own
    choices."""
    from benchmark import run

    result = run.run_cell(tiny_cell(), SEED, 0.5, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.fixture(scope="module")
def greedy_sample(family):
    """(tiny cell at a wide flat vocabulary, two requests past ``dense_len``
    whose tokens are the reference's own greedy choices)."""
    ref = family.reference
    cell = tiny_cell()
    cell["config"] = dict(cell["config"], vocab_size=8192)
    cfg = cell["config"]
    rng = np.random.default_rng(3)
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(SEED))
    step = jax.jit(lambda rows: ref.forward(weights, rows, cfg).argmax(-1))
    sample = []
    for i in range(2):
        seq = rng.integers(0, 8192, 70 + 20 * i).tolist()
        n = len(seq)
        for _ in range(8):
            pad = np.zeros((1, 128), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(step(pad)[0, len(seq) - 1]))
        sample.append({"id": i, "prompt": seq[:n], "tokens": seq[n:]})
    return cell, sample


def test_gaps_by_blocks_are_the_one_shot_drivers(greedy_sample):
    """``serve_engine_blocks`` (the hidden states of the whole row, the head
    over the served positions) against ``serve_engine`` (every position's
    logits): the same gaps to rounding, for the program's tokens and for
    a control; ``fp8`` comes out not zero where the sound tokens read 0."""
    from benchmark.drivers import serve_engine, serve_engine_blocks

    cell, sample = greedy_sample
    quants = ("fp8",)
    a = serve_engine.served_token_gaps(cell, SEED, sample, quants)
    b = serve_engine_blocks.served_token_gaps(cell, SEED, sample, quants)
    for key in ("program",) + quants:
        for x, y in zip(a[key], b[key]):
            assert x.shape == y.shape == (8,)
            assert np.allclose(x, y, atol=1e-5), key
    assert max(float(g.max()) for g in b["program"]) == 0.0
    assert max(float(g.max()) for g in b["fp8"]) > 0
    # the served positions 70.. lie past dense_len 64: selection is on
    assert serve_engine_blocks.run is not serve_engine.run
    assert serve_engine_blocks.Loop is serve_engine.Loop


# ---------------------------------------------------------------- readers
def read(ctx, name):
    return harness.load_module("layer_metrics", name).read(ctx)


KERNEL = 'custom-call( custom_call_target="tpu_custom_call" | s32[128] %x)'


def ctx_of(ops, stats=None, cell=CELL):
    ticks = [{"t0": 100.0 + i, "t1": 100.9 + i} for i in range(4)]
    facts = {"trace": {"t0": 100.0, "t1": 102.0}, "ticks": ticks,
             "max_seqs": 64}
    if stats is not None:
        facts["engine_stats"] = stats
    return {"cell": harness.load_cell(cell),
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "facts": facts,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops}]}]}}


def edges(**delta):
    zero = {k: 1000 for k in delta}
    return {"trace_open": zero,
            "trace_close": {k: 1000 + v for k, v in delta.items()}}


OPS = [["lightning_step.16", 0, 2e6, "lightning_step.16 " + KERNEL],
       ["lightning_step.21", 4e6, 1e6, "lightning_step.21 " + KERNEL],
       ["paged_attn.7", 9e6, 4e6, "paged_attn.7 " + KERNEL]]


def test_state_roofline_against_a_hand_count():
    """Two ticks of 64 live slots over 6 Lightning layers: 768 rows of 2 x
    2,097,152 B of state and 65,536 B of float32 vectors = 3.27 GB, 4.0 ms
    at 819 GB/s, over 3 ms of ``lightning_step``."""
    cfg = harness.load_cell(CELL)["config"]
    st = edges(state_rows_stepped=768, decode_ticks=2)
    least = 768 * (2 * 2_097_152 + 32 * 4 * 128 * 4)
    assert sparse_linear.lightning_step_bytes(768, cfg) == least
    got = read(ctx_of(OPS, st), "lightning_state_roofline")
    assert got == pytest.approx(100 * least / 819e9 / 3e-3)
    assert got > 105               # reported as it reads, never clamped
    assert read(ctx_of(OPS, st), "lightning_step_ms.decode") \
        == pytest.approx(1.5)


def test_chunk_roofline_against_a_hand_count():
    """Two prompts padded to 16,384 positions each in the traced window, six
    Lightning layers: 32 x 128 x (3 x 2 + 4) B a position and 2,097,152 B of
    state a prompt = 8.08 GB, 9.9 ms at 819 GB/s (the recurrence's 2.6
    MFLOP a position a layer are 2.6 ms at the bfloat16 peak: the bytes
    bound it), over 40 ms of ``lightning_chunk``; the step's time is not
    counted."""
    cfg = harness.load_cell(CELL)["config"]
    st = edges(padded_prefill_tokens=32768, prefill_dispatches=2)
    least = 6 * (32768 * 32 * 128 * 10 + 2 * 2_097_152)
    assert sparse_linear.lightning_chunk_bytes(32768, 2, cfg) == least
    assert sparse_linear.lightning_chunk_flops(32768, cfg) \
        == 6 * 32768 * 32 * 5 * 128 * 128
    assert least / 819e9 > 3 * sparse_linear.lightning_chunk_flops(
        32768, cfg) / 197e12
    ops = OPS + [["lightning_chunk.3", 30e6, 25e6,
                  "lightning_chunk.3 " + KERNEL],
                 ["lightning_chunk.4", 60e6, 15e6,
                  "lightning_chunk.4 " + KERNEL]]
    got = read(ctx_of(ops, st), "lightning_chunk_roofline")
    assert got == pytest.approx(100 * least / 819e9 / 40e-3)
    assert 24 < got < 25
    # the chunk kernel's name is not the step's
    assert read(ctx_of(ops, st), "lightning_step_ms.decode") \
        == pytest.approx(1.5)
    assert read(ctx_of(OPS, st), "lightning_chunk_roofline") is None


def test_pages_share_and_attention_roofline_against_a_hand_count():
    """Two ticks of 64 rows at 12,000 positions: a walk of every page is 750
    pages a row a layer (the host's ``kv_pages_read``: 96,000), x 2 kv heads
    x 2 layers = 384,000 pairs; the lists hold 253 pages a (row, kv head)
    (63 blocks of 4 and the partly filled one): 129,536 pairs = 33.7%, and
    129,536 x 8,192 B = 1.06 GB, 1.30 ms at 819 GB/s, over 4 ms of
    ``paged_attn``."""
    cfg = harness.load_cell(CELL)["config"]
    selected = 2 * 64 * 2 * 2 * 253
    st = edges(kv_pages_selected=selected, kv_pages_read=2 * 64 * 750)
    assert sparse_linear.pages_walked(2 * 64 * 750, cfg) == 384_000
    assert read(ctx_of(OPS, st), "sparse_pages_pct.decode") \
        == pytest.approx(100 * selected / 384_000)
    assert sparse_linear.selected_page_bytes(cfg, 16) == 8192
    got = read(ctx_of(OPS, st), "sparse_attn_roofline")
    assert got == pytest.approx(100 * selected * 8192 / 819e9 / 4e-3)
    assert 32 < got < 33
    # a program that hands attention every page reads 100%
    st = edges(kv_pages_selected=384_000, kv_pages_read=96_000)
    assert read(ctx_of(OPS, st), "sparse_pages_pct.decode") \
        == pytest.approx(100.0)


def test_readers_at_tiny_from_an_engine_run(family):
    """The readers' counts against what a TINY engine really did: one
    request of 70 prompt tokens and 7 outputs alone in 2 slots over pages of
    2 (6 decode ticks at positions 70..75, all past ``dense_len`` 64; 2
    Lightning layers; 2 ``minicpm4`` layers of 2 kv heads, 4 blocks of 8 a
    list)."""
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServingEngine,
    )

    cfg = dict(family.TINY)
    params = family.program_weights(family.reference.seed_key(SEED), cfg,
                                    jnp.float32)
    eng = ServingEngine(family.serve_model(params, cfg, jnp.float32),
                        ServeConfig(max_seqs=2, block_size=2,
                                    max_blocks_per_seq=48,
                                    prefill_cap_tokens=128, moe_stats=True))
    before = dict(eng.stats)
    eng.run([Request(req_id=0, tokens=list(range(70)), max_new_tokens=7)])
    ops = [["lightning_step.1", 0, 1e3, "lightning_step.1 " + KERNEL],
           ["lightning_chunk.1", 2e3, 1e3, "lightning_chunk.1 " + KERNEL],
           ["paged_attn.1", 4e3, 1e3, "paged_attn.1 " + KERNEL]]
    ctx = ctx_of(ops, {"trace_open": before, "trace_close": dict(eng.stats)})
    ctx["cell"] = dict(ctx["cell"], config=cfg)
    ctx["cell"]["program"] = dict(
        ctx["cell"]["program"], weights_dtype="float32",
        serve_config=dict(ctx["cell"]["program"]["serve_config"],
                          block_size=2))
    assert eng.stats["state_rows_stepped"] == 6 * 2
    assert eng.stats["sparse_rows"] == 6 * 2 and not eng.stats["dense_rows"]
    # positions 70, 71 are in block 8 (2 and 3 keys of it: 1, 2 pages),
    # 72..75 open block 9: 3 whole blocks of 4 pages and the own block's
    pages = [12 + -(-(p % 8 + 1) // 2) for p in range(70, 76)]
    assert eng.stats["kv_pages_selected"] == 2 * 2 * sum(pages)
    walked = sum(-(-(p + 1) // 2) for p in range(70, 76))
    assert eng.stats["kv_pages_read"] == walked
    assert read(ctx, "sparse_pages_pct.decode") == pytest.approx(
        100 * sum(pages) / walked)
    assert read(ctx, "sparse_attn_roofline") == pytest.approx(
        100 * 2 * 2 * sum(pages) * (2 * 2 * 16 * 2) / 819e9 / 1e-6)
    least = 12 * (2 * 4 * 16 * 16 * 4 + 4 * 4 * 16 * 4)
    assert read(ctx, "lightning_state_roofline") \
        == pytest.approx(100 * least / 819e9 / 1e-6)
    assert eng.stats["padded_prefill_tokens"] == 96     # the table's width
    least = 2 * (96 * 4 * 16 * (3 * 4 + 4) + 4 * 16 * 16 * 4)
    assert read(ctx, "lightning_chunk_roofline") \
        == pytest.approx(100 * least / 819e9 / 1e-6)
    # 34 windows of the prompt, then positions 71, 73, 75 close one each
    assert eng.stats["ck_rows_written"] == 2 * (34 + 3)


NEW = ["lightning_step_ms.decode", "lightning_state_roofline",
       "lightning_chunk_roofline", "sparse_pages_pct.decode",
       "sparse_attn_roofline"]


@pytest.mark.parametrize("name", NEW)
def test_readers_with_nothing_to_read_return_nothing(name):
    if name != "lightning_step_ms.decode":
        assert read(ctx_of(OPS), name) is None         # an older driver
        assert read(ctx_of(OPS, {"open": {}, "close": {}}), name) is None
        # a program that keeps none of these counters (the parent)
        bare = {"trace_open": {"ticks": 1}, "trace_close": {"ticks": 9}}
        assert read(ctx_of(OPS, bare), name) is None
    st = edges(state_rows_stepped=9, kv_pages_selected=9, kv_pages_read=9,
               padded_prefill_tokens=256, prefill_dispatches=1)
    if name != "sparse_pages_pct.decode":
        assert read(ctx_of([], st), name) is None          # no kernel op
        assert read(dict(ctx_of(OPS, st), trace={"planes": []}), name) is None
        # another family's cell, whose configuration has not these keys
        if name != "lightning_step_ms.decode":
            assert read(ctx_of(OPS, st, "serve.laguna-s-2.1.backlog-8k"),
                        name) is None
    else:
        assert read(ctx_of(OPS, edges(kv_pages_selected=9, kv_pages_read=0)),
                    name) is None                          # no decode tick


def test_new_readers_are_listed_for_this_cell_alone():
    manifest = harness.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "serve_out_tokens_per_s"
    assert {listed[n]["unit"] for n in NEW} == {"ms", "%"}
    for name in ("paged_attn_roofline", "state_live_pct.decode",
                 "kda_step_ms.decode", "kda_state_roofline",
                 "kda_chunk_roofline", "hybrid_attn_roofline",
                 "tick_host_ms.decode", "lower_s", "compile_misses"):
        assert CELL not in listed[name]["workloads"], name
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the new kernels' names hold no other reader's pattern
    for kernel in (sparse_linear.LIGHTNING_KERNEL,
                   sparse_linear.LIGHTNING_CHUNK_KERNEL):
        for other in ("paged_attn", "kda_", "flash_attention", "flash_mha",
                      "moe_gmm", "lion_"):
            assert other not in kernel
