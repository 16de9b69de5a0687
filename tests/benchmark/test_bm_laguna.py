"""The ``laguna`` family, its plain reference and the readers of
``serve.laguna-s-2.1.backlog-8k`` on the CPU at the family's tiny size: the
reference against the program through the serving driver (``correct``
true), the fp8 control coming out not correct, the reference's own
invariants (causal, banded, the share), the configuration's arithmetic, and
each new reader against hand counts on made-up traces and counters (a
program without the kernel or the counters reports nothing)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import harness, hybrid_cache

CELL = "serve.laguna-s-2.1.backlog-8k"
SEED = 2 ** 31 + 30
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def test_the_cell_is_found_by_name_and_states_its_cut():
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "serve_engine" and cell["chips"] == 1
    assert cell["traffic_name"] == "backlog-8k"
    assert body["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                 "vocab_size": 100352}
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == (5, 128, 50176)
    assert {"router_score", "output_gate", "rope_layout", "qk_norm",
            "window", "weights", "serving_dtypes", "sizes"} \
        <= set(body["assumed"])
    assert "128 / 128" in body["deployment"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"paged_attn_ms.decode", "hybrid_attn_roofline",
            "window_pages_pct.decode", "moe_held_pct.decode",
            "moe_held_hit_pct.decode", "moe_gmm_ms.decode",
            "moe_gmm_roofline", "prefill_ms.decode", "tick_ms.decode",
            "peak_hbm_gb.decode", "slots_busy_pct.decode",
            "host_gap_ms.decode", "decode_device_ms.decode",
            "compile_s"} <= reported
    # their readers take one pool geometry, JoyAI's key names or a pinned
    # list of cells
    assert not reported & {"paged_attn_roofline", "mla_attn_ms.decode",
                           "moe_experts_hit_pct.decode", "lower_s",
                           "tick_host_ms.decode", "admit_ms.decode",
                           "compile_misses"}
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"serve_out_tokens_per_s", "setup_s"}
    sc, t = cell["program"]["serve_config"], cell["traffic"]
    assert sc["max_blocks_per_seq"] * sc["block_size"] \
        == t["prompt_len"]["hi"] + t["output_len"]["hi"] == 8960
    assert not sc.get("prefix_cache") and not sc.get("speculate")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_published_config():
    """Every key of the catalog's ``config`` under the same name with the
    same value, the three of ``reduced`` apart; the per-layer lists whole."""
    body = harness.load_cell(CELL)["config"]
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Laguna-S-2.1")
    assert body["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in body["reduced"]:
            assert body["published"][key] == value != body[key], key
        else:
            assert body[key] == value, key
    assert len(body["layer_types"]) == 48


def test_weights_and_pools_are_the_sizes_the_file_reckons(family):
    """11.14 GB of bfloat16 weights (5,572 M parameters) from shapes alone;
    full layers' pages 2.68 GB, window layers' rings 0.42 GB; a reference
    row of 8,960 tokens."""
    cell = harness.load_cell(CELL)
    cfg = cell["config"]
    tree = jax.eval_shape(lambda: family.program_weights(
        jax.random.key(0), cfg, jnp.bfloat16))
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    assert round(count(tree) / 1e6) == 5572
    per_layer = [round(count(b) / 1e5) / 10 for b in tree["blocks"]]
    assert per_layer == [157.4, 1281.3, 1281.3, 1281.3, 1262.4]
    assert [b["attn"]["wq"].shape[1] // 128 for b in tree["blocks"]] \
        == [48, 72, 72, 72, 48]
    moe = tree["blocks"][1]["moe"]
    assert moe["w_gate"].shape == (128, 3072, 1024)       # the banks held
    assert moe["router"].shape == (256, 3072)             # all its outputs
    assert moe["bias"].shape == (256,) and moe["bias"].dtype == jnp.float32
    assert "mlp" in tree["blocks"][0] and "moe" not in tree["blocks"][0]
    assert tree["wte"].shape == (50176, 3072)
    assert family.reference_row_len(cell) == 8960 and family.vocab(cfg) == 50176
    sc = cell["program"]["serve_config"]
    page = hybrid_cache.page_bytes(cfg, sc["block_size"])
    assert page == 65536 and hybrid_cache.layer_kinds(cfg) == (2, 3)
    assert sc["num_blocks"] * page * 2 == 2_684_354_560            # 2.68 GB
    assert sc["max_seqs"] * 33 * page * 3 == 415_236_096           # 0.42 GB


def test_check_config_holds_the_published_widths(family):
    body = harness.load_cell(CELL)["config"]
    family.check_config(body)
    for key, wrong in (("hidden_size", 2048), ("head_dim", 64),
                       ("num_key_value_heads", 4), ("sliding_window", 256),
                       ("moe_intermediate_size", 512),
                       ("num_experts_per_tok", 8), ("num_hidden_layers", 4),
                       ("num_experts", 512),
                       ("published", dict(body["published"],
                                          num_experts=128))):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))
    rope = json.loads(json.dumps(body["rope_parameters"]))
    rope["full_attention"]["factor"] = 64
    with pytest.raises(AssertionError):
        family.check_config(dict(body, rope_parameters=rope))
    heads = [48] * 48
    with pytest.raises(AssertionError):
        family.check_config(dict(body, num_attention_heads_per_layer=heads))


def test_sound_tiny_run_is_correct_through_the_driver():
    """The driver end to end at TINY (the same code path as the cell:
    prefill, ring and pages, the held range): the served tokens are the
    reference's own choices."""
    result = rehearse.run_tiny(CELL, 1, seconds=1.0, seed=SEED)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


@pytest.fixture(scope="module")
def greedy_sample(family):
    """(tiny cell at a wide flat vocabulary, three requests whose tokens are
    the reference's own greedy choices)."""
    ref = family.reference
    cell = rehearse.tiny_cell(CELL)
    cell["config"] = dict(cell["config"], vocab_size=8192)
    cfg = cell["config"]
    rng = np.random.default_rng(3)
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(SEED))
    step = jax.jit(lambda rows: ref.forward(weights, rows, cfg).argmax(-1))
    sample = []
    for i in range(3):
        seq = rng.integers(0, 8192, 24).tolist()
        for _ in range(8):
            pad = np.zeros((1, 32), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(step(pad)[0, len(seq) - 1]))
        sample.append({"id": i, "prompt": seq[:24], "tokens": seq[24:]})
    return cell, sample


@pytest.mark.parametrize("control", ["fp8", "slip"])
def test_serving_control_is_not_correct(family, greedy_sample, control,
                                        monkeypatch):
    """A control's forward pass in the program's place: its first choices
    lie below the reference's best by more than the reference's own tokens
    do (which lie at 0). ``fp8``: every position a little (the mean's
    control). ``slip``: one position in 7 here, by some logit spreads, and
    the others not at all (the max's)."""
    from benchmark.drivers import serve_engine

    cell, sample = greedy_sample
    monkeypatch.setattr(family.reference, "SLIP_EVERY", 7)
    gaps = serve_engine.served_token_gaps(cell, SEED, sample, (control,))
    sound = max(float(g.max()) for g in gaps["program"])
    worst = max(float(g.max()) for g in gaps[control])
    assert sound <= 1e-6 < 0.01 < worst, (sound, worst)
    if control == "slip":
        # served tokens sit at positions 23..30 of a row: 27 slips alone
        for g in gaps["slip"]:
            assert (g > 0).tolist() == [i == 4 for i in range(8)], g
        assert worst > 0.3, worst      # fp8 reads under 0.06 here


def test_reference_invariants(family):
    ref, cfg = family.reference, family.TINY
    a = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    b = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    c = ref.init_weights(ref.seed_key(SEED + 1), cfg, jnp.float32)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
    dense, moe = a["layers"][:2]
    assert "gate" in dense and "router" in moe
    assert moe["router"].shape == (8, 64) and moe["exp_gate"].shape[0] == 4
    assert [w["q"].shape[1] // 16 for w in a["layers"]] == [4, 6, 6, 6, 4]
    assert float(jnp.abs(moe["router_bias"]).max()) > 0
    # causal: a later token changes no earlier logit
    rows = np.random.default_rng(1).integers(0, 256, (1, 32)).astype(np.int32)
    other = rows.copy()
    other[0, 9] = (other[0, 9] + 1) % 256
    x, y = ref.forward(a, rows, cfg), ref.forward(a, other, cfg)
    assert float(jnp.abs(x[0, :9] - y[0, :9]).max()) == 0.0
    assert float(jnp.abs(x[0, 9:] - y[0, 9:]).max()) > 0
    # banded: a window layer's output at position i moves with the key at
    # i - 7 and not with the key at i - 8; a full layer's moves with both
    u = jnp.asarray(np.random.default_rng(2).standard_normal((1, 32, 64)),
                    jnp.float32)
    for layer, reach in ((1, 7), (0, 31)):
        out = ref._attention(u, a["layers"][layer], cfg, layer, None)
        moved = ref._attention(u.at[0, 3].add(1.0), a["layers"][layer], cfg,
                               layer, None)
        changed = np.asarray(jnp.abs(out - moved).max(-1)[0] > 0)
        assert changed[3:3 + reach + 1].all() and not changed[:3].any()
        assert not changed[3 + reach + 1:].any()
    # the weights of a token's picks sum to the scaling factor over ALL its
    # picks, held here or not
    idx, w = ref.route(jnp.ones((3, 64)), moe, cfg)
    assert idx.shape == (3, 2) and np.allclose(w.sum(-1), 2.5, atol=1e-5)
    assert int(idx.max()) < 8 == ref.routed_experts(cfg)
    # the lower precisions are different functions, the unknown one an error
    for quant in ("bf16", "int8", "fp8"):
        z = ref.forward(a, rows, cfg, quant)
        assert 0 < float(jnp.abs(z - x).max()) < 1.0
    with pytest.raises(ValueError):
        ref.forward(a, rows, cfg, "fp4")


def test_reference_shares_sum_to_the_uncut_layer(family):
    """The reference's own share rule: experts 0-3 held plus experts 4-7
    held, the shared expert counted once, is the layer holding all 8."""
    ref = family.reference
    whole_cfg = dict(family.TINY, num_experts=8, reduced=[], published={})
    w = ref.init_weights(ref.seed_key(SEED), whole_cfg, jnp.float32)
    layer = w["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, 24, 64)),
                    jnp.float32)
    whole = ref._experts(x, layer, whole_cfg, None)
    banks = ("exp_gate", "exp_up", "exp_down")
    lo = ref._experts(x, dict(layer, **{k: layer[k][:4] for k in banks}),
                      family.TINY, None)
    # the upper half, as that chip would see it: its banks first
    upper = dict(layer, **{k: layer[k][4:] for k in banks})
    upper["router"] = jnp.roll(layer["router"], -4, 0)
    upper["router_bias"] = jnp.roll(layer["router_bias"], -4, 0)
    hi = ref._experts(x, upper, family.TINY, None)
    flat = x.reshape(24, 64)
    shared = ref._swiglu(flat, layer["sh_gate"], layer["sh_up"],
                         layer["sh_down"], None).reshape(1, 24, 64)
    assert float(jnp.abs(lo + hi - shared - whole).max()) < 1e-5
    assert float(jnp.abs(lo - whole).max()) > 1e-3    # a part, not the whole


def test_program_layout_shares_the_reference_arrays(family):
    w = family.reference.init_weights(family.reference.seed_key(1),
                                      family.TINY, jnp.float32)
    tree = family.to_program(w)
    assert tree["blocks"][1]["moe"]["w_gate"] is w["layers"][1]["exp_gate"]
    assert tree["blocks"][0]["attn"]["wg"] is w["layers"][0]["g"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))


# ---------------------------------------------------------------- readers
def read(ctx, name):
    return harness.load_module("layer_metrics", name).read(ctx)


KERNEL = 'custom-call( custom_call_target="tpu_custom_call" | s32[128] %x)'


def ctx_of(ops, stats=None, cell=CELL):
    ticks = [{"t0": 100.0 + i, "t1": 100.9 + i} for i in range(4)]
    facts = {"trace": {"t0": 100.0, "t1": 102.0}, "ticks": ticks,
             "kv_pool": {"leaf_shape": [20480, 16, 1, 1024], "leaves": 10,
                         "itemsize": 2}}
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


ATTN_OPS = [["paged_attn.3", 0, 2e6, "paged_attn.3 " + KERNEL],
            ["paged_attn.9", 4e6, 1e6, "paged_attn.9 " + KERNEL],
            ["moe_gmm.7", 9e6, 5e6, "moe_gmm.7 " + KERNEL]]


def test_hybrid_roofline_against_a_hand_count():
    """15,000 pages a full layer and 2,100 a window layer over the traced
    ticks: (15,000 x 2 + 2,100 x 3) pages of 65,536 B = 2.38 GB, 2.9 ms at
    819 GB/s, over 3 ms of ``paged_attn``."""
    st = edges(kv_pages_read=15000, kv_window_pages_read=2100)
    got = read(ctx_of(ATTN_OPS, st), "hybrid_attn_roofline")
    least = (15000 * 2 + 2100 * 3) * 16 * 1024 * 2 * 2
    assert least == 2_378_956_800
    assert got == pytest.approx(100 * least / 819e9 / 3e-3)
    assert 96 < got < 97
    # reported as it reads, never clamped
    fast = [["paged_attn.3", 0, 1e6, "paged_attn.3 " + KERNEL]]
    assert read(ctx_of(fast, st), "hybrid_attn_roofline") > 105
    # a window that is ignored would read a full layer's pages in all five
    assert hybrid_cache.hybrid_attn_bytes(
        1, 1, harness.load_cell(CELL)["config"], 16) == 5 * 65536


def test_hybrid_roofline_at_tiny_from_an_engine_run():
    """The reader's bytes against the pages a TINY engine really walked,
    counted here from the request's lengths: one request of 9 prompt tokens
    and 6 outputs over pages of 4 and a window of 8 (5 decode ticks at
    lengths 10..14: a full layer reads 3 + 3 + 3 + 4 + 4 pages, a window
    layer 3 + 3 + 2 + 3 + 3)."""
    from benchmark.families import laguna as family
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServingEngine,
    )

    cfg = dict(family.TINY)
    params = family.program_weights(family.reference.seed_key(SEED), cfg,
                                    jnp.float32)
    eng = ServingEngine(family.serve_model(params, cfg, jnp.float32),
                        ServeConfig(max_seqs=2, block_size=4,
                                    max_blocks_per_seq=8, moe_stats=True))
    before = dict(eng.stats)
    eng.run([Request(req_id=0, tokens=list(range(9)), max_new_tokens=6)])
    ctx = ctx_of([["paged_attn.1", 0, 1e3, "paged_attn.1 " + KERNEL]],
                 {"trace_open": before, "trace_close": dict(eng.stats)})
    ctx["cell"] = dict(ctx["cell"], config=cfg)
    ctx["facts"]["kv_pool"] = {"leaf_shape": [16, 4, 1, 128], "leaves": 10,
                               "itemsize": 4}
    page = 4 * 128 * 4 * 2                 # rows x lanes x float32 x (k, v)
    least = (17 * 2 + 14 * 3) * page
    assert read(ctx, "hybrid_attn_roofline") \
        == pytest.approx(100 * least / 819e9 / 1e-6)
    assert read(ctx, "window_pages_pct.decode") == pytest.approx(1400 / 17)
    # 5 decode tokens x 2 picks x 4 expert layers; 4 of 8 experts held
    routed = 5 * 2 * 4
    rows = eng.stats["moe_assignments"]
    assert eng.stats["moe_routed"] == routed and 0 < rows < routed
    assert read(ctx, "moe_held_pct.decode") \
        == pytest.approx(100 * rows / routed)
    assert read(ctx, "moe_held_hit_pct.decode") == pytest.approx(
        100 * eng.stats["moe_experts_hit"] / (5 * 4 * 4))


def test_window_and_held_shares_from_the_counters():
    st = edges(kv_pages_read=15000, kv_window_pages_read=2100,
               moe_assignments=64 * 10 * 4 * 82 // 2,
               moe_routed=64 * 10 * 4 * 82, moe_experts_hit=82 * 4 * 118,
               decode_ticks=82, moe_prefill_experts_hit=99999,
               moe_prefill_routed=99999)
    ctx = ctx_of([], st)
    assert read(ctx, "window_pages_pct.decode") == pytest.approx(14.0)
    assert read(ctx, "moe_held_pct.decode") == pytest.approx(50.0)
    assert read(ctx, "moe_held_hit_pct.decode") \
        == pytest.approx(100 * 118 / 128)


def test_kernel_times_of_both_layer_kinds_under_one_name():
    ctx = ctx_of(ATTN_OPS)          # 2 ticks lie inside the traced window
    assert read(ctx, "paged_attn_ms.decode") == pytest.approx(1.5)
    assert read(ctx, "moe_gmm_ms.decode") == pytest.approx(2.5)


def test_gmm_roofline_counts_the_rows_computed_here():
    """The accepted reader over this family's keys (``hidden_size``,
    ``moe_intermediate_size``) and counters: rows computed here, not picks
    made."""
    from benchmark.lib import latent_moe

    st = edges(moe_assignments=1280 * 82, moe_experts_hit=472 * 82,
               moe_prefill_assignments=0, moe_prefill_experts_hit=0,
               moe_routed=2560 * 82)
    ops = [["moe_gmm.7", 0, 1.0e9, "moe_gmm.7 " + KERNEL]]
    got = read(ctx_of(ops, st), "moe_gmm_roofline")
    want = latent_moe.moe_gmm_bytes(1280 * 82, 472 * 82, 3072, 1024) / 819e9
    assert got == pytest.approx(100 * want / 1.0)
    assert 85 < got < 95          # 8.9 GB of banks a tick in 12.2 ms


NEW = ["hybrid_attn_roofline", "window_pages_pct.decode",
       "moe_held_pct.decode", "moe_held_hit_pct.decode"]


@pytest.mark.parametrize("name", NEW)
def test_readers_with_nothing_to_read_return_nothing(name):
    assert read(ctx_of(ATTN_OPS), name) is None        # an older driver
    assert read(ctx_of(ATTN_OPS, {"open": {}, "close": {}}), name) is None
    # a program that keeps none of these counters (the parent)
    bare = {"trace_open": {"ticks": 1, "kv_pages_read": 5},
            "trace_close": {"ticks": 9, "kv_pages_read": 50}}
    assert read(ctx_of(ATTN_OPS, bare), name) is None
    st = edges(kv_pages_read=9, kv_window_pages_read=3, moe_assignments=9,
               moe_routed=0, moe_experts_hit=9, decode_ticks=0)
    if name == "hybrid_attn_roofline":
        assert read(ctx_of([], st), name) is None      # no kernel op
        assert read(dict(ctx_of(ATTN_OPS, st), trace={"planes": []}),
                    name) is None
        # another family's cell, whose configuration names no layer kinds
        assert read(ctx_of(ATTN_OPS, st, "serve.gpt2-xl.decode-backlog"),
                    name) is None
    elif name != "window_pages_pct.decode":
        assert read(ctx_of(ATTN_OPS, st), name) is None    # no decode tick


def test_new_readers_are_listed_for_this_cell_alone():
    manifest = harness.load_manifest()
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "serve_out_tokens_per_s"
            assert metric["unit"] == "%"
    assert [m["name"] for m in manifest["per_layer"][-4:]] == NEW
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "laguna-s-2.1"
    assert len(manifest["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_reference_imports_nothing_of_the_package():
    import ast
    import inspect

    from benchmark.reference import laguna as ref

    tree = ast.parse(inspect.getsource(ref))
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and (m.startswith("distributed_lion_tpu")
                          or m.startswith("benchmark")) for m in mods), mods
