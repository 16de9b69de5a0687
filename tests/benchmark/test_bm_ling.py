"""The ``ling_3_flash`` family, its plain reference and the readers of
``serve.ling-3.0-flash-vl.backlog-1k-long`` on the CPU at the family's tiny
size: the reference against the program through the serving driver
(``correct`` true), the ``fp8`` and ``slip`` controls coming out not
correct, the reference's own invariants (causal, the recurrence, the groups),
the configuration's arithmetic, and each new reader against hand counts on
made-up traces and counters (a program without the kernel or the counters
reports nothing)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import harness, linear_state

CELL = "serve.ling-3.0-flash-vl.backlog-1k-long"
SEED = 2 ** 31 + 32
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def test_the_cell_is_found_by_name_and_states_its_cut():
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "serve_engine" and cell["chips"] == 1
    assert cell["traffic_name"] == "backlog-1k-long"
    assert body["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "num_experts", "vocab_size"]
    assert body["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    assert (body["num_hidden_layers"], body["first_k_dense_replace"],
            body["num_experts"], body["vocab_size"]) == (7, 1, 128, 39296)
    assert {"model_type", "tower", "layer_pattern", "kda_conv", "kda_qk_norm",
            "kda_safe_gate", "kda_rule", "kda_output_norm", "output_gate",
            "mla", "router", "swiglu_limits", "head", "weights",
            "serving_dtypes", "sizes"} <= set(body["assumed"])
    assert "128 / 128 / 128 / 128" in body["deployment"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"kda_step_ms.decode", "kda_state_roofline", "kda_chunk_roofline",
            "state_live_pct.decode", "kda_banks_hit_pct.decode",
            "moe_held_pct.decode", "moe_gmm_ms.decode", "moe_gmm_roofline",
            "mla_attn_ms.decode", "prefill_ms.decode", "tick_ms.decode",
            "peak_hbm_gb.decode", "slots_busy_pct.decode",
            "host_gap_ms.decode", "decode_device_ms.decode",
            "compile_s"} == reported
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"serve_out_tokens_per_s", "setup_s"}
    sc, t = cell["program"]["serve_config"], cell["traffic"]
    assert sc["max_blocks_per_seq"] * sc["block_size"] \
        == t["prompt_len"]["hi"] + t["output_len"]["hi"] == 8192
    assert sc["max_seqs"] == t["deck"] == 128
    assert not sc.get("prefix_cache") and not sc.get("speculate")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_published_config():
    """Every key of the catalog's ``config`` under the same name with the
    same value, the four of ``reduced`` apart; the per-layer lists whole."""
    body = harness.load_cell(CELL)["config"]
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Ling-3.0-flash-VL")
    assert body["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in body["reduced"]:
            assert body["published"][key] == value != body[key], key
        else:
            assert body[key] == value, key
    assert len(body["expert_swiglu_limit_list"]) == 42


def test_weights_and_caches_are_the_sizes_the_file_reckons(family):
    """10.34 GB of bfloat16 weights (5,169 M parameters) from shapes alone;
    1.61 GB of state, 0.84 GB of latent pages; a reference row of 8,192."""
    cell = harness.load_cell(CELL)
    cfg = cell["config"]
    tree = jax.eval_shape(lambda: family.program_weights(
        jax.random.key(0), cfg, jnp.bfloat16))
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    assert round(count(tree) / 1e6) == 5169 == family.CUT_PARAMETERS_M
    assert count(tree) == family.cut_parameters(cfg)
    per_layer = [round(count(b) / 1e5) / 10 for b in tree["blocks"]]
    assert per_layer == [99.8, 814.8, 814.8, 814.8, 814.8, 794.2, 814.8]
    assert ["kda" in b for b in tree["blocks"]] \
        == [True, True, True, True, True, False, True]
    assert round(count(tree["blocks"][0]["kda"]) / 1e4) == 5265   # 52.65 M
    assert round(count(tree["blocks"][5]["attn"]) / 1e4) == 3197  # 31.97 M
    moe = tree["blocks"][1]["moe"]
    assert moe["w_gate"].shape == (128, 2560, 768)        # the banks held
    assert moe["router"].shape == (512, 2560)             # all its outputs
    assert moe["bias"].shape == (512,) and moe["bias"].dtype == jnp.float32
    assert "mlp" in tree["blocks"][0] and "moe" not in tree["blocks"][0]
    assert tree["wte"].shape == (39296, 2560)
    assert family.reference_row_len(cell) == 8192 and family.vocab(cfg) == 39296
    sc = cell["program"]["serve_config"]
    assert linear_state.state_row_bytes(cfg) == 2_097_152            # 2.10 MB
    assert linear_state.state_layers(cfg) == 6
    assert linear_state.expert_layers(cfg) == 6
    assert sc["max_seqs"] * 6 * 2_097_152 == 1_610_612_736           # 1.61 GB
    assert sc["num_blocks"] * sc["block_size"] * 640 * 2 == 838_860_800


def test_check_config_holds_the_published_widths(family):
    body = harness.load_cell(CELL)["config"]
    family.check_config(body)
    for key, wrong in (("hidden_size", 2048), ("head_dim", 64),
                       ("kv_lora_rank", 256), ("n_group", 1),
                       ("topk_group", 8), ("moe_intermediate_size", 512),
                       ("num_experts_per_tok", 6), ("layer_group_size", 4),
                       ("short_conv_kernel_size", 2), ("kda_lower_bound", -8),
                       ("num_hidden_layers", 6), ("num_experts", 96),
                       ("vocab_size", 50176), ("q_lora_rank", 1536),
                       ("published", dict(body["published"],
                                          num_experts=128))):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))
    clamps = list(body["expert_swiglu_limit_list"])
    clamps[3] = 4
    with pytest.raises(AssertionError):
        family.check_config(dict(body, expert_swiglu_limit_list=clamps))


def test_sound_tiny_run_is_correct_through_the_driver():
    """The driver end to end at TINY (the same code path as the cell:
    prefill, state and pages in ``engine.pages``, the held range): the
    served tokens are the reference's own choices."""
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


def test_serving_controls_are_not_correct(family, greedy_sample, monkeypatch):
    """A control's forward pass in the program's place: its first choices
    lie below the reference's best by more than the reference's own tokens
    do (which lie at 0). ``fp8``: every position a little (the mean's
    control). ``slip``: one position in 7 here, by some logit spreads, and
    the others not at all (the max's). (``state16`` moves no first choice in
    8 tokens at this size: what it is at the cell's size is the chip's to
    say, PERF.md section 2.)"""
    from benchmark.drivers import serve_engine

    cell, sample = greedy_sample
    monkeypatch.setattr(family.reference, "SLIP_EVERY", 7)
    gaps = serve_engine.served_token_gaps(cell, SEED, sample,
                                          ("fp8", "slip"))
    sound = max(float(g.max()) for g in gaps["program"])
    for control in ("fp8", "slip"):
        worst = max(float(g.max()) for g in gaps[control])
        assert sound <= 1e-6 < 1e-3 < worst, (control, sound, worst)
    # served tokens sit at positions 23..30 of a row: 27 slips alone
    for g in gaps["slip"]:
        assert (g > 0).tolist() == [i == 4 for i in range(8)], g


def test_reference_invariants(family):
    ref, cfg = family.reference, family.TINY
    a = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    b = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    c = ref.init_weights(ref.seed_key(SEED + 1), cfg, jnp.float32)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
    dense, mla, kda = a["layers"]
    assert "gate" in dense and "conv" in dense and "router" in mla
    assert "kv_a" in mla and "conv" in kda and "q" in kda
    assert mla["router"].shape == (16, 64) and mla["exp_gate"].shape[0] == 8
    assert kda["conv"].shape == (4, 192) and kda["A_log"].shape == (4,)
    assert float(kda["dt_bias"].mean()) < -3 and kda["A_log"].dtype == jnp.float32
    fwd = jax.jit(lambda w, r, q=None: ref.forward(w, r, cfg, q),
                  static_argnums=2)
    # causal: a later token changes no earlier logit
    rows = np.random.default_rng(1).integers(0, 256, (1, 32)).astype(np.int32)
    other = rows.copy()
    other[0, 9] = (other[0, 9] + 1) % 256
    x, y = fwd(a, rows), fwd(a, other)
    assert float(jnp.abs(x[0, :9] - y[0, :9]).max()) == 0.0
    assert float(jnp.abs(x[0, 9:] - y[0, 9:]).max()) > 0
    # the weights of a token's picks sum to the scaling factor over ALL its
    # picks, held here or not, and lie in at most topk_group groups
    idx, w = ref.route(jnp.asarray(np.random.default_rng(2).standard_normal(
        (9, 64)), jnp.float32), mla, cfg)
    assert idx.shape == (9, 2) and np.allclose(w.sum(-1), 2.5, atol=1e-5)
    assert int(idx.max()) < 16 == ref.routed_experts(cfg)
    assert all(len(set(r // 4)) <= 2 for r in np.asarray(idx))
    # the controls are different functions, the unknown one an error
    z = fwd(a, rows, "state16")
    assert 0 < float(jnp.abs(z - x).max()) < 1.0
    with pytest.raises(ValueError):
        ref.forward(a, rows, cfg, "fp4")


def test_reference_recurrence_by_hand(family):
    """One head, two channels, three steps of the rule as written in the
    issue: decay, read, rank-one write, output."""
    S = np.zeros((2, 2))
    outs = []
    q = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
    k = np.array([[1.0, 0.0], [1.0, 0.0], [0.0, 1.0]])
    v = np.array([[2.0, 4.0], [1.0, 1.0], [3.0, 0.0]])
    alpha, beta = np.array([0.5, 0.25]), 0.5
    for t in range(3):
        S = alpha[:, None] * S
        S = S + beta * np.outer(k[t], v[t] - S.T @ k[t])
        outs.append(S.T @ q[t])
    from distributed_lion_tpu.ops import kda

    state = jnp.zeros((1, 1, 2, 2))
    for t in range(3):
        o, state = kda.kda_step_xla(
            state, *(jnp.asarray(x[t], jnp.float32)[None, None]
                     for x in (q, k, v)),
            jnp.log(jnp.asarray(alpha, jnp.float32))[None, None],
            jnp.full((1, 1), beta))
        assert np.allclose(o[0, 0], outs[t], atol=1e-6)
    assert np.allclose(outs[0], [1.0, 2.0]) and np.allclose(S, state[0, 0])


def test_program_layout_shares_the_reference_arrays(family):
    w = family.reference.init_weights(family.reference.seed_key(1),
                                      family.TINY, jnp.float32)
    tree = family.to_program(w)
    assert tree["blocks"][1]["moe"]["w_gate"] is w["layers"][1]["exp_gate"]
    assert tree["blocks"][0]["kda"]["wf"] is w["layers"][0]["f"]
    assert tree["blocks"][1]["attn"]["wq"] is w["layers"][1]["q"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))


# ---------------------------------------------------------------- readers
def read(ctx, name):
    return harness.load_module("layer_metrics", name).read(ctx)


KERNEL = 'custom-call( custom_call_target="tpu_custom_call" | s32[128] %x)'


def ctx_of(ops, stats=None, cell=CELL):
    ticks = [{"t0": 100.0 + i, "t1": 100.9 + i} for i in range(4)]
    facts = {"trace": {"t0": 100.0, "t1": 102.0}, "ticks": ticks,
             "max_seqs": 128,
             "kv_pool": {"leaf_shape": [128, 3, 12288], "leaves": 13,
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


OPS = [["kda_step.16", 0, 2e6, "kda_step.16 " + KERNEL],
       ["kda_step.21", 4e6, 4e6, "kda_step.21 " + KERNEL],
       ["moe_gmm.7", 9e6, 5e6, "moe_gmm.7 " + KERNEL]]


def test_state_roofline_against_a_hand_count():
    """Two ticks of 128 live slots over 6 KDA layers: 1,536 rows of 2 x
    2,097,152 B of state and 82,048 B of vectors = 6.57 GB, 8.0 ms at 819
    GB/s, over 6 ms of ``kda_step`` + 4 more: here 10 ms in all."""
    st = edges(state_rows_stepped=1536, decode_ticks=2)
    least = 1536 * (2 * 2_097_152 + 32 * (5 * 128 + 1) * 4)
    assert linear_state.kda_step_bytes(
        1536, harness.load_cell(CELL)["config"]) == least == 6_568_476_672
    ops = OPS + [["kda_step.17", 20e6, 4e6, "kda_step.17 " + KERNEL]]
    got = read(ctx_of(ops, st), "kda_state_roofline")
    assert got == pytest.approx(100 * least / 819e9 / 10e-3)
    assert 80 < got < 81
    # reported as it reads, never clamped
    assert read(ctx_of(OPS[:1], st), "kda_state_roofline") > 105
    assert read(ctx_of(OPS, st), "kda_step_ms.decode") == pytest.approx(3.0)


def test_chunk_roofline_against_a_hand_count():
    """Three prompts padded to 4,096 + 2,048 + 256 positions in the traced
    window, six KDA layers: 82,048 B a position and 2,097,152 B of state a
    prompt = 3.19 GB, 3.89 ms at 819 GB/s (the recurrence's 3.7 MFLOP a
    position a layer are 0.72 ms at the bfloat16 peak: the bytes bound it),
    over 40 ms of ``kda_chunk``; the step kernel's time is not counted."""
    cfg = harness.load_cell(CELL)["config"]
    st = edges(padded_prefill_tokens=6400, prefill_dispatches=3)
    least = 6 * (6400 * 32 * (5 * 128 + 1) * 4 + 3 * 2_097_152)
    assert linear_state.kda_chunk_bytes(6400, 3, cfg) == least
    assert linear_state.kda_chunk_flops(6400, cfg) \
        == 6 * 6400 * 32 * 7 * 128 * 128
    assert least / 819e9 > 5 * linear_state.kda_chunk_flops(6400, cfg) / 197e12
    ops = OPS + [["kda_chunk.3", 30e6, 25e6, "kda_chunk.3 " + KERNEL],
                 ["kda_chunk.4", 60e6, 15e6, "kda_chunk.4 " + KERNEL]]
    got = read(ctx_of(ops, st), "kda_chunk_roofline")
    assert got == pytest.approx(100 * least / 819e9 / 40e-3)
    assert 9 < got < 10
    # the chunk kernel's name is not the step's
    assert read(ctx_of(ops, edges(state_rows_stepped=1536, decode_ticks=2)),
                "kda_step_ms.decode") == pytest.approx(3.0)
    # a window with no prefill, a program without the kernel: nothing
    assert read(ctx_of(ops, edges(padded_prefill_tokens=0,
                                  prefill_dispatches=0)),
                "kda_chunk_roofline") is None
    assert read(ctx_of(OPS, st), "kda_chunk_roofline") is None
    assert read(ctx_of(ops), "kda_chunk_roofline") is None


def test_live_and_banks_shares_from_the_counters():
    st = edges(state_rows_stepped=82 * 6 * 120, decode_ticks=82,
               moe_experts_hit=82 * 6 * 96, moe_prefill_experts_hit=99999)
    ctx = ctx_of([], st)
    assert read(ctx, "state_live_pct.decode") == pytest.approx(100 * 120 / 128)
    assert read(ctx, "kda_banks_hit_pct.decode") == pytest.approx(75.0)


def test_readers_at_tiny_from_an_engine_run(family):
    """The readers' counts against what a TINY engine really stepped: one
    request of 9 prompt tokens and 6 outputs alone in 2 slots (5 decode
    ticks, 2 KDA layers: 10 rows of 20; 2 expert layers of 8 banks)."""
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServingEngine,
    )

    cfg = dict(family.TINY)
    params = family.program_weights(family.reference.seed_key(SEED), cfg,
                                    jnp.float32)
    eng = ServingEngine(family.serve_model(params, cfg, jnp.float32),
                        ServeConfig(max_seqs=2, block_size=8,
                                    max_blocks_per_seq=4, moe_stats=True))
    before = dict(eng.stats)
    eng.run([Request(req_id=0, tokens=list(range(9)), max_new_tokens=6)])
    ctx = ctx_of([["kda_step.1", 0, 1e3, "kda_step.1 " + KERNEL]],
                 {"trace_open": before, "trace_close": dict(eng.stats)})
    ctx["cell"] = dict(ctx["cell"], config=cfg)
    ctx["facts"]["max_seqs"] = 2
    assert eng.stats["state_rows_stepped"] == 10
    assert eng.stats["state_bytes"] == 2 * 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert read(ctx, "state_live_pct.decode") == pytest.approx(50.0)
    least = 10 * (2 * 4 * 16 * 16 * 4 + 4 * (5 * 16 + 1) * 4)
    assert read(ctx, "kda_state_roofline") \
        == pytest.approx(100 * least / 819e9 / 1e-6)
    assert read(ctx, "kda_banks_hit_pct.decode") == pytest.approx(
        100 * eng.stats["moe_experts_hit"] / (5 * 2 * 8))
    routed = 5 * 2 * 2           # 5 decode tokens x 2 picks x 2 layers
    assert eng.stats["moe_routed"] == routed
    assert read(ctx, "moe_held_pct.decode") == pytest.approx(
        100 * eng.stats["moe_assignments"] / routed)


NEW = ["kda_step_ms.decode", "kda_state_roofline", "state_live_pct.decode",
       "kda_banks_hit_pct.decode", "kda_chunk_roofline"]


@pytest.mark.parametrize("name", NEW)
def test_readers_with_nothing_to_read_return_nothing(name):
    if name != "kda_step_ms.decode":
        assert read(ctx_of(OPS), name) is None         # an older driver
        assert read(ctx_of(OPS, {"open": {}, "close": {}}), name) is None
        # a program that keeps none of these counters (the parent)
        bare = {"trace_open": {"ticks": 1, "kv_pages_read": 5},
                "trace_close": {"ticks": 9, "kv_pages_read": 50}}
        assert read(ctx_of(OPS, bare), name) is None
    st = edges(state_rows_stepped=9, moe_experts_hit=9, decode_ticks=0,
               padded_prefill_tokens=256, prefill_dispatches=1)
    if name in ("kda_step_ms.decode", "kda_state_roofline",
                "kda_chunk_roofline"):
        assert read(ctx_of(OPS[2:], st), name) is None     # no kernel op
        assert read(dict(ctx_of(OPS, st), trace={"planes": []}), name) is None
    else:
        assert read(ctx_of(OPS, st), name) is None         # no decode tick
    if name == "kda_banks_hit_pct.decode":
        # another family's cell, whose configuration has not these keys
        st = edges(moe_experts_hit=9, decode_ticks=3)
        assert read(ctx_of(OPS, st, "serve.laguna-s-2.1.backlog-8k"),
                    name) is None


def test_new_readers_are_listed_for_this_cell_alone():
    manifest = harness.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "serve_out_tokens_per_s"
    assert {listed[n]["unit"] for n in NEW} == {"ms", "%"}
    for name in ("mla_attn_roofline", "moe_held_hit_pct.decode",
                 "moe_experts_hit_pct.decode", "paged_attn_roofline",
                 "tick_host_ms.decode", "admit_ms.decode", "lower_s",
                 "compile_misses"):
        assert CELL not in listed[name]["workloads"], name
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_reference_imports_nothing_of_the_package():
    import ast
    import inspect

    from benchmark.reference import ling_3_flash as ref

    tree = ast.parse(inspect.getsource(ref))
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and (m.startswith("distributed_lion_tpu")
                          or m.startswith("benchmark")) for m in mods), mods
