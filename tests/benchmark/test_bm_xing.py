"""The ``xing4_0`` family, its plain reference and the readers of
``serve.xing4.0-29b-a4b.backlog-4k-in`` on the CPU at the family's tiny size:
the configuration file against the catalog row's every key, the cut's
arithmetic, the cell and its three readers in the manifest (membership, not
position), the reference against the program through the serving driver
(``correct`` true), the ``sink1`` control, and each new reader against hand
counts on a made-up trace and against what a TINY engine really did (a
program without the kernels or the counter reports nothing)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import harness, hyper_stream

CELL = "serve.xing4.0-29b-a4b.backlog-4k-in"
CONFIG = "xing4.0-29b-a4b"
SEED = 2 ** 31 + 39
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["mhc_ms.decode", "mhc_roofline", "mhc_share_pct.decode"]


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def test_the_cell_is_found_by_name_and_states_its_cut(family):
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "serve_engine_blocks" and cell["chips"] == 1
    assert cell["traffic_name"] == "backlog-4k-in"
    assert cell["config_name"] == CONFIG
    assert body["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "num_nextn_predict_layers"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "first_k_dense_replace": 2,
                                 "num_nextn_predict_layers": 1}
    assert (body["num_hidden_layers"], body["first_k_dense_replace"],
            body["num_nextn_predict_layers"]) == (6, 1, 0)
    assert {"weights", "serving_dtypes", "hyper_connections",
            "mix_input_norm", "sinkhorn", "residual_mix", "stream_ends",
            "rope", "multi_token_prediction", "sizes"} \
        <= set(body["assumed"])
    assert "no layer divided" in body["deployment"]
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {
        "compile_s", "tick_ms.decode", "decode_device_ms.decode",
        "host_gap_ms.decode", "slots_busy_pct.decode", "peak_hbm_gb.decode",
        "prefill_ms.decode", "mla_attn_ms.decode", "mla_attn_roofline",
        "moe_gmm_ms.decode", "moe_gmm_roofline",
        "moe_experts_hit_pct.decode"} == reported
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"serve_out_tokens_per_s", "setup_s"}
    sc, t = cell["program"]["serve_config"], cell["traffic"]
    assert sc == {"max_seqs": 64, "block_size": 16, "max_blocks_per_seq": 288,
                  "num_blocks": 18432, "prefill_cap_tokens": 4096,
                  "temperature": 0.0, "eos_id": None, "moe_stats": True}
    assert sc["max_blocks_per_seq"] * sc["block_size"] \
        == t["prompt_len"]["hi"] + t["output_len"]["hi"] == 4608 \
        == family.reference_row_len(cell)
    assert sc["num_blocks"] == sc["max_seqs"] * sc["max_blocks_per_seq"]
    assert (t["mix_seed"], t["deck"], t["arrivals"]) == (
        3901, 128, {"kind": "backlog", "count": 1200})
    assert t["prompt_len"] == {"median": 3072, "sigma": 0.2, "lo": 2304,
                               "hi": 4096}
    assert t["output_len"] == {"median": 256, "sigma": 0.4, "lo": 128,
                               "hi": 512}
    w = cell["program"]["window"]
    assert (w["open"], w["ticks"], w["trace_after_s"], w["trace_s"]) \
        == ("after_ticks", 64, 1.0, 3.0)
    # every prompt in the one bucket
    from benchmark.drivers import serve_engine
    assert serve_engine.buckets_of(cell) == [4096]
    limits = cell["correct"]["limits"]
    assert set(limits) == {"served_logit_gap_max", "served_logit_gap_mean"}


def test_the_configuration_holds_the_catalogs_keys_and_its_arithmetic(family):
    manifest = harness.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    body = harness.read_json(harness.ROOT, entry["file"])
    harness.check_config_file(entry, body)
    matrices, mix = family.cut_parameters(body)
    # 127.5 + 5 x 744.3 + 939.5 M, and 6 x 2 x (14,336 x 24 + 27) of mix
    assert matrices == 4_788_486_144 and round(matrices / 1e5) == 47885
    assert matrices == (28_409_856 + 99_090_432) \
        + 5 * (28_409_856 + 715_882_496) + 939_524_096
    assert mix == 12 * (14336 * 24 + 27) == 4_129_092
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert entry["source"] == body["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in body, key
            if key in entry["reduced"]:
                assert body["published"][key] == value != body[key], key
            else:
                assert body[key] == value, key
                if key in family.PUBLISHED:
                    assert family.PUBLISHED[key] == value, key
    for key, wrong in (("hidden_size", 2048), ("hc_mult", 2),
                       ("hc_sinkhorn_iters", 3), ("n_routed_experts", 8),
                       ("vocab_size", 16384), ("num_hidden_layers", 4),
                       ("first_k_dense_replace", 3), ("q_lora_rank", 1536),
                       ("rope_scaling", dict(body["rope_scaling"],
                                             factor=40))):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))


def test_program_layout_shares_the_reference_arrays(family):
    """Every leaf of the program's tree is the reference's own array but a
    sublayer's ``phi``, which is the same matrix packed."""
    init = jax.jit(lambda key, dtype: family.reference.init_weights(
        key, family.TINY, dtype), static_argnums=(1,))
    w = init(family.reference.seed_key(1), jnp.float32)
    mix = family.model_config(family.TINY, jnp.float32).mix
    tree = family.to_program(w, mix)
    assert tree["blocks"][0]["attn"]["wkv_a"] is w["layers"][0]["kv_a"]
    assert tree["blocks"][1]["moe"]["w_down"] is w["layers"][1]["exp_down"]
    assert tree["blocks"][1]["hc_mlp"]["b"] is w["layers"][1]["ffn_hc_b"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))
    phi = tree["blocks"][0]["hc_attn"]["phi"]
    assert phi.shape == (4 * 64, 128) and phi.dtype == jnp.bfloat16
    parts, c = phi.astype(jnp.float32), mix.width
    assert bool((parts[:, :c] + parts[:, c:2 * c] + parts[:, 2 * c:3 * c]
                 == w["layers"][0]["attn_hc_phi"]).all())
    # b_res carries the diagonal, a is ones, phi is float32 in the reference
    b = w["layers"][0]["attn_hc_b"]
    assert float(b[8:].reshape(4, 4).diagonal().min()) > 2.0
    assert w["layers"][0]["attn_hc_phi"].dtype == jnp.float32
    bf = init(family.reference.seed_key(1), jnp.bfloat16)
    assert bf["layers"][0]["attn_hc_phi"].dtype == jnp.float32
    assert bf["layers"][0]["q_a"].dtype == jnp.bfloat16
    assert float(jnp.abs(bf["layers"][0]["q_a"].astype(jnp.float32)).max()) \
        < 0.2                                   # a matrix, not ones


def test_the_reference_imports_nothing_of_the_package():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(
        harness.load_family({"model_type": "xing4_0"}).reference))
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and (m.startswith("distributed_lion_tpu")
                          or m.startswith("benchmark")) for m in mods), mods


# ------------------------------------------------------------ the driver
def test_sound_tiny_run_is_correct_through_the_driver():
    """The driver end to end at TINY (the same code path as the cell)."""
    from benchmark import run

    result = run.run_cell(rehearse.tiny_cell(CELL), SEED, 0.5, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0


def test_the_controls_are_other_passes_of_the_reference(family):
    """``sink1`` (one Sinkhorn step), ``slip`` and ``fp8`` each move the
    reference's logits; ``served_logits`` is ``forward`` over the span, for
    the float32 pass and for a control."""
    ref = family.reference
    cfg = dict(family.TINY, vocab_size=1024)
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(SEED))
    rows = np.random.default_rng(5).integers(0, 1024, (1, 64)).astype(
        np.int32)
    full = jax.jit(lambda r: ref.forward(weights, r, cfg))(rows)
    for quant in ("sink1", "fp8"):
        low = jax.jit(lambda r: ref.forward(weights, r, cfg, quant))(rows)
        assert float(jnp.abs(low - full).max()) > 1e-3, quant
        span = jax.jit(lambda r: ref.served_logits(weights, r, cfg, 40, 16,
                                                   quant))(rows)
        assert np.allclose(span, low[:, 40:56], atol=1e-5), quant
    # slip: position 250 of every 251, rolled half the vocabulary round
    slipped = ref._slip(full, 200)
    moved = np.asarray(jnp.abs(slipped - full).max(-1)[0] > 0)
    assert moved.tolist() == [i == 50 for i in range(64)]
    assert np.array_equal(slipped[0, 50], np.roll(full[0, 50], 512))
    span = jax.jit(lambda r: ref.served_logits(weights, r, cfg, 60, 16))(rows)
    assert np.allclose(span, full[:, 48:64], atol=1e-5)    # clipped to the row
    with pytest.raises(ValueError, match="unknown precision"):
        ref.forward(weights, rows, cfg, "fp4")


# ---------------------------------------------------------------- readers
def read(ctx, name):
    return harness.load_module("layer_metrics", name).read(ctx)


KERNEL = 'custom-call( custom_call_target="tpu_custom_call" | bf16[64,14336] %x)'


def ctx_of(ops, stats=None, modules=None, cell=CELL):
    ticks = [{"t0": 100.0 + i, "t1": 100.9 + i} for i in range(4)]
    facts = {"trace": {"t0": 100.0, "t1": 102.0, "window_s": 2.0},
             "ticks": ticks, "max_seqs": 64}
    if stats is not None:
        facts["engine_stats"] = stats
    lines = [{"name": "XLA Ops", "events": ops}]
    if modules is not None:
        lines.append({"name": "XLA Modules", "events": modules})
    return {"cell": harness.load_cell(cell),
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "facts": facts,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": lines}]}}


def edges(**delta):
    zero = {k: 1000 for k in delta}
    return {"trace_open": zero,
            "trace_close": {k: 1000 + v for k, v in delta.items()}}


# two decode ticks (1 ms each: 0.1 + 0.2 ms of mix in each) round one
# prefill (10 ms: 2 + 3 ms of mix), 6 ms of other ops
OPS = [["mhc_pre.1", 0.1e6, 0.1e6, "mhc_pre.1 " + KERNEL],
       ["mhc_post.1", 0.5e6, 0.2e6, "mhc_post.1 " + KERNEL],
       ["mhc_pre.7", 2.0e6, 2.0e6, "mhc_pre.7 " + KERNEL],
       ["fusion.3", 4.0e6, 1.0e6, "fusion.3 fusion( kind=kLoop | %mhc_pre.7)"],
       ["mhc_post.7", 5.0e6, 3.0e6, "mhc_post.7 " + KERNEL],
       ["moe_gmm.2", 8.0e6, 4.0e6, "moe_gmm.2 " + KERNEL],
       ["mhc_pre.1", 13.1e6, 0.1e6, "mhc_pre.1 " + KERNEL],
       ["mhc_post.1", 13.5e6, 0.2e6, "mhc_post.1 " + KERNEL],
       ["fusion.9", 13.8e6, 0.2e6, "fusion.9 fusion( kind=kLoop | %p)"]]
MODULES = [["jit_decode_tick(123)", 0.0, 1.0e6, "jit_decode_tick(123)"],
           ["jit_prefill(456)", 2.0e6, 10.0e6, "jit_prefill(456)"],
           ["jit_decode_tick(123)", 13.0e6, 1.0e6, "jit_decode_tick(123)"]]


def test_bytes_of_the_mix():
    """A (row, sublayer) pair moves (3 n + 2) d values: 100,352 B at 4 x
    3,584 in bfloat16; a 4,096-token prefill of 12 sublayers 4.9 GB."""
    assert hyper_stream.mhc_bytes(1, 4, 3584) == 100_352
    assert hyper_stream.mhc_bytes(1, 4, 3584) == (28672 + 7168) \
        + (28672 + 7168 + 28672)
    assert hyper_stream.mhc_bytes(4096 * 12, 4, 3584) == 4_932_501_504
    assert hyper_stream.mhc_bytes(10, 2, 128, 4) == 10 * 8 * 128 * 4


def test_readers_against_a_hand_count():
    """5.6 ms of the two kernels in a trace of 10.8 busy ms (a fusion that
    merely consumes a kernel's output is not the kernel): 0.3 ms a decode
    tick (the prefill's 5 ms are not a tick's), 51.9% of the device's time;
    12,000 (row, sublayer) pairs are 1.2 GB, 1.47 ms at 819 GB/s: 26.3% of
    the kernels' time."""
    st = edges(mhc_rows=12000, decode_ticks=2)
    ctx = ctx_of(OPS, st, MODULES)
    assert read(ctx, "mhc_ms.decode") == pytest.approx(0.3)
    assert read(ctx, "mhc_share_pct.decode") == pytest.approx(
        100 * 5.6 / 10.8)
    least = 12000 * 100_352 / 819e9
    assert read(ctx, "mhc_roofline") == pytest.approx(100 * least / 5.6e-3)
    assert 26 < read(ctx, "mhc_roofline") < 27
    # reported as it reads, never clamped
    st = edges(mhc_rows=120000)
    assert read(ctx_of(OPS, st, MODULES), "mhc_roofline") > 105
    found = hyper_stream.kernel_s_in(ctx["trace"]["planes"][0],
                                     hyper_stream.MHC_KERNELS, r"^jit_prefill")
    assert found == (pytest.approx(5e-3), 1)


@pytest.mark.parametrize("name", NEW)
def test_readers_with_nothing_to_read_return_nothing(name):
    st = edges(mhc_rows=9)
    other = [op for op in OPS if not op[0].startswith("mhc_")]
    # a program without the kernels (the parent), no device plane
    assert read(ctx_of(other, st, MODULES), name) is None
    assert read(dict(ctx_of(OPS, st, MODULES), trace={"planes": []}),
                name) is None
    if name == "mhc_ms.decode":
        assert read(ctx_of(OPS, st), name) is None       # no program named
        assert read(ctx_of(OPS, st, MODULES[1:2]), name) is None  # no tick
    if name == "mhc_roofline":
        assert read(ctx_of(OPS, None, MODULES), name) is None  # older driver
        bare = {"trace_open": {"ticks": 1}, "trace_close": {"ticks": 9}}
        assert read(ctx_of(OPS, bare, MODULES), name) is None  # no counter
        assert read(ctx_of(OPS, st, MODULES,
                           "serve.joyai-llm-flash.backlog-2k"), name) is None


def test_readers_at_tiny_from_an_engine_run(family):
    """``mhc_roofline``'s count against what a TINY engine really did: one
    request of 20 prompt tokens and 7 outputs alone in 2 slots (6 decode
    ticks; 2 layers = 4 sublayers; 4 streams of 64 in float32)."""
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServingEngine,
    )

    cfg = dict(family.TINY)
    params = family.program_weights(family.reference.seed_key(SEED), cfg,
                                    jnp.float32)
    eng = ServingEngine(family.serve_model(params, cfg, jnp.float32),
                        ServeConfig(max_seqs=2, block_size=8,
                                    max_blocks_per_seq=8,
                                    prefill_cap_tokens=64, moe_stats=True))
    before = dict(eng.stats)
    eng.run([Request(req_id=0, tokens=list(range(20)), max_new_tokens=7)])
    assert eng.stats["mhc_rows"] == (20 + 6) * 4
    assert 0 < eng.stats["mhc_res_defect_max"] < 100_000
    ops = [["mhc_pre.1", 0, 1e3, "mhc_pre.1 " + KERNEL],
           ["mhc_post.1", 2e3, 1e3, "mhc_post.1 " + KERNEL]]
    ctx = ctx_of(ops, {"trace_open": before, "trace_close": dict(eng.stats)})
    ctx["cell"] = dict(ctx["cell"], config=cfg)
    ctx["cell"]["program"] = dict(ctx["cell"]["program"],
                                  weights_dtype="float32")
    least = 104 * 14 * 64 * 4
    assert read(ctx, "mhc_roofline") \
        == pytest.approx(100 * least / 819e9 / 2e-6)


def test_new_readers_and_the_cell_are_in_the_manifest():
    manifest = harness.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "serve_out_tokens_per_s"
        assert listed[name]["layer"] == "residual mix (ops/mhc)"
        assert listed[name]["source"] == "device_trace"
    assert {listed[n]["unit"] for n in NEW} == {"ms", "%"}
    for name in ("serve_out_tokens_per_s",):
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in entry["workloads"]
    for name in ("mla_attn_roofline", "moe_gmm_roofline", "mla_attn_ms.decode",
                 "moe_gmm_ms.decode", "moe_experts_hit_pct.decode",
                 "prefill_ms.decode", "compile_s"):
        assert CELL in listed[name]["workloads"], name
    for name in ("paged_attn_roofline", "paged_attn_ms.decode",
                 "kda_step_ms.decode", "lightning_step_ms.decode",
                 "moe_held_pct.decode", "tick_host_ms.decode", "lower_s"):
        assert CELL not in listed[name]["workloads"], name
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["config"] == CONFIG and entry["traffic"] == "backlog-4k-in"
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(config["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the new kernels' names hold no other reader's pattern, nor theirs ours
    import re
    for other in ("paged_attn", "kda_", "flash_attention", "flash_mha",
                  "moe_gmm", "lion_", "lightning_", "mla_paged_attn"):
        assert not re.search(hyper_stream.MHC_KERNELS, other)
        assert other not in hyper_stream.MHC_KERNELS
