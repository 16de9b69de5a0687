"""The ``joyai_llm_flash`` family, its plain reference and the readers of
``serve.joyai-llm-flash.backlog-2k`` on the CPU at the family's tiny size:
the reference against the program through the serving driver (``correct``
true), the fp8 control coming out not correct, the reference's own
invariants, the configuration's arithmetic, and each new reader on made-up
traces and counters (a program without the kernels or the counters reports
nothing)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import harness, latent_moe

CELL = "serve.joyai-llm-flash.backlog-2k"
SEED = 2 ** 31 + 26


@pytest.fixture(scope="module")
def family():
    return harness.load_family(harness.load_cell(CELL)["config"])


def test_the_cell_is_found_by_name_and_states_its_cut():
    cell = harness.load_cell(CELL)
    body = cell["config"]
    assert cell["driver"] == "serve_engine" and cell["chips"] == 1
    assert body["reduced"] == ["num_hidden_layers",
                               "num_nextn_predict_layers"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "num_nextn_predict_layers": 1}
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"]) == (5, 256, 129280)
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"mla_attn_ms.decode", "mla_attn_roofline", "moe_gmm_ms.decode",
            "moe_gmm_roofline", "moe_experts_hit_pct.decode",
            "prefill_ms.decode", "tick_ms.decode", "peak_hbm_gb.decode",
            "slots_busy_pct.decode", "host_gap_ms.decode",
            "decode_device_ms.decode", "compile_s"} <= reported
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"serve_out_tokens_per_s", "setup_s"}


def test_weights_and_pool_are_the_sizes_the_file_reckons(family):
    """11.12 GB of bfloat16 weights (5,558 M parameters) from shapes alone,
    and a reference row of 2,816 tokens (176 whole pages)."""
    cell = harness.load_cell(CELL)
    cfg = cell["config"]
    tree = jax.eval_shape(lambda: family.program_weights(
        jax.random.key(0), cfg, jnp.bfloat16))
    n = sum(x.size for x in jax.tree.leaves(tree))
    assert round(n / 1e6) == 5558
    moe = tree["blocks"][1]["moe"]
    assert moe["w_gate"].shape == (256, 2048, 768)
    assert moe["bias"].dtype == jnp.float32
    assert "mlp" in tree["blocks"][0] and "moe" not in tree["blocks"][0]
    assert family.reference_row_len(cell) == 2816
    sc = cell["program"]["serve_config"]
    pages = sc["max_seqs"] * sc["max_blocks_per_seq"]
    assert pages * 16 * 640 * 2 * 5 == 2_516_582_400          # 2.52 GB


def test_check_config_holds_the_published_widths(family):
    body = harness.load_cell(CELL)["config"]
    family.check_config(body)
    for key, wrong in (("kv_lora_rank", 256), ("n_routed_experts", 64),
                       ("vocab_size", 32000), ("moe_intermediate_size", 512)):
        with pytest.raises(AssertionError):
            family.check_config(dict(body, **{key: wrong}))


def test_sound_tiny_run_is_correct_through_the_driver():
    """The driver end to end at TINY (the same code path as the cell): the
    served tokens are the reference's own choices."""
    result = rehearse.run_tiny(CELL, 1, seconds=1.0, seed=SEED)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def test_serving_control_in_fp8_is_not_correct(family):
    """The fp8 forward pass in the program's place, at a wide flat
    vocabulary: its first choices lie below the reference's best by more
    than the reference's own tokens do (which lie at 0)."""
    from benchmark.drivers import serve_engine

    ref = family.reference
    cell = rehearse.tiny_cell(CELL)
    cell["config"] = dict(cell["config"], vocab_size=8192)
    cfg = cell["config"]
    rng = np.random.default_rng(3)
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(SEED))
    sample = []
    for i in range(3):
        seq = rng.integers(0, 8192, 24).tolist()
        for _ in range(8):      # greedy tokens of the reference itself
            pad = np.zeros((1, 32), np.int32)
            pad[0, :len(seq)] = seq
            logits = ref.forward(weights, pad, cfg)
            seq.append(int(logits[0, len(seq) - 1].argmax()))
        sample.append({"id": i, "prompt": seq[:24], "tokens": seq[24:]})
    gaps = serve_engine.served_token_gaps(cell, SEED, sample, ("fp8",))
    sound = max(float(g.max()) for g in gaps["program"])
    control = max(float(g.max()) for g in gaps["fp8"])
    assert sound <= 1e-6 < 0.01 < control, (sound, control)


def test_reference_invariants(family):
    ref, cfg = family.reference, family.TINY
    a = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    b = ref.init_weights(ref.seed_key(SEED), cfg, jnp.float32)
    c = ref.init_weights(ref.seed_key(SEED + 1), cfg, jnp.float32)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
    dense, moe = a["layers"]
    assert "gate" in dense and "router" in moe
    assert float(jnp.abs(moe["router_bias"]).max()) > 0
    # causal: a later token changes no earlier logit
    rows = np.random.default_rng(1).integers(0, 256, (1, 16)).astype(np.int32)
    other = rows.copy()
    other[0, 9] = (other[0, 9] + 1) % 256
    x, y = ref.forward(a, rows, cfg), ref.forward(a, other, cfg)
    assert float(jnp.abs(x[0, :9] - y[0, :9]).max()) == 0.0
    assert float(jnp.abs(x[0, 9:] - y[0, 9:]).max()) > 0
    # the weights of a token's experts sum to the scaling factor
    idx, w = ref.route(jnp.ones((3, 64)), moe, cfg)
    assert idx.shape == (3, 2) and np.allclose(w.sum(-1), 2.5, atol=1e-5)
    # the lower precisions are different functions, the unknown one an error
    for quant in ("bf16", "int8", "fp8"):
        z = ref.forward(a, rows, cfg, quant)
        assert 0 < float(jnp.abs(z - x).max()) < 1.0
    with pytest.raises(ValueError):
        ref.forward(a, rows, cfg, "fp4")


def test_program_layout_shares_the_reference_arrays(family):
    w = family.reference.init_weights(family.reference.seed_key(1),
                                      family.TINY, jnp.float32)
    tree = family.to_program(w)
    assert tree["blocks"][1]["moe"]["w_gate"] is w["layers"][1]["exp_gate"]
    assert tree["blocks"][0]["attn"]["wkv_b"] is w["layers"][0]["kv_b"]
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(w))


# ---------------------------------------------------------------- readers
def read(ctx, name):
    return harness.load_module("layer_metrics", name).read(ctx)


KERNEL = 'custom-call( custom_call_target="tpu_custom_call" | s32[128] %x)'


def ctx_of(ops, stats=None):
    ticks = [{"t0": 100.0 + i, "t1": 100.9 + i} for i in range(4)]
    cell = harness.load_cell(CELL)
    facts = {"trace": {"t0": 100.0, "t1": 102.0}, "ticks": ticks,
             "kv_pool": {"leaf_shape": [24576, 16, 1, 640], "leaves": 5,
                         "itemsize": 2}}
    if stats is not None:
        facts["engine_stats"] = stats
    return {"cell": cell,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "facts": facts,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops}]}]}}


def edges(**delta):
    zero = {k: 1000 for k in delta}
    return {"trace_open": zero,
            "trace_close": {k: 1000 + v for k, v in delta.items()}}


def test_kernel_times_per_traced_tick_by_name():
    ops = [["mla_paged_attn.3", 0, 3e6, "mla_paged_attn.3 " + KERNEL],
           ["moe_gmm.7", 4e6, 9e6, "moe_gmm.7 " + KERNEL],
           ["moe_gmm.8", 14e6, 1e6, "moe_gmm.8 " + KERNEL],
           ["paged_attn.1", 20e6, 5e6, "paged_attn.1 " + KERNEL],
           ["fusion.2", 30e6, 7e6, "fusion.2 fusion( | %moe_gmm.8)"]]
    ctx = ctx_of(ops)               # 2 ticks lie inside the traced window
    assert read(ctx, "mla_attn_ms.decode") == pytest.approx(1.5)
    assert read(ctx, "moe_gmm_ms.decode") == pytest.approx(5.0)
    none = ctx_of([ops[3], ops[4]])
    assert read(none, "mla_attn_ms.decode") is None
    assert read(none, "moe_gmm_ms.decode") is None


def test_mla_roofline_is_the_larger_of_bytes_and_operations():
    # a tick of 14,000 pages in 5 leaves of 20,480 B a page: 1.43 GB
    ops = [["mla_paged_attn.3", 0, 3.4e6, "mla_paged_attn.3 " + KERNEL]]
    got = read(ctx_of(ops, edges(kv_pages_read=14000)), "mla_attn_roofline")
    by_bytes = 14000 * 16 * 640 * 2 * 5 / 819e9
    by_flops = 14000 * 16 * 32 * (576 + 512) * 2 * 5 / 197e12
    assert by_bytes > by_flops
    assert got == pytest.approx(100 * by_bytes / 3.4e-3) and 50 < got < 52
    # reported as it reads, never clamped
    fast = [["mla_paged_attn.3", 0, 1e6, "mla_paged_attn.3 " + KERNEL]]
    assert read(ctx_of(fast, edges(kv_pages_read=14000)),
                "mla_attn_roofline") > 100
    assert latent_moe.mla_attn_bytes(1, 16, 640, 5) == 102_400
    assert latent_moe.mla_attn_flops(1, 16, 32, 576, 512, 1) \
        == 16 * 32 * 1088 * 2


def test_gmm_roofline_from_the_engines_counters():
    ops = [["moe_gmm.7", 0, 12e6, "moe_gmm.7 " + KERNEL]]
    st = edges(moe_assignments=4096, moe_experts_hit=1000,
               moe_prefill_assignments=0, moe_prefill_experts_hit=0)
    got = read(ctx_of(ops, st), "moe_gmm_roofline")
    flops = 4096 * 3 * 2 * 2048 * 768
    bytes_ = (1000 * 3 * 2048 * 768 + 4096 * 3 * (2048 + 768)) * 2
    assert latent_moe.moe_gmm_flops(4096, 2048, 768) == flops
    assert latent_moe.moe_gmm_bytes(4096, 1000, 2048, 768) == bytes_
    assert bytes_ / 819e9 > flops / 197e12              # a decode tick
    assert got == pytest.approx(100 * bytes_ / 819e9 / 12e-3)
    # a 2,048-token prefill is 64 rows an expert: the banks still bound it
    st = edges(moe_assignments=0, moe_experts_hit=0,
               moe_prefill_assignments=4 * 16384,
               moe_prefill_experts_hit=1024)
    got = read(ctx_of(ops, st), "moe_gmm_roofline")
    assert got == pytest.approx(100 * latent_moe.moe_gmm_bytes(
        4 * 16384, 1024, 2048, 768) / 819e9 / 12e-3)
    # the same rows over few experts: operations bound it
    st["trace_close"]["moe_prefill_experts_hit"] = 1000 + 16
    got = read(ctx_of(ops, st), "moe_gmm_roofline")
    assert got == pytest.approx(
        100 * (4 * 16384 * 3 * 2 * 2048 * 768 / 197e12) / 12e-3)


def test_experts_hit_share_of_the_decode_dispatches():
    st = edges(moe_experts_hit=82 * 4 * 250, decode_ticks=82,
               moe_prefill_experts_hit=99999)
    got = read(ctx_of([], st), "moe_experts_hit_pct.decode")
    assert got == pytest.approx(100 * 250 / 256)


@pytest.mark.parametrize("name", ["mla_attn_roofline", "moe_gmm_roofline",
                                  "moe_experts_hit_pct.decode"])
def test_readers_with_nothing_to_read_return_nothing(name):
    ops = [["mla_paged_attn.3", 0, 3e6, "mla_paged_attn.3 " + KERNEL],
           ["moe_gmm.7", 4e6, 9e6, "moe_gmm.7 " + KERNEL]]
    assert read(ctx_of(ops), name) is None             # an older driver
    assert read(ctx_of(ops, {"open": {}, "close": {}}), name) is None
    # a program that keeps none of these counters (the parent)
    bare = {"trace_open": {"ticks": 1}, "trace_close": {"ticks": 9}}
    assert read(ctx_of(ops, bare), name) is None
    st = edges(kv_pages_read=9, moe_assignments=9, moe_experts_hit=9,
               decode_ticks=0)
    if name != "moe_experts_hit_pct.decode":
        assert read(ctx_of([], st), name) is None      # no kernel op
        assert read(dict(ctx_of(ops, st), trace={"planes": []}), name) is None
    else:
        assert read(ctx_of(ops, st), name) is None     # no decode tick


def test_prefill_span_reader(monkeypatch):
    from distributed_lion_tpu.train import journal

    spans = [{"name": "serve/prefill", "t0": 100.1, "t1": 100.13,
              "id": 1, "parent": 0},
             {"name": "serve/prefill", "t0": 100.5, "t1": 100.55,
              "id": 2, "parent": 0},
             {"name": "serve/prefill", "t0": 101.0, "t1": 101.04,
              "id": 3, "parent": 0},
             {"name": "serve/prefill", "t0": 150.0, "t1": 151.0,
              "id": 4, "parent": 0},          # outside the traced window
             {"name": "serve/tick", "t0": 100.0, "t1": 100.2,
              "id": 5, "parent": 0}]
    monkeypatch.setattr(journal, "traced", lambda: spans, raising=False)
    assert read(ctx_of([]), "prefill_ms.decode") == pytest.approx(40.0)
    monkeypatch.setattr(journal, "traced", lambda: [], raising=False)
    assert read(ctx_of([]), "prefill_ms.decode") is None


def test_the_reference_imports_nothing_of_the_package():
    import ast
    import inspect

    from benchmark.reference import joyai_llm_flash as ref

    tree = ast.parse(inspect.getsource(ref))
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and (m.startswith("distributed_lion_tpu")
                          or m.startswith("benchmark")) for m in mods), mods
