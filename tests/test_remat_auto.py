"""``remat_policy='auto'``: the resolver as a function of shapes and a memory
limit (no device: parameters are shapes, the limit and the backend's name
are handed in), and the trainer that says what it resolved to."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.analysis.trace_check import iter_eqns
from distributed_lion_tpu.models import gpt2, llama
from distributed_lion_tpu.models.gpt2 import GPT2Config
from distributed_lion_tpu.parallel import make_mesh
from distributed_lion_tpu.train import loop, remat
from distributed_lion_tpu.train.loop import TrainConfig, Trainer

GB = 10 ** 9
V5E_LIMIT = 16_910_000_000   # a v5e's bytes_limit (my chip runs, PR 31: 16.91 GB)


def _on_a_tpu(monkeypatch, limit):
    """The resolver asks the backend's name (which kernels `auto` takes at
    these shapes) and the device's memory; say both for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(loop, "device_bytes_limit", lambda: limit)


def _gpt2_124m(**kw):
    cfg = GPT2Config.gpt2_124m(
        **{"dropout": 0.0, "remat_policy": "auto", **kw})
    return cfg, jax.eval_shape(lambda: gpt2.gpt2_init(jax.random.key(0), cfg))


def _mesh(**axes):
    n = int(np.prod(list(axes.values()))) if axes else 1
    return make_mesh(**axes, devices=jax.devices()[:n])


def _resolve(monkeypatch, limit, *, rows, data=1, model_kw=None,
             mesh_kw=None, cfg_kw=None):
    _on_a_tpu(monkeypatch, limit)
    model_cfg, params = _gpt2_124m(**(model_kw or {}))
    cfg = TrainConfig(lion=True, per_device_train_batch_size=rows,
                      block_size=1024, **(cfg_kw or {}))
    return loop.apply_remat_policy(
        cfg, model_cfg, _mesh(data=data, **(mesh_kw or {})), params)


# cell 1 (20 sequences a microbatch, one worker) and cell 4 (4 sequences a
# worker, 4 workers): `none` on a v5e, then `dots`, then `full` as the
# device shrinks. The counts: cell 1 6.44 / 6.05 / 3.28 GB, cell 4 5.85 /
# 5.86 / 5.27 GB (at 4 sequences `dots` saves no more than `none`: its one
# recomputed block outweighs eleven blocks' tenth)
@pytest.mark.parametrize("rows,data,limit_gb,rung", [
    (20, 1, 16.91, "none"), (20, 1, 8.7, "dots"), (20, 1, 6.0, "full"),
    (20, 1, 4.0, "full"),
    (4, 4, 16.91, "none"), (4, 4, 7.7, "full"), (4, 4, 6.0, "full"),
], ids=lambda v: str(v))
def test_cells_resolve_by_the_limit(monkeypatch, rows, data, limit_gb, rung):
    model_cfg, decision = _resolve(monkeypatch, int(limit_gb * GB),
                                   rows=rows, data=data)
    assert decision.rung == rung, decision.line()
    assert (model_cfg.remat, model_cfg.remat_policy) == {
        "none": (False, "full"), "dots": (True, "dots"),
        "full": (True, "full")}[rung]
    assert set(decision.predicted) == set(remat.RUNGS)
    assert decision.line().startswith(f"[setup] remat: {rung} (predicted ")


def test_cell_1_predictions_are_the_documented_ones(monkeypatch):
    """PERF.md quotes these; the described v5e's compiler reads 6.51 / 6.17
    / 3.13 GB for the same step (tests/test_chip_compile.py holds a block's
    share of it)."""
    _, decision = _resolve(monkeypatch, V5E_LIMIT, rows=20)
    got = {r: round(b / GB, 1) for r, b in decision.predicted.items()}
    assert got == {"none": 6.4, "dots": 6.0, "full": 3.3}
    assert decision.line() == ("[setup] remat: none (predicted 6.4 of 16.91 "
                               "GB; dots 6.0, full 3.3)")


def test_the_logits_in_hbm_kept_cell_1_off_none():
    """Until PR 29 the loss head held the float32 logits: 5.8 GB more in the
    fixed part at 20 sequences. `none` did not fit then."""
    cfg, params = _gpt2_124m()
    n = gpt2.count_params(params)
    saved = remat.block_saved_bytes(cfg, 20, 1024)
    fixed = remat.fixed_bytes(
        n_params=n, compute_dtype=jnp.bfloat16,
        param_dtype=jnp.float32, state_bytes=4 * n,
        head=remat.head_bytes(cfg, 20, 1024, fused=True), world=1)
    assert remat.resolve(saved, 12, fixed, V5E_LIMIT).rung == "none"
    assert remat.resolve(saved, 12, fixed + int(5.8 * GB),
                         V5E_LIMIT).rung != "none"


def test_llama_7b_under_run_sft_stays_full(monkeypatch):
    """Llama-2-7B widths at run_sft's batch (4 x 1024, LoRA adapters over a
    frozen float32 base): nothing but `full` can be asked of 16 GB."""
    _on_a_tpu(monkeypatch, V5E_LIMIT)
    model_cfg = dataclasses.replace(llama.LlamaConfig.named("llama2_7b"),
                                    remat_policy="auto")
    base = jax.eval_shape(
        lambda: llama.llama_init(jax.random.key(0), model_cfg))
    adapters = {"a": jax.ShapeDtypeStruct((32, 4096, 16), jnp.float32)}
    cfg = TrainConfig(lion=True, per_device_train_batch_size=4,
                      block_size=1024)
    out, decision = loop.apply_remat_policy(cfg, model_cfg, _mesh(data=1),
                                            adapters, frozen=base)
    assert decision.rung == "full" and not decision.unmodelled
    assert (out.remat, out.remat_policy) == (True, "full")
    # and with the base as small as 4-bit codes would make it, a block's
    # residuals alone (0.38 GB x 32) are past the share
    small = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (a.size // 8,), jnp.float32), base)
    _, decision = loop.apply_remat_policy(cfg, model_cfg, _mesh(data=1),
                                          adapters, frozen=small)
    assert decision.rung == "full" and not decision.unmodelled


@pytest.mark.parametrize("case,why", [
    ("moe", "moe_experts > 0"), ("pipe", "pipeline_parallel > 1"),
    ("seq", "seq_parallel > 1"), ("cpu", "no bytes_limit")])
def test_what_the_count_does_not_model_stays_full(monkeypatch, case, why):
    kw = {"moe": dict(model_kw=dict(moe_experts=4)),
          "pipe": dict(mesh_kw=dict(pipe=2)),
          "seq": dict(mesh_kw=dict(seq=2))}.get(case, {})
    model_cfg, decision = _resolve(
        monkeypatch, None if case == "cpu" else V5E_LIMIT, rows=20, **kw)
    assert decision.rung == "full" and why in decision.unmodelled
    assert (model_cfg.remat, model_cfg.remat_policy) == (True, "full")
    assert decision.line() == ("[setup] remat: full (auto does not model "
                               f"{decision.unmodelled})")
    assert not decision.predicted


@pytest.mark.parametrize("model_kw,override,want", [
    (dict(remat_policy="full"), "", (True, "full")),
    (dict(remat_policy="dots"), "", (True, "dots")),
    (dict(remat=False), "", (False, "auto")),
    (dict(), "full", (True, "full")),
    (dict(), "dots", (True, "dots")),
    (dict(remat_policy="dots"), "full", (True, "full")),
], ids=["full", "dots", "remat-off", "override-full", "override-dots",
        "override-wins"])
def test_an_explicit_setting_is_obeyed_untouched(monkeypatch, model_kw,
                                                 override, want):
    model_cfg, decision = _resolve(monkeypatch, V5E_LIMIT, rows=20,
                                   model_kw=model_kw,
                                   cfg_kw=dict(remat_policy=override))
    assert decision is None
    assert (model_cfg.remat, model_cfg.remat_policy) == want


def test_override_auto_resolves_a_config_that_said_full(monkeypatch):
    model_cfg, decision = _resolve(monkeypatch, V5E_LIMIT, rows=20,
                                   model_kw=dict(remat_policy="full"),
                                   cfg_kw=dict(remat_policy="auto"))
    assert decision.rung == "none" and not model_cfg.remat


@pytest.mark.parametrize("policy", ["auto", "full", "dots", "sometimes"])
def test_an_override_needs_remat_and_a_known_name(policy):
    cfg = TrainConfig(lion=True, remat_policy=policy)
    with pytest.raises(ValueError, match="unknown remat_policy"
                       if policy == "sometimes" else "remat=False"):
        loop.apply_remat_policy(cfg, GPT2Config.tiny(remat=False), None, None)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_auto_reaching_a_model_function_raises(family):
    with pytest.raises(ValueError, match="the trainer's to resolve"):
        if family == "gpt2":
            gpt2._block_remat_for(GPT2Config.tiny(remat_policy="auto"))
        else:
            llama._block_remat_for(llama.LlamaConfig.tiny(remat_policy="auto"))


def test_tensor_parallel_divides_what_lives_column_parallel():
    cfg = GPT2Config.gpt2_124m()
    one = remat.block_saved_bytes(cfg, 20, 1024)
    two = remat.block_saved_bytes(cfg, 20, 1024, tp=2)
    u = 20 * 1024 * 768 * 2
    stat = 20 * 12 * 1024 * 4
    assert one == {"none": 10 * u + stat, "dots": 9 * u, "full": u}
    assert two == {"none": 6 * u + stat // 2, "dots": int(5.5 * u), "full": u}
    # materialized scores: float32 probabilities and their bf16 copy; the
    # library's kernel: two statistics a row and head, each over 128 lanes
    xla = remat.block_saved_bytes(cfg, 20, 1024, attn="xla")
    assert xla["none"] - one["none"] == 20 * 12 * 1024 * (1024 * 6 - 4)
    lib = remat.block_saved_bytes(cfg, 20, 1024, attn="library")
    assert lib["none"] - one["none"] == 20 * 12 * 1024 * (1024 - 4)
    assert xla["dots"] == lib["dots"] == one["dots"]


def test_llama_block_counts_gqa_and_both_mlp_projections():
    """Against the described v5e's compiler (PR 31, 2,048 wide, 16 heads,
    d_ff 5,632, 4 x 2,048 tokens, the library's kernel; a block's slope
    between two and three blocks): `none` 14.62 U and `dots` 9.85 U at 16
    kv heads where the count says 15.50 and 10.50; 13.68 and 8.72 at 4 kv
    heads where it says 14.00 and 9.00."""
    cfg = llama.LlamaConfig.named("llama2_7b")
    u = 4 * 1024 * 4096 * 2
    got = remat.block_saved_bytes(cfg, 4, 1024)
    mlp = 2 * 11008 / 4096
    assert got["none"] == int(u * (6 + mlp)) + 4 * 32 * 1024 * 4
    assert got["dots"] == int(u * (5 + mlp))      # 32 kv heads: no GQA
    gqa = dataclasses.replace(cfg, n_kv_head=8)
    assert remat.block_saved_bytes(gqa, 4, 1024)["dots"] == int(
        u * (2 + 1.5 + mlp))
    assert remat.block_saved_bytes(gqa, 4, 1024)["none"] == int(
        u * (2 + 2.5 + mlp)) + 4 * 32 * 1024 * 4


# --------------------------------------------------------- the trainer says it
def _remat_eqns(trainer):
    batch = jnp.zeros((trainer.global_train_batch(), trainer.cfg.block_size),
                      jnp.int32)
    jaxpr = jax.make_jaxpr(trainer._train_step_core)(
        trainer.params, trainer.state, trainer.vote_health,
        trainer._frozen_arg(), batch, jax.random.key(0))
    return [e for e in iter_eqns(jaxpr)
            if e.primitive.name in ("checkpoint", "remat", "remat2")]


@pytest.mark.parametrize("limit,rung", [(16 * GB, "none"), (None, "full")],
                         ids=["none", "full"])
def test_trainer_says_what_auto_resolved_to(monkeypatch, capsys, tmp_path,
                                            limit, rung):
    """`Trainer.for_gpt2` on the tiny preset: exactly one `remat_resolved`
    event and the `[setup] remat:` line, and the step's jaxpr holds a
    checkpoint equation a block exactly when it said `full`."""
    from distributed_lion_tpu.train import journal

    monkeypatch.setattr(loop, "device_bytes_limit", lambda: limit)
    cfg = TrainConfig(lion=True, async_grad=True, per_device_train_batch_size=2,
                      gradient_accumulation_steps=2, block_size=32,
                      journal=True, journal_dir=str(tmp_path))
    model_cfg = GPT2Config.tiny(remat_policy="auto")
    trainer = Trainer.for_gpt2(cfg, make_mesh(data=8), model_cfg)
    try:
        events = [r for r in trainer.journal.records()
                  if r.get("name") == "remat_resolved"]
        assert [e["rung"] for e in events] == [rung]
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("[setup] remat:")]
        assert len(lines) == 1 and lines[0].startswith(
            f"[setup] remat: {rung} (")
        if rung == "none":
            assert events[0]["bytes_limit"] == limit
            assert events[0]["predicted_none"] <= remat.MEMORY_SHARE * limit
            assert not _remat_eqns(trainer)
        else:
            assert "bytes_limit" in events[0]["unmodelled"]
            # forward's checkpoint eqn a block, twice traced (value + grad)
            assert len(_remat_eqns(trainer)) >= model_cfg.n_layer
    finally:
        trainer.close()
        journal.uninstall(trainer.journal)


def test_an_explicit_policy_is_not_a_resolution(capsys):
    trainer = Trainer.for_gpt2(
        TrainConfig(lion=True, async_grad=True, per_device_train_batch_size=2,
                    block_size=32),
        make_mesh(data=8), GPT2Config.tiny(remat_policy="dots"))
    trainer.close()
    out = capsys.readouterr().out
    assert "[setup] remat:" not in out
    assert _remat_eqns(trainer)
    # the other set-up line a Lion trainer prints: the wire auto resolved
    # to and which of ops/codec's two bit orders its bytes are in
    assert "[setup] vote: packed_a2a x1 buckets, planar codec" in out
    # and how the Lion kernels take this tree (ops/pallas_lion.leaf_layout;
    # GPT-2 124M's and a LoRA tree's lines: tests/test_pallas_lion.py): at
    # d_model 64 only the two [64, 256] fc weights have whole 128-lane rows
    assert ("[setup] lion: 2 leaves in place (26.3% of coordinates), 26 "
            "through the flat path, 6 kernel calls a step") in out
