"""``models/mellum`` under ``Trainer`` on the CPU at the family's tiny size,
against the plain reference (``benchmark/reference/mellum``), and the pieces
this family brought: the grouped product's gradient, the ``flash_gqa`` kernel
pair (interpret mode) with its window held to the position, YaRN's table, the
share test over four held ranges, and ``run_clm --model_family mellum``.
One jitted program a comparison (``tests/_sharded.py``'s rule)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.lib import harness
from distributed_lion_tpu.models.laguna import Rope
from distributed_lion_tpu.models.mellum import (
    MELLUM_COUNTERS,
    MellumConfig,
    mellum_apply,
)
from distributed_lion_tpu.ops import pallas_flash_attn, pallas_moe_gmm
from distributed_lion_tpu.parallel.expert import (
    grouped_matmul,
    moe_dropless_ffn,
)
from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train.loop import TrainConfig, Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 43


@pytest.fixture(scope="module")
def family():
    return harness.load_module("families", "mellum")


def _rows(step, rows, block, vocab):
    return np.random.default_rng([SEED, step]).integers(
        0, vocab, (rows, block), dtype=np.int32)


# ------------------------------------------------ trainer against reference
@pytest.mark.parametrize("dtype,loss_tol,grad_tol,block", [
    ("float32", 2e-6, 2e-4, 32), ("bfloat16", 2e-3, 0.25, 32),
    ("float32", 2e-6, 2e-4, 512)])
def test_trainer_follows_the_reference_for_two_steps(family, dtype, loss_tol,
                                                     grad_tol, block):
    """Loss and every leaf's gradient of the first two optimizer steps
    (2 x 2 rows of 32 tokens, Lion at W = 1), the gradient read from the
    momentum as the benchmark's driver reads it. float32 compute: tight;
    bfloat16 compute: within a stated band of the float32 reference. Rows
    of 512 tokens make a microbatch one chunk of picks (2 x 512 x 2), so the
    combine's transpose is the one bounded by the rows in groups."""
    cfg, ref = family.TINY, family.reference
    b2, lr, wd = 0.99, 1e-3, 0.1
    tcfg = TrainConfig(lion=True, async_grad=True, learning_rate=lr,
                       weight_decay=wd, warmup_steps=0, max_steps=50,
                       per_device_train_batch_size=2,
                       gradient_accumulation_steps=2, block_size=block,
                       logging_steps=1000, eval_steps=1000, save_steps=1000,
                       seed=0)
    mesh = make_mesh(data=1, devices=jax.devices()[:1])
    model_cfg = MellumConfig.from_hf(cfg, compute_dtype=jnp.dtype(dtype),
                                     remat_policy="full")
    w = jax.jit(lambda key: ref.init_weights(key, cfg, jnp.float32))(
        ref.seed_key(SEED))
    # a copy: the step donates its parameters
    trainer = Trainer.for_mellum(tcfg, mesh, model_cfg, initial_params=jax.jit(
        lambda w: jax.tree.map(jnp.copy, family.to_program(w)))(w))
    grad_fn = jax.jit(lambda w, r: ref.loss_and_grad(w, r, cfg, 2))
    step_fn = jax.jit(lambda w, m, g, lr: ref.vote_lion_step(
        w, m, g, lr, wd, 0.9, b2))
    momenta = [jax.tree.map(jnp.zeros_like, w)]
    first_grad = jax.jit(lambda m, p: jax.tree.map(
        lambda m, p: (m - b2 * p)[0] / (1 - b2), m, p))
    keep = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    m_prev = jax.tree.map(jnp.zeros_like, trainer.state.exp_avg)
    key = jax.random.key(1)
    for s in range(2):
        rows = _rows(s, 4, block, cfg["vocab_size"])
        trainer.params, trainer.state, _, metrics = trainer._train_step(
            trainer.params, trainer.state, trainer.vote_health,
            trainer._frozen_arg(), jnp.asarray(rows), key)
        got = first_grad(trainer.state.exp_avg, m_prev)
        m_prev = keep(trainer.state.exp_avg)   # the step donates its state
        loss, g = grad_fn(w, jnp.asarray(rows))
        assert abs(float(metrics["loss"]) - float(loss)) \
            <= loss_tol * float(loss), (s, metrics["loss"], loss)
        want = family.program_leaves(family.to_program(g))
        norms = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
        median = float(np.median(list(norms.values())))
        for k, mine in family.program_leaves(got).items():
            gap = float(jnp.linalg.norm(mine - want[k])) \
                / max(norms[k], median)
            assert gap <= grad_tol, (s, k, gap)
        if s == 0:
            routed = 4 * block * 4 * 2
            assert float(metrics["moe_routed"]) == routed
            assert 0 < float(metrics["moe_assignments"]) < routed
            # 128 picks a call: under one chunk, not bounded; 2,048: the one
            # chunk that holds the rows in groups, which is all of them
            assert float(metrics["moe_rows_moved"]) == routed
        w, momenta = step_fn(w, momenta, [g], ref.cosine_warmup_lr(
            s, lr, 0, 50))
    trainer.close()


def test_forward_matches_the_reference_and_counts_its_picks(family):
    cfg, ref = family.TINY, family.reference
    model_cfg = MellumConfig.from_hf(cfg, compute_dtype=jnp.float32)
    w = jax.jit(lambda key: ref.init_weights(key, cfg, jnp.float32))(
        ref.seed_key(7))
    tokens = jnp.asarray(_rows(0, 2, 48, cfg["vocab_size"]))
    mine = jax.jit(lambda p, t: mellum_apply(p, t, model_cfg))(
        family.to_program(w), tokens)
    want = jax.jit(lambda w, t: ref.forward(w, t, cfg))(w, tokens)
    np.testing.assert_allclose(mine, want, atol=2e-5)
    assert model_cfg.held == (0, 4) and model_cfg.n_experts == 8
    assert set(MELLUM_COUNTERS) == {"moe_assignments", "moe_experts_hit",
                                    "moe_load_max", "moe_routed",
                                    "moe_rows_moved"}


# ------------------------------------------------- the grouped product's vjp
@pytest.mark.parametrize("sizes,rows", [
    ([100, 0, 1, 300, 150], 700),       # an empty group, a group of one row
    ([512, 512, 0, 0, 76], 1100),       # whole tiles, then empties
    ([0, 0, 0, 0, 3], 64),              # nearly all tail
])
def test_grouped_matmul_gradient_is_ragged_dots(sizes, rows):
    """``custom_vjp`` against the autodiff of ``lax.ragged_dot``, on the
    path the CPU takes and through the two kernels in interpret mode, over
    a held range with a tail (rows past the last group)."""
    E, K, N = len(sizes), 128, 256
    ks = jax.random.split(jax.random.key(0), 3)
    lhs = jax.random.normal(ks[0], (rows, K))
    dy = jax.random.normal(ks[1], (rows, N))
    rhs = jax.random.normal(ks[2], (E, K, N))
    gs = jnp.array(sizes, jnp.int32)
    live = (jnp.arange(rows) < sum(sizes))[:, None]

    def through(product):
        return jax.jit(jax.grad(
            lambda lhs, rhs: jnp.where(live, product(lhs, rhs) * dy, 0).sum(),
            (0, 1)))(lhs, rhs)

    want = through(lambda a, b: lax.ragged_dot(a, b, gs))
    mine = through(lambda a, b: grouped_matmul(a, b, gs, True))
    for a, b in zip(mine, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    dlhs = pallas_moe_gmm.moe_gmm(jnp.where(live, dy, 0),
                                  jnp.swapaxes(rhs, 1, 2), gs, tail=True,
                                  interpret=True)
    drhs = pallas_moe_gmm.moe_gmm_drhs(lhs, dy, gs, interpret=True)
    np.testing.assert_allclose(jnp.where(live, dlhs, 0), want[0], atol=1e-3)
    np.testing.assert_allclose(drhs, want[1], atol=1e-3)


# ------------------------------------------------------------- flash_gqa
def _banded(q, k, v, H, window):
    B, T, _ = q.shape
    KV = k.shape[2] // 128
    qh = q.reshape(B, T, KV, H // KV, 128)
    kh, vh = k.reshape(B, T, KV, 128), v.reshape(B, T, KV, 128)
    s = jnp.einsum("bsgrd,btgd->bgrst", qh, kh,
                   precision="highest") / math.sqrt(128)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bgrst,btgd->bsgrd", p, vh,
                      precision="highest").reshape(B, T, H * 128)


@pytest.mark.parametrize("T,window", [
    (640, 256),    # blocks of 128: tiles wholly outside, inside, across
    (640, 0),      # a full layer
    (384, 100),    # a window inside one block
])
def test_flash_gqa_pair_matches_the_plain_banded_softmax(T, window):
    """Forward and the three gradients in interpret mode (float32), 4
    query heads over 2 kv heads of 128, against the masked softmax."""
    B, H, KV = 1, 4, 2
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (B, T, H * 128))
    k = jax.random.normal(ks[1], (B, T, KV * 128))
    v = jax.random.normal(ks[2], (B, T, KV * 128))
    w = jax.random.normal(ks[3], (B, T, H * 128))

    def both(attend):
        return jax.jit(lambda q, k, v: jax.value_and_grad(
            lambda q, k, v: (attend(q, k, v) * w).sum(), (0, 1, 2))(q, k, v))

    mine = both(lambda q, k, v: pallas_flash_attn.flash_gqa(
        q, k, v, H, window, True))(q, k, v)
    want = both(lambda q, k, v: _banded(q, k, v, H, window))(q, k, v)
    assert abs(mine[0] - want[0]) <= 1e-3 * abs(want[0])
    for a, b in zip(mine[1], want[1]):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(jnp.abs(b).max())


def test_the_windows_edge_is_held_to_the_position():
    """One loud key at ``j0`` (every other key and value zero): the query at
    ``j0 + window - 1`` (``i - window + 1 = j0``) still reads it, the query
    at ``j0 + window`` (``i - window = j0``) does not; and the key's
    gradient comes from the first and from nothing past the band."""
    T, H, window, j0 = 640, 2, 256, 130
    last = j0 + window - 1
    e = jnp.zeros((128,)).at[0].set(6.0)
    q = jnp.tile(e, (1, T, H))
    k = jnp.zeros((1, T, 128)).at[0, j0].set(e)
    v = jnp.zeros((1, T, 128)).at[0, j0].set(1.0)

    def run(k):
        def attend(k):
            return pallas_flash_attn.flash_gqa(q, k, v, H, window, True)

        return (attend(k), jax.grad(lambda k: attend(k)[0, last].sum())(k),
                jax.grad(lambda k: attend(k)[0, last + 1:].sum())(k))

    out, dk_last, dk_past = jax.jit(run)(k)
    seen = np.asarray(out[0, :, 0])
    assert seen[j0 - 1] == 0 and seen[j0] > 0.05
    assert seen[last] > 0.05 and seen[last + 1] == 0
    assert float(jnp.abs(dk_last[0, j0]).max()) > 1e-3
    assert float(jnp.abs(dk_past[0, j0]).max()) == 0


def test_flash_gqa_says_which_shapes_it_takes():
    takes = pallas_flash_attn.gqa_train_kernel_takes
    assert takes(8192, 128, jnp.bfloat16) and takes(640, 128, jnp.float32)
    assert not takes(8192, 64, jnp.bfloat16)      # heads of 128 only
    assert not takes(100, 128, jnp.bfloat16)      # whole blocks of rows
    assert not takes(16384, 128, jnp.bfloat16)    # a lane block's T in VMEM


# ------------------------------------------------------------------- YaRN
def test_yarn_table_is_the_references_at_factor_16(family):
    spec = family.PUBLISHED["rope_parameters"]["full_attention"]
    rope = Rope.from_hf(spec, 128)
    assert rope == MellumConfig().rope_full
    assert rope.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    ref = family.reference
    np.testing.assert_allclose(rope.inv_freq(), ref.inv_freq(spec, 128),
                               rtol=1e-6)
    # the fast dims keep their frequency, the slow ones turn 16 times slower
    plain = Rope.from_hf(
        family.PUBLISHED["rope_parameters"]["sliding_attention"], 128)
    ratio = plain.inv_freq() / rope.inv_freq()
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(16.0)
    pos = jnp.array([0, 1, 1023, 8191])
    cos, sin = jax.jit(rope.angles)(pos)
    want_cos, want_sin = ref.rope_table(spec, 128, 8192)
    np.testing.assert_allclose(cos, want_cos[pos], atol=2e-3)
    np.testing.assert_allclose(sin, want_sin[pos], atol=2e-3)


# -------------------------------------------------------------- the share
def test_the_four_shares_sum_to_the_whole_layer():
    """Experts 0-1, 2-3, 4-5, 6-7 of a router of 8 (the cell's 0-15 ..
    48-63 of 64): no shared expert, so nothing is counted once, and the
    four shares' outputs and assignments add up to the uncut layer's."""
    E, D, F, N, k = 8, 64, 32, 96, 3
    ks = jax.random.split(jax.random.key(3), 5)
    whole = {"router": jax.random.normal(ks[0], (E, D)),
             "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.1,
             "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.1,
             "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.1}
    x = jax.random.normal(ks[4], (N, D))

    @jax.jit
    def run(whole, x):
        full = moe_dropless_ffn(whole, x, top_k=k, scale=1.0,
                                return_counters=True)
        shares = []
        for first in range(0, E, 2):
            part = dict(whole, **{n: whole[n][first:first + 2]
                                  for n in ("w_gate", "w_up", "w_down")})
            shares.append(moe_dropless_ffn(part, x, top_k=k, scale=1.0,
                                           held=(first, 2),
                                           return_counters=True))
        return full, shares

    (y, counters), shares = run(whole, x)
    np.testing.assert_allclose(sum(s[0] for s in shares), y, atol=1e-5)
    assert sum(int(s[1]["moe_assignments"]) for s in shares) \
        == int(counters["moe_assignments"]) == N * k
    assert all(int(s[1]["moe_routed"]) == N * k for s in shares)


# ------------------------------------------------------------ configuration
def test_config_from_the_published_keys(family):
    path = os.path.join(ROOT, "benchmark", "configs",
                        "mellum2-12b-a2.5b.json")
    cfg = MellumConfig.from_file(path)
    assert (cfg.n_layer, cfg.n_experts, cfg.top_k) == (4, 64, 8)
    assert cfg.held == (0, 16) and cfg.banks == 16
    assert cfg.windowed == (True, True, True, False) and cfg.window == 1024
    assert (cfg.vocab_size, cfg.d_model, cfg.moe_d_ff) == (24576, 2304, 896)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (32, 4, 128)
    assert cfg.rope_full == MellumConfig().rope_full
    assert cfg.rope_window == Rope(500000.0, 128)
    with open(path) as f:
        body = json.load(f)
    assert family.parameters(body) == 595_154_176
    with pytest.raises(ValueError, match="not implemented"):
        MellumConfig.from_hf(dict(body, norm_topk_prob=False))
    with pytest.raises(ValueError, match="only sparse"):
        MellumConfig.from_hf(dict(body, mlp_layer_types=["dense"] * 28))


def test_run_clm_names_the_family_with_a_file(family, tmp_path, capsys):
    from distributed_lion_tpu.cli import run_clm

    flags = family.train_flags(family.TINY)
    assert flags["model_family"] == "mellum"
    with open(flags["model_name"]) as f:
        assert json.load(f) == json.loads(json.dumps(family.TINY))
    run_clm.main(["--model_family", "mellum", "--model_name",
                  flags["model_name"], "--lion", "--async_grad",
                  "--dataset", "synthetic", "--synthetic_blocks", "64",
                  "--per_device_train_batch_size", "1",
                  "--gradient_accumulation_steps", "1", "--block_size", "32",
                  "--max_steps", "3", "--logging_first_step",
                  "--warmup_steps", "0"])
    out = capsys.readouterr().out
    # logged after the first step and the last: the interval of 50 never
    # closes in three steps
    assert [line.split()[0] for line in out.splitlines()
            if line.startswith("step=") and "train/loss" in line] \
        == ["step=1", "step=3"]
    assert "[trainer] Mellum" in out and "experts 0-3 of 8 held" in out
    assert "[setup] remat: full" in out and "train/moe_routed=" in out
    assert "train/moe_rows_moved=" in out
    with pytest.raises(ValueError, match="does not take"):
        run_clm.main(["--model_family", "mellum", "--model_name", "tiny",
                      "--moe_experts", "2"])
    with pytest.raises(NotImplementedError, match="data axis"):
        run_clm.main(["--model_family", "mellum", "--model_name", "tiny",
                      "--tensor_parallel", "2"])


def test_remat_counts_this_block(family):
    """The resolver counts the expert-and-window block from its own tensors
    (q 4,096 wide, the sorted rows a pick): at the cell's shapes on a v5e
    every rung is over the share and the answer is ``full``; a device three
    times the size keeps every residual; shapes the kernel pair does not
    take are not modelled."""
    from distributed_lion_tpu.train import remat

    path = os.path.join(ROOT, "benchmark", "configs",
                        "mellum2-12b-a2.5b.json")
    cfg = MellumConfig.from_file(path, remat_policy="auto")
    mesh = make_mesh(data=1, devices=jax.devices()[:1])
    tcfg = TrainConfig(lion=True, async_grad=True, block_size=8192,
                       per_device_train_batch_size=2,
                       gradient_accumulation_steps=2)
    params = jax.eval_shape(
        lambda: family.reference.init_weights(
            jax.random.key(0), json.load(open(path)), jnp.float32))
    saved = remat.expert_block_saved_bytes(cfg, 2, 8192)
    u = 2 * 8192 * 2304 * 2
    assert saved["full"] == u
    # input and second residual, q and the attention's output (4,096 wide),
    # k and v, eight picks' sorted rows, outputs, gate and up; statistics
    assert saved["none"] == int(u * (2 + 2 * 4096 / 2304 + 1024 / 2304
                                     + 8 * (2 + 2 * 896 / 2304))
                                ) + 4 * 2 * 32 * 8192 + 4 * 2 * 8192 * 64
    on_v5e = remat.resolve_for(tcfg, cfg, mesh, params,
                               bytes_limit=int(16.91e9))
    assert on_v5e.rung == "full" and not on_v5e.unmodelled
    assert on_v5e.predicted["none"] > on_v5e.predicted["dots"] \
        > on_v5e.predicted["full"] > 0.72 * 16.91e9
    assert remat.resolve_for(tcfg, cfg, mesh, params,
                             bytes_limit=int(48e9)).rung == "none"
    odd = remat.resolve_for(
        dataclasses.replace(tcfg, block_size=8192 + 64), cfg, mesh, params,
        bytes_limit=int(48e9))
    assert odd.rung == "full" and "kernel pair" in odd.unmodelled
