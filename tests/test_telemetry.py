"""Vote-health telemetry (ISSUE 2): the observability contract.

What these pin:

- telemetry is OBSERVATIONAL — elections/params/momentum are bit-identical
  to telemetry-off on the XLA path, for every wire cadence in the PR-1
  matrix (vote_buckets {1,4} x vote_every {1,4} x det/stoch), and the
  VoteHealth accumulator itself is bit-identical across vote_buckets
  (bucketing changes when bytes move, never what telemetry sees);
- the Pallas stats kernel (ops/pallas_lion.bucket_vote_stats) bins margins
  exactly like the jnp reference and produces bitwise-equal accumulators;
- measured wire counters (parallel/collectives.WIRE_TALLY, captured from
  the live operand shapes at trace time) equal ops/codec's analytic
  bytes-received accounting EXACTLY — drift == 0 in-process — for every
  wire x vote_every x vote_buckets, including hier's DCN leg;
- the anomaly layer: an injected NaN trips the sentinel, writes a crash
  bundle naming the poisoned leaf, and (with --trace_on_anomaly) captures
  a trace window before raising;
- MetricsLogger emits STRICT JSON for non-finite floats (null + "<k>_repr")
  and scripts/validate_metrics.py is the CI check for that contract.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _sharded import (
    assert_trees_equal,
    leafy_problem,
    lion_state_specs,
    run_sharded,
    sharded,
    sharded_opt_step,
    toy_problem,
)

from distributed_lion_tpu.ops.codec import wire_bytes_per_param
from distributed_lion_tpu.optim import (
    distributed_lion,
    expand_worker_state,
    init_global_state,
    squeeze_worker_state,
)
from distributed_lion_tpu.parallel import collectives
from distributed_lion_tpu.train import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 43  # ragged on purpose: with vote_every=4 the last rotation slot is
# pure alignment padding (zero real coordinates) — the voted_steps
# normalization must keep hist mass at exactly 1.0 through it


def _run(mesh, telemetry_on, wire="sign_psum", buckets=1, ve=1, stoch=False,
         kern="xla", steps=5, problem=toy_problem, row_block=0):
    """Drive opt.step under shard_map with the trainer's fold wiring."""
    params, grads = problem()
    N = sum(p.size for p in jax.tree.leaves(params))
    opt = distributed_lion(
        0.01, weight_decay=0.01, wire=wire, vote_buckets=buckets,
        vote_every=ve, max_grad_norm=1.0 if stoch else None, kernel=kern,
        row_block=row_block, telemetry=telemetry_on)
    rng = jax.random.key(7) if stoch else None
    state = init_global_state(opt, params, 8, rng=rng)
    vh = telemetry.init_vote_health(N, ve) if telemetry_on else {}
    st_spec = lion_state_specs(state)
    vh_spec = jax.tree.map(lambda _: P(), vh)

    def body(p, g, st, v):
        st = squeeze_worker_state(st)
        g = jax.tree.map(lambda x: x[0], g)
        if telemetry_on:
            p2, st2, frame = opt.step(p, g, st)
            v = telemetry.fold(v, frame, "data", 8, N)
        else:
            p2, st2 = opt.step(p, g, st)
        return p2, expand_worker_state(st2), v

    step = sharded(body, mesh, (P(), P("data"), st_spec, vh_spec),
                   (P(), st_spec, vh_spec), check_vma=False)
    p, st, v = params, state, vh
    for _ in range(steps):
        p, st, v = step(p, grads, st, v)
    return p, st, v


# ----------------------------------------------------- observational contract
@pytest.mark.parametrize("stoch", [False, True],
                         ids=["deterministic", "stochastic"])
@pytest.mark.parametrize("ve", [1, 4])
def test_vote_health_bucket_invariant_and_elections_unperturbed(
        mesh8, ve, stoch):
    """The satellite matrix: across vote_buckets {1,4} the accumulator is
    BIT-identical (same elections, same tallies, just pipelined wires), and
    params/momentum with telemetry on equal the telemetry-off run exactly —
    telemetry must not perturb the PR-1-pinned elections."""
    p_off, st_off, _ = _run(mesh8, False, ve=ve, stoch=stoch)
    runs = {b: _run(mesh8, True, ve=ve, stoch=stoch, buckets=b)
            for b in (1, 4)}
    assert_trees_equal(runs[1][2], runs[4][2])  # vh bitwise across B
    assert_trees_equal(p_off, runs[1][0])  # params untouched
    assert_trees_equal(st_off.exp_avg, runs[1][1].exp_avg)  # momentum too
    d = telemetry.drain(runs[1][2], margin_exact=True)
    # sign_psum moves the exact tally: every voted coordinate lands in a
    # margin bin, so mass == 1 even through the zero-coordinate lazy slot
    assert abs(d["hist_mass"] - 1.0) < 1e-4
    assert d["voted_per_step"] > 0
    assert 0.0 <= d["disagree_frac"] <= 1.0
    if stoch:
        assert 0.0 < d["stoch_flip_frac"] < 1.0
    else:
        assert d["stoch_flip_frac"] == 0.0
    if ve > 1:
        assert d["valid_frac"] < 1.0  # cold-start sparsity is visible
    else:
        assert d["valid_frac"] == 1.0


def test_lazy_cold_start_counts_no_flips(mesh8):
    """Under vote_every=K, slots 1..K-1 first vote against the cache's
    zero-init bytes; counting those as flips would fake a ~0.5 flip rate
    for a perfectly stable election. The frame's flip_valid gate must keep
    the first full rotation out of the flip statistics entirely."""
    _, _, vh4 = _run(mesh8, True, ve=4, steps=4)  # counts 0..3: all cold
    d = telemetry.drain(vh4, margin_exact=True)
    assert d["flip_rate"] == 0.0
    assert int(np.asarray(vh4.flip_steps)) == 0
    _, _, vh6 = _run(mesh8, True, ve=4, steps=6)  # counts 4, 5 are warm
    assert int(np.asarray(vh6.flip_steps)) == 2


def test_proxy_wire_hist_zeroed_not_faked(mesh8):
    """packed_a2a ships a ±1 verdict proxy — magnitude never crosses the
    wire, so the margin histogram must be zeroed (margin_exact=0), not
    populated with fake unanimous margins; disagreement (which needs only
    the election) still reports."""
    p_off, _, _ = _run(mesh8, False, wire="packed_a2a")
    p_on, _, vh = _run(mesh8, True, wire="packed_a2a")
    assert_trees_equal(p_off, p_on)
    d = telemetry.drain(vh, margin_exact=False)
    assert d["hist_mass"] == 0.0 and d["margin_exact"] == 0
    assert 0.0 < d["disagree_frac"] < 1.0


def test_pallas_telemetry_matches_xla_and_bucket_invariant(mesh8):
    """The Pallas window path: one step from identical state produces a
    BITWISE-equal accumulator to the XLA path (same ballots, same totals,
    same binning), and the accumulator stays bucket-invariant over multiple
    steps. Params are compared to telemetry-off within a few f32 ulps only:
    in interpret mode the fused-apply kernel inlines into the surrounding
    XLA graph, and telemetry's extra consumers of ballots/totals can shift
    fma fusion by 1-2 ulps (elections — the integer totals — are exact; on
    hardware the kernel is opaque and the wobble disappears)."""
    _, _, v_x = _run(mesh8, True, kern="xla", buckets=1, steps=1)
    _, _, v_p = _run(mesh8, True, kern="pallas", buckets=3, steps=1)
    assert_trees_equal(v_x, v_p)
    r1 = _run(mesh8, True, kern="pallas", buckets=1)
    r3 = _run(mesh8, True, kern="pallas", buckets=3)
    assert_trees_equal(r1[2], r3[2])
    p_off, _, _ = _run(mesh8, False, kern="pallas", buckets=3)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), p_off, r3[0])


@pytest.mark.parametrize("wire", ["sign_psum", "packed_a2a"])
def test_leaf_shaped_telemetry_matches_xla(mesh8, wire):
    """Leaves taken where they lie vote in an order of the step's own; what
    the host reads does not show it: the accumulator (the packed ``elected``
    against the step before, the margins, the disagreements) is BITWISE the
    XLA path's after two steps, with a leaf split across buckets."""
    runs = [_run(mesh8, True, wire=wire, kern=kern, buckets=buckets, steps=2,
                 problem=leafy_problem, row_block=32)
            for kern, buckets in (("xla", 1), ("pallas", 4))]
    assert_trees_equal(runs[0][2], runs[1][2])
    assert_trees_equal(runs[0][0], runs[1][0])
    assert_trees_equal(runs[0][1].exp_avg, runs[1][1].exp_avg)


def test_bucket_vote_stats_kernel_matches_reference():
    """The Pallas stats kernel must bin margins exactly like
    telemetry.margin_hist and count disagreements exactly — at ragged sizes
    spanning multiple grid blocks."""
    from distributed_lion_tpu.ops.pallas_lion import bucket_vote_stats

    rng = np.random.default_rng(3)
    for n in (5, 128, 1003, 70_000):
        ballots = jnp.asarray(
            rng.choice([-1, 1], size=(n,)).astype(np.int8))
        totals = jnp.asarray(rng.integers(-8, 9, size=(n,)).astype(np.int32))
        hist, dis = bucket_vote_stats(ballots, totals, 8, telemetry.NBINS,
                                      interpret=True)
        ref_hist = telemetry.margin_hist(totals, 8)
        np.testing.assert_array_equal(np.asarray(hist), np.asarray(ref_hist))
        ref_dis = int(np.sum((np.asarray(ballots) > 0)
                             != (np.asarray(totals) > 0)))
        assert int(dis) == ref_dis
        assert int(np.asarray(hist).sum()) == n


# ------------------------------------------------------- measured wire ledger
@pytest.mark.parametrize("wire", ["sign_psum", "packed_allgather",
                                  "packed_a2a", "hier:4"])
@pytest.mark.parametrize("ve,buckets", [(1, 1), (1, 4), (4, 1), (4, 3)])
def test_measured_wire_equals_analytic_exactly(mesh8, wire, ve, buckets):
    """The drift==0 satellite: the trace-time wire ledger (live operand
    shapes at the collective call sites) equals ops/codec's analytic
    bytes-received accounting EXACTLY — per optimizer step, through lazy
    slicing and bucket splits, including hier's DCN leg. Abstract eval
    only: no compile, no execution."""
    params, grads = toy_problem()
    opt = distributed_lion(0.01, wire=wire, vote_every=ve,
                           vote_buckets=buckets)
    state = init_global_state(opt, params, 8)
    measured = telemetry.measure_step_wire(
        sharded_opt_step(opt, mesh8, state), params, grads, state)
    acct = wire_bytes_per_param(N, 8, wire, vote_every=ve,
                                vote_buckets=buckets)
    assert measured["bytes_per_step"] == acct["bytes_per_step"], (
        measured, acct)
    assert measured["dcn_bytes_per_step"] == acct.get(
        "dcn_bytes_per_step", 0)
    assert measured["calls_per_step"] >= 1


def test_wire_tally_inert_outside_capture(mesh8):
    """Recording outside a capture is a no-op sink — running a vote must
    not leak entries or fail."""
    ballots = jnp.ones((64,), jnp.bool_)

    def f(b):
        return collectives.majority_vote_bucketed(b[0], "data",
                                                  "sign_psum", 2)

    out = run_sharded(f, mesh8, (P("data"),), P(), jnp.tile(ballots, (8, 1)),
                      check_vma=False)
    assert np.asarray(out).all()
    with collectives.WIRE_TALLY.capture() as entries:
        jax.eval_shape(  # a jit of its own: the one above is traced already
            sharded(f, mesh8, (P("data"),), P(), check_vma=False),
            jnp.tile(ballots, (8, 1)))
    assert len(entries) == 2  # one record per bucket collective


# --------------------------------------------------------- trainer end-to-end
def _tiny_trainer_cfg(**kw):
    from distributed_lion_tpu.train.loop import TrainConfig

    base = dict(lion=True, async_grad=True, wire="sign_psum", vote_every=1,
                vote_buckets=2, learning_rate=1e-3, warmup_steps=1,
                max_steps=4, per_device_train_batch_size=1,
                gradient_accumulation_steps=1, block_size=32,
                logging_steps=2, output_dir=None,
                resume_from_checkpoint=False)
    base.update(kw)
    return TrainConfig(**base)


def test_trainer_telemetry_end_to_end(mesh8, tmp_path):
    """The acceptance criterion, at the trainer: telemetry-on logs the
    vote-health block and the measured-wire cross-check (drift == 0), the
    loss trajectory is IDENTICAL to telemetry-off (elections unperturbed
    end-to-end), and the JSONL it writes is strict-valid."""
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config

    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    losses = {}
    for tel in (True, False):
        cfg = _tiny_trainer_cfg(
            telemetry=tel, output_dir=str(tmp_path / f"t{tel}"))
        tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
        blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                      model_cfg.vocab_size, seed=4)
        hist = tr.train(batch_iterator(blocks, tr.global_train_batch(),
                                       seed=0), max_steps=4)
        losses[tel] = [h["loss"] for h in hist if "loss" in h]
        if tel:
            rows = [h for h in hist if "vote/hist_mass" in h]
            assert rows, "telemetry produced no vote-health rows"
            r = rows[-1]
            assert abs(r["vote/hist_mass"] - 1.0) < 1e-4
            assert r["vote/margin_exact"] == 1
            assert len(r["vote/margin_hist"]) == telemetry.NBINS
            assert r["comm_drift_bytes"] == 0
            assert (r["comm_measured_bytes_per_step"]
                    == r["comm_bytes_per_step"])
            # one collective per bucket on this cfg (vote_buckets=2)
            assert r["comm_measured_calls_per_step"] == 2
            assert tr.telemetry_summary() is not None
            jsonl = tmp_path / "tTrue" / "metrics.jsonl"
            rc = subprocess.run(
                [sys.executable, os.path.join(REPO, "scripts",
                                              "validate_metrics.py"),
                 str(jsonl)], capture_output=True, text=True)
            assert rc.returncode == 0, rc.stdout + rc.stderr
        else:
            assert tr.telemetry_summary() is None
        tr.close()
    assert losses[True] == losses[False]


def test_trainer_telemetry_guards(mesh8):
    from distributed_lion_tpu.train.loop import make_optimizer

    with pytest.raises(ValueError, match="telemetry"):
        make_optimizer(_tiny_trainer_cfg(lion=False, async_grad=False,
                                         telemetry=True))
    with pytest.raises(ValueError, match="vote axis|election"):
        distributed_lion(axis_name=None, telemetry=True)


def test_nan_sentinel_writes_crash_bundle_naming_leaf(mesh8, tmp_path):
    """The injected-NaN acceptance test: poisoning one param leaf trips the
    sentinel, raises FloatingPointError, and the crash bundle names exactly
    the poisoned leaf with strict-JSON contents."""
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    cfg = _tiny_trainer_cfg(vote_buckets=1, max_steps=3, logging_steps=1,
                            nan_sentinel=True, output_dir=str(tmp_path),
                            save_steps=10**6)
    tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    tr.params["wte"] = tr.params["wte"].at[0, 0].set(float("nan"))
    blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                  model_cfg.vocab_size, seed=4)
    with pytest.raises(FloatingPointError, match="non-finite"):
        tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                 max_steps=3)
    crash_root = tmp_path / "crash"
    bundles = sorted(crash_root.iterdir())
    assert len(bundles) == 1
    with open(bundles[0] / "bundle.json") as f:
        bundle = json.load(f)  # strict JSON or this raises
    assert any("wte" in k for k in bundle["nonfinite_params"]), bundle
    assert bundle["reason"].startswith("non-finite")
    assert bundle["config"]["nan_sentinel"] is True
    assert bundle["metrics_window"], "recent metrics window missing"
    tr.close()


def test_trace_on_anomaly_captures_window_then_raises(mesh8, tmp_path):
    """--trace_on_anomaly: the sentinel arms a profiler window at the trip
    instead of raising immediately; the trace lands inside the crash bundle
    and the run still ends in FloatingPointError."""
    import glob

    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    cfg = _tiny_trainer_cfg(vote_buckets=1, max_steps=8, logging_steps=1,
                            nan_sentinel=True, trace_on_anomaly=True,
                            output_dir=str(tmp_path), save_steps=10**6)
    tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    tr.params["wte"] = tr.params["wte"].at[0, 0].set(float("nan"))
    blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                  model_cfg.vocab_size, seed=4)
    with pytest.raises(FloatingPointError):
        tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                 max_steps=8)
    traces = glob.glob(str(tmp_path / "crash" / "*" / "trace" / "**" / "*"),
                       recursive=True)
    assert any(os.path.isfile(f) for f in traces), "no anomaly trace files"
    tr.close()


def test_trace_on_anomaly_mid_profile_window(mesh8, tmp_path):
    """A --profile_dir window can be mid-capture when the sentinel trips:
    the anomaly handler must flush the open jax profiler session before
    arming its own window, or start_trace raises RuntimeError and neither
    trace survives."""
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train.loop import Trainer

    model_cfg = GPT2Config.tiny()
    cfg = _tiny_trainer_cfg(vote_buckets=1, max_steps=10, logging_steps=1,
                            nan_sentinel=True, trace_on_anomaly=True,
                            output_dir=str(tmp_path / "out"),
                            profile_dir=str(tmp_path / "prof"),
                            profile_start_step=0, profile_num_steps=50,
                            save_steps=10**6)
    tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    tr.params["wte"] = tr.params["wte"].at[0, 0].set(float("nan"))
    blocks = synthetic_lm_dataset(max(32, tr.global_train_batch()), 32,
                                  model_cfg.vocab_size, seed=4)
    with pytest.raises(FloatingPointError):  # NOT RuntimeError
        tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                 max_steps=10)
    tr.close()


def test_host_step_skew_single_process():
    assert telemetry.host_step_skew(123) is None


# ----------------------------------------------------- strict-JSON satellites
def test_metrics_logger_nonfinite_is_strict_json(tmp_path):
    from distributed_lion_tpu.train.metrics import MetricsLogger

    logger = MetricsLogger(str(tmp_path))
    logger.log(1, {"loss": float("nan"), "aux": float("inf"),
                   "hist": [1.0, float("-inf")], "ok": 2.0})
    logger.close()
    lines = (tmp_path / "metrics.jsonl").read_text().strip().splitlines()
    rec = json.loads(lines[-1], parse_constant=lambda s: pytest.fail(
        f"bare {s} token in output"))
    assert rec["train/loss"] is None and rec["train/loss_repr"] == "nan"
    assert rec["train/aux"] is None and rec["train/aux_repr"] == "inf"
    assert rec["train/hist"] == [1.0, None]
    assert rec["train/ok"] == 2.0


def test_validate_metrics_rejects_bare_nan(tmp_path):
    good = tmp_path / "good.jsonl"
    good.write_text('{"step": 1, "loss": null, "loss_repr": "nan"}\n')
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"step": 1, "loss": NaN}\n{"step": 2, "loss": 1.0}\n')
    script = os.path.join(REPO, "scripts", "validate_metrics.py")
    ok = subprocess.run([sys.executable, script, str(good)],
                       capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    fail = subprocess.run([sys.executable, script, str(bad)],
                          capture_output=True, text=True)
    assert fail.returncode == 1
    assert "NaN" in fail.stdout or "constant" in fail.stdout
