"""graft-check tier 2 (analysis/trace_check.py) + the runtime retrace
guard (train/loop --retrace_guard).

The contract pinned here is the static counterpart of PR 2's
``comm_drift_bytes == 0``: the collective-primitive inventory of the
ACTUAL compiled train step — call sites, axis names, operand element
counts — exactly matches the wire recipe's expected set for all 4 wires ×
vote_buckets {1, 4} (and a lazy vote_every=4 cell), the step carries zero
host callbacks, donation survives lowering, and bf16 param leaves are
never upcast to f32. Plus: the retrace guard catches an injected
recompile, and elections stay bit-identical with the analysis features
enabled."""

import numpy as np
import pytest
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_lion_tpu.analysis import trace_check
from distributed_lion_tpu.models.gpt2 import GPT2Config
from distributed_lion_tpu.parallel import collectives
from distributed_lion_tpu.parallel.mesh import DATA_AXIS
from distributed_lion_tpu.train.loop import TrainConfig, Trainer

MODEL = GPT2Config.tiny(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                        n_ctx=64)


def _trainer(mesh, **kw):
    cfg = TrainConfig(
        lion=True, async_grad=True, wire=kw.pop("wire", "sign_psum"),
        vote_every=kw.pop("vote_every", 1),
        vote_buckets=kw.pop("vote_buckets", 1),
        per_device_train_batch_size=1, gradient_accumulation_steps=1,
        block_size=32, logging_steps=1, warmup_steps=1, max_steps=100,
        learning_rate=1e-3, output_dir=None, **kw)
    return Trainer.for_gpt2(cfg, mesh, MODEL)


def _batch(tr, block=32, fill=0):
    return np.full((tr.global_train_batch(), block), fill, np.int32)


# ------------------------------------------------- the wire-recipe contract
@pytest.mark.parametrize("wire", ["sign_psum", "packed_allgather",
                                  "packed_a2a", "hier:4"])
@pytest.mark.parametrize("vote_buckets", [1, 4])
def test_collective_inventory_matches_wire_recipe(mesh8, wire, vote_buckets):
    """All 4 wires x vote_buckets {1,4}: the compiled step's large-operand
    collective inventory IS the wire recipe's expected set — no extra
    collective, no missing bucket, no axis surprise — and the step holds
    zero host callbacks, donation survives lowering, and no bf16 param
    leaf is upcast."""
    tr = _trainer(mesh8, wire=wire, vote_buckets=vote_buckets)
    rep = trace_check.check_trainer(tr, _batch(tr))
    tr.close()
    assert rep["inventory_ok"], (rep["expected"], rep["observed"])
    assert rep["host_callbacks"] == []
    assert rep["donation_ok"], rep["donation"]
    assert rep["upcast_ok"], rep["param_upcasts"]
    assert rep["ok"]
    # per-bucket structure: one call-site group per bucket
    per_bucket = {"sign_psum": 1, "packed_allgather": 1,
                  "packed_a2a": 2, "hier:4": 3}[wire]
    assert len(rep["observed"]) == per_bucket * vote_buckets


@pytest.mark.parametrize("depth", [0, 1])
def test_hier_dcn_depth_inventory_invariant(mesh8, depth):
    """ISSUE 8: the hier wire's collective inventory is DEPTH-invariant —
    at any --dcn_pipeline_depth every step runs exactly one launch (legs
    1+2) and one consume (leg 3), so the expected set equals the
    synchronous wire's: no duplicate DCN collective, ICI legs unchanged,
    and zero host callbacks (the dcn_delay emulator is only traced when
    the fault is armed)."""
    tr = _trainer(mesh8, wire="hier:4", vote_buckets=2,
                  dcn_pipeline_depth=depth)
    rep = trace_check.check_trainer(tr, _batch(tr))
    tr.close()
    assert rep["ok"], (rep["expected"], rep["observed"],
                       rep["host_callbacks"])
    assert rep["expected"] == [list(c) for c in trace_check.expected_wire_calls(
        tr.n_params, 8, "hier:4", vote_buckets=2, dcn_pipeline_depth=0)]
    assert len(rep["observed"]) == 3 * 2  # 3 ppermute sites x 2 buckets


def test_hier_duplicate_dcn_collective_detected(mesh8):
    """The failure mode the depth cells exist to catch: a broken pipeline
    that consumes BOTH a fresh and a stale election per step (e.g. a
    cold-start implemented as a traced second election instead of the
    valid-mask) doubles leg-3 ring call sites — the contract must FAIL it,
    not average it away."""
    from functools import partial as _partial

    from distributed_lion_tpu.ops.codec import hier_chunk_slot_bytes

    # n large enough that the DCN/elected legs' chunk/8 operands clear
    # SCALAR_MAX (tiny ballots would file them as scalar reductions)
    n, g = 8192, 4

    @_partial(jax.shard_map, mesh=mesh8, in_specs=(P("data"), P()),
              out_specs=P(), check_vma=False)
    def broken(b, ring):
        slot = collectives.hier_launch(b[0], DATA_AXIS, 8, g)
        fresh = collectives.hier_consume(slot, n, DATA_AXIS, 8, g)
        stale = collectives.hier_consume(ring[0], n, DATA_AXIS, 8, g)
        return fresh & stale

    ring = jnp.zeros((8, hier_chunk_slot_bytes(n, 8, g)), jnp.uint8)
    ballots = jnp.zeros((8, n), jnp.bool_)
    calls, callbacks = trace_check.collective_calls(broken, ballots, ring)
    observed = sorted(c.key for c in calls
                      if c.nelems > trace_check.SCALAR_MAX)
    expected = trace_check.expected_wire_calls(n, 8, f"hier:{g}",
                                               dcn_pipeline_depth=1)
    assert not callbacks
    assert observed != expected  # the duplicate consume must surface
    assert len(observed) == len(expected) + 1


def test_lazy_vote_inventory(mesh8):
    """vote_every=4: the wire recipe's expected set follows the rotating
    1/K slice (codec.vote_chunk_elems), not the full ballot."""
    tr = _trainer(mesh8, wire="packed_a2a", vote_every=4, vote_buckets=4)
    rep = trace_check.check_trainer(tr, _batch(tr))
    tr.close()
    assert rep["ok"], (rep["expected"], rep["observed"],
                       rep["host_callbacks"], rep["param_upcasts"])


def test_contract_fails_on_wrong_recipe(mesh8):
    """The check can actually FAIL: judging a sign_psum step against the
    packed_allgather recipe must not pass (guards against a vacuous
    matcher)."""
    tr = _trainer(mesh8, wire="sign_psum", vote_buckets=1)
    args = (tr.params, tr.state, tr.vote_health, tr._frozen_arg(),
            _batch(tr), jax.random.key(0))
    rep = trace_check.check_step(
        tr._train_step_core, args, n_params=tr.n_params, world=tr.world,
        wire="packed_allgather", vote_every=1, vote_buckets=1)
    tr.close()
    assert not rep["inventory_ok"]


def test_host_callback_detected(mesh8):
    """A debug/callback primitive smuggled into a shard_map'd step is
    reported (and fails the contract)."""

    @partial(jax.shard_map, mesh=mesh8, in_specs=P(), out_specs=P(),
             check_vma=False)
    def f(x):
        jax.debug.print("sneaky {}", x.sum())
        return collectives.vote_total(x > 0, DATA_AXIS, "sign_psum")

    calls, callbacks = trace_check.collective_calls(
        f, jnp.zeros((1024,), jnp.float32))
    assert callbacks, "debug print must surface as a host callback"
    assert any(c.prim == "psum" for c in calls)


def test_param_upcast_detected():
    """A step that wholesale-upcasts bf16 params to f32 is flagged; the
    same math kept in bf16 is not."""
    params = {"w": jnp.zeros((256,), jnp.bfloat16)}

    def bad(params, x):
        return (params["w"].astype(jnp.float32) * x).sum()

    def good(params, x):
        return (params["w"] * x.astype(jnp.bfloat16)).sum()

    assert trace_check.param_upcasts(
        bad, (params, jnp.ones((256,), jnp.float32))) == [(256,)]
    assert trace_check.param_upcasts(
        good, (params, jnp.ones((256,), jnp.float32))) == []


# ------------------------------------------------------- the retrace guard
def _iter_of(tr, block, n=8, fill=1):
    def gen():
        while True:
            yield _batch(tr, block, fill)
    return gen()


def test_retrace_guard_catches_injected_recompile_error(mesh8):
    tr = _trainer(mesh8, retrace_guard="error")
    tr.train(_iter_of(tr, 32), max_steps=2)
    with pytest.raises(RuntimeError, match="RETRACE"):
        # a narrower batch = a new abstract signature = a recompile; the
        # guard refuses BEFORE jax pays for the second specialization
        tr.train(_iter_of(tr, 16), max_steps=1)
    with pytest.raises(RuntimeError, match="RETRACE"):
        # the refused signature was NOT adopted: a caller that catches and
        # re-dispatches the same shapes is refused again, not silently
        # recompiled on the retry
        tr.train(_iter_of(tr, 16), max_steps=1)
    tr.close()


def test_retrace_guard_warn_counts_and_logs_metric(mesh8, capsys):
    tr = _trainer(mesh8, retrace_guard="warn")
    tr.train(_iter_of(tr, 32), max_steps=1)
    assert tr.retrace_count == 0
    hist = tr.train(_iter_of(tr, 16), max_steps=1)
    assert tr.retrace_count == 1
    assert "RETRACE" in capsys.readouterr().out
    assert any(h.get("retraces") == 1 for h in hist)
    # same shapes again: no further retrace
    tr.train(_iter_of(tr, 16), max_steps=1)
    assert tr.retrace_count == 1
    # alternating BACK to an already-compiled signature costs jax nothing
    # (both specializations are cached) and must not re-warn forever
    tr.train(_iter_of(tr, 32), max_steps=1)
    assert tr.retrace_count == 1
    tr.close()


def test_retrace_guard_rejects_bad_mode(mesh8):
    with pytest.raises(ValueError, match="retrace_guard"):
        _trainer(mesh8, retrace_guard="loud")


def test_elections_bit_identical_with_analysis_features(mesh8):
    """--retrace_guard (the analysis subsystem's only runtime hook) is
    purely observational: losses and params are bit-identical to a guard-
    off run over the same batches."""
    runs = {}
    for mode in ("off", "error"):
        tr = _trainer(mesh8, wire="packed_a2a", vote_buckets=4,
                      retrace_guard=mode)
        hist = tr.train(_iter_of(tr, 32), max_steps=3)
        runs[mode] = (hist, jax.device_get(tr.params))
        tr.close()
    losses = {m: [h["loss"] for h in runs[m][0] if "loss" in h]
              for m in runs}
    assert losses["off"] == losses["error"]
    for a, b in zip(jax.tree.leaves(runs["off"][1]),
                    jax.tree.leaves(runs["error"][1])):
        assert np.array_equal(a, b)
