"""Batch-sharded expert-parallel decode (ISSUE 16): the engine's decode /
prefill / verify batch sharded over the EXPERT mesh axis — ep as a
throughput lever, not just an HBM lever.

Pins, all on the 8-device CPU mesh:

- ep_batch at ep=1 is BIT-identical to the replicated engine, including
  the KV page pool bytes (the sharding is a pure re-schedule);
- ep ∈ {2, 4} and ep×tp are token-identical to the unsharded engine,
  greedy and sampled, composing with --prefix_cache and ngram
  speculation;
- ragged occupancy (some groups with empty slots) stays identical — the
  valid-lane mask, not slot packing, carries correctness;
- the two-microbatch overlap split (--serve_ep_overlap) is
  bit-identical to the unsplit tick;
- the routing stats ep ∈ {1, 2} are bit-equal to the unsharded engine
  (psummed counters + the static stats_lanes prefill budget);
- the training-side --ep_dcn_pipeline ring crash-resumes bit-identical;
- every infeasible configuration is refused loudly at build time.
"""

import numpy as np
import pytest

import jax

from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_init
from distributed_lion_tpu.serve.engine import (
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)

MOE = GPT2Config.tiny(moe_experts=4)


@pytest.fixture(scope="module")
def moe_params():
    return gpt2_init(jax.random.key(0), MOE)


def _requests(n=4, max_new=8, lens=(3, 9, 5, 14), seed=7):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i,
                    tokens=list(map(int, rng.integers(1, MOE.vocab_size, L))),
                    max_new_tokens=max_new, seed=i)
            for i, L in enumerate(lens[:n])]


def _engine(params, **kw):
    base = dict(max_seqs=4, block_size=4, max_blocks_per_seq=8)
    base.update(kw)
    return ServingEngine(ServeModel.for_gpt2(params, MOE), ServeConfig(**base))


def _run(eng, reqs):
    done = eng.run([Request(r.req_id, list(r.tokens), r.max_new_tokens,
                            r.seed) for r in reqs])
    return {r.req_id: done[r.req_id].tokens for r in reqs}


@pytest.fixture(scope="module")
def baseline(moe_params):
    reqs = _requests()
    return reqs, _run(_engine(moe_params), reqs)


def test_ep_batch_ep1_bit_identical_with_pages(moe_params, baseline):
    """ep_batch over an axis of size 1 is the SAME program modulo a
    trivial shard_map — tokens AND the full KV page pool must match
    bit for bit."""
    reqs, base = baseline
    ref = _engine(moe_params)
    got = _engine(moe_params, ep=1, ep_batch=True)
    assert _run(ref, reqs) == base
    assert _run(got, reqs) == base
    for lr, lg in zip(ref.pages, got.pages):
        for k in lr:
            np.testing.assert_array_equal(np.asarray(lr[k]),
                                          np.asarray(lg[k]))


@pytest.mark.parametrize("ep", [2, 4])
def test_ep_batch_token_identical(moe_params, baseline, ep):
    reqs, base = baseline
    assert _run(_engine(moe_params, ep=ep, ep_batch=True), reqs) == base


def test_ep_batch_with_tp(moe_params, baseline):
    reqs, base = baseline
    assert _run(_engine(moe_params, ep=2, tp=2, ep_batch=True), reqs) == base


def test_ep_batch_sampled(moe_params):
    """Seeded sampling rides per-slot fold_in keys that never see the
    mesh — temperature/top_k outputs are identical under the sharding."""
    reqs = _requests()
    samp = dict(temperature=0.9, top_k=40)
    base = _run(_engine(moe_params, **samp), reqs)
    assert _run(_engine(moe_params, ep=2, ep_batch=True, **samp),
                reqs) == base


def test_ep_batch_ragged_occupancy(moe_params):
    """3 requests on a 4-slot, 2-group engine: one group decodes with an
    empty slot. The valid-lane mask keeps the live rows identical."""
    reqs = _requests(n=3)
    base = _run(_engine(moe_params), reqs)
    assert _run(_engine(moe_params, ep=2, ep_batch=True), reqs) == base


def test_ep_batch_prefix_cache(moe_params):
    rng = np.random.default_rng(23)
    sys_p = list(map(int, rng.integers(1, MOE.vocab_size, 9)))
    reqs = [Request(req_id=i, tokens=sys_p + list(
        map(int, rng.integers(1, MOE.vocab_size, 2))),
        max_new_tokens=5, seed=i) for i in range(4)]
    base = _run(_engine(moe_params, num_blocks=64), reqs)
    got = _run(_engine(moe_params, num_blocks=64, prefix_cache=True,
                       ep=2, ep_batch=True), reqs)
    assert got == base


def test_ep_batch_ngram_speculation(moe_params):
    motif = list(map(int,
                     np.random.default_rng(19).integers(1, MOE.vocab_size,
                                                        4)))
    reqs = [Request(req_id=i, tokens=motif * 4, max_new_tokens=10, seed=i)
            for i in range(3)]
    base = _run(_engine(moe_params, max_blocks_per_seq=16), reqs)
    got = _run(_engine(moe_params, max_blocks_per_seq=16,
                       speculate="ngram:4", ep=2, ep_batch=True), reqs)
    assert got == base


def test_ep_overlap_bit_identical(moe_params, baseline):
    """The two-microbatch split is a pure re-schedule: attention is
    row-local and inference routing is no-drop (exact per token), so
    half-batch dispatch order cannot change a single token."""
    reqs, base = baseline
    assert _run(_engine(moe_params, ep=2, ep_batch=True, ep_overlap=True),
                reqs) == base


@pytest.mark.parametrize("ep", [1, 2])
def test_moe_stats_bit_equal_under_sharding(moe_params, ep):
    """The routing-load counters psum over the expert axis (each shard
    tallies only its own rows) and the batch-1 prefill budget uses the
    static true lane width, not ep x lanes — the aggregated stats must
    equal the unsharded engine's exactly."""
    reqs = _requests()
    e0 = _engine(moe_params, moe_stats=True)
    _run(e0, reqs)
    e1 = _engine(moe_params, moe_stats=True, ep=ep, ep_batch=True)
    _run(e1, reqs)
    for k in ("moe_valid_tokens", "moe_kept_tokens", "moe_capacity_slots"):
        assert e0.stats[k] == e1.stats[k], (k, e0.stats, e1.stats)
    # batch-sharded, the token vector is fed back expert-sharded as the
    # dispatches return it: no gather, so this layout runs ahead too
    assert e1._run_ahead and 0 < e1.stats["run_ahead_ticks"] \
        == e0.stats["run_ahead_ticks"]


def test_ep_batch_refusals(moe_params):
    with pytest.raises(ValueError, match="serve_ep_batch"):
        _engine(moe_params, ep_batch=True)  # no expert axis
    with pytest.raises(ValueError, match="max_seqs"):
        _engine(moe_params, max_seqs=6, ep=4, ep_batch=True)
    with pytest.raises(ValueError, match="num_blocks"):
        _engine(moe_params, ep=4, ep_batch=True, num_blocks=66)
    with pytest.raises(ValueError, match="serve_ep_overlap"):
        _engine(moe_params, ep=4, ep_batch=True, ep_overlap=True)  # 1 slot
    with pytest.raises(ValueError, match="even"):
        _engine(moe_params, max_seqs=3, ep_overlap=True)


def test_ep_dcn_pipeline_ring_crash_resume(tmp_path):
    """Training satellite: the --ep_dcn_pipeline balance ring is live
    optimizer state — a run killed after a mid-flight save must resume
    bit-identical (losses, params, ring) to an uninterrupted run."""
    from distributed_lion_tpu.data.sources import (
        batch_iterator,
        synthetic_lm_dataset,
    )
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    model = GPT2Config.tiny(n_layer=4, moe_experts=4)
    mesh = make_mesh(data=2, expert=2, devices=jax.devices()[:4])

    def cfg(outdir=None):
        return TrainConfig(
            lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
            max_steps=4, per_device_train_batch_size=1,
            gradient_accumulation_steps=1, block_size=32, logging_steps=1,
            save_steps=2, output_dir=outdir, seed=5,
            expert_parallel=2, ep_dcn_pipeline=2)

    blocks = synthetic_lm_dataset(32, 32, model.vocab_size, seed=1)

    def losses(hist):
        return [x["loss"] for x in hist if "loss" in x]

    t_ref = Trainer.for_gpt2(cfg(), mesh, model, seed=3)
    ref = losses(t_ref.train(batch_iterator(blocks, t_ref.global_train_batch(),
                                            seed=5)))
    ref_params = jax.device_get(t_ref.params)
    ref_ring = np.asarray(jax.device_get(t_ref.state.moe_ring))
    t_ref.close()
    assert np.any(ref_ring != 0.0)  # the ring really is in flight

    out = str(tmp_path / "run")
    t1 = Trainer.for_gpt2(cfg(out), mesh, model, seed=3)
    part1 = losses(t1.train(batch_iterator(blocks, t1.global_train_batch(),
                                           seed=5), max_steps=2))
    t1.close()
    t2 = Trainer.for_gpt2(cfg(out), mesh, model, seed=3)
    assert t2.step_count == 2
    part2 = losses(t2.train(batch_iterator(blocks, t2.global_train_batch(),
                                           seed=5)))
    got_params = jax.device_get(t2.params)
    got_ring = np.asarray(jax.device_get(t2.state.moe_ring))
    t2.close()

    np.testing.assert_array_equal(part1 + part2, ref)
    jax.tree.map(np.testing.assert_array_equal, got_params, ref_params)
    np.testing.assert_array_equal(got_ring, ref_ring)

    # a depth toggle on resume is refused loudly (the in-flight ring
    # cannot be remapped)
    import dataclasses
    with pytest.raises(ValueError, match="ep_dcn_pipeline"):
        Trainer.for_gpt2(dataclasses.replace(cfg(out), ep_dcn_pipeline=1),
                         mesh, model, seed=3)


def test_ep_dcn_pipeline_refusals():
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    mesh = make_mesh(data=8)
    model = GPT2Config.tiny(n_layer=4, moe_experts=4)

    def cfg(**kw):
        base = dict(lion=True, async_grad=True, learning_rate=1e-3,
                    warmup_steps=1, max_steps=2,
                    per_device_train_batch_size=1,
                    gradient_accumulation_steps=1, block_size=32,
                    logging_steps=1, output_dir=None, seed=5)
        base.update(kw)
        return TrainConfig(**base)

    with pytest.raises(ValueError, match=">= 0"):
        Trainer.for_gpt2(cfg(ep_dcn_pipeline=-1), mesh, model)
    with pytest.raises(ValueError, match="moe_ring"):
        Trainer.for_gpt2(cfg(ep_dcn_pipeline=2, lion=False,
                             async_grad=False), mesh, model)
    with pytest.raises(ValueError, match="dense"):
        Trainer.for_gpt2(cfg(ep_dcn_pipeline=0), mesh, GPT2Config.tiny())
