"""Hierarchical (two-level) vote wire: ``wire="hier:<g>"``.

±1 ballots are psum'd inside g-worker ICI subgroups; only the subgroups'
bit-packed 1-bit verdicts cross the group boundary (the DCN leg on a
multi-host mesh). Net-new vs the reference (whose only collective is a flat
world-wide all_gather, /root/reference/distributed_lion.py:80-81); the
hierarchy is the standard scale-out shape for meshes where intra-host ICI is
cheap and cross-host DCN is the budgeted fabric.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _sharded import run_sharded
from distributed_lion_tpu.ops.codec import parse_wire, wire_bytes_per_param
from distributed_lion_tpu.parallel.collectives import (
    majority_vote,
    majority_vote_psum,
)

W = 8


def _vote_all(mesh, votes: np.ndarray, wire: str) -> np.ndarray:
    """Run majority_vote over the data axis; votes is [W, n] bool.
    Returns the elected bools from every worker, stacked [W, n]."""
    def body(v):
        elected = majority_vote(v[0], "data", wire)
        return elected[None]

    return np.asarray(run_sharded(body, mesh, P("data"), P("data"),
                                  jnp.asarray(votes)))


def test_parse_wire():
    assert parse_wire("hier:4") == ("hier", 4)
    assert parse_wire("sign_psum") == ("sign_psum", None)
    with pytest.raises(ValueError):
        parse_wire("hier:zero")
    with pytest.raises(ValueError):
        parse_wire("hier:0")
    with pytest.raises(ValueError):
        parse_wire("carrier_pigeon")


@pytest.mark.parametrize("g", [1, W])
def test_degenerate_groups_match_flat_vote(mesh8, g):
    rng = np.random.default_rng(0)
    votes = rng.random((W, 203)) < 0.5
    flat = _vote_all(mesh8, votes, "sign_psum")
    hier = _vote_all(mesh8, votes, f"hier:{g}")
    np.testing.assert_array_equal(hier, flat)


def test_majority_of_majorities_semantics(mesh8):
    # W=8, g=4 → 2 subgroups. Coordinate 0: ballots [+,+,+,-] [-,-,-,+]
    # → verdicts [+, -] → group-level tie → -1, though the flat vote is 4-4
    # tie → -1 as well. Coordinate 1: [+,+,-,-] [+,+,+,+] → group 0 tie → -,
    # group 1 +, tie → -1 — but the flat vote is 6-2 → +1. The hierarchy is
    # a different (documented) electorate.
    votes = np.zeros((W, 2), bool)
    votes[:, 0] = [1, 1, 1, 0, 0, 0, 0, 1]
    votes[:, 1] = [1, 1, 0, 0, 1, 1, 1, 1]
    flat = _vote_all(mesh8, votes, "sign_psum")
    hier = _vote_all(mesh8, votes, "hier:4")
    assert not flat[0, 0] and not hier[0, 0]
    assert flat[0, 1] and not hier[0, 1]


def test_replica_consistency_and_unanimity(mesh8):
    rng = np.random.default_rng(1)
    votes = rng.random((W, 130)) < 0.5
    votes[:, :10] = True   # unanimous + must elect +
    votes[:, 10:20] = False  # unanimous - must elect -
    out = _vote_all(mesh8, votes, "hier:2")
    for w in range(1, W):
        np.testing.assert_array_equal(out[0], out[w])
    assert out[0, :10].all() and not out[0, 10:20].any()


@pytest.mark.parametrize("w,g", [(2, 1), (2, 2), (4, 2), (6, 2), (6, 3),
                                 (8, 2), (8, 4)])
def test_hier_matches_numpy_oracle(w, g):
    """Fuzz: elected bits equal a numpy majority-of-majorities oracle for
    every (world, group) combination the 8-device mesh can host."""
    rng = np.random.default_rng(w * 10 + g)
    votes = rng.random((w, 97)) < 0.5
    mesh = Mesh(np.array(jax.devices()[:w]), ("data",))

    def body(v):
        return majority_vote(v[0], "data", f"hier:{g}")[None]

    out = run_sharded(body, mesh, P("data"), P("data"), jnp.asarray(votes))
    got = np.asarray(out)[0]

    groups = votes.reshape(w // g, g, -1)
    tallies = groups.sum(1) * 2 - g            # per-group ±1 sums
    verdicts = tallies > 0                     # group tie → -1
    expected = verdicts.sum(0) * 2 > (w // g)  # group-level tie → -1
    np.testing.assert_array_equal(got, expected)
    for row in np.asarray(out)[1:]:
        np.testing.assert_array_equal(row, got)


def test_group_size_must_divide_world(mesh8):
    votes = np.zeros((W, 16), bool)
    with pytest.raises(ValueError, match="divide"):
        _vote_all(mesh8, votes, "hier:3")


def test_wire_accounting_hier():
    n = 124_000_000
    acct = wire_bytes_per_param(n, world_size=32, wire="hier:8")
    # DCN leg: (G−1)=3 hops × (n/g)/8 packed bytes → 3/8 bit/param crossing
    # the slow fabric — under BASELINE.md's 0.5 bit/param budget outright,
    # vs packed_allgather's 32 bits/param at the same world size.
    assert acct["hier_groups"] == 4
    assert acct["dcn_bits_per_param"] == pytest.approx(3 / 8, rel=1e-3)
    flat = wire_bytes_per_param(n, world_size=32, wire="packed_allgather")
    assert acct["dcn_bytes_per_step"] < flat["bytes_per_step"] / 32
    # composed with vote_every both legs are divided by K
    lazy = wire_bytes_per_param(n, world_size=32, wire="hier:8", vote_every=8)
    assert lazy["dcn_bits_per_param"] == pytest.approx(3 / 64, rel=1e-2)
    assert lazy["bytes_per_step"] == pytest.approx(acct["bytes_per_step"] / 8,
                                                   rel=1e-2)
    with pytest.raises(ValueError, match="divide"):
        wire_bytes_per_param(n, world_size=32, wire="hier:5")


def test_train_step_with_hier_wire():
    """End-to-end: vote-Lion training over dp=8 with the hier wire — loss
    goes down and replicas stay bit-identical."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.parallel.mesh import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, Trainer

    mesh = make_mesh(data=W)
    model_cfg = GPT2Config.tiny()
    cfg = TrainConfig(
        lion=True, async_grad=True, learning_rate=3e-3, warmup_steps=2,
        max_steps=24, per_device_train_batch_size=2,
        gradient_accumulation_steps=1, block_size=32, logging_steps=4,
        eval_steps=1000, save_steps=1000, wire="hier:4", output_dir=None,
    )
    trainer = Trainer.for_gpt2(cfg, mesh, model_cfg)
    blocks = synthetic_lm_dataset(512, cfg.block_size, model_cfg.vocab_size, seed=3)
    it = batch_iterator(blocks, trainer.global_train_batch(), seed=0)
    history = trainer.train(it, max_steps=24)
    losses = [h["loss"] for h in history if "loss" in h]
    assert losses[-1] < losses[0]
    # replicated params must remain bit-identical across all 8 devices
    leaf = trainer.params["wte"]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    trainer.close()
