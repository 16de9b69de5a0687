"""Multi-chip default recipe: the comm sentinels (wire='auto',
vote_every=0) must resolve to the measured minimum-byte wire (packed_a2a
on big dp meshes) with the reference's STRICT every-step vote — lazy
vote_every is opt-in until the full-scale parity:lazy leg passes the
pre-registered criterion (check_evidence parity:lazy; the round-4 lazy
auto-default claimed runs/parity evidence that was never captured —
VERDICT weak #1). The recipe itself lives in ONE place,
train/loop.resolve_auto_comm; these tests pin its decision matrix and that
the Trainer applies it end to end."""

import builtins
import os

import jax
import numpy as np
import pytest

from distributed_lion_tpu.parallel.mesh import make_mesh
from distributed_lion_tpu.train.loop import (
    AUTO_BUCKET_MIN_COORDS,
    AUTO_LAZY_MIN_PARAMS,
    TrainConfig,
    Trainer,
    resolve_auto_comm,
)


def test_big_replicated_dp_gets_budget_recipe(mesh8):
    r = resolve_auto_comm(TrainConfig(), mesh8, 124_000_000,
                          params_replicated=True)
    # strict every-step voting until parity:lazy PASSES (lazy is opt-in)
    assert (r.wire, r.vote_every) == ("packed_a2a", 1)
    # and the full 124M-coordinate per-step ballot is big enough for the
    # pipelined (bucketed) wire — tests/test_vote_buckets.py pins the rest
    assert r.vote_buckets == 4


def test_tiny_ballot_keeps_strict_vote(mesh8):
    r = resolve_auto_comm(TrainConfig(), mesh8, AUTO_LAZY_MIN_PARAMS - 1,
                          params_replicated=True)
    assert (r.wire, r.vote_every) == ("packed_a2a", 1)


def test_sharded_params_keep_strict_vote(mesh8):
    """tp/pp/ep-sharded params make the lazy elected-sign cache unsound
    (per-rank ballots over different local shards) — auto must not pick
    vote_every > 1 there, whatever the lazy default becomes once
    parity:lazy evidence lands."""
    r = resolve_auto_comm(TrainConfig(), mesh8, 124_000_000,
                          params_replicated=False)
    assert r.vote_every == 1


def test_world_one_is_silent(mesh8):
    mesh1 = make_mesh(data=1, devices=jax.devices()[:1])
    r = resolve_auto_comm(TrainConfig(), mesh1, 124_000_000,
                          params_replicated=True)
    assert (r.wire, r.vote_every) == ("sign_psum", 1)


def test_explicit_choice_is_never_overridden(mesh8):
    cfg = TrainConfig(wire="sign_psum", vote_every=1, vote_buckets=1)
    assert resolve_auto_comm(cfg, mesh8, 124_000_000, True) is cfg
    # explicit wire/cadence with the buckets sentinel still resolvable:
    # only vote_buckets may change
    part = TrainConfig(wire="sign_psum", vote_every=1)
    r = resolve_auto_comm(part, mesh8, 124_000_000, True)
    assert (r.wire, r.vote_every, r.vote_buckets) == ("sign_psum", 1, 4)


def test_vote_buckets_threshold_boundary(mesh8):
    """The bucketed-wire auto threshold is judged on the PER-STEP ballot
    slice, exactly at AUTO_BUCKET_MIN_COORDS: at the boundary the pipeline
    arms (4 buckets), one coordinate below it stays monolithic."""
    base = dict(wire="packed_a2a", vote_every=1)
    at = resolve_auto_comm(TrainConfig(**base), mesh8,
                           AUTO_BUCKET_MIN_COORDS, params_replicated=True)
    assert at.vote_buckets == 4
    below = resolve_auto_comm(TrainConfig(**base), mesh8,
                              AUTO_BUCKET_MIN_COORDS - 1,
                              params_replicated=True)
    assert below.vote_buckets == 1


def test_vote_buckets_threshold_counts_lazy_slice(mesh8):
    """Under vote_every=K only 1/K of the ballot rides the wire per step —
    the bucket decision follows the slice (codec.vote_chunk_elems), not
    the full ballot: a 4x-threshold ballot at K=4 sits exactly at the
    boundary; 32 coordinates fewer drops the slice below it."""
    base = dict(wire="packed_a2a", vote_every=4)
    at = resolve_auto_comm(TrainConfig(**base), mesh8,
                           4 * AUTO_BUCKET_MIN_COORDS,
                           params_replicated=True)
    assert at.vote_buckets == 4
    below = resolve_auto_comm(TrainConfig(**base), mesh8,
                              4 * AUTO_BUCKET_MIN_COORDS - 32,
                              params_replicated=True)
    assert below.vote_buckets == 1


def test_vote_buckets_world_one_stays_monolithic():
    """W=1 has no wire to pipeline: even an enormous ballot keeps the
    single-collective graph."""
    mesh1 = make_mesh(data=1, devices=jax.devices()[:1])
    r = resolve_auto_comm(
        TrainConfig(wire="sign_psum", vote_every=1), mesh1,
        10 * AUTO_BUCKET_MIN_COORDS, params_replicated=True)
    assert r.vote_buckets == 1


def test_explicit_vote_buckets_one_is_preserved(mesh8):
    """--vote_buckets 1 is an operator decision, not a sentinel: auto must
    never re-bucket it however large the ballot."""
    cfg = TrainConfig(wire="packed_a2a", vote_every=1, vote_buckets=1)
    assert resolve_auto_comm(cfg, mesh8, 10 * AUTO_BUCKET_MIN_COORDS,
                             params_replicated=True) is cfg


def test_trainer_resolves_and_steps_with_auto_recipe(mesh8):
    """End to end: a Trainer built with default comm fields on a dp=8 mesh
    resolves to the budget wire and completes a train step (the same leg
    __graft_entry__._dryrun_auto_budget runs for the driver)."""
    from distributed_lion_tpu.data.sources import batch_iterator, synthetic_lm_dataset
    from distributed_lion_tpu.models.gpt2 import GPT2Config

    model_cfg = GPT2Config.tiny(vocab_size=2048, n_layer=2, n_head=8,
                                d_model=768, n_ctx=64)
    cfg = TrainConfig(
        lion=True, async_grad=True, learning_rate=1e-3, warmup_steps=1,
        max_steps=1, per_device_train_batch_size=1,
        gradient_accumulation_steps=1, block_size=64, logging_steps=1,
        output_dir=None,
    )
    tr = Trainer.for_gpt2(cfg, mesh8, model_cfg)
    assert tr.n_params >= AUTO_LAZY_MIN_PARAMS
    assert (tr.cfg.wire, tr.cfg.vote_every) == ("packed_a2a", 1)
    blocks = synthetic_lm_dataset(max(64, tr.global_train_batch()),
                                  cfg.block_size, model_cfg.vocab_size)
    hist = tr.train(batch_iterator(blocks, tr.global_train_batch(), seed=0),
                    max_steps=1)
    tr.close()
    assert np.isfinite([h["loss"] for h in hist if "loss" in h]).all()


@pytest.fixture
def host_reads(monkeypatch):
    """Every path ``open`` is given and every environment variable that is
    looked up, while the test runs."""
    seen = {"open": [], "env": []}
    real_open = builtins.open

    def spy_open(file, *a, **k):
        seen["open"].append(os.path.abspath(os.fspath(file))
                            if isinstance(file, (str, os.PathLike))
                            else file)
        return real_open(file, *a, **k)

    env_type = type(os.environ)
    for name in ("get", "__getitem__", "__contains__"):
        real = getattr(env_type, name)

        def spy(self, key, *a, _real=real):
            seen["env"].append(key)
            return _real(self, key, *a)

        monkeypatch.setattr(env_type, name, spy)
    monkeypatch.setattr(builtins, "open", spy_open)
    return seen


def _reads_of_a_tuner(seen):
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts") + os.sep
    return ([p for p in seen["open"]
             if isinstance(p, str) and p.startswith(scripts)]
            + [k for k in seen["env"] if str(k).startswith("DLT_")])


def test_resolve_auto_comm_reads_nothing_from_the_host(mesh8, host_reads):
    """The bucket count comes from the ballot's size and nothing else: no
    file under ``scripts/``, no ``DLT_*`` variable (a tuning cache keyed
    by device kind sat in front of this heuristic until PR 28, and missed
    on every chip)."""
    r = resolve_auto_comm(TrainConfig(), mesh8, 124_000_000,
                          params_replicated=True)
    assert (r.wire, r.vote_buckets) == ("packed_a2a", 4)
    assert _reads_of_a_tuner(host_reads) == []
    assert host_reads["open"] == []


def test_trainer_construction_reads_no_tuning_cache(mesh8, host_reads):
    from distributed_lion_tpu.models.gpt2 import GPT2Config

    cfg = TrainConfig(lion=True, async_grad=True, kernel="pallas",
                      max_steps=1, per_device_train_batch_size=1,
                      gradient_accumulation_steps=1, block_size=32,
                      output_dir=None)
    os.environ.get("DLT_PROBE")         # the recorder sees what is read
    assert set(_reads_of_a_tuner(host_reads)) == {"DLT_PROBE"}
    host_reads["env"].clear()
    tr = Trainer.for_gpt2(cfg, mesh8, GPT2Config.tiny())
    tr.close()
    assert _reads_of_a_tuner(host_reads) == []


def test_make_optimizer_degrades_sentinels_strict():
    """Standalone make_optimizer callers (no mesh in the signature) get the
    reference's strict semantics from an unresolved cfg, not a crash."""
    from distributed_lion_tpu.train.loop import make_optimizer

    make_optimizer(TrainConfig())  # wire='auto', vote_every=0 must not raise


def test_resolve_dropout_family_defaults():
    from distributed_lion_tpu.cli.run_clm import resolve_dropout

    assert resolve_dropout(None, "gpt2", 1) == 0.1
    assert resolve_dropout(None, "llama", 1) == 0.0
    assert resolve_dropout(None, "gpt2", 2) == 0.0  # pp: unsupported
    # sp skips attention-prob dropout — 0.1 would silently be a different
    # regularizer than the HF default, so auto stays off there
    assert resolve_dropout(None, "gpt2", 1, sp=2) == 0.0
    assert resolve_dropout(0.1, "gpt2", 1, sp=2) == 0.1  # explicit opt-in
    assert resolve_dropout(0.0, "gpt2", 1) == 0.0   # explicit opt-out wins
    assert resolve_dropout(0.3, "gpt2", 1) == 0.3


def test_multihost_hier_groups_are_data_rows_per_host(monkeypatch):
    """code-review r4: hier's subgroups must be whole DATA rows sharing a
    host. data is the slowest mesh axis, so a host of L devices holds
    L // inner data rows (inner = product of model axes) — grouping by
    local_device_count alone would straddle hosts whenever inner > 1."""
    from distributed_lion_tpu.train import loop as L

    monkeypatch.setattr(L.jax, "process_count", lambda: 2)
    monkeypatch.setattr(L.jax, "local_device_count", lambda: 4)

    # dp=4 x sp=2 over 8 'devices', 2 'hosts' of 4: each host holds 2 whole
    # data rows -> hier:2, not hier:4
    mesh = make_mesh(data=4, seq=2, devices=jax.devices()[:8])
    r = resolve_auto_comm(TrainConfig(), mesh, 124_000_000,
                          params_replicated=True)
    assert r.wire == "hier:2"

    # dp=2 x tensor=2 x seq=2: inner=4 == local -> 1 data row per host,
    # no intact ICI subgroup -> fall back to the flat sub-2-bit wire
    mesh = make_mesh(data=2, tensor=2, seq=2, devices=jax.devices()[:8])
    r = resolve_auto_comm(TrainConfig(), mesh, 124_000_000,
                          params_replicated=False)
    assert r.wire == "packed_a2a"

    # pure dp over 2 hosts: groups = all 4 local devices
    mesh = make_mesh(data=8, devices=jax.devices()[:8])
    r = resolve_auto_comm(TrainConfig(), mesh, 124_000_000,
                          params_replicated=True)
    assert r.wire == "hier:4"
