"""``correct`` can fail: the control (the reference in a lower precision, in
the program's place) comes out not correct, and a run whose timed path is
broken underneath comes out not correct. Tiny sizes, CPU; the chip gate is
skipped and the rest of a run is driven as ``run.py`` drives it.

The limits used here are the tiny size's own, read the same way as the
cells' (PERF.md section 2): the sound runs' largest over three seeds was
0.28 for ``grad_sketch_gap`` and the fp8 control's smallest 1.8, so 0.7
stands between; at this size every served token is the reference's own
choice (gap 0) under every precision, so the serving control is a forward
pass with its matmuls in fp8 over a model whose logits are nearly flat.
"""

import time

import numpy as np
import pytest

from benchmark import rehearse
from benchmark.lib import harness

SEED = 2 ** 31 + 3
TINY_TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.05,
                     "grad_sketch_gap": 0.035, "update_norm_gap": 0.2}


def drive(cell, seconds=1.0):
    """What ``run.py`` does after its look for a chip."""
    from benchmark import run

    return run.run_cell(cell, SEED, seconds, False,
                        {"platform": "cpu", "kind": "cpu",
                         "count": cell["chips"]},
                        t_process=time.monotonic())


@pytest.fixture(scope="module")
def train_cell():
    import jax

    # tests/conftest.py gives the CPU backend 8 devices and the trainer
    # takes every device it sees as a worker: the tiny job has that many
    cell = rehearse.tiny_cell("train.gpt2-124m.readme",
                              chips=jax.device_count())
    cell["correct"]["limits"] = dict(TINY_TRAIN_LIMITS)
    return cell


def test_sound_training_run_is_correct_and_control_is_not(train_cell):
    from benchmark.drivers import train_clm

    result = drive(train_cell)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    reference = train_clm.reference_numbers(train_cell, SEED, quant=None)
    control = train_clm.reference_numbers(train_cell, SEED, quant="fp8")
    check = harness.Check()
    train_clm.compare(control, reference, TINY_TRAIN_LIMITS, check)
    assert not check.ok, "the fp8 reference must come out not correct"
    failed = {r[0] for r in check.rows if not r[3]}
    assert "grad_sketch_gap" in failed


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        train_cell, monkeypatch):
    from distributed_lion_tpu.train import loop

    real_init = loop.Trainer.__init__

    def broken_init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        real_step = self._train_step

        def lazy_step(params, state, vh, frozen, batch, key):
            import jax
            import jax.numpy as jnp

            kept = jax.tree.map(jnp.copy, (params, state))  # step donates
            _, _, vh2, metrics = real_step(params, state, vh, frozen, batch,
                                           key)
            return kept[0], kept[1], vh2, metrics   # nothing learned

        self._train_step = lazy_step

    monkeypatch.setattr(loop.Trainer, "__init__", broken_init)
    result = drive(train_cell)
    assert result["correct"] is False


def test_a_part_of_the_batch_left_out_is_not_correct(train_cell, monkeypatch):
    from benchmark.lib import traffic

    real_batch = traffic.train_batch
    calls = {"n": 0}

    def feed_repeats_rows(seed, step, rows, block, vocab):
        calls["n"] += 1
        out = real_batch(seed, step, rows, block, vocab)
        if calls["n"] <= 3:          # the program's feed; the reference
            out[rows // 2:] = out[:rows - rows // 2]  # regenerates its own
        return out

    monkeypatch.setattr(traffic, "train_batch", feed_repeats_rows)
    result = drive(train_cell)
    assert result["correct"] is False


@pytest.fixture(scope="module")
def serve_cell():
    # a candidate cell (open loop): BENCHMARK.json does not list it, so the
    # metrics it would report are named here
    cell = rehearse.tiny_cell("serve.gpt2-124m.chat-rate")
    cell["end_to_end"] = [{"name": "ttft_p95_ms", "unit": "ms"},
                          {"name": "itl_p95_ms", "unit": "ms"},
                          {"name": "setup_s", "unit": "s"}]
    return cell


def test_sound_serving_run_is_correct(serve_cell):
    result = drive(serve_cell, seconds=1.5)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 5
    assert result["metrics"]["ttft_p95_ms"]["value"] > 0
    assert result["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_token_altered_where_it_is_produced_is_not_correct(
        serve_cell, monkeypatch):
    from distributed_lion_tpu.serve import engine as engine_mod

    real_init = engine_mod.ServingEngine.__init__

    def broken_init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        real_tick = self._decode_tick

        def off_by_one(params, pages, *rest):
            (toks, st), pages = real_tick(params, pages, *rest)
            return ((toks + 1) % 256, st), pages

        self._decode_tick = off_by_one

    monkeypatch.setattr(engine_mod.ServingEngine, "__init__", broken_init)
    result = drive(serve_cell, seconds=1.5)
    assert result["correct"] is False


def test_serving_control_in_fp8_is_not_correct():
    """The lower precision in the program's place: at each position of the
    same prompts and tokens, the token the fp8 forward pass puts first lies
    below the reference's best by more than the sound runs' gap."""
    from benchmark.drivers import serve_engine

    cell = rehearse.tiny_cell("serve.gpt2-124m.chat-rate")
    # a wide, nearly flat vocabulary, as the published models have at
    # seeded weights: top-2 margins small enough for a precision to matter
    cell["config"] = dict(cell["config"], vocab_size=8192, n_embd=128)
    rng = np.random.default_rng(3)
    sample = [{"id": i, "prompt": rng.integers(0, 8192, 24).tolist(),
               "tokens": []} for i in range(4)]
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gpt2 as ref

    weights = jax.jit(lambda k: ref.init_weights(
        k, cell["config"], jnp.bfloat16))(ref.seed_key(SEED))
    for s in sample:      # greedy tokens of the reference itself: gap 0
        seq = list(s["prompt"])
        for _ in range(24):
            logits = ref.forward(weights, np.asarray([seq], np.int32),
                                 cell["config"])
            seq.append(int(logits[0, -1].argmax()))
        s["tokens"] = seq[24:]
    gaps = serve_engine.served_token_gaps(cell, SEED, sample, ("fp8",))
    sound = max(float(g.max()) for g in gaps["program"])
    control = max(float(g.max()) for g in gaps["fp8"])
    limit = 0.005     # this size's own: sound 0, the control read 0.018
    assert sound <= limit < control, "fp8 must come out not correct"
