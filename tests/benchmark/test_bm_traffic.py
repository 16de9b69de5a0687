"""The traffic generator is a pure function of the mix file and the seed;
every seed offers the same multiset of work in another order."""

import numpy as np

from benchmark.lib import harness, traffic

CHAT = harness.read_json(harness.BENCH_DIR, "traffic", "chat-rate.json")
BACKLOG = harness.read_json(harness.BENCH_DIR, "traffic", "decode-backlog.json")
BIG = 2 ** 31 + 77  # the driver's seeds pass 32 signed bits


def test_same_seed_same_requests():
    a = traffic.serve_requests(CHAT, 5.0, BIG, 50257)
    b = traffic.serve_requests(CHAT, 5.0, BIG, 50257)
    assert a == b and len(a) > 50


def test_seed_reorders_but_keeps_the_multiset():
    pa, oa = traffic.request_sizes(CHAT, 500, 1)
    pb, ob = traffic.request_sizes(CHAT, 500, BIG)
    assert sorted(zip(pa, oa)) == sorted(zip(pb, ob))
    assert list(zip(pa, oa)) != list(zip(pb, ob))
    ta, tb = (traffic.arrival_times(CHAT, 10.0, s) for s in (1, BIG))
    assert not np.array_equal(ta[:50], tb[:50])


def test_lengths_respect_the_mix():
    p, o = traffic.request_sizes(CHAT, 2000, 3)
    spec = CHAT["prompt_len"]
    assert p.min() >= spec["lo"] and p.max() <= spec["hi"]
    assert abs(np.median(p) - spec["median"]) < 0.15 * spec["median"]
    assert o.min() >= CHAT["output_len"]["lo"]
    assert o.max() <= CHAT["output_len"]["hi"]


def test_due_times_are_seconds_at_the_rate():
    rate = CHAT["arrivals"]["rate_per_s"]
    t = traffic.arrival_times(CHAT, 30.0, 5)
    assert np.all(np.diff(t) >= 0) and t[-1] < 30.0
    assert abs(len(t) / 30.0 - rate) < 0.15 * rate


def test_bursts_bring_extra_arrivals_at_one_instant():
    mix = dict(CHAT, arrivals={"kind": "poisson", "rate_per_s": 20.0,
                               "burst_every": 5, "burst_size": 3})
    t = traffic.arrival_times(mix, 20.0, 9)
    assert (np.diff(t) == 0).sum() >= len(t) // 5


def test_backlog_is_all_due_at_zero_and_decks_repeat():
    reqs = traffic.serve_requests(BACKLOG, 30.0, 4, 50257)
    assert len(reqs) == BACKLOG["arrivals"]["count"]
    assert all(r["due_s"] == 0.0 for r in reqs)
    deck = BACKLOG["deck"]
    sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
    assert sorted(sizes[:deck]) == sorted(sizes[deck:2 * deck])
    assert all(lo + out <= 1024 for lo, out in sizes)


def test_train_rows_all_differ_and_follow_the_seed():
    a = traffic.train_batch(BIG, 0, 8, 64, 256)
    assert a.shape == (8, 64) and a.dtype == np.int32
    assert len({row.tobytes() for row in a}) == 8
    assert np.array_equal(a, traffic.train_batch(BIG, 0, 8, 64, 256))
    assert not np.array_equal(a, traffic.train_batch(BIG, 1, 8, 64, 256))
