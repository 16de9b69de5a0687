"""Tick-stamp arithmetic on a hand-made tick log."""

import pytest

from benchmark.lib import ticklog

# stamps after ticks 1..8, in seconds
STAMPS = {t: 0.010 * t for t in range(1, 9)}


def test_tokens_land_on_the_ticks_that_made_them():
    # submitted after 2 ticks had run, waited 1 tick in the queue: first
    # token in tick 3, the same tick's decode makes token 1, then one a tick
    timing = {"queue_ticks": 1, "ttft_ticks": 1, "decode_ticks": 3}
    assert ticklog.consistent(5, timing)
    assert ticklog.token_ticks(2, timing, 5) == [3, 3, 4, 5, 6]
    times = ticklog.token_times(2, timing, 5, STAMPS)
    assert times == pytest.approx([0.03, 0.03, 0.04, 0.05, 0.06])
    gaps = [b - a for a, b in zip(times, times[1:])]
    assert gaps == pytest.approx([0.0, 0.01, 0.01, 0.01])


def test_single_token_request_and_inconsistent_clocks():
    assert ticklog.consistent(1, {"queue_ticks": 0, "ttft_ticks": 0,
                                  "decode_ticks": 0})
    assert ticklog.token_ticks(4, {"queue_ticks": 0}, 1) == [4]
    # a request that never produced a token has no ttft clock
    assert not ticklog.consistent(0, {"queue_ticks": 5, "decode_ticks": 0})
    # token count and tick clocks disagree (e.g. two tokens a tick)
    assert not ticklog.consistent(9, {"queue_ticks": 0, "ttft_ticks": 0,
                                      "decode_ticks": 3})


def test_ttft_is_charged_from_the_due_time():
    due = 0.012                      # due during tick 2
    timing = {"queue_ticks": 2, "ttft_ticks": 2, "decode_ticks": 0}
    first = ticklog.token_times(1, timing, 1, STAMPS)[0]
    assert first - due == pytest.approx(0.018)


def test_percentile_matches_numpy():
    import numpy as np

    values = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (0, 50, 95, 100):
        assert ticklog.percentile(values, q) == pytest.approx(
            float(np.percentile(values, q)))
