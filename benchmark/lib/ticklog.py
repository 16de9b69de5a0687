"""Turning the engine's tick clocks into times.

The serving engine stamps every request in its own tick count
(``Completion.timing``: ``queue_ticks``, ``decode_ticks``); the driver
stamps the wall clock after every ``engine.step()``. A request submitted
when ``submit_tick`` ticks had run gets its first token in tick
``first = submit_tick + queue_ticks`` (the tick whose prefill sampled it).
``step()`` runs its decode dispatch after admission, so the slot decodes in
that same tick: token 1 is also made in tick ``first``, and token k >= 1 in
tick ``first + k - 1``. A request of n >= 2 tokens therefore ends in tick
``first + n - 2`` and ``decode_ticks == n - 2`` (0 for n == 1). True while
a decode tick makes one token a slot (no speculation)."""

from __future__ import annotations


def consistent(n_tokens: int, timing: dict) -> bool:
    """Do the tick clocks agree with the number of tokens delivered?"""
    if not timing or "ttft_ticks" not in timing or n_tokens < 1:
        return False
    return int(timing["decode_ticks"]) == max(n_tokens - 2, 0)


def token_ticks(submit_tick: int, timing: dict, n_tokens: int) -> list:
    first = int(submit_tick) + int(timing["queue_ticks"])
    return [first + max(k - 1, 0) for k in range(n_tokens)]


def token_times(submit_tick: int, timing: dict, n_tokens: int,
                stamps: dict) -> list:
    """Wall time of every token: the stamp taken after the tick that made
    it. ``stamps`` maps tick number (1-based, ``engine.stats['ticks']``
    after the step) to seconds."""
    return [stamps[t] for t in token_ticks(submit_tick, timing, n_tokens)]


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear interpolation between order
    statistics (numpy's default), exact on the sample."""
    values = sorted(values)
    if not values:
        return float("nan")
    pos = (len(values) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)
