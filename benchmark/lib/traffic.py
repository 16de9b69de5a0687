"""The one general traffic generator. A traffic mix is a data file of
parameters (``benchmark/traffic/<mix>.json``); this module turns it and a
seed into requests with due times (serving) or token batches (training).

The sizes and arrival gaps of a mix are a fixed multiset drawn from the
mix's own ``mix_seed``; ``--seed`` only reorders them (and draws the token
ids), so every seed offers the same work in another order. Arithmetic
copied from ``scripts/workload_gen.py`` (seeded lognormal lengths, Poisson
and burst arrivals), with due times in seconds instead of engine ticks.
"""

from __future__ import annotations

import numpy as np


def _rng(*ints) -> np.random.Generator:
    return np.random.default_rng([int(i) & 0xFFFFFFFFFFFF for i in ints])


def lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    """``n`` whole lengths: lognormal with the given median and sigma,
    clipped to ``[lo, hi]``."""
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["lo"], spec["hi"]).astype(np.int64)


def request_sizes(traffic: dict, count: int, seed: int):
    """``count`` (prompt_len, output_len) pairs. The mix is a deck of
    ``deck`` pairs (default: all ``count``) drawn from ``mix_seed``; the
    deck repeats, and each repeat is shuffled by the seed."""
    deck = int(traffic.get("deck") or count)
    rng = _rng(traffic["mix_seed"], 1)
    prompts = lognormal_lengths(rng, deck, traffic["prompt_len"])
    outputs = lognormal_lengths(rng, deck, traffic["output_len"])
    order_rng = _rng(seed, 2)
    idx = np.concatenate([order_rng.permutation(deck)
                          for _ in range(-(-count // deck))])[:count]
    return prompts[idx], outputs[idx]


def arrival_times(traffic: dict, horizon_s: float, seed: int) -> np.ndarray:
    """Due times in seconds from 0, increasing, covering ``horizon_s``.

    ``backlog``: ``count`` requests all due at 0. ``poisson``: exponential
    gaps at ``rate_per_s``; with ``burst_every`` = k and ``burst_size`` = b,
    every k-th arrival brings b - 1 more at the same instant. The gaps are a
    fixed multiset from ``mix_seed``, reordered by the seed."""
    arr = traffic["arrivals"]
    if arr["kind"] == "backlog":
        return np.zeros(int(arr["count"]))
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    rate = float(arr["rate_per_s"])
    n = int(np.ceil(rate * horizon_s * 1.25)) + 16
    gaps = _rng(traffic["mix_seed"], 3).exponential(1.0 / rate, n)
    gaps = gaps[_rng(seed, 4).permutation(n)]
    times = np.cumsum(gaps)
    every, size = int(arr.get("burst_every", 0)), int(arr.get("burst_size", 1))
    if every > 0 and size > 1:
        times = np.sort(np.concatenate(
            [times] + [times[every - 1::every]] * (size - 1)))
    return times[times < horizon_s]


def serve_requests(traffic: dict, horizon_s: float, seed: int, vocab: int):
    """The run's requests, in due order: a list of dicts ``id``, ``due_s``,
    ``prompt`` (token ids), ``max_new_tokens``."""
    due = arrival_times(traffic, horizon_s, seed)
    prompts, outputs = request_sizes(traffic, len(due), seed)
    rng = _rng(seed, 5)
    return [{"id": i, "due_s": float(t),
             "prompt": rng.integers(0, vocab, int(p)).tolist(),
             "max_new_tokens": int(o)}
            for i, (t, p, o) in enumerate(zip(due, prompts, outputs))]


def train_batch(seed: int, step: int, rows: int, block: int,
                vocab: int) -> np.ndarray:
    """The token batch of optimizer step ``step`` (0-based): ``rows``
    sequences of ``block`` uniform token ids, all rows different."""
    return _rng(seed, 6, step).integers(0, vocab, (rows, block),
                                        dtype=np.int32)
