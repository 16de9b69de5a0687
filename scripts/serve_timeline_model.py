"""A backlog cell's timeline without the chip: which seeds read high or low.

    python scripts/serve_timeline_model.py serve.laguna-s-2.1.backlog-8k \\
        --seeds 3000030541,77035 [--random 600]

The benchmark's generator deals the cell's deck for a seed
(``benchmark/lib/traffic.request_sizes``); this plays the deal through the
engine's admission rule (``ServingEngine._admit``: first pending request
always, further ones while their padded bucket fits ``prefill_cap_tokens``)
and the driver's window (``drivers/serve_engine``: opens ``window.ticks``
ticks after every slot is full, closes on the first tick past
``--seconds``), with a decode tick and a prefill at MEASURED costs, and
prints tokens/s, ticks and prefills a seed, and over ``--random`` seeds the
standard deviation and how many sets of six spread under 3% with the
farthest run left out (the driver's admission rule for a new cell).

The defaults are cell 6's costs after PR 30 (PERF.md section 5: a tick
21.8 ms, 6 ms beside a tick's first prefill, a prefill 16 / 27.7 / 44.9 /
80.9 / 152.3 ms by bucket at the mean pad of 28%, half of a prefill's cost
falling with its pad). With them it read the 12 untraced runs of calls I
and J within 1.2% each (PERF.md section 6, PR 30): the cell's seed-to-seed
spread is the deal and nothing else. It is a model: no number it prints is
a measurement.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
from collections import deque

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers.serve_engine import bucket_tokens  # noqa: E402
from benchmark.lib import harness, traffic  # noqa: E402


def run(cell, seed, costs, tick, beside, pad_share, seconds):
    sc, win = cell["program"]["serve_config"], cell["program"]["window"]
    tr = cell["traffic"]
    sizes = traffic.request_sizes(tr, int(tr["arrivals"]["count"]), seed)
    queue = deque(zip(*(s.tolist() for s in sizes)))
    slots = [None] * sc["max_seqs"]
    t, ticks, full, t_open, log = 0.0, 0, None, None, []
    while True:
        if t_open is None:
            if full is None and None not in slots:
                full = ticks
            if full is not None and ticks - full >= int(win["ticks"]):
                t_open = t
        elif log[-1][0] - t_open >= seconds:
            break
        budget, admitted, tokens = sc["prefill_cap_tokens"], 0, 0
        while queue and None in slots:
            prompt, out = queue[0]
            padded = bucket_tokens(prompt, sc["block_size"],
                                   sc["max_blocks_per_seq"])
            if admitted and padded > budget:
                break
            queue.popleft()
            t += costs[padded] / (1 - pad_share * 0.28) * (
                1 - pad_share * (1 - prompt / padded))
            t += 0 if admitted else beside
            budget -= padded
            admitted += 1
            tokens += 1
            slots[slots.index(None)] = out - 1 if out > 1 else None
        for i, left in enumerate(slots):
            if left is not None:
                tokens += 1
                slots[i] = left - 1 if left > 1 else None
        t += tick
        ticks += 1
        log.append((t, tokens, admitted))
    inside = [x for x in log if x[0] > t_open]
    return (sum(x[1] for x in inside) / (inside[-1][0] - t_open),
            len(inside), sum(x[2] for x in inside))


def spread(values, drop_farthest=True):
    """Quartile distance over the median (``statistics.quantiles``), the
    run farthest from the median left out where that narrows it."""
    def one(v):
        q = statistics.quantiles(v, n=4)
        return (q[2] - q[0]) / statistics.median(v)
    if not drop_farthest:
        return one(values)
    mid = statistics.median(values)
    rest = sorted(values, key=lambda x: abs(x - mid))[:-1]
    return min(one(values), one(rest))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--random", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--tick_ms", type=float, default=21.8)
    ap.add_argument("--beside_ms", type=float, default=6.0)
    ap.add_argument("--pad_share", type=float, default=0.5)
    ap.add_argument("--prefill_ms", default="512:16,1024:27.7,2048:44.9,"
                    "4096:80.9,8192:152.3")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    costs = {int(k): float(v) / 1e3 for k, v in
             (kv.split(":") for kv in args.prefill_ms.split(","))}

    def read(seed):
        return run(cell, seed, costs, args.tick_ms / 1e3,
                   args.beside_ms / 1e3, args.pad_share, args.seconds)

    named = [int(s) for s in args.seeds.split(",") if s]
    for seed in named:
        rate, ticks, prefills = read(seed)
        print(f"seed {seed}: {rate:.1f} tokens/s, {ticks} ticks, "
              f"{prefills} prefills")
    if len(named) >= 4:
        rates = [read(s)[0] for s in named]
        print(f"these {len(named)}: spread {100 * spread(rates, False):.2f}%"
              f", {100 * spread(rates):.2f}% with the farthest left out")
    if args.random:
        seeds = np.random.default_rng(0).integers(1, 2 ** 31, args.random)
        rates = [read(int(s))[0] for s in seeds]
        sets = [spread(rates[i:i + 6]) for i in range(0, len(rates) - 5, 6)]
        print(f"{args.random} random seeds: median "
              f"{statistics.median(rates):.1f}, standard deviation "
              f"{100 * statistics.stdev(rates) / statistics.mean(rates):.2f}"
              f"%; sets of six under 3%: "
              f"{sum(s < 0.03 for s in sets)} of {len(sets)}")


if __name__ == "__main__":
    main()
