"""Run a cell's two sets of runs and print each metric's spread, the way
the contract measures a bound: two sets of the same seeds, one fresh process
a run; a spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the bound
is about five times the wider of the two sets' spreads, never under 1%.

    python3 benchmark/sets.py --workload <cell> --seeds 1,2,3,4,5,6 [--out chiprun_out/sets.jsonl]

This parent never touches JAX (one process holds the chip at a time).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for which in range(args.sets):
        for seed in seeds:
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", "0"],
                capture_output=True, text=True)
            lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
            try:
                result = json.loads(lines[-1])
            except (IndexError, ValueError):
                result = None
            row = {"set": which, "seed": seed, "rc": proc.returncode,
                   "result": result,
                   "checks": [ln for ln in lines if ln.startswith("[check]")],
                   # what a far-off run is explained from: step times,
                   # the window's ticks and its slowest ones
                   "notes": [ln for ln in lines if ln.startswith(
                       ("[train_clm]", "[serve_engine]"))]}
            rows.append(row)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            ok = result is not None and result.get("correct")
            values = {k: v["value"] for k, v in
                      (result or {}).get("metrics", {}).items()}
            print(f"[sets] set {which} seed {seed} rc {proc.returncode} "
                  f"correct {ok} {values}", flush=True)
            if not ok:
                print("\n".join(lines[-12:]), flush=True)
                print(proc.stderr[-1500:], flush=True)
    names = sorted({k for r in rows if r["result"]
                    for k in r["result"]["metrics"]})
    for name in names:
        per_set = []
        for which in range(args.sets):
            values = [r["result"]["metrics"][name]["value"] for r in rows
                      if r["set"] == which and r["result"]]
            if name == "setup_s":
                values = values[1:] if which == 0 else values  # first compiles
            if len(values) >= 2:
                per_set.append((statistics.median(values), spread(values),
                                len(values)))
        text = "; ".join(f"set {i}: median {m!r} spread {s:.5f} n={n}"
                         for i, (m, s, n) in enumerate(per_set))
        widest = max((s for _, s, _ in per_set), default=float("nan"))
        print(f"[sets] {name}: {text}; widest {widest:.5f} -> bound "
              f"{max(0.01, 5 * widest):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
