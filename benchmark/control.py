"""The control of ``correct``, and the readings its limits are set from.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 8 --quants int8,fp8

For every seed, in ONE process: a short run of the cell at its own size
(the program's numbers, exactly as a benchmark run compares them), and the
same numbers with the plain reference computed in a lower precision put in
the program's place. Prints every number of every seed, then for each
number the largest that the sound runs gave and the smallest that each
control gave. A limit belongs above the first and below the second. The
benchmark's own runs never call this. Needs the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.lib import harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--quants", default="int8,fp8")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_tpu(cell["chips"])
    cell["control_quants"] = [q for q in args.quants.split(",") if q]
    sound: dict = {}
    control: dict = {q: {} for q in cell["control_quants"]}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        check = harness.Check()
        driver = __import__(f"benchmark.drivers.{cell['driver']}",
                            fromlist=["run"])
        driver.run(cell, seed, args.seconds, None, time.monotonic,
                   time.monotonic(), check)
        check.print()
        for name, value, _, ok, _ in check.rows:
            sound.setdefault(name, []).append(value)
        for quant, ctl in check.controls.items():
            for name, value, limit, ok, _ in ctl.rows:
                control[quant].setdefault(name, []).append(value)
                print(f"[control {quant}] seed {seed} {name} = {value!r} "
                      f"(limit {limit!r}) {'passes' if ok else 'fails'}",
                      flush=True)
            print(f"[control {quant}] seed {seed}: "
                  f"{'NOT correct' if not ctl.ok else 'CORRECT (the limit does not catch it)'}",
                  flush=True)
        rows.append({"seed": seed, "sound": {r[0]: r[1] for r in check.rows},
                     "control": {q: {r[0]: r[1] for r in c.rows}
                                 for q, c in check.controls.items()}})
    print("[control] number: largest sound | smallest control (ratio)")
    for name, values in sound.items():
        line = f"[control] {name}: sound max {max(values)!r} (n={len(values)})"
        for quant, numbers in control.items():
            if name in numbers:
                low = min(numbers[name])
                ratio = low / max(values) if max(values) > 0 else float("inf")
                line += f" | {quant} min {low!r} (x{ratio:.1f})"
        print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": device,
                       "rows": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
