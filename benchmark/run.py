"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A fresh process per run. Fails (non-zero exit, no result line) unless JAX
finds a TPU with the chips the cell asks for. Everything that belongs to
one cell, configuration, traffic mix, driver or per-layer metric is a file
of its own under ``benchmark/``, found by the names in ``BENCHMARK.json``.
The last line of standard output is the result as one JSON object.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, t_process: float = T_PROCESS,
             dump_trace: str = "") -> dict:
    """Drive one cell and build the result object (no device gate here:
    ``main`` owns it, and the tests call this on the CPU)."""
    meter = harness.CompileMeter()
    check = harness.Check()
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        out = driver.run(cell, seed, seconds, trace_dir, time.monotonic,
                         t_process, check)
        check.print()
        compile_facts = meter.snapshot()
        print(f"[run] compile {compile_facts['compile_s']:.1f} s, persistent "
              f"cache hits {compile_facts['cache_hits']} misses "
              f"{compile_facts['cache_misses']}", flush=True)
        device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
        result = {"correct": check.ok, "attempted": out["attempted"],
                  "failed": out["failed"], "device": device}
        if not trace:
            units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
            result["metrics"] = {
                name: {"value": out["end_to_end"][name], "unit": unit}
                for name, unit in units.items()}
            return result
        from benchmark.lib import xplane

        tr = xplane.load(xplane.find_xplane(trace_dir),
                         stats=bool(dump_trace))
        if dump_trace:
            os.makedirs(os.path.dirname(dump_trace) or ".", exist_ok=True)
            with open(dump_trace, "w") as f:
                json.dump(dict(xplane.skeleton(tr),
                               op_table=xplane.op_table(tr)), f)
        facts = dict(out["facts"], compile=compile_facts,
                     end_to_end=out["end_to_end"],
                     memory_peak_bytes=out["memory_peak_bytes"])
        ctx = {"cell": cell, "trace": tr, "facts": facts,
               "peaks": harness.peaks_for(device["kind"])
               if device["platform"] == "tpu" else None}
        metrics = {}
        for m in cell["per_layer"]:
            value = harness.load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=xplane.device_busy_s(tr),
                      window_s=facts["trace"]["window_s"])
        result["breakdown"] = {"device_ops": xplane.top_ops(tr, 10),
                               "idle_gaps": xplane.idle_gaps(tr, 10)}
        return result
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="also write a small copy of the trace here (JSON)")
    ap.add_argument("--override", default="",
                    help="JSON laid over the cell's files, for trial runs "
                         "only (a rate sweep); never used by the driver")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if "candidate" in cell:
        print(f"[run] {args.workload} is a CANDIDATE, not a cell of "
              "BENCHMARK.json: a trial", flush=True)
    if args.override:
        cell = harness.merge(cell, json.loads(args.override))
        print(f"[run] OVERRIDE {args.override}: a trial, not the cell",
              flush=True)
    device = harness.require_tpu(cell["chips"])
    harness.peaks_for(device["kind"])
    print(f"[run] {cell['name']} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} on {device}", flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                      dump_trace=args.dump_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
