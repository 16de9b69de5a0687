"""Microbenchmark the vote wires at 124M-scale ballot vectors.

Measures wall-clock per vote and trace+compile time for each wire format
(``sign_psum``, ``packed_allgather``, ``packed_a2a``, ``hier:<g>``) over a
mesh — the default backend's devices, or with ``--force-cpu`` a
forced-host-device CPU mesh (collectives are then shared-memory copies, so
absolute latency is a proxy; byte volumes and compile behavior are exact).
The CPU is only ever the caller's choice: without ``--force-cpu`` a backend
holding fewer than ``--world`` devices is an error.

The compile-time column is the point of the scan-based rings
(parallel/collectives._hier_elect): pre-scan, a hier ring at g=16 unrolled
3(g−1) ppermute ops into the trace; now the trace is O(1) in g.

    python scripts/bench_wires.py --n 124000000 --world 8 --force-cpu \
        --wires sign_psum packed_allgather packed_a2a hier:2 hier:4
    python scripts/bench_wires.py --compile-only --world 32 --force-cpu \
        --wires hier:16 --n 65536

Each run prints one JSON line per (wire, world) combo; paste into
scripts/SWEEP_wires.md.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def run_inner(args) -> None:
    import numpy as np

    if args.force_cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_lion_tpu.parallel.collectives import vote_total
    from distributed_lion_tpu.ops.codec import wire_bytes_per_param

    w = args.world
    devs = jax.devices()
    if len(devs) < w:
        raise SystemExit(f"need {w} devices, have {len(devs)}")
    mesh = Mesh(np.array(devs[:w]), ("data",))
    n = args.n
    rng = np.random.default_rng(0)
    # uint8 draw, not rng.random: a float64 [w, n] transient would be ~8 GB
    # at the default 124M-coordinate size
    votes_np = rng.integers(0, 2, (w, n), dtype=np.uint8).astype(bool)

    for wire in args.wires:
        def body(v):
            # chain XOR of the elected bits back into the ballots so
            # repeated votes are data-dependent (no DCE / overlap games)
            elected = vote_total(v[0], "data", wire) > 0
            return jnp.logical_xor(v[0], elected)[None]

        f = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"))
        )
        votes = jax.device_put(
            jnp.asarray(votes_np), NamedSharding(mesh, P("data")))

        t0 = time.perf_counter()
        lowered = f.lower(votes)
        t_trace = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        stablehlo_lines = lowered.as_text().count("\n")

        acct = wire_bytes_per_param(n, w, wire)
        row = {
            "wire": wire,
            "world": w,
            "n": n,
            "backend": devs[0].platform,
            "trace_s": round(t_trace, 3),
            "compile_s": round(t_compile, 3),
            "stablehlo_lines": stablehlo_lines,
            "bits_per_param": acct.get("bits_per_param"),
        }
        if not args.compile_only:
            out = compiled(votes)
            jax.block_until_ready(out)  # warmup
            reps = args.reps
            t0 = time.perf_counter()
            for _ in range(reps):
                out = compiled(out)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / reps
            row["vote_ms"] = round(dt * 1e3, 2)
            row["effective_GBps"] = round(
                acct["bytes_per_step"] / dt / 1e9, 3)
        print(json.dumps(row), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=124_000_000)
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--wires", nargs="+",
                    default=["sign_psum", "packed_allgather", "packed_a2a",
                             "hier:2", "hier:4"])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--force-cpu", action="store_true")
    ap.add_argument("--timeout", type=float, default=1800.0)
    args = ap.parse_args()

    if args.inner:
        run_inner(args)
        return

    # Orchestrate in a child so a hung accelerator backend can't wedge the
    # run and so a forced host-device count lands before jax import. The
    # platform is the CALLER's choice: --force-cpu runs on --world virtual
    # CPU devices; without it the child uses the default backend and fails
    # loudly if that holds fewer than --world devices. Nothing here probes
    # for a device and quietly picks the CPU.
    env = dict(os.environ)
    child = [sys.executable, os.path.abspath(__file__), "--inner",
             "--n", str(args.n), "--world", str(args.world),
             "--reps", str(args.reps), "--wires", *args.wires]
    if args.compile_only:
        child.append("--compile-only")
    if args.force_cpu:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={args.world}")
        child.append("--force-cpu")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(child, timeout=args.timeout, env=env, cwd=repo_root)
    sys.exit(proc.returncode)


if __name__ == "__main__":
    main()
