"""Rehearsals that cost no chip time. None of them is a chip run and no
number printed here is ever reported as one.

    python3 benchmark/rehearse.py tiny     <cell>   # the driver end to end on the CPU at a tiny size
    python3 benchmark/rehearse.py virtual4 <cell>   # a four-chip cell on four virtual CPU devices
    python3 benchmark/rehearse.py compile  <cell>   # a serving cell's programs compiled at REAL size for a described v5e

``tiny`` and ``virtual4`` shrink the model and the traffic (never the
code path); ``compile`` builds the engine's dispatches from shapes only and
prints the compiler's ``memory_analysis()`` for the decode tick and every
prefill bucket beside the chip's memory. The train step's real-size compile
for a described chip is not here (the trainer places its own parameters on
``jax.devices()``): see PERF.md, Open questions.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cell(name: str, chips: int = 1) -> dict:
    """The cell with its family's tiny model and a tiny traffic: same
    driver, same code."""
    from benchmark.lib import harness

    cell = harness.load_cell(name)
    cell["config"] = dict(harness.load_family(cell["config"]).TINY)
    cell["chips"] = chips
    if cell["driver"] == "train_clm":
        cell["traffic"].update(block_size=64, per_device_train_batch_size=2,
                               gradient_accumulation_steps=2)
    else:
        backlog = cell["traffic"]["arrivals"]["kind"] == "backlog"
        cell["program"]["serve_config"].update(
            max_seqs=4, block_size=8, max_blocks_per_seq=16,
            num_blocks=0 if backlog else 128, prefill_cap_tokens=64)
        cell["traffic"].update(
            prompt_len={"median": 24, "sigma": 0.4, "lo": 8, "hi": 64},
            output_len={"median": 12, "sigma": 0.4, "lo": 4, "hi": 24},
            deck=4 if backlog else None)
        cell["program"]["window"].update(ticks=8, seconds=0.5, drain_s=2.0)
        cell["correct"]["min_tokens"] = 1
        if not backlog:
            cell["traffic"]["arrivals"]["rate_per_s"] = 20.0
    return cell


def run_tiny(name: str, chips: int, seconds: float = 2.0,
             trace: bool = False, seed: int = 2 ** 31 + 12345) -> dict:
    from benchmark import run

    return run.run_cell(tiny_cell(name, chips), seed, seconds, trace,
                        {"platform": "cpu", "kind": "cpu", "count": chips},
                        t_process=time.monotonic())


def compile_serve(name: str) -> None:
    """Decode tick and every prefill bucket of a serving cell, compiled
    for one chip of a described v5e:2x2 from shapes alone."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.drivers import serve_engine
    from benchmark.lib import harness
    from distributed_lion_tpu.serve.engine import ServeConfig, ServingEngine

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(name)
    cfg, sc = cell["config"], dict(cell["program"]["serve_config"])
    family = harness.load_family(cfg)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    dtype = serve_engine.weights_dtype(cell)

    def on_chip(tree, shape=lambda s: s):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            shape(x.shape), x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: family.program_weights(jax.random.key(0), cfg, dtype)))
    pool = ServeConfig(**sc).resolved_num_blocks()
    # the engine itself is built over a token pool of pages (its host
    # tables do not enter the compiled programs); the programs are then
    # lowered with the pool's leaves as the engine lays them out, at the
    # pool's real number of pages
    engine = ServingEngine(family.serve_model(params, cfg, dtype),
                           ServeConfig(**dict(sc, num_blocks=64)))
    pages = on_chip(jax.eval_shape(lambda: engine.pages),
                    shape=lambda s: (pool,) + tuple(s[1:]))
    leaves = jax.tree.leaves(pages)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    print(f"[rehearse] {name}: weights {weights / 1e9:.2f} GB, pool {pool} "
          f"pages in {len(leaves)} leaves {list(leaves[0].shape)} = "
          f"{pool_bytes / 1e9:.2f} GB, chip {hbm / 1e9:.0f} GB")

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    S, W = sc["max_seqs"], sc["max_blocks_per_seq"]
    programs = {"decode": (s((S, W)), s((S,)), s((S,)), s((S,), jnp.bool_),
                           s((S,), jnp.uint32), s((S,)))}
    for bucket in serve_engine.buckets_of(cell):
        programs[f"prefill@{bucket}"] = (
            s((1, W)), s((1, bucket)), s((1,)), s(()), s((), jnp.uint32),
            s(()))
    # the decode program takes its kernel where the backend is a TPU, and
    # this process's backend is the CPU: say "tpu" in its place while the
    # programs lower (as tests/test_chip_compile.py does)
    real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        for label, rest in programs.items():
            inner = engine._dispatches[label.split("@")[0]]["inner"]
            t0 = time.monotonic()
            compiled = jax.jit(inner, donate_argnums=(1,)).lower(
                params, pages, *rest).compile()
            report(label, compiled, time.monotonic() - t0, hbm)
    finally:
        jax.default_backend = real_backend


def report(label: str, compiled, secs: float, hbm: float) -> None:
    m = compiled.memory_analysis()
    live = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    kernels = len(re.findall(r'custom_call_target="tpu_custom_call"',
                             compiled.as_text()))
    print(f"[rehearse]   {label}: compiled in {secs:.1f} s, {kernels} Mosaic "
          f"kernel call(s); arguments {m.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.2f} GB of which aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB -> live {live / 1e9:.2f} GB "
          f"({'fits' if live < hbm else 'DOES NOT FIT'})", flush=True)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) != 2 or argv[0] not in ("tiny", "virtual4", "compile"):
        print(__doc__, file=sys.stderr)
        return 2
    kind, name = argv
    os.environ["JAX_PLATFORMS"] = "cpu"
    if kind == "virtual4":
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, ROOT)
    if kind == "compile":
        compile_serve(name)
        return 0
    result = run_tiny(name, 4 if kind == "virtual4" else 1)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
