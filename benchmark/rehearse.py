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
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"vocab_size": 256, "n_positions": 128, "n_embd": 64, "n_layer": 2,
        "n_head": 4, "layer_norm_epsilon": 1e-5}


def tiny_cell(name: str, chips: int = 1) -> dict:
    """The cell with a tiny model and traffic: same driver, same code."""
    from benchmark.lib import harness

    cell = harness.load_cell(name)
    cell["config"] = dict(TINY)
    cell["chips"] = chips
    if cell["driver"] == "train_clm":
        cell["program"]["flags"]["model_name"] = "tiny"
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
    from benchmark.lib import gpt2_program, harness
    from benchmark.reference import gpt2 as ref
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.serve.engine import (
        ServeConfig,
        ServeModel,
        ServingEngine,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(name)
    cfg, sc = cell["config"], dict(cell["program"]["serve_config"])
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    dtype = jnp.dtype(cell["program"].get("weights_dtype", "bfloat16"))

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda: gpt2_program.to_program(
        ref.init_weights(jax.random.key(0), cfg, dtype))))
    model_cfg = GPT2Config(**gpt2_program.gpt2_config_kwargs(cfg),
                           param_dtype=dtype, compute_dtype=jnp.bfloat16)
    pool = ServeConfig(**sc).resolved_num_blocks()
    # the engine itself is built over a token pool of pages (its host
    # tables do not enter the compiled programs); the programs are then
    # lowered with the pool at its real size
    engine = ServingEngine(ServeModel.for_gpt2(params, model_cfg),
                           ServeConfig(**dict(sc, num_blocks=64)))
    page = jax.ShapeDtypeStruct(
        (pool, sc["block_size"], cfg["n_head"], cfg["n_embd"] // cfg["n_head"]),
        jnp.bfloat16, sharding=chip)
    pages = [{"k": page, "v": page} for _ in range(cfg["n_layer"])]
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool_bytes = 2 * cfg["n_layer"] * page.size * 2
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    print(f"[rehearse] {name}: weights {weights / 1e9:.2f} GB, pool {pool} "
          f"pages = {pool_bytes / 1e9:.2f} GB, chip {hbm / 1e9:.0f} GB")

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    S, W = sc["max_seqs"], sc["max_blocks_per_seq"]
    programs = {"decode": (s((S, W)), s((S,)), s((S,)), s((S,), jnp.bool_),
                           s((S,), jnp.uint32), s((S,)))}
    for bucket in serve_engine.buckets_of(cell):
        programs[f"prefill@{bucket}"] = (
            s((1, W)), s((1, bucket)), s((1,)), s(()), s((), jnp.uint32),
            s(()))
    for label, rest in programs.items():
        inner = engine._dispatches[label.split("@")[0]]["inner"]
        t0 = time.monotonic()
        compiled = jax.jit(inner, donate_argnums=(1,)).lower(
            params, pages, *rest).compile()
        m = compiled.memory_analysis()
        live = m.argument_size_in_bytes + m.temp_size_in_bytes \
            + m.output_size_in_bytes - m.alias_size_in_bytes
        print(f"[rehearse]   {label}: compiled in "
              f"{time.monotonic() - t0:.1f} s; arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB of which aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} GB -> live "
              f"{live / 1e9:.2f} GB ({'fits' if live < hbm else 'DOES NOT FIT'})",
              flush=True)


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
