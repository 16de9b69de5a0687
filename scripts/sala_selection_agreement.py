"""How often the served program and the plain reference keep another set of
blocks in a ``minicpm4`` layer (chip only; not part of the benchmark's runs).

    python3 scripts/sala_selection_agreement.py [--seed N] [--tokens 16384]

The configuration of ``serve.minicpm-sala.backlog-16k`` with the benchmark's
seeded weights, one prompt of ``--tokens`` random ids: the program's prefill
(``models/minicpm_sala``: bfloat16, selection tile by tile in
``ops/sparse_select.kept_blocks``) against
``benchmark/reference/minicpm_sala.selection_sets`` (float32, query by
query). Both score, sum and rank in float32; the program's queries and
compressed keys are bfloat16 and already differ from the reference's by the
rounding of the layers below, so near-tied blocks (the 64th and 65th of up
to 256) change places. Prints, for each ``minicpm4`` layer, the share of
(position past ``dense_len``, kv head) pairs whose sets differ and how many
blocks differ there.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=3000037909)
    ap.add_argument("--tokens", type=int, default=16384)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import harness
    from distributed_lion_tpu.models.minicpm_sala import (
        minicpm_sala_decode_paged,
    )
    from distributed_lion_tpu.ops import sparse_select as ss
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    harness.require_tpu(1)
    cell = harness.load_cell("serve.minicpm-sala.backlog-16k")
    cfg = cell["config"]
    family = harness.load_family(cfg)
    ref = family.reference
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(args.seed))
    model = family.serve_model(family.to_program(weights), cfg, jnp.bfloat16)
    T, block = args.tokens, 16
    dense = cfg["sparse_config"]["dense_len"]
    rows = np.random.default_rng(args.seed & 0xFFFF).integers(
        0, cfg["vocab_size"], (1, T)).astype(np.int32)

    seen: dict = {}
    layers = itertools.count()
    kept_blocks = ss.kept_blocks

    def spy(q, ck, p, sp, n_blocks):
        out = kept_blocks(q, ck, p, sp, n_blocks)
        layer = next(layers)       # traced once a layer, in layer order

        def keep(o, p):
            seen.setdefault(layer, {})[int(p[0])] = np.asarray(o)
        jax.debug.callback(keep, out, p)
        return out

    ss.kept_blocks = spy
    pages = init_page_leaves(
        model.n_layer, T // block, block, model.page_leaves, jnp.bfloat16,
        state=(model.state_layers, 1, model.state_leaves))
    tables = jnp.arange(T // block, dtype=jnp.int32)[None]
    zero = jnp.zeros((1,), jnp.int32)
    # weights are ARGUMENTS: closed over, 5.64 GB of constants in the
    # program take the host's 40 GiB to compile (my chip run, PR 37)
    out = jax.jit(lambda params, t, pg: minicpm_sala_decode_paged(
        params, t, model.cfg, pg, tables, zero, zero,
        logit_index=T - 1)[0])(model.params, rows, pages)
    jax.block_until_ready(out)
    jax.effects_barrier()
    ss.kept_blocks = kept_blocks
    sets = jax.jit(lambda w, r: ref.selection_sets(w, r, cfg))(weights, rows)
    report = []
    for layer, want in zip(sorted(seen), sets):
        tiles = seen[layer]
        got = np.concatenate([tiles[p] for p in sorted(tiles)])  # [T', G, nb]
        first = min(tiles)
        want = np.asarray(want[0, first:first + len(got)])
        at = first + np.arange(len(got))
        reach = np.arange(got.shape[-1])[None, :] <= (at // 64)[:, None]
        differ = ((got != want) & reach[:, None, :]).sum(-1)    # [T', G]
        report.append({
            "layer": layer, "positions": int(len(got)), "first": int(first),
            "pairs_differing_pct": 100.0 * float((differ > 0).mean()),
            "blocks_differing_where_they_do":
                float(differ[differ > 0].mean() / 2) if differ.any() else 0.0})
        print(f"[agreement] minicpm4 layer {layer}: positions "
              f"{first}..{first + len(got) - 1} (dense_len {dense}), "
              f"{report[-1]['pairs_differing_pct']:.2f}% of (position, kv "
              f"head) pairs keep another set, "
              f"{report[-1]['blocks_differing_where_they_do']:.2f} blocks of "
              f"64 differ where they do", flush=True)
    print(json.dumps({"seed": args.seed, "tokens": T, "layers": report}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
