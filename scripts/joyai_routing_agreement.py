"""How often the served program and the plain reference send a token to
another set of experts (chip only; not part of the benchmark's runs).

    python3 scripts/joyai_routing_agreement.py [--seed N] [--tokens 2048]

The configuration of ``serve.joyai-llm-flash.backlog-2k`` with the
benchmark's seeded weights, one prompt of ``--tokens`` random ids: the
program's prefill (``models/joyai``: bfloat16, the grouped-matmul kernel)
against ``benchmark/reference/joyai_llm_flash`` (float32). Both route with a
float32 router; the program's router reads a bfloat16 hidden state that
already differs from the reference's by the rounding of the layers below,
so near-tied experts (the 8th and 9th of 256) change places. Prints, for
each expert layer, the share of positions whose set of experts differs and
how many experts differ there, and the last position's logits side by side.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=3000026909)
    ap.add_argument("--tokens", type=int, default=2048)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import harness
    from distributed_lion_tpu.models import joyai
    from distributed_lion_tpu.parallel import expert
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    harness.require_tpu(1)
    cell = harness.load_cell("serve.joyai-llm-flash.backlog-2k")
    cfg = cell["config"]
    family = harness.load_family(cfg)
    ref = family.reference
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(args.seed))
    model = family.serve_model(family.to_program(weights), cfg, jnp.bfloat16)
    T, block = args.tokens, 16
    rows = np.random.default_rng(args.seed & 0xFFFF).integers(
        0, cfg["vocab_size"], (1, T)).astype(np.int32)

    seen: list = []

    def spy(fn):
        def wrapped(*a, **k):
            out = fn(*a, **k)
            seen.append(out[0])
            return out
        return wrapped

    expert.sigmoid_topk_route = spy(expert.sigmoid_topk_route)
    ref.route = spy(ref.route)
    pages = init_page_leaves(model.n_layer, T // block, block,
                             model.page_leaves, jnp.bfloat16)
    tables = jnp.arange(T // block, dtype=jnp.int32)[None]

    def program(params, toks, pages):
        seen.clear()
        logits, _ = joyai.joyai_decode_paged(
            params, toks, model.cfg, pages, tables, jnp.zeros((1,), jnp.int32),
            logit_index=T - 1)
        return logits[0, 0], list(seen)

    def reference(weights, toks):
        seen.clear()
        return ref.forward(weights, toks, cfg)[0, -1], list(seen)

    got, mine = jax.jit(program)(model.params, rows, pages)
    want, theirs = jax.jit(reference)(weights, rows)
    out = {"seed": args.seed, "tokens": T, "layers": []}
    for a, b in zip(mine, theirs):
        a, b = np.sort(np.asarray(a), -1), np.sort(np.asarray(b), -1)
        same = np.asarray([len(np.intersect1d(x, y)) for x, y in zip(a, b)])
        k = a.shape[-1]
        out["layers"].append({
            "positions_with_another_set_pct": 100.0 * float(np.mean(same < k)),
            "experts_differing_mean_where_any": float(
                np.mean(k - same[same < k])) if (same < k).any() else 0.0})
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    out["last_position"] = {
        "logit_abs_diff_max": float(np.abs(got - want).max()),
        "logit_std": float(want.std()),
        "same_argmax": bool(got.argmax() == want.argmax()),
        "reference_gap_of_programs_choice": float(
            want.max() - want[got.argmax()])}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
