"""How a lower precision, and one more token, move the set a dots3-note full
layer keeps (chip only, real size).

    python scripts/dots3_selection_agreement.py [--seed N] [--tokens 12288]

Two shares, both from the benchmark's plain reference
(``benchmark/reference/dots3_note.selection_sets``) over one prompt of the
cell's traffic with the cell's seeded weights:

- **margin**: the share of a query's 2,048 kept positions that another
  precision keeps differently (float32 against the matmuls' operands rounded
  to bfloat16, which is what the served program computes in), a full layer
  at a time. Near-tied index scores send the two to other positions at the
  margin of the 2,048; ``correct``'s limits cannot see that share, the
  cell's note gives it.
- **churn**: the share of a query's kept positions that the next query (one
  token later) does not keep: how far the kept set moves from tick to tick.
- **recent**: the share of a query's kept positions that lie among its last
  2,048 (a selection that only kept the recent past would read 100%).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import harness, traffic

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=3000046909)
    ap.add_argument("--tokens", type=int, default=12288)
    ap.add_argument("--workload", default="serve.dots3-note-prev.backlog-12k")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_tpu(cell["chips"])
    cfg = cell["config"]
    family = harness.load_family(cfg)
    ref, topk = family.reference, cfg["index_topk"]
    weights = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.bfloat16))(
        ref.seed_key(args.seed))
    row = np.asarray(traffic._rng(args.seed, 5).integers(
        0, family.vocab(cfg), (1, args.tokens)), np.int32)

    def sets(quant):
        # the weights go in as an operand: closed over, 8 GB of constants
        return jax.jit(lambda w, r: ref.selection_sets(w, r, cfg, quant))(
            weights, row)

    def shares(a, b):
        """Per query past topk: kept by a and not by b, over topk."""
        return np.asarray((a & ~b).sum(-1))[topk:] / topk

    exact, rounded = sets(None), sets("bf16")
    t = np.arange(args.tokens)
    for layer, (a, b) in enumerate(zip(exact, rounded)):
        a, b = a[0], b[0]
        margin = shares(a, b)
        churn = shares(a[:-1], a[1:])
        recent = np.asarray(
            (a & (t[None, :] > t[:, None] - topk)).sum(-1))[topk:] / topk
        print(f"[dots3_selection_agreement] full layer {layer}: queries "
              f"{topk}..{args.tokens - 1} keep {topk} positions each; "
              f"bfloat16 operands keep another position in "
              f"{100 * margin.mean():.2f}% of them (worst query "
              f"{100 * margin.max():.2f}%); the next query drops "
              f"{100 * churn.mean():.1f}% of a query's set; "
              f"{100 * recent.mean():.1f}% of a set lies among the query's "
              f"last {topk} positions (uniform over the context would be "
              f"{100 * np.minimum(1, topk / (t[topk:] + 1)).mean():.1f}%)",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
