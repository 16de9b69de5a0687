"""Driver ``serve_engine_blocks``: ``serve_engine`` for a cell whose
reference rows are too long for one pass's logits (20,480 positions x 73,448
vocabulary rows in float32 are 6.0 GB beside the weights, and twice that with
a control beside them). It differs from that driver in ONE thing: the gaps
of ``correct`` are taken from ``reference.served_logits`` (the hidden states
of the whole row, the head over the served positions only) where that driver
asks ``reference.forward`` for every position's logits. The loop, the
warm-up, the window, the reduction and the sample are that driver's own
functions, imported; the numbers compared and their limits are the same.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import serve_engine
from benchmark.drivers.serve_engine import (  # noqa: F401  (the one loop)
    Loop,
    pick_sample,
    reduce_run,
    warm_buckets,
)
from benchmark.lib import harness


def served_token_gaps(cell, seed, sample, quants=()) -> dict:
    """``serve_engine.served_token_gaps``'s numbers, a request at a time:
    the row's logits at the positions that chose its served tokens only
    (``len(prompt) - 1`` onward, a span of the traffic's longest output
    rounded up to the reference's head block)."""
    import jax
    import jax.numpy as jnp

    cfg = cell["config"]
    out = {"program": [], **{q: [] for q in quants}}
    if not sample:
        return out
    family = harness.load_family(cfg)
    ref, dtype = family.reference, serve_engine.weights_dtype(cell)
    weights = jax.jit(lambda key: ref.init_weights(key, cfg, dtype))(
        ref.seed_key(seed))
    width = family.reference_row_len(cell)
    step = min(ref.HEAD_BLOCK, width)
    span = min(-(-int(cell["traffic"]["output_len"]["hi"]) // step) * step,
               width // step * step)

    def gaps(weights, row, lo, lows):
        logits = ref.served_logits(weights, row, cfg, lo, span)
        best = logits.max(-1)
        # position lo + i chose the token at lo + i + 1
        nxt = jax.lax.dynamic_slice_in_dim(
            jnp.pad(row, ((0, 0), (0, span))), lo + 1, span, axis=1)
        return [best - jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
                for t in [nxt] + list(lows)]

    # a control's pass is a program of its own: one program that held the
    # float32 pass's hidden states and a control's together would not fit
    # beside the weights (11.2 GB of temporaries at 20,480 positions)
    first_choice = {q: jax.jit(lambda weights, row, lo, q=q: ref.served_logits(
        weights, row, cfg, lo, span, q).argmax(-1)) for q in quants}
    fn = jax.jit(gaps)
    for s in sample:
        seq = list(s["prompt"]) + list(s["tokens"])
        row = np.zeros((1, width), np.int32)
        row[0, :len(seq)] = seq
        first = len(s["prompt"]) - 1
        lo = np.int32(min(first, width - span))   # served_logits clips alike
        lows = [first_choice[q](weights, row, lo) for q in quants]
        got = jax.device_get(fn(weights, row, lo, lows))
        for key, g in zip(("program",) + tuple(quants), got):
            out[key].append(g[0, first - lo:first - lo + len(s["tokens"])])
    return out


def run(cell, seed, seconds, trace_dir, clock, t_process, check) -> dict:
    """``serve_engine.run`` with this file's gaps in the one place it asks
    for them."""
    theirs = serve_engine.served_token_gaps
    serve_engine.served_token_gaps = served_token_gaps
    try:
        return serve_engine.run(cell, seed, seconds, trace_dir, clock,
                                t_process, check)
    finally:
        serve_engine.served_token_gaps = theirs
