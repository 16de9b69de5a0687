"""Driver ``train_clm_lean``: ``train_clm``'s loop, its feed, its clock and
its comparison as they are, for a model whose plain reference fills the chip.

``train_clm.reference_numbers`` keeps the first weights, the weights, a
momentum and a gradient a worker (four float32 trees) while
``leaf_numbers`` lays one more tree out flat beside a tree of random bits:
at 595 M parameters that is 9.5 GB + 9.5 GB of a 16.9 GB chip (the described
v5e's compiler: 9.53 GB of temporaries for the sketch alone), and the
reference's own gradient program holds 6.9 GB beside its output. This file
gives the same numbers from the same functions of the family's reference in
an order that holds three trees at most beside a program's temporaries:

- the first weights are not kept: they are made again from the seed when the
  change after the steps is measured;
- the momentum is made (zeros) only after the first gradient's numbers are
  taken, and the Lion step donates weights and momenta;
- nothing else differs: ``run`` is ``train_clm.run`` with this module's
  ``reference_numbers`` in the place of its own, controls included.

One worker or several; the rows, the steps, the learning rates and the
numbers' keys are ``train_clm``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import train_clm
from benchmark.drivers.train_clm import (
    CHECK_STEPS,
    by_leaf,
    job,
    leaf_numbers,
)
from benchmark.lib import harness, traffic


def reference_numbers(cell: dict, seed: int, quant=None) -> dict:
    """``train_clm.reference_numbers``' numbers (losses of the first
    CHECK_STEPS steps, every worker's first gradient norm and sketch a
    leaf, the norm of every leaf's change after the steps), holding at most
    the weights, the momenta and one step's gradients."""
    import jax
    import jax.numpy as jnp

    cfg, j = cell["config"], job(cell)
    family = harness.load_family(cfg)
    ref = family.reference
    micro = int(cell["correct"].get("reference_micro", 2))
    per_worker = j["accum"] * j["micro"]
    rows = j["world"] * per_worker
    vocab = family.vocab(cfg)
    make = jax.jit(lambda key: ref.init_weights(key, cfg, jnp.float32))
    grad_fn = jax.jit(lambda w, r: ref.loss_and_grad(w, r, cfg, micro, quant))
    step_fn = jax.jit(lambda w, m, g, lr: ref.vote_lion_step(
        w, m, g, lr, j["wd"], j["b1"], j["b2"]), donate_argnums=(0, 1))
    numbers_fn = jax.jit(lambda g: {k: v for k, v in leaf_numbers(
        family.program_leaves(family.to_program(g)),
        lead=0).items() if k != "keys"})
    w = make(ref.seed_key(seed))
    momenta = None
    out: dict = {"loss": []}
    for s in range(CHECK_STEPS):
        batch = traffic.train_batch(seed, s, rows, j["block"], vocab)
        losses, grads, per = [], [], []
        for worker in range(j["world"]):
            loss, g = grad_fn(w, batch[worker * per_worker:
                                       (worker + 1) * per_worker])
            losses.append(loss)
            grads.append(g)
            if s == 0:
                per.append(jax.device_get(numbers_fn(g)))
        out["loss"].append(float(np.mean(jax.device_get(losses))))
        if s == 0:
            out.update(by_leaf(np.stack([p["norm"] for p in per]),
                               np.stack([p["sketch"] for p in per]),
                               family.leaf_keys(cfg)))
            momenta = [jax.tree.map(jnp.zeros_like, g) for g in grads]
        lr = ref.cosine_warmup_lr(s, j["lr"], j["warmup"], j["max_steps"])
        w, momenta = step_fn(w, momenta, grads, lr)
        del grads, g
    del momenta
    # the first weights again, as a program of their own: made inside the
    # subtraction they fuse into it and round otherwise
    delta = jax.jit(lambda a, b: family.reference_leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(w, make(ref.seed_key(seed)))
    out["update_norms"] = {k: float(v)
                           for k, v in jax.device_get(delta).items()}
    return out


def run(cell: dict, seed: int, seconds: float, trace_dir, clock, t_process,
        check) -> dict:
    """``train_clm.run`` with this module's ``reference_numbers``."""
    real = train_clm.reference_numbers
    train_clm.reference_numbers = reference_numbers
    try:
        return train_clm.run(cell, seed, seconds, trace_dir, clock,
                             t_process, check)
    finally:
        train_clm.reference_numbers = real
