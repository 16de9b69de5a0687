"""Driver ``train_clm``: one training cell through ``cli/run_clm.main``.

The program is driven through its own entry point and its own loop
(``Trainer.train``). The benchmark supplies the weights (made on the device
from ``--seed``, in the layout the configuration's family gives:
``harness.load_family``; this file names no family and reads no key of a
configuration), the token batches (its own iterator, also from the seed)
and the clock. The compiled step and its state that set-up drives through
the first ``CHECK_STEPS`` steps is the same object the window then times.

Timing, from the benchmark's side only: every ``next()`` of the feed runs
after one more step was dispatched. The feed keeps a tiny device marker
``state.count + 0`` per step and blocks on the marker of two steps ago, so
the host never runs more than two steps ahead and the device never waits
for it; the return of that block is the step's drained end. The window
opens at a full drain after the check steps and closes at the first
drained end at or past ``--seconds``.
"""

from __future__ import annotations

import contextlib
import gc
import statistics
import time

import numpy as np

from benchmark.lib import harness, traffic

CHECK_STEPS = 3          # steps the reference follows (set-up, not timed)
RUN_AHEAD = 2            # steps the host may lead the device by
TRACE_AFTER = 2          # timed steps before the traced sub-window opens
TRACE_MIN_STEPS, TRACE_MIN_S, TRACE_MAX_STEPS = 2, 2.0, 24
SKETCH = 32              # random-sign projections a leaf's gradient gets


def leaf_numbers(leaves: dict, lead: int) -> dict:
    """For every leaf (``leaves`` maps a key to an array whose leading
    ``lead`` axes are workers): its L2 norm and ``SKETCH`` projections on
    fixed random +-1 vectors (the bits of one seeded uint32 per
    coordinate). The sketch is linear: unlike a norm it moves in
    proportion to an error in the gradient, and unlike a plain sum an
    error of one sign does not pile up in it. One flat pass over all
    leaves in key order, so both sides of the comparison use the same
    signs and the program compiles quickly."""
    import jax
    import jax.numpy as jnp

    keys = sorted(leaves, key=str)
    flats = [leaves[k].astype(jnp.float32).reshape(
        leaves[k].shape[:lead] + (-1,)) for k in keys]
    sizes = [f.shape[-1] for f in flats]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    flat = jnp.concatenate(flats, axis=-1)
    bits = jax.random.bits(jax.random.key(20220), (flat.shape[-1],),
                           jnp.uint32)

    def per_leaf(x):
        return jnp.stack([x[..., a:b].sum(-1)
                          for a, b in zip(bounds, bounds[1:])], axis=-1)

    def one(_, k):
        sign = 1.0 - 2.0 * ((bits >> k) & 1).astype(jnp.float32)
        return None, per_leaf(flat * sign)

    _, proj = jax.lax.scan(one, None, jnp.arange(SKETCH, dtype=jnp.uint32))
    proj = jnp.moveaxis(proj, 0, -1)            # [..., leaves, SKETCH]
    norms = jnp.sqrt(per_leaf(flat * flat))     # [..., leaves]
    return {"keys": keys, "norm": norms, "sketch": proj}


class _WindowClosed(Exception):
    """Raised by the feed inside ``Trainer.train`` to end the run."""


def job(cell: dict) -> dict:
    """Sizes of the cell's job: batch shape from the traffic mix, the
    optimizer's settings from the cell's ``program`` flags."""
    flags = flags_of(cell)
    return {"block": int(flags["block_size"]),
            "micro": int(flags["per_device_train_batch_size"]),
            "accum": int(flags["gradient_accumulation_steps"]),
            "world": int(cell["chips"]),
            "lr": float(flags["learning_rate"]),
            "wd": float(flags["weight_decay"]),
            "warmup": int(flags["warmup_steps"]),
            "max_steps": int(flags["max_steps"]),
            "b1": float(flags.get("beta1", 0.9)),
            "b2": float(flags.get("beta2", 0.99))}


def flags_of(cell: dict) -> dict:
    """The program's flags: the cell's own, the model as the family names
    it to the trainer, the batch shape from the traffic mix."""
    sizes = {k: cell["traffic"][k] for k in (
        "block_size", "per_device_train_batch_size",
        "gradient_accumulation_steps")}
    family = harness.load_family(cell["config"])
    return {**cell["program"]["flags"],
            **family.train_flags(cell["config"]), **sizes}


def argv_of(cell: dict) -> list:
    argv = []
    for key, value in flags_of(cell).items():
        if value is True:
            argv.append(f"--{key}")
        elif value is not False:
            argv += [f"--{key}", str(value)]
    # the program never sees the run's seed: weights and tokens come from
    # the benchmark, and a seed in the program's own flags could only
    # change what it compiles
    return argv


class Feed:
    """The benchmark's batch iterator: inputs, clock and captures."""

    def __init__(self, trainer, cell, seed, seconds, trace_dir, clock,
                 t_process):
        self.tr, self.cell, self.seed = trainer, cell, seed
        self.seconds, self.trace_dir, self.clock = seconds, trace_dir, clock
        self.t_process = t_process
        self.family = harness.load_family(cell["config"])
        self.job = job(cell)
        self.rows = self.job["world"] * self.job["accum"] * self.job["micro"]
        self.vocab = self.family.vocab(cell["config"])
        self.calls = 0
        self.marks: dict = {}
        self.done: dict = {}
        self.captured: dict = {"loss": []}
        self.t_open = None
        self.trace = {"state": "off" if not trace_dir else "armed"}
        self._real_step = None

    # -- captures of the check steps ------------------------------------
    def _spy_on(self):
        tr = self.tr
        self._real_step = tr._train_step

        def spied(*args):
            out = self._real_step(*args)
            self.captured["loss"].append(out[3]["loss"])
            return out

        tr._train_step = spied

    def _spy_off(self):
        self.tr._train_step = self._real_step

    def _grad_numbers(self):
        """Norm and sketch of every leaf of every worker's first gradient,
        worked out from the momentum after one step (it started at zero:
        m1 = (1 - b2) g1)."""
        import jax

        scale = 1.0 / (1.0 - self.job["b2"])

        def numbers(tree):
            out = leaf_numbers(self.family.program_leaves(tree), lead=1)
            return {"norm": scale * out["norm"],
                    "sketch": scale * out["sketch"]}

        return jax.jit(numbers)(self.tr.state.exp_avg)

    def _update_norms(self):
        import jax
        import jax.numpy as jnp

        cfg, family = self.cell["config"], self.family

        def norms(params, key):
            start = family.program_weights(key, cfg, jnp.float32)
            return jax.tree.map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                params, start)

        return jax.jit(norms)(self.tr.params,
                              family.reference.seed_key(self.seed))

    # -- the iterator ----------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self.trace["state"] != "on":
            return self._next()
        import jax

        with jax.profiler.TraceAnnotation("bench/feed"):
            return self._next()

    def _next(self):
        import jax

        k = self.calls            # steps dispatched so far
        self.calls += 1
        if k == 0:
            self._spy_on()
        elif k == 1:
            self.captured["grad"] = self._grad_numbers()
        if k == CHECK_STEPS:
            self._spy_off()
            self.captured["update_norms"] = self._update_norms()
            jax.block_until_ready((self.tr.params, self.captured))
            self.captured = jax.device_get(self.captured)
            self.t_open = self.clock()          # set-up ends here
            self.setup_s = self.t_open - self.t_process
        elif k > CHECK_STEPS:
            self._after_step(k)
        return traffic.train_batch(self.seed, k, self.rows,
                                   self.job["block"], self.vocab)

    def _after_step(self, k: int) -> None:
        import jax

        self.marks[k] = self.tr.state.count + 0
        self._trace_edges(k)
        j = k - RUN_AHEAD
        if j in self.marks:
            jax.block_until_ready(self.marks.pop(j))
            self.done[j] = self.clock()
            if self.done[j] - self.t_open >= self.seconds and \
                    self.trace["state"] in ("off", "done"):
                for later in sorted(self.marks):
                    jax.block_until_ready(self.marks.pop(later))
                raise _WindowClosed

    def _trace_edges(self, k: int) -> None:
        import jax

        t = self.trace
        if t["state"] == "armed" and k == CHECK_STEPS + TRACE_AFTER:
            jax.block_until_ready(self.tr.params)
            from benchmark.lib.tracing import start_trace

            start_trace(self.trace_dir)
            t.update(state="on", k0=k, t0=self.clock())
        elif t["state"] == "on":
            n, dt = k - t["k0"], self.clock() - t["t0"]
            if (n >= TRACE_MIN_STEPS and dt >= TRACE_MIN_S) \
                    or n >= TRACE_MAX_STEPS:
                jax.block_until_ready(self.tr.params)
                t.update(t1=self.clock())
                jax.profiler.stop_trace()
                t.update(state="done", steps=n, window_s=t["t1"] - t["t0"])


def run(cell: dict, seed: int, seconds: float, trace_dir, clock, t_process,
        check) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.cli import run_clm
    from distributed_lion_tpu.train import loop

    state: dict = {}
    family = harness.load_family(cell["config"])
    real_train = loop.Trainer.train

    def train(self, train_iter, eval_blocks=None, max_steps=None):
        # the program's own weights are replaced by the benchmark's, made
        # on the device from the seed in one call (same tree, same places)
        self.params = harness.seeded_weights(
            family, cell["config"], seed, jnp.float32,
            jax.tree.map(lambda p: p.sharding, self.params))
        feed = Feed(self, cell, seed, seconds, trace_dir, clock, t_process)
        state.update(feed=feed, n_params=self.n_params,
                     wire=self.cfg.wire, vote_buckets=self.cfg.vote_buckets)
        if trace_dir:
            # the dispatch as a span on the profiler's clock (traced runs
            # only: they report no end-to-end metric)
            inner = self._train_step

            def annotated(*args):
                with jax.profiler.TraceAnnotation("bench/dispatch"):
                    return inner(*args)

            self._train_step = annotated
        try:
            real_train(self, feed, eval_blocks=None)
        except _WindowClosed:
            pass
        state["memory_peak_bytes"] = harness.memory_peak_bytes()
        raise _WindowClosed  # leave run_clm.main before its eval and save

    loop.Trainer.train = train
    try:
        with contextlib.suppress(_WindowClosed):
            run_clm.main(argv_of(cell))
    finally:
        loop.Trainer.train = real_train
    feed = state.pop("feed")
    program = feed.captured
    j = feed.job
    done = sorted(feed.done.items())
    steps = len(done)
    elapsed = done[-1][1] - feed.t_open if done else float("nan")
    tokens_per_step = feed.rows * j["block"]
    step_s = [b[1] - a[1] for a, b in zip([(0, feed.t_open)] + done, done)]
    print(f"[train_clm] {steps} whole steps in {elapsed:.3f} s; step time "
          f"median {statistics.median(step_s):.4f} s n={len(step_s)} "
          f"(min {min(step_s):.4f} max {max(step_s):.4f}); wire "
          f"{state['wire']} x {state['vote_buckets']} buckets", flush=True)
    feed.tr = None
    gc.collect()

    t_ref = time.monotonic()
    reference = reference_numbers(cell, seed, quant=None)
    compare(program_numbers_as_reference(program, cell["config"], family),
            reference, cell["correct"]["limits"], check)
    print(f"[train_clm] reference: {time.monotonic() - t_ref:.1f} s "
          "(after the window, not in setup_s)", flush=True)
    for quant in cell.get("control_quants", ()):     # benchmark/control.py
        ctl = harness.Check()
        compare(reference_numbers(cell, seed, quant), reference,
                cell["correct"]["limits"], ctl)
        check.controls[quant] = ctl

    rate = steps * tokens_per_step / elapsed / j["world"]
    return {
        "end_to_end": {"train_tokens_per_s_per_chip": rate,
                       "setup_s": feed.setup_s},
        "attempted": steps, "failed": 0,
        "memory_peak_bytes": state["memory_peak_bytes"],
        "facts": {"steps": steps, "elapsed_s": elapsed, "step_s": step_s,
                  "tokens_per_step": tokens_per_step, "world": j["world"],
                  "n_params": state["n_params"], "job": j,
                  "trace": feed.trace},
    }


# ------------------------------------------------------------ correctness
def reference_numbers(cell: dict, seed: int, quant=None) -> dict:
    """Losses of the first CHECK_STEPS steps, every worker's first
    gradient norm per leaf and the norm of every leaf's change after the
    steps, from the plain reference (or, with ``quant``, the control)."""
    import jax
    import jax.numpy as jnp

    cfg, j = cell["config"], job(cell)
    family = harness.load_family(cfg)
    ref = family.reference
    micro = int(cell["correct"].get("reference_micro", 2))
    per_worker = j["accum"] * j["micro"]
    rows = j["world"] * per_worker
    vocab = family.vocab(cfg)
    w0 = jax.jit(lambda key: ref.init_weights(key, cfg, jnp.float32))(
        ref.seed_key(seed))
    grad_fn = jax.jit(lambda w, r: ref.loss_and_grad(w, r, cfg, micro, quant))
    step_fn = jax.jit(lambda w, m, g, lr: ref.vote_lion_step(
        w, m, g, lr, j["wd"], j["b1"], j["b2"]))
    numbers_fn = jax.jit(lambda g: {k: v for k, v in leaf_numbers(
        family.program_leaves(family.to_program(g)),
        lead=0).items() if k != "keys"})
    w = w0
    momenta = [jax.tree.map(jnp.zeros_like, w0) for _ in range(j["world"])]
    out: dict = {"loss": []}
    for s in range(CHECK_STEPS):
        batch = traffic.train_batch(seed, s, rows, j["block"], vocab)
        losses, grads = [], []
        for worker in range(j["world"]):
            loss, g = grad_fn(w, batch[worker * per_worker:
                                       (worker + 1) * per_worker])
            losses.append(loss)
            grads.append(g)
        out["loss"].append(float(np.mean(jax.device_get(losses))))
        if s == 0:
            per = [jax.device_get(numbers_fn(g)) for g in grads]
            out.update(by_leaf(np.stack([p["norm"] for p in per]),
                               np.stack([p["sketch"] for p in per]),
                               family.leaf_keys(cfg)))
        lr = ref.cosine_warmup_lr(s, j["lr"], j["warmup"], j["max_steps"])
        w, momenta = step_fn(w, momenta, grads, lr)
        del grads
    delta = jax.jit(lambda a, b: family.reference_leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(w, w0)
    out["update_norms"] = {k: float(v)
                           for k, v in jax.device_get(delta).items()}
    return out


def by_leaf(norm, sketch, keys: list) -> dict:
    """``[workers, leaves]`` norms and ``[workers, leaves, SKETCH]``
    sketches as dicts keyed by leaf (``keys``: the family's ``leaf_keys``,
    put in :func:`leaf_numbers`' order here)."""
    keys = sorted(keys, key=str)
    norm, sketch = np.asarray(norm, np.float64), np.asarray(sketch, np.float64)
    return {"grad_norms": {k: norm[:, i] for i, k in enumerate(keys)},
            "grad_sketch": {k: sketch[:, i] for i, k in enumerate(keys)}}


def program_numbers_as_reference(program: dict, cfg: dict, family) -> dict:
    """The feed's captures, keyed like the reference's numbers."""
    upd = family.program_leaves(program["update_norms"])
    return {"loss": [float(x) for x in program["loss"]],
            **by_leaf(program["grad"]["norm"], program["grad"]["sketch"],
                      family.leaf_keys(cfg)),
            "update_norms": {k: float(v) for k, v in upd.items()}}


def worst_sketch_gap(got: dict, want: dict) -> tuple:
    """The widest distance between a leaf's sketch and the reference's
    (per worker), against the length of the reference's sketch of that
    leaf or of the median leaf, whichever is larger. Estimates the
    relative error of the leaf's gradient."""
    length = {k: np.linalg.norm(np.asarray(v, np.float64), axis=-1)
              for k, v in want.items()}
    median = statistics.median(float(np.max(v)) for v in length.values())
    worst, where = 0.0, None
    for key, ref_blocks in want.items():
        diff = np.linalg.norm(np.asarray(got[key], np.float64)
                              - np.asarray(ref_blocks, np.float64), axis=-1)
        gap = float(np.max(diff / np.maximum(length[key], median)))
        if not gap <= worst:
            worst, where = gap, key
    return worst, where


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The widest gap between a leaf's norm and the reference's, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns (gap, leaf)."""
    median = statistics.median(float(np.max(v)) for v in want.values())
    worst, where = 0.0, None
    for key, ref_norm in want.items():
        ref_norm = np.asarray(ref_norm, np.float64).reshape(-1)
        mine = np.asarray(got[key], np.float64).reshape(-1)
        gap = float(np.max(np.abs(mine - ref_norm)
                           / np.maximum(ref_norm, median)))
        if not gap <= worst:   # also catches NaN
            worst, where = gap, key
    return worst, where


def compare(program: dict, reference: dict, limits: dict, check) -> None:
    if len(program["loss"]) != CHECK_STEPS:
        check.fail("loss_steps", f"{len(program['loss'])} losses captured")
        return
    for s, (got, want) in enumerate(zip(program["loss"], reference["loss"])):
        check.add(f"loss_gap.step{s + 1}", abs(got - want) / abs(want),
                  limits["loss_gap"], f"program {got!r} reference {want!r}")
    gap, leaf = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    check.add("grad_norm_gap", gap, limits["grad_norm_gap"],
              f"worst leaf {leaf}")
    gap, leaf = worst_sketch_gap(program["grad_sketch"],
                                 reference["grad_sketch"])
    check.add("grad_sketch_gap", gap, limits["grad_sketch_gap"],
              f"worst leaf {leaf}")
    gap, leaf = worst_leaf_gap(program["update_norms"],
                               reference["update_norms"])
    check.add("update_norm_gap", gap, limits["update_norm_gap"],
              f"worst leaf {leaf}")
