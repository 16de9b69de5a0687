"""Driver ``serve_engine``: one serving cell through ``ServingEngine``.

The model comes from the configuration's family (``harness.load_family``:
its ``ServeModel`` over weights made on the device from ``--seed``); this
file names no family and reads no key of a configuration. The driver submits
each request when it is due (open loop: never waits for a reply), calls
``engine.step()`` in a loop, and stamps its own wall clock after every step
against ``engine.stats['ticks']``; ``lib/ticklog`` turns the engine's tick
clocks into times. A backlog is the same loop with every request due at 0.

The window opens after the cell's warm-up (``program.window``): a number of
seconds of the same arrivals, or a number of ticks with every slot full.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from benchmark.lib import harness, ticklog, traffic

OK_REASONS = ("length", "eos")


def bucket_tokens(n: int, block: int, max_blocks: int) -> int:
    """The engine's prefill padding rule (power-of-two pages), copied so
    that set-up can warm exactly the buckets the mix will use."""
    blocks = 1
    while blocks * block < n:
        blocks *= 2
    return min(blocks, max_blocks) * block


def buckets_of(cell: dict) -> list:
    sc, p = cell["program"]["serve_config"], cell["traffic"]["prompt_len"]
    lo = bucket_tokens(p["lo"], sc["block_size"], sc["max_blocks_per_seq"])
    hi = bucket_tokens(p["hi"], sc["block_size"], sc["max_blocks_per_seq"])
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


def weights_dtype(cell: dict):
    import jax.numpy as jnp

    return jnp.dtype(cell["program"].get("weights_dtype", "bfloat16"))


def build_engine(cell: dict, seed: int, family):
    from distributed_lion_tpu.serve.engine import ServeConfig, ServingEngine

    cfg, dtype = cell["config"], weights_dtype(cell)
    params = harness.seeded_weights(family, cfg, seed, dtype)
    return ServingEngine(family.serve_model(params, cfg, dtype),
                         ServeConfig(**cell["program"]["serve_config"]))


def pool_facts(engine) -> dict:
    """The page pool as the engine holds it: the shape of one leaf
    (``[pages, block_size, groups, row width]``), how many leaves (keys and
    values of every layer) and the bytes of a value."""
    import jax

    leaves = jax.tree.leaves(engine.pages)
    return {"leaf_shape": list(leaves[0].shape), "leaves": len(leaves),
            "itemsize": leaves[0].dtype.itemsize}


class Loop:
    """Submit-when-due and step, with the benchmark's stamps."""

    def __init__(self, engine, clock, annotate=False):
        from distributed_lion_tpu.serve.engine import Request

        self.engine, self.clock, self.Request = engine, clock, Request
        self.stamps: dict = {}        # tick -> seconds, after the step
        self.ticks: list = []         # per tick: dict of counts and times
        self.submitted: dict = {}     # id -> (submit_s, submit_tick)
        self.first_tick: dict = {}    # id -> tick of the first token
        self.completions: dict = {}   # id -> Completion
        self.annotate = annotate

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def submit(self, req: dict) -> None:
        eng = self.engine
        self.submitted[req["id"]] = (self.clock(), eng.stats["ticks"])
        eng.submit(self.Request(req_id=req["id"], tokens=req["prompt"],
                                max_new_tokens=req["max_new_tokens"],
                                seed=0))

    def step(self) -> None:
        eng = self.engine
        before = (eng.stats["prefill_dispatches"], eng.stats["decode_tokens"])
        t0 = self.clock()
        with self.span("bench/step"):
            done = eng.step()
        t1 = self.clock()
        tick = eng.stats["ticks"]
        self.stamps[tick] = t1
        active = 0
        for slot in eng.slots:
            if slot is not None:
                active += 1
                self.first_tick.setdefault(slot.req.req_id, tick)
        for c in done:
            self.completions[c.req_id] = c
        self.ticks.append({
            "tick": tick, "t0": t0, "t1": t1, "active": active,
            "pending": len(eng.pending),
            "prefills": eng.stats["prefill_dispatches"] - before[0],
            "decode_tokens": eng.stats["decode_tokens"] - before[1]})

    def first_token_tick(self, req_id):
        c = self.completions.get(req_id)
        if c is not None and c.timing and "ttft_ticks" in c.timing:
            return self.submitted[req_id][1] + int(c.timing["queue_ticks"])
        return self.first_tick.get(req_id)


def warm_buckets(loop: Loop, cell: dict, vocab: int) -> None:
    """Compile (or load from the cache) the decode program and every
    prefill bucket the mix can produce, and no other."""
    hi = int(cell["traffic"]["prompt_len"]["hi"])
    rng = np.random.default_rng(0)
    for i, bucket in enumerate(buckets_of(cell)):
        n = min(bucket, hi)
        loop.submit({"id": f"warm{i}", "max_new_tokens": 3,
                     "prompt": rng.integers(0, vocab, n).tolist()})
    while loop.engine.has_work():
        loop.step()


def run(cell: dict, seed: int, seconds: float, trace_dir, clock, t_process,
        check) -> dict:
    from distributed_lion_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    family = harness.load_family(cell["config"])
    vocab = family.vocab(cell["config"])
    window = cell["program"]["window"]
    drain_s = float(window.get("drain_s", 5.0))
    engine = build_engine(cell, seed, family)
    loop = Loop(engine, clock, annotate=bool(trace_dir))
    warm_buckets(loop, cell, vocab)
    warm_ids = set(loop.submitted)

    warm_s = float(window.get("seconds", 0.0))
    reqs = traffic.serve_requests(cell["traffic"],
                                  warm_s + seconds + 1.0, seed, vocab)
    t0 = clock()
    after_ticks = window["open"] == "after_ticks"
    t_open = None if after_ticks else t0 + warm_s
    t_close = full_tick = None
    stats: dict = {}      # the engine's counters at the windows' edges
    nxt = 0
    trace = {"state": "armed" if trace_dir else "off",
             "after_s": min(float(window.get("trace_after_s", 1.0)),
                            seconds / 4),
             "for_s": min(float(window.get("trace_s", 3.0)), seconds / 2)}
    while True:
        now = clock()
        with loop.span("bench/submit"):
            while nxt < len(reqs) and t0 + reqs[nxt]["due_s"] <= now:
                loop.submit(reqs[nxt])
                nxt += 1
        ticks = engine.stats["ticks"]
        if t_open is None:
            # a backlog's window opens once every slot is full and the
            # cell's number of ticks has run with them full
            if full_tick is None and all(s is not None
                                         for s in engine.slots):
                full_tick = ticks
            if full_tick is not None and \
                    ticks - full_tick >= int(window["ticks"]):
                t_open = loop.ticks[-1]["t1"]
        elif t_close is None:
            if after_ticks and loop.ticks[-1]["t1"] - t_open >= seconds:
                t_close = loop.ticks[-1]["t1"]     # whole ticks only
            elif not after_ticks and now - t_open >= seconds:
                t_close = t_open + seconds
        if t_open is not None and now >= t_open:
            # no tick runs between an edge and its copy of the counters
            edge = _trace_edges(trace, trace_dir, now, t_open, clock)
            for at in ("open", edge):
                if at and at not in stats:
                    stats[at] = dict(engine.stats)
        if t_close is not None and "close" not in stats:
            stats["close"] = dict(engine.stats)
        if t_close is not None and trace["state"] in ("off", "done"):
            waiting = [] if after_ticks else [
                r for r in reqs if t_open <= t0 + r["due_s"] < t_close
                and loop.first_token_tick(r["id"]) is None]
            if not waiting or now - t_close >= drain_s:
                break
        if engine.has_work():
            loop.step()
        else:
            with loop.span("bench/idle_wait"):
                time.sleep(0.0002)
    t_end = clock()
    setup_s = t_open - t_process
    peak = harness.memory_peak_bytes()
    facts = reduce_run(loop, reqs, warm_ids, t0, t_open, t_close, t_end,
                       after_ticks, cell)
    facts.update(trace=trace, engine_stats=stats, kv_pool=pool_facts(engine))
    sample = pick_sample(loop, reqs, t_open, t_close, seed, cell)

    # free the program's state before the reference runs
    loop.engine = None
    del engine
    gc.collect()
    t_ref = time.monotonic()
    quants = tuple(cell.get("control_quants", ()))   # benchmark/control.py
    gaps = served_token_gaps(cell, seed, sample, quants)
    limits = cell["correct"]["limits"]
    n_tok = int(sum(len(g) for g in gaps["program"]))
    widest = max((float(np.max(g)) for g in gaps["program"] if len(g)),
                 default=float("nan"))
    check.add("served_logit_gap_max", widest, limits["served_logit_gap_max"],
              f"{n_tok} served tokens of {len(sample)} requests, longest "
              f"{max((len(s['tokens']) for s in sample), default=0)} tokens")
    mean = float(np.mean(np.concatenate(gaps["program"]))) if n_tok \
        else float("nan")
    check.add("served_logit_gap_mean", mean, limits["served_logit_gap_mean"],
              "mean over the same served tokens")
    if n_tok < int(cell["correct"].get("min_tokens", 1)):
        check.fail("served_tokens", f"only {n_tok} served tokens to compare")
    check.add("failed_requests", facts["failed"], 0)
    for quant in quants:
        ctl = harness.Check()
        ctl.add("served_logit_gap_max",
                max(float(np.max(g)) for g in gaps[quant]),
                limits["served_logit_gap_max"], f"control {quant}")
        ctl.add("served_logit_gap_mean",
                float(np.mean(np.concatenate(gaps[quant]))),
                limits["served_logit_gap_mean"], f"control {quant}")
        check.controls[quant] = ctl
    print(f"[serve_engine] reference: {time.monotonic() - t_ref:.1f} s "
          "(after the window, not in setup_s)", flush=True)
    facts["e2e"]["setup_s"] = setup_s
    return {"end_to_end": facts.pop("e2e"), "attempted": facts["attempted"],
            "failed": facts["failed"], "memory_peak_bytes": peak,
            "facts": facts}


def _trace_edges(trace, trace_dir, now, t_open, clock):
    """Open or close the traced sub-window when it is due; returns the
    edge crossed (``trace_open`` / ``trace_close``) or None."""
    import jax

    if trace["state"] == "armed" and now - t_open >= trace["after_s"]:
        from benchmark.lib.tracing import start_trace

        start_trace(trace_dir)
        trace.update(state="on", t0=clock())
        return "trace_open"
    if trace["state"] == "on" and now - trace["t0"] >= trace["for_s"]:
        trace.update(t1=clock())
        jax.profiler.stop_trace()
        trace.update(state="done", window_s=trace["t1"] - trace["t0"])
        return "trace_close"
    return None


# ------------------------------------------------------------- reduction
def good(c, want_tokens=None) -> bool:
    """A completion that ran to its length with consistent tick clocks."""
    return (c.reason in OK_REASONS
            and ticklog.consistent(len(c.tokens), c.timing)
            and (want_tokens is None or len(c.tokens) == want_tokens))


def reduce_run(loop: Loop, reqs, warm_ids, t0, t_open, t_close, t_end,
               after_ticks, cell) -> dict:
    """End-to-end numbers and the counters the per-layer readers use."""
    by_id = {r["id"]: r for r in reqs}
    in_window = [t for t in loop.ticks if t_open < t["t1"] <= t_close]
    tokens = sum(t["prefills"] + t["decode_tokens"] for t in in_window)
    span = (in_window[-1]["t1"] - t_open) if in_window else float("nan")
    tick_t0 = {t["tick"]: t["t0"] for t in loop.ticks}
    bad = [rid for rid, c in loop.completions.items() if rid not in warm_ids
           and not good(c, by_id[rid]["max_new_tokens"])]
    failed = len(bad)

    # requests DUE in the window (open loop): a stall is charged to the
    # requests behind it, and one without a first token has missed
    due = [] if after_ticks else [
        r for r in reqs if t_open <= t0 + r["due_s"] < t_close]
    ttft, late, queue_wait = [], [], []
    for r in due:
        when = t0 + r["due_s"]
        late.append((loop.submitted[r["id"]][0] - when) * 1e3)
        first = loop.first_token_tick(r["id"])
        if first is None:
            failed += 1
            ttft.append((t_end - when) * 1e3)   # still waiting: at least this
            continue
        ttft.append((loop.stamps[first] - when) * 1e3)
        queue_wait.append(max(tick_t0[first] - when, 0.0) * 1e3)

    gaps, finished = [], 0
    for rid, c in loop.completions.items():
        if rid in warm_ids or rid in bad:
            continue
        times = ticklog.token_times(loop.submitted[rid][1], c.timing,
                                    len(c.tokens), loop.stamps)
        finished += t_open < times[-1] <= t_close
        gaps += [(b - a) * 1e3 for a, b in zip(times, times[1:])
                 if t_open < b <= t_close]
    e2e = {"serve_out_tokens_per_s": tokens / span}
    if ttft:
        e2e["ttft_p95_ms"] = ticklog.percentile(ttft, 95)
    if gaps:
        e2e["itl_p95_ms"] = ticklog.percentile(gaps, 95)
    if due and in_window:
        mid = in_window[len(in_window) // 2]
        done_due = sum(1 for r in due if r["id"] in loop.completions)
        print(f"[serve_engine] open loop: {len(due)} due, {done_due} of them "
              f"complete by the end of the drain ({done_due / len(due):.3f}); "
              f"queue at mid-window {mid['pending']}, at its end "
              f"{in_window[-1]['pending']}", flush=True)
    slowest = sorted(in_window, key=lambda t: t["t0"] - t["t1"])[:3]
    print("[serve_engine] slowest ticks (ms at s into the window x prefills): "
          + ", ".join(f"{(t['t1'] - t['t0']) * 1e3:.1f} at "
                      f"{t['t0'] - t_open:.2f} x {t['prefills']}"
                      for t in slowest)
          + f"; {sum(t['prefills'] for t in in_window)} prefills in all",
          flush=True)
    print(f"[serve_engine] window {t_close - t_open:.3f} s: {len(in_window)} "
          f"ticks, {tokens} output tokens, {finished} requests finished, "
          f"{len(due)} due; ttft median "
          f"{ticklog.percentile(ttft, 50):.3f} ms n={len(ttft)}; token gap "
          f"median {ticklog.percentile(gaps, 50):.3f} ms n={len(gaps)}; "
          f"failed {failed}", flush=True)
    return {"e2e": e2e, "failed": failed,
            "attempted": (finished if after_ticks else len(due)) + len(bad),
            "ticks": in_window, "late_ms": late, "queue_wait_ms": queue_wait,
            "ttft_ms": ttft, "gaps_ms": gaps, "t_open": t_open,
            "t_close": t_close,
            "max_seqs": cell["program"]["serve_config"]["max_seqs"]}


# ------------------------------------------------------------ correctness
def pick_sample(loop: Loop, reqs, t_open, t_close, seed, cell) -> list:
    """A sample, drawn from the seed, of the requests the window finished,
    with the longest in it: prompt and served tokens of each."""
    by_id = {r["id"]: r for r in reqs}
    done = []
    for rid, c in loop.completions.items():
        if rid not in by_id or not good(c, by_id[rid]["max_new_tokens"]):
            continue
        end = ticklog.token_times(loop.submitted[rid][1], c.timing,
                                  len(c.tokens), loop.stamps)[-1]
        if t_open < end <= t_close:
            done.append({"id": rid, "prompt": by_id[rid]["prompt"],
                         "tokens": list(c.tokens)})
    done.sort(key=lambda s: s["id"])
    if not done:
        return []
    k = min(int(cell["correct"]["sample_requests"]), len(done))
    longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"])
                  + len(done[i]["tokens"]))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 7])
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    return [done[i] for i in [longest] + rest[:k - 1]]


def served_token_gaps(cell, seed, sample, quants=()) -> dict:
    """For every served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token is the
    reference's own choice): ``out["program"]``, one array per request.
    For each precision in ``quants`` the same number for the token that a
    forward pass in that lower precision puts first at the same position:
    the control, ``out[quant]``."""
    import jax
    import jax.numpy as jnp

    cfg = cell["config"]
    out = {"program": [], **{q: [] for q in quants}}
    if not sample:
        return out
    family = harness.load_family(cfg)
    ref, dtype = family.reference, weights_dtype(cell)
    weights = jax.jit(lambda key: ref.init_weights(key, cfg, dtype))(
        ref.seed_key(seed))
    width = family.reference_row_len(cell)
    rows = np.zeros((len(sample), width), np.int32)
    for row, s in zip(rows, sample):
        seq = list(s["prompt"]) + list(s["tokens"])
        row[:len(seq)] = seq

    def gaps(weights, rows):
        logits = ref.forward(weights, rows, cfg)[:, :-1]
        best = logits.max(-1)
        served = jnp.take_along_axis(logits, rows[:, 1:, None], -1)[..., 0]
        res = [best - served]
        for quant in quants:
            low = ref.forward(weights, rows, cfg, quant)[:, :-1].argmax(-1)
            res.append(best - jnp.take_along_axis(
                logits, low[..., None], -1)[..., 0])
        return res

    per_block = int(cell["correct"].get("reference_rows", 2))
    pad = -len(rows) % per_block        # whole blocks: one compiled shape
    padded = np.concatenate([rows, np.repeat(rows[-1:], pad, 0)])
    fn = jax.jit(gaps)
    parts = [jax.device_get(fn(weights, padded[i:i + per_block]))
             for i in range(0, len(padded), per_block)]
    merged = [np.concatenate([p[j] for p in parts]) for j in
              range(len(parts[0]))]
    for i, s in enumerate(sample):
        lo = len(s["prompt"]) - 1
        hi = lo + len(s["tokens"])
        for j, key in enumerate(("program",) + tuple(quants)):
            out[key].append(merged[j][i, lo:hi])
    return out
