"""Loss-parity experiment: vote-Lion (W=8) vs local Lion at equal global batch.

BASELINE.md north-star #1 — "distributed-vote Lion matches single-worker
Lion's loss curve at equal global batch" — at real scale: the GPT-2 124M
*architecture* (12L, d=768, T=1024) over real local text through the native
BPE pipeline, a few thousand optimizer steps, on the real chip.

Single-chip discipline: the 8 voters run as VIRTUAL workers on one device —
a ``lax.scan`` over 8 per-worker (momentum, microbatch) slices computing the
exact vote-Lion algorithm with ops/lion_math's op ordering (wd → ballot →
vote → apply → momentum-from-local-grad). This is algebraically identical to
the dp=8 mesh path: the wire tests (tests/test_distributed_lion.py,
test_hier_vote.py) already pin that every wire computes exactly this
ballot-sum election, so the only thing a real 8-chip mesh would change is
WHERE the int8 sum runs.

Phases:
    python scripts/loss_parity.py --phase prep        # corpus + vocab + tokens (CPU ok)
    python scripts/loss_parity.py --phase run --mode local
    python scripts/loss_parity.py --phase run --mode vote
    python scripts/loss_parity.py --phase report      # REPORT.md from the JSONLs

prep: concatenates ~200MB of local Python/Markdown sources, trains a 16384-
token byte-level BPE with the HF ``tokenizers`` trainer (Rust — the pure-
Python ``train_bpe`` is for small vocabularies; the ARTIFACT is the standard
vocab.json+merges.txt this framework's native C++ BPE consumes), then
encodes the corpus with OUR tokenizer (data/bpe._NativeCore) into a token
memmap. Model embeddings size to the 16k vocab → ~98M params.

Reference anchors: canonical config lr 1e-4, wd 0.1, bf16, T=1024
(/root/reference/README.md:18-38); update semantics distributed_lion.py:61-96.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in __import__("sys").path:  # `python scripts/loss_parity.py`
    __import__("sys").path.insert(0, REPO)
DEFAULT_OUT = os.path.join(REPO, "runs", "parity")
VOCAB = 16384
T = 1024
WORKERS = 8
ROWS_PER_WORKER = 4          # global batch 32 rows = 32768 tokens/step
SMOKE = False                # --smoke: tiny model/seq for a CPU pipeline check
REDUCED = False              # --reduced: ≥10M-param short-seq legs sized so a
# 2000-step curve completes on the 1-core CPU host without a chip (round-4
# review §next-1/3: "the claim is about trajectory, not
# throughput"). Writes to runs/parity_cpu so a later TPU window can still
# capture the full-scale legs in runs/parity without colliding.
LR, WD, B1, B2 = 1e-4, 0.1, 0.9, 0.99
WARMUP = 100


# ------------------------------------------------------------------- prep

def _corpus_files(max_bytes: int) -> list:
    pats = [
        os.path.join(REPO, "**", "*.py"),
        os.path.join(REPO, "**", "*.md"),
        "/opt/venv/lib/**/*.py",
    ]
    out, total = [], 0
    for pat in pats:
        for p in sorted(glob.glob(pat, recursive=True)):
            try:
                sz = os.path.getsize(p)
            except OSError:
                continue
            if sz < 256:
                continue
            out.append(p)
            total += sz
            if total >= max_bytes:
                return out
    return out


def prep(out_dir: str, max_bytes: int) -> None:
    os.makedirs(out_dir, exist_ok=True)
    corpus_path = os.path.join(out_dir, "corpus.txt")
    if not os.path.exists(corpus_path):
        files = _corpus_files(max_bytes)
        print(f"[prep] concatenating {len(files)} files")
        with open(corpus_path, "w", encoding="utf-8") as w:
            for p in files:
                try:
                    with open(p, encoding="utf-8", errors="replace") as f:
                        w.write(f.read())
                    w.write("\n\n")
                except OSError:
                    continue
        print(f"[prep] corpus: {os.path.getsize(corpus_path)/1e6:.0f} MB")

    tok_dir = os.path.join(out_dir, "tok")
    if not os.path.exists(os.path.join(tok_dir, "vocab.json")):
        # vocab learned by the fast Rust trainer; ARTIFACT is the standard
        # GPT-2 file format our native BPE loads (data/bpe.BPETokenizer)
        from tokenizers import Tokenizer, models, pre_tokenizers, trainers

        t0 = time.time()
        hf = Tokenizer(models.BPE())
        hf.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
        trainer = trainers.BpeTrainer(
            vocab_size=VOCAB - 1,  # + <|endoftext|> on our side
            special_tokens=[],
            initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        )
        hf.train([corpus_path], trainer)
        os.makedirs(tok_dir, exist_ok=True)
        hf.model.save(tok_dir)  # vocab.json + merges.txt
        print(f"[prep] 16k BPE vocabulary trained in {time.time()-t0:.0f}s")

    tokens_path = os.path.join(out_dir, "tokens.npy")
    if not os.path.exists(tokens_path):
        import numpy as np

        from distributed_lion_tpu.data.bpe import BPETokenizer

        tok = BPETokenizer.load(tok_dir)
        assert tok.vocab_size <= VOCAB, tok.vocab_size
        t0 = time.time()
        ids: list = []
        with open(corpus_path, encoding="utf-8") as f:
            while True:
                chunk = f.read(4 << 20)
                if not chunk:
                    break
                ids.append(np.asarray(tok.encode(chunk), np.int32))
        stream = np.concatenate(ids)
        np.save(tokens_path, stream)
        mb = os.path.getsize(corpus_path) / 1e6
        print(f"[prep] {stream.size/1e6:.1f}M tokens in {time.time()-t0:.0f}s "
              f"({mb/(time.time()-t0):.1f} MB/s native BPE)")
    else:
        import numpy as np

        stream = np.load(tokens_path, mmap_mode="r")
    print(f"[prep] ready: {stream.size/1e6:.1f}M tokens at {tokens_path}")


# -------------------------------------------------------------------- run

def _blocks(out_dir: str):
    import numpy as np

    tokens_path = os.path.join(out_dir, "tokens.npy")
    if not os.path.exists(tokens_path):
        # reduced legs live in runs/parity_cpu but share the prepared
        # full-scale corpus/token stream — same data, same 16k vocab
        tokens_path = os.path.join(DEFAULT_OUT, "tokens.npy")
    stream = np.load(tokens_path, mmap_mode="r")
    n_blocks = stream.size // T
    blocks = stream[: n_blocks * T].reshape(n_blocks, T)
    n_eval = 64
    return blocks[n_eval:], blocks[:n_eval]  # train, held-out


def run(out_dir: str, mode: str, steps: int, log_every: int,
        eval_every: int, seed: int, force_cpu: bool = False) -> None:
    assert mode in ("local", "vote", "lazy")
    os.makedirs(out_dir, exist_ok=True)  # reduced legs skip the prep phase
    import jax

    if force_cpu:
        # the caller asked for the CPU (--force-cpu); must land before
        # first backend use
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.gpt2 import GPT2Config, gpt2_apply, gpt2_init
    from distributed_lion_tpu.models.loss import clm_loss_and_metrics
    from distributed_lion_tpu.ops.lion_math import (
        apply_signed_update,
        decay_params,
        local_lion_leaf,
        momentum_update,
        sign_vote_bool,
    )
    from distributed_lion_tpu.train.schedule import cosine_schedule_with_warmup

    dev = jax.devices()[0]
    print(f"[run:{mode}] backend={dev.platform} ({dev.device_kind})")
    import dataclasses

    if SMOKE:
        cfg = GPT2Config.tiny(vocab_size=VOCAB, n_ctx=T)
    elif REDUCED:
        # smallest scale at which the shipped lazy auto-default applies
        # (train/loop.resolve_auto_comm: W>1 ∧ replicated ∧ ≥10M params):
        # GPT2Config.small = 6L d=320 over the 16k vocab ≈ 12.7M params
        # (the shared reduced evidence preset). Short T keeps a 2000-step
        # leg within hours on the single host core.
        cfg = GPT2Config.small(vocab_size=VOCAB, n_ctx=T)
    else:
        cfg = GPT2Config.gpt2_124m(vocab_size=VOCAB)
    # f32 MASTER params (compute stays bf16, the config default): Lion's
    # fixed ±lr update is 1e-4 while bf16's ULP at |p| >= 0.05 is ~4e-4 —
    # bf16-stored params would silently absorb the entire update on most
    # large-magnitude coordinates (verified: apply_signed_update on bf16
    # p=0.05..0.5 is a no-op at lr=1e-4). Same reason torch training keeps
    # f32 master weights under bf16 autocast.
    cfg = dataclasses.replace(cfg, remat=False, attn_impl="xla")
    params = gpt2_init(jax.random.key(seed), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    if REDUCED:
        # the reduced legs exist to evidence the ≥10M lazy auto-default —
        # a sub-threshold model would test a config the default never sees
        assert n_params >= 10_000_000, n_params
    print(f"[run:{mode}] {n_params/1e6:.1f}M params "
          f"({'reduced CPU-scale' if REDUCED else '124M'} architecture, "
          f"{VOCAB} local vocab)")
    schedule = cosine_schedule_with_warmup(LR, WARMUP, steps)

    def loss_fn(p, batch):
        logits = gpt2_apply(p, batch, cfg, dropout_key=None)
        loss, _ = clm_loss_and_metrics(logits, batch)
        return loss

    grad_fn = jax.value_and_grad(loss_fn)
    gb = WORKERS * ROWS_PER_WORKER

    if mode == "local":
        moms = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        @jax.jit
        def step_fn(params, moms, count, batch):  # batch [gb, T]
            lr = schedule(count)
            loss, g = grad_fn(params, batch)
            out = jax.tree.map(
                lambda p, gg, m: local_lion_leaf(p, gg, m, lr, WD, B1, B2),
                params, g, moms,
                is_leaf=lambda x: isinstance(x, jnp.ndarray),
            )
            params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
            moms = jax.tree.map(lambda o: o[1], out,
                                is_leaf=lambda x: isinstance(x, tuple))
            return params, moms, count + 1, loss
    elif mode == "vote":
        # W=8 virtual vote workers: scan over per-worker (momentum slice,
        # microbatch); ballots accumulate as an int8 ±1 sum (the sign_psum
        # election); every worker applies the identical elected update.
        moms = jax.tree.map(
            lambda p: jnp.zeros((WORKERS,) + p.shape, jnp.float32), params)

        @jax.jit
        def step_fn(params, moms, count, batch):  # batch [W, rows, T]
            lr = schedule(count)

            def worker(ballots, xs):
                m_w, b = xs
                loss, g = grad_fn(params, b)
                ballots = jax.tree.map(
                    lambda bt, gg, mm: bt + jnp.where(
                        sign_vote_bool(gg, mm, B1), 1, -1).astype(jnp.int8),
                    ballots, g, m_w)
                m_new = jax.tree.map(
                    lambda gg, mm: momentum_update(gg, mm, B2), g, m_w)
                return ballots, (m_new, loss)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.int8), params)
            ballots, (moms_new, losses) = jax.lax.scan(
                worker, zeros, (moms, batch))
            params = jax.tree.map(
                lambda p, bt: apply_signed_update(
                    decay_params(p, lr, WD), bt > 0, lr),
                params, ballots)
            return params, moms_new, count + 1, losses.mean()
    else:  # mode == "lazy": the budget-meeting wire at realistic scale —
        # vote_every=4 lazy sign refresh (optim.distributed_lion._elect_lazy
        # semantics: rotating 1/K slice of the FLAT ballot vector voted each
        # step, cached elected signs elsewhere, cold-start validity mask).
        # With the packed_a2a wire this config is ~0.5 bit/param/step.
        from distributed_lion_tpu.ops.codec import vote_chunk_elems

        K = 4
        flat_leaves, treedef = jax.tree.flatten(params)
        sizes = [int(np.prod(p.shape)) for p in flat_leaves]
        shapes = [p.shape for p in flat_leaves]
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        n_total = int(offsets[-1])
        chunk = vote_chunk_elems(n_total, K)
        moms = jax.tree.map(
            lambda p: jnp.zeros((WORKERS,) + p.shape, jnp.float32), params)
        cache = jnp.zeros((K * chunk,), bool)

        @jax.jit
        def step_fn(params, moms, cache, count, batch):  # batch [W, rows, T]
            lr = schedule(count)

            def worker(ballots, xs):
                m_w, b = xs
                loss, g = grad_fn(params, b)
                votes = jnp.concatenate([
                    sign_vote_bool(gg, mm, B1).reshape(-1)
                    for gg, mm in zip(jax.tree.leaves(g), jax.tree.leaves(m_w))
                ])
                ballots = ballots + jnp.where(votes, 1, -1).astype(jnp.int8)
                m_new = jax.tree.map(
                    lambda gg, mm: momentum_update(gg, mm, B2), g, m_w)
                return ballots, (m_new, loss)

            ballots, (moms_new, losses) = jax.lax.scan(
                worker, jnp.zeros((n_total,), jnp.int8), (moms, batch))
            pad = K * chunk - n_total
            padded = (jnp.concatenate([ballots, jnp.zeros((pad,), jnp.int8)])
                      if pad else ballots)
            slot = jax.lax.rem(count, jnp.int32(K))
            sl = jax.lax.dynamic_slice(padded, (slot * chunk,), (chunk,))
            cache = jax.lax.dynamic_update_slice(cache, sl > 0, (slot * chunk,))
            slot_idx = jnp.arange(K * chunk, dtype=jnp.int32) // chunk
            valid = (slot_idx <= count)[:n_total].astype(jnp.float32)
            sign_flat = jnp.where(cache[:n_total], 1.0, -1.0) * valid
            new_leaves = [
                decay_params(p, lr, WD)
                - jnp.asarray(lr, p.dtype)
                * sign_flat[offsets[i]:offsets[i + 1]].reshape(
                    shapes[i]).astype(p.dtype)
                for i, p in enumerate(jax.tree.leaves(params))
            ]
            params = jax.tree.unflatten(treedef, new_leaves)
            return params, moms_new, cache, count + 1, losses.mean()

    @jax.jit
    def eval_loss(params, batch):
        return loss_fn(params, batch)

    train_blocks, eval_blocks = _blocks(out_dir)
    eval_dev = jnp.asarray(np.asarray(eval_blocks[:32]), jnp.int32)
    rng = np.random.default_rng(seed + 1)
    order = rng.permutation(len(train_blocks))
    pos = 0

    def next_batch():
        nonlocal pos, order
        if pos + gb > len(order):
            order = rng.permutation(len(train_blocks))
            pos = 0
        idx = np.sort(order[pos: pos + gb])
        pos += gb
        rows = np.asarray(train_blocks[idx], np.int32)
        if mode in ("vote", "lazy"):
            return jnp.asarray(rows.reshape(WORKERS, ROWS_PER_WORKER, T))
        return jnp.asarray(rows)

    log_path = os.path.join(out_dir, f"{mode}.jsonl")
    ckpt_path = os.path.join(out_dir, f"{mode}.ckpt.npz")
    dtype_name = str(cfg.param_dtype.__name__
                     if hasattr(cfg.param_dtype, "__name__")
                     else cfg.param_dtype)

    # ---- mid-leg checkpoint/resume: a 2000-step leg is ~45 min of chip
    # time and a backend can be lost without warning — without resume, every
    # drop restarts the leg from step 0 AND truncates the partial curve
    # (mode "w"). Params+momenta+iterator state persist every SAVE_EVERY
    # steps (atomic tmp+rename), so a re-fired leg loses at most that
    # window. The checkpoint stamps mode/dtype/steps and is ignored on
    # mismatch (a config change must not silently splice curves).
    SAVE_EVERY = 250
    p_leaves, p_tree = jax.tree.flatten(params)
    m_leaves, m_tree = jax.tree.flatten(moms)
    count = jnp.int32(0)
    start_step = 0
    wall_base = 0.0  # cumulative wall time from earlier windows
    resumed = False
    if os.path.exists(ckpt_path):
        # TRANSACTIONAL restore: decode everything into temporaries first —
        # a partial/old-format npz that raises halfway must not leave
        # params at checkpoint values while the leg restarts "fresh" at
        # step 0 (a silently corrupt curve)
        try:
            ck = np.load(ckpt_path, allow_pickle=False)
            meta_ok = (str(ck["mode"]) == mode
                       and str(ck["param_dtype"]) == dtype_name
                       and int(ck["steps"]) == steps)
            if meta_ok:
                r_params = jax.tree.unflatten(
                    p_tree, [jnp.asarray(ck[f"p{i}"])
                             for i in range(len(p_leaves))])
                r_moms = jax.tree.unflatten(
                    m_tree, [jnp.asarray(ck[f"m{i}"])
                             for i in range(len(m_leaves))])
                r_cache = (jnp.asarray(ck["cache"])
                           if mode == "lazy" else None)
                r_count = jnp.int32(int(ck["count"]))
                r_pos = int(ck["pos"])
                r_order = np.asarray(ck["order"])
                r_rng_state = json.loads(str(ck["rng_state"]))
                r_wall = float(ck["wall_s"]) if "wall_s" in ck else 0.0
                # every key decoded — commit the restore atomically
                params, moms, count = r_params, r_moms, r_count
                if mode == "lazy":
                    cache = r_cache
                start_step = int(ck["step"]) + 1
                pos, order = r_pos, r_order
                rng.bit_generator.state = r_rng_state
                wall_base = r_wall
                resumed = True
                # rows past the checkpoint will be re-run and re-logged —
                # drop them now or the curve carries duplicate steps. Parse
                # per line: a TORN last line is the normal artifact of the
                # crash resume exists for, and must be dropped, not abort
                # the prune (report()'s loader would crash on it later).
                try:
                    kept = []
                    with open(log_path) as f:
                        for ln in f:
                            try:
                                d = json.loads(ln)
                            except json.JSONDecodeError:
                                continue
                            if d.get("meta") or d.get("step", steps) \
                                    < start_step:
                                kept.append(ln)
                    with open(log_path, "w") as f:
                        f.writelines(kept)
                except OSError:
                    pass
                print(f"[run:{mode}] resumed checkpoint at step {start_step}")
            else:
                print(f"[run:{mode}] checkpoint config mismatch — fresh run")
        except Exception as e:  # corrupt/partial ckpt: fresh run
            print(f"[run:{mode}] checkpoint unreadable ({e}) — fresh run")

    def save_ckpt(s):
        arrs = {f"p{i}": np.asarray(p) for i, p in
                enumerate(jax.tree.leaves(params))}
        arrs.update({f"m{i}": np.asarray(m) for i, m in
                     enumerate(jax.tree.leaves(moms))})
        if mode == "lazy":
            arrs["cache"] = np.asarray(cache)
        arrs.update(mode=mode, param_dtype=dtype_name, steps=steps,
                    step=s, count=int(np.asarray(count)), pos=pos,
                    order=order,
                    rng_state=json.dumps(rng.bit_generator.state),
                    # cumulative wall time: logged wall_s/tok-s must stay
                    # monotone and honest across resume boundaries
                    wall_s=wall_base + (time.time() - t0))
        tmp = ckpt_path + ".tmp.npz"  # .npz suffix: np.savez appends it
        np.savez(tmp, **arrs)         # to any other name, breaking the
        os.replace(tmp, ckpt_path)    # atomic rename

    t0 = time.time()
    # header row stamps the config so curve consumers (check_evidence,
    # report) can reject runs captured under a different precision —
    # bf16-era curves had frozen large-magnitude params (see the f32
    # master-params comment above) and must not be compared against
    # f32 runs as if the optimizer mode were the difference. Written on
    # fresh runs AND on a resume whose log vanished (a ckpt without its
    # jsonl would otherwise produce a headerless curve check_evidence
    # rejects for no visible reason).
    need_meta = (not resumed or not os.path.exists(log_path)
                 or os.path.getsize(log_path) == 0)
    with open(log_path, "a" if resumed else "w") as logf:
        if need_meta:
            logf.write(json.dumps({
                "meta": True, "mode": mode, "param_dtype": dtype_name,
                "lr": LR, "workers": WORKERS, "steps": steps,
                # scale + provenance stamps: the report/check must only
                # compare legs with identical config, and reduced CPU legs
                # must be distinguishable from full-scale TPU captures
                "d_model": cfg.d_model, "n_layer": cfg.n_layer, "T": T,
                "rows_per_worker": ROWS_PER_WORKER,
                "n_params": n_params, "seed": seed,
                "backend": dev.platform, "reduced": REDUCED,
            }) + "\n")
        for s in range(start_step, steps):
            if mode == "lazy":
                params, moms, cache, count, loss = step_fn(
                    params, moms, cache, count, next_batch())
            else:
                params, moms, count, loss = step_fn(
                    params, moms, count, next_batch())
            if (s + 1) % SAVE_EVERY == 0 and s != steps - 1:
                save_ckpt(s)
            if s % log_every == 0 or s == steps - 1:
                lv = float(np.asarray(jax.device_get(loss)))
                rec = {"step": s, "loss": round(lv, 5),
                       "lr": float(schedule(s)),
                       "tokens": (s + 1) * gb * T,
                       "wall_s": round(wall_base + time.time() - t0, 1)}
                logf.write(json.dumps(rec) + "\n")
                logf.flush()
                print(f"[run:{mode}] step {s}: loss {lv:.4f} "
                      f"({rec['tokens']/max(rec['wall_s'],1e-9)/1e3:.1f}k tok/s)")
            if eval_every and (s + 1) % eval_every == 0:
                ev = float(np.asarray(jax.device_get(
                    eval_loss(params, eval_dev))))
                logf.write(json.dumps(
                    {"step": s, "eval_loss": round(ev, 5)}) + "\n")
                logf.flush()
                print(f"[run:{mode}] step {s}: eval {ev:.4f}")
    # a completed leg's checkpoint is dead weight (and a stale one could
    # splice duplicate tail rows if the jsonl were ever lost) — drop it
    try:
        os.remove(ckpt_path)
    except OSError:
        pass
    print(f"[run:{mode}] done: {log_path}")


# ----------------------------------------------------------------- report

def report(out_dir: str) -> None:
    def load(mode):
        tr, ev, meta = {}, {}, None
        path = os.path.join(out_dir, f"{mode}.jsonl")
        if not os.path.exists(path):
            return None, None, None
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn last line: the leg died mid-write
                    # AFTER the capture threshold — the curve is valid
                if r.get("meta"):
                    meta = r
                elif "eval_loss" in r:
                    ev[r["step"]] = r["eval_loss"]
                elif "loss" in r:
                    tr[r["step"]] = r["loss"]
        return tr, ev, meta

    tr_l, ev_l, meta_l = load("local")
    tr_v, ev_v, _ = load("vote")
    tr_z, ev_z, _ = load("lazy")  # optional third curve: vote_every=4 wire
    if not tr_l or not tr_v:
        raise SystemExit(
            "[report] need BOTH local.jsonl and vote.jsonl with train "
            "records; run --phase run --mode local and --mode vote first"
        )
    common = sorted(set(tr_l) & set(tr_v))
    if not common:
        raise SystemExit("[report] local and vote curves share no logged steps")
    has_lazy = bool(tr_z)
    # scale/provenance line from the leg's own meta stamp — a reduced CPU
    # leg set must not publish a report claiming 124M/T=1024 full-scale
    # provenance (the jsonl is the source of truth, the prose follows it)
    m = meta_l or {}
    if m.get("d_model"):
        arch = (f"GPT-2-family {m['n_params']/1e6:.1f}M params "
                f"({m['n_layer']}L d={m['d_model']} T={m['T']}, "
                f"{VOCAB}-token local BPE vocab)"
                + (f", {m.get('backend', '?')} backend"
                   if m.get("backend") else "")
                + (" — REDUCED CPU-leg scale"
                   if m.get("reduced") else ""))
    else:
        arch = ("GPT-2 124M architecture (12L d=768 T=1024, 16,384-token "
                "local BPE vocab ≈ 98M params)")
    lines = [
        "# Loss parity: vote-Lion (W=8) vs local Lion — equal global batch",
        "",
        arch + ", real local text, canonical reference config "
        "(lr 1e-4, wd 0.1, cosine+warmup). Generated by "
        "scripts/loss_parity.py; raw curves in local.jsonl / vote.jsonl"
        + (" / lazy.jsonl (vote_every=4 — the ≤0.5 bit/param wire)"
           if has_lazy else "") + ".",
        "",
        "| step | local loss | vote-W8 loss | Δ |"
        + (" lazy-K4 loss | Δ |" if has_lazy else ""),
        "|---|---|---|---|" + ("---|---|" if has_lazy else ""),
    ]
    show = [s for i, s in enumerate(common)
            if i % max(1, len(common) // 20) == 0] + common[-1:]
    for s in dict.fromkeys(show):
        d = tr_v[s] - tr_l[s]
        row = f"| {s} | {tr_l[s]:.4f} | {tr_v[s]:.4f} | {d:+.4f} |"
        if has_lazy:
            row += (f" {tr_z[s]:.4f} | {tr_z[s] - tr_l[s]:+.4f} |"
                    if s in tr_z else " — | — |")
        lines.append(row)
    # ---- the ONE numeric parity statement: the pre-registered pass/fail
    # criterion (VERDICT r4 #4), imported from check_evidence so the
    # report and the evidence gate can never disagree on what "parity
    # achieved" means — no second, differently-spanned mad is printed
    # alongside it (two divergent numbers in one document, code-review r5)
    import sys as _sys
    _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from check_evidence import (PARITY_EPS_NATS, PARITY_TAIL_FRAC,
                                parity_mad)
    lines += ["",
              f"## Criterion (pre-registered): mean |Δloss| vs local over "
              f"the last {1 - PARITY_TAIL_FRAC:.0%} of steps ≤ "
              f"{PARITY_EPS_NATS} nats", ""]
    abs_dir = os.path.abspath(out_dir)
    for label in ("vote", "lazy"):
        if label == "lazy" and not has_lazy:
            continue
        v = parity_mad(abs_dir, label)
        verdict = ("UNCOMPUTABLE (leg missing/unqualified/config mismatch)"
                   if v is None else
                   f"{v:.4f} nats — "
                   + ("PASS" if v <= PARITY_EPS_NATS else "FAIL"))
        lines += [f"- {label} vs local: {verdict}", ""]
    if ev_l and ev_v:
        lines += ["| step | local eval | vote-W8 eval |"
                  + (" lazy-K4 eval |" if has_lazy else ""),
                  "|---|---|---|" + ("---|" if has_lazy else "")]
        for s in sorted(set(ev_l) & set(ev_v)):
            row = f"| {s} | {ev_l[s]:.4f} | {ev_v[s]:.4f} |"
            if has_lazy:
                row += (f" {ev_z[s]:.4f} |" if ev_z and s in ev_z
                        else " — |")
            lines.append(row)
        lines.append("")
    path = os.path.join(out_dir, "REPORT.md")
    with open(path, "w") as f:
        f.write("\n".join(lines))
    print(f"[report] {path}\n" + "\n".join(lines[:14]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=("prep", "run", "report", "all"),
                    default="all")
    ap.add_argument("--mode", choices=("local", "vote", "lazy"),
                    default="local")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--log_every", type=int, default=10)
    ap.add_argument("--eval_every", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--corpus_bytes", type=int, default=200_000_000)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short seq: CPU pipeline check only")
    ap.add_argument("--reduced", action="store_true",
                    help="≥10M-param short-seq legs on the CPU backend, "
                    "written to runs/parity_cpu (the no-chip legs; "
                    "full-scale TPU legs in runs/parity take precedence)")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend (the caller's choice, "
                    "never a fallback); implied by --smoke")
    args = ap.parse_args()
    global SMOKE, REDUCED, T, ROWS_PER_WORKER
    if args.smoke:
        SMOKE = True
        T = 128
        ROWS_PER_WORKER = 1
        args.cpu = True
    elif args.reduced:
        REDUCED = True
        T = 256
        ROWS_PER_WORKER = 1   # global batch 8 rows = 2048 tokens/step
        args.cpu = True
        if os.path.abspath(args.out) == os.path.abspath(DEFAULT_OUT):
            # path-compare, not string-compare: `--out runs/parity` (or a
            # trailing slash) must ALSO redirect — a reduced leg writing
            # into the full-scale directory would truncate a captured TPU
            # curve via run()'s mode-"w" open (code-review r5)
            args.out = DEFAULT_OUT + "_cpu"
    if args.phase in ("prep", "all"):
        # reduced legs share the full-scale corpus/tokens via _blocks()'s
        # fallback — prep into the shared DEFAULT_OUT, never into the
        # reduced dir (a second ~200MB corpus + hours of 1-core BPE
        # retraining, which the watcher would then auto-commit)
        prep(DEFAULT_OUT if REDUCED else args.out, args.corpus_bytes)
    if args.phase == "run":
        run(args.out, args.mode, args.steps, args.log_every,
            args.eval_every, args.seed, force_cpu=args.cpu)
    elif args.phase == "all":
        for mode in ("local", "vote"):
            run(args.out, mode, args.steps, args.log_every,
                args.eval_every, args.seed, force_cpu=args.cpu)
        report(args.out)
    if args.phase == "report":
        report(args.out)


if __name__ == "__main__":
    main()
