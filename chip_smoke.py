"""Chip smoke: the vote-Lion trainer and the paged serving engine, end to end
on a TPU, through the entry points a user calls, at GPT-2 124M width.

    python chip_smoke.py             # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # ONLY the data-parallel vote phase

One process, JAX touched once, no child that needs the chip. Fails (non-zero
exit, no ``"ok": true`` line) when JAX finds no TPU, when any phase raises,
and when a phase that should hold a Mosaic kernel does not. Everything is
generated from fixed seeds; nothing is downloaded. Times printed here are
information for the builder, not a benchmark.

The last stdout line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
import traceback

MODEL_NAME = "gpt2_124m"      # 12L, d 768, 12 heads, T 1024 — full width
VOCAB = 50257
N_124M = 124_439_808          # its flat parameter count
TRAIN_STEPS = 4
MULTICHIP_STEPS = 3
FLASH_TOL = 2e-2              # |flash - xla| on bf16 outputs / grads of O(1)
XENT_LOSS_TOL = 1e-5          # |fused - dense| / loss, the loss head's kernels
XENT_GRAD_TOL = 2e-2          # |fused - dense| of a bf16-rounded gradient,
#                               relative to the largest reference value
LOGIT_TOL = 5e-2              # |paged - dense| on f32 logits (bf16 compute)
LOSS_TOL = 2e-3               # |loss_auto - loss_ref| / loss, multichip phase

TRAIN_ARGV = [
    "--model_name", MODEL_NAME, "--dataset", "synthetic", "--lion",
    "--async_grad", "--telemetry", "--block_size", "1024",
    "--per_device_train_batch_size", "4",
    # attention-prob dropout needs materialized scores, so with GPT-2's
    # default 0.1 the trainer keeps XLA attention by design; 0 puts the
    # auto-resolved flash kernel in the step this smoke is here to prove
    "--dropout", "0.0",
    "--gradient_accumulation_steps", "2", "--synthetic_blocks", "256",
    # constant LR from step 0: with a warmup the first update is scaled by
    # lr(0) = 0 and comparing two runs' step-1 parameters proves nothing
    "--learning_rate", "1e-4", "--lr_scheduler_type", "constant",
    "--warmup_steps", "0", "--seed", "1234",
    "--logging_steps", "1", "--eval_steps", "1000000",
    "--max_eval_samples", "8", "--per_device_eval_batch_size", "4",
]


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what) -> None:
    """A failed check fails the phase (not an ``assert``: those vanish
    under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


@contextlib.contextmanager
def captured(owner, name, sink: list):
    """Record what ``owner.name(...)`` returns (or, for a method, ``self``)
    while a CLI ``main`` runs — observation only, the call is untouched."""
    orig = getattr(owner, name)

    def wrapper(*a, **k):
        out = orig(*a, **k)
        sink.append(a[0] if isinstance(owner, type) else out)
        return out

    setattr(owner, name, wrapper)
    try:
        yield
    finally:
        setattr(owner, name, orig)


def mosaic_kernels(text: str) -> list:
    """Names of the Mosaic (Pallas TPU) kernels in a lowered program."""
    if "tpu_custom_call" not in text:
        return []
    return sorted({seg.split('"')[0]
                   for seg in text.split('kernel_name = "')[1:]})


def has_mosaic(text: str) -> bool:
    return bool(mosaic_kernels(text))


# ------------------------------------------------------------------ device
def phase_device(cache_dir) -> dict:
    import jax
    import jaxlib

    devs = jax.devices()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = getattr(devs[0].client, "platform_version", "unknown")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: {info} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    log(f"compile cache dir: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    return info


# ----------------------------------------------------------------- kernels
def _report_mismatch(name: str, bad, *context) -> int:
    import jax.numpy as jnp
    import numpy as np

    n_bad = int(jnp.sum(bad))
    if n_bad:
        idx = np.asarray(jnp.nonzero(bad, size=min(n_bad, 8))[0])
        log(f"  {name}: {n_bad} of {bad.size} coordinates differ from the "
            f"XLA path; first at {idx.tolist()}")
        for label, arr in context:
            log(f"    {label}: {np.asarray(arr[idx]).tolist()}")
    return n_bad


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.ops import lion_math, pallas_lion
    from distributed_lion_tpu.ops.attention import attention_xla
    from distributed_lion_tpu.ops.pallas_flash_attn import (
        flash_qkv,
        kernel_takes,
    )
    from distributed_lion_tpu.train import telemetry

    n, b1, b2, wd, world = N_124M, 0.9, 0.99, 0.1, 4
    lr = jnp.float32(1e-4)  # a traced f32 scalar, as the trainer's schedule
    kg, km, kp, kt = jax.random.split(jax.random.key(0), 4)
    g = jax.random.normal(kg, (n,), jnp.float32)
    p = jax.random.normal(kp, (n,), jnp.float32) * 0.02
    # an exact tally of `world` ±1 ballots: same parity as world, in [-W, W]
    total = (2 * jax.random.randint(kt, (n,), 0, world + 1) - world
             ).astype(jnp.int32)
    for mom_dtype in (jnp.float32, jnp.bfloat16):
        tag = jnp.dtype(mom_dtype).name
        m = (jax.random.normal(km, (n,), jnp.float32) * 0.5).astype(mom_dtype)
        gm = g.astype(mom_dtype)  # the optimizer hands grads in mom dtype
        # the reference is ops/lion_math on the f32 view of the same
        # operands — the kernels widen bf16 momentum/grads before the math
        g32, m32 = gm.astype(jnp.float32), m.astype(jnp.float32)

        ballots_k = jax.jit(lambda g, m: pallas_lion.fused_ballots(g, m, b1))
        check(has_mosaic(ballots_k.lower(gm, m).as_text()),
              "fused_ballots lowered without a Mosaic kernel")
        got = ballots_k(gm, m)
        ref = jax.jit(lambda g, m: jnp.where(
            lion_math.sign_vote_bool(g, m, b1), 1, -1).astype(jnp.int8)
        )(g32, m32)
        u = jax.jit(lambda g, m: lion_math.interp(g, m, b1))(g32, m32)
        n_bad = _report_mismatch(f"fused_ballots[m={tag}]", got != ref,
                                 ("b1*m+(1-b1)*g", u), ("g", g32), ("m", m32))
        if n_bad:
            # the only disagreement the two paths may show is rounding of
            # the blend where it is within an ulp of zero (e.g. a fused
            # multiply-add on one side): hold the chip to THAT
            scale = jnp.abs(m32 * b1) + jnp.abs(g32 * (1 - b1))
            excused = jnp.abs(u) <= scale * 2.0 ** -22
            check(bool(jnp.all(excused | (got == ref))),
                  "fused_ballots differs from the XLA path away from u == 0")
        log(f"  fused_ballots[m={tag}] n={n}: compiled Mosaic kernel, "
            f"{n_bad} ballots differ from ops/lion_math")

        apply_k = jax.jit(lambda p, g, m, t: pallas_lion.fused_apply(
            p, g, m, t, lr, wd, b2))
        check(has_mosaic(apply_k.lower(p, gm, m, total).as_text()),
              "fused_apply lowered without a Mosaic kernel")
        p_k, m_k = apply_k(p, gm, m, total)

        def apply_ref(p, g, m, t):
            p2 = lion_math.apply_signed_update(
                lion_math.decay_params(p, lr, wd), t > 0, lr)
            return p2, lion_math.momentum_update(g, m, b2).astype(mom_dtype)

        p_r, m_r = jax.jit(apply_ref)(p, g32, m32, total)
        bad_p = _report_mismatch(f"fused_apply.p[m={tag}]", p_k != p_r,
                                 ("kernel", p_k), ("xla", p_r))
        bad_m = _report_mismatch(f"fused_apply.m[m={tag}]", m_k != m_r,
                                 ("kernel", m_k), ("xla", m_r))
        # values to a few roundings of the operands (a fused multiply-add
        # on one side moves the last place; nothing may move further)
        check(bool(jnp.all(jnp.abs(p_k - p_r)
                           <= 2.0 ** -21 * (jnp.abs(p) + lr))),
              "fused_apply params off by more than rounding")
        m_eps = 2.0 ** (-21 if mom_dtype == jnp.float32 else -7)
        check(bool(jnp.all(
            jnp.abs(m_k.astype(jnp.float32) - m_r.astype(jnp.float32))
            <= m_eps * (jnp.abs(m32 * b2) + jnp.abs(g32 * (1 - b2))))),
              "fused_apply momentum off by more than rounding")
        log(f"  fused_apply[m={tag}] n={n}: compiled Mosaic kernel, "
            f"{bad_p} params / {bad_m} momenta differ from ops/lion_math")
        del p_k, m_k, p_r, m_r, got, ref, u, gm, g32, m32, m

    # the leaf-shaped entries against the flat ones at wte's shape, in the
    # windows leaf_layout cuts under four buckets (a later first block, the
    # ragged end of 50257 rows): the same arithmetic read where the leaf
    # lies, so ballots, parameters and momenta bit for bit
    from distributed_lion_tpu.ops.codec import bucket_bounds

    R, C = 50257, 768
    pieces = pallas_lion.leaf_layout(
        [(R, C)], bucket_bounds(R * C, 4, world, "packed_a2a")).pieces
    check(len(pieces) > 4 and pieces[-1].r1 == R,
          f"leaf_layout cut wte into {pieces}")
    m = jax.random.normal(km, (R * C,), jnp.float32) * 0.5
    pw, gw, tw = p[:R * C], g[:R * C], total[:R * C]
    flat_b = jax.jit(lambda g, m: pallas_lion.fused_ballots(g, m, b1))(gw, m)
    flat_p, flat_m = jax.jit(lambda p, g, m, t: pallas_lion.fused_apply(
        p, g, m, t, lr, wd, b2))(pw, gw, m, tw)

    def where_it_lies(p2, g2, m2, t2):
        tiles = lambda x, pc: x[pc.r0:pc.r1].reshape(  # noqa: E731
            pc.r1 - pc.r0, C // 128, 128).transpose(1, 0, 2)
        ballots = [pallas_lion.leaf_ballots(
            g2, m2, b1, rows=(pc.r0, pc.r1), block=pc.block) for pc in pieces]
        for pc in pieces:
            p2, m2 = pallas_lion.leaf_apply(
                p2, g2, m2, tiles(t2, pc).astype(jnp.int8), lr, wd, b2,
                rows=(pc.r0, pc.r1), block=pc.block)
        return jnp.concatenate([b.transpose(1, 0, 2).reshape(-1)
                                for b in ballots]), p2, m2

    leaf_k = jax.jit(where_it_lies, donate_argnums=(0, 2))
    args = (pw.reshape(R, C), gw.reshape(R, C), m.reshape(R, C),
            tw.reshape(R, C))
    leaf_k = leaf_k.lower(*args).compile()
    text = leaf_k.as_text()
    check(text.count("tpu_custom_call") == 2 * len(pieces)
          and not re.search(r"= f32\[50257,768\]\S* copy\(", text),
          "the leaf-shaped kernels do not take wte where it lies")
    leaf_b, leaf_p, leaf_m = leaf_k(*args)
    for name, got, want in (("ballots", leaf_b, flat_b),
                            ("params", leaf_p.reshape(-1), flat_p),
                            ("momenta", leaf_m.reshape(-1), flat_m)):
        check(bool(jnp.all(got == want)),
              f"leaf-shaped {name} differ from the flat kernels'")
    log(f"  leaf_ballots / leaf_apply [{R}, {C}] in {len(pieces)} windows: "
        f"{2 * len(pieces)} Mosaic calls in place, equal to the flat kernels "
        f"bit for bit")
    del m, pw, gw, tw, flat_b, flat_p, flat_m, leaf_b, leaf_p, leaf_m, args

    ballots = jnp.where(g > 0, 1, -1).astype(jnp.int8)
    stats_k = jax.jit(lambda b, t: pallas_lion.bucket_vote_stats(
        b, t, world, telemetry.NBINS))
    check(has_mosaic(stats_k.lower(ballots, total).as_text()),
          "bucket_vote_stats lowered without a Mosaic kernel")
    hist_k, dis_k = stats_k(ballots, total)
    hist_r = jax.jit(lambda t: telemetry.margin_hist(t, world))(total)
    dis_r = jnp.sum((ballots > 0) != (total > 0))
    check((hist_k == hist_r).all() and int(dis_k) == int(dis_r),
          f"bucket_vote_stats {hist_k}/{dis_k} != {hist_r}/{dis_r}")
    check(int(hist_k.sum()) == n,
          'int(hist_k.sum()) == n')
    log(f"  bucket_vote_stats n={n}: compiled Mosaic kernel, margin "
        f"histogram and disagreement count equal telemetry.margin_hist")
    del g, p, total, ballots

    # the trainer's own flash kernels (ops/pallas_flash_attn), token-major,
    # fwd + fused bwd, vs attention_xla on the same values laid out
    # head-major: at the train step's shape, and at one shape `auto` takes
    # on the kernel's construction alone (a head a lane block, T = 2048)
    for B, T, H, hd in ((4, 1024, 12, 64), (1, 2048, 8, 128)):
        D = H * hd
        check(kernel_takes(T, H, hd, jnp.bfloat16),
              f"pallas_flash_attn refuses T {T}, {H} heads of {hd}")
        kq, kw = jax.random.split(jax.random.key(T))
        qkv = jax.random.normal(kq, (B, T, 3 * D), jnp.bfloat16)
        w = jax.random.normal(kw, (B, T, D), jnp.bfloat16)

        def run(attn):
            def loss(x):
                out = attn(x)
                return jnp.sum(out.astype(jnp.float32)
                               * w.astype(jnp.float32)), out

            return jax.jit(jax.value_and_grad(loss, has_aux=True))

        def reference(x):
            q, k, v = (x[:, :, i * D:(i + 1) * D].reshape(B, T, H, hd)
                       .transpose(0, 2, 1, 3) for i in range(3))
            return attention_xla(q, k, v).transpose(0, 2, 1, 3).reshape(
                B, T, D)

        kernel = run(lambda x: flash_qkv(x, H))
        names = mosaic_kernels(kernel.lower(qkv).as_text())
        check(names == ["flash_attention_fwd", "flash_mha_bwd"],
              f"flash_qkv lowered to {names}")
        (_, out_k), grad_k = kernel(qkv)
        (_, out_x), grad_x = run(reference)(qkv)
        pairs = [(out_k, out_x)] + [
            (grad_k[:, :, i * D:(i + 1) * D], grad_x[:, :, i * D:(i + 1) * D])
            for i in range(3)]
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in pairs]
        log(f"  flash_qkv [{B},{T},3x{H}x{hd}] fwd+bwd vs attention_xla: max "
            f"|diff| out/dq/dk/dv = {[round(e, 5) for e in errs]} (tol "
            f"{FLASH_TOL} relative to the largest reference value)")
        for err, (_, ref) in zip(errs, pairs):
            check(err <= FLASH_TOL * max(1.0, float(jnp.max(jnp.abs(
                ref.astype(jnp.float32))))),
                  f"flash_qkv off by {err}")

    # the loss head's kernel pair (ops/pallas_xent) through the entry the
    # trainer's loss builder calls, at the train step's microbatch against
    # the published head as it lies ([4 x 1023 labelled rows, 768] x
    # [50257, 768]), vs the einsum + clm_loss_and_metrics it replaces:
    # loss, accuracy and both gradients
    from distributed_lion_tpu.models.loss import clm_loss_and_metrics
    from distributed_lion_tpu.ops import xent as xent_ops

    kh, kw, kt = jax.random.split(jax.random.key(29), 3)
    hidden = jax.random.normal(kh, (4, 1024, 768), jnp.bfloat16)
    head = jax.random.normal(kw, (VOCAB, 768), jnp.float32) * 0.05
    tokens = jax.random.randint(kt, (4, 1024), 0, VOCAB)
    check(xent_ops.head_path("vd", 768, jnp.bfloat16) == "fused",
          "the loss head does not take ops/pallas_xent at d 768, bf16")

    def dense(h, w, t):
        logits = jnp.einsum("btd,vd->btv", h, w.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        return clm_loss_and_metrics(logits, t)

    def run(fn):
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))

    fused = run(lambda h, w, t: xent_ops.clm_head_loss(h, w, t, layout="vd"))
    names = mosaic_kernels(fused.lower(hidden, head, tokens).as_text())
    check(names == ["fused_xent_bwd", "fused_xent_fwd"],
          f"the loss head lowered to {names}")
    (loss_k, m_k), grads_k = fused(hidden, head, tokens)
    (loss_x, m_x), grads_x = run(dense)(hidden, head, tokens)
    gap = abs(float(loss_k) - float(loss_x)) / float(loss_x)
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            / float(jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(grads_k, grads_x)]
    log(f"  fused_xent [4x1023,768]x[{VOCAB},768] fwd+bwd vs einsum + "
        f"clm_loss_and_metrics: loss {float(loss_k):.6f} / "
        f"{float(loss_x):.6f} (gap {gap:.2e}, tol {XENT_LOSS_TOL}), accuracy "
        f"{float(m_k['accuracy']):.6f} / {float(m_x['accuracy']):.6f}, max "
        f"|diff| dh/dwte over the largest reference value = "
        f"{[round(e, 5) for e in errs]} (tol {XENT_GRAD_TOL})")
    check(gap <= XENT_LOSS_TOL, f"fused_xent loss off by {gap}")
    check(float(m_k["n_tokens"]) == float(m_x["n_tokens"]) == 4 * 1023,
          "fused_xent counts other tokens than the dense loss")
    check(abs(float(m_k["accuracy"]) - float(m_x["accuracy"]))
          <= 2 / (4 * 1023), "fused_xent argmax differs from the dense one")
    for err in errs:
        check(err <= XENT_GRAD_TOL, f"fused_xent gradient off by {err}")
    del hidden, head, grads_k, grads_x


# ------------------------------------------------------------------- train
# ---------------------------------------------------------------- train_moe
def clock(fn, *args):
    """``(fn(*args), least milliseconds of five more calls)``."""
    import jax

    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, min(times) * 1e3


def expert_combine_at_size(n: int = 2 * 8192, d: int = 2304,
                           mean: int = 32283) -> None:
    """The combine of a layer that holds a range of its experts, at cell
    10's widths (16,384 tokens x 8 picks of 2,304 bfloat16) with 0, 32,283
    (the cell's mean) and all 131,072 rows in groups: its transpose bounded
    by the count (``parallel/expert._combine_held``) against autodiff's
    through the plain gather, mask and einsum. The forward is the plain
    program's own, so the outputs are equal; ``dy`` is equal in the rows
    below the count; ``dw`` is within float32 rounding. Each transpose's
    time is printed (the least of five; called alone the bounded one first
    copies ``y``, 0.6 GB, over which it writes ``dy``: the step's ``y`` is
    its own to overwrite)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.parallel import expert

    k, held = 8, 16
    m = n * k
    ks = jax.random.split(jax.random.key(44), 3)
    y = jax.random.normal(ks[0], (m, d), jnp.bfloat16)
    w = jax.random.uniform(ks[1], (n, k), jnp.float32)
    dout = jax.random.normal(ks[2], (n, d), jnp.float32)

    def plain(y, w, order, back, flat):
        rows = expert._unsorted_rows(y, order, back).reshape(n, k, d)
        rows = jnp.where((flat < held).reshape(n, k, 1), rows, 0)
        return jnp.einsum("nkd,nk->nd", rows.astype(jnp.float32), w)

    def bounded(y, w, order, back, flat):
        return expert._combine_held(y, w, order, back, flat, held)

    def both(fn):
        def run(y, w, order, back, flat, dout):
            out, transpose = jax.vjp(
                lambda y, w: fn(y, w, order, back, flat), y, w)
            return (out,) + transpose(dout)
        return jax.jit(run)

    for count in (0, mean, m):
        rng = np.random.default_rng(count)
        flat = np.full(m, held, np.int32)
        flat[rng.choice(m, count, replace=False)] = rng.integers(
            0, held, count)
        order = jnp.asarray(np.argsort(flat, kind="stable"), jnp.int32)
        back = jnp.argsort(order).astype(jnp.int32)
        flat = jnp.asarray(flat)
        (out, dy, dw), t_mine = clock(both(bounded), y, w, order, back, flat,
                                      dout)
        (out_want, dy_want, dw_want), t_plain = clock(
            both(plain), y, w, order, back, flat, dout)
        at = f"{count} of {m} rows in groups"
        check(bool((out == out_want).all()), f"the combine differs ({at})")
        live = (jnp.arange(m) < count)[:, None]
        check(bool(jnp.where(live, dy == dy_want, True).all()),
              f"the combine's dy differs below the count ({at})")
        gap = float(jnp.abs(dw - dw_want).max())
        check(gap <= 1e-5 * max(float(jnp.abs(dw_want).max()), 1.0),
              f"the combine's dw gap {gap} ({at})")
        log(f"train_moe combine, {at}: forward and transpose {t_mine:.3f} ms "
            f"bounded, {t_plain:.3f} plain; dw gap {gap:.2e}")


def qk_rope_at_size(T: int = 8192, B: int = 2) -> None:
    """q's and k's head norm and rotation at cell 10's shapes (a microbatch
    of 2 x 8,192 rows, 32 and 4 heads of 128, bfloat16, YaRN's table): the
    kernel pair ``qk_rope_fwd`` / ``qk_rope_bwd`` (``ops/pallas_qk_rope``)
    against the plain expression it stands for
    (``models/mellum.head_norm_rope``: ``apply_rope_half(_rms_norm(...))``
    over ``[B T, n, 1, 128]``), both
    held to that expression in float32: the kernel rounds once and must be
    no further from it than the plain bfloat16 path, which rounds twice.
    Each one's time is printed, forward alone and forward with both
    gradients (the least of five)."""
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.models.mellum import MellumConfig, head_norm_rope
    from distributed_lion_tpu.ops.pallas_qk_rope import qk_norm_rope

    cos, sin = MellumConfig().rope_full.angles(jnp.arange(T))

    def plain(y, scale):
        return head_norm_rope(y, scale, cos, sin, 1e-6)

    def fused(y, scale):
        return qk_norm_rope(y, scale, cos, sin, 1e-6)

    def gap(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.abs(a - b).max() / jnp.abs(b).max())

    for n in (32, 4):
        ks = jax.random.split(jax.random.key(45 + n), 3)
        y = 3 * jax.random.normal(ks[0], (B, T, n * 128), jnp.bfloat16)
        scale = 1 + 0.1 * jax.random.normal(ks[1], (128,), jnp.float32)
        w = jax.random.normal(ks[2], (B, T, n * 128), jnp.bfloat16)

        def both(fn):
            return jax.jit(jax.value_and_grad(
                lambda y, scale: (fn(y, scale).astype(jnp.float32)
                                  * w.astype(jnp.float32)).sum(), (0, 1)))

        kernels = set(mosaic_kernels(both(fused).lower(y, scale).as_text()))
        check({"qk_rope_fwd", "qk_rope_bwd"} <= kernels,
              f"the norm and rotation's kernels: {kernels}")
        exact = (plain(y.astype(jnp.float32), scale),
                 *both(plain)(y.astype(jnp.float32), scale)[1])
        at = f"[{B * T}, {n * 128}]"
        gaps = {}
        for name, fn in (("kernel", fused), ("plain", plain)):
            out, t_fwd = clock(jax.jit(fn), y, scale)
            (_, (dx, dscale)), t_both = clock(both(fn), y, scale)
            gaps[name] = [gap(a, b) for a, b in zip((out, dx, dscale), exact)]
            log(f"train_moe qk_rope {at} {name}: forward {t_fwd:.3f} ms, "
                f"with dx and dscale {t_both:.3f} ms; against float32 "
                "output {:.2e}, dx {:.2e}, dscale {:.2e} of the largest "
                "entry".format(*gaps[name]))
        # one bfloat16 rounding of the largest entry, and no further from
        # float32 than the expression that rounds twice
        check(max(gaps["kernel"]) < 4e-3
              and all(a <= b + 1e-4 for a, b in zip(gaps["kernel"],
                                                    gaps["plain"])),
              f"qk_rope {at} gaps {gaps}")


def phase_train_moe() -> None:
    """The trained expert-and-window block at the published widths
    (models/mellum: hidden 2,304, 32 query heads over 4 kv heads of 128,
    experts of 896, 16 of 64 held, top 8) on 8,192-token rows: the grouped
    product's gradient in the ``moe_gmm`` kernels against the autodiff of
    ``lax.ragged_dot``, the ``flash_gqa`` pair against the plain banded
    softmax with the window's edge held to the position, and a block's
    loss and gradients through the kernels in the lowered program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from distributed_lion_tpu.models.mellum import (
        MellumConfig,
        mellum_hidden,
        mellum_init,
    )
    from distributed_lion_tpu.ops import pallas_flash_attn as flash
    from distributed_lion_tpu.parallel.expert import grouped_matmul

    # -- the grouped product and its two gradient products, a held range
    # with a tail (three quarters of the rows), an empty group, one of 1
    d, f, held, rows = 2304, 896, 16, 8 * 8192
    sizes = jnp.array([2048, 0, 1, 4095] + [850] * 12, jnp.int32)
    n = int(sizes.sum())
    ks = jax.random.split(jax.random.key(43), 8)
    lhs = jax.random.normal(ks[0], (rows, d), jnp.bfloat16)
    rhs = (jax.random.normal(ks[1], (held, d, f)) * 0.02).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[2], (rows, f), jnp.bfloat16)
    live = (jnp.arange(rows) < n)[:, None]

    def through(product):
        def loss(lhs, rhs):
            out = product(lhs, rhs).astype(jnp.float32)
            return jnp.where(live, out * dy.astype(jnp.float32), 0).sum()

        return jax.jit(jax.grad(loss, (0, 1)))

    mine = through(lambda a, b: grouped_matmul(a, b, sizes, True))
    text = mine.lower(lhs, rhs).as_text()
    check({"moe_gmm", "moe_gmm_drhs"} <= set(mosaic_kernels(text)),
          f"the gradient's kernels: {mosaic_kernels(text)}")
    want = through(lambda a, b: lax.ragged_dot(
        a, b, sizes, preferred_element_type=jnp.float32).astype(a.dtype))
    got = mine(lhs, rhs)
    # rows past the last group: zero here, whatever the forward left there
    # (`ragged_dot`'s own gradient leaves them undefined on a TPU: compared
    # over the groups' rows only)
    check(float(jnp.abs(got[0][n:].astype(jnp.float32)).max()) == 0,
          "dlhs is not zero in the rows past the last group")
    for name, a, b in zip(("dlhs", "drhs"), got, want(lhs, rhs)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if name == "dlhs":
            a, b = a[:n], b[:n]
        gap = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        log(f"train_moe {name} against ragged_dot's autodiff: {gap:.2e} of "
            "the largest entry")
        check(gap < 2e-2, f"{name} gap {gap}")
        check(bool(jnp.isfinite(a).all()), f"{name} is not finite")

    # -- the combine's transpose, bounded by the rows in groups
    expert_combine_at_size()

    # -- q's and k's head norm and rotation where the projection wrote them
    qk_rope_at_size()

    # -- the attention pair at 32 / 4 heads of 128 over 8,192 positions
    T, H, KV, window = 8192, 32, 4, 1024
    q = jax.random.normal(ks[3], (1, T, H * 128), jnp.bfloat16)
    k = jax.random.normal(ks[4], (1, T, KV * 128), jnp.bfloat16)
    v = jax.random.normal(ks[5], (1, T, KV * 128), jnp.bfloat16)
    w = jax.random.normal(ks[6], (1, T, H * 128), jnp.bfloat16)

    def plain(q, k, v, window):
        """Rows 4,096 .. 4,607 of the masked softmax, float32 (a block of
        queries: 32 heads x 512 x 8,192 scores)."""
        rows_ = slice(4096, 4608)
        qh = q[0, rows_].reshape(512, KV, H // KV, 128).astype(jnp.float32)
        kh = k[0].reshape(T, KV, 128).astype(jnp.float32)
        vh = v[0].reshape(T, KV, 128).astype(jnp.float32)
        s = jnp.einsum("sgrd,tgd->grst", qh, kh,
                       precision=lax.Precision.HIGHEST) / 128 ** 0.5
        i = jnp.arange(4096, 4608)[:, None]
        j = jnp.arange(T)[None, :]
        seen = (j <= i) & ((i - j < window) if window else True)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("grst,tgd->sgrd", p, vh,
                          precision=lax.Precision.HIGHEST).reshape(512, -1)

    for win in (window, 0):
        def mine_loss(q, k, v):
            out = flash.flash_gqa(q, k, v, H, win)
            return (out[0, 4096:4608].astype(jnp.float32)
                    * w[0, 4096:4608].astype(jnp.float32)).sum(), out

        def plain_loss(q, k, v):
            out = plain(q, k, v, win)
            return (out * w[0, 4096:4608].astype(jnp.float32)).sum(), out

        a = jax.jit(jax.value_and_grad(mine_loss, (0, 1, 2), has_aux=True))
        b = jax.jit(jax.value_and_grad(plain_loss, (0, 1, 2), has_aux=True))
        kernels = set(mosaic_kernels(a.lower(q, k, v).as_text()))
        check({"flash_gqa_lse", "flash_gqa_dq", "flash_gqa_dkv"} <= kernels,
              f"the attention pair's kernels: {kernels}")
        (_, out_a), grads_a = a(q, k, v)
        (_, out_b), grads_b = b(q, k, v)
        gap = float(jnp.abs(out_a[0, 4096:4608].astype(jnp.float32)
                            - out_b).max())
        log(f"train_moe flash_gqa window {win}: output gap {gap:.2e}")
        check(gap < 3e-2, f"flash_gqa window {win} output gap {gap}")
        for name, ga, gb in zip("qkv", grads_a, grads_b):
            ga, gb = ga.astype(jnp.float32), gb.astype(jnp.float32)
            gap = float(jnp.abs(ga - gb).max() / jnp.abs(gb).max())
            log(f"train_moe flash_gqa window {win}: d{name} gap {gap:.2e} "
                "of the largest entry")
            check(gap < 4e-2, f"flash_gqa window {win} d{name} gap {gap}")
    # a loud key at j0: read by the query at j0 + 1023, not by j0 + 1024
    j0 = 3000
    e = jnp.zeros((128,), jnp.bfloat16).at[0].set(12.0)   # score 12.7
    seen = jax.jit(lambda: flash.flash_gqa(
        jnp.tile(e, (1, T, H)), jnp.zeros((1, T, KV * 128), jnp.bfloat16
                                          ).at[0, j0, :128].set(e),
        jnp.zeros((1, T, KV * 128), jnp.bfloat16).at[0, j0, :128].set(1.0),
        H, window))()[0, :, 0].astype(jnp.float32)
    check(float(seen[j0 - 1]) == 0 and float(seen[j0]) > 0.9
          and float(seen[j0 + window - 1]) > 0.9
          and float(seen[j0 + window]) == 0,
          f"the window's edge: {seen[j0 - 1]}, {seen[j0]}, "
          f"{seen[j0 + window - 1]}, {seen[j0 + window]}")
    log("train_moe window edge: key 3000 read by queries 3000 .. 4023 only")

    # -- one window and one full block, loss and gradients in one program
    cfg = MellumConfig(vocab_size=1024, n_layer=2, windowed=(True, False),
                       held=(0, 16), remat_policy="full")
    params = jax.jit(lambda key: mellum_init(key, cfg))(ks[7])
    tokens = jax.random.randint(ks[7], (1, T), 0, 1024)

    def loss(params, tokens):
        hidden, counters = mellum_hidden(params, tokens, cfg)
        return hidden.astype(jnp.float32).mean(), counters

    step = jax.jit(jax.value_and_grad(loss, has_aux=True))
    kernels = set(mosaic_kernels(step.lower(params, tokens).as_text()))
    check({"moe_gmm", "moe_gmm_drhs", "flash_gqa_lse", "flash_gqa_dq",
           "flash_gqa_dkv", "qk_rope_fwd", "qk_rope_bwd"} <= kernels,
          f"the block's kernels: {kernels}")
    (value, counters), grads = step(params, tokens)
    flat = jnp.concatenate([g.reshape(-1) for g in jax.tree.leaves(grads)])
    check(bool(jnp.isfinite(flat).all()) and bool(jnp.isfinite(value)),
          "a gradient of the block is not finite")
    check(int(counters["moe_routed"]) == 2 * T * 8, counters)
    share = int(counters["moe_assignments"]) / int(counters["moe_routed"])
    log(f"train_moe block: kernels {sorted(kernels)}, held share "
        f"{share:.3f} of the picks, {int(counters['moe_experts_hit'])} "
        "experts hit in 2 layers")
    check(0.15 < share < 0.35, f"held share {share}")


def _metrics_rows(out_dir: str) -> list:
    with open(os.path.join(out_dir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def _lowered_step_text(trainer) -> str:
    import jax

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    cfg = trainer.cfg
    batch = jax.ShapeDtypeStruct(
        (trainer.global_train_batch(), cfg.block_size), "int32",
        sharding=jax.sharding.NamedSharding(trainer.mesh, trainer.batch_spec))
    args = jax.tree.map(abstract, (trainer.params, trainer.state,
                                   trainer.vote_health, trainer._frozen_arg()))
    return trainer._train_step.lower(
        *args, batch, jax.random.key(cfg.seed + 1)).as_text()


def phase_train(out_dir: str) -> None:
    import math

    from distributed_lion_tpu.cli import run_clm
    from distributed_lion_tpu.ops import attention as attention_ops
    from distributed_lion_tpu.ops.pallas_lion import resolve_kernel_mode
    from distributed_lion_tpu.train import journal, loop, resilience

    check(resolve_kernel_mode("auto") is False,
          "kernel=auto did not resolve to the compiled Pallas path")
    check(attention_ops.qkv_kernel_applies(1024, 12, 64, "bfloat16"),
          "attention auto does not take ops/pallas_flash_attn at the train "
          "step's shape (T 1024, 12 heads of 64, bf16)")

    argv = TRAIN_ARGV + ["--output_dir", out_dir, "--save_steps",
                         str(TRAIN_STEPS)]
    trainers: list = []
    t0 = time.time()
    with captured(loop.Trainer, "train", trainers):
        run_clm.main(argv + ["--max_steps", str(TRAIN_STEPS)])
    wall = time.time() - t0
    trainer = trainers[0]
    rows = [r for r in _metrics_rows(out_dir) if "train/loss" in r]
    losses = [r["train/loss"] for r in rows]
    check([r["step"] for r in rows] == list(range(1, TRAIN_STEPS + 1)),
          rows)
    check(all(isinstance(x, float) and math.isfinite(x) for x in losses),
          losses)
    vote_keys = sorted(k for k in rows[-1] if k.startswith("train/vote/"))
    check(vote_keys,
          "no train/vote/* telemetry in metrics.jsonl")
    check(abs(sum(rows[-1]["train/vote/margin_hist"]) - 1.0) < 1e-6,
          'abs(sum(rows[-1]["train/vote/margin_hist"]) - 1.0) < 1e-6')
    text = _lowered_step_text(trainer)
    kernels = mosaic_kernels(text)
    check(kernels,
          "train step lowered without a Mosaic kernel")
    check({"flash_attention_fwd", "flash_mha_bwd"} <= set(kernels),
          f"attention auto did not put ops/pallas_flash_attn's kernels in "
          f"the step: {kernels}")
    check({"fused_xent_fwd", "fused_xent_bwd"} <= set(kernels),
          f"the dense branch's loss head did not put ops/pallas_xent's "
          f"kernels in the step: {kernels}")
    check("50257xf32" not in text,
          "the step holds a float32 buffer of the logits' shape")
    log("  " + "; ".join(journal.new_resolved_lines()
                        or ["attention, cross-entropy: resolved lines "
                            "already printed by the trainer"]))
    tokens_per_step = trainer.global_train_batch() * trainer.cfg.block_size
    step_s = [tokens_per_step / r["train/tokens_per_sec"] for r in rows]
    log(f"  run_clm: {TRAIN_STEPS} steps, losses {losses}, wire="
        f"{trainer.cfg.wire} kernel=auto->pallas, Mosaic kernels in the "
        f"step: {kernels}")
    log(f"  telemetry keys: {vote_keys}")
    log(f"  info only: first step (compile included) {step_s[0]:.1f} s, "
        f"steady step {min(step_s[1:]):.3f} s, phase wall {wall:.1f} s")

    ckpt_dir = os.path.join(out_dir, "checkpoints")
    step = resilience.latest_valid_step_in(ckpt_dir)
    check(step == TRAIN_STEPS,
          f"newest committed checkpoint is {step}")
    check(resilience.verify_step_dir(resilience.step_dir(ckpt_dir, step)),
          'resilience.verify_step_dir(resilience.step_dir(ckpt_dir, step))')
    # restorable, through the user's path: the same command resumes from
    # the committed step and trains one more
    resumed: list = []
    with captured(loop.Trainer, "train", resumed):
        run_clm.main(argv + ["--max_steps", str(TRAIN_STEPS + 1)])
    rows2 = [r for r in _metrics_rows(out_dir) if "train/loss" in r]
    check([r["step"] for r in rows2][-2:] == [TRAIN_STEPS, TRAIN_STEPS + 1],
          '[r["step"] for r in rows2][-2:] == [TRAIN_STEPS, TRAIN_STEPS + 1]')
    check(math.isfinite(rows2[-1]["train/loss"]),
          'math.isfinite(rows2[-1]["train/loss"])')
    check(resumed[0].step_count == TRAIN_STEPS + 1,
          'resumed[0].step_count == TRAIN_STEPS + 1')
    log(f"  checkpoint step {step} committed, verified and resumed from: "
        f"step {TRAIN_STEPS + 1} loss {rows2[-1]['train/loss']}")
    check(os.path.isfile(os.path.join(out_dir, "model.npz")),
          'os.path.isfile(os.path.join(out_dir, "model.npz"))')


# ------------------------------------------------------------------- serve
SERVE_PROMPTS = [
    "The vote",                                            # 8 tokens
    "Majority vote over sign bits, " * 2,                  # 60
    "a longer prompt for a bigger prefill bucket. " * 5,   # 225
    "Lion momentum diverges per worker. ",                 # 35
    "ties elect minus one",                                # 20
]
SERVE_NEW_TOKENS = 12
SERVE_BLOCK, SERVE_MAX_BLOCKS = 16, 32   # 512 attended slots per sequence


def assert_donated(engine) -> None:
    donated = {k: d["donate"] for k, d in engine._dispatches.items()}
    check(donated["decode"] and donated["prefill"],
          f"page pool not donated off-CPU: {donated}")


def assert_pool_in_place(engine, kernels=("paged_attn",)) -> None:
    """The gate on the pool's resident layout: compile ``decode``,
    ``prefill`` and ``cow`` as the engine jitted them and fail if the
    optimized HLO copies anything the size of a pool leaf (a re-layout of
    the pool inside a dispatch: serve/kv_cache's module note), or if the
    decode program lacks one of ``kernels``."""
    from distributed_lion_tpu.analysis.serve_check import (
        lowered_dispatch,
        pool_leaf_copies,
    )

    # one leaf of every shape the pool holds (a window family: pages, rings)
    leaves = list({leaf.shape: leaf for layer in engine.pages
                   for leaf in layer.values()}.values())
    bucket = engine.cfg.block_size * engine.cfg.max_blocks_per_seq
    for kind in ("decode", "prefill", "cow"):
        lowered = lowered_dispatch(engine, kind, bucket)
        text = lowered.compile().as_text()
        for leaf in leaves:
            copies = pool_leaf_copies(text, leaf)
            check(not copies, f"{kind} copies the pool: {copies[:2]}")
        if kind == "decode":
            held = mosaic_kernels(lowered.as_text())
            check(set(kernels) <= set(held),
                  f"decode holds {held}, not all of {kernels}")
    log(f"  decode, prefill@{bucket} and cow hold no copy of a pool leaf ("
        + ", ".join(f"{x.dtype.name}{list(x.shape)}" for x in leaves)
        + f"); decode holds {', '.join(kernels)}")


def phase_serve(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.cli import run_generate, run_serve
    from distributed_lion_tpu.models.gpt2 import gpt2_decode_paged
    from distributed_lion_tpu.serve.kv_cache import init_pages

    model = ["--model_path", out_dir, "--model_family", "gpt2",
             "--model_name", MODEL_NAME, "--vocab_size", str(VOCAB)]
    block, max_blocks = SERVE_BLOCK, SERVE_MAX_BLOCKS
    engines: list = []
    t0 = time.time()
    with captured(run_serve, "build_engine", engines):
        records = run_serve.main(
            model + ["--prompt", *SERVE_PROMPTS, "--temperature", "0",
                     "--max_new_tokens", str(SERVE_NEW_TOKENS),
                     "--max_seqs", "3", "--block_size", str(block),
                     "--max_blocks_per_seq", str(max_blocks)])
    wall = time.time() - t0
    tok, engine = engines[0]
    check(len(records) == len(SERVE_PROMPTS),
          'len(records) == len(SERVE_PROMPTS)')
    for rec in records:
        check(rec["reason"] in ("length", "eos"),
              rec)
        check(1 <= len(rec["tokens"]) <= SERVE_NEW_TOKENS,
              rec)
    check(not engine.has_work() and not engine.pending,
          'not engine.has_work() and not engine.pending')
    check(engine.tables.free_blocks == engine.tables.num_blocks,
          "page pool did not return to empty")
    counts = engine.compile_counts()
    stats = engine.stats
    check(stats["prefill_dispatches"] == len(SERVE_PROMPTS),
          'stats["prefill_dispatches"] == len(SERVE_PROMPTS)')
    check(stats["decode_ticks"] > 0 and counts.get("prefill", 0) >= 2,
          (stats, counts))
    assert_donated(engine)
    assert_pool_in_place(engine)
    check(stats["decode_attn_kernel_ticks"] == stats["decode_ticks"], stats)
    log(f"  decode_attn_kernel_ticks {stats['decode_attn_kernel_ticks']} of "
        f"{stats['decode_ticks']} decode ticks; kv_pages_read "
        f"{stats['kv_pages_read']} of kv_pages_table "
        f"{stats['kv_pages_table']}; run_ahead_ticks "
        f"{stats['run_ahead_ticks']} of them, run_ahead_drains "
        f"{stats['run_ahead_drains']}, run_ahead_discarded "
        f"{stats['run_ahead_discarded']}; prefill_fresh_dispatches "
        f"{stats['prefill_fresh_dispatches']} of "
        f"{stats['prefill_dispatches']} prefills; read_wait_s "
        f"{stats['read_wait_s']:.3f}, gc_pause_s {stats['gc_pause_s']:.3f} "
        f"in {stats['gc_collections']} collections, slow_ticks "
        f"{stats['slow_ticks']} (slow_tick_excess_s "
        f"{stats['slow_tick_excess_s']:.3f})")
    log(f"  run_serve: {len(records)} requests complete "
        f"({[r['reason'] for r in records]}), pool back to "
        f"{engine.tables.free_blocks}/{engine.tables.num_blocks} free, "
        f"donated dispatches compiled: {counts}, "
        f"ticks={stats['ticks']} decode_ticks={stats['decode_ticks']}, "
        f"wall {wall:.1f} s")

    # the repo's own reference: the dense-cache run_generate path. Teacher-
    # force prompt + served tokens through both caches; compare logits, and
    # tokens wherever the reference's top-2 margin exceeds the tolerance (a
    # model a few steps old has near-flat logits)
    gen_args = run_generate.GenerateArguments(
        model_path=out_dir, model_family="gpt2", model_name=MODEL_NAME,
        vocab_size=VOCAB)
    _, cfg, params, decode, init_cache = run_generate.build(gen_args)
    params = jax.device_put(params)
    attended = block * max_blocks
    # one right-padded batch (causal: a pad tail cannot reach a real
    # position), so each cache path compiles ONE program for all requests
    seqs = [tok.encode(p, add_bos=False) + rec["tokens"][:-1]
            for p, rec in zip(SERVE_PROMPTS, records)]
    n_seq, width = len(seqs), -(-max(map(len, seqs)) // block) * block
    toks = np.zeros((n_seq, width), np.int32)
    for row, seq in zip(toks, seqs):
        row[:len(seq)] = seq
    dense = jax.jit(lambda p, t: decode(
        p, t, init_cache(n_seq, attended), 0)[0])(params, toks)
    pages = init_pages(cfg.n_layer, n_seq * max_blocks, block, cfg.n_head,
                       cfg.head_dim, cfg.compute_dtype)
    # shuffled page ownership: every read must go through the table
    tables = jnp.arange(n_seq * max_blocks, dtype=jnp.int32)[::-1].reshape(
        n_seq, max_blocks)
    # as the engine serves them: the prompts in one window (the gather
    # path), then the served tokens one a step (S = 1: the paged_attn
    # kernel), each row at its own position
    gots = [rec["tokens"] for rec in records]
    plens = np.asarray([len(s) - len(g) + 1 for s, g in zip(seqs, gots)])
    p_width = -(-int(plens.max()) // block) * block
    window, pages = jax.jit(lambda p, t, pg: gpt2_decode_paged(
        p, t, cfg, pg, tables, jnp.zeros((n_seq,), jnp.int32),
        jnp.arange(p_width)[None, :] < jnp.asarray(plens)[:, None]))(
            params, toks[:, :p_width], pages)
    step = jax.jit(lambda p, t, pg, pos, act: gpt2_decode_paged(
        p, t, cfg, pg, tables, pos, act), donate_argnums=(2,))
    text = step.lower(params, np.zeros((n_seq, 1), np.int32), pages,
                      jnp.asarray(plens, jnp.int32),
                      np.ones((n_seq, 1), bool)).as_text()
    check("paged_attn" in mosaic_kernels(text),
          f"the single-token step holds no paged_attn kernel: "
          f"{mosaic_kernels(text)}")
    rows = [[np.asarray(window[i, n - 1], np.float32)]
            for i, n in enumerate(plens)]
    for j in range(max(map(len, gots)) - 1):
        act = np.asarray([j < len(g) - 1 for g in gots])
        nxt = np.asarray([g[j] if a else 0 for g, a in zip(gots, act)],
                         np.int32)
        logits, pages = step(params, nxt[:, None], pages,
                             jnp.asarray(plens + j, jnp.int32), act[:, None])
        for i in np.flatnonzero(act):
            rows[i].append(np.asarray(logits[i, 0], np.float32))
    worst, checked, total = 0.0, 0, 0
    for i, (prompt, rec) in enumerate(zip(SERVE_PROMPTS, records)):
        got = np.asarray(rec["tokens"])
        first = len(seqs[i]) - len(got)  # the last prompt position
        d = np.asarray(dense[i, first:len(seqs[i])], np.float32)
        g = np.stack(rows[i])
        check(np.isfinite(d).all() and d.shape == (len(got), VOCAB), d.shape)
        worst = max(worst, float(np.abs(d - g).max()))
        top2 = np.sort(d, axis=-1)[:, -2:]
        decisive = (top2[:, 1] - top2[:, 0]) > 2 * LOGIT_TOL
        total += len(decisive)
        checked += int(decisive.sum())
        check((d.argmax(-1)[decisive] == got[decisive]).all(),
              (prompt, d.argmax(-1).tolist(), got.tolist()))
    log(f"  paged vs dense logits (teacher-forced on the served tokens): "
        f"max |diff| {worst:.5f} (tol {LOGIT_TOL}); served tokens equal "
        f"the dense argmax at all {checked} of {total} positions whose "
        f"top-2 margin exceeds {2 * LOGIT_TOL}")
    check(worst <= LOGIT_TOL,
          f"paged logits off by {worst}")


def teacher_forced_gap(decode, fresh, prompts, gots, block) -> float:
    """Largest |difference| of logits, teacher-forced on the served tokens,
    between one window over prompt + served (S > 1) and the prompt's window
    followed by one token a step (S = 1: the kernel path).
    ``decode(toks, pages, pos, valid) -> (logits, pages)`` is the family's
    paged hook over fixed tables; ``fresh()`` an empty pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_seq = len(prompts)
    seqs = [p + g[:-1] for p, g in zip(prompts, gots)]
    width = -(-max(map(len, seqs)) // block) * block
    toks = np.zeros((n_seq, width), np.int32)
    for row, seq in zip(toks, seqs):
        row[:len(seq)] = seq
    zero = jnp.zeros((n_seq,), jnp.int32)
    whole = jax.jit(lambda t, pg: decode(t, pg, zero, None)[0])(toks, fresh())
    plens = np.asarray([len(p) for p in prompts])
    p_width = -(-int(plens.max()) // block) * block
    window, pages = jax.jit(lambda t, pg: decode(
        t, pg, zero,
        jnp.arange(p_width)[None, :] < jnp.asarray(plens)[:, None]))(
            toks[:, :p_width], fresh())
    step = jax.jit(decode, donate_argnums=(1,))
    worst = max(float(jnp.abs(window[i, n - 1] - whole[i, n - 1]).max())
                for i, n in enumerate(plens))
    for j in range(len(gots[0]) - 1):
        nxt = np.asarray([g[j] for g in gots], np.int32)
        logits, pages = step(nxt[:, None], pages,
                             jnp.asarray(plens + j, jnp.int32),
                             np.ones((n_seq, 1), bool))
        for i in range(n_seq):
            worst = max(worst, float(jnp.abs(
                logits[i, 0] - whole[i, plens[i] + j]).max()))
    return worst


def phase_serve_latent() -> None:
    """The latent-cache family (models/joyai) at a small lane-aligned size
    through the constructors ``run_serve --model_family joyai`` calls: the
    decode tick holds ``mla_paged_attn`` and ``moe_gmm`` and no dispatch
    copies the one pool leaf; the dropless layer's counters conserve
    tokens; and the S = 1 kernel path (absorbed attention, grouped-matmul
    kernel at a few rows an expert) gives the logits of the one-window
    gather path (expanded keys and values) on the tokens it served."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.joyai import (
        JoyAIConfig, joyai_decode_paged, joyai_init,
    )
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServeModel, ServingEngine,
    )
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    cfg = JoyAIConfig.tiny(
        vocab_size=1024, d_model=256, n_head=4, q_lora_rank=128,
        kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=64, d_ff=512, n_experts=8, top_k=2, moe_d_ff=128)
    params = joyai_init(jax.random.key(26), cfg)
    block, max_blocks = 16, 8
    model = ServeModel.for_joyai(params, cfg)
    engine = ServingEngine(model, ServeConfig(
        max_seqs=4, block_size=block, max_blocks_per_seq=max_blocks,
        moe_stats=True, prefill_cap_tokens=128))
    rng = np.random.default_rng(26)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (21, 37, 50)]
    done = engine.run([Request(req_id=i, tokens=p,
                               max_new_tokens=SERVE_NEW_TOKENS)
                       for i, p in enumerate(prompts)])
    stats = engine.stats
    check(all(done[i].reason == "length" for i in range(len(prompts))), done)
    check(engine.tables.free_blocks == engine.tables.num_blocks,
          "latent page pool did not return to empty")
    assert_donated(engine)
    assert_pool_in_place(engine, kernels=("mla_paged_attn", "moe_gmm"))
    check(stats["mla_kernel_ticks"] == stats["decode_ticks"] > 0, stats)
    moe_layers = cfg.n_layer - cfg.first_dense
    check(stats["moe_assignments"]
          == stats["decode_tokens"] * cfg.top_k * moe_layers, stats)
    check(stats["moe_prefill_assignments"]
          == stats["prefill_tokens"] * cfg.top_k * moe_layers, stats)
    log(f"  mla_kernel_ticks {stats['mla_kernel_ticks']} of "
        f"{stats['decode_ticks']} decode ticks; kv_pages_read "
        f"{stats['kv_pages_read']} of kv_pages_table "
        f"{stats['kv_pages_table']}; moe_assignments "
        f"{stats['moe_assignments']} (= tokens x {cfg.top_k}), "
        f"moe_experts_hit {stats['moe_experts_hit']}, moe_load_max "
        f"{stats['moe_load_max']}; prefill: "
        f"{stats['moe_prefill_assignments']} / "
        f"{stats['moe_prefill_experts_hit']} / "
        f"{stats['moe_prefill_load_max']}")

    # teacher-forced on the served tokens: one window over prompt + served
    # (S > 1: gather, expand, chunked attention) against the prompt's window
    # then one token a step (S = 1: the absorbed kernel)
    n_seq = len(prompts)
    tables = jnp.arange(n_seq * max_blocks, dtype=jnp.int32)[::-1].reshape(
        n_seq, max_blocks)
    worst = teacher_forced_gap(
        lambda t, pg, pos, valid: joyai_decode_paged(
            params, t, cfg, pg, tables, pos, valid),
        lambda: init_page_leaves(cfg.n_layer, n_seq * max_blocks, block,
                                 model.page_leaves, cfg.compute_dtype),
        prompts, [done[i].tokens for i in range(n_seq)], block)
    log(f"  absorbed kernel path vs expanded gather path, logits "
        f"teacher-forced on the served tokens: max |diff| {worst:.5f} "
        f"(tol {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"latent decode logits off by {worst}")
    latent_prefill_at_size()


def latent_prefill_at_size() -> None:
    """A latent family's prefill attention from position 0 at the published
    shape (32 heads of 192 / 128, rank 512, YaRN scale 0.14468: Xing4.0 and,
    but for the scale, JoyAI), at 4,096 and 2,048 positions: the tiled
    kernel ``latent_prefill`` (``ops/attention.latent_fresh_attention``,
    rows of 3/4 of the window as the backlog cells' prompts average, and
    whole) against the chunked XLA walk over the expanded rows every other
    call takes, each timed alone from the same q, rows and ``w_kvb`` to the
    token-major output, and both against a float32 softmax on the last 256
    queries."""
    import functools

    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.models.joyai import expand_rows
    from distributed_lion_tpu.ops.attention import (
        chunked_causal_attention, latent_fresh_applies,
        latent_fresh_attention,
    )

    H, r, dn, dr, dv, scale, tail = 32, 512, 128, 64, 128, 0.14468, 256
    bf, f32 = jnp.bfloat16, jnp.float32
    for S in (4096, 2048):
        ks = jax.random.split(jax.random.key(47 + S), 3)
        q = (2.0 * jax.random.normal(ks[0], (1, H, S, dn + dr))).astype(bf)
        row = jax.random.normal(ks[1], (1, S, r + dr), bf)
        w = (jax.random.normal(ks[2], (r, H, dn + dv)) / r ** 0.5).astype(bf)
        pos = jnp.zeros((1,), jnp.int32)
        some = jnp.arange(S)[None, :] < 3 * S // 4

        def walk(q, row, w):
            k, v = expand_rows(row, w, dn)
            out = chunked_causal_attention(q, k, v, pos, scale=scale)
            return out.transpose(0, 2, 1, 3).reshape(1, S, H * dv)

        def reference(q, row, w):
            k, v = expand_rows(row, w, dn)
            s = jnp.einsum("hsd,htd->hst", q[0, :, -tail:].astype(f32),
                           k[0].astype(f32), precision="highest") * scale
            seen = jnp.arange(S)[None, :] <= (S - tail
                                              + jnp.arange(tail))[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            out = jnp.einsum("hst,htd->hsd", p, v[0].astype(f32),
                             precision="highest")
            return out.transpose(1, 0, 2).reshape(tail, H * dv)

        kernel = functools.partial(latent_fresh_attention, scale=scale)
        out, full_ms = clock(jax.jit(kernel), q, row, w)
        part, part_ms = clock(jax.jit(kernel), q, row, w, some)
        ref_walk, walk_ms = clock(jax.jit(walk), q, row, w)
        ref = jax.jit(reference)(q, row, w)
        top = float(jnp.abs(ref).max())
        err = float(jnp.abs(out[0, -tail:].astype(f32) - ref).max()) / top
        err_walk = float(jnp.abs(ref_walk[0, -tail:].astype(f32)
                                 - ref).max()) / top
        apart = float(jnp.abs(part[:, :3 * S // 4].astype(f32)
                              - out[:, :3 * S // 4].astype(f32)).max())
        past = -(-3 * S // 4 // 512) * 512   # the first tile wholly past it
        log(f"  latent prefill at {S} positions, {H} heads of {dn + dr} / "
            f"{dv}: {full_ms:.2f} ms a layer through latent_prefill "
            f"({part_ms:.2f} with rows of {3 * S // 4}), {walk_ms:.2f} ms "
            f"through the chunked XLA walk; last {tail} queries against a "
            f"float32 softmax: worst |diff| / max {err:.5f} the kernel, "
            f"{err_walk:.5f} the walk (tol 0.02); the rule takes it: "
            f"{latent_fresh_applies(S, dn, dv)}")
        check(err <= 0.02, f"latent_prefill off by {err}")
        check(apart == 0.0, f"a row's length moved its real queries: {apart}")
        check(not bool(part[:, past:].any()),
              "a tile past the row's length is not zero")


def phase_serve_window() -> None:
    """The window-and-full family (models/laguna) at a small lane-aligned
    size through the constructors ``run_serve --model_family laguna``
    calls: the decode tick holds ``paged_attn`` for both layer kinds (one
    call a layer) and ``moe_gmm`` over the banks held, no dispatch copies a
    page leaf or a ring; the counters say what a window layer's walk read
    against a full layer's and how many picks were held here; and the S = 1
    kernel path over ring and pages gives the logits of one prefill window
    over the same tokens (fresh keys, banded). Then the ring's walk at the
    published shape, held to the position (:func:`ring_edges_at_size`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.analysis.serve_check import lowered_dispatch
    from distributed_lion_tpu.models.laguna import (
        LagunaConfig, Rope, laguna_decode_paged, laguna_init,
    )
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServeModel, ServingEngine,
    )
    from distributed_lion_tpu.ops.attention import ring_pages
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    cfg = LagunaConfig.tiny(
        vocab_size=1024, d_model=256, n_kv_head=2, head_dim=64,
        heads=(4, 6, 6, 6, 4), window=40, d_ff=512, moe_d_ff=128,
        shared_d_ff=128, held=(0, 4),
        rope_full=Rope(5e5, 32, 128.0, 64, 32.0, 1.0, 1.4852030263919618),
        rope_window=Rope(1e4, 64))
    params = laguna_init(jax.random.key(30), cfg)
    block, max_blocks = 16, 8
    ring = ring_pages(cfg.window, block)                      # 4 pages
    model = ServeModel.for_laguna(params, cfg)
    engine = ServingEngine(model, ServeConfig(
        max_seqs=4, block_size=block, max_blocks_per_seq=max_blocks,
        moe_stats=True, prefill_cap_tokens=128))
    rng = np.random.default_rng(30)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (21, 64, 90)]
    done = engine.run([Request(req_id=i, tokens=p,
                               max_new_tokens=SERVE_NEW_TOKENS)
                       for i, p in enumerate(prompts)])
    stats = engine.stats
    check(all(done[i].reason == "length" for i in range(len(prompts))), done)
    check(engine.tables.free_blocks == engine.tables.num_blocks,
          "page pool did not return to empty")
    check([layer["k"].shape[0] for layer in engine.pages]
          == [32, 4 * ring, 4 * ring, 4 * ring, 32], engine.pages)
    assert_donated(engine)
    assert_pool_in_place(engine, kernels=("paged_attn", "moe_gmm"))
    calls = len(re.findall(
        r"%paged_attn(?:\.\d+)? = [^\n]*custom-call",
        lowered_dispatch(engine, "decode").compile().as_text()))
    check(calls == cfg.n_layer, f"{calls} paged_attn calls in decode")
    check(stats["window_kernel_ticks"] == stats["decode_attn_kernel_ticks"]
          == stats["decode_ticks"] > 0, stats)
    moe_layers = cfg.n_layer - len(cfg.dense_layers)
    check(stats["moe_routed"]
          == stats["decode_tokens"] * cfg.top_k * moe_layers, stats)
    check(0 < stats["moe_assignments"] < stats["moe_routed"], stats)
    check(stats["kv_window_pages_read"] < stats["kv_pages_read"], stats)
    log(f"  window_kernel_ticks {stats['window_kernel_ticks']} and "
        f"decode_attn_kernel_ticks {stats['decode_attn_kernel_ticks']} of "
        f"{stats['decode_ticks']} decode ticks; kv_pages_read "
        f"{stats['kv_pages_read']} of kv_pages_table "
        f"{stats['kv_pages_table']}, kv_window_pages_read "
        f"{stats['kv_window_pages_read']} (a ring of {ring} pages a slot); "
        f"moe_assignments {stats['moe_assignments']} of moe_routed "
        f"{stats['moe_routed']} (experts 0-3 of 8 held), moe_experts_hit "
        f"{stats['moe_experts_hit']}, moe_load_max {stats['moe_load_max']}; "
        f"prefill: {stats['moe_prefill_assignments']} of "
        f"{stats['moe_prefill_routed']} / "
        f"{stats['moe_prefill_experts_hit']} / "
        f"{stats['moe_prefill_load_max']}")

    # teacher-forced on the served tokens: one prefill window over prompt +
    # served (fresh keys, banded) against the prompt's window and then one
    # token a step through ring and pages (S = 1: the kernel, both kinds)
    n_seq = len(prompts)
    tables = jnp.arange(n_seq * max_blocks, dtype=jnp.int32)[::-1].reshape(
        n_seq, max_blocks)
    slots = jnp.arange(n_seq, dtype=jnp.int32)[::-1]
    worst = teacher_forced_gap(
        lambda t, pg, pos, valid: laguna_decode_paged(
            params, t, cfg, pg, tables, slots, pos, valid),
        lambda: init_page_leaves(
            cfg.n_layer, n_seq * max_blocks, block, model.page_leaves,
            cfg.compute_dtype, ring=(cfg.window_layers, n_seq * ring)),
        prompts, [done[i].tokens for i in range(n_seq)], block)
    log(f"  kernel path over ring and pages vs one banded prefill window, "
        f"logits teacher-forced on the served tokens: max |diff| "
        f"{worst:.5f} (tol {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"window decode logits off by {worst}")
    ring_edges_at_size()
    full_prefill_at_size()


def ring_edges_at_size() -> None:
    """A window layer's decode walk at the PUBLISHED shape (72 query heads
    over 8 kv heads of 128, window 512, pages of 16: a ring of 33), held to
    the position. Random keys weigh nearly alike, so 16 keys more or fewer
    of 512 move a head's output by 3% and bfloat16 hides a key at the edge
    (PERF.md, PR 30: the benchmark's ``correct`` passes a window of 496).
    Here a loud key (a score some 17 above the others') sits just inside
    each edge of every row's window, at ``pos - 511`` and at ``pos``, and
    one just outside at ``pos - 512``: a walk that starts a key early or
    late, drops the query's own position or reads a page the ring has
    given to a later position changes a head's output by a third of its
    size. Rows end inside a page, at a page's edge and past one, two and
    seventeen laps of the ring. The reference is a float32 softmax over
    the last 512 keys of the plain sequence. Then the check itself is
    checked: the same walk told the window is 496 or 513, or over rings of
    32 pages, must fail it."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.ops import attention as ops

    H, KV, hd, window, bs = 72, 8, 128, 512, 16
    R = ops.ring_pages(window, bs)
    ends = [511, 512, 527, 528, 1040, 33 * 16 * 2 - 1, 33 * 16 * 2, 8959]
    B, T = len(ends), max(ends) + 1
    rng = np.random.default_rng(3030)
    loud = rng.standard_normal((B, KV, hd)).astype(np.float32)
    loud *= math.sqrt(hd) / np.linalg.norm(loud, axis=-1, keepdims=True)
    k = rng.standard_normal((B, T, KV, hd)).astype(np.float32)
    v = rng.standard_normal((B, T, KV, hd)).astype(np.float32)
    for b, p in enumerate(ends):
        for at in (p - window, p - window + 1, p):
            if at >= 0:
                k[b, at] = 1.5 * loud[b]
    q = np.repeat(loud, H // KV, 1) \
        + 0.5 * rng.standard_normal((B, H, hd)).astype(np.float32)
    q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v))
    pos = jnp.asarray(ends, jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)[::-1]

    def walk(window):
        """The sequences written into their rings as a prefill writes
        them, then one decode read a row."""
        leaf = jnp.zeros((B * ops.ring_pages(window, bs), bs, 1, KV * hd),
                         jnp.bfloat16)
        kp, vp = (ops.ring_scatter_kv(leaf, slots, jnp.zeros_like(pos), t,
                                      pos + 1, window=window) for t in (k, v))
        out, read = ops.ring_decode_attention(
            q[:, :, None], kp, vp, slots, pos, window=window, kv_heads=KV)
        return np.asarray(out[:, :, 0], np.float32), np.asarray(read)

    want = np.zeros((B, H, hd), np.float32)
    kf, vf, qf = (np.asarray(t, np.float32) for t in (k, v, q))
    for b, p in enumerate(ends):
        lo = max(p - window + 1, 0)
        kk = np.repeat(kf[b, lo:p + 1], H // KV, 1)
        vv = np.repeat(vf[b, lo:p + 1], H // KV, 1)
        sc = np.einsum("hd,thd->ht", qf[b], kk) / math.sqrt(hd)
        w = np.exp(sc - sc.max(1, keepdims=True))
        want[b] = np.einsum("ht,thd->hd", w / w.sum(1, keepdims=True), vv)

    def off(got):
        return float(np.abs(got - want).max() / np.abs(want).max())

    got, read = walk(window)
    sound = off(got)
    check(read.tolist() == [p // bs - max(p - window + 1, 0) // bs + 1
                            for p in ends] and read.max() <= R, read)
    early, late = off(walk(window + 1)[0]), off(walk(window - 16)[0])
    whole, ops.ring_pages = ops.ring_pages, lambda w, b: -(-w // b)
    try:       # a ring that holds the pages a window fills and none more
        short = off(walk(window)[0])
    finally:
        ops.ring_pages = whole
    log(f"  ring walk at 72 heads x 128, window 512, {B} rows ending at "
        f"{ends}: worst |diff| / max {sound:.5f} (tol 0.02; pages handed "
        f"{read.tolist()}); told 513: {early:.3f}, told 496: {late:.3f}, "
        f"a ring of 32 pages: {short:.3f} (each must exceed 0.1)")
    check(sound <= 0.02, f"ring walk at the published shape off by {sound}")
    check(min(early, late, short) > 0.1,
          f"the ring check does not see a wrong window: {early} {late} "
          f"{short}")


def full_prefill_at_size() -> None:
    """A full layer's prefill attention at the PUBLISHED shape (48 query
    heads over 8 kv heads of 128) over 2,048 fresh keys: on the chip
    ``banded_causal_attention`` without a band is the tiled kernel
    ``flash_gqa_fwd``, held to a float32 softmax over each query's own
    and earlier keys; a loud key sits right after every 100th query, so a
    block that reads one position past the diagonal fails it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.ops import attention as ops

    H, KV, hd, S = 48, 8, 128, 2048
    rng = np.random.default_rng(3031)
    q, k, v = (rng.standard_normal((1, n, S, hd)).astype(np.float32)
               for n in (H, KV, KV))
    k[:, :, 1::100] = 3.0 * q[:, ::H // KV, 0:-1:100]
    q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v))
    fn = jax.jit(ops.banded_causal_attention)
    kernels = mosaic_kernels(fn.lower(q, k, v).as_text())
    check(kernels == ["flash_gqa_fwd"], f"a full layer's prefill holds "
          f"{kernels}, not the tiled kernel")
    got = np.asarray(fn(q, k, v), np.float32)
    qf, kf, vf = (np.asarray(t, np.float32) for t in (q, k, v))
    kf, vf = (np.repeat(t, H // KV, 1) for t in (kf, vf))
    sc = np.einsum("bhsd,bhtd->bhst", qf, kf) / math.sqrt(hd)
    sc = np.where(np.tri(S, dtype=bool), sc, -np.inf)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("bhst,bhtd->bhsd", w / w.sum(-1, keepdims=True), vf)
    worst = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"  full layer's prefill at 48 heads x 128 over {S} keys through "
        f"flash_gqa_fwd: worst |diff| / max {worst:.5f} (tol 0.02)")
    check(worst <= 0.02, f"the tiled prefill kernel is off by {worst}")


# --------------------------------------------------------------- multichip
def phase_serve_state() -> None:
    """The recurrent-state family (models/ling) at a small lane-aligned
    size through the constructors ``run_serve --model_family ling`` calls:
    the decode tick holds ``kda_step`` once a KDA layer beside
    ``mla_paged_attn`` and ``moe_gmm``, no dispatch copies a state leaf or
    the latent pool; slots are admitted twice (a second tenant reads nothing
    of the first); and the S = 1 kernel path through state and pages gives
    the logits of one prefill window (chunked form) on the tokens it served.
    That comparison runs with float32 weights and activations under
    ``default_matmul_precision("highest")``: in bfloat16 (and in float32 at
    a TPU's default precision, which rounds float32 operands to bfloat16:
    0.24 on the chip, PR 32) the two paths' matmuls round their outputs
    apart by an ulp here and there, and this family at these toy widths
    carries that to 0.2-0.3 in a logit ON THE CPU TOO (no kernel there;
    float32 agrees to 2e-6), which would hide a kernel's fault. Before it,
    the delta rule at the published shape (:func:`kda_at_size`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.ling import (
        LingConfig, ling_decode_paged, ling_init,
    )
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServeModel, ServingEngine,
    )
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    cfg = LingConfig.tiny(
        vocab_size=1024, d_model=256, n_head=32, head_dim=128,
        kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=64, d_ff=512, n_experts=16, top_k=2, n_group=4,
        topk_group=2, moe_d_ff=128, shared_d_ff=128, held=(0, 8))
    params = ling_init(jax.random.key(32), cfg)
    block, max_blocks, n_seq = 16, 8, 4
    model = ServeModel.for_ling(params, cfg)
    engine = ServingEngine(model, ServeConfig(
        max_seqs=n_seq, block_size=block, max_blocks_per_seq=max_blocks,
        moe_stats=True, prefill_cap_tokens=128))
    rng = np.random.default_rng(32)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (21, 37, 50, 64, 9, 30)]
    done = engine.run([Request(req_id=i, tokens=p,
                               max_new_tokens=SERVE_NEW_TOKENS)
                       for i, p in enumerate(prompts)])
    stats = engine.stats
    check(all(done[i].reason == "length" for i in range(len(prompts))), done)
    assert_donated(engine)
    assert_pool_in_place(engine, kernels=("kda_step", "mla_paged_attn",
                                          "moe_gmm"))
    kda = len(cfg.kda_layers)
    check(stats["state_rows_stepped"] == stats["decode_tokens"] * kda, stats)
    check(stats["state_resets"] == len(prompts) > n_seq, stats)
    check(stats["mla_kernel_ticks"] == stats["decode_ticks"] > 0, stats)
    log(f"  state_rows_stepped {stats['state_rows_stepped']} (= decode "
        f"tokens x {kda} KDA layers), state_resets {stats['state_resets']} "
        f"over {n_seq} slots, state_bytes {stats['state_bytes']}; "
        f"moe_assignments {stats['moe_assignments']} of moe_routed "
        f"{stats['moe_routed']}")

    kda_at_size()
    tables = jnp.arange(n_seq * max_blocks, dtype=jnp.int32)[::-1].reshape(
        n_seq, max_blocks)
    slots = jnp.arange(n_seq, dtype=jnp.int32)
    f32 = jnp.float32
    cfg32 = dataclasses.replace(cfg, param_dtype=f32, compute_dtype=f32)
    params32 = jax.tree.map(lambda x: x.astype(f32), params)
    leaves32 = ServeModel.for_ling(params32, cfg32).state_leaves
    with jax.default_matmul_precision("highest"):
        worst = teacher_forced_gap(
            lambda t, pg, pos, valid: ling_decode_paged(
                params32, t, cfg32, pg, tables, slots, pos, valid),
            lambda: init_page_leaves(
                cfg.n_layer, n_seq * max_blocks, block, model.page_leaves,
                f32, state=(cfg.kda_layers, n_seq, leaves32)),
            prompts[:n_seq], [done[i].tokens for i in range(n_seq)], block)
    log(f"  kernel path through state and pages vs one chunked prefill "
        f"window in float32 at the highest matmul precision, logits "
        f"teacher-forced on the served tokens: max |diff| {worst:.5f} "
        f"(tol {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"state decode logits off by {worst}")


def kda_at_size() -> None:
    """The gated delta rule at the published shape (32 heads of 128 x 128),
    every gate at the bound -5 and then near 0: 4,096 positions in chunks,
    by the ``kda_chunk`` kernel (what ``ops/kda.kda_chunked`` runs here) and
    by the XLA form, each against the token-by-token scan, where the form
    that divides keys by their cumulative decay would overflow; then the
    ``kda_step`` kernel over 128 slots with dead slots among them against
    the plain step, the dead slots' states bit for bit untouched, timed."""
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.ops import kda, pallas_kda

    H, d, T, B = 32, 128, 4096, 128
    ks = jax.random.split(jax.random.key(3232), 8)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (1, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (1, T, H, d)))
    v = jax.random.normal(ks[2], (1, T, H, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (1, T, H)))
    zero = jnp.zeros((1, H, d, d), jnp.float32)

    @jax.jit
    def scan(q, k, v, g, beta):
        def step(S, x):
            o, S = kda.kda_step_xla(S, *x)
            return S, o
        S, o = jax.lax.scan(step, zero, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), S

    check(pallas_kda.chunk_kernel_takes(zero.shape, zero.dtype),
          "the chunk kernel refuses the published shape")
    forms = (("kda_chunk kernel", jax.jit(kda.kda_chunked)),
             ("XLA form", jax.jit(kda.kda_chunked_xla)))
    for name, gate in (("-5 (the bound)", -5.0), ("-0.001", -1e-3)):
        g = jnp.full((1, T, H, d), gate)
        o2, s2 = scan(q, k, v, g, beta)
        scale = float(jnp.abs(o2).max())
        for form, chunked in forms:
            o1, s1 = chunked(q, k, v, g, beta, zero)
            err = max(float(jnp.abs(o1 - o2).max()),
                      float(jnp.abs(s1 - s2).max()))
            check(bool(jnp.isfinite(o1).all()),
                  f"{form} not finite at {name}")
            check(err <= 2e-3 * max(scale, 1.0),
                  f"{form} vs scan at gates {name}: {err} of {scale}")
            t0, n = time.time(), 5
            for _ in range(n):
                o1 = chunked(q, k, v, g, beta, zero)[0]
            jax.block_until_ready(o1)
            log(f"  {form}, {T} positions, gates {name}: max |diff| to the "
                f"scan {err:.2e} (outputs to {scale:.3f}); "
                f"{1e3 * (time.time() - t0) / n:.2f} ms a layer's worth")

    state = jax.random.normal(ks[4], (B, H, d, d))
    q1, k1, v1 = (x[0, :B] for x in (q, k, v))
    g1 = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H, d)) - 3)
    b1 = beta[0, :B]
    live = (jnp.arange(B) % 7 != 3) & (jnp.arange(B) > 1)
    o_ref, s_ref = jax.jit(kda.kda_step_xla)(state, q1, k1, v1, g1, b1, live)
    step = jax.jit(pallas_kda.kda_step, donate_argnums=(0,))
    o_k, s_k = step(state + 0, q1, k1, v1, g1, b1, live)
    err = max(float(jnp.abs(o_k - o_ref).max()),
              float(jnp.abs(s_k - s_ref).max()))
    check(err <= 1e-4, f"kda_step kernel vs plain step: {err}")
    check(bool((s_k[~live] == state[~live]).all()),
          "kda_step wrote a dead slot's state")
    s_k = jax.block_until_ready(step(s_k, q1, k1, v1, g1, b1, live)[1])
    t0, n = time.time(), 20
    for _ in range(n):
        s_k = step(s_k, q1, k1, v1, g1, b1, live)[1]
    jax.block_until_ready(s_k)
    ms = 1e3 * (time.time() - t0) / n
    rows = int(live.sum())
    log(f"  kda_step kernel, {rows} live of {B} slots: max |diff| {err:.2e}, "
        f"dead slots untouched; {ms:.3f} ms a call = "
        f"{rows * 2 * H * d * d * 4 / ms / 1e6:.0f} GB/s of state")


def phase_serve_sparse() -> None:
    """The block-sparse family (models/minicpm_sala) at a small lane-aligned
    size through the constructors ``run_serve --model_family minicpm_sala``
    calls, with a ``dense_len`` of 256 so that its rows select: the decode
    tick holds ``lightning_step`` once a Lightning layer and ``paged_attn``
    once a ``minicpm4`` layer, no dispatch copies a state leaf, a page leaf
    or the compressed keys; slots are admitted twice; and the S = 1 kernel
    path through pages, lists and states gives the logits of one prefill
    window (masked tiles, chunked form) on the tokens it served, in float32
    at the highest matmul precision (``phase_serve_state`` says why). Before
    it, selection and the recurrence at the published shape
    (:func:`sparse_at_size`, :func:`lightning_at_size`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.minicpm_sala import (
        MiniCPMSalaConfig, minicpm_sala_decode_paged, minicpm_sala_init,
    )
    from distributed_lion_tpu.ops.sparse_select import SparseConfig
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServeModel, ServingEngine,
    )
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    cfg = MiniCPMSalaConfig.tiny(
        vocab_size=1024, d_model=256, n_head=32, n_kv_head=2, head_dim=128,
        d_ff=512, sparse=SparseConfig(32, 16, 64, 128, 4, 1, 256),
        dim_model_base=64)
    params = minicpm_sala_init(jax.random.key(37), cfg)
    block, max_blocks, n_seq = 16, 40, 4
    model = ServeModel.for_minicpm_sala(params, cfg)
    engine = ServingEngine(model, ServeConfig(
        max_seqs=n_seq, block_size=block, max_blocks_per_seq=max_blocks,
        moe_stats=True, prefill_cap_tokens=512))
    rng = np.random.default_rng(37)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (300, 512, 411, 270, 140, 333)]
    done = engine.run([Request(req_id=i, tokens=p,
                               max_new_tokens=SERVE_NEW_TOKENS)
                       for i, p in enumerate(prompts)])
    stats = engine.stats
    check(all(done[i].reason == "length" for i in range(len(prompts))), done)
    assert_donated(engine)
    assert_pool_in_place(engine, kernels=("lightning_step", "paged_attn"))
    states = len(cfg.lightning_layers)
    check(stats["state_rows_stepped"] == stats["decode_tokens"] * states,
          stats)
    check(stats["state_resets"] == len(prompts) > n_seq, stats)
    check(stats["decode_attn_kernel_ticks"] == stats["decode_ticks"] > 0,
          stats)
    check(stats["sparse_rows"] > 0 and stats["dense_rows"] > 0, stats)
    walked = stats["kv_pages_read"] * cfg.n_kv_head * len(cfg.sparse_layers)
    check(0 < stats["kv_pages_selected"] < walked, stats)
    # a copy a block of four pages a leaf (2 a page by the page: 0.5 / 2.0;
    # a list's last block is partly filled, so a little over a half)
    run = engine.tables.run_pages
    ratio = stats["kv_copies"] / stats["kv_pages_selected"]
    check(run == cfg.sparse.block_size // block == 4, run)
    check(0.5 <= ratio < 0.7, f"kv_copies / kv_pages_selected {ratio}")
    log(f"  state_rows_stepped {stats['state_rows_stepped']} (= decode "
        f"tokens x {states} Lightning layers), state_resets "
        f"{stats['state_resets']} over {n_seq} slots; sparse_rows "
        f"{stats['sparse_rows']}, dense_rows {stats['dense_rows']}, "
        f"kv_pages_selected {stats['kv_pages_selected']} of {walked} a "
        f"walk of every page would hand attention, kv_copies "
        f"{stats['kv_copies']} ({ratio:.3f} a selected page; pages minted "
        f"in runs of {run}), ck_rows_written {stats['ck_rows_written']}")

    sparse_at_size()
    sparse_walk_at_size()
    lightning_at_size()
    # a block's four pages an aligned run, as the engine mints them
    heads = jnp.arange(n_seq * max_blocks // run, dtype=jnp.int32)[::-1] * run
    tables = (heads[:, None] + jnp.arange(run)).reshape(n_seq, max_blocks)
    slots = jnp.arange(n_seq, dtype=jnp.int32)
    f32 = jnp.float32
    cfg32 = dataclasses.replace(cfg, param_dtype=f32, compute_dtype=f32)
    params32 = jax.tree.map(lambda x: x.astype(f32), params)
    with jax.default_matmul_precision("highest"):
        worst = teacher_forced_gap(
            lambda t, pg, pos, valid: minicpm_sala_decode_paged(
                params32, t, cfg32, pg, tables, slots, pos, valid),
            lambda: init_page_leaves(
                cfg.n_layer, n_seq * max_blocks, block, model.page_leaves,
                f32, state=(cfg.lightning_layers, n_seq,
                            model.state_leaves)),
            prompts[:n_seq], [done[i].tokens for i in range(n_seq)], block)
    log(f"  kernel path through pages, lists and states vs one prefill "
        f"window in float32 at the highest matmul precision, logits "
        f"teacher-forced on the served tokens: max |diff| {worst:.5f} "
        f"(tol {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"sparse decode logits off by {worst}")


def sparse_at_size() -> None:
    """Selection and attention over the selected pages at the published
    shape (32 query heads, 2 kv heads of 128, pages of 16, blocks of 64, the
    64 best of 256 at 16,384 positions), with moderately loud keys planted
    in three blocks a kv head, other blocks for each head, outside the first
    block and the local window: the compacted lists must hold the planted
    blocks' pages (their own head's, not the other's), the kernel's output
    over the lists must be the masked dense attention's and must carry the
    planted values, a walk of every page (``nosel``) must differ from it by
    far more than the tolerance, and the prefill's masked tiles must give
    the decode's answer at the same position; timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.ops import sparse_select as ss
    from distributed_lion_tpu.ops.attention import (
        paged_decode_attention, paged_scatter_kv,
    )

    sp = ss.SparseConfig()
    H, KV, hd, bs, S = 32, 2, 128, 16, 16384
    nb = S // bs
    planted = ((37, 101, 150), (11, 99, 170))
    ks = jax.random.split(jax.random.key(3737), 6)

    def normed(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))

    centre = normed(jax.random.normal(ks[0], (KV, hd)))
    q = normed(jnp.repeat(centre, H // KV, 0)[None, :, None]
               + 0.5 * jax.random.normal(ks[1], (1, H, S, hd)))
    k = normed(jax.random.normal(ks[2], (1, S, KV, hd)))
    v = jax.random.normal(ks[3], (1, S, KV, hd))
    mark = jax.random.normal(ks[4], (KV, hd)) * 4
    for g, blocks in enumerate(planted):
        for b in blocks:
            at = slice(64 * b, 64 * b + 64)
            k = k.at[0, at, g].set(0.5 * centre[g])
            v = v.at[0, at, g].set(mark[g])
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    # a block's four pages an aligned run, the runs scattered
    tables = (jax.random.permutation(ks[5], nb // 4).astype(jnp.int32)[:, None]
              * 4 + jnp.arange(4)).reshape(1, nb)
    zero = jnp.zeros((1,), jnp.int32)
    pool = jnp.zeros((nb, bs, 1, KV * hd), jnp.bfloat16)
    k_pages = paged_scatter_kv(pool, tables, zero, k)
    v_pages = paged_scatter_kv(pool, tables, zero, v)
    rows, whole = ss.prefill_compressed(k, jnp.asarray([S]), sp)
    ck = ss.scatter_compressed(jnp.zeros((nb, 1, 1, KV * hd), jnp.bfloat16),
                               tables, rows, whole)
    pos = jnp.asarray([S - 1])
    q1 = q[:, :, -1]

    @jax.jit
    def decode(q1, k_pages, v_pages, ck):
        lists, held, sparse = ss.decode_page_lists(
            q1, ck, tables, pos, jnp.asarray([True]), sp, KV, bs)
        out = ss.sparse_decode_attention(q1, k_pages, v_pages, lists, held,
                                         KV, sp.block_size // bs)
        return lists, held, out

    lists, held, out = decode(q1, k_pages, v_pages, ck)
    check(bool((held == 63 * 64 + 64).all()), f"lists hold {held}")
    check(lists.shape == (1, KV, 128), lists.shape)   # dense_len / 64 wide
    runs = np.asarray(tables[0, ::4]) // 4       # a block's run of the pool
    for g in range(KV):
        mine = set(np.asarray(lists[0, g]).tolist())
        for b in planted[g]:
            check(runs[b] in mine,
                  f"kv head {g}'s list lacks its planted block {b}")
        other = [b for b in planted[1 - g] if runs[b] in mine]
        check(len(other) < 3, f"kv head {g}'s list is the other head's")
        check(runs[0] in mine and set(runs[-32:]) <= mine,
              "the first block or the local window is missing")

    f32 = jnp.float32
    kept = ss.kept_blocks(q1.reshape(1, KV, H // KV, hd),
                          rows[0].reshape(-1, KV, hd), pos, sp, nb // 4)[0]
    mask = jnp.repeat(kept, 64, -1)                         # [KV, S]
    sim = jnp.einsum("grd,tgd->grt", q1[0].reshape(KV, H // KV, hd).astype(f32),
                     k[0].astype(f32)) / np.sqrt(hd)
    dense = jax.nn.softmax(sim, -1)
    masked = jax.nn.softmax(jnp.where(mask[:, None], sim, -jnp.inf), -1)
    want = jnp.einsum("grt,tgd->grd", masked, v[0].astype(f32)).reshape(H, hd)
    every = jnp.einsum("grt,tgd->grd", dense, v[0].astype(f32)).reshape(H, hd)
    err = float(jnp.abs(out[0].astype(f32) - want).max())
    apart = float(jnp.abs(every - want).max())
    carried = float((out[0].astype(f32).reshape(KV, H // KV, hd)
                     * mark[:, None]).sum(-1).min() / (mark * mark).sum(-1).max())
    walk = paged_decode_attention(q1[:, :, None], k_pages, v_pages, tables,
                                  pos, kv_heads=KV)[0, :, 0].astype(f32)
    walked = float(jnp.abs(walk - want).max())
    # probabilities go to the value product in bfloat16: 2^-8 of outputs
    # that reach |mark|
    tol = 0.01 * max(float(jnp.abs(want).max()), 1.0)
    t0, n = time.time(), 20
    for _ in range(n):
        got = decode(q1, k_pages, v_pages, ck)[2]
    jax.block_until_ready(got)
    log(f"  selection at 16,384 positions, 1 row: lists hold the planted "
        f"blocks of each kv head; kernel over the lists vs masked dense "
        f"{err:.4f}; a walk of every page lies {apart:.3f} away; planted "
        f"values carry {carried:.2f} of the output; "
        f"{1e3 * (time.time() - t0) / n:.2f} ms a call; the kernel's own "
        f"walk of every page lies {walked:.3f} away (tol {tol:.3f})")
    check(err <= tol, f"attention over the lists off by {err}")
    check(apart > 5 * tol,
          f"a walk of every page is only {apart} from the selected one")
    check(carried > 0.2, f"the planted values carry {carried} of the output")
    check(walked > 5 * tol,
          "the program that walks every page passes the selected check")

    tiles = jax.jit(lambda q, k, v, rows: ss.sparse_prefill_attention(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), rows, sp))
    got = tiles(q, k, v, rows)
    last = float(jnp.abs(got[0, :, -1].astype(f32) - want).max())
    jax.block_until_ready(got)
    t0 = time.time()
    jax.block_until_ready(tiles(q, k, v, rows))
    check(last <= tol, f"prefill's last query off by {last}")
    log(f"  prefill attention of one minicpm4 layer at 16,384 (dense to "
        f"8,192 by the tiled kernel, masked tiles past it): last query vs "
        f"masked dense {last:.4f}; {1e3 * (time.time() - t0):.1f} ms a layer")


def sparse_walk_at_size() -> None:
    """``paged_attn`` alone at the shape of cell 8's decode tick (64 rows x 2
    kv heads = 128 lists of the 64 best blocks of 64 positions at 16-20k
    contexts, 32 query heads of 128, a pool of 81,920 pages of 16 x 256
    lanes), under both views of the same pool: lists of 253-256 PAGES of 16,
    the pages strewn over the pool one by one (what single-page minting
    leaves after the first minutes) and then the same blocks as aligned
    runs of four; and lists of 64 RUNS over the pool viewed ``[20480, 64, 1,
    256]``. The last two read the same bytes and must agree bit for bit.
    Timed, with the copies each starts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.ops.pallas_paged_attn import paged_attn

    B, H, KV, hd, bs, r, K = 64, 32, 2, 128, 16, 4, 64
    NB, per = 81920, 1280
    rng = np.random.default_rng(41)
    ks = jax.random.split(jax.random.key(4141), 3)
    k_pages, v_pages = (
        jax.random.normal(ks[i], (NB, bs, 1, KV * hd), jnp.bfloat16)
        for i in range(2))
    q = jax.random.normal(ks[2], (B * KV, H, hd), jnp.bfloat16)
    pos = rng.integers(16384, 20480, B)               # the tick's positions
    own = pos // 64
    blocks = np.empty((B, KV, K), np.int64)
    for b in range(B):
        for g in range(KV):        # first block, the local 32, 31 of the rest
            far = rng.choice(np.arange(1, own[b] - 31), K - 33, replace=False)
            blocks[b, g] = np.sort(np.concatenate(
                [[0], far, np.arange(own[b] - 31, own[b] + 1)]))
    held = np.repeat(((K - 1) * 64 + pos % 64 + 1)[:, None], KV, 1)
    lengths = jnp.asarray(held.reshape(-1), jnp.int32)
    run_tables = (rng.permutation(NB // r)[:, None] * r
                  + np.arange(r)).reshape(B, per)      # aligned runs
    page_tables = rng.permutation(NB).reshape(B, per)  # a page at a time
    at = (blocks[..., None] * r + np.arange(r)).reshape(B, KV, K * r)
    rows = np.arange(B)[:, None, None]
    walks = {
        "pages of 16, strewn": (k_pages, v_pages, page_tables[rows, at]),
        "pages of 16, in runs": (k_pages, v_pages, run_tables[rows, at]),
        "runs of 64": (k_pages.reshape(NB // r, r * bs, 1, -1),
                       v_pages.reshape(NB // r, r * bs, 1, -1),
                       run_tables[rows, blocks * r] // r)}
    outs = {}
    for name, (kp, vp, lists) in walks.items():
        lists = jnp.asarray(lists.reshape(B * KV, -1), jnp.int32)
        page = kp.shape[1]
        copies = 2 * int((-(-held // page)).sum())
        call = jax.jit(lambda q, kp, vp, lists: paged_attn(
            q, kp, vp, lists, lengths, kv_heads=KV))
        outs[name] = jax.block_until_ready(call(q, kp, vp, lists))
        t0, n = time.time(), 50
        for _ in range(n):
            got = call(q, kp, vp, lists)
        jax.block_until_ready(got)
        ms = 1e3 * (time.time() - t0) / n
        moved = copies * page * KV * hd * 2
        log(f"  paged_attn alone, 128 lists of 64 blocks, {name}: lists of "
            f"{lists.shape[1]}, kv_copies {copies} of "
            f"{page * KV * hd * 2} B a layer; {ms:.3f} ms a call = "
            f"{1e6 * ms / copies:.1f} ns a copy, {moved / ms / 1e6:.0f} GB/s "
            f"moved")
    check(bool((outs["runs of 64"] == outs["pages of 16, in runs"]).all()),
          "the walk by runs is not bit for bit the walk by pages")
    check(bool(jnp.isfinite(outs["runs of 64"].astype(jnp.float32)).all()),
          "the walk by runs is not finite")


def lightning_at_size() -> None:
    """The Lightning recurrence at the published shape (32 heads of 128 x
    128, the slopes 2^-0.25 .. 2^-8): 16,384 positions in chunks by the
    ``lightning_chunk`` kernel, a row cut at 12,345, against the
    token-by-token scan; then the ``lightning_step`` kernel over 64 slots
    with dead slots among them against the plain step, the dead slots'
    states bit for bit untouched; both timed."""
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.models.minicpm_sala import lightning_slopes
    from distributed_lion_tpu.ops import lightning, pallas_lightning

    H, d, T, B = 32, 128, 16384, 64
    ks = jax.random.split(jax.random.key(373), 5)
    q, k, v = (jax.random.normal(ks[i], (1, T, H, d)).astype(jnp.bfloat16)
               for i in range(3))
    slope = lightning_slopes(H)
    lengths = jnp.asarray([12345])
    zero = jnp.zeros((1, H, d, d), jnp.float32)

    @jax.jit
    def scan(q, k, v):
        def step(S, x):
            q, k, v, t = x
            o, S = lightning.lightning_step_xla(
                S, q, k, v, jnp.exp(-slope), t < lengths)
            return S, o
        S, o = jax.lax.scan(step, zero, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v)) + (jnp.arange(T),))
        return jnp.moveaxis(o, 0, 1), S

    check(pallas_lightning.chunk_kernel_takes(zero.shape, zero.dtype),
          "the chunk kernel refuses the published shape")
    chunked = jax.jit(lightning.lightning_chunked)
    o2, s2 = scan(q, k, v)
    o1, s1 = chunked(q, k, v, slope, lengths, zero)
    keep = (jnp.arange(T) < lengths[0])[None, :, None, None]
    scale = float(jnp.abs(o2).max())
    err = max(float(jnp.abs((o1 - o2) * keep).max()),
              float(jnp.abs(s1 - s2).max()))
    check(bool(jnp.isfinite(o1).all()), "lightning_chunk not finite")
    check(err <= 2e-3 * max(scale, 1.0),
          f"lightning_chunk vs scan: {err} of {scale}")
    t0, n = time.time(), 5
    for _ in range(n):
        o1 = chunked(q, k, v, slope, lengths, zero)[0]
    jax.block_until_ready(o1)
    log(f"  lightning_chunk kernel, {T} positions cut at 12,345: max |diff| "
        f"to the scan {err:.2e} (outputs to {scale:.1f}); "
        f"{1e3 * (time.time() - t0) / n:.2f} ms a layer's worth")

    state = jax.random.normal(ks[3], (B, H, d, d))
    q1, k1, v1 = (x[0, :B].astype(jnp.float32) for x in (q, k, v))
    live = (jnp.arange(B) % 7 != 3) & (jnp.arange(B) > 1)
    lam = jnp.exp(-slope)
    o_ref, s_ref = jax.jit(lightning.lightning_step_xla)(
        state, q1, k1, v1, lam, live)
    step = jax.jit(pallas_lightning.lightning_step, donate_argnums=(0,))
    o_k, s_k = step(state + 0, q1, k1, v1, lam, live)
    err = max(float(jnp.abs(o_k - o_ref).max()),
              float(jnp.abs(s_k - s_ref).max()))
    check(err <= 1e-3, f"lightning_step kernel vs plain step: {err}")
    check(bool((s_k[~live] == state[~live]).all()),
          "lightning_step wrote a dead slot's state")
    s_k = jax.block_until_ready(step(s_k, q1, k1, v1, lam, live)[1])
    t0, n = time.time(), 20
    for _ in range(n):
        s_k = step(s_k, q1, k1, v1, lam, live)[1]
    jax.block_until_ready(s_k)
    ms = 1e3 * (time.time() - t0) / n
    rows = int(live.sum())
    log(f"  lightning_step kernel, {rows} live of {B} slots: max |diff| "
        f"{err:.2e}, dead slots untouched; {ms:.3f} ms a call = "
        f"{rows * 2 * H * d * d * 4 / ms / 1e6:.0f} GB/s of state")


def phase_serve_dsa() -> None:
    """The learned-indexer family (models/dots3) at a small lane-aligned
    size through the constructors ``run_serve --model_family dots3`` calls,
    with an ``index_topk`` of 128 so that its rows select: the decode tick
    holds ``dsa_index`` and ``dsa_attn`` once a full layer and
    ``window_mla_attn`` once a sliding layer, no dispatch copies a page leaf,
    an index-key leaf or a ring; slots are admitted twice; and the S = 1
    kernel path through pages, index keys and rings gives the logits of one
    prefill window (the chunked walk under the mask) on the tokens it
    served, in float32 at the highest matmul precision. Then the kernels
    against ``ops/dsa.py`` at the published shapes (:func:`dsa_at_size`),
    the latent ring held to the position (:func:`latent_ring_at_size`) and
    the 12,288-token prefill's two attentions, timed
    (:func:`dsa_prefill_at_size`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.dots3 import (
        Dots3Config, Latent, dots3_decode_paged, dots3_init, lora_rescale,
    )
    from distributed_lion_tpu.ops.attention import ring_pages
    from distributed_lion_tpu.serve.engine import (
        Request, ServeConfig, ServeModel, ServingEngine,
    )
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    cfg = Dots3Config.tiny(
        vocab_size=1024, d_model=256,
        full=Latent(8, 128, 128, 64, 64, 64, 8e7, 1e-5,
                    (lora_rescale(256, 128), lora_rescale(256, 128))),
        swa=Latent(4, 128, 256, 64, 64, 64, 5e4, 1e-5,
                   (lora_rescale(256, 128), lora_rescale(256, 256))),
        window=33, index_n_heads=4, index_head_dim=128, index_topk=128,
        d_ff=512, moe_d_ff=128, held=(0, 4), page_run=64)
    params = dots3_init(jax.random.key(46), cfg)
    block, max_blocks, n_seq = 16, 64, 4   # every bucket a power of two
    model = ServeModel.for_dots3(params, cfg)
    engine = ServingEngine(model, ServeConfig(
        max_seqs=n_seq, block_size=block, max_blocks_per_seq=max_blocks,
        moe_stats=True, prefill_cap_tokens=512))
    rng = np.random.default_rng(46)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (300, 500, 411, 270, 140, 333)]   # 500 + 8 served: a window of 512
    done = engine.run([Request(req_id=i, tokens=p,
                               max_new_tokens=SERVE_NEW_TOKENS)
                       for i, p in enumerate(prompts)])
    stats = engine.stats
    check(all(done[i].reason == "length" for i in range(len(prompts))), done)
    assert_donated(engine)
    assert_pool_in_place(engine, kernels=("dsa_index", "dsa_attn",
                                          "window_mla_attn"))
    check(stats["mla_kernel_ticks"] == stats["decode_ticks"] > 0, stats)
    check(stats["window_kernel_ticks"] == stats["decode_ticks"], stats)
    fulls = len(cfg.full_layers)
    check(stats["dsa_rows"] == stats["decode_tokens"] * fulls, stats)
    check(0 < stats["dsa_keys_kept"] < stats["dsa_keys_visible"],
          stats)
    check(stats["dsa_keys_kept"]
          == stats["dsa_rows"] * cfg.index_topk, stats)
    run = engine.tables.run_pages
    check(run == cfg.page_run // block == 4, run)
    log(f"  dsa_rows {stats['dsa_rows']} (= decode tokens x {fulls} full "
        f"layers), keys kept {stats['dsa_keys_kept']} of "
        f"{stats['dsa_keys_visible']} visible (top {cfg.index_topk}); "
        f"window pages read {stats['kv_window_pages_read']} (a ring of "
        f"{ring_pages(cfg.window, block)}); pages minted in runs of {run}")

    dsa_at_size()
    latent_ring_at_size()
    dsa_prefill_at_size()
    # a run's four pages aligned, as the engine mints them
    heads = jnp.arange(n_seq * max_blocks // run, dtype=jnp.int32)[::-1] * run
    tables = (heads[:, None] + jnp.arange(run)).reshape(n_seq, max_blocks)
    slots = jnp.arange(n_seq, dtype=jnp.int32)
    f32 = jnp.float32
    cfg32 = dataclasses.replace(cfg, param_dtype=f32, compute_dtype=f32)
    params32 = jax.tree.map(lambda x: x.astype(f32), params)
    ring = n_seq * ring_pages(cfg.window, block)
    with jax.default_matmul_precision("highest"):
        worst = teacher_forced_gap(
            lambda t, pg, pos, valid: dots3_decode_paged(
                params32, t, cfg32, pg, tables, slots, pos, valid),
            lambda: init_page_leaves(
                cfg.n_layer, n_seq * max_blocks, block, model.page_leaves,
                f32, ring=(cfg.window_layers, ring, model.window_leaves)),
            prompts[:n_seq], [done[i].tokens for i in range(n_seq)], block)
    log(f"  kernel path through pages, index keys and rings vs one prefill "
        f"window in float32 at the highest matmul precision, logits "
        f"teacher-forced on the served tokens: max |diff| {worst:.5f} "
        f"(tol {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"dsa decode logits off by {worst}")


def dsa_at_size() -> None:
    """The decode tick's indexer and kept-set attention at the PUBLISHED
    shape (64 index heads of 128, 128 heads over latent rows of 576 values in
    640 lanes, pages of 16 minted in runs of 4, the 2,048 best kept) over 64
    rows of 2,047 to 16,383 cached positions: ``dsa_index`` against the
    gathered rows through ``ops/dsa.index_scores``; ``kept_positions``
    against ``lax.top_k``'s set, row for row; ``dsa_attn`` against a float32
    softmax over the kept rows alone. Each is timed, and beside them the
    same walk with no mask and the whole sort."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.ops import dsa
    from distributed_lion_tpu.ops.attention import (
        mla_decode_attention, paged_gather_kv,
    )

    B, Hi, di, H, W, r_dim = 64, 64, 128, 128, 640, 576
    bs, nb, topk, run = 16, 1024, 2048, 64
    NB = B * nb
    rng = np.random.default_rng(4646)
    ends = rng.integers(8192, 16384, B)
    ends[:5] = [2046, 2047, 2048, 12287, 16383]
    pos = jnp.asarray(ends, jnp.int32)
    heads = rng.permutation(NB // 4)[:B * nb // 4].reshape(B, nb // 4) * 4
    tables = jnp.asarray(
        (heads[:, :, None] + np.arange(4)).reshape(B, nb), jnp.int32)
    key = jax.random.key(4646)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    ik = jax.random.normal(k1, (NB, bs, 1, di), jnp.bfloat16)
    kv = jnp.pad(jax.random.normal(k2, (NB, bs, 1, r_dim), jnp.bfloat16),
                 ((0, 0), (0, 0), (0, 0), (0, W - r_dim)))
    qi = (1.4 * jax.random.normal(k3, (B, Hi, di))).astype(jnp.bfloat16)
    wi = jax.random.normal(k4, (B, Hi), jnp.float32) / math.sqrt(Hi * di)
    q_abs = jnp.pad(
        (0.6 * jax.random.normal(k5, (B, H, r_dim))).astype(jnp.bfloat16),
        ((0, 0), (0, 0), (0, W - r_dim)))
    scale = 1.0 / math.sqrt(192)
    T = nb * bs
    visible = jnp.arange(T)[None, :] <= pos[:, None]

    # the big arrays go in as operands: a closure would bake 1.6 GB of
    # constants into every program
    index = jax.jit(lambda ik: dsa.decode_index_scores(
        qi, wi, ik, tables, pos, page_run=run))
    plain = jax.jit(lambda ik: dsa.index_scores(
        qi[:, None], wi[:, None],
        paged_gather_kv(ik, tables)[:, :, 0])[:, 0])
    got, index_ms = clock(index, ik)
    want, plain_ms = clock(plain, ik)
    big = float(jnp.abs(jnp.where(visible, want, 0)).max())
    off = float(jnp.abs(jnp.where(visible, got - want, 0)).max()) / big
    log(f"  dsa_index at {B} rows x {Hi} heads of {di} over "
        f"{int((pos + 1).sum())} index keys by runs of {run}: {index_ms:.3f} "
        f"ms a layer ({float((pos + 1).sum()) * 256 / index_ms / 1e6:.0f} "
        f"GB/s of index keys); gathered through XLA {plain_ms:.3f} ms; worst "
        f"|diff| / max {off:.2e}")
    check(off <= 1e-5, f"dsa_index off by {off}")
    del ik, want

    select = jax.jit(lambda s: dsa.kept_positions(s, visible, topk))
    keep, select_ms = clock(select, got)

    def by_sort(s):
        idx = jax.lax.top_k(jnp.where(visible, s, -jnp.inf), topk)[1]
        hit = jnp.zeros((B, T), bool).at[
            jnp.arange(B)[:, None], idx].set(True)
        return hit & visible

    sort, sort_ms = clock(jax.jit(by_sort), got)
    n_kept = np.asarray(keep.sum(1))
    check(n_kept.tolist() == np.minimum(ends + 1, topk).tolist(), n_kept)
    check(bool((keep == sort).all()),
          f"kept_positions differs from the sort's set in "
          f"{int((keep != sort).sum())} places")
    log(f"  kept_positions, the {topk} best of 2,047-16,383 a row, {B} rows: "
        f"{select_ms:.3f} ms a layer, the sort's set row for row "
        f"(lax.top_k and a scatter: {sort_ms:.3f} ms)")

    attn = jax.jit(lambda kv, m: dsa.kept_decode_attention(
        q_abs, kv, tables, pos, m, scale=scale, page_run=run))
    walk = jax.jit(lambda kv: mla_decode_attention(
        q_abs, kv, tables, pos, scale=scale))

    def reference(kv, m):
        """A float32 softmax over the rows ``m`` leaves, eight rows of the
        batch at a time (all 64 would hold 2.7 GB of float32 rows)."""
        def some(args):
            q, tab, mm = args
            rows = paged_gather_kv(kv, tab)[:, :, 0].astype(jnp.float32)
            s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows,
                           precision="highest") * scale
            p = jax.nn.softmax(jnp.where(mm[:, None], s, -jnp.inf), -1)
            return jnp.einsum("bht,btw->bhw", p, rows, precision="highest")

        def parts(x):
            return x.reshape((B // 8, 8) + x.shape[1:])

        return jax.lax.map(some, (parts(q_abs), parts(tables), parts(m))
                           ).reshape(B, H, W)

    out, attn_ms = clock(attn, kv, keep)
    _, walk_ms = clock(walk, kv)
    reference = jax.jit(reference)
    ref = reference(kv, keep)
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max()
                / jnp.abs(ref).max())
    every = reference(kv, visible)
    moved = float(jnp.abs(every - ref).max() / jnp.abs(ref).max())
    kept_bytes = float(keep.sum()) * 1280
    log(f"  dsa_attn at {H} heads over rows of {W} lanes, "
        f"{int(keep.sum())} kept of {int(visible.sum())} visible: "
        f"{attn_ms:.3f} ms a layer ({kept_bytes / attn_ms / 1e6:.0f} GB/s of "
        f"kept rows; the same walk with no mask {walk_ms:.3f} ms); worst "
        f"|diff| / max against a float32 softmax over the kept rows "
        f"{err:.5f} (tol 0.02; attending every visible row instead moves it "
        f"by {moved:.3f})")
    check(err <= 0.02, f"dsa_attn off by {err}")
    check(moved > 0.1, f"the check does not see a walk with no mask: {moved}")


def latent_ring_at_size() -> None:
    """A sliding layer's decode walk at the PUBLISHED shape (64 heads over
    latent rows of 1,088 values in 1,152 lanes, window 513, pages of 16: a
    ring of 34), held to the position as :func:`ring_edges_at_size` holds the
    key-and-value ring: a loud row just inside each edge of every row's
    window, at ``pos - 512`` and at ``pos``, and one just outside at ``pos -
    513`` (each the loud direction plus its own noise: a latent row is key
    and value both, so three equal rows would average to the same output
    whether two or three are seen). The reference is a float32 softmax over the last 513 rows of the
    plain sequence. Then the check itself is checked: the same walk told the
    window is 512 or 514, or over rings of 32 pages, must fail it (513
    positions touch 33 pages of 16 at most: ``ring_pages`` keeps one to
    spare, so it is a ring one short of what a window FILLS that fails)."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.ops import attention as ops

    H, W, width, window, bs = 64, 1152, 1088, 513, 16
    R = ops.ring_pages(window, bs)
    ends = [511, 512, 513, 528, 543, 544, 1040, R * bs * 2 - 1, R * bs * 2,
            12287, 16383]
    B, T = len(ends), max(ends) + 1
    scale = 1.0 / 16.0
    rng = np.random.default_rng(4647)
    loud = rng.standard_normal((B, width)).astype(np.float32)
    loud *= 14.6 / np.linalg.norm(loud, axis=-1, keepdims=True)
    rows = rng.standard_normal((B, T, width)).astype(np.float32)
    for b, p in enumerate(ends):
        for at in (p - window, p - window + 1, p):
            if at >= 0:      # as loud as each other, and each its own row:
                # a latent row is key AND value, so equal rows would hide
                # one more or one fewer of them
                rows[b, at] += 1.5 * loud[b]
    q = loud[:, None] + 0.5 * rng.standard_normal(
        (B, H, width)).astype(np.float32)
    q = jnp.pad(jnp.asarray(q, jnp.bfloat16),
                ((0, 0), (0, 0), (0, W - width)))
    rows = jnp.asarray(rows, jnp.bfloat16)
    pos = jnp.asarray(ends, jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)[::-1]

    def walk(window):
        leaf = jnp.zeros((B * ops.ring_pages(window, bs), bs, 1, W),
                         jnp.bfloat16)
        leaf = ops.ring_scatter_kv(leaf, slots, jnp.zeros_like(pos),
                                   rows[:, :, None], pos + 1, window=window)
        out, read = ops.ring_mla_decode_attention(
            q, leaf, slots, pos, window=window, scale=scale)
        return np.asarray(out[..., :width], np.float32), np.asarray(read)

    want = np.zeros((B, H, width), np.float32)
    rf, qf = np.asarray(rows, np.float32), np.asarray(q, np.float32)
    for b, p in enumerate(ends):
        lo = max(p - window + 1, 0)
        sc = qf[b, :, :width] @ rf[b, lo:p + 1].T * scale
        w = np.exp(sc - sc.max(1, keepdims=True))
        want[b] = (w / w.sum(1, keepdims=True)) @ rf[b, lo:p + 1]

    def off(got):
        return float(np.abs(got - want).max() / np.abs(want).max())

    got, read = walk(window)
    sound = off(got)
    check(read.tolist() == [p // bs - max(p - window + 1, 0) // bs + 1
                            for p in ends] and read.max() <= R, read)
    early, late = off(walk(window + 1)[0]), off(walk(window - 1)[0])
    whole, ops.ring_pages = ops.ring_pages, lambda w, b: -(-w // b) - 1
    try:       # a ring one page short of the pages a window fills
        short = off(walk(window)[0])
    finally:
        ops.ring_pages = whole
    log(f"  latent ring walk at 64 heads x 1,152 lanes, window 513, {B} rows "
        f"ending at {ends}: worst |diff| / max {sound:.5f} (tol 0.02; pages "
        f"handed {read.tolist()}); told 514: {early:.3f}, told 512: "
        f"{late:.3f}, a ring of 32 pages: {short:.3f} (each must exceed 0.1)")
    check(sound <= 0.02, f"latent ring walk off by {sound}")
    check(min(early, late, short) > 0.1,
          f"the ring check does not see a wrong window: {early} {late} "
          f"{short}")


def dsa_prefill_at_size() -> None:
    """The 12,288-token prefill's two attentions at the published shapes,
    timed: a full layer's under the indexer's mask
    (``ops/dsa.dsa_prefill_attention``: 128 heads of 192 / 128, 64 index
    heads of 128, the 2,048 best a query) through the tiled kernel
    ``dsa_prefill`` and through the XLA walk every other call takes, one
    against the other and the kernel's last 128 queries against a float32
    softmax over ``lax.top_k``'s set; and a sliding layer's banded walk (64
    heads of 256 / 128, window 513)."""
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.ops import dsa, pallas_dsa
    from distributed_lion_tpu.ops.attention import banded_causal_attention

    S, H, dk, dv, Hi, di, topk = 12288, 128, 192, 128, 64, 128, 2048
    keys = jax.random.split(jax.random.key(4648), 6)
    bf = jnp.bfloat16
    q = (0.5 * jax.random.normal(keys[0], (1, H, S, dk))).astype(bf)
    k = jax.random.normal(keys[1], (1, H, S, dk), bf)
    v = jax.random.normal(keys[2], (1, H, S, dv), bf)
    qi = (1.4 * jax.random.normal(keys[3], (1, S, Hi, di))).astype(bf)
    wi = jax.random.normal(keys[4], (1, S, Hi)) / math.sqrt(Hi * di)
    ki = jax.random.normal(keys[5], (1, S, di), bf)
    scale = 1.0 / math.sqrt(dk)
    lengths = jnp.full((1,), S, jnp.int32)

    def attend(q, k, v, qi, wi, ki):
        return dsa.dsa_prefill_attention(q, k, v, qi, wi, ki, lengths,
                                         topk=topk, scale=scale)

    check(pallas_dsa.prefill_takes(S, dv), "the kernel refuses the shape")
    (out, counts), full_ms = clock(jax.jit(attend), q, k, v, qi, wi, ki)
    takes, pallas_dsa.prefill_takes = pallas_dsa.prefill_takes, \
        lambda *a: False
    try:       # the walk every other call takes, at the same shape (a
        # function of its own: jit would hand back the kernel's program)
        (walk, _), walk_ms = clock(jax.jit(lambda *a: attend(*a)),
                                   q, k, v, qi, wi, ki)
    finally:
        pallas_dsa.prefill_takes = takes
    apart = float(jnp.abs(out.astype(jnp.float32) - walk.astype(jnp.float32)
                          ).max() / jnp.abs(walk.astype(jnp.float32)).max())
    del walk
    tail = 128

    def reference(q, k, v, qi, wi, ki):
        sc = dsa.index_scores(qi[:, -tail:], wi[:, -tail:], ki)[0]
        seen = jnp.arange(S)[None, :] <= (S - tail + jnp.arange(tail))[:, None]
        idx = jax.lax.top_k(jnp.where(seen, sc, -jnp.inf), topk)[1]
        keep = jnp.zeros((tail, S), bool).at[
            jnp.arange(tail)[:, None], idx].set(True)
        s = jnp.einsum("hsd,htd->hst", q[0, :, -tail:].astype(jnp.float32),
                       k[0].astype(jnp.float32), precision="highest") * scale
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        return jnp.einsum("hst,htd->hsd", p, v[0].astype(jnp.float32),
                          precision="highest")

    ref = jax.jit(reference)(q, k, v, qi, wi, ki)
    err = float(jnp.abs(out[0, :, -tail:].astype(jnp.float32) - ref).max()
                / jnp.abs(ref).max())
    kept = int(counts["dsa_keys_kept"])
    want = sum(min(t + 1, topk) for t in range(S))
    log(f"  full layer's prefill at {S} positions, {H} heads of {dk} / {dv} "
        f"under the indexer's mask: {full_ms:.1f} ms a layer through "
        f"dsa_prefill, {walk_ms:.1f} ms through the XLA walk (worst |diff| / "
        f"max between them {apart:.5f}); kept {kept} of "
        f"{int(counts['dsa_keys_visible'])} visible; last {tail} queries "
        f"against a float32 softmax over lax.top_k's set: worst |diff| / "
        f"max {err:.5f} (tol 0.03)")
    check(kept == want, f"the prefill kept {kept} keys, not {want}")
    check(err <= 0.03, f"dsa prefill off by {err}")
    check(apart <= 0.03, f"kernel and walk apart by {apart}")
    del out, ref, q, k, v
    H2, dk2 = 64, 256
    q2 = (0.5 * jax.random.normal(keys[0], (1, H2, S, dk2))).astype(bf)
    k2 = jax.random.normal(keys[1], (1, H2, S, dk2), bf)
    v2 = jax.random.normal(keys[2], (1, H2, S, dv), bf)
    banded = jax.jit(lambda q, k, v: banded_causal_attention(
        q, k, v, window=513))
    _, band_ms = clock(banded, q2, k2, v2)
    log(f"  sliding layer's prefill at {S} positions, {H2} heads of {dk2} / "
        f"{dv}, window 513, banded: {band_ms:.1f} ms a layer")


def phase_serve_mhc() -> None:
    """The hyper-connection mix at the published shape (4 streams of 3,584,
    bfloat16): the kernels ``mhc_pre`` / ``mhc_post`` against ``ops/mhc``'s
    ``jax.numpy`` forms over a 4,096-row prefill and a 64-row decode tick
    with rows without a token among them, the mixing matrices' distance
    from doubly stochastic (under 0.1 with the seeded weights' dominant
    diagonal, under 1e-3 where the matrix mixes), the stream rewritten in
    place, all four calls timed against the bytes the mix needs."""
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.ops import mhc, pallas_mhc

    cfg = mhc.MixConfig()
    n, d = cfg.n, 3584
    f32, bf = jnp.float32, jnp.bfloat16
    ks = jax.random.split(jax.random.key(391), 6)
    a = jnp.ones((3,), f32)

    def mix(key, std, diag):
        k1, k2 = jax.random.split(key)
        phi = jax.random.normal(k1, (n * d, cfg.width)) * std
        b = jax.random.normal(k2, (cfg.width,)) * 0.5
        return (mhc.pack_phi(phi, cfg),
                b.at[2 * n:].add(diag * jnp.eye(n).reshape(-1)))

    packed, b = mix(ks[0], 0.02, 4.0)         # the seeded weights' law
    flat_phi, flat_b = mix(ks[1], 0.002, 0.0)  # no entry dominates
    pre_x = jax.jit(lambda X, p, b: mhc.mhc_pre_xla(X, p, a, b, cfg))
    post_x = jax.jit(lambda X, y, c, v: mhc.mhc_post_xla(X, y, c, v, cfg))
    pre_k = jax.jit(lambda X, p, b: pallas_mhc.mhc_pre(X, p, a, b, cfg))
    post_k = jax.jit(lambda X, y, c, v: pallas_mhc.mhc_post(X, y, c, v, cfg),
                     donate_argnums=(0,))
    for rows in (4096, 64):
        X = jax.random.normal(ks[2], (rows, n * d)).astype(bf)
        y = jax.random.normal(ks[3], (rows, d)).astype(bf)
        valid = jnp.arange(rows) % 7 != 3
        check(pallas_mhc.kernel_takes(X.shape, X.dtype, cfg),
              "the kernels refuse the published shape")
        u0, c0 = pre_x(X, packed, b)
        u1, c1 = pre_k(X, packed, b)
        err_c = float(jnp.abs(c0 - c1).max())
        err_u = float(jnp.abs(u0.astype(f32) - u1.astype(f32)).max())
        top_u = float(jnp.abs(u0.astype(f32)).max())
        check(bool(jnp.isfinite(c1).all()), "mhc_pre coefficients not finite")
        check(err_c <= 1e-4, f"mhc_pre coefficients vs ops/mhc: {err_c}")
        check(err_u <= top_u * 2.0 ** -6, f"mhc_pre u vs ops/mhc: {err_u}")
        seeded = float(mhc.mhc_defect(c1, valid, cfg))
        mixed = float(mhc.mhc_defect(pre_k(X, flat_phi, flat_b)[1],
                                     valid, cfg))
        check(seeded <= 0.1, f"Hres {seeded} from doubly stochastic")
        check(mixed <= 1e-3, f"a mixing Hres {mixed} from doubly stochastic")
        o0 = post_x(X, y, c1, valid)
        o1 = post_k(X + 0, y, c1, valid)
        err_o = float(jnp.abs(o0.astype(f32) - o1.astype(f32)).max())
        top_o = float(jnp.abs(o0.astype(f32)).max())
        check(err_o <= top_o * 2.0 ** -6, f"mhc_post vs ops/mhc: {err_o}")
        check(bool((o1[~valid] == X[~valid]).all()),
              "mhc_post wrote a row without a token")
        text = post_k.lower(X, y, c1, valid).compile().as_text()
        check("input_output_alias" in text and not re.search(
            r"= bf16\[%d,%d\]\S* copy\(" % (rows, n * d), text),
            "mhc_post copies the stream")
        times = {}
        for name, fn in (("pre kernel", lambda: pre_k(X, packed, b)[0]),
                         ("pre xla", lambda: pre_x(X, packed, b)[0])):
            jax.block_until_ready(fn())
            t0, reps = time.time(), 20
            for _ in range(reps):
                out = fn()
            jax.block_until_ready(out)
            times[name] = 1e3 * (time.time() - t0) / reps
        for name, fn in (("post kernel", post_k), ("post xla", post_x)):
            S = jax.block_until_ready(fn(X + 0, y, c1, valid))
            t0, reps = time.time(), 20
            for _ in range(reps):
                S = fn(S, y, c1, valid)
            jax.block_until_ready(S)
            times[name] = 1e3 * (time.time() - t0) / reps
        need_pre, need_post = rows * (n + 1) * d * 2, rows * (2 * n + 1) * d * 2
        log(f"  mhc at [{rows}, {n} x {d}]: coefficients to {err_c:.1e}, u "
            f"to {err_u:.3f} of {top_u:.1f}, stream to {err_o:.3f} of "
            f"{top_o:.1f}; Hres {seeded:.4f} from doubly stochastic seeded, "
            f"{mixed:.1e} mixing; pre {times['pre kernel']:.3f} ms = "
            f"{need_pre / times['pre kernel'] / 1e6:.0f} GB/s (xla "
            f"{times['pre xla']:.3f}), post {times['post kernel']:.3f} ms = "
            f"{need_post / times['post kernel'] / 1e6:.0f} GB/s (xla "
            f"{times['post xla']:.3f})")


def _replica_check(tree, what: str) -> int:
    """Every leaf fully replicated over distinct devices and its replicas
    bit-identical (exact). Returns the device count seen."""
    import jax
    import numpy as np

    n_dev = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        check(len(devs) == len(shards) == len(jax.devices()),
              f"{what}{jax.tree_util.keystr(path)} lives on {len(devs)} devices")
        first = np.asarray(shards[0].data)
        check(first.shape == leaf.shape,
              "param leaf is not replicated")
        for s in shards[1:]:
            check(np.array_equal(first, np.asarray(s.data)),
                  f"{what}{jax.tree_util.keystr(path)}: replicas differ")
        n_dev = len(devs)
    return n_dev


def _vote_run(tag: str, extra: list, out_dir: str) -> dict:
    """One run_clm run on the data=4 mesh, stepped one optimizer step at a
    time so the replicas can be compared after every vote."""
    import jax
    import numpy as np

    from distributed_lion_tpu.cli import run_clm
    from distributed_lion_tpu.train import loop

    seen: dict = {"params": [], "tag": tag}
    orig = loop.Trainer.train

    def stepwise(self, train_iter, eval_blocks=None, max_steps=None):
        history = []
        n_dev = len(jax.devices())
        mom = jax.tree.leaves(self.state.exp_avg)
        for m in mom:
            devs = {s.device for s in m.addressable_shards}
            check(len(devs) == n_dev and m.shape[0] == n_dev and all(
                s.data.shape[0] == 1 for s in m.addressable_shards),
                  "stacked momentum is not one worker per device")
        batch = jax.device_put(
            np.zeros((self.global_train_batch(), self.cfg.block_size),
                     np.int32),
            jax.sharding.NamedSharding(self.mesh, self.batch_spec))
        check(len({s.device for s in batch.addressable_shards}) == n_dev \
            and batch.addressable_shards[0].data.shape[0] * n_dev \
            == batch.shape[0],
              "batch is not split over the devices")
        while self.step_count < self.cfg.max_steps:
            history += orig(self, train_iter, eval_blocks, max_steps=1)
            check(_replica_check(self.params, f"{tag} params") == n_dev,
                  '_replica_check(self.params, f"{tag} params") == n_dev')
            seen["params"].append(
                [np.asarray(x.addressable_shards[0].data)
                 for x in jax.tree.leaves(self.params)])
        seen["cfg"] = self.cfg
        seen["text"] = _lowered_step_text(self)
        return history

    loop.Trainer.train = stepwise
    t0 = time.time()
    try:
        run_clm.main(TRAIN_ARGV + extra + [
            "--output_dir", out_dir, "--max_steps", str(MULTICHIP_STEPS),
            "--save_steps", "1000000"])
    finally:
        loop.Trainer.train = orig
    seen["wall"] = time.time() - t0
    seen["losses"] = [r["train/loss"] for r in _metrics_rows(out_dir)
                      if "train/loss" in r]
    shutil.rmtree(out_dir)  # ~3 GB of checkpoint + export per run
    cfg = seen["cfg"]
    log(f"  [{tag}] wire={cfg.wire} vote_buckets={cfg.vote_buckets} "
        f"kernel={cfg.kernel} Mosaic={has_mosaic(seen['text'])}: params "
        f"replicated on {len(jax.devices())} distinct devices and "
        f"bit-identical after each of {MULTICHIP_STEPS} votes; momentum "
        f"and batch one shard per device; losses {seen['losses']}; "
        f"wall {seen['wall']:.1f} s")
    return seen


def _election_on_fixed_gradients() -> None:
    """The election alone, isolated from the forward/backward pass: the SAME
    per-worker gradients through the optimizer step as the trainer's auto
    rule builds it (wire, buckets, Pallas) and as the reference election
    (sign_psum, XLA kernel). Exact: every parameter must come out
    bit-identical, on a 124M-coordinate tree, momentum zero and nonzero."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_lion_tpu.optim import (
        distributed_lion,
        init_global_state,
    )
    from distributed_lion_tpu.optim.sharded import (
        make_sharded_step,
        shard_state,
    )
    from distributed_lion_tpu.parallel import make_mesh
    from distributed_lion_tpu.train.loop import TrainConfig, resolve_auto_comm

    mesh, w = make_mesh(), len(jax.devices())
    half = N_124M // 2
    shapes = {"a": (half,), "b": (N_124M - half - 1001,), "c": (1001,)}
    n = sum(s[0] for s in shapes.values())
    cfg = resolve_auto_comm(TrainConfig(), mesh, n, params_replicated=True)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    keys = jax.random.split(jax.random.key(7), 2 * len(shapes))
    params = {k: jax.jit(lambda key, s=s: 0.02 * jax.random.normal(key, s),
                         out_shardings=repl)(keys[i])
              for i, (k, s) in enumerate(shapes.items())}
    grads = {k: jax.jit(lambda key, s=s: jax.random.normal(key, (w,) + s),
                        out_shardings=split)(keys[len(shapes) + i])
             for i, (k, s) in enumerate(shapes.items())}
    results = {}
    for tag, wire, buckets, kernel in (
            ("auto", cfg.wire, cfg.vote_buckets, "auto"),
            ("reference", "sign_psum", 1, "xla")):
        opt = distributed_lion(learning_rate=1e-4, weight_decay=0.1,
                               wire=wire, vote_buckets=buckets, kernel=kernel)
        state = shard_state(init_global_state(opt, params, world=w), mesh)
        step = make_sharded_step(opt, mesh)
        if kernel == "auto":
            check(has_mosaic(step.lower(params, grads, state).as_text()),
                  "the auto optimizer step holds no Mosaic kernel")
        p, snaps = params, []
        for _ in range(2):  # second step: the momentum is no longer zero
            p, state = step(p, grads, state)
            snaps.append({k: np.asarray(v.addressable_shards[0].data)
                          for k, v in p.items()})
        results[tag] = snaps
    differ = [sum(int((a[k] != b[k]).sum()) for k in a)
              for a, b in zip(results["auto"], results["reference"])]
    moved = sum(int((results["reference"][0][k]
                     != np.asarray(params[k].addressable_shards[0].data)).sum())
                for k in shapes)
    check(moved > 0.5 * n, f"only {moved} of {n} parameters moved")
    log(f"  election on identical gradients, n={n}, wire={cfg.wire} x "
        f"{cfg.vote_buckets} buckets + Pallas vs sign_psum + XLA kernel: "
        f"{differ} coordinates differ after steps 1, 2 (exact, expected 0)")
    check(differ == [0, 0], "the auto election departs from the reference "
                            "election on identical gradients")


def phase_multichip(work: str) -> None:
    import math

    _election_on_fixed_gradients()

    auto = _vote_run("auto", [], os.path.join(work, "auto"))
    check(auto["cfg"].wire != "sign_psum" and has_mosaic(auto["text"]),
          "the auto run did not take the packed wire + Pallas kernels")
    ref = _vote_run("reference", ["--wire", "sign_psum", "--kernel", "xla",
                                  "--vote_buckets", "1"],
                    os.path.join(work, "ref"))
    n = sum(x.size for x in ref["params"][0])

    def differing(run):  # per step: coordinates whose parameter differs
        return [sum(int((a != b).sum()) for a, b in zip(pa, pb))
                for pa, pb in zip(run["params"], ref["params"])]

    # both runs start from the same seed and see the same batch, and every
    # update is exactly -lr * elected sign on top of the same decay: equal
    # step-1 parameters <=> equal step-1 elections. The two runs are two
    # differently fused bf16 forward/backward programs, so gradients within
    # rounding of zero may vote differently: on four v5e chips 1.1e-3 of the
    # step-1 elections differed where the election ALONE (above, identical
    # gradients) is exact. Printed; held only to "no gross departure".
    diff_auto = differing(auto)
    moved = sum(int((a != b).sum()) for a, b in
                zip(ref["params"][0], ref["params"][1]))
    check(moved > 0.5 * n, f"only {moved} of {n} parameters moved in a step")
    differ = diff_auto[0]
    log(f"  elected sign at step 1, auto vs reference election: {differ} "
        f"of {n} coordinates differ (share {differ / n:.3e}); "
        f"after steps 2.. : {diff_auto[1:]}")
    for la, lr in zip(auto["losses"], ref["losses"]):
        check(math.isfinite(la) and abs(la - lr) <= LOSS_TOL * abs(lr),
              (auto["losses"], ref["losses"]))
    log(f"  losses agree to {LOSS_TOL:g} relative")
    check(differ <= 1e-2 * n,
          "auto election departs grossly from the reference")
    hier = _vote_run("hier:2", ["--wire", "hier:2"],
                     os.path.join(work, "hier2"))
    diff_hier = differing(hier)
    log(f"  hier:2 (majority of majorities — may legitimately differ from "
        f"the flat vote): {diff_hier[0]} of {n} step-1 coordinates differ "
        f"(share {diff_hier[0] / n:.3e}); after steps 2.. : {diff_hier[1:]}")
    check(all(math.isfinite(x) for x in hier["losses"]),
          f"hier:2 losses not finite: {hier['losses']}")


# -------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--only", default="",
                    help="run this one phase (kernels, train, train_moe, serve, "
                         "serve_latent, serve_window, serve_state, "
                         "serve_sparse, serve_mhc, serve_dsa, multichip) and no "
                         "other")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "devices", file=sys.stderr)
        return 2

    from distributed_lion_tpu.utils import compile_cache

    def meter():  # what a second run in the same call compares
        t = compile_cache.totals()
        return t["cache_hits"], t["cache_misses"], t["compile_s"]

    t_start = time.time()
    # turns the cache on and the compile ledger's listeners with it
    device = phase_device(compile_cache.enable_compilation_cache())
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    out_dir = os.path.join(work, "train")
    phases = ([("kernels", phase_kernels),
               ("train", lambda: phase_train(out_dir)),
               ("train_moe", phase_train_moe),
               ("serve", lambda: phase_serve(out_dir)),
               ("serve_latent", phase_serve_latent),
               ("serve_window", phase_serve_window),
               ("serve_state", phase_serve_state),
               ("serve_sparse", phase_serve_sparse),
               ("serve_mhc", phase_serve_mhc),
               ("serve_dsa", phase_serve_dsa)]
              if args.chips == 1 else
              [("multichip", lambda: phase_multichip(work))])
    if args.only:
        phases = [p for p in phases if p[0] == args.only]
    failed = []
    for name, fn in phases:
        log(f"phase {name}")
        before, t0 = meter(), time.time()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        h, m, c = (a - b for a, b in zip(meter(), before))
        log(f"phase {name} {'FAILED' if name in failed else 'ok'} in "
            f"{time.time() - t0:.1f} s — compile {c:.1f} s, persistent "
            f"cache hits {h} misses {m}")
    shutil.rmtree(work, ignore_errors=True)
    for line in compile_cache.ledger_lines():
        log(line)
    h, m, c = meter()
    log(f"total {time.time() - t_start:.1f} s — backend compile {c:.1f} s, "
        f"persistent cache hits {h} misses {m}")
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
