"""Time the loss head's kernel pair alone on the chip at a train cell's
shape: ``python scripts/xent_microbench.py [--rows N] [--vocab V] [--d D]
[tnxtv[xgroupMB]...]`` (the defaults are cell 1's: 20 x 1,024 rows against
GPT-2's head; cell 10's are ``--rows 16384 --vocab 24576 --d 2304``).
Forward and forward + backward (a vjp with a given cotangent: kernels and
the label's gathered product only) against the dense einsum + log_softmax
at the same rows, and the error against it; beside each timing the cut it
ran (groups x row blocks a group, pad rows). A chip-only tool."""
import argparse
import time

import jax
import jax.numpy as jnp

from distributed_lion_tpu.ops import pallas_xent as X


def dense(h, w, labels):
    logits = jnp.einsum("nd,vd->nv", h, w, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return nll, logits.argmax(-1).astype(jnp.int32)


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main():
    if jax.default_backend() != "tpu":
        raise SystemExit("xent_microbench needs a TPU")
    ap = argparse.ArgumentParser(description=__doc__.split(":")[0])
    ap.add_argument("--rows", type=int, default=20 * 1024)
    ap.add_argument("--vocab", type=int, default=50257)
    ap.add_argument("--d", type=int, default=768)
    ap.add_argument("specs", nargs="*", metavar="tnxtv[xgroupMB]")
    args = ap.parse_args()
    n, V, D = args.rows, args.vocab, args.d
    specs = args.specs or ["x".join(map(str, X.tiles_for(n, V, D)))]
    h = jax.random.normal(jax.random.key(0), (n, D), jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (V, D)) * 0.05
         ).astype(jnp.bfloat16)
    labels = jax.random.randint(jax.random.key(2), (n,), 0, V)
    g = jax.random.uniform(jax.random.key(3), (n,)) / n
    flop = 2 * n * D * V

    def vjp_of(fn):
        return jax.jit(lambda h, w, g: jax.vjp(
            lambda h, w: fn(h, w)[0], h, w)[1](g))

    ref = jax.jit(lambda h, w: dense(h, w, labels))
    ref_vjp = vjp_of(ref)
    f0 = timed(ref, h, w)
    fb0 = timed(ref_vjp, h, w, g)
    print(f"rows {n}, vocab {V}, d {D}: dense fwd {f0:.2f} ms, fwd+bwd "
          f"{fb0:.2f} ms; one product at 197 TFLOP/s {flop / 197e9:.2f} ms",
          flush=True)
    want, want_idx = ref(h, w)
    want_dh, want_dw = ref_vjp(h, w, g)
    budget = X.DH_VMEM_BYTES
    for spec in specs:
        tn, tv, *mb = (int(x) for x in spec.split("x"))
        X.DH_VMEM_BYTES = mb[0] << 20 if mb else budget
        groups, per_group = X.row_groups(n, D, tn)
        mine = jax.jit(lambda h, w: X.fused_xent(h, w, labels, 0, (tn, tv)))
        mine_vjp = vjp_of(mine)
        try:
            f = timed(mine, h, w)
            fb = timed(mine_vjp, h, w, g)
        except Exception as e:  # a refused tile pair: say so, try the next
            print(f"tiles {spec}: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
            continue
        got, idx = mine(h, w)
        dh, dw = mine_vjp(h, w, g)
        print(f"tiles {spec} (groups {groups} x {per_group} blocks for "
              f"{-(-n // tn)}, pad rows {-n % tn}): fwd "
              f"{f:.2f} ms ({flop / f / 1.97e9:.1f}% of peak), fwd+bwd "
              f"{fb:.2f} ms (bwd {fb - f:.2f}: {3 * flop / (fb - f) / 1.97e9:.1f}%"
              f" of peak over its 3 products); nll max err "
              f"{float(jnp.abs(got - want).max()):.2e}, argmax differs "
              f"{int((idx != want_idx).sum())}, dh rel {rel(dh, want_dh):.2e},"
              f" dw rel {rel(dw, want_dw):.2e}", flush=True)


if __name__ == "__main__":
    main()
