"""Time the trainer's flash kernels alone on the chip at a train cell's
shape: ``python scripts/flash_microbench.py [B] [blocks...]``. Forward and
forward + backward (a vjp with a given cotangent: kernels only), and the
error against ``attention_xla`` in bfloat16. A chip-only tool."""
import sys
import time

import jax
import jax.numpy as jnp

from distributed_lion_tpu.ops import pallas_flash_attn as F
from distributed_lion_tpu.ops.attention import attention_xla

H, HD, T = 12, 64, 1024
D = H * HD


def heads(x):          # [B, T, D] -> [B, H, T, hd]
    return x.reshape(x.shape[0], T, H, HD).transpose(0, 2, 1, 3)


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_microbench needs a TPU")
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    blocks = [int(a) for a in sys.argv[2:]] or [F.block_for(T)]
    qkv = jax.random.normal(jax.random.key(0), (B, T, 3 * D), jnp.bfloat16)
    do = jax.random.normal(jax.random.key(1), (B, T, D), jnp.bfloat16)
    q, k, v = (heads(qkv[:, :, i * D:(i + 1) * D]) for i in range(3))
    do_h = heads(do)

    want = jax.jit(attention_xla)(q, k, v)
    want_g = jax.jit(lambda q, k, v, d: jax.vjp(attention_xla, q, k, v)[1](d)
                     )(q, k, v, do_h)
    for blk in blocks:
        F.block_for = lambda T, blk=blk: blk
        mine = lambda x: F.flash_qkv(x, H)          # noqa: E731
        f = timed(jax.jit(mine), qkv)
        fb = timed(jax.jit(lambda x, d: jax.vjp(mine, x)[1](d)), qkv, do)
        got = heads(jax.jit(mine)(qkv)).astype(jnp.float32)
        err = float(jnp.abs(got - want.astype(jnp.float32)).max())
        (g,) = jax.jit(lambda x, d: jax.vjp(mine, x)[1](d))(qkv, do)
        gerr = [float(jnp.abs(heads(g[:, :, i * D:(i + 1) * D]).astype(
            jnp.float32) - want_g[i].astype(jnp.float32)).max())
            for i in range(3)]
        print(f"B={B} block {blk}: fwd {f:.3f} ms, fwd+bwd {fb:.3f} ms "
              f"(bwd {fb - f:.3f}); max err fwd {err:.4f}, dq/dk/dv "
              f"{gerr[0]:.4f}/{gerr[1]:.4f}/{gerr[2]:.4f}", flush=True)


if __name__ == "__main__":
    main()
