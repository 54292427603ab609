"""Kernel microbenchmarks on the current devices (run on real TPU).

    python scripts/bench_kernels.py [--iters 10]

Times each op chained inside ONE jit dispatch (lax.scan) so dispatch
overhead cancels. Used to make
data-driven kernel choices — the fused-vs-jnp RMSNorm decision and the
flash block-size table in PERF.md come from this script.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def scan_time(fn, init, iters=10):
    @jax.jit
    def run(c):
        def body(c, _):
            return fn(c), None

        out, _ = jax.lax.scan(body, c, None, length=iters)
        return out

    jax.block_until_ready(run(init))
    t0 = time.perf_counter()
    jax.block_until_ready(run(init))
    return (time.perf_counter() - t0) / iters


def bench_rmsnorm(iters: int) -> None:
    from midgpt_tpu.ops.fused_norm import fused_rms_norm

    shapes = [(16, 1024, 768), (8, 1024, 2048)]
    for shape in shapes:
        x0 = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)

        def jnp_norm(x):
            out = x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-5
            )
            return out

        for name, f in (("jnp", jnp_norm), ("fused", lambda x: fused_rms_norm(x, None, 1e-5))):
            t = scan_time(lambda x, f=f: f(x).astype(jnp.bfloat16), x0, iters)
            g = jax.grad(lambda x, f=f: f(x).astype(jnp.float32).sum())
            tb = scan_time(lambda x, g=g: g(x).astype(jnp.bfloat16), x0, iters)
            print(
                f"rmsnorm {shape} {name:5s}: fwd {t*1e6:7.1f} us   "
                f"fwd+bwd {tb*1e6:7.1f} us"
            )


def bench_flash_blocks(iters: int) -> None:
    from midgpt_tpu.ops.flash import flash_attention

    b, h, t, c = 16, 12, 1024, 64
    kk = jax.random.normal(jax.random.PRNGKey(4), (b, h, t, c), jnp.bfloat16)
    vv = jax.random.normal(jax.random.PRNGKey(5), (b, h, t, c), jnp.bfloat16)
    q0 = jax.random.normal(jax.random.PRNGKey(6), (b, h, t, c), jnp.bfloat16)
    fl = 2 * 2 * b * h * t * t * c / 2
    for bs in (128, 256, 512, 1024):
        f = lambda q, bs=bs: flash_attention(
            q, kk, vv, causal=True, block_q=bs, block_k=bs
        ).astype(jnp.bfloat16)
        tf = scan_time(f, q0, iters)
        g = jax.grad(
            lambda q, bs=bs: flash_attention(
                q, kk, vv, causal=True, block_q=bs, block_k=bs
            ).astype(jnp.float32).sum()
        )
        tb = scan_time(lambda q, g=g: g(q).astype(jnp.bfloat16), q0, iters)
        print(
            f"flash blk {bs:4d}: fwd {tf*1e3:6.2f} ms ({fl/tf/1e12:5.1f} TF/s)  "
            f"fwd+dq {tb*1e3:6.2f} ms"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"device: {jax.devices()[0].device_kind} x{jax.device_count()}")
    bench_rmsnorm(args.iters)
    bench_flash_blocks(args.iters)


if __name__ == "__main__":
    main()
