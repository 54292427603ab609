"""Microbench: the attention SUB-PATH at the 124M shape, on the real chip.

Quantifies what the projection-natural fused kernel can win (r3): the
current path pays QK-LayerNorm + RoPE (loop fusions, with backward) and
four [B,T,H,C]<->[B,H,T,C] transposes around the flash kernel; the fused
design eliminates all of it. Measures, fwd+bwd each:

  flash_core   pre-transposed [B,H,T,C] q,k,v -> flash -> sum
  full_path    qkv [B,T,(H+2Hkv)C] -> slice/LN/rope/transpose -> flash
               -> transpose back (the real per-layer subgraph)
  naive_path   same but attention via the XLA naive path

full_path - flash_core = the overhead the fused kernel attacks (x n_layer).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

B, T, H, HKV, C = 16, 1024, 12, 12, 64
D = H * C


def _time(fn, *args, n=20):
    fn_j = jax.jit(fn)
    out = fn_j(*args)
    jax.block_until_ready(out)
    # time a chain with one host read at the end
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn_j(*args)
    _ = float(jnp.sum(out[0]) if isinstance(out, tuple) else jnp.sum(out))
    return (time.perf_counter() - t0) / n * 1e3


def main():
    from midgpt_tpu.models.layers import LayerNorm, apply_rotary, rope_tables
    from midgpt_tpu.ops.flash import flash_attention
    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    qkv = jax.random.normal(key, (B, T, (H + 2 * HKV) * C), jnp.bfloat16)
    qp = jax.random.normal(key, (B, H, T, C), jnp.bfloat16)
    kp = jax.random.normal(key, (B, HKV, T, C), jnp.bfloat16)
    vp = jax.random.normal(key, (B, HKV, T, C), jnp.bfloat16)
    sin, cos = rope_tables(C, T)
    q_norm = LayerNorm.init(C)
    k_norm = LayerNorm.init(C)

    def flash_core(q, k, v):
        return flash_attention(q, k, v)

    def full_path(qkv, q_norm, k_norm):
        q = qkv[..., : H * C].reshape(B, T, H, C)
        k = qkv[..., H * C : (H + HKV) * C].reshape(B, T, HKV, C)
        v = qkv[..., (H + HKV) * C :].reshape(B, T, HKV, C)
        q, k = q_norm(q), k_norm(k)
        q = jnp.transpose(q, (0, 2, 1, 3))
        k = jnp.transpose(k, (0, 2, 1, 3))
        v = jnp.transpose(v, (0, 2, 1, 3))
        q = apply_rotary(q, sin, cos)
        k = apply_rotary(k, sin, cos)
        out = flash_attention(q, k, v)
        return jnp.transpose(out, (0, 2, 1, 3)).reshape(B, T, H * C)

    def naive_core(q, k, v):
        from midgpt_tpu.ops.attention import naive_attention

        return naive_attention(q, k, v, causal=True)

    results = {}
    for name, fn, args in [
        ("flash_core_fwd", flash_core, (qp, kp, vp)),
        ("naive_core_fwd", naive_core, (qp, kp, vp)),
        ("full_path_fwd", functools.partial(full_path), (qkv, q_norm, k_norm)),
    ]:
        results[name] = _time(fn, *args)

    def grad_of(fn, nargs):
        def loss(*a):
            return jnp.sum(fn(*a).astype(jnp.float32))

        return jax.grad(loss, argnums=tuple(range(nargs)))

    results["flash_core_fb"] = _time(grad_of(flash_core, 3), qp, kp, vp)
    results["naive_core_fb"] = _time(grad_of(naive_core, 3), qp, kp, vp)
    results["full_path_fb"] = _time(
        grad_of(lambda a, qn, kn: full_path(a, qn, kn), 1), qkv, q_norm, k_norm
    )

    for k_, v_ in results.items():
        print(f"{k_:>18}: {v_:7.2f} ms")
    print(
        f"\noverhead fwd  (full - flash): {results['full_path_fwd'] - results['flash_core_fwd']:.2f} ms"
    )
    print(
        f"overhead f+b  (full - flash): {results['full_path_fb'] - results['flash_core_fb']:.2f} ms"
    )


if __name__ == "__main__":
    main()
