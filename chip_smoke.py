#!/usr/bin/env python3
"""Does the system still start on the chip? One process, the normal entry
points, random weights from ``--seed``, nothing measured.

    python chip_smoke.py             # one TPU chip: phases 1-8
    python chip_smoke.py --only 6    # phase 1 and the phases named
    python chip_smoke.py --chips 4   # four chips: the two sharded paths only

One chip, in order — any failed assertion ends the run non-zero:

1. device   — the backend is a TPU, or stop; versions, cache directory.
2. kernels  — every Pallas kernel of the main path, compiled (not
   interpreted) at ``openwebtext`` widths, forward and backward where it
   has one, against the reference already in the tree.
3. train    — ``openwebtext`` at full width AND depth through the calls
   ``launch.py`` makes (``get_config`` -> ``apply_overrides`` ->
   ``train``) on the seeded synthetic corpus; only batch, accumulation and
   the schedule's length are cut (``TRAIN_SET``).
4. resume   — the checkpoint restores; its parameters reproduce the run's
   final validation loss to the bit; ``train`` takes one more step from it.
5. serve    — the restored weights in ``ServingEngine``: defaults, then
   speculative, then int8 weights + int8 KV. Every emitted token is held
   to one full-context forward of the same weights (``LOGIT_TOL_ULPS``).

6. block    — the block-diffusion forward (``verify_tokens_paged`` with
   ``block_len=4`` at the block window's T = 8 rows a slot: two blocks,
   causal across them, rows of one all see each other) at 32 query
   heads over 4 KV heads of 128, half-split RoPE and QK-RMSNorm, the
   expert layer behind it (every expert chosen, so that no near-tie of the
   router widens the comparison), bf16, ragged lengths: the Pallas kernel under the
   block mask (one body with decode's: ops/paged_attn.py) against
   the XLA gather path (``KERNEL_TOL``), and under the
   causal mask it must NOT agree (the mask is really another).

7. gated delta rule — the decode step's Pallas kernel (``ops/gated_delta``)
   at 32 slots of 30 heads with a state of 96 x 192 (the benchmark's hybrid
   cell), compiled, against its ``jax.numpy`` body, in place in a stack of
   two layers; and a 256-token chunk of the chunked form against the rule
   a token at a time.

8. latent attention — the paged kernel's latent mode (``ops/paged_attn``,
   one page DMA for scores and values) at the published widths of the
   benchmark's latent cell (32 heads, rows of 512 + 64 in 640 lanes) over a
   33,792-position table at pages of 16, 32 and 64, compiled, against the
   ``jax.numpy`` gather path; and one 256-row prefill chunk against 33 k
   pooled rows (absorbed, heads in groups) against the published form with
   every key up-projected.

Four chips: ``openwebtext`` on an fsdp=2 x tensor=2 mesh against a
one-device mesh (same seed, data, global batch), and a tp=2 x 2-replica
``ServingCluster`` against one single-chip engine.

The last line of standard output is the result, and is printed only when
every phase passed. Times on earlier lines are smoke timings: none is a
record. A rehearsal without the chip (tiny sizes, interpret mode) drives
this module from outside; there is no switch for it in here.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.metadata
import io
import json
import math
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

CONFIG = "openwebtext"
# what one 16 GB chip holds, and a schedule short enough to watch: the
# config's own 2048 x 16-way accumulation and 60k-step schedule are the
# only things cut. Width, depth, T and vocab are the config's.
TRAIN_SET = [
    "batch_size=16", "g_accum_iters=2", "steps_per_dispatch=5",
    "max_steps=40", "warmup_steps=10", "lr_decay_steps=40",
    "eval_interval=40", "eval_batches=4", "log_interval=1",
    "train_telemetry=true",
]
# four chips: same global batch on both meshes, a few steps
SHARDED_SET = [
    "batch_size=16", "g_accum_iters=2", "warmup_steps=4",
    "lr_decay_steps=12",
]
SHARDED_STEPS, SHARDED_K = 6, 2
# |loss(fsdp x tp) - loss(one device)| per step: bf16 compute with the
# contractions split across chips reassociates every matmul
SHARDED_LOSS_TOL = 0.05

# kernels vs references at openwebtext widths
KB, KT, KH, KC = 4, 1024, 12, 64
# max |kernel - reference| over max |reference|: bf16 inputs (8
# significant bits), f32 accumulation in another order
KERNEL_TOL = 2e-2

# serving: an emitted token's teacher-forced logit must be within this
# many bf16 ulps (2**-8 relative) of that row's largest logit magnitude
# below the row's max. The engine's bf16 K/V cache, paged softmax order
# and chunk boundaries differ from the one-shot forward by rounding only.
LOGIT_TOL_ULPS = 8
# an int8 KV page adds its grid step (page absmax / 127, po2-rounded up)
# to every cached K/V element — coarser than bf16 for small elements
LOGIT_TOL_ULPS_KV8 = 32
NEW_TOKENS = 24
PROMPT_LENS = (20, 75, 150, 300, 41, 150)
SHARED_PREFIX = 64  # tokens shared by the prompts of 150 and 300
FORWARD_LEN = 384  # one compile serves every teacher-forced check
ENGINE_KW = {"slots": 4, "prefill_chunk": 64}  # beside the engine's defaults


def say(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(Exception):
    pass


_failed_checks = []


def check(cond: bool, what: str) -> None:
    """A failed check is said at once and fails its phase at the phase's
    end: one chip run then shows everything that is wrong in a phase,
    not the first thing."""
    if not cond:
        _failed_checks.append(what)
        say(f"  CHECK FAILED: {what}")


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    del _failed_checks[:]
    say(f"== {name}")
    yield
    if _failed_checks:
        raise PhaseFailed(
            f"{name}: {len(_failed_checks)} check(s) failed: "
            + "; ".join(_failed_checks)
        )
    say(f"== {name}: passed (smoke timing {time.perf_counter() - t0:.1f} s)")


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------


def phase_device(want_chips: int):
    import jax

    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke: the JAX backend is {jax.default_backend()!r}, "
            "not a TPU; nothing was run",
            file=sys.stderr,
        )
        raise SystemExit(2)
    import jaxlib

    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    check(
        len(devices) >= want_chips,
        f"--chips {want_chips} needs {want_chips} devices, JAX has "
        f"{len(devices)}",
    )
    say(
        f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} count={len(devices)}"
    )
    say(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {importlib.metadata.version('libtpu')}")
    placed = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} "
        f"({'placed by JAX_COMPILATION_CACHE_DIR' if placed else 'in the checkout'}"
        f"; {entries} entries at start)")
    return devices


# ---------------------------------------------------------------------------
# kernel facts: what a traced function's Pallas calls are
# ---------------------------------------------------------------------------


def pallas_calls(fn, *args):
    """(kernel name, interpret flag) of every ``pallas_call`` in the
    trace of ``fn(*args)`` — what the program contains, not what a
    resolver says it should."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                src = eqn.params["jaxpr"].debug_info.func_src_info
                found.append((
                    # "_fwd_kernel at .../ops/fused_norm.py:35"
                    src.replace(os.path.join(REPO, ""), ""),
                    bool(eqn.params["interpret"]),
                ))
                continue
            for p in eqn.params.values():
                for c in p if isinstance(p, (tuple, list)) else (p,):
                    inner = getattr(c, "jaxpr", c)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def check_compiled_kernels(label: str, jitted, *args) -> None:
    """The trace of the jitted function about to run holds Pallas
    kernels, none asks for the interpreter, and its lowered module holds
    the TPU custom call."""
    calls = pallas_calls(jitted, *args)
    check(bool(calls), f"{label}: no Pallas kernel in the trace")
    check(
        not any(interp for _, interp in calls),
        f"{label}: a kernel is interpreted: {calls}",
    )
    check("tpu_custom_call" in jitted.lower(*args).as_text(),
          f"{label}: no TPU custom call lowered")
    names = sorted({n for n, _ in calls})
    say(f"  {label}: {len(calls)} compiled kernel call(s) {names}")


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# ---------------------------------------------------------------------------
# 2. kernels
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.models.layers import _duplicate_interleaved, rope_tables
    from midgpt_tpu.ops.flash import flash_attention, flash_attention_reference
    from midgpt_tpu.ops.fused_attn import (
        fused_attention,
        fused_attention_qkv,
        fused_attention_reference,
    )
    from midgpt_tpu.ops.fused_norm import fused_rms_norm

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    bf16 = jnp.bfloat16

    def rnd(shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def fwd_bwd(fn, argnums):
        def scalar(*a):
            out = fn(*a)
            # a fixed random cotangent, so that every output element
            # reaches the backward pass with its own weight
            w = jax.random.normal(
                jax.random.PRNGKey(seed + 1), out.shape, jnp.float32
            )
            return jnp.sum(out.astype(jnp.float32) * w), out

        return jax.jit(jax.value_and_grad(scalar, argnums, has_aux=True))

    def compare(label, kernel_fn, ref_fn, args, argnums):
        run = fwd_bwd(kernel_fn, argnums)
        check_compiled_kernels(label, run, *args)
        (_, out), grads = run(*args)
        (_, out_r), grads_r = fwd_bwd(ref_fn, argnums)(*args)
        errs = [rel_err(out, out_r)] + [
            rel_err(g, gr) for g, gr in zip(grads, grads_r)
        ]
        check(
            all(np.isfinite(np.asarray(g, np.float32)).all()
                for g in (out, *grads)),
            f"{label}: non-finite output or gradient",
        )
        check(
            max(errs) <= KERNEL_TOL,
            f"{label}: rel. error vs reference {errs} > {KERNEL_TOL}",
        )
        say(f"  {label}: forward + {len(grads)} gradients agree with the "
            f"reference (max rel. error {max(errs):.2e})")

    # flash attention vs flash_attention_reference
    qkv_h = [rnd((KB, KH, KT, KC)) for _ in range(3)]
    compare("flash_attention", flash_attention, flash_attention_reference,
            qkv_h, (0, 1, 2))

    # fused QK-LN + RoPE + attention, both entries, vs its reference
    sin, cos = (
        _duplicate_interleaved(jnp.asarray(t, jnp.float32))
        for t in rope_tables(KC, KT)
    )  # the [T, C] tables Attention._fused_call hands the kernel
    wq, wk = (1.0 + 0.1 * rnd((KC,), jnp.float32) for _ in range(2))
    qkv_n = [rnd((KB, KT, KH * KC)) for _ in range(3)]
    compare(
        "fused_attention",
        lambda q, k, v, wq, wk: fused_attention(
            q, k, v, wq, wk, sin, cos, KH, KH
        ),
        lambda q, k, v, wq, wk: fused_attention_reference(
            q, k, v, wq, wk, sin, cos, KH, KH
        ),
        qkv_n + [wq, wk], (0, 1, 2, 3, 4),
    )
    packed = jnp.concatenate(qkv_n, axis=-1)

    def packed_ref(qkv, wq, wk):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return fused_attention_reference(q, k, v, wq, wk, sin, cos, KH, KH)

    compare(
        "fused_attention_qkv",
        lambda qkv, wq, wk: fused_attention_qkv(
            qkv, wq, wk, sin, cos, KH, KH
        ),
        packed_ref, [packed, wq, wk], (0, 1, 2),
    )

    # fused RMSNorm vs the plain one
    def plain_rms(x, w, eps=1e-6):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)

    compare(
        "fused_rms_norm", fused_rms_norm, plain_rms,
        [rnd((KB, KT, KH * KC)), (1.0 + 0.1 * rnd((KH * KC,)))], (0, 1),
    )

    # the paged pair vs the XLA gather path, through the model calls the
    # engine's programs make
    paged_kernels_vs_gather(seed)


def paged_kernels_vs_gather(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.config import get_config
    from midgpt_tpu.models.gpt import (
        GPT,
        decode_step_paged,
        verify_tokens_paged,
    )
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving import PagedKVPool, pages_needed

    cfg = dataclasses.replace(get_config(CONFIG).model, n_layer=2)
    model = cast_floating(
        GPT.init(jax.random.PRNGKey(seed), cfg), jnp.bfloat16
    )
    s, ps, t = 8, 16, 4
    pmax = pages_needed(cfg.block_size, ps)
    npool = 2 * pmax
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 8)
    bt = jax.random.randint(ks[0], (s, pmax), 0, npool).astype(jnp.int32)
    # ragged: empty, a partial page, page-aligned, ..., the full table
    pooled_len = jnp.asarray(
        [0, 13, 32, 100, 257, 640, 1000, pmax * ps - t - 8], jnp.int32
    )
    tokens = jax.random.randint(ks[1], (s,), 0, cfg.vocab_size, jnp.int32)
    cand = jax.random.randint(ks[2], (s, t), 0, cfg.vocab_size, jnp.int32)
    rr = 8
    for pool_name, kv_quant in (("bf16", None), ("int8", "int8")):
        pool = PagedKVPool.init(cfg, npool, ps, jnp.bfloat16,
                                kv_quant=kv_quant)
        if kv_quant:
            pool = dataclasses.replace(
                pool,
                k=jax.random.randint(ks[3], pool.k.shape, -127, 128,
                                     jnp.int32).astype(jnp.int8),
                v=jax.random.randint(ks[4], pool.v.shape, -127, 128,
                                     jnp.int32).astype(jnp.int8),
                # page scales that put |K|, |V| in the bf16 pool's range
                # (|code| <= 127, scale <= 2**-5: at most ~4)
                scale_k=jnp.exp2(jax.random.randint(
                    ks[5], pool.scale_k.shape, -10, -4).astype(jnp.float32)),
                scale_v=jnp.exp2(jax.random.randint(
                    ks[6], pool.scale_v.shape, -10, -4).astype(jnp.float32)),
            )
        else:
            pool = dataclasses.replace(
                pool,
                k=jax.random.normal(ks[3], pool.k.shape).astype(pool.k.dtype),
                v=jax.random.normal(ks[4], pool.v.shape).astype(pool.v.dtype),
            )
        rk = jnp.zeros((cfg.n_layer, s, cfg.kv_heads, rr, cfg.head_dim),
                       pool.row_dtype)
        rk = rk.at[:, :, :, 0, :].set(0.25)
        rv = jnp.zeros_like(rk).at[:, :, :, 0, :].set(-0.5)
        r = jnp.asarray(1, jnp.int32)
        pos = pooled_len + 1

        # the model is an entry parameter, as in the engine's programs: a
        # closed-over model is baked into the executable as constants
        def decode(kernel):
            return lambda mod, tk, pk, pv, b_, rk_, rv_, pl_, sk, sv: (
                decode_step_paged(
                    mod, tk, pos, pk, pv, b_, rk_, rv_, r, pl_,
                    cfg.block_size, pool_sk=sk, pool_sv=sv,
                    paged_kernel=kernel,
                )[0]
            )

        def verify(kernel):
            return lambda mod, c_, pk, pv, b_, pl_, sk, sv: (
                verify_tokens_paged(
                    mod, c_, pl_, pk, pv, b_, cfg.block_size,
                    pool_sk=sk, pool_sv=sv, paged_kernel=kernel,
                )[0]
            )

        d_args = (model, tokens, pool.k, pool.v, bt, rk, rv, pooled_len,
                  pool.scale_k, pool.scale_v)
        v_args = (model, cand, pool.k, pool.v, bt, pooled_len,
                  pool.scale_k, pool.scale_v)
        for label, make, args in (
            (f"paged_decode_attention[{pool_name}]", decode, d_args),
            (f"paged_verify_attention[{pool_name}]", verify, v_args),
        ):
            run = jax.jit(make("pallas"))
            check_compiled_kernels(label, run, *args)
            got = run(*args)
            want = jax.jit(make("xla"))(*args)
            check(np.isfinite(np.asarray(got, np.float32)).all(),
                  f"{label}: non-finite logits")
            err = rel_err(got, want)
            check(err <= KERNEL_TOL,
                  f"{label}: rel. error vs the XLA gather {err} > {KERNEL_TOL}")
            say(f"  {label}: logits agree with the XLA gather path "
                f"(max rel. error {err:.2e}, shape {tuple(got.shape)})")
        # the twin (ops/paged_attn.py, THE CONTRACT, clause 1): a verify
        # dispatch of ONE candidate row is a one-step decode window, to
        # the bit, compiled, on this chip
        rk1 = jnp.zeros((cfg.n_layer, s, cfg.kv_heads, 1, cfg.head_dim),
                        pool.row_dtype)
        as_decode = jax.jit(
            lambda mod, tk, pk, pv, b_, rk_, pl_, sk, sv: decode_step_paged(
                mod, tk, pl_, pk, pv, b_, rk_, rk_,
                jnp.asarray(0, jnp.int32), pl_, cfg.block_size, pool_sk=sk,
                pool_sv=sv, paged_kernel="pallas",
            )[0]
        )(model, tokens, pool.k, pool.v, bt, rk1, pooled_len,
          pool.scale_k, pool.scale_v)
        as_verify = jax.jit(verify("pallas"))(
            model, tokens[:, None], pool.k, pool.v, bt, pooled_len,
            pool.scale_k, pool.scale_v)[:, 0]
        twin = np.array_equal(np.asarray(as_decode, np.float32),
                              np.asarray(as_verify, np.float32))
        check(twin, f"paged twin[{pool_name}]: a one-row verify is not the "
                    f"decode step to the bit "
                    f"(rel. error {rel_err(as_verify, as_decode)})")
        say(f"  paged twin[{pool_name}]: one verify row == the decode step, "
            f"to the bit")


def block_forward_vs_gather(seed: int) -> None:
    """Phase 6: the block-diffusion forward, kernel against gather."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.models.gpt import GPT, verify_tokens_paged
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving import PagedKVPool, pages_needed

    blk = 4
    cfg = ModelConfig(
        block_size=768, vocab_size=8192, n_layer=2, n_head=32, n_kv_head=4,
        head_width=128, n_embd=2048, qk_norm_kind="rms", rope_style="half",
        rope_base=1e6, norm_scale=True, norm_eps=1e-6, mlp="experts",
        experts=4, experts_per_token=4, expert_hidden=768, block_len=blk,
        block_steps=blk, mask_token=8191,
    )
    model = cast_floating(
        GPT.init(jax.random.PRNGKey(seed), cfg), jnp.bfloat16
    )
    s, ps = 8, 16
    pmax = pages_needed(cfg.block_size, ps)
    npool = 2 * pmax
    ks = jax.random.split(jax.random.PRNGKey(seed + 11), 4)
    bt = jax.random.randint(ks[0], (s, pmax), 0, npool).astype(jnp.int32)
    # ragged, whole blocks: empty, inside a page, page-aligned, ..., full
    t = 2 * blk  # the window's rows a slot: the block that lands | the next
    start = jnp.asarray(
        [0, 12, 32, 100, 256, 500, 640, pmax * ps - t], jnp.int32
    )
    cand = jax.random.randint(ks[1], (s, t), 0, cfg.vocab_size, jnp.int32)
    pool = PagedKVPool.init(cfg, npool, ps, jnp.bfloat16)
    pool = dataclasses.replace(
        pool,
        k=jax.random.normal(ks[2], pool.k.shape).astype(pool.k.dtype),
        v=jax.random.normal(ks[3], pool.v.shape).astype(pool.v.dtype),
    )

    def forward(kernel, block_len):
        return jax.jit(lambda mod, c_, pk, pv, b_, st: verify_tokens_paged(
            mod, c_, st, pk, pv, b_, cfg.block_size, paged_kernel=kernel,
            block_len=block_len,
        )[0])

    args = (model, cand, pool.k, pool.v, bt, start)
    label = "paged_verify_attention[block mask, 32 over 4 heads of 128]"
    run = forward("pallas", blk)
    check_compiled_kernels(label, run, *args)
    got, want = run(*args), forward("xla", blk)(*args)
    check(np.isfinite(np.asarray(got, np.float32)).all(),
          f"{label}: non-finite logits")
    err = rel_err(got, want)
    check(err <= KERNEL_TOL,
          f"{label}: rel. error vs the XLA gather {err} > {KERNEL_TOL}")
    other = rel_err(forward("pallas", 0)(*args), want)
    check(other > 10 * KERNEL_TOL,
          f"{label}: the causal kernel agrees with the block-mask gather "
          f"({other}): the mask changed nothing")
    say(f"  {label}: logits agree with the XLA gather path (max rel. error "
        f"{err:.2e}; the causal kernel is {other:.2e} away)")


def gated_delta_vs_numpy(seed: int) -> None:
    """Phase 7: the gated delta rule's step kernel and its chunked form,
    at the published widths, against the ``jax.numpy`` bodies."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.ops import gated_delta as gd

    s, h, dk, dv, t = 32, 30, 96, 192, 256
    ks = jax.random.split(jax.random.PRNGKey(seed + 23), 6)
    bf = jnp.bfloat16

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = (unit(jax.random.normal(ks[0], (s, t, h, dk))) / dk ** 0.5).astype(bf)
    k = unit(jax.random.normal(ks[1], (s, t, h, dk))).astype(bf)
    v = jax.random.normal(ks[2], (s, t, h, dv)).astype(bf)
    g = -2.0 * jax.random.uniform(ks[3], (s, t, h)) ** 4  # alpha 0.14 .. 1
    beta = 2.0 * jax.random.uniform(ks[4], (s, t, h))
    stack = jax.random.normal(ks[5], (2, s, h, dk, dv), jnp.float32)

    label = "gdn_step[32 slots, 30 heads of 96 x 192]"
    run = jax.jit(lambda *a: gd.step(*a, 1), donate_argnums=(5,))
    one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    check_compiled_kernels(label, run, *one, stack)
    o_want, s_want = jax.jit(gd.step_reference)(*one, stack[1])
    other = np.asarray(stack[0])
    o_got, got = run(*one, stack)
    err = max(rel_err(o_got, o_want), rel_err(got[1], s_want))
    check(err <= 1e-5, f"{label}: rel. error vs the jax.numpy body {err}")
    check(np.array_equal(np.asarray(got[0]), other),
          f"{label}: the other layer's states moved")
    say(f"  {label}: output and state agree with the jax.numpy body "
        f"(max rel. error {err:.2e}), the other layer's rows untouched")

    label = "gdn chunked[256 tokens, chunks of 64]"
    s0 = got[1][:4]
    args = tuple(a[:4] for a in (q, k, v, g, beta)) + (s0,)
    o_c, s_c = jax.jit(gd.chunked)(*args)
    with jax.default_matmul_precision("highest"):
        o_r, s_r = jax.jit(gd.recurrent)(*args)
    err = max(rel_err(o_c, o_r), rel_err(s_c, s_r))
    check(np.isfinite(np.asarray(o_c)).all(), f"{label}: non-finite output")
    check(err <= KERNEL_TOL,
          f"{label}: rel. error vs the token-by-token rule {err} > "
          f"{KERNEL_TOL}")
    say(f"  {label}: output and state agree with the rule taken a token at "
        f"a time (max rel. error {err:.2e})")


def latent_attention_vs_numpy(seed: int) -> None:
    """Phase 8: the latent decode kernel and one latent prefill chunk, at
    the published widths over a 33 k table, against ``jax.numpy``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.models.gpt import LatentAttention, _gather_attend
    from midgpt_tpu.models.layers import rope_tables
    from midgpt_tpu.ops.paged_attn import paged_latent_attention, supported
    from midgpt_tpu.pytree import cast_floating

    block, bf = 33792, jnp.bfloat16
    cfg = ModelConfig(
        block_size=block, vocab_size=1024, n_layer=1, n_head=32, n_embd=2048,
        attention="latent", latent_q=1536, latent_kv=512, latent_nope=128,
        latent_rope=64, latent_v=128, rope_base=32e6, norm_scale=True,
    )
    attn = cast_floating(
        LatentAttention.init(jax.random.PRNGKey(seed + 31), cfg), bf
    )
    row, dc, s = attn.row, attn.kv_rank, 4
    ks = jax.random.split(jax.random.PRNGKey(seed + 37), 5)
    # ragged: empty, inside a page, inside a band, the whole table
    lens = jnp.asarray([0, 700, 20000, block - 16], jnp.int32)
    q = jax.random.normal(ks[0], (s, 1, 32, row)).astype(bf)
    rows = jax.random.normal(ks[1], (s, 1, 16, row)).astype(bf)
    flat = jax.random.normal(ks[2], (2 * block, row)).astype(bf)
    flat = flat.at[:, dc + 64:].set(0)
    r = jnp.int32(5)
    want = None
    for ps in (16, 32, 64):
        pmax = block // ps
        label = f"paged_latent_attention[32 rows of 640 lanes, pages of {ps}]"
        check(supported(pmax, ps, row, 2, groups=32, heads=1, latent=True),
              f"{label}: the geometry is refused")
        # the same positions' rows whatever the page: slot i's table is
        # pages i * pmax / 2 on, so the slots share half their pages
        pool = flat.reshape(1, 2 * pmax, ps, row)
        bt = (jnp.arange(s)[:, None] * (pmax // 4)
              + jnp.arange(pmax)[None]).astype(jnp.int32)
        run = jax.jit(lambda q_, p_, b_, l_, r_: paged_latent_attention(
            q_, p_, b_, l_, r_, r, 0, v_lanes=dc, scale_dim=192))
        check_compiled_kernels(label, run, q, pool, bt, lens, rows)
        got = run(q, pool, bt, lens, rows)
        if want is None:
            mask_pool = jnp.where(
                jnp.arange(block)[None] < lens[:, None], 0.0, -jnp.inf
            )[:, None, None, None, :]
            mask_rec = jnp.where(jnp.arange(16) <= r, 0.0, -jnp.inf)
            want = jax.jit(lambda q_, p_, b_, r_: _gather_attend(
                q_[:, :, :, None], r_, r_[..., :dc], mask_pool, mask_rec,
                p_, None, None, None, b_, 0, scale_dim=192,
            )[:, :, :, 0])(q, pool, bt, rows)
        check(np.isfinite(np.asarray(got, np.float32)).all(),
              f"{label}: non-finite output")
        err = rel_err(got, want)
        check(err <= KERNEL_TOL,
              f"{label}: rel. error vs the gather path {err} > {KERNEL_TOL}")
        say(f"  {label}: agrees with the gather path (max rel. error "
            f"{err:.2e})")

    label = "latent prefill chunk[256 rows against 33 k pooled rows]"
    t, start, ps = 256, block - 512, 64
    x = jax.random.normal(ks[3], (1, start + t, 2048)).astype(bf)
    sin, cos = rope_tables(64, block, 32e6)
    sin, cos = jnp.asarray(sin, bf), jnp.asarray(cos, bf)

    @jax.jit
    def pooled_rows(xc, sin_c, cos_c):
        return attn._project(xc, sin_c, cos_c)[2]

    cuts = list(range(0, start, 4096)) + [start]
    ctx = jnp.concatenate([
        pooled_rows(x[:, i:j], sin[i:j], cos[i:j])
        for i, j in zip(cuts, cuts[1:])], axis=1)[0]  # [start, row]
    pool = jnp.zeros((1, block // ps, ps, row), bf).at[0].set(
        jnp.pad(ctx, ((0, block - start), (0, 0))).reshape(-1, ps, row))
    bt = jnp.arange(block // ps, dtype=jnp.int32)[None]
    ii = jnp.arange(t)
    chunk = jax.jit(lambda xc, p_: attn.prefill_paged_at(
        xc, p_, bt, 0, jnp.where(jnp.arange(block) < start, 0.0, -jnp.inf),
        jnp.where(ii[None, :] <= ii[:, None], 0.0, -jnp.inf),
        sin[start:start + t], cos[start:start + t])[0])
    got = chunk(x[:, start:], pool)

    @jax.jit
    def published(xc, ctx_rows):
        # every key up-projected, nothing absorbed: the chunk's rows
        # against [context | themselves]
        q_nope, q_rope, own = attn._project(
            xc, sin[start:start + t], cos[start:start + t])
        allr = jnp.concatenate([ctx_rows, own[0]], axis=0)
        kv = attn.wkv_b(allr[:, :dc]).reshape(-1, 32, 256)
        sc = jnp.einsum("htn,shn->hts", q_nope[0], kv[..., :128],
                        preferred_element_type=jnp.float32)
        sc = sc + jnp.einsum("htr,sr->hts", q_rope[0], allr[:, dc:dc + 64],
                             preferred_element_type=jnp.float32)
        seen = jnp.arange(start + t)[None, :] <= (start + ii)[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc / 192 ** 0.5, -jnp.inf), -1)
        o = jnp.einsum("hts,shv->thv", p.astype(bf), kv[..., 128:])
        return attn.wo(o.reshape(1, t, 32 * 128))

    err = rel_err(got, published(x[:, start:], ctx))
    check(np.isfinite(np.asarray(got, np.float32)).all(),
          f"{label}: non-finite output")
    check(err <= KERNEL_TOL,
          f"{label}: rel. error vs the published form {err} > {KERNEL_TOL}")
    say(f"  {label}: the absorbed chunk agrees with the published form "
        f"(max rel. error {err:.2e})")


# ---------------------------------------------------------------------------
# 3. train   4. resume
# ---------------------------------------------------------------------------


def check_full_size(m) -> None:
    check(
        (m.n_layer, m.n_embd, m.n_head, m.block_size, m.vocab_size)
        == (12, 768, 12, 1024, 50304),
        f"{CONFIG} is not at its full width and depth: {m}",
    )


def write_corpus(data_dir: str) -> None:
    """The seeded synthetic corpus, by the repo's own script. The child
    imports numpy only: it needs no chip and is gone before JAX starts
    compiling."""
    subprocess.run(
        [sys.executable,
         os.path.join(REPO, "data", "shakespeare_char", "prepare.py"),
         "--synthetic", "--out_dir", data_dir],
        check=True, stdout=subprocess.DEVNULL,
    )


def smoke_config(workdir: str, seed: int, overrides):
    from launch import apply_overrides
    from midgpt_tpu.config import get_config

    cfg = apply_overrides(get_config(CONFIG), overrides)
    return dataclasses.replace(
        cfg, rundir=os.path.join(workdir, "run"),
        data_dir=os.path.join(workdir, "data"), seed=seed,
    )


class Tee(io.TextIOBase):
    def __init__(self, out):
        self.out, self.kept = out, []

    def write(self, s):
        self.kept.append(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def data_loader(cfg, split: str, stream: int):
    """The loader ``train()`` builds for this split on one process."""
    from midgpt_tpu.data import Loader, load_shard

    return Loader(
        shard=load_shard(os.path.join(cfg.data_dir, f"{split}.bin"), 0, 1),
        block_size=cfg.model.block_size,
        batch_shape=(cfg.g_accum_iters, cfg.batch_size // cfg.g_accum_iters),
        seed=cfg.data_seed, process_index=0, stream=stream,
    )


def logged_losses(rundir: str):
    rows = [json.loads(line) for line in open(
        os.path.join(rundir, "metrics.jsonl"))]
    return [(r["step"], r["loss/optimized"]) for r in rows
            if "loss/optimized" in r]


def phase_train(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu import native
    from midgpt_tpu.checkpoint import Checkpointer
    from midgpt_tpu.config import to_json
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.train import train

    m = cfg.model
    check_full_size(m)
    say(f"  {CONFIG}: L={m.n_layer} D={m.n_embd} H={m.n_head} "
        f"T={m.block_size} V={m.vocab_size}; cut for one chip: {TRAIN_SET}")
    os.makedirs(cfg.rundir, exist_ok=True)
    with open(os.path.join(cfg.rundir, "config.json"), "w") as f:
        f.write(to_json(cfg))  # as launch.py does; resume checks against it

    tee = Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        final = train(cfg)
    wall = time.perf_counter() - t0
    said = "".join(tee.kept)
    ladder = [ln for ln in said.splitlines() if "first-step OOM" in ln]
    say(f"  remat step-down ladder: "
        f"{'; '.join(ladder) if ladder else 'did not act'}")

    losses = logged_losses(cfg.rundir)
    say("  loss by step: " + " ".join(
        f"{s}:{v:.3f}" for s, v in losses if s == 1 or s % 5 == 0))
    check(len(losses) >= 10, f"too few logged losses: {losses}")
    vals = np.asarray([v for _, v in losses])
    check(bool(np.isfinite(vals).all()), f"non-finite loss: {losses}")
    ln_v = math.log(m.vocab_size)
    # a random init's logits are not flat: its loss sits a little above
    # ln V (by about half the logits' variance), never far from it
    check(abs(vals[0] - ln_v) < 1.5,
          f"first loss {vals[0]:.3f} is not near ln(V)={ln_v:.3f}")
    check(vals[-5:].mean() < vals[:5].mean() - 1.0,
          f"loss did not fall: {losses}")
    check(math.isfinite(final["val_loss"]), f"val loss {final['val_loss']}")
    windows = cfg.max_steps // cfg.steps_per_dispatch
    check(final["train_dispatches"] == windows,
          f"{final['train_dispatches']} dispatches for {windows} windows")
    say(f"  loss: {vals[0]:.3f} (ln V = {ln_v:.3f}) -> {vals[-1]:.3f} over "
        f"{cfg.max_steps} steps in {windows} dispatches of "
        f"{cfg.steps_per_dispatch}; val {final['val_loss']:.3f}")

    ckpt = Checkpointer(cfg.rundir, async_save=False)
    check(ckpt.latest_step() == cfg.max_steps - 1,
          f"checkpoint step {ckpt.latest_step()} != {cfg.max_steps - 1}")
    _, saved = ckpt.restore({})  # the JSON metadata alone
    ckpt.close()
    resolved = saved["config"]["model"]
    say(f"  resolved knobs: remat={resolved['remat']} "
        f"scan_unroll={resolved['scan_unroll']} "
        f"attn_impl={resolved['attn_impl']} (see kernels below); "
        f"data gather: {native.gather_backend()}")
    check(resolved["remat"] != "auto" and resolved["scan_unroll"] >= 1,
          f"knobs left unresolved: {resolved}")

    # what attention the train program holds: trace the model's forward
    # at the trained shape and read its kernels
    shapes = jax.eval_shape(lambda k: GPT.init(k, m), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, m.block_size), jnp.int32)
    calls = pallas_calls(lambda mod, tok: mod(tok), shapes, tokens)
    check(bool(calls) and not any(i for _, i in calls),
          f"attn_impl={m.attn_impl!r} did not resolve to a compiled "
          f"kernel: {calls}")
    say(f"  attn_impl={m.attn_impl!r} resolved to kernel(s) "
        f"{sorted({n for n, _ in calls})}, none interpreted")

    # smoke timings from the run's own telemetry: the first window
    # carries the compile
    tl = json.load(open(os.path.join(cfg.rundir, "train_timeline.json")))
    spans = [e["dur"] / 1e6 for e in tl["traceEvents"]
             if e.get("name") == "train_window" and e.get("ph") == "X"]
    if len(spans) >= 2:
        later = sorted(spans[1:])[len(spans[1:]) // 2]
        say(f"  smoke timing: train() {wall:.1f} s; first window "
            f"{spans[0]:.1f} s (holds the compile: ~{spans[0] - later:.1f} "
            f"s), later windows median {later:.2f} s")
    return final


def phase_resume(cfg, final):
    """Restore through the public Checkpointer, as sample.py does."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.checkpoint import Checkpointer
    from midgpt_tpu.config import from_dict
    from midgpt_tpu.models.gpt import GPT, GPT_PARAM_RULES
    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.parallel.sharding import param_shardings
    from midgpt_tpu.train import evaluate, make_eval_step, train

    ckpt = Checkpointer(cfg.rundir, async_save=False)
    _, meta = ckpt.restore({})
    run_cfg = from_dict(meta["config"])  # as train() resolved it
    mesh = create_mesh(run_cfg.mesh)
    abstract = jax.eval_shape(
        lambda k: GPT.init(k, run_cfg.model), jax.random.PRNGKey(0)
    )
    abstract = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, param_shardings(mesh, abstract, GPT_PARAM_RULES),
    )
    items, meta = ckpt.restore({"params": abstract})
    ckpt.close()
    params = items["params"]
    check(meta["step"] == cfg.max_steps - 1,
          f"restored step {meta['step']} != {cfg.max_steps - 1}")
    # a parameter checksum that means something: the restored parameters
    # give the validation loss the trained ones gave, to the bit (same
    # fixed eval batches, same program)
    val_loss = evaluate(
        make_eval_step(run_cfg, mesh), params,
        data_loader(run_cfg, "val", stream=1), mesh,
        run_cfg.eval_batches, 0 if run_cfg.eval_fixed else run_cfg.max_steps,
    )
    l1 = float(sum(jnp.sum(jnp.abs(p.astype(jnp.float32)))
                   for p in jax.tree.leaves(params)))
    check(val_loss == final["val_loss"],
          f"restored parameters give val loss {val_loss!r}, the run ended "
          f"at {final['val_loss']!r}")
    say(f"  restored step {meta['step']}; sum|params| = {l1:.6e}; val loss "
        f"{val_loss!r} == the run's final {final['val_loss']!r}")

    more = dataclasses.replace(cfg, max_steps=cfg.max_steps + 1)
    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        final2 = train(more)
    check(f"resumed from step {cfg.max_steps - 1}" in "".join(tee.kept),
          "train() did not resume from the checkpoint")
    check(final2["train_dispatches"] == 1,
          f"one more step took {final2['train_dispatches']} dispatches")
    check(math.isfinite(final2["val_loss"]), "non-finite loss after resume")
    say(f"  one more step from the checkpoint: step {cfg.max_steps} ran, "
        f"val loss {final2['val_loss']:.3f}")
    return params


# ---------------------------------------------------------------------------
# 5. serve
# ---------------------------------------------------------------------------


def make_prompts(data_dir: str, seed: int):
    import numpy as np

    val = np.fromfile(os.path.join(data_dir, "val.bin"), np.uint16)
    rng = np.random.default_rng(seed)
    shared = val[1000:1000 + SHARED_PREFIX]
    prompts = []
    for i, n in enumerate(PROMPT_LENS):
        start = int(rng.integers(2000, len(val) - 400))
        body = val[start:start + n]
        if n >= 150:  # the long ones open with the shared prefix
            body = np.concatenate([shared, body[SHARED_PREFIX:]])
        prompts.append(body.astype(np.int32))
    return prompts


def serve_requests(engine, prompts, mid_run_from: int = 3):
    """Submit the first few, let the scheduler run, admit the rest
    mid-run, drain. Returns the emitted tokens per prompt."""
    rids = [engine.submit(p, NEW_TOKENS, seed=i)
            for i, p in enumerate(prompts[:mid_run_from])]
    for _ in range(2):
        engine.step()
    rids += [engine.submit(p, NEW_TOKENS, seed=mid_run_from + i)
             for i, p in enumerate(prompts[mid_run_from:])]
    finished = engine.run()
    return [list(finished[r].tokens) for r in rids]


def teacher_forced_gaps(forward, model, prompts, streams, ulps: int, label):
    """Every emitted token against one full-context forward of the same
    weights: the gap of its logit below the row's max, in units of the
    tolerance (a worst ratio <= 1 passes)."""
    import numpy as np

    worst, flips, total = 0.0, 0, 0
    for p, toks in zip(prompts, streams):
        check(len(toks) == NEW_TOKENS,
              f"{label}: a request emitted {len(toks)} of {NEW_TOKENS}")
        full = np.zeros((1, FORWARD_LEN), np.int32)
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        full[0, :len(seq)] = seq
        logits = np.asarray(forward(model, full)[0], np.float32)
        for i, tok in enumerate(toks):
            row = logits[len(p) + i - 1]
            check(bool(np.isfinite(row).all()), f"{label}: non-finite logits")
            tol = ulps * 2.0 ** -8 * float(np.max(np.abs(row)))
            gap = float(row.max() - row[tok])
            worst = max(worst, gap / tol)
            flips += int(gap > 0)
            total += 1
    check(worst <= 1.0,
          f"{label}: an emitted token sits {worst:.2f}x the tolerance "
          f"({ulps} bf16 ulps) below the teacher-forced argmax")
    say(f"  {label}: {total} emitted tokens, {total - flips} are the "
        f"teacher-forced argmax, {flips} within tolerance (worst "
        f"{worst:.2f} of {ulps} bf16 ulps)")


def phase_serve(params, cfg, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.quant import dequantize_model, quantize_model
    from midgpt_tpu.sampling import generate
    from midgpt_tpu.serving import ServingEngine

    model = cast_floating(params, jnp.bfloat16)
    prompts = make_prompts(cfg.data_dir, seed)
    forward = jax.jit(lambda mod, tok: mod(tok))

    engines = (
        ("defaults", {}, model, LOGIT_TOL_ULPS),
        ("speculate=3", {"speculate": 3}, model, LOGIT_TOL_ULPS),
        # int8 code x power-of-two scale is exact in bf16: the same
        # weights the quantized engine serves
        ("int8 weights + int8 KV", {"quant": "int8", "kv_quant": "int8"},
         cast_floating(dequantize_model(quantize_model(model)),
                       jnp.bfloat16), LOGIT_TOL_ULPS_KV8),
    )
    streams_default = None
    for label, kw, reference_model, ulps in engines:
        t0 = time.perf_counter()
        eng = ServingEngine(model, **ENGINE_KW, **kw)
        check(eng.paged_kernel == "pallas",
              f"{label}: paged_kernel='auto' resolved to "
              f"{eng.paged_kernel!r} on a TPU at this geometry")
        streams = serve_requests(eng, prompts)
        st = eng.stats()
        say(f"  engine[{label}]: paged_kernel='auto' -> "
            f"{eng.paged_kernel!r}; {st['tokens_generated']} tokens, "
            f"{st['decode_dispatches']} decode + {st['prefill_dispatches']} "
            f"prefill-chunk + {st['verify_dispatches']} verify dispatches; "
            f"prefix cache saved {st['prefill_tokens_saved']} of "
            f"{st['prompt_tokens_total']} prompt tokens "
            f"(smoke timing {time.perf_counter() - t0:.1f} s)")
        check(st["prefill_tokens_saved"] >= SHARED_PREFIX - 16,
              f"{label}: the shared prefix was not served from the cache")
        check(st["prefill_dispatches"] > len(prompts),
              f"{label}: prefill was not chunked")
        if kw.get("speculate"):
            check(st["verify_dispatches"] > 0,
                  f"{label}: the verify program did not run: {st}")
        else:
            check(st["decode_dispatches"] > 0, f"{label}: no decode window")
        teacher_forced_gaps(forward, reference_model, prompts, streams,
                            ulps, f"engine[{label}]")
        if not kw:
            streams_default = streams
        elif "speculate" in kw:
            same = sum(a == b for a, b in zip(streams, streams_default))
            say(f"  information: speculate=3 streams equal the default "
                f"engine's for {same} of {len(streams)} requests")

    # information only: the CPU contract of the verify skill's canonical
    # drive is engine == exact sampler token for token
    for i in (0, 4):
        want = np.asarray(generate(
            model, jnp.asarray(prompts[i])[None], NEW_TOKENS,
            key=jax.random.PRNGKey(0), temperature=0.0,
        ))[0].tolist()
        got = streams_default[i]
        diff = next((j for j, (a, b) in enumerate(zip(got, want)) if a != b),
                    None)
        verdict = ("equal token for token" if diff is None
                   else f"first differ at token {diff}")
        say(f"  information: engine vs sampling.generate, request {i} "
            f"(prompt {len(prompts[i])}): {verdict}")


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------


def train_curve(cfg, mesh, label: str):
    """A few steps of the train window ``train()`` dispatches, on
    ``mesh``, from ``cfg.seed`` and the loader's first batches. Returns
    the per-step losses and the final state."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.sharding import make_global_array
    from midgpt_tpu.train import get_train_window, init_state, make_optimizer

    tx, _ = make_optimizer(cfg)
    state = init_state(cfg, mesh, tx, jax.random.PRNGKey(cfg.seed))
    loader = data_loader(cfg, "train", stream=0)
    window = get_train_window(cfg, mesh, SHARDED_K)
    spec = P(None, None, ("replica", "fsdp"), "sequence")
    key = jax.random.PRNGKey(cfg.seed)
    losses = []
    t0 = time.perf_counter()
    for _ in range(SHARDED_STEPS // SHARDED_K):
        xs, ys = zip(*(loader.next() for _ in range(SHARDED_K)))
        state, out = window(
            state, make_global_array(np.stack(xs), mesh, spec),
            make_global_array(np.stack(ys), mesh, spec), key,
        )
        losses += np.asarray(out["loss"], np.float32).tolist()
    say(f"  {label}: losses {[round(v, 4) for v in losses]} "
        f"(smoke timing {time.perf_counter() - t0:.1f} s)")
    check(bool(np.isfinite(losses).all()), f"{label}: non-finite loss")
    return losses, state


def bytes_in_use(devices):
    return {d.id: (d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in devices}


def phase_sharded_train(cfg, devices):
    import jax
    import numpy as np

    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.parallel.mesh import create_mesh, device_layout

    mesh_cfg = MeshConfig(replica=1, fsdp=2, sequence=1, tensor=2)
    cfg4 = dataclasses.replace(cfg, mesh=mesh_cfg)
    _, branch = device_layout(mesh_cfg, list(devices[:4]))
    mesh4 = create_mesh(mesh_cfg, devices=devices[:4])
    ids = [d.id for d in mesh4.devices.flat]
    say(f"  mesh {dict(mesh4.shape)} built by {branch}: device ids {ids}")
    check(branch == "create_device_mesh",
          f"the real mesh was laid out by {branch!r}")
    check(len(set(ids)) == 4, f"mesh devices are not four distinct: {ids}")
    losses4, state4 = train_curve(cfg4, mesh4, "fsdp=2 x tensor=2")

    sharded = [p for p in jax.tree.leaves(state4.params)
               if len(p.sharding.device_set) == 4
               and not p.sharding.is_fully_replicated]
    check(bool(sharded), "no parameter is sharded over all four devices")
    big = max(sharded, key=lambda p: p.size)
    say(f"  a sharded parameter: shape {tuple(big.shape)}, spec "
        f"{big.sharding.spec}, on {len(big.sharding.device_set)} devices, "
        f"shard shape {big.addressable_shards[0].data.shape}")
    used = bytes_in_use(devices[:4])
    say(f"  bytes in use per device: {used}")
    check(all(v > 0 for v in used.values()),
          f"a device holds nothing: {used}")
    del state4, sharded, big  # free the chips for the one-device run

    one = MeshConfig(replica=1, fsdp=1, sequence=1, tensor=1)
    cfg1 = dataclasses.replace(cfg, mesh=one)
    mesh1 = create_mesh(one, devices=devices[:1])
    losses1, _ = train_curve(cfg1, mesh1, "one device (devices[:1])")
    gap = float(np.max(np.abs(np.asarray(losses4) - np.asarray(losses1))))
    check(gap <= SHARDED_LOSS_TOL,
          f"loss curves differ by {gap} > {SHARDED_LOSS_TOL}")
    check(losses4[-1] < losses4[0], f"sharded loss did not fall: {losses4}")
    say(f"  loss curves agree: max |difference| {gap:.4f} <= "
        f"{SHARDED_LOSS_TOL}")


def phase_sharded_serve(cfg, devices, seed: int):
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving import ServingCluster, ServingEngine
    from midgpt_tpu.serving.cluster import serving_meshes

    model = cast_floating(
        GPT.init(jax.random.PRNGKey(seed), cfg.model), jnp.bfloat16
    )
    prompts = make_prompts(cfg.data_dir, seed)
    forward = jax.jit(lambda mod, tok: mod(tok))

    single = ServingEngine(model, **ENGINE_KW)
    s_streams = serve_requests(single, prompts)
    say(f"  single-chip engine: paged_kernel -> {single.paged_kernel!r}, "
        f"{single.stats()['tokens_generated']} tokens")
    teacher_forced_gaps(forward, model, prompts, s_streams, LOGIT_TOL_ULPS,
                        "single-chip engine")

    meshes = serving_meshes(tp_size=2, dp_replicas=2, devices=devices[:4])
    cluster = ServingCluster(model, meshes=meshes, **ENGINE_KW)
    c_streams = serve_requests(cluster, prompts)
    st = cluster.stats()
    pools = []
    for i, eng in enumerate(cluster.engines):
        devs = sorted(d.id for d in eng.pool.k.sharding.device_set)
        pools.append(devs)
        say(f"  replica {i}: tp={eng.tp} paged_kernel -> "
            f"{eng.paged_kernel!r}; pool committed to devices {devs}")
    check(pools[0] != pools[1] and not set(pools[0]) & set(pools[1]),
          f"replica pools share devices: {pools}")
    check(devices[0].id not in pools[1],
          f"replica 1's pool sits on the first device: {pools}")
    check(len(set(pools[0]) | set(pools[1])) == 4,
          f"the cluster does not span four devices: {pools}")
    per_replica = [e.stats()["tokens_generated"] for e in cluster.engines]
    check(all(n > 0 for n in per_replica),
          f"a replica served nothing: {per_replica}")
    used = bytes_in_use(devices[:4])
    say(f"  tokens per replica {per_replica}; bytes in use per device {used}")
    check(all(v > 0 for v in used.values()), f"a device holds nothing: {used}")
    teacher_forced_gaps(forward, model, prompts, c_streams, LOGIT_TOL_ULPS,
                        "tp=2 x dp=2 cluster")
    same = sum(a == b for a, b in zip(c_streams, s_streams))
    say(f"  information: cluster streams equal the single-chip engine's "
        f"for {same} of {len(prompts)} requests (tp reassociates bf16 sums; "
        f"{st['tokens_generated']} tokens)")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--workdir", default=os.path.join(REPO, "chip_smoke_out"),
        help="corpus, run directory and checkpoint land here",
    )
    ap.add_argument(
        "--only", default="",
        help="one chip: after phase 1, only the phases numbered here "
             "(2, 6, 7 and 8 stand alone; 4 needs 3, 5 needs 4), e.g. 2,6",
    )
    args = ap.parse_args()
    only = {int(n) for n in args.only.split(",") if n}
    want = lambda n: not only or n in only  # noqa: E731
    t_start = time.perf_counter()
    try:
        with phase("1 device"):
            devices = phase_device(args.chips)
        # the smoke's own output of an earlier run: a stale checkpoint
        # there would turn "train" into "resume"
        shutil.rmtree(os.path.join(args.workdir, "run"), ignore_errors=True)
        os.makedirs(args.workdir, exist_ok=True)
        write_corpus(os.path.join(args.workdir, "data"))
        if args.chips == 1:
            cfg = smoke_config(args.workdir, args.seed, TRAIN_SET)
            if want(2):
                with phase("2 kernels"):
                    phase_kernels(args.seed)
            if want(3):
                with phase("3 train"):
                    final = phase_train(cfg)
            if want(4):
                with phase("4 resume"):
                    params = phase_resume(cfg, final)
            if want(5):
                with phase("5 serve"):
                    phase_serve(params, cfg, args.seed)
            if want(6):
                with phase("6 block-diffusion forward"):
                    block_forward_vs_gather(args.seed)
            if want(7):
                with phase("7 gated delta rule"):
                    gated_delta_vs_numpy(args.seed)
            if want(8):
                with phase("8 latent attention"):
                    latent_attention_vs_numpy(args.seed)
        else:
            cfg = smoke_config(args.workdir, args.seed, SHARDED_SET)
            with phase("sharded training: fsdp=2 x tensor=2 vs one device"):
                phase_sharded_train(cfg, devices)
            with phase("sharded serving: tp=2 x dp_replicas=2 vs one chip"):
                phase_sharded_serve(cfg, devices, args.seed)
    except PhaseFailed as e:
        say(f"FAILED: {e}")
        return 1
    import jax

    say(f"smoke timing: whole run {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
