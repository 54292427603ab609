"""Serving bench: prefill + KV-cached decode throughput at the 124M shape.

Measures on the real chip (random-init weights — throughput only):
  prefill_tok_s        tokens/s through prefill (B=8, P=512)
  decode_tok_s         KV-cached in-window decode tokens/s (256 steps)
  decode_ms_per_tok    per-token latency of the same
  slide_kv_tok_s       past-window decode, ring-buffer KV mode
  slide_exact_tok_s    past-window decode, reference-parity recompute mode

The KV-cached decode path is a flagship redesign claim (the reference
re-runs the full forward per token, /root/reference/sample.py:68-95);
these are its numbers (VERDICT r2 Next #5). Writes
artifacts/bench_decode.json and prints one JSON line.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp


def _sync(out):
    return int(jnp.sum(jax.tree.leaves(out)[0]))


def _timed(fn, *args, n=4):
    """Chained-delta timing: a forced host read is the hard sync, and
    the (1 call) vs (n calls) delta cancels its cost."""
    _sync(fn(*args))  # compile + hard sync
    t0 = time.perf_counter()
    _sync(fn(*args))
    t1 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    t2 = time.perf_counter()
    return max(1e-9, ((t2 - t1) - (t1 - t0)) / (n - 1))


def measure_decode(include_sliding: bool = False) -> dict:
    """Prefill + KV-decode throughput keys (``decode_*``) at the 124M
    shape; with ``include_sliding`` also the past-window modes (two extra
    heavy compiles — the standalone script runs them, bench.py doesn't)."""
    from midgpt_tpu.config import get_config
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.sampling import make_sampler

    cfg = get_config("openwebtext").model
    cfg = dataclasses.replace(cfg, attn_impl="auto")
    model = cast_floating(GPT.init(jax.random.PRNGKey(0), cfg), jnp.bfloat16)

    b, p = 8, 512
    key = jax.random.PRNGKey(1)
    prompt = jax.random.randint(key, (b, p), 0, cfg.vocab_size)

    # prefill timed on its FULL output (logits AND cache): returning only
    # logits lets XLA dead-code the ~150 MB of KV-cache writes, and a
    # max_new_tokens=0 sampler loses the whole forward (measured 6M "tok/s")
    from midgpt_tpu.models.gpt import KVCache, prefill

    cache = KVCache.init(cfg, b, p, dtype=jnp.bfloat16)
    # jit outputs are fully materialized regardless of which leaf the host
    # reads, so timing jit(prefill) on its full (logits, cache) output
    # through the shared _timed helper is sufficient
    t_prefill = _timed(jax.jit(prefill), model, prompt, cache)
    # decode rate = delta between two samplers (prefill cost cancels)
    n_dec = 256
    t_one = _timed(make_sampler(1, temperature=1.0), model, prompt, key)
    t_full = _timed(make_sampler(1 + n_dec, temperature=1.0), model, prompt, key)
    dec_per_tok = max(1e-9, (t_full - t_one) / n_dec)

    # HBM roofline for one decode step (all B tokens): stream every param
    # once (batched matvecs amortize over B) + stream the live KV slots of
    # all layers once (scores read K, value-sum reads V — both touched).
    # Measured rd+wr bandwidth on this chip class ~820 GB/s (PERF.md r5
    # probe); use 800 as the denominator so the floor is conservative.
    from midgpt_tpu.models.gpt import count_params

    param_bytes = count_params(model) * 2  # bf16 stream
    # in-window phase averages W/2 live slots; use the mean over the
    # measured 256-step window starting at p
    live_slots = min(p + n_dec / 2, cfg.block_size)
    kv_bytes = (
        cfg.n_layer * b * cfg.kv_heads * live_slots * cfg.head_dim * 2 * 2
    )
    floor_ms = (param_bytes + kv_bytes) / 800e9 * 1e3
    record = {
        "decode_shape": "124M B=8 T=1024 bf16",
        "decode_prefill_tok_s": round(b * p / t_prefill, 1),
        "decode_tok_s": round(b / dec_per_tok, 1),
        "decode_ms_per_tok": round(dec_per_tok * 1e3, 3),
        "decode_hbm_floor_ms": round(floor_ms, 3),
        "decode_vs_floor": round(dec_per_tok * 1e3 / floor_ms, 2),
    }
    if include_sliding:
        # past-window sliding: full-window prompt; per-token rate from the
        # mode-matched delta between 1-step and (1+n)-step samplers (same
        # pattern as the in-window block — the baseline's one step and the
        # prefill cost cancel exactly)
        n_slide = 64
        prompt_w = jax.random.randint(
            key, (b, cfg.block_size), 0, cfg.vocab_size
        )
        per_tok = {}
        for mode in ("kv", "exact"):
            t_one = _timed(
                make_sampler(1, sliding=mode), model, prompt_w, key
            )
            t_many = _timed(
                make_sampler(1 + n_slide, sliding=mode), model, prompt_w, key
            )
            per_tok[mode] = max(1e-9, (t_many - t_one) / n_slide)
        kv_per_tok, exact_per_tok = per_tok["kv"], per_tok["exact"]
        record.update(
            {
                "slide_kv_tok_s": round(b / kv_per_tok, 1),
                "slide_exact_tok_s": round(b / exact_per_tok, 1),
                "slide_speedup_kv_vs_exact": round(exact_per_tok / kv_per_tok, 1),
            }
        )
    return record


def main() -> None:
    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    record = {"device": jax.devices()[0].device_kind}
    record.update(measure_decode(include_sliding=True))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outdir = os.path.join(repo, "artifacts")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "bench_decode.json"), "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
