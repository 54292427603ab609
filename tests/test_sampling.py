"""KV-cache decode parity with the full forward, and generation sanity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT, KVCache, decode_step, prefill
from midgpt_tpu.sampling import generate

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)


@pytest.mark.parametrize(
    "kv_heads,cache_dtype,atol",
    [
        (None, jnp.float32, 2e-4),
        (2, jnp.float32, 2e-4),  # GQA (llama-family serving shape)
        # a bf16 cache rounds every stored K/V row (2^-9 relative): the
        # f32 forward is still the reference, at bf16's tolerance
        (None, jnp.bfloat16, 2e-2),
        (2, jnp.bfloat16, 2e-2),
    ],
)
def test_decode_matches_full_forward(kv_heads, cache_dtype, atol):
    """Stepping token-by-token through the cache must reproduce the full
    batched forward's last-position logits at every position."""
    cfg = dataclasses.replace(CFG, n_kv_head=kv_heads)  # None = MHA default
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)

    full_logits = model(tokens)  # [B, T, V]

    cache = KVCache.init(cfg, batch=2, max_len=16, dtype=cache_dtype)
    for t in range(16):
        logits_t, cache = decode_step(
            model, tokens[:, t], jnp.asarray(t, jnp.int32), cache
        )
        np.testing.assert_allclose(
            np.asarray(logits_t),
            np.asarray(full_logits[:, t, :]),
            atol=atol,
            err_msg=f"position {t}",
        )


def test_prefill_matches_stepwise():
    model = GPT.init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, CFG.vocab_size)
    cache = KVCache.init(CFG, batch=2, max_len=12, dtype=jnp.float32)
    logits, cache2 = prefill(model, tokens, cache)
    full = model(tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, -1, :]), atol=2e-4
    )
    # caches populated only up to the prompt length (time-minor layout)
    assert not np.allclose(np.asarray(cache2.k[..., :8]), 0)
    np.testing.assert_array_equal(np.asarray(cache2.k[..., 8:]), 0)


def test_generate_shapes_and_determinism():
    model = GPT.init(jax.random.PRNGKey(0), CFG)
    prompt = jnp.zeros((3, 4), dtype=jnp.int32)
    out1 = generate(
        model, prompt, 8, key=jax.random.PRNGKey(5), temperature=1.0,
        cache_dtype=jnp.float32,
    )
    assert out1.shape == (3, 8)
    assert (np.asarray(out1) >= 0).all() and (np.asarray(out1) < CFG.vocab_size).all()
    out2 = generate(
        model, prompt, 8, key=jax.random.PRNGKey(5), temperature=1.0,
        cache_dtype=jnp.float32,
    )
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize(
    "block_size,n_new",
    [
        (64, 6),  # all cached
        (10, 6),  # p + new == block_size: the cached scan's last slot
        (10, 8),  # two tokens past it: the seam into the exact re-forward
    ],
)
def test_generate_greedy_matches_argmax_rollout(block_size, n_new):
    cfg = dataclasses.replace(CFG, block_size=block_size)
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 4), 0, cfg.vocab_size)
    out = generate(
        model, prompt, n_new, key=jax.random.PRNGKey(0), temperature=0.0,
        cache_dtype=jnp.float32,
    )
    # manual greedy rollout with full forwards of the last block_size tokens
    seq = np.asarray(prompt)
    for _ in range(n_new):
        logits = model(jnp.asarray(seq[:, -block_size:]))
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        seq = np.concatenate([seq, [[nxt]]], axis=1)
    np.testing.assert_array_equal(np.asarray(out[0]), seq[0, 4:])


def test_generate_default_cache_dtype_with_f32_model():
    """Regression: bf16 cache + float32 params must not crash (decode casts
    K/V into the cache dtype)."""
    model = GPT.init(jax.random.PRNGKey(0), CFG)
    prompt = jnp.zeros((1, 4), dtype=jnp.int32)
    out = generate(model, prompt, 4, key=jax.random.PRNGKey(0))
    assert out.shape == (1, 4)


def test_generate_gqa_variant():
    cfg = dataclasses.replace(CFG, n_kv_head=2)
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((2, 3), dtype=jnp.int32)
    out = generate(
        model, prompt, 5, key=jax.random.PRNGKey(1), cache_dtype=jnp.float32
    )
    assert out.shape == (2, 5)


def test_sharded_sampler_matches_unsharded(mesh8):
    """make_sampler under the 8-device mesh (TP-sharded params + cache)
    must reproduce single-device greedy generation exactly."""
    from jax.sharding import NamedSharding

    from midgpt_tpu.models.gpt import GPT_PARAM_RULES
    from midgpt_tpu.parallel.sharding import param_shardings
    from midgpt_tpu.sampling import make_sampler

    model = GPT.init(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab_size)
    key = jax.random.PRNGKey(2)

    ref = generate(
        model, prompt, 12, key=key, temperature=0.0, cache_dtype=jnp.float32
    )

    shardings = param_shardings(mesh8, model, GPT_PARAM_RULES)
    sharded_model = jax.tree.map(jax.device_put, model, shardings)
    sampler = make_sampler(
        12, mesh=mesh8, temperature=0.0, cache_dtype=jnp.float32
    )
    out = sampler(sharded_model, prompt, key)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_batched_prefill_matches_stepwise_oracle():
    """One-pass prefill (batched forward collecting K/V from the block
    scan) vs the token-by-token decode_step oracle: same cache contents
    and same next-token logits."""
    from midgpt_tpu.models.gpt import prefill_stepwise

    model = GPT.init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, CFG.vocab_size)

    cache_a = KVCache.init(CFG, batch=2, max_len=24, dtype=jnp.float32)
    logits_a, cache_a = prefill(model, tokens, cache_a)
    cache_b = KVCache.init(CFG, batch=2, max_len=24, dtype=jnp.float32)
    logits_b, cache_b = prefill_stepwise(model, tokens, cache_b)

    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(cache_a.k), np.asarray(cache_b.k), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(cache_a.v), np.asarray(cache_b.v), atol=2e-5
    )


def test_generate_flash_configured_unaligned_prompt(pallas_interpret):
    """attn_impl='flash' models must still sample with prompts that don't
    divide the kernel block size (prefill remaps to the auto dispatch)."""
    cfg = dataclasses.replace(CFG, attn_impl="flash")
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 13), 0, cfg.vocab_size)
    toks = generate(
        model, prompt, 4, key=jax.random.PRNGKey(2), temperature=0.0,
        cache_dtype=jnp.float32,
    )
    assert toks.shape == (1, 4)


@pytest.mark.slow  # >20 s (24 unjitted oracle forwards, one compile per
# growing crop shape) — moved off tier-1 per conftest's >20 s convention;
# CI home: hlo-audit's slow-tier step
def test_generate_past_block_size_matches_sliding_window_oracle():
    """Generation beyond block_size: the ring-buffer cache must reproduce
    the reference's sliding-window conditioning (sample.py:74
    ``idx[:, -block_size:]`` + full forward per token) token for token.
    Greedy decoding so any divergence is a hard mismatch."""
    cfg = dataclasses.replace(CFG, block_size=16)
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, cfg.vocab_size)
    n_new = 24  # 5 + 24 = 29 >> block_size 16

    toks = generate(
        model, prompt, n_new, key=jax.random.PRNGKey(4),
        temperature=0.0, cache_dtype=jnp.float32,
    )

    # reference-style oracle: crop to the last block_size tokens, full
    # forward, pluck the last real position, greedy argmax
    idx = np.asarray(prompt)
    for _ in range(n_new):
        idx_cond = idx[:, -cfg.block_size:]
        logits = np.asarray(model(jnp.asarray(idx_cond)))
        nxt = logits[:, idx_cond.shape[1] - 1, :].argmax(-1)
        idx = np.concatenate([idx, nxt[:, None].astype(idx.dtype)], axis=1)
    oracle = idx[:, 5:]

    np.testing.assert_array_equal(np.asarray(toks), oracle)


def test_generate_long_prompt_cropped_like_reference():
    """A prompt longer than block_size conditions on its last block_size
    tokens (sample.py:74)."""
    cfg = dataclasses.replace(CFG, block_size=16)
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    long_prompt = jax.random.randint(
        jax.random.PRNGKey(5), (1, 23), 0, cfg.vocab_size
    )
    t1 = generate(
        model, long_prompt, 4, key=jax.random.PRNGKey(6),
        temperature=0.0, cache_dtype=jnp.float32,
    )
    t2 = generate(
        model, long_prompt[:, -16:], 4, key=jax.random.PRNGKey(6),
        temperature=0.0, cache_dtype=jnp.float32,
    )
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
