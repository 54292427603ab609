"""KV-cached autoregressive generation (fixed batch).

Replaces the reference sampler's pad-to-block_size full re-forward per token
(/root/reference/sample.py:68-95) with prefill + incremental decode under
``lax.scan`` — one compiled program, O(T) per token, static shapes.
Capability parity: temperature-scaled categorical sampling; adds greedy
(temperature=0) and top-k.

This module is the FIXED-BATCH path (one contiguous cache sized for the
batch, all requests start and stop together): the plain, exact-parity
oracle the serving tests compare against, not a serving path. Under real
traffic — requests arriving and finishing independently — route through
``midgpt_tpu.serving`` instead: paged KV pool, continuous batching, and K
decode steps fused per XLA dispatch (``serving.generate_served`` is the
drop-in batch entry point; ``sample.py --serve`` uses it)."""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import GPT, KVCache, decode_step, prefill

Array = jax.Array


def _scaled_masked(
    logits: Array, temperature: float, top_k: tp.Optional[int]
) -> Array:
    """Temperature-scale and top-k-mask ``logits`` — the pre-sampling
    arithmetic SHARED by :func:`sample_token` (which feeds the result to
    a key-derived categorical) and :func:`target_probs` (which softmaxes
    it into the acceptance distribution of the sampled verify program).
    One body on purpose: the choreo prover compares the two call sites
    op for op, so the tempering/masking arithmetic must literally be the
    same code, not two copies that could drift."""
    logits = logits / temperature
    if top_k is not None:
        assert top_k > 0, f"top_k must be positive, got {top_k}"
        top_k = min(top_k, logits.shape[-1])  # clamp to vocab
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return logits


def sample_token(
    logits: Array, key: Array, temperature: float, top_k: tp.Optional[int]
) -> Array:
    """One sampling decision: greedy argmax at ``temperature == 0``,
    temperature-scaled (optionally top-k-filtered) categorical otherwise.
    Shared by the fixed-batch sampler below and the serving engine's
    decode window AND verify program: at ``temperature == 0`` the verify
    program's acceptance check is this function's argmax branch applied
    per candidate row (greedy speculation is exactly greedy-equivalent);
    at ``temperature > 0`` the verify program's row-0 draw is this very
    function under the same (seed, token-index) derived key, and its
    rejection-sampling acceptance threshold is :func:`target_probs` —
    the softmax of the SAME tempered/masked logits this function draws
    from.

    Under a tensor-parallel serving mesh ``logits`` arrives
    VOCAB-SHARDED: the greedy branch partitions cleanly (per-shard
    argmax + a [B, tp]-sized combiner gather — the only thing that ever
    crosses chips is one (value, index) pair per shard, never the row).
    The temperature branch's top-k sort and categorical draw may gather
    the row per slot — correct, but the gathered-row-free contract is
    greedy-only (the sharded-serving audits gate the greedy programs)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, _scaled_masked(logits, temperature, top_k), axis=-1
    ).astype(jnp.int32)


def derive_request_key(key: Array, seed: Array, token_index: Array) -> Array:
    """The per-request, per-position sampling key:
    ``fold_in(fold_in(key, request_seed), token_index)``. This is the
    serving determinism contract in one place — a request's stream
    position ``i`` is always drawn with this key, whether the draw
    happens in a decode window step, as the sampled verify program's
    row 0, or as the residual resample after a rejected draft (the
    verify program carries the residual as logits, so the NEXT
    dispatch's row-0 draw at this very key IS the residual draw). Keys
    are a function of (request seed, stream position) only — never slot
    index, window size, batch composition, or chunking — which is what
    makes sampled streams bitwise scheduling-invariant."""
    return jax.random.fold_in(jax.random.fold_in(key, seed), token_index)


# Salt folded into a position's derived key to produce the ACCEPTANCE
# uniform for that position (speculative rejection sampling). A salted
# substream, not the categorical stream itself: position i's categorical
# key must stay untouched so a rejection at i resamples with exactly the
# key the non-speculative engine would have used there.
SPEC_ACCEPT_SALT = 0x5BEC


def target_probs(
    logits: Array, temperature: float, top_k: tp.Optional[int]
) -> Array:
    """The model's sampling distribution as probabilities, in f32:
    ``softmax(_scaled_masked(logits))``. This is BY CONSTRUCTION the
    distribution :func:`sample_token` draws from at the same
    ``(temperature, top_k)`` — the verify program's acceptance test
    ``u * q(t) <= p(t)`` and residual ``max(p - q, 0)`` use it, so
    accepted drafts are distributed exactly like decode-window draws
    (standard speculative-sampling exactness). f32 throughout: the
    acceptance compare is the new near-tie surface (the same bug class
    the PR 4/5 dtype drifts hit), and the choreo prover pins it."""
    return jax.nn.softmax(
        _scaled_masked(logits.astype(jnp.float32), temperature, top_k),
        axis=-1,
    )


def acceptance_mask(u: Array, q_sel: Array, p_sel: Array) -> Array:
    """Rejection-sampling acceptance: accept a drafted token ``t`` iff
    ``u * q(t) <= p(t)`` — the multiplied form of ``u <= p(t)/q(t)``
    (no division, so a zero draft probability cannot produce inf/nan;
    ``q(t) = 0`` accepts always, which is the correct limit: the draft
    distribution then carries no mass to reject against). For one-hot
    n-gram drafts ``q(t) = 1`` and this degenerates to ``u <= p(t)``.

    A named module-level seam on purpose: the acceptance compare is
    where a dtype drift would silently skew the sampled distribution
    (bf16 rounds p near ulp boundaries), so the choreo prover proves its
    operands are f32 and the fault-injection test monkeypatches THIS
    function with a drifted-dtype variant to prove exactly that clause
    fails."""
    return (u * q_sel) <= p_sel


def token_confidence(logits: Array) -> tp.Tuple[Array, Array]:
    """Greedy pick and its confidence for every row of ``logits`` [..., V]:
    ``x = argmax`` and ``c = softmax(logits)[x]``, in f32 — the
    block-diffusion reveal rule's two inputs (temperature 0). The
    confidence is ``1 / sum(exp(l - max l))``: the pick's own exponent is
    ``exp(0)``."""
    lg = logits.astype(jnp.float32)
    top = jnp.max(lg, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(lg - top), axis=-1)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), conf


def reveal_most_confident(conf: Array, masked: Array, n: int) -> Array:
    """``low_confidence_static`` selection: a bool mask over the last axis
    choosing the ``n`` still-``masked`` positions of highest ``conf``
    (all of them where fewer are masked; ties go to the lower index, as
    ``lax.top_k`` orders them). Revealed positions are never chosen."""
    n = min(n, conf.shape[-1])
    ranked = jnp.where(masked, conf, -1.0)  # a confidence is > 0
    _, idx = jax.lax.top_k(ranked, n)  # [..., n]
    chosen = jnp.any(
        jax.nn.one_hot(idx, conf.shape[-1], dtype=jnp.bool_), axis=-2
    )
    return chosen & masked


def residual_logits(
    p: Array, q: Array, temperature: float
) -> tp.Tuple[Array, Array]:
    """Logits whose :func:`sample_token` draw IS the rejection-sampling
    residual draw: ``temperature * log(normalize(max(p - q, 0)))``, plus
    the residual mass ``sum(max(p - q, 0))`` (callers fall back to the
    raw logits row when the mass is 0 — a float-exactness corner where
    ``p <= q`` everywhere, meaning the acceptance test could not have
    rejected except at an exact boundary).

    Why this shape: the verify program does not draw the resample token
    in-dispatch (the rejected row's K/V was computed for the DRAFT
    token, so an in-dispatch resample would need pending-token replumb
    of the pool write path). Instead it CARRIES these logits out, and
    the next dispatch's ordinary row-0 ``sample_token`` at the position's
    derived key performs the draw: the temperature division cancels the
    ``temperature *`` here, top-k masking is a no-op on a <= top_k
    support vector (the kth-largest of a shorter-support row sorts to
    -inf, and nothing compares below -inf), and the categorical's
    gumbel-argmax is shift-invariant — so the draw is exactly
    ``categorical(residual)`` with zero special cases in the sampler.
    (Exact float ties inside ``_scaled_masked``'s kth threshold can
    widen p's support past top_k; the carried draw then re-applies
    top-k on the residual — a measure-zero corner that keeps streams
    deterministic either way.)"""
    resid = jnp.maximum(p - q, 0.0)
    mass = jnp.sum(resid, axis=-1)
    denom = jnp.where(mass > 0.0, mass, 1.0)[..., None]
    norm = jnp.where(resid > 0.0, resid / denom, 1.0)
    out = jnp.where(
        resid > 0.0, temperature * jnp.log(norm), -jnp.inf
    )
    return out, mass


def generate(
    model: GPT,
    prompt: Array,  # [B, P] int32
    max_new_tokens: int,
    *,
    key: Array,
    temperature: float = 1.0,
    top_k: tp.Optional[int] = None,
    cache_dtype=jnp.bfloat16,
) -> Array:
    """Returns [B, max_new_tokens] sampled continuations (parity:
    sample.py:68-95 generate, temperature semantics sample.py:88-92).

    Up to ``block_size`` total tokens, decoding is KV-cached (O(W)/token vs
    the reference's full re-forward per token): ``prefill`` + one scan of
    ``decode_step``. Past ``block_size`` the window must slide (sample.py:74
    ``idx[:, -block_size:]``): the cropped-window full forward is re-run
    per token — bit-parity with the reference, which *recomputes the hidden
    states of past tokens under the shrunken context* each step, at the
    same O(W * fwd)/token cost the reference always pays."""
    b, p = prompt.shape
    cfg = model.config
    if p > cfg.block_size:
        # reference conditions on the last block_size tokens (sample.py:74)
        prompt = prompt[:, -cfg.block_size :]
        p = cfg.block_size
    total = p + max_new_tokens
    w = min(total, cfg.block_size)  # sliding-window size (semantics)
    cache = KVCache.init(cfg, b, w, dtype=cache_dtype)
    logits, cache = prefill(model, prompt, cache)

    def body(carry, pos):
        logits, cache, k = carry
        k, sub = jax.random.split(k)
        tok = sample_token(logits, sub, temperature, top_k)
        new_logits, cache = decode_step(model, tok, pos, cache)
        return (new_logits, cache, k), tok

    # the w - p tokens decodable before the window would slide
    (logits, _, key), toks1 = jax.lax.scan(
        body, (logits, cache, key), jnp.arange(p, w, dtype=jnp.int32)
    )
    toks1 = jnp.transpose(toks1)  # [B, w - p]
    if total <= w:
        return toks1

    # exact sliding: re-run the cropped-window full forward per token
    n2 = total - w
    window = jnp.concatenate([prompt, toks1], axis=1)  # [B, W]
    # single-chip full forward: ring needs a live mesh and an explicit
    # 'flash' may not divide W — same impl fallback prefill uses
    # (models/gpt.py prefill)
    impl = "auto" if cfg.attn_impl in ("ring", "ulysses", "flash", "fused") else cfg.attn_impl

    def body2(carry, _):
        logits, window, k = carry
        k, sub = jax.random.split(k)
        tok = sample_token(logits, sub, temperature, top_k)
        window = jnp.concatenate([window[:, 1:], tok[:, None]], axis=1)
        new_logits = model(window, attn_impl=impl)[:, -1, :]
        return (new_logits, window, k), tok

    (_, _, _), toks2 = jax.lax.scan(
        body2, (logits, window, key), None, length=n2
    )
    return jnp.concatenate([toks1, jnp.transpose(toks2)], axis=1)


def make_sampler(
    max_new_tokens: int,
    *,
    mesh=None,
    temperature: float = 1.0,
    top_k: tp.Optional[int] = None,
    cache_dtype=jnp.bfloat16,
):
    """A jitted ``(model, prompt, key) -> tokens`` sampler.

    With ``mesh``, generation runs under the mesh's axis rules: restored
    params keep their TP/FSDP shardings and GSPMD distributes the decode
    matmuls + KV cache — the multi-chip serving path for models too big
    for one chip (absent from the reference, whose sampler is strictly
    single-process full-replication, sample.py:177-182)."""
    from midgpt_tpu.parallel.sharding import axis_rules

    def fn(model: GPT, prompt: Array, key: Array) -> Array:
        with axis_rules(mesh):  # axis_rules(None) is an explicit no-op scope
            return generate(
                model,
                prompt,
                max_new_tokens,
                key=key,
                temperature=temperature,
                top_k=top_k,
                cache_dtype=cache_dtype,
            )

    return jax.jit(fn)
