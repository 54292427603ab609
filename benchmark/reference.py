"""The plain reference: midGPT's block, loss and optimizer in straightforward
``jax.numpy`` and float32, independent of ``midgpt_tpu``. It imports nothing
of the program and takes nothing the program made: its weights come from
:mod:`benchmark.weights` and the seed.

Published description (AllanYangZhou/midGPT ``src/model.py``, ``src/train.py``):
pre-norm residual blocks with weightless RMSNorm (eps 1e-6; final norm 1e-5),
a fused QKV projection, per-head LayerNorm of q and k (scale, no bias, eps
1e-6), interleaved (GPT-J) RoPE at base 10000, causal softmax attention with
the 1/sqrt(C) scale inside the softmax argument, tanh-GELU MLP of 4 D, untied
head, logits and cross-entropy in float32, mean over tokens. Optimizer: clip
by global norm, Adam with bias correction, weight decay of wd/lr on every
leaf, warm-up then cosine schedule, times -1.

Matrix products run at ``Precision.HIGHEST``: on a TPU a float32 product is
otherwise rounded to bfloat16 passes. ``quant`` rounds both operands of every
matrix product first; it is how the controls compute in a lower precision.

Memory: rows are processed ``rows`` at a time inside each layer
(``lax.map`` over row blocks under ``jax.checkpoint``), so the scores and the
logits of one block are all that is live beside the parameters; weight
gradients accumulate in the transposed map's carry.
"""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Quant = tp.Optional[str]


def _round_to(x, quant: Quant):
    """Per-tensor scaled rounding of a matrix-product operand; its
    cotangent is rounded the same way, as a backward product in that
    precision would have it."""
    if quant is None:
        return x

    @jax.custom_vjp
    def rounded(v):
        return _round_value(v, quant)

    rounded.defvjp(lambda v: (_round_value(v, quant), None),
                   lambda _, g: (_round_value(g, quant),))
    return rounded(x)


def _round_value(x, quant: str):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if quant == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if quant == "int8":
        s = amax / 127.0
        return jnp.round(x / s) * s
    if quant == "int4":
        s = amax / 7.0
        return jnp.round(x / s) * s
    raise ValueError(f"unknown rounding {quant!r}")


def _mm(a, b, quant: Quant):
    return jnp.matmul(_round_to(a, quant), _round_to(b, quant), precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer_norm(x, w, eps=1e-6):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(var + eps) * w


def _rope(x, base):
    """x: [..., T, C]; pairs (2i, 2i+1) rotate by pos * base**(-2i/C)."""
    t, c = x.shape[-2], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, c, 2, dtype=jnp.float32) / c))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)
    x1, x2 = x[..., ::2], x[..., 1::2]
    rot = jnp.stack((-x2, x1), axis=-1).reshape(x.shape)
    return x * cos + rot * sin


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def _block(h, lw, n_head, base, quant):
    """One residual block on rows ``h`` [R, T, D]; ``lw``: this layer's
    leaves in float32."""
    r, t, d = h.shape
    c = d // n_head
    qkv = _mm(_rms(h, 1e-6), lw["wqkv"], quant)
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(r, t, n_head, c)
               for i in range(3))
    q = _layer_norm(q, lw["q_norm"])
    k = _layer_norm(k, lw["k_norm"])
    q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
    q, k = _rope(q, base), _rope(k, base)
    s = jnp.einsum("rhqc,rhkc->rhqk", _round_to(q, quant),
                   _round_to(k, quant), precision=HI)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s / math.sqrt(c), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqk,rhkc->rhqc", _round_to(p, quant),
                   _round_to(v, quant), precision=HI)
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(r, t, d)
    h = h + _mm(o, lw["wo"], quant)
    u = _gelu(_mm(_rms(h, 1e-6), lw["w_up"], quant))
    return h + _mm(u, lw["w_down"], quant)


_LAYER_LEAVES = ("wqkv", "wo", "q_norm", "k_norm", "w_up", "w_down")


def _by_rows(fn, x, rows):
    """``fn`` over blocks of ``rows`` rows of ``x`` [B, ...], one at a time."""
    b = x.shape[0]
    rows = min(rows, b)
    assert b % rows == 0, (b, rows)
    xb = x.reshape(b // rows, rows, *x.shape[1:])
    out = jax.lax.map(jax.checkpoint(fn), xb)
    return out.reshape(b, *out.shape[2:])


def hidden(w, tokens, sizes, *, quant: Quant = None, rows: int = 2):
    """Final hidden states before the last norm, [B, T, D] float32."""
    base = float(sizes.get("rope_base", 10000.0))
    h = jnp.take(w["wte"].astype(jnp.float32), tokens, axis=0)

    @jax.checkpoint
    def layer(h, lw):
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        fn = lambda hb: _block(hb, lw, sizes["n_head"], base, quant)  # noqa: E731
        return _by_rows(fn, h, rows), None

    h, _ = jax.lax.scan(layer, h, {k: w[k] for k in _LAYER_LEAVES})
    return h


def logits_of(w, h, quant: Quant = None):
    return _mm(_rms(h, 1e-5), w["lm_head"].astype(jnp.float32), quant)


def loss(w, x, y, sizes, *, quant: Quant = None, rows: int = 2, keep=None):
    """Mean cross-entropy over the tokens of rows ``[:keep]`` (all rows
    when ``keep`` is None: the fault of a batch half left out sets it)."""
    if keep is not None:
        x, y = x[:keep], y[:keep]
    h = hidden(w, x, sizes, quant=quant, rows=rows)
    head = w["lm_head"].astype(jnp.float32)

    def block_loss(hy):
        hb, yb = hy
        z = _mm(_rms(hb, 1e-5), head, quant)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        tgt = jnp.take_along_axis(z, yb[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt, axis=-1)

    b = x.shape[0]
    r = min(rows, b)
    hb = h.reshape(b // r, r, *h.shape[1:])
    yb = y.reshape(b // r, r, y.shape[1])
    per_row = jax.lax.map(jax.checkpoint(block_loss), (hb, yb))
    return jnp.sum(per_row) / (x.shape[0] * x.shape[1])


# ---------------------------------------------------------------------------
# The optimizer, as midGPT's train.py chains it
# ---------------------------------------------------------------------------


def learning_rate(step, hp):
    """0 -> lr over ``warmup_steps``, cosine to ``min_lr`` at
    ``lr_decay_steps`` (optax.warmup_cosine_decay_schedule)."""
    step = jnp.asarray(step, jnp.float32)
    peak, end = hp["learning_rate"], hp["min_lr"]
    warm, total = hp["warmup_steps"], hp["lr_decay_steps"]
    up = peak * step / warm
    frac = jnp.clip((step - warm) / max(1, total - warm), 0.0, 1.0)
    down = end + (peak - end) * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < warm, up, down)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in tree.values()))


def adam_update(w, mu, nu, grads, step, hp):
    """One optimizer update of float32 leaves; ``step`` counts from 0."""
    b1, b2, eps = hp["beta1"], hp["beta2"], 1e-8
    gnorm = global_norm(grads)
    clip = hp["grad_clip"]
    scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
    t = jnp.asarray(step, jnp.float32) + 1.0
    lr = learning_rate(step, hp)
    wd = hp["weight_decay"] / hp["learning_rate"]
    new_w, new_mu, new_nu = {}, {}, {}
    for k in w:
        g = grads[k] * scale
        m = b1 * mu[k] + (1.0 - b1) * g
        v = b2 * nu[k] + (1.0 - b2) * g * g
        u = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
        new_w[k] = w[k] - lr * (u + wd * w[k])
        new_mu[k], new_nu[k] = m, v
    return new_w, new_mu, new_nu, gnorm


def make_train_step(sizes, hp, *, quant: Quant = None, rows: int = 2,
                    keep=None, frozen: bool = False):
    """``step(w, mu, nu, i, x, y) -> (w, mu, nu, loss, grad_norm)``, jitted
    and donating. ``keep`` and ``frozen`` plant faults: the mean over the
    first ``keep`` rows only; a state returned unchanged."""

    def step(w, mu, nu, i, x, y):
        val, grads = jax.value_and_grad(loss)(
            w, x, y, sizes, quant=quant, rows=rows, keep=keep)
        new = adam_update(w, mu, nu, grads, i, hp)
        if frozen:
            return w, mu, nu, val, new[3]
        return new[0], new[1], new[2], val, new[3]

    return jax.jit(step, donate_argnums=(0, 1, 2))


def leaf_norms(tree) -> tp.Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


# ---------------------------------------------------------------------------
# Serving: one full forward over a prompt with its served tokens
# ---------------------------------------------------------------------------


def make_sequence_logits(sizes, *, quant: Quant = None):
    """``f(w, seq [T]) -> logits [T, V]`` float32, jitted; ``w`` may be
    stored in bfloat16 (each layer is widened as it is used)."""

    def f(w, seq):
        h = hidden(w, seq[None, :], sizes, quant=quant, rows=1)
        return logits_of(w, h, quant)[0]

    return jax.jit(f)
