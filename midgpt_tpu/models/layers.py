"""Primitive NN layers, batched-native.

Capability parity with /root/reference/src/layers.py, redesigned TPU-first:
every op works on full ``[..., T, D]`` batches (big MXU-friendly matmuls)
instead of the reference's per-token modules vmapped by the caller
(/root/reference/src/model.py:104). Weights are stored ``(in, out)`` so the
forward is a plain ``x @ W`` contraction XLA maps straight onto the MXU.

Numerics preserved exactly (SURVEY.md 2.3):
- Linear: truncated-normal init in [-2, 2] scaled 1/sqrt(fan_in)
  (layers.py:49-50), no bias.
- RMSNorm: x * rsqrt(mean(x^2) + eps), optional learned scale
  (layers.py:60-75); weightless for block and final norms.
- QK-norm: mean-subtracting LayerNorm with weight, no bias, eps 1e-6
  (model.py:52-53).
- RoPE: GPT-J interleaved rotate-every-two, base 10000, tables precomputed
  in NumPy at trace time so they constant-fold (layers.py:79-99).
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from midgpt_tpu.pytree import module, static

KeyArray = jax.Array
Array = jax.Array


@module
class Embedding:
    """Token-id -> vector gather (parity: layers.py:13-34)."""

    weight: Array  # [V, D]

    @staticmethod
    def init(key: KeyArray, vocab_size: int, dim: int, std: float) -> "Embedding":
        w = std * jax.random.normal(key, (vocab_size, dim), dtype=jnp.float32)
        return Embedding(weight=w)

    def __call__(self, tokens: Array) -> Array:  # [...] int -> [..., D]
        with jax.named_scope("embedding"):
            return jnp.take(self.weight, tokens, axis=0)


@module
class Linear:
    """Bias-free linear, weight stored (in, out) (parity: layers.py:37-57,
    transposed for x @ W)."""

    weight: Array  # [in, out]

    @staticmethod
    def init(key: KeyArray, in_features: int, out_features: int) -> "Linear":
        w = (1 / math.sqrt(in_features)) * jax.random.truncated_normal(
            key, lower=-2, upper=2, shape=(in_features, out_features), dtype=jnp.float32
        )
        return Linear(weight=w)

    def __call__(self, x: Array) -> Array:  # [..., in] -> [..., out]
        with jax.named_scope("linear"):
            return x @ self.weight


@module
class RMSNorm:
    """x * rsqrt(mean(x^2, -1) + eps) [* weight] (parity: layers.py:60-75).

    impl: "jnp" (XLA-fused elementwise chain) | "fused" (Pallas one-pass
    kernel, midgpt_tpu.ops.fused_norm) | "auto" (= jnp, by measurement).
    The fused path needs D % 128 == 0 and a TPU; otherwise it silently
    falls back to jnp.

    Why auto == jnp: measured on a v5e-class chip
    (scripts/bench_kernels.py, r2): fused fwd is slightly faster
    (6.5us vs 10.2us at [16,1024,768]) but its custom-VJP backward costs
    236us vs jnp's 10us — XLA fuses the jnp backward into neighboring ops
    while the Pallas backward is a separate kernel launch + extra HBM
    round trip. Training always takes the jnp path; "fused" remains a
    tested oracle and a forward-only/inference option.
    """

    weight: tp.Optional[Array]  # [D] or None
    eps: float = static(default=1e-6)
    impl: str = static(default="auto")

    @staticmethod
    def init(
        dim: int, use_weight: bool = False, eps: float = 1e-6,
        impl: str = "auto",
    ) -> "RMSNorm":
        w = jnp.ones((dim,), dtype=jnp.float32) if use_weight else None
        return RMSNorm(weight=w, eps=eps, impl=impl)

    def __call__(self, x: Array) -> Array:
        from midgpt_tpu.utils.platform import is_tpu_backend

        with jax.named_scope("rmsnorm"):
            if (
                self.impl == "fused"
                and x.shape[-1] % 128 == 0
                and is_tpu_backend()
            ):
                from midgpt_tpu.ops.fused_norm import fused_rms_norm

                w = (
                    self.weight.astype(x.dtype)
                    if self.weight is not None
                    else None
                )
                return fused_rms_norm(x, w, self.eps)
            out = x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps
            )
            if self.weight is not None:
                out = out * self.weight.astype(out.dtype)
            return out


@module
class LayerNorm:
    """Mean-subtracting LayerNorm, learned scale, no bias. Used for per-head
    QK normalization (parity: model.py:52-53, eqx.nn.LayerNorm(C, eps=1e-6,
    use_weight=True, use_bias=False))."""

    weight: Array  # [D]
    eps: float = static(default=1e-6)

    @staticmethod
    def init(dim: int, eps: float = 1e-6) -> "LayerNorm":
        return LayerNorm(weight=jnp.ones((dim,), dtype=jnp.float32), eps=eps)

    def __call__(self, x: Array) -> Array:
        with jax.named_scope("layernorm"):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            centered = x - mean
            var = jnp.mean(jnp.square(centered), axis=-1, keepdims=True)
            out = centered * jax.lax.rsqrt(var + self.eps)
            return out * self.weight.astype(out.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (interleaved / GPT-J style)
# ---------------------------------------------------------------------------


def rope_tables(
    head_dim: int, seq_len: int, base: float = 10000.0
) -> tp.Tuple[np.ndarray, np.ndarray]:
    """Precompute sin/cos tables [T, head_dim//2] in NumPy at trace time so
    XLA constant-folds them (parity: layers.py:79-82)."""
    inv_freq = 1.0 / (base ** (np.arange(0, head_dim, 2) / head_dim))
    angles = np.einsum("i,j->ij", np.arange(seq_len), inv_freq)
    return np.sin(angles), np.cos(angles)


def rotate_every_two(x: Array) -> Array:
    """[a b c d] -> [-b a -d c] (parity: layers.py:85-89).

    Reference form (kept as the oracle for tests); apply_rotary uses the
    matmul form below on the hot path."""
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    y = jnp.stack((-x2, x1), axis=-1)
    return jnp.reshape(y, x.shape)


@functools.lru_cache(maxsize=None)
def _rotation_matrix(c: int, dtype_name: str, style: str = "interleaved") -> np.ndarray:
    """[C, C] constant R with (x @ R) == rotate_every_two(x) — or, for
    ``style="half"``, == rotate_half(x).

    Strided even/odd slicing on the minor (lane) dim lowers to a gather on
    TPU — and its transpose (the VJP) to a scatter-add, which profiling
    showed as a top copy cost in the train step. As a signed permutation
    matrix the op runs on the MXU instead, and its VJP is x @ R.T (another
    matmul). Each output element receives exactly one +-x term, so the
    result is bit-identical to the slicing form in any dtype."""
    r = np.zeros((c, c), dtype=np.float32)
    if style == "half":
        idx = np.arange(c // 2)
        r[idx + c // 2, idx] = -1.0  # y[i] = -x[i + C/2]
        r[idx, idx + c // 2] = 1.0  # y[i + C/2] = x[i]
    else:
        idx = np.arange(0, c, 2)
        r[idx + 1, idx] = -1.0  # y[2i] = -x[2i+1]
        r[idx, idx + 1] = 1.0  # y[2i+1] = x[2i]
    return r.astype(dtype_name)


def rotate_half(x: Array) -> Array:
    """[x1 | x2] -> [-x2 | x1] over the two halves of the last dim: the
    oracle of ``apply_rotary(style="half")``."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((-x2, x1), axis=-1)


def _duplicate_interleaved(t: Array) -> Array:
    """[..., D/2] -> [..., D] duplicating each column across even/odd lanes."""
    y = jnp.stack((t, t), axis=-1)
    return jnp.reshape(y, t.shape[:-1] + (t.shape[-1] * 2,))


def apply_rotary(
    x: Array,
    sin: tp.Union[Array, np.ndarray],
    cos: tp.Union[Array, np.ndarray],
    style: str = "interleaved",
) -> Array:
    """Apply RoPE. ``x``: [..., T, C]; sin/cos: [T, C//2] (parity:
    layers.py:92-99). ``style``: which lanes pair up — "interleaved"
    (2i, 2i+1), or "half" (i, i + C/2: ``rotate_half``); "none" is no
    rotation at all."""
    if style == "none":  # a model without a rotary embedding
        return x
    with jax.named_scope("rope"):
        sin = jnp.asarray(sin, dtype=x.dtype)
        cos = jnp.asarray(cos, dtype=x.dtype)
        if style == "half":
            sin_full = jnp.concatenate((sin, sin), axis=-1)  # [T, C]
            cos_full = jnp.concatenate((cos, cos), axis=-1)
        else:
            sin_full = _duplicate_interleaved(sin)  # [T, C]
            cos_full = _duplicate_interleaved(cos)
        rot = jnp.asarray(_rotation_matrix(x.shape[-1], x.dtype.name, style))
        return x * cos_full + (x @ rot) * sin_full


# ---------------------------------------------------------------------------
# Dropout (functional)
# ---------------------------------------------------------------------------


def dropout(
    x: Array,
    rate: float,
    key: tp.Optional[KeyArray],
    deterministic: bool,
) -> Array:
    """Inverted dropout; no-op when deterministic or rate == 0."""
    if deterministic or rate == 0.0:
        return x
    assert key is not None, "dropout in training mode requires a PRNG key"
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, p=keep, shape=x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))
