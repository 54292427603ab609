"""Weights from the seed for the block-diffusion expert model (``sdar_moe``):
one function, called by the harness to fill the program's model and, again,
by the plain reference for its own copy. The same key gives the same arrays
on the same device, in the type asked for.

``sizes`` is the configuration file: ``n_layer``, ``n_embd``, ``n_head``,
``n_kv_head``, ``head_width``, ``experts``, ``expert_hidden``,
``vocab_size``. Layout: ``wqkv`` is q | k | v side by side, heads major;
``w13`` is an expert's W1 (gate) | W3 (up) side by side; ``w2`` its down
projection. Matrices are truncated-normal in [-2, 2] over sqrt(fan_in), the
embedding and the untied head normal over sqrt(D), each its own draw; norm
scales are 1 + 0.1 normal, so that a scale left out shows.

The expert leaves are 1.2 GB and 0.6 GB a layer in bfloat16: every leaf is
drawn a layer at a time (``lax.map``), so that the float32 draw of one
layer is all that is live beside what is kept."""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

LEAVES = ("wte", "wqkv", "wo", "q_norm", "k_norm", "ln1", "ln2", "router",
          "w13", "w2", "ln_f", "lm_head")
_SCALES = ("q_norm", "k_norm", "ln1", "ln2", "ln_f")


def shapes(sizes) -> tp.Dict[str, tp.Tuple[int, ...]]:
    n, d, v = sizes["n_layer"], sizes["n_embd"], sizes["vocab_size"]
    h, hkv, c = sizes["n_head"], sizes["n_kv_head"], sizes["head_width"]
    e, f = sizes["experts"], sizes["expert_hidden"]
    return {
        "wte": (v, d), "wqkv": (n, d, (h + 2 * hkv) * c), "wo": (n, h * c, d),
        "q_norm": (n, c), "k_norm": (n, c), "ln1": (n, d), "ln2": (n, d),
        "router": (n, d, e), "w13": (n, e, d, 2 * f), "w2": (n, e, f, d),
        "ln_f": (d,), "lm_head": (d, v),
    }


def leaf(name: str, key, sizes, dtype):
    """One leaf, from the run's key: separate so that a caller can rebuild
    a single leaf without holding the rest."""
    shape = shapes(sizes)[name]
    k = jax.random.fold_in(key, 1 + LEAVES.index(name))
    if name in _SCALES:
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if name in ("wte", "lm_head"):
        w = jax.random.normal(k, shape, jnp.float32)
        return (w / math.sqrt(sizes["n_embd"])).astype(dtype)
    fan_in = shape[-2]

    def layer(i):
        w = jax.random.truncated_normal(
            jax.random.fold_in(k, i), -2.0, 2.0, shape[1:], jnp.float32)
        return (w / math.sqrt(fan_in)).astype(dtype)

    return jax.lax.map(layer, jnp.arange(shape[0]))


def make(key, sizes, dtype) -> tp.Dict[str, jax.Array]:
    return {name: leaf(name, key, sizes, dtype) for name in LEAVES}
