"""Weights from the seed: one function, called by the harness to fill the
program's model and, again, by the plain reference for its own copy. The
same key gives the same arrays on the same device, in the type asked for.

The distribution is midGPT's: matrices truncated-normal in [-2, 2] over
sqrt(fan_in); the embedding normal over sqrt(D); the head starts as the
embedding's transpose (tied at init only); QK-norm scales one.
"""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

LEAVES = ("wte", "wqkv", "wo", "q_norm", "k_norm", "w_up", "w_down", "lm_head")


def shapes(sizes) -> tp.Dict[str, tp.Tuple[int, ...]]:
    n, d, v = sizes["n_layer"], sizes["n_embd"], sizes["vocab_size"]
    c = d // sizes["n_head"]
    f = int(sizes.get("mlp_ratio", 4.0) * d)
    return {
        "wte": (v, d), "wqkv": (n, d, 3 * d), "wo": (n, d, d),
        "q_norm": (n, c), "k_norm": (n, c),
        "w_up": (n, d, f), "w_down": (n, f, d), "lm_head": (d, v),
    }


def leaf(name: str, key, sizes, dtype):
    """One leaf, from the run's key: separate so that a caller can rebuild
    a single leaf without holding the rest."""
    shape = shapes(sizes)[name]
    if name in ("q_norm", "k_norm"):
        return jnp.ones(shape, dtype)
    if name in ("wte", "lm_head"):
        d = sizes["n_embd"]
        w = jax.random.normal(
            jax.random.fold_in(key, 0), shapes(sizes)["wte"], jnp.float32
        ) / math.sqrt(d)
        return (w if name == "wte" else w.T).astype(dtype)
    k = jax.random.fold_in(key, 1 + LEAVES.index(name))
    fan_in = shape[1]
    w = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
    return (w / math.sqrt(fan_in)).astype(dtype)


def make(key, sizes, dtype) -> tp.Dict[str, jax.Array]:
    return {name: leaf(name, key, sizes, dtype) for name in LEAVES}


def key_of(seed: int):
    """Seeds run to a little over 2**31: fold the two halves in."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, seed >> 31)
