"""Weights from the seed for a model of Mamba-1 and full-attention layers
(``jamba``): one function, called by the harness to fill the program's model
and, again, by the plain reference for its own copy. The same key gives the
same arrays on the same device, in the type asked for.

``sizes`` is the configuration file, read under its published names
(``hidden_size``, ``mamba_*``, ``attn_layer_offset`` / ``attn_layer_period``,
...). Leaves of the attention layers are named ``f_*`` and carry a leading
axis of those layers, those of the Mamba layers ``m_*``. Layout: ``f_wqkv``
is q | k | v side by side, heads major; ``m_w_in`` is x | z; ``m_w_x`` is
dt | B | C; ``m_conv`` is ``[taps, E]`` and its LAST row meets the newest
token; ``m_a_log`` is ``[E, N]``. The head is the embedding (tied), so there
is no ``lm_head``.

Mamba's published initialisation, where it has one: ``A_log = log(1 .. N)``
on every channel, ``D = 1``, ``b_dt = softplus^-1(step)`` with the step
log-uniform in [0.001, 0.1], the convolution's weight and bias uniform in
+-1/sqrt(taps) (PyTorch's ``Conv1d`` default, which Mamba keeps). Matrices
are truncated-normal in [-2, 2] over sqrt(fan_in) (``W_dt`` at 1/sqrt(R) is
Mamba's own), the embedding normal over sqrt(D); norm scales 1 + 0.1 normal,
so that a scale left out shows.

Every stacked leaf is drawn a layer at a time (``lax.map``), so that the
float32 draw of one layer is all that is live beside what is kept."""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

_BLOCK = ("ln1", "ln2", "w_gate", "w_up", "w_down")
_MAMBA = ("w_in", "conv", "conv_bias", "w_x", "dt_norm", "b_norm", "c_norm",
          "w_dt", "dt_bias", "a_log", "d", "w_out")
LEAVES = (
    ("wte", "ln_f")
    + tuple("f_" + n for n in ("wqkv", "wo") + _BLOCK)
    + tuple("m_" + n for n in _MAMBA + _BLOCK)
)
_SCALES = ("ln_f", "f_ln1", "f_ln2", "m_ln1", "m_ln2", "m_dt_norm",
           "m_b_norm", "m_c_norm")


def layer_types(sizes) -> tp.List[str]:
    """Per layer "attention" or "mamba", from the published offset and
    period (HF's ``JambaConfig.layers_block_type``)."""
    off, per = sizes["attn_layer_offset"], sizes["attn_layer_period"]
    return ["attention" if i % per == off else "mamba"
            for i in range(sizes["num_hidden_layers"])]


def dims(sizes) -> tp.Dict[str, int]:
    d = sizes["hidden_size"]
    h = sizes["num_attention_heads"]
    return {
        "d": d, "e": sizes["mamba_expand"] * d, "n": sizes["mamba_d_state"],
        "r": sizes["mamba_dt_rank"], "taps": sizes["mamba_d_conv"],
        "h": h, "hkv": sizes["num_key_value_heads"], "c": d // h,
        "f": sizes["intermediate_size"], "v": sizes["vocab_size"],
    }


def shapes(sizes) -> tp.Dict[str, tp.Tuple[int, ...]]:
    k = dims(sizes)
    d, e, n, r, f = k["d"], k["e"], k["n"], k["r"], k["f"]
    kinds = layer_types(sizes)
    nm, nf = kinds.count("mamba"), kinds.count("attention")
    out = {
        "wte": (k["v"], d), "ln_f": (d,),
        "f_wqkv": (nf, d, (k["h"] + 2 * k["hkv"]) * k["c"]),
        "f_wo": (nf, k["h"] * k["c"], d),
        "m_w_in": (nm, d, 2 * e), "m_conv": (nm, k["taps"], e),
        "m_conv_bias": (nm, e), "m_w_x": (nm, e, r + 2 * n),
        "m_dt_norm": (nm, r), "m_b_norm": (nm, n), "m_c_norm": (nm, n),
        "m_w_dt": (nm, r, e), "m_dt_bias": (nm, e), "m_a_log": (nm, e, n),
        "m_d": (nm, e), "m_w_out": (nm, e, d),
    }
    for p, count in (("f_", nf), ("m_", nm)):
        out.update({p + "ln1": (count, d), p + "ln2": (count, d),
                    p + "w_gate": (count, d, f), p + "w_up": (count, d, f),
                    p + "w_down": (count, f, d)})
    return out


def leaf(name: str, key, sizes, dtype):
    """One leaf, from the run's key: separate so that a caller can rebuild
    a single leaf without holding the rest."""
    shape = shapes(sizes)[name]
    k = jax.random.fold_in(key, 1 + LEAVES.index(name))
    f32 = jnp.float32
    if name in _SCALES:
        return (1.0 + 0.1 * jax.random.normal(k, shape, f32)).astype(dtype)
    if name == "wte":
        w = jax.random.normal(k, shape, f32)
        return (w / math.sqrt(sizes["hidden_size"])).astype(dtype)
    if name == "m_a_log":
        n = shape[-1]
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=f32)), shape).astype(dtype)
    if name == "m_d":
        return jnp.ones(shape, dtype)
    if name == "m_dt_bias":
        step = jnp.exp(jax.random.uniform(
            k, shape, f32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    if name in ("m_conv", "m_conv_bias"):
        bound = 1.0 / math.sqrt(sizes["mamba_d_conv"])
        return jax.random.uniform(k, shape, f32, -bound, bound).astype(dtype)
    scale = 1.0 / math.sqrt(shape[-2])

    def layer(i):
        w = jax.random.truncated_normal(
            jax.random.fold_in(k, i), -2.0, 2.0, shape[1:], f32)
        return (w * scale).astype(dtype)

    return jax.lax.map(layer, jnp.arange(shape[0]))


def make(key, sizes, dtype) -> tp.Dict[str, jax.Array]:
    return {name: leaf(name, key, sizes, dtype) for name in LEAVES}
