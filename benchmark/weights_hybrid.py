"""Weights from the seed for a model of full-attention and gated-delta-rule
layers (``olmo_hybrid``): one function, called by the harness to fill the
program's model and, again, by the plain reference for its own copy. The same
key gives the same arrays on the same device, in the type asked for.

``sizes`` is the configuration file. Leaves of the full-attention stack are
named ``f_*`` and carry a leading axis of its layers, those of the linear
stack ``l_*``. Layout: ``f_wqkv`` is q | k | v side by side, heads major;
``l_wqkv`` likewise (the convolution's input, ``2 Hk dk + Hv dv`` wide);
``l_conv`` is ``[taps, channels]`` and its LAST row meets the newest token;
``l_wba`` is the two ``[D, Hv]`` gates side by side, beta's then a's.

Matrices are truncated-normal in [-2, 2] over sqrt(fan_in), the embedding and
the untied head normal over sqrt(D), each its own draw; norm scales are 1 +
0.1 normal, so that a scale left out shows. What is drawn so that the
recurrence does something over 2,048 tokens, and how:

- a time scale ``tau`` a (layer, head), log-uniform over 1.5 to 4,096
  tokens: ``l_dt_bias = softplus^-1(1 / tau)`` and ``l_a_log`` 0.1 normal, so
  that with nothing else ``alpha = exp(-1 / tau)`` lies between 0.51 and
  0.9998, a quarter of the heads forgetting within a dozen tokens and a
  quarter remembering a request's whole length (which is what makes a state
  left over from the slot's last request show);
- ``l_wba`` at half the usual scale: the mixer reads the residual stream
  un-normed (the norm sits on its output), whose RMS grows from 1 to about 5
  over the layers, and a full-scale gate would saturate — every beta at 0 or
  2, every decay at 1 or 0;
- ``l_conv`` normal over sqrt(taps): the convolution keeps its input's scale.

The state neither dies nor blows up: with ``|k| = 1`` and beta in (0, 2) a
step's transition ``alpha (I - beta k k^T)`` never lengthens a vector.

Every stacked leaf is drawn a layer at a time (``lax.map``), so that the
float32 draw of one layer is all that is live beside what is kept."""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

_BLOCK = ("ln1", "ln2", "w_gate", "w_up", "w_down")
LEAVES = (
    ("wte", "lm_head", "ln_f")
    + tuple("f_" + n for n in ("wqkv", "wo", "q_norm", "k_norm") + _BLOCK)
    + tuple("l_" + n for n in (
        "wqkv", "conv", "wg", "wba", "a_log", "dt_bias", "o_norm", "wo",
    ) + _BLOCK)
)
_SCALES = ("ln_f", "f_q_norm", "f_k_norm", "f_ln1", "f_ln2", "l_o_norm",
           "l_ln1", "l_ln2")


def shapes(sizes) -> tp.Dict[str, tp.Tuple[int, ...]]:
    d, v, f = sizes["n_embd"], sizes["vocab_size"], sizes["mlp_hidden"]
    h, hkv, c = sizes["n_head"], sizes["n_kv_head"], sizes["head_width"]
    hk, hv = sizes["linear_key_heads"], sizes["linear_value_heads"]
    dk, dv = sizes["linear_key_dim"], sizes["linear_value_dim"]
    nl = sum(1 for k in sizes["layer_types"] if k == "linear_attention")
    nf = len(sizes["layer_types"]) - nl
    ch = 2 * hk * dk + hv * dv
    out = {
        "wte": (v, d), "lm_head": (d, v), "ln_f": (d,),
        "f_wqkv": (nf, d, (h + 2 * hkv) * c), "f_wo": (nf, h * c, d),
        "f_q_norm": (nf, h * c), "f_k_norm": (nf, hkv * c),
        "l_wqkv": (nl, d, ch), "l_conv": (nl, sizes["linear_conv"], ch),
        "l_wg": (nl, d, hv * dv), "l_wba": (nl, d, 2 * hv),
        "l_a_log": (nl, hv), "l_dt_bias": (nl, hv), "l_o_norm": (nl, dv),
        "l_wo": (nl, hv * dv, d),
    }
    for p, n in (("f_", nf), ("l_", nl)):
        out.update({p + "ln1": (n, d), p + "ln2": (n, d),
                    p + "w_gate": (n, d, f), p + "w_up": (n, d, f),
                    p + "w_down": (n, f, d)})
    return out


def leaf(name: str, key, sizes, dtype):
    """One leaf, from the run's key: separate so that a caller can rebuild
    a single leaf without holding the rest."""
    shape = shapes(sizes)[name]
    k = jax.random.fold_in(key, 1 + LEAVES.index(name))
    f32 = jnp.float32
    if name in _SCALES:
        return (1.0 + 0.1 * jax.random.normal(k, shape, f32)).astype(dtype)
    if name in ("wte", "lm_head"):
        w = jax.random.normal(k, shape, f32)
        return (w / math.sqrt(sizes["n_embd"])).astype(dtype)
    if name == "l_a_log":
        return (0.1 * jax.random.normal(k, shape, f32)).astype(dtype)
    if name == "l_dt_bias":
        tau = jnp.exp(jax.random.uniform(
            k, shape, f32, math.log(1.5), math.log(4096.0)))
        return jnp.log(jnp.expm1(1.0 / tau)).astype(dtype)
    if name == "l_conv":
        w = jax.random.normal(k, shape, f32)
        return (w / math.sqrt(shape[1])).astype(dtype)
    scale = (0.5 if name == "l_wba" else 1.0) / math.sqrt(shape[-2])

    def layer(i):
        w = jax.random.truncated_normal(
            jax.random.fold_in(k, i), -2.0, 2.0, shape[1:], f32)
        return (w * scale).astype(dtype)

    return jax.lax.map(layer, jnp.arange(shape[0]))


def make(key, sizes, dtype) -> tp.Dict[str, jax.Array]:
    return {name: leaf(name, key, sizes, dtype) for name in LEAVES}
