"""Weights from the seed for a latent-attention expert model
(``joyai_llm_flash``: DeepSeek-V3's block): one function, called by the
harness to fill the program's model and, again, by the plain reference for its
own copy. The same key gives the same arrays on the same device, in the type
asked for.

``sizes`` is the configuration file. The leading ``dense_layers`` layers are
one stack (leaves ``d_*``, a leading axis of those layers), the expert layers
another (``e_*``). Both kinds of layer hold the attention's leaves: ``wq_a``
[D, q_rank], ``q_norm``, ``wq_b`` [q_rank, H (nope + rope)] (a head's q_nope |
q_rope), ``wkv_a`` [D, kv_rank + rope] (the latent | the rotary key),
``kv_norm``, ``wkv_b`` [kv_rank, H (nope + v)] (a head's k_nope | v), ``wo``
[H v, D], ``ln1``, ``ln2``. A dense layer adds ``w_gate``, ``w_up``,
``w_down``; an expert layer ``router`` [D, E], ``bias`` [E] (float32: it moves
the choice only), ``w13`` (an expert's W1 gate | W3 up side by side), ``w2``,
and the shared expert's ``s_gate``, ``s_up``, ``s_down``.

Matrices are truncated-normal in [-2, 2] over sqrt(fan_in), the embedding and
the untied head normal over sqrt(D), each its own draw; norm scales are 1 +
0.1 normal, so that a scale left out shows. Three things are drawn otherwise:

- ``wq_b`` at ``SCORE_GAIN`` times the usual scale. With every matrix at unit
  scale a row's scores ``(q_nope . k_nope + q_rope . kr) / sqrt(192)`` have a
  standard deviation of 0.8: over 33 k random keys the softmax is then an
  average over thousands of them, a wrong page moves a logit by a
  two-thousandth of itself, and ``correct`` could not see the cache at all. At
  2.5 (measured at the published widths: ``benchmark/tests
  /test_serve_latent.py`` holds the tiny size to the same rule) the effective
  number of keys ``N exp(-sigma^2)`` is tens, as a trained model's heads over a
  long document;
- ``bias`` normal at 0.02, a tenth of a sigmoid score's spread (0.2 at unit
  router logits): it changes which expert is 8th for some rows and leaves the
  load as it was;
- the routed experts' ``w2`` at ``ROUTED_GAIN`` = a quarter of the usual
  scale. Random experts have nothing to do with one another, the 8th and 9th
  of 256 sigmoid scores lie 0.08 of a logit apart, and a chosen expert's
  weight is 2.5 / 8 whatever its score: at unit scale one flipped choice moves
  the residual stream by a tenth of itself, bf16's 0.4 % of a layer (every
  layer agrees with the reference to that, routing to the row) flips 7 % of
  the rows in the next, and five layers on the program's logits lie 0.42 of
  their spread from the float32 reference's — where the int8-rounded
  reference lies 0.77, and no limit separates them. At a quarter the same
  forward reads 0.11 against 0.46, and the mean gap of the first choice 0.021
  against 0.42 (my chip runs, PR 34: five layers at the published widths, 1,024
  tokens; at a tenth 0.07 / 0.43). A trained model's neighbouring experts are
  not strangers; the routing, the bytes and the arithmetic are unchanged.

Every stacked leaf is drawn a layer at a time (``lax.map``), so that the
float32 draw of one layer is all that is live beside what is kept."""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

SCORE_GAIN = 3.2
ROUTED_GAIN = 0.25

_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "ln1",
         "ln2")
_DENSE = ("w_gate", "w_up", "w_down")
_EXPERT = ("router", "bias", "w13", "w2", "s_gate", "s_up", "s_down")
LEAVES = (
    ("wte", "lm_head", "ln_f")
    + tuple("d_" + n for n in _ATTN + _DENSE)
    + tuple("e_" + n for n in _ATTN + _EXPERT)
)
_SCALES = ("q_norm", "kv_norm", "ln1", "ln2", "ln_f")


def shapes(sizes) -> tp.Dict[str, tp.Tuple[int, ...]]:
    d, v, h = sizes["n_embd"], sizes["vocab_size"], sizes["n_head"]
    dq, dc = sizes["latent_q"], sizes["latent_kv"]
    dn, dr, dv = sizes["latent_nope"], sizes["latent_rope"], sizes["latent_v"]
    e, f = sizes["experts"], sizes["expert_hidden"]
    fs, fd = sizes["shared_experts"] * f, sizes["mlp_hidden"]
    nd = sizes["dense_layers"]
    ne = sizes["n_layer"] - nd
    out = {"wte": (v, d), "lm_head": (d, v), "ln_f": (d,)}
    for p, n in (("d_", nd), ("e_", ne)):
        out.update({
            p + "wq_a": (n, d, dq), p + "q_norm": (n, dq),
            p + "wq_b": (n, dq, h * (dn + dr)),
            p + "wkv_a": (n, d, dc + dr), p + "kv_norm": (n, dc),
            p + "wkv_b": (n, dc, h * (dn + dv)), p + "wo": (n, h * dv, d),
            p + "ln1": (n, d), p + "ln2": (n, d),
        })
    out.update({"d_w_gate": (nd, d, fd), "d_w_up": (nd, d, fd),
                "d_w_down": (nd, fd, d)})
    out.update({
        "e_router": (ne, d, e), "e_bias": (ne, e),
        "e_w13": (ne, e, d, 2 * f), "e_w2": (ne, e, f, d),
        "e_s_gate": (ne, d, fs), "e_s_up": (ne, d, fs),
        "e_s_down": (ne, fs, d),
    })
    return out


def leaf(name: str, key, sizes, dtype):
    """One leaf, from the run's key: separate so that a caller can rebuild
    a single leaf without holding the rest."""
    shape = shapes(sizes)[name]
    k = jax.random.fold_in(key, 1 + LEAVES.index(name))
    f32 = jnp.float32
    base = name.split("_", 1)[1] if name[:2] in ("d_", "e_") else name
    if base in _SCALES:
        return (1.0 + 0.1 * jax.random.normal(k, shape, f32)).astype(dtype)
    if base in ("wte", "lm_head"):
        w = jax.random.normal(k, shape, f32)
        return (w / math.sqrt(sizes["n_embd"])).astype(dtype)
    if base == "bias":
        return 0.02 * jax.random.normal(k, shape, f32)
    gain = {"wq_b": SCORE_GAIN, "w2": ROUTED_GAIN}.get(base, 1.0)
    scale = gain / math.sqrt(shape[-2])

    def layer(i):
        w = jax.random.truncated_normal(
            jax.random.fold_in(k, i), -2.0, 2.0, shape[1:], f32)
        return (w * scale).astype(dtype)

    return jax.lax.map(layer, jnp.arange(shape[0]))


def make(key, sizes, dtype) -> tp.Dict[str, jax.Array]:
    return {name: leaf(name, key, sizes, dtype) for name in LEAVES}
