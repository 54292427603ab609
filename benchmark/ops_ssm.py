"""The yardstick's arithmetic for a model of Mamba-1 and full-attention layers
(``jamba``): what a decode step has to move and a forward has to compute,
from the configuration's published sizes alone (``hidden_size``,
``intermediate_size``, ``num_attention_heads``, ``num_key_value_heads``,
``vocab_size``, ``mamba_expand``, ``mamba_d_state``, ``mamba_dt_rank``,
``mamba_d_conv``, ``attn_layer_offset`` / ``attn_layer_period``). The peaks
are :mod:`benchmark.ops`'s.

Bytes are counted as the arrays hold them, not as the chip's tiles pad them:
a convolution tail of ``taps - 1`` = 3 rows a slot and layer is 3 rows here,
and 8 or 16 in a tile (:func:`tail_bytes_tiled` says how many)."""

from __future__ import annotations

from benchmark.weights_ssm import dims, layer_types


def layers(sizes):
    """(Mamba layers, attention layers)."""
    kinds = layer_types(sizes)
    return kinds.count("mamba"), kinds.count("attention")


def _mlp_params(sizes) -> int:
    k = dims(sizes)
    return 3 * k["d"] * k["f"]  # SwiGLU


def mamba_layer_params(sizes) -> int:
    """Elements of the matrices a row contracts against in a Mamba layer:
    ``W_in`` (x | z), ``W_x`` (dt | B | C), ``W_dt``, ``W_out``, and the
    MLP."""
    k = dims(sizes)
    d, e, n, r = k["d"], k["e"], k["n"], k["r"]
    return d * 2 * e + e * (r + 2 * n) + r * e + e * d + _mlp_params(sizes)


def full_layer_params(sizes) -> int:
    """The same in an attention layer: Wq, Wk, Wv, Wo and the MLP."""
    k = dims(sizes)
    d, c = k["d"], k["c"]
    return d * (k["h"] + 2 * k["hkv"]) * c + k["h"] * c * d + _mlp_params(
        sizes)


def matmul_params(sizes) -> int:
    """Every matrix element one row contracts against in a forward: both
    kinds of layer and the head, which is the embedding (tied; the gather
    at the input is not a product)."""
    nm, nf = layers(sizes)
    k = dims(sizes)
    return (nm * mamba_layer_params(sizes) + nf * full_layer_params(sizes)
            + k["d"] * k["v"])


def total_params(sizes) -> int:
    """Every stored element: the matrices, the embedding once, the
    convolution and its bias, ``b_dt``, ``A_log``, ``D`` and every norm's
    scale."""
    nm, nf = layers(sizes)
    k = dims(sizes)
    d, e, n, r, taps = k["d"], k["e"], k["n"], k["r"], k["taps"]
    mamba_rest = taps * e + e + e + e * n + e + r + 2 * n + 2 * d
    return (matmul_params(sizes) + nm * mamba_rest + nf * 2 * d + d)


def state_elems_per_slot(sizes) -> int:
    """One Mamba layer's recurrent state of one slot: ``E x N``."""
    k = dims(sizes)
    return k["e"] * k["n"]


def tail_elems_per_slot(sizes) -> int:
    """One Mamba layer's convolution tail of one slot: ``(taps - 1) x E``."""
    k = dims(sizes)
    return (k["taps"] - 1) * k["e"]


def tail_bytes_tiled(sizes, cache_bytes: int = 2) -> int:
    """The same tail as the chip's tiles hold it: ``taps - 1`` rows padded
    to a sublane tile (8 rows of 32 bits, 16 of 16)."""
    k = dims(sizes)
    rows = 8 * 4 // cache_bytes
    return -(-(k["taps"] - 1) // rows) * rows * k["e"] * cache_bytes


def row_forward_flops(sizes, context: float) -> float:
    """One row's forward with ``context`` keys visible to it in the
    attention layers: 2 a matrix element; the scan's ``6 E N`` a Mamba layer
    (``Delta A``, its exp, the decay, ``Delta x B``, the add, ``h C`` a
    multiply-add); ``4 H C context`` an attention layer for scores and
    values."""
    nm, nf = layers(sizes)
    k = dims(sizes)
    scan = 6.0 * nm * state_elems_per_slot(sizes)
    attn = 4.0 * nf * k["h"] * k["c"] * context
    return 2.0 * matmul_params(sizes) + scan + attn


def prompt_flops(sizes, prompt: int) -> float:
    """One forward over a prompt's rows, each seeing what lies before it."""
    return prompt * row_forward_flops(sizes, (prompt + 1) / 2.0)


def state_bytes_per_call(sizes, slots: float, state_bytes: int = 4) -> float:
    """Bytes one decode call of the scan kernel (``%ssm_step``) has to move
    for ``slots`` slots, one row each: one Mamba layer's state, read once and
    written once; the row's ``x``, ``Delta``, ``B``, ``C`` in and ``y`` out,
    float32; and ``A`` once. What ``ssm_step_roofline.serve`` divides by the
    kernel's time a call."""
    k = dims(sizes)
    e, n = k["e"], k["n"]
    rows = slots * (3 * e + 2 * n) * 4
    return 2.0 * slots * state_elems_per_slot(sizes) * state_bytes + rows + (
        e * n * 4)


def recurrent_stream_bytes(sizes, slots_live: float, state_bytes: int = 4,
                           cache_bytes: int = 2) -> float:
    """Bytes of recurrent state a decode step has to move for ``slots_live``
    decoding slots: in every Mamba layer the state and the convolution's
    tail, read and written."""
    nm, _ = layers(sizes)
    return 2.0 * nm * slots_live * (
        state_elems_per_slot(sizes) * state_bytes
        + tail_elems_per_slot(sizes) * cache_bytes)


def decode_stream_bytes(sizes, slots_live: float, live_tokens: float,
                        weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step has to move: the matrices of both kinds of
    layer and the tied head once, K and V of the ``live_tokens`` resident
    positions (summed over the slots) in the attention layers, and the
    recurrent state and tail of the ``slots_live`` decoding slots, read and
    written. Activations, the embedding's gathered rows, norm scales, the
    convolution's weights, ``A_log``, ``D`` and ``b_dt`` are left out (under
    1 %)."""
    _, nf = layers(sizes)
    k = dims(sizes)
    kv = live_tokens * 2 * nf * k["hkv"] * k["c"]
    return (matmul_params(sizes) * weight_bytes + kv * cache_bytes
            + recurrent_stream_bytes(sizes, slots_live,
                                     cache_bytes=cache_bytes))
