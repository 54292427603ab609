"""The yardstick's arithmetic for a model whose layers are of two kinds, full
attention and a gated delta rule (``olmo_hybrid``): what a decode step has to
move and a forward has to compute, from the configuration's sizes alone
(``layer_types``, ``n_embd``, ``n_head``, ``n_kv_head``, ``head_width``,
``mlp_hidden``, ``vocab_size``, ``linear_key_heads``, ``linear_value_heads``,
``linear_key_dim``, ``linear_value_dim``, ``linear_conv``). The peaks are
:mod:`benchmark.ops`'s."""

from __future__ import annotations


def layers(sizes):
    """(linear layers, full-attention layers)."""
    lin = sum(1 for k in sizes["layer_types"] if k == "linear_attention")
    return lin, len(sizes["layer_types"]) - lin


def _mlp_params(sizes) -> int:
    return 3 * sizes["n_embd"] * sizes["mlp_hidden"]  # SwiGLU


def _conv_channels(sizes) -> int:
    return (2 * sizes["linear_key_heads"] * sizes["linear_key_dim"]
            + sizes["linear_value_heads"] * sizes["linear_value_dim"])


def full_layer_params(sizes) -> int:
    """Elements of the matrices a row contracts against in a full-attention
    layer: Wq, Wk, Wv, Wo and the MLP."""
    d, c = sizes["n_embd"], sizes["head_width"]
    h, hkv = sizes["n_head"], sizes["n_kv_head"]
    return d * (h + 2 * hkv) * c + h * c * d + _mlp_params(sizes)


def linear_layer_params(sizes) -> int:
    """The same in a linear-attention layer: Wq, Wk, Wv (the convolution's
    input), the gate Wg, the two ``[D, Hv]`` gates, Wo, and the MLP."""
    d, hv = sizes["n_embd"], sizes["linear_value_heads"]
    vdim = hv * sizes["linear_value_dim"]
    return (d * _conv_channels(sizes) + d * vdim + 2 * d * hv + vdim * d
            + _mlp_params(sizes))


def matmul_params(sizes) -> int:
    """Every matrix element one row contracts against in a forward: both
    kinds of layer and the head (the embedding is a gather)."""
    lin, full = layers(sizes)
    return (lin * linear_layer_params(sizes) + full * full_layer_params(sizes)
            + sizes["n_embd"] * sizes["vocab_size"])


def state_elems_per_slot(sizes) -> int:
    """One linear layer's recurrent state of one slot."""
    return (sizes["linear_value_heads"] * sizes["linear_key_dim"]
            * sizes["linear_value_dim"])


def row_forward_flops(sizes, context: float) -> float:
    """One row's forward with ``context`` keys visible to it in the
    full-attention layers: 2 a matrix element; a linear layer's state read
    twice and written once a token, ``4 dk dv`` a head (decay and the two
    contractions a multiply-add each, the rank-one write one);
    ``4 H C context`` a full layer for scores and values."""
    lin, full = layers(sizes)
    state = 4.0 * lin * state_elems_per_slot(sizes)
    attn = 4.0 * full * sizes["n_head"] * sizes["head_width"] * context
    return 2.0 * matmul_params(sizes) + state + attn


def prompt_flops(sizes, prompt: int) -> float:
    """One forward over a prompt's rows, each seeing what lies before it."""
    return prompt * row_forward_flops(sizes, (prompt + 1) / 2.0)


def state_bytes_per_call(sizes, slots: float, state_bytes: int = 4) -> float:
    """Bytes one call of the step kernel has to move: one linear layer's
    state of ``slots`` slots, read once and written once. What
    ``gdn_step_roofline.serve`` divides by the kernel's time a call (q, k,
    v and the output, under 1 % of it, are left out)."""
    return 2.0 * slots * state_elems_per_slot(sizes) * state_bytes


def recurrent_stream_bytes(sizes, slots_live: float, state_bytes: int = 4,
                           cache_bytes: int = 2) -> float:
    """Bytes of recurrent state a decode step has to move for ``slots_live``
    decoding slots: in every linear layer the state and the convolution's
    tail, read and written."""
    lin, _ = layers(sizes)
    tail = (sizes["linear_conv"] - 1) * _conv_channels(sizes)
    return 2.0 * lin * slots_live * (
        state_elems_per_slot(sizes) * state_bytes + tail * cache_bytes)


def decode_stream_bytes(sizes, slots_live: float, live_tokens: float,
                        weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step has to move: the matrices of both kinds of
    layer and the head, K and V of the ``live_tokens`` resident positions
    (summed over the slots) in the full-attention layers, and the
    recurrent state of the ``slots_live`` decoding slots, read and written.
    Activations, the embedding's gathered rows, norm scales and the
    convolution's weights are left out (under 1 %)."""
    _, full = layers(sizes)
    kv = (live_tokens * 2 * full * sizes["n_kv_head"] * sizes["head_width"])
    return (matmul_params(sizes) * weight_bytes + kv * cache_bytes
            + recurrent_stream_bytes(sizes, slots_live,
                                     cache_bytes=cache_bytes))
