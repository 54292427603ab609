"""The yardstick's arithmetic for a latent-attention expert model
(``joyai_llm_flash``): what a decode step has to move and a forward has to
compute, from the configuration's sizes alone (``n_layer``, ``dense_layers``,
``n_embd``, ``n_head``, ``latent_q``, ``latent_kv``, ``latent_nope``,
``latent_rope``, ``latent_v``, ``mlp_hidden``, ``experts``,
``experts_per_token``, ``expert_hidden``, ``shared_experts``, ``vocab_size``).
The peaks are :mod:`benchmark.ops`'s.

What is cached a token a layer is the latent and the rotary key, ``latent_kv +
latent_rope`` values: whatever rows the program pads them to, the yardstick
counts those, so that padding shows as lost roofline."""

from __future__ import annotations


def attention_params(sizes) -> int:
    """Elements of a layer's attention matrices: Wqa, Wqb, Wkva, Wkvb, Wo."""
    d, h = sizes["n_embd"], sizes["n_head"]
    dq, dc = sizes["latent_q"], sizes["latent_kv"]
    dn, dr, dv = sizes["latent_nope"], sizes["latent_rope"], sizes["latent_v"]
    return (d * dq + dq * h * (dn + dr) + d * (dc + dr)
            + dc * h * (dn + dv) + h * dv * d)


def expert_params(sizes) -> int:
    """One routed expert's three matrices."""
    return 3 * sizes["n_embd"] * sizes["expert_hidden"]


def dense_layer_params(sizes) -> int:
    """A leading dense layer: attention and the SwiGLU of ``mlp_hidden``."""
    return attention_params(sizes) + 3 * sizes["n_embd"] * sizes["mlp_hidden"]


def expert_layer_fixed_params(sizes) -> int:
    """What an expert layer reads whatever the routing: attention, the
    router, the shared experts."""
    return (attention_params(sizes) + sizes["n_embd"] * sizes["experts"]
            + sizes["shared_experts"] * expert_params(sizes))


def layers(sizes):
    """(dense layers, expert layers)."""
    return sizes["dense_layers"], sizes["n_layer"] - sizes["dense_layers"]


def latent_bytes_per_token(sizes, cache_bytes: int = 2) -> int:
    """A token's cache in one layer: the latent and the rotary key."""
    return (sizes["latent_kv"] + sizes["latent_rope"]) * cache_bytes


def latent_read_bytes_per_layer(sizes, live_tokens: float,
                                cache_bytes: int = 2) -> float:
    """Bytes of cache one layer's decode call has to read for
    ``live_tokens`` resident positions, summed over the slots, each slot's
    walk counted (a kernel that read a shared page once for several slots
    would walk fewer pages, and its count would fall with its time): what
    ``mla_decode_roofline.serve`` divides by the kernel's time a call."""
    return live_tokens * latent_bytes_per_token(sizes, cache_bytes)


def decode_weight_bytes(sizes, experts_touched: float,
                        weight_bytes: int = 2) -> float:
    """The matrices one decode step has to read: every layer's attention,
    the dense layers' MLP, the expert layers' router, shared experts and the
    ``experts_touched`` routed experts that had a row (a mean over expert
    layers and steps, from the program's counters), and the head."""
    nd, ne = layers(sizes)
    elems = (nd * dense_layer_params(sizes)
             + ne * (expert_layer_fixed_params(sizes)
                     + experts_touched * expert_params(sizes))
             + sizes["n_embd"] * sizes["vocab_size"])
    return elems * weight_bytes


def decode_stream_bytes(sizes, experts_touched: float, live_tokens: float,
                        weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step has to move: :func:`decode_weight_bytes` and
    the latents of the ``live_tokens`` resident positions (summed over the
    slots) in every layer. Activations, the embedding's gathered rows and
    norm scales are left out (under 1 %)."""
    return (decode_weight_bytes(sizes, experts_touched, weight_bytes)
            + sizes["n_layer"] * latent_read_bytes_per_layer(
                sizes, live_tokens, cache_bytes))


def latent_bytes_share(sizes, experts_touched: float,
                       live_tokens: float) -> float:
    """The latents' share of :func:`decode_stream_bytes`."""
    return (sizes["n_layer"] * latent_read_bytes_per_layer(sizes, live_tokens)
            / decode_stream_bytes(sizes, experts_touched, live_tokens))


def active_matmul_params(sizes) -> int:
    """Elements of every matrix ONE ROW contracts against in a forward, in
    the published form (its own keys' and values' up-projection among the
    attention matrices): attention everywhere, the dense MLP, the router,
    the row's ``experts_per_token`` experts and the shared ones, the head."""
    nd, ne = layers(sizes)
    per_expert_layer = (
        expert_layer_fixed_params(sizes)
        + sizes["experts_per_token"] * expert_params(sizes))
    return (nd * dense_layer_params(sizes) + ne * per_expert_layer
            + sizes["n_embd"] * sizes["vocab_size"])


def row_forward_flops(sizes, context: float) -> float:
    """One row's forward with ``context`` keys visible to it, in the
    published form: 2 a matrix element, and a layer's scores and values
    ``2 H (nope + rope + v) context`` (whatever the program runs: the
    absorbed form costs 2 x 1,088 a head a key and counts as this)."""
    per_key = sizes["latent_nope"] + sizes["latent_rope"] + sizes["latent_v"]
    attn = 2.0 * sizes["n_layer"] * sizes["n_head"] * per_key * context
    return 2.0 * active_matmul_params(sizes) + attn


def prompt_flops(sizes, prompt: int, cached: int = 0) -> float:
    """One forward over the rows of a prompt that were computed — those
    behind the ``cached`` positions a prefix hit served — each seeing what
    lies before it."""
    rows = prompt - cached
    return rows * row_forward_flops(sizes, cached + (rows + 1) / 2.0)
