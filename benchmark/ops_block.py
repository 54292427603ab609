"""The yardstick's arithmetic for the block-diffusion expert model: what one
forward has to compute and to read, from the configuration's sizes alone
(``n_layer``, ``n_embd``, ``n_head``, ``n_kv_head``, ``head_width``,
``experts``, ``experts_per_token``, ``expert_hidden``, ``vocab_size``,
``block_len``, ``block_steps``). The peaks are :mod:`benchmark.ops`'s."""

from __future__ import annotations


def _attention_params(sizes) -> int:
    d, c = sizes["n_embd"], sizes["head_width"]
    h, hkv = sizes["n_head"], sizes["n_kv_head"]
    return d * (h + 2 * hkv) * c + h * c * d


def _expert_params(sizes) -> int:
    return 3 * sizes["n_embd"] * sizes["expert_hidden"]


def active_matmul_params(sizes) -> int:
    """Elements of every matrix ONE ROW contracts against in a forward:
    attention, the router, its ``experts_per_token`` experts, in each
    layer, and the head (the embedding is a gather)."""
    per_layer = (
        _attention_params(sizes) + sizes["n_embd"] * sizes["experts"]
        + sizes["experts_per_token"] * _expert_params(sizes))
    return sizes["n_layer"] * per_layer + sizes["n_embd"] * sizes["vocab_size"]


def row_forward_flops(sizes, context: float) -> float:
    """One row's forward with ``context`` keys visible to it."""
    attn = 4 * sizes["n_layer"] * sizes["n_head"] * sizes["head_width"] * context
    return 2.0 * active_matmul_params(sizes) + attn


def published_loop_flops(sizes, tokens: float, context: float) -> float:
    """The work of the published generation loop for ``tokens`` generated
    tokens at a mean context of ``context``: a block of ``block_len``
    tokens costs ``block_steps + 1`` forwards of ``block_len`` rows (the
    denoising forwards and the commit pass), so a token costs ``block_steps
    + 1`` row-forwards — whatever the program actually runs: a program that
    fuses or skips a pass moves tokens/s, not this."""
    return tokens * (sizes["block_steps"] + 1) * row_forward_flops(sizes, context)


def prompt_flops(sizes, prompt: int) -> float:
    """One forward over a prompt's rows, each seeing what lies before it."""
    return prompt * row_forward_flops(sizes, (prompt + 1) / 2.0)


def forward_stream_bytes(sizes, experts_touched: float, live_tokens: float,
                         weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one window forward has to read: in every layer the attention
    and router matrices and the matrices of the ``experts_touched`` experts
    (a mean over layers and forwards, from the program's counters) that had
    a row; the head; and K and V of the ``live_tokens`` resident positions
    summed over the slots. Activations and the embedding's gathered rows
    are left out (under 1 % at 128 rows)."""
    dense = _attention_params(sizes) + sizes["n_embd"] * sizes["experts"]
    per_layer = dense + experts_touched * _expert_params(sizes)
    weights = sizes["n_layer"] * per_layer + sizes["n_embd"] * sizes["vocab_size"]
    kv = (live_tokens * 2 * sizes["n_layer"] * sizes["n_kv_head"]
          * sizes["head_width"])
    return weights * weight_bytes + kv * cache_bytes


def expert_matmul_bytes_per_call(sizes, experts_touched: float,
                                 weight_bytes: int = 2) -> float:
    """Bytes one grouped matmul of the expert layer has to read: the
    matrices of the ``experts_touched`` experts that had a row. A layer
    makes two calls, W1 | W3 and then W2, so a call reads half of an
    expert's three matrices on average: what ``expert_gmm_roofline.serve``
    divides by the kernel's time a call. The sorted rows (a few MB) are
    left out."""
    return experts_touched * _expert_params(sizes) / 2.0 * weight_bytes


def verify_kv_bytes_per_layer(sizes, live_tokens: float,
                              cache_bytes: int = 2) -> float:
    """Bytes of K and V one layer's block forward has to read for
    ``live_tokens`` resident positions summed over the slots: what
    ``paged_verify_roofline.serve`` divides by the verify kernel's time."""
    return (live_tokens * 2 * sizes["n_kv_head"] * sizes["head_width"]
            * cache_bytes)
