"""The yardstick's arithmetic: the one table of peaks, and the functions
that count a model's operations and bytes from its sizes alone.

Copies of ``midgpt_tpu.utils.metrics.flops_per_token`` /
``decode_flops_per_token`` and, per layer, of
``midgpt_tpu.analysis.traffic.kv_stream_bytes`` (a later PR may change the
program's, not the yardstick's). ``sizes`` is a
configuration file's dict: ``n_layer``, ``n_head``, ``n_embd``,
``vocab_size``, ``block_size``, ``mlp_ratio``.
"""

from __future__ import annotations

import typing as tp

# One chip's published peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e", system architecture
# (https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s in bf16, 16 GB of
# HBM at 819 GB/s. JAX calls the v5e "TPU v5 lite".
PEAKS: tp.Dict[str, tp.Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9},
}


class UnknownDevice(LookupError):
    """A device kind with no row in :data:`PEAKS`. There is no default."""


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise UnknownDevice(
            f"no published {what!r} peak for device kind {device_kind!r}; "
            f"add a row to benchmark/ops.py:PEAKS with its source"
        ) from None


def head_dim(sizes) -> int:
    return sizes["n_embd"] // sizes["n_head"]


def mlp_hidden(sizes) -> int:
    return int(sizes.get("mlp_ratio", 4.0) * sizes["n_embd"])


def matmul_params(sizes) -> int:
    """Elements of every matrix a forward pass contracts against: the block
    projections and the head (the embedding is a gather)."""
    d = sizes["n_embd"]
    per_layer = d * 3 * d + d * d + 2 * d * mlp_hidden(sizes)
    return sizes["n_layer"] * per_layer + d * sizes["vocab_size"]


def total_params(sizes) -> int:
    """Every stored element: matrices, the embedding, the QK-norm scales."""
    return (
        matmul_params(sizes)
        + sizes["vocab_size"] * sizes["n_embd"]
        + sizes["n_layer"] * 2 * head_dim(sizes)
    )


def train_flops_per_token(sizes, seq_len: tp.Optional[int] = None) -> float:
    """Forward + backward, 6 per matrix element plus causal attention
    (two T x C matmuls a head, halved). Recomputation is not counted."""
    t = seq_len or sizes["block_size"]
    attn = 6 * 2 * sizes["n_layer"] * sizes["n_embd"] * t / 2
    return 6.0 * matmul_params(sizes) + attn


def forward_flops_per_token(sizes, context: float) -> float:
    """One token's forward pass with ``context`` keys visible to it."""
    attn = 4 * sizes["n_layer"] * sizes["n_embd"] * context
    return 2.0 * matmul_params(sizes) + attn


def forward_flops_of_sequence(sizes, first: int, last: int) -> float:
    """Forward FLOPs of the tokens at positions ``first`` .. ``last - 1``
    of one sequence, each attending to everything up to itself."""
    n = max(0, last - first)
    mean_ctx = (first + last + 1) / 2.0
    return n * forward_flops_per_token(sizes, mean_ctx)


def attn_train_flops_per_layer(sizes, batch: int, seq_len: int) -> float:
    """Forward + backward FLOPs of one layer's causal attention (QK^T and
    PV forward: 4 T C a head and query, halved by the mask; backward twice
    that, recomputation not counted) for ``batch`` rows of ``seq_len``:
    what ``attn_roofline.train`` divides by the kernels' time."""
    fwd = 4 * sizes["n_embd"] * seq_len * seq_len / 2
    return 3.0 * fwd * batch


def kv_read_bytes_per_layer(sizes, live_tokens: float,
                            cache_bytes: int = 2) -> float:
    """Bytes of K and V one layer's decode attention has to read for
    ``live_tokens`` resident positions summed over its slots: what
    ``paged_attn_roofline.serve`` divides by the paged kernel's time."""
    return live_tokens * 2 * sizes["n_embd"] * cache_bytes
