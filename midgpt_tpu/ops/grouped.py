"""The grouped matmul of the routed-expert layer: rows sorted by group,
``[M, K]`` against ``[G, K, N]`` with the per-group row counts.

Two lowerings of one contraction, by measurement on a v5e (PERF.md section
6, PR 28: 128 groups of ~8 rows, K x N = 2048 x 1536 and 768 x 2048, bf16):
``jax.lax.ragged_dot``, which the TPU compiler lowers to a kernel of its
own, streams the group matrices at a third of the chip's HBM rate (2.7 and
1.6 ms); the Pallas grouped matmul that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``) with K-deep tiles streams
them at four fifths (1.2 and 0.6 ms). So a TPU takes the Pallas kernel
where the shapes are whole tiles, and everything else ``ragged_dot`` (the
CPU, where the tests run; ragged shapes). Both skip a group that
has no rows: the expert layer reads the whole layer stack as L x E groups
(``models.gpt.ExpertMLP``) at no cost. Both differentiate."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from midgpt_tpu.utils.platform import is_tpu_backend

Array = jax.Array

TILE_ROWS = 128
# a group matrix tile of at most this many elements (3 MB in bf16, twice
# that double-buffered): K as deep as it goes first, since a step then
# needs no accumulation across K
_TILE_ELEMS = 2048 * 768


def _largest_divisor(x: int, candidates) -> int:
    for c in candidates:
        if c <= x and x % c == 0:
            return c
    return 0


def tiling(m: int, k: int, n: int):
    """The kernel's (rows, K, N) tile for an ``[m, k] x [G, k, n]``
    product, or None where the shapes are not whole tiles."""
    if m % TILE_ROWS or k % 128 or n % 128:
        return None
    tk = _largest_divisor(k, (2048, 1536, 1024, 768, 512, 256, 128))
    tn = _largest_divisor(
        n, [c for c in (2048, 1024, 512, 256, 128) if c * tk <= _TILE_ELEMS]
    )
    return (TILE_ROWS, tk, tn) if tk and tn else None


def grouped_matmul(
    xs: Array,  # [M, K] rows sorted by group
    w: Array,  # [G, K, N]
    group_sizes: Array,  # [G] int32, sum <= M
    interpret: bool = False,  # a test's choice, never the program's
) -> Array:  # [M, N] in xs.dtype
    m, k = xs.shape
    tiles = tiling(m, k, w.shape[-1])
    if tiles is None or not (interpret or is_tpu_backend()):
        return jax.lax.ragged_dot(xs, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    with jax.named_scope("expert_gmm"):
        # (positional: the kernel's custom VJP takes its static arguments
        # by position)
        return gmm(
            xs, w, group_sizes.astype(jnp.int32), xs.dtype, tiles,
            None, None, False, interpret,
        )
