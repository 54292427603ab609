"""Pallas TPU flash attention (causal, GQA-aware, custom VJP).

Replaces the reference's naive O(T^2)-memory attention
(/root/reference/src/model.py:71-79) with a blockwise online-softmax kernel:
scores never materialize in HBM; softmax runs in float32 with the 1/sqrt(C)
scale folded into the softmax argument, exactly mirroring the reference
numerics (SURVEY.md 2.3).

Layout is [B, H, T, C] — the only layout Mosaic can block per-head: the
last two block dims must be (multiple-of-8, multiple-of-128-or-full), so a
projection-natural [B, T, H, C] per-head block (1, rows, 1, C) is illegal
on hardware (measured r2; see PERF.md "transpose-free layout post-mortem").
K/V may carry fewer (grouped) heads — the grid maps each Q head to its KV
group, so tensor-parallel head sharding composes (each shard sees a
smaller H).

Forward residual is the standard (out, logsumexp) pair; backward runs two
kernels (dQ over Q blocks; dK/dV over KV blocks) plus a trivial elementwise
delta precomputation.
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG_INF = -1e30  # avoids NaN from (-inf) - (-inf) in fully-masked rows


def _auto_block(t: int, cap: int = 1024) -> int:
    """Largest power-of-two block <= cap that divides T.

    Measured on a v5e-class chip (B=16, H=12, T=1024, C=64, bench_kernels.py):
    fwd 12.3ms @ 128 -> 3.2ms @ 1024; fwd+bwd 19.5ms @ 128 -> 10.2ms @ 1024.
    The dominant cost is per-grid-step matmul issue overhead at tiny blocks,
    so bigger is strictly better until the VMEM working set (~12 MB at 1024
    for the dkv kernel) nears the 16 MB scoped limit."""
    b = cap
    while b > 8 and t % b:
        b //= 2
    return min(b, t)


def _block_sizes(
    t: int, bq: tp.Optional[int], bk: tp.Optional[int], causal: bool
) -> tp.Tuple[int, int]:
    bq = _auto_block(t) if bq is None else min(bq, t)
    bk = _auto_block(t) if bk is None else min(bk, t)
    assert t % bq == 0 and t % bk == 0, (
        f"seq len {t} must be a multiple of block sizes ({bq}, {bk})"
    )
    # the causal block-skip logic compares q/k block indices directly
    assert not causal or bq == bk, (
        f"causal path requires block_q == block_k, got ({bq}, {bk})"
    )
    return bq, bk


def _causal_mask_block(iq, ik, bq: int, bk: int) -> Array:
    """Boolean [bq, bk] mask for the (iq, ik) block pair: True = visible."""
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _wrap32(c: int):
    import numpy as np

    return jnp.int32(np.uint32(c).astype(np.int32))


def _hash_finalize(x: Array) -> Array:
    """murmur3-style 32-bit finalizer (good avalanche) on int32 with
    wrapping arithmetic — plain vector integer ops, so it runs identically
    under Mosaic and the Pallas CPU interpreter (pltpu.prng_* has no
    interpret-mode lowering, which would make dropout untestable here)."""
    srl = jax.lax.shift_right_logical
    x = (x ^ srl(x, 16)) * _wrap32(0x7FEB352D)
    x = (x ^ srl(x, 15)) * _wrap32(0x846CA68B)
    return x ^ srl(x, 16)


def _dropout_keep_block(
    seed, head_id, rows0, cols0, bq: int, bk: int, keep: float
) -> Array:
    """Deterministic Bernoulli(keep) over global score coordinates.

    Element (row, col) of attention head ``head_id`` keeps its probability
    iff hash(seed, head_id, row, col) falls under the keep threshold. The
    same counters regenerate the identical mask in the backward kernels —
    nothing is stored. [bq, bk] bool."""
    rows = rows0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = cols0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    x = rows * _wrap32(0x9E3779B1) + cols * _wrap32(0x85EBCA77)
    x = x ^ (seed + head_id * _wrap32(0xC2B2AE35))
    u24 = _hash_finalize(x) & jnp.int32(0x00FFFFFF)
    return u24 < jnp.int32(int(keep * (1 << 24)))


def _seed_vec(seed, row_off, col_off, bh_off=None) -> Array:
    """[4] int32 SMEM payload: dropout seed + GLOBAL anchors of this
    call's local (0, 0, 0, 0): score row/col offsets and the flat
    ``batch * H_total + head`` base. Anchors let a ring-attention hop or
    a batch/head-sharded call (parallel/ring.py) regenerate the exact
    mask a single-device call would use at the same global coordinates —
    sharded dropout is bit-identical to dense flash dropout."""
    z = jnp.zeros((), jnp.int32)
    r = z if row_off is None else jnp.asarray(row_off, jnp.int32).reshape(())
    c = z if col_off is None else jnp.asarray(col_off, jnp.int32).reshape(())
    bh = z if bh_off is None else jnp.asarray(bh_off, jnp.int32).reshape(())
    return jnp.stack([
        jnp.asarray(seed, jnp.int32).reshape(()), r, c, bh,
    ])


def _struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """pallas_call out_shape inheriting the manual-axes vma of ``like``.

    Inside a ``check_vma=True`` shard_map region (the PP stage region,
    parallel/pipeline.py:169, and the data/TP wrap in ops/attention.py) a
    plain ShapeDtypeStruct fails pallas type-checking; carrying the input
    operand's vma keeps the output varying over the same manual axes."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _act_spec(rows: int, c: int, row_fn, head_fn):
    """BlockSpec for a q/k/v/o/do activation carrying ``rows`` sequence rows.

    ``row_fn(grid indices) -> row-block index``; ``head_fn(h) -> head (or KV
    group) index``. The kernel always sees a [rows, c] tile."""
    return pl.BlockSpec(
        (1, 1, rows, c),
        lambda *g: (g[0], head_fn(g[1]), row_fn(*g), 0),
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    *refs,
    scale: float, causal: bool, bq: int, bk: int, nk: int,
    keep: tp.Optional[float] = None, n_head: int = 0,
):
    if keep is not None:
        seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    iq, ik = pl.program_id(2), pl.program_id(3)
    # program_id must bind OUTSIDE pl.when bodies (no interpret lowering
    # inside the cond); the flat batch-head id seeds the dropout hash
    bh = (
        seed_ref[3] + pl.program_id(0) * n_head + pl.program_id(1)
        if keep is not None
        else None
    )

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    last_k = iq if causal else nk - 1
    run = (ik <= iq) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # [bq, C]
        k = k_ref[0, 0]  # [bk, C]
        v = v_ref[0, 0]  # [bk, C]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        z = s * scale
        if causal:
            # only the diagonal block needs the element-level mask
            z = jnp.where(
                jnp.logical_or(ik != iq, _causal_mask_block(iq, ik, bq, bk)),
                z,
                _NEG_INF,
            )
        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(z, axis=1, keepdims=True)  # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)  # [bq, 1]
        p = jnp.exp(z - m_next)  # [bq, bk] f32
        # l (and thus lse) accumulates the UNDROPPED sum: dropout applies
        # to softmax OUTPUTS (out = (softmax(z) * mask / keep) @ v), so
        # only the value accumulation sees the mask
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        p_acc = p
        if keep is not None:
            mask = _dropout_keep_block(
                seed_ref[0], bh,
                seed_ref[1] + iq * bq, seed_ref[2] + ik * bk, bq, bk, keep,
            )
            p_acc = jnp.where(mask, p * (1.0 / keep), 0.0)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_bcast = jax.lax.broadcast_in_dim(m_next, m_ref.shape, (0, 1))
        l_bcast = jax.lax.broadcast_in_dim(l_next, l_ref.shape, (0, 1))
        m_ref[:] = m_bcast
        l_ref[:] = l_bcast

    @pl.when(ik == last_k)
    def _finalize():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        # causal rows always have >= 1 visible key, so l > 0
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l)


def _flash_forward(
    q: Array, k: Array, v: Array, *, causal: bool, bq: int, bk: int,
    keep: tp.Optional[float] = None, seed: tp.Optional[Array] = None,
    row_off: tp.Optional[Array] = None, col_off: tp.Optional[Array] = None,
    bh_off: tp.Optional[Array] = None, n_head_total: tp.Optional[int] = None,
) -> tp.Tuple[Array, Array]:
    b, h, t, c = q.shape
    _, hkv, s, _ = k.shape
    assert s == t, "self-attention only (use decode path for caches)"
    groups = h // hkv
    bq, bk = _block_sizes(t, bq, bk, causal)
    nq, nk = t // bq, t // bk
    scale = 1.0 / math.sqrt(c)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        keep=keep, n_head=n_head_total or h,
    )
    row_q = lambda b_, h_, iq, ik: iq  # noqa: E731
    # trimmed causal grid: masked (ik > iq) steps are compute-skipped
    # (pl.when); clamping their block index to the diagonal makes them
    # alias the resident block, so they trigger no DMA either (r3)
    if causal:
        row_k = lambda b_, h_, iq, ik: jnp.minimum(ik, iq)  # noqa: E731
    else:
        row_k = lambda b_, h_, iq, ik: ik  # noqa: E731
    kv_head = lambda h_: h_ // groups  # noqa: E731
    q_head = lambda h_: h_  # noqa: E731
    in_specs = [
        _act_spec(bq, c, row_q, q_head),
        _act_spec(bk, c, row_k, kv_head),
        _act_spec(bk, c, row_k, kv_head),
    ]
    operands = (q, k, v)
    if keep is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        operands = (_seed_vec(seed, row_off, col_off, bh_off),) + operands
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            _act_spec(bq, c, row_q, q_head),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_shape=[
            _struct((b, h, t, c), q.dtype, q),
            _struct((b, h, t, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, c), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    *refs,
    scale: float, causal: bool, bq: int, bk: int, nk: int,
    keep: tp.Optional[float] = None, n_head: int = 0,
):
    if keep is not None:
        (seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
    iq, ik = pl.program_id(2), pl.program_id(3)
    bh = (
        seed_ref[3] + pl.program_id(0) * n_head + pl.program_id(1)
        if keep is not None
        else None
    )

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ik <= iq) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [bq, 1] f32
        delta = delta_ref[0, 0]  # [bq, 1] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        z = s * scale
        if causal:
            z = jnp.where(
                jnp.logical_or(ik != iq, _causal_mask_block(iq, ik, bq, bk)),
                z,
                _NEG_INF,
            )
        p = jnp.exp(z - lse)  # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if keep is not None:
            # out = (p * mask/keep) @ v, so dz = p * (mask/keep * dp - delta)
            # with the SAME regenerated mask (delta already absorbs out's
            # dropped entries — it is rowsum(do * out))
            mask = _dropout_keep_block(
                seed_ref[0], bh,
                seed_ref[1] + iq * bq, seed_ref[2] + ik * bk, bq, bk, keep,
            )
            dp = jnp.where(mask, dp * (1.0 / keep), 0.0)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    last_k = iq if causal else nk - 1

    @pl.when(ik == last_k)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs,
    scale: float, causal: bool, bq: int, bk: int, nq: int,
    keep: tp.Optional[float] = None, n_head: int = 0,
):
    if keep is not None:
        (seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
    ik, iq = pl.program_id(2), pl.program_id(3)
    bh = (
        seed_ref[3] + pl.program_id(0) * n_head + pl.program_id(1)
        if keep is not None
        else None
    )

    @pl.when(iq == (ik if causal else 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (iq >= ik) if causal else (iq >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # [bq, C]
        k = k_ref[0, 0]  # [bk, C]
        v = v_ref[0, 0]
        do = do_ref[0, 0]  # [bq, C]
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        z = s * scale
        if causal:
            z = jnp.where(
                jnp.logical_or(ik != iq, _causal_mask_block(iq, ik, bq, bk)),
                z,
                _NEG_INF,
            )
        p = jnp.exp(z - lse)  # [bq, bk]
        p_v = p
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if keep is not None:
            # NOTE transposed grid: this kernel's block rows start at
            # iq * bq (grid is (b, h, ik, iq))
            mask = _dropout_keep_block(
                seed_ref[0], bh,
                seed_ref[1] + iq * bq, seed_ref[2] + ik * bk, bq, bk, keep,
            )
            inv = 1.0 / keep
            p_v = jnp.where(mask, p * inv, 0.0)
            dp = jnp.where(mask, dp * inv, 0.0)
        # dv += (p * mask/keep)^T @ do  -> [bk, C]
        dv_acc[:] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale  # [bq, bk]
        # dk += ds^T @ q -> [bk, C]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(
    q: Array, k: Array, v: Array, out: Array, lse: Array, do: Array,
    *, causal: bool, bq: int, bk: int, dlse: tp.Optional[Array] = None,
    keep: tp.Optional[float] = None, seed: tp.Optional[Array] = None,
    row_off: tp.Optional[Array] = None, col_off: tp.Optional[Array] = None,
    bh_off: tp.Optional[Array] = None, n_head_total: tp.Optional[int] = None,
) -> tp.Tuple[Array, Array, Array]:
    b, h, t, c = q.shape
    hkv = k.shape[1]
    groups = h // hkv
    bq, bk = _block_sizes(t, bq, bk, causal)
    nq, nk = t // bq, t // bk
    scale = 1.0 / math.sqrt(c)
    seed_ops: tp.Tuple[Array, ...] = ()
    seed_specs: tp.List[tp.Any] = []
    if keep is not None:
        seed_ops = (_seed_vec(seed, row_off, col_off, bh_off),)
        seed_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]

    # delta_i = rowsum(dO * O) — cheap elementwise, fused by XLA; stored
    # [B, H, T, 1] (tiny, consumed by the kernels only).
    # When the caller also consumes lse (flash_attention_lse), its
    # cotangent folds in exactly here: dL/dz_ij = p_ij (dp_ij - delta_i
    # + dlse_i), since dlse_i/dz_ij = p_ij — so delta_eff = delta - dlse.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    row_q34 = lambda b_, h_, iq, ik: iq  # noqa: E731 — grid (b,h,iq,ik)
    row_k43 = lambda b_, h_, ik, iq: ik  # noqa: E731 — grid (b,h,ik,iq)
    # trimmed causal grid: skipped steps alias the diagonal block (no DMA)
    if causal:
        row_k34 = lambda b_, h_, iq, ik: jnp.minimum(ik, iq)  # noqa: E731
        row_q43 = lambda b_, h_, ik, iq: jnp.maximum(iq, ik)  # noqa: E731
    else:
        row_k34 = lambda b_, h_, iq, ik: ik  # noqa: E731
        row_q43 = lambda b_, h_, ik, iq: iq  # noqa: E731
    kv_head = lambda h_: h_ // groups  # noqa: E731
    q_head = lambda h_: h_  # noqa: E731

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
            keep=keep, n_head=n_head_total or h,
        ),
        grid=(b, h, nq, nk),
        in_specs=seed_specs + [
            _act_spec(bq, c, row_q34, q_head),
            _act_spec(bk, c, row_k34, kv_head),
            _act_spec(bk, c, row_k34, kv_head),
            _act_spec(bq, c, row_q34, q_head),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_specs=_act_spec(bq, c, row_q34, q_head),
        out_shape=_struct((b, h, t, c), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*seed_ops, q, k, v, do, lse, delta)

    # dK/dV per Q-head (summed over GQA groups afterwards)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nq=nq,
            keep=keep, n_head=n_head_total or h,
        ),
        grid=(b, h, nk, nq),
        in_specs=seed_specs + [
            _act_spec(bq, c, row_q43, q_head),
            _act_spec(bk, c, row_k43, kv_head),
            _act_spec(bk, c, row_k43, kv_head),
            _act_spec(bq, c, row_q43, q_head),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda b_, h_, ik, iq: (b_, h_, row_q43(b_, h_, ik, iq), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda b_, h_, ik, iq: (b_, h_, row_q43(b_, h_, ik, iq), 0),
            ),
        ],
        out_specs=[
            _act_spec(bk, c, row_k43, q_head),
            _act_spec(bk, c, row_k43, q_head),
        ],
        out_shape=[
            _struct((b, h, t, c), k.dtype, q),
            _struct((b, h, t, c), v.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, c), jnp.float32),
            pltpu.VMEM((bk, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*seed_ops, q, k, v, do, lse, delta)

    if groups > 1:
        dk = dk_h.reshape(b, hkv, groups, t, c).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(b, hkv, groups, t, c).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    causal: bool = True,
    block_q: tp.Optional[int] = None,
    block_k: tp.Optional[int] = None,
) -> Array:
    """Flash attention output only — delegates to flash_attention_lse (the
    dropped lse's cotangent instantiates to zeros, making the backward's
    ``delta - dlse`` fold a no-op), so there is a single VJP pair to
    maintain."""
    out, _ = flash_attention_lse(q, k, v, causal, block_q, block_k)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_lse_core(
    q: Array,
    k: Array,
    v: Array,
    seed: Array,      # [] int32 (ignored when rate == 0.0)
    row_off: Array,   # [] int32 — global row of this call's (0,0) score
    col_off: Array,   # [] int32 — global col of this call's (0,0) score
    bh_off: Array,    # [] int32 — global batch*H_total + head of local (0,0)
    rate: float,
    causal: bool,
    block_q: tp.Optional[int],
    block_k: tp.Optional[int],
    n_head_total: tp.Optional[int],
) -> tp.Tuple[Array, Array]:
    """Single VJP pair behind every flash entry point: (out, lse) with a
    differentiable lse (cotangent folds into the backward as
    ``delta - dlse``), optional in-kernel dropout (rate > 0), and global
    score-coordinate offsets so ring hops reproduce the exact
    single-device mask (see _seed_vec)."""
    keep = None if rate == 0.0 else 1.0 - rate
    out, lse = _flash_forward(
        q, k, v, causal=causal, bq=block_q, bk=block_k,
        keep=keep, seed=seed, row_off=row_off, col_off=col_off,
        bh_off=bh_off, n_head_total=n_head_total,
    )
    return out, lse[..., 0]


def _core_vjp_fwd(
    q, k, v, seed, row_off, col_off, bh_off,
    rate, causal, block_q, block_k, n_head_total,
):
    keep = None if rate == 0.0 else 1.0 - rate
    out, lse = _flash_forward(
        q, k, v, causal=causal, bq=block_q, bk=block_k,
        keep=keep, seed=seed, row_off=row_off, col_off=col_off,
        bh_off=bh_off, n_head_total=n_head_total,
    )
    return (out, lse[..., 0]), (
        q, k, v, seed, row_off, col_off, bh_off, out, lse,
    )


def _core_vjp_bwd(rate, causal, block_q, block_k, n_head_total, residuals, cts):
    q, k, v, seed, row_off, col_off, bh_off, out, lse = residuals
    do, dlse = cts
    keep = None if rate == 0.0 else 1.0 - rate
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, do,
        causal=causal, bq=block_q, bk=block_k, dlse=dlse[..., None],
        keep=keep, seed=seed, row_off=row_off, col_off=col_off,
        bh_off=bh_off, n_head_total=n_head_total,
    )
    return dq, dk, dv, None, None, None, None


_flash_lse_core.defvjp(_core_vjp_fwd, _core_vjp_bwd)

def _z() -> Array:
    return jnp.zeros((), jnp.int32)


def flash_attention_lse(
    q: Array,
    k: Array,
    v: Array,
    causal: bool = True,
    block_q: tp.Optional[int] = None,
    block_k: tp.Optional[int] = None,
) -> tp.Tuple[Array, Array]:
    """Flash attention returning (out [B,H,T,C], lse [B,H,T]).

    The lse output is differentiable — its cotangent folds into the
    backward kernels as ``delta - dlse`` (see _flash_backward) — which is
    what lets ring attention (midgpt_tpu.parallel.ring) run this kernel
    per hop and still autodiff through the streaming LSE merge."""
    return _flash_lse_core(
        q, k, v, _z(), _z(), _z(), _z(), 0.0, causal, block_q, block_k, None
    )


def flash_attention_dropout_lse(
    q: Array,
    k: Array,
    v: Array,
    seed: Array,
    rate: float,
    causal: bool = True,
    block_q: tp.Optional[int] = None,
    block_k: tp.Optional[int] = None,
    row_off: tp.Optional[Array] = None,
    col_off: tp.Optional[Array] = None,
    bh_off: tp.Optional[Array] = None,
    n_head_total: tp.Optional[int] = None,
) -> tp.Tuple[Array, Array]:
    """(out, lse) flash attention with in-kernel dropout AND global score
    offsets — the ring-attention hop entry (parallel/ring.py): lse stays
    differentiable through the streaming merge, and (row_off, col_off)
    anchor the hop's mask in GLOBAL coordinates so the full ring pass
    drops exactly the same (head, row, col) set a single-device call
    would."""
    z = _z()
    return _flash_lse_core(
        q, k, v, seed,
        z if row_off is None else row_off,
        z if col_off is None else col_off,
        z if bh_off is None else bh_off,
        rate, causal, block_q, block_k, n_head_total,
    )


def flash_attention_reference(q, k, v, causal=True):
    """jnp oracle with identical math, for tests."""
    from midgpt_tpu.ops.attention import naive_attention

    return naive_attention(q, k, v, causal=causal)


# ---------------------------------------------------------------------------
# Attention dropout (in-kernel mask regeneration, no stored mask)
# ---------------------------------------------------------------------------


def flash_attention_dropout(
    q: Array,
    k: Array,
    v: Array,
    seed: Array,  # [] or [1] int32 — per-call dropout seed
    rate: float,
    causal: bool = True,
    block_q: tp.Optional[int] = None,
    block_k: tp.Optional[int] = None,
) -> Array:
    """Flash attention with ATTENTION dropout: out = (softmax(z) * M/keep) @ v
    with M ~ Bernoulli(keep) regenerated IN-KERNEL from (seed, b*H+h, row,
    col) by a counter-based hash (_dropout_keep_block) — no O(T^2) mask in
    HBM, and the backward kernels rebuild the identical mask from the same
    counters. This removes the last math capability the kernels lacked
    (VERDICT r3 Next #8): shakespeare_char — the only dropout config,
    /root/reference/src/model.py:78 — no longer pins training to naive
    O(T^2) attention.

    The mask stream differs from naive_attention's jax.random.bernoulli
    (different PRNG), so parity tests compare against an oracle built from
    dropout_mask_reference — same hash, dense evaluation."""
    out, _ = _flash_lse_core(
        q, k, v, jnp.asarray(seed, jnp.int32).reshape(()), _z(), _z(), _z(),
        rate, causal, block_q, block_k, None,
    )
    return out


def dropout_mask_reference(
    seed: Array, b: int, h: int, t: int, rate: float
) -> Array:
    """[B, H, T, T] boolean keep-mask — the DENSE evaluation of the exact
    hash the kernels regenerate blockwise. Test oracle only (O(T^2))."""
    keep = 1.0 - rate
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    x = rows * _wrap32(0x9E3779B1) + cols * _wrap32(0x85EBCA77)
    head_ids = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
    x = x[None, None] ^ (
        jnp.asarray(seed, jnp.int32).reshape(()) + head_ids * _wrap32(0xC2B2AE35)
    )
    u24 = _hash_finalize(x) & jnp.int32(0x00FFFFFF)
    return u24 < jnp.int32(int(keep * (1 << 24)))
