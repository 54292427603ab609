"""Projection-natural fused attention: QK-LayerNorm + RoPE + flash in one
Pallas kernel family, reading and writing ``[B, T, H*C]`` (the layout the
QKV projection produces) instead of ``[B, H, T, C]``.

Why this exists (r3, PERF.md): at the 124M shape the flash kernel itself is
at the platform ceiling, but the step pays ~38 ms of *surroundings* — the
QK-LayerNorm forward+backward and RoPE loop fusions plus four
[B,T,H,C]<->[B,H,T,C] transposes per layer. This kernel eliminates all of
it: the prologue of every block recomputes LN (f32) and RoPE (as a [C,C]
signed-permutation matmul, bit-identical to rotate-every-two — see
models/layers.py:_rotation_matrix) on the fly, and gradients flow back to
the raw projection output and the LN weights without any intermediate
[B,H,T,C] arrays existing in HBM.

Layout trick: a per-head block of a natural [B,T,H,C] array is (1, rows,
1, C) — illegal on TPU (Mosaic needs the last two block dims to be
(multiple-of-8, multiple-of-128-or-full); measured r2, PERF.md
"transpose-free post-mortem"). Treating the array as [B, T, H*C] and
blocking the LANE dim at 128 is legal — so for C=64 each grid step owns
TWO heads (a 128-lane "head pair"), and for C>=128 exactly one. Blocks
are [rows, 128] regardless of model width, so VMEM stays ~3 MB per step
even at D=4096.

Supported: C a multiple of 128 with any GQA grouping, or C == 64 with MHA
(a C=64 head-pair maps to one 128-lane KV block only when Hkv == H).
Callers fall back to ops.flash otherwise (ops/attention.py dispatch).

LN-weight grads: each backward kernel accumulates per-row partials
``sum_h dnorm * xhat`` into a [B, T, C] output resident across the head
grid dim; the [C] gradient is a cheap XLA reduction outside.

Numerics: LN and softmax in f32; RoPE in f32 before casting to the input
dtype for the MXU matmuls. The reference path (model.py:34-81 equivalent:
LayerNorm in input dtype) differs by bf16 rounding only.
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp

import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from midgpt_tpu.models.layers import _rotation_matrix
from midgpt_tpu.ops.flash import _auto_block, _causal_mask_block

Array = jax.Array

_NEG_INF = -1e30


def supported(n_head: int, n_kv_head: int, head_dim: int) -> bool:
    """Shapes this kernel family handles; callers fall back to ops.flash."""
    if n_head % n_kv_head != 0:
        return False  # GQA group size must be integral (matches ops.attention)
    if head_dim % 128 == 0:
        return True
    return head_dim == 64 and n_head == n_kv_head and n_head % 2 == 0


# Per-direction block caps, keyed by heads-per-block. Measured in the full
# 124M train step (B=24, r3): 1024 blocks everywhere + a 64M vmem budget
# -> 236 ms/step; dkv capped to 512 under the default 16M budget -> 267 ms.
# The hpb==2 backward bodies keep two [bq,bk] f32 score/prob/ds sets alive
# (17.03M scoped at 1024 blocks), hence the raised vmem_limit_bytes below.
# hpb=1 (C>=128) caps at 2048: lets T=2048 (llama family) take the
# single-block COMBINED backward — measured 61.0% -> 62.7% MFU on the
# llama rung (r3); the [2048,2048] f32 temps fit the raised vmem budget.
_FWD_CAP = {1: 2048, 2: 1024}
_BWD_DQ_CAP = {1: 2048, 2: 1024}
_BWD_DKV_CAP = {1: 1024, 2: 1024}

# measurement escape hatch (r5, VERDICT r4 Next #2): raise the dkv cap
# from one env var so the llama rung's 2048-block dkv can be timed in a
# single command without editing the table. Kept out of the default path
# until a chip measurement lands — at 2048 the hpb=1 dkv body's
# [2048, 2048] f32 temps brush the raised VMEM budget.
import os as _os

if _os.environ.get("MIDGPT_DKV_CAP"):
    _BWD_DKV_CAP = {k: int(_os.environ["MIDGPT_DKV_CAP"]) for k in _BWD_DKV_CAP}


def _ln_rope(x, w_ref, sin_ref, cos_ref, rot_ref, eps: float):
    """f32 LayerNorm (mean-subtract, weight, no bias) + interleaved RoPE on
    one [rows, C] head slice. Returns (roped f32, xhat f32, rstd f32)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centered = x - mean
    var = jnp.mean(jnp.square(centered), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = centered * rstd
    ln = xhat * w_ref[0]
    sin = sin_ref[...]
    cos = cos_ref[...]
    rot = rot_ref[...]
    roped = ln * cos + jax.lax.dot_general(
        ln, rot, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * sin
    return roped, xhat, rstd


def _ln_rope_bwd(d_roped, xhat, rstd, w_ref, sin_ref, cos_ref, rot_ref):
    """VJP through RoPE then LN for one [rows, C] head slice.

    Returns (dx_raw f32, dw_rows f32) where dw_rows = dnorm * xhat (summed
    over heads by the caller, over rows/batch outside the kernel)."""
    sin = sin_ref[...]
    cos = cos_ref[...]
    rot = rot_ref[...]
    # roped = ln*cos + (ln@R)*sin  ->  d_ln = d*cos + (d*sin)@R^T
    d_ln = d_roped * cos + jax.lax.dot_general(
        d_roped * sin, rot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    w = w_ref[0]
    dw_rows = d_ln * xhat  # d/dw of (xhat*w), per row
    dxhat = d_ln * w
    # LN backward: dx = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat*xhat))
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = rstd * (dxhat - m1 - xhat * m2)
    return dx, dw_rows


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, wq_ref, wk_ref, sq_ref, cq_ref, sk_ref, ck_ref,
    rot_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, bq: int, bk: int, nk: int, hpb: int,
    c: int, eps: float,
):
    iq, ik = pl.program_id(1), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    last_k = iq if causal else nk - 1
    run = (ik <= iq) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q2 = q_ref[0].astype(jnp.float32)  # [bq, hpb*C]
        k2 = k_ref[0].astype(jnp.float32)  # [bk, hpb*C]
        v2 = v_ref[0]  # [bk, hpb*C] input dtype
        for a in range(hpb):
            sl = slice(a * c, (a + 1) * c)
            qh, _, _ = _ln_rope(q2[:, sl], wq_ref, sq_ref, cq_ref, rot_ref, eps)
            kh, _, _ = _ln_rope(k2[:, sl], wk_ref, sk_ref, ck_ref, rot_ref, eps)
            vh = v2[:, sl]
            s = jax.lax.dot_general(
                qh.astype(v2.dtype), kh.astype(v2.dtype),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )  # [bq, bk]
            z = s * scale
            if causal:
                z = jnp.where(
                    jnp.logical_or(ik != iq, _causal_mask_block(iq, ik, bq, bk)),
                    z,
                    _NEG_INF,
                )
            m_prev = m_ref[a][:, :1]
            l_prev = l_ref[a][:, :1]
            m_cur = jnp.max(z, axis=1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(z - m_next)
            l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[a] = jax.lax.broadcast_in_dim(m_next, m_ref[a].shape, (0, 1))
            l_ref[a] = jax.lax.broadcast_in_dim(l_next, l_ref[a].shape, (0, 1))

    @pl.when(ik == last_k)
    def _finalize():
        for a in range(hpb):
            sl = slice(a * c, (a + 1) * c)
            m = m_ref[a][:, :1]
            l = l_ref[a][:, :1]
            o_ref[0, :, sl] = (acc_ref[:, sl] / l).astype(o_ref.dtype)
            lse_ref[0, a] = m + jnp.log(l)


def _fused_forward(q, k, v, wq, wk, sin, cos, *, n_head, n_kv_head, causal,
                   bq, bk, head_dim=None, koff=0, voff=0, eps=1e-6):
    """koff/voff: lane-block offsets of K and V inside their arrays — 0 for
    split q/k/v inputs; the packed-qkv entry passes the SAME [B,T,F] array
    as q, k and v with offsets, so no slice copies ever happen."""
    b, t, _ = q.shape
    c = head_dim if head_dim is not None else q.shape[-1] // n_head
    hpb = 2 if c == 64 else 1
    h2 = n_head // hpb
    groups = n_head // n_kv_head
    bq = _auto_block(t, _FWD_CAP[hpb]) if bq is None else min(bq, t)
    bk = _auto_block(t, _FWD_CAP[hpb]) if bk is None else min(bk, t)
    assert t % bq == 0 and t % bk == 0
    assert not causal or bq == bk
    nq, nk = t // bq, t // bk
    scale = 1.0 / math.sqrt(c)

    rot = jnp.asarray(_rotation_matrix(c, "float32"))
    sin_f = jnp.asarray(sin, jnp.float32)
    cos_f = jnp.asarray(cos, jnp.float32)
    wq2 = wq.astype(jnp.float32).reshape(1, c)
    wk2 = wk.astype(jnp.float32).reshape(1, c)

    lanes = hpb * c  # always a multiple of 128 (or full C)

    # kv head-block index for a q head-block: hpb==2 requires MHA (checked
    # in `supported`), so the pair maps 1:1; hpb==1 maps h -> h // groups.
    kv_of = (lambda g: g) if hpb == 2 else (lambda g: g // groups)
    # trimmed causal grid: steps with ik > iq are compute-skipped (pl.when);
    # clamping their data indices to the diagonal block makes them alias the
    # block already resident, so the skipped steps also trigger NO DMA.
    kclamp = (lambda ik, iq: jnp.minimum(ik, iq)) if causal else (
        lambda ik, iq: ik
    )

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        hpb=hpb, c=c, eps=eps,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, nq, h2, nk),
        in_specs=[
            pl.BlockSpec((1, bq, lanes), lambda b_, iq, g, ik: (b_, iq, g)),
            pl.BlockSpec(
                (1, bk, lanes),
                lambda b_, iq, g, ik: (b_, kclamp(ik, iq), koff + kv_of(g)),
            ),
            pl.BlockSpec(
                (1, bk, lanes),
                lambda b_, iq, g, ik: (b_, kclamp(ik, iq), voff + kv_of(g)),
            ),
            pl.BlockSpec((1, c), lambda *g: (0, 0)),  # wq
            pl.BlockSpec((1, c), lambda *g: (0, 0)),  # wk
            pl.BlockSpec((bq, c), lambda b_, iq, g, ik: (iq, 0)),  # sin_q
            pl.BlockSpec((bq, c), lambda b_, iq, g, ik: (iq, 0)),  # cos_q
            pl.BlockSpec(
                (bk, c), lambda b_, iq, g, ik: (kclamp(ik, iq), 0)
            ),  # sin_k
            pl.BlockSpec(
                (bk, c), lambda b_, iq, g, ik: (kclamp(ik, iq), 0)
            ),  # cos_k
            pl.BlockSpec((c, c), lambda *g: (0, 0)),  # rot
        ],
        out_specs=[
            pl.BlockSpec((1, bq, lanes), lambda b_, iq, g, ik: (b_, iq, g)),
            pl.BlockSpec((1, hpb, bq, 1), lambda b_, iq, g, ik: (b_, g, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, n_head * c), q.dtype),
            jax.ShapeDtypeStruct((b, n_head, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, lanes), jnp.float32),
            pltpu.VMEM((hpb, bq, 128), jnp.float32),
            pltpu.VMEM((hpb, bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            # the hpb==2 bodies carry two [bq,bk] f32 temp sets; the default
            # 16M scoped-VMEM budget rejects 1024 blocks (17.03M measured)
            # while the chip has 128M physical VMEM. 64M keeps 1024 blocks.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(q, k, v, wq2, wk2, sin_f, cos_f, sin_f, cos_f, rot)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, wq_ref, wk_ref,
    sq_ref, cq_ref, sk_ref, ck_ref, rot_ref,
    dq_ref, dwq_ref, dq_acc, dwq_acc,
    *, scale: float, causal: bool, bq: int, bk: int, nk: int, nh2: int,
    hpb: int, c: int, eps: float,
):
    iq, g, ik = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(jnp.logical_and(g == 0, ik == 0))
    def _init_dw():
        dwq_acc[:] = jnp.zeros_like(dwq_acc)

    run = (ik <= iq) if causal else (ik >= 0)

    @pl.when(run)
    def _compute():
        q2 = q_ref[0].astype(jnp.float32)
        k2 = k_ref[0].astype(jnp.float32)
        v2 = v_ref[0]
        do2 = do_ref[0].astype(jnp.float32)
        for a in range(hpb):
            sl = slice(a * c, (a + 1) * c)
            qh, _, _ = _ln_rope(q2[:, sl], wq_ref, sq_ref, cq_ref, rot_ref, eps)
            kh, _, _ = _ln_rope(k2[:, sl], wk_ref, sk_ref, ck_ref, rot_ref, eps)
            vh = v2[:, sl]
            lse = lse_ref[0, a]  # [bq, 1]
            delta = delta_ref[0, a]
            s = jax.lax.dot_general(
                qh.astype(v2.dtype), kh.astype(v2.dtype),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
            z = s * scale
            if causal:
                z = jnp.where(
                    jnp.logical_or(ik != iq, _causal_mask_block(iq, ik, bq, bk)),
                    z,
                    _NEG_INF,
                )
            p = jnp.exp(z - lse)
            dp = jax.lax.dot_general(
                do2[:, sl].astype(v2.dtype), vh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta) * scale
            dq_acc[:, sl] += jax.lax.dot_general(
                ds.astype(v2.dtype), kh.astype(v2.dtype),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )

    last_k = iq if causal else nk - 1

    @pl.when(ik == last_k)
    def _finalize():
        q2 = q_ref[0].astype(jnp.float32)
        for a in range(hpb):
            sl = slice(a * c, (a + 1) * c)
            _, xhat, rstd = _ln_rope(q2[:, sl], wq_ref, sq_ref, cq_ref, rot_ref, eps)
            dx, dw_rows = _ln_rope_bwd(
                dq_acc[:, sl], xhat, rstd, wq_ref, sq_ref, cq_ref, rot_ref
            )
            dq_ref[0, :, sl] = dx.astype(dq_ref.dtype)
            dwq_acc[:] += dw_rows

    @pl.when(jnp.logical_and(g == nh2 - 1, ik == last_k))
    def _flush_dw():
        dwq_ref[0] = dwq_acc[:]


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, wq_ref, wk_ref,
    sq_ref, cq_ref, sk_ref, ck_ref, rot_ref,
    dk_ref, dv_ref, dwk_ref, dk_acc, dv_acc, dwk_acc,
    *, scale: float, causal: bool, bq: int, bk: int, nq: int, nh2: int,
    hpb: int, c: int, eps: float,
):
    ik, g, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first_q = ik if causal else 0

    @pl.when(iq == first_q)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(jnp.logical_and(g == 0, iq == first_q))
    def _init_dw():
        dwk_acc[:] = jnp.zeros_like(dwk_acc)

    run = (iq >= ik) if causal else (iq >= 0)

    @pl.when(run)
    def _compute():
        q2 = q_ref[0].astype(jnp.float32)
        k2 = k_ref[0].astype(jnp.float32)
        v2 = v_ref[0]
        do2 = do_ref[0].astype(jnp.float32)
        for a in range(hpb):
            sl = slice(a * c, (a + 1) * c)
            qh, _, _ = _ln_rope(q2[:, sl], wq_ref, sq_ref, cq_ref, rot_ref, eps)
            kh, _, _ = _ln_rope(k2[:, sl], wk_ref, sk_ref, ck_ref, rot_ref, eps)
            vh = v2[:, sl]
            lse = lse_ref[0, a]
            delta = delta_ref[0, a]
            s = jax.lax.dot_general(
                qh.astype(v2.dtype), kh.astype(v2.dtype),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
            z = s * scale
            if causal:
                z = jnp.where(
                    jnp.logical_or(ik != iq, _causal_mask_block(iq, ik, bq, bk)),
                    z,
                    _NEG_INF,
                )
            p = jnp.exp(z - lse)  # [bq, bk]
            doh = do2[:, sl].astype(v2.dtype)
            dv_acc[:, sl] += jax.lax.dot_general(
                p.astype(v2.dtype), doh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                doh, vh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta) * scale  # [bq, bk]
            dk_acc[:, sl] += jax.lax.dot_general(
                ds.astype(v2.dtype), qh.astype(v2.dtype),
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )

    @pl.when(iq == nq - 1)
    def _finalize():
        k2 = k_ref[0].astype(jnp.float32)
        for a in range(hpb):
            sl = slice(a * c, (a + 1) * c)
            _, xhat, rstd = _ln_rope(k2[:, sl], wk_ref, sk_ref, ck_ref, rot_ref, eps)
            dx, dw_rows = _ln_rope_bwd(
                dk_acc[:, sl], xhat, rstd, wk_ref, sk_ref, ck_ref, rot_ref
            )
            dk_ref[0, :, sl] = dx.astype(dk_ref.dtype)
            dv_ref[0, :, sl] = dv_acc[:, sl].astype(dv_ref.dtype)
            dwk_acc[:] += dw_rows

    @pl.when(jnp.logical_and(g == nh2 - 1, iq == nq - 1))
    def _flush_dw():
        dwk_ref[0] = dwk_acc[:]


def _bwd_combined_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, out_ref, wq_ref, wk_ref,
    sq_ref, cq_ref, sk_ref, ck_ref, rot_ref,
    dq_ref, dk_ref, dv_ref, dwq_ref, dwk_ref, dwq_acc, dwk_acc,
    *, scale: float, causal: bool, t: int, nh2: int, hpb: int, c: int,
    eps: float,
):
    """Single-pass backward for the whole-sequence-in-one-block case
    (nq == nk == 1, i.e. T <= the block cap). Computes the score matrix and
    softmax ONCE and emits dq, dk, dv together — 5 block matmuls instead of
    the 7 the two-kernel path pays (QK^T and dO@V^T are otherwise
    recomputed), a 2/7 FLOP cut on the dominant bucket (r3 profile: the
    backward kernels are 68.5 of 236 ms at the 124M shape)."""
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init_dw():
        dwq_acc[:] = jnp.zeros_like(dwq_acc)
        dwk_acc[:] = jnp.zeros_like(dwk_acc)

    q2 = q_ref[0].astype(jnp.float32)
    k2 = k_ref[0].astype(jnp.float32)
    v2 = v_ref[0]
    do2 = do_ref[0]
    for a in range(hpb):
        sl = slice(a * c, (a + 1) * c)
        qh, q_xhat, q_rstd = _ln_rope(
            q2[:, sl], wq_ref, sq_ref, cq_ref, rot_ref, eps
        )
        kh, k_xhat, k_rstd = _ln_rope(
            k2[:, sl], wk_ref, sk_ref, ck_ref, rot_ref, eps
        )
        vh = v2[:, sl]
        doh = do2[:, sl]
        lse = lse_ref[0, a]  # [t, 1]
        # delta_i = rowsum(dO * O) for this head — computed in-kernel from
        # blocks already resident (saves the ~5 ms XLA mul/reduce pass)
        delta = jnp.sum(
            doh.astype(jnp.float32) * out_ref[0, :, sl].astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )
        qh_c = qh.astype(v2.dtype)
        kh_c = kh.astype(v2.dtype)
        s = jax.lax.dot_general(
            qh_c, kh_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [t, t]
        z = s * scale
        if causal:
            z = jnp.where(_causal_mask_block(0, 0, t, t), z, _NEG_INF)
        p = jnp.exp(z - lse)
        p_c = p.astype(v2.dtype)
        dv_h = jax.lax.dot_general(
            p_c, doh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [t, C]
        dp = jax.lax.dot_general(
            doh, vh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [t, t]
        ds = p * (dp - delta) * scale
        ds_c = ds.astype(v2.dtype)
        dq_rot = jax.lax.dot_general(
            ds_c, kh_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_rot = jax.lax.dot_general(
            ds_c, qh_c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_raw, dwq_rows = _ln_rope_bwd(
            dq_rot, q_xhat, q_rstd, wq_ref, sq_ref, cq_ref, rot_ref
        )
        dk_raw, dwk_rows = _ln_rope_bwd(
            dk_rot, k_xhat, k_rstd, wk_ref, sk_ref, ck_ref, rot_ref
        )
        dq_ref[0, :, sl] = dq_raw.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk_raw.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv_h.astype(dv_ref.dtype)
        dwq_acc[:] += dwq_rows
        dwk_acc[:] += dwk_rows

    @pl.when(g == nh2 - 1)
    def _flush_dw():
        dwq_ref[0] = dwq_acc[:]
        dwk_ref[0] = dwk_acc[:]


def _fused_backward_combined(q, k, v, wq, wk, sin, cos, lse, do, out, *,
                             n_head, n_kv_head, c, hpb, koff, voff, causal,
                             eps=1e-6):
    b, t, _ = q.shape
    h2 = n_head // hpb
    groups = n_head // n_kv_head
    lanes = hpb * c
    scale = 1.0 / math.sqrt(c)

    rot = jnp.asarray(_rotation_matrix(c, "float32"))
    sin_f = jnp.asarray(sin, jnp.float32)
    cos_f = jnp.asarray(cos, jnp.float32)
    wq2 = wq.astype(jnp.float32).reshape(1, c)
    wk2 = wk.astype(jnp.float32).reshape(1, c)

    kv_of = (lambda g: g) if hpb == 2 else (lambda g: g // groups)
    wspec = pl.BlockSpec((1, c), lambda *g: (0, 0))
    rspec = pl.BlockSpec((c, c), lambda *g: (0, 0))
    tspec = pl.BlockSpec((t, c), lambda *g: (0, 0))

    act = lambda off: pl.BlockSpec(  # noqa: E731
        (1, t, lanes), lambda b_, g: (b_, 0, off(g))
    )
    dq, dk_h, dv_h, dwq_rows, dwk_rows = pl.pallas_call(
        functools.partial(
            _bwd_combined_kernel, scale=scale, causal=causal, t=t, nh2=h2,
            hpb=hpb, c=c, eps=eps,
        ),
        grid=(b, h2),
        in_specs=[
            act(lambda g: g),
            act(lambda g: koff + kv_of(g)),
            act(lambda g: voff + kv_of(g)),
            act(lambda g: g),
            pl.BlockSpec((1, hpb, t, 1), lambda b_, g: (b_, g, 0, 0)),
            pl.BlockSpec((1, t, lanes), lambda b_, g: (b_, 0, g)),  # out
            wspec, wspec, tspec, tspec, tspec, tspec, rspec,
        ],
        out_specs=[
            act(lambda g: g),
            act(lambda g: g),
            act(lambda g: g),
            pl.BlockSpec((1, t, c), lambda b_, g: (b_, 0, 0)),
            pl.BlockSpec((1, t, c), lambda b_, g: (b_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, n_head * c), q.dtype),
            jax.ShapeDtypeStruct((b, t, n_head * c), k.dtype),
            jax.ShapeDtypeStruct((b, t, n_head * c), v.dtype),
            jax.ShapeDtypeStruct((b, t, c), jnp.float32),
            jax.ShapeDtypeStruct((b, t, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, c), jnp.float32),
            pltpu.VMEM((t, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(q, k, v, do, lse, out, wq2, wk2, sin_f, cos_f, sin_f, cos_f, rot)
    return dq, dk_h, dv_h, dwq_rows, dwk_rows


def _fused_backward(q, k, v, wq, wk, sin, cos, out, lse, do, *, n_head,
                    n_kv_head, causal, bq, bk, head_dim=None, koff=0,
                    voff=0, eps=1e-6):
    b, t, _ = q.shape
    c = head_dim if head_dim is not None else q.shape[-1] // n_head
    hpb = 2 if c == 64 else 1
    h2 = n_head // hpb
    groups = n_head // n_kv_head
    bq_dq = _auto_block(t, _BWD_DQ_CAP[hpb]) if bq is None else min(bq, t)
    bq_kv = _auto_block(t, _BWD_DKV_CAP[hpb]) if bq is None else min(bq, t)
    if causal or bk is None:
        bk_dq, bk_kv = bq_dq, bq_kv  # causal block-skip compares indices 1:1
    else:
        bk_dq = bk_kv = min(bk, t)
    scale = 1.0 / math.sqrt(c)
    lanes = hpb * c

    rot = jnp.asarray(_rotation_matrix(c, "float32"))
    sin_f = jnp.asarray(sin, jnp.float32)
    cos_f = jnp.asarray(cos, jnp.float32)
    wq2 = wq.astype(jnp.float32).reshape(1, c)
    wk2 = wk.astype(jnp.float32).reshape(1, c)

    if bq is None and bk is None and t <= _BWD_DQ_CAP[hpb]:
        # whole sequence in one block: single-pass combined kernel (which
        # also computes delta = rowsum(dO*O) in-kernel)
        dq, dk_h, dv_h, dwq_rows, dwk_rows = _fused_backward_combined(
            q, k, v, wq, wk, sin, cos, lse, do, out, n_head=n_head,
            n_kv_head=n_kv_head, c=c, hpb=hpb, koff=koff, voff=voff,
            causal=causal, eps=eps,
        )
        return _bwd_epilogue(
            dk_h, dv_h, dq, dwq_rows, dwk_rows, b, t, n_head, n_kv_head, c,
            groups, k.dtype, v.dtype, wq.dtype, wk.dtype,
        )

    # delta_i = rowsum(dO * O) per head, [B, H, T, 1] f32 (tiny)
    prod = (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        b, t, n_head, c
    )
    delta = jnp.transpose(prod.sum(-1), (0, 2, 1))[..., None]

    kv_of = (lambda g: g) if hpb == 2 else (lambda g: g // groups)
    # trimmed causal grid (see _fused_forward): skipped steps alias the
    # diagonal block so they cost no DMA
    kcl = (lambda ik, iq: jnp.minimum(ik, iq)) if causal else (
        lambda ik, iq: ik
    )
    qcl = (lambda iq, ik: jnp.maximum(iq, ik)) if causal else (
        lambda iq, ik: iq
    )

    wspec = pl.BlockSpec((1, c), lambda *g: (0, 0))
    rspec = pl.BlockSpec((c, c), lambda *g: (0, 0))

    # ---- dQ + dwq: grid (b, iq, h2, ik) --------------------------------
    bq, bk = bq_dq, bk_dq
    nq, nk = t // bq, t // bk
    sq_q = pl.BlockSpec((bq, c), lambda b_, iq, g, ik: (iq, 0))
    sk_q = pl.BlockSpec((bk, c), lambda b_, iq, g, ik: (kcl(ik, iq), 0))
    dq, dwq_rows = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
            nh2=h2, hpb=hpb, c=c, eps=eps,
        ),
        grid=(b, nq, h2, nk),
        in_specs=[
            pl.BlockSpec((1, bq, lanes), lambda b_, iq, g, ik: (b_, iq, g)),
            pl.BlockSpec(
                (1, bk, lanes),
                lambda b_, iq, g, ik: (b_, kcl(ik, iq), koff + kv_of(g)),
            ),
            pl.BlockSpec(
                (1, bk, lanes),
                lambda b_, iq, g, ik: (b_, kcl(ik, iq), voff + kv_of(g)),
            ),
            pl.BlockSpec((1, bq, lanes), lambda b_, iq, g, ik: (b_, iq, g)),
            pl.BlockSpec((1, hpb, bq, 1), lambda b_, iq, g, ik: (b_, g, iq, 0)),
            pl.BlockSpec((1, hpb, bq, 1), lambda b_, iq, g, ik: (b_, g, iq, 0)),
            wspec, wspec, sq_q, sq_q, sk_q, sk_q, rspec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, lanes), lambda b_, iq, g, ik: (b_, iq, g)),
            pl.BlockSpec((1, bq, c), lambda b_, iq, g, ik: (b_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, n_head * c), q.dtype),
            jax.ShapeDtypeStruct((b, t, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, lanes), jnp.float32),
            pltpu.VMEM((bq, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            # the hpb==2 bodies carry two [bq,bk] f32 temp sets; the default
            # 16M scoped-VMEM budget rejects 1024 blocks (17.03M measured)
            # while the chip has 128M physical VMEM. 64M keeps 1024 blocks.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(q, k, v, do, lse, delta, wq2, wk2, sin_f, cos_f, sin_f, cos_f, rot)

    # ---- dK/dV (per q-head) + dwk: grid (b, ik, h2, iq) ----------------
    bq, bk = bq_kv, bk_kv
    nq, nk = t // bq, t // bk
    sq_k = pl.BlockSpec((bq, c), lambda b_, ik, g, iq: (qcl(iq, ik), 0))
    sk_k = pl.BlockSpec((bk, c), lambda b_, ik, g, iq: (ik, 0))
    dk_h, dv_h, dwk_rows = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nq=nq,
            nh2=h2, hpb=hpb, c=c, eps=eps,
        ),
        grid=(b, nk, h2, nq),
        in_specs=[
            pl.BlockSpec(
                (1, bq, lanes), lambda b_, ik, g, iq: (b_, qcl(iq, ik), g)
            ),
            pl.BlockSpec(
                (1, bk, lanes), lambda b_, ik, g, iq: (b_, ik, koff + kv_of(g))
            ),
            pl.BlockSpec(
                (1, bk, lanes), lambda b_, ik, g, iq: (b_, ik, voff + kv_of(g))
            ),
            pl.BlockSpec(
                (1, bq, lanes), lambda b_, ik, g, iq: (b_, qcl(iq, ik), g)
            ),
            pl.BlockSpec(
                (1, hpb, bq, 1), lambda b_, ik, g, iq: (b_, g, qcl(iq, ik), 0)
            ),
            pl.BlockSpec(
                (1, hpb, bq, 1), lambda b_, ik, g, iq: (b_, g, qcl(iq, ik), 0)
            ),
            wspec, wspec, sq_k, sq_k, sk_k, sk_k, rspec,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, lanes), lambda b_, ik, g, iq: (b_, ik, g)),
            pl.BlockSpec((1, bk, lanes), lambda b_, ik, g, iq: (b_, ik, g)),
            pl.BlockSpec((1, bk, c), lambda b_, ik, g, iq: (b_, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, n_head * c), k.dtype),
            jax.ShapeDtypeStruct((b, t, n_head * c), v.dtype),
            jax.ShapeDtypeStruct((b, t, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, lanes), jnp.float32),
            pltpu.VMEM((bk, lanes), jnp.float32),
            pltpu.VMEM((bk, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            # the hpb==2 bodies carry two [bq,bk] f32 temp sets; the default
            # 16M scoped-VMEM budget rejects 1024 blocks (17.03M measured)
            # while the chip has 128M physical VMEM. 64M keeps 1024 blocks.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(q, k, v, do, lse, delta, wq2, wk2, sin_f, cos_f, sin_f, cos_f, rot)

    return _bwd_epilogue(
        dk_h, dv_h, dq, dwq_rows, dwk_rows, b, t, n_head, n_kv_head, c,
        groups, k.dtype, v.dtype, wq.dtype, wk.dtype,
    )


def _bwd_epilogue(dk_h, dv_h, dq, dwq_rows, dwk_rows, b, t, n_head,
                  n_kv_head, c, groups, k_dtype, v_dtype, wq_dtype, wk_dtype):
    if groups > 1:
        # per-q-head dk/dv -> per-kv-head (GQA, hpb==1 only)
        dk = (
            dk_h.reshape(b, t, n_kv_head, groups, c).sum(3).reshape(b, t, -1)
        ).astype(k_dtype)
        dv = (
            dv_h.reshape(b, t, n_kv_head, groups, c).sum(3).reshape(b, t, -1)
        ).astype(v_dtype)
    else:
        dk, dv = dk_h, dv_h
    dwq = dwq_rows.sum((0, 1)).astype(wq_dtype)
    dwk = dwk_rows.sum((0, 1)).astype(wk_dtype)
    return dq, dk, dv, dwq, dwk


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def fused_attention(
    q: Array,  # [B, T, H*C]  raw (pre-LN, pre-RoPE) projections
    k: Array,  # [B, T, Hkv*C]
    v: Array,  # [B, T, Hkv*C]
    wq: Array,  # [C] q-LayerNorm weight
    wk: Array,  # [C] k-LayerNorm weight
    sin: Array,  # [T, C] duplicated-interleaved RoPE table
    cos: Array,  # [T, C]
    n_head: int,
    n_kv_head: int,
    causal: bool = True,
    block_q: tp.Optional[int] = None,
    block_k: tp.Optional[int] = None,
    eps: float = 1e-6,
) -> Array:
    """QK-LayerNorm + RoPE + causal flash attention, projection-natural.

    Returns [B, T, H*C] in the same layout the output projection consumes.
    Differentiable in q, k, v, wq, wk."""
    out, _ = _fused_forward(
        q, k, v, wq, wk, sin, cos, n_head=n_head, n_kv_head=n_kv_head,
        causal=causal, bq=block_q, bk=block_k, eps=eps,
    )
    return out


def _fused_vjp_fwd(q, k, v, wq, wk, sin, cos, n_head, n_kv_head, causal,
                   block_q, block_k, eps):
    out, lse = _fused_forward(
        q, k, v, wq, wk, sin, cos, n_head=n_head, n_kv_head=n_kv_head,
        causal=causal, bq=block_q, bk=block_k, eps=eps,
    )
    return out, (q, k, v, wq, wk, sin, cos, out, lse)


def _fused_vjp_bwd(n_head, n_kv_head, causal, block_q, block_k, eps, res, do):
    q, k, v, wq, wk, sin, cos, out, lse = res
    dq, dk, dv, dwq, dwk = _fused_backward(
        q, k, v, wq, wk, sin, cos, out, lse, do, n_head=n_head,
        n_kv_head=n_kv_head, causal=causal, bq=block_q, bk=block_k, eps=eps,
    )
    return dq, dk, dv, dwq, dwk, None, None


fused_attention.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)


def _packed_geometry(qkv, n_head, n_kv_head):
    f = qkv.shape[-1]
    c = f // (n_head + 2 * n_kv_head)
    hpb = 2 if c == 64 else 1
    lanes = hpb * c
    assert (n_head * c) % lanes == 0 and (n_kv_head * c) % lanes == 0
    koff = (n_head * c) // lanes
    voff = koff + (n_kv_head * c) // lanes
    return c, koff, voff


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def fused_attention_qkv(
    qkv: Array,  # [B, T, (H + 2*Hkv) * C] — raw fused-projection output
    wq: Array,
    wk: Array,
    sin: Array,
    cos: Array,
    n_head: int,
    n_kv_head: int,
    causal: bool = True,
    eps: float = 1e-6,
) -> Array:
    """Packed-qkv entry: the kernels read Q, K and V straight out of the
    projection output via lane-offset block index maps — the q/k/v slice
    copies (forward) and their pad+add VJP (backward, ~16 ms/step of
    dynamic-update-slice fusions at the 124M shape, r3 profile) never
    exist. The backward emits one lane-concat of (dq, dk, dv) instead."""
    c, koff, voff = _packed_geometry(qkv, n_head, n_kv_head)
    out, _ = _fused_forward(
        qkv, qkv, qkv, wq, wk, sin, cos, n_head=n_head, n_kv_head=n_kv_head,
        causal=causal, bq=None, bk=None, head_dim=c, koff=koff, voff=voff,
        eps=eps,
    )
    return out


def _packed_vjp_fwd(qkv, wq, wk, sin, cos, n_head, n_kv_head, causal, eps):
    c, koff, voff = _packed_geometry(qkv, n_head, n_kv_head)
    out, lse = _fused_forward(
        qkv, qkv, qkv, wq, wk, sin, cos, n_head=n_head, n_kv_head=n_kv_head,
        causal=causal, bq=None, bk=None, head_dim=c, koff=koff, voff=voff,
        eps=eps,
    )
    return out, (qkv, wq, wk, sin, cos, out, lse)


def _packed_vjp_bwd(n_head, n_kv_head, causal, eps, res, do):
    qkv, wq, wk, sin, cos, out, lse = res
    c, koff, voff = _packed_geometry(qkv, n_head, n_kv_head)
    dq, dk, dv, dwq, dwk = _fused_backward(
        qkv, qkv, qkv, wq, wk, sin, cos, out, lse, do, n_head=n_head,
        n_kv_head=n_kv_head, causal=causal, bq=None, bk=None, head_dim=c,
        koff=koff, voff=voff, eps=eps,
    )
    dqkv = jnp.concatenate([dq, dk, dv], axis=-1)
    return dqkv, dwq, dwk, None, None


fused_attention_qkv.defvjp(_packed_vjp_fwd, _packed_vjp_bwd)


def fused_attention_reference(q, k, v, wq, wk, sin, cos, n_head, n_kv_head,
                              causal=True, eps=1e-6):
    """jnp oracle: the exact unfused path (LN -> transpose -> RoPE ->
    attention -> transpose back), f32 LN to match the kernel."""
    from midgpt_tpu.ops.attention import naive_attention

    b, t, _ = q.shape
    c = q.shape[-1] // n_head

    def ln(x, w):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        cent = x32 - mean
        var = jnp.mean(jnp.square(cent), axis=-1, keepdims=True)
        return cent * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)

    rot = jnp.asarray(_rotation_matrix(c, "float32"))

    def rope(x):  # [..., T, C] f32
        return x * cos + (x @ rot) * sin

    qh = ln(q.reshape(b, t, n_head, c), wq)
    kh = ln(k.reshape(b, t, n_kv_head, c), wk)
    vh = v.reshape(b, t, n_kv_head, c)
    qh = jnp.transpose(qh, (0, 2, 1, 3))
    kh = jnp.transpose(kh, (0, 2, 1, 3))
    vh = jnp.transpose(vh, (0, 2, 1, 3))
    qh = rope(qh).astype(q.dtype)
    kh = rope(kh).astype(k.dtype)
    outh = naive_attention(qh, kh, vh, causal=causal)
    return jnp.transpose(outh, (0, 2, 1, 3)).reshape(b, t, n_head * c)
