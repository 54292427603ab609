"""Decoder-only transformer (GPT / Llama family), batched-native.

Capability parity with /root/reference/src/model.py (GPT: RoPE, weightless
RMSNorm pre-norms, per-head QK-LayerNorm, GELU MLP, scan-over-layers with
whole-block remat, init-shared wte/lm_head), redesigned TPU-first:

- operates on whole ``[B, T]`` batches (one big MXU matmul per projection)
  instead of per-sequence modules vmapped by the caller (model.py:140-158);
- fused QKV projection sized ``(H + 2*Hkv) * C`` so GQA (Llama family,
  BASELINE.json configs) falls out of the same code path;
- optional SwiGLU MLP and weighted RMSNorms for the Llama-style family;
- activation shardings tagged with logical axis names
  (midgpt_tpu.parallel.sharding) so DP/FSDP/SP/TP are rule-table entries;
- attention is dispatched (naive oracle / Pallas flash / ring) via
  midgpt_tpu.ops.attention.

Layer stacking: blocks are created with ``jax.vmap`` over the layer axis and
iterated with ``lax.scan`` (+ configurable remat) for O(1) compile time in
depth (parity: model.py:130-155).
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.layers import (
    Embedding,
    LayerNorm,
    Linear,
    RMSNorm,
    apply_rotary,
    dropout,
    rope_tables,
)
from midgpt_tpu.ops.attention import attention
from midgpt_tpu.ops.grouped import grouped_matmul
from midgpt_tpu.parallel.sharding import current_mesh, shard_act
from midgpt_tpu.pytree import module, static

Array = jax.Array
KeyArray = jax.Array


def _fused_attention_sharded(qkv, wq, wk, sin, cos, h, hkv, eps):
    """Run the fused kernel per shard. Under a live multi-device mesh a
    bare ``pallas_call`` (an opaque custom call) would make GSPMD gather
    the sharded activations onto every device; wrapping in ``shard_map``
    keeps each device's kernel on its local batch — and, under TP, on its
    local HEADS: tensor shards the head dim, each shard running the
    split-input kernel with H/tp (and Hkv/tp) heads. T stays whole (the
    SP case takes the ring path, _use_fused)."""
    from midgpt_tpu.ops.fused_attn import fused_attention, fused_attention_qkv
    from midgpt_tpu.parallel.sharding import current_mesh, shard_act

    mesh = current_mesh()
    data_axes = ("replica", "fsdp")
    tp = mesh.shape.get("tensor", 1) if mesh is not None else 1
    if mesh is None or (
        tp == 1 and all(mesh.shape.get(a, 1) == 1 for a in data_axes)
    ):
        return fused_attention_qkv(qkv, wq, wk, sin, cos, h, hkv, True, eps)

    from jax.sharding import PartitionSpec as P

    if tp == 1:
        fn = lambda q_, wq_, wk_, s_, c_: fused_attention_qkv(  # noqa: E731
            q_, wq_, wk_, s_, c_, h, hkv, True, eps
        )
        return shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(data_axes), P(), P(), P(), P()),
            out_specs=P(data_axes),
            check_vma=False,
        )(qkv, wq, wk, sin, cos)

    # TP: split q/k/v (GSPMD reshards each slice head-contiguous per the
    # "heads" rule) and run the split-entry kernel with local head counts
    c = qkv.shape[-1] // (h + 2 * hkv)
    q = shard_act(qkv[..., : h * c], "batch", "seq", "heads")
    k = shard_act(qkv[..., h * c : (h + hkv) * c], "batch", "seq", "kv_heads")
    v = shard_act(qkv[..., (h + hkv) * c :], "batch", "seq", "kv_heads")

    fn = lambda q_, k_, v_, wq_, wk_, s_, c_: fused_attention(  # noqa: E731
        q_, k_, v_, wq_, wk_, s_, c_, h // tp, hkv // tp, True, None, None, eps
    )
    act = P(data_axes, None, "tensor")
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(act, act, act, P(), P(), P(), P()),
        out_specs=act,
        check_vma=False,
    )(q, k, v, wq, wk, sin, cos)


def _gathered_pool_view(pool_x, scale_x, bt, layer, hkv):
    """The block-table gather: the slots' pages as a logical KV view
    ``[S, Hkv, C, W = Pmax*PS]`` in page order, time minor: both of the
    XLA path's contractions then reduce as they did before the pool's
    page became C-minor (PR 26) — the PV sums over the minor axis, which
    XLA's CPU backend accumulates in index order whatever the shape (a
    reduction over a non-minor axis it sometimes splits; the bitwise
    tests against the interpreted kernel found that in one band
    width). Float pools return the
    gathered pages in POOL dtype — byte-for-byte the pre-existing path
    (downstream ``.astype(f32)`` upcasts are where the choreography
    fixes the arithmetic). Int8 pools dequantize at the view:
    ``f32(codes) * scale`` with the per-(page, KV-head) po2 scale
    broadcast to its page's columns — EXACT (|code| <= 127, po2 scale;
    midgpt_tpu.quant's KV grid contract), so every downstream consumer
    sees precisely the grid values a bf16 pool would have held.
    ``mode="clip"``, NOT "fill": block-table pads carry the out-of-range
    sentinel, and fill-mode NaNs would poison the score sum straight
    through the additive mask (0 * NaN = NaN); clipped garbage is erased
    by the -inf mask before the softmax."""
    # one gather out of the pool as it lies, the layer folded into the
    # page index: ``pool_x[layer]`` first is a slice the compiler makes
    # real, a whole layer's pages copied for a table's worth of reads
    n_layer, npool, ps, hc = pool_x.shape  # a row carries all heads: Hkv*C
    rows = jnp.clip(bt, 0, npool - 1) + layer * npool
    pk_l = jnp.take(
        pool_x.reshape(n_layer * npool, ps, hc), rows, axis=0, mode="clip"
    )
    s_, pmax = bt.shape
    ck = jnp.transpose(
        pk_l.reshape(s_, pmax * ps, hkv, hc // hkv), (0, 2, 3, 1)
    )  # [S, Hkv, C, W]
    if scale_x is None:
        return ck
    sc = jnp.take(scale_x[layer], bt, axis=0, mode="clip")  # [S, Pmax, Hkv]
    scw = jnp.transpose(sc, (0, 2, 1))[:, :, None, :, None]
    scw = jnp.broadcast_to(
        scw, (s_, hkv, 1, pmax, ps)
    ).reshape(s_, hkv, 1, pmax * ps)
    return ck.astype(jnp.float32) * scw


def _gathered_pool_scales(scale_x, bt, layer):
    """Per-slot per-page scale gather ``[S, Pmax, Hkv]`` for the Pallas
    kernels (which dequantize in-kernel and only need the tiny scale
    planes gathered, never the payload)."""
    if scale_x is None:
        return None
    return jnp.take(scale_x[layer], bt, axis=0, mode="clip")


def _rows_on_pool_grid(k, v, start, bt, pool_sk, pool_sv, layer, ps):
    """Round a dispatch's own K/V rows ``[S, Hkv, T, C]`` (row j of slot s
    at position ``start[s] + j``) through their target pages' int8 grids,
    so that every reader of a position — in-dispatch or from the pool
    after the write — sees one value (the kv-quant scheduling invariance)."""
    from midgpt_tpu.quant import round_kv_rows_to_grid
    from midgpt_tpu.serving.paged import kv_row_scales

    sk_all, sv_all = kv_row_scales(
        k, v, start, bt, pool_sk[layer], pool_sv[layer], ps
    )  # [S, Hkv, T]
    return round_kv_rows_to_grid(k, sk_all), round_kv_rows_to_grid(v, sv_all)


def _gather_attend(
    qg,  # [S, Hkv, G, T, C] query rows, grouped by KV head
    own_k,  # [S, Hkv, R, C] the dispatch's own rows, not yet in the pool:
    own_v,  # verify's T candidates, or decode's recent buffer (T = 1)
    mask_pool,  # additive f32, broadcastable to [S, Hkv, G, T, W]
    mask_own,  # additive f32, broadcastable to [S, Hkv, G, T, R]
    pool_k, pool_v, pool_sk, pool_sv, bt, layer,
    scale_dim: tp.Optional[int] = None,
):
    """The XLA gather path's attention core in the DECODE choreography,
    shared by the decode window and the verify program: the slots' pages
    gathered through ``bt`` (:func:`_gathered_pool_view`), scores as f32
    broadcast-multiply + reduce (q upcast first, cache upcast first, sum
    over C: what XLA fuses over the gathered view, and what CPUs and the
    geometries the kernel refuses run), mask added before the in-softmax
    ``/ sqrt(c)``, ONE joint f32 softmax over [pool | own rows] (exact,
    not an approximation), f32 probs through both P·V sums. The Pallas
    kernel (ops.paged_attn) forms the same sums on the matrix unit — on
    the chip the multiply-sum form, in a kernel, streamed the cache at a
    quarter of the rate (PERF.md section 6, PR 33) — and is held to this
    core at a tolerance (its module docstring, THE CONTRACT). Returns f32
    ``[S, Hkv, G, T, C]``.

    A LATENT pool (``pool_v`` None; ``LatentAttention``): one "KV head"
    whose key row is the pooled row as it lies and whose value is that row's
    first ``own_v.shape[-1]`` lanes — one gather serves both sums — with the
    softmax scaled by ``scale_dim`` (the published head's q·k width, not the
    row's)."""
    from midgpt_tpu.ops.paged_attn import banded_fold, resolved_band_pages

    hkv, c = qg.shape[1], qg.shape[-1]
    ps = pool_k.shape[2]
    # clip-mode gather, dequantized at the view for an int8 pool. It
    # indexes the (replicated) page dim of a KV-head-sharded pool, so it
    # is shard-local: each device gathers its own heads' pages. Pin the
    # view so the partitioner can never "help" by regathering heads (the
    # no-batch-allgather-in-page-gather audit rule gates that footgun).
    latent = pool_v is None
    ck = _gathered_pool_view(pool_k, pool_sk, bt, layer, hkv)
    if latent:
        cv = ck[:, :, : own_v.shape[-1]]
    else:
        cv = _gathered_pool_view(pool_v, pool_sv, bt, layer, hkv)
        ck = shard_act(ck, None, "kv_heads", None, None)
        cv = shard_act(cv, None, "kv_heads", None, None)
    s_pool = jnp.sum(
        qg[..., :, None].astype(jnp.float32)
        * ck[:, :, None, None].astype(jnp.float32),
        axis=-2,
    )  # [S, Hkv, G, T, W]
    s_own = jnp.sum(
        qg[:, :, :, :, None, :].astype(jnp.float32)
        * own_k[:, :, None, None].astype(jnp.float32),
        axis=-1,
    )  # [S, Hkv, G, T, R]
    s_all = jnp.concatenate([s_pool + mask_pool, s_own + mask_own], axis=-1)
    probs = jax.nn.softmax(s_all / math.sqrt(scale_dim or c), axis=-1)  # f32
    w_pool = s_pool.shape[-1]
    p_pool = probs[..., :w_pool]
    p_own = probs[..., w_pool:]
    # P·V over the pool in the banded kernel's pinned ascending-band
    # order (ops.paged_attn.banded_fold, same band plan): f32 addition is
    # not associative, so matching the kernel's chunked reduction order
    # is what keeps kernel and XLA within their tolerance at long
    # contexts. One band (every small geometry) is the single unsliced
    # reduce.
    bw = resolved_band_pages(
        bt.shape[1], ps, c, jnp.dtype(pool_k.dtype).itemsize, latent
    ) * ps
    if bw >= w_pool:
        o_pool = jnp.sum(
            p_pool[:, :, :, :, None, :]
            * cv[:, :, None, None].astype(jnp.float32),
            axis=-1,
        )  # [S, Hkv, G, T, C]
    else:
        # plain lax slices (NOT mixed None+slice indexing, which lowers
        # to a gather and hides the band start from the choreo prover's
        # order extractor)
        o_pool = banded_fold([
            jnp.sum(
                jax.lax.slice_in_dim(
                    p_pool, lo, lo + bw, axis=-1
                )[:, :, :, :, None, :]
                * jax.lax.slice_in_dim(
                    cv, lo, lo + bw, axis=-1
                )[:, :, None, None].astype(jnp.float32),
                axis=-1,
            )
            for lo in range(0, w_pool, bw)
        ])
    o_own = jnp.sum(
        p_own[..., None] * own_v[:, :, None, None].astype(jnp.float32),
        axis=-2,
    )  # [S, Hkv, G, T, C]
    return o_pool + o_own


def _paged_kernel_dispatch(kind: str, layer: int, tensors, scales, block=1):
    """Run a serving paged-attention kernel (ops.paged_attn), wrapped in
    ``shard_map`` under a live TP mesh: a bare ``pallas_call`` is an
    opaque custom call, and GSPMD would gather the KV-head-sharded pool
    onto every device (the same trap ``_fused_attention_sharded``
    documents). Each shard runs the kernel on its own Hkv/tp heads —
    the pool's page/time dims stay whole per shard, block tables and
    lengths ride replicated, so the walk is shard-local exactly like
    the XLA gather it replaces."""
    from midgpt_tpu.ops.paged_attn import (
        paged_decode_attention,
        paged_verify_attention,
    )

    mesh = current_mesh()
    tp = mesh.shape.get("tensor", 1) if mesh is not None else 1
    quant = scales[0] is not None
    sc = tuple(scales) if quant else ()

    if kind == "decode":
        q, pool_k, pool_v, bt, pooled_len, rkl, rvl, r = tensors
        # (the kernel's events in a device trace are ``%closed_call.N``,
        # which the benchmark's paged_attn_roofline.serve matches: the
        # name of the jitted call, ops.paged_attn._DECODE_CALL)
        call = lambda *a: paged_decode_attention(  # noqa: E731
            a[0], a[1], a[2], a[3], a[4], a[5], a[6], a[7], layer,
            *(a[8:] or (None, None)),
        )
        specs = [
            ("tensor", 1), ("tensor", 3), ("tensor", 3), (None, None),
            (None, None), ("tensor", 1), ("tensor", 1), (None, None),
        ]
    else:
        q, kc, vc, pool_k, pool_v, bt, start = tensors
        call = lambda *a: paged_verify_attention(  # noqa: E731
            a[0], a[1], a[2], a[3], a[4], a[5], a[6], layer,
            *(a[7:] or (None, None)), block=block,
        )
        specs = [
            ("tensor", 1), ("tensor", 1), ("tensor", 1), ("tensor", 3),
            ("tensor", 3), (None, None), (None, None),
        ]
    args = tuple(tensors) + sc
    if mesh is None or tp == 1:
        return call(*args)

    from jax.sharding import PartitionSpec as P

    def spec_for(arr, axis_pos):
        name, pos = axis_pos
        if name is None:
            return P(*([None] * arr.ndim))
        entries = [None] * arr.ndim
        entries[pos] = name
        return P(*entries)

    if quant:
        specs = specs + [("tensor", 2), ("tensor", 2)]  # [S, Pmax, Hkv]
    in_specs = tuple(spec_for(a, sp) for a, sp in zip(args, specs))
    out_spec = P(None, "tensor", *([None] * (args[0].ndim - 2)))
    return shard_map(
        call,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_spec,
        check_vma=False,
    )(*args)


@module
class Attention:
    """Causal self-attention with QK-norm + RoPE (parity: model.py:34-81)."""

    wqkv: Linear  # [D, (H + 2*Hkv) * C]
    wo: Linear  # [H*C, D]
    q_norm: tp.Optional[tp.Union[LayerNorm, RMSNorm]]
    k_norm: tp.Optional[tp.Union[LayerNorm, RMSNorm]]
    n_head: int = static()
    n_kv_head: int = static()
    dropout_rate: float = static(default=0.0)
    ring_schedule: str = static(default="zigzag")
    rope_style: str = static(default="interleaved")
    # the QK norm runs over the whole H*C projection, not per head
    qk_norm_full: bool = static(default=False)

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "Attention":
        k1, k2 = jax.random.split(key)
        c = cfg.head_dim
        hkv = cfg.kv_heads
        qkv_out = (cfg.n_head + 2 * hkv) * c
        assert cfg.qk_norm_kind in ("layer", "rms", "rms_full"), (
            cfg.qk_norm_kind
        )
        assert cfg.rope_style in ("interleaved", "half", "none"), (
            cfg.rope_style
        )
        full = cfg.qk_norm and cfg.qk_norm_kind == "rms_full"

        def head_norm(heads):
            if not cfg.qk_norm:
                return None
            if full:
                return RMSNorm.init(
                    heads * c, use_weight=True, eps=cfg.norm_eps or 1e-6,
                    impl="jnp",
                )
            if cfg.qk_norm_kind == "rms":
                return RMSNorm.init(c, use_weight=True, eps=1e-6, impl="jnp")
            return LayerNorm.init(c, eps=1e-6)

        return Attention(
            wqkv=Linear.init(k1, cfg.n_embd, qkv_out),
            wo=Linear.init(k2, cfg.n_head * c, cfg.n_embd),
            q_norm=head_norm(cfg.n_head),
            k_norm=head_norm(hkv),
            n_head=cfg.n_head,
            n_kv_head=hkv,
            dropout_rate=cfg.dropout,
            ring_schedule=cfg.ring_schedule,
            rope_style=cfg.rope_style,
            qk_norm_full=full,
        )

    def _split_qkv(self, qkv: Array) -> tp.Tuple[Array, Array, Array]:
        """The fused projection ``[B, T, (H + 2 Hkv) C]`` as per-head q
        ``[B, T, H, C]`` and k, v ``[B, T, Hkv, C]``, QK-normed."""
        b, t, _ = qkv.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        full = self.qk_norm_full
        q = qkv[..., : h * c]
        q = (self.q_norm(q) if full else q).reshape(b, t, h, c)
        k = qkv[..., h * c : (h + hkv) * c]
        k = (self.k_norm(k) if full else k).reshape(b, t, hkv, c)
        v = qkv[..., (h + hkv) * c :].reshape(b, t, hkv, c)
        if self.q_norm is not None and not full:
            q = self.q_norm(q)
            k = self.k_norm(k)
        return q, k, v

    def __call__(
        self,
        x: Array,  # [B, T, D]
        sin,
        cos,
        *,
        impl: str = "naive",
        key: tp.Optional[KeyArray] = None,
        deterministic: bool = True,
        return_kv: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, tp.Tuple[Array, Array]]]:
        b, t, d = x.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        adrop_key, pdrop_key = (
            jax.random.split(key) if key is not None else (None, None)
        )
        if impl == "fused" and (
            return_kv or not isinstance(self.q_norm, LayerNorm)
            or self.rope_style != "interleaved"
        ):
            # return_kv needs per-head K/V (prefill), and the kernel requires
            # qk-norm; same math either way, so degrade to auto dispatch
            impl = "auto"
        if self._use_fused(impl, t, deterministic) and not return_kv:
            return self._fused_call(x, sin, cos, pdrop_key, deterministic)
        with jax.named_scope("attention"):
            q, k, v = self._split_qkv(self.wqkv(x))
            # [B, H, T, C]
            q = jnp.transpose(q, (0, 2, 1, 3))
            k = jnp.transpose(k, (0, 2, 1, 3))
            v = jnp.transpose(v, (0, 2, 1, 3))
            q = apply_rotary(q, sin, cos, self.rope_style)
            k = apply_rotary(k, sin, cos, self.rope_style)
            q = shard_act(q, "batch", "heads", "seq", "head_dim")
            k = shard_act(k, "batch", "kv_heads", "seq", "head_dim")
            v = shard_act(v, "batch", "kv_heads", "seq", "head_dim")
            if impl == "ulysses":
                from midgpt_tpu.parallel.sharding import current_mesh
                from midgpt_tpu.parallel.ulysses import ulysses_attention

                mesh = current_mesh()
                assert mesh is not None, (
                    "attn_impl='ulysses' requires running inside "
                    "axis_rules(mesh)"
                )
                if self.dropout_rate > 0.0 and not deterministic:
                    u_seed = jax.random.randint(
                        adrop_key, (), -(2**31), 2**31 - 1, dtype=jnp.int32
                    )
                    out = ulysses_attention(
                        q, k, v, mesh,
                        dropout_rate=self.dropout_rate, dropout_seed=u_seed,
                    )
                else:
                    out = ulysses_attention(q, k, v, mesh)
            elif impl == "ring":
                from midgpt_tpu.parallel.ring import ring_attention
                from midgpt_tpu.parallel.sharding import current_mesh

                mesh = current_mesh()
                assert mesh is not None, (
                    "attn_impl='ring' requires running inside axis_rules(mesh)"
                )
                schedule = self.ring_schedule
                if schedule == "zigzag" and t % (2 * mesh.shape["sequence"]):
                    schedule = "standard"  # zigzag needs T | 2S
                if self.dropout_rate > 0.0 and not deterministic:
                    # in-hop counter-hash dropout at global coordinates
                    # (ring.py); zigzag interleaves half-chunks, which the
                    # scalar hash offsets can't express — degrade to the
                    # standard schedule (r5; the only dropout configs are
                    # the small shakespeare family)
                    seed = jax.random.randint(
                        adrop_key, (), -(2**31), 2**31 - 1, dtype=jnp.int32
                    )
                    out = ring_attention(
                        q, k, v, mesh, schedule="standard",
                        dropout_rate=self.dropout_rate, dropout_seed=seed,
                    )
                else:
                    out = ring_attention(q, k, v, mesh, schedule=schedule)
            else:
                out = attention(
                    q,
                    k,
                    v,
                    impl=impl,
                    causal=True,
                    dropout_rate=self.dropout_rate,
                    dropout_key=adrop_key,
                    deterministic=deterministic,
                )
            out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * c)
            out = self.wo(out)
            out = dropout(out, self.dropout_rate, pdrop_key, deterministic)
            out = shard_act(out, "batch", "seq", "embed")
            if return_kv:
                # post-norm, post-rope K and raw V [B, Hkv, T, C] — exactly
                # what the KV cache stores (decode() writes the same)
                return out, (k, v)
            return out


    def _use_fused(self, impl: str, t: int, deterministic: bool) -> bool:
        """Route to the projection-natural fused kernel (ops/fused_attn):
        QK-LN + RoPE + flash in one Pallas call, no [B,H,T,C] intermediates.
        impl="fused" forces it (tests, via the interpret fixture); "auto"
        takes it on TPU under the same conditions flash requires."""
        from midgpt_tpu.ops.fused_attn import supported

        if impl not in ("fused", "auto") or not (
            # the kernel is QK-LayerNorm + interleaved RoPE and nothing else
            isinstance(self.q_norm, LayerNorm)
            and self.rope_style == "interleaved"
        ):
            return False
        shape_ok = (
            supported(self.n_head, self.n_kv_head, self.head_dim())
            and t >= 128
            and t % 128 == 0
            and (self.dropout_rate == 0.0 or deterministic)
        )
        mesh = current_mesh()
        tp = mesh.shape.get("tensor", 1) if mesh is not None else 1
        sp = mesh.shape.get("sequence", 1) if mesh is not None else 1
        pp = mesh.shape.get("pipeline", 1) if mesh is not None else 1
        # TP is fine when every shard keeps whole supported heads (each
        # device runs the split-entry kernel with H/tp, Hkv/tp heads);
        # SP shards T, which the kernel grid cannot see — ring territory
        tp_ok = (
            tp == 1
            or (
                self.n_head % tp == 0
                and self.n_kv_head % tp == 0
                and supported(
                    self.n_head // tp, self.n_kv_head // tp, self.head_dim()
                )
            )
        )
        # pipeline: the stages already run inside a shard_map over
        # 'pipeline'; _fused_attention_sharded's in_specs would declare the
        # activations replicated over that axis and force GSPMD to regather
        # them (ADVICE r3) — the flash/naive path handles PP meshes
        mesh_unsupported = sp > 1 or pp > 1 or not tp_ok
        if impl == "fused":
            assert shape_ok, (
                "attn_impl='fused' requires qk-norm, T % 128 == 0, no "
                "attention dropout, and a supported head shape "
                "(C % 128 == 0, or C == 64 with MHA)"
            )
            assert not mesh_unsupported, (
                "attn_impl='fused' cannot run under a sequence-sharded "
                "mesh, or a tensor sharding that breaks the per-shard "
                "head shape; use attn_impl='auto' (falls back) or 'ring'"
            )
            return True
        from midgpt_tpu.utils.platform import is_tpu_backend

        if mesh_unsupported:
            return False
        return shape_ok and is_tpu_backend()

    def head_dim(self) -> int:
        # static: wo is [H*C, D]
        return self.wo.weight.shape[0] // self.n_head

    def _fused_call(self, x, sin, cos, pdrop_key, deterministic):
        from midgpt_tpu.models.layers import _duplicate_interleaved

        h, hkv = self.n_head, self.n_kv_head
        with jax.named_scope("fused_attention"):
            qkv = self.wqkv(x)  # [B, T, (H + 2Hkv) C]
            # single-device / data-sharded meshes take the packed entry
            # (lane-offset reads, no slice copies, no pad+add VJP); TP
            # meshes split q/k/v and run per head shard — both inside
            # _fused_attention_sharded.
            qkv = shard_act(qkv, "batch", "seq", None)
            sin_full = _duplicate_interleaved(jnp.asarray(sin, jnp.float32))
            cos_full = _duplicate_interleaved(jnp.asarray(cos, jnp.float32))
            out = _fused_attention_sharded(
                qkv, self.q_norm.weight, self.k_norm.weight,
                sin_full, cos_full, h, hkv, self.q_norm.eps,
            )
            out = self.wo(out)
            out = dropout(out, self.dropout_rate, pdrop_key, deterministic)
            return shard_act(out, "batch", "seq", "embed")

    def _decode_qkv(
        self, x: Array, sin_rows: Array, cos_rows: Array, *, pin: bool = True
    ) -> tp.Tuple[Array, Array, Array]:
        """The prologue of every cached-attention body: project ``x``
        [S, T, D] (a decode step is T = 1) to q [S, H, T, C] and k/v
        [S, Hkv, T, C], with the optional QK-norm and the rope rows of the
        tokens' absolute positions applied.

        ``pin``: the whole-head TP constraints of the serving meshes
        (serving_logical_rules) — the slot dim stays replicated (DP is
        shared-nothing engine replicas, not a sharded slot axis) and every
        per-head tensor splits over 'tensor'; no-ops outside an axis_rules
        scope. Off for :meth:`decode_at`, which runs under the TRAINING
        rule table with the batch dim sharded: a batch-replicated pin
        would force a per-layer-per-token reshard there."""
        b, t, d = x.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        q, k, v = self._split_qkv(self.wqkv(x))  # [S, T, heads, C]
        q = jnp.transpose(q, (0, 2, 1, 3))  # [S, H, T, C]
        k = jnp.transpose(k, (0, 2, 1, 3))  # [S, Hkv, T, C]
        v = jnp.transpose(v, (0, 2, 1, 3))
        q = apply_rotary(q, sin_rows, cos_rows, self.rope_style)
        k = apply_rotary(k, sin_rows, cos_rows, self.rope_style)
        if pin:
            q = shard_act(q, None, "heads", None, None)
            k = shard_act(k, None, "kv_heads", None, None)
            v = shard_act(v, None, "kv_heads", None, None)
        return q, k, v

    def _merge_heads_out(self, out: Array, *, pin: bool = True) -> Array:
        """The epilogue: per-head rows [S, H, T, C] -> ``wo`` of the merged
        [S, T, H*C]. The merged dim stays head-contiguous tensor-sharded
        (``pin``, as in :meth:`_decode_qkv`): wo is row-parallel
        (GPT_PARAM_RULES), so the contraction runs on local heads and
        GSPMD inserts ONE psum on the [.., D] result."""
        b, h, t, c = out.shape
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * c)
        if pin:
            out = shard_act(out, None, None, "heads")
        return self.wo(out)

    def decode_at(
        self,
        x: Array,  # [B, 1, D] — one new token per sequence
        cache_k: Array,  # [L, B, Hkv, C, W] FULL stacked cache (time-minor)
        cache_v: Array,  # [L, B, Hkv, C, W]
        layer: int,  # STATIC layer index into the stacked cache
        slot: Array,  # [] int32 — cache slot to write (the token's position)
        mask: Array,  # [W] f32 additive mask over cache slots (0 / -inf)
        sin_row: Array,  # [1, C//2] rope row at the token's ABSOLUTE position
        cos_row: Array,
    ) -> tp.Tuple[Array, Array, Array]:
        """Single-token incremental attention against a contiguous KV cache.

        The reference has no decode path (sample.py:72-94 re-runs the full
        forward per token); this is the plain cached one: O(W) per token,
        static shapes, jit/scan-friendly — the oracle the paged serving
        bodies are tested against.

        Takes the WHOLE stacked cache and a static ``layer``: the write is
        one [B, Hkv, 1, C] dynamic_update_slice row that XLA aliases in
        place, and the read is a static slice that fuses into the attention
        einsums — nothing copies or re-stacks the [L, ...] cache (the old
        scan-over-layers decode re-materialized all L·B·Hkv·W·C elements of
        both caches per token: ~300 MB/step at the 124M shape, the dominant
        term in the measured 2.9 ms/token, PERF.md 'Serving bench')."""
        b, one, d = x.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        q, k, v = self._decode_qkv(x, sin_row, cos_row, pin=False)
        # cache is time-minor ([B, Hkv, C, W] per layer — see KVCache): the
        # new row lands as a single-lane column write
        kc = jnp.transpose(k, (0, 1, 3, 2))  # [B, Hkv, C, 1]
        vc = jnp.transpose(v, (0, 1, 3, 2))
        zero = jnp.zeros((), slot.dtype)
        at = (jnp.asarray(layer, slot.dtype), zero, zero, zero, slot)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, kc.astype(cache_k.dtype)[None], at
        )
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, vc.astype(cache_v.dtype)[None], at
        )
        ck, cv = cache_k[layer], cache_v[layer]  # [B, Hkv, C, W] views
        # single-query attention as broadcast-multiply + reduce, NOT
        # dot_general: a [1, C] x [C, W] matvec uses one MXU row per pass
        # and measured ~160 GB/s; the VPU form streams the cache at full
        # rate (profiled 1.26 -> ~0.3 ms/step at 124M W=1024, PERF.md r4).
        # f32 casts fuse into the reduce — nothing materializes at [.., C, W].
        qg = q.reshape(b, hkv, h // hkv, 1, c)
        qcw = jnp.transpose(qg, (0, 1, 2, 4, 3))  # [B, Hkv, G, C, 1]
        scores = jnp.sum(
            qcw.astype(jnp.float32) * ck[:, :, None].astype(jnp.float32),
            axis=-2,
        )  # [B, Hkv, G, W]
        probs = jax.nn.softmax(
            (scores + mask) / math.sqrt(c), axis=-1
        )  # [B, Hkv, G, W] f32 — reduces over W must accumulate in f32
        out = jnp.sum(
            probs[:, :, :, None, :] * cv[:, :, None].astype(jnp.float32),
            axis=-1,
        ).astype(x.dtype)  # [B, Hkv, G, C]
        out = self._merge_heads_out(out.reshape(b, h, 1, c), pin=False)
        return out, cache_k, cache_v

    def decode_paged_at(
        self,
        x: Array,  # [S, 1, D] — one new token per decode SLOT
        pool_k: Array,  # [L, NP, PS, Hkv*C] page pool, READ-ONLY here
        pool_v: Array,  # [L, NP, PS, Hkv*C]
        bt: Array,  # [S, Pmax] int32 per-slot block tables (page ids)
        rk: Array,  # [L, S, Hkv, R, C] recent-K write buffer (row writes)
        rv: Array,  # [L, S, Hkv, R, C]
        layer: int,  # STATIC layer index
        r: Array,  # [] int32 — step index within the decode window
        mask_pool: Array,  # [S, W=Pmax*PS] additive f32 over paged slots
        mask_rec: Array,  # [R] additive f32 over recent rows
        sin_rows: Array,  # [S, 1, 1, C//2] per-slot rope rows (positions differ)
        cos_rows: Array,
        pooled_len: tp.Optional[Array] = None,  # [S] int32 (kernel / kv-quant)
        pool_sk: tp.Optional[Array] = None,  # [L, NP, Hkv] f32 (int8 pool)
        pool_sv: tp.Optional[Array] = None,
        paged_kernel: str = "xla",
    ) -> tp.Tuple[Array, Array, Array]:
        """Single-token attention against a PAGED KV pool read through
        per-slot block tables, plus the write-combining recent buffer.

        Every slot (request) owns a list of fixed-size pages in a shared
        pool (``midgpt_tpu.serving``) — its logical KV is the
        concatenation of its block-table pages, gathered through ``bt``.
        The pool is READ-ONLY inside a decode window: a per-token write
        into the big time-minor pool is a scattered column that either
        flips its layout or pays scattered RMW (PERF.md r4 'Serving'), so
        each step's K/V row goes into a small time-MAJOR recent buffer
        (one contiguous tile row per (slot, kv-head)) and the window's
        rows reach the pages in one bulk flush. The softmax runs jointly
        over [pool | recent rows] — exact, not an approximation
        (:func:`_gather_attend`, the core verify shares). Positions
        differ PER SLOT (continuous batching mixes requests at different
        depths), hence per-slot rope rows and a [S, W] mask.

        ``paged_kernel="pallas"`` replaces the gather + two-part softmax
        with the ragged Pallas kernel (ops.paged_attn): the block table
        is walked IN-KERNEL over each slot's ``pooled_len``, LIVE pages
        stream from HBM exactly once and dead ones not at all, and no
        ``[S, Pmax*PS, ...]`` gathered intermediate exists — the same
        sums on the matrix unit (held to the core at a tolerance; tested). An int8 pool
        (``pool_sk``/``pool_sv`` given) dequantizes per (page, KV-head)
        po2 scale — in-kernel on the kernel path, at the gathered view
        here — and this step's K/V row is rounded through its target
        page's grid BEFORE the recent buffer sees it, so in-window reads
        and post-flush pool reads of the same position are
        indistinguishable (the invariance the token-identity matrix
        rests on)."""
        b, one, d = x.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        q, k, v = self._decode_qkv(x, sin_rows, cos_rows)
        quant = pool_sk is not None
        ps = pool_k.shape[2]
        zero = jnp.zeros((), r.dtype)
        if quant:
            # round this step's row through its page's int8 grid before
            # ANY reader (the recent buffer, this very step's scores)
            # sees it. The page scale: derived from this row when the
            # page is born at this position, from the page's in-window
            # birth row (already rounded — derivation is rounding-
            # stable) when born earlier in this window, else the pool's
            # recorded scale. (Verify and prefill see all their rows at
            # once: _rows_on_pool_grid.)
            from midgpt_tpu.quant import round_kv_rows_to_grid
            from midgpt_tpu.serving.paged import kv_row_scales

            # the scale-derivation rows enter in COMPUTE dtype, exactly
            # like verify's candidate rows: the recent buffer's bf16
            # grid values upcast exactly, and matching operand dtypes
            # keep the decode and verify attention traces op-identical
            # (the choreography prover compares them record for record)
            at4 = (zero, zero, r, zero)
            tmp_k = jax.lax.dynamic_update_slice(
                rk[layer].astype(k.dtype), k, at4
            )
            tmp_v = jax.lax.dynamic_update_slice(
                rv[layer].astype(v.dtype), v, at4
            )
            sk_all, sv_all = kv_row_scales(
                tmp_k, tmp_v, pooled_len, bt, pool_sk[layer],
                pool_sv[layer], ps,
            )  # [S, Hkv, R]
            sk_r = jax.lax.dynamic_slice_in_dim(sk_all, r, 1, axis=2)
            sv_r = jax.lax.dynamic_slice_in_dim(sv_all, r, 1, axis=2)
            k = round_kv_rows_to_grid(k, sk_r)  # [S, Hkv, 1, C]
            v = round_kv_rows_to_grid(v, sv_r)
        at = (jnp.asarray(layer, r.dtype), zero, zero, r, zero)
        rk = jax.lax.dynamic_update_slice(rk, k.astype(rk.dtype)[None], at)
        rv = jax.lax.dynamic_update_slice(rv, v.astype(rv.dtype)[None], at)
        rk = shard_act(rk, None, None, "kv_heads", None, None)
        rv = shard_act(rv, None, None, "kv_heads", None, None)
        rkl, rvl = rk[layer], rv[layer]  # [S, Hkv, R, C]
        if paged_kernel == "pallas":
            # the ragged in-kernel block-table walk (ops.paged_attn):
            # the core's sums on the matrix unit, none of its HBM gather
            qs = shard_act(
                q.reshape(b, hkv, h // hkv, c), None, "kv_heads", None, None
            )
            out = _paged_kernel_dispatch(
                "decode", layer,
                (qs, pool_k, pool_v, bt, pooled_len, rkl, rvl, r),
                (_gathered_pool_scales(pool_sk, bt, layer),
                 _gathered_pool_scales(pool_sv, bt, layer)),
            )  # [S, Hkv, G, C]
            out = shard_act(out, None, "kv_heads", None, None)
        else:
            # the one query row's own rows are the recent buffer's
            out = _gather_attend(
                q.reshape(b, hkv, h // hkv, 1, c), rkl, rvl,
                mask_pool[:, None, None, None, :], mask_rec,
                pool_k, pool_v, pool_sk, pool_sv, bt, layer,
            ).astype(x.dtype)  # [S, Hkv, G, 1, C]
        return self._merge_heads_out(out.reshape(b, h, 1, c)), rk, rv

    def prefill_paged_at(
        self,
        x: Array,  # [1, T, D] — the prefill chunk's hidden states
        pool_k: Array,  # [L, NP, PS, Hkv*C] page pool, READ-ONLY here
        pool_v: Array,  # [L, NP, PS, Hkv*C]
        bt: Array,  # [1, Pmax] int32 — the slot's block table
        layer: int,  # STATIC layer index
        mask_pool: Array,  # [W = Pmax*PS] additive f32 (0 where pos < start)
        mask_self: Array,  # [T, T] additive causal f32 within the chunk
        sin_rows: Array,  # [T, C//2] rope rows at the chunk's positions
        cos_rows: Array,
        start: tp.Optional[Array] = None,  # [] int32 (kv-quant only)
        pool_sk: tp.Optional[Array] = None,  # [L, NP, Hkv] f32 (int8 pool)
        pool_sv: tp.Optional[Array] = None,
    ) -> tp.Tuple[Array, Array, Array]:
        """Multi-query attention for a PREFILL CHUNK over a pre-populated
        block table: the chunk's T tokens attend jointly to the slot's
        already-resident pages (positions < chunk start — the cached
        prefix and/or earlier chunks) and to themselves (causal). The
        suffix-only prefill path of the prefix cache: a request whose
        prompt prefix is already in the pool computes only this chunk's
        FLOPs, and chunked prefill resumes a long prompt mid-stream from
        whatever the block table already holds. Joint softmax over
        [pages | chunk] — exact, same two-part discipline as
        :meth:`decode_paged_at`. Returns (out, k, v) with k/v the chunk's
        post-rope K / raw V [1, Hkv, T, C] for the page write.

        The score/probs arithmetic deliberately MIRRORS
        ops.attention.naive_attention op for op: compute-dtype operands
        with f32 einsum accumulation, additive mask applied before the
        in-softmax scale, probs cast to the value dtype before the PV
        contraction. With an empty pool part the whole computation is
        then bitwise what ``model.hidden`` + naive attention produces,
        so a bf16-cache engine stays greedy-token-identical to the
        fixed-batch sampler (a cast-to-f32-early variant drifted by ~2
        bf16 ulps in the pool K/V — enough to flip near-tied greedy
        argmaxes on a real checkpoint, caught by the sample.py --serve
        verify drive). This contract is MACHINE-CHECKED: the
        choreography prover (midgpt_tpu.analysis.choreo, CI
        serving-choreo job) normalizes this subgraph's op-and-dtype
        trace out of the compiled chunk program's jaxpr and asserts its
        softmax core equals naive_attention's — a cast-early edit here
        turns that gate red before anything compiles."""
        b, t, d = x.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        q, k, v = self._decode_qkv(x, sin_rows, cos_rows)
        if pool_sk is not None:
            # int8 pool: round the chunk's own K/V rows through their
            # target pages' grids BEFORE the in-chunk self-attention.
            # Without this, a later chunk would read these positions
            # from the pool (grid values) while the monolithic prefill
            # read them in-chunk un-rounded — chunked vs monolithic
            # streams would diverge under kv-quant. With it, every
            # reader of a position sees one value, whatever the chunk
            # grid. (The bf16 pool keeps the naive-attention contract
            # un-rounded — rounding there is the identity at serving
            # dtype, and the choreography prover pins that path.)
            k, v = _rows_on_pool_grid(
                k, v, jnp.reshape(start, (1,)).astype(jnp.int32), bt,
                pool_sk, pool_sv, layer, pool_k.shape[2],
            )
        # gather the slot's pages -> logical KV [1, Hkv, C, W] in page
        # order; int8 pools dequantize at the view (_gathered_pool_view)
        ck = _gathered_pool_view(pool_k, pool_sk, bt, layer, hkv)
        cv = _gathered_pool_view(pool_v, pool_sv, bt, layer, hkv)
        ck = shard_act(ck, None, "kv_heads", None, None)
        cv = shard_act(cv, None, "kv_heads", None, None)
        qg = q.reshape(b, hkv, h // hkv, t, c)
        s_pool = jnp.einsum(
            "bhgtc,bhcw->bhgtw", qg, ck.astype(qg.dtype),
            preferred_element_type=jnp.float32,
        )  # [1, Hkv, G, T, W]
        s_self = jnp.einsum(
            "bhgtc,bhsc->bhgts", qg, k,
            preferred_element_type=jnp.float32,
        )  # [1, Hkv, G, T, T]
        s_all = jnp.concatenate(
            [s_pool + mask_pool, s_self + mask_self], axis=-1
        )
        scale = 1.0 / jnp.sqrt(c).astype(jnp.float32)
        probs = jax.nn.softmax(s_all * scale, axis=-1)
        probs = probs.astype(v.dtype)
        p_pool = probs[..., : s_pool.shape[-1]]
        p_self = probs[..., s_pool.shape[-1]:]
        o_pool = jnp.einsum("bhgtw,bhcw->bhgtc", p_pool, cv.astype(v.dtype))
        o_self = jnp.einsum("bhgts,bhsc->bhgtc", p_self, v)
        out = (o_pool + o_self).astype(x.dtype).reshape(b, h, t, c)
        return self._merge_heads_out(out), k, v

    def verify_paged_at(
        self,
        x: Array,  # [S, T, D] — the verify dispatch's candidate rows
        pool_k: Array,  # [L, NP, PS, Hkv*C] page pool, READ-ONLY here
        pool_v: Array,  # [L, NP, PS, Hkv*C]
        bt: Array,  # [S, Pmax] int32 per-slot block tables
        layer: int,  # STATIC layer index
        mask_pool: Array,  # [S, 1, 1, 1, W] additive f32 (0 where resident)
        mask_self: Array,  # [T, T] additive causal f32 within the rows
        sin_rows: Array,  # [S, 1, T, C//2] per-slot rope rows
        cos_rows: Array,
        start: tp.Optional[Array] = None,  # [S] int32 (kernel / kv-quant)
        pool_sk: tp.Optional[Array] = None,  # [L, NP, Hkv] f32 (int8 pool)
        pool_sv: tp.Optional[Array] = None,
        paged_kernel: str = "xla",
        block: int = 1,  # STATIC: > 1 = rows see their whole block (the
        # kernel's own-rows mask; ``mask_self`` carries the XLA path's)
    ) -> tp.Tuple[Array, Array, Array]:
        """Multi-query attention for SPECULATIVE VERIFICATION: all T
        candidate rows of every slot attend jointly to the slot's
        resident pages plus themselves (causal), one joint softmax.

        The dtype choreography is :meth:`decode_paged_at`'s — one
        function, :func:`_gather_attend` — NOT the prefill chunk's
        naive_attention choreography. Acceptance compares the verify
        logits' argmax against what the decode window would have
        sampled; on a real bf16 checkpoint the two choreographies
        disagree by ~2 bf16 ulps, enough to flip near-tied greedy
        argmaxes (caught by the sample.py --serve --serve_spec verify
        drive on a trained checkpoint — the same class of flip PR 4 hit
        with a cast-early prefill variant). Sharing the decode
        arithmetic pins spec-on to the decode path at f32-reduction
        granularity, the same equivalence class as the tested K=4 vs
        K=1 window invariance; the choreography prover
        (midgpt_tpu.analysis.choreo, CI serving-choreo job) still
        asserts the verify program's normalized attention trace equals
        the decode window's OP FOR OP."""
        b, t, d = x.shape
        h, hkv = self.n_head, self.n_kv_head
        c = self.head_dim()
        q, k, v = self._decode_qkv(x, sin_rows, cos_rows)
        row_dt = jnp.bfloat16 if pool_k.dtype == jnp.int8 else pool_k.dtype
        if pool_sk is not None:
            # round the candidate rows through their target pages' int8
            # grids: the verify self-reads, the decode window's recent-
            # buffer reads, and the post-flush pool reads of the same
            # positions must all see the identical grid values, or
            # near-tied acceptance argmaxes flip between spec-on and
            # spec-off (the PR 5 bug class, int8 edition). Rows past the
            # watermark never land (flush mask) — rounding them is
            # harmless.
            k, v = _rows_on_pool_grid(
                k, v, start, bt, pool_sk, pool_sv, layer, pool_k.shape[2]
            )
        qg = q.reshape(b, hkv, h // hkv, t, c)  # [S, Hkv, G, T, C]
        # the decode window stores each step's K/V into the CACHE-dtype
        # recent buffer and reads it back for the in-window scores — so
        # rows see cache-rounded self keys/values. Mirror that rounding
        # (an identity when cache dtype == compute dtype, but an f32
        # model over a bf16 pool would otherwise score un-rounded self
        # keys and flip near-tied acceptance argmaxes)
        kc = k.astype(row_dt)
        vc = v.astype(row_dt)
        if paged_kernel == "pallas":
            # the ragged in-kernel block-table walk (ops.paged_attn):
            # the core's sums on the matrix unit, none of its HBM gather;
            # one body with the decode window's, whatever ``block``
            qg = shard_act(qg, None, "kv_heads", None, None, None)
            out = _paged_kernel_dispatch(
                "verify", layer,
                (qg, kc, vc, pool_k, pool_v, bt, start),
                (_gathered_pool_scales(pool_sk, bt, layer),
                 _gathered_pool_scales(pool_sv, bt, layer)),
                block=block,
            )  # [S, Hkv, G, T, C]
            out = shard_act(out, None, "kv_heads", None, None, None)
        else:
            out = _gather_attend(
                qg, kc, vc, mask_pool, mask_self,
                pool_k, pool_v, pool_sk, pool_sv, bt, layer,
            ).astype(x.dtype)  # [S, Hkv, G, T, C]
        return self._merge_heads_out(out.reshape(b, h, t, c)), k, v


@module
class GatedDeltaNet:
    """The linear-attention mixer: a gated delta rule (ops/gated_delta) over
    q, k, v that come through a short causal depthwise convolution and SiLU;
    q and k L2-normalised per head, the rule's output RMS-normed per head
    and gated. What a layer carries from token to token is per head a
    ``[dk, dv]`` float32 state and the convolution's last ``taps - 1``
    inputs (the tail), nothing that grows with the context.

    Three bodies over one set of steps: :meth:`__call__` (a whole sequence
    from an empty state), :meth:`prefill_at` (a chunk of one slot that takes
    and returns state and tail; right-padding does not advance either) and
    :meth:`decode_at` (one token for S slots against the engine's stacks)."""

    wqkv: Linear  # [D, 2 Hk dk + Hv dv]: q | k | v, heads major
    conv: Array  # [taps, 2 Hk dk + Hv dv]; row taps-1 meets the newest token
    wg: Linear  # [D, Hv dv] the output gate
    wba: Linear  # [D, 2 Hv]: beta | a
    a_log: Array  # [Hv]
    dt_bias: Array  # [Hv]
    o_norm: RMSNorm  # over dv, one scale shared by the heads
    wo: Linear  # [Hv dv, D]
    key_heads: int = static()
    value_heads: int = static()
    key_dim: int = static()
    neg_eigval: bool = static(default=False)

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "GatedDeltaNet":
        hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
        assert hk >= 1 and hv % hk == 0 and dk >= 1 and dv >= 1, (
            hk, hv, dk, dv
        )
        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        ch, taps = cfg.linear_channels, cfg.linear_conv
        # decays spread over time scales of a token to a few thousand
        tau = jnp.exp(jax.random.uniform(
            k6, (hv,), jnp.float32, math.log(1.5), math.log(4096.0)
        ))
        return GatedDeltaNet(
            wqkv=Linear.init(k1, cfg.n_embd, ch),
            conv=jax.random.normal(k2, (taps, ch), jnp.float32)
            / math.sqrt(taps),
            wg=Linear.init(k3, cfg.n_embd, hv * dv),
            wba=Linear.init(k4, cfg.n_embd, 2 * hv),
            a_log=jnp.zeros((hv,), jnp.float32),
            dt_bias=jnp.log(jnp.expm1(1.0 / tau)),  # softplus^-1(1 / tau)
            o_norm=RMSNorm.init(
                dv, use_weight=True, eps=cfg.norm_eps or 1e-6, impl="jnp"
            ),
            wo=Linear.init(k5, hv * dv, cfg.n_embd),
            key_heads=hk, value_heads=hv, key_dim=dk,
            neg_eigval=cfg.linear_neg_eigval,
        )

    @property
    def taps(self) -> int:
        return self.conv.shape[0]

    def _project(self, x: Array):
        """``x`` ``[B, T, D]`` -> the convolution's input ``[B, T, Ch]``, the
        gate ``[B, T, Hv dv]`` and, float32, ``beta`` and the log decay
        ``g`` ``[B, T, Hv]``."""
        f32 = jnp.float32
        hv = self.value_heads
        with jax.named_scope("gdn_proj"):
            raw = self.wqkv(x)
            gate = self.wg(x)
            ba = self.wba(x).astype(f32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            if self.neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(self.a_log.astype(f32)) * jax.nn.softplus(
                ba[..., hv:] + self.dt_bias.astype(f32)
            )
        return raw, gate, beta, g

    def _conv_heads(self, window: Array):
        """``window`` ``[B, taps - 1 + T, Ch]``: the tail, then T new inputs.
        Convolution and SiLU in float32, then per head q (L2-normalised,
        over sqrt(dk)), k (L2-normalised) ``[B, T, Hv, dk]`` and v
        ``[B, T, Hv, dv]`` in the inputs' type."""
        f32 = jnp.float32
        hk, hv, dk = self.key_heads, self.value_heads, self.key_dim
        b, n, ch = window.shape
        t = n - (self.taps - 1)
        with jax.named_scope("gdn_conv"):
            w = self.conv.astype(f32)
            y = sum(
                w[j] * jax.lax.slice_in_dim(window, j, j + t, axis=1).astype(f32)
                for j in range(self.taps)
            )
            y = jax.nn.silu(y)
            q = y[..., : hk * dk].reshape(b, t, hk, dk)
            k = y[..., hk * dk : 2 * hk * dk].reshape(b, t, hk, dk)
            v = y[..., 2 * hk * dk :].reshape(b, t, hv, -1)

            def unit(a):
                return a * jax.lax.rsqrt(
                    jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6
                )

            q, k = unit(q) * dk ** -0.5, unit(k)
            if hv != hk:  # value heads share key heads in groups
                q = jnp.repeat(q, hv // hk, axis=2)
                k = jnp.repeat(k, hv // hk, axis=2)
        dt = window.dtype
        return q.astype(dt), k.astype(dt), v.astype(dt)

    def _out(self, o: Array, gate: Array) -> Array:
        """The rule's output ``[B, T, Hv, dv]`` float32, normed per head and
        gated, through ``wo``."""
        b, t, hv, dv = o.shape
        with jax.named_scope("gdn_out"):
            z = jax.nn.silu(gate.astype(jnp.float32)).reshape(b, t, hv, dv)
            y = (self.o_norm(o) * z).astype(gate.dtype)
            return self.wo(y.reshape(b, t, hv * dv))

    def prefill_at(
        self,
        x: Array,  # [B, T, D]
        state: Array,  # [B, Hv, dk, dv] float32
        tail: Array,  # [B, taps - 1, Ch]
        real_n: tp.Optional[Array] = None,  # [] int32: rows past it are pad
    ) -> tp.Tuple[Array, Array, Array]:
        from midgpt_tpu.ops.gated_delta import chunked

        t = x.shape[1]
        raw, gate, beta, g = self._project(x)
        window = jnp.concatenate([tail.astype(raw.dtype), raw], axis=1)
        q, k, v = self._conv_heads(window)
        keep = self.taps - 1
        if real_n is None:
            tail = window[:, t:]
        else:
            # padding advances nothing: no decay, no write, and the tail
            # is the last inputs of the real rows
            live = (jnp.arange(t) < real_n)[None, :, None]
            beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
            tail = jax.lax.dynamic_slice_in_dim(window, real_n, keep, axis=1)
        o, state = chunked(q, k, v, g, beta, state)
        return self._out(o, gate), state, tail.astype(x.dtype)

    def __call__(self, x: Array) -> Array:
        b = x.shape[0]
        ch = self.conv.shape[1]
        dv = self.wo.weight.shape[0] // self.value_heads
        out, _, _ = self.prefill_at(
            x,
            jnp.zeros((b, self.value_heads, self.key_dim, dv), jnp.float32),
            jnp.zeros((b, self.taps - 1, ch), x.dtype),
        )
        return out

    def decode_at(
        self,
        x: Array,  # [S, 1, D] — one new token per decode slot
        states: Array,  # [Ll, S, Hv, dk, dv] float32, every linear layer's
        tails: Array,  # [Ll, S, taps - 1, Ch]
        layer: int,  # STATIC: this layer's row of both
        valid: Array,  # [S] bool — a slot whose token is none leaves its
        # state and tail as they were
    ) -> tp.Tuple[Array, Array, Array]:
        from midgpt_tpu.ops.gated_delta import step

        raw, gate, beta, g = self._project(x)
        tail = tails[layer]
        window = jnp.concatenate([tail.astype(raw.dtype), raw], axis=1)
        q, k, v = self._conv_heads(window)
        beta = jnp.where(valid[:, None], beta[:, 0], 0.0)
        g = jnp.where(valid[:, None], g[:, 0], 0.0)
        o, states = step(q[:, 0], k[:, 0], v[:, 0], g, beta, states, layer)
        new_tail = jnp.where(
            valid[:, None, None], window[:, 1:].astype(tails.dtype), tail
        )
        zero = jnp.zeros((), jnp.int32)
        tails = jax.lax.dynamic_update_slice(
            tails, new_tail[None],
            (jnp.asarray(layer, jnp.int32), zero, zero, zero),
        )
        return self._out(o[:, None], gate), states, tails


# a prefill chunk's f32 score block ``[heads, T, W + T]`` is held for the
# softmax: heads are taken in groups of at most this many bytes of it (256 rows
# against 33k pooled positions are 35 MB a head)
_LATENT_SCORE_BYTES = 512 * 1024 * 1024


@module
class LatentAttention:
    """Multi-head latent attention (DeepSeek-V2/V3's): queries through a
    low-rank bottleneck with an RMSNorm, keys and values up-projected from
    ONE normed latent ``c`` a token (``kv_rank`` wide), and a decoupled rotary
    key ``kr`` of ``rope`` lanes a token that all heads share beside each
    head's ``nope`` unrotated lanes. A head's score is ``(q_nope . k_nope +
    q_rope . kr) / sqrt(nope + rope)``, its value ``v_dim`` wide.

    What a layer caches a token is the pooled ROW ``[c | kr | 0...]`` — after
    the norm and after the rotation, zero-padded to whole lane tiles
    (``ModelConfig.latent_row``) — and nothing a head. Two orders of one sum:

    - PUBLISHED (:meth:`__call__`, a whole sequence from nothing): ``[k_nope
      | v]_h = c Wkvb_h``, then ordinary attention a head;
    - ABSORBED (the cached bodies): with ``Wkvb_h = [Wuk_h | Wuv_h]``,
      ``q^_h = q_nope_h Wuk_h^T``, a score is ``(q^_h . c + q_rope_h . kr)``
      — the head's query row ``[q^_h | q_rope_h]`` against the pooled row as
      it lies — ``o^_h = sum_t p_t c_t`` and ``o_h = o^_h Wuv_h``. To the
      attention core that is ONE KV head of ``n_head`` query rows whose key
      is the row and whose value is the row's first ``kv_rank`` lanes, so the
      decode step runs through :func:`_gather_attend` or the paged kernel's
      latent mode (ops.paged_attn: one page DMA serves both products) and the
      prefill chunk through the naive-attention choreography of
      :meth:`Attention.prefill_paged_at`; the scale stays that of the
      published head, never the row's width."""

    wq_a: Linear  # [D, q_rank]
    q_norm: RMSNorm  # over q_rank
    wq_b: Linear  # [q_rank, H (nope + rope)]: a head's q_nope | q_rope
    wkv_a: Linear  # [D, kv_rank + rope]: the latent | the rotary key
    kv_norm: RMSNorm  # over kv_rank
    wkv_b: Linear  # [kv_rank, H (nope + v_dim)]: a head's k_nope | v
    wo: Linear  # [H v_dim, D]
    n_head: int = static()
    nope: int = static()
    rope: int = static()
    v_dim: int = static()
    row: int = static()  # lanes of a pooled row
    rope_style: str = static(default="interleaved")

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "LatentAttention":
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        h, dn, dr, dv = (cfg.n_head, cfg.latent_nope, cfg.latent_rope,
                         cfg.latent_v)
        eps = cfg.norm_eps or 1e-6
        return LatentAttention(
            wq_a=Linear.init(k1, cfg.n_embd, cfg.latent_q),
            q_norm=RMSNorm.init(cfg.latent_q, True, eps, "jnp"),
            wq_b=Linear.init(k2, cfg.latent_q, h * (dn + dr)),
            wkv_a=Linear.init(k3, cfg.n_embd, cfg.latent_kv + dr),
            kv_norm=RMSNorm.init(cfg.latent_kv, True, eps, "jnp"),
            wkv_b=Linear.init(k4, cfg.latent_kv, h * (dn + dv)),
            wo=Linear.init(k5, h * dv, cfg.n_embd),
            n_head=h, nope=dn, rope=dr, v_dim=dv, row=cfg.latent_row,
            rope_style=cfg.rope_style,
        )

    @property
    def kv_rank(self) -> int:
        return self.wkv_b.weight.shape[-2]

    def _project(self, x: Array, sin_rows: Array, cos_rows: Array):
        """The prologue: ``x`` [S, T, D] to a head's ``q_nope`` [S, H, T,
        nope] and rotated ``q_rope`` [S, H, T, rope], and the tokens' pooled
        rows [S, T, row] = normed latent | rotated key | zeros."""
        s, t, _ = x.shape
        h, dn, dr, dc = self.n_head, self.nope, self.rope, self.kv_rank
        with jax.named_scope("mla_q"):
            q = self.wq_b(self.q_norm(self.wq_a(x))).reshape(s, t, h, dn + dr)
            q = jnp.transpose(q, (0, 2, 1, 3))  # [S, H, T, nope + rope]
            q_nope = q[..., :dn]
            q_rope = apply_rotary(
                q[..., dn:], sin_rows, cos_rows, self.rope_style
            )
        with jax.named_scope("mla_kv_a"):
            kv = self.wkv_a(x)  # [S, T, kv_rank + rope]
            c = self.kv_norm(kv[..., :dc])
            # one rotary key a token: a "head" axis of one, for the rows'
            # broadcast
            kr = apply_rotary(
                kv[..., dc:][:, None], sin_rows, cos_rows, self.rope_style
            )[:, 0]
            pad = jnp.zeros((s, t, self.row - dc - dr), c.dtype)
            rows = jnp.concatenate([c, kr, pad], axis=-1)
        return q_nope, q_rope, rows

    def _up(self):
        """``Wkvb`` a head: ``Wuk`` [kv_rank, H, nope], ``Wuv`` [kv_rank, H,
        v_dim]."""
        w = self.wkv_b.weight.reshape(
            self.kv_rank, self.n_head, self.nope + self.v_dim
        )
        return w[..., : self.nope], w[..., self.nope:]

    def _absorb(self, q_nope: Array, q_rope: Array) -> Array:
        """A head's query row against a pooled row: ``[q_nope Wuk^T | q_rope
        | 0...]`` [S, H, T, row]."""
        with jax.named_scope("mla_absorb"):
            q_hat = jnp.einsum(
                "shtn,chn->shtc", q_nope, self._up()[0].astype(q_nope.dtype)
            )
            pad = jnp.zeros(
                q_hat.shape[:-1] + (self.row - self.kv_rank - self.rope,),
                q_hat.dtype,
            )
            return jnp.concatenate([q_hat, q_rope, pad], axis=-1)

    def _out(self, o_lat: Array) -> Array:
        """The epilogue: a head's weighted latent ``o^`` [S, H, T, kv_rank]
        through ``Wuv`` and ``wo`` to [S, T, D]."""
        s, h, t, _ = o_lat.shape
        with jax.named_scope("mla_out"):
            o = jnp.einsum(
                "shtc,chv->sthv", o_lat, self._up()[1].astype(o_lat.dtype)
            )
            return self.wo(o.reshape(s, t, h * self.v_dim))

    def __call__(
        self, x: Array, sin, cos, *, return_kv: bool = False, **_
    ) -> Array:
        """A whole sequence from nothing, in the published form."""
        assert not return_kv, "a latent layer's cache is rows, not K/V"
        b, t, _ = x.shape
        h, dn, dc = self.n_head, self.nope, self.kv_rank
        q_nope, q_rope, rows = self._project(x, sin, cos)
        c, kr = rows[..., :dc], rows[..., dc : dc + self.rope]
        kv = self.wkv_b(c).reshape(b, t, h, dn + self.v_dim)
        scores = jnp.einsum(
            "bhtn,bshn->bhts", q_nope, kv[..., :dn],
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bhtr,bsr->bhts", q_rope, kr, preferred_element_type=jnp.float32
        )
        ii = jnp.arange(t)
        scores = jnp.where(ii[None, :] <= ii[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(
            scores / math.sqrt(dn + self.rope), axis=-1
        ).astype(x.dtype)
        o = jnp.einsum("bhts,bshv->bthv", probs, kv[..., dn:])
        return shard_act(
            self.wo(o.reshape(b, t, h * self.v_dim)), "batch", "seq", "embed"
        )

    def decode_paged_at(
        self,
        x: Array,  # [S, 1, D] — one new token per decode slot
        pool: Array,  # [L, NP, PS, row] the latent page pool, READ-ONLY here
        bt: Array,  # [S, Pmax] int32 block tables
        rk: Array,  # [L, S, 1, R, row] the window's recent rows
        layer: int,  # STATIC: this layer's row of ``pool`` and ``rk``
        r: Array,  # [] int32 — step index within the decode window
        mask_pool: Array,  # [S, W = Pmax*PS] additive f32
        mask_rec: Array,  # [R] additive f32
        sin_rows: Array,  # [S, 1, 1, rope // 2]
        cos_rows: Array,
        pooled_len: Array,  # [S] int32
        paged_kernel: str = "xla",
    ) -> tp.Tuple[Array, Array]:
        """One token a slot against the slot's pooled rows and the window's
        recent ones, absorbed: ``q^``, ONE attention call, ``Wuv``, ``wo``."""
        s = x.shape[0]
        h, dc = self.n_head, self.kv_rank
        q_nope, q_rope, rows = self._project(x, sin_rows, cos_rows)
        q_hat = self._absorb(q_nope, q_rope)  # [S, H, 1, row]
        zero = jnp.zeros((), r.dtype)
        rk = jax.lax.dynamic_update_slice(
            rk, rows.astype(rk.dtype)[None, :, None],
            (jnp.asarray(layer, r.dtype), zero, zero, r, zero),
        )
        rkl = rk[layer]  # [S, 1, R, row]
        with jax.named_scope("mla_decode"):
            if paged_kernel == "pallas":
                from midgpt_tpu.ops.paged_attn import paged_latent_attention

                out = paged_latent_attention(
                    q_hat[:, None, :, 0], pool, bt, pooled_len, rkl, r, layer,
                    v_lanes=dc, scale_dim=self.nope + self.rope,
                )  # [S, 1, H, kv_rank]
            else:
                out = _gather_attend(
                    q_hat[:, None], rkl, rkl[..., :dc],
                    mask_pool[:, None, None, None, :], mask_rec,
                    pool, None, None, None, bt, layer,
                    scale_dim=self.nope + self.rope,
                ).astype(x.dtype)  # [S, 1, H, 1, kv_rank]
        return self._out(out.reshape(s, h, 1, dc)), rk

    def prefill_paged_at(
        self,
        x: Array,  # [1, T, D] — the prefill chunk's hidden states
        pool: Array,  # [L, NP, PS, row], READ-ONLY here
        bt: Array,  # [1, Pmax] int32 — the slot's block table
        layer: int,  # STATIC
        mask_pool: Array,  # [W = Pmax*PS] additive f32 (0 where pos < start)
        mask_self: Array,  # [T, T] additive causal f32 within the chunk
        sin_rows: Array,  # [T, rope // 2]
        cos_rows: Array,
    ) -> tp.Tuple[Array, Array]:
        """A chunk's T rows against the slot's pooled rows and themselves,
        absorbed, in :meth:`Attention.prefill_paged_at`'s choreography
        (compute-dtype operands, f32 accumulation, mask before the scale,
        probabilities rounded to the value dtype): the pooled rows are read
        as they lie, nothing is up-projected a context token. Absorbed costs
        2 x 1,088 FLOP a head a key against 2 x 320 and ``kv_rank x H (nope +
        v_dim)`` a context token published: at 256 rows a chunk the same
        arithmetic within a quarter, without a [W, H, nope + v_dim]
        intermediate a layer (PERF.md section 6, PR 34). Returns the chunk's
        pooled rows [1, 1, T, row] for the page write."""
        _, t, _ = x.shape
        h, dc = self.n_head, self.kv_rank
        q_nope, q_rope, rows = self._project(x, sin_rows, cos_rows)
        q_hat = self._absorb(q_nope, q_rope)  # [1, H, T, row]
        own = rows[:, None]  # [1, 1, T, row]
        with jax.named_scope("mla_prefill"):
            ck = _gathered_pool_view(pool, None, bt, layer, 1)  # [1,1,row,W]
            ck = ck.astype(q_hat.dtype)
            w = ck.shape[-1]
            scale = 1.0 / jnp.sqrt(self.nope + self.rope).astype(jnp.float32)

            def attend(qg):  # [1, 1, G, T, row] -> [1, 1, G, T, kv_rank]
                s_pool = jnp.einsum(
                    "bhgtc,bhcw->bhgtw", qg, ck,
                    preferred_element_type=jnp.float32,
                )
                s_self = jnp.einsum(
                    "bhgtc,bhsc->bhgts", qg, own,
                    preferred_element_type=jnp.float32,
                )
                s_all = jnp.concatenate(
                    [s_pool + mask_pool, s_self + mask_self], axis=-1
                )
                probs = jax.nn.softmax(s_all * scale, axis=-1).astype(
                    own.dtype
                )
                return jnp.einsum(
                    "bhgtw,bhcw->bhgtc", probs[..., :w], ck[:, :, :dc]
                ) + jnp.einsum(
                    "bhgts,bhsc->bhgtc", probs[..., w:], own[..., :dc]
                )

            g = h
            while g % 2 == 0 and g * t * (w + t) * 4 > _LATENT_SCORE_BYTES:
                g //= 2
            if g == h:
                out = attend(q_hat[:, None])
            else:
                out = jax.lax.map(
                    attend, q_hat.reshape(h // g, 1, 1, g, t, self.row)
                )
            out = out.astype(x.dtype).reshape(1, h, t, dc)
        return self._out(out), own


def mlp_hidden_dim(cfg: ModelConfig) -> int:
    """MLP hidden width. Fractional ratios (SwiGLU's 8/3) round UP to a
    multiple of 256 — int(8/3 * 4096) = 10922 is not even lane-aligned
    and tiles terribly on the 128-wide MXU, while 256-rounding gives
    exactly Llama's published 11008 (the same rule Llama uses:
    multiple_of=256). Integral products (GELU 4x) are untouched;
    cfg.mlp_hidden pins an exact width (e.g. for old checkpoints)."""
    if cfg.mlp_hidden is not None:
        return cfg.mlp_hidden
    f = cfg.mlp_ratio * cfg.n_embd
    if f == int(f):
        return int(f)
    return 256 * -(-int(f) // 256)


def maybe_pin_mlp_hidden(cfg: ModelConfig, stored_params_meta: tp.Any) -> ModelConfig:
    """Reconcile ``cfg`` with a checkpoint's stored MLP width.

    Checkpoints written before fractional SwiGLU widths rounded up to a
    multiple of 256 hold ``int(mlp_ratio * n_embd)``-wide tensors; a config
    with ``mlp_hidden=None`` would now resolve to the rounded width and the
    restore templates would mismatch. Given the checkpoint's param METADATA
    (``Checkpointer.item_metadata()[...]["params"]`` — shapes only, no array
    reads), pin ``cfg.mlp_hidden`` to whatever width the checkpoint actually
    holds. No-op when the widths already agree or ``mlp_hidden`` is pinned."""
    import dataclasses

    if cfg.mlp_hidden is not None:
        return cfg
    stored = None
    for path, leaf in jax.tree_util.tree_flatten_with_path(stored_params_meta)[0]:
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        if "w_down" in keys:
            # blocks are layer-stacked: w_down.weight is [L, F, D]
            stored = int(leaf.shape[-2])
            break
    if stored is None or stored == mlp_hidden_dim(cfg):
        return cfg
    return dataclasses.replace(cfg, mlp_hidden=stored)


def pin_mlp_hidden_from_ckpt(cfg: ModelConfig, ckpt: tp.Any) -> ModelConfig:
    """The restore-time entry point for ``maybe_pin_mlp_hidden``: no-op
    unless the width is fractional and unpinned (the only case the
    256-rounding rule changed), so ordinary restores skip the checkpoint
    metadata read (and its Orbax handler warnings). ``ckpt`` is anything
    with ``item_metadata()`` returning a ``{"params": ...}`` metadata tree
    (midgpt_tpu.checkpoint.Checkpointer)."""
    if cfg.mlp_hidden is not None:
        return cfg
    if cfg.mlp_ratio * cfg.n_embd == int(cfg.mlp_ratio * cfg.n_embd):
        return cfg
    return maybe_pin_mlp_hidden(cfg, ckpt.item_metadata()["params"])


@module
class MLP:
    """GELU MLP (parity: model.py:17-31) or SwiGLU (Llama family)."""

    w_up: Linear  # [D, F]
    w_down: Linear  # [F, D]
    w_gate: tp.Optional[Linear]  # [D, F] (SwiGLU only)
    dropout_rate: float = static(default=0.0)

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "MLP":
        k1, k2, k3 = jax.random.split(key, 3)
        f = mlp_hidden_dim(cfg)
        if cfg.mlp == "swiglu":
            gate = Linear.init(k3, cfg.n_embd, f)
        elif cfg.mlp == "gelu":
            gate = None
        else:
            raise ValueError(f"unknown mlp kind {cfg.mlp!r}")
        return MLP(
            w_up=Linear.init(k1, cfg.n_embd, f),
            w_down=Linear.init(k2, f, cfg.n_embd),
            w_gate=gate,
            dropout_rate=cfg.dropout,
        )

    def __call__(
        self,
        x: Array,
        *,
        key: tp.Optional[KeyArray] = None,
        deterministic: bool = True,
    ) -> Array:
        with jax.named_scope("mlp"):
            up = self.w_up(x)
            if self.w_gate is not None:
                hidden = jax.nn.silu(self.w_gate(x)) * up
            else:
                hidden = jax.nn.gelu(up)
            hidden = shard_act(hidden, "batch", "seq", "mlp")
            out = self.w_down(hidden)
            out = dropout(out, self.dropout_rate, key, deterministic)
            return shard_act(out, "batch", "seq", "embed")


@module
class MoEMLP:
    """Switch-style top-1 mixture of GELU experts (Switch Transformer,
    arXiv:2101.03961) — the expert-parallel (ep) MLP variant. Absent from
    the reference (its MLP is dense, model.py:17-31); built TPU-first:

    - routing/dispatch as DENSE one-hot einsums with STATIC shapes — the
      canonical TPU MoE formulation (no sorts, no ragged gathers, every
      FLOP on the MXU); capacity is per batch row and scales with top_k
      (K claims per token share the buffers): C = ceil(cf * top_k * T / E).
    - experts stacked [E, D, F]/[E, F, D] and sharded over the 'tensor'
      mesh axis (GPT_PARAM_RULES): each shard computes its local experts'
      [B, E/tp, C, *] blocks and GSPMD inserts the psum on the combine
      contraction — expert parallelism without any hand-written
      collective.
    - the load-balance auxiliary loss (E * sum_e f_e * p_e; 1.0 when
      perfectly balanced) is returned next to the output and threaded to
      the trainer through the layer scan (GPT.hidden(return_aux=True)).

    Tokens overflowing an expert's capacity are dropped (contribute zero;
    the residual connection passes them through) — standard Switch
    semantics. At top_k > 1 capacity slots fill in TOKEN order with first
    and second choices interleaved (one cumsum over the combined
    assignment matrix) — a deliberate deviation from GShard, which fills
    every first choice before admitting any second choice. The single
    cumsum keeps the fill one static-shaped pass; the difference only
    shows under overflow, where GShard would evict a late token's FIRST
    choice in favor of an early token's second choice slightly less
    often. Router runs in f32 for a stable softmax."""

    router: Linear  # [D, E]
    expert_up: Array  # [E, D, F]
    expert_down: Array  # [E, F, D]
    capacity_factor: float = static(default=1.25)
    dropout_rate: float = static(default=0.0)
    top_k: int = static(default=1)  # 1 = Switch, 2 = GShard-style

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "MoEMLP":
        kr, ku, kd = jax.random.split(key, 3)
        e, d, f = cfg.moe_experts, cfg.n_embd, mlp_hidden_dim(cfg)
        # per-expert init identical to Linear.init (truncated normal,
        # lecun scaling) so experts start like the dense MLP they replace
        up = (1.0 / jnp.sqrt(d)) * jax.random.truncated_normal(
            ku, lower=-2, upper=2, shape=(e, d, f), dtype=jnp.float32
        )
        down = (1.0 / jnp.sqrt(f)) * jax.random.truncated_normal(
            kd, lower=-2, upper=2, shape=(e, f, d), dtype=jnp.float32
        )
        assert 1 <= cfg.moe_top_k <= e, cfg.moe_top_k
        return MoEMLP(
            router=Linear.init(kr, d, e),
            expert_up=up.astype(jnp.float32),
            expert_down=down.astype(jnp.float32),
            capacity_factor=cfg.moe_capacity,
            dropout_rate=cfg.dropout,
            top_k=cfg.moe_top_k,
        )

    @property
    def n_experts(self) -> int:
        return self.expert_up.shape[0]

    def __call__(
        self,
        x: Array,  # [B, T, D]
        *,
        key: tp.Optional[KeyArray] = None,
        deterministic: bool = True,
        return_dropped: bool = False,
    ) -> tp.Tuple[Array, ...]:
        """(y, aux) — with ``return_dropped`` also the dropped-claim
        fraction: routing claims past their expert's capacity contribute
        zero output (standard Switch drop semantics), and that fraction
        is the one silent failure mode of the subsystem — a collapsed
        router looks fine in the loss curve while most tokens pass
        through the residual untouched (VERDICT r5 Next #7)."""
        b, t, d = x.shape
        e = self.n_experts
        # GShard capacity: K claims per token share the buffers, so C
        # scales with top_k — at K=2 an unscaled C would drop ~(2-cf)/2
        # of all claims even under perfect balance (code review r5)
        cap = int(-(-self.capacity_factor * self.top_k * t // e))  # ceil
        cap = max(1, min(cap, t))
        k = self.top_k
        with jax.named_scope("moe"):
            # f32 router (tiny [D, E] matmul; softmax stability)
            logits = self.router(x.astype(jnp.float32))  # [B, T, E]
            # keep every routing tensor batch/seq-sharded: unconstrained,
            # GSPMD re-shards the [B,T,E] probs around top_k with a
            # batch all-gather (caught by the HLO audit)
            logits = shard_act(logits, "batch", "seq", None)
            probs = jax.nn.softmax(logits, axis=-1)
            # K iterative argmax extractions instead of lax.top_k: XLA's
            # TopK lowering under GSPMD replicates the batch dim (a
            # full-batch all-gather, caught by the HLO audit); max/argmax
            # reductions partition cleanly, and K is static and tiny
            vals, idxs = [], []
            remaining = probs
            for _ in range(k):
                vals.append(jnp.max(remaining, axis=-1))
                ix = jnp.argmax(remaining, axis=-1)
                idxs.append(ix)
                remaining = remaining * (
                    1.0 - jax.nn.one_hot(ix, e, dtype=probs.dtype)
                )
            topv = jnp.stack(vals, axis=-1)  # [B, T, K]
            topi = jnp.stack(idxs, axis=-1)
            topv = shard_act(topv, "batch", "seq", None)
            topi = shard_act(topi, "batch", "seq", None)
            # chosen-expert assignment matrix (<= K ones per token) and
            # per-(token, expert) combine weight: top-1 keeps the raw
            # Switch prob; K > 1 renormalizes the chosen gates to sum 1
            # (GShard) so identical experts reproduce the dense MLP
            choice_oh = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # [B,T,K,E]
            choice_oh = shard_act(choice_oh, "batch", "seq", None, None)
            assign = jnp.sum(choice_oh, axis=2)  # [B, T, E] in {0, 1}
            assign = shard_act(assign, "batch", "seq", None)
            gates = topv / jnp.sum(topv, axis=-1, keepdims=True) if k > 1 else topv
            w = jnp.einsum("btke,btk->bte", choice_oh, gates)  # [B, T, E]
            w = shard_act(w, "batch", "seq", None)

            # load-balance aux (Switch eq. 4) over FIRST choices
            first = choice_oh[:, :, 0]  # [B, T, E]
            frac = jnp.mean(first, axis=1)  # [B, E]
            pmean = jnp.mean(probs, axis=1)  # [B, E]
            aux = e * jnp.mean(jnp.sum(frac * pmean, axis=-1))

            # position of each (token, expert) claim within the expert's
            # capacity buffer — columns are independent, so one cumsum
            # covers any K. NOTE: this fills slots in token order with
            # 1st/2nd choices interleaved, NOT GShard's
            # first-choices-first order (see the class docstring) — a
            # deliberate trade of fill-priority fidelity for a single
            # static-shaped pass
            pos = jnp.cumsum(assign, axis=1) * assign  # [B, T, E], 1-based
            pos = shard_act(pos, "batch", "seq", None)
            keep = (assign * (pos <= cap)).astype(x.dtype)  # [B, T, E]
            keep = shard_act(keep, "batch", "seq", None)
            pos0 = jnp.clip(pos.astype(jnp.int32) - 1, 0, cap - 1)
            slot_oh = jax.nn.one_hot(pos0, cap, dtype=x.dtype)  # [B,T,E,C]

            # dispatch -> [B,E,C,D] (one-hot einsums: all static shapes,
            # all MXU)
            disp = keep[..., None] * slot_oh  # [B, T, E, C]
            disp = shard_act(disp, "batch", "seq", "expert", None)
            xe = jnp.einsum("btec,btd->becd", disp, x)
            xe = shard_act(xe, "batch", "expert", None, "embed")
            h = jax.nn.gelu(
                jnp.einsum(
                    "becd,edf->becf", xe, self.expert_up.astype(x.dtype)
                )
            )
            # NOT "mlp" on the last dim: it aliases 'tensor', which the
            # expert dim already occupies
            h = shard_act(h, "batch", "expert", None, None)
            ye = jnp.einsum(
                "becf,efd->becd", h, self.expert_down.astype(x.dtype)
            )
            # combine scaled by the per-expert gate (router grad path)
            comb = disp * w.astype(x.dtype)[..., None]
            y = jnp.einsum("btec,becd->btd", comb, ye)
            y = dropout(y, self.dropout_rate, key, deterministic)
            y = shard_act(y, "batch", "seq", "embed")
            if not return_dropped:
                return y, aux
            # fraction of routing claims past capacity (dropped): scalar
            # reductions partition cleanly under any mesh
            n_claims = jnp.sum(assign.astype(jnp.float32))
            n_kept = jnp.sum(keep.astype(jnp.float32))
            dropped = 1.0 - n_kept / jnp.maximum(n_claims, 1.0)
            return y, aux, dropped


@module
class ExpertMLP:
    """Dropless top-k mixture of SwiGLU experts (``mlp="experts"``): the
    sparse layer of the serving path, and the one to extend (MoEMLP above
    is the capacity-factor layer ``train()`` grew up with).

    ``p = softmax_f32(h Wr)`` over all E experts; the k largest are
    chosen; ``g = p / sum(chosen p)`` where ``renorm``; ``y = sum_e g_e
    (silu(h W1_e) * (h W3_e)) W2_e``. No capacity and no dropped token at
    any skew: the (token, expert) claims are SORTED BY EXPERT and the
    three projections are two grouped matmuls over the sorted rows
    (``ops.grouped.grouped_matmul``: rows [N*k, .] against [E, ., .] with
    the per-expert row counts; ``jax.lax.ragged_dot``, or on a TPU the
    Pallas grouped matmul that ships with JAX, by measurement) — work and
    memory are O(N k), there is no
    ``[tokens, experts, capacity]`` tensor, and an expert no claim chose
    costs nothing. ``w_in`` holds W1 | W3 side by side so that gate and up
    are one pass over the sorted rows. One body for every call shape: a
    prefill chunk's hundreds of rows, a denoising forward's slots x block,
    a ``train()`` batch (``ragged_dot`` differentiates).

    ``scoring="sigmoid"`` (DeepSeek-V3's router): ``p = sigmoid_f32(h
    Wr)``, each expert's own; ``bias`` [E] is added to ``p`` for the CHOICE
    of the k and never enters a weight; ``g = p / (sum(chosen p) + 1e-20)``
    where ``renorm``, times ``scale``. ``shared``: a dense SwiGLU every live
    row goes through, outside the sort, added once. With softmax scoring, no
    bias, scale 1 and no shared expert the traced layer is what it was.

    Not here (ROADMAP Reach A1): an expert axis over a mesh (under a
    tensor mesh the experts ride replicated), int8 expert tensors."""

    router: Linear  # [D, E]
    w_in: Array  # [E, D, 2F]: W1 (gate) | W3 (up)
    w_out: Array  # [E, F, D]: W2
    top_k: int = static()
    renorm: bool = static(default=True)
    bias: tp.Optional[Array] = None  # [E] f32: moves the choice only
    shared: tp.Optional[MLP] = None  # SwiGLU of shared_experts * F
    scoring: str = static(default="softmax")
    scale: float = static(default=1.0)

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "ExpertMLP":
        kr, ki, ko, kb, ks = jax.random.split(key, 5)
        e, d, f = cfg.experts, cfg.n_embd, cfg.expert_hidden
        assert e >= 1 and f >= 1 and 1 <= cfg.experts_per_token <= e, (
            e, f, cfg.experts_per_token
        )
        shared = None
        if cfg.shared_experts:
            k1, k2, k3 = jax.random.split(ks, 3)
            fs = cfg.shared_experts * f
            shared = MLP(
                w_up=Linear.init(k1, d, fs), w_down=Linear.init(k2, fs, d),
                w_gate=Linear.init(k3, d, fs),
            )
        w_in = (1.0 / math.sqrt(d)) * jax.random.truncated_normal(
            ki, lower=-2, upper=2, shape=(e, d, 2 * f), dtype=jnp.float32
        )
        w_out = (1.0 / math.sqrt(f)) * jax.random.truncated_normal(
            ko, lower=-2, upper=2, shape=(e, f, d), dtype=jnp.float32
        )
        return ExpertMLP(
            router=Linear.init(kr, d, e), w_in=w_in, w_out=w_out,
            top_k=cfg.experts_per_token, renorm=cfg.expert_renorm,
            # (a tenth of a sigmoid score's spread: it moves some choices)
            bias=0.025 * jax.random.normal(kb, (e,), jnp.float32)
            if cfg.expert_bias else None,
            shared=shared, scoring=cfg.expert_scoring,
            scale=float(cfg.expert_scale),
        )

    def __call__(
        self,
        x: Array,  # [..., D]
        *,
        return_rows: bool = False,
        stacked: tp.Optional[tp.Tuple[Array, Array, tp.Any]] = None,
        live: tp.Optional[Array] = None,  # [...] bool, one per row
    ) -> tp.Tuple[Array, ...]:
        """(y, aux), and with ``return_rows`` the rows routed to each
        expert ``[E]`` int32 (they sum to N k: nothing is dropped). ``aux``
        is Switch's load-balance loss over first choices, as MoEMLP's.

        ``live`` (the block window's forward, whose rows are not all a
        token's): a row that is not live claims no expert — its claims sort
        past the last group and are in no group's size, so the grouped
        matmul neither reads a matrix nor runs a tile for them — counts in
        no ``rows`` and gets ``y`` = 0. Absent, every row is live and the
        trace is what it was.

        ``stacked`` = (every layer's ``w_in`` [L, E, D, 2F], ``w_out``
        [L, E, F, D], this layer's index): the serving programs' way to
        the expert tensors. They hold the model stacked over layers and
        reach a layer by slicing; a grouped matmul is a kernel call that
        no slice fuses into, so the compiler would COPY each layer's
        0.8 + 0.4 GB in front of it (hoisted out of the window's scan, all
        layers at once: 6.5 GB of temporaries at 6 x 128 experts, found by
        compiling for a described v5e). Instead the stack is read as it
        lies, as L x E groups of which only this layer's have rows."""
        d = x.shape[-1]
        e, k = self.router.weight.shape[-1], self.top_k
        w_in, w_out = (self.w_in, self.w_out) if stacked is None else (
            a.reshape((-1,) + a.shape[2:]) for a in stacked[:2]
        )
        f = w_out.shape[1]
        xf = x.reshape(-1, d)
        with jax.named_scope("expert_route"):
            # the router in f32 on every backend: a default-precision f32
            # product is bf16 passes on the TPU, and the 8th-against-9th
            # expert decision is the layer's near-tie surface
            squash = (
                jax.nn.sigmoid if self.scoring == "sigmoid"
                else functools.partial(jax.nn.softmax, axis=-1)
            )
            probs = squash(
                jnp.matmul(
                    xf.astype(jnp.float32),
                    self.router.weight.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                ),
            )  # [N, E]
            if self.bias is None:
                topv, topi = jax.lax.top_k(probs, k)  # [N, k]
            else:
                _, topi = jax.lax.top_k(
                    probs + self.bias.astype(jnp.float32), k
                )
                topv = jnp.take_along_axis(probs, topi, axis=-1)
            norm = jnp.sum(topv, axis=-1, keepdims=True)
            if self.scoring == "sigmoid":
                norm = norm + 1e-20  # (the published router's guard)
            gates = topv / norm if self.renorm else topv
            if self.scale != 1.0:
                gates = gates * self.scale
            claims = topi.reshape(-1)  # [N k]: claim j is token j // k's
            if live is not None:
                # expert id E: past every group, dropped by the count below
                claims = jnp.where(
                    jnp.repeat(live.reshape(-1), k), claims, e
                )
            order = jnp.argsort(claims, stable=True)  # sorted by expert
            rows = jnp.zeros((e,), jnp.int32).at[claims].add(1)
            first = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
            aux = e * jnp.sum(
                jnp.mean(first, axis=0) * jnp.mean(probs, axis=0)
            )
            xs = jnp.take(xf, order // k, axis=0)  # [N k, D]
        y_shared = None
        if self.shared is not None:
            with jax.named_scope("expert_shared"):
                y_shared = self.shared(xf[None])[0].astype(jnp.float32)
        with jax.named_scope("expert_matmul"):
            groups = rows
            if stacked is not None:
                at = jnp.asarray(stacked[2], jnp.int32) * e
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros((w_in.shape[0],), jnp.int32), rows, (at,)
                )
            h = grouped_matmul(xs, w_in.astype(x.dtype), groups)
            act = jax.nn.silu(h[:, :f]) * h[:, f:]
            ys = grouped_matmul(act, w_out.astype(x.dtype), groups)
            # back to claim order, then the gated sum over a token's k
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype)
            )
            yk = jnp.take(ys, back, axis=0).reshape(-1, k, d)
            y = jnp.sum(yk.astype(jnp.float32) * gates[..., None], axis=1)
            if y_shared is not None:
                y = y + y_shared
            y = y.astype(x.dtype).reshape(x.shape)
            if live is not None:
                # (what the kernel left in rows of no group is not a number)
                y = jnp.where(live[..., None], y, 0)
        if return_rows:
            return y, aux, rows
        return y, aux


def make_mlp(key: KeyArray, cfg: ModelConfig):
    """MLP factory: dense (gelu/swiglu), MoE or routed experts by cfg.mlp."""
    if cfg.mlp == "moe":
        return MoEMLP.init(key, cfg)
    if cfg.mlp == "experts":
        return ExpertMLP.init(key, cfg)
    return MLP.init(key, cfg)


def stacked_experts(model, layer) -> tp.Optional[tp.Tuple]:
    """``ExpertMLP``'s ``stacked`` argument for ``layer`` of ``model``
    (None for every other kind of MLP): the expert tensors of the stack
    ``model.blocks`` and the layer's index there — behind
    ``config.dense_layers`` leading dense layers, which have none."""
    mlp = model.blocks.mlp
    dense = model.config.dense_layers
    if not isinstance(mlp, ExpertMLP) or (
        isinstance(layer, int) and layer < dense
    ):
        return None
    return mlp.w_in, mlp.w_out, layer - dense if dense else layer


def mlp_call(mlp, x, *, key=None, deterministic=True, with_stats=False,
             stacked=None, live=None):
    """(y, aux) for every MLP kind — dense returns aux = 0. With
    ``with_stats``: (y, aux, dropped_frac), 0 for the kinds that drop
    nothing. ``stacked`` and ``live`` are ExpertMLP's."""
    if isinstance(mlp, ExpertMLP):
        y, aux = mlp(x, stacked=stacked, live=live)
        return (y, aux, jnp.zeros((), jnp.float32)) if with_stats else (y, aux)
    if with_stats:
        if isinstance(mlp, MoEMLP):
            return mlp(
                x, key=key, deterministic=deterministic, return_dropped=True
            )
        y = mlp(x, key=key, deterministic=deterministic)
        zero = jnp.zeros((), jnp.float32)
        return y, zero, zero
    out = mlp(x, key=key, deterministic=deterministic)
    if isinstance(mlp, MoEMLP):
        return out
    return out, jnp.zeros((), jnp.float32)


@module
class Block:
    """Pre-norm residual block (parity: model.py:84-105). ``attn`` is the
    block's mixer: full attention, (``kind="linear"``) a gated delta rule,
    or (``kind="latent"``) latent attention; ``mlp`` what ``cfg.mlp`` says,
    or (``dense=True``: a leading dense layer) a SwiGLU of ``mlp_hidden``.
    ``norm_order="post"`` puts each norm on its sub-layer's OUTPUT, before
    the residual add."""

    attn: tp.Union[Attention, GatedDeltaNet, LatentAttention]
    mlp: tp.Union[MLP, "MoEMLP", "ExpertMLP"]
    ln1: RMSNorm
    ln2: RMSNorm
    norm_order: str = static(default="pre")

    def _pre(self, norm: RMSNorm, x: Array) -> Array:
        return norm(x) if self.norm_order == "pre" else x

    def _post(self, norm: RMSNorm, y: Array) -> Array:
        return norm(y) if self.norm_order == "post" else y

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig, kind: str = "full",
             dense: bool = False) -> "Block":
        import dataclasses

        k1, k2 = jax.random.split(key)
        mixer = {"linear": GatedDeltaNet, "latent": LatentAttention}.get(
            kind, Attention
        )
        return Block(
            norm_order=cfg.norm_order,
            attn=mixer.init(k1, cfg),
            mlp=make_mlp(
                k2, dataclasses.replace(cfg, mlp="swiglu") if dense else cfg
            ),
            # weightless block norms (model.py:94-95, layers.py:64-68)
            # unless the config asks for the learned scale
            ln1=RMSNorm.init(
                cfg.n_embd, use_weight=cfg.norm_scale,
                eps=cfg.norm_eps or 1e-6, impl=cfg.norm_impl,
            ),
            ln2=RMSNorm.init(
                cfg.n_embd, use_weight=cfg.norm_scale,
                eps=cfg.norm_eps or 1e-6, impl=cfg.norm_impl,
            ),
        )

    def __call__(
        self,
        x: Array,
        sin,
        cos,
        *,
        impl: str = "naive",
        key: tp.Optional[KeyArray] = None,
        deterministic: bool = True,
        return_kv: bool = False,
        return_aux: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, tp.Tuple[Array, Array]]]:
        attn_key, mlp_key = (
            jax.random.split(key) if key is not None else (None, None)
        )
        kv = None
        if isinstance(self.attn, GatedDeltaNet):
            assert not return_kv, "a linear-attention layer has no K/V"
            attn_out = self.attn(self._pre(self.ln1, x))
        else:
            attn_out = self.attn(
                self._pre(self.ln1, x), sin, cos, impl=impl, key=attn_key,
                deterministic=deterministic, return_kv=return_kv,
            )
            if return_kv:
                attn_out, kv = attn_out
        x = x + self._post(self.ln1, attn_out)
        y, aux = mlp_call(
            self.mlp, self._pre(self.ln2, x), key=mlp_key,
            deterministic=deterministic,
        )
        x = x + self._post(self.ln2, y)
        if return_aux:
            return ((x, aux), kv) if return_kv else (x, aux)
        return (x, kv) if return_kv else x

    def decode_at(self, x, cache_k, cache_v, layer, slot, mask, sin_row, cos_row):
        attn_out, cache_k, cache_v = self.attn.decode_at(
            self._pre(self.ln1, x), cache_k, cache_v, layer, slot, mask,
            sin_row, cos_row,
        )
        return self._mlp_residual(x, attn_out), cache_k, cache_v

    def decode_paged_at(
        self, x, pool_k, pool_v, bt, rk, rv, layer, r, mask_pool, mask_rec,
        sin_rows, cos_rows, pooled_len=None, pool_sk=None, pool_sv=None,
        paged_kernel="xla",
    ):
        with jax.named_scope("attention"):
            attn_out, rk, rv = self.attn.decode_paged_at(
                self._pre(self.ln1, x), pool_k, pool_v, bt, rk, rv, layer,
                r, mask_pool, mask_rec, sin_rows, cos_rows,
                pooled_len=pooled_len, pool_sk=pool_sk, pool_sv=pool_sv,
                paged_kernel=paged_kernel,
            )
        return self._mlp_residual(x, attn_out), rk, rv

    def _mlp_residual(self, x, mixer_out, **kw):
        """Both residual adds behind a mixer's output."""
        x = x + self._post(self.ln1, mixer_out)
        y = mlp_call(self.mlp, self._pre(self.ln2, x), **kw)[0]
        return x + self._post(self.ln2, y)

    def decode_latent_at(
        self, x, pool, bt, rk, layer, r, mask_pool, mask_rec, sin_rows,
        cos_rows, pooled_len, paged_kernel="xla", experts=None, live=None,
    ):
        """A latent-attention block's decode step (LatentAttention
        .decode_paged_at). ``experts``, ``live``: ExpertMLP's ``stacked`` and
        ``live``; of such a block also the rows routed to each expert [E]."""
        with jax.named_scope("attention"):
            out, rk = self.attn.decode_paged_at(
                self._pre(self.ln1, x), pool, bt, rk, layer, r, mask_pool,
                mask_rec, sin_rows, cos_rows, pooled_len,
                paged_kernel=paged_kernel,
            )
        if not isinstance(self.mlp, ExpertMLP):
            return self._mlp_residual(x, out), rk
        x = x + self._post(self.ln1, out)
        y, _, rows = self.mlp(
            self._pre(self.ln2, x), return_rows=True, stacked=experts,
            live=live,
        )
        return x + self._post(self.ln2, y), rk, rows

    def prefill_latent_at(
        self, x, pool, bt, layer, mask_pool, mask_self, sin_rows, cos_rows,
        experts=None,
    ):
        """A latent-attention block over one slot's prefill chunk
        (LatentAttention.prefill_paged_at)."""
        with jax.named_scope("attention"):
            out, rows = self.attn.prefill_paged_at(
                self._pre(self.ln1, x), pool, bt, layer, mask_pool,
                mask_self, sin_rows, cos_rows,
            )
        return self._mlp_residual(x, out, stacked=experts), rows

    def decode_state_at(self, x, states, tails, layer, valid):
        """A linear-attention block's decode step (GatedDeltaNet.decode_at)."""
        out, states, tails = self.attn.decode_at(
            self._pre(self.ln1, x), states, tails, layer, valid
        )
        return self._mlp_residual(x, out), states, tails

    def prefill_state_at(self, x, state, tail, real_n):
        """A linear-attention block over one slot's prefill chunk
        (GatedDeltaNet.prefill_at)."""
        out, state, tail = self.attn.prefill_at(
            self._pre(self.ln1, x), state, tail, real_n
        )
        return self._mlp_residual(x, out), state, tail

    def prefill_paged_at(
        self, x, pool_k, pool_v, bt, layer, mask_pool, mask_self,
        sin_rows, cos_rows, start=None, pool_sk=None, pool_sv=None,
        sp=False, experts=None,
    ):
        if not sp:
            with jax.named_scope("attention"):
                attn_out, k, v = self.attn.prefill_paged_at(
                    self._pre(self.ln1, x), pool_k, pool_v, bt, layer,
                    mask_pool, mask_self, sin_rows, cos_rows, start=start,
                    pool_sk=pool_sk, pool_sv=pool_sv,
                )
            return self._mlp_residual(x, attn_out, stacked=experts), k, v
        # Sequence-parallel prefill (Megatron-SP style): the per-token
        # segments that tensor parallelism leaves REPLICATED — ln1/ln2,
        # both residual adds — run with the chunk's T rows sharded over
        # 'tensor' (the 'sp' logical axis), and a pure all-gather of
        # rows restores full T before each parallel region. Every
        # floating-point op keeps its exact off-path operands: a row's
        # layernorm reduces over D inside that row, the gathers move
        # bytes without touching values, and the attention/matmul block
        # below is the IDENTICAL head-parallel arithmetic (one joint
        # softmax — the choreo prover checks the same signature either
        # way). Pinning attn/mlp outputs replicated BEFORE re-sharding
        # rows keeps the row-parallel psum an all-reduce — left free,
        # GSPMD may fuse it to reduce-scatter, whose partial-sum order
        # is not contractually the all-reduce's (the PR 9 lse-merge
        # lesson, one level down). That is what makes sp=True bitwise
        # against sp=False by construction rather than by tolerance.
        assert self.norm_order == "pre", self.norm_order
        x = shard_act(x, None, "sp", None)
        h1 = shard_act(self.ln1(x), None, None, None)  # gather rows
        with jax.named_scope("attention"):
            attn_out, k, v = self.attn.prefill_paged_at(
                h1, pool_k, pool_v, bt, layer, mask_pool, mask_self,
                sin_rows, cos_rows, start=start, pool_sk=pool_sk,
                pool_sv=pool_sv,
            )
        attn_out = shard_act(attn_out, None, None, None)  # pin the psum
        x = x + shard_act(attn_out, None, "sp", None)
        h2 = shard_act(self.ln2(x), None, None, None)  # gather rows
        mlp_out = shard_act(mlp_call(self.mlp, h2)[0], None, None, None)
        x = x + shard_act(mlp_out, None, "sp", None)
        return x, k, v

    def verify_paged_at(
        self, x, pool_k, pool_v, bt, layer, mask_pool, mask_self,
        sin_rows, cos_rows, start=None, pool_sk=None, pool_sv=None,
        paged_kernel="xla", block=1, expert_rows=False, experts=None,
        live=None,
    ):
        """``block`` > 1: the rows' own mask is causal across blocks of
        that many and bidirectional inside one (``mask_self`` says the
        same to the XLA path; the kernel builds its own from ``block``).
        ``expert_rows`` (an ExpertMLP model): also the rows routed to each
        expert here, ``[E]``. ``experts``, ``live``: ExpertMLP's
        ``stacked`` and ``live``."""
        assert self.norm_order == "pre", self.norm_order
        attn_out, k, v = self.attn.verify_paged_at(
            self.ln1(x), pool_k, pool_v, bt, layer, mask_pool, mask_self,
            sin_rows, cos_rows, start=start, pool_sk=pool_sk,
            pool_sv=pool_sv, paged_kernel=paged_kernel, block=block,
        )
        x = x + attn_out
        if expert_rows:
            y, _, rows = self.mlp(
                self.ln2(x), return_rows=True, stacked=experts, live=live
            )
            return x + y, k, v, rows
        x = x + mlp_call(
            self.mlp, self.ln2(x), stacked=experts, live=live
        )[0]
        return x, k, v


def embed_tokens(wte: Embedding, tokens: Array) -> Array:
    """Token embedding that stays SPMD-friendly under tensor parallelism.

    When the vocab dim is tensor-sharded (GPT_PARAM_RULES), a jnp.take
    whose indexed dim is sharded forces SPMD into involuntary full
    rematerialization; the TPU-native embedding under TP is a one-hot
    contraction — GSPMD turns the vocab-sharded einsum into a partial
    matmul + psum over 'tensor', and the MXU eats it. With an unsharded
    vocab the plain gather is cheaper. Shared by the batched forward and
    the KV-cache decode path."""
    mesh = current_mesh()
    with jax.named_scope("embed"):
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            one_hot = jax.nn.one_hot(
                tokens, wte.weight.shape[0], dtype=wte.weight.dtype
            )
            one_hot = shard_act(one_hot, "batch", "seq", "vocab")
            return one_hot @ wte.weight
        return wte(tokens)


@module
class GPT:
    """The full model. ``blocks`` leaves carry a leading n_layer axis."""

    wte: Embedding  # [V, D]
    blocks: Block  # stacked: every leaf [L, ...] (the full-attention layers)
    ln_f: RMSNorm
    lm_head: tp.Optional[Linear]  # [D, V]; None when tie_embeddings
    config: ModelConfig = static()
    # a model with ``config.layer_types``: its linear-attention layers, a
    # stack of their own; ``config.layer_plan`` says which layer is which
    lin_blocks: tp.Optional[Block] = None
    # the leading ``config.dense_layers`` layers, whose MLP is a dense SwiGLU
    # whatever the others' is: one stack a (mixer, MLP) kind present
    # (``config.stack_plan``)
    dense_blocks: tp.Optional[Block] = None

    def layer(self, n: int) -> Block:
        """Layer ``n`` of the model, out of the stack that holds it (static
        slices)."""
        stack, i = self.config.stack_plan[n]
        return jax.tree.map(lambda a: a[i], getattr(self, stack))

    @staticmethod
    def init(key: KeyArray, cfg: ModelConfig) -> "GPT":
        block_key, head_key = jax.random.split(key)
        block_keys = jax.random.split(block_key, cfg.n_layer)
        lin_blocks = dense_blocks = None
        kind = "latent" if cfg.latent else "full"
        if cfg.dense_layers:
            assert not cfg.linear_layers, "dense_layers beside layer_types"
            dense_blocks = jax.vmap(
                lambda k: Block.init(k, cfg, kind, dense=True)
            )(block_keys[: cfg.dense_layers])
            block_keys = block_keys[cfg.dense_layers:]
        if cfg.linear_layers:
            assert cfg.kv_layers, "a model needs a full-attention layer"
            where = {
                kind: jnp.asarray(
                    [i for i, (k, _) in enumerate(cfg.layer_plan) if k == kind]
                )
                for kind in ("full", "linear")
            }
            lin_blocks = jax.vmap(lambda k: Block.init(k, cfg, "linear"))(
                block_keys[where["linear"]]
            )
            block_keys = block_keys[where["full"]]
        blocks = jax.vmap(lambda k: Block.init(k, cfg, kind))(block_keys)
        embed_std = 1 / math.sqrt(cfg.n_embd)
        wte_wt = embed_std * jax.random.normal(
            head_key, (cfg.vocab_size, cfg.n_embd), dtype=jnp.float32
        )
        if cfg.tie_embeddings:
            lm_head = None  # reuse wte.weight.T at the head
        else:
            # reference semantics: same init array, independent params
            # (model.py:134-138; SURVEY.md 2.3 "init-only tying")
            lm_head = Linear(weight=wte_wt.T)
        return GPT(
            wte=Embedding(weight=wte_wt),
            blocks=blocks,
            ln_f=RMSNorm.init(
                cfg.n_embd, use_weight=cfg.norm_scale,
                eps=cfg.norm_eps or 1e-5, impl=cfg.norm_impl,
            ),
            lm_head=lm_head,
            config=cfg,
            lin_blocks=lin_blocks,
            dense_blocks=dense_blocks,
        )

    def hidden(
        self,
        tokens: Array,  # [B, T] int32
        *,
        key: tp.Optional[KeyArray] = None,
        deterministic: bool = True,
        attn_impl: tp.Optional[str] = None,
        return_kv: bool = False,
        return_aux: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, tp.Tuple[Array, Array]]]:
        """[B, T, D] final (ln_f-normalized) hidden states; with
        ``return_kv`` also the per-layer post-rope K / raw V stacked
        [L, B, Hkv, T, C] (collected as scan ys — the prefill path).
        ``return_aux`` additionally returns the MoE load-balance loss
        SUMMED over layers (the scan carries ``aux_in + aux``; 0.0 for
        dense MLPs) — the trainer consumes it when cfg.mlp == "moe"
        (train.loss_fn scales the sum by ``moe_aux_weight``, so the
        effective per-layer weight shrinks as 1/n_layer relative to a
        mean; Switch's own formulation also sums over layers)."""
        cfg = self.config
        impl = attn_impl if attn_impl is not None else cfg.attn_impl
        b, t = tokens.shape
        assert t <= cfg.block_size, f"sequence {t} > block_size {cfg.block_size}"
        sin, cos = rope_tables(cfg.rope_dim, t, cfg.rope_base)

        drop_key, scan_keys = (None, None)
        if key is not None:
            drop_key, block_key = jax.random.split(key)
            scan_keys = jax.random.split(block_key, cfg.n_layer)

        with jax.named_scope("gpt"):
            h = embed_tokens(self.wte, tokens)  # [B, T, D]
            h = dropout(h, cfg.dropout, drop_key, deterministic)
            h = shard_act(h, "batch", "seq", "embed")

            def body(carry, layer):
                block, k = layer
                if return_aux:
                    h_in, aux_in = carry
                    out = block(
                        h_in, sin, cos, impl=impl, key=k,
                        deterministic=deterministic, return_kv=return_kv,
                        return_aux=True,
                    )
                    if return_kv:
                        (h_out, aux), kv = out
                        return (h_out, aux_in + aux), kv
                    h_out, aux = out
                    return (h_out, aux_in + aux), None
                out = block(
                    carry, sin, cos, impl=impl, key=k,
                    deterministic=deterministic, return_kv=return_kv,
                )
                if return_kv:
                    return out  # (x, (k, v)) — kv stacked by scan as ys
                return out, None

            if cfg.remat == "full":
                # whole-block remat (parity: model.py:149-153)
                body = jax.checkpoint(body)
            elif cfg.remat == "dots":
                body = jax.checkpoint(
                    body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                )
            elif cfg.remat not in ("none", "auto"):
                # "auto" reaching the model means no trainer resolved it
                # (inference/sampling) — remat is moot without gradients,
                # so it behaves as "none"; train() resolves it by HBM fit
                # (midgpt_tpu.train.resolve_auto_knobs)
                raise ValueError(f"unknown remat policy {cfg.remat!r}")

            unroll = cfg.scan_unroll if cfg.scan_unroll else cfg.n_layer
            carry0 = (h, jnp.zeros((), jnp.float32)) if return_aux else h
            if self.lin_blocks is not None or self.dense_blocks is not None:
                # layers of two kinds: no one scan body fits them, so the
                # plan is walked layer by layer
                assert not return_kv, "return_kv needs full attention only"
                carry, kvs = carry0, None
                for i in range(cfg.n_layer):
                    k = None if scan_keys is None else scan_keys[i]
                    carry, _ = body(carry, (self.layer(i), k))
            else:
                carry, kvs = jax.lax.scan(
                    body, carry0, (self.blocks, scan_keys), unroll=unroll
                )
            if return_aux:
                # SUM over layers (Switch eq. 4 applies alpha per layer
                # and sums) — a mean would weaken balancing pressure by
                # n_layer (code review r5)
                h, aux = carry
            else:
                h = carry
            h = self.ln_f(h)
            if return_aux:
                return ((h, kvs), aux) if return_kv else (h, aux)
            return (h, kvs) if return_kv else h

    def moe_stats(
        self, tokens: Array, *, attn_impl: tp.Optional[str] = None
    ) -> tp.Dict[str, Array]:
        """Router telemetry from one deterministic forward: the MoE
        load-balance aux (summed over layers, the training convention)
        and the dropped-claim fraction (mean over layers). Runs its own
        layer scan so the hot ``hidden`` path carries no stats plumbing;
        the trainer calls this once per eval interval (utils.metrics logs
        the two scalars) — a collapsed or overflowing router becomes
        visible the interval it happens instead of never."""
        cfg = self.config
        assert cfg.mlp == "moe", "moe_stats requires an MoE model"
        impl = attn_impl if attn_impl is not None else cfg.attn_impl
        b, t = tokens.shape
        sin, cos = rope_tables(cfg.head_dim, t, cfg.rope_base)

        with jax.named_scope("moe_stats"):
            h = embed_tokens(self.wte, tokens)
            h = shard_act(h, "batch", "seq", "embed")

            def body(hc, block):
                attn_out = block.attn(
                    block.ln1(hc), sin, cos, impl=impl, deterministic=True
                )
                hc = hc + attn_out
                y, aux, dropped = mlp_call(
                    block.mlp, block.ln2(hc), with_stats=True
                )
                return hc + y, (aux, dropped)

            _, (auxs, droppeds) = jax.lax.scan(body, h, self.blocks)
        return {
            "aux": jnp.sum(auxs),
            "dropped_frac": jnp.mean(droppeds),
        }

    def head_weight(self, dtype) -> Array:
        """[D, V] lm-head weight in ``dtype`` (the shared wte array when
        init-only-tied/tied, SURVEY.md 2.3). Full-precision heads only —
        a quantized head has no standalone weight to hand out (the scale
        belongs in the matmul epilogue); use :meth:`project`."""
        assert not hasattr(self.lm_head, "scale"), (
            "quantized head: use GPT.project — materializing "
            "head_weight would dequantize the full [D, V] matrix"
        )
        return (
            self.wte.weight.T.astype(dtype)
            if self.lm_head is None
            else self.lm_head.weight.astype(dtype)
        )

    def project(self, h: Array) -> Array:
        """Hidden states ``[..., D]`` -> vocab logits ``[..., V]`` — the
        ONE lm-head entry point every forward/decode/prefill/verify path
        uses. For a quantized model (midgpt_tpu.quant) this fuses the
        dequant epilogue ``(h @ w_int8) * scale`` so the int8 head is
        what streams from HBM; full-precision models keep the plain
        ``h @ head_weight`` contraction (bit-identical to the
        pre-quantization code path)."""
        from midgpt_tpu.quant import QuantLinear

        with jax.named_scope("head"):
            if isinstance(self.lm_head, QuantLinear):
                return self.lm_head(h)
            return h @ self.head_weight(h.dtype)

    def __call__(
        self,
        tokens: Array,  # [B, T] int32
        *,
        key: tp.Optional[KeyArray] = None,
        deterministic: bool = True,
        attn_impl: tp.Optional[str] = None,
    ) -> Array:  # [B, T, V] logits in compute dtype
        h = self.hidden(
            tokens, key=key, deterministic=deterministic, attn_impl=attn_impl
        )
        logits = self.project(h)  # [B, T, V]
        return shard_act(logits, "batch", "seq", "vocab")


@module
class KVCache:
    """Per-layer KV cache; leaves carry a leading n_layer axis, matching the
    scan-stacked block params.

    TIME IS THE MINOR DIM ([..., C, W], not [..., W, C]): TPU tiles the last
    two dims to (8, 128), so a W-major cache with C=64 pads every 64-lane
    row to 128 — 2x the HBM footprint AND half the effective read bandwidth
    on the decode path, which is cache-read-bound (measured 1.33 us/slot vs
    the 0.36 us roofline, PERF.md r4). With W minor the tiles are full:
    C=64 sublanes are a legal multiple of 8 and W pads only to the next 128.
    The attention einsums contract identically either way — only the index
    order changes."""

    k: Array  # [L, B, Hkv, C, T_max]
    v: Array  # [L, B, Hkv, C, T_max]

    @staticmethod
    def init(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> "KVCache":
        shape = (cfg.n_layer, batch, cfg.kv_heads, cfg.head_dim, max_len)
        return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def decode_step(
    model: GPT,
    tokens: Array,  # [B] int32 — the newest token per sequence
    pos: Array,  # [] int32 — ABSOLUTE position of this token (tokens so far)
    cache: KVCache,
    rope_len: tp.Optional[int] = None,
) -> tp.Tuple[Array, KVCache]:
    """One incremental decoding step: logits for the next token + updated
    cache. O(W) per token vs the reference's O(T * full-forward)
    (sample.py:72-94). Append-at-``pos`` decoding into a cache of W slots,
    ``pos < W``: the plain oracle of the paged serving programs
    (``sampling.generate`` re-runs the cropped full forward once the
    window would have to slide). ``rope_len`` sizes the rope tables
    (defaults to W).

    The layer loop is STRAIGHT-LINE code over static layer slices — not a
    lax.scan. Scanning the cache through as xs/ys re-stacked every element
    of both [L, B, Hkv, W, C] caches per token (~300 MB at 124M, ~6x the
    weights); serving is HBM-bound, so that re-stack dominated the step.
    Unrolled, each layer is one in-place row write + a static-slice read,
    the block weights stream exactly once per token, and XLA fuses the
    whole layer into a handful of kernels."""
    cfg = model.config
    assert model.lin_blocks is None and not cfg.latent, (
        "decode_step needs full attention only"
    )
    w = cache.k.shape[-1]
    sin_np, cos_np = rope_tables(cfg.head_dim, rope_len or w, cfg.rope_base)
    sin_t, cos_t = jnp.asarray(sin_np), jnp.asarray(cos_np)

    # slot s holds position s: written (and causal) iff s <= pos
    mask = jnp.where(jnp.arange(w) <= pos, 0.0, -jnp.inf).astype(jnp.float32)
    sin_row = jax.lax.dynamic_slice_in_dim(sin_t, pos, 1, axis=0)
    cos_row = jax.lax.dynamic_slice_in_dim(cos_t, pos, 1, axis=0)

    h = embed_tokens(model.wte, tokens[:, None])  # [B, 1, D]
    ck, cv = cache.k, cache.v
    sin_h, cos_h = sin_row.astype(h.dtype), cos_row.astype(h.dtype)
    for i in range(cfg.n_layer):
        block = jax.tree.map(lambda a: a[i], model.blocks)  # static slices
        h, ck, cv = block.decode_at(h, ck, cv, i, pos, mask, sin_h, cos_h)
    h = model.ln_f(h)
    logits = model.project(h)[:, 0, :]  # [B, V]
    return logits, KVCache(k=ck, v=cv)


def decode_step_paged(
    model: GPT,
    tokens: Array,  # [S] int32 — the newest token per decode slot
    pos: Array,  # [S] int32 — PER-SLOT absolute position of this token
    pool_k: Array,  # [L, NP, PS, Hkv*C] page pool, READ-ONLY here
    pool_v: Array,  # [L, NP, PS, Hkv*C]
    bt: Array,  # [S, Pmax] int32 per-slot block tables
    rk: Array,  # [L, S, Hkv, R, C] recent buffers
    rv: Array,
    r: Array,  # [] int32 — step index within the decode window
    pooled_len: Array,  # [S] int32 — tokens already flushed to the pool
    rope_len: int,
    pool_sk: tp.Optional[Array] = None,  # [L, NP, Hkv] f32 (int8 pool)
    pool_sv: tp.Optional[Array] = None,
    paged_kernel: str = "xla",
    layer_scan: str = "off",
    state: tp.Optional[tp.Tuple[Array, Array]] = None,
    valid: tp.Optional[Array] = None,
) -> tp.Tuple[Array, ...]:
    """One decode step of the continuous-batching engine: every slot
    attends over its OWN block-table pages (positions < pooled_len[s])
    plus the shared recent buffer (window positions pooled_len[s]..r),
    and appends its token's K/V to the recent buffer. The pool is never
    written here — ``midgpt_tpu.serving.flush_recent`` folds the window's
    rows into the pages in one bulk scatter at window end (why:
    ``Attention.decode_paged_at``). There is no sliding window: pages
    are append-only and the engine caps each request at ``block_size``
    total tokens.

    ``layer_scan="on"`` folds the layer loop into ONE ``lax.scan`` over
    the stacked block params (ROADMAP item 1: the unrolled loop
    re-dispatches the whole per-layer kernel set L times per step —
    launch overhead the [B, 1, D] decode shapes cannot hide). The
    read-only pool planes, scale planes and recent buffers ride as
    per-layer scan ``xs`` (a dynamic-slice read per iteration — nothing
    re-stacks, unlike carrying a written cache through a scan, the
    decode_step trap) and each iteration returns its layer's updated
    recent rows as ``ys``. The body calls the very same
    ``Block.decode_paged_at`` as the unrolled path, on a ``[1, ...]``
    per-layer view with ``layer=0``, so the scan BODY's arithmetic is
    op-for-op the per-layer trace — which the scan-equivalence prover
    (midgpt_tpu.analysis.fusion, CI serving-choreo job) proves
    statically and the bitwise on-vs-off token-identity matrix pins at
    runtime.

    A model with linear-attention layers (``config.layer_plan``) also takes
    ``state`` — every linear layer's per-slot states ``[Ll, S, Hv, dk, dv]``
    and convolution tails ``[Ll, S, taps - 1, Ch]`` — and ``valid`` ``[S]``
    (a slot whose token is none leaves both as they were), and returns the
    new ``state`` after ``rk, rv``. The pool's and the recent buffers' layer
    axis counts the full-attention layers only.

    A latent-attention model (``config.latent``): ``pool_k`` is the latent
    pool and ``rk`` the window's recent rows ``[L, S, 1, R, row]``;
    ``pool_v`` and ``rv`` are None, and come back None. ``valid`` ``[S]``: a
    slot whose token is none claims no expert; of a model with expert layers
    the rows routed to each expert of each expert layer ``[Le, E]`` int32
    are returned last."""
    cfg = model.config
    s = tokens.shape[0]
    pmax = bt.shape[1]
    ps = pool_k.shape[2]
    rr = rk.shape[3]
    # KV-head-sharded pool (TP serving): pages and the time dim stay
    # whole per shard, so every block-table gather below is shard-local
    if not cfg.latent:
        pool_k = shard_act(pool_k, None, None, None, "kv_heads")
        pool_v = shard_act(pool_v, None, None, None, "kv_heads")
    if pool_sk is not None:
        pool_sk = shard_act(pool_sk, None, None, "kv_heads")
        pool_sv = shard_act(pool_sv, None, None, "kv_heads")
    sin_np, cos_np = rope_tables(cfg.rope_dim, rope_len, cfg.rope_base)
    sin_t, cos_t = jnp.asarray(sin_np), jnp.asarray(cos_np)

    # paged slot j of the gathered [W = Pmax*PS] view holds logical
    # position j for that slot; valid iff already flushed to the pool
    idx = jnp.arange(pmax * ps)
    mask_pool = jnp.where(
        idx[None, :] < pooled_len[:, None], 0.0, -jnp.inf
    ).astype(jnp.float32)  # [S, W]
    # recent row j holds the slot's window position pooled_len + j;
    # causal bound j <= r (rows > r are unwritten). Always >= 1 valid
    # row (row r = the token itself), so empty slots never softmax over
    # an all-masked axis.
    ridx = jnp.arange(rr)
    mask_rec = jnp.where(ridx <= r, 0.0, -jnp.inf).astype(jnp.float32)
    pos_c = jnp.clip(pos, 0, rope_len - 1)
    sin_rows = jnp.take(sin_t, pos_c, axis=0)[:, None, None, :]  # [S,1,1,C/2]
    cos_rows = jnp.take(cos_t, pos_c, axis=0)[:, None, None, :]

    h = embed_tokens(model.wte, tokens[:, None])  # [S, 1, D]
    sin_h, cos_h = sin_rows.astype(h.dtype), cos_rows.astype(h.dtype)
    assert layer_scan in ("on", "off"), layer_scan
    assert (state is None) == (model.lin_blocks is None)
    expert_rows = []
    if layer_scan == "on":
        assert state is None and not cfg.latent, (
            "layer_scan='on' needs identical full-attention layers"
        )
        quant = pool_sk is not None

        def body(hc, xs):
            block, pk_l, pv_l, rk_l, rv_l = xs[:5]
            sk_l = xs[5][None] if quant else None
            sv_l = xs[6][None] if quant else None
            hc, rk1, rv1 = block.decode_paged_at(
                hc, pk_l[None], pv_l[None], bt, rk_l[None], rv_l[None],
                0, r, mask_pool, mask_rec, sin_h, cos_h,
                pooled_len=pooled_len, pool_sk=sk_l, pool_sv=sv_l,
                paged_kernel=paged_kernel,
            )
            return hc, (rk1[0], rv1[0])

        xs = (model.blocks, pool_k, pool_v, rk, rv)
        if quant:
            xs = xs + (pool_sk, pool_sv)
        h, (rk, rv) = jax.lax.scan(body, h, xs)
    else:
        for n, (kind, i) in enumerate(cfg.layer_plan):
            block = model.layer(n)
            if kind == "linear":
                h, *state = block.decode_state_at(h, *state, i, valid)
                continue
            if kind == "latent":
                h, rk, *rows = block.decode_latent_at(
                    h, pool_k, bt, rk, i, r, mask_pool, mask_rec, sin_h,
                    cos_h, pooled_len, paged_kernel=paged_kernel,
                    experts=stacked_experts(model, n),
                    live=None if valid is None else valid[:, None],
                )
                expert_rows += rows
                continue
            h, rk, rv = block.decode_paged_at(
                h, pool_k, pool_v, bt, rk, rv, i, r, mask_pool, mask_rec,
                sin_h, cos_h, pooled_len=pooled_len, pool_sk=pool_sk,
                pool_sv=pool_sv, paged_kernel=paged_kernel,
            )
    h = model.ln_f(h)
    # vocab-sharded logits (TP lm head is column-parallel): nothing here
    # gathers the [S, V] row — greedy argmax partitions over 'tensor'
    logits = shard_act(model.project(h)[:, 0, :], None, "vocab")  # [S, V]
    if state is not None:
        return logits, rk, rv, tuple(state)
    if expert_rows:
        return logits, rk, rv, jnp.stack(expert_rows)
    return logits, rk, rv


def prefill_chunk_paged(
    model: GPT,
    tokens: Array,  # [1, T] int32 — one prefill chunk (right-padded)
    start: Array,  # [] int32 — absolute position of chunk token 0
    pool_k: Array,  # [L, NP, PS, Hkv*C] page pool, READ-ONLY here
    pool_v: Array,
    bt: Array,  # [1, Pmax] int32 — the slot's block table
    rope_len: int,
    pool_sk: tp.Optional[Array] = None,  # [L, NP, Hkv] f32 (int8 pool)
    pool_sv: tp.Optional[Array] = None,
    layer_scan: str = "off",
    sp: bool = False,
    block_len: int = 0,
    state: tp.Optional[tp.Tuple[Array, Array]] = None,
    real_n: tp.Optional[Array] = None,
) -> tp.Tuple[Array, ...]:
    """Suffix-only prefill of one chunk against a pre-populated block
    table: the chunk's tokens (context positions ``start .. start+T-1``)
    attend to everything already resident in the slot's pages (positions
    ``< start`` — the prefix-cache hit and/or earlier chunks of the same
    prompt) plus themselves, causally, in one joint softmax per layer.

    ``sp=True`` (ServingEngine ``prefill_sp``) is the sequence-parallel
    variant: the chunk's T rows are sharded over 'tensor' (logical axis
    'sp') through every segment tensor parallelism otherwise replicates
    — embedding output, ln1/ln2, the residual adds, ln_f — with row
    all-gathers restoring full T at each parallel-region boundary. The
    attention and matmul arithmetic is byte-for-byte the sp=False code
    (same one-joint-softmax choreography; see Block.prefill_paged_at),
    so streams are bitwise identical while the replicated O(T·D)
    per-token work and activation traffic scale 1/tp.

    This is what makes both tentpole features exact rather than
    approximate: a prefix-cache hit skips the cached pages' prefill
    compute entirely (only the suffix runs through here), and chunked
    Sarathi-style prefill resumes a long prompt mid-stream from the
    partially-built block table — in both cases the attention each token
    sees is identical to the monolithic full-prompt forward.

    Returns ``(h, ks, vs)``: the chunk's final hidden states [1, T, D]
    (logits come from the last REAL row) and the per-layer post-rope K /
    raw V [L, 1, Hkv, T, C] for the page write
    (serving.paged.write_token_rows). Pad rows beyond the chunk's real
    length are harmless: causally invisible to real rows (they sit at
    LATER positions) and their K/V rows are masked out of the write.

    A model with linear-attention layers takes the slot's ``state`` as the
    chunk finds it — states ``[Ll, 1, Hv, dk, dv]`` and convolution tails
    ``[Ll, 1, taps - 1, Ch]`` — and ``real_n`` (pad rows advance neither),
    and returns the state behind the chunk's last real row after ``ks,
    vs``, which then count the full-attention layers only.

    A latent-attention model: ``pool_k`` is the latent pool, ``pool_v``
    None; ``ks`` are the chunk's pooled rows ``[L, 1, 1, T, row]`` and
    ``vs`` is None."""
    cfg = model.config
    b, t = tokens.shape
    assert b == 1, f"chunk prefill is per-slot, got batch {b}"
    pmax = bt.shape[1]
    ps = pool_k.shape[2]
    if not cfg.latent:
        pool_k = shard_act(pool_k, None, None, None, "kv_heads")
        pool_v = shard_act(pool_v, None, None, None, "kv_heads")
    if pool_sk is not None:
        pool_sk = shard_act(pool_sk, None, None, "kv_heads")
        pool_sv = shard_act(pool_sv, None, None, "kv_heads")
    sin_np, cos_np = rope_tables(cfg.rope_dim, rope_len, cfg.rope_base)
    sin_t, cos_t = jnp.asarray(sin_np), jnp.asarray(cos_np)

    # paged slot w of the gathered [W = Pmax*PS] view holds logical
    # position w; resident (and < any chunk position) iff w < start
    idx = jnp.arange(pmax * ps)
    mask_pool = jnp.where(idx < start, 0.0, -jnp.inf).astype(jnp.float32)
    # in-chunk causal mask; row i may attend chunk rows j <= i — or, for
    # a block-diffusion model (``block_len`` > 0; ``start`` is then a
    # multiple of it), every row of its own block and of those before
    ii = jnp.arange(t)
    bi = ii // block_len if block_len > 1 else ii
    mask_self = jnp.where(
        bi[None, :] <= bi[:, None], 0.0, -jnp.inf
    ).astype(jnp.float32)  # [T, T]
    pos = jnp.clip(start + ii, 0, rope_len - 1)  # pad tail clips harmlessly
    sin_rows = jnp.take(sin_t, pos, axis=0)  # [T, C//2]
    cos_rows = jnp.take(cos_t, pos, axis=0)

    h = embed_tokens(model.wte, tokens)  # [1, T, D]
    if sp:
        # pin the embedding's vocab psum replicated (identical all-reduce
        # to the sp=False trace) before slicing rows locally
        h = shard_act(h, None, None, None)
        h = shard_act(h, None, "sp", None)
    sin_h, cos_h = sin_rows.astype(h.dtype), cos_rows.astype(h.dtype)
    assert layer_scan in ("on", "off"), layer_scan
    assert (state is None) == (model.lin_blocks is None)
    if layer_scan == "on":
        assert state is None and not cfg.latent, (
            "layer_scan='on' needs identical full-attention layers"
        )
        # layer loop folded into one lax.scan (see decode_step_paged):
        # read-only pool/scale planes ride as xs, the chunk's per-layer
        # K/V land as scan ys — exactly the jnp.stack of the unrolled
        # loop, produced a layer at a time
        quant = pool_sk is not None

        def body(hc, xs):
            block, pk_l, pv_l = xs[:3]
            sk_l = xs[3][None] if quant else None
            sv_l = xs[4][None] if quant else None
            hc, k, v = block.prefill_paged_at(
                hc, pk_l[None], pv_l[None], bt, 0, mask_pool, mask_self,
                sin_h, cos_h, start=start, pool_sk=sk_l, pool_sv=sv_l,
                sp=sp, experts=stacked_experts(model, xs[-1]),
            )
            return hc, (k, v)

        xs = (model.blocks, pool_k, pool_v)
        if quant:
            xs = xs + (pool_sk, pool_sv)
        if stacked_experts(model, 0) is not None:
            xs = xs + (jnp.arange(cfg.n_layer, dtype=jnp.int32),)
        h, (ks, vs) = jax.lax.scan(body, h, xs)
    else:
        ks, vs, states, tails = [], [], [], []
        for n, (kind, i) in enumerate(cfg.layer_plan):
            block = model.layer(n)  # static slices
            if kind == "linear":
                h, s_i, t_i = block.prefill_state_at(
                    h, state[0][i], state[1][i], real_n
                )
                states.append(s_i)
                tails.append(t_i)
                continue
            if kind == "latent":
                h, k = block.prefill_latent_at(
                    h, pool_k, bt, i, mask_pool, mask_self, sin_h, cos_h,
                    experts=stacked_experts(model, n),
                )
                ks.append(k)
                continue
            h, k, v = block.prefill_paged_at(
                h, pool_k, pool_v, bt, i, mask_pool, mask_self, sin_h,
                cos_h, start=start, pool_sk=pool_sk, pool_sv=pool_sv,
                sp=sp, experts=stacked_experts(model, i),
            )
            ks.append(k)
            vs.append(v)
        ks, vs = jnp.stack(ks), jnp.stack(vs) if vs else None
    h = model.ln_f(h)
    if cfg.latent:
        return h, ks, None  # ks: [L, 1, 1, T, row]
    if sp:
        # final ln_f ran row-sharded; gather the chunk back replicated so
        # the caller's last-real-row slice and lm-head projection are the
        # sp=False trace verbatim
        h = shard_act(h, None, None, None)
    ks = shard_act(ks, None, None, "kv_heads", None, None)
    vs = shard_act(vs, None, None, "kv_heads", None, None)
    if state is not None:
        return h, ks, vs, (jnp.stack(states), jnp.stack(tails))
    return h, ks, vs  # ks/vs: [L, 1, Hkv, T, C]


def verify_tokens_paged(
    model: GPT,
    tokens: Array,  # [S, T] int32 — candidate rows per decode slot
    start: Array,  # [S] int32 — per-slot absolute position of row 0 (the
    # slot's write watermark: tokens already resident in the pool)
    pool_k: Array,  # [L, NP, PS, Hkv*C] page pool, READ-ONLY here
    pool_v: Array,
    bt: Array,  # [S, Pmax] int32 per-slot block tables
    rope_len: int,
    pool_sk: tp.Optional[Array] = None,  # [L, NP, Hkv] f32 (int8 pool)
    pool_sv: tp.Optional[Array] = None,
    paged_kernel: str = "xla",
    layer_scan: str = "off",
    block_len: int = 0,
    expert_rows: bool = False,
    live: tp.Optional[Array] = None,  # [S, T] bool
    head_block: tp.Optional[Array] = None,  # [S] int32
) -> tp.Tuple[Array, ...]:
    """Speculative-decoding VERIFICATION forward: score every slot's
    ``[T = spec_len + 1]`` candidate rows (the true next token + the
    drafted continuation) in one batched multi-query pass over the
    resident paged KV — all slots, all rows, ONE dispatch.

    This is :func:`prefill_chunk_paged` generalized from one slot to the
    whole decode batch: each slot's rows attend to its OWN block-table
    pages (positions ``< start[s]`` — per-slot masks, continuous batching
    mixes depths) plus themselves causally, one joint softmax
    (``Attention.verify_paged_at``). The attention DTYPE CHOREOGRAPHY
    mirrors the decode window's (``decode_paged_at``), not the prefill
    chunk's: acceptance compares these logits' argmax against what the
    decode path would have sampled at the same positions, and on a real
    bf16 checkpoint the prefill choreography differs by enough bf16 ulps
    to flip near-tied greedy argmaxes (caught by the sample.py --serve
    --serve_spec drive). Mirrored, greedy acceptance decisions are the
    decisions the non-speculative engine would have made one token at a
    time.

    Returns ``(logits, ks, vs)``: per-row next-token logits [S, T, V]
    (row j scores position ``start + j + 1`` — exact whenever rows
    ``0..j`` are the true context, which is precisely what acceptance
    checks) and the rows' post-rope K / raw V [L, S, Hkv, T, C] for the
    watermark-masked page write (only accepted rows' K/V ever lands;
    rejected rows are dropped by the scatter mask — the rollback).

    ``block_len`` > 0 is the BLOCK-DIFFUSION forward (serving.engine's
    block window): ``start`` is a multiple of the block length, the T rows
    are whole blocks, and a row sees every row of its own block and of the
    blocks before it — the rows' own mask is the only thing that changes;
    logits of row j then predict position ``start + j`` ITSELF. So the rows
    ``[block n, its final tokens | block n+1, masked]`` are at once block
    n's commit (its K/V as every later block will read them: it does not
    see block n+1) and block n+1's denoising forward over the context that
    commit leaves. ``head_block`` names, per slot, the one block of the
    rows that the head runs on: logits are then ``[S, block_len, V]`` (the
    head is the widest matrix there is, and a block that is only being
    committed needs no logits). ``live``: the rows that are a token's —
    the others (a slot with nothing to commit carries a dead second block)
    claim no expert (``ExpertMLP``); what is computed for them is used by
    nobody and seen by no live row, which the caller's layout has to make
    true. ``expert_rows`` (a model whose MLP is an ExpertMLP): a fourth
    result, the rows routed to each expert in each layer ``[L, E]`` int32."""
    cfg = model.config
    s, t = tokens.shape
    block = max(1, block_len)
    assert t % block == 0, (t, block)
    pmax = bt.shape[1]
    ps = pool_k.shape[2]
    pool_k = shard_act(pool_k, None, None, None, "kv_heads")
    pool_v = shard_act(pool_v, None, None, None, "kv_heads")
    if pool_sk is not None:
        pool_sk = shard_act(pool_sk, None, None, "kv_heads")
        pool_sv = shard_act(pool_sv, None, None, "kv_heads")
    assert not cfg.latent, "the verify rows have no latent form yet"
    sin_np, cos_np = rope_tables(cfg.head_dim, rope_len, cfg.rope_base)
    sin_t, cos_t = jnp.asarray(sin_np), jnp.asarray(cos_np)

    # paged slot w of the gathered [W = Pmax*PS] view holds logical
    # position w; resident iff w < start[s] — per-slot, broadcast over
    # (Hkv, G, T) in the [S, Hkv, G, T, W] score tensor
    idx = jnp.arange(pmax * ps)
    mask_pool = jnp.where(
        idx[None, :] < start[:, None], 0.0, -jnp.inf
    ).astype(jnp.float32)[:, None, None, None, :]  # [S, 1, 1, 1, W]
    ii = jnp.arange(t)
    bi = ii // block if block > 1 else ii
    mask_self = jnp.where(
        bi[None, :] <= bi[:, None], 0.0, -jnp.inf
    ).astype(jnp.float32)  # [T, T]
    pos = jnp.clip(start[:, None] + ii[None, :], 0, rope_len - 1)  # [S, T]
    sin_rows = jnp.take(sin_t, pos, axis=0)[:, None]  # [S, 1, T, C//2]
    cos_rows = jnp.take(cos_t, pos, axis=0)[:, None]

    h = embed_tokens(model.wte, tokens)  # [S, T, D]
    sin_h, cos_h = sin_rows.astype(h.dtype), cos_rows.astype(h.dtype)
    assert layer_scan in ("on", "off"), layer_scan
    assert model.lin_blocks is None, "verify needs full attention only"
    if layer_scan == "on":
        # layer loop folded into one lax.scan (see decode_step_paged):
        # the candidate rows' per-layer K/V land as scan ys
        quant = pool_sk is not None

        def body(hc, xs):
            blk, pk_l, pv_l = xs[:3]
            sk_l = xs[3][None] if quant else None
            sv_l = xs[4][None] if quant else None
            hc, *out = blk.verify_paged_at(
                hc, pk_l[None], pv_l[None], bt, 0, mask_pool, mask_self,
                sin_h, cos_h, start=start, pool_sk=sk_l, pool_sv=sv_l,
                paged_kernel=paged_kernel, block=block,
                expert_rows=expert_rows,
                experts=stacked_experts(model, xs[-1]), live=live,
            )
            return hc, tuple(out)

        xs = (model.blocks, pool_k, pool_v)
        if quant:
            xs = xs + (pool_sk, pool_sv)
        if stacked_experts(model, 0) is not None:
            xs = xs + (jnp.arange(cfg.n_layer, dtype=jnp.int32),)
        h, outs = jax.lax.scan(body, h, xs)
    else:
        per_layer = []
        for i in range(cfg.n_layer):
            blk = jax.tree.map(lambda a: a[i], model.blocks)  # static
            h, *out = blk.verify_paged_at(
                h, pool_k, pool_v, bt, i, mask_pool, mask_self, sin_h,
                cos_h, start=start, pool_sk=pool_sk, pool_sv=pool_sv,
                paged_kernel=paged_kernel, block=block,
                expert_rows=expert_rows, experts=stacked_experts(model, i),
                live=live,
            )
            per_layer.append(out)
        outs = tuple(jnp.stack(o) for o in zip(*per_layer))
    ks, vs = outs[:2]
    if head_block is not None:
        h = h.reshape(s, t // block, block, -1)[jnp.arange(s), head_block]
    h = model.ln_f(h)
    # vocab-sharded per-row logits (column-parallel head) — acceptance
    # argmaxes partition over 'tensor', no gathered [S, T, V] buffer
    logits = shard_act(model.project(h), None, None, "vocab")  # [S, T, V]
    ks = shard_act(ks, None, None, "kv_heads", None, None)
    vs = shard_act(vs, None, None, "kv_heads", None, None)
    if expert_rows:
        return logits, ks, vs, outs[2]
    return logits, ks, vs  # ks/vs: [L, S, Hkv, T, C]


def prefill(
    model: GPT, tokens: Array, cache: KVCache
) -> tp.Tuple[Array, KVCache]:
    """Fill the cache with a whole prompt in ONE batched forward pass —
    the per-layer post-rope K / raw V come out of the same scan that runs
    the blocks (return_kv), stacked along the layer axis by lax.scan.
    O(1) passes vs the reference's O(P x full-forward) loop
    (sample.py:72-94). Returns logits after the last prompt token + the
    filled cache."""
    cfg = model.config
    b, p = tokens.shape
    t_max = cache.k.shape[-1]
    assert p <= t_max, f"prompt {p} exceeds cache length {t_max}"
    # ring needs a live mesh, and an explicit 'flash' may not divide an
    # arbitrary prompt length — 'auto' keeps the flash fast path for
    # aligned prompts and falls back to naive otherwise
    impl = (
        "auto"
        if cfg.attn_impl in ("ring", "ulysses", "flash", "fused")
        else cfg.attn_impl
    )

    h, (ks, vs) = model.hidden(
        tokens, deterministic=True, attn_impl=impl, return_kv=True
    )  # ks/vs: [L, B, Hkv, P, C]
    # one-time transpose into the time-minor cache layout (KVCache) —
    # prefill is compute-bound, the relayout is noise there
    ks = jnp.transpose(ks, (0, 1, 2, 4, 3))  # [L, B, Hkv, C, P]
    vs = jnp.transpose(vs, (0, 1, 2, 4, 3))
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache.k, ks.astype(cache.k.dtype), 0, axis=4
    )
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache.v, vs.astype(cache.v.dtype), 0, axis=4
    )
    logits = model.project(h[:, -1, :])  # [B, V]
    return logits, KVCache(k=cache_k, v=cache_v)


def prefill_stepwise(
    model: GPT, tokens: Array, cache: KVCache
) -> tp.Tuple[Array, KVCache]:
    """Token-by-token prefill via decode_step — the oracle the batched
    prefill is tested against."""

    def body(carry, tok):
        pos, cache = carry
        logits, cache = decode_step(model, tok, pos, cache)
        return (pos + 1, cache), logits

    b, t = tokens.shape
    (_, cache), logits_all = jax.lax.scan(
        body, (jnp.zeros((), jnp.int32), cache), jnp.transpose(tokens)
    )
    return logits_all[-1], cache


def count_params(model: GPT) -> int:
    """Non-embedding param count (parity: model.py:161-164 — subtract the
    duplicated wte/lm_head array when untied)."""
    from midgpt_tpu.pytree import count_params as _count

    total = _count(model)
    if model.lm_head is not None:
        total -= model.lm_head.weight.size
    return total


# ---------------------------------------------------------------------------
# Parameter partition rules (replaces shard_gpt's size heuristic,
# model.py:167-178). Specs are right-aligned against param rank, so the same
# rule covers stacked [L, ...] scan params and unstacked ones.
# ---------------------------------------------------------------------------

from jax.sharding import PartitionSpec as P  # noqa: E402

GPT_PARAM_RULES: tp.Sequence[tp.Tuple[str, P]] = (
    # [V, D]: vocab over tensor, embed over fsdp
    (r"wte/weight", P("tensor", "fsdp")),
    # column-parallel: [L, D, (H+2Hkv)C] — in over fsdp, out over tensor
    (r"attn/wqkv/weight", P("fsdp", "tensor")),
    # row-parallel: [L, H*C, D] — in over tensor, out over fsdp
    (r"attn/wo/weight", P("tensor", "fsdp")),
    (r"attn/(q|k)_norm/weight", P()),
    (r"mlp/w_(up|gate)/weight", P("fsdp", "tensor")),
    (r"mlp/w_down/weight", P("tensor", "fsdp")),
    # QuantLinear per-OUTPUT-channel dequant scales (midgpt_tpu.quant,
    # the int8 serving pytree): a scale vector [L, out] / [out] must
    # shard exactly like its weight's OUT dim, or the fused epilogue
    # multiply regathers the activation it scales. Column-parallel
    # weights (out over tensor) -> scale over tensor; row-parallel
    # weights (out over fsdp) -> scale over fsdp. Right-aligned, so the
    # same rule covers stacked [L, out] and the unstacked head [out].
    (r"attn/wqkv/scale", P("tensor")),
    (r"attn/wo/scale", P("fsdp")),
    (r"mlp/w_(up|gate)/scale", P("tensor")),
    (r"mlp/w_down/scale", P("fsdp")),
    (r"lm_head/scale", P("tensor")),
    # MoE (mlp="moe"): experts over 'tensor' (expert parallelism), the
    # dense dims over fsdp (ZeRO); the tiny [D, E] router replicated.
    # Right-aligned onto the stacked [L, E, D, F] / [L, E, F, D] leaves.
    (r"mlp/expert_up", P("tensor", "fsdp", None)),
    (r"mlp/expert_down", P("tensor", None, "fsdp")),
    (r"mlp/router/weight", P()),
    # routed experts (mlp="experts"): replicated — an expert axis over a
    # mesh is ROADMAP Reach A1
    (r"mlp/w_in|mlp/w_out", P()),
    (r"ln_f/weight|ln1/weight|ln2/weight", P()),
    # [D, V]: embed over fsdp, vocab over tensor
    (r"lm_head/weight", P("fsdp", "tensor")),
)

# Pipeline-parallel variant: block leaves additionally shard their leading
# (stacked-layer) axis over 'pipeline' — L/S layers per stage, which is what
# parallel.pipeline's shard_map strips. Non-stacked params (wte, ln_f,
# lm_head) stay pipeline-replicated: embedding/head run outside the pipeline
# (parallel.pipeline.gpt_pipeline_hidden). Specs here are full-rank (the
# right-alignment padding in param_shardings would otherwise misplace the
# leading 'pipeline' entry).
GPT_PP_PARAM_RULES: tp.Sequence[tp.Tuple[str, P]] = (
    (r"wte/weight", P("tensor", "fsdp")),
    (r"attn/wqkv/weight", P("pipeline", "fsdp", "tensor")),
    (r"attn/wo/weight", P("pipeline", "tensor", "fsdp")),
    (r"attn/(q|k)_norm/weight", P("pipeline", None)),
    (r"mlp/w_(up|gate)/weight", P("pipeline", "fsdp", "tensor")),
    (r"mlp/w_down/weight", P("pipeline", "tensor", "fsdp")),
    (r"ln_f/weight", P()),
    (r"ln1/weight|ln2/weight", P("pipeline", None)),
    (r"lm_head/weight", P("fsdp", "tensor")),
)


def gpt_param_rules(pipeline: bool = False) -> tp.Sequence[tp.Tuple[str, P]]:
    """Partition-rule table for a GPT; ``pipeline=True`` adds the
    stacked-layer-axis sharding the PP trainer needs."""
    return GPT_PP_PARAM_RULES if pipeline else GPT_PARAM_RULES
