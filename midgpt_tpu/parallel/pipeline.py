"""Pipeline parallelism: GPipe-style microbatch streaming over a
``'pipeline'`` mesh axis.

Absent from the reference (SURVEY.md 2.6: no PP anywhere); provided here as
the TPU-native building block. Design (idiomatic JAX, no hand-scheduled
backward):

- Layer-stacked params (leading ``[L, ...]`` axis, the same layout the
  scan-over-layers model already uses) are split into S contiguous stage
  chunks; inside ``jax.shard_map`` each device along the ``'pipeline'``
  axis holds ``L/S`` layers.
- Microbatches stream through the ring: one ``lax.scan`` over
  ``M + S - 1`` ticks; every tick each stage runs its layer chunk on its
  current activation and hands the result to the next stage with
  ``lax.ppermute`` (neighbor-only ICI traffic). Stage 0 injects a fresh
  microbatch per tick; the last stage banks its outputs.
- The backward pass is DERIVED BY AD: ppermute's transpose is the reverse
  permute, scan's transpose runs the ticks backwards — exactly the
  reverse-schedule GPipe backward, with whole-stage rematerialization via
  ``jax.checkpoint`` around the stage body.
- The (S-1)-tick bubble is the standard GPipe cost: utilization
  M / (M + S - 1); choose M >= 4*S to keep it small.

This module is schedule-complete and differentiable; wiring it into the
GPT trainer (embedding/head placement, composing with the fsdp/tensor
axes via partial-auto shard_map) is the integration step tracked in
SURVEY.md §7 stage extensions.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _to_varying(x: Array, axis: str) -> Array:
    """Promote ``x`` to VARYING along the mesh axis — a type-system
    annotation for ``check_vma`` regions, a value-level no-op."""
    return jax.lax.pcast(x, axis, to="varying")


StageFn = tp.Callable[..., Array]
"""(stage_params, activation [Bm, ...][, keys [L/S, 2]]) -> activation
[Bm, ...]; applies one stage's worth of layers (e.g. a lax.scan over the
local layer chunk). The keys argument is passed iff ``keys`` was given to
pipeline_forward (per-layer dropout keys for the current microbatch)."""


def pipeline_forward(
    stacked_params: tp.Any,  # pytree, every leaf [L, ...]
    x: Array,  # [M, Bm, ...] microbatched input activations
    stage_fn: StageFn,
    mesh: Mesh,
    *,
    keys: tp.Optional[Array] = None,  # [L, M, 2] uint32 per-layer/microbatch
    axis: str = "pipeline",
    remat: bool = True,
    check_vma: bool = True,
) -> Array:
    """Run ``x`` through all L layers, pipelined over the ``axis`` stages.

    Returns [M, Bm, ...] outputs (same sharding layout as ``x``).

    ``keys`` threads dropout through the tick schedule: raw uint32
    [L, M, 2] key data, split over stages on the layer axis exactly like
    the params; at tick t, stage s slices the keys of the microbatch it is
    processing (m = t - s) and hands its [L/S, 2] slab to stage_fn.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]
    leaves = jax.tree.leaves(stacked_params)
    assert leaves, "stacked_params must contain at least one array"
    n_layer = leaves[0].shape[0]
    assert n_layer % n_stages == 0, (
        f"n_layer {n_layer} not divisible by {n_stages} pipeline stages"
    )

    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)

    def per_stage(params_local, x_local, keys_local):
        # params_local leaves: [L/S, ...] (shard_map strips the stage dim)
        # x_local: [M, Bm, ...] (replicated across the pipeline axis).
        # Everything entering the tick carry is promoted to pipeline-VARYING
        # (pcast to='varying'): the carry mixes per-stage values (ppermute output, banked
        # activations) with broadcast inputs, and an invariant/varying mix in
        # a scan carry is unsound — it surfaced as an XLA miscompile
        # ("Invalid binary instruction opcode copy") under check_vma=False.
        x_local = _to_varying(x_local, axis)
        s_idx = jax.lax.axis_index(axis)
        n_ticks = m + n_stages - 1
        zero_act = jnp.zeros_like(x_local[0])

        def tick(carry, t):
            recv, outputs = carry
            # stage 0 pulls microbatch t (clamped; masked off after M)
            mb = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, m - 1), axis=0, keepdims=False
            )
            in_act = jnp.where(s_idx == 0, mb, recv)
            # active window for this stage: t in [s_idx, s_idx + M)
            active = jnp.logical_and(t >= s_idx, t < s_idx + m)
            if keys_local is None:
                out_act = body(params_local, in_act)
            else:
                # this stage is working on microbatch t - s_idx (clamped
                # on inactive ticks, whose output is masked anyway)
                k_mb = jax.lax.dynamic_index_in_dim(
                    keys_local, jnp.clip(t - s_idx, 0, m - 1),
                    axis=1, keepdims=False,
                )  # [L/S, 2]
                out_act = body(params_local, in_act, k_mb)
            out_act = jnp.where(active, out_act, zero_act)
            # bank the last stage's finished microbatch (m_done = t - (S-1));
            # non-banking ticks write back the existing slot unchanged
            m_done = t - (n_stages - 1)
            is_last = s_idx == n_stages - 1
            do_bank = jnp.logical_and(is_last, m_done >= 0)
            slot = jnp.clip(m_done, 0, m - 1)
            prev = jax.lax.dynamic_index_in_dim(
                outputs, slot, axis=0, keepdims=False
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(do_bank, out_act, prev), slot, axis=0
            )
            # hand activations to the next stage (ring; last->0 edge is
            # ignored because stage 0 reads the fresh microbatch instead)
            sent = jax.lax.ppermute(
                out_act,
                axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (sent, outputs), None

        outputs0 = _to_varying(
            jnp.zeros((m,) + x_local.shape[1:], x_local.dtype), axis
        )
        (_, outputs), _ = jax.lax.scan(
            tick, (zero_act, outputs0), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs; share them around the ring
        outputs = jax.lax.psum(
            jnp.where(s_idx == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    in_specs = (
        jax.tree.map(lambda _: P(axis), stacked_params),  # stage dim = leading
        P(),  # input replicated over the pipeline axis
        P(axis) if keys is not None else P(),  # keys split like the params
    )
    # partial-auto: only the pipeline axis is manual; any other mesh axes
    # (replica/fsdp/sequence/tensor) stay under GSPMD, so PP composes with
    # the data/tensor shardings of the surrounding train step
    return shard_map(
        per_stage,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        axis_names={axis},
        check_vma=check_vma,
    )(stacked_params, x, keys)


def stage_scan_fn(block_fn: tp.Callable[[tp.Any, Array], Array]) -> StageFn:
    """Lift a single-layer ``block_fn(params_1layer, x) -> x`` into a
    StageFn that scans over the stage's local layer chunk — the same
    scan-over-layers pattern the full model uses (models/gpt.py)."""

    def stage(params_local, x):
        def body(h, layer_params):
            return block_fn(layer_params, h), None

        out, _ = jax.lax.scan(body, x, params_local)
        return out

    return stage


def gpt_pipeline_hidden(
    model,  # midgpt_tpu.models.gpt.GPT
    tokens: Array,  # [B, T] int32
    mesh: Mesh,
    *,
    n_micro: int = 0,
    axis: str = "pipeline",
    key: tp.Optional[Array] = None,
    deterministic: bool = True,
    boundary_dtype: tp.Optional[str] = None,
) -> Array:
    """GPT forward with the block stack pipelined over ``axis``.

    The integration split (SURVEY.md 2.6 PP row): embedding runs BEFORE the
    pipeline and ln_f/lm-head AFTER it, as ordinary GSPMD ops over the full
    mesh — the natural TPU placement of the reference's stage-0-embedding /
    stage-(S-1)-head convention, since wte/ln_f/head params are not
    layer-stacked and GSPMD already shards them (fsdp/tensor). Only the
    ``blocks`` stack (leaves ``[L, ...]``, L/S layers per stage) enters
    the shard_map, which is manual ONLY over the pipeline axis — data /
    tensor sharding of the activations stays with GSPMD (partial-auto).

    Dropout threads through the tick schedule: per-(layer, microbatch)
    keys ride the stage shard_map next to the params (pipeline_forward's
    ``keys``), so dropout configs train under PP too (r3 left this
    deterministic-only). Returns ln_f-normalized hidden [B, T, D]."""
    from midgpt_tpu.models.gpt import embed_tokens
    from midgpt_tpu.models.layers import dropout as dropout_fn, rope_tables
    from midgpt_tpu.parallel.sharding import axis_rules, shard_act

    cfg = model.config
    assert cfg.attn_impl not in ("ring", "ulysses"), (
        "sequence-parallel attention (ring/ulysses) inside pipeline stages "
        "is unsupported (the sequence axis is invisible inside the "
        "pipeline's manual region)"
    )
    b, t = tokens.shape
    s = mesh.shape[axis]
    if n_micro:
        m = n_micro
        assert b % m == 0, f"batch {b} not divisible by {m} microbatches"
    else:
        # auto: aim for 2 microbatches per stage (bubble (S-1)/(M+S-1)),
        # clamped to the largest divisor of the batch — grad-accumulation
        # microsteps can hand this a batch smaller than 2*S
        m = min(2 * s, b)
        while b % m:
            m -= 1
        if m < s:
            import warnings

            warnings.warn(
                f"pipeline auto-microbatching degraded to {m} microbatches "
                f"for batch {b} over {s} stages (bubble "
                f"{(s - 1) / (m + s - 1):.0%}); pick a batch divisible by "
                f"2*pipeline or set MeshConfig.pp_microbatches",
                stacklevel=2,
            )
    sin, cos = rope_tables(cfg.head_dim, t, cfg.rope_base)
    impl = cfg.attn_impl
    has_dropout = cfg.dropout > 0.0 and not deterministic and key is not None

    drop_key, block_key = (
        jax.random.split(key) if has_dropout else (None, None)
    )
    h = embed_tokens(model.wte, tokens)  # [B, T, D]
    h = dropout_fn(h, cfg.dropout, drop_key, not has_dropout)
    h = shard_act(h, "batch", "seq", "embed")
    compute_dtype = h.dtype
    # activations cross the shard_map boundary (and the inter-stage
    # ppermutes) in float32 by default: a bf16 manual-boundary all-reduce
    # crashes XLA CPU's AllReducePromotion pass on the current pin
    # ("Invalid binary instruction opcode copy" — re-confirmed r4; the
    # same bug bit the chunked-loss shard_map, ops/loss.py). The pass is
    # CPU-backend-side, so MeshConfig.pp_boundary_dtype="bfloat16" is
    # worth trying on real TPU hardware (halves ppermute bytes).
    if boundary_dtype is not None:
        bdtype = jnp.dtype(boundary_dtype)
    else:
        bdtype = jnp.float32 if compute_dtype == jnp.bfloat16 else compute_dtype
    h = h.astype(bdtype).reshape(m, b // m, t, cfg.n_embd)

    keys = None
    if has_dropout:
        n_layer = cfg.n_layer
        keys = jax.random.split(block_key, n_layer * m).reshape(n_layer, m, 2)

    def stage_fn(params_local, x, *stage_keys):
        # one cast per stage boundary, not per layer; no activation-sharding
        # constraints inside the manual region (the pipeline axis is
        # invisible to GSPMD there; auto axes keep the inputs' shardings)
        with axis_rules(None):
            if stage_keys:
                def body(hh, layer):
                    bp, k_l = layer
                    return bp(
                        hh, sin, cos, impl=impl, key=k_l, deterministic=False
                    ), None

                y, _ = jax.lax.scan(
                    body, x.astype(compute_dtype),
                    (params_local, stage_keys[0]),
                )
            else:
                def body(hh, bp):
                    return bp(hh, sin, cos, impl=impl, deterministic=True), None

                y, _ = jax.lax.scan(body, x.astype(compute_dtype), params_local)
        return y.astype(bdtype)

    out = pipeline_forward(
        model.blocks, h, stage_fn, mesh, keys=keys, axis=axis
    )
    h = out.reshape(b, t, cfg.n_embd).astype(compute_dtype)
    h = shard_act(h, "batch", "seq", "embed")
    return model.ln_f(h)
