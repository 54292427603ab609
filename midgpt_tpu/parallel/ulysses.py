"""All-to-all sequence parallelism (DeepSpeed-Ulysses style) — the second
context-parallel mechanism beside ring attention (parallel/ring.py).

Where the ring streams K/V chunks around the 'sequence' axis and merges
partial softmaxes, Ulysses TRADES the sharded axis: one ``all_to_all``
re-shards [B, H, T/S, C] -> [B, H/S, T, C] (heads scatter, sequence
gathers), each device then runs ordinary FULL-sequence causal attention
on its head group, and a second all_to_all restores the sequence-sharded
layout. Consequences, vs ring:

- attention math is the plain single-device kernel — no streaming-LSE
  merge, no per-hop scheduling; the flash kernel (and its in-kernel
  dropout, anchored at global (row, col, batch*H+head) coordinates via
  ops/flash._seed_vec) applies unchanged, so DROPOUT IS EXACT here with
  no schedule restrictions (ring degrades zigzag -> standard for it);
- communication is 2 all-to-alls of the full activations per call
  (O(B*H*T*C/S) per device) instead of (S-1) K/V chunk hops — cheaper
  for moderate S on all-to-all-friendly interconnects, but per-device
  attention memory is O(T) (the full sequence), so the EXTREME-context
  regime (T too big for one device even at H/S heads) still needs ring;
- requires H (and Hkv, for GQA) divisible by S.

Differentiable end to end: ``lax.all_to_all``'s transpose is the reverse
all_to_all, so autodiff derives the backward schedule. Absent from the
reference (SURVEY.md 5.7: full T everywhere); SNIPPETS/PAPERS document
the public Ulysses recipe this follows.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _local_attention(
    q: Array, k: Array, v: Array,
    use_flash: bool,
    keep: tp.Optional[float],
    seed,
    bh_off,
    n_head_total: tp.Optional[int],
) -> Array:
    """Full-sequence causal attention on the local head group."""
    if use_flash:
        if keep is not None:
            from midgpt_tpu.ops.flash import flash_attention_dropout_lse

            out, _ = flash_attention_dropout_lse(
                q, k, v, seed, 1.0 - keep, True,
                bh_off=bh_off, n_head_total=n_head_total,
            )
            return out
        from midgpt_tpu.ops.flash import flash_attention

        return flash_attention(q, k, v, causal=True)
    if keep is not None:
        # naive oracle with the kernels' counter-hash mask at global
        # (batch*H+head) coordinates — mirrors ring._chunk_attention
        import math

        from midgpt_tpu.ops.flash import _hash_finalize, _wrap32

        b, h, t, c = q.shape
        hkv = k.shape[1]
        groups = h // hkv
        qg = q.reshape(b, hkv, groups, t, c)
        z = jnp.einsum(
            "bkgqc,bkjc->bkgqj", qg, k, preferred_element_type=jnp.float32
        ) / math.sqrt(c)
        causal = jnp.tril(jnp.ones((t, t), bool))
        z = jnp.where(causal, z, -1e30)
        p = jax.nn.softmax(z, axis=-1)
        rows = jnp.arange(t, dtype=jnp.int32)
        x = (
            rows[:, None] * _wrap32(0x9E3779B1)
            + rows[None, :] * _wrap32(0x85EBCA77)
        )
        nh = jnp.int32(n_head_total or h)
        head_ids = (
            jnp.asarray(bh_off, jnp.int32)
            + jnp.arange(b, dtype=jnp.int32).reshape(b, 1, 1) * nh
            + jnp.arange(h, dtype=jnp.int32).reshape(1, hkv, groups)
        )
        hx = x[None, None, None] ^ (
            jnp.asarray(seed, jnp.int32).reshape(())
            + head_ids[..., None, None] * _wrap32(0xC2B2AE35)
        )
        u24 = _hash_finalize(hx) & jnp.int32(0x00FFFFFF)
        mask = u24 < jnp.int32(int(keep * (1 << 24)))
        p = jnp.where(mask, p * (1.0 / keep), 0.0)
        out = jnp.einsum("bkgqj,bkjc->bkgqc", p.astype(v.dtype), v)
        return out.reshape(b, h, t, c)
    from midgpt_tpu.ops.attention import naive_attention

    return naive_attention(q, k, v, causal=True)


def ulysses_attention(
    q: Array,  # [B, H, T, C] global, T sharded over 'sequence'
    k: Array,  # [B, Hkv, T, C]
    v: Array,
    mesh: Mesh,
    *,
    axis_name: str = "sequence",
    batch_axes: tp.Tuple[str, ...] = ("replica", "fsdp"),
    use_flash: tp.Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_seed: tp.Optional[Array] = None,
) -> Array:
    """Causal attention with T sharded over ``axis_name`` via head/sequence
    all-to-alls. Requires H % S == 0 and Hkv % S == 0 (GQA) and T % S == 0.
    TP composition is out of scope v1 (the head groups the all_to_all
    forms would collide with a 'tensor' head sharding) — callers gate on
    tensor == 1 (models/gpt.py)."""
    s = mesh.shape[axis_name]
    b, h, t, c = q.shape
    hkv = k.shape[1]
    assert t % s == 0, f"T={t} not divisible by sequence axis {s}"
    assert h % s == 0 and hkv % s == 0, (
        f"ulysses needs head counts divisible by the sequence axis "
        f"(H={h}, Hkv={hkv}, S={s}); use attn_impl='ring' otherwise"
    )
    assert mesh.shape.get("tensor", 1) == 1, (
        "ulysses + tensor parallelism is unsupported (v1); use ring"
    )
    if use_flash is None:
        from midgpt_tpu.utils.platform import is_tpu_backend

        use_flash = is_tpu_backend() and t >= 128 and t % 128 == 0
    if dropout_rate > 0.0:
        assert dropout_seed is not None, "ulysses dropout needs dropout_seed"

    from midgpt_tpu.parallel.sharding import fit_axes

    b_axes = fit_axes(mesh, b, batch_axes)
    spec = P(b_axes if b_axes else None, None, axis_name, None)
    b_shards = 1
    for a in b_axes:
        b_shards *= mesh.shape[a]
    b_local = b // b_shards

    keep = None if dropout_rate == 0.0 else 1.0 - dropout_rate

    def body(ql, kl, vl, sl):
        # [B_l, H, T/S, C] -> heads scatter / sequence gather
        qh = jax.lax.all_to_all(
            ql, axis_name, split_axis=1, concat_axis=2, tiled=True
        )  # [B_l, H/S, T, C]
        kh = jax.lax.all_to_all(
            kl, axis_name, split_axis=1, concat_axis=2, tiled=True
        )
        vh = jax.lax.all_to_all(
            vl, axis_name, split_axis=1, concat_axis=2, tiled=True
        )
        bh_off = None
        if keep is not None:
            # global flat batch*H + head of this device's (0, 0): batch
            # offset from the batch shards, head offset from the sequence
            # shard's head group
            b_idx = jnp.int32(0)
            for a in b_axes:
                b_idx = b_idx * jnp.int32(mesh.shape[a]) + jax.lax.axis_index(a)
            seq_idx = jax.lax.axis_index(axis_name)
            bh_off = (
                b_idx * jnp.int32(b_local) * jnp.int32(h)
                + seq_idx * jnp.int32(h // s)
            )
        out = _local_attention(
            qh, kh, vh, use_flash, keep, sl, bh_off, n_head_total=h
        )
        # inverse: sequence scatter / heads gather
        return jax.lax.all_to_all(
            out, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    seed = (
        jnp.asarray(dropout_seed, jnp.int32).reshape(())
        if dropout_seed is not None
        else jnp.zeros((), jnp.int32)
    )
    manual = set(b_axes) | {axis_name}
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
        axis_names=manual,
        check_vma=False,
    )
    return fn(q, k, v, seed)
