"""The main path's Pallas kernels, COMPILED for a described TPU v5e — no
chip attached, nothing runs. Interpret mode (every other kernel test in
this suite) checks arithmetic; it does not check what Mosaic accepts:
both paged kernels passed every interpreted test for eleven PRs while
the chip's compiler refused them at every shape (an unsupported vector
reshape, a scale BlockSpec off the tile grid, DMA slices narrower than a
lane tile). These compiles are that missing check, at ``openwebtext``
widths (12 heads of 64, T=1024; serving: 8 slots, 64 pages of 16).

The topology is described inside a module-scoped fixture — never at
import: only one process may load the TPU's library, the suite runs
under several workers, and each worker imports every file. All compile
tests live in THIS file so that one worker owns the library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# openwebtext: B8 T1024 H12 C64 D768; serving: S8, page 16, Pmax 64
B, T, H, C = 8, 1024, 12, 64
S, PS, PMAX, NP, L = 8, 16, 64, 512, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — whatever refuses, skip, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles_to_kernel(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _scalar(out):
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_decode_kernel_compiles(one_chip, pool):
    from midgpt_tpu.ops.paged_attn import paged_decode_attention, supported

    quant = pool == "int8"
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    assert supported(PMAX, PS, C, jnp.dtype(pool_dt).itemsize, groups=1)
    shapes = [
        ((S, H, 1, C), jnp.bfloat16),           # q [S, Hkv, G, C]
        ((L, NP, H, C, PS), pool_dt),           # pool K
        ((L, NP, H, C, PS), pool_dt),           # pool V
        ((S, PMAX), jnp.int32),                 # block tables
        ((S,), jnp.int32),                      # pooled_len
        ((S, H, 8, C), jnp.bfloat16),           # recent K rows
        ((S, H, 8, C), jnp.bfloat16),           # recent V rows
        ((), jnp.int32),                        # step in window
    ] + 2 * quant * [((S, PMAX, H), jnp.float32)]

    def fn(q, pk, pv, bt, ln, rk, rv, r, *scales):
        return paged_decode_attention(
            q, pk, pv, bt, ln, rk, rv, r, 1, *scales
        )

    _compiles_to_kernel(fn, one_chip, *shapes)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_verify_kernel_compiles(one_chip, pool):
    from midgpt_tpu.ops.paged_attn import paged_verify_attention, supported

    quant = pool == "int8"
    pool_dt = jnp.int8 if quant else jnp.bfloat16
    t = 4  # speculate = 3
    assert supported(
        PMAX, PS, C, jnp.dtype(pool_dt).itemsize, groups=1, spec_t=t
    )
    shapes = [
        ((S, H, 1, t, C), jnp.bfloat16),        # q [S, Hkv, G, T, C]
        ((S, H, t, C), jnp.bfloat16),           # self K rows
        ((S, H, t, C), jnp.bfloat16),           # self V rows
        ((L, NP, H, C, PS), pool_dt),
        ((L, NP, H, C, PS), pool_dt),
        ((S, PMAX), jnp.int32),
        ((S,), jnp.int32),                      # write watermark
    ] + 2 * quant * [((S, PMAX, H), jnp.float32)]

    def fn(q, kc, vc, pk, pv, bt, start, *scales):
        return paged_verify_attention(
            q, kc, vc, pk, pv, bt, start, 1, *scales
        )

    _compiles_to_kernel(fn, one_chip, *shapes)


def test_flash_attention_compiles_fwd_bwd(one_chip):
    from midgpt_tpu.ops.flash import flash_attention

    fn = jax.value_and_grad(
        lambda q, k, v: _scalar(flash_attention(q, k, v)), argnums=(0, 1, 2)
    )
    _compiles_to_kernel(fn, one_chip, *(3 * [((B, H, T, C), jnp.bfloat16)]))


@pytest.mark.parametrize("entry", ["split", "packed_qkv"])
def test_fused_attention_compiles_fwd_bwd(one_chip, entry):
    from midgpt_tpu.ops.fused_attn import (
        fused_attention,
        fused_attention_qkv,
        supported,
    )

    assert supported(H, H, C)
    tables = [((C,), jnp.float32)] * 2 + [((T, C), jnp.float32)] * 2
    if entry == "split":
        fn = jax.value_and_grad(
            lambda q, k, v, wq, wk, sin, cos: _scalar(
                fused_attention(q, k, v, wq, wk, sin, cos, H, H)
            ),
            argnums=(0, 1, 2, 3, 4),
        )
        shapes = 3 * [((B, T, H * C), jnp.bfloat16)] + tables
    else:
        fn = jax.value_and_grad(
            lambda qkv, wq, wk, sin, cos: _scalar(
                fused_attention_qkv(qkv, wq, wk, sin, cos, H, H)
            ),
            argnums=(0, 1, 2),
        )
        shapes = [((B, T, 3 * H * C), jnp.bfloat16)] + tables
    _compiles_to_kernel(fn, one_chip, *shapes)


def test_fused_rms_norm_compiles_fwd_bwd(one_chip):
    from midgpt_tpu.ops.fused_norm import fused_rms_norm

    fn = jax.value_and_grad(
        lambda x, w: _scalar(fused_rms_norm(x, w)), argnums=(0, 1)
    )
    _compiles_to_kernel(
        fn, one_chip, ((B, T, H * C), jnp.bfloat16), ((H * C,), jnp.bfloat16)
    )
