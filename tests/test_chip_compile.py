"""The main path's Pallas kernels, COMPILED for a described TPU v5e — no
chip attached, nothing runs. Interpret mode (every other kernel test in
this suite) checks arithmetic; it does not check what Mosaic accepts:
both paged kernels passed every interpreted test for eleven PRs while
the chip's compiler refused them at every shape (an unsupported vector
reshape, a scale BlockSpec off the tile grid, DMA slices narrower than a
lane tile). These compiles are that missing check, at ``openwebtext``
widths (12 heads of 64, T=1024; serving: 8 slots, 64 pages of 16) and,
for the paged kernels, at the benchmark's serving cell's (16 heads of
128).

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


# the two serving geometries: openwebtext's 12 heads of 64, and the
# benchmark's serving cell (midgpt-xl), 16 heads of 128
GEOMETRIES = {"owt": (H, C), "xl": (16, 128)}


def _paged_shapes(heads, c, pool_dt, pmax, q_shape, rows, slots=S):
    quant = pool_dt == jnp.int8
    pool = ((L, NP, PS, heads * c), pool_dt)  # a page row: all heads' C
    return quant, pool, [
        (q_shape, jnp.bfloat16),
        ((slots, heads, rows, c), jnp.bfloat16),    # K rows
        ((slots, heads, rows, c), jnp.bfloat16),    # V rows
    ], 2 * quant * [((slots, pmax, heads), jnp.float32)]


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_decode_kernel_compiles(one_chip, geometry, pool):
    from midgpt_tpu.ops.paged_attn import paged_decode_attention, supported

    heads, c = GEOMETRIES[geometry]
    pool_dt = jnp.int8 if pool == "int8" else jnp.bfloat16
    assert supported(PMAX, PS, c, jnp.dtype(pool_dt).itemsize, groups=1)
    _, pool_s, (q, rk, rv), scales = _paged_shapes(
        heads, c, pool_dt, PMAX, (S, heads, 1, c), 8
    )
    shapes = [
        q, pool_s, pool_s,
        ((S, PMAX), jnp.int32),                 # block tables
        ((S,), jnp.int32),                      # pooled_len
        rk, rv,
        ((), jnp.int32),                        # step in window
    ] + scales

    def fn(q, pk, pv, bt, ln, rk, rv, r, *scales):
        return paged_decode_attention(
            q, pk, pv, bt, ln, rk, rv, r, 1, *scales
        )

    _compiles_to_kernel(fn, one_chip, *shapes)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_verify_kernel_compiles(one_chip, geometry, pool):
    from midgpt_tpu.ops.paged_attn import paged_verify_attention, supported

    heads, c = GEOMETRIES[geometry]
    pool_dt = jnp.int8 if pool == "int8" else jnp.bfloat16
    t = 4  # speculate = 3
    assert supported(
        PMAX, PS, c, jnp.dtype(pool_dt).itemsize, groups=1, spec_t=t
    )
    _, pool_s, (q, kc, vc), scales = _paged_shapes(
        heads, c, pool_dt, PMAX, (S, heads, 1, t, c), t
    )
    shapes = [
        q, kc, vc, pool_s, pool_s,
        ((S, PMAX), jnp.int32),
        ((S,), jnp.int32),                      # write watermark
    ] + scales

    def fn(q, kc, vc, pk, pv, bt, start, *scales):
        return paged_verify_attention(
            q, kc, vc, pk, pv, bt, start, 1, *scales
        )

    _compiles_to_kernel(fn, one_chip, *shapes)


# the two decode cells of the benchmark, and a one-step window: (slots,
# KV heads, pages a slot, recent rows = decode steps a dispatch, KV heads
# a grid step); heads of 128, MHA
DECODE_CELLS = {
    "serve-xl-decode": (8, 16, 48, 4, 16),
    "serve-olmo-hybrid-decode": (32, 30, 128, 16, 15),
    # a window of ONE step: one recent row (Mosaic refuses a product of
    # one column; the kernel pads the rows' own K/V to a sublane tile)
    "xl-one-step-window": (8, 16, 48, 1, 16),
}


@pytest.mark.parametrize("cell", list(DECODE_CELLS))
def test_paged_decode_kernel_compiles_at_the_decode_cells(one_chip, cell):
    """The decode kernel at the two cells that run it every decode step
    (ONE query row a head, padded to a sublane tile in the kernel; 16
    heads a grid step over 6 bands, and 15 of 30 heads over 16), two
    layers of it in one program: one ``%closed_call.N`` custom call a
    layer — the name and the count the benchmark's
    ``paged_attn_roofline.serve`` finds the kernel's events by."""
    import re

    from midgpt_tpu.ops.paged_attn import (
        head_block, paged_decode_attention, supported,
    )

    slots, heads, pmax, rr, hb = DECODE_CELLS[cell]
    c = 128
    assert supported(pmax, PS, c, 2, groups=1, heads=heads)
    assert head_block(heads, pmax, PS, c, 2, groups=1) == hb
    pool = ((L, slots * pmax, PS, heads * c), jnp.bfloat16)
    rows = ((slots, heads, rr, c), jnp.bfloat16)
    shapes = [
        ((slots, heads, 1, c), jnp.bfloat16), pool, pool,
        ((slots, pmax), jnp.int32), ((slots,), jnp.int32), rows, rows,
        ((), jnp.int32),
    ]

    def fn(q, pk, pv, bt, ln, rk, rv, r):
        for layer in range(L):
            q = paged_decode_attention(q, pk, pv, bt, ln, rk, rv, r, layer)
        return q

    text = _compiles_to_kernel(fn, one_chip, *shapes)
    assert len(re.findall(
        r"%closed_call\.\d+ = \S+ custom-call\(", text
    )) == L


def test_paged_decode_kernel_compiles_100k_token_table(one_chip):
    """The gate accepts a 100k-token block table (6250 pages: 50 bands
    of 125, four heads a grid step — tests/test_paged_attn.py pins the
    arithmetic); this is the compile that says the gate is right."""
    from midgpt_tpu.ops.paged_attn import paged_decode_attention, supported

    pmax = 6250
    assert supported(pmax, PS, C, 2, groups=1)
    _, pool_s, (q, rk, rv), _ = _paged_shapes(
        H, C, jnp.bfloat16, pmax, (S, H, 1, C), 8
    )
    shapes = [
        q, pool_s, pool_s, ((S, pmax), jnp.int32), ((S,), jnp.int32),
        rk, rv, ((), jnp.int32),
    ]

    def fn(q, pk, pv, bt, ln, rk, rv, r):
        return paged_decode_attention(q, pk, pv, bt, ln, rk, rv, r, 1)

    _compiles_to_kernel(fn, one_chip, *shapes)


def test_paged_latent_kernel_compiles_at_the_latent_cell(one_chip):
    """The kernel's latent mode at ``serve-joyai-flash-docs``'s geometry:
    32 slots, every one of 32 heads' rows against ONE "KV head" of 640-lane
    pooled rows (the latent's 512, the rotary key's 64, zeros), a
    33,792-position table at the cell's pages of 64 — resident in VMEM, 41
    MB, so that one page DMA serves scores and values — 16 recent rows; two
    layers of it in one program, one ``%mla_decode.N`` custom call a layer:
    the name ``mla_decode_roofline.serve`` finds the kernel's events by."""
    import re

    from midgpt_tpu.ops.paged_attn import paged_latent_attention, supported

    slots, heads, row, dc, ps, rr = 32, 32, 640, 512, 64, 16
    pmax = 33792 // ps
    assert supported(pmax, ps, row, 2, groups=heads, heads=1, latent=True)
    shapes = [
        ((slots, 1, heads, row), jnp.bfloat16),
        ((L, 4520, ps, row), jnp.bfloat16),
        ((slots, pmax), jnp.int32), ((slots,), jnp.int32),
        ((slots, 1, rr, row), jnp.bfloat16), ((), jnp.int32),
    ]

    def fn(q, pool, bt, ln, rows, r):
        for layer in range(L):
            o = paged_latent_attention(
                q, pool, bt, ln, rows, r, layer, v_lanes=dc, scale_dim=192)
            q = q.at[..., :dc].set(o)
        return q

    text = _compiles_to_kernel(fn, one_chip, *shapes)
    assert len(re.findall(
        r"%mla_decode(\.\d+)? = \S+ custom-call\(", text
    )) == L


# the block-diffusion cell (serve-sdar-block4): 32 slots of 48 pages, 32
# query heads over 4 KV heads of 128, blocks of 4; a forward carries two
# blocks a slot (the block that is committed and the one being denoised)
BLOCK_CELL = dict(slots=32, hkv=4, g=8, t=8, c=128, pmax=48, block=4)


def _block_kernel_compiles(
    one_chip, pool_dt, *, slots, hkv, g, t, c, pmax, block=None
):
    from midgpt_tpu.ops.paged_attn import paged_verify_attention

    _, pool, rows, scales = _paged_shapes(
        hkv, c, pool_dt, pmax, (slots, hkv, g, t, c), t, slots=slots
    )
    shapes = rows + [
        pool, pool, ((slots, pmax), jnp.int32), ((slots,), jnp.int32),
    ] + scales

    def fn(q, kc, vc, pk, pv, bt, start, *scales):
        return paged_verify_attention(
            q, kc, vc, pk, pv, bt, start, 1, *scales, block=block or t
        )

    return _compiles_to_kernel(fn, one_chip, *shapes)


@pytest.mark.parametrize("t", [4, 8])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_block_kernel_compiles(one_chip, pool, t):
    """The verify kernel under the block mask — the one body's two
    products on the matrix unit, the f32 probabilities as limbs — at the
    benchmark cell's geometry: all four KV heads a grid step, at the
    window's two blocks of rows a slot and at one."""
    from midgpt_tpu.ops.paged_attn import head_block, supported

    pool_dt = jnp.int8 if pool == "int8" else jnp.bfloat16
    geo = dict(BLOCK_CELL, t=t)
    gate = dict(groups=geo["g"], spec_t=t)
    itemsize = jnp.dtype(pool_dt).itemsize
    assert supported(geo["pmax"], PS, geo["c"], itemsize, heads=geo["hkv"],
                     **gate)
    assert head_block(geo["hkv"], geo["pmax"], PS, geo["c"], itemsize,
                      **gate) == geo["hkv"]
    _block_kernel_compiles(one_chip, pool_dt, **geo)


def test_paged_block_kernel_100k_token_table(one_chip):
    """A 100k-token block table under the block mask: never a kernel the
    gate admits and Mosaic refuses. At the cell's heads of 128 no band
    plan fits (as for decode) and ``auto`` takes the gather path; at
    heads of 64 the gate admits the dense ``[G*T, W + T]`` score rows
    (four heads a grid step, 50 bands) — and this is the compile that
    says it is right to."""
    from midgpt_tpu.ops.paged_attn import supported

    pmax = 6250
    assert not supported(pmax, PS, 128, 2, groups=8, spec_t=4, heads=4)
    assert supported(pmax, PS, C, 2, groups=1, spec_t=4, heads=H)
    _block_kernel_compiles(
        one_chip, jnp.bfloat16, slots=S, hkv=H, g=1, t=4, c=C, pmax=pmax
    )


def _serving_cell(one_chip):
    """The serving cell's widths at two layers, as described arrays:
    the model in bf16, its pool, and ``arr(shape, dtype)``."""
    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.models import GPT
    from midgpt_tpu.serving.paged import PagedKVPool

    heads, c = GEOMETRIES["xl"]
    cfg = ModelConfig(
        block_size=PMAX * PS, vocab_size=50304, n_layer=L, n_head=heads,
        n_embd=heads * c,
    )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda a: arr(a.shape, a.dtype), tree)

    model = described(jax.eval_shape(
        lambda k: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            GPT.init(k, cfg),
        ), jax.random.PRNGKey(0),
    ))
    pool = described(jax.eval_shape(
        lambda: PagedKVPool.init(cfg, NP, PS, jnp.bfloat16)
    ))
    return cfg, model, pool, arr


def _pool_copies(text):
    """The compiled program's ``copy`` instructions of the pool's shape."""
    import re

    heads, c = GEOMETRIES["xl"]
    pool_shape = f"bf16[{L},{NP},{PS},{heads * c}]"
    return [
        line.strip()[:160] for line in text.splitlines()
        if re.search(r"= \S+ copy\(", line)
        and line.split("= ", 1)[1].startswith(pool_shape)
    ]


def test_decode_window_compiles_without_a_pool_copy(one_chip):
    """The whole decode window of the serving cell's widths (two layers
    of it), compiled: NO ``copy`` in it has the pool's shape. The pool
    is the largest thing a serving engine holds; before PR 26 the window
    re-laid all of it four times a dispatch — twice for the kernel's
    operand layout, and, once that was gone, twice more for the row
    scatter's (serving.paged._layer_index) — 9 % of the device's time
    in the benchmark's serving cell."""
    import re

    from midgpt_tpu.serving.engine import make_decode_window

    cfg, model, pool, arr = _serving_cell(one_chip)
    fn = make_decode_window(
        model, slots=S, window=4, pmax=PMAX, rope_len=cfg.block_size,
        paged_kernel="pallas",
    )
    text = fn.lower(
        model, pool, arr((S, cfg.vocab_size), jnp.float32),
        arr((S, PMAX), jnp.int32), arr((S,), jnp.int32),
        arr((S,), jnp.bool_), arr((S,), jnp.int32), arr((S,), jnp.int32),
        arr((S,), jnp.int32), arr((S,), jnp.int32), arr((2,), jnp.uint32),
    ).compile().as_text()
    # one kernel call a layer, under the name the benchmark's trace
    # reduction finds it by (benchmark/metrics/paged_attn_roofline.serve)
    assert len(re.findall(
        r"%closed_call\.\d+ = \S+ custom-call\(", text
    )) == L
    assert not _pool_copies(text), _pool_copies(text)


def test_prefill_chunk_compiles_without_a_pool_copy(one_chip):
    """The prefill chunk of the serving cell (64 tokens into one slot's
    pages), under the same guard: its row scatter with the layer axis
    left a ``:`` re-laid the pool twice round it, 6.8 ms of the chunk's
    15.3 (PERF.md section 6, PR 26)."""
    from midgpt_tpu.serving.engine import make_prefill_chunk_program

    cfg, model, pool, arr = _serving_cell(one_chip)
    chunk = 64
    fn = make_prefill_chunk_program(
        model, chunk_len=chunk, pmax=PMAX, rope_len=cfg.block_size,
    )
    text = fn.lower(
        model, pool, arr((S, cfg.vocab_size), jnp.float32),
        arr((), jnp.int32), arr((1, chunk), jnp.int32), arr((), jnp.int32),
        arr((), jnp.int32), arr((PMAX,), jnp.int32),
    ).compile().as_text()
    assert not _pool_copies(text), _pool_copies(text)


def test_block_window_compiles_without_a_pool_or_an_expert_copy(
        one_chip, monkeypatch):
    """The block-diffusion window at the benchmark cell's widths (32 query
    heads over 4 KV heads of 128, 128 experts of 768, two layers of it, 32
    slots of 48 pages), compiled: the verify kernel takes the block mask at
    T = 8 (two blocks a slot: the one that lands and the one being
    denoised) and nothing re-lays its output inside the call; the pool, a carry
    of the window's scan that every forward reads
    and every commit writes, is never copied; and no temporary is the size
    of a layer's expert tensors — sliced out of the layer stack in front of
    the grouped matmul they were, 6.5 GB of them at six layers (the stack
    is read as it lies: ``ExpertMLP``'s ``stacked``)."""
    import re

    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.models import GPT
    from midgpt_tpu.ops import grouped
    from midgpt_tpu.serving.engine import make_block_window
    from midgpt_tpu.serving.paged import PagedKVPool

    # compiled for a TPU from a process whose backend is the CPU: the
    # grouped matmul takes what it takes on the chip
    monkeypatch.setattr(grouped, "is_tpu_backend", lambda: True)
    slots, pmax, blk = 32, 48, 4
    cfg = ModelConfig(
        block_size=pmax * PS, vocab_size=151936, n_layer=L, n_head=32,
        n_kv_head=4, head_width=128, n_embd=2048, qk_norm_kind="rms",
        rope_style="half", rope_base=1e6, norm_scale=True, norm_eps=1e-6,
        mlp="experts", experts=128, experts_per_token=8, expert_hidden=768,
        block_len=blk, block_steps=blk, mask_token=151669,
    )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda a: arr(a.shape, a.dtype), tree)

    model = described(jax.eval_shape(
        lambda k: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            GPT.init(k, cfg),
        ), jax.random.PRNGKey(0),
    ))
    pool = described(jax.eval_shape(
        lambda: PagedKVPool.init(cfg, slots * pmax, PS, jnp.bfloat16)
    ))
    fn = make_block_window(
        model, slots=slots, window=5, pmax=pmax, rope_len=cfg.block_size,
        paged_kernel="pallas",
    )
    compiled = fn.lower(
        model, pool, arr((slots, pmax), jnp.int32), arr((slots,), jnp.int32),
        arr((slots,), jnp.bool_), arr((slots,), jnp.int32),
        arr((slots,), jnp.int32), arr((slots,), jnp.int32),
        arr((slots, blk), jnp.int32), arr((slots, blk), jnp.bool_),
        arr((slots, blk), jnp.int32), arr((slots,), jnp.bool_),
        arr((slots, blk), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%paged_verify\S* = \S+ custom-call\(", text)) == L
    # the kernel writes a KV head's G*T rows as whole tiles: nothing is
    # re-laid inside the jitted call (the 6-d ``[S, Hkv, G, T, 1, C]``
    # output was, 1.2 ms a forward: PERF.md section 6, PR 29); what reads
    # the output is the attention's own transpose in front of ``wo``
    assert not [
        line.strip()[:160] for line in text.splitlines()
        if re.search(r"= \S+ copy\(", line) and "paged_verify" in line
    ]
    assert f"bf16[{slots},4,8,{2 * blk},1,128]" not in text
    # two grouped matmuls a layer, the Pallas kernel (ops/grouped.py)
    assert len(re.findall(r"%gmm\S* = \S+ custom-call\(", text)) == 2 * L
    pool_shape = f"bf16[{L},{slots * pmax},{PS},512]"
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if re.search(r"= \S+ copy\(", line)
        and line.split("= ", 1)[1].startswith(pool_shape)
    ]
    assert not copies, copies
    one_layer_of_experts = 128 * 2048 * 768 * 3 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < (
        one_layer_of_experts // 2
    )


def test_gdn_step_kernel_compiles_in_place(one_chip):
    """The gated delta rule's decode kernel at the benchmark cell's widths
    (32 slots, 30 heads, a state of 96 x 192 a head, twelve layers' stack):
    Mosaic takes it, its events carry the name the benchmark's reduction
    finds them by (benchmark/metrics/gdn_step_roofline.serve), and the
    stack is aliased input to output, nothing of its size copied."""
    import re

    from midgpt_tpu.ops import gated_delta as gd

    s, h, dk, dv, ll = 32, 30, 96, 192, 12
    bf, f32 = jnp.bfloat16, jnp.float32

    def fn(q, k, v, g, beta, stack):
        return gd._STEP_CALL(q, k, v, g, beta, stack, layer=3, interpret=False)

    args = [
        jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
        for shape, dt in (
            ((s, h, dk), bf), ((s, h, dk), bf), ((s, h, dv), bf),
            ((s, h), f32), ((s, h), f32), ((ll, s, h, dk, dv), f32),
        )
    ]
    compiled = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"%gdn_step(\.\d+)? = .*custom-call\(", text)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= ll * s * h * dk * dv * 4
    assert stats.temp_size_in_bytes < s * h * dk * dv * 4
    assert gd.kernel_shapes_ok(h, dk, dv)


def _hybrid_cell(one_chip, monkeypatch):
    """A model of three gated-delta-rule layers and one full-attention
    layer at the benchmark cell's widths (D 3840, 30 heads, V 100352; 32
    slots of 128 pages), as described arrays: the program's arguments
    ``(model, pool, logits, state)``, ``arr``, and ``cache_copies(text)``,
    the compiled program's copies of the pool's or the state's shape."""
    import re

    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.models import GPT
    from midgpt_tpu.ops import gated_delta as gd
    from midgpt_tpu.serving.paged import PagedKVPool, RecurrentState

    # compiled for a TPU from a process whose backend is the CPU: the step
    # takes the kernel it takes on the chip
    monkeypatch.setattr(gd, "is_tpu_backend", lambda: True)
    slots, pmax, pages = 32, 128, 4096
    cfg = ModelConfig(
        block_size=pmax * PS, vocab_size=100352, n_layer=4, n_head=30,
        n_kv_head=30, head_width=128, n_embd=3840, mlp="swiglu",
        mlp_hidden=11008, qk_norm_kind="rms_full", rope_style="none",
        norm_scale=True, norm_eps=1e-6, norm_order="post",
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_key_heads=30, linear_value_heads=30, linear_key_dim=96,
        linear_value_dim=192, linear_neg_eigval=True,
    )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def described(tree):
        return jax.tree.map(lambda a: arr(a.shape, a.dtype), tree)

    model = described(jax.eval_shape(
        lambda k: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), GPT.init(k, cfg)
        ), jax.random.PRNGKey(0),
    ))
    pool = described(jax.eval_shape(
        lambda: PagedKVPool.init(cfg, pages, PS, jnp.bfloat16)
    ))
    state = described(jax.eval_shape(
        lambda: RecurrentState.init(cfg, slots, jnp.bfloat16)
    ))
    logits = arr((slots, cfg.vocab_size), jnp.float32)

    def cache_copies(text):
        shapes = (f"bf16[1,{pages},{PS},3840]", "f32[3,32,30,96,192]")
        return [
            line.strip()[:160] for line in text.splitlines()
            if re.search(r"= \S+ copy\(", line)
            and line.split("= ", 1)[1].startswith(shapes)
        ]

    return cfg, (model, pool, logits, state), arr, cache_copies


def test_hybrid_window_compiles_without_a_cache_copy(one_chip, monkeypatch):
    """The decode window of :func:`_hybrid_cell`: one step-kernel call a
    linear layer and one paged-attention call a full one in the window's
    step, and neither the page pool nor the recurrent state — both
    donated, both carried through the window's scan — is copied."""
    import re

    from midgpt_tpu.serving.engine import make_decode_window

    cfg, (model, pool, logits, state), arr, cache_copies = _hybrid_cell(
        one_chip, monkeypatch
    )
    slots, pmax = logits.shape[0], cfg.block_size // PS
    i32 = lambda *shape: arr(shape, jnp.int32)  # noqa: E731
    window = make_decode_window(
        model, slots=slots, window=16, pmax=pmax, rope_len=cfg.block_size,
        paged_kernel="pallas",
    )
    text = window.lower(
        model, pool, logits, i32(slots, pmax), i32(slots),
        arr((slots,), jnp.bool_), i32(slots), i32(slots), i32(slots),
        i32(slots), arr((2,), jnp.uint32), state,
    ).compile().as_text()
    assert len(re.findall(r"%gdn_step(?:\.\d+)? = .*? custom-call\(", text)) == 3
    assert len(re.findall(r"%closed_call\.\d+ = \S+ custom-call\(", text)) == 1
    assert not cache_copies(text), cache_copies(text)


def test_hybrid_chunk_compiles_without_a_cache_copy(one_chip, monkeypatch):
    """A 256-token prefill chunk of :func:`_hybrid_cell` (the chunked rule,
    a slot's state taken, advanced and put back): no copy of the pool or of
    the state either."""
    from midgpt_tpu.serving.engine import make_prefill_chunk_program

    cfg, (model, pool, logits, state), arr, cache_copies = _hybrid_cell(
        one_chip, monkeypatch
    )
    pmax = cfg.block_size // PS
    i32 = lambda *shape: arr(shape, jnp.int32)  # noqa: E731
    chunk = make_prefill_chunk_program(
        model, chunk_len=256, pmax=pmax, rope_len=cfg.block_size,
    )
    text = chunk.lower(
        model, pool, logits, i32(), i32(1, 256), i32(), i32(), i32(pmax),
        state, arr((), jnp.bool_),
    ).compile().as_text()
    assert not cache_copies(text), cache_copies(text)


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
