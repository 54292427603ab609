"""Pallas ragged paged-attention kernel (ops/paged_attn) + int8 KV pool
(serving.paged kv_quant): the exactness contracts that make both landable.

- The kernel is ONE body whose two products run on the matrix unit
  (ops/paged_attn.py, THE CONTRACT): a causal-verify row and the decode
  row of the same token agree TO THE BIT (what speculative acceptance
  rests on); the XLA gather path is matched at a tolerance that still
  catches the bug class it guards against (a bf16 accumulation moves a
  logit by 1e-3; the tolerance is 1e-5) — asserted at the op level
  (decode + verify + block forward, ragged lengths, MHA and GQA, raw
  f32 logits) — and the engine's greedy streams stay token-for-token
  (cache x chunking x speculation x eviction).
- The int8 KV grid is bitwise-dequantizable (po2 page scales — the
  quant.py contract applied to the KV stream) and page scales are a
  pure function of the token stream, so int8-KV streams are INVARIANT
  to window size, chunk size, speculation, eviction, and the kernel
  backend — asserted pairwise across the feature matrix.
- Page scales travel atomically with page payloads through
  copy-on-write duplication and cold retirement (a stale scale on an
  aliased page is the silent-corruption case — deterministic, bit-
  stable, and wrong; the prefix-cache-hit identity test pins it).

Kernels execute through the Pallas CPU interpreter on this tier (the
same bodies the TPU compiles; tests/test_chip_compile.py holds the
Mosaic compiles) — asked for by the ``pallas_interpret`` fixture, never
chosen by the program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT, decode_step_paged, verify_tokens_paged
from midgpt_tpu.quant import (
    kv_scale_from_absmax,
    po2_ceil_exact,
    quantize_kv_rows,
    round_kv_rows_to_grid,
)
from midgpt_tpu.sampling import generate
from midgpt_tpu.serving import PagedKVPool, ServingEngine, generate_served
from midgpt_tpu.serving.paged import kv_row_scales

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)
# GQA shape: 4 query heads sharing 2 KV heads — the grouped walk
GQA_CFG = dataclasses.replace(CFG, n_kv_head=2)


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret):
    yield


def _model(cfg=CFG):
    return GPT.init(jax.random.PRNGKey(0), cfg)


def _prompts(n, base_len=5, stride=3):
    return [
        np.asarray(
            jax.random.randint(
                jax.random.PRNGKey(100 + i), (base_len + stride * i,), 0,
                CFG.vocab_size,
            )
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# the po2 KV grid (quant.py): exactness units
# ---------------------------------------------------------------------------


def test_po2_ceil_exact_is_po2_and_tight():
    y = jnp.asarray(
        [1.0, 127.0, 0.5, 3.7, 2.0**-10, 126.99, 2.0**20], jnp.float32
    )
    s = np.asarray(po2_ceil_exact(y))
    assert np.all(np.log2(s) == np.round(np.log2(s))), "not powers of two"
    assert np.all(s >= np.asarray(y) * (1 - 1e-7))
    assert np.all(s < 2 * np.asarray(y) + 1e-30), "not the SMALLEST po2"
    # the boundary case log2-based derivations get wrong: exact po2 in
    assert float(po2_ceil_exact(jnp.float32(0.25))) == 0.25


def test_po2_ceil_exact_full_exponent_range():
    """Bit-exact over EVERY f32 exponent, not just the friendly middle
    band: jnp.exp2 is a polynomial approximation that is off by ulps at
    integer arguments outside roughly [-14, 28] (and flushes to 0 below
    ~-125 on XLA CPU), which is how an earlier exp2-based derivation
    produced non-po2 'po2' scales for any page with birth absmax below
    ~8e-3 — real checkpoints hit that immediately. po2_ceil_exact must
    land every exact power of two on itself and every other input on
    the next po2 up, across the whole normal + subnormal range."""
    import math

    # every exact po2 maps to itself
    for e in range(-149, 128):
        p = math.ldexp(1.0, e)
        assert float(po2_ceil_exact(jnp.float32(p))) == p, e
    # off-po2 inputs round UP to the adjacent po2, full exponent sweep
    for e in range(-148, 127):
        y = np.float32(1.5 * math.ldexp(1.0, e))
        if y <= 0:  # subnormal product underflow on the host — skip
            continue
        m, ee = np.frexp(y)
        want = math.ldexp(1.0, int(ee - 1) if m == 0.5 else int(ee))
        assert float(po2_ceil_exact(jnp.asarray(y))) == want, e
    # the review's repro: tiny absmax must still give a true po2 scale
    s = float(kv_scale_from_absmax(jnp.float32(1e-7)))
    assert s > 0 and math.log2(s) == int(math.log2(s)), s


def test_kv_scale_rounding_stable():
    """derive(round_to_grid(row, derive(row))) == derive(row) — the
    property that lets the bulk page writes re-derive scales from the
    already-rounded rows they receive (serving.paged docstring)."""
    for i in range(64):
        # magnitudes from 1e-36 (the KV_SCALE_MIN clamp band) to 1e20 —
        # stability and the bitwise grid must hold at EVERY magnitude,
        # not just the exp2-friendly middle (see
        # test_po2_ceil_exact_full_exponent_range)
        row = jax.random.normal(
            jax.random.PRNGKey(i), (64,), jnp.float32
        ) * (10.0 ** (i % 15 * 4 - 36))
        s0 = kv_scale_from_absmax(jnp.max(jnp.abs(row)))
        rounded = round_kv_rows_to_grid(row[None], s0[None])[0]
        s1 = kv_scale_from_absmax(jnp.max(jnp.abs(rounded)))
        assert float(s0) == float(s1), (i, float(s0), float(s1))
    # all-zero rows take the inert scale 1.0
    assert float(kv_scale_from_absmax(jnp.float32(0.0))) == 1.0


def test_page_level_bitwise_dequant_contract():
    """THE int8-KV exactness statement, at page granularity: attending
    int8 codes via ``f32(q) * scale`` is bitwise identical to attending
    a bf16 pool that holds the dequantized values — and those values
    round-trip bf16 exactly (|code| <= 127 times a po2 scale). An int8
    pool is a bf16 pool whose values lie on the grid; nothing more."""
    rows = jax.random.normal(
        jax.random.PRNGKey(3), (8, 16, 64), jnp.bfloat16
    )  # [Hkv, PS, C] one page of K rows
    scales = kv_scale_from_absmax(
        jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=(1, 2))
    )  # [Hkv] — one scale per (page, KV-head) plane
    codes = quantize_kv_rows(rows, scales[:, None])
    assert codes.dtype == jnp.int8
    # dequantize-then-attend reference: grid values in a bf16 pool
    grid_bf16 = (
        codes.astype(jnp.float32) * scales[:, None, None]
    ).astype(jnp.bfloat16)
    a = grid_bf16.astype(jnp.float32)  # what the bf16 pool path streams
    b = codes.astype(jnp.float32) * scales[:, None, None]  # in-kernel
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the rounded rows every in-dispatch reader saw ARE those values
    in_dispatch = round_kv_rows_to_grid(rows, scales[:, None])
    np.testing.assert_array_equal(
        np.asarray(in_dispatch.astype(jnp.float32)), np.asarray(a)
    )


def test_kv_row_scales_page_birth_vs_pool_lookup():
    """Rows quantize under their page's BIRTH scale: in-batch birth rows
    derive it, rows on pages born earlier read the recorded plane."""
    ps, pmax, npool, hkv, c, t = 4, 4, 8, 2, 8, 6
    rows = jax.random.normal(jax.random.PRNGKey(0), (1, hkv, t, c))
    pool_scale = jnp.full((npool, hkv), 0.125, jnp.float32)
    bt = jnp.asarray([[3, 5, 1, 7]], jnp.int32)
    base = jnp.asarray([2], jnp.int32)  # rows at positions 2..7
    sk, sv = kv_row_scales(rows, rows, base, bt, pool_scale, pool_scale, ps)
    # positions 2,3 sit on page 0 (born pre-batch): the recorded 0.125
    np.testing.assert_array_equal(np.asarray(sk[0, :, :2]), 0.125)
    # position 4 = 1*ps births page 1 in-batch: derived from row j=2
    derived = kv_scale_from_absmax(
        jnp.max(jnp.abs(rows[0, :, 2, :].astype(jnp.float32)), axis=-1)
    )
    np.testing.assert_array_equal(
        np.asarray(sk[0, :, 2]), np.asarray(derived)
    )
    # positions 5..7 share page 1's birth scale
    for j in (3, 4, 5):
        np.testing.assert_array_equal(
            np.asarray(sk[0, :, j]), np.asarray(derived)
        )


# ---------------------------------------------------------------------------
# kernel vs XLA path at the op level: contract 2 of ops/paged_attn.py
# ---------------------------------------------------------------------------


def _assert_gather_contract(got, want):
    """Contract 2: the kernel against the gather path at ``rtol=1e-5``
    — the same sums in another order (f32 models over f32 and int8 pools
    here: every product is exact or at full precision, every sum f32).
    A bf16 accumulation anywhere moves these logits by 1e-3."""
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)



def _random_pool(pool, ks):
    """``pool`` filled from the keys ``ks[:4]``: random codes under
    random po2 page scales where it is an int8 pool, normal f32 values
    where not."""
    if not pool.quantized:
        return dataclasses.replace(
            pool,
            k=jax.random.normal(ks[0], pool.k.shape, jnp.float32),
            v=jax.random.normal(ks[1], pool.v.shape, jnp.float32),
        )

    def codes(key, like):
        return jax.random.randint(
            key, like.shape, -127, 128, jnp.int32
        ).astype(jnp.int8)

    def scales(key, like):
        return jnp.exp2(
            jax.random.randint(key, like.shape, -8, -2).astype(jnp.float32)
        )

    return dataclasses.replace(
        pool, k=codes(ks[0], pool.k), v=codes(ks[1], pool.v),
        scale_k=scales(ks[2], pool.scale_k),
        scale_v=scales(ks[3], pool.scale_v),
    )


def _decode_setup(cfg, kv_quant=None, seed=1, pmax=8):
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    s, ps = 4, 8
    npool = 24
    pool = PagedKVPool.init(cfg, npool, ps, jnp.float32, kv_quant=kv_quant)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = _random_pool(pool, ks)
    bt = jax.random.randint(ks[4], (s, pmax), 0, npool).astype(jnp.int32)
    # ragged lengths: empty, partial page, page-aligned, full table
    pooled_len = jnp.asarray([0, 13, pmax * ps // 2, pmax * ps], jnp.int32)
    tokens = jax.random.randint(ks[5], (s,), 0, cfg.vocab_size)
    return model, pool, bt, pooled_len, tokens.astype(jnp.int32)


@pytest.mark.parametrize("cfg", [CFG, GQA_CFG], ids=["mha", "gqa"])
@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["f32", "kv8"])
def test_decode_kernel_vs_xla(cfg, kv_quant):
    """decode_step_paged with paged_kernel='pallas' returns the XLA
    gather path's logits (contract 2) — ragged per-slot lengths (incl.
    an empty slot and a partial page), both pool precisions, MHA (ONE
    query row a head: seven rows of padding that must not leak) and
    GQA."""
    model, pool, bt, pooled_len, tokens = _decode_setup(cfg, kv_quant)
    l, s = cfg.n_layer, tokens.shape[0]
    rr = 4
    rk = jnp.zeros((l, s, cfg.kv_heads, rr, cfg.head_dim), pool.row_dtype)
    rv = jnp.zeros_like(rk)
    pos = pooled_len + 1  # one recent row already written
    rk = rk.at[:, :, :, 0, :].set(0.25)
    rv = rv.at[:, :, :, 0, :].set(-0.5)
    r = jnp.asarray(1, jnp.int32)
    outs = {}
    for kern in ("xla", "pallas"):
        logits, rko, rvo = jax.jit(
            lambda tk, pk, pv, b_, rk_, rv_, pl_, sk, sv: decode_step_paged(
                model, tk, pos, pk, pv, b_, rk_, rv_, r, pl_,
                cfg.block_size, pool_sk=sk, pool_sv=sv, paged_kernel=kern,
            )
        )(tokens, pool.k, pool.v, bt, rk, rv, pooled_len,
          pool.scale_k, pool.scale_v)
        outs[kern] = (
            np.asarray(logits, np.float32), np.asarray(rko, np.float32),
        )
    _assert_gather_contract(outs["pallas"][0], outs["xla"][0])
    _assert_gather_contract(outs["pallas"][1], outs["xla"][1])
    # the first layer's recent rows are upstream of any attention
    np.testing.assert_array_equal(outs["xla"][1][0], outs["pallas"][1][0])


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["f32", "kv8"])
def test_verify_kernel_vs_xla(kv_quant):
    """verify_tokens_paged: all candidate rows, joint pool+self softmax —
    the kernel against the XLA path (contract 2), and the returned K/V
    rows (what the watermark flush writes) too."""
    cfg = GQA_CFG
    model, pool, bt, pooled_len, _ = _decode_setup(cfg, kv_quant)
    s, t = 4, 3
    cand = jax.random.randint(
        jax.random.PRNGKey(9), (s, t), 0, cfg.vocab_size
    ).astype(jnp.int32)
    outs = {}
    for kern in ("xla", "pallas"):
        logits, ks, vs = jax.jit(
            lambda c_, pk, pv, b_, pl_, sk, sv: verify_tokens_paged(
                model, c_, pl_, pk, pv, b_, cfg.block_size,
                pool_sk=sk, pool_sv=sv, paged_kernel=kern,
            )
        )(cand, pool.k, pool.v, bt, pooled_len, pool.scale_k, pool.scale_v)
        outs[kern] = (
            np.asarray(logits, np.float32), np.asarray(ks, np.float32),
            np.asarray(vs, np.float32),
        )
    for a, b in zip(outs["xla"], outs["pallas"]):
        _assert_gather_contract(b, a)


@pytest.mark.parametrize("kern", ["xla", "pallas"])
@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["bf16", "kv8"])
def test_verify_one_row_is_decode(kv_quant, kern):
    """A verify dispatch of ONE candidate row is a one-step decode window
    (``r = 0`` of a one-row recent buffer), to the bit: logits and the K/V
    row that lands. On the gather path both run one core
    (gpt._gather_attend: decode's recent buffer in the place of verify's
    own rows), on the kernel one body — what speculative acceptance rests
    on. An f32 model over a bf16 / int8 pool: verify must round its own
    row to the pool's row dtype as the recent buffer does. (A longer
    recent buffer adds masked columns, exact zeros, to the softmax axis;
    XLA's CPU backend then sums that axis in another order and the logits
    move by an ulp: R = 4 is equal in value, not to the bit.)"""
    cfg = GQA_CFG
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    s, ps, pmax, npool, rr = 4, 8, 8, 24, 1
    pool = PagedKVPool.init(cfg, npool, ps, jnp.bfloat16, kv_quant=kv_quant)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    pool = _random_pool(pool, ks)
    if not pool.quantized:  # _random_pool fills a float pool in f32
        pool = dataclasses.replace(
            pool, k=pool.k.astype(jnp.bfloat16), v=pool.v.astype(jnp.bfloat16)
        )
    bt = jax.random.randint(ks[4], (s, pmax), 0, npool).astype(jnp.int32)
    # empty, mid-page, a page born at this very position, full but one
    pooled_len = jnp.asarray([0, 13, 2 * ps, pmax * ps - 1], jnp.int32)
    tokens = jax.random.randint(
        ks[5], (s,), 0, cfg.vocab_size
    ).astype(jnp.int32)
    rk = jnp.zeros(
        (cfg.n_layer, s, cfg.kv_heads, rr, cfg.head_dim), pool.row_dtype
    )
    d_logits, rko, rvo = jax.jit(
        lambda tk, pk, pv, rk_, rv_, sk, sv: decode_step_paged(
            model, tk, pooled_len, pk, pv, bt, rk_, rv_,
            jnp.asarray(0, jnp.int32), pooled_len, cfg.block_size,
            pool_sk=sk, pool_sv=sv, paged_kernel=kern,
        )
    )(tokens, pool.k, pool.v, rk, jnp.zeros_like(rk),
      pool.scale_k, pool.scale_v)
    v_logits, vks, vvs = jax.jit(
        lambda tk, pk, pv, sk, sv: verify_tokens_paged(
            model, tk[:, None], pooled_len, pk, pv, bt, cfg.block_size,
            pool_sk=sk, pool_sv=sv, paged_kernel=kern,
        )
    )(tokens, pool.k, pool.v, pool.scale_k, pool.scale_v)
    assert np.isfinite(np.asarray(d_logits)).all()
    np.testing.assert_array_equal(
        np.asarray(d_logits), np.asarray(v_logits[:, 0])
    )
    for rec, rows in ((rko, vks), (rvo, vvs)):
        np.testing.assert_array_equal(
            np.asarray(rec[:, :, :, :1], np.float32),
            np.asarray(rows.astype(pool.row_dtype), np.float32),
        )


# f32-nb2 (2 bands) proves the multi-band fold in tier-1; the deeper
# band counts and the int8-pool multiband cells ride the slow tier to
# keep tier-1 inside the 870 s verify budget (the serving-longctx CI
# job runs the banded legs fast + slow, and serving-choreo runs this
# file unfiltered). int8 at NB=1 stays fast via the kv8 cells of
# test_decode_kernel_vs_xla above.
@pytest.mark.parametrize(
    "kv_quant,band_pages_,pmax",
    [
        pytest.param(None, 4, 8, id="f32-nb2"),
        pytest.param(None, 2, 8, id="f32-nb4", marks=pytest.mark.slow),
        pytest.param(None, 1, 8, id="f32-nb8", marks=pytest.mark.slow),
        # the int8 two-band cell of tier-1: two bands of 16 rows
        pytest.param("int8", 2, 4, id="kv8-nb2-bw16"),
        # (two int8 bands of 32 rows were a strict xfail while the
        # contract was the bit: XLA's CPU backend vectorizes the gather
        # path's fused dequantize-and-reduce at exactly that width and
        # the two sides differed by 2.4e-6 — PERF.md section 7. Under
        # the tolerance the cell is an ordinary one.)
        pytest.param("int8", 4, 8, id="kv8-nb2", marks=pytest.mark.slow),
        pytest.param("int8", 2, 8, id="kv8-nb4", marks=pytest.mark.slow),
        pytest.param("int8", 1, 8, id="kv8-nb8", marks=pytest.mark.slow),
    ],
)
def test_banded_kernel_vs_banded_xla(kv_quant, band_pages_, pmax,
                                     paged_hook):
    """Genuinely MULTI-banded streaming (ISSUE 20): force the band plan
    below the whole table (the auto-sizer picks one band at this tiny
    geometry) and hold kernel to XLA (contract 2) for decode AND
    verify. Both sides slice per band and fold partials through
    banded_fold, so this exercises the whole banded contract: per-band
    masking, per-band dequant slices, and the pinned ascending fold —
    at 8, 4, and 2 pages per band against the pmax=8 table, and an int8
    pool's two bands of a pmax=4 table."""
    paged_hook("_FORCE_BAND_PAGES", band_pages_)
    cfg = GQA_CFG
    model, pool, bt, pooled_len, tokens = _decode_setup(
        cfg, kv_quant, pmax=pmax
    )
    l, s = cfg.n_layer, tokens.shape[0]
    rk = jnp.zeros((l, s, cfg.kv_heads, 4, cfg.head_dim), pool.row_dtype)
    rk = rk.at[:, :, :, 0, :].set(0.25)
    rv = jnp.zeros_like(rk).at[:, :, :, 0, :].set(-0.5)
    pos = pooled_len + 1
    r = jnp.asarray(1, jnp.int32)
    outs = {}
    for kern in ("xla", "pallas"):
        logits, _, _ = jax.jit(
            lambda tk, pk, pv, b_, rk_, rv_, pl_, sk, sv: decode_step_paged(
                model, tk, pos, pk, pv, b_, rk_, rv_, r, pl_,
                cfg.block_size, pool_sk=sk, pool_sv=sv, paged_kernel=kern,
            )
        )(tokens, pool.k, pool.v, bt, rk, rv, pooled_len,
          pool.scale_k, pool.scale_v)
        outs[kern] = np.asarray(logits, np.float32)
    _assert_gather_contract(outs["pallas"], outs["xla"])
    cand = jax.random.randint(
        jax.random.PRNGKey(9), (s, 3), 0, cfg.vocab_size
    ).astype(jnp.int32)
    vouts = {}
    for kern in ("xla", "pallas"):
        logits, _, _ = jax.jit(
            lambda c_, pk, pv, b_, pl_, sk, sv: verify_tokens_paged(
                model, c_, pl_, pk, pv, b_, cfg.block_size,
                pool_sk=sk, pool_sv=sv, paged_kernel=kern,
            )
        )(cand, pool.k, pool.v, bt, pooled_len, pool.scale_k, pool.scale_v)
        vouts[kern] = np.asarray(logits, np.float32)
    _assert_gather_contract(vouts["pallas"], vouts["xla"])


# ---------------------------------------------------------------------------
# engine token identity: the matrix with the kernel on
# ---------------------------------------------------------------------------


def _exact(model, prompt, n_new):
    return np.asarray(
        generate(
            model, jnp.asarray(prompt)[None], n_new,
            key=jax.random.PRNGKey(9), temperature=0.0,
            cache_dtype=jnp.float32,
        )
    )[0]


@pytest.fixture(scope="module")
def kernel_case():
    model = _model()
    prompts = _prompts(3)
    lens = [9, 12, 7]
    refs = [_exact(model, p, n) for p, n in zip(prompts, lens)]
    return model, prompts, lens, refs


def _run_engine(model, prompts, lens, **kw):
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, **kw,
    )
    rids = [eng.submit(p, n) for p, n in zip(prompts, lens)]
    fin = eng.run()
    eng.alloc.check()
    if eng.index is not None:
        eng.index.check(eng.alloc)
    assert eng.alloc.held_pages == 0
    return [fin[r].tokens for r in rids]


def test_engine_kernel_token_identity_matrix(kernel_case):
    """Acceptance: greedy streams with paged_kernel='pallas' are token-
    identical to the XLA path AND the exact fixed-batch sampler across
    prefix-cache x chunked-prefill x speculation (mid-run admission:
    more requests than slots)."""
    model, prompts, lens, refs = kernel_case
    base = [list(map(int, r)) for r in refs]
    for variant in [
        dict(prefix_cache=False),
        dict(prefix_cache=True, prefill_chunk=5),
        dict(prefix_cache=True, speculate=4),
    ]:
        toks = _run_engine(
            model, prompts, lens, paged_kernel="pallas", **variant
        )
        assert toks == base, f"pallas variant {variant} diverged"


def test_engine_kernel_under_eviction(kernel_case):
    """Kernel path x page pressure: eviction/re-admission keeps streams
    identical to the exact sampler (the ragged walk sees rebuilt block
    tables and re-prefilled pages)."""
    model = _model()
    prompts = _prompts(4, base_len=6, stride=0)
    refs = [_exact(model, p, 16) for p in prompts]
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=5, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
        paged_kernel="pallas",
    )
    rids = [eng.submit(p, 16) for p in prompts]
    fin = eng.run()
    assert eng.evictions > 0, "trace was sized to force eviction"
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )


# ---------------------------------------------------------------------------
# int8 KV pool: stream invariance + scale atomicity
# ---------------------------------------------------------------------------


def test_kv_quant_stream_invariance_matrix(kernel_case):
    """Acceptance: int8-KV greedy streams are IDENTICAL across the
    feature matrix — cache on/off x chunked/monolithic x speculation x
    window size x kernel backend. (The streams legitimately differ from
    the full-precision pool — KV quantization is lossy — but they may
    not depend on any scheduling knob: page scales are a pure function
    of the token stream.)"""
    model, prompts, lens, _ = kernel_case
    base = None
    for variant in [
        dict(prefix_cache=False, paged_kernel="xla"),
        dict(prefix_cache=True, prefill_chunk=5, paged_kernel="xla"),
        dict(prefix_cache=False, speculate=4, paged_kernel="xla"),
        dict(prefix_cache=True, paged_kernel="pallas"),
        dict(prefix_cache=True, speculate=4, paged_kernel="pallas"),
    ]:
        toks = _run_engine(
            model, prompts, lens, kv_quant="int8", **variant
        )
        if base is None:
            base = toks
        else:
            assert toks == base, f"kv-quant variant {variant} diverged"


def test_kv_quant_window_size_invariance(kernel_case):
    """K=1 quantizes at every window boundary, K=4 once per window —
    in-window grid rounding makes the streams indistinguishable."""
    model, prompts, lens, _ = kernel_case
    k1 = [
        t.tolist() for t in generate_served(
            model, prompts, max(lens), window=1, page_size=8,
            cache_dtype=jnp.float32, kv_quant="int8", paged_kernel="xla",
        )
    ]
    k4 = [
        t.tolist() for t in generate_served(
            model, prompts, max(lens), window=4, page_size=8,
            cache_dtype=jnp.float32, kv_quant="int8", paged_kernel="xla",
        )
    ]
    assert k1 == k4


def test_kv_quant_prefix_cache_hit_identity():
    """Satellite regression (the silent-corruption case): a prefix-cache
    hit under kv-quant aliases int8 pages INTO a new block table — the
    dequant is only right if the per-page scales arrived with the
    payload. Cold-hit, COW partial-page copy, and decode-written pages
    are all exercised; streams must equal the cache-off run exactly."""
    model = _model()
    prompt = _prompts(1, base_len=24)[0]
    tails = _prompts(2, base_len=3, stride=2)
    # the repeat of the bare prompt is the COW trigger: its match is
    # capped at p-1, leaving a partial-page tail that aliases the
    # already-indexed full page via copy_page (payload + scale)
    reqs = [prompt] + [np.concatenate([prompt, t]) for t in tails] + [prompt]
    lens = [6, 8, 7, 5]

    def run(prefix_cache):
        eng = ServingEngine(
            model, slots=1, page_size=8, window=4, temperature=0.0,
            cache_dtype=jnp.float32, prefix_cache=prefix_cache,
            kv_quant="int8",
        )
        rids = []
        for p, n in zip(reqs, lens):
            rids.append(eng.submit(p, n))
        fin = eng.run()
        return [fin[r].tokens for r in rids], eng

    cold, _ = run(False)
    hit, eng = run(True)
    assert hit == cold, "aliased page served a stale scale"
    # the hits really happened (this test must exercise aliasing): the
    # second/third requests share prompt pages + the COW partial page
    assert eng.prompt_tokens_cached > 0
    assert eng.copy_dispatches >= 1


def test_kv_quant_eviction_cold_retire_carries_scales():
    """Evicted requests' pages retire COLD with their scales; re-
    admission re-hits them and the continuation is bit-identical to the
    never-evicted run."""
    model = _model()
    prompts = _prompts(4, base_len=6, stride=0)
    plenty = [
        _run_engine(
            model, prompts, [16] * 4, kv_quant="int8", prefix_cache=True
        )
    ][0]
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=5, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
        kv_quant="int8",
    )
    rids = [eng.submit(p, 16) for p in prompts]
    fin = eng.run()
    assert eng.evictions > 0
    assert [fin[r].tokens for r in rids] == plenty


def test_write_prompt_pages_quantized_roundtrip():
    """The page-aligned bulk write path: rows land as int8 codes + birth
    scales, and reading them back dequantizes to exactly the grid
    rounding of the written rows (error <= scale/2 vs the originals)."""
    from midgpt_tpu.serving.paged import write_prompt_pages

    cfg = CFG
    ps, n = 8, 2
    pool = PagedKVPool.init(cfg, 6, ps, kv_quant="int8")
    ks = jax.random.normal(
        jax.random.PRNGKey(1),
        (cfg.n_layer, cfg.kv_heads, n * ps, cfg.head_dim), jnp.float32,
    )
    vs = jax.random.normal(jax.random.PRNGKey(2), ks.shape, jnp.float32)
    rows = jnp.asarray([4, 1], jnp.int32)
    pool = write_prompt_pages(pool, ks, vs, rows)
    for li in range(cfg.n_layer):
        for pi, page in enumerate([4, 1]):
            got = jnp.transpose(
                pool.k[li, page].astype(jnp.float32).reshape(
                    ps, cfg.kv_heads, cfg.head_dim
                ), (1, 0, 2),
            ) * pool.scale_k[li, page][:, None, None]  # [Hkv, PS, C]
            page_rows = ks[li, :, pi * ps : (pi + 1) * ps, :]  # [Hkv,PS,C]
            # dequant equals the canonical grid rounding of the written
            # rows EXACTLY (incl. the +-127 clip for rows past the birth
            # row's headroom)
            s_rows = jnp.broadcast_to(
                pool.scale_k[li, page][:, None], (cfg.kv_heads, ps)
            )
            want_grid = round_kv_rows_to_grid(page_rows, s_rows)
            np.testing.assert_array_equal(
                np.asarray(got),
                np.asarray(want_grid.astype(jnp.float32)),
            )
            # the BIRTH row (the scale's source) is never clipped and
            # lands within scale/2 of the original
            scale = pool.scale_k[li, page]  # [Hkv]
            birth_err = jnp.abs(got[:, 0, :] - page_rows[:, 0, :])
            assert float(
                jnp.max(birth_err / scale[:, None])
            ) <= 0.5 + 1e-6


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------


def test_paged_kernel_auto_resolves_to_xla_on_cpu():
    eng = ServingEngine(_model(), slots=1, page_size=8, window=2)
    assert eng.paged_kernel == "xla"  # no TPU backend in this suite
    with pytest.raises(AssertionError):
        ServingEngine(_model(), slots=1, page_size=8, paged_kernel="mosaic")


def test_kernel_supported_gates_on_vmem():
    """The gate is what the chip's compiler was seen to accept
    (tests/test_chip_compile.py holds the compiles). The kernel walks a
    slot's live pages out of HBM itself, so what a grid step holds is
    a fetch buffer of two bands (one computing, one on its way), one
    band's f32 compute and the flat-softmax score rows."""
    from midgpt_tpu.ops.paged_attn import (
        head_block,
        supported,
        vmem_bytes,
    )

    # the shapes compiled for the described v5e: 12 heads of 64, the
    # serving cell's 16 heads of 128, both pool precisions, verify rows
    for c in (64, 128):
        for itemsize in (2, 1):
            assert supported(pmax=64, page_size=16, c=c, itemsize=itemsize,
                             groups=1)
            assert supported(pmax=64, page_size=16, c=c, itemsize=itemsize,
                             groups=1, spec_t=4)
    assert supported(pmax=128, page_size=16, c=128, itemsize=2, groups=4)
    # the serving cell's arithmetic, pinned so a dropped term moves a
    # literal: 8 pages a band, every head in one grid step — the fetch
    # buffer's two bands of 8 pages of [16, 16*128] bf16 each; one
    # [128, 128] f32 band and its [8, 128] score tile (an MHA head's one
    # row, padded to a sublane tile); 16 heads' three dense [8, W + 128]
    # f32 row sets
    fetch = 2 * 8 * (16 * 2048 * 2)
    band = 128 * 128 * 4 + 8 * 128 * 4
    scores = 16 * 3 * 8 * (1024 + 128) * 4
    assert head_block(16, 64, 16, 128, 2, groups=1) == 16
    assert head_block(12, 64, 16, 64, 2, groups=1) == 12
    assert vmem_bytes(64, 16, 128, 2, groups=1, heads=16) \
        == fetch + band + scores == 2_887_680
    # the olmo cell's four full layers (30 heads, 128 pages): 16 bands,
    # so MAX_UNROLL lets fifteen heads share a grid step (and a DMA)
    assert head_block(30, 128, 16, 128, 2, groups=1) == 15
    # no term scales with Pmax but the score rows ...
    assert supported(pmax=4096, page_size=16, c=64, itemsize=2, groups=1)
    # ... and those scale with the REAL group count and spec length,
    # not a cap
    assert supported(pmax=4096, page_size=16, c=64, itemsize=2, groups=16)
    assert not supported(pmax=4096, page_size=16, c=64, itemsize=2,
                         groups=64)
    assert supported(pmax=256, page_size=16, c=64, itemsize=2, groups=12)
    assert supported(pmax=512, page_size=16, c=64, itemsize=2, groups=4)
    assert supported(pmax=512, page_size=16, c=64, itemsize=2, groups=4,
                     spec_t=64)
    assert not supported(pmax=512, page_size=16, c=64, itemsize=2,
                         groups=4, spec_t=128)


def test_kernel_gate_accepts_100k_token_pmax():
    """At a 100k-token context the block table spans
    ``pages_needed(100_000, 16) = 6250`` pages. The kernel holds two
    bands of them — of 50 bands of 125 pages, four heads a grid step —
    and the
    gate says yes (tests/test_chip_compile.py compiles that very table);
    what still says no is a score row of 100k positions for each of 32
    query heads a KV head. The byte arithmetic is pinned exactly so a
    dropped term moves a literal; the band PLAN (which fixes the PV
    fold order on the XLA side too) is pinned with it."""
    from midgpt_tpu.ops.paged_attn import (
        BAND_VMEM_BUDGET,
        VMEM_BUDGET,
        band_pages,
        head_block,
        supported,
        vmem_bytes,
    )
    from midgpt_tpu.serving.paged import pages_needed

    pmax = pages_needed(100_000, 16)
    assert pmax == 6250
    w = pmax * 16  # 100_000 resident positions
    # band plan, bf16 and int8: 50 bands of 2000 positions — the
    # narrowest divisor that leaves at most MAX_BANDS bands
    assert band_pages(pmax, 16, 64, 2) == 125
    assert band_pages(pmax, 16, 64, 1) == 125
    assert 2 * 2 * 64 * 2000 * 2 + 2 * 64 * 2000 * 4 <= BAND_VMEM_BUDGET
    # four heads a step: 50 bands x 4 heads stay inside MAX_UNROLL, and
    # four heads of 64 are two whole lane tiles
    assert head_block(12, pmax, 16, 64, 2, groups=1) == 4
    # bf16, at two heads: the fetch buffer's two bands of 125 pages of
    # [16, 128] each; the band's f32 view and its [8, 2048] score tiles;
    # two heads' three dense [8, W + 128] row sets
    fetch_bf16 = 2 * 125 * (16 * 128 * 2)
    band_f32 = 2000 * 128 * 4 + 8 * 2048 * 4
    scores = 2 * 3 * 8 * (w + 128) * 4
    assert vmem_bytes(pmax, 16, 64, 2, groups=1, heads=2) \
        == fetch_bf16 + band_f32 + scores == 21_338_112 < VMEM_BUDGET
    assert vmem_bytes(pmax, 16, 64, 2, groups=1, heads=4) \
        == 2 * fetch_bf16 + band_f32 + 2 * scores == 41_586_688 < VMEM_BUDGET
    assert supported(pmax, 16, 64, 2, groups=1)
    # asked of a device's own head count, the gate answers for the block
    # the call will run: 12 heads go four a step; 7 heads of 64 have no
    # whole-lane-tile block but all seven, whose 350 unrolled bodies a
    # pass are past MAX_UNROLL
    assert supported(pmax, 16, 64, 2, groups=1, heads=12)
    assert head_block(7, pmax, 16, 64, 2, groups=1) is None
    assert not supported(pmax, 16, 64, 2, groups=1, heads=7)
    # int8: [16, 128] pages pad to (32, 128) tiles, plus the f32
    # dequantized band
    fetch_int8 = 2 * 125 * (32 * 128 * 1) + 125 * (16 * 128 * 4)
    assert vmem_bytes(pmax, 16, 64, 1, groups=1, heads=2) \
        == fetch_int8 + band_f32 + scores == 22_362_112
    assert supported(pmax, 16, 64, 1, groups=1)
    # 12 query heads a KV head are 16 dense rows, a third of the budget
    # (padded 8x, as the VPU rows were, one head's alone overflowed it);
    # 32 query heads a KV head overflow
    assert vmem_bytes(pmax, 16, 64, 2, groups=12, heads=1) \
        == 21_403_648 < VMEM_BUDGET
    assert supported(pmax, 16, 64, 2, groups=12)
    assert not supported(pmax, 16, 64, 2, groups=32)
    assert not supported(pmax, 16, 64, 1, groups=32)
    # no band plan, no kernel: a head dim so wide one page overflows
    # the band budget, and a prime page count whose only fitting
    # divisor needs > MAX_BANDS bands
    assert band_pages(pmax, 16, 16384, 2) is None
    assert not supported(pmax, 16, 16384, 2)
    assert band_pages(6247, 16, 64, 2) is None
    assert not supported(6247, 16, 64, 2, groups=12)


def test_kernel_gate_prices_one_body_whatever_the_mask():
    """The gate prices ONE body: dense ``[G*T, W + T]`` f32 score rows,
    three sets of them, and a ``[G*T, BW]`` band tile set — whatever
    the mask kind, which is no argument of the gate any more (a
    block-diffusion forward passes its rows a slot as ``spec_t``, as
    speculative verify does). Pinned at the benchmark's block-diffusion
    cell (32 slots of 48 pages, 8 query heads over T = 4 a KV head, 4 KV
    heads of 128); the compiles that say the gate is right are
    tests/test_chip_compile.py's."""
    import inspect

    from midgpt_tpu.ops import paged_attn
    from midgpt_tpu.ops.paged_attn import (
        VMEM_BUDGET, head_block, supported, vmem_bytes,
    )

    assert not hasattr(paged_attn, "verify_contraction")
    for fn in (vmem_bytes, head_block, supported):
        assert "block" not in inspect.signature(fn).parameters
    cell = dict(groups=8, spec_t=4)
    # two bands of 8 pages of [16, 4*128] bf16; the band's view and one
    # [32, 128] f32 tile set; 4 heads x 3 x 32 rows of 768 + 128 lanes
    fetch = 2 * 8 * (16 * 512 * 2)
    band = 128 * 128 * 4 + 32 * 128 * 4
    scores = 4 * 3 * 32 * (768 + 128) * 4
    assert vmem_bytes(48, 16, 128, 2, heads=4, **cell) \
        == fetch + band + scores == 1_720_320
    assert head_block(4, 48, 16, 128, 2, **cell) == 4
    assert supported(48, 16, 128, 2, heads=4, **cell)
    # a 65k-token table of heads of 128 (64 bands of 64 pages): the
    # dense rows fit two heads a step (VMEM_BUDGET: four do not)
    assert supported(4096, 16, 128, 2, heads=4, **cell)
    assert head_block(4, 4096, 16, 128, 2, **cell) == 2
    assert vmem_bytes(4096, 16, 128, 2, heads=2, **cell) < VMEM_BUDGET
    # at 100k tokens heads of 128 have no band plan
    assert not supported(6250, 16, 128, 2, heads=4, **cell)


def test_auto_kernel_follows_the_gate_on_tpu(monkeypatch):
    """``auto`` resolves from the platform AND the geometry gate: with
    the backend forced to TPU a 64-token table takes the kernel, and a
    table no band plan fits (a prime count of 6247 pages) is served by
    the XLA gather."""
    import midgpt_tpu.utils.platform as platform

    monkeypatch.setattr(platform, "is_tpu_backend", lambda: True)
    eng_short = ServingEngine(
        _model(), slots=1, page_size=16, window=2, paged_kernel="auto"
    )
    assert eng_short.paged_kernel == "pallas"
    long_cfg = dataclasses.replace(CFG, block_size=16 * 6247)
    eng = ServingEngine(
        _model(long_cfg), slots=1, page_size=16, window=2,
        num_pages=8, paged_kernel="auto",
    )
    assert eng.paged_kernel == "xla"
    # asked for by name where it cannot compile: an error, not a
    # quiet switch to another path under the kernel's name
    with pytest.raises(ValueError, match="does not take the kernels"):
        ServingEngine(
            _model(long_cfg), slots=1, page_size=16, window=2,
            num_pages=8, paged_kernel="pallas",
        )


def test_engine_rejects_unknown_kv_quant():
    with pytest.raises(AssertionError):
        ServingEngine(_model(), slots=1, page_size=8, kv_quant="int4")


def _walk_setup(kv_quant, live_pages, nan_unowned=False):
    """A table of 64 pages (four bands of 16) whose slots hold
    ``live_pages`` pages each, every slot's pages its own; dead block-
    table entries carry the out-of-range sentinel. With ``nan_unowned``
    every page no slot holds is NaN in the returned pool, and zero in
    the second pool returned."""
    cfg = dataclasses.replace(GQA_CFG, block_size=512)
    ps, pmax, s = 8, 64, len(live_pages)
    npool = sum(live_pages) + 3
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    pool = PagedKVPool.init(cfg, npool, ps, jnp.float32, kv_quant=kv_quant)
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    pool = _random_pool(pool, ks)
    bt = np.full((s, pmax), npool, np.int32)
    nxt = 0
    for i, n in enumerate(live_pages):
        bt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    # the last live page is part-filled: 3 of its 8 rows are past the end
    pooled_len = jnp.asarray(
        [max(0, n * ps - 3) for n in live_pages], jnp.int32
    )
    tokens = jax.random.randint(ks[4], (s,), 0, cfg.vocab_size)
    zeroed = pool
    if nan_unowned:
        owned = (jnp.arange(npool) < nxt)[None, :, None, None]
        zeroed = dataclasses.replace(
            pool, k=jnp.where(owned, pool.k, 0.0),
            v=jnp.where(owned, pool.v, 0.0),
        )
        pool = dataclasses.replace(
            pool, k=jnp.where(owned, pool.k, jnp.nan),
            v=jnp.where(owned, pool.v, jnp.nan),
        )
    return cfg, model, pool, zeroed, jnp.asarray(bt), pooled_len, \
        tokens.astype(jnp.int32)


def _decode_logits(cfg, model, pool, bt, pooled_len, tokens, kern):
    l, s = cfg.n_layer, tokens.shape[0]
    rk = jnp.zeros((l, s, cfg.kv_heads, 4, cfg.head_dim), pool.row_dtype)
    rk = rk.at[:, :, :, 0, :].set(0.25)
    rv = jnp.zeros_like(rk).at[:, :, :, 0, :].set(-0.5)
    r = jnp.asarray(1, jnp.int32)
    logits, _, _ = jax.jit(
        lambda tk, pk, pv, b_, rk_, rv_, pl_, sk, sv: decode_step_paged(
            model, tk, pl_ + 1, pk, pv, b_, rk_, rv_, r, pl_,
            cfg.block_size, pool_sk=sk, pool_sv=sv, paged_kernel=kern,
        )
    )(tokens, pool.k, pool.v, bt, rk, rv, pooled_len,
      pool.scale_k, pool.scale_v)
    return np.asarray(logits, np.float32)


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["f32", "kv8"])
def test_live_page_walk_ragged_vs_xla(kv_quant):
    """The live-page walk at its corners, on a table of four bands:
    slots of 0, 1, 17 and 63 live pages — a slot the walk never
    touches, one inside its first band, one a page into its second, one
    a page short of the table's end. Whole bands are skipped, part-live
    bands zero-filled; the logits stay the XLA path's (contract 2),
    which gathers and sums every page of every slot."""
    from midgpt_tpu.ops.paged_attn import band_pages

    assert band_pages(64, 8, 8, 4) == 16
    cfg, model, pool, _, bt, pooled_len, tokens = _walk_setup(
        kv_quant, [0, 1, 17, 63]
    )
    outs = {
        kern: _decode_logits(cfg, model, pool, bt, pooled_len, tokens, kern)
        for kern in ("xla", "pallas")
    }
    _assert_gather_contract(outs["pallas"], outs["xla"])
    cand = jax.random.randint(
        jax.random.PRNGKey(9), (tokens.shape[0], 3), 0, cfg.vocab_size
    ).astype(jnp.int32)
    vouts = {}
    for kern in ("xla", "pallas"):
        logits, _, _ = jax.jit(
            lambda c_, pk, pv, b_, pl_, sk, sv: verify_tokens_paged(
                model, c_, pl_, pk, pv, b_, cfg.block_size,
                pool_sk=sk, pool_sv=sv, paged_kernel=kern,
            )
        )(cand, pool.k, pool.v, bt, pooled_len, pool.scale_k, pool.scale_v)
        vouts[kern] = np.asarray(logits, np.float32)
    _assert_gather_contract(vouts["pallas"], vouts["xla"])


def test_live_page_walk_never_reads_unowned_pages():
    """Every page no slot holds is NaN. NaN passes the additive -inf
    mask and ``0 x NaN`` passes the PV sum, so one dead page fetched or
    one buffer row left as VMEM had it would show: the kernel's logits
    are finite, and what the XLA path gives on the same pool with those
    pages zeroed (contract 2)."""
    cfg, model, pool, zeroed, bt, pooled_len, tokens = _walk_setup(
        None, [0, 1, 17, 63], nan_unowned=True
    )
    assert bool(jnp.isnan(pool.k).any())
    got = _decode_logits(cfg, model, pool, bt, pooled_len, tokens, "pallas")
    want = _decode_logits(cfg, model, zeroed, bt, pooled_len, tokens, "xla")
    _assert_gather_contract(got, want)


# ---------------------------------------------------------------------------
# the bare kernel against the gather path's arithmetic in plain f32, per
# mask kind (block forward, causal verify, decode) and pool precision
# ---------------------------------------------------------------------------


def _gather_reference(q, kc, vc, pool_k, pool_v, bt, start, layer, seen,
                      scale_k=None, scale_v=None):
    """The gather path's attention (``models.gpt._gather_attend``) in
    plain float32: every page of every table gathered, one joint
    softmax of the masked pool scores and the rows' own (``seen``
    [T, R] bool: which of the R own rows row t sees), mask before the
    scale, f32 probabilities through PV. Pages past a slot's length are
    selected away, not multiplied by zero: the pool of these tests
    holds NaN there."""
    s, hkv, g, t, c = q.shape
    w = bt.shape[1] * pool_k.shape[2]
    hi = jax.lax.Precision.HIGHEST

    def view(pool, scale):
        x = pool[layer][bt].astype(jnp.float32)   # [S, Pmax, PS, Hkv*C]
        x = x.reshape(s, bt.shape[1], -1, hkv, c)
        if scale is not None:
            x = x * scale[layer][bt][:, :, None, :, None]
        live = (jnp.arange(w) < start[:, None])[:, :, None, None]
        return jnp.where(live, x.reshape(s, w, hkv, c), 0.0)

    qf = q.astype(jnp.float32)
    s_pool = jnp.einsum(
        "shgtc,swhc->shgtw", qf, view(pool_k, scale_k), precision=hi
    ) + jnp.where(jnp.arange(w) < start[:, None], 0.0, -jnp.inf)[
        :, None, None, None, :]
    s_self = jnp.einsum(
        "shgtc,shrc->shgtr", qf, kc.astype(jnp.float32), precision=hi
    ) + jnp.where(seen, 0.0, -jnp.inf)
    probs = jax.nn.softmax(
        jnp.concatenate([s_pool, s_self], -1) / np.sqrt(c), axis=-1
    )
    return jnp.einsum(
        "shgtw,swhc->shgtc", probs[..., :w], view(pool_v, scale_v),
        precision=hi,
    ) + jnp.einsum(
        "shgtr,shrc->shgtc", probs[..., w:], vc.astype(jnp.float32),
        precision=hi,
    )


def _ragged_pool(pool, s, hkv, c, ps, pmax, layers, start, seed=17):
    """A pool of ``pmax`` pages a slot filled to ``start`` tokens a slot,
    every slot's pages its own and one page more that is nobody's — NaN
    in a float pool: a dead band computed on, or a buffer row left as
    found, would show. Returns the pool, its page scales (int8) and the
    block table, plus three spare keys."""
    pool_dt = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[pool]
    live = -(-np.asarray(start) // ps)
    npool = int(live.sum()) + 1  # the last page is nobody's: NaN
    bt = np.full((s, pmax), npool - 1, np.int32)
    nxt = 0
    for i, n in enumerate(live):
        bt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    shape = (layers, npool, ps, hkv * c)
    scale_k = scale_v = None
    if pool == "int8":
        pk, pv = (
            jax.random.randint(k_, shape, -127, 128, jnp.int32).astype(
                jnp.int8) for k_ in ks[:2]
        )
        scale_k, scale_v = (
            jnp.exp2(jax.random.randint(
                k_, (layers, npool, hkv), -8, -2).astype(jnp.float32))
            for k_ in ks[2:4]
        )
    else:
        owned = (jnp.arange(npool) < npool - 1)[None, :, None, None]
        pk, pv = (
            jnp.where(owned, jax.random.normal(k_, shape), jnp.nan).astype(
                pool_dt) for k_ in ks[:2]
        )
    return pk, pv, scale_k, scale_v, jnp.asarray(bt), ks[4:]


def _assert_reference_contract(got, want, pool):
    """Contract 2 on the bare kernel's output: ``rtol=1e-5`` over an f32
    pool; over a bf16 or int8 pool the output, rounded to bf16 once at
    the end, within one bf16 ulp of the reference's (and nearly all of
    it to the bit)."""
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    if pool == "f32":
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                                   atol=1e-5)
        return
    want = np.asarray(want.astype(jnp.bfloat16), np.float32)
    # (an element that cancels to ~1e-6 keeps the f32 sums' own error,
    # which is not small against ITS ulp: the absolute floor is theirs)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    assert (np.abs(got - want) <= ulp + 1e-6).all(), (
        np.abs(got - want).max()
    )
    assert (got == want).mean() > 0.9  # and most of it to the bit


# the mask kinds of the one body: (groups, rows a slot T, block)
_ROW_KINDS = {
    "one-block": (8, 4, 4), "two-blocks": (8, 8, 4), "causal": (2, 3, 1),
}


@pytest.mark.parametrize("rows", list(_ROW_KINDS))
@pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
def test_verify_kernel_vs_gather_reference(pool, rows):
    """The interpreted kernel's many-row program at the benchmark cells'
    head geometry in small — heads of 128, both KV heads in one grid
    step, two bands of 128 rows — under each mask kind: the block mask
    (8 query heads a KV head over T = 4 rows, and 8: two blocks, causal
    across, bidirectional inside) and the causal one (speculative
    verify: 2 x 3 = 6 rows a KV head, padded to a sublane tile), on
    ragged starts: 0, inside the first band, on the band's edge, inside
    the second, and one that fills the table. Every page past a slot's
    length is NaN."""
    from midgpt_tpu.ops.paged_attn import (
        band_pages, head_block, paged_verify_attention,
    )

    g, t, blk = _ROW_KINDS[rows]
    s, hkv, c, ps, pmax, layers = 5, 2, 128, 16, 16, 2
    dt = jnp.float32 if pool == "f32" else jnp.bfloat16
    itemsize = {"f32": 4, "bf16": 2, "int8": 1}[pool]
    assert band_pages(pmax, ps, c, itemsize) == 8  # two bands of 128 rows
    assert head_block(hkv, pmax, ps, c, itemsize, groups=g, spec_t=t) == hkv
    start = jnp.asarray([0, 12, 128, 200, pmax * ps], jnp.int32)
    pk, pv, scale_k, scale_v, bt, ks = _ragged_pool(
        pool, s, hkv, c, ps, pmax, layers, start
    )
    q = jax.random.normal(ks[0], (s, hkv, g, t, c)).astype(dt)
    kc = jax.random.normal(ks[1], (s, hkv, t, c)).astype(dt)
    vc = jax.random.normal(ks[2], (s, hkv, t, c)).astype(dt)
    gathered = tuple(
        None if sc is None else jnp.take(sc[1], bt, axis=0)
        for sc in (scale_k, scale_v)
    )
    got = paged_verify_attention(
        q, kc, vc, pk, pv, bt, start, 1, *gathered, block=blk
    )
    assert got.shape == q.shape and got.dtype == dt
    ii = jnp.arange(t) // blk
    want = _gather_reference(
        q, kc, vc, pk, pv, bt, start, 1, ii[None, :] <= ii[:, None],
        scale_k, scale_v,
    )
    _assert_reference_contract(got, want, pool)


@pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
def test_one_row_decode_vs_gather_reference(pool):
    """The decode program of an MHA model — ONE query row a head, which
    the kernel pads to a sublane tile with seven zero rows — on ragged
    lengths: an empty slot (the row sees the window's recent rows
    alone), a slot whose last band is dead, a band's edge, a part-live
    second band, a full table. The padded rows score 0 everywhere (a
    uniform softmax, never a NaN) and must not leak: the one real row
    is the reference's, and finite although every unowned page is
    NaN."""
    from midgpt_tpu.ops.paged_attn import head_block, paged_decode_attention

    s, hkv, c, ps, pmax, layers, rr, r = 5, 2, 128, 16, 16, 2, 4, 2
    dt = jnp.float32 if pool == "f32" else jnp.bfloat16
    itemsize = {"f32": 4, "bf16": 2, "int8": 1}[pool]
    assert head_block(hkv, pmax, ps, c, itemsize, groups=1) == hkv
    start = jnp.asarray([0, 12, 128, 200, pmax * ps], jnp.int32)
    pk, pv, scale_k, scale_v, bt, ks = _ragged_pool(
        pool, s, hkv, c, ps, pmax, layers, start
    )
    q = jax.random.normal(ks[0], (s, hkv, 1, c)).astype(dt)
    rk = jax.random.normal(ks[1], (s, hkv, rr, c)).astype(dt)
    rv = jax.random.normal(ks[2], (s, hkv, rr, c)).astype(dt)
    gathered = tuple(
        None if sc is None else jnp.take(sc[1], bt, axis=0)
        for sc in (scale_k, scale_v)
    )
    got = paged_decode_attention(
        q, pk, pv, bt, start, rk, rv, jnp.asarray(r, jnp.int32), 1,
        *gathered,
    )
    assert got.shape == q.shape and got.dtype == dt
    want = _gather_reference(
        q[:, :, :, None], rk, rv, pk, pv, bt, start, 1,
        (jnp.arange(rr) <= r)[None, :], scale_k, scale_v,
    )[:, :, :, 0]
    _assert_reference_contract(got, want, pool)


@pytest.mark.parametrize("pool", ["f32", "bf16", "int8"])
def test_no_mask_kind_selects_arithmetic(pool):
    """Contract 1 on the bare kernel: the last of four rows sees all
    four under the causal mask, under the block mask (one block of 4)
    and as a decode step at ``r = 3`` of a four-row recent buffer —
    equal masks, so the three programs' outputs for that token agree TO
    THE BIT: the mask kind selects what a row sees, never how it is
    summed (there is one contraction; ``verify_contraction`` is gone).
    MHA: one row a head against four, each padded to one sublane
    tile."""
    from midgpt_tpu.ops.paged_attn import (
        paged_decode_attention, paged_verify_attention,
    )

    s, hkv, c, ps, pmax, layers, t = 5, 2, 128, 16, 16, 2, 4
    dt = jnp.float32 if pool == "f32" else jnp.bfloat16
    start = jnp.asarray([0, 12, 128, 200, pmax * ps], jnp.int32)
    pk, pv, scale_k, scale_v, bt, ks = _ragged_pool(
        pool, s, hkv, c, ps, pmax, layers, start
    )
    q = jax.random.normal(ks[0], (s, hkv, 1, t, c)).astype(dt)
    kc = jax.random.normal(ks[1], (s, hkv, t, c)).astype(dt)
    vc = jax.random.normal(ks[2], (s, hkv, t, c)).astype(dt)
    gathered = tuple(
        None if sc is None else jnp.take(sc[1], bt, axis=0)
        for sc in (scale_k, scale_v)
    )
    causal, block = (
        np.asarray(paged_verify_attention(
            q, kc, vc, pk, pv, bt, start, 1, *gathered, block=blk
        )[:, :, :, t - 1], np.float32)
        for blk in (1, t)
    )
    decode = np.asarray(paged_decode_attention(
        q[:, :, :, t - 1], pk, pv, bt, start, kc, vc,
        jnp.asarray(t - 1, jnp.int32), 1, *gathered,
    ), np.float32)
    assert np.isfinite(decode).all()
    np.testing.assert_array_equal(causal, decode)
    np.testing.assert_array_equal(block, decode)


def test_prob_limbs_sum_to_the_probability_to_the_bit():
    """``prob_limbs``: ``hi + mid + lo == p`` exactly — over softmax
    outputs (sharp and flat rows), over 0, 1, 1e-30 and the neighbours
    of powers of two, jitted as the kernel runs it (a compiler that
    dropped a bf16 round trip as excess precision would show here)."""
    from midgpt_tpu.ops.paged_attn import prob_limbs

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    p = jnp.concatenate([
        jax.nn.softmax(jax.random.normal(ks[0], (64, 384)) * 4.0).ravel(),
        jax.nn.softmax(jax.random.normal(ks[1], (8, 2048)) * 0.1).ravel(),
        jax.random.uniform(ks[2], (4096,)),
        jnp.asarray([0.0, 1.0, 1e-30, 0.5, 2.0 ** -20, 1.0 - 2.0 ** -24,
                     0.5 + 2.0 ** -24, 2.0 ** -100], jnp.float32),
    ]).astype(jnp.float32)
    hi, mid, lo = jax.jit(prob_limbs)(p)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    np.testing.assert_array_equal((f32(lo) + f32(mid)) + f32(hi),
                                  np.asarray(p))
    assert (f32(mid) != 0).any() and (f32(lo) != 0).any()


def test_limb_pv_is_the_full_precision_pv():
    """``pv_limbs`` (f32 probabilities as three bf16 limbs, ONE bf16
    pass, row groups added lo, mid, hi) against the same product at
    ``Precision.HIGHEST``: the same sum in another order, ``rtol=1e-6``.
    Probabilities rounded to bf16 — the result this must NOT be — miss
    it by 1e-3."""
    from midgpt_tpu.ops.paged_attn import pv_limbs

    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    p = jax.nn.softmax(jax.random.normal(ks[0], (8, 128)) * 2.0)
    v = jax.random.normal(ks[1], (128, 128)).astype(jnp.bfloat16)
    want = jnp.dot(p, v.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    got = pv_limbs(p, v)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    rounded = jnp.dot(
        p.astype(jnp.bfloat16).astype(jnp.float32), v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    assert np.abs(np.asarray(rounded - want)).max() > 1e-4


# ---------------------------------------------------------------------------
# slow tier: sharded kernel + kv-quant
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tp2_kernel_and_kv_quant_identity():
    """tp=2 sharded serving with the Pallas kernel (shard_map-wrapped,
    per-shard ragged walk over Hkv/tp heads) and the int8 pool (scale
    planes sharded with their heads): token-identical to the single-chip
    engine, both precisions."""
    from midgpt_tpu.serving import serving_meshes

    model = _model()
    prompts = _prompts(3)
    lens = [10, 10, 10]
    mesh = serving_meshes(tp_size=2)[0]
    base = _run_engine(model, prompts, lens, paged_kernel="xla")
    tp_pal = _run_engine(
        model, prompts, lens, mesh=mesh, paged_kernel="pallas"
    )
    assert tp_pal == base
    base_q = _run_engine(model, prompts, lens, kv_quant="int8")
    tp_q = _run_engine(
        model, prompts, lens, mesh=mesh, kv_quant="int8",
        paged_kernel="pallas",
    )
    assert tp_q == base_q


@pytest.mark.slow
def test_tp4_kernel_kv_quant_spec_identity():
    """tp=4 x kernel x int8 KV x speculation — the deep end of the
    acceptance matrix in one rung."""
    from midgpt_tpu.serving import serving_meshes

    model = _model()
    prompts = _prompts(3)
    lens = [10, 10, 10]
    mesh = serving_meshes(tp_size=4)[0]
    base_q = _run_engine(
        model, prompts, lens, kv_quant="int8", speculate=4
    )
    tp_q = _run_engine(
        model, prompts, lens, mesh=mesh, kv_quant="int8",
        paged_kernel="pallas", speculate=4,
    )
    assert tp_q == base_q
