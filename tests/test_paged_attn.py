"""Pallas ragged paged-attention kernel (ops/paged_attn) + int8 KV pool
(serving.paged kv_quant): the exactness contracts that make both landable.

- The kernel is BITWISE the XLA gather path — not close, equal: the
  serving suite's greedy token-identity matrix is the landing gate, and
  ulp-level drift flips near-tied argmaxes on real checkpoints (the PR
  4/PR 5 lesson). Asserted at the op level (decode + verify, ragged
  lengths, GQA, f32 comparison of the raw logits) and end-to-end
  (engine streams across cache x chunking x speculation x eviction).
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
# kernel vs XLA path: bitwise at the op level
# ---------------------------------------------------------------------------


def _decode_setup(cfg, kv_quant=None, seed=1):
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    s, ps, pmax = 4, 8, 8
    npool = 24
    pool = PagedKVPool.init(cfg, npool, ps, jnp.float32, kv_quant=kv_quant)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    if kv_quant:
        codes = jax.random.randint(
            ks[0], pool.k.shape, -127, 128, jnp.int32
        ).astype(jnp.int8)
        pool = dataclasses.replace(
            pool, k=codes,
            v=jax.random.randint(
                ks[1], pool.v.shape, -127, 128, jnp.int32
            ).astype(jnp.int8),
            scale_k=jnp.exp2(jax.random.randint(
                ks[2], pool.scale_k.shape, -8, -2
            ).astype(jnp.float32)),
            scale_v=jnp.exp2(jax.random.randint(
                ks[3], pool.scale_v.shape, -8, -2
            ).astype(jnp.float32)),
        )
    else:
        pool = dataclasses.replace(
            pool,
            k=jax.random.normal(ks[0], pool.k.shape, jnp.float32),
            v=jax.random.normal(ks[1], pool.v.shape, jnp.float32),
        )
    bt = jax.random.randint(ks[4], (s, pmax), 0, npool).astype(jnp.int32)
    # ragged lengths: empty, partial page, page-aligned, full table
    pooled_len = jnp.asarray([0, 13, 32, pmax * ps], jnp.int32)
    tokens = jax.random.randint(ks[5], (s,), 0, cfg.vocab_size)
    return model, pool, bt, pooled_len, tokens.astype(jnp.int32)


@pytest.mark.parametrize("cfg", [CFG, GQA_CFG], ids=["mha", "gqa"])
@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["f32", "kv8"])
def test_decode_kernel_bitwise_vs_xla(cfg, kv_quant):
    """decode_step_paged with paged_kernel='pallas' returns BITWISE the
    XLA gather path's logits — ragged per-slot lengths (incl. an empty
    slot and a partial page), both pool precisions, MHA and GQA."""
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
    np.testing.assert_array_equal(outs["xla"][0], outs["pallas"][0])
    np.testing.assert_array_equal(outs["xla"][1], outs["pallas"][1])


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["f32", "kv8"])
def test_verify_kernel_bitwise_vs_xla(kv_quant):
    """verify_tokens_paged: all candidate rows, joint pool+self softmax —
    kernel bitwise against the XLA path, and the returned K/V rows (what
    the watermark flush writes) equal too."""
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
        np.testing.assert_array_equal(a, b)


# f32-nb2 (2 bands) proves the multi-band fold in tier-1; the deeper
# band counts and the int8-pool multiband cells ride the slow tier to
# keep tier-1 inside the 870 s verify budget (the serving-longctx CI
# job runs the banded legs fast + slow, and serving-choreo runs this
# file unfiltered). int8 at NB=1 stays fast via the kv8 cells of
# test_decode_kernel_bitwise_vs_xla above.
@pytest.mark.parametrize(
    "kv_quant,band_pages_",
    [
        pytest.param(None, 4, id="f32-nb2"),
        pytest.param(None, 2, id="f32-nb4", marks=pytest.mark.slow),
        pytest.param(None, 1, id="f32-nb8", marks=pytest.mark.slow),
        pytest.param("int8", 4, id="kv8-nb2", marks=pytest.mark.slow),
        pytest.param("int8", 2, id="kv8-nb4", marks=pytest.mark.slow),
        pytest.param("int8", 1, id="kv8-nb8", marks=pytest.mark.slow),
    ],
)
def test_banded_kernel_bitwise_vs_banded_xla(kv_quant, band_pages_,
                                             monkeypatch):
    """Genuinely MULTI-banded streaming (ISSUE 20): force the band plan
    below the whole table (the auto-sizer picks one band at this tiny
    geometry) and re-pin kernel == XLA to the f32 bit for decode AND
    verify. Both sides slice per band and fold partials through
    banded_fold, so this exercises the whole banded contract: per-band
    masking, per-band dequant slices, and the pinned ascending fold —
    at 8, 4, and 2 pages per band against the pmax=8 table."""
    import midgpt_tpu.ops.paged_attn as pa

    monkeypatch.setattr(pa, "_FORCE_BAND_PAGES", band_pages_)
    cfg = GQA_CFG
    model, pool, bt, pooled_len, tokens = _decode_setup(cfg, kv_quant)
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
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])
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
    np.testing.assert_array_equal(vouts["xla"], vouts["pallas"])


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
            got = (
                pool.k[li, page].astype(jnp.float32)
                * pool.scale_k[li, page][:, None, None]
            )  # [Hkv, C, PS]
            page_rows = ks[li, :, pi * ps : (pi + 1) * ps, :]  # [Hkv,PS,C]
            # dequant equals the canonical grid rounding of the written
            # rows EXACTLY (incl. the +-127 clip for rows past the birth
            # row's headroom)
            s_rows = jnp.broadcast_to(
                pool.scale_k[li, page][:, None], (cfg.kv_heads, ps)
            )
            want_grid = round_kv_rows_to_grid(page_rows, s_rows)
            np.testing.assert_array_equal(
                np.asarray(jnp.transpose(got, (0, 2, 1))),
                np.asarray(want_grid.astype(jnp.float32)),
            )
            # the BIRTH row (the scale's source) is never clipped and
            # lands within scale/2 of the original
            scale = pool.scale_k[li, page]  # [Hkv]
            birth_err = jnp.abs(got[:, :, 0] - page_rows[:, 0, :])
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
    """The gate is what the chip's compiler was seen to accept (PR 21,
    tests/test_chip_compile.py holds the compiles): every block-table
    page is a VMEM-resident block, so the working set is O(Pmax) plus
    one band's f32 compute and the flat-softmax score rows."""
    from midgpt_tpu.ops.paged_attn import supported

    # the shapes compiled for the described v5e
    assert supported(pmax=64, page_size=16, c=64, itemsize=2, groups=1)
    assert supported(pmax=64, page_size=16, c=64, itemsize=1, groups=1)
    assert supported(pmax=128, page_size=16, c=128, itemsize=2, groups=4)
    assert supported(pmax=128, page_size=16, c=128, itemsize=1, groups=4,
                     spec_t=4)
    # the page blocks scale with Pmax ...
    assert not supported(pmax=4096, page_size=16, c=64, itemsize=2,
                         groups=1)
    # ... the f32 product and the score rows with the REAL group count
    # and spec length, not a cap
    assert supported(pmax=256, page_size=16, c=64, itemsize=2, groups=12)
    assert not supported(pmax=256, page_size=16, c=64, itemsize=2,
                         groups=128)
    assert supported(pmax=512, page_size=16, c=64, itemsize=2, groups=4)
    assert not supported(pmax=512, page_size=16, c=64, itemsize=2,
                         groups=4, spec_t=8)


def test_kernel_gate_rejects_100k_token_pmax():
    """At a 100k-token context the block table spans
    ``pages_needed(100_000, 16) = 6250`` pages. PR 20's manual band DMA
    would have held O(band) of them; Mosaic refuses that DMA for
    16-wide pages, the page-block walk that does compile holds all of
    them, and the gate says no. The byte arithmetic is pinned exactly
    so a dropped term moves a literal; the band PLAN (which fixes the
    PV fold order on the XLA side too) is pinned unchanged."""
    from midgpt_tpu.ops.paged_attn import (
        BAND_VMEM_BUDGET,
        VMEM_BUDGET,
        band_pages,
        supported,
        vmem_bytes,
    )
    from midgpt_tpu.serving.paged import pages_needed

    pmax = pages_needed(100_000, 16)
    assert pmax == 6250
    w = pmax * 16  # 100_000 resident positions
    # band plan, bf16 and int8: 50 bands of 2000 positions
    assert band_pages(pmax, 16, 64, 2) == 125
    assert band_pages(pmax, 16, 64, 1) == 125
    assert 2 * 2 * 64 * 2000 * 2 + 2 * 64 * 2000 * 4 <= BAND_VMEM_BUDGET
    # bf16: [64, 16] pages pad to [64, 128] tiles, K and V, twice each
    pages_bf16 = 2 * 2 * pmax * (64 * 128 * 2)
    band_bf16 = 64 * 2000 * (2 + 4) + 12 * 64 * 2000 * 4
    scores = 2 * 12 * 8 * w * 4
    assert vmem_bytes(pmax, 16, 64, 2, groups=12) \
        == pages_bf16 + band_bf16 + scores == 493_312_000 > VMEM_BUDGET
    assert not supported(pmax, 16, 64, 2, groups=12)
    # int8: same padded page bytes per element, plus the scale rows
    pages_int8 = 2 * 2 * pmax * (64 * 128 * 1)
    band_int8 = 64 * 2000 * (1 + 4) + 12 * 64 * 2000 * 4
    assert vmem_bytes(pmax, 16, 64, 1, groups=12) \
        == pages_int8 + band_int8 + scores + 2 * 2 * 8 * w * 4 \
        == 301_184_000
    assert not supported(pmax, 16, 64, 1, groups=12)
    # no band plan, no kernel: a head dim so wide one page overflows
    # the band budget, and a prime page count whose only fitting
    # divisor needs > MAX_BANDS bands
    assert band_pages(pmax, 16, 16384, 2) is None
    assert not supported(pmax, 16, 16384, 2)
    assert band_pages(6247, 16, 64, 2) is None
    assert not supported(6247, 16, 64, 2, groups=12)


def test_auto_kernel_follows_the_gate_on_tpu(monkeypatch):
    """``auto`` resolves from the platform AND the geometry gate: with
    the backend forced to TPU a 64-token table takes the kernel, a
    100k-token one is served by the XLA gather."""
    import midgpt_tpu.utils.platform as platform

    monkeypatch.setattr(platform, "is_tpu_backend", lambda: True)
    eng_short = ServingEngine(
        _model(), slots=1, page_size=16, window=2, paged_kernel="auto"
    )
    assert eng_short.paged_kernel == "pallas"
    long_cfg = dataclasses.replace(CFG, block_size=100_000)
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
