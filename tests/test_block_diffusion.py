"""Generation by diffusion over blocks, and the dropless expert layer, through
``ServingEngine`` — held to the plain reference of the architecture
(``benchmark/reference_block.py``: float32, no kernel, no cache, no batching,
every expert computed for every row) on seeded random weights at a small size:
D 64, 8 query heads over 2 KV heads of 16 (so H C = 128 != D), 16 experts
top-4, blocks of 4 with 4 denoising steps, V 512. Logits are compared, not
sampled tokens; where tokens are compared the model is float32 and the seeds
leave no near-tie."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_block as rb
from benchmark import weights_block
from benchmark.kinds.serve_block import fill_model
from midgpt_tpu import sampling
from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import ExpertMLP, GPT, verify_tokens_paged
from midgpt_tpu.models.layers import apply_rotary, rotate_half
from midgpt_tpu.serving import ENGINE_STATS_KEYS, ServingEngine

SIZES = dict(
    n_layer=2, n_head=8, n_kv_head=2, head_width=16, n_embd=64,
    vocab_size=512, block_size=128, experts=16, experts_per_token=4,
    expert_hidden=32, expert_renorm=True, norm_eps=1e-6, rope_base=1e6,
    block_len=4, block_steps=4, mask_token=511,
)
CFG = ModelConfig(
    qk_norm=True, qk_norm_kind="rms", rope_style="half", norm_scale=True,
    mlp="experts", tie_embeddings=False, attn_impl="naive", remat="none",
    **SIZES,
)
B = SIZES["block_len"]
NEW = 14  # not a multiple of the block: the last block is cut
# P mod B = 1, 0, 2, 3, 3; one prompt shorter than a block; two chunks
PROMPT_LENS = (9, 16, 22, 3, 35)


@pytest.fixture(scope="module")
def weights():
    return weights_block.make(jax.random.PRNGKey(3), SIZES, jnp.float32)


@pytest.fixture(scope="module")
def model(weights):
    return fill_model(weights, CFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 510, size=n).astype(np.int32) for n in PROMPT_LENS]


@pytest.fixture(scope="module")
def forward():
    return rb.make_forward(SIZES)


@pytest.fixture(scope="module")
def reference(weights, prompts, forward):
    """The published loop's tokens, reveal steps and per-forward log."""
    return [rb.generate(weights, p, NEW, SIZES, forward=forward)
            for p in prompts]


def engine(model, **kw):
    kw = {"slots": 3, "page_size": 16, "window": 5, "prefill_chunk": 16,
          "cache_dtype": jnp.float32, "paged_kernel": "xla", **kw}
    return ServingEngine(model, **kw)


@pytest.fixture(scope="module")
def served(model, prompts):
    eng = engine(model)
    rids = [eng.submit(p, NEW) for p in prompts]
    eng.run()
    return eng, [eng.finished[r] for r in rids]


# -- (b), (e): reveal order and final tokens, every P mod B ----------------


@pytest.mark.parametrize("i", range(len(PROMPT_LENS)))
def test_tokens_and_reveal_steps_equal_the_published_loop(served, reference, i):
    _, reqs = served
    toks, steps, _ = reference[i]
    assert reqs[i].tokens == list(toks), PROMPT_LENS[i] % B
    assert reqs[i].reveal_steps == list(steps)
    assert len(reqs[i].tokens) == NEW


def test_counters_count_blocks_not_steps(served):
    eng, reqs = served
    st = eng.stats()
    assert tuple(st) == ENGINE_STATS_KEYS
    # a block costs one forward a position to reveal and no other: its
    # commit rides with the next block's first forward, and the block that
    # ends a request is not committed
    blocks = [-(-(p + NEW) // B) - p // B for p in PROMPT_LENS]
    revealed = [b * B - p % B for b, p in zip(blocks, PROMPT_LENS)]
    assert st["tokens_revealed"] == sum(revealed) == st["denoise_forwards"]
    assert st["commit_forwards"] == st["blocks_committed"] == sum(
        b - 1 for b in blocks)
    assert st["tokens_generated"] == NEW * len(PROMPT_LENS)
    # of the 2 B rows a slot-forward runs, its own block's are live, and
    # the other half where a block lands; only live rows claim experts
    assert st["block_rows_run"] == 2 * B * st["denoise_forwards"]
    assert st["block_rows_live"] == B * (
        st["denoise_forwards"] + st["commit_forwards"])
    assert 0.5 < st["block_rows_live"] / st["block_rows_run"] < 1
    assert st["expert_rows_dropped"] == 0
    assert st["expert_rows_routed"] == (
        st["block_rows_live"] * SIZES["experts_per_token"] * SIZES["n_layer"])
    assert eng.expert_rows.sum() == st["expert_rows_routed"]
    assert 0 < st["experts_touched"] <= SIZES["experts"] * st[
        "expert_layer_forwards"]


def test_block_window_keeps_a_one_chunk_budget(served):
    """A block window's ``window`` counts forwards, not token steps, so with
    ``prefill_chunk`` and no ``prefill_budget`` the budget stays one chunk
    (a decode window's is one chunk a step); a step that ran any chunk
    counts once in ``prefill_steps``."""
    eng, _ = served
    assert eng.prefill_budget == eng.prefill_chunk == 16
    st = eng.stats()
    assert 0 < st["prefill_steps"] <= st["prefill_dispatches"]


# -- (a), (c): logits of every denoising step, and the K/V committed --------


def _pool_rows(eng, s, n):
    """Slot ``s``'s first ``n`` resident rows of K and V, [L, n, Hkv, C]."""
    pages = eng.bt[s, : -(-n // eng.page_size)]
    out = []
    for a in (eng.pool.k, eng.pool.v):
        rows = np.asarray(a)[:, pages].reshape(a.shape[0], -1, a.shape[-1])
        out.append(rows[:, :n].reshape(
            a.shape[0], n, SIZES["n_kv_head"], SIZES["head_width"]))
    return out


@pytest.mark.parametrize("i", (0, 2))
def test_every_denoising_forward_and_the_committed_kv(
        model, weights, prompts, reference, forward, i):
    """One request, a window of ONE forward: before each engine step the
    slot's state goes through the program's own forward
    (``verify_tokens_paged`` under the block mask, over the engine's pool:
    the rows the window lays out, the pending block's final tokens in front
    of the current block where one is pending) and the current block's
    logits are held to the reference's at that (block, step); after the
    run's last commit the pool's rows are held to the K/V of ONE full
    reference forward under M over the final sequence."""
    eng = engine(model, slots=1, window=1)
    eng.submit(prompts[i], NEW)
    log = iter(reference[i][2])
    fwd = jax.jit(lambda m, t, st, pk, pv, bt, hb: verify_tokens_paged(
        m, t, st, pk, pv, bt, CFG.block_size, block_len=B, head_block=hb)[0])
    real, seen = eng._window_fn, []

    def window(m, pool, bt, pooled_len, done, emitted, budget, eos, tok,
               rev, at, pend, pend_tok):
        if not bool(done[0]):  # every forward of a slot in flight denoises
            assert not bool(rev[0].all())
            blk, step, want, masked, _ = next(log)
            assert blk * B == int(pooled_len[0]) + B * int(pend[0])
            assert (masked == ~np.asarray(rev[0])).all()
            rows = jnp.concatenate(
                (pend_tok, tok) if bool(pend[0])
                else (tok, jnp.full_like(tok, CFG.mask_token)), axis=1)
            got = fwd(m, rows, pooled_len, pool.k, pool.v, bt,
                      pend.astype(jnp.int32))[0]
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=2e-4, atol=2e-4)
            seen.append((blk, step))
        return real(m, pool, bt, pooled_len, done, emitted, budget, eos, tok,
                    rev, at, pend, pend_tok)

    eng._window_fn = window
    kv = None
    while eng.has_work:
        if eng.slot_req[0] is not None:
            n = int(eng.pooled_len[0])
            kv = (n, _pool_rows(eng, 0, n))
        eng.step()
    seen = len(seen)
    assert seen == len(reference[i][2]) and next(log, None) is None
    n, (k_got, v_got) = kv
    p = len(prompts[i])
    assert n == (p + NEW - 1) // B * B  # all but the block that ended it
    seq = np.concatenate([prompts[i], reference[i][0]])[:n].astype(np.int32)
    _, ks, vs = forward(weights, jnp.asarray(seq), jnp.arange(n),
                        jnp.asarray(rb.block_mask(n, B)))
    for got, want in ((k_got, ks), (v_got, vs)):
        np.testing.assert_allclose(
            got, np.transpose(np.asarray(want), (0, 2, 1, 3)),
            rtol=2e-4, atol=2e-4)


# -- the fused forward: a commit and the next block's first step at once ----


@pytest.mark.parametrize("kernel", ("xla", "pallas"))
def test_fused_forward_is_the_commit_then_the_denoising_forward(
        model, request, kernel):
    """``[block n final | block n+1 masked]`` at T = 2B against the two
    forwards of the published loop (commit at T = B, the rows flushed, then
    the denoising forward at T = B over the longer context): the second
    half's logits and the first half's K/V. And a dead second half (mask
    tokens that claim no expert) leaves the first half's logits and K/V
    what a forward of the block alone gives."""
    from midgpt_tpu.serving import PagedKVPool
    from midgpt_tpu.serving.paged import flush_recent

    if kernel == "pallas":
        request.getfixturevalue("pallas_interpret")
    s, ps = 4, 16
    pmax = CFG.block_size // ps
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    pool = PagedKVPool.init(CFG, s * pmax, ps, jnp.float32)
    pool = dataclasses.replace(
        pool, k=jax.random.normal(ks[0], pool.k.shape),
        v=jax.random.normal(ks[1], pool.v.shape))
    bt = jax.random.permutation(ks[2], s * pmax).reshape(s, pmax).astype(
        jnp.int32)
    start = jnp.asarray([0, 12, 32, 100], jnp.int32)
    final = jax.random.randint(ks[3], (s, B), 0, 510, jnp.int32)
    masked = jnp.full((s, B), CFG.mask_token, jnp.int32).at[:, 1].set(7)

    @functools.partial(jax.jit, static_argnames="expert_rows")
    def fwd(tokens, start, pool, **kw):
        return verify_tokens_paged(
            model, tokens, start, pool.k, pool.v, bt, CFG.block_size,
            paged_kernel=kernel, block_len=B, **kw)

    _, k1, v1 = fwd(final, start, pool)  # the commit forward
    landed = flush_recent(pool, k1, v1, bt, start, jnp.ones((s, B), bool))
    want, _, _ = fwd(masked, start + B, landed)
    got, k2, v2 = fwd(
        jnp.concatenate((final, masked), axis=1), start, pool,
        live=jnp.ones((s, 2 * B), bool), head_block=jnp.ones((s,), jnp.int32))
    assert got.shape == want.shape == (s, B, CFG.vocab_size)
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
    for a, b in ((k2, k1), (v2, v1)):
        np.testing.assert_allclose(
            np.asarray(a[:, :, :, :B]), np.asarray(b), **tol)
    # nothing to commit: [block | dead rows]
    alone, k3, v3 = fwd(masked, start, pool)
    half = jnp.arange(2 * B)[None, :] < B
    got, k4, v4 = fwd(
        jnp.concatenate((masked, jnp.full_like(masked, CFG.mask_token)), 1),
        start, pool, live=jnp.broadcast_to(half, (s, 2 * B)),
        head_block=jnp.zeros((s,), jnp.int32), expert_rows=True)[:3]
    np.testing.assert_allclose(np.asarray(got), np.asarray(alone), **tol)
    for a, b in ((k4, k3), (v4, v3)):
        np.testing.assert_allclose(
            np.asarray(a[:, :, :, :B]), np.asarray(b), **tol)
        assert np.isfinite(np.asarray(a)).all()  # dead rows: unread, finite


def test_dead_rows_claim_no_expert(weights):
    """``ExpertMLP(live=)``: a dead row is in no group, counts in no
    ``rows`` and gets 0; the live rows get what they get without it."""
    mlp = jax.tree.map(lambda a: a[0], fill_model(weights, CFG).blocks.mlp)
    h = jnp.asarray(np.random.default_rng(4).normal(size=(3, 8, 64)),
                    jnp.float32)
    live = jnp.asarray(np.random.default_rng(5).random((3, 8)) < 0.6)
    y, _, rows = mlp(h, return_rows=True, live=live)
    want, _, every = mlp(h, return_rows=True)
    n = int(live.sum())
    assert 0 < n < 24 and int(rows.sum()) == n * SIZES["experts_per_token"]
    assert int(every.sum()) == 24 * SIZES["experts_per_token"]
    np.testing.assert_allclose(
        np.asarray(y)[np.asarray(live)], np.asarray(want)[np.asarray(live)],
        rtol=1e-6, atol=1e-6)
    assert (np.asarray(y)[~np.asarray(live)] == 0).all()
    alone, _, rows_alone = mlp(h[live], return_rows=True)
    assert (np.asarray(rows_alone) == np.asarray(rows)).all()
    np.testing.assert_allclose(
        np.asarray(alone), np.asarray(y)[np.asarray(live)],
        rtol=1e-6, atol=1e-6)


# -- a pending block and the scheduler --------------------------------------


@pytest.mark.parametrize("how", ("evicted", "eos", "budget"))
def test_a_pending_block_does_not_outlive_its_slot(
        model, prompts, reference, how):
    """A slot evicted while its last complete block is still to land, a
    request that EOS ends inside a block, one that its budget ends: the
    tokens (and reveal steps) of an undisturbed run, and no pending state
    left in any slot."""
    toks, steps, _ = reference[2]
    toks, steps = list(toks), list(steps)
    eos = None
    if how == "eos":
        eos = int(toks[9])  # inside the third generated block
        cut = toks.index(eos) + 1
        toks, steps = toks[:cut], steps[:cut]
    eng = engine(model, slots=2, window=1 if how == "evicted" else 5)
    rid = eng.submit(prompts[2], NEW, eos_id=eos)
    other = eng.submit(prompts[0], NEW)
    evicted = 0
    while eng.has_work:
        eng.step()
        s = next((s for s in range(eng.slots) if eng.slot_req[s] is not None
                  and eng.slot_req[s].rid == rid), None)
        if how == "evicted" and s is not None and eng.blk_pend[s] and (
                evicted < 2):
            eng._evict(s)  # the pending block's tokens are the request's
            evicted += 1
            assert not eng.blk_pend[s]
    assert evicted == (2 if how == "evicted" else 0)
    assert eng.finished[rid].tokens == toks
    assert eng.finished[rid].reveal_steps == steps
    assert eng.finished[other].tokens == list(reference[0][0])
    assert not eng.blk_pend.any()
    st = eng.stats()
    assert st["commit_forwards"] == st["blocks_committed"]
    assert st["expert_rows_dropped"] == 0


def test_prefix_cache_reuses_whole_pages_only(model, prompts, reference):
    """A second request with the same prompt finds its whole pages resident
    (a page is four blocks); what a partial page would add is not taken: a
    block's K/V are those of ALL its final tokens."""
    eng = engine(model, slots=1)
    first = eng.submit(prompts[4], NEW)
    eng.run()
    again = eng.submit(prompts[4], NEW)
    eng.run()
    assert eng.finished[again].cached_tokens == 32  # of 35: two pages
    assert eng.finished[again].tokens == eng.finished[first].tokens == list(
        reference[4][0])


def test_eos_ends_a_request_inside_a_block(model, prompts, reference):
    toks = list(reference[0][0])
    eos = toks[6]
    cut = toks.index(eos) + 1
    eng = engine(model, slots=1)
    rid = eng.submit(prompts[0], NEW, eos_id=int(eos))
    eng.run()
    assert eng.finished[rid].tokens == toks[:cut]
    assert len(eng.finished[rid].reveal_steps) == cut


def test_what_block_diffusion_does_not_compose_with_is_refused(model):
    with pytest.raises(ValueError, match="temperature"):
        engine(model, temperature=0.7)
    with pytest.raises(ValueError, match="speculate"):
        engine(model, speculate=2)
    with pytest.raises(AssertionError, match="whole blocks"):
        engine(model, prefill_chunk=6)


# -- (f): the Pallas verify body under mask M -------------------------------


@pytest.mark.parametrize("block_len", (4, 0))
def test_pallas_verify_body_under_the_block_mask(
        model, pallas_interpret, block_len):
    """The interpreted kernel against the XLA gather path, ragged lengths,
    T = 8 rows a slot (two blocks: causal across, bidirectional inside) —
    and, with ``block_len=0``, the causal mask both take by default."""
    from midgpt_tpu.serving import PagedKVPool

    s, ps, t = 4, 16, 8
    pmax = CFG.block_size // ps
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    pool = PagedKVPool.init(CFG, 2 * pmax, ps, jnp.float32)
    pool = dataclasses.replace(
        pool, k=jax.random.normal(ks[0], pool.k.shape),
        v=jax.random.normal(ks[1], pool.v.shape))
    bt = jax.random.randint(ks[2], (s, pmax), 0, 2 * pmax).astype(jnp.int32)
    start = jnp.asarray([0, 12, 32, 100], jnp.int32)
    cand = jax.random.randint(ks[3], (s, t), 0, 510, jnp.int32)
    out = {
        kernel: verify_tokens_paged(
            model, cand, start, pool.k, pool.v, bt, CFG.block_size,
            paged_kernel=kernel, block_len=block_len)
        for kernel in ("pallas", "xla")
    }
    for got, want in zip(out["pallas"], out["xla"]):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    causal = verify_tokens_paged(
        model, cand, start, pool.k, pool.v, bt, CFG.block_size,
        paged_kernel="xla")[0]
    same = np.allclose(np.asarray(causal), np.asarray(out["xla"][0]),
                       rtol=1e-3, atol=1e-3)
    assert same == (block_len == 0)  # the block mask is another mask


@pytest.mark.parametrize("pool_kind", ("bf16", "int8"))
def test_pallas_block_forward_at_the_cells_head_geometry(
        pallas_interpret, pool_kind):
    """The whole forward at the benchmark cell's head geometry in small (8
    query heads a KV head, heads of 128, both KV heads in one grid step),
    a bf16 model over a bf16 and over an int8 pool, T = 8 rows (two blocks)
    on ragged starts that include 0 and a full table: the kernel's
    contraction on the matrix unit against the gather path's VPU sums.
    Both multiply bf16 exactly and sum in f32, in another order: the
    attention output differs by a bf16 ulp here and there, the logits by
    what a layer makes of that (the kernel alone is held to the ulp in
    ``tests/test_paged_attn.py``)."""
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving import PagedKVPool

    cfg = dataclasses.replace(
        CFG, n_layer=1, n_head=16, n_kv_head=2, head_width=128,
        block_size=128, mlp="gelu", vocab_size=128,
    )
    model = cast_floating(GPT.init(jax.random.PRNGKey(2), cfg), jnp.bfloat16)
    s, ps, t = 3, 16, 8
    pmax = cfg.block_size // ps
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    quant = "int8" if pool_kind == "int8" else None
    pool = PagedKVPool.init(cfg, 2 * pmax, ps, jnp.bfloat16, kv_quant=quant)
    if quant:
        pool = dataclasses.replace(
            pool,
            k=jax.random.randint(ks[0], pool.k.shape, -127, 128).astype(
                jnp.int8),
            v=jax.random.randint(ks[1], pool.v.shape, -127, 128).astype(
                jnp.int8),
            scale_k=jnp.exp2(jax.random.randint(
                ks[2], pool.scale_k.shape, -8, -5).astype(jnp.float32)),
            scale_v=jnp.exp2(jax.random.randint(
                ks[3], pool.scale_v.shape, -8, -5).astype(jnp.float32)),
        )
    else:
        pool = dataclasses.replace(
            pool, k=jax.random.normal(ks[0], pool.k.shape, jnp.bfloat16),
            v=jax.random.normal(ks[1], pool.v.shape, jnp.bfloat16))
    bt = jax.random.randint(ks[4], (s, pmax), 0, 2 * pmax).astype(jnp.int32)
    start = jnp.asarray([0, 12, pmax * ps - t], jnp.int32)
    cand = jax.random.randint(ks[5], (s, t), 0, 126, jnp.int32)
    out = {
        kernel: np.asarray(verify_tokens_paged(
            model, cand, start, pool.k, pool.v, bt, cfg.block_size,
            pool_sk=pool.scale_k, pool_sv=pool.scale_v,
            paged_kernel=kernel, block_len=B)[0], np.float32)
        for kernel in ("pallas", "xla")
    }
    assert np.isfinite(out["pallas"]).all()
    err = np.abs(out["pallas"] - out["xla"]).max() / np.abs(out["xla"]).max()
    assert err < 2e-2, err  # chip_smoke.py's KERNEL_TOL


def test_no_mask_kind_selects_the_kernels_arithmetic(model, pallas_interpret):
    """There is ONE contraction: neither the engine nor the kernel module
    names a choice any more, and the block model's window and a causal
    speculative engine are built on the same body. One block of rows under
    the block mask and the same rows under the causal mask differ in what a
    row SEES; the block's last row sees all of it under both, and (one
    layer: deeper, the rows it sees have themselves seen other things) its
    logits agree to the bit."""
    from midgpt_tpu.ops import paged_attn
    from midgpt_tpu.serving import PagedKVPool

    assert not hasattr(paged_attn, "verify_contraction")
    for eng in (engine(model), engine(model, paged_kernel="pallas")):
        assert not hasattr(eng, "verify_contraction")
    cfg = dataclasses.replace(CFG, n_layer=1, mlp="gelu")
    plain = dataclasses.replace(cfg, block_len=0, block_steps=0, mask_token=-1)
    spec = ServingEngine(GPT.init(jax.random.PRNGKey(0), plain), slots=2,
                         page_size=16, speculate=3, cache_dtype=jnp.float32,
                         paged_kernel="pallas")
    assert spec.paged_kernel == "pallas"
    one = GPT.init(jax.random.PRNGKey(2), cfg)
    pool = PagedKVPool.init(cfg, 8, 16, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    pool = dataclasses.replace(
        pool, k=jax.random.normal(ks[0], pool.k.shape),
        v=jax.random.normal(ks[1], pool.v.shape))
    tok = jax.random.randint(ks[2], (2, B), 0, 510).astype(jnp.int32)
    bt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    start = jnp.asarray([0, 21], jnp.int32)
    last = {
        blk: np.asarray(verify_tokens_paged(
            one, tok, start, pool.k, pool.v, bt, cfg.block_size,
            paged_kernel="pallas", block_len=blk)[0], np.float32)[:, B - 1]
        for blk in (B, 0)
    }
    assert np.isfinite(last[B]).all()
    np.testing.assert_array_equal(last[B], last[0])
    first = np.asarray(verify_tokens_paged(
        one, tok, start, pool.k, pool.v, bt, cfg.block_size,
        paged_kernel="pallas", block_len=B)[0], np.float32)[:, 0]
    causal_first = np.asarray(verify_tokens_paged(
        one, tok, start, pool.k, pool.v, bt, cfg.block_size,
        paged_kernel="pallas")[0], np.float32)[:, 0]
    assert not np.allclose(first, causal_first, atol=1e-4)  # another mask


# -- (d): the expert layer against the loop over experts --------------------


def _layer_weights(weights, layer=0):
    return {k: np.asarray(weights[k][layer]) for k in ("router", "w13", "w2")}


def _reference_experts(h, lw, top_k):
    lw = {k: jnp.asarray(v) for k, v in lw.items()}
    return np.asarray(rb._experts(jnp.asarray(h), lw, top_k, True, None))


@pytest.mark.parametrize("routing", ("uniform", "skewed"))
def test_expert_layer_equals_the_loop_over_experts(weights, routing):
    """Under the seed's routing, and with every row sent to the SAME two
    experts of 16 (top-2): no row is dropped at any skew, and the sorted
    grouped matmul gives what computing every expert for every row gives."""
    lw = _layer_weights(weights)
    top_k = 4
    if routing == "skewed":
        top_k = 2
        bias = np.zeros((64, 16), np.float32)
        bias[:, [3, 7]] = 1.0  # |h . 1| dominates: rows pick 3 and 7
        lw["router"] = lw["router"] * 1e-3 + bias
    rng = np.random.default_rng(1)
    h = np.abs(rng.normal(size=(2, 24, 64))).astype(np.float32)
    mlp = ExpertMLP(
        router=dataclasses.replace(
            ExpertMLP.init(jax.random.PRNGKey(0), CFG).router,
            weight=jnp.asarray(lw["router"])),
        w_in=jnp.asarray(lw["w13"]), w_out=jnp.asarray(lw["w2"]),
        top_k=top_k, renorm=True)
    y, aux, rows = mlp(jnp.asarray(h), return_rows=True)
    assert int(rows.sum()) == 48 * top_k  # zero dropped
    if routing == "skewed":
        assert rows[3] == rows[7] == 48 and int(rows.sum()) == 96
    want = _reference_experts(h.reshape(-1, 64), lw, top_k).reshape(h.shape)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)
    assert np.isfinite(float(aux))


def test_expert_layer_reads_the_stack_as_it_lies(weights):
    """``stacked``: every layer's experts as L x E groups, only this
    layer's with rows — the same result as the layer's own slice."""
    h = jnp.asarray(np.random.default_rng(2).normal(size=(5, 64)), jnp.float32)
    mlp = fill_model(weights, CFG).blocks.mlp  # stacked [L, ...] leaves
    for layer in range(SIZES["n_layer"]):
        own = jax.tree.map(lambda a: a[layer], mlp)
        want = own(h, return_rows=True)
        got = own(h, return_rows=True,
                  stacked=(mlp.w_in, mlp.w_out, jnp.int32(layer)))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-6, atol=1e-6)
        assert (np.asarray(got[2]) == np.asarray(want[2])).all()


def test_grouped_matmul_kernel_equals_ragged_dot():
    """``ops.grouped``: the Pallas grouped matmul a TPU takes (interpreted
    here), against ``ragged_dot``, with groups that have no rows — the
    layer stack read as L x E groups leaves most of them empty."""
    from midgpt_tpu.ops import grouped

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    xs = jax.random.normal(ks[0], (256, 256), jnp.float32)
    w = jax.random.normal(ks[1], (12, 256, 384), jnp.float32)
    sizes = jnp.asarray([0, 0, 0, 0, 0, 0, 100, 0, 28, 1, 0, 127], jnp.int32)
    assert grouped.tiling(256, 256, 384) == (128, 256, 128)
    assert grouped.tiling(1024, 2048, 1536) == (128, 2048, 512)
    assert grouped.tiling(1024, 768, 2048) == (128, 768, 2048)
    assert grouped.tiling(100, 256, 384) is None
    got = grouped.grouped_matmul(xs, w, sizes, interpret=True)
    want = jax.lax.ragged_dot(xs, w, sizes)  # what the CPU takes
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_expert_layer_trains(model):
    """A ``train()``-shaped call: [B, T, D] through the same body, with
    gradients into the router and both expert tensors."""
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 510)
    grads = jax.grad(
        lambda m: jnp.sum(m(tok).astype(jnp.float32) ** 2))(model)
    for leaf in (grads.blocks.mlp.w_in, grads.blocks.mlp.w_out,
                 grads.blocks.mlp.router.weight):
        assert np.isfinite(np.asarray(leaf)).all()
        assert float(jnp.abs(leaf).sum()) > 0.0


def test_block_window_mirrors_the_verify_program_op_for_op():
    """The choreography prover's verify-equals-decode clause, adapted: a
    block-diffusion engine has no token-at-a-time window, so its block
    window is held to the verify program of the same model at T = B."""
    from midgpt_tpu.analysis.harness import prove_block_choreography

    report = prove_block_choreography(CFG)
    names = [c.name for c in report.checks]
    assert "block-window-mirrors-verify" in names
    assert report.ok, [(c.name, c.detail) for c in report.checks if not c.ok]


# -- the pieces ------------------------------------------------------------


def test_half_split_rope_is_rotate_half():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 16))
    ang = jax.random.normal(jax.random.PRNGKey(1), (5, 8))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    want = x * jnp.concatenate((cos, cos), -1) + rotate_half(x) * (
        jnp.concatenate((sin, sin), -1))
    np.testing.assert_allclose(
        np.asarray(apply_rotary(x, sin, cos, "half")), np.asarray(want),
        rtol=1e-6, atol=1e-6)
    assert not np.allclose(np.asarray(apply_rotary(x, sin, cos)),
                           np.asarray(want), atol=1e-3)


def test_confidence_and_reveal_rule():
    logits = jnp.asarray([[[0.0, 2.0, 1.0], [3.0, 0.0, 0.0],
                           [0.5, 0.5, 0.4], [0.0, 0.0, 9.0]]])
    pick, conf = sampling.token_confidence(logits)
    assert pick.tolist() == [[1, 0, 0, 2]]
    np.testing.assert_allclose(
        np.asarray(conf),
        np.asarray(jnp.max(jax.nn.softmax(logits, -1), -1)), rtol=1e-6)
    masked = jnp.asarray([[True, True, True, False]])
    # the surest position is revealed already: of the masked, 1 then 0
    one = sampling.reveal_most_confident(conf, masked, 1)
    two = sampling.reveal_most_confident(conf, masked, 2)
    assert one.tolist() == [[False, True, False, False]]
    assert two.tolist() == [[True, True, False, False]]
    few = sampling.reveal_most_confident(
        conf, jnp.asarray([[False, False, True, False]]), 2)
    assert few.tolist() == [[False, False, True, False]]


def test_existing_configs_are_what_they_were():
    """The new fields' defaults change nothing: no new leaf, the head width
    D // H, LayerNorm on q and k, weightless block norms."""
    from midgpt_tpu.models.layers import LayerNorm
    from midgpt_tpu.pytree import tree_paths

    cfg = ModelConfig(block_size=32, vocab_size=64, n_layer=1, n_head=2,
                      n_embd=32)
    m = GPT.init(jax.random.PRNGKey(0), cfg)
    assert cfg.head_dim == 16 and cfg.block_len == 0
    assert [p for p, _ in tree_paths(m)] == [
        "wte/weight", "blocks/attn/wqkv/weight", "blocks/attn/wo/weight",
        "blocks/attn/q_norm/weight", "blocks/attn/k_norm/weight",
        "blocks/mlp/w_up/weight", "blocks/mlp/w_down/weight",
        "lm_head/weight"]
    assert isinstance(m.blocks.attn.q_norm, LayerNorm)
    assert m.ln_f.eps == 1e-5 and m.blocks.ln1.eps == 1e-6
