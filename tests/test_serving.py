"""Serving engine (midgpt_tpu.serving): page-allocator invariants, paged
decode parity against the exact sampler, fused K-step window vs K=1
(including EOS inside a window), scheduler admit/evict behavior under
scripted traces, prefix-cache/chunked-prefill exactness, and
self-speculative decoding (n-gram drafting + single-dispatch
verification: token identity vs spec-off, dispatch accounting, and
watermark-rollback invariants under forced full rejection). Beyond the
reference (its sampler is fixed-batch, full-re-forward per token,
sample.py:68-95)."""

import dataclasses
import re
import zlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import (
    GPT,
    KVCache,
    decode_step,
    decode_step_paged,
    prefill,
)
from midgpt_tpu.sampling import generate
from midgpt_tpu.serving import (
    PageAllocator,
    PagedKVPool,
    PrefixIndex,
    ServingEngine,
    flush_recent,
    generate_served,
    pages_needed,
    write_prompt_pages,
)
from midgpt_tpu.serving.engine import _REFUSED

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)


def _model():
    return GPT.init(jax.random.PRNGKey(0), CFG)


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


def _exact(model, prompt, n_new):
    """The existing exact sampler, greedy, per request."""
    return np.asarray(
        generate(
            model, jnp.asarray(prompt)[None], n_new,
            key=jax.random.PRNGKey(9), temperature=0.0,
            cache_dtype=jnp.float32,
        )
    )[0]


@pytest.fixture(scope="module")
def shared_prefix_case():
    """Shared-prefix trace + exact-sampler refs, computed once: the
    prefix-cache/chunking identity test and the speculative identity
    matrix drive the same requests (each _exact call compiles its own
    sampler, so recomputing per test is pure wall-clock)."""
    model = _model()
    sys_prompt = _prompts(1, base_len=18)[0]
    tails = _prompts(4, base_len=3, stride=2)
    prompts = [np.concatenate([sys_prompt, t]) for t in tails]
    lens = [9, 12, 7, 10]
    refs = [_exact(model, p, n) for p, n in zip(prompts, lens)]
    return model, prompts, lens, refs


@pytest.fixture(scope="module")
def eviction_case():
    """Equal-length eviction-pressure trace + refs at the two generation
    lengths the eviction tests use (16 and 24), computed once."""
    model = _model()
    prompts = _prompts(4, base_len=6, stride=0)
    refs16 = [_exact(model, p, 16) for p in prompts]
    refs24 = [_exact(model, p, 24) for p in prompts]
    return model, prompts, refs16, refs24


# ---------------------------------------------------------------------------
# Page allocator invariants
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_roundtrip():
    a = PageAllocator(8)
    p1 = a.alloc(3)
    p2 = a.alloc(5)
    a.check()
    assert a.free_pages == 0 and a.held_pages == 8
    assert len(set(p1) | set(p2)) == 8, "pages must be unique across owners"
    a.free(p1)
    a.check()
    assert a.free_pages == 3
    p3 = a.alloc(2)
    a.check()
    assert not set(p3) & set(p2), "freed-then-realloc'd pages stay disjoint"


def test_allocator_exhaustion_and_double_free():
    a = PageAllocator(4)
    held = a.alloc(4)
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(held[:2])
    with pytest.raises(ValueError):
        a.free(held[:1])  # double free
    with pytest.raises(ValueError):
        a.free([99])  # foreign page
    a.check()


def test_allocator_fragmentation_reuse():
    """Interleaved alloc/free must never lose pages: after any sequence,
    free + held == num_pages and a full-pool alloc succeeds once all owners
    release."""
    a = PageAllocator(16)
    owners = [a.alloc(n) for n in (2, 3, 4, 7)]  # pool exactly full
    a.check()
    a.free(owners[1])
    a.free(owners[3])
    a.check()
    b = a.alloc(10)  # exactly the freed count
    a.check()
    assert a.free_pages == 0
    a.free(owners[0] + owners[2] + b)
    a.check()
    assert len(a.alloc(16)) == 16  # nothing leaked


def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(64, 16) == 4


# ---------------------------------------------------------------------------
# Paged decode parity (logits + tokens) vs the exact sampler / oracle
# ---------------------------------------------------------------------------


def test_paged_decode_logits_match_decode_step_oracle():
    """Teacher-forced: decode_step_paged against the per-token decode_step
    ring oracle at every position, across page boundaries."""
    model = _model()
    p, n_steps, ps = 5, 13, 4  # crosses several page boundaries
    total = p + n_steps
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (1, total), 0, CFG.vocab_size
    )

    cache = KVCache.init(CFG, 1, total, dtype=jnp.float32)
    _, cache = prefill(model, tokens[:, :p], cache)
    oracle = []
    for t in range(p, total):
        lo, cache = decode_step(
            model, tokens[:, t], jnp.asarray(t, jnp.int32), cache,
            rope_len=CFG.block_size,
        )
        oracle.append(np.asarray(lo))

    pmax = pages_needed(CFG.block_size, ps)
    pool = PagedKVPool.init(CFG, pmax, ps, dtype=jnp.float32)
    pad = pages_needed(p, ps) * ps
    h, (ks, vs) = model.hidden(
        jnp.pad(tokens[:, :p], ((0, 0), (0, pad - p))), return_kv=True
    )
    rows = np.full((pad // ps,), pool.num_pages, np.int32)
    rows[: pages_needed(p, ps)] = np.arange(pages_needed(p, ps))
    pool = write_prompt_pages(pool, ks[:, 0], vs[:, 0], jnp.asarray(rows))

    bt = np.full((1, pmax), pool.num_pages, np.int32)
    bt[0, :pmax] = np.arange(pmax)  # identity block table
    bt = jnp.asarray(bt)
    got = []
    base = p
    window = 4
    while base < total:
        k_eff = min(window, total - base)
        rshape = (CFG.n_layer, 1, CFG.kv_heads, window, CFG.head_dim)
        rk = jnp.zeros(rshape, jnp.float32)
        rv = jnp.zeros(rshape, jnp.float32)
        pooled = jnp.asarray([base], jnp.int32)
        for r in range(k_eff):
            t = base + r
            lg, rk, rv = decode_step_paged(
                model, tokens[:, t], jnp.asarray([t], jnp.int32),
                pool.k, pool.v, bt, rk, rv, jnp.asarray(r, jnp.int32),
                pooled, CFG.block_size,
            )
            got.append(np.asarray(lg))
        valid = jnp.ones((1, window), bool) & (
            jnp.arange(window)[None, :] < k_eff
        )
        pool = flush_recent(pool, rk, rv, bt, pooled, valid)
        base += k_eff

    for i, (a, b) in enumerate(zip(oracle, got)):
        np.testing.assert_allclose(
            a, b, atol=2e-4, err_msg=f"step {i} (pos {p + i})"
        )


def test_engine_matches_exact_sampler_per_request():
    """Greedy engine output == the existing exact sampler, per request,
    under mixed prompt lengths and full-batch continuous decode."""
    model = _model()
    prompts = _prompts(3)
    refs = [_exact(model, p, 12) for p in prompts]
    outs = generate_served(
        model, prompts, 12, window=4, page_size=8, cache_dtype=jnp.float32
    )
    for i, (r, o) in enumerate(zip(refs, outs)):
        np.testing.assert_array_equal(r, o, err_msg=f"request {i}")


def test_engine_admits_mid_run_with_parity():
    """More requests than slots: late requests are admitted mid-run as
    slots free, and every output still matches the exact sampler."""
    model = _model()
    prompts = _prompts(5, base_len=4, stride=2)
    lens = [6, 14, 9, 11, 7]  # staggered finish -> staggered admission
    refs = [_exact(model, p, n) for p, n in zip(prompts, lens)]
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32,
    )
    rids = [eng.submit(p, n) for p, n in zip(prompts, lens)]
    fin = eng.run()
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )
    assert eng.stats()["slot_occupancy"] > 0.5
    eng.alloc.check()
    assert eng.alloc.held_pages == 0, "finished requests must free pages"


def test_fused_window_matches_k1_including_eos_mid_window():
    """K=4 fused decode reproduces the K=1 token stream exactly — with an
    EOS landing strictly inside a window (not on its boundary), after
    which the slot pads harmlessly to the boundary."""
    model = _model()
    prompt = _prompts(1)[0]
    ref = _exact(model, prompt, 16)
    # choose an EOS the greedy rollout actually emits at a non-boundary
    # step (r % 4 != 3); fall back to any emitted token
    eos, eos_pos = None, None
    for i, t in enumerate(ref.tolist()):
        if ref.tolist().index(t) == i and i % 4 not in (3,) and i > 0:
            eos, eos_pos = int(t), i
            break
    assert eos is not None, "degenerate rollout; adjust prompt seed"
    out_k4 = generate_served(
        model, [prompt], 16, eos_id=eos, window=4, page_size=8,
        cache_dtype=jnp.float32,
    )[0]
    out_k1 = generate_served(
        model, [prompt], 16, eos_id=eos, window=1, page_size=8,
        cache_dtype=jnp.float32,
    )[0]
    np.testing.assert_array_equal(out_k4, out_k1)
    assert out_k4.tolist() == ref.tolist()[: eos_pos + 1], (
        "sequence must stop at (and include) the first EOS"
    )


def test_engine_temperature_stream_invariant_to_window_and_slots():
    """Categorical sampling: a request's token stream derives from
    (seed, token-index) alone — identical across K, slot count, and batch
    composition."""
    model = _model()
    prompts = _prompts(3)

    def run(window, slots):
        eng = ServingEngine(
            model, slots=slots, page_size=8, window=window,
            temperature=0.8, top_k=20, cache_dtype=jnp.float32, seed=3,
        )
        rids = [eng.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
        fin = eng.run()
        return [fin[r].tokens for r in rids]

    a = run(4, 3)
    b = run(1, 3)
    c = run(2, 1)  # serial slots: different batch composition entirely
    assert a == b == c


# ---------------------------------------------------------------------------
# Scheduler: scripted arrival trace, eviction, dispatch accounting
# ---------------------------------------------------------------------------


def test_scheduler_scripted_arrival_trace():
    """Requests arriving between windows are admitted at the next
    boundary; occupancy and lifecycle timestamps are recorded."""
    model = _model()
    prompts = _prompts(4, base_len=4, stride=1)
    refs = [_exact(model, p, 8) for p in prompts]
    fake_now = {"t": 0.0}
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, clock=lambda: fake_now["t"],
    )
    # t=0: two arrivals; after the first window two more arrive
    r0 = eng.submit(prompts[0], 8)
    r1 = eng.submit(prompts[1], 8)
    fake_now["t"] = 1.0
    eng.step()
    r2 = eng.submit(prompts[2], 8)
    r3 = eng.submit(prompts[3], 8)
    fin = eng.run()
    for i, r in enumerate([r0, r1, r2, r3]):
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )
    # late arrivals were admitted mid-run: their TTFT clock starts at
    # submission, and first_token_time >= submit_time for everyone
    for r in (r0, r1, r2, r3):
        req = fin[r]
        assert req.first_token_time is not None
        assert req.first_token_time >= req.submit_time
        assert req.finish_time >= req.first_token_time


def test_scheduler_evicts_under_page_pressure_and_recovers(eviction_case):
    """A pool too small for all requests at once forces eviction; evicted
    requests re-queue with progress kept and still finish with exact
    parity."""
    model, prompts, refs, _ = eviction_case
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=5, window=4,
        temperature=0.0, cache_dtype=jnp.float32,
    )
    rids = [eng.submit(p, 16) for p in prompts]
    fin = eng.run()
    assert eng.evictions > 0, "trace was sized to force eviction"
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )
    eng.alloc.check()
    assert eng.alloc.held_pages == 0


def test_prefill_chunk_stages_a_private_block_table_row(monkeypatch):
    """The chunk program runs when the device gets to it, and the
    scheduler may reset the slot's block-table row later in the same
    step (an eviction while another slot grows). On the CPU backend
    ``jnp.asarray`` of a 64-byte-aligned host array SHARES its memory, so
    a row staged that way was read after the reset: the chunk's rows were
    dropped, and its pages, already registered as a cached prefix, served
    the re-admitted request unwritten (PR 26: one run in ten of
    test_spill_sampled_identity wherever the table happened to be
    aligned). Here the table is aligned on purpose and the program
    replaced by one that keeps what it was handed."""
    import midgpt_tpu.serving.engine as engine_mod

    handed = []

    def program(model, **_):
        def chunk_fn(model, pool, logits, slot, toks, start, n, bt_row):
            handed.append(bt_row)
            return pool, logits

        return chunk_fn

    monkeypatch.setattr(engine_mod, "make_prefill_chunk_program", program)
    eng = ServingEngine(
        _model(), slots=2, page_size=8, window=4, prefill_chunk=8,
        prefill_budget=8, cache_dtype=jnp.float32,
    )
    raw = np.empty(eng.bt.nbytes + 64, np.uint8)
    off = -raw.ctypes.data % 64
    aligned = raw[off:off + eng.bt.nbytes].view(eng.bt.dtype)
    aligned = aligned.reshape(eng.bt.shape)
    aligned[...] = eng.bt
    eng.bt = aligned
    eng.submit(_prompts(1, base_len=22)[0], 4)
    eng.step()  # admits into slot 0 and dispatches its first chunk
    assert len(handed) == 1 and eng.prefilling[0]
    row = eng.bt[0].copy()
    assert (row[:3] != eng._sentinel).all()
    eng.bt[0, :] = eng._sentinel  # what _release_slot does
    np.testing.assert_array_equal(np.asarray(handed[0]), row)


def test_steady_state_one_dispatch_per_k_tokens():
    """With all slots busy and no EOS, decode runs exactly one dispatch
    per K generated tokens per active batch."""
    model = _model()
    k, slots, n_new = 4, 2, 16
    prompts = _prompts(slots, base_len=5, stride=1)
    eng = ServingEngine(
        model, slots=slots, page_size=8, window=k, temperature=0.0,
        cache_dtype=jnp.float32,
    )
    for p in prompts:
        eng.submit(p, n_new)
    eng.run()
    st = eng.stats()
    assert st["decode_dispatches"] == n_new // k
    assert st["tokens_generated"] == slots * n_new
    assert st["tokens_per_dispatch"] == slots * k
    assert st["slot_occupancy"] == 1.0


def _greedy_by_whole_forward(model, prompt, n_new):
    """Greedy continuation by the model's whole-sequence forward, a token
    at a time: no cache, no pages, no window (the forward the hybrid and
    latent test files hold to their plain references)."""
    fwd = jax.jit(lambda m, t: m(t))
    seq = [int(t) for t in prompt]
    for _ in range(n_new):
        padded = np.zeros((1, model.config.block_size), np.int32)
        padded[0, : len(seq)] = seq
        logits = fwd(model, jax.device_put(padded))[0, len(seq) - 1]
        seq.append(int(np.argmax(logits)))
    return seq[len(prompt):]


def _transfer_case(kind):
    """``(model, engine keywords, prompts, n_new, oracle)`` of one kind of
    window: the plain decode window, the verify dispatch, a model with a
    recurrent state beside the pool, one with a latent pool and expert
    counters, and the block window — each other file's own small model."""
    if kind in ("plain", "speculative"):
        kw = {"slots": 2, "page_size": 8, "window": 4, "prefill_chunk": 8,
              "prefill_budget": 8}
        if kind == "speculative":
            kw["speculate"] = 4
        return _model(), kw, _prompts(3, base_len=11), 12, _exact
    if kind == "block":
        import test_block_diffusion as t
        from benchmark import reference_block as rb
        from benchmark import weights_block as w

        weights = w.make(jax.random.PRNGKey(3), t.SIZES, jnp.float32)
        forward = rb.make_forward(t.SIZES)
        kw = {"slots": 3, "page_size": 16, "window": 5, "prefill_chunk": 16}
        return (
            t.fill_model(weights, t.CFG), kw,
            [np.arange(n, dtype=np.int32) * 7 % 510 for n in (22, 35, 9)], 14,
            lambda _, p, n: rb.generate(
                weights, p, n, t.SIZES, forward=forward)[0],
        )
    if kind == "hybrid":
        import test_hybrid_serving as t
        from benchmark import weights_hybrid as w
    else:
        import test_latent_serving as t
        from benchmark import weights_latent as w
    model = t.fill_model(
        w.make(jax.random.PRNGKey(3), t.SIZES, jnp.float32), t.CFG)
    kw = {"slots": 2, "window": 4, "prefill_chunk": 16, "prefill_budget": 16,
          "page_size": 16 if kind == "hybrid" else 4}
    prompts = [np.arange(n, dtype=np.int32) * 7 % 510 for n in (37, 9)]
    return model, kw, prompts, 8, _greedy_by_whole_forward


@pytest.mark.parametrize(
    "kind", ["plain", "speculative", "hybrid", "latent", "block"])
def test_one_read_a_window_one_put_a_dispatch(kind, monkeypatch):
    """What crosses between host and device in an engine step: a window —
    decode, verify or block, whatever else its model returns — is read
    with ONE ``jax.device_get`` (``device_reads == decode_dispatches``
    after every step), a prefill chunk reads nothing, every dispatch puts
    its host arrays with ONE ``jax.device_put`` and hands its program no
    host value to transfer on the way in (``jnp.asarray(<python int>)`` and
    a NumPy argument of a jitted call are such transfers: the guard refuses
    them) — and the tokens are the plain oracle's."""
    model, kw, prompts, n_new, oracle = _transfer_case(kind)
    want = [[int(t) for t in oracle(model, p, n_new)] for p in prompts]
    eng = ServingEngine(
        model, cache_dtype=jnp.float32, paged_kernel="xla", **kw)
    rids = [eng.submit(p, n_new) for p in prompts]
    put, get = mock.Mock(wraps=jax.device_put), mock.Mock(wraps=jax.device_get)
    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(jax, "device_get", get)
    chunk_only_steps = 0
    with jax.transfer_guard_host_to_device("disallow"):
        while eng.has_work:
            chunks, windows = eng.prefill_dispatches, eng.decode_dispatches
            eng.step()
            assert eng.device_reads == eng.decode_dispatches
            chunk_only_steps += (
                eng.prefill_dispatches > chunks
                and eng.decode_dispatches == windows)
    assert chunk_only_steps, "no step of prefill chunks alone: nothing shown"
    st = eng.stats()
    assert st["prefill_chunks"] > len(prompts)  # chunked
    assert get.call_count == st["device_reads"] == st["decode_dispatches"] > 0
    assert put.call_count == (
        st["decode_dispatches"] + st["prefill_dispatches"]
        + st["copy_dispatches"])
    assert [list(eng.finished[r].tokens) for r in rids] == want


def test_repeated_eviction_rebuilds_context_without_duplication(
    eviction_case,
):
    """Regression (code review): a request evicted TWICE must rebuild its
    admission context from the original prompt + all generated tokens —
    appending to an already-grown prompt duplicated the first eviction's
    tokens, corrupting the context and livelocking tight pools."""
    model, prompts, _, refs = eviction_case
    n_new = 24  # long generations -> many growth events -> re-evictions
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=5, window=4,
        temperature=0.0, cache_dtype=jnp.float32,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    fin = eng.run()
    assert max(r.evictions for r in fin.values()) >= 2, (
        "trace was sized to evict some request at least twice; got "
        f"{[r.evictions for r in fin.values()]}"
    )
    for i, r in enumerate(rids):
        # the rebuilt context is prompt0 + a PREFIX of the generated
        # tokens (those emitted before the last eviction) — duplication
        # would break the prefix property
        pr = fin[r].prompt
        np.testing.assert_array_equal(pr[: prompts[i].size], prompts[i])
        tail = pr[prompts[i].size:]
        np.testing.assert_array_equal(
            tail, np.asarray(fin[r].tokens[: tail.size], np.int32),
            err_msg=f"request {i}: context not prompt0 + generated prefix",
        )
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )
    eng.alloc.check()
    assert eng.alloc.held_pages == 0


def test_page_size_must_divide_block_size():
    """Regression (code review): a page grid that doesn't tile block_size
    would pad a near-block prompt past the model's context — reject at
    construction."""
    model = _model()
    with pytest.raises(AssertionError):
        ServingEngine(model, slots=1, page_size=12)  # 64 % 12 != 0


def test_growth_capped_at_remaining_budget():
    """Regression (code review): near end-of-generation, page growth must
    cap at the request's remaining budget — a 60-token prompt with
    max_new=4 exactly fills block_size=64, and demanding pages for
    pooled_len + window tokens would ask past the request's lifetime
    (MemoryError with one slot, spurious evictions under pressure)."""
    model = _model()
    prompt = _prompts(1, base_len=CFG.block_size - 4)[0]  # 60 tokens
    ref = _exact(model, prompt, 4)
    out = generate_served(
        model, [prompt], 4, window=8, page_size=8, slots=1,
        cache_dtype=jnp.float32,
    )[0]
    np.testing.assert_array_equal(out, ref)


def test_engine_rejects_oversized_requests():
    from midgpt_tpu.serving import AdmissionRejected

    model = _model()
    eng = ServingEngine(model, slots=1, page_size=8, window=2)
    with pytest.raises(AdmissionRejected) as exc:
        eng.submit(np.zeros((4,), np.int32), CFG.block_size)  # no room
    assert exc.value.reason == "budget_exceeds_block"
    assert eng.stats()["reject_reasons"] == {"budget_exceeds_block": 1}
    # long prompts crop to the last block_size - max_new tokens
    long_prompt = _prompts(1, base_len=CFG.block_size + 10)[0]
    rid = eng.submit(long_prompt, 4)
    assert eng.queue[-1].prompt.size == CFG.block_size - 4
    ref = _exact(model, long_prompt[-(CFG.block_size - 4):], 4)
    fin = eng.run()
    np.testing.assert_array_equal(np.asarray(fin[rid].tokens), ref)


# ---------------------------------------------------------------------------
# Prefix cache (copy-on-write page sharing) + chunked prefill
# ---------------------------------------------------------------------------


def test_prefix_cache_and_chunking_token_identity(shared_prefix_case):
    """Acceptance: greedy output is token-identical per request with the
    prefix cache on vs off and with chunked vs monolithic prefill —
    shared-prefix traffic, mid-run admission (more requests than slots),
    all against the exact fixed-batch sampler."""
    model, prompts, lens, refs = shared_prefix_case

    def run(prefix_cache, prefill_chunk):
        eng = ServingEngine(
            model, slots=2, page_size=8, window=4, temperature=0.0,
            cache_dtype=jnp.float32, prefix_cache=prefix_cache,
            prefill_chunk=prefill_chunk,
        )
        rids = [eng.submit(p, n) for p, n in zip(prompts, lens)]
        fin = eng.run()
        eng.alloc.check()
        if eng.index is not None:
            eng.index.check(eng.alloc)
        assert eng.alloc.held_pages == 0
        return [fin[r].tokens for r in rids], eng

    base, _ = run(False, None)
    for variant in [(True, None), (False, 8), (True, 8), (True, 5)]:
        toks, eng = run(*variant)
        assert toks == base, f"variant {variant} diverged"
    for i, r in enumerate(base):
        np.testing.assert_array_equal(np.asarray(r), refs[i], err_msg=f"req {i}")


def test_shared_prefix_skips_prefill_compute():
    """Acceptance: a two-request shared-prefix scenario demonstrably
    skips the shared pages' prefill — the second request computes only
    the uncached suffix (token count asserted) and the hit rate is
    positive."""
    model = _model()
    prompt = _prompts(1, base_len=24)[0]
    eng = ServingEngine(
        model, slots=1, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefix_cache=True,
    )
    r1 = eng.submit(prompt, 6)
    eng.run()
    computed_first = eng.prefill_tokens_computed
    assert computed_first == 24  # cold cache: the whole prompt
    r2 = eng.submit(prompt, 6)
    fin = eng.run()
    # the second admission recomputes ONLY the last prompt token (the
    # p-1 cap that produces the first decode logits); 16 tokens ride the
    # two full shared pages, 7 the copy-on-write partial page
    assert eng.prefill_tokens_computed - computed_first == 1
    assert eng.prompt_tokens_cached == 23
    assert eng.copy_dispatches == 1
    st = eng.stats()
    assert st["prefix_hit_rate"] > 0
    assert st["prefill_tokens_saved"] == 23
    np.testing.assert_array_equal(
        np.asarray(fin[r1].tokens), np.asarray(fin[r2].tokens)
    )
    ref = _exact(model, prompt, 6)
    np.testing.assert_array_equal(np.asarray(fin[r2].tokens), ref)


def test_multiturn_hits_decode_written_pages_with_parity():
    """Multi-turn shape: turn 2's prompt extends turn 1's prompt AND its
    GENERATED tokens, so the cache hit aliases pages whose K/V was
    written by the decode flush, not by prefill — the one page-content
    source the other exactness tests never exercise (decode and chunk
    prefill use different einsum arithmetic; reuse must still be
    token-identical to the cache-off recompute)."""
    model = _model()
    p0 = _prompts(1, base_len=12)[0]

    def run(cache):
        eng = ServingEngine(
            model, slots=1, page_size=8, window=4, temperature=0.0,
            cache_dtype=jnp.float32, prefix_cache=cache,
        )
        rA = eng.submit(p0, 10)
        finA = eng.run()
        turn2 = np.concatenate([
            p0, np.asarray(finA[rA].tokens, np.int32),
            np.asarray([7, 3], np.int32),  # the "user reply"
        ])
        rB = eng.submit(turn2, 10)
        finB = eng.run()
        return finA[rA].tokens, finB[rB].tokens, eng

    toks_a_on, toks_b_on, eng_on = run(True)
    toks_a_off, toks_b_off, _ = run(False)
    assert toks_a_on == toks_a_off and toks_b_on == toks_b_off
    # turn 2 really did alias decode-written pages: p0 is 12 tokens, so
    # any hit past page 1 (16 tokens) covers generated positions
    assert eng_on.prompt_tokens_cached > len(p0)
    ref = _exact(model, np.concatenate([
        p0, np.asarray(toks_a_on, np.int32), np.asarray([7, 3], np.int32)
    ]), 10)
    np.testing.assert_array_equal(np.asarray(toks_b_on), ref)


def test_eviction_readmission_rehits_cache_with_parity(eviction_case):
    """Under page pressure an evicted request's pages retire COLD; its
    re-admission re-prefills via cache hits (tokens saved > 0) and the
    output still matches the exact sampler bit-for-bit."""
    model, prompts, _, refs = eviction_case
    n_new = 24
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=5, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    fin = eng.run()
    assert eng.evictions > 0, "trace was sized to force eviction"
    assert eng.prompt_tokens_cached > 0, (
        "re-admissions should re-prefill via the cold prefix cache"
    )
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )
    eng.alloc.check()
    eng.index.check(eng.alloc)
    assert eng.alloc.held_pages == 0


def test_chunked_prefill_interleaves_with_decode():
    """Sarathi property: with a per-window token budget, a long prompt's
    prefill spreads over several windows while an already-running request
    keeps decoding — the long prompt never monopolizes a window."""
    model = _model()
    short = _prompts(1, base_len=4)[0]
    long = _prompts(1, base_len=48)[0]
    refs = [_exact(model, short, 16), _exact(model, long, 8)]
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefill_chunk=8, prefill_budget=8,
    )
    r_short = eng.submit(short, 16)
    eng.step()  # short is decoding
    req_short = next(
        r for r in eng.slot_req if r is not None and r.rid == r_short
    )
    tokens_before = len(req_short.tokens)
    r_long = eng.submit(long, 8)
    # the long prompt needs ceil(48/8)=6 chunks at 8 tokens/window: the
    # short request must make decode progress during that prefill
    eng.step()
    eng.step()
    assert any(
        eng.prefilling[s] for s in range(eng.slots)
    ), "long prompt should still be prefilling after 2 windows"
    assert len(req_short.tokens) > tokens_before, (
        "decode starved while the long prompt prefilled"
    )
    fin = eng.run()
    np.testing.assert_array_equal(np.asarray(fin[r_short].tokens), refs[0])
    np.testing.assert_array_equal(np.asarray(fin[r_long].tokens), refs[1])
    assert eng.prefill_dispatches >= 6


def test_default_budget_prefills_a_window_of_chunks():
    """With ``prefill_chunk`` and no ``prefill_budget`` the budget is one
    chunk per token step of the window: a 32-token prompt at chunks of 8
    and ``window=4`` prefills whole in the step that admits it, its four
    granules in ONE chunk program, and decodes in that very step's window
    — one engine step that ran chunks."""
    model = _model()
    prompt = _prompts(1, base_len=32)[0]
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    assert eng.prefill_budget == 32
    rid = eng.submit(prompt, 8)
    eng.step()
    st = eng.stats()
    assert (st["prefill_chunks"], st["prefill_dispatches"],
            st["prefill_steps"]) == (4, 1, 1)
    assert st["prefill_tokens_computed"] == 32
    assert not eng.prefilling[0]
    assert st["decode_dispatches"] == 1 and st["tokens_generated"] == 4
    fin = eng.run()
    np.testing.assert_array_equal(np.asarray(fin[rid].tokens),
                                  _exact(model, prompt, 8))
    assert eng.stats()["prefill_steps"] == 1


@pytest.mark.parametrize("kw, chunks, dispatches, first", [
    (dict(window=1), 1, 1, [8, 0]),  # a one-step window: one chunk
    (dict(window=4, prefill_budget=8), 1, 1, [8, 0]),  # explicit: as before
    (dict(window=4, prefill_budget=16), 2, 2, [8, 8]),
    (dict(window=4, speculate=4), 1, 1, [8, 0]),  # a verify is one step
    (dict(window=4), 4, 2, [16, 16]),  # the default: a program a slot
], ids=["window1", "explicit8", "explicit16", "speculative", "window4"])
def test_budget_sets_the_chunks_an_engine_step(kw, chunks, dispatches, first):
    """Chunks an engine step under each budget, two prompts of 32 tokens
    prefilling side by side: the budget is handed out round-robin, a
    granule of 8 at a time (``first``: what each slot holds after the first
    step), and each slot's granules of a step run as one program;
    ``prefill_steps`` counts the steps that ran one or more, so granules a
    prefill step = ``prefill_chunks`` / steps."""
    model = _model()
    prompts = _prompts(2, base_len=32, stride=0)
    eng = ServingEngine(
        model, slots=2, page_size=8, temperature=0.0,
        cache_dtype=jnp.float32, prefill_chunk=8, **kw,
    )
    rids = [eng.submit(p, 8) for p in prompts]
    per_step = []
    while eng.has_work:
        before = eng.prefill_chunks, eng.prefill_dispatches
        eng.step()
        if not per_step:
            assert sorted(eng.pooled_len[:2].tolist()) == sorted(first)
        per_step.append((eng.prefill_chunks - before[0],
                         eng.prefill_dispatches - before[1]))
    ran = [n for n in per_step if n[0]]
    # 2 prompts x 4 chunks, `chunks` in every step that runs any
    assert ran == [(chunks, dispatches)] * (8 // chunks)
    st = eng.stats()
    assert st["prefill_steps"] == len(ran)
    assert st["prefill_chunks"] == 8
    assert st["prefill_dispatches"] == 8 // chunks * dispatches
    for r, p in zip(rids, prompts):
        np.testing.assert_array_equal(np.asarray(eng.finished[r].tokens),
                                      _exact(model, p, 8))


@pytest.fixture(scope="module")
def long_context_case():
    """A 700-token prompt on the small model at 1,024 positions, and the
    exact sampler's tokens for it."""
    model = GPT.init(
        jax.random.PRNGKey(0), dataclasses.replace(CFG, block_size=1024))
    prompt = _prompts(1, base_len=700)[0]
    return model, prompt, _exact(model, prompt, 6)


@pytest.mark.parametrize("chunk, chunks, dispatches", [
    (64, 11, 3),  # 4 + 4 + 3 granules: the last program, 188 rows from
    # position 512 in a bucket of 256, runs past the slot's last page
    (256, 3, 3),  # at the cap: 256 + 256 + 188, nothing merges
    (512, 2, 2),  # above it: 512 + 188, nothing merges
], ids=["chunk64", "chunk256", "chunk512"])
def test_granules_merge_up_to_the_cap(long_context_case, chunk, chunks,
                                      dispatches):
    """A slot's granules of a step run as one program of at most
    ``max(prefill_chunk, PREFILL_MERGE_ROWS)`` rows: under the cap they
    merge, at or above it every granule is a program of its own
    (``prefill_chunks == prefill_dispatches``) — and the tokens are the
    exact sampler's either way."""
    from midgpt_tpu.serving.engine import PREFILL_MERGE_ROWS

    assert PREFILL_MERGE_ROWS == 256
    model, prompt, want = long_context_case
    eng = ServingEngine(
        model, slots=1, page_size=16, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefill_chunk=chunk,
    )
    rid = eng.submit(prompt, 6)
    fin = eng.run()
    st = eng.stats()
    assert (st["prefill_chunks"], st["prefill_dispatches"]) == (
        chunks, dispatches)
    assert st["prefill_tokens_computed"] == len(prompt)
    np.testing.assert_array_equal(np.asarray(fin[rid].tokens), want)


def test_a_merged_bucket_past_the_block_table_drops_its_padding():
    """A merged program's bucket may run past the slot's block table and
    the rope table (60 tokens of a 64-position model, granules of 8 under
    a budget of 40: the second program holds 20 rows from position 40 in
    a bucket of 32, eight rows past the table): its padded rows write
    nothing and the tokens are those of a monolithic prefill and of the
    exact sampler."""
    model = _model()
    prompt = _prompts(1, base_len=CFG.block_size - 4)[0]  # 60 tokens

    def run(**kw):
        eng = ServingEngine(
            model, slots=1, page_size=8, window=4, temperature=0.0,
            cache_dtype=jnp.float32, **kw,
        )
        rid = eng.submit(prompt, 4)
        return list(eng.run()[rid].tokens), eng

    got, eng = run(prefill_chunk=8, prefill_budget=40)
    assert (eng.prefill_chunks, eng.prefill_dispatches) == (8, 2)
    assert 40 + eng._prefill_bucket(20) > eng.pmax * eng.page_size
    mono, _ = run()
    assert got == mono
    np.testing.assert_array_equal(np.asarray(got), _exact(model, prompt, 4))


def test_warm_prefill_warms_the_merged_buckets():
    """``warm_prefill`` compiles every bucket a dispatch can take: with
    chunking, up to the rows a slot's granules of a step can merge into,
    whatever ``max_tokens`` says; monolithic, up to ``max_tokens``."""
    model = GPT.init(
        jax.random.PRNGKey(0), dataclasses.replace(CFG, block_size=1024))

    def warm(max_tokens, **kw):
        eng = ServingEngine(
            model, slots=1, page_size=16, cache_dtype=jnp.float32, **kw)
        return eng.warm_prefill(max_tokens)

    # the benchmark's call: the chunk as max_tokens, budget 4 x 64; under
    # the ridge no program is shorter than the granule
    assert warm(64, prefill_chunk=64) == [64, 128, 256]
    assert warm(64, prefill_chunk=64, window=2) == [64, 128]
    assert warm(64, prefill_chunk=64, prefill_budget=64) == [64]
    assert warm(64, prefill_chunk=24) == [32, 64, 128]
    assert warm(16, prefill_chunk=512) == [16, 32, 64, 128, 256, 512]
    assert warm(100, prefill_chunk=None) == [16, 32, 64, 128]


def test_sharing_invariants_property_loop():
    """Property-style allocator/index invariants under a busy shared-
    prefix trace with pressure: after EVERY scheduler step — refcounts
    never negative (alloc.check), free+held+cached == num_pages, COW/tail
    pages never aliased by two writers, shared pages only ever full
    (indexed) ones, LRU only holds refcount-0 pages."""
    model = _model()
    sys_prompt = _prompts(1, base_len=16)[0]
    tails = _prompts(6, base_len=2, stride=1)
    prompts = [np.concatenate([sys_prompt, t]) for t in tails]
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=10, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
        prefill_chunk=8,
    )
    rids = [eng.submit(p, 10, seed=i) for i, p in enumerate(prompts)]
    steps = 0
    while (eng.queue or eng._active_slots()) and steps < 500:
        eng.step()
        steps += 1
        eng.alloc.check()
        eng.index.check(eng.alloc)
        ps = eng.page_size
        for s in eng._active_slots():
            n_pages = len(eng.slot_pages[s])
            pl = int(eng.pooled_len[s])
            for i, pg in enumerate(eng.slot_pages[s]):
                if pg in eng.index:
                    continue  # full + indexed: immutable, safely shared
                # private (writable) pages must have exactly one owner
                # and appear in exactly one block table
                assert eng.alloc.refcount(pg) == 1, (
                    f"writer page {pg} shared (ref "
                    f"{eng.alloc.refcount(pg)})"
                )
                owners = [
                    v for v in eng._active_slots()
                    if pg in eng.slot_pages[v]
                ]
                assert owners == [s], (
                    f"page {pg} aliased by slots {owners}"
                )
    assert steps < 500, "engine did not drain"
    assert eng.alloc.held_pages == 0
    # freeing a request decrefs exactly its pages: everything is now
    # free or cold-cached
    assert (
        eng.alloc.free_pages + eng.alloc.cached_pages
        == eng.alloc.num_pages
    )
    # all requests completed with the right token counts
    for r in rids:
        assert len(eng.finished[r].tokens) == 10


def test_cold_lru_eviction_only_reclaims_refcount_zero_leaves():
    """Unit-level: evict_cold_leaf never returns a page that is still
    referenced or that an indexed child chains through."""
    alloc = PageAllocator(8)
    index = PrefixIndex(4)
    # two chains: [a, b] and [c]; a/b retire cold, c stays held
    a, b, c = alloc.alloc(3)
    a = index.register(-1, [1, 2, 3, 4], a)
    b = index.register(a, [5, 6, 7, 8], b)
    c = index.register(-1, [9, 9, 9, 9], c)
    alloc.decref(a, cache=True)
    index.touch_cold(a)
    alloc.decref(b, cache=True)
    index.touch_cold(b)
    # a was touched first (LRU) but has child b -> b must evict first
    v1 = index.evict_cold_leaf()
    assert v1 == b
    alloc.reclaim(v1)
    v2 = index.evict_cold_leaf()
    assert v2 == a
    alloc.reclaim(v2)
    # c is held (refcount 1): never reclaimable
    assert index.evict_cold_leaf() is None
    assert alloc.refcount(c) == 1 and c in index
    alloc.check()
    index.check(alloc)


def test_allocator_refcount_never_negative():
    a = PageAllocator(4)
    (p,) = a.alloc(1)
    a.incref(p)
    assert a.refcount(p) == 2
    assert a.decref(p) == 1
    assert a.decref(p) == 0
    with pytest.raises(ValueError):
        a.decref(p)  # already free: refcount can never go negative
    with pytest.raises(ValueError):
        a.incref(p)  # free pages cannot be shared
    a.check()
    # cached pages revive through incref
    (q,) = a.alloc(1)
    a.decref(q, cache=True)
    assert a.cached_pages == 1
    a.incref(q)
    assert a.refcount(q) == 1 and a.cached_pages == 0
    a.check()


# ---------------------------------------------------------------------------
# Self-speculative decoding: n-gram drafting + single-dispatch verification
# ---------------------------------------------------------------------------


class _OracleProposer:
    """Test proposer that drafts the TRUE greedy continuation (known from
    a spec-off reference run) — every draft verifies, so dispatch counts
    hit their floor deterministically."""

    def __init__(self, seqs):
        # seqs: list of full token lists (prompt + greedy continuation)
        self.seqs = [[int(t) for t in s] for s in seqs]

    def propose(self, ctx, n):
        ctx = [int(t) for t in ctx]
        for full in self.seqs:
            if full[: len(ctx)] == ctx and len(full) > len(ctx) + 1:
                return full[len(ctx) + 1 : len(ctx) + 1 + n]
        return []


class _AntiOracleProposer(_OracleProposer):
    """Adversarial proposer: drafts are the true continuation shifted by
    one token id — every draft is guaranteed WRONG, so every verify
    dispatch fully rejects (the watermark-rollback worst case)."""

    def propose(self, ctx, n):
        good = super().propose(ctx, n)
        return [(t + 1) % CFG.vocab_size for t in good]


def test_ngram_proposer_periodic_and_no_match():
    from midgpt_tpu.serving import NgramProposer

    p = NgramProposer(max_ngram=3, min_ngram=1)
    # periodic context: the suffix [2, 3] recurs; the continuation chain
    # after the match predicts positions len(ctx)+1.. (the engine's row 0
    # covers position len(ctx) itself, so drafts skip one token)
    ctx = [1, 2, 3, 1, 2, 3, 1, 2, 3]
    # suffix match predicts next = 1 (skipped), then 2, 3, 1, ...
    assert p.propose(ctx, 4) == [2, 3, 1, 2]
    # all-distinct context: nothing recurs, no drafts
    assert p.propose(list(range(10, 30)), 4) == []
    # too-short context: no earlier occurrence exists
    assert p.propose([5], 4) == []
    # constant runs: drafts are read out of history verbatim (no
    # extrapolation), so a short run yields what the earliest match can
    # see and a long run fills the whole draft
    assert p.propose([7, 7, 7, 7], 3) == [7]
    assert p.propose([7] * 8, 3) == [7, 7, 7]


def test_spec_token_identity_matrix(shared_prefix_case):
    """Acceptance: greedy output with speculation on is token-identical
    to the non-speculative engine across prefix-cache on/off x chunked
    vs monolithic prefill — shared-prefix traffic, mid-run admission —
    and to the exact fixed-batch sampler."""
    model, prompts, lens, refs = shared_prefix_case

    def run(speculate, prefix_cache, prefill_chunk):
        eng = ServingEngine(
            model, slots=2, page_size=8, window=4, temperature=0.0,
            cache_dtype=jnp.float32, prefix_cache=prefix_cache,
            prefill_chunk=prefill_chunk, speculate=speculate,
        )
        rids = [eng.submit(p, n) for p, n in zip(prompts, lens)]
        fin = eng.run()
        eng.alloc.check()
        if eng.index is not None:
            eng.index.check(eng.alloc)
        assert eng.alloc.held_pages == 0
        return [fin[r].tokens for r in rids]

    # the spec-off engine == exact-sampler identity across these axes is
    # PR 4's test_prefix_cache_and_chunking_token_identity; here the
    # refs ARE the spec-off streams, so comparing each spec-on variant
    # to them is exactly spec-on vs spec-off (one engine run per variant)
    base = [list(map(int, r)) for r in refs]
    # two spec-on variants span both cache states and both prefill modes
    # (each distinct spec_len would compile its own verify program;
    # runtime draft-length variation is covered by the adaptive
    # controller, which the full-rejection test drives to its floor)
    for variant in [(4, True, None), (4, False, 8)]:
        assert run(*variant) == base, f"variant {variant} diverged"


def test_spec_identity_under_eviction_and_readmission(eviction_case):
    """Speculation x page pressure: evicted requests re-queue, re-admit
    (through the prefix cache), and keep speculating — output still
    matches the exact sampler bit-for-bit and pages all come home."""
    model, prompts, refs, _ = eviction_case
    n_new = 16  # 3 pages per request x 2 slots > the 5-page pool
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=5, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
        speculate=4,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    fin = eng.run()
    assert eng.evictions > 0, "trace was sized to force eviction"
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(fin[r].tokens), refs[i], err_msg=f"request {i}"
        )
    eng.alloc.check()
    eng.index.check(eng.alloc)
    assert eng.alloc.held_pages == 0


def test_spec_dispatch_accounting_on_repetitive_prompt():
    """Acceptance: on a repetitive-text prompt the n-gram proposer's
    drafts verify, so a single slot emits MORE than one token per decode
    dispatch — with the stream still identical to spec-off."""
    model = _model()
    pat = np.asarray(
        jax.random.randint(jax.random.PRNGKey(500), (4,), 0, CFG.vocab_size)
    )
    prompt = np.tile(pat, 6)  # 24 tokens of period-4 text
    n_new = 20
    ref = _exact(model, prompt, n_new)
    eng = ServingEngine(
        model, slots=1, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, speculate=4,
    )
    rid = eng.submit(prompt, n_new)
    fin = eng.run()
    np.testing.assert_array_equal(np.asarray(fin[rid].tokens), ref)
    st = eng.stats()
    assert st["tokens_generated"] == n_new
    assert st["decode_dispatches"] < n_new, st
    assert st["tokens_per_dispatch"] > 1.0, st
    assert st["spec_accepted_tokens"] > 0
    assert st["verify_dispatches"] == st["decode_dispatches"]
    # spec-off at window=1 pays exactly one dispatch per token: the
    # speculative engine provably beat one-token-per-forward
    assert st["decode_dispatches"] < len(ref)


@pytest.mark.slow
def test_spec_oracle_hits_dispatch_floor():
    """With a perfect proposer the dispatch count hits its deterministic
    floor: ceil(n_new / (spec_len + 1)) verify dispatches per request."""
    model = _model()
    prompts = _prompts(2, base_len=5, stride=0)  # equal length: 1 batch
    n_new, spec = 12, 4
    refs = np.asarray(
        generate(
            model, jnp.stack([jnp.asarray(p) for p in prompts]), n_new,
            key=jax.random.PRNGKey(9), temperature=0.0,
            cache_dtype=jnp.float32,
        )
    )
    seqs = [
        list(map(int, p)) + list(map(int, r)) for p, r in zip(prompts, refs)
    ]
    eng = ServingEngine(
        model, slots=2, page_size=8, temperature=0.0,
        cache_dtype=jnp.float32, speculate=spec,
        proposer=_OracleProposer(seqs),
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    fin = eng.run()
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(np.asarray(fin[r].tokens), refs[i])
    st = eng.stats()
    assert st["decode_dispatches"] == -(-n_new // (spec + 1))  # 12 -> 3
    assert st["tokens_per_dispatch"] == 2 * n_new / 3  # both slots
    assert st["spec_acceptance_rate"] == 1.0
    # full acceptance keeps every request's adaptive draft length maxed
    assert all(fin[r].spec_k == spec for r in rids)


def test_spec_full_rejection_watermark_property_loop():
    """Acceptance: forced FULL-REJECTION verify dispatches (adversarial
    proposer — every draft wrong) under page pressure, chunked prefill
    and the prefix cache. After every scheduler step the allocator/index
    invariants and the single-writer property must hold (rejected rows'
    K/V never lands, the watermark only advances over verified context),
    and the final streams still match the exact sampler: a hostile
    proposer costs throughput, never correctness."""
    model = _model()
    prompts = _prompts(4, base_len=6, stride=1)
    n_new = 12
    refs = [_exact(model, p, n_new) for p in prompts]
    seqs = [
        list(map(int, p)) + list(map(int, r)) for p, r in zip(prompts, refs)
    ]
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=6, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
        prefill_chunk=8, speculate=4, proposer=_AntiOracleProposer(seqs),
    )
    rids = [eng.submit(p, n_new, seed=i) for i, p in enumerate(prompts)]
    steps = 0
    while (eng.queue or eng._active_slots()) and steps < 500:
        eng.step()
        steps += 1
        eng.alloc.check()
        eng.index.check(eng.alloc)
        for s in eng._active_slots():
            # the watermark never runs ahead of verified host-side
            # context (speculative rows beyond it were rolled back)
            assert int(eng.pooled_len[s]) <= len(eng.slot_ctx[s])
            for pg in eng.slot_pages[s]:
                if pg in eng.index:
                    continue  # full + indexed: immutable, safely shared
                assert eng.alloc.refcount(pg) == 1, (
                    f"writer page {pg} shared"
                )
                owners = [
                    v for v in eng._active_slots()
                    if pg in eng.slot_pages[v]
                ]
                assert owners == [s], f"page {pg} aliased by {owners}"
    assert steps < 500, "engine did not drain"
    assert eng.spec_drafted > 0, "adversarial drafts never ran"
    assert eng.spec_accepted == 0, "anti-oracle drafts must all reject"
    for i, r in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(eng.finished[r].tokens), refs[i], err_msg=f"req {i}"
        )
    # full rejection decays every request's draft length to the floor
    assert all(eng.finished[r].spec_k == 1 for r in rids)
    eng.alloc.check()
    assert eng.alloc.held_pages == 0


@pytest.mark.slow
def test_spec_eos_mid_verify_matches_spec_off():
    """An EOS landing inside a verify dispatch (among the accepted rows)
    truncates the emission at the EOS — same stop point as spec-off."""
    model = _model()
    prompt = _prompts(1)[0]
    ref = _exact(model, prompt, 16)
    eos = int(ref[len(ref.tolist()) // 2])  # a token the rollout emits
    off = generate_served(
        model, [prompt], 16, eos_id=eos, window=4, page_size=8,
        cache_dtype=jnp.float32,
    )[0]
    on = generate_served(
        model, [prompt], 16, eos_id=eos, window=4, page_size=8,
        cache_dtype=jnp.float32, speculate=4,
    )[0]
    np.testing.assert_array_equal(on, off)
    assert int(on[-1]) == eos and eos not in on[:-1].tolist()


def test_sampling_config_typed_errors():
    """Sampled speculation is supported (the greedy-only assert is
    gone): the ctor builds the rejection-sampling verify program at
    temperature > 0. Only genuinely invalid sampling configs raise, and
    they raise TYPED errors."""
    model = _model()
    eng = ServingEngine(model, slots=1, temperature=0.8, speculate=4)
    assert eng.temperature == 0.8 and eng.speculate == 4
    with pytest.raises(ValueError, match="temperature"):
        ServingEngine(model, slots=1, temperature=-0.5)
    with pytest.raises(ValueError, match="top_k"):
        ServingEngine(model, slots=1, temperature=0.8, top_k=0)


# what asks a ServingEngine for each feature of _REFUSED (a mesh is the
# mesh8 fixture's, and block_len is the model's own)
_FEATURE_KW = {
    "temperature > 0": {"temperature": 0.7},
    "speculate": {"speculate": 2},
    "quant": {"quant": "int8"},
    "kv_quant": {"kv_quant": "int8"},
    "a mesh": {},
    "role != 'both'": {"role": "prefill"},
    "spill": {"spill": "on"},
    "prefill_sp": {"prefill_sp": "on"},
    "block_len": {},
}
# the small test models of each kind (both recurrent mixers for
# "recurrent")
_KIND_FAMILIES = {
    "recurrent": ("gated_delta", "mamba"),
    "latent": ("latent",),
    "block": ("block",),
}


@pytest.mark.parametrize("kind,family,feature", [
    (kind, family, feature)
    for kind, (_, refused) in _REFUSED.items()
    for family in _KIND_FAMILIES[kind]
    for feature in refused
])
def test_what_a_kind_of_model_lacks_is_refused_by_name(
        family_config, mesh8, kind, family, feature):
    """Every entry of the engine's one refusal table: a model of that kind
    asked for that feature is refused at construction, with the kind's
    wording and the feature's name. The model is abstract (shapes only):
    nothing of it is read before the refusal."""
    cfg = family_config(family)
    if feature == "block_len":
        cfg = dataclasses.replace(cfg, block_len=4, block_steps=4,
                                  mask_token=cfg.vocab_size - 1)
    model = jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), cfg))
    kw = {"mesh": mesh8} if feature == "a mesh" else _FEATURE_KW[feature]
    subject = _REFUSED[kind][0]
    with pytest.raises(ValueError, match=re.escape(subject)
                       + " does not support .*" + re.escape(feature)):
        ServingEngine(model, slots=2, page_size=16, **kw)


@pytest.mark.slow
def test_spec_identity_with_bf16_cache_under_f32_model():
    """Regression (code review): the decode window reads even in-window
    K/V back through the CACHE-dtype recent buffer, so the verify
    program must round its in-dispatch self K/V to pool dtype before
    scoring — an f32 model over a bf16 pool would otherwise compare
    acceptance argmaxes against un-rounded keys (a far larger gap than
    the bf16 ulp flips the CLI drive catches). f32-model + bf16-cache is
    exactly the combination neither the f32/f32 fast tests nor the
    bf16/bf16 checkpoint drive covers."""
    model = _model()  # f32 params
    prompts = _prompts(2)
    outs = {}
    for spec in (0, 4):
        outs[spec] = generate_served(
            model, prompts, 12, window=4, page_size=8,
            cache_dtype=jnp.bfloat16, speculate=spec,
        )
    for a, b in zip(outs[0], outs[4]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_verify_program_audit_donation_and_host_sync():
    """The compiled speculative verify program passes the serving
    invariants (pool + logits donation intact, no host sync) — with
    speculation on, every decode dispatch is this program."""
    from midgpt_tpu.analysis.harness import audit_verify_program
    from midgpt_tpu.config import get_config

    analysis, report = audit_verify_program(
        get_config("shakespeare_char"), slots=2, spec_len=4, page_size=8
    )
    assert report.ok, report.violations
    assert analysis.donated_leaves == 3  # pool.k, pool.v, logits
    assert len({e.param_number for e in analysis.aliases}) >= 3


@pytest.mark.slow
def test_prefill_chunk_audit_donation_and_host_sync():
    """The compiled suffix-prefill chunk program passes the serving
    invariants (donation intact, no host sync) — the program chunked
    prefill dispatches between every pair of decode windows."""
    from midgpt_tpu.analysis.harness import audit_prefill_chunk
    from midgpt_tpu.config import get_config

    analysis, report = audit_prefill_chunk(
        get_config("shakespeare_char"), chunk_len=32, page_size=8
    )
    assert report.ok, report.violations
    assert analysis.donated_leaves == 3  # pool.k, pool.v, logits


@pytest.mark.slow
def test_decode_window_audit_donation_and_host_sync():
    """The compiled K-step decode window passes the serving invariants:
    pool + logits donation intact, no host round-trips inside the window
    (the same two regressions the CI serving-audit job gates on)."""
    from midgpt_tpu.analysis.harness import audit_decode_window
    from midgpt_tpu.config import get_config

    analysis, report = audit_decode_window(
        get_config("shakespeare_char"), slots=2, window=4, page_size=8
    )
    assert report.ok, report.violations
    assert analysis.donated_leaves == 3  # pool.k, pool.v, logits
    assert len({e.param_number for e in analysis.aliases}) >= 3


# ---------------------------------------------------------------------------
# Sampled speculation (temperature > 0): rejection-sampling verify
# ---------------------------------------------------------------------------
#
# At temperature > 0 spec-on is NOT bitwise spec-off (accepted drafts are
# draws from the proposer's q, not fresh draws from p) — the contract is
# (a) SCHEDULING INVARIANCE: the sampled spec-on stream is a pure function
#     of (request seed, engine seed, sampling knobs), bitwise identical
#     across slots / window / batch composition / chunking / prefix cache /
#     eviction — within each arithmetic cell (kv-quant changes the
#     arithmetic, so cells are compared within themselves);
# (b) DISTRIBUTIONAL EXACTNESS: accept-with-min(1, p/q) + residual
#     resample + bonus row reproduce the spec-off sampling distribution for
#     ANY honest proposer (statistical test below);
# (c) DEGENERATE ANCHOR: with no drafts the verify program IS the decode
#     sampler — bitwise spec-off.


def _rep_prompts(n, period=4, reps=6):
    """Repetitive-text prompts (the fixture the n-gram proposer can
    actually draft against)."""
    return [
        np.tile(
            np.asarray(
                jax.random.randint(
                    jax.random.PRNGKey(700 + i), (period,), 0,
                    CFG.vocab_size,
                )
            ),
            reps,
        )
        for i in range(n)
    ]


def _run_sampled(model, prompts, lens, **kw):
    """One sampled spec-on rollout; returns (streams, engine)."""
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("page_size", 8)
    kw.setdefault("slots", 2)
    kw.setdefault("window", 4)
    kw.setdefault("speculate", 4)
    eng = ServingEngine(model, temperature=0.8, top_k=20, seed=3, **kw)
    rids = [
        eng.submit(p, n, seed=i)
        for i, (p, n) in enumerate(zip(prompts, lens))
    ]
    fin = eng.run()
    eng.alloc.check()
    assert eng.alloc.held_pages == 0
    return [list(map(int, fin[r].tokens)) for r in rids], eng


class _EmptyProposer:
    """Never drafts: every verify dispatch degenerates to row 0."""

    def propose(self, ctx, n):
        return []


class _SoftModelProposer:
    """Honest soft-distribution proposer (serving.speculate.SoftProposer
    protocol): each draft is genuinely SAMPLED from the claimed q row —
    the rejection-sampling exactness precondition — with q computed by
    the monolithic full-precision forward at ``q_temperature`` (defaults
    to the verify temperature: a near-oracle whose only p/q mismatch is
    the paged verify arithmetic; a flatter ``q_temperature`` forces
    heavy rejection and drives real mass through the residual resample
    without breaking exactness). Drafting is derandomized from
    (request seed, context) — crc32-seeded numpy rng, NOT Python
    ``hash`` (salted per process) — so drafts are a pure function of
    the request and cannot perturb scheduling invariance, while staying
    honest draws from q ACROSS requests (the seed is the per-request
    entropy; ctx alone would collapse same-prompt requests onto one
    deterministic draft and break rejection-sampling exactness — the
    reason propose_soft receives the seed at all)."""

    soft = True

    def __init__(self, model, temperature, top_k, q_temperature=None):
        self.model = model
        self.temperature = (
            temperature if q_temperature is None else q_temperature
        )
        self.top_k = top_k
        self._fwd = jax.jit(lambda m, x: m(x))

    def _dist(self, toks):
        from midgpt_tpu.sampling import target_probs

        toks = list(toks)[-CFG.block_size:]
        # fixed-shape forward: causal attention ignores the zero padding
        # after position len(toks) - 1, and one compile serves every call
        x = np.zeros((1, CFG.block_size), np.int32)
        x[0, : len(toks)] = toks
        logits = self._fwd(self.model, jnp.asarray(x))[0, len(toks) - 1]
        q = np.asarray(
            target_probs(logits, self.temperature, self.top_k), np.float64
        )
        return q / q.sum()

    def propose(self, ctx, n):  # greedy path: unused at temperature > 0
        return []

    def propose_soft(self, ctx, n, seed):
        if n <= 0:
            return [], np.zeros((0, CFG.vocab_size), np.float32)
        ctx = [int(t) for t in ctx]
        rng = np.random.default_rng(
            (seed, zlib.crc32(np.asarray(ctx, np.int64).tobytes()))
        )
        # drafts cover positions len(ctx)+1.. (verify row 0 samples
        # position len(ctx) itself), so guess the skipped token first —
        # a wrong guess only costs acceptance, never exactness
        skip = int(rng.choice(CFG.vocab_size, p=self._dist(ctx)))
        toks, qs = [], []
        for _ in range(n):
            q = self._dist(ctx + [skip] + toks)
            toks.append(int(rng.choice(CFG.vocab_size, p=q)))
            qs.append(q.astype(np.float32))
        return toks, np.stack(qs)


def test_spec_sampled_stream_invariant_to_scheduling():
    """Contract (a), fast tier: sampled spec-on streams are bitwise
    invariant to slots / prefix cache / chunked prefill — drafts ride
    the n-gram proposer against repetitive prompts, so acceptance AND
    rejection-residual paths both execute."""
    model = _model()
    prompts = _rep_prompts(3)
    lens = [10, 12, 8]
    a, ea = _run_sampled(model, prompts, lens, slots=2, prefix_cache=True)
    b, _ = _run_sampled(
        model, prompts, lens, slots=1, prefix_cache=False, prefill_chunk=8
    )
    assert a == b
    assert ea.spec_drafted > 0, "repetitive fixture must actually draft"
    assert all(len(t) == n for t, n in zip(a, lens))


def test_spec_sampled_no_drafts_is_bitwise_spec_off():
    """Contract (c): with a proposer that never drafts, every verify
    dispatch degenerates to the decode sampler — the sampled spec-on
    stream is BITWISE the spec-off stream (same derived per-request
    keys, same arithmetic). This anchors the verify program's row-0
    sampler to the plain window."""
    model = _model()
    prompts = _prompts(3)
    lens = [8, 10, 6]
    off, _ = _run_sampled(model, prompts, lens, speculate=0)
    on, eng = _run_sampled(
        model, prompts, lens, speculate=4, proposer=_EmptyProposer()
    )
    assert on == off
    assert eng.spec_drafted == 0


def test_spec_sampled_soft_proposer_dispatch_win():
    """The perf claim at temperature > 0: a near-oracle soft proposer
    (q ~= p) gets drafts ACCEPTED through the rejection sampler, so a
    single slot emits more than one token per decode dispatch on the
    repetitive-prompt fixture — E[accepted] + 1 per verify launch."""
    model = _model()
    prompt = _rep_prompts(1)[0]
    n_new = 16
    prop = _SoftModelProposer(model, 0.8, 20)
    eng = ServingEngine(
        model, slots=1, page_size=8, window=4, temperature=0.8, top_k=20,
        cache_dtype=jnp.float32, speculate=4, proposer=prop, seed=3,
    )
    rid = eng.submit(prompt, n_new, seed=0)
    fin = eng.run()
    assert len(fin[rid].tokens) == n_new
    st = eng.stats()
    assert st["spec_accepted_tokens"] > 0, st
    assert st["tokens_per_dispatch"] > 1.0, st
    assert st["decode_dispatches"] < n_new, st


@pytest.mark.slow
def test_spec_sampled_invariance_matrix_slow():
    """Contract (a), full single-chip matrix: within each arithmetic
    cell (f32 pool; int8-quantized bf16 pool) the sampled spec-on
    stream is bitwise identical across slots, prefix cache on/off,
    chunked prefill, and page pressure with eviction/re-admission.
    Cross-cell equality is NOT asserted — kv-quant changes the
    arithmetic. tp=2 rides test_serving_sharded.py."""
    model = _model()
    prompts = _rep_prompts(3)
    lens = [10, 12, 8]
    scheds = (
        dict(slots=2, prefix_cache=True),
        dict(slots=1, prefix_cache=False),
        dict(slots=3, prefill_chunk=8),
        dict(slots=2, prefill_chunk=5, num_pages=7, prefix_cache=True),
    )
    for arith in (
        dict(cache_dtype=jnp.float32),
        dict(kv_quant="int8", cache_dtype=jnp.bfloat16),
    ):
        base = None
        for sched in scheds:
            toks, eng = _run_sampled(model, prompts, lens, **arith, **sched)
            if "num_pages" in sched:
                assert eng.evictions > 0, "pressure leg was sized to evict"
            if base is None:
                base = toks
            assert toks == base, (arith, sched)


@pytest.mark.slow
def test_spec_sampled_statistical_faithfulness_slow():
    """Contract (b): distributional exactness of accept / residual /
    bonus. The proposer claims a DELIBERATELY mismatched q (flatter:
    q_temperature 1.6 vs verify 0.8), so a large fraction of drafts
    reject and the residual resample carries real probability mass —
    exactness must come from the rejection arithmetic, not from q ~= p.
    Over a seed ensemble: position 0 is bitwise spec-off (same derived
    key, same carried prefill logits); later positions pass two-sample
    TV + pooled chi-square gates sized generously above the N-sample
    noise floor (expected TV ~ sqrt(k / (pi N)) ~= 0.13 at k = 20,
    N = 300; deterministic seeds, no flake)."""
    model = _model()
    prompt = _prompts(1, base_len=8)[0]
    N, n_new = 300, 3

    def ensemble(**kw):
        eng = ServingEngine(
            model, slots=4, page_size=8, window=4, temperature=0.8,
            top_k=20, cache_dtype=jnp.float32, prefix_cache=True, seed=3,
            **kw,
        )
        rids = [eng.submit(prompt, n_new, seed=i) for i in range(N)]
        fin = eng.run()
        return np.asarray([fin[r].tokens for r in rids]), eng

    off, _ = ensemble()
    on, eng = ensemble(
        speculate=3,
        proposer=_SoftModelProposer(model, 0.8, 20, q_temperature=1.6),
    )
    st = eng.stats()
    assert st["spec_drafted_tokens"] > 0
    # the mismatched q must actually reject (residual path under test)
    assert st["spec_acceptance_rate"] < 0.9, st
    np.testing.assert_array_equal(on[:, 0], off[:, 0])
    for j in range(1, n_new):
        ca = np.bincount(off[:, j], minlength=CFG.vocab_size)
        cb = np.bincount(on[:, j], minlength=CFG.vocab_size)
        tv = 0.5 * np.abs(ca / N - cb / N).sum()
        assert tv < 0.25, (j, tv)
        # pooled two-sample chi-square, no scipy: merge cells with < 10
        # pooled counts, stat ~ chi2(df) under H0, gate at ~4 sigma
        pooled = ca + cb
        big = pooled >= 10
        a = np.append(ca[big], ca[~big].sum()).astype(np.float64)
        b = np.append(cb[big], cb[~big].sum()).astype(np.float64)
        keep = (a + b) > 0
        a, b = a[keep], b[keep]
        stat = ((a - b) ** 2 / (a + b)).sum()
        df = max(len(a) - 1, 1)
        assert stat < df + 4.0 * np.sqrt(2.0 * df), (j, stat, df)
