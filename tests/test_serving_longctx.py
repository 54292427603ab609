"""Long-context serving (ISSUE 19): sequence-parallel prefill +
host-RAM cold-page spill — the landing gates asserted directly.

- **SP prefill bit-identity**: ``prefill_sp="on"`` shards the prefill
  chunk's query rows over the 'tensor' axis but runs the off-path
  arithmetic verbatim (the choreo prover's sp leg proves zero added
  arithmetic; these tests pin the streams). Greedy AND sampled streams
  are bitwise identical to ``prefill_sp="off"`` — and to the
  single-chip engine — across cache x chunk x spec x kv-quant at tp=2
  (fast) and tp=4 (slow). Decode programs are
  untouched by construction (separate ``_PROGRAM_CACHE`` entries; the
  resolved sp value forks only the prefill-chunk key).
- **Spill bit-identity**: with ``spill="on"`` cold prefix pages move to
  host RAM instead of being reclaimed and fault back byte-exactly
  through the jitted page-write path, so pressured streams equal the
  ample-pool reference bit for bit — including eviction-under-pressure
  mid-spill (a bounded host budget forcing discards), COW against a
  spilled parent page, and a disaggregated handoff whose prefix chain
  is partially spilled on the prefill replica.
- **Accounting**: the allocator identity plus the extended spill ledger
  (resident-indexed and spilled node sets disjoint, spill store and
  index in bijection, spilled subtrees closed downward) re-check after
  EVERY scheduler step in spill mode.
- **No-wedge acceptance**: a pool smaller than a long request's chain
  plus its concurrent short traffic finishes everything — parking +
  spill absorb the pressure; nothing raises ``PoolOverloaded`` and the
  long prompt's chain survives (host-side) to serve a fault-back hit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import MeshConfig, ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.parallel.mesh import create_mesh
from midgpt_tpu.serving import ServingCluster, ServingEngine, pages_needed
from midgpt_tpu.serving.engine import _PROGRAM_CACHE

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)


@pytest.fixture(scope="module")
def model():
    return GPT.init(jax.random.PRNGKey(0), CFG)


def _mesh(tp):
    return create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=1, tensor=tp),
        devices=jax.devices()[:tp],
    )


def _prompts(n, base_len=5, stride=3, seed0=100):
    return [
        np.asarray(
            jax.random.randint(
                jax.random.PRNGKey(seed0 + i), (base_len + stride * i,), 0,
                CFG.vocab_size,
            )
        )
        for i in range(n)
    ]


def _run(model, mesh, prompts, n_new, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("window", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    eng = ServingEngine(model, mesh=mesh, **kw)
    rids = [eng.submit(p, n_new, seed=i) for i, p in enumerate(prompts)]
    finished = eng.run()
    return [list(map(int, finished[r].tokens)) for r in rids], eng


def _check(eng):
    """Allocator identity + prefix-index structure + the spill ledger
    (store/index bijection, downward closure) in one call."""
    eng.alloc.check()
    if eng.index is not None:
        eng.index.check(eng.alloc, eng._spill_store)


def _force_spill(eng, k=None):
    """Push ``k`` coldest-eligible cached pages (all of them when None)
    out to the host store through the engine's own reservation path —
    the same code a pressured admit runs, just without needing filler
    traffic to generate the pressure."""
    assert eng._spill_store is not None
    target = (
        eng.alloc.num_pages if k is None else eng.alloc.free_pages + k
    )
    eng._try_reserve(target)


# ---------------------------------------------------------------------------
# sequence-parallel prefill: resolution + bit-identity
# ---------------------------------------------------------------------------


def test_sp_resolution_and_program_cache_fork(model):
    """"auto" turns on exactly when the mesh has a tensor axis; the
    RESOLVED value rides the prefill-chunk program-cache key (decode
    keys untouched), so on/off engines never share a compilation."""
    single = ServingEngine(model, slots=1, page_size=8, window=2)
    assert single.prefill_sp == "off"  # no axis to shard over
    tp_auto = ServingEngine(
        model, slots=1, page_size=8, window=2, mesh=_mesh(2)
    )
    assert tp_auto.prefill_sp == "on"
    tp_off = ServingEngine(
        model, slots=1, page_size=8, window=2, mesh=_mesh(2),
        prefill_sp="off",
    )
    assert tp_off.prefill_sp == "off"
    # run one tiny prompt through each resolved mode: the cache must
    # hold prefill_chunk entries for BOTH sp values (key slot 5), and
    # no decode/verify key carries an sp field at all
    for eng in (tp_auto, tp_off):
        eng.submit(_prompts(1)[0], 2, seed=0)
        eng.run()
    sps = {k[5] for k in _PROGRAM_CACHE if k[0] == "prefill_chunk"}
    assert {"on", "off"} <= sps
    assert all(
        k[0] in ("prefill_chunk", "decode_window", "verify_program")
        or "sp" not in str(k[0])
        for k in _PROGRAM_CACHE
    )


def test_sp_prefill_greedy_identity_tp2(model):
    """The tentpole gate, fast shape: long-ish chunked prompts, greedy —
    sp=on streams equal sp=off on the SAME tp=2 mesh AND the single-chip
    engine, bit for bit, with the prefix cache exercised."""
    prompts = _prompts(3, base_len=20, stride=6)
    kw = dict(page_size=8, prefill_chunk=8, prefix_cache=True)
    ref, _ = _run(model, None, prompts, 10, **kw)
    off, _ = _run(model, _mesh(2), prompts, 10, prefill_sp="off", **kw)
    on, eng = _run(model, _mesh(2), prompts, 10, prefill_sp="on", **kw)
    assert on == off == ref
    assert eng.prefill_sp == "on"


def test_sp_prefill_sampled_identity_tp2(model):
    """Sampled streams (temperature + top_k, per-request seeds): the
    sp=on engine draws the identical token sequence — sampling reads
    logits, and sp must not perturb a single bit of them."""
    prompts = _prompts(3, base_len=16, stride=5)
    kw = dict(
        page_size=8, prefill_chunk=8, temperature=0.8, top_k=16,
    )
    off, _ = _run(model, _mesh(2), prompts, 12, prefill_sp="off", **kw)
    on, _ = _run(model, _mesh(2), prompts, 12, prefill_sp="on", **kw)
    assert on == off


# ---------------------------------------------------------------------------
# host spill: bit-identity + fault-back + accounting
# ---------------------------------------------------------------------------


def test_spill_pressure_greedy_identity_and_faultback(model):
    """A pool too small for the trace's chains: cold pages spill
    instead of being reclaimed, streams stay bitwise the ample-pool
    reference, and resubmitting the prompts hits the HOST-side prefix
    (fault-back > 0) with the same streams again."""
    prompts = _prompts(4, base_len=22, stride=0, seed0=300)
    kw = dict(page_size=8, prefill_chunk=8, prefix_cache=True)
    ref, _ = _run(model, None, prompts, 12, **kw)
    got, eng = _run(
        model, None, prompts, 12, num_pages=8, spill="on", **kw
    )
    assert got == ref
    st = eng.stats()
    assert st["spilled_pages"] > 0, "pool pressure never materialized"
    _check(eng)
    # resubmit the same prompts on the SAME engine: matches walk onto
    # spilled nodes and fault back byte-exactly
    rids = [eng.submit(p, 12, seed=i) for i, p in enumerate(prompts)]
    fin = eng.run()
    again = [list(map(int, fin[r].tokens)) for r in rids]
    assert again == ref
    assert eng.stats()["spill_faultback_pages"] > 0
    _check(eng)


def test_spill_kv8_scale_planes_travel_with_payload(model):
    """int8 KV pool under spill: the per-(page, head) scale planes spill
    and fault back WITH their payloads — a stale scale on a revived
    page would be deterministic silent corruption, caught here as a
    stream mismatch."""
    prompts = _prompts(3, base_len=22, stride=0, seed0=400)
    kw = dict(
        page_size=8, prefill_chunk=8, kv_quant="int8", prefix_cache=True
    )
    ref, _ = _run(model, None, prompts, 10, **kw)
    got, eng = _run(
        model, None, prompts, 10, num_pages=7, spill="on", **kw
    )
    assert got == ref
    assert eng.stats()["spilled_pages"] > 0
    rids = [eng.submit(p, 10, seed=i) for i, p in enumerate(prompts)]
    fin = eng.run()
    assert [list(map(int, fin[r].tokens)) for r in rids] == ref
    assert eng.stats()["spill_faultback_pages"] > 0
    _check(eng)


def test_spill_sampled_identity(model):
    """Sampled spill streams: temperature > 0 with per-request seeds —
    pressure + spill + fault-back must not shift the sampled sequence
    by a single draw."""
    prompts = _prompts(3, base_len=22, stride=0, seed0=500)
    kw = dict(
        page_size=8, prefill_chunk=8, temperature=0.8, top_k=16,
        prefix_cache=True,
    )
    ref, _ = _run(model, None, prompts, 12, **kw)
    got, eng = _run(
        model, None, prompts, 12, num_pages=7, spill="on", **kw
    )
    assert got == ref
    assert eng.stats()["spilled_pages"] > 0
    _check(eng)


def test_spill_prefetch_batches_faultbacks_and_keeps_streams(
    model, monkeypatch
):
    """Prefetch-on-queue (ISSUE 20): the scheduler probes the wait-queue
    head's prompt each step and fault-backs its matched spilled nodes
    BEFORE admission in ONE batched import_pages call. Streams must stay
    bitwise identical to fault-on-match (imports are byte-exact either
    way) while the import dispatch count on the TTFT path DROPS — the
    per-node fault_back calls collapse into per-step batches."""
    import midgpt_tpu.serving.engine as engine_mod

    prompts = _prompts(4, base_len=22, stride=0, seed0=700)
    kw = dict(page_size=8, prefill_chunk=8, prefix_cache=True)
    ref, _ = _run(model, None, prompts, 12, **kw)
    real_import = engine_mod.import_pages
    calls = {}
    engines = {}
    for mode in ("off", "on"):
        counter = {"n": 0}

        def counting(pool, ids, *a, _c=counter, **k2):
            _c["n"] += 1
            return real_import(pool, ids, *a, **k2)

        monkeypatch.setattr(engine_mod, "import_pages", counting)
        got, eng = _run(
            model, None, prompts, 12, num_pages=8, spill="on",
            spill_prefetch=mode, **kw
        )
        assert got == ref
        assert eng.stats()["spilled_pages"] > 0
        assert eng.stats()["spill_resident_pages"] > 0
        _check(eng)
        # resubmit the same prompts: matches walk onto spilled nodes —
        # the import calls from HERE to stream completion are the
        # revival dispatches on the resubmits' TTFT path
        base = counter["n"]
        rids = [eng.submit(p, 12, seed=i) for i, p in enumerate(prompts)]
        fin = eng.run()
        assert [list(map(int, fin[r].tokens)) for r in rids] == ref
        _check(eng)
        calls[mode] = counter["n"] - base
        engines[mode] = eng
    st_on, st_off = engines["on"].stats(), engines["off"].stats()
    assert st_off["spill_prefetch_pages"] == 0
    assert st_on["spill_prefetch_pages"] > 0
    assert st_on["spill_faultback_pages"] > 0
    assert calls["on"] > 0 and calls["off"] > 0
    assert calls["on"] < calls["off"], (calls, st_on, st_off)


def test_eviction_under_pressure_mid_spill(model):
    """spill_budget_pages bounds host residency: past it the oldest
    spilled prefixes are discarded outright (true reclaim resumes, the
    degradation floor) — the engine keeps serving, streams stay
    bitwise, and the ledger stays consistent through the spill/discard
    churn."""
    prompts = _prompts(5, base_len=22, stride=0, seed0=600)
    kw = dict(page_size=8, prefill_chunk=8, prefix_cache=True)
    ref, _ = _run(model, None, prompts, 12, **kw)
    got, eng = _run(
        model, None, prompts, 12, num_pages=8, spill="on",
        spill_budget_pages=3, **kw
    )
    assert got == ref
    st = eng.stats()
    assert st["spilled_pages"] > 0
    assert st["spill_discards"] > 0, "budget never forced a discard"
    assert st["spill_resident_pages"] <= 3
    _check(eng)


def test_spill_store_nbytes_counter_and_protected_discard():
    """HostSpillStore.nbytes is a running counter (put/pop — the
    telemetry gauge must not walk every payload per sample), and
    PrefixIndex.discard_spilled_oldest honors the protect set an
    in-flight fault-back passes."""
    from midgpt_tpu.serving.paged import HostSpillStore, PrefixIndex

    store = HostSpillStore(budget_pages=1)
    x = np.arange(8, dtype=np.float32)
    store.put(-2, (x, x, None, None))
    store.put(-3, (x, x, x, x))
    assert store.nbytes == 6 * x.nbytes
    store.pop(-2)
    assert store.nbytes == 4 * x.nbytes
    store.pop(-3)
    assert store.nbytes == 0

    idx = PrefixIndex(2)
    p0 = idx.register(PrefixIndex._ROOT, (1, 2), 0)
    p1 = idx.register(p0, (3, 4), 1)
    idx.touch_cold(p1)
    idx.touch_cold(p0)
    v1 = idx.spill(p1)  # deepest-first: the tail spills oldest
    v0 = idx.spill(p0)
    # whole chain protected: nothing is discardable
    assert idx.discard_spilled_oldest({v0, v1}) is None
    # tail protected only: v0 still has a (spilled) child -> still None
    assert idx.discard_spilled_oldest({v1}) is None
    # unprotected: oldest childless (the tail) goes first
    assert idx.discard_spilled_oldest() == v1
    assert idx.discard_spilled_oldest({v1}) == v0


def test_budget_discard_protects_inflight_faultback_chain(model):
    """Regression: a fault-back's own reservation can spill victims
    past spill_budget_pages, and the budget-discard pass used to drop
    the oldest CHILDLESS spilled node — deepest-first spill makes that
    exactly the tail of the chain being materialized. The chain's vids
    are now protected for the duration (host residency transiently
    overshoots, then drains as the fault-back pops the payloads);
    before, the in-flight vid was discarded out from under _fault_back
    (KeyError at the store pop), or a later chain node silently
    survived as a negative virtual id in the slot's block table."""
    ps = 8
    a = _prompts(1, base_len=20, stride=0, seed0=800)[0]  # 2-node chain
    b = _prompts(1, base_len=28, stride=0, seed0=801)[0]  # fills the pool
    kw = dict(page_size=ps, prefill_chunk=8, prefix_cache=True)
    ref, _ = _run(model, None, [a], 3, **kw)
    eng = ServingEngine(
        model, slots=2, page_size=ps, window=4,
        cache_dtype=jnp.float32, prefill_chunk=8, spill="on",
        spill_budget_pages=2, num_pages=4,
    )
    r1 = eng.submit(a, 3, seed=0)
    assert list(map(int, eng.run()[r1].tokens)) == ref[0]
    _force_spill(eng)  # a's 2-node chain -> host, store AT budget
    assert len(eng._spill_store) == 2
    r2 = eng.submit(b, 3, seed=1)
    eng.run()  # b's cold chain occupies all but one HBM page
    assert eng.alloc.free_pages == 1
    # the host budget tightens below the spilled chain between
    # admissions: the next discard pass runs with a's nodes oldest AND
    # the pool pressured enough that a's own fault-back must spill b
    eng._spill_store.budget_pages = 1
    r3 = eng.submit(a, 3, seed=0)
    fin = eng.run()
    assert list(map(int, fin[r3].tokens)) == ref[0]
    st = eng.stats()
    assert st["spill_faultback_pages"] >= 2  # both chain nodes revived
    assert st["spill_discards"] > 0  # budget pressed mid-admission
    _check(eng)


def test_cow_on_spilled_parent_page(model):
    """A new request sharing a PARTIAL page with a spilled chain: the
    COW source page faults back from host before it is copied. Chain
    [p0, p1, p2] spills deepest-first; prompt B = A's first 12 tokens
    matches p0 fully and extends 4 tokens INTO p1 (spilled) -> the COW
    candidate is a virtual node, faulted back then copied — bitwise
    the no-spill run."""
    ps = 8
    a = _prompts(1, base_len=2 * ps, stride=0, seed0=700)[0]  # 2 pages
    b = a[: ps + 4]  # pure prefix ending mid-page-1: the COW shape
    # reference: same two requests, ample pool, no spill
    ref, _ = _run(
        model, None, [a, b], 8, page_size=ps, prefill_chunk=8,
        prefix_cache=True,
    )
    eng = ServingEngine(
        model, slots=2, page_size=ps, window=4,
        cache_dtype=jnp.float32, prefill_chunk=8, spill="on",
    )
    r1 = eng.submit(a, 8, seed=0)
    fin = eng.run()
    got_a = list(map(int, fin[r1].tokens))
    # a's chain is cold: spill it ENTIRELY so the match-walk must fault
    # the COW source back from the host store
    _force_spill(eng)
    assert eng.stats()["spilled_pages"] >= 2
    assert eng.index.coldest_leaf() is None  # nothing resident-cold left
    _check(eng)
    r2 = eng.submit(b, 8, seed=1)
    fin = eng.run()
    got_b = list(map(int, fin[r2].tokens))
    assert [got_a, got_b] == ref
    assert eng.stats()["spill_faultback_pages"] >= 2  # p0 + the COW src
    _check(eng)


def test_spill_invariants_property_loop(model):
    """Property-style: a busy shared-prefix trace in spill mode with
    real pressure — after EVERY scheduler step the allocator identity
    holds, the index/store ledger agrees (disjoint resident/spilled
    sets, downward closure — index.check with the store), LRU holds
    only refcount-0 resident pages, and writer pages have exactly one
    owner."""
    sys_prompt = _prompts(1, base_len=16, seed0=800)[0]
    tails = _prompts(6, base_len=2, stride=1, seed0=810)
    prompts = [np.concatenate([sys_prompt, t]) for t in tails]
    eng = ServingEngine(
        model, slots=2, page_size=8, num_pages=10, window=4,
        temperature=0.0, cache_dtype=jnp.float32, prefix_cache=True,
        prefill_chunk=8, spill="on",
    )
    rids = [eng.submit(p, 10, seed=i) for i, p in enumerate(prompts)]
    steps = 0
    while (eng.queue or eng._active_slots()) and steps < 500:
        eng.step()
        steps += 1
        _check(eng)
        # every indexed node is resident-or-spilled, never both; the
        # spilled count and the host store agree
        spilled = {n for n in eng.index._meta if eng.index.is_spilled(n)}
        assert len(spilled) == len(eng._spill_store)
        for s in eng._active_slots():
            for pg in eng.slot_pages[s]:
                assert pg >= 0 and not eng.index.is_spilled(pg)
                if pg in eng.index:
                    continue
                assert eng.alloc.refcount(pg) == 1, (
                    f"writer page {pg} shared"
                )
    assert steps < 500, "engine did not drain"
    assert eng.alloc.held_pages == 0
    assert (
        eng.alloc.free_pages + eng.alloc.cached_pages
        == eng.alloc.num_pages
    )
    for r in rids:
        assert len(eng.finished[r].tokens) == 10
    assert eng.stats()["spilled_pages"] > 0, "trace never pressured"


# ---------------------------------------------------------------------------
# composition: sp + spill, disagg handoff, the no-wedge acceptance gate
# ---------------------------------------------------------------------------


def test_sp_and_spill_compose_tp2(model):
    """Both tentpole halves at once: tp=2 SP prefill over a pool small
    enough to spill — streams bitwise the single-chip ample-pool
    engine."""
    prompts = _prompts(3, base_len=22, stride=0, seed0=900)
    kw = dict(page_size=8, prefill_chunk=8, prefix_cache=True)
    ref, _ = _run(model, None, prompts, 10, **kw)
    got, eng = _run(
        model, _mesh(2), prompts, 10, prefill_sp="on", spill="on",
        num_pages=7, **kw
    )
    assert got == ref
    assert eng.prefill_sp == "on"
    assert eng.stats()["spilled_pages"] > 0
    _check(eng)


def test_disagg_handoff_of_partially_spilled_chain(model):
    """Disaggregated pools with spill on the prefill replica: turn 1
    hands off and its prompt chain retires cold on the prefill engine;
    we spill PART of that chain (deepest-first, so the spilled nodes
    are a suffix); turn 2 (prompt + turn-1 output + new tokens) prefix-
    matches the partially-spilled chain, faults the suffix back, and
    hands off — bitwise the monolithic engine serving the same two
    turns."""
    kw = dict(
        slots=2, page_size=8, window=4, cache_dtype=jnp.float32,
        prefill_chunk=8, prefix_cache=True, spill="on",
    )
    a = _prompts(1, base_len=26, stride=0, seed0=1000)[0]
    # monolithic reference, turn by turn
    mono = ServingEngine(model, **kw)
    r1 = mono.submit(a, 8, seed=0)
    ref1 = list(map(int, mono.run()[r1].tokens))
    b = np.concatenate(
        [a, np.asarray(ref1, np.int32),
         _prompts(1, base_len=5, stride=0, seed0=1001)[0]]
    )
    r2 = mono.submit(b, 8, seed=1)
    ref2 = list(map(int, mono.run()[r2].tokens))

    cl = ServingCluster(
        model, prefill_replicas=1, decode_replicas=1, **kw
    )
    rid1 = cl.submit(a, 8, seed=0)
    while cl.has_work:
        cl.step()
        for i in cl._alive():
            _check(cl.engines[i])
    cl._harvest()
    assert list(map(int, cl.finished[rid1].tokens)) == ref1
    pre = next(e for e in cl.engines if e.role == "prefill")
    # spill a strict subset of a's prompt chain (the deepest pages)
    chain_pages = pre.alloc.cached_pages
    assert chain_pages >= 3, "prefill replica retained no chain"
    _force_spill(pre, 2)
    st = pre.stats()
    assert st["spilled_pages"] == 2
    assert 0 < st["spill_resident_pages"] < chain_pages
    _check(pre)
    rid2 = cl.submit(b, 8, seed=1)
    while cl.has_work:
        cl.step()
        for i in cl._alive():
            _check(cl.engines[i])
    cl._harvest()
    assert list(map(int, cl.finished[rid2].tokens)) == ref2
    assert pre.stats()["spill_faultback_pages"] > 0


def test_long_prompt_completes_in_undersized_pool_no_wedge(model):
    """The acceptance gate: the pool is smaller than the long request's
    chain plus its concurrent short traffic (8 pages vs a 7-page
    lifetime + 2 pages per short) — parking + spill absorb the
    pressure, every request finishes bitwise-correct, nothing raises
    PoolOverloaded, and the long chain survives host-side to serve a
    fault-back hit afterwards."""
    ps = 8
    long_p = _prompts(1, base_len=40, stride=0, seed0=1100)[0]
    shorts = _prompts(4, base_len=6, stride=0, seed0=1110)
    lifetime = pages_needed(len(long_p) + 16, ps)
    assert lifetime == 7
    # ample-pool references
    ref_long, _ = _run(
        model, None, [long_p], 16, page_size=ps, prefill_chunk=8
    )
    ref_short, _ = _run(
        model, None, shorts, 8, page_size=ps, prefill_chunk=8
    )
    eng = ServingEngine(
        model, slots=2, page_size=ps, num_pages=8, window=4,
        cache_dtype=jnp.float32, prefill_chunk=8, prefix_cache=True,
        spill="on",
    )
    assert eng.alloc.num_pages < lifetime + pages_needed(6 + 8, ps)
    rl = eng.submit(long_p, 16, seed=0)
    rs = [eng.submit(p, 8, seed=1 + i) for i, p in enumerate(shorts)]
    steps = 0
    while (eng.queue or eng._active_slots()) and steps < 600:
        eng.step()  # PoolOverloaded here would fail the test outright
        steps += 1
        _check(eng)
    assert steps < 600, "engine wedged under long+short pressure"
    fin = eng.finished
    assert list(map(int, fin[rl].tokens)) == ref_long[0]
    assert [list(map(int, fin[r].tokens)) for r in rs] == ref_short
    st = eng.stats()
    assert st["spilled_pages"] > 0, "undersized pool never spilled"
    assert st["deferred_submits"] == 0 and st["shed_requests"] == 0
    # the long chain is still matchable (host or resident): resubmit
    # and require a fault-back hit with the identical stream
    r2 = eng.submit(long_p, 16, seed=0)
    fin = eng.run()
    assert list(map(int, fin[r2].tokens)) == ref_long[0]
    assert st["spill_faultback_pages"] <= eng.stats()[
        "spill_faultback_pages"
    ]
    _check(eng)


# ---------------------------------------------------------------------------
# slow tier: the full identity matrix (CI serving-longctx job)
# ---------------------------------------------------------------------------

MATRIX_SLOW = [
    pytest.param(True, None, 0, None, id="cache"),
    pytest.param(False, 8, 0, None, id="chunked-nocache"),
    pytest.param(True, 8, 3, None, id="chunked-spec"),
    pytest.param(True, 8, 0, "int8", id="chunked-kv8"),
    pytest.param(True, 8, 3, "int8", id="chunked-spec-kv8"),
    pytest.param(True, 8, 0, None, id="chunked"),
]


@pytest.mark.slow
@pytest.mark.parametrize("cache,chunk,spec,kvq", MATRIX_SLOW)
def test_sp_identity_matrix_tp2_slow(model, cache, chunk, spec, kvq):
    prompts = _prompts(3, base_len=18, stride=4, seed0=1200)
    kw = dict(
        page_size=8, prefix_cache=cache, prefill_chunk=chunk,
        speculate=spec, kv_quant=kvq,
    )
    off, _ = _run(model, _mesh(2), prompts, 10, prefill_sp="off", **kw)
    on, _ = _run(model, _mesh(2), prompts, 10, prefill_sp="on", **kw)
    assert on == off


@pytest.mark.slow
@pytest.mark.parametrize(
    "temperature", [0.0, 0.8], ids=["greedy", "sampled"]
)
def test_sp_identity_tp4_slow(model, temperature):
    prompts = _prompts(3, base_len=18, stride=4, seed0=1300)
    kw = dict(
        page_size=8, prefill_chunk=8, temperature=temperature,
        top_k=16 if temperature else None,
    )
    ref, _ = _run(model, None, prompts, 10, **kw)
    on, _ = _run(model, _mesh(4), prompts, 10, prefill_sp="on", **kw)
    assert on == ref


@pytest.mark.slow
@pytest.mark.parametrize(
    "spec,kvq",
    [
        pytest.param(0, None, id="plain"),
        pytest.param(3, None, id="spec"),
        pytest.param(0, "int8", id="kv8"),
        pytest.param(3, "int8", id="spec-kv8"),
    ],
)
def test_spill_identity_matrix_slow(model, spec, kvq):
    prompts = _prompts(4, base_len=22, stride=0, seed0=1400)
    kw = dict(
        page_size=8, prefill_chunk=8, prefix_cache=True,
        speculate=spec, kv_quant=kvq,
    )
    ref, _ = _run(model, None, prompts, 12, **kw)
    got, eng = _run(
        model, None, prompts, 12, num_pages=8, spill="on", **kw
    )
    assert got == ref
    assert eng.stats()["spilled_pages"] > 0
    _check(eng)
