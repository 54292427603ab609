"""The async streaming front door (midgpt_tpu.serving.frontdoor):
per-request token streams bit-identical to the synchronous loop across
the feature matrix, cancellation-safe teardown (allocator + PrefixIndex
invariants property-checked after every scheduler step; pages retire
cold so prefix hits survive; survivors bit-identical to a
never-submitted run), priority admission with a PROVEN aging starvation
bound, pre-dispatch deadline sheds (typed outcome, virtual clock),
awaitable defer backpressure, deterministic cluster tie-breaks, and the
chaos composition acceptance gate: cancel + crash + deadline-shed in
one scripted plan with replay-identical event sequences."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import (
    AdmissionRejected,
    AsyncFrontDoor,
    Cancelled,
    DeadlineExceeded,
    FaultPlan,
    PoolOverloaded,
    ServingCluster,
    ServingEngine,
    VirtualClock,
)

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)

_KW = dict(
    slots=2, page_size=8, window=4, temperature=0.0,
    cache_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def model():
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


def run(coro):
    """Drive an async test body (no pytest-asyncio dependency)."""
    return asyncio.run(coro)


def _sync_refs(model, prompts, n_new=8, kw=None, seeds=None):
    eng = ServingEngine(model, **(kw or _KW))
    seeds = seeds if seeds is not None else list(range(len(prompts)))
    rids = [
        eng.submit(p, n_new, seed=s) for p, s in zip(prompts, seeds)
    ]
    fin = eng.run()
    return [list(map(int, fin[r].tokens)) for r in rids]


async def _drain_all(fd):
    while await fd.pump():
        pass


# ---------------------------------------------------------------------------
# VirtualClock
# ---------------------------------------------------------------------------


def test_virtual_clock():
    c = VirtualClock()
    assert c() == 0.0 and c() == 0.0  # tick=0: reads don't advance
    assert c.advance(2.5) == 2.5 and c() == 2.5
    t = VirtualClock(start=1.0, tick=0.5)
    assert t() == 1.0 and t() == 1.5  # tick: deterministic auto-advance


# ---------------------------------------------------------------------------
# Bit-identity: streams through the front door == the synchronous loop
# ---------------------------------------------------------------------------


def test_stream_tokens_match_sync_loop(model):
    """Manual-pump drive, default feature combo: every stream's tokens
    are bitwise the synchronous ``run()`` harvest, invariants checked
    after every scheduler round."""
    prompts = _prompts(4)
    refs = _sync_refs(model, prompts)

    async def go():
        eng = ServingEngine(model, **_KW)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        streams = [
            await fd.submit(p, 8, seed=i) for i, p in enumerate(prompts)
        ]
        await _drain_all(fd)
        return streams

    streams = run(go())
    assert [s.tokens for s in streams] == refs
    assert [s.outcome for s in streams] == ["finished"] * 4


def test_background_driver_streams_match_sync_loop(model):
    """The real serving mode: background driver task (step in a worker
    thread), tokens consumed with ``async for`` — same streams."""
    prompts = _prompts(4)
    refs = _sync_refs(model, prompts)

    async def go():
        eng = ServingEngine(model, **_KW)
        async with AsyncFrontDoor(eng) as fd:
            streams = [
                await fd.submit(p, 8, seed=i)
                for i, p in enumerate(prompts)
            ]

            async def consume(st):
                return [t async for t in st]

            got = await asyncio.gather(*(consume(s) for s in streams))
        return got

    assert run(go()) == refs


_MATRIX = [
    # (prefix_cache, chunk, spec, kvq)
    (False, None, 0, None),
    (True, 8, 0, None),
    (True, 8, 4, None),
    (True, None, 4, "int8"),
    (False, 8, 0, "int8"),
]


@pytest.mark.slow
@pytest.mark.parametrize(
    "cache,chunk,spec,kvq", _MATRIX,
    ids=["nocache", "cache-chunk", "chunk-spec", "spec-kvq8",
         "nocache-chunk-kvq8"],
)
def test_stream_identity_matrix(model, cache, chunk, spec, kvq):
    """The acceptance bit-identity gate across cache x chunk x spec x
    kv-quant: front-door streams == synchronous loop."""
    kw = dict(
        _KW, prefix_cache=cache, prefill_chunk=chunk, speculate=spec,
        kv_quant=kvq,
    )
    prompts = _prompts(5, base_len=5, stride=2)
    refs = _sync_refs(model, prompts, n_new=12, kw=kw)

    async def go():
        eng = ServingEngine(model, **kw)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        streams = [
            await fd.submit(p, 12, seed=i) for i, p in enumerate(prompts)
        ]
        await _drain_all(fd)
        return streams

    streams = run(go())
    assert [s.tokens for s in streams] == refs


@pytest.mark.slow
def test_telemetry_inert_through_frontdoor(model):
    """Tracing through the front door changes nothing: identical
    streams with telemetry on vs off, and the traced run produced
    events (cancellation included in the taxonomy)."""
    prompts = _prompts(3)

    async def go(telemetry):
        eng = ServingEngine(model, telemetry=telemetry, **_KW)
        fd = AsyncFrontDoor(eng)
        streams = [
            await fd.submit(p, 8, seed=i) for i, p in enumerate(prompts)
        ]
        streams[1].cancel()
        await _drain_all(fd)
        return eng, [s.tokens for s in streams]

    eng_on, on = run(go(True))
    _, off = run(go(False))
    assert on == off
    kinds = {ev.kind for ev in eng_on.telemetry.events}
    assert "cancelled" in kinds


# ---------------------------------------------------------------------------
# Cancellation-safe teardown
# ---------------------------------------------------------------------------


def test_cancel_releases_slot_and_pages_cold(model):
    """Cancel mid-decode: the slot frees immediately at the boundary,
    the allocator identity holds, and the cancelled request's pages
    retired COLD — a follow-up request with the same prompt hits the
    prefix cache on them."""
    prompts = _prompts(2, base_len=17, stride=0)

    async def go():
        eng = ServingEngine(model, **_KW)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        st = await fd.submit(prompts[0], 16, seed=0)
        for _ in range(3):
            await fd.pump()
        assert st.tokens, "request should be mid-decode"
        st.cancel()
        await fd.pump()
        assert st.outcome == "cancelled"
        assert eng._active_slots() == [], "slot must be reclaimed"
        assert eng.alloc.held_pages == 0
        assert eng.alloc.cached_pages > 0, "pages must retire cold"
        with pytest.raises(Cancelled):
            await st.result()
        # same prompt again: the cold pages serve prefix hits
        st2 = await fd.submit(prompts[0], 8, seed=0)
        await _drain_all(fd)
        assert st2.outcome == "finished"
        assert eng.prompt_tokens_cached > 0, (
            "prefix hits must survive the cancellation"
        )
        st3 = await fd.submit(prompts[0], 8, seed=0)  # idempotent cancel
        st3.cancel()
        st3.cancel()
        await _drain_all(fd)
        assert eng.stats()["cancelled_requests"] == 2
        return True

    assert run(go())


def _never_submitted_ref(model, kw, survivor_prompt, n_new):
    eng = ServingEngine(model, **kw)
    rid = eng.submit(survivor_prompt, n_new, seed=1)
    return list(map(int, eng.run()[rid].tokens))


def test_cancel_during_prefill_chunk(model):
    """Satellite: cancel a request midway through CHUNKED prefill (some
    chunks resident, prompt incomplete). Allocator + PrefixIndex
    invariants hold, and the co-scheduled survivor's stream is
    bit-identical to a run where the victim was never submitted."""
    kw = dict(_KW, prefill_chunk=4, prefill_budget=4)
    victim = _prompts(1, base_len=24, stride=0)[0]
    survivor = _prompts(2, base_len=7, stride=0)[1]
    ref = _never_submitted_ref(model, kw, survivor, 10)

    async def go():
        eng = ServingEngine(model, **kw)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        v = await fd.submit(victim, 8, seed=0)
        s = await fd.submit(survivor, 10, seed=1)
        await fd.pump()  # victim admitted, one 4-token chunk resident
        vs = [
            sl for sl in eng._active_slots()
            if eng.slot_req[sl].rid == v.rid
        ]
        assert vs and eng.prefilling[vs[0]], (
            "victim must be mid-prefill when cancelled"
        )
        v.cancel()
        await _drain_all(fd)
        assert v.outcome == "cancelled" and v.tokens == []
        assert s.outcome == "finished"
        assert eng.alloc.held_pages == 0
        eng.alloc.check()
        eng.index.check(eng.alloc)
        return s.tokens

    assert run(go()) == ref


@pytest.mark.slow
def test_cancel_mid_verify_dispatch(model):
    """Satellite: cancel a SPECULATING request between verify
    dispatches (drafts pending, carried logits live). The write
    watermark already rolled back rejected rows, so teardown leaves the
    allocator identity and the index single-writer/refcount invariants
    intact; the survivor matches a never-submitted run bit for bit and
    the victim's partial stream is a prefix of its solo reference."""
    kw = dict(_KW, speculate=4)
    prompts = _prompts(2, base_len=9, stride=2)
    ref_survivor = _never_submitted_ref(model, kw, prompts[1], 12)
    solo_victim = _sync_refs(model, [prompts[0]], n_new=12, kw=kw,
                             seeds=[0])[0]

    async def go():
        eng = ServingEngine(model, **kw)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        v = await fd.submit(prompts[0], 12, seed=0)
        s = await fd.submit(prompts[1], 12, seed=1)
        while not v.tokens:
            await fd.pump()
        assert eng.verify_dispatches >= 1, "must be mid-speculation"
        v.cancel()
        await _drain_all(fd)
        assert v.outcome == "cancelled"
        assert s.outcome == "finished"
        assert eng.alloc.held_pages == 0
        eng.alloc.check()
        eng.index.check(eng.alloc)
        return v.tokens, s.tokens

    v_toks, s_toks = run(go())
    assert s_toks == ref_survivor
    assert v_toks == solo_victim[: len(v_toks)] and v_toks


def test_cancel_queued_and_parked(model):
    """Cancelling work that never reached a slot: a queued request
    leaves the queue; a parked request leaves the parking lot — both
    typed, counted, and invariant-clean."""

    async def go():
        eng = ServingEngine(model, **_KW)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        a = await fd.submit(_prompts(1)[0], 8, seed=0)
        b = await fd.submit(_prompts(2)[1], 8, seed=1)
        c = await fd.submit(_prompts(3)[2], 8, seed=2)
        # nothing stepped yet: c is queued; cancel applies immediately
        c.cancel()
        assert c.outcome == "cancelled" and not any(
            r.rid == c.rid for r in eng.queue
        )
        # park b manually through the engine's own path, then cancel it
        await fd.pump()
        await _drain_all(fd)
        assert a.outcome == "finished" and b.outcome == "finished"
        assert eng.stats()["cancelled_requests"] == 1
        return True

    assert run(go())


# ---------------------------------------------------------------------------
# Priority + deadline admission
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_priority_dispatch_order(model):
    """With one slot, a high-priority later submission dispatches
    before a low-priority earlier one (fresh band is priority-ordered),
    while default priorities keep exact FIFO."""

    async def go():
        eng = ServingEngine(model, slots=1, page_size=8, window=4,
                            temperature=0.0, cache_dtype=jnp.float32)
        fd = AsyncFrontDoor(eng)
        filler = await fd.submit(_prompts(1)[0], 4, seed=0)
        lo = await fd.submit(_prompts(2)[1], 4, seed=1, priority=0)
        hi = await fd.submit(_prompts(3)[2], 4, seed=2, priority=5)
        order = []

        async def consume(name, st):
            async for _ in st:
                pass
            order.append(name)

        fd.start()
        await asyncio.gather(
            consume("filler", filler), consume("lo", lo),
            consume("hi", hi),
        )
        await fd.close()
        return order

    order = run(go())
    assert order.index("hi") < order.index("lo"), order


def test_aging_starvation_bound(model):
    """The adversarial starvation gate: slots=1, a fresh priority-10
    request arrives EVERY scheduler step, and a priority-0 request must
    still dispatch within the provable bound — with priority_aging=1.0
    its effective priority outranks every fresh arrival after 10 queued
    steps (ties break oldest-first), so it must be running by a small
    constant past that."""
    bound_steps = 10 + 6  # (P_hi - P_lo) / aging + slot-turnover slack

    async def go():
        eng = ServingEngine(model, slots=1, page_size=8, window=4,
                            temperature=0.0, cache_dtype=jnp.float32,
                            priority_aging=1.0)
        fd = AsyncFrontDoor(eng)
        flood_prompts = _prompts(6, base_len=5, stride=0)
        low = await fd.submit(_prompts(1)[0], 4, seed=0, priority=0)
        admitted_at = None
        for step in range(40):
            await fd.submit(
                flood_prompts[step % 6], 4, seed=step + 1, priority=10
            )
            await fd.pump()
            if admitted_at is None and (
                low.tokens
                or any(
                    eng.slot_req[s].rid == low.rid
                    for s in eng._active_slots()
                )
            ):
                admitted_at = step + 1
                break
        return admitted_at

    admitted_at = run(go())
    assert admitted_at is not None and admitted_at <= bound_steps, (
        f"low-priority request starved: not dispatched within "
        f"{bound_steps} steps (got {admitted_at})"
    )


def test_deadline_shed_before_dispatch(model):
    """A queued request whose deadline passes (virtual clock) is shed
    BEFORE any dispatch: typed outcome, counter, zero tokens, event
    recorded; in-flight requests are never shed mid-decode."""

    async def go():
        clk = VirtualClock()
        eng = ServingEngine(model, slots=1, page_size=8, window=4,
                            temperature=0.0, cache_dtype=jnp.float32,
                            clock=clk, telemetry=True)
        fd = AsyncFrontDoor(eng, check_invariants=True)
        a = await fd.submit(_prompts(1)[0], 8, seed=0, deadline_s=100.0)
        b = await fd.submit(_prompts(2)[1], 8, seed=1, deadline_s=5.0)
        await fd.pump()  # a admitted (slots=1), b queued
        clk.advance(10.0)  # b expires queued; a's SLO still holds
        await _drain_all(fd)
        assert a.outcome == "finished"
        assert b.outcome == "expired" and b.tokens == []
        with pytest.raises(DeadlineExceeded):
            await b.result()
        st = eng.stats()
        assert st["deadline_shed_requests"] == 1
        assert st["cancelled_requests"] == 0
        kinds = [ev.kind for ev in eng.telemetry.events]
        assert "deadline_shed" in kinds
        assert b.rid in eng.expired
        return True

    assert run(go())


def test_unpark_sheds_expired_and_keeps_priority_order(model):
    """Satellite (the old FIFO ``_unpark``): a parked request past its
    deadline sheds ON RELEASE (counted + evented) instead of
    re-queuing, and released survivors re-enter through the priority
    selector rather than blind FIFO."""
    clk = VirtualClock()
    eng = ServingEngine(model, slots=1, page_size=8, window=4,
                        temperature=0.0, cache_dtype=jnp.float32,
                        clock=clk, telemetry=True)
    expired = eng.lookup(eng.submit(_prompts(1)[0], 8, deadline_s=5.0))
    alive = eng.lookup(eng.submit(_prompts(2)[1], 8, deadline_s=100.0))
    # park both through the engine's own bookkeeping (progress-free
    # park, as the livelock guard would)
    eng.queue.clear()
    expired.evictions = alive.evictions = 1
    eng.parked.extend([expired, alive])
    clk.advance(10.0)
    eng._unpark()
    assert [r.rid for r in eng.queue] == [alive.rid]
    assert expired.outcome == "expired"
    assert eng.stats()["deadline_shed_requests"] == 1
    ev = [e for e in eng.telemetry.events if e.kind == "deadline_shed"]
    assert ev and ev[0].data.get("where") == "parked"
    # released survivors ride the resumed band: a later fresh
    # high-priority submission does NOT overtake them
    fresh_rid = eng.submit(_prompts(3)[2], 8, priority=99)
    qi = eng._select_queued()
    assert eng.queue[qi].rid == alive.rid, (
        "resumed (progress-holding) work must outrank fresh submissions"
    )
    assert fresh_rid != alive.rid


def test_backpressure_defer_awaits_and_shed_raises(model):
    """PR 10's overload outcomes through the front door: defer =
    SUSPENDED submission that completes once the queue drains (the
    awaitable retry-after), shed = immediate typed raise."""

    async def go():
        eng = ServingEngine(model, slots=1, page_size=8, window=4,
                            temperature=0.0, cache_dtype=jnp.float32,
                            max_queue=1, overload_policy="defer")
        fd = AsyncFrontDoor(eng)
        s1 = await fd.submit(_prompts(1)[0], 4, seed=0)
        await fd.pump()  # s1 takes the slot; the queue is empty again
        t2 = asyncio.create_task(fd.submit(_prompts(2)[1], 4, seed=1))
        await asyncio.sleep(0)
        t3 = asyncio.create_task(fd.submit(_prompts(3)[2], 4, seed=2))
        await asyncio.sleep(0)
        # t2 filled the queue; t3 must be suspended on backpressure
        assert t2.done() and not t3.done(), "defer must suspend, not raise"
        deferred_before = eng.stats()["deferred_submits"]
        for _ in range(60):
            await fd.pump()
            if t3.done():
                break
        s3 = await t3
        await _drain_all(fd)
        assert deferred_before >= 1
        assert [s1.outcome, (await t2).outcome, s3.outcome] == (
            ["finished"] * 3
        )
        # raise mode surfaces the typed outcome instead of waiting
        eng2 = ServingEngine(model, slots=1, page_size=8, window=4,
                             temperature=0.0, cache_dtype=jnp.float32,
                             max_queue=1, overload_policy="defer")
        fd2 = AsyncFrontDoor(eng2, backpressure="raise")
        await fd2.submit(_prompts(1)[0], 4, seed=0)
        await fd2.pump()  # first request into the slot
        await fd2.submit(_prompts(2)[1], 4, seed=1)
        with pytest.raises(PoolOverloaded):
            await fd2.submit(_prompts(3)[2], 4, seed=2)
        # shed policy: AdmissionRejected raises through either mode
        eng3 = ServingEngine(model, slots=1, page_size=8, window=4,
                             temperature=0.0, cache_dtype=jnp.float32,
                             max_queue=1, overload_policy="shed")
        fd3 = AsyncFrontDoor(eng3)
        await fd3.submit(_prompts(1)[0], 4, seed=0)
        await fd3.pump()
        await fd3.submit(_prompts(2)[1], 4, seed=1)
        with pytest.raises(AdmissionRejected):
            await fd3.submit(_prompts(3)[2], 4, seed=2)
        await _drain_all(fd2)
        await _drain_all(fd3)
        return True

    assert run(go())


# ---------------------------------------------------------------------------
# Cluster: deterministic tie-breaks + cancellation routing
# ---------------------------------------------------------------------------


def test_cluster_tiebreak_deterministic_and_placement_pinned(model):
    """Satellite: least-loaded admission tie-breaks are deterministic
    (equal load -> lowest replica index), so a trace's placement
    replays identically through the front door — pinned by routing the
    same trace twice and comparing every route."""
    prompts = _prompts(6, base_len=4, stride=1)

    def routes():
        cl = ServingCluster(model, replicas=3, **_KW)
        for i, p in enumerate(prompts):
            cl.submit(p, 6, seed=i)
        return [cl._route[g][0] for g in sorted(cl._route)]

    r1, r2 = routes(), routes()
    assert r1 == r2, "placement must be replay-deterministic"
    # equal-load start: the first three go 0, 1, 2 by the lowest-index
    # tie-break, round-robin while loads stay equal
    assert r1[:3] == [0, 1, 2], r1


@pytest.mark.slow
def test_cluster_cancel_routes_to_owner(model):
    """Cluster-global cancellation follows the route to the owning
    replica; terminal dicts mirror at cluster level and the route
    drops (no later failover can re-serve cancelled work)."""

    async def go():
        cl = ServingCluster(model, replicas=2, **_KW)
        fd = AsyncFrontDoor(cl, check_invariants=True)
        prompts = _prompts(4)
        streams = [
            await fd.submit(p, 16, seed=i)
            for i, p in enumerate(prompts)
        ]
        while not streams[2].tokens:
            await fd.pump()
        streams[2].cancel()
        await _drain_all(fd)
        assert streams[2].outcome == "cancelled"
        assert streams[2].rid in cl.cancelled
        assert streams[2].rid not in cl._route
        assert [streams[i].outcome for i in (0, 1, 3)] == (
            ["finished"] * 3
        )
        assert cl.stats()["cancelled_requests"] == 1
        return True

    assert run(go())


# ---------------------------------------------------------------------------
# The chaos composition acceptance gate
# ---------------------------------------------------------------------------


def _frontdoor_chaos_run(model, prompts, plan, cancel_at, deadline_s):
    """One deterministic front-door chaos drive: submissions before any
    step, scripted cancels keyed to harvested token counts, deadlines
    on a shared virtual clock advanced one unit per pump."""

    async def go():
        clk = VirtualClock()
        cl = ServingCluster(
            model, replicas=3, fault_plan=plan, telemetry=True,
            clock=clk, backoff_s=0.0, max_retries=2, **_KW,
        )
        fd = AsyncFrontDoor(cl, check_invariants=True)
        streams = []
        for i, p in enumerate(prompts):
            streams.append(await fd.submit(
                p, 8, seed=i,
                deadline_s=deadline_s.get(i),
                priority=i % 2,
            ))
        cancelled = set()
        for _ in range(200):
            alive = await fd.pump()
            clk.advance(1.0)
            for i, at in cancel_at.items():
                if i not in cancelled and len(streams[i].tokens) >= at:
                    streams[i].cancel()
                    cancelled.add(i)
            if not alive:
                break
        assert all(s.outcome is not None for s in streams), [
            s.outcome for s in streams
        ]
        sigs = tuple(
            t.sequence_signature() for t in cl.telemetries
            if t is not None
        )
        return streams, cl, sigs

    return run(go())


def test_chaos_cancel_crash_deadline_composite(model):
    """Acceptance: one scripted plan drives a replica crash while
    cancellations and deadline sheds flow through the front door.
    Untouched survivors stay bit-identical to the fault-free
    synchronous run, cancelled streams are exact prefixes, expired
    requests emit nothing after shed, and the whole composition
    REPLAYS with identical per-replica event sequences."""
    prompts = _prompts(6, base_len=5, stride=2)
    refs = _sync_refs(model, prompts)
    plan = FaultPlan.parse("2:crash@0")
    cancel_at = {1: 2}          # cancel stream 1 after 2 tokens
    deadline_s = {4: 3.0}       # stream 4 expires while queued/evicted

    first = _frontdoor_chaos_run(model, prompts, plan, cancel_at,
                                 deadline_s)
    streams, cl, sigs = first
    outcomes = [s.outcome for s in streams]
    assert outcomes[1] == "cancelled"
    for i, s in enumerate(streams):
        if s.outcome == "finished":
            assert s.tokens == refs[i], f"survivor {i} diverged"
        elif s.outcome == "cancelled":
            assert s.tokens == refs[i][: len(s.tokens)]
        else:
            assert s.outcome == "expired"
            assert s.tokens == refs[i][: len(s.tokens)]
    assert "dead" in cl.health
    st = cl.stats()
    assert st["cancelled_requests"] >= 1
    assert st["failovers"] >= 1
    for i in cl._alive():
        cl.engines[i].alloc.check()

    # replay: same plan, same trace, same cancel/deadline script —
    # identical outcomes, streams, AND event sequences
    streams2, cl2, sigs2 = _frontdoor_chaos_run(
        model, prompts, plan, cancel_at, deadline_s
    )
    assert [s.outcome for s in streams2] == outcomes
    assert [s.tokens for s in streams2] == [s.tokens for s in streams]
    assert sigs2 == sigs, (
        "chaos + cancel + deadline replay must reproduce every "
        "replica's event sequence exactly"
    )
    assert cl2.health == cl.health


@pytest.mark.slow
def test_chaos_composite_matrix_cache_chunk_spec(model):
    """Slow tier: the same cancel + crash + deadline composition over
    the cache+chunk+spec feature combo."""
    kw = dict(_KW, prefill_chunk=8, speculate=4)
    prompts = _prompts(6, base_len=5, stride=2)
    refs = _sync_refs(model, prompts, kw=kw)
    plan = FaultPlan.parse("2:crash@0")

    async def go():
        clk = VirtualClock()
        cl = ServingCluster(
            model, replicas=3, fault_plan=plan, telemetry=True,
            clock=clk, backoff_s=0.0, max_retries=2, **kw,
        )
        fd = AsyncFrontDoor(cl, check_invariants=True)
        streams = [
            await fd.submit(p, 8, seed=i, deadline_s=(
                3.0 if i == 4 else None
            ))
            for i, p in enumerate(prompts)
        ]
        cancelled = False
        for _ in range(200):
            alive = await fd.pump()
            clk.advance(1.0)
            if not cancelled and len(streams[1].tokens) >= 2:
                streams[1].cancel()
                cancelled = True
            if not alive:
                break
        return streams

    streams = run(go())
    for i, s in enumerate(streams):
        assert s.outcome is not None
        if s.outcome == "finished":
            assert s.tokens == refs[i], f"survivor {i} diverged"
        else:
            assert s.tokens == refs[i][: len(s.tokens)]
    assert streams[1].outcome == "cancelled"


# ---------------------------------------------------------------------------
# Stats façade
# ---------------------------------------------------------------------------


def test_frontdoor_stats(model):
    async def go():
        eng = ServingEngine(model, **_KW)
        fd = AsyncFrontDoor(eng)
        await fd.submit(_prompts(1)[0], 4, seed=0)
        await _drain_all(fd)
        st = fd.stats()
        assert st["frontdoor_steps"] == fd.steps >= 1
        assert st["frontdoor_live_streams"] == 0
        assert st["cancelled_requests"] == 0
        assert st["deadline_shed_requests"] == 0
        return True

    assert run(go())
