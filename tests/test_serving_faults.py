"""Fault tolerance of the serving stack (midgpt_tpu.serving.faults):
FaultPlan parse/spec roundtrip, allocator quarantine invariants, typed
admission rejection + bounded-queue shed/defer, pool-exhaustion edges
(single request parks instead of MemoryError; two-request eviction
thrash trips the livelock guard), and the cluster failover suite —
replica crash / wedged dispatch (wall-clock watchdog) / transient retry
with capped backoff — with the landing gate asserted directly: every
surviving request's greedy stream is BIT-IDENTICAL to the fault-free
run, and the allocator identity ``free + held + cached + quarantined ==
num_pages`` holds after every injected fault. The slow tier runs the
same composite chaos plan across the prefix-cache x chunked-prefill x
speculation x kv-quant matrix."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import (
    AdmissionRejected,
    ClusterUnavailable,
    FaultEvent,
    FaultPlan,
    PageAllocator,
    PoolOverloaded,
    ServingCluster,
    ServingEngine,
)

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
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


def _drive(obj, check_engines, max_steps=200):
    """Step ``obj`` (engine or cluster) to drain, re-checking the
    allocator identity on every live engine after every scheduler step —
    i.e. after every injected fault (events fire at step tops)."""
    for _ in range(max_steps):
        if not obj.has_work:
            return
        obj.step()
        for e in check_engines():
            e.alloc.check()
    raise AssertionError(f"did not drain in {max_steps} steps")


@pytest.fixture(scope="module")
def cluster_case(model):
    """One fault-free reference run: 4 requests through a single engine.
    Every chaos variant below must reproduce these streams bit-for-bit
    (and the ref run warms the program cache, so chaos steps are
    dispatch-only — which the watchdog tests rely on for timing)."""
    prompts = _prompts(4, base_len=5, stride=2)
    kw = dict(
        slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32,
    )
    eng = ServingEngine(model, **kw)
    rids = [eng.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    fin = eng.run()
    refs = [list(map(int, fin[r].tokens)) for r in rids]
    return prompts, kw, refs


def _chaos_run(model, prompts, kw, plan, n_new=8, **cluster_kw):
    cl = ServingCluster(model, fault_plan=plan, **cluster_kw, **kw)
    rids = [cl.submit(p, n_new, seed=i) for i, p in enumerate(prompts)]
    _drive(cl, lambda: [cl.engines[i] for i in cl._alive()])
    fin = cl.finished
    assert sorted(fin) == sorted(rids), "every request must finish"
    return cl, [list(map(int, fin[r].tokens)) for r in rids]


# ---------------------------------------------------------------------------
# FaultPlan: spec grammar + determinism plumbing
# ---------------------------------------------------------------------------


def test_fault_plan_parse_spec_roundtrip():
    spec = "6:crash@1;4:wedge@0:0.5;3:transient;2:exhaust@0:all:3"
    plan = FaultPlan.parse(spec)
    assert len(plan) == 4
    # events sort by step, stably
    assert [ev.step for ev in plan] == [2, 3, 4, 6]
    assert plan.replicas == {0, 1}
    assert FaultPlan.parse(plan.spec()).spec() == plan.spec()
    ex = plan.events_for(0, 2)[0]
    assert ex.kind == "exhaust" and ex.pages == -1 and ex.hold_steps == 3
    assert plan.events_for(0, 4)[0].seconds == 0.5
    assert plan.events_for(1, 6)[0].kind == "crash"
    assert plan.events_for(1, 2) == []
    # a bounded-pages exhaust roundtrips its count too
    ev = FaultEvent(step=1, kind="exhaust", pages=2, hold_steps=2)
    assert FaultPlan.parse(FaultPlan([ev]).spec()).events[0].pages == 2


def test_fault_event_validation():
    with pytest.raises(AssertionError):
        FaultEvent(step=0, kind="crash")  # steps are 1-based
    with pytest.raises(AssertionError):
        FaultEvent(step=1, kind="meteor")


# ---------------------------------------------------------------------------
# Allocator quarantine (the `exhaust` fault's host-side mechanism)
# ---------------------------------------------------------------------------


def test_allocator_quarantine_invariants():
    a = PageAllocator(8)
    held = a.alloc(3)
    assert a.quarantine(2) == 2
    a.check()
    assert a.free_pages == 3 and a.quarantined_pages == 2
    assert a.quarantine() == 3  # -1 = the rest of the free list
    a.check()
    assert a.free_pages == 0 and a.quarantined_pages == 5
    # held pages are untouched; new allocation feels the pressure
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(held)
    a.check()
    assert a.free_pages == 3  # frees bypass the quarantine
    assert a.release_quarantined() == 5
    a.check()
    assert a.free_pages == 8 and a.quarantined_pages == 0
    assert a.quarantine(99) == 8  # capped at the free list


# ---------------------------------------------------------------------------
# Typed admission + bounded-queue overload policy
# ---------------------------------------------------------------------------


def test_admission_rejections_typed_and_counted(model):
    eng = ServingEngine(
        model, slots=1, page_size=4, num_pages=2, window=2,
        cache_dtype=jnp.float32,
    )
    cases = [
        ("bad_budget", lambda: eng.submit(np.zeros(4, np.int32), 0)),
        ("budget_exceeds_block",
         lambda: eng.submit(np.zeros(4, np.int32), CFG.block_size)),
        ("empty_prompt", lambda: eng.submit(np.zeros(0, np.int32), 4)),
        # 4 prompt + 8 new = 3 pages over a 2-page pool: never servable
        ("lifetime_exceeds_pool",
         lambda: eng.submit(np.zeros(4, np.int32), 8)),
    ]
    for reason, call in cases:
        with pytest.raises(AdmissionRejected) as exc:
            call()
        assert exc.value.reason == reason
    st = eng.stats()
    assert st["admission_rejected"] == 4
    assert st["reject_reasons"] == {r: 1 for r, _ in cases}
    assert not eng.queue, "rejected requests must not be enqueued"


def test_cluster_passes_rejection_through_without_burning_rid(model):
    cl = ServingCluster(
        model, replicas=2, slots=1, page_size=4, num_pages=2, window=2,
        cache_dtype=jnp.float32,
    )
    with pytest.raises(AdmissionRejected):
        cl.submit(np.zeros(4, np.int32), 8)
    assert not cl._route and cl._next_rid == 0
    assert cl.stats()["reject_reasons"] == {"lifetime_exceeds_pool": 1}


def test_cluster_submit_spills_over_a_full_queue(model):
    """The routing metric (queue + parked + active) is not the metric
    the bound is enforced on (queue alone): when the least-loaded
    replica's queue is full, admission must spill to a replica with
    queue room instead of shedding — and shed only when EVERY healthy
    queue is full."""
    cl = ServingCluster(
        model, replicas=2, slots=2, page_size=8, window=4,
        cache_dtype=jnp.float32, max_queue=1, overload_policy="shed",
    )
    prompts = _prompts(4, base_len=4, stride=0)
    # replica 1: two ACTIVE requests (load 2, queue 0); replica 0: a
    # full queue (load 1) — least-loaded picks 0, but only 1 has room
    cl.engines[1].submit(prompts[0], 16)
    cl.engines[1].step()  # admit (the queue bound is on the queue alone)
    cl.engines[1].submit(prompts[1], 16)
    cl.engines[1].step()
    assert len(cl.engines[1]._active_slots()) == 2
    assert not cl.engines[1].queue
    cl.engines[0].submit(prompts[2], 8)
    rid = cl.submit(prompts[3], 8)
    assert cl._route[rid][0] == 1, "must spill to the replica with room"
    # now every queue is full: the overload outcome finally surfaces
    with pytest.raises(AdmissionRejected) as exc:
        cl.submit(prompts[3], 8)
    assert exc.value.reason == "queue_full"


def test_bounded_queue_defer_and_shed(model):
    prompts = _prompts(3, base_len=4, stride=0)
    defer = ServingEngine(
        model, slots=1, page_size=8, window=4, cache_dtype=jnp.float32,
        max_queue=2, overload_policy="defer",
    )
    rids = [defer.submit(p, 4) for p in prompts[:2]]
    with pytest.raises(PoolOverloaded) as exc:
        defer.submit(prompts[2], 4)
    assert exc.value.reason == "queue_full"
    st = defer.stats()
    assert st["deferred_submits"] == 1 and st["shed_requests"] == 0
    assert st["admission_rejected"] == 0, "defer is not a rejection"
    fin = defer.run()  # the queue drains; deferred work can resubmit
    assert sorted(fin) == sorted(rids)
    defer.submit(prompts[2], 4)  # backpressure lifted

    shed = ServingEngine(
        model, slots=1, page_size=8, window=4, cache_dtype=jnp.float32,
        max_queue=1, overload_policy="shed",
    )
    shed.submit(prompts[0], 4)
    with pytest.raises(AdmissionRejected) as exc:
        shed.submit(prompts[1], 4)
    assert exc.value.reason == "queue_full"
    st = shed.stats()
    assert st["shed_requests"] == 1
    assert st["reject_reasons"] == {"queue_full": 1}


# ---------------------------------------------------------------------------
# Pool-exhaustion edges: park instead of MemoryError; livelock guard
# ---------------------------------------------------------------------------


def test_single_request_pool_exhaustion_parks_and_recovers(model):
    """A lone request whose window growth hits an exhausted pool (all
    free pages quarantined mid-decode) PARKS with progress kept — the
    old hard ``MemoryError`` — and resumes bit-identically once pages
    come back."""
    kw = dict(
        slots=1, page_size=4, num_pages=4, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefix_cache=False,
    )
    prompt = _prompts(1, base_len=3)[0]
    ref_eng = ServingEngine(model, **kw)
    ref_rid = ref_eng.submit(prompt, 12)
    ref = list(map(int, ref_eng.run()[ref_rid].tokens))

    plan = FaultPlan([FaultEvent(step=2, kind="exhaust", hold_steps=2)])
    eng = ServingEngine(model, fault_hook=plan.hook(0), **kw)
    rid = eng.submit(prompt, 12)
    _drive(eng, lambda: [eng])
    assert list(map(int, eng.finished[rid].tokens)) == ref
    st = eng.stats()
    assert st["faults_injected"] == 1
    assert st["overload_parks"] >= 1, "the lone request must have parked"
    assert st["parked_requests"] == 0
    assert eng.alloc.held_pages == 0 and eng.alloc.quarantined_pages == 0


def test_eviction_thrash_livelock_guard(model):
    """Two requests whose window growth trades the same pages. The first
    growth pass evicts the just-prefilled loser at ZERO progress — the
    opening beat of an eviction livelock — and the guard parks it at
    ``park_threshold`` zero-progress evictions instead of letting it
    re-prefill in a loop. At the default threshold the same trace is
    allowed to keep trading (every later steal hits a victim that
    progressed, so thrash resets — that is productive preemption, not
    livelock). Both modes finish with streams bit-identical to
    uncontended runs."""
    kw = dict(
        slots=2, page_size=4, num_pages=5, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefix_cache=False,
    )
    prompts = _prompts(2, base_len=8, stride=0)
    # uncontended reference: same geometry (programs already compiled),
    # one request at a time so no eviction pressure exists
    ref_eng = ServingEngine(model, **kw)
    refs = []
    for i, p in enumerate(prompts):
        r = ref_eng.submit(p, 8, seed=i)
        refs.append(list(map(int, ref_eng.run()[r].tokens)))

    def contended(park_threshold):
        eng = ServingEngine(model, park_threshold=park_threshold, **kw)
        rids = [eng.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
        _drive(eng, lambda: [eng])
        assert [
            list(map(int, eng.finished[r].tokens)) for r in rids
        ] == refs, f"park_threshold={park_threshold} diverged"
        assert eng.alloc.held_pages == 0
        return eng.stats()

    st = contended(park_threshold=1)
    assert st["livelock_parks"] >= 1, "the thrash guard must have fired"
    assert st["parked_requests"] == 0
    # default threshold: the trace's steals all made progress, so the
    # guard correctly stays out of the way
    st = contended(park_threshold=2)
    assert st["livelock_parks"] == 0
    assert st["evictions"] >= 2


# ---------------------------------------------------------------------------
# Cluster failover: crash / transient retry / wedge watchdog
# ---------------------------------------------------------------------------


def test_cluster_crash_failover_bit_identical(model, cluster_case):
    """Replica 0 crashes mid-decode (its requests have emitted tokens):
    the survivors finish EVERY request with streams bit-equal to the
    fault-free run — re-queueing is the eviction path, placement is
    invariant, so failover replay is exact."""
    prompts, kw, refs = cluster_case
    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse("2:crash@0"), replicas=2
    )
    assert got == refs
    assert cl.health == ["dead", "healthy"]
    assert cl.health_reason[0] == "crashed"
    st = cl.stats()
    assert st["failovers"] == 1 and st["dead_replicas"] == 1
    assert st["requeued_requests"] >= 1
    assert st["faults_injected"] == 1
    # the dead replica's emitted-so-far work was preserved, not redone
    assert cl.engines[0].tokens_generated >= 1


def test_cluster_transient_retry_same_replica(model, cluster_case):
    """One scripted transient dispatch error: the same replica retries
    (suspect -> healthy), no failover, streams identical."""
    prompts, kw, refs = cluster_case
    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse("2:transient@0"),
        replicas=2, backoff_s=0.0,
    )
    assert got == refs
    assert cl.health == ["healthy", "healthy"]
    st = cl.stats()
    assert st["retries"] == 1 and st["failovers"] == 0
    assert st["watchdog_trips"] == 0


def test_cluster_transient_exhaustion_fails_over(model, cluster_case):
    """max_retries consecutive transients exhaust the backoff ladder:
    the replica goes dead and its backlog fails over — still
    bit-identical."""
    prompts, kw, refs = cluster_case
    # step 2 raises; retries re-enter step() at fault_steps 3, 4, 5
    plan = FaultPlan.parse(
        "2:transient@0;3:transient@0;4:transient@0;5:transient@0"
    )
    cl, got = _chaos_run(
        model, prompts, kw, plan, replicas=2, max_retries=3, backoff_s=0.0,
    )
    assert got == refs
    assert cl.health[0] == "dead"
    assert cl.health_reason[0] == "transient_exhausted"
    st = cl.stats()
    assert st["retries"] == 3 and st["failovers"] == 1


def test_cluster_wedge_watchdog_failover(model, cluster_case):
    """A dispatch that never returns, scripted: it stalls past the
    wall-clock watchdog; the replica is
    abandoned (dead, never re-stepped) and its backlog fails over
    bit-identically."""
    prompts, kw, refs = cluster_case
    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse("2:wedge@0:1.5"),
        replicas=2, dispatch_timeout_s=0.5,
    )
    assert got == refs
    assert cl.health == ["dead", "healthy"]
    assert cl.health_reason[0] == "wedged"
    st = cl.stats()
    assert st["watchdog_trips"] == 1 and st["failovers"] == 1
    # COLD failover: a watchdog trip means the wedged step thread may
    # still be running, so the engine is never drained — its slots stay
    # frozen and its requests were re-served from scratch on the
    # survivor (from the cluster's submission record)
    assert cl.engines[0]._active_slots(), (
        "a watchdog-tripped engine must not be drained"
    )
    assert st["requeued_requests"] >= 1


def test_all_replicas_dead_raises_cluster_unavailable(model, cluster_case):
    prompts, kw, _ = cluster_case
    cl = ServingCluster(
        model, replicas=2, fault_plan=FaultPlan.parse("1:crash@0;1:crash@1"),
        **kw,
    )
    for i, p in enumerate(prompts):
        cl.submit(p, 8, seed=i)
    with pytest.raises(ClusterUnavailable):
        cl.run()
    assert cl.health == ["dead", "dead"]
    with pytest.raises(ClusterUnavailable):
        cl.submit(prompts[0], 8)


# ---------------------------------------------------------------------------
# Disaggregated handoff faults (prefill -> decode page handoff)
# ---------------------------------------------------------------------------


def test_handoff_fault_reserves_cold_bit_identical(model, cluster_case):
    """A scripted ``handoff`` fault poisons the next export on the
    prefill replica: HandoffFailed fires BEFORE any state leaves the
    slot, the cluster abandons that copy and re-serves the request cold
    from its submission record — streams bit-identical, and the replica
    stays healthy (a dropped handoff is not a crash)."""
    prompts, kw, refs = cluster_case
    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse("2:handoff@0"),
        prefill_replicas=1, decode_replicas=1,
    )
    assert got == refs
    assert cl.health == ["healthy", "healthy"]
    st = cl.stats()
    assert st["handoff_failures"] == 1
    assert st["requeued_requests"] >= 1
    # the failed export never counted; the cold re-serve hands off fine
    assert st["handoffs"] == len(prompts)
    assert st["faults_injected"] == 1


def test_prefill_replica_crash_mid_disagg_failover(model, cluster_case):
    """A prefill-pool replica crashes with requests in flight: its
    backlog re-serves cold on the SURVIVING prefill replica (submission
    targets stay inside the prefill pool) and every stream is
    bit-identical — handoff adds no new failover state, and requests
    already imported into the decode pool are untouched."""
    prompts, kw, refs = cluster_case
    # step 1: the crash fires before the first handoff pump, so the
    # replica still owns its share of the backlog when it dies
    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse("1:crash@0"),
        prefill_replicas=2, decode_replicas=1,
    )
    assert got == refs
    assert cl.health == ["dead", "healthy", "healthy"]
    st = cl.stats()
    assert st["failovers"] == 1 and st["requeued_requests"] >= 1
    assert st["handoffs"] == len(prompts)
    assert st["handoff_failures"] == 0


def test_prefill_pool_death_degrades_to_decode_pool(model, cluster_case):
    """The ENTIRE prefill pool dies: submission targets degrade to the
    surviving decode pool — a decode-class engine is a full engine, so
    the re-served requests prefill and decode locally (no handoff) and
    the streams still match the monolithic reference."""
    prompts, kw, refs = cluster_case
    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse("2:crash@0"),
        prefill_replicas=1, decode_replicas=1,
    )
    assert got == refs
    assert cl.health == ["dead", "healthy"]
    st = cl.stats()
    assert st["failovers"] == 1
    assert st["handoff_failures"] == 0


# ---------------------------------------------------------------------------
# The chaos acceptance matrix
# ---------------------------------------------------------------------------

# one composite plan: transient (retried) then crash on replica 0,
# allocator exhaustion on the survivor, a wedge on replica 2 — every
# fault kind in one scripted, replayable run with replica 1 surviving
_CHAOS = "2:transient@0;4:crash@0;3:exhaust@1:all:2;3:wedge@2:1.5"


def _chaos_matrix_case(model, prefix_cache, chunk, spec, kvq):
    prompts = _prompts(6, base_len=5, stride=2)
    # a shared prefix on half the trace gives the cache something to hit
    prompts = [
        np.concatenate([prompts[0][:4], p]) if i % 2 else p
        for i, p in enumerate(prompts)
    ]
    kw = dict(
        slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefix_cache=prefix_cache,
        prefill_chunk=chunk, speculate=spec, kv_quant=kvq,
    )
    ref_eng = ServingEngine(model, **kw)
    rids = [ref_eng.submit(p, 16, seed=i) for i, p in enumerate(prompts)]
    fin = ref_eng.run()
    refs = [list(map(int, fin[r].tokens)) for r in rids]

    cl, got = _chaos_run(
        model, prompts, kw, FaultPlan.parse(_CHAOS),
        replicas=3, dispatch_timeout_s=0.5, max_retries=2, backoff_s=0.0,
        n_new=16,
    )
    assert got == refs, "surviving streams must be bit-identical"
    assert cl.health[1] == "healthy" and "dead" in cl.health
    st = cl.stats()
    assert st["failovers"] >= 1
    assert st["faults_injected"] >= 3
    for e in cl.engines:
        assert e.alloc.quarantined_pages == 0
    # replaying the same plan over the same trace is bit-identical too
    cl2, got2 = _chaos_run(
        model, prompts, kw, FaultPlan.parse(_CHAOS),
        replicas=3, dispatch_timeout_s=0.5, max_retries=2, backoff_s=0.0,
        n_new=16,
    )
    assert got2 == got
    assert cl2.health == cl.health


def test_chaos_composite_plan_bit_identical(model):
    """Acceptance (fast tier): crash mid-decode + wedged dispatch +
    transient error + pool exhaustion in ONE scripted plan — every
    request finishes, streams bit-equal the fault-free run, the run
    replays identically, and no fault path raises."""
    _chaos_matrix_case(model, True, None, 0, None)


def test_chaos_telemetry_flight_dumps_and_replay(model, cluster_case,
                                                 tmp_path):
    """Chaos + telemetry composition (serving.telemetry): one composite
    plan drives every terminal fault path — crash (warm failover),
    wedge past the watchdog (cold), and exhausted transient retries —
    with tracing ON and a flight_dir armed. Surviving streams stay
    bit-identical to the fault-free run, the replayed run produces
    IDENTICAL per-replica event sequences (wall-clock annotations
    excluded — Event.signature), and every dead replica left a
    flight-recorder artifact carrying its event/dispatch rings
    including the scripted injection that killed it."""
    import json
    import os

    prompts, kw, refs = cluster_case
    # replica 0 crashes, replica 1 wedges into the 0.5 s watchdog,
    # replica 2 exhausts max_retries=2 transients (steps 2, 3, 4 — the
    # retries re-enter step()), replica 3 survives and drains everything
    spec = "2:crash@0;2:wedge@1:1.5;2:transient@2;3:transient@2;4:transient@2"

    def run(sub):
        d = tmp_path / sub
        d.mkdir()
        cl = ServingCluster(
            model, replicas=4, fault_plan=FaultPlan.parse(spec),
            dispatch_timeout_s=0.5, max_retries=2, backoff_s=0.0,
            telemetry=True, flight_dir=str(d), **kw,
        )
        rids = [cl.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
        _drive(cl, lambda: [cl.engines[i] for i in cl._alive()])
        return cl, [list(map(int, cl.finished[r].tokens)) for r in rids]

    cl, got = run("a")
    assert got == refs, "surviving streams must stay bit-identical"
    assert cl.health == ["dead", "dead", "dead", "healthy"]
    assert {os.path.basename(p) for p in cl.flight_dumps} == {
        "flight_replica0_crashed.json",
        "flight_replica1_wedged.json",
        "flight_replica2_transient_exhausted.json",
    }, "crash, watchdog, and exhausted-retry paths must all dump"
    for p in cl.flight_dumps:
        rec = json.load(open(p))
        assert rec["telemetry"]["events"], p
        assert any(
            e["kind"] == "fault" for e in rec["telemetry"]["events"]
        ), f"{p} must record the scripted injection"
        assert rec["stats"]["faults_injected"] >= 1

    sigs = [t.sequence_signature() for t in cl.telemetries]
    assert all(len(s) > 0 for s in sigs)
    cl2, got2 = run("b")
    assert got2 == got
    assert [t.sequence_signature() for t in cl2.telemetries] == sigs, (
        "replaying the same plan must reproduce every replica's event "
        "sequence exactly (wall clock excluded)"
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "prefix_cache,chunk,spec,kvq",
    [
        (False, None, 0, None),
        (False, 8, 0, None),
        (True, 8, 4, None),
        (True, None, 4, "int8"),
    ],
    ids=["nocache", "chunked", "cache-chunk-spec", "cache-spec-kvq8"],
)
def test_chaos_matrix_bit_identical(model, prefix_cache, chunk, spec, kvq):
    """Acceptance (slow tier): the same composite chaos plan across the
    prefix-cache x chunked-prefill x speculation x kv-quant matrix."""
    _chaos_matrix_case(model, prefix_cache, chunk, spec, kvq)
