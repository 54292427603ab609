"""Disaggregated prefill/decode serving + prefix-affinity routing
(midgpt_tpu.serving.cluster): the landing gates asserted directly.

- **Bit-identity**: every stream through a disaggregated cluster
  (prefill pool -> page handoff -> decode pool) equals the monolithic
  single-engine reference token for token, and is invariant to the pool
  split (1+1 / 2+1 / 2+2). Fast tier pins the greedy/cache case; the
  slow tier crosses cache x chunk x spec(greedy+sampled) x kv-quant,
  plus eviction-under-pressure around the handoff.
- **Handoff hygiene**: the allocator identity (free + held + cached +
  quarantined == num_pages) and the PrefixIndex structural invariants
  re-check on EVERY engine after EVERY cluster step — i.e. after every
  export/import — and the prefix chain serves hits on BOTH sides of a
  handoff (export retires the source pages cold; import re-registers
  the chain in the destination index).
- **Affinity routing**: on a deterministic zipf shared-prefix tenant
  trace, prefix-affinity admission yields a strictly higher cluster
  prefix-cache hit rate than blind least-loaded admission at EQUAL
  goodput (same streams, same token count) — the ISSUE's acceptance
  gate, enforced repo-side. The load-imbalance cap is pinned too: a
  cache hit never justifies routing to a replica more than
  ``affinity_max_imbalance`` requests deeper than the shallowest.
- **Composition**: cancellation catches a request in handoff limbo
  (exported, not yet imported) — the record drops, nothing leaks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import ServingCluster, ServingEngine

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)

BASE_KW = dict(slots=2, page_size=8, window=4, cache_dtype=jnp.float32)


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


def _check(cl):
    """Allocator + prefix-index invariants on every live engine — run
    after every cluster step, i.e. after every export/import pair."""
    for i in cl._alive():
        e = cl.engines[i]
        e.alloc.check()
        if e.index is not None:
            e.index.check(e.alloc)


def _drive(cl, max_steps=400):
    for _ in range(max_steps):
        if not cl.has_work:
            return
        cl.step()
        _check(cl)
    raise AssertionError(f"cluster did not drain in {max_steps} steps")


def _mono_ref(model, prompts, n_new, **kw):
    eng = ServingEngine(model, **kw)
    rids = [eng.submit(p, n_new, seed=i) for i, p in enumerate(prompts)]
    fin = eng.run()
    return [list(map(int, fin[r].tokens)) for r in rids]


def _disagg_run(model, prompts, n_new, split, **kw):
    p, d = split
    cl = ServingCluster(
        model, prefill_replicas=p, decode_replicas=d, **kw
    )
    rids = [cl.submit(pr, n_new, seed=i) for i, pr in enumerate(prompts)]
    _drive(cl)
    cl._harvest()
    fin = cl.finished
    assert sorted(fin) == sorted(rids), "every request must finish"
    return [list(map(int, fin[r].tokens)) for r in rids], cl


# ---------------------------------------------------------------------------
# fast tier: 1+1 greedy/cache bit-identity + handoff accounting
# ---------------------------------------------------------------------------


def test_disagg_1p1_streams_bit_identical_to_monolithic(model):
    """The tentpole gate, fast shape: chunked prefill on the prefill
    replica, page handoff, decode on the decode replica — greedy
    streams equal the monolithic engine bit for bit, each request hands
    off exactly once, and the page/byte accounting is non-trivial."""
    prompts = _prompts(4, base_len=5, stride=2)
    ref = _mono_ref(model, prompts, 8, **BASE_KW)
    got, cl = _disagg_run(model, prompts, 8, (1, 1), **BASE_KW)
    assert got == ref
    st = cl.stats()
    assert st["handoffs"] == len(prompts)
    assert st["handoff_pages_moved"] > 0
    assert st["handoff_bytes"] > 0
    assert st["handoff_failures"] == 0
    assert st["prefill_replicas"] == 1 and st["decode_replicas"] == 1
    # role split did what it says: the prefill replica never decoded,
    # the decode replica never chunk-prefilled (no evictions here)
    assert cl.engines[0].decode_dispatches == 0
    assert cl.engines[0].prefill_dispatches > 0
    assert cl.engines[1].decode_dispatches > 0
    assert cl.engines[1].prefill_dispatches == 0


def test_disagg_split_placement_invariance_fast(model):
    """1+1 vs 2+1 vs 2+2: the pool split is a latency/throughput
    decision, never a correctness one — all splits yield the same
    streams (greedy, prefix cache on)."""
    prompts = _prompts(4, base_len=5, stride=2)
    ref = _mono_ref(model, prompts, 8, **BASE_KW)
    for split in ((1, 1), (2, 1), (2, 2)):
        got, cl = _disagg_run(model, prompts, 8, split, **BASE_KW)
        assert got == ref, split
        assert cl.stats()["handoffs"] == len(prompts), split


def test_handoff_reregisters_prefix_on_both_sides(model):
    """Export retires the source chain COLD (the prefill replica keeps
    serving hits on it) and import re-registers it in the destination
    index — so the handed-off prefix is queryable on BOTH pools, and a
    repeat prompt prefills via cache hits on the prefill replica."""
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(9), (33,), 0, CFG.vocab_size)
    )
    cl = ServingCluster(
        model, prefill_replicas=1, decode_replicas=1, **BASE_KW
    )
    r0 = cl.submit(prompt, 6, seed=0)
    _drive(cl)
    pre, dec = cl.engines
    probe = [int(t) for t in prompt[:-1]]
    assert pre.index.match(probe)[2] > 0, "source chain must survive export"
    assert dec.index.match(probe)[2] > 0, "import must re-register the chain"
    # the repeat prompt hits the prefill replica's cache
    saved0 = pre.prompt_tokens_cached
    r1 = cl.submit(prompt, 6, seed=0)
    _drive(cl)
    cl._harvest()
    assert pre.prompt_tokens_cached > saved0
    assert cl.finished[r1].tokens == cl.finished[r0].tokens


def test_cancel_catches_request_in_handoff_limbo(model):
    """A request exported off the prefill pool but not yet imported
    (decode slots full) lives only as the cluster's HandoffRecord;
    cancel must find it there — record dropped, outcome cancelled,
    nothing leaks, and it can never be re-served."""
    kw = dict(BASE_KW, slots=1)
    prompts = _prompts(2, base_len=5, stride=2)
    cl = ServingCluster(
        model, prefill_replicas=1, decode_replicas=1, **kw
    )
    rids = [cl.submit(p, 12, seed=i) for i, p in enumerate(prompts)]
    for _ in range(100):
        if cl._handoff:
            break
        assert cl.has_work
        cl.step()
        _check(cl)
    assert cl._handoff, "second request must park in handoff limbo"
    (grid,) = cl._handoff
    assert cl.lookup(grid) is not None  # visible to the front door
    assert cl.cancel(grid) is True
    assert grid not in cl._handoff and grid not in cl._route
    assert cl.cancelled[grid].outcome == "cancelled"
    assert cl.cancel(grid) is False  # idempotent
    _drive(cl)
    cl._harvest()
    done = [r for r in rids if r in cl.finished]
    assert done == [r for r in rids if r != grid]
    _check(cl)


# ---------------------------------------------------------------------------
# fast tier: prefix-affinity routing
# ---------------------------------------------------------------------------


def _zipf_trace(n_requests=12, n_tenants=3, sys_len=24, seed=0):
    """Deterministic zipf-tenant shared-prefix trace (the PR 13 bench
    workload, miniaturized): each request is one of ``n_tenants``
    system prompts + a unique tail token."""
    rng = np.random.default_rng(seed)
    tenants = [
        np.asarray(
            jax.random.randint(
                jax.random.PRNGKey(50 + t), (sys_len,), 0, CFG.vocab_size
            )
        )
        for t in range(n_tenants)
    ]
    w = 1.0 / np.arange(1, n_tenants + 1)
    w /= w.sum()
    return [
        np.concatenate(
            [tenants[rng.choice(n_tenants, p=w)],
             np.asarray([i % CFG.vocab_size], np.int32)]
        )
        for i in range(n_requests)
    ]


def test_affinity_beats_least_loaded_on_zipf_trace(model):
    """THE acceptance gate: on the zipf shared-prefix tenant trace,
    prefix-affinity routing yields a strictly higher cluster-wide
    prefix hit rate than least-loaded admission at EQUAL goodput (the
    streams are identical — placement never changes tokens). Arrivals
    interleave with scheduler steps so the router sees resident state,
    exactly like a live trace."""
    trace = _zipf_trace()
    kw = dict(BASE_KW, prefix_cache=True)
    results = {}
    for aff in (False, True):
        cl = ServingCluster(model, replicas=2, affinity=aff, **kw)
        rids = []
        for i, p in enumerate(trace):
            rids.append(cl.submit(p, 6, seed=i))
            cl.step()
            _check(cl)
        _drive(cl)
        cl._harvest()
        st = cl.stats()
        results[aff] = (
            [list(map(int, cl.finished[r].tokens)) for r in rids],
            st["prefill_tokens_saved"] / max(1, st["prompt_tokens_total"]),
            st["tokens_generated"],
            st,
        )
    streams_off, hit_off, toks_off, _ = results[False]
    streams_on, hit_on, toks_on, st_on = results[True]
    assert streams_on == streams_off, "placement must never change tokens"
    assert toks_on == toks_off, "equal goodput"
    assert hit_on > hit_off, (hit_on, hit_off)
    assert st_on["prefix_affinity_hits"] > 0
    # the first request of each tenant can't hit anywhere — those are
    # the fallback admissions, counted separately
    assert st_on["prefix_affinity_hits"] + st_on["routed_fallback"] == len(
        trace
    )


def test_affinity_load_imbalance_cap(model):
    """A cache hit may justify a bounded load gap, never starvation:
    with ``affinity_max_imbalance=0`` a loaded replica is ineligible
    even when it holds the whole prefix; with the default cap the same
    submission routes to the cache."""
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (33,), 0, CFG.vocab_size)
    )
    filler = _prompts(1, base_len=6, stride=0)[0]

    def drive_case(cap):
        cl = ServingCluster(
            model, replicas=2, affinity=True,
            affinity_max_imbalance=cap, **BASE_KW,
        )
        cl.submit(prompt, 6, seed=0)
        _drive(cl)  # replica 0 now holds the prefix, both loads 0
        cl.submit(filler, 6, seed=1)  # backlog on replica 0 (tie-break)
        rid = cl.submit(prompt, 6, seed=2)
        return cl, cl._route[rid][0]

    cl0, routed_capped = drive_case(0)
    assert routed_capped == 1, "cap 0: the loaded cache replica is barred"
    assert cl0.routed_fallback >= 1
    cl4, routed_free = drive_case(4)
    assert routed_free == 0, "cap 4: the cache hit justifies the gap"
    assert cl4.prefix_affinity_hits >= 1


# ---------------------------------------------------------------------------
# slow tier: the full feature cross + eviction pressure mid-handoff
# ---------------------------------------------------------------------------

MATRIX_SLOW = (
    dict(prefix_cache=False),
    dict(prefix_cache=True, prefill_chunk=4),
    dict(kv_quant="int8"),
    dict(speculate=2),
    dict(speculate=2, temperature=0.8, top_k=12),
)


@pytest.mark.slow
@pytest.mark.parametrize(
    "extra", MATRIX_SLOW,
    ids=["cache-off", "chunk", "kvq", "spec", "spec-sampled"],
)
def test_disagg_matrix_bit_identical_across_splits(model, extra):
    """The full landing gate: cache x chunk x spec(greedy+sampled) x
    kv-quant, each bit-identical to the monolithic engine across every
    pool split."""
    prompts = _prompts(4, base_len=5, stride=2)
    kw = dict(BASE_KW, **extra)
    ref = _mono_ref(model, prompts, 8, **kw)
    for split in ((1, 1), (2, 1), (2, 2)):
        got, cl = _disagg_run(model, prompts, 8, split, **kw)
        assert got == ref, (split, extra)
        assert cl.stats()["handoffs"] >= len(prompts), (split, extra)


@pytest.mark.slow
def test_disagg_eviction_under_pressure_mid_handoff(model):
    """A page pool too small to hold every request forces evictions on
    both pools while handoffs are in flight: evicted decode slots
    re-prefill LOCALLY (a decode-class engine is a full engine), the
    invariants hold after every step, and the streams still equal the
    monolithic engine under the same pressure."""
    prompts = _prompts(4, base_len=9, stride=3)
    kw = dict(BASE_KW, page_size=4, num_pages=8)
    ref = _mono_ref(model, prompts, 10, **kw)
    got, cl = _disagg_run(model, prompts, 10, (1, 1), **kw)
    assert got == ref
    st = cl.stats()
    assert st["evictions"] > 0, "the pressure shape must actually evict"
    assert st["handoffs"] >= len(prompts)
