"""Serving telemetry (midgpt_tpu.serving.telemetry): the metrics
registry (counters/gauges/fixed-bucket histograms, registry-backed
engine counter attributes), the pinned ``stats()`` key contract at
engine AND cluster level, per-request lifecycle tracing (event taxonomy,
derived queue-delay/TTFT/TBT/eviction-stall metrics under a fake clock),
the flight recorder (bounded rings, JSON dump), Chrome trace-event
export, and the two hard gates: greedy streams BITWISE identical with
tracing on vs off across the feature matrix (tracing selects the very
same cached program objects — prove_telemetry_inert), and replayed runs
producing identical event sequences with wall-clock excluded."""

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import (
    CLUSTER_STATS_KEYS,
    ENGINE_STATS_KEYS,
    EngineTelemetry,
    MetricsRegistry,
    FaultEvent,
    FaultPlan,
    ServingCluster,
    ServingEngine,
    chrome_trace,
)
from midgpt_tpu.serving.telemetry import EVENT_KINDS, percentile
from midgpt_tpu.telemetry import Histogram

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


_KW = dict(
    slots=2, page_size=8, window=4, temperature=0.0,
    cache_dtype=jnp.float32,
)


def _run(model, telemetry=None, n=3, n_new=8, clock=None, **kw):
    merged = dict(_KW, **kw)
    if clock is not None:
        merged["clock"] = clock
    eng = ServingEngine(model, telemetry=telemetry, **merged)
    rids = [eng.submit(p, n_new, seed=i) for i, p in enumerate(_prompts(n))]
    fin = eng.run()
    return eng, [list(map(int, fin[r].tokens)) for r in rids]


# ---------------------------------------------------------------------------
# Metrics registry units
# ---------------------------------------------------------------------------


def test_metrics_registry_units():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(3)
    assert reg.counter("hits") is c and c.value == 4
    depth = [7.0]
    reg.gauge("depth", fn=lambda: depth[0])
    reg.gauge("live", fn=lambda: 42.0)
    labels = {"a": 1}
    reg.attach_labels("reasons", labels)
    labels["b"] = 2  # attached by reference: snapshot sees live mutation
    snap = reg.snapshot()
    assert snap["counters"] == {"hits": 4}
    assert snap["gauges"] == {"depth": 7.0, "live": 42.0}
    assert snap["labeled"] == {"reasons": {"a": 1, "b": 2}}
    json.dumps(snap)  # the whole snapshot must be JSON-exportable


def test_histogram_fixed_buckets():
    h = Histogram("lat", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):
        h.observe(v)
    # <=0.1 catches 0.05 and the boundary 0.1; overflow catches 100
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5 and h.total == pytest.approx(102.65)
    h.reset()
    assert h.counts == [0, 0, 0, 0] and h.count == 0 and h.total == 0.0
    with pytest.raises(AssertionError):
        Histogram("bad", bounds=(1.0, 0.5))  # bounds must ascend


def test_latency_buckets_resolve_a_time_to_first_token():
    """The shared ladder: six buckets a decade from 1 ms to 10 s, so that
    the waits ``--metrics_out`` shows — a time to the first token of
    0.2-0.8 s in the serving cells — spread over five buckets, not two."""
    from midgpt_tpu.telemetry import LATENCY_BUCKETS_S as ladder

    assert (ladder[0], ladder[-1], len(ladder)) == (0.001, 10.0, 25)
    assert list(ladder) == sorted(set(ladder))
    for lo, hi in zip(ladder, ladder[6:]):
        assert hi == pytest.approx(10 * lo)  # one decade on, the same steps
    h = Histogram("ttft_s")
    assert h.bounds == ladder
    for v in (0.2, 0.25, 0.4, 0.6, 0.8):
        h.observe(v)
    assert sum(1 for c in h.counts if c) == 5
    # every bound prints as it is written (a Prometheus ``le`` label)
    assert all(repr(b) == f"{b:g}" for b in ladder if b < 1)


def test_percentile_nearest_rank():
    assert percentile([], 0.5) is None
    vals = [1.0, 2.0, 3.0, 4.0]
    assert percentile(vals, 0.0) == 1.0
    assert percentile(vals, 0.5) == 3.0
    assert percentile(vals, 0.99) == 4.0


# ---------------------------------------------------------------------------
# stats() is a documented, pinned contract (registry refactors must not
# drop a key bench_serving or the r6 queue reads)
# ---------------------------------------------------------------------------


def test_engine_stats_key_contract(model):
    eng, _ = _run(model)
    st = eng.stats()
    assert tuple(st.keys()) == ENGINE_STATS_KEYS, (
        "ServingEngine.stats() keys drifted from the "
        "telemetry.ENGINE_STATS_KEYS contract"
    )
    # the façade and the registry snapshot agree on the shared counters
    snap = eng.metrics_snapshot()
    for k in ("decode_dispatches", "prefill_dispatches",
              "tokens_generated", "evictions"):
        assert st[k] == snap["counters"][k]
    assert st["reject_reasons"] == snap["labeled"]["reject_reasons"]
    json.dumps(snap)
    # beside the dispatches, the device->host reads the step loop issued
    # (PR 35): one a window
    assert ENGINE_STATS_KEYS[1:3] == ("decode_dispatches", "device_reads")
    assert st["device_reads"] == snap["counters"]["device_reads"]
    assert st["device_reads"] == st["decode_dispatches"] > 0
    # the paged kernel's walk (PR 26), counted per decode step from the
    # scheduler's own lengths — pages the occupied slots hold, against the
    # pages their block tables could; behind it the recurrent state's keys
    # (PR 32), zero for a model without linear-attention layers
    assert ENGINE_STATS_KEYS[-13:-11] == ("kv_pages_walked", "kv_pages_table")
    assert ENGINE_STATS_KEYS[-11:-5] == (
        "state_resets", "state_reprefill_tokens", "prefix_hits_refused",
        "recurrent_slot_steps", "recurrent_state_bytes", "kv_bytes_live",
    )
    assert not any(st[k] for k in ENGINE_STATS_KEYS[-11:-6])
    assert 0 < st["kv_pages_walked"] <= st["kv_pages_table"]
    # behind them the walk's pages counted once each, and a latent cache's
    # keys (PR 34), zero for a model without latent attention
    assert ENGINE_STATS_KEYS[-5:-2] == (
        "kv_pages_distinct", "latent_layers", "latent_bytes_live",
    )
    # last, the prefill scan's rows of a Mamba model (PR 42): zero here
    assert ENGINE_STATS_KEYS[-2:] == ("ssm_rows_scanned", "ssm_rows_run")
    assert not st["ssm_rows_scanned"] and not st["ssm_rows_run"]
    assert 0 < st["kv_pages_distinct"] <= st["kv_pages_walked"]
    assert not st["latent_layers"] and not st["latent_bytes_live"]
    assert st["kv_pages_table"] == (
        st["windows"] * eng.window * eng.slots * eng.bt.shape[1]
    )


def test_cluster_stats_key_contract_and_aggregation(model):
    cl = ServingCluster(model, replicas=2, **_KW)
    prompts = _prompts(4)
    rids = [cl.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    cl.run()
    st = cl.stats()
    assert tuple(st.keys()) == CLUSTER_STATS_KEYS, (
        "ServingCluster.stats() keys drifted from the "
        "telemetry.CLUSTER_STATS_KEYS contract"
    )
    per = st["per_replica"]
    assert len(per) == 2
    for p in per:
        assert tuple(p.keys()) == ENGINE_STATS_KEYS
    # aggregation still sums the summable counters
    for k in ("decode_dispatches", "tokens_generated", "windows",
              "prompt_tokens_total"):
        assert st[k] == sum(p[k] for p in per)
    assert st["tokens_generated"] == sum(
        len(cl.finished[r].tokens) for r in rids
    )
    # a non-disaggregated cluster reports the disagg counters as flat
    # zeros — the keys are pinned either way
    assert st["prefill_replicas"] == 0 and st["decode_replicas"] == 0
    for k in ("handoffs", "handoff_pages_moved", "handoff_bytes",
              "handoff_failures", "prefix_affinity_hits",
              "routed_fallback"):
        assert st[k] == 0, k
    json.dumps(cl.metrics_snapshot())


def test_disagg_cluster_stats_same_contract(model):
    """A disaggregated cluster answers the SAME pinned key tuple — the
    pool split changes counter values, never the stats façade."""
    cl = ServingCluster(
        model, prefill_replicas=1, decode_replicas=1, **_KW
    )
    prompts = _prompts(3)
    [cl.submit(p, 6, seed=i) for i, p in enumerate(prompts)]
    cl.run()
    st = cl.stats()
    assert tuple(st.keys()) == CLUSTER_STATS_KEYS
    assert st["prefill_replicas"] == 1 and st["decode_replicas"] == 1
    assert st["handoffs"] == len(prompts)
    assert st["handoff_pages_moved"] > 0 and st["handoff_bytes"] > 0
    snap = cl.metrics_snapshot()
    assert snap["cluster"]["handoffs"] == st["handoffs"]
    assert snap["cluster"]["handoff_bytes"] == st["handoff_bytes"]
    json.dumps(snap)


def test_counter_attributes_are_registry_backed(model):
    eng, _ = _run(model)
    assert eng.decode_dispatches >= 1
    # the bench's warmup reset: plain attribute assignment must hit the
    # registry Counter (property setter), not shadow it
    eng.decode_dispatches = 0
    assert eng.metrics.counter("decode_dispatches").value == 0
    assert eng.stats()["decode_dispatches"] == 0
    eng.decode_dispatches += 5
    assert eng.metrics_snapshot()["counters"]["decode_dispatches"] == 5


# ---------------------------------------------------------------------------
# Lifecycle tracing + derived metrics (fake clock: derived values exact)
# ---------------------------------------------------------------------------


def test_lifecycle_event_taxonomy_and_derived_metrics(model):
    tick = itertools.count()
    eng, streams = _run(
        model, telemetry=True, clock=lambda: float(next(tick)),
        prefill_chunk=4,
    )
    tele = eng.telemetry
    kinds = {ev.kind for ev in tele.events}
    assert kinds <= set(EVENT_KINDS)
    assert {"submit", "queued", "admitted", "prefill_chunk",
            "decode_window", "tokens", "finished"} <= kinds
    for rid, toks in enumerate(streams):
        evs = tele.request_log[rid]
        order = [ev.kind for ev in evs]
        # lifecycle orders correctly: submitted, queued, admitted before
        # any tokens, finished last
        assert order[0] == "submit" and order[1] == "queued"
        assert order.index("admitted") < order.index("tokens")
        assert order[-1] == "finished"
        m = tele.request_metrics(rid)
        assert m["finished"] and m["tokens"] == len(toks)
        # fake clock: every derived value is an exact tick difference
        assert m["queue_delay_s"] >= 0 and float(m["queue_delay_s"]).is_integer()
        assert m["ttft_s"] > 0
        assert len(m["tbt_s"]) == len(toks) - 1
        assert m["dispatches"] >= 1
        assert m["tokens_per_dispatch"] == pytest.approx(
            m["tokens"] / m["dispatches"]
        )
        assert m["eviction_stall_s"] == 0.0
    # events carry the scheduler-step key space (fault_step convention)
    assert all(ev.step <= eng.fault_step for ev in tele.events)
    # the latency histograms populated from the same clock
    snap = eng.metrics_snapshot()
    assert snap["histograms"]["ttft_s"]["count"] == len(streams)
    assert snap["histograms"]["queue_delay_s"]["count"] == len(streams)
    assert snap["histograms"]["tbt_s"]["count"] == sum(
        len(s) - 1 for s in streams
    )
    assert snap["histograms"]["dispatch_s"]["count"] == eng.decode_dispatches


def test_eviction_stall_and_park_resume_events(model):
    """A scripted allocator exhaustion parks the lone request; telemetry
    must show evicted -> parked -> resumed -> admitted and account the
    outage as eviction stall."""
    plan = FaultPlan([FaultEvent(step=2, kind="exhaust", hold_steps=2)])
    kw = dict(
        slots=1, page_size=4, num_pages=4, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefix_cache=False,
        fault_hook=plan.hook(0), telemetry=True,
    )
    eng = ServingEngine(model, **kw)
    rid = eng.submit(_prompts(1, base_len=3)[0], 12)
    for _ in range(100):
        if not eng.has_work:
            break
        eng.step()
    assert rid in eng.finished
    tele = eng.telemetry
    kinds = [ev.kind for ev in tele.request_log[rid]]
    i_evict = kinds.index("evicted")
    assert kinds[i_evict + 1] == "parked"
    assert "resumed" in kinds[i_evict:]
    # re-admitted after the quarantine release (possibly bounced more
    # than once while the hold was still on)
    assert kinds.count("admitted") >= 2
    m = tele.request_metrics(rid)
    assert m["eviction_stall_s"] > 0
    assert m["evictions"] >= 1
    # the scripted injection itself is on the timeline
    faults = [ev for ev in tele.events if ev.kind == "fault"]
    assert len(faults) == 1 and faults[0].data["fault"] == "exhaust"


def test_shed_and_deferred_events(model):
    shed = ServingEngine(
        model, max_queue=1, overload_policy="shed", telemetry=True, **_KW
    )
    shed.submit(_prompts(1)[0], 4)
    with pytest.raises(Exception):
        shed.submit(_prompts(2)[1], 4)
    assert [ev.kind for ev in shed.telemetry.events
            if ev.kind in ("shed", "deferred")] == ["shed"]

    defer = ServingEngine(
        model, max_queue=1, overload_policy="defer", telemetry=True, **_KW
    )
    defer.submit(_prompts(1)[0], 4)
    with pytest.raises(Exception):
        defer.submit(_prompts(2)[1], 4)
    assert [ev.kind for ev in defer.telemetry.events
            if ev.kind in ("shed", "deferred")] == ["deferred"]


# ---------------------------------------------------------------------------
# The hard gate: tracing is inert — identical programs, bitwise streams,
# replay-deterministic event sequences
# ---------------------------------------------------------------------------


def _identity_case(model, **kw):
    eng_off, s_off = _run(model, telemetry=None, **kw)
    eng_on, s_on = _run(model, telemetry=True, **kw)
    assert s_on == s_off, f"streams diverged with tracing on ({kw})"
    # program-cache identity: tracing must select the SAME jitted
    # callables (telemetry is not a factory parameter), so the audit
    # matrix proven for the untraced programs covers the traced engine
    for attr in ("_window_fn", "_verify_fn"):
        assert getattr(eng_on, attr) is getattr(eng_off, attr), attr
    assert len(eng_on.telemetry.events) > 0
    return eng_on


def test_telemetry_identity_default(model):
    _identity_case(model)


def test_telemetry_false_means_off(model):
    """bench_serving passes the computed bool straight through —
    telemetry=False must construct a tracing-off engine, not crash
    (the r6 `serving_tele_off` overhead rung is exactly this path)."""
    eng, _ = _run(model, telemetry=False)
    assert eng.telemetry is None
    assert eng.stats()["tokens_generated"] > 0


def test_telemetry_identity_spec_chunked(model):
    _identity_case(model, speculate=4, prefill_chunk=4)


@pytest.mark.slow
@pytest.mark.parametrize(
    "kw",
    [
        dict(prefix_cache=False),
        dict(prefill_chunk=8, kv_quant="int8"),
        dict(prefill_chunk=8, speculate=4, kv_quant="int8"),
        dict(prefix_cache=False, prefill_chunk=8, speculate=4),
        dict(kv_quant="int8", cache_dtype=jnp.bfloat16),
    ],
    ids=["nocache", "chunk-kv8", "chunk-spec-kv8", "nocache-chunk-spec",
         "kv8-bf16"],
)
def test_telemetry_identity_matrix(model, kw):
    """Acceptance: greedy streams with telemetry on are bitwise
    identical to telemetry off across cache x chunk x spec x
    kv-quant."""
    _identity_case(model, **kw)


def test_replay_produces_identical_event_sequence(model):
    run1 = _identity_case(model, prefill_chunk=4)
    eng2, _ = _run(model, telemetry=True, prefill_chunk=4)
    sig1 = run1.telemetry.sequence_signature()
    sig2 = eng2.telemetry.sequence_signature()
    assert sig1 == sig2, (
        "replaying the same trace must reproduce the event sequence "
        "(wall-clock annotations excluded)"
    )
    # ... and the signatures really do exclude wall clock: the raw
    # timestamps differ between the runs
    t1 = [ev.t for ev in run1.telemetry.events]
    t2 = [ev.t for ev in eng2.telemetry.events]
    assert t1 != t2


def test_prove_telemetry_inert_harness():
    from midgpt_tpu.analysis.harness import prove_telemetry_inert

    rep = prove_telemetry_inert(speculate=4, prefill_chunk=4)
    assert rep["ok"] and rep["streams_identical"]
    assert "_verify_fn" in rep["programs_identical"]
    assert rep["events_recorded"] > 0


# ---------------------------------------------------------------------------
# Flight recorder + Chrome trace export
# ---------------------------------------------------------------------------


def test_flight_recorder_rings_bounded(model):
    tele = EngineTelemetry(ring=8, dispatch_ring=4)
    eng, _ = _run(model, telemetry=tele, n=3, n_new=8)
    assert len(tele.events) == 8, "event ring must cap at its capacity"
    assert len(tele.dispatches) <= 4
    # the ring keeps the MOST RECENT events (a flight recorder, not a
    # head sample): the last event of the run is present
    assert tele.events[-1].kind == "finished"


def test_flight_dump_structure(model, tmp_path):
    eng, streams = _run(model, telemetry=True)
    path = str(tmp_path / "flight.json")
    rec = eng.flight_dump("unit_test", path=path, extra={"replica": 7})
    on_disk = json.load(open(path))
    assert on_disk["reason"] == "unit_test" and on_disk["replica"] == 7
    assert on_disk["path"] == path
    assert on_disk["stats"]["tokens_generated"] == sum(
        len(s) for s in streams
    )
    assert on_disk["metrics"]["counters"]["decode_dispatches"] >= 1
    evs = on_disk["telemetry"]["events"]
    assert evs and {"seq", "step", "kind", "t"} <= set(evs[0])
    assert on_disk["telemetry"]["dispatches"]
    assert rec["fault_step"] == eng.fault_step
    # without tracing the dump still carries stats + metrics
    eng2, _ = _run(model, telemetry=None)
    rec2 = eng2.flight_dump("no_trace")
    assert rec2["telemetry"] is None and rec2["stats"]["windows"] >= 1


def test_chrome_trace_structure(model):
    eng, streams = _run(model, telemetry=True, prefill_chunk=4)
    trace = chrome_trace(eng.telemetry)
    json.dumps(trace)
    evs = trace["traceEvents"]
    assert evs
    for ev in evs:
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
    # request lanes: one active span + one tokens instant per request
    req_spans = [e for e in evs if e["ph"] == "X" and e["pid"] == 1]
    assert {e["tid"] for e in req_spans} == set(range(len(streams)))
    assert any(e["name"] == "active" for e in req_spans)
    # dispatch lanes carry the program launches
    disp = [e for e in evs if e["ph"] == "X" and e["pid"] == 2]
    assert len(disp) == len(eng.telemetry.dispatches)
    assert {e["name"] for e in disp} <= {
        "decode_window", "verify_dispatch", "prefill_chunk"
    }


def test_chrome_trace_handoff_spans(model):
    """Page handoffs render as X-phase spans on the prefill replica's
    dispatch lane (their own tid), carrying page/byte args — and the
    decode replica's lane shows decode windows only: the class split is
    visible straight off the timeline."""
    cl = ServingCluster(
        model, prefill_replicas=1, decode_replicas=1, telemetry=True,
        **_KW,
    )
    prompts = _prompts(3)
    [cl.submit(p, 6, seed=i) for i, p in enumerate(prompts)]
    cl.run()
    pre, dec = cl.engines
    evs = chrome_trace(pre.telemetry)["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X" and e["pid"] == 2]
    hand = [e for e in spans if e["name"] == "handoff"]
    assert len(hand) == len(prompts)
    assert {e["tid"] for e in hand} == {3}, "handoffs get their own lane"
    for e in hand:
        assert e["args"]["pages"] > 0 and e["args"]["bytes"] > 0
    assert not any(e["name"] == "decode_window" for e in spans)
    dspans = [
        e for e in chrome_trace(dec.telemetry)["traceEvents"]
        if e["ph"] == "X" and e["pid"] == 2
    ]
    assert dspans and all(e["name"] == "decode_window" for e in dspans)


def test_chrome_trace_engine_lane_carries_ridless_events(model):
    """shed/deferred fire before any rid exists and scripted faults are
    engine-scoped — they render on the engine lane (from the recency
    ring), not silently vanish from the export."""
    eng = ServingEngine(
        model, max_queue=1, overload_policy="shed", telemetry=True, **_KW
    )
    eng.submit(_prompts(1)[0], 4)
    with pytest.raises(Exception):
        eng.submit(_prompts(2)[1], 4)
    eng.run()
    evs = chrome_trace(eng.telemetry)["traceEvents"]
    lane = [e for e in evs if e.get("pid") == 3 and e["ph"] == "i"]
    assert [e["name"] for e in lane] == ["shed"]
    assert all(e["ts"] >= 0 for e in lane)


def test_profiler_hooks_fire_at_step_window(model, tmp_path, monkeypatch):
    calls = []
    import jax.profiler as prof

    monkeypatch.setattr(
        prof, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(prof, "stop_trace", lambda: calls.append(("stop",)))
    tele = EngineTelemetry(
        profile_dir=str(tmp_path), profile_steps=(2, 3)
    )
    _run(model, telemetry=tele)
    assert calls == [("start", str(tmp_path)), ("stop",)]

    # a workload draining BEFORE the configured stop step must still
    # finalize the trace (run() stops an in-flight profile at drain —
    # a dangling trace is unwritten and poisons the next start_trace)
    calls.clear()
    tele2 = EngineTelemetry(
        profile_dir=str(tmp_path), profile_steps=(2, 10_000)
    )
    _run(model, telemetry=tele2)
    assert calls == [("start", str(tmp_path)), ("stop",)]
    assert not tele2._profiling


# ---------------------------------------------------------------------------
# bench_serving record contract (slow: subprocess drive of the CLI)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bench_serving_telemetry_record_contract(tmp_path):
    """The tiny-preset bench with chaos + --timeline_dir must emit the
    telemetry-derived record keys, the Perfetto timeline artifacts, and
    the dead-replica flight dump — the exact surface the r6 queue and
    the serving-chaos CI job consume."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "rec.json")
    tl = str(tmp_path / "tl")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    prom = str(tmp_path / "metrics.prom")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "bench_serving.py"),
         "--preset", "tiny", "--dp_replicas", "2",
         "--fault_plan", "1:transient@0;2:crash@0",
         "--dispatch_timeout_s", "60", "--deadline_s", "600",
         "--timeline_dir", tl, "--metrics_out", prom, "--out", out],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out))
    assert rec["status"] == "ok"
    assert rec["serve_telemetry"] == "on"
    assert rec["serve_tbt_p99_ms"] is not None
    assert rec["serve_queue_delay_p50_ms"] is not None
    # floor + attainment + MFU ride every record (PR 15 contract): the
    # static per-token floor, the measured ms/tok, their ratio, and the
    # compute-side fraction — the ledger's static/wall-clock key split
    # depends on this inventory
    assert rec["serve_floor_ms_per_tok_static"] > 0
    assert rec["serve_ms_per_tok"] > 0
    assert rec["serve_attainment_frac"] == pytest.approx(
        rec["serve_floor_ms_per_tok_static"] / rec["serve_ms_per_tok"],
        rel=1e-2,
    )
    # utilization against a chip's peak is not measured on the CPU
    assert rec["serve_mfu"] is None
    assert rec["serve_hbm_floor_ms_static"] > 0
    # --metrics_out: Prometheus text exposition over the cluster
    # registry, path recorded in-band
    assert rec["serve_metrics_out"] == prom
    text = open(prom).read()
    assert "# TYPE midgpt_tokens_generated_total counter" in text
    assert 'replica="0"' in text and 'replica="1"' in text
    assert 'scope="cluster"' in text
    assert rec["serve_requests_finished"] == rec["serve_requests"]
    # disagg/affinity keys ride EVERY record — flat defaults off the
    # monolithic dp=2 path (the disagg CI job asserts the live values)
    assert rec["serve_disagg"] is None
    assert rec["serve_affinity"] == "off"
    assert rec["serve_ttft_by_class"] is None
    assert rec["serve_handoff_count"] == 0
    assert rec["serve_handoff_pages"] == 0
    assert rec["serve_handoff_bytes"] == 0
    assert rec["serve_handoff_failures"] == 0
    assert rec["serve_prefix_affinity_hits"] == 0
    assert rec["serve_routed_fallback"] == 0
    for f in rec["serve_timeline_files"]:
        assert os.path.exists(f), f
    names = {os.path.basename(f) for f in rec["serve_timeline_files"]}
    assert {"timeline_replica0.json", "request_metrics.json",
            "metrics_snapshot.json"} <= names
    assert rec["serve_flight_dumps"], "the crashed replica must dump"
    dump = json.load(open(rec["serve_flight_dumps"][0]))
    assert dump["reason"] == "crashed" and dump["telemetry"]["events"]
    # the timeline is a loadable Chrome trace
    tr = json.load(open(os.path.join(tl, "timeline_replica0.json")))
    assert tr["traceEvents"]


@pytest.mark.slow
def test_bench_serving_sampled_spec_record_contract(tmp_path):
    """--temperature composed with --spec on (rejection-sampling
    verification): the record must carry the sampling shape next to the
    speculation counters — the surface the r6 queue's spec-sampled rung
    pair and the serving-choreo sampled-chat CI leg consume."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "rec_sampled.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "bench_serving.py"),
         "--preset", "tiny", "--spec", "on", "--spec_len", "4",
         "--temperature", "0.8", "--top_k", "20", "--repetitive",
         "--window", "2", "--deadline_s", "600", "--out", out],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out))
    assert rec["status"] == "ok"
    assert rec["serve_temperature"] == 0.8
    assert rec["serve_top_k"] == 20
    # every decode dispatch IS a verify dispatch with spec on, and the
    # acceptance rate is the rejection sampler's measured accept
    # fraction (a float even when the random-init model accepts none)
    assert rec["serve_verify_dispatches"] > 0
    assert rec["serve_spec_drafted_tokens"] > 0
    assert rec["serve_spec_acceptance_rate"] is not None
    assert "T=0.8" in rec["serve_shape"]
    assert "topk=20" in rec["serve_shape"]


@pytest.mark.slow
def test_bench_serving_longctx_record_contract(tmp_path):
    """--prompt_len + --prefill_sp + --spill (the long-context serving
    rungs): the record must carry the resolved SP mode, the long-prompt
    TTFT lane, the static SP-prefill floor pair, and the spill
    counters — the exact surface the r6 sp-off/sp-on pair and the
    spill-pressure rung consume. The undersized pool must actually
    spill AND the run must still drain clean (the no-wedge contract)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "rec_longctx.json")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "bench_serving.py"),
         "--preset", "tiny", "--prompt_len", "64", "--sys_prompt_len", "64",
         "--requests", "6", "--slots", "1", "--tp", "2",
         "--prefill_chunk", "32", "--spill", "on", "--num_pages", "10",
         "--deadline_s", "600", "--out", out],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out))
    assert rec["status"] == "ok"
    # prefill_sp="auto" resolved on against the tp=2 mesh, in the
    # record AND the shape (the rung pair pins off/on explicitly)
    assert rec["serve_prefill_sp"] == "on"
    assert "sp=on" in rec["serve_shape"] and "spill" in rec["serve_shape"]
    assert rec["serve_prompt_len"] == 64
    # every prompt is long by construction, so the long lane equals the
    # overall p99 and must be populated
    assert rec["serve_ttft_long_p99"] is not None
    assert rec["serve_ttft_long_p99"] == rec["serve_ttft_p99_ms"]
    # the prefill compute floors divide by the chip's peak FLOP/s: the
    # keys ride the record, not measured on the CPU
    assert rec["serve_prefill_floor_ms_static"] is None
    assert rec["serve_prefill_sp_floor_ms_static"] is None
    # the 10-page pool is smaller than the 6-request working set: cold
    # chains must have spilled to host RAM, and the host store's
    # cumulative residency may legitimately exceed the pool itself
    assert rec["serve_num_pages"] == 10
    assert rec["serve_spilled_pages"] > 0
    assert rec["serve_spill_resident_pages"] > 0
    for k in ("serve_spill_faultback_pages", "serve_spill_prefetch_pages",
              "serve_spill_readmissions", "serve_spill_discards"):
        assert isinstance(rec[k], int) and rec[k] >= 0, k
    # requested vs resolved kernel (ISSUE 20): the record carries BOTH —
    # a long-context row claiming pallas cannot hide an XLA fallback.
    # This CPU run requested the default "auto" and must have resolved
    # to a concrete backend (xla off-TPU).
    assert rec["serve_paged_kernel"] == "auto"
    assert rec["serve_paged_kernel_resolved"] == "xla"
    # no-wedge: everything finished, nothing shed or deferred
    assert rec["serve_requests_finished"] == rec["serve_requests"]
    assert rec["serve_shed_requests"] == 0
    assert rec["serve_error"] is None


# ---------------------------------------------------------------------------
# Shared substrate (PR 15): serving re-exports the midgpt_tpu.telemetry
# core unchanged, and the Prometheus exporter renders registry
# snapshots against the pinned stats-key contracts
# ---------------------------------------------------------------------------


def test_engine_telemetry_is_the_serving_taxonomy_of_the_shared_log():
    """EngineTelemetry is the serving-taxonomy specialization of the
    shared TelemetryLog; the names the engine, the cluster and
    bench_serving import from serving.telemetry are the substrate's own
    objects."""
    import midgpt_tpu.serving.telemetry as serving_tele
    import midgpt_tpu.telemetry as core
    from midgpt_tpu.telemetry import TelemetryLog

    for name in ("MetricsRegistry", "percentile", "write_json"):
        assert getattr(serving_tele, name) is getattr(core, name), name
    assert issubclass(EngineTelemetry, TelemetryLog)
    assert EngineTelemetry.event_kinds == EVENT_KINDS
    # the base rejects kinds outside the subclass taxonomy
    t = EngineTelemetry()
    with pytest.raises(AssertionError):
        t.emit("window_launch", step=0, t=0.0)


def test_prometheus_text_format_units():
    """Exposition-format details the scrape side depends on: counters
    get _total, labeled families one series per key, histograms render
    CUMULATIVE buckets + +Inf + _sum/_count, labels merge, and each
    family gets exactly one # TYPE header even across snapshots."""
    from midgpt_tpu.telemetry import prometheus_text

    reg = MetricsRegistry()
    reg.counter("hits").inc(3)
    reg.attach_labels("reasons", {"full": 2})
    reg.gauge("depth", fn=lambda: 1.5)
    h = reg.histogram("lat", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    reg2 = MetricsRegistry()
    reg2.counter("hits").inc(7)
    text = prometheus_text([
        ({"replica": "0"}, reg.snapshot()),
        ({"replica": "1"}, reg2.snapshot()),
    ])
    assert 'midgpt_hits_total{replica="0"} 3' in text
    assert 'midgpt_hits_total{replica="1"} 7' in text
    assert 'midgpt_reasons_total{key="full",replica="0"} 2' in text
    assert 'midgpt_depth{replica="0"} 1.5' in text
    assert 'midgpt_lat_bucket{le="0.1",replica="0"} 1' in text
    # cumulative: the 1.0 bucket includes the 0.1 bucket's observation
    assert 'midgpt_lat_bucket{le="1.0",replica="0"} 1' in text
    assert 'midgpt_lat_bucket{le="+Inf",replica="0"} 2' in text
    assert 'midgpt_lat_count{replica="0"} 2' in text
    assert text.count("# TYPE midgpt_hits_total counter") == 1


def test_prometheus_text_covers_engine_counter_contract(model):
    """Every registry-backed engine counter (the objects behind the
    pinned ENGINE_STATS_KEYS facade) must appear in the exposition —
    the exporter cannot silently drop part of the contract surface."""
    from midgpt_tpu.serving.engine import _ENGINE_COUNTERS
    from midgpt_tpu.telemetry import prometheus_text

    eng, _ = _run(model)
    text = prometheus_text(eng.metrics_snapshot())
    for name in _ENGINE_COUNTERS:
        assert f"midgpt_{name}_total" in text, name
    # always-on histograms ride along (queue delay observed per admit)
    assert "midgpt_queue_delay_s_bucket" in text
    assert "# TYPE midgpt_tokens_generated_total counter" in text


def test_prometheus_text_cluster_expands_replicas(model):
    """A cluster snapshot expands to per-replica series plus the
    cluster-level scalars as scope="cluster" gauges."""
    from midgpt_tpu.telemetry import prometheus_text

    cl = ServingCluster(model, replicas=2, **_KW)
    for i, p in enumerate(_prompts(4)):
        cl.submit(p, 8, seed=i)
    cl.run()
    text = prometheus_text(cl.metrics_snapshot())
    assert 'midgpt_tokens_generated_total{replica="0"}' in text
    assert 'midgpt_tokens_generated_total{replica="1"}' in text
    assert 'midgpt_failovers{scope="cluster"} 0' in text
    assert 'midgpt_dp_replicas{scope="cluster"} 2' in text
