"""Serving telemetry: per-request lifecycle tracing, a metrics
registry, and a fault flight recorder.

The serving stack through PR 11 is feature-rich and machine-audited but
blind at runtime: ``ServingEngine.stats()`` was a flat counter dict,
per-request latency existed only as bench_serving's aggregate TTFT
percentiles, and the two wedged hardware sessions (r4/r5) produced *no*
timing data at all. This module is the serving half of the observability
substrate — the registry/event-ring/flight-recorder core now lives in
:mod:`midgpt_tpu.telemetry` (shared with the training loop's
:mod:`midgpt_tpu.train_telemetry`).
Four pieces, one design constraint:

1. **Per-request lifecycle tracing** (:class:`EngineTelemetry`): typed
   events — ``submit``, ``queued``, ``admitted``, ``prefill_chunk``,
   ``decode_window``, ``verify_dispatch``, ``tokens``, ``evicted``,
   ``parked``, ``resumed``, ``finished``, ``shed``, ``deferred``,
   ``fault`` — keyed to *engine-local scheduler steps* (the FaultPlan
   convention: a chaos replay produces the identical event *sequence*)
   with monotonic wall-clock annotations from the engine's injectable
   ``clock``. Wall-clock lives ONLY in the ``t``/``dur`` fields, never
   in ``data``, so :meth:`EngineTelemetry.sequence_signature` (events
   minus wall-clock) is replay-deterministic and directly comparable
   across runs. Derived per-request metrics
   (:meth:`EngineTelemetry.request_metrics`): queue delay, TTFT,
   per-token TBT, eviction-stall time, tokens-per-dispatch.

2. **A metrics registry** (:class:`MetricsRegistry`): counters, gauges
   (callback-evaluated at snapshot), and fixed-bucket histograms. The
   engine's ad-hoc counter attributes are registry-backed (properties
   over :class:`Counter` objects), so the registry is the single source
   and ``stats()`` is a stable façade over it — the exact key inventory
   is the :data:`ENGINE_STATS_KEYS`/:data:`CLUSTER_STATS_KEYS` contract,
   pinned by test. ``snapshot()`` is JSON-exportable, and
   :func:`midgpt_tpu.telemetry.prometheus_text` renders it in Prometheus
   text exposition format (``bench_serving --metrics_out``).

3. **A flight recorder**: a bounded ring of recent events plus the last
   N dispatch records, dumped as a structured JSON artifact
   (``ServingEngine.flight_dump``) from the cluster's fault paths
   (replica crash, watchdog trip, exhausted retries — see
   ``ServingCluster(flight_dir=...)``) and from bench_serving's
   whole-trace watchdog — so a wedged hardware run yields a timeline,
   not a bare ``{"status": "watchdog"}`` row.

4. **Timeline export** in Chrome trace-event format
   (:meth:`EngineTelemetry.chrome_trace` — request lanes + dispatch
   lanes, openable in Perfetto / chrome://tracing), plus optional
   ``jax.profiler`` start/stop hooks around a selected scheduler-step
   window (``profile_dir``/``profile_steps``).

**Two switches, one log**: ``ServingEngine(telemetry=...)``, and a
profiler session. An engine built without a log attaches one of its own
at the first ``step()`` that finds a ``jax.profiler`` session open, with
the beginning of every request then in flight back-filled from the
stamps the request carries, and drops it at the first step after the
session closed; either kind of log is kept by
``midgpt_tpu.telemetry.session_logs()`` past its engine, for whoever
reads the session's trace. A request's lifetime cannot be a
``TraceAnnotation`` (lifetimes overlap, annotations nest on a thread):
its events are on the trace's clock by their engine step — the k-th
``midgpt.engine.step`` span of the trace is step
``session_steps[0] + k``.

**The hard constraint**: tracing must not perturb the dispatch
pipeline. Telemetry is NOT a parameter of any program factory — an
engine with tracing on selects the *identical cached jitted callables*
(asserted by ``analysis.harness.prove_telemetry_inert`` and the
``--telemetry`` audit leg), every emission reads only host-side state
the scheduler already holds (no device access, no new syncs), and
dispatch durations are stamped at the window's *existing* device->host
harvest read. When disabled, each emission site costs one ``is None``
check. Greedy streams with telemetry on are bitwise identical to
telemetry off across the whole feature matrix (tests/test_telemetry.py).

Granularity honesty: the engine emits tokens in window batches (K per
dispatch), so per-token TBT is the gap between consecutive *harvest*
timestamps — within one window the gap is 0, across windows it is the
window's wall time. The percentiles therefore describe the cadence a
streaming client would actually see from this engine, not a smoothed
per-token rate.
"""

from __future__ import annotations

import typing as tp

from midgpt_tpu.telemetry import (  # noqa: F401 — the names the engine,
    # the cluster and bench_serving import from here
    MetricsRegistry,
    TelemetryLog,
    percentile,
    write_json,
)

__all__ = [
    "CLUSTER_STATS_KEYS",
    "ENGINE_STATS_KEYS",
    "EngineTelemetry",
    "EVENT_KINDS",
    "MetricsRegistry",
    "chrome_trace",
    "percentile",
]


# ---------------------------------------------------------------------------
# The stats() façade contract (satellite: pinned by tests/test_telemetry.py)
# ---------------------------------------------------------------------------

#: The exact key inventory of ``ServingEngine.stats()``. bench_serving
#: and the r6 hardware queue read these keys by name; the registry
#: refactor (counters behind properties) must never drop or rename one.
ENGINE_STATS_KEYS: tp.Tuple[str, ...] = (
    "tp",
    "decode_dispatches",
    "device_reads",
    "windows_ahead",
    "prefill_dispatches",
    "prefill_chunks",
    "prefill_steps",
    "copy_dispatches",
    "tokens_generated",
    "windows",
    "slot_occupancy",
    "evictions",
    "free_pages",
    "cached_pages",
    "cold_reclaims",
    "spilled_pages",
    "spill_faultback_pages",
    "spill_prefetch_pages",
    "spill_readmissions",
    "spill_discards",
    "spill_resident_pages",
    "prompt_tokens_total",
    "prefill_tokens_saved",
    "prefill_tokens_computed",
    "prefix_hit_rate",
    "tokens_per_dispatch",
    "verify_dispatches",
    "spec_drafted_tokens",
    "spec_accepted_tokens",
    "spec_acceptance_rate",
    "admission_rejected",
    "reject_reasons",
    "shed_requests",
    "deferred_submits",
    "livelock_parks",
    "overload_parks",
    "parked_requests",
    "cancelled_requests",
    "deadline_shed_requests",
    "faults_injected",
    "denoise_forwards",
    "commit_forwards",
    "blocks_committed",
    "tokens_revealed",
    "block_rows_live",
    "block_rows_run",
    "expert_rows_routed",
    "expert_rows_dropped",
    "expert_rows_max",
    "experts_touched",
    "expert_layer_forwards",
    "kv_pages_walked",
    "kv_pages_table",
    "state_resets",
    "state_reprefill_tokens",
    "prefix_hits_refused",
    "recurrent_slot_steps",
    "recurrent_state_bytes",
    "kv_bytes_live",
    "kv_pages_distinct",
    "latent_layers",
    "latent_bytes_live",
    "ssm_rows_scanned",
    "ssm_rows_run",
)

#: ``ServingCluster.stats()`` = the summed engine inventory plus these
#: cluster-level keys (aggregation: sums, except the documented means).
CLUSTER_STATS_KEYS: tp.Tuple[str, ...] = ENGINE_STATS_KEYS + (
    "dp_replicas",
    "prefill_replicas",
    "decode_replicas",
    "watchdog_trips",
    "retries",
    "failovers",
    "requeued_requests",
    "handoffs",
    "handoff_pages_moved",
    "handoff_bytes",
    "handoff_failures",
    "prefix_affinity_hits",
    "routed_fallback",
    "dead_replicas",
    "replica_health",
    "replica_health_reason",
    "per_replica",
)


# ---------------------------------------------------------------------------
# Events
# ---------------------------------------------------------------------------

#: The lifecycle taxonomy. ``submit`` = accepted by admission control;
#: ``queued`` = entered the wait queue (also fired by failover
#: resubmission); ``admitted`` = took a decode slot; ``prefill_chunk`` /
#: ``decode_window`` / ``verify_dispatch`` = one compiled-program launch
#: (dispatch lanes); ``tokens`` = one slot's harvest from one dispatch;
#: ``evicted``/``parked``/``resumed`` = the preemption/overload paths;
#: ``finished`` = the request completed; ``shed``/``deferred`` =
#: bounded-queue overload outcomes; ``fault`` = a scripted FaultPlan
#: injection firing; ``cancelled`` = the submitter tore the request
#: down (slot reclaimed, pages released — serving.frontdoor);
#: ``deadline_shed`` = the scheduler dropped a queued/parked request
#: whose deadline passed before dispatch (the pre-dispatch SLO shed);
#: ``handoff`` = a prefill→decode page move (direction="export" on the
#: source engine, "import" on the destination — disaggregated pools);
#: ``routed_affinity`` / ``routed_fallback`` = the cluster's admission
#: decision (prefix-affinity hit vs least-loaded fallback), emitted on
#: the chosen replica's telemetry; ``step`` = the slot census of one
#: engine step that had work, taken where its window is (or would have
#: been) dispatched: ``decoding`` + ``prefilling`` + ``empty`` = the
#: engine's slots, ``queued`` and ``parked`` the requests waiting for one.
EVENT_KINDS: tp.Tuple[str, ...] = (
    "submit",
    "queued",
    "admitted",
    "prefill_chunk",
    "decode_window",
    "verify_dispatch",
    "tokens",
    "evicted",
    "parked",
    "resumed",
    "finished",
    "shed",
    "deferred",
    "fault",
    "cancelled",
    "deadline_shed",
    "handoff",
    "routed_affinity",
    "routed_fallback",
    "step",
)


# ---------------------------------------------------------------------------
# EngineTelemetry
# ---------------------------------------------------------------------------


class EngineTelemetry(TelemetryLog):
    """Per-engine event log + flight-recorder rings (the serving
    specialization of :class:`midgpt_tpu.telemetry.TelemetryLog`:
    the serving lifecycle taxonomy plus derived per-request metrics).

    Two views of one stream: ``request_log`` keeps every event per
    request id (the timeline / derived-metrics view, bounded per
    request), while ``events`` is the bounded *recency* ring the flight
    recorder dumps (``ring`` events). ``dispatches`` is the companion
    ring of the last ``dispatch_ring`` compiled-program launches.

    ``profile_dir`` + ``profile_steps=(start, stop)`` arm the optional
    ``jax.profiler`` hooks: the engine starts a profiler trace at the
    top of scheduler step ``start`` and stops it at the top of ``stop``
    — a bounded window around exactly the steps under investigation,
    host-driven, with no effect on the compiled programs.
    """

    event_kinds = EVENT_KINDS

    # -- derived per-request metrics ---------------------------------------

    def token_times(self, rid: int) -> tp.List[float]:
        """Each emitted token's harvest timestamp (a ``tokens`` event
        with ``n`` tokens contributes ``n`` copies of its ``t``)."""
        out: tp.List[float] = []
        for ev in self.request_log.get(rid, ()):
            if ev.kind == "tokens":
                out.extend([ev.t] * ev.data.get("n", 0))
        return out

    def request_metrics(self, rid: int) -> tp.Optional[tp.Dict[str, tp.Any]]:
        """Derived lifecycle metrics for one request (None if the rid
        was never seen): queue delay (submit -> first admission), TTFT
        (submit -> first token), the per-token TBT series (consecutive
        harvest-timestamp gaps — see the module docstring's granularity
        note), eviction-stall time (eviction/park -> re-admission, summed
        over preemptions), tokens, and tokens-per-dispatch (dispatches =
        harvests that included this request).

        The time to the first token in three parts that add up to it,
        evictions or none: ``queue_delay_s``; ``prefill_s``, first
        admission -> prompt resident (its chunks' enqueues and every
        engine step it waited for the prefill budget between them — the
        enqueue of its last chunk, or the admission that found the whole
        prompt cached, whichever came last before the first token); and
        ``first_window_s``, from there to the harvest that brought the
        first token: the window it had to wait out. All None where the
        log does not hold the request's first token (a log that attached
        later, :meth:`TelemetryLog.open_session`)."""
        evs = self.request_log.get(rid)
        if not evs:
            return None
        submit_t: tp.Optional[float] = None
        first_admit_t: tp.Optional[float] = None
        resident_t: tp.Optional[float] = None
        first_tok: tp.Optional[Event] = None
        finish_t: tp.Optional[float] = None
        stall = 0.0
        stall_since: tp.Optional[float] = None
        dispatches = 0
        evictions = 0
        for ev in evs:
            if ev.kind in ("submit", "queued") and submit_t is None:
                submit_t = ev.t
            elif ev.kind == "admitted":
                if first_admit_t is None:
                    first_admit_t = ev.t
                if first_tok is None:
                    resident_t = ev.t
                if stall_since is not None:
                    stall += ev.t - stall_since
                    stall_since = None
            elif ev.kind == "prefill_chunk":
                if first_tok is None:
                    resident_t = ev.t
            elif ev.kind in ("evicted", "parked"):
                if ev.kind == "evicted":
                    evictions += 1
                if stall_since is None:
                    stall_since = ev.t
            elif ev.kind == "tokens":
                dispatches += 1
                if first_tok is None and ev.data.get("n", 0):
                    first_tok = ev
            elif ev.kind == "finished":
                finish_t = ev.t
        tok_ts = self.token_times(rid)
        tbt = [b - a for a, b in zip(tok_ts, tok_ts[1:])]
        # the first token the log saw is the request's first
        whole = (
            first_tok is not None and submit_t is not None
            and first_tok.data.get("total", 0) <= first_tok.data["n"]
        )
        split = whole and first_admit_t is not None
        return {
            "rid": rid,
            "queue_delay_s": (
                first_admit_t - submit_t
                if submit_t is not None and first_admit_t is not None
                else None
            ),
            "prefill_s": resident_t - first_admit_t if split else None,
            "first_window_s": first_tok.t - resident_t if split else None,
            "ttft_s": first_tok.t - submit_t if whole else None,
            "tbt_s": tbt,
            "eviction_stall_s": stall,
            "evictions": evictions,
            "tokens": len(tok_ts),
            "dispatches": dispatches,
            "tokens_per_dispatch": (
                len(tok_ts) / dispatches if dispatches else None
            ),
            "e2e_s": (
                finish_t - submit_t
                if submit_t is not None and finish_t is not None
                else None
            ),
            "finished": finish_t is not None,
        }

    def finished_request_metrics(self) -> tp.List[tp.Dict[str, tp.Any]]:
        """Derived metrics for every request whose log ends in
        ``finished`` — the population bench_serving's TBT/queue-delay
        percentiles are computed over."""
        out = []
        for rid in self.request_log:
            m = self.request_metrics(rid)
            if m is not None and m["finished"]:
                out.append(m)
        return out


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------

_REQ_PID = 1
_DISPATCH_PID = 2
_ENGINE_PID = 3
_SPAN_FOR = {
    # state entered at this event kind -> span name closed by the next
    # lifecycle transition
    "queued": "queued",
    "admitted": "active",
    "evicted": "requeued",
    "parked": "parked",
    "resumed": "queued",
}
_CLOSERS = (
    "queued", "admitted", "evicted", "parked", "resumed", "finished",
    "cancelled", "deadline_shed",  # terminal like finished: close the
    # open span, open nothing (absent from _SPAN_FOR)
)


def _span(name: str, t0: float, t1: float, tid: int, base: float, **args):
    return {
        "name": name,
        "ph": "X",
        "pid": _REQ_PID,
        "tid": tid,
        "ts": (t0 - base) * 1e6,
        "dur": max(0.0, (t1 - t0)) * 1e6,
        "args": args,
    }


def chrome_trace(tele: EngineTelemetry) -> tp.Dict[str, tp.Any]:
    """Export a telemetry log as a Chrome trace-event JSON object
    (``json.dump`` it to a file and open in Perfetto). Layout: one
    process of request lanes (tid = request id; spans for the
    queued/active/requeued/parked phases, instants for tokens and
    faults) and one process of dispatch lanes (one lane per dispatch
    kind, spans from the dispatch ring). Timestamps are microseconds
    relative to the earliest recorded event."""
    events: tp.List[tp.Dict[str, tp.Any]] = []
    all_ts = [ev.t for evs in tele.request_log.values() for ev in evs]
    all_ts += [d.t for d in tele.dispatches]
    all_ts += [ev.t for ev in tele.events if ev.rid is None]
    base = min(all_ts) if all_ts else 0.0

    events.append({
        "ph": "M", "pid": _REQ_PID, "name": "process_name",
        "args": {"name": "requests"},
    })
    events.append({
        "ph": "M", "pid": _DISPATCH_PID, "name": "process_name",
        "args": {"name": "dispatches"},
    })

    for rid, evs in sorted(tele.request_log.items()):
        events.append({
            "ph": "M", "pid": _REQ_PID, "tid": rid, "name": "thread_name",
            "args": {"name": f"request {rid}"},
        })
        open_name: tp.Optional[str] = None
        open_t = 0.0
        last_t = evs[-1].t if evs else 0.0
        for ev in evs:
            if ev.kind in _CLOSERS:
                if open_name is not None:
                    events.append(_span(open_name, open_t, ev.t, rid, base))
                open_name = _SPAN_FOR.get(ev.kind)
                open_t = ev.t
            if ev.kind in ("tokens", "submit", "finished", "cancelled",
                           "deadline_shed"):
                events.append({
                    "name": ev.kind,
                    "ph": "i",
                    "s": "t",
                    "pid": _REQ_PID,
                    "tid": rid,
                    "ts": (ev.t - base) * 1e6,
                    "args": dict(ev.data, step=ev.step),
                })
        if open_name is not None:
            events.append(_span(open_name, open_t, last_t, rid, base))

    # rid-less lifecycle events (shed/deferred at rejection time — no
    # rid ever exists — and scripted fault injections) live only on the
    # recency ring; render them as instants on an engine lane so
    # overload and chaos show up in Perfetto next to the lanes they
    # explain. (Window-summary events are rid-less too but already
    # render as spans on the dispatch lanes — excluded here.)
    ridless = [
        ev for ev in tele.events
        if ev.rid is None and ev.kind in ("shed", "deferred", "fault")
    ]
    if ridless:
        events.append({
            "ph": "M", "pid": _ENGINE_PID, "name": "process_name",
            "args": {"name": "engine"},
        })
        for ev in ridless:
            events.append({
                "name": ev.kind,
                "ph": "i",
                "s": "p",
                "pid": _ENGINE_PID,
                "tid": 0,
                "ts": (ev.t - base) * 1e6,
                "args": dict(ev.data, step=ev.step),
            })

    lanes = {
        "decode_window": 0, "verify_dispatch": 1, "prefill_chunk": 2,
        "handoff": 3,
    }
    for kind, tid in lanes.items():
        events.append({
            "ph": "M", "pid": _DISPATCH_PID, "tid": tid,
            "name": "thread_name", "args": {"name": kind},
        })
    for d in tele.dispatches:
        events.append({
            "name": d.kind,
            "ph": "X",
            "pid": _DISPATCH_PID,
            "tid": lanes.get(d.kind, 4),
            "ts": (d.t - base) * 1e6,
            "dur": max(0.0, d.dur) * 1e6,
            "args": dict(d.data, step=d.step, tokens=d.tokens,
                         rids=list(d.rids)),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
