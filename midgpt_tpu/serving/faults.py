"""Deterministic fault injection + the typed failure surface of serving.

Two things live here, and they are one design:

1. **The typed exceptions** every runtime failure of the serving stack
   degrades through. The engine never raises a bare ``assert``/
   ``MemoryError`` at a request anymore: admission failures are
   :class:`AdmissionRejected` (permanent — this request can never be
   served here, with a machine-readable ``reason``) or
   :class:`PoolOverloaded` (transient backpressure — retry later);
   replica-level failures the cluster recovers from are
   :class:`ReplicaCrash` / :class:`WedgedDispatch` /
   :class:`TransientDispatchError`; :class:`ClusterUnavailable` is the
   end of the line (every replica dead with work still pending).
   :class:`Cancelled` and :class:`DeadlineExceeded` are the two
   POST-ADMISSION terminal outcomes the async front door
   (serving.frontdoor) surfaces to a caller awaiting a stream's result:
   the engine records them as ``Request.outcome`` (``"cancelled"`` /
   ``"expired"``) plus counters and lifecycle events — a cancel or a
   pre-dispatch deadline shed is a scheduled outcome, not a crash —
   and the front door raises the exception form only from
   ``TokenStream.result()``.

2. **A scripted, replayable chaos harness.** A :class:`FaultPlan` is an
   ordered list of :class:`FaultEvent` s keyed to *engine-local
   scheduler-step counters* — NOT wall clock — so a chaos run is a pure
   function of (trace, plan): replaying the same plan over the same
   request trace reproduces the same admissions, evictions, failovers
   and (by the engine's determinism contract) the same token streams
   bit for bit. Events fire at the TOP of ``ServingEngine.step`` via
   the ``fault_hook`` seam, BEFORE any dispatch mutates engine or pool
   state — which is exactly what makes failover replay exact: a
   crashed/wedged replica's requests carry only really-emitted tokens,
   and re-queueing them is the (already bit-identical) eviction path.

The hook is zero-cost when absent: an engine without a plan pays one
``is None`` check per scheduler window, nothing else.

Event kinds:

- ``crash``     — the replica dies on the spot (:class:`ReplicaCrash`):
                  the cluster marks it dead and fails its requests over.
- ``wedge``     — the dispatch stalls (``seconds`` of simulated stall,
                  then :class:`WedgedDispatch`): the cluster's
                  wall-clock watchdog trips and abandons the replica.
- ``transient`` — one retriable dispatch failure
                  (:class:`TransientDispatchError`): the cluster
                  retries the same replica with capped exponential
                  backoff; consecutive events exhaust the retries into
                  a failover.
- ``exhaust``   — allocator pressure: quarantine ``pages`` free pages
                  (-1 = all) for ``hold_steps`` scheduler steps —
                  drives the engine's overload paths (eviction,
                  parking) without any device-side fault at all.
- ``handoff``   — poison the NEXT page handoff off this replica
                  (:class:`HandoffFailed` at export time, not at the
                  step top): the disaggregated cluster's prefill→decode
                  page move fails mid-flight and the request re-serves
                  cold from the submission record. Armed at the step
                  the event names; fires when the cluster next exports.

Compact spec grammar (the ``--fault_plan`` CLI flag)::

    STEP:KIND[@REPLICA][:ARG[:ARG2]] [; ...]

    "6:crash@1"            replica 1 crashes at its 6th step
    "4:wedge@0:0.5"        replica 0 stalls 0.5 s, watchdog territory
    "3:transient"          replica 0, one retriable failure at step 3
    "2:exhaust@0:all:3"    quarantine all free pages for 3 steps
    "2:handoff@0"          replica 0's next page export fails
"""

from __future__ import annotations

import dataclasses
import time
import typing as tp

__all__ = [
    "AdmissionRejected",
    "Cancelled",
    "ClusterUnavailable",
    "DeadlineExceeded",
    "FaultEvent",
    "FaultPlan",
    "HandoffFailed",
    "PoolOverloaded",
    "ReplicaCrash",
    "ServingFault",
    "TransientDispatchError",
    "WedgedDispatch",
]


class ServingFault(Exception):
    """Base of every typed serving failure (injected or organic)."""


class ReplicaCrash(ServingFault):
    """The replica process/device is gone; its engine must not be
    stepped again. The cluster marks it dead and fails over."""


class WedgedDispatch(ServingFault):
    """A dispatch stalled past any useful deadline. Raised by the scripted wedge after its stall; in production
    the wall-clock watchdog usually trips first and the replica is
    abandoned mid-flight."""


class TransientDispatchError(ServingFault):
    """A retriable dispatch failure (flaky interconnect, preempted
    runtime): the same replica may well succeed on retry."""


class _ReasonedFault(ServingFault):
    def __init__(self, reason: str, message: str):
        self.reason = reason
        super().__init__(f"[{reason}] {message}")


class AdmissionRejected(_ReasonedFault):
    """Permanent admission failure: this request can never be served by
    this engine (``reason`` is machine-readable — e.g.
    ``lifetime_exceeds_pool``, ``budget_exceeds_block``,
    ``empty_prompt``, ``bad_budget``, ``queue_full`` under the shed
    policy). Counted in engine and cluster ``stats()``."""


class PoolOverloaded(_ReasonedFault):
    """Transient overload backpressure: the request was NOT accepted
    but may be resubmitted later (``reason="queue_full"`` under the
    defer policy — the bounded wait queue is full right now)."""


class Cancelled(ServingFault):
    """The request was cancelled by its submitter after admission
    (``ServingEngine.cancel`` / ``TokenStream.cancel``): its slot was
    reclaimed and its pages released at the next scheduler boundary.
    Never raised by the engine itself — the scheduler records the
    outcome (``Request.outcome == "cancelled"``, the ``cancelled``
    lifecycle event, the ``cancelled_requests`` counter); the async
    front door raises this from ``TokenStream.result()`` so a caller
    awaiting a full completion gets a typed outcome."""

    def __init__(self, rid: int, tokens_emitted: int = 0):
        self.rid = rid
        self.tokens_emitted = tokens_emitted
        super().__init__(
            f"request {rid} cancelled after {tokens_emitted} tokens"
        )


class DeadlineExceeded(ServingFault):
    """The request's deadline passed while it was still waiting for
    dispatch (queued or parked), so the scheduler SHED it before
    spending any more compute on it — tokens it would have emitted past
    the deadline count for nothing under an SLO, and serving them
    starves requests that can still meet theirs. Recorded as
    ``Request.outcome == "expired"`` + the ``deadline_shed`` event +
    the ``deadline_shed_requests`` counter; raised only by
    ``TokenStream.result()``. A request already IN a decode slot is
    never shed mid-flight — it finishes late and the bench counts it
    deadline-missed instead."""

    def __init__(self, rid: int, tokens_emitted: int = 0):
        self.rid = rid
        self.tokens_emitted = tokens_emitted
        super().__init__(
            f"request {rid} shed: deadline passed before dispatch "
            f"({tokens_emitted} tokens emitted)"
        )


class HandoffFailed(ServingFault):
    """A prefill→decode page handoff failed mid-flight (the replica
    crashed or the page move was poisoned by a scripted ``handoff``
    fault) BEFORE the exported state left the source engine. The slot
    is still intact on the prefill replica; the cluster abandons that
    copy and re-serves the request COLD from its submission record —
    the same stream by the determinism contract. Never surfaces to a
    submitter: it is a cluster-internal failover trigger, counted in
    ``handoff_failures``."""


class ClusterUnavailable(ServingFault):
    """Every replica is dead and requests are still pending — the one
    failure the cluster cannot degrade through."""


_KINDS = ("crash", "wedge", "transient", "exhaust", "handoff")


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scripted fault, keyed to a replica's scheduler-step counter.

    ``step`` is 1-based and engine-local: the event fires at the top of
    that replica's ``step()`` call number ``step`` (retries count — a
    cluster retry re-enters ``step()``, so consecutive ``transient``
    events model consecutive failures of one logical dispatch)."""

    step: int
    kind: str
    replica: int = 0
    seconds: float = 0.25  # wedge: simulated stall before the raise
    pages: int = -1  # exhaust: free pages to quarantine (-1 = all)
    hold_steps: int = 1  # exhaust: scheduler steps until auto-release

    def __post_init__(self):
        assert self.kind in _KINDS, f"unknown fault kind {self.kind!r}"
        assert self.step >= 1, f"steps are 1-based, got {self.step}"
        assert self.replica >= 0, self.replica
        assert self.hold_steps >= 1, self.hold_steps

    def spec(self) -> str:
        base = f"{self.step}:{self.kind}@{self.replica}"
        if self.kind == "wedge":
            return f"{base}:{self.seconds:g}"
        if self.kind == "exhaust":
            pages = "all" if self.pages < 0 else str(self.pages)
            return f"{base}:{pages}:{self.hold_steps}"
        return base


class FaultPlan:
    """An ordered, replayable fault script over a (multi-replica)
    serving deployment. Build from events or :meth:`parse` a compact
    spec string; install per replica via
    ``ServingEngine(fault_hook=plan.hook(i))`` (the cluster does this
    for you: ``ServingCluster(..., fault_plan=plan)``)."""

    def __init__(self, events: tp.Iterable[FaultEvent]):
        evs = list(events)
        # stable order: by step, then original position — events of one
        # (replica, step) fire in authoring order
        self.events: tp.Tuple[FaultEvent, ...] = tuple(
            sorted(evs, key=lambda e: e.step)  # sorted() is stable
        )
        self._by_key: tp.Dict[tp.Tuple[int, int], tp.List[FaultEvent]] = {}
        for ev in self.events:
            self._by_key.setdefault((ev.replica, ev.step), []).append(ev)

    def __len__(self) -> int:
        return len(self.events)

    def __iter__(self):
        return iter(self.events)

    @property
    def replicas(self) -> tp.Set[int]:
        return {ev.replica for ev in self.events}

    def events_for(self, replica: int, step: int) -> tp.List[FaultEvent]:
        return self._by_key.get((replica, step), [])

    def spec(self) -> str:
        """The compact string form; ``FaultPlan.parse(plan.spec())``
        reproduces the plan (roundtrip-tested)."""
        return ";".join(ev.spec() for ev in self.events)

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        events = []
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            fields = part.split(":")
            assert len(fields) >= 2, f"malformed fault event {part!r}"
            step = int(fields[0])
            head = fields[1]
            if "@" in head:
                kind, rep = head.split("@", 1)
                replica = int(rep)
            else:
                kind, replica = head, 0
            kw: tp.Dict[str, tp.Any] = {}
            if kind == "wedge" and len(fields) > 2:
                kw["seconds"] = float(fields[2])
            if kind == "exhaust":
                if len(fields) > 2:
                    kw["pages"] = -1 if fields[2] == "all" else int(fields[2])
                if len(fields) > 3:
                    kw["hold_steps"] = int(fields[3])
            events.append(
                FaultEvent(step=step, kind=kind, replica=replica, **kw)
            )
        return cls(events)

    def hook(self, replica: int = 0) -> "_EngineFaultHook":
        """The per-engine injection callable for ``replica`` — stateful
        (it tracks pending quarantine releases), so take a fresh hook
        per engine instance."""
        return _EngineFaultHook(self, replica)


class _EngineFaultHook:
    """Installed as ``ServingEngine(fault_hook=...)``; called at the top
    of every ``step()`` with the engine, after ``engine.fault_step`` was
    incremented. Raises the scripted typed faults; mutates only the
    host-side allocator (quarantine) — never device state — so every
    injection point leaves the engine resumable/drainable."""

    def __init__(self, plan: FaultPlan, replica: int):
        self._plan = plan
        self._replica = replica
        self._release_at: tp.Optional[int] = None

    def __call__(self, engine) -> None:
        step = engine.fault_step
        if self._release_at is not None and step >= self._release_at:
            engine.alloc.release_quarantined()
            self._release_at = None
            engine._unpark()  # quarantine-parked requests may fit again
        for ev in self._plan.events_for(self._replica, step):
            engine.faults_injected += 1
            if engine.telemetry is not None:
                # the injection itself is telemetry (the flight recorder
                # must show WHAT fired before the timeline goes quiet);
                # spec() is deterministic, so replays keep identical
                # event sequences
                engine.telemetry.emit(
                    "fault", step=step, t=engine.clock(),
                    fault=ev.kind, spec=ev.spec(),
                )
            if ev.kind == "exhaust":
                engine.alloc.quarantine(ev.pages)
                due = step + ev.hold_steps
                self._release_at = (
                    due if self._release_at is None
                    else max(self._release_at, due)
                )
            elif ev.kind == "handoff":
                # armed, not raised: the fault fires inside the NEXT
                # export_request off this engine (the page move is a
                # cluster action, not a step-top dispatch)
                engine._handoff_poison = True
            elif ev.kind == "crash":
                raise ReplicaCrash(f"scripted crash at step {step}")
            elif ev.kind == "transient":
                raise TransientDispatchError(
                    f"scripted transient dispatch error at step {step}"
                )
            elif ev.kind == "wedge":
                time.sleep(ev.seconds)
                raise WedgedDispatch(
                    f"scripted {ev.seconds:g}s wedge at step {step}"
                )
