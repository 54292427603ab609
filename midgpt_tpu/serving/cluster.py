"""Shared-nothing data-parallel serving: N independent engine replicas
under one admission scheduler, with failover.

Tensor parallelism (``ServingEngine(mesh=...)``) scales a single engine
DOWN the latency axis — the model's weights and KV pool split over the
'tensor' axis of ONE mesh, every dispatch runs collectives. Data
parallelism scales UP the throughput axis, and for serving the right
shape is SHARED-NOTHING: each replica is a complete ``ServingEngine``
owning its own devices (or mesh slice), page pool, prefix cache, and
scheduler state, with no collective ever crossing replicas — a replica
failure or a slow request affects only its own slots, and replicas can
be added/removed without recompiling anything (the Gemma-on-TPU serving
comparison and the pjit scaling study, PAPERS.md, both benchmark exactly
this TPxDP composition).

:class:`ServingCluster` is the scheduler above the replicas:

- **Least-loaded admission**: ``submit`` routes each request to the
  healthy replica with the smallest backlog (queued + active requests;
  deterministic lowest-index tie-break). Because every engine's token
  stream is a function of the request alone (the determinism contract in
  ``serving.engine``), placement NEVER changes a request's tokens — only
  its latency — which the cluster test asserts directly.
- **Per-replica health + failover** (serving.faults): every replica is
  ``healthy``, ``suspect``, or ``dead``. A wall-clock dispatch watchdog
  (``dispatch_timeout_s``) catches a dispatch that never returns; a
  ``TransientDispatchError`` is retried on the same replica with capped
  exponential backoff (``max_retries``/``backoff_s``/``backoff_cap_s``,
  suspect while retrying); a ``ReplicaCrash``, a watchdog trip, or
  exhausted retries mark the replica DEAD and its backlog fails over —
  WARM when the replica's step thread provably completed by raising
  (the engine drains exactly: in-flight slots convert through the
  bit-identical eviction path, progress preserved), COLD on a watchdog
  trip (the thread may still be running, so the engine is never
  touched again and its requests re-serve from scratch off the
  cluster's submission record). Failures are processed only after
  every replica's step has settled, so failover never mutates an
  engine mid-step. **Failover replay is bit-identical** either way:
  scripted faults fire at step boundaries (before any dispatch mutates
  state), re-queueing rides the eviction path or the determinism
  contract, and placement invariance makes the surviving stream equal
  to the fault-free run token for token — the chaos suite proves it,
  not just asserts it plausible.
- **Per-replica prefix caches + affinity routing**: no cross-replica
  page sharing (pages live in per-replica pools on disjoint devices),
  so a shared-prefix mix hits best when co-located. ``affinity=True``
  turns admission content-aware: the cluster probes each candidate
  replica's :class:`PrefixIndex` (``match`` is read-only — probing
  perturbs nothing) and routes to the longest resident-prefix overlap,
  bounded by a load-imbalance cap (``affinity_max_imbalance``) so
  affinity can never starve a replica; zero overlap falls back to
  least-loaded. Placement still never changes tokens — only hit rate
  and latency — so every determinism/failover contract is untouched.
- **Disaggregated prefill/decode pools**
  (``prefill_replicas=``/``decode_replicas=``): the first P replicas
  run ``role="prefill"`` engines (chunked prefill to completion, then
  the slot parks handoff-ready), the next D run ``role="decode"``.
  After every scheduler round the cluster PUMPS handoffs: each ready
  slot exports its block-table pages + carried logits row
  (``engine.export_request`` → :class:`HandoffRecord`, host arrays —
  the honest DCN wire model) and imports into the least-loaded decode
  replica (``engine.import_request``), which aliases whatever prefix
  its own index already holds and resumes decoding bit-identically.
  Admission and failover target the prefill pool (decode replicas
  receive work only via handoff), degrading to any alive replica when
  the whole prefill pool is dead. A scripted ``handoff`` fault raises
  :class:`HandoffFailed` at export — the source copy is abandoned and
  the request re-serves COLD from the submission record, same stream.
- **Aggregated stats**: :meth:`stats` sums the per-engine counters and
  keeps the per-replica breakdown, in the same key layout as
  ``ServingEngine.stats`` (bench_serving emits it unchanged), plus the
  cluster-level failover counters (watchdog trips, retries, failovers,
  re-queued requests, replica health).

This is the seam the async front door (serving.frontdoor, ROADMAP
item 3 — shipped) slots into: streaming/cancellation/priorities wrap
``submit``/``step``/``cancel``/``lookup`` here without touching the
engines — and the health/failover layer beneath it is what lets that
front door promise SLOs.
"""

from __future__ import annotations

import concurrent.futures
import os
import sys
import time
import typing as tp

import numpy as np

from midgpt_tpu.serving.engine import HandoffRecord, Request, ServingEngine
from midgpt_tpu.serving.telemetry import EngineTelemetry
from midgpt_tpu.telemetry import span
from midgpt_tpu.serving.faults import (
    AdmissionRejected,
    ClusterUnavailable,
    FaultPlan,
    HandoffFailed,
    PoolOverloaded,
    ReplicaCrash,
    TransientDispatchError,
    WedgedDispatch,
)


class _WatchdogTrip(Exception):
    """Internal marker: the cluster's wall-clock wait on a replica step
    expired with the step thread STILL RUNNING. Never raised by engine
    code — it exists to distinguish a true watchdog trip (cold,
    engine-abandoning failover) from an organic ``TimeoutError`` raised
    inside step(), which on Python 3.11+ is the same class as
    ``concurrent.futures.TimeoutError`` (thread completed → warm
    failover, like any crash)."""


def serving_meshes(
    tp_size: int = 1,
    dp_replicas: int = 1,
    devices: tp.Optional[tp.Sequence] = None,
) -> tp.List:
    """Disjoint tensor-only meshes for a TPxDP serving deployment: the
    first ``tp_size * dp_replicas`` devices split into ``dp_replicas``
    contiguous groups of ``tp_size`` (contiguous = ICI-adjacent under the
    standard device enumeration, the layout the pjit scaling study uses
    for its TP groups). ``tp_size == 1`` with one replica returns
    ``[None]`` — the engine's single-chip fast path, no mesh machinery at
    all; multi-replica tp=1 gets real 1-device meshes so each replica's
    arrays COMMIT to its own device instead of piling onto device 0."""
    import jax

    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.parallel.mesh import create_mesh

    assert tp_size >= 1 and dp_replicas >= 1, (tp_size, dp_replicas)
    if tp_size == 1 and dp_replicas == 1:
        return [None]
    devices = list(devices) if devices is not None else jax.devices()
    need = tp_size * dp_replicas
    assert len(devices) >= need, (
        f"tp={tp_size} x dp_replicas={dp_replicas} needs {need} devices, "
        f"have {len(devices)}"
    )
    cfg = MeshConfig(replica=1, fsdp=1, sequence=1, tensor=tp_size)
    return [
        create_mesh(cfg, devices=devices[i * tp_size : (i + 1) * tp_size])
        for i in range(dp_replicas)
    ]


class ServingCluster:
    """N shared-nothing :class:`ServingEngine` replicas + least-loaded
    admission + health-tracked failover. The cluster's request ids are
    its own (monotone, globally unique); per-replica ids stay internal.

    ``meshes`` pins each replica to its own mesh (``serving_meshes``
    builds the standard TPxDP split); ``replicas=N`` without meshes runs
    N schedulers on the default device — still useful: it is the
    scheduler-correctness configuration the tests drive, and the
    single-host shape the async front door (serving.frontdoor)
    multiplexes. All other keyword arguments go to every engine
    verbatim.

    Disaggregation + routing knobs:

    - ``prefill_replicas=P, decode_replicas=D`` — disaggregated pools:
      the first P replicas run ``role="prefill"`` (chunked prefill to
      completion, then the slot parks handoff-ready), the last D run
      ``role="decode"``; the cluster pumps page handoffs between them
      after every scheduler round. Pool split never changes tokens —
      the disagg test matrix proves 1+1 / 2+1 / 2+2 bit-identical to
      the monolithic engine.
    - ``affinity=True`` — prefix-affinity admission: route to the
      replica whose :class:`PrefixIndex` holds the longest resident
      prefix of the prompt, bounded by ``affinity_max_imbalance``
      (max backlog gap vs the least-loaded replica a hit may justify;
      zero overlap falls back to pure least-loaded). Off by default:
      placement order is part of the replay-determinism surface the
      existing tests pin, so content-aware routing is opt-in.

    Fault-tolerance knobs:

    - ``dispatch_timeout_s`` — wall-clock watchdog per replica step;
      ``None`` (default) disables it. A trip marks the replica dead
      (its dispatch may never return — re-using it would double-serve)
      and fails its backlog over.
    - ``max_retries`` / ``backoff_s`` / ``backoff_cap_s`` — capped
      exponential backoff for :class:`TransientDispatchError`
      (``sleep(min(backoff_s * 2**attempt, backoff_cap_s))`` before each
      retry); the replica rides ``suspect`` while retrying and returns
      ``healthy`` on success.
    - ``fault_plan`` — a :class:`~midgpt_tpu.serving.faults.FaultPlan`;
      each replica gets its own scripted hook
      (``plan.hook(replica_index)``), making whole-cluster chaos runs
      replayable bit for bit.
    """

    def __init__(
        self,
        model,
        *,
        replicas: tp.Optional[int] = None,
        meshes: tp.Optional[tp.Sequence] = None,
        prefill_replicas: tp.Optional[int] = None,
        decode_replicas: tp.Optional[int] = None,
        affinity: bool = False,
        affinity_max_imbalance: int = 4,
        fault_plan: tp.Optional[FaultPlan] = None,
        dispatch_timeout_s: tp.Optional[float] = None,
        max_retries: int = 3,
        backoff_s: float = 0.05,
        backoff_cap_s: float = 1.0,
        flight_dir: tp.Optional[str] = None,
        **engine_kwargs,
    ):
        # disaggregated mode: the first P replicas prefill, the next D
        # decode (replica index order = [prefill pool | decode pool],
        # so meshes= pins pools to device groups positionally)
        roles: tp.Optional[tp.List[str]] = None
        if prefill_replicas is not None or decode_replicas is not None:
            assert (
                prefill_replicas is not None and prefill_replicas >= 1
                and decode_replicas is not None and decode_replicas >= 1
            ), (
                "disaggregated mode needs BOTH prefill_replicas>=1 and "
                f"decode_replicas>=1, got {prefill_replicas}+"
                f"{decode_replicas}"
            )
            total = prefill_replicas + decode_replicas
            assert replicas is None or replicas == total, (
                f"replicas={replicas} contradicts "
                f"{prefill_replicas}+{decode_replicas} pools"
            )
            replicas = total
            roles = (
                ["prefill"] * prefill_replicas
                + ["decode"] * decode_replicas
            )
        if meshes is None:
            assert replicas is not None and replicas >= 1, (
                "need replicas=N, prefill_replicas=P + decode_replicas=D, "
                "or an explicit meshes= list"
            )
            meshes = [None] * replicas
        else:
            meshes = list(meshes)
            assert replicas is None or replicas == len(meshes), (
                f"replicas={replicas} contradicts {len(meshes)} meshes"
            )
        assert len(meshes) >= 1
        assert max_retries >= 0 and backoff_s >= 0.0, (
            max_retries, backoff_s,
        )
        # telemetry rides through engine_kwargs: telemetry=True gives
        # every replica its OWN EngineTelemetry (each engine constructs
        # one); a shared instance across replicas would interleave
        # event streams from concurrently-stepping threads, so it is
        # rejected here
        assert not (
            isinstance(engine_kwargs.get("telemetry"), EngineTelemetry)
            and len(meshes) > 1
        ), (
            "pass telemetry=True for a multi-replica cluster — each "
            "replica needs its own EngineTelemetry instance"
        )
        # flight_dir: where dead-replica flight-recorder artifacts land
        # (crash / watchdog trip / exhausted retries — every terminal
        # path dumps; paths collected in self.flight_dumps). None
        # disables the dumps.
        self.flight_dir = flight_dir
        self.flight_dumps: tp.List[str] = []
        self.engines: tp.List[ServingEngine] = []
        for i, m in enumerate(meshes):
            kw = dict(engine_kwargs)
            if roles is not None:
                kw["role"] = roles[i]
            if fault_plan is not None:
                kw["fault_hook"] = fault_plan.hook(i)
            self.engines.append(ServingEngine(model, mesh=m, **kw))
        # pool topology + routing policy
        self.disaggregated = roles is not None
        self.prefill_replicas = int(prefill_replicas or 0)
        self.decode_replicas = int(decode_replicas or 0)
        self._prefill_pool = (
            list(range(self.prefill_replicas)) if self.disaggregated
            else list(range(len(self.engines)))
        )
        self._decode_pool = (
            list(range(self.prefill_replicas, len(self.engines)))
            if self.disaggregated else []
        )
        self.affinity = bool(affinity)
        self.affinity_max_imbalance = int(affinity_max_imbalance)
        assert self.affinity_max_imbalance >= 0
        self.dispatch_timeout_s = dispatch_timeout_s
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        # per-replica health: healthy -> suspect (retrying a transient)
        # -> healthy, or -> dead (crash / watchdog trip / retries
        # exhausted). Dead is terminal: the backlog failed over, and a
        # wedged dispatch may still hold the old engine's buffers.
        self.health: tp.List[str] = ["healthy"] * len(self.engines)
        self.health_reason: tp.List[tp.Optional[str]] = (
            [None] * len(self.engines)
        )
        self.watchdog_trips = 0
        self.retries = 0
        self.failovers = 0
        self.requeued_requests = 0
        # disaggregation + routing counters (CLUSTER_STATS_KEYS)
        self.handoffs = 0
        self.handoff_pages_moved = 0
        self.handoff_bytes = 0
        self.handoff_failures = 0
        self.prefix_affinity_hits = 0
        self.routed_fallback = 0
        self.first_fault_time: tp.Optional[float] = None
        # global rid -> (replica index, engine-local rid)
        self._route: tp.Dict[int, tp.Tuple[int, int]] = {}
        # global rid -> (prompt, max_new_tokens, eos_id, seed, submit
        # time, priority, deadline, routing decision): the cold failover
        # record (dropped at harvest)
        self._submitted: tp.Dict[int, tp.Tuple] = {}
        # global rid -> HandoffRecord: exported off a prefill replica,
        # awaiting a decode-pool slot (the route re-points on import; a
        # record in limbo is self-contained host data, so it survives
        # the death of its source replica)
        self._handoff: tp.Dict[int, HandoffRecord] = {}
        self._next_rid = 0
        self.finished: tp.Dict[int, Request] = {}
        # post-admission terminal outcomes that are not completions
        # (mirrors the per-engine dicts; harvested like finished)
        self.cancelled: tp.Dict[int, Request] = {}
        self.expired: tp.Dict[int, Request] = {}
        # one stepping thread per replica: ServingEngine.step blocks on
        # its window's device->host read, and a sequential loop would
        # keep replica B's devices idle while replica A's window
        # computes — time-multiplexing the "parallel" replicas. Engines
        # share no state (that is the design), jax dispatch/blocking
        # reads release the GIL, and each engine only ever runs on ONE
        # thread at a time (submit/step/run are driven from the caller's
        # thread; the pool just fans one step() per engine out). The
        # watchdog also needs the pool (a timeout requires stepping on a
        # thread the caller can abandon), so a single replica gets one
        # when dispatch_timeout_s is set. Workers are over-provisioned:
        # a wedged step occupies its worker until the stall ends, and
        # retries/failover must still find a free thread meanwhile.
        self._pool = (
            concurrent.futures.ThreadPoolExecutor(
                max_workers=max(4, 2 * len(self.engines)),
                thread_name_prefix="serving-replica",
            )
            if len(self.engines) > 1 or dispatch_timeout_s is not None
            else None
        )

    @property
    def replicas(self) -> int:
        return len(self.engines)

    def _alive(self) -> tp.List[int]:
        return [
            i for i in range(len(self.engines)) if self.health[i] != "dead"
        ]

    @property
    def has_work(self) -> bool:
        """Un-harvested cluster requests remain. Routes outlive replica
        deaths (failover re-points them at survivors) and a pending
        handoff record is a live request between pools, so this is the
        drain condition even mid-failover/mid-handoff."""
        return bool(self._route) or bool(self._handoff) or any(
            self.engines[i].has_work for i in self._alive()
        )

    def _load(self, e: ServingEngine) -> int:
        """Backlog of one replica: queued + parked + in-flight requests.
        Counting requests (not tokens) keeps admission O(1) and
        deterministic; remaining-token estimates are a policy refinement
        the seam allows."""
        return len(e.queue) + len(e.parked) + len(e._active_slots())

    def _least_loaded(self, alive: tp.Sequence[int]) -> int:
        return min(alive, key=lambda j: (self._load(self.engines[j]), j))

    def _submit_targets(self) -> tp.List[int]:
        """Replicas admission (and cold re-serve) may target: the alive
        prefill pool when disaggregated — decode replicas only receive
        work via handoff — degrading to ANY alive replica when the
        whole prefill pool is dead (a decode-class engine is a full
        engine: it can prefill and decode, just off its roofline)."""
        alive = self._alive()
        if not self.disaggregated:
            return alive
        pool = [i for i in self._prefill_pool if self.health[i] != "dead"]
        return pool or alive

    def _affinity_overlap(self, j: int, toks: tp.Sequence[int]) -> int:
        """Longest resident-prefix overlap (in tokens) replica ``j``
        holds for this prompt — the per-replica sketch the affinity
        router reads is the engine's own :class:`PrefixIndex`, probed
        directly: ``match`` is read-only (no LRU mutation), so probing
        every candidate perturbs nothing and needs no shadow state that
        could drift from the pool it describes."""
        idx = self.engines[j].index
        if idx is None or not toks:
            return 0
        return int(idx.match(list(toks))[2])

    def _route_order(
        self,
        cands: tp.Sequence[int],
        prompt: np.ndarray,
        max_new_tokens: int,
    ) -> tp.Tuple[tp.List[int], int]:
        """Candidate replicas in admission-preference order, plus the
        best resident-prefix overlap (0 when affinity is off or
        nothing matched). Affinity picks the longest overlap among
        replicas within ``affinity_max_imbalance`` of the minimum load
        (ties: least loaded, then lowest index) and puts it FIRST —
        the least-loaded order follows as the spillover tail, so a
        full queue on the affinity target degrades exactly like the
        blind policy. The overlap probe crops the prompt exactly like
        ``engine.submit`` will (block - max_new window, last-prompt
        token excluded), so it scores the tokens the engine would
        actually admit against its cache."""
        loads = {j: self._load(self.engines[j]) for j in cands}
        order = sorted(cands, key=lambda j: (loads[j], j))
        if not self.affinity:
            return order, 0
        pp = np.asarray(prompt, np.int32).reshape(-1)
        keep = self.engines[order[0]].block - max_new_tokens
        if 0 < keep < pp.size:
            pp = pp[-keep:]
        toks = [int(t) for t in pp[:-1]] if pp.size > 1 else []
        cap = loads[order[0]] + self.affinity_max_imbalance
        eligible = [j for j in cands if loads[j] <= cap]
        best = max(
            eligible,
            key=lambda j: (self._affinity_overlap(j, toks), -loads[j], -j),
        )
        overlap = self._affinity_overlap(best, toks)
        if overlap > 0:
            order = [best] + [j for j in order if j != best]
        return order, overlap

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        *,
        eos_id: tp.Optional[int] = None,
        seed: int = 0,
        priority: int = 0,
        deadline_s: tp.Optional[float] = None,
        deadline: tp.Optional[float] = None,
    ) -> int:
        """Admit onto the least-loaded HEALTHY replica (lowest index on
        ties — deterministic, so a test trace routes identically every
        run); with ``affinity=True`` the replica with the longest
        resident-prefix overlap is preferred within the load-imbalance
        cap, and in disaggregated mode only the prefill pool is
        targeted. Returns the cluster-global request id. Raises
        :class:`ClusterUnavailable` when every replica is dead, and
        passes the engine's typed admission outcomes
        (``AdmissionRejected``/``PoolOverloaded``) through to the
        caller — a rejection burns no cluster rid.

        A ``queue_full`` outcome SPILLS OVER: the routing metric (queue
        + parked + active) is not the metric the bound is enforced on
        (queue alone), so the least-loaded replica's full queue must
        not shed a request another healthy replica has room for — the
        remaining replicas are tried in load order and the overload
        outcome raises only when every queue is full. (Per-engine
        ``queue_full`` counters therefore count per-replica admission
        attempts; the request is only actually shed/deferred when the
        LAST replica refuses.) Permanent rejections are identical on
        every replica and re-raise immediately."""
        if not self._alive():
            raise ClusterUnavailable("every replica is dead")
        order, overlap = self._route_order(
            self._submit_targets(), prompt, max_new_tokens
        )
        # the ABSOLUTE deadline is fixed here, at first cluster
        # admission (unless the caller anchored it earlier — e.g. the
        # front door at ARRIVAL time), and rides the submission record:
        # a cold-failover re-serve must keep the ORIGINAL SLO, exactly
        # like it keeps the original submit time (priority rides the
        # same way)
        if deadline is None and deadline_s is not None:
            deadline = self.engines[order[0]].clock() + deadline_s
        local = None
        for n, i in enumerate(order):
            try:
                local = self.engines[i].submit(
                    prompt, max_new_tokens, eos_id=eos_id, seed=seed,
                    priority=priority, deadline=deadline,
                )
                break
            except (AdmissionRejected, PoolOverloaded) as exc:
                if exc.reason != "queue_full" or n == len(order) - 1:
                    raise
        assert local is not None
        # the routing decision is scored at the replica that actually
        # admitted: a queue_full spillover off the affinity target is a
        # fallback even when the probe matched
        routed = "least_loaded"
        if self.affinity:
            if overlap > 0 and n == 0:
                routed = "affinity"
                self.prefix_affinity_hits += 1
                self.engines[i]._emit(
                    "routed_affinity", rid=local, overlap=overlap,
                    replica=i,
                )
            else:
                routed = "fallback"
                self.routed_fallback += 1
                self.engines[i]._emit(
                    "routed_fallback", rid=local, replica=i,
                )
        rid = self._next_rid
        self._next_rid += 1
        self._route[rid] = (i, local)
        # submission record for COLD failover: a watchdog-tripped
        # replica's step thread may still be running, so its engine can
        # never be touched again — surviving requests are then re-served
        # from scratch from this record (same tokens, by the determinism
        # contract; only the already-emitted progress is recomputed).
        # The ORIGINAL submit time rides along so a re-served request's
        # TTFT still measures from first submission — hiding the outage
        # the watchdog just detected would defeat the metric. The
        # routing decision rides too (front door/failover observability).
        self._submitted[rid] = (
            np.asarray(prompt, np.int32).reshape(-1).copy(),
            max_new_tokens, eos_id, seed, self.engines[i].clock(),
            priority, deadline, routed,
        )
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancellation routing: tear the cluster-global request down on
        whichever replica currently serves it (the route survives
        failover, so this follows the request). Idempotent; returns
        True when the request was live. The submission record drops
        with the route — a cancelled request must never be re-served by
        a later cold failover."""
        rec = self._handoff.pop(rid, None)
        if rec is not None:
            # caught between pools: the exported record IS the request
            # now (the source slot already released); dropping it is
            # the cancellation — no engine holds any state to tear down
            rec.req.outcome = "cancelled"
            self.cancelled[rid] = rec.req
            self._submitted.pop(rid, None)
            return True
        route = self._route.get(rid)
        if route is None:
            return False
        i, local = route
        req = self.engines[i].lookup(local)
        if self.health[i] == "dead" or req is None:
            if req is not None and req.outcome != "pending":
                # already terminal on the dead replica: harvest under
                # its REAL outcome instead of relabeling it cancelled
                dest = {
                    "finished": self.finished,
                    "cancelled": self.cancelled,
                    "expired": self.expired,
                }[req.outcome]
                dest[rid] = req
                del self._route[rid]
                self._submitted.pop(rid, None)
                return req.outcome == "cancelled"
            # a cold-abandoned replica's engine is never touched again;
            # the request exists only as the submission record now —
            # dropping route + record IS the cancellation (it was going
            # to be re-served from scratch)
            req = self.engines[i].make_request(
                self._submitted[rid][0], self._submitted[rid][1],
                eos_id=self._submitted[rid][2],
                seed=self._submitted[rid][3],
            )
            req.rid = local
            req.outcome = "cancelled"
            self.cancelled[rid] = req
            del self._route[rid]
            self._submitted.pop(rid, None)
            return True
        ok = self.engines[i].cancel(local)
        if ok:
            self.cancelled[rid] = self.engines[i].cancelled[local]
            del self._route[rid]
            self._submitted.pop(rid, None)
        return ok

    def lookup(self, rid: int) -> tp.Optional[Request]:
        """The live or terminal :class:`Request` for a cluster-global
        id (the front door's harvest seam). After a COLD failover the
        returned object is the survivor's fresh re-serve — its token
        list regrows the same stream from zero (determinism contract),
        which is exactly what the front door's per-stream cursor
        needs."""
        for d in (self.finished, self.cancelled, self.expired):
            req = d.get(rid)
            if req is not None:
                return req
        rec = self._handoff.get(rid)
        if rec is not None:
            return rec.req  # mid-handoff: live, tokens pending
        route = self._route.get(rid)
        if route is None:
            return None
        i, local = route
        if self.health[i] == "dead":
            return None  # between death and failover re-pointing
        return self.engines[i].lookup(local)

    def _harvest(self) -> None:
        for rid, (i, local) in list(self._route.items()):
            e = self.engines[i]
            req = e.finished.get(local)
            dest = self.finished
            if req is None:
                req = e.cancelled.get(local)
                dest = self.cancelled
            if req is None:
                req = e.expired.get(local)
                dest = self.expired
            if req is not None:
                dest[rid] = req
                del self._route[rid]
                self._submitted.pop(rid, None)

    # -- failure handling ---------------------------------------------------

    def _mark_dead(self, i: int, reason: str) -> None:
        self.health[i] = "dead"
        self.health_reason[i] = reason
        if self.first_fault_time is None:
            self.first_fault_time = time.monotonic()
        if self.flight_dir is not None:
            self._flight_dump(i, reason)

    def _flight_dump(self, i: int, reason: str) -> None:
        """Persist replica ``i``'s flight recorder on the one choke
        point every terminal failure crosses (crash, watchdog trip,
        exhausted retries all land in ``_mark_dead``). Best-effort BY
        DESIGN: on a watchdog trip the step thread may still be
        appending to the rings (snapshot-copied under the GIL), and a
        dump failure must never mask the failover it documents — it
        degrades to a stderr line."""
        path = os.path.join(
            self.flight_dir, f"flight_replica{i}_{reason}.json"
        )
        try:
            rec = self.engines[i].flight_dump(
                reason, path=path, extra={"replica": i},
            )
            self.flight_dumps.append(rec["path"])
        except Exception as e:  # noqa: BLE001 — see docstring
            print(
                f"flight-recorder dump for replica {i} ({reason}) "
                f"failed: {e}",
                file=sys.stderr,
            )

    def _failover(self, i: int, cold: bool = False) -> None:
        """Fail dead replica ``i``'s backlog over to the survivors;
        cluster rids keep pointing at the same logical requests — only
        the (replica, local-rid) route changes. Two modes:

        - WARM (default; the replica's step thread provably completed
          by raising): the engine drains — in-flight slots convert
          through the (bit-identical) eviction path, then queue and
          parking lot — and the survivors resume with progress kept.
        - COLD (``cold=True``; a watchdog trip — the step thread may
          still be running inside the runtime): the engine is never
          touched again (draining it would race live slot/page
          mutations). Every request still routed to it re-serves FROM
          SCRATCH off the cluster's submission record — the same stream
          by the determinism contract, with only the un-harvested
          progress recomputed, and the ORIGINAL submit time kept so
          TTFT still shows the outage.

        ``resubmit`` (not ``submit``) either way: already-accepted work
        bypasses the bounded-queue admission control."""
        self._harvest()  # dict reads are GIL-safe; scoop what finished
        self.failovers += 1
        drained = (
            None if cold
            else {r.rid: r for r in self.engines[i].drain_requests()}
        )
        mine = [g for g, (ri, _) in self._route.items() if ri == i]
        n_moved = len(mine) if cold else len(drained)
        self.requeued_requests += n_moved
        if not self._alive():
            if self._route or self._handoff:
                raise ClusterUnavailable(
                    f"replica {i} died ({self.health_reason[i]}) with "
                    f"{n_moved} requests to fail over and no survivors"
                )
            return
        # disaggregated: failed-over work re-enters through the prefill
        # pool (it re-prefills — possibly via cache hits — then hands
        # off again), keeping the pool discipline; a drained request
        # resubmitted anywhere still yields the same stream
        targets = self._submit_targets()
        for grid in mine:
            if cold:
                prompt, n, eos_id, seed, t0, prio, deadline, _routed = (
                    self._submitted[grid]
                )
                j = self._least_loaded(targets)
                req = self.engines[j].make_request(
                    prompt, n, eos_id=eos_id, seed=seed, priority=prio,
                    deadline=deadline,
                )
                req.submit_time = t0
            else:
                req = drained.pop(self._route[grid][1], None)
                if req is None:
                    continue  # finished and harvested above
                j = self._least_loaded(targets)
            self._route[grid] = (j, self.engines[j].resubmit(req))
        assert cold or not drained, (
            f"drained requests {sorted(drained)} had no cluster route"
        )

    # -- the prefill -> decode handoff pump ---------------------------------

    def _requeue_cold(self, grid: int) -> None:
        """Re-serve one cluster request from scratch off its submission
        record, onto the least-loaded submit target (prefill pool when
        disaggregated). Same stream by the determinism contract; the
        ORIGINAL submit time / priority / deadline ride along — this is
        the single-request version of a cold failover, used when a
        handoff export fails."""
        prompt, n, eos_id, seed, t0, prio, deadline, _routed = (
            self._submitted[grid]
        )
        targets = self._submit_targets()
        if not targets:
            raise ClusterUnavailable(
                f"no replica alive to re-serve request {grid}"
            )
        j = self._least_loaded(targets)
        req = self.engines[j].make_request(
            prompt, n, eos_id=eos_id, seed=seed, priority=prio,
            deadline=deadline,
        )
        req.submit_time = t0
        self._route[grid] = (j, self.engines[j].resubmit(req))
        self.requeued_requests += 1

    def _pump_handoffs(self) -> None:
        """Move every handoff-ready slot from the prefill pool to the
        decode pool: export (pages + scale planes + carried logits row
        leave as host arrays — the honest DCN wire model), then import
        into the least-loaded alive decode replica. Runs at the END of
        each scheduler round, after every replica's step has settled —
        the pump is a cluster action on engines that are provably not
        mid-step, the same invariant failover relies on.

        A full decode pool keeps the record pending (retried next
        round; ``has_work`` counts it). A dead decode pool degrades to
        importing into alive prefill replicas — a prefill-role engine
        decodes an IMPORTED slot normally (the role only parks its own
        prefill completions), so the cluster limps instead of
        deadlocking. A scripted export fault (:class:`HandoffFailed`)
        abandons the source copy and re-serves COLD from the
        submission record — bit-identical, chaos-replayed."""
        if not self.disaggregated:
            return
        rev = {route: g for g, route in self._route.items()}
        for i in self._prefill_pool:
            if self.health[i] == "dead":
                continue
            eng = self.engines[i]
            for s in eng.handoff_ready_slots():
                req = eng.slot_req[s]
                grid = rev.get((i, req.rid))
                if grid is None:
                    continue  # not cluster-routed (direct engine use)
                try:
                    with span(
                        "midgpt.cluster.handoff", eng.telemetry, "handoff",
                        clock=eng.clock, rids=(req.rid,),
                        step=eng.fault_step, rid=req.rid,
                    ) as sp:
                        rec = eng.export_request(s)
                        sp.data.update(pages=rec.n_pages, bytes=rec.nbytes)
                except HandoffFailed:
                    self.handoff_failures += 1
                    # the export raised BEFORE any state left the slot:
                    # abandon this copy (pages release through the
                    # normal path — no cancel, the request is not
                    # cancelled) and re-serve cold
                    eng._live.pop(req.rid, None)
                    eng._release_slot(s)
                    del self._route[grid]
                    self._requeue_cold(grid)
                    continue
                del self._route[grid]
                self._handoff[grid] = rec
        for grid in list(self._handoff):
            rec = self._handoff[grid]
            targets = [
                j for j in self._decode_pool if self.health[j] != "dead"
            ] or [
                j for j in self._prefill_pool if self.health[j] != "dead"
            ]
            for j in sorted(
                targets, key=lambda j: (self._load(self.engines[j]), j)
            ):
                local = self.engines[j].import_request(rec)
                if local is not None:
                    self._route[grid] = (j, local)
                    del self._handoff[grid]
                    self.handoffs += 1
                    self.handoff_pages_moved += rec.n_pages
                    self.handoff_bytes += rec.nbytes
                    break

    @staticmethod
    def _classify(exc: BaseException) -> tp.Tuple[str, bool]:
        """(death reason, cold failover?) for a terminal step fault. A
        watchdog trip is the ONLY cold case — every other fault is a
        raise out of the step thread, which proves it completed (a
        scripted wedge's stall, in particular, has already ended)."""
        if isinstance(exc, _WatchdogTrip):
            return "wedged", True
        if isinstance(exc, WedgedDispatch):
            return "wedged", False
        return "crashed", False

    def _mark_terminal(self, i: int, exc: BaseException) -> bool:
        """Classify a terminal fault, count it, mark the replica dead;
        returns whether its failover must run COLD. Split from the
        failover itself so step() can mark ALL of a round's faults dead
        before any backlog moves."""
        reason, cold = self._classify(exc)
        if reason == "wedged":
            self.watchdog_trips += 1
        self._mark_dead(i, reason)
        return cold

    def _terminal_failure(self, i: int, exc: BaseException) -> None:
        """The one dead/failover transition: classify, mark dead, fail
        the backlog over."""
        self._failover(i, cold=self._mark_terminal(i, exc))

    @staticmethod
    def _settle(f, timeout: tp.Optional[float]) -> bool:
        """Wait for one replica-step future. Raises :class:`_WatchdogTrip`
        ONLY when the wait expires with the step thread still running —
        on Python 3.11+ ``concurrent.futures.TimeoutError`` IS the
        builtin ``TimeoutError``, so one raised organically INSIDE
        step() (thread completed) must NOT classify as a trip (a trip
        triggers the cold, engine-abandoning failover; a completed
        thread permits the warm drain)."""
        try:
            return bool(f.result(timeout=timeout))
        except concurrent.futures.TimeoutError:
            if not f.done():
                raise _WatchdogTrip() from None
            exc = f.exception()
            if exc is None:
                return bool(f.result())  # completed right at the deadline
            raise exc

    def _step_one(self, i: int, timeout: tp.Optional[float]) -> bool:
        """One replica step, on the pool when there is one (so the wait
        can be abandoned); raises the step's fault, if any."""
        if self._pool is None:
            return bool(self.engines[i].step())
        return self._settle(self._pool.submit(self.engines[i].step), timeout)

    def _recover(self, i: int) -> None:
        """Retry replica ``i`` after a transient failure: capped
        exponential backoff, suspect while retrying, healthy on success,
        dead + failover when the retries exhaust (or the retry hits a
        harder fault). The backoff sleeps run INLINE in the cluster's
        scheduling thread — deliberate: the retry must re-enter the
        replica's step() before the next scheduler round so scripted
        transient sequences stay replayable (``backoff_cap_s`` bounds
        the stall the other replicas see)."""
        self.health[i] = "suspect"
        self.health_reason[i] = "transient"
        for attempt in range(self.max_retries):
            time.sleep(
                min(self.backoff_s * (2 ** attempt), self.backoff_cap_s)
            )
            self.retries += 1
            try:
                self._step_one(i, self.dispatch_timeout_s)
            except TransientDispatchError:
                continue
            except self._STEP_FAULTS as exc:
                self._terminal_failure(i, exc)
                return
            self.health[i] = "healthy"
            self.health_reason[i] = None
            return
        self._mark_dead(i, "transient_exhausted")
        self._failover(i)

    # every fault class a replica step can surface; anything else is a
    # real bug and propagates. concurrent.futures.TimeoutError is listed
    # separately for Python < 3.11, where it is not the builtin
    # TimeoutError (organic timeouts classify as crashes either way —
    # _settle converts genuine wait-expiries to _WatchdogTrip first)
    _STEP_FAULTS = (
        TransientDispatchError,
        WedgedDispatch,
        ReplicaCrash,
        TimeoutError,
        concurrent.futures.TimeoutError,
        _WatchdogTrip,
    )

    def step(self) -> bool:
        """One scheduler window on EVERY live replica, dispatched
        CONCURRENTLY (one thread per engine): each engine's step blocks
        on its own device->host read, so the threads overlap the
        replicas' windows on their disjoint devices — aggregate
        throughput scales with replicas instead of time-multiplexing
        them. Replica failures route through the health state machine
        (watchdog / retry / failover) instead of propagating — in two
        phases: every replica's future SETTLES (completes, raises, or
        times out) before any failure is processed, so failover
        re-queueing never mutates an engine whose own step is still in
        flight (each engine stays single-threaded, and the chaos replay
        contract stays exact). Returns True while any replica has (or
        had) work; raises :class:`ClusterUnavailable` if every replica
        is dead with requests still pending."""
        alive = self._alive()
        if not alive:
            if self._route or self._handoff:
                raise ClusterUnavailable(
                    "every replica is dead with requests pending"
                )
            return False
        progressed = False
        faults: tp.List[tp.Tuple[int, BaseException]] = []
        if self._pool is None:
            try:
                progressed = bool(self.engines[alive[0]].step())
            except self._STEP_FAULTS as exc:
                faults.append((alive[0], exc))
        else:
            futs = [
                (i, self._pool.submit(self.engines[i].step)) for i in alive
            ]
            # ONE deadline for the whole round, from dispatch: the
            # futures run concurrently, so waiting them out in sequence
            # against per-wait timeouts would detect a wedge on the
            # last replica up to N*timeout late
            deadline = (
                None if self.dispatch_timeout_s is None
                else time.monotonic() + self.dispatch_timeout_s
            )
            for i, f in futs:
                try:
                    r = self._settle(
                        f,
                        None if deadline is None
                        else max(0.0, deadline - time.monotonic()),
                    )
                    progressed = r or progressed
                except self._STEP_FAULTS as exc:
                    faults.append((i, exc))
        # a fault is progress: its backlog moved or retried, and a
        # drained cluster never re-steps
        progressed = progressed or bool(faults)
        # mark EVERY terminal fault dead before running ANY failover:
        # two replicas faulting in the same round must not fail over
        # onto each other (a crash's warm drain re-queued onto a
        # watchdog-tripped engine whose step thread is still running
        # would violate the never-mutate-mid-step contract)
        terminal = [
            (i, self._mark_terminal(i, exc))
            for i, exc in faults
            if not isinstance(exc, TransientDispatchError)
        ]
        # retries next (the replica heals or joins the dead set), then
        # the failovers — every target is settled and provably alive
        for i, exc in faults:
            if isinstance(exc, TransientDispatchError):
                self._recover(i)
        for i, cold in terminal:
            self._failover(i, cold=cold)
        # handoffs pump AFTER failures settle: every engine touched is
        # provably not mid-step, and a slot that went handoff-ready
        # this round reaches its decode replica before the next one
        self._pump_handoffs()
        self._harvest()
        return progressed

    def run(self, max_windows: int = 100_000) -> tp.Dict[int, Request]:
        """Drive :meth:`step` until every live replica drains; returns
        the finished requests by cluster-global id."""
        for _ in range(max_windows):
            if not self.has_work:
                break
            self.step()
        else:
            raise RuntimeError(
                f"cluster did not drain in {max_windows} windows"
            )
        self._harvest()
        return self.finished

    def stats(self) -> tp.Dict[str, tp.Any]:
        """Summed engine counters (ServingEngine.stats key layout) plus
        ``dp_replicas``, the ``per_replica`` breakdown, and the
        cluster-level failover counters."""
        per = [e.stats() for e in self.engines]
        agg: tp.Dict[str, tp.Any] = {}
        for k in per[0]:
            if k in ("slot_occupancy", "prefix_hit_rate",
                     "tokens_per_dispatch", "spec_acceptance_rate"):
                agg[k] = round(sum(s[k] for s in per) / len(per), 4)
            elif k == "tp":
                agg[k] = per[0][k]
            elif isinstance(per[0][k], dict):
                merged: tp.Dict[str, int] = {}
                for s in per:
                    for kk, vv in s[k].items():
                        merged[kk] = merged.get(kk, 0) + vv
                agg[k] = merged
            else:
                agg[k] = sum(s[k] for s in per)
        agg["dp_replicas"] = len(per)
        agg["prefill_replicas"] = self.prefill_replicas
        agg["decode_replicas"] = self.decode_replicas
        agg["watchdog_trips"] = self.watchdog_trips
        agg["retries"] = self.retries
        agg["failovers"] = self.failovers
        agg["requeued_requests"] = self.requeued_requests
        agg["handoffs"] = self.handoffs
        agg["handoff_pages_moved"] = self.handoff_pages_moved
        agg["handoff_bytes"] = self.handoff_bytes
        agg["handoff_failures"] = self.handoff_failures
        agg["prefix_affinity_hits"] = self.prefix_affinity_hits
        agg["routed_fallback"] = self.routed_fallback
        agg["dead_replicas"] = self.health.count("dead")
        agg["replica_health"] = list(self.health)
        agg["replica_health_reason"] = list(self.health_reason)
        agg["per_replica"] = per
        return agg

    @property
    def telemetries(self) -> tp.List[tp.Optional[EngineTelemetry]]:
        """The per-replica telemetry instances (None entries when
        tracing is off) — bench_serving merges their derived request
        metrics and writes one timeline artifact per replica."""
        return [e.telemetry for e in self.engines]

    def metrics_snapshot(self) -> tp.Dict[str, tp.Any]:
        """Cluster-level registry export: the failover counters and
        health state next to every replica's full
        ``ServingEngine.metrics_snapshot()`` — the JSON artifact the r6
        queue stores beside its bench rows. ``stats()`` remains the
        stable façade (telemetry.CLUSTER_STATS_KEYS contract)."""
        return {
            "cluster": {
                "dp_replicas": len(self.engines),
                "prefill_replicas": self.prefill_replicas,
                "decode_replicas": self.decode_replicas,
                "watchdog_trips": self.watchdog_trips,
                "retries": self.retries,
                "failovers": self.failovers,
                "requeued_requests": self.requeued_requests,
                "handoffs": self.handoffs,
                "handoff_pages_moved": self.handoff_pages_moved,
                "handoff_bytes": self.handoff_bytes,
                "handoff_failures": self.handoff_failures,
                "prefix_affinity_hits": self.prefix_affinity_hits,
                "routed_fallback": self.routed_fallback,
                "dead_replicas": self.health.count("dead"),
                "replica_health": list(self.health),
                "replica_health_reason": list(self.health_reason),
                "flight_dumps": list(self.flight_dumps),
            },
            "replicas": [e.metrics_snapshot() for e in self.engines],
        }
