"""Serving subsystem: paged KV cache + continuous-batching engine.

Public surface:

- :class:`~midgpt_tpu.serving.paged.PagedKVPool`,
  :class:`~midgpt_tpu.serving.paged.PageAllocator`,
  :class:`~midgpt_tpu.serving.paged.PrefixIndex` — the page pool, its
  host-side refcounting allocator, and the content-addressed prefix
  index behind copy-on-write page sharing.
- :class:`~midgpt_tpu.serving.engine.ServingEngine` — the scheduler:
  ``submit()`` requests, ``run()`` to drain, per-request
  :class:`~midgpt_tpu.serving.engine.Request` records with TTFT/latency
  timestamps. ``prefix_cache=True`` shares already-resident pages across
  requests (prefill skips the cached prefix); ``prefill_chunk=N``
  prefills Sarathi-style in N-token chunks interleaved with decode.
- :func:`~midgpt_tpu.serving.engine.make_decode_window`,
  :func:`~midgpt_tpu.serving.engine.make_prefill_chunk_program` — the
  fused K-step decode program and the suffix-prefill chunk program
  (both audited for donation and host-sync regressions:
  ``python -m midgpt_tpu.analysis --serving``).
- :func:`~midgpt_tpu.serving.engine.make_block_window` — the window of
  a block-diffusion model (``ModelConfig.block_len``): K forwards of
  every slot's current block, each with the commit of the block before
  it where that is yet to land, the reveal rule on the device (README
  "Block-diffusion generation").
- :func:`~midgpt_tpu.serving.engine.make_verify_program`,
  :class:`~midgpt_tpu.serving.speculate.NgramProposer` — self-speculative
  decoding: draft-model-free n-gram drafting plus the single-dispatch
  paged verification program (``ServingEngine(speculate=N)``; audited
  next to the other two serving programs).
- :class:`~midgpt_tpu.serving.cluster.ServingCluster`,
  :func:`~midgpt_tpu.serving.cluster.serving_meshes` — TPxDP: the engine
  shards its model/KV pool over a tensor-only mesh
  (``ServingEngine(mesh=...)``, whole-KV-head pool sharding), and the
  cluster runs N shared-nothing engine replicas (least-loaded admission,
  per-replica prefix caches, aggregated stats) above it — with
  per-replica health, a dispatch watchdog, transient-error retry, and
  bit-identical failover of a dead replica's backlog. Disaggregated
  serving rides the same seam:
  ``ServingCluster(prefill_replicas=P, decode_replicas=D)`` splits the
  pools by roofline (compute-bound prefill vs HBM-bound decode), pages
  hand off between them via
  :func:`~midgpt_tpu.serving.paged.export_pages` /
  :func:`~midgpt_tpu.serving.paged.import_pages`
  (:class:`~midgpt_tpu.serving.engine.HandoffRecord` carries payloads,
  int8 scale planes, and the final prefill logits row — decode resumes
  bit-identically), and ``affinity=True`` routes admission to the
  replica with the longest resident-prefix overlap (load-imbalance
  capped; :class:`~midgpt_tpu.serving.faults.HandoffFailed` is the
  typed fault for a handoff that dies mid-flight).
- :class:`~midgpt_tpu.serving.faults.FaultPlan` and the typed failure
  surface (:class:`~midgpt_tpu.serving.faults.AdmissionRejected`,
  :class:`~midgpt_tpu.serving.faults.PoolOverloaded`, the replica fault
  exceptions) — deterministic, scripted chaos injection keyed to
  scheduler-step boundaries, replayable bit for bit.
- :class:`~midgpt_tpu.serving.telemetry.EngineTelemetry`,
  :class:`~midgpt_tpu.serving.telemetry.MetricsRegistry`,
  :func:`~midgpt_tpu.serving.telemetry.chrome_trace` — the observability
  layer: per-request lifecycle tracing keyed to scheduler steps
  (``ServingEngine(telemetry=True)``; zero program perturbation — the
  traced engine launches the identical cached jitted callables and
  greedy streams are bitwise identical either way), the registry behind
  ``stats()`` (``ENGINE_STATS_KEYS``/``CLUSTER_STATS_KEYS`` pin the
  façade's key contract), the fault flight recorder
  (``ServingEngine.flight_dump``, ``ServingCluster(flight_dir=...)``),
  and Perfetto-loadable timeline export.
- :class:`~midgpt_tpu.serving.frontdoor.AsyncFrontDoor`,
  :class:`~midgpt_tpu.serving.frontdoor.TokenStream`,
  :class:`~midgpt_tpu.serving.frontdoor.VirtualClock` — the asyncio
  streaming front door (ROADMAP item 3): per-request async token
  streams at the window-harvest cadence, cancellation-safe teardown
  (slot reclaim + cold page retire, invariants property-checked),
  priority/deadline admission with awaitable backpressure, and a
  manual-pump determinism seam (streams bit-identical to the
  synchronous loop; chaos replays event-sequence-identical). The
  engine-side policy underneath: ``submit(priority=, deadline_s=)``,
  aging starvation-proof admission, pre-dispatch deadline sheds
  (:class:`~midgpt_tpu.serving.faults.DeadlineExceeded`), and
  ``cancel()`` (:class:`~midgpt_tpu.serving.faults.Cancelled`).
- :func:`generate_served` — one-shot batch generation through the engine
  (the ``sample.py --serve`` path).
"""

from __future__ import annotations

import typing as tp

import numpy as np

from midgpt_tpu.serving.cluster import ServingCluster, serving_meshes
from midgpt_tpu.serving.faults import (
    AdmissionRejected,
    Cancelled,
    ClusterUnavailable,
    DeadlineExceeded,
    FaultEvent,
    FaultPlan,
    HandoffFailed,
    PoolOverloaded,
    ReplicaCrash,
    ServingFault,
    TransientDispatchError,
    WedgedDispatch,
)
from midgpt_tpu.serving.frontdoor import (
    AsyncFrontDoor,
    TokenStream,
    VirtualClock,
)
from midgpt_tpu.serving.engine import (
    HandoffRecord,
    Request,
    ServingEngine,
    make_copy_page_program,
    make_block_window,
    make_decode_window,
    make_prefill_chunk_program,
    make_verify_program,
)
from midgpt_tpu.serving.speculate import NgramProposer, Proposer
from midgpt_tpu.serving.telemetry import (
    CLUSTER_STATS_KEYS,
    ENGINE_STATS_KEYS,
    EngineTelemetry,
    MetricsRegistry,
    chrome_trace,
)
from midgpt_tpu.serving.paged import (
    PageAllocator,
    PagedKVPool,
    PrefixIndex,
    RecurrentState,
    copy_page,
    export_pages,
    flush_recent,
    import_pages,
    pages_needed,
    write_prompt_pages,
    write_token_rows,
)

__all__ = [
    "AdmissionRejected",
    "AsyncFrontDoor",
    "CLUSTER_STATS_KEYS",
    "Cancelled",
    "ClusterUnavailable",
    "DeadlineExceeded",
    "ENGINE_STATS_KEYS",
    "EngineTelemetry",
    "FaultEvent",
    "FaultPlan",
    "HandoffFailed",
    "HandoffRecord",
    "MetricsRegistry",
    "NgramProposer",
    "PageAllocator",
    "PagedKVPool",
    "RecurrentState",
    "PoolOverloaded",
    "PrefixIndex",
    "Proposer",
    "ReplicaCrash",
    "Request",
    "ServingCluster",
    "ServingEngine",
    "ServingFault",
    "TokenStream",
    "TransientDispatchError",
    "VirtualClock",
    "WedgedDispatch",
    "chrome_trace",
    "copy_page",
    "serving_meshes",
    "export_pages",
    "flush_recent",
    "generate_served",
    "import_pages",
    "make_copy_page_program",
    "make_block_window",
    "make_decode_window",
    "make_prefill_chunk_program",
    "make_verify_program",
    "pages_needed",
    "write_prompt_pages",
    "write_token_rows",
]


def generate_served(
    model,
    prompts: tp.Sequence[np.ndarray],
    max_new_tokens: int,
    *,
    eos_id: tp.Optional[int] = None,
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
    slots: tp.Optional[int] = None,
    window: int = 4,
    page_size: int = 16,
    cache_dtype=None,
    seed: int = 0,
    prefix_cache: bool = True,
    prefill_chunk: tp.Optional[int] = None,
    prefill_budget: tp.Optional[int] = None,
    speculate: int = 0,
    quant: tp.Optional[str] = None,
    kv_quant: tp.Optional[str] = None,
    paged_kernel: str = "auto",
    layer_scan: str = "off",
    mesh=None,
) -> tp.List[np.ndarray]:
    """One-shot batch generation routed through the serving engine: submit
    every prompt, drain, return the generated token arrays in submission
    order. The engine path to the fixed-batch ``sampling.generate`` —
    same greedy tokens, 1/K the decode dispatches, and per-request early
    exit at ``eos_id``. ``speculate=N`` turns decode dispatches into
    n-gram-drafted verify dispatches emitting ``1 + accepted`` tokens
    each — at ``temperature == 0`` acceptance is argmax agreement (same
    tokens, fewer launches); at ``temperature > 0`` it is rejection
    sampling against the decode sampler's own distribution (same token
    DISTRIBUTION and the same per-request key-derivation determinism,
    fewer launches).
    ``quant="int8"`` serves the int8 per-channel quantized weight path
    (midgpt_tpu.quant: dequant fused into each matmul — halves the
    per-token weight stream; po2 scales keep greedy output token-
    identical to the engine running the dequantized weights)."""
    import jax.numpy as jnp

    eng = ServingEngine(
        model,
        slots=slots if slots is not None else max(1, min(8, len(prompts))),
        page_size=page_size,
        window=window,
        temperature=temperature,
        top_k=top_k,
        cache_dtype=cache_dtype if cache_dtype is not None else jnp.bfloat16,
        seed=seed,
        prefix_cache=prefix_cache,
        prefill_chunk=prefill_chunk,
        prefill_budget=prefill_budget,
        speculate=speculate,
        quant=quant,
        kv_quant=kv_quant,
        paged_kernel=paged_kernel,
        layer_scan=layer_scan,
        mesh=mesh,
    )
    rids = [
        eng.submit(p, max_new_tokens, eos_id=eos_id, seed=i)
        for i, p in enumerate(prompts)
    ]
    finished = eng.run()
    return [np.asarray(finished[r].tokens, np.int32) for r in rids]
