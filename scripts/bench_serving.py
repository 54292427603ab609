"""Serving bench: continuous-batching throughput under a Poisson request mix.

Drives midgpt_tpu.serving.ServingEngine with seeded Poisson arrivals
(random prompt/generation lengths), measures end-to-end on the real
clock, and emits ONE JSON record:

  serve_tok_s            generated tokens/s over the whole trace
  serve_ttft_p50_ms      time-to-first-token, median (arrival -> first token)
  serve_ttft_p99_ms      ... and p99
  serve_slot_occupancy   mean fraction of decode slots busy per window
  serve_decode_dispatches / serve_prefill_dispatches
  serve_tokens_per_dispatch   steady-state K * slots when saturated
  serve_prefix_hit_rate  prompt tokens served from the prefix cache
  serve_prefill_tokens_saved / serve_prefill_tokens_computed
  serve_cow_copies       copy-on-write page duplications
  serve_spec_acceptance_rate  drafted tokens the verify program accepted
                         (argmax agreement at temperature 0, rejection
                         sampling at temperature > 0)
  serve_verify_dispatches     speculative verify dispatches
  serve_quant            int8 quantized weight path on/off
  serve_peak_hbm_bytes   device peak HBM after the trace (null on CPU)
  serve_tbt_p50_ms / serve_tbt_p99_ms   per-token time-between-tokens at
                         the harvest cadence (telemetry-derived; tokens
                         land in fused K-token windows, so p50 collapses
                         toward 0 as K grows and p99 shows the window
                         wall time — serving.telemetry docstring)
  serve_queue_delay_p50_ms / _p99_ms    submit -> first admission
  serve_timeline_files   Perfetto-loadable Chrome trace timelines +
                         per-request derived metrics + the metrics
                         registry snapshot (--timeline_dir)
  serve_flight_dumps     dead-replica flight-recorder artifacts from
                         chaos runs; watchdog rows carry their dumps
                         in-band under "flight_recorder"
  serve_bytes_per_token_static  the analysis/traffic.py static HBM
                         decomposition (weights + live KV + logits per
                         decode step, per chip under --tp) at the
                         trace's mean live context — the roofline the
                         measured serve_tok_s is compared against, and
                         the generator of PERF.md's floor table
  serve_hbm_floor_ms_static     its ms/step floor at 800 GB/s

The quantized weight path (--quant on) converts the model to the int8
per-channel pytree (midgpt_tpu.quant) before the engine compiles its
programs: the weight stream every decode step pays halves (bf16 -> int8
bytes), which PERF.md r5's roofline puts at ~0.31 ms of the 0.43 ms
124M B=8 floor — run --quant off/on on the same trace to ladder it.

Self-speculative decoding (--spec on): every decode dispatch drafts up
to --spec_len tokens per request by n-gram lookup over the request's
own history and verifies them in one dispatch —
serve_tokens_per_dispatch is the headline (1 + E[accepted] tokens per
launch vs exactly 1 for --spec off at --window 1). At --temperature 0
acceptance is argmax agreement; at --temperature > 0 it is rejection
sampling against the decode sampler's own distribution (same token
distribution, same per-request key-derivation determinism — the
sampled-chat leg the speedup was previously locked out of), and
serve_spec_acceptance_rate reports the measured accept fraction either
way. Pair it with --repetitive, which tiles each prompt from a short
random pattern (the self-repeating traffic shape prompt-lookup drafting
exists for); random incompressible prompts keep acceptance (and the
win) near zero.

A shared-system-prompt mix (--sys_prompt_len N) prepends one fixed
N-token prefix to --sys_prompt_frac of all requests — the dominant
shape of production traffic (system prompts / few-shot templates) and
what the prefix cache exists for; run it with --prefix_cache on/off to
ladder the win. --prefill_chunk C prefills Sarathi-style in C-token
chunks interleaved with decode (bounds TTFT under long prompts).

Trace replay (--trace poisson|bursty|diurnal, serving.frontdoor): the
goodput-under-SLO harness — the metric the Gemma-on-TPU serving paper
(PAPERS.md) actually compares systems on. Seed-pinned arrival shapes
(memoryless / burst-arrival / rate-swept "diurnal"), long-tail
lognormal prompt lengths, shared-prefix TENANT mixes (--tenants K
zipf-assigned system prompts of --sys_prompt_len tokens), per-request
priorities (--priority_levels), per-request e2e deadlines (--slo_ms
[+ --slo_per_token_ms x budget]), and client cancellations
(--cancel_frac, after a seeded number of streamed tokens). The trace
drives the ASYNC front door (AsyncFrontDoor token streams over the
engine/cluster — so it composes with --fault_plan, --dp_replicas, and
--timeline_dir unchanged) and the record gains:

  serve_goodput_slo_tok_s   tokens from DEADLINE-MET requests only / wall
  serve_deadline_met / serve_deadline_missed   finished in/after SLO
  serve_deadline_shed       shed BEFORE dispatch (queued/parked expiry)
  serve_cancelled           client-cancelled streams (slot reclaimed,
                            pages retired cold)

Deadline-expired requests shed pre-dispatch by the engine's priority/
aging admission policy; tokens a late request still produced count in
serve_tok_s (work done) but not in goodput-under-SLO (work banked).

Chaos runs (--fault_plan "2:transient@0;4:crash@0", serving.faults spec
grammar) drive the trace through a ServingCluster with scripted,
deterministic fault injection: replica crashes/wedges/transient errors
recover via health-tracked failover (bit-identical streams — the chaos
suite's landing gate), and the record gains "status" plus recovery and
goodput-under-faults metrics (serve_goodput_tok_s counts only FINISHED
requests' tokens; serve_recovery_s is first-replica-death -> drain).
A whole-trace deadline (--deadline_s) turns a run that hangs into a
structured {"status": "watchdog"} row and a non-zero exit instead of an
opaque hang.

The decode-dispatch arithmetic is the point (PERF.md): the fixed-batch
sampler launches one XLA dispatch per generated token; the engine fuses K
whole-model steps per launch, so the dispatch count is ~tokens/(K*slots)
plus one prefill per admission. Random-init weights — throughput only.

    python scripts/bench_serving.py                 # 124M shape on device
    python scripts/bench_serving.py --preset tiny   # CPU sanity run
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=("124m", "tiny"), default="124m")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=16.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--window", type=int, default=8,
                    help="decode steps fused per dispatch (K)")
    ap.add_argument("--page_size", type=int, default=16)
    ap.add_argument("--min_prompt", type=int, default=32)
    ap.add_argument("--max_prompt", type=int, default=256)
    ap.add_argument("--min_new", type=int, default=32)
    ap.add_argument("--max_new", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix_cache", choices=("on", "off"), default="on")
    ap.add_argument("--prefill_chunk", type=int, default=0,
                    help="chunked-prefill chunk size in tokens "
                    "(0 = monolithic prefill)")
    ap.add_argument("--prompt_len", type=int, default=0,
                    help="long-document preset: pin EVERY prompt to "
                    "exactly this many tokens (overriding --min_prompt/"
                    "--max_prompt) and widen the model's block_size to "
                    "fit prompt_len + sys_prompt + max_new — the 100k-"
                    "token serving shape the sequence-parallel prefill "
                    "and host-spill rungs measure (0 = off)")
    ap.add_argument("--prefill_sp", choices=("auto", "on", "off"),
                    default="auto",
                    help="sequence-parallel prefill (serving.engine "
                    "prefill_sp): shard each prefill chunk's query rows "
                    "across the 'tensor' mesh axis so a chunk's "
                    "attention+MLP compute drops to 1/tp per chip — "
                    "streams stay bitwise identical to 'off' (choreo-"
                    "prover gated). 'auto' = on when tp > 1; decode is "
                    "untouched either way")
    ap.add_argument("--spill", choices=("on", "off"), default="off",
                    help="host-RAM cold-page spill (serving.paged "
                    "HostSpillStore): under pool pressure, refcount-0 "
                    "cached pages (+ int8 scale planes) move to host "
                    "RAM in LRU order instead of being discarded, and "
                    "fault back byte-exactly on a prefix hit — the "
                    "prefix cache's capacity extends past HBM. Requires "
                    "--prefix_cache on")
    ap.add_argument("--spill_budget_pages", type=int, default=0,
                    help="cap on host-resident spilled pages (0 = "
                    "unbounded): past it the oldest childless spilled "
                    "pages are discarded, never the pool wedged")
    ap.add_argument("--num_pages", type=int, default=0,
                    help="KV pool size in pages (0 = slots * pages-per-"
                    "slot default): the spill-pressure rungs size the "
                    "pool BELOW the trace's working set so cold pages "
                    "actually spill")
    ap.add_argument("--sys_prompt_len", type=int, default=0,
                    help="length of a shared system prompt prepended to "
                    "--sys_prompt_frac of requests (0 = independent "
                    "prompts)")
    ap.add_argument("--sys_prompt_frac", type=float, default=1.0)
    ap.add_argument("--spec", choices=("on", "off"), default="off",
                    help="self-speculative decoding (n-gram drafting + "
                    "single-dispatch verification): argmax acceptance "
                    "at --temperature 0, rejection-sampling acceptance "
                    "at --temperature > 0 — same stream contract "
                    "either way")
    ap.add_argument("--spec_len", type=int, default=8,
                    help="max draft tokens per verify dispatch (--spec on)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the "
                    "dispatch-arithmetic default): > 0 samples every "
                    "emitted token from the temperature/top_k-shaped "
                    "distribution with per-request (seed, token-index) "
                    "key derivation, and composes with --spec on via "
                    "rejection-sampling verification — the sampled-chat "
                    "traffic shape")
    ap.add_argument("--top_k", type=int, default=None,
                    help="top-k sampling cutoff (--temperature > 0)")
    ap.add_argument("--repetitive", action="store_true",
                    help="tile each prompt from a short random pattern — "
                    "the self-repeating workload n-gram drafting targets")
    ap.add_argument("--kv_quant", choices=("on", "off"), default="off",
                    help="int8-quantized paged KV pool (serving.paged): "
                    "page payloads store int8 with one f32 po2 scale "
                    "per (page, KV-head) plane, halving the K+V HBM "
                    "stream every decode step pays — the largest "
                    "remaining stream after --quant halves the weights "
                    "(PERF.md floor decomposition)")
    ap.add_argument("--paged_kernel", choices=("auto", "pallas", "xla"),
                    default="auto",
                    help="paged-attention backend: 'pallas' reads each "
                    "slot's pages through its block table IN-KERNEL "
                    "(ops.paged_attn — no gathered [S, Pmax*PS, ...] "
                    "intermediate; a compiled kernel, so TPU only), "
                    "'xla' keeps the gather path, 'auto' = pallas on "
                    "TPU where ops.paged_attn.supported() takes the "
                    "geometry")
    ap.add_argument("--quant", choices=("on", "off"), default="off",
                    help="serve the int8 per-channel quantized weight "
                    "path (midgpt_tpu.quant): dequant fused into each "
                    "matmul, halving the per-token weight HBM stream — "
                    "visible as both serve_tok_s (latency) and "
                    "serve_peak_hbm_bytes (memory)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree per engine replica: "
                    "weights column/row-parallel, KV pool sharded by "
                    "whole KV heads, vocab-sharded logits — the "
                    "per-chip weight/KV stream drops to 1/tp at the "
                    "cost of 2 activation-row psums per layer (PERF.md "
                    "arithmetic); needs tp*dp_replicas devices")
    ap.add_argument("--dp_replicas", type=int, default=1,
                    help="shared-nothing data-parallel engine replicas "
                    "under least-loaded admission "
                    "(midgpt_tpu.serving.ServingCluster); each replica "
                    "owns tp devices, its own page pool and prefix "
                    "cache — throughput scales, nothing is shared")
    ap.add_argument("--disagg", default=None, metavar="P+D",
                    help="disaggregated prefill/decode pools: 'P+D' runs "
                    "P prefill-class replicas (chunked prefill to "
                    "completion, then page handoff) and D decode-class "
                    "replicas (midgpt_tpu.serving.ServingCluster("
                    "prefill_replicas=, decode_replicas=)); streams stay "
                    "bit-identical to the monolithic engine, the record "
                    "gains handoff counters and a per-class TTFT split. "
                    "Mutually exclusive with --dp_replicas > 1")
    ap.add_argument("--affinity", choices=("on", "off"), default="off",
                    help="prefix-affinity admission: route each request "
                    "to the replica whose resident prefix cache overlaps "
                    "its prompt longest (load-imbalance capped, "
                    "least-loaded fallback) — the zipf --tenants trace "
                    "is the workload where this strictly beats blind "
                    "least-loaded on serve_prefix_hit_rate")
    ap.add_argument("--fault_plan", default=None,
                    help="scripted chaos (serving.faults spec grammar, "
                    "e.g. '2:transient@0;4:crash@0'): deterministic "
                    "fault injection keyed to scheduler steps, driven "
                    "through a ServingCluster so crash/wedge/transient "
                    "recover via failover — the record gains recovery + "
                    "goodput-under-faults metrics")
    ap.add_argument("--dispatch_timeout_s", type=float, default=None,
                    help="cluster wall-clock dispatch deadline: a "
                    "replica step exceeding this is abandoned and its "
                    "backlog fails over")
    ap.add_argument("--max_retries", type=int, default=3,
                    help="capped-exponential-backoff retries for "
                    "transient dispatch errors before failover")
    ap.add_argument("--backoff_s", type=float, default=0.05)
    ap.add_argument("--trace", choices=("off", "poisson", "bursty",
                                        "diurnal"), default="off",
                    help="trace-replay mode (serving.frontdoor): drive "
                    "the request mix through the ASYNC front door with "
                    "the named seed-pinned arrival shape — 'poisson' "
                    "memoryless at --rate, 'bursty' Poisson burst "
                    "epochs of --burst_size back-to-back arrivals, "
                    "'diurnal' a sinusoidal rate sweep over the trace "
                    "— plus long-tail lognormal prompt lengths; emits "
                    "goodput-under-SLO next to the raw tok/s")
    ap.add_argument("--burst_size", type=int, default=8,
                    help="arrivals per burst epoch (--trace bursty)")
    ap.add_argument("--slo_ms", type=float, default=0.0,
                    help="per-request end-to-end SLO in ms from "
                    "arrival (0 = no deadline): requests finishing "
                    "late count deadline-missed, requests still "
                    "queued/parked past it are SHED before dispatch "
                    "(typed outcome), and serve_goodput_slo_tok_s "
                    "counts deadline-met tokens only")
    ap.add_argument("--slo_per_token_ms", type=float, default=0.0,
                    help="extra SLO budget per requested token "
                    "(deadline = arrival + slo_ms + slo_per_token_ms "
                    "* max_new)")
    ap.add_argument("--priority_levels", type=int, default=1,
                    help="uniform seeded per-request priority in "
                    "[0, L): the engine's aging admission dispatches "
                    "high first, starvation-proof (1 = FIFO)")
    ap.add_argument("--cancel_frac", type=float, default=0.0,
                    help="fraction of requests whose client cancels "
                    "the stream after a seeded number of tokens — "
                    "exercises cancellation-safe teardown under load")
    ap.add_argument("--tenants", type=int, default=0,
                    help="shared-prefix tenant mix (--trace modes): K "
                    "distinct --sys_prompt_len-token system prompts, "
                    "zipf-ish assigned, replacing the single shared "
                    "prefix of --sys_prompt_frac")
    ap.add_argument("--max_queue", type=int, default=0,
                    help="bounded engine wait queue (0 = unbounded): "
                    "with the front door, defer outcomes become "
                    "awaitable backpressure on the submitting client")
    ap.add_argument("--telemetry", choices=("on", "off"), default="on",
                    help="per-request lifecycle tracing "
                    "(serving.telemetry): on gives the record TBT and "
                    "queue-delay percentiles and arms the flight "
                    "recorder / timeline export. Tracing never touches "
                    "the compiled programs (greedy streams are bitwise "
                    "identical on/off; measured overhead is the "
                    "host-side scheduler only — PERF.md) — 'off' exists "
                    "to ladder exactly that claim on hardware")
    ap.add_argument("--metrics_out", default=None,
                    help="write the metrics-registry snapshot (engine "
                    "or cluster + per-replica) in Prometheus text "
                    "exposition format to this path "
                    "(midgpt_tpu.telemetry.prometheus_text) — the "
                    "pull-scrape view of metrics_snapshot.json")
    ap.add_argument("--timeline_dir", default=None,
                    help="write per-replica Chrome trace-event timelines "
                    "(openable in Perfetto), the per-request derived "
                    "metrics, and the metrics-registry snapshot under "
                    "this directory; also where dead-replica "
                    "flight-recorder dumps land on chaos runs "
                    "(default: flight dumps go next to --out)")
    ap.add_argument("--deadline_s", type=float, default=900.0,
                    help="whole-trace deadline: if the trace has not "
                    "drained by then, emit a structured "
                    '{"status": "watchdog"} row and exit 4')
    ap.add_argument("--out", default=None,
                    help="output JSON path (default "
                    "artifacts/bench_serving.json)")
    args = ap.parse_args()

    # whole-RUN deadline, armed BEFORE backend init so that it covers
    # start-up and compilation too. Past it, at any phase, the run
    # yields a STRUCTURED row ({"status": "watchdog", "phase": ...}),
    # not an opaque hang. Daemon thread + os._exit like bench.py's.
    import threading

    shape = (
        f"{args.preset} S={args.slots} K={args.window} "
        f"page={args.page_size} cache={args.prefix_cache} "
        f"chunk={args.prefill_chunk or 'mono'} "
        f"sys={args.sys_prompt_len} "
        f"spec={args.spec_len if args.spec == 'on' else 'off'}"
        f"{f' T={args.temperature:g}' if args.temperature else ''}"
        f"{f' topk={args.top_k}' if args.top_k else ''}"
        f"{' rep' if args.repetitive else ''}"
        f" quant={args.quant} kv_quant={args.kv_quant}"
        f" kernel={args.paged_kernel}"
        f" tp={args.tp} dp={args.dp_replicas}"
        f"{f' plen={args.prompt_len}' if args.prompt_len else ''}"
        f" sp={args.prefill_sp}"
        f"{' spill' if args.spill == 'on' else ''}"
        f"{f' pool={args.num_pages}' if args.num_pages else ''}"
        f"{f' disagg={args.disagg}' if args.disagg else ''}"
        f"{' affinity' if args.affinity == 'on' else ''}"
        f"{' faults=' + args.fault_plan if args.fault_plan else ''}"
        f"{' trace=' + args.trace if args.trace != 'off' else ''}"
        f"{f' slo={args.slo_ms:g}ms' if args.slo_ms else ''}"
        f"{f' prio={args.priority_levels}' if args.priority_levels > 1 else ''}"
        f"{f' cancel={args.cancel_frac:g}' if args.cancel_frac else ''}"
        f"{f' tenants={args.tenants}' if args.tenants else ''}"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = args.out or os.path.join(repo, "artifacts", "bench_serving.json")
    run_done = threading.Event()
    phase = {"name": "init"}  # init -> warmup -> trace
    # the deadline fires from a daemon thread while main may be stuck
    # inside a dispatch: engines land here after construction so the
    # thread can dump their flight recorders (host-side rings,
    # snapshot-copied under the GIL — best-effort by design)
    holder = {"engines": ()}

    def _run_watchdog():
        if run_done.wait(args.deadline_s) or run_done.is_set():
            return
        # flight-recorder dumps FIRST, path recorded in-band: the whole
        # point of the telemetry layer is that a wedged run still
        # yields a timeline, not a bare {"status": "watchdog"} row
        flight = []
        for i, e in enumerate(holder["engines"]):
            try:
                p = (
                    os.path.join(
                        args.timeline_dir, f"flight_replica{i}_watchdog.json"
                    )
                    if args.timeline_dir
                    else os.path.splitext(os.path.abspath(out))[0]
                    + f".flight{i}.json"
                )
                rec = e.flight_dump(
                    "watchdog", path=p,
                    extra={"replica": i, "phase": phase["name"]},
                )
                flight.append(rec["path"])
            except Exception:  # noqa: BLE001 — a dump must not mask the row
                pass
        row = {
            "status": "watchdog",
            "phase": phase["name"],
            "serve_shape": shape,
            "serve_deadline_s": args.deadline_s,
            "flight_recorder": flight,
            "error": (
                f"serving bench exceeded {args.deadline_s:.0f}s in the "
                f"{phase['name']} phase"
            ),
        }
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
        print(json.dumps(row), flush=True)
        os._exit(4)

    threading.Thread(target=_run_watchdog, daemon=True).start()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.config import get_config
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving import ServingEngine
    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.preset == "tiny":
        from midgpt_tpu.config import ModelConfig

        cfg = ModelConfig(
            block_size=128, vocab_size=256, n_layer=2, n_head=4, n_embd=64,
            dropout=0.0, attn_impl="naive", remat="none",
        )
        args.min_prompt, args.max_prompt = 4, 16
        args.min_new, args.max_new = 4, 16
        args.requests = min(args.requests, 16)
        args.rate = 1e9  # arrivals immediate: CPU sanity, not latency
    else:
        cfg = dataclasses.replace(
            get_config("openwebtext").model, attn_impl="auto"
        )
    if args.prompt_len:
        # long-document preset: every prompt exactly --prompt_len tokens
        # (applied AFTER the tiny preset's overrides so it wins), and
        # the model widened to hold the full context — at 100k tokens
        # the widened wpe table is the only parameter that grows
        args.min_prompt = args.max_prompt = args.prompt_len
        need = args.sys_prompt_len + args.prompt_len + args.max_new
        if need > cfg.block_size:
            cfg = dataclasses.replace(cfg, block_size=need)
    assert args.max_prompt + args.max_new <= cfg.block_size, (
        "request mix must fit block_size"
    )
    model = cast_floating(GPT.init(jax.random.PRNGKey(0), cfg), jnp.bfloat16)
    if args.quant == "on":
        # quantize HERE and rebind so the bf16 weights are actually
        # dropped — quantizing inside the engine would leave this
        # binding alive and serve_peak_hbm_bytes would report bf16 +
        # int8 resident, hiding the residency win the flag measures
        from midgpt_tpu.quant import quantize_model

        model = quantize_model(model)

    rng = np.random.default_rng(args.seed)
    # arrival process — seed-pinned so a trace replays identically:
    # poisson (memoryless, the legacy default), bursty (Poisson burst
    # EPOCHS of --burst_size back-to-back arrivals — flash-crowd
    # shape), diurnal (interarrival rate swept sinusoidally through
    # one "day" over the trace — peak/trough load in one run)
    if args.trace == "bursty":
        n_bursts = -(-args.requests // args.burst_size)
        epochs = np.cumsum(
            rng.exponential(args.burst_size / args.rate, n_bursts)
        )
        arrivals = np.repeat(epochs, args.burst_size)[: args.requests]
    elif args.trace == "diurnal":
        phase = 2.0 * np.pi * np.arange(args.requests) / max(
            1, args.requests
        )
        inst_rate = args.rate * (1.0 + 0.8 * np.sin(phase))
        arrivals = np.cumsum(
            rng.exponential(1.0, args.requests) / np.maximum(
                inst_rate, 1e-9
            )
        )
    else:  # poisson (and the legacy synchronous path)
        arrivals = np.cumsum(
            rng.exponential(1.0 / args.rate, args.requests)
        )
    if args.trace != "off":
        # long-tail prompt lengths: lognormal clipped into the
        # configured band — the realistic mix (most prompts short, a
        # heavy tail of long ones) the chunked-prefill path exists for
        ln = rng.lognormal(
            mean=np.log(max(2.0, args.min_prompt * 2.0)), sigma=0.8,
            size=args.requests,
        )
        plens = np.clip(
            ln.astype(np.int64), args.min_prompt, args.max_prompt
        )
    else:
        plens = rng.integers(
            args.min_prompt, args.max_prompt + 1, args.requests
        )
    nnews = rng.integers(args.min_new, args.max_new + 1, args.requests)
    # scheduling attributes (seed-pinned): priority levels, per-request
    # deadlines, scripted client cancellations
    priorities = (
        rng.integers(0, args.priority_levels, args.requests)
        if args.priority_levels > 1
        else np.zeros(args.requests, np.int64)
    )
    deadlines_s = [
        (args.slo_ms + args.slo_per_token_ms * int(nnews[i])) / 1e3
        if args.slo_ms > 0 else None
        for i in range(args.requests)
    ]
    cancel_mask = rng.random(args.requests) < args.cancel_frac
    cancel_after = [
        int(rng.integers(1, max(2, int(nnews[i]))))
        if cancel_mask[i] else None
        for i in range(args.requests)
    ]
    sys_prompt = rng.integers(
        0, cfg.vocab_size, size=args.sys_prompt_len
    ).astype(np.int32)
    # tenant mix: K distinct system prompts, zipf-ish popularity —
    # the shared-prefix traffic shape at multi-tenant scale (tenant 0
    # hottest, so its prefix chain stays resident across the trace)
    tenant_of = None
    if args.tenants > 0 and args.sys_prompt_len > 0:
        weights = 1.0 / np.arange(1, args.tenants + 1)
        tenant_of = rng.choice(
            args.tenants, size=args.requests, p=weights / weights.sum()
        )
        tenant_prompts = [
            rng.integers(0, cfg.vocab_size, size=args.sys_prompt_len)
            .astype(np.int32)
            for _ in range(args.tenants)
        ]
    shared_mask = rng.random(args.requests) < args.sys_prompt_frac
    if args.repetitive:
        # self-repeating prompts: a short pattern tiled to length — the
        # n-gram proposer finds the period and drafts whole repeats
        def rep_prompt(p):
            pat = rng.integers(
                0, cfg.vocab_size, size=max(2, int(p) // 8)
            ).astype(np.int32)
            return np.tile(pat, -(-int(p) // pat.size))[: int(p)]

        prompts = [rep_prompt(p) for p in plens]
    else:
        prompts = [
            rng.integers(0, cfg.vocab_size, size=int(p)).astype(np.int32)
            for p in plens
        ]
    if args.sys_prompt_len:
        assert args.sys_prompt_len + args.max_prompt + args.max_new <= (
            cfg.block_size
        ), "system prompt + request mix must fit block_size"
        if tenant_of is not None:
            prompts = [
                np.concatenate([tenant_prompts[tenant_of[i]], p])
                for i, p in enumerate(prompts)
            ]
        else:
            prompts = [
                np.concatenate([sys_prompt, p]) if shared_mask[i] else p
                for i, p in enumerate(prompts)
            ]

    from midgpt_tpu.serving import (
        AdmissionRejected,
        ClusterUnavailable,
        FaultPlan,
        PoolOverloaded,
        ServingCluster,
        serving_meshes,
    )

    plan = FaultPlan.parse(args.fault_plan) if args.fault_plan else None
    engine_kw = dict(
        slots=args.slots,
        page_size=args.page_size,
        window=args.window,
        temperature=args.temperature,
        top_k=args.top_k,
        seed=args.seed,
        prefix_cache=args.prefix_cache == "on",
        prefill_chunk=args.prefill_chunk or None,
        speculate=args.spec_len if args.spec == "on" else 0,
        kv_quant="int8" if args.kv_quant == "on" else None,
        paged_kernel=args.paged_kernel,
        prefill_sp=args.prefill_sp,
        spill=args.spill,
        spill_budget_pages=args.spill_budget_pages or None,
        num_pages=args.num_pages or None,
        max_queue=args.max_queue or None,
        # telemetry=True gives each engine/replica its OWN
        # EngineTelemetry (tracing never touches the compiled programs
        # — the engines still hit the same program cache entries)
        telemetry=args.telemetry == "on",
    )
    # disaggregated pools: '--disagg P+D' replaces the homogeneous
    # --dp_replicas fleet with P prefill-class + D decode-class replicas
    disagg_p = disagg_d = 0
    if args.disagg:
        assert args.dp_replicas == 1, (
            "--disagg P+D and --dp_replicas are mutually exclusive "
            "(disagg fixes the replica count at P+D)"
        )
        parts = args.disagg.split("+")
        assert len(parts) == 2, f"--disagg wants 'P+D', got {args.disagg!r}"
        disagg_p, disagg_d = int(parts[0]), int(parts[1])
    n_replicas = (disagg_p + disagg_d) if args.disagg else args.dp_replicas
    if args.disagg and args.tp == 1 and jax.device_count() < n_replicas:
        # scheduler-correctness mode (the replicas=N documented shape):
        # all pools on the default device — CPU drives of the disagg
        # seam without forcing a host device count
        meshes = [None] * n_replicas
    else:
        meshes = serving_meshes(tp_size=args.tp, dp_replicas=n_replicas)
    # fault injection and the dispatch watchdog live in the cluster's
    # health/failover layer, so chaos runs always drive a cluster (a
    # 1-replica cluster is the degenerate case: faults still degrade
    # into typed outcomes instead of crashing the bench)
    use_cluster = (
        n_replicas > 1
        or plan is not None
        or args.dispatch_timeout_s is not None
    )
    if use_cluster:
        eng = ServingCluster(
            model, meshes=meshes, fault_plan=plan,
            prefill_replicas=disagg_p or None,
            decode_replicas=disagg_d or None,
            affinity=args.affinity == "on",
            dispatch_timeout_s=args.dispatch_timeout_s,
            max_retries=args.max_retries, backoff_s=args.backoff_s,
            # dead-replica flight recorders (crash / watchdog trip /
            # exhausted retries) land next to the timelines, or next to
            # the bench record when no --timeline_dir was given
            flight_dir=(
                args.timeline_dir
                or os.path.dirname(os.path.abspath(out))
            ),
            **engine_kw,
        )
        engines = eng.engines
    else:
        eng = ServingEngine(model, mesh=meshes[0], **engine_kw)
        engines = [eng]
    holder["engines"] = tuple(engines)
    # the engine resolved paged_kernel="auto" to a concrete backend;
    # the watchdog closure reads the rebound name
    shape = shape.replace(
        f"kernel={args.paged_kernel}", f"kernel={engines[0].paged_kernel}"
    )
    # likewise prefill_sp="auto" resolved against the engine's mesh
    # (on iff tensor > 1) — the record and shape carry the live mode
    shape = shape.replace(
        f"sp={args.prefill_sp}", f"sp={engines[0].prefill_sp}"
    )

    # warmup: compile the decode window + EVERY prefill-chunk bucket the
    # trace can dispatch, on EVERY replica. Full-prompt buckets are not
    # enough: with the prefix cache on, admissions prefill arbitrary
    # suffix lengths (and chunking caps them at prefill_chunk), so the
    # cache-on/chunked ladder rungs would otherwise pay XLA compiles
    # inside the timed region — corrupting exactly the comparison they
    # exist for. (DP replicas share program wrappers only when pinned to
    # identical devices — they are not — so each warms its own.)
    phase["name"] = "warmup"
    for e in engines:
        e._fault_hook = None  # chaos must not fire inside warmup
        e.submit(prompts[0], int(nnews[0]))
        if e.role == "prefill":
            # a prefill-class replica never decodes: step to the
            # handoff-ready park (compiling every prefill bucket the
            # trace needs), then export-and-discard to clear the slot
            while e.has_work and not e.handoff_ready_slots():
                e.step()
            for s in e.handoff_ready_slots():
                e.export_request(s)
        else:
            e.run()
        e.warm_prefill(max(p.size for p in prompts))
        e.finished.clear()
        e.clear_prefix_cache()  # measured hit rates: the trace alone
        for attr in ("decode_dispatches", "prefill_dispatches",
                     "prefill_chunks", "copy_dispatches", "tokens_generated", "windows",
                     "occupancy_sum", "evictions", "prompt_tokens_total",
                     "prompt_tokens_cached", "prefill_tokens_computed",
                     "cold_reclaims", "verify_dispatches", "spec_drafted",
                     "spec_accepted", "cancelled_requests",
                     "deadline_shed_requests", "spilled_pages",
                     "spill_faultback_pages", "spill_prefetch_pages",
                     "spill_readmissions", "spill_discards"):
            setattr(e, attr, 0)
        # telemetry + histogram reset: the measured trace's timeline and
        # latency distributions must start at zero like its fault_steps
        # and counters do
        e.metrics.reset_histograms()
        if e.telemetry is not None:
            e.telemetry.reset()
    if use_cluster:
        eng.finished.clear()
        eng._route.clear()
        eng._handoff.clear()
    if plan is not None:
        # re-arm FRESH hooks with step counters at zero: the scripted
        # plan is keyed to the measured trace's scheduler steps, not the
        # warmup's
        for i, e in enumerate(engines):
            e._fault_hook = plan.hook(i)
            e.fault_step = 0
            e.faults_injected = 0

    phase["name"] = "trace"
    status, status_error = "ok", None
    t0 = time.monotonic()
    if args.trace != "off":
        # ---- the async front-door drive (serving.frontdoor) ----
        import asyncio

        from midgpt_tpu.serving import AsyncFrontDoor

        streams: dict = {}  # request index -> TokenStream (the tenant
        # breakdown below needs the per-request terminal outcome)

        async def _drive_trace():
            fd = AsyncFrontDoor(eng)
            consumers = []

            async def consume(i, stream):
                n = 0
                async for _tok in stream:
                    n += 1
                    if cancel_after[i] is not None and n >= cancel_after[i]:
                        stream.cancel()

            async with fd:
                start = time.monotonic()
                for i in range(args.requests):
                    delay = arrivals[i] - (time.monotonic() - start)
                    if delay > 0:
                        await asyncio.sleep(delay)
                    # the SLO anchors at ARRIVAL (absolute deadline on
                    # the engines' monotonic clock): time spent waiting
                    # in submit backpressure counts against it — an
                    # admission-anchored deadline would inflate goodput
                    # exactly under the overload it is meant to measure
                    stream = await fd.submit(
                        prompts[i], int(nnews[i]), seed=i,
                        priority=int(priorities[i]),
                        deadline=(
                            None if deadlines_s[i] is None
                            else start + arrivals[i] + deadlines_s[i]
                        ),
                    )
                    streams[i] = stream
                    consumers.append(
                        asyncio.create_task(consume(i, stream))
                    )
                await asyncio.gather(*consumers)
                await fd.drain()
            return fd

        try:
            fd = asyncio.run(_drive_trace())
            if fd.error is not None:
                raise fd.error
        except ClusterUnavailable as exc:
            status, status_error = "unavailable", str(exc)
    else:
        submitted = 0
        try:
            while submitted < args.requests or eng.has_work:
                now = time.monotonic() - t0
                while (
                    submitted < args.requests
                    and arrivals[submitted] <= now
                ):
                    try:
                        eng.submit(
                            prompts[submitted], int(nnews[submitted]),
                            seed=submitted,
                        )
                    except PoolOverloaded:
                        # bounded queue full (defer, --max_queue): step
                        # below to drain, then retry this arrival — the
                        # synchronous analogue of the front door's
                        # awaitable backpressure
                        break
                    except AdmissionRejected as exc:
                        if exc.reason != "queue_full":
                            raise
                        # shed policy: the request is dropped and
                        # counted by the engine — move on
                    submitted += 1
                progressed = eng.step()
                if not progressed and submitted < args.requests:
                    time.sleep(
                        max(
                            0.0,
                            arrivals[submitted]
                            - (time.monotonic() - t0),
                        )
                    )
        except ClusterUnavailable as exc:
            # every replica died with work pending: still a structured
            # row — the goodput metrics below cover what DID finish
            status, status_error = "unavailable", str(exc)
    wall = time.monotonic() - t0
    t_end = time.monotonic()
    # the watchdog stays armed: the report phase still talks to the
    # device (memory_stats, the tp>1 comms summary re-compiles the
    # window), so a post-trace wedge must still yield a structured row
    phase["name"] = "report"

    # device peak HBM AFTER the trace: the halved weight stream is a
    # residency win too (int8 params + the same KV pool). CPU backends
    # report no memory_stats — emit null rather than a fake number.
    mem = jax.devices()[0].memory_stats() or {}
    peak_hbm = mem.get("peak_bytes_in_use")

    # per-axis comms summary of the sharded decode window (analysis/cost):
    # compile the SAME program geometry (full size, same mesh shape)
    # through the audit harness and attribute each collective's wire
    # bytes to its mesh axis — the static per-dispatch number PERF.md's
    # comms arithmetic is stated against (2 activation psums/layer + the
    # argmax combiner under TP). Cost honesty: this is a second AOT
    # compile of the window (jax's dispatch-path executable cache does
    # not serve .lower().compile()) plus a transient second model/pool
    # on device — it runs AFTER the timed region and the peak-HBM read,
    # so it can only cost queue wall-clock, and a failure here must not
    # lose the bench record. tp=1 has no collectives — emit zeros.
    comms_bytes, comms_by_axis, comms_count = 0, {}, 0
    if args.tp > 1:
        try:
            from midgpt_tpu.analysis import hlo as hlo_mod
            from midgpt_tpu.analysis.cost import cost_report
            from midgpt_tpu.analysis.harness import compile_decode_window
            from midgpt_tpu.analysis.rules import StepAnalysis

            exp = dataclasses.replace(get_config("openwebtext"), model=cfg)
            hlo, amesh, donated, blk, _, _, _ = compile_decode_window(
                exp, slots=args.slots, window=args.window,
                page_size=args.page_size, shrink=False,
                quant=args.quant == "on", mesh_shape={"tensor": args.tp},
            )
            analysis = StepAnalysis.from_text(
                hlo, hlo_mod.MeshInfo.from_mesh(amesh, num_slices=1),
                global_batch=args.slots, block=blk, donated_leaves=donated,
            )
            rep = cost_report(analysis)
            comms_bytes = rep["value"]
            comms_by_axis = rep["by_axis"]
            comms_count = rep["collective_count"]
        except Exception as e:  # noqa: BLE001 — summary is best-effort
            print(f"comms summary skipped: {e}", file=sys.stderr)
            comms_bytes = None

    # static dispatch/launch structure of THIS trace's decode program
    # (analysis.dispatch — the launch-side twin of the byte
    # decomposition below): trace the engine's own decode/verify
    # program geometry and record launches-per-window, inlined layer
    # bodies and host transfers next to the measured tok/s, so each
    # row carries its static structure in-band. Best-effort like the
    # comms summary — tracing only, after the timed region.
    disp = {}
    try:
        from midgpt_tpu.analysis.dispatch import dispatch_report
        from midgpt_tpu.serving.engine import trace_serving_programs

        jaxprs = trace_serving_programs(
            engines[0].model, slots=args.slots, window=args.window,
            spec_len=max(1, args.spec_len if args.spec == "on" else 1),
            page_size=args.page_size,
            kv_quant="int8" if args.kv_quant == "on" else None,
            paged_kernel=engines[0].paged_kernel,
        )
        key = "verify" if args.spec == "on" else "decode_window"
        rep = dispatch_report(
            jaxprs[key], program=key,
            window_steps=1 if args.spec == "on" else args.window,
        )
        disp = rep.to_dict()
    except Exception as e:  # noqa: BLE001 — summary is best-effort
        print(f"dispatch summary skipped: {e}", file=sys.stderr)

    # static HBM decomposition for THIS trace's geometry (analysis/
    # traffic.py — the same arithmetic that generates PERF.md's floor
    # table): weight + live-KV + logits streams per decode step at the
    # trace's mean live context, per chip under TP. Recorded next to
    # the measured tok/s so the floor PERF.md compares against is
    # generated, not hand-computed.
    from midgpt_tpu.analysis.traffic import floor_decomposition

    # mean over the FINAL prompt list (includes the shared system
    # prefix and repetitive tiling): those tokens are live KV context
    # during decode exactly like any other prompt token
    live_mean = float(
        np.mean([p.size for p in prompts]) + np.mean(nnews) / 2.0
    )
    static = floor_decomposition(
        cfg, slots=args.slots, live_tokens=live_mean,
        quant=args.quant == "on", kv_quant=args.kv_quant == "on",
        page_size=args.page_size, tp_degree=args.tp,
    )

    ttfts = sorted(
        (r.first_token_time - r.submit_time) * 1e3
        for r in eng.finished.values()
        if r.first_token_time is not None
    )
    pct = (  # noqa: E731
        (lambda q: round(ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))], 1))
        if ttfts else (lambda q: None)
    )
    # long-prompt TTFT lane: the percentile the SP-prefill rung pair
    # ladders. With --prompt_len every request is long by construction;
    # otherwise "long" = the top quartile of the configured prompt band
    # (+ any shared prefix, which prefills like prompt tokens)
    long_thresh = args.prompt_len or (
        args.sys_prompt_len + (3 * args.max_prompt) // 4
    )
    ttfts_long = sorted(
        (r.first_token_time - r.submit_time) * 1e3
        for r in eng.finished.values()
        if r.first_token_time is not None
        and (r.prompt0.size or r.prompt.size) >= long_thresh
    )
    ttft_long_p99 = (
        round(ttfts_long[min(len(ttfts_long) - 1,
                             int(0.99 * len(ttfts_long)))], 1)
        if ttfts_long else None
    )
    # --disagg: TTFT split by the replica class that FINISHED each
    # request (decode-class replicas own every post-handoff first token;
    # prefill-class entries are non-empty only in degraded operation).
    # The engine-level finished dicts survive cluster harvest, so the
    # split reads them directly.
    ttft_by_class = None
    if args.disagg:
        ttft_by_class = {}
        for cls in ("prefill", "decode"):
            vals = sorted(
                (r.first_token_time - r.submit_time) * 1e3
                for e in engines if e.role == cls
                for r in e.finished.values()
                if r.first_token_time is not None
            )
            ttft_by_class[cls] = {
                "n": len(vals),
                "p50_ms": (
                    round(vals[min(len(vals) - 1, len(vals) // 2)], 1)
                    if vals else None
                ),
                "p99_ms": (
                    round(vals[min(len(vals) - 1,
                                   int(0.99 * len(vals)))], 1)
                    if vals else None
                ),
            }
    st = eng.stats()

    # measured-vs-floor attainment + serving MFU: ms/tok measured over
    # the trace vs the static
    # per-token HBM floor above, and the achieved fraction of peak
    # FLOPs at the decode forward's per-token FLOP count — bandwidth
    # and compute ceilings side by side in one row.
    from midgpt_tpu.utils.metrics import (
        UnknownDevicePeak,
        decode_flops_per_token,
        device_peak_flops,
    )

    # None = not measured: a CPU run has no peak to hold its rate against
    try:
        peak_flops = device_peak_flops()
    except UnknownDevicePeak:
        peak_flops = None

    ms_per_tok = (
        wall * 1e3 / st["tokens_generated"]
        if st["tokens_generated"] else None
    )
    n_chips = max(1, args.tp * n_replicas)
    # static SP-prefill compute floor pair (the long-context twin of
    # the HBM decode floor above): prefilling a mean-length prompt
    # costs prompt_tokens x flops-per-token at the prompt's mean live
    # context, compute-bound. The pair BRACKETS the rung pair's
    # measured TTFT — `floor` is the one-chip compute floor (all row
    # work replicated), `sp_floor` divides by tp (every per-row
    # segment sharded over 'tensor'); plain TP already shards the
    # matmul FLOPs, SP additionally shards the replicated per-token
    # segments, so the realized prefill lands between the two.
    prompt_mean = float(np.mean([p.size for p in prompts]))
    sp_on = engines[0].prefill_sp == "on"
    prefill_floor_ms = prefill_sp_floor_ms = serve_mfu_v = None
    if peak_flops is not None:
        prefill_floor_ms = round(
            prompt_mean * decode_flops_per_token(cfg, prompt_mean / 2.0)
            / peak_flops * 1e3, 4,
        )
        prefill_sp_floor_ms = round(
            prefill_floor_ms / (args.tp if sp_on else 1), 4
        )
        if wall > 0:
            serve_mfu_v = round(
                (st["tokens_generated"] / wall)
                * decode_flops_per_token(cfg, live_mean)
                / (peak_flops * n_chips), 6,
            )

    # per-tenant SLO/goodput breakdown (--trace + --tenants): the zipf
    # tenant mix becomes observable per tenant — which tenants' tokens
    # banked within deadline, not just the aggregate
    tenant_requests = tenant_goodput = tenant_met = None
    if args.trace != "off" and tenant_of is not None:
        tenant_requests = {str(t): 0 for t in range(args.tenants)}
        tenant_met = {str(t): 0 for t in range(args.tenants)}
        _tenant_toks = {str(t): 0 for t in range(args.tenants)}
        for i, s_ in streams.items():
            tkey = str(int(tenant_of[i]))
            tenant_requests[tkey] += 1
            req = s_.request
            if s_.outcome == "finished" and req is not None and (
                req.deadline is None
                or (
                    req.finish_time is not None
                    and req.finish_time <= req.deadline
                )
            ):
                tenant_met[tkey] += 1
                _tenant_toks[tkey] += len(req.tokens)
        tenant_goodput = {
            t: round(n / wall, 1) for t, n in _tenant_toks.items()
        }

    # Prometheus text exposition over the metrics registry (engine or
    # cluster + replicas) — the scrape-format twin of the
    # metrics_snapshot.json artifact
    metrics_out_path = None
    if args.metrics_out:
        from midgpt_tpu.telemetry import prometheus_text

        metrics_out_path = os.path.abspath(args.metrics_out)
        os.makedirs(
            os.path.dirname(metrics_out_path) or ".", exist_ok=True
        )
        with open(metrics_out_path, "w") as f:
            f.write(prometheus_text(eng.metrics_snapshot()))

    # telemetry-derived per-request latency percentiles + timeline
    # artifacts (serving.telemetry). TBT granularity honesty: the
    # engine emits tokens in window batches, so the per-token gaps are
    # the HARVEST cadence a streaming client would see (0 within one
    # fused window, the window wall time across windows) — the p99 is
    # the interesting lane, the p50 collapses toward 0 as K grows.
    from midgpt_tpu.serving.telemetry import (
        chrome_trace,
        percentile,
        write_json,
    )

    teles = [
        (i, e.telemetry)
        for i, e in enumerate(engines)
        if e.telemetry is not None
    ]
    req_metrics = [m for _, t in teles for m in t.finished_request_metrics()]
    tbts = sorted(dt * 1e3 for m in req_metrics for dt in m["tbt_s"])
    qdelays = sorted(
        m["queue_delay_s"] * 1e3
        for m in req_metrics
        if m["queue_delay_s"] is not None
    )
    pms = (  # noqa: E731
        lambda vals, q: (
            round(percentile(vals, q), 3) if vals else None
        )
    )
    timeline_files = []
    if args.timeline_dir and teles:
        for i, t in teles:
            timeline_files.append(write_json(
                os.path.join(args.timeline_dir, f"timeline_replica{i}.json"),
                chrome_trace(t),
            ))
        timeline_files.append(write_json(
            os.path.join(args.timeline_dir, "request_metrics.json"),
            {"requests": req_metrics},
        ))
        # the registry snapshot (counters + gauges + histograms) rides
        # along so a row has its dispatch-level breakdown
        # next to the ms/tok headline
        timeline_files.append(write_json(
            os.path.join(args.timeline_dir, "metrics_snapshot.json"),
            eng.metrics_snapshot(),
        ))
    # goodput under faults: each finished request's tokens count exactly
    # once, however many times faults made the engines recompute them.
    # serve_tok_s (tokens_generated) stays the raw engine WORK rate — a
    # warm failover carries emitted tokens to the survivor (no recount),
    # but a COLD one re-serves from scratch, so the dead replica's
    # progress is generated twice; the gap between the two rates is the
    # throughput the faults burned.
    good_tokens = sum(len(r.tokens) for r in eng.finished.values())
    # goodput UNDER SLO (the trace-replay headline): only tokens from
    # requests that finished WITHIN their deadline bank — a late finish
    # is engine work (serve_tok_s) that earned nothing, a pre-dispatch
    # shed never became work at all. Without --slo_ms every finish
    # counts (goodput_slo == goodput).
    met = [
        r for r in eng.finished.values()
        if r.deadline is None or (
            r.finish_time is not None and r.finish_time <= r.deadline
        )
    ]
    slo_tokens = sum(len(r.tokens) for r in met)
    n_missed = len(eng.finished) - len(met)
    n_cancelled = len(getattr(eng, "cancelled", {}))
    n_expired = len(getattr(eng, "expired", {}))
    # recovery: wall-clock from the first replica death to trace drain
    first_fault = getattr(eng, "first_fault_time", None)
    record = {
        "device": jax.devices()[0].device_kind,
        "status": status,
        "serve_shape": shape,
        "serve_tp": args.tp,
        "serve_dp_replicas": args.dp_replicas,
        "serve_comms_bytes_per_dispatch": comms_bytes,
        "serve_comms_by_axis": comms_by_axis,
        "serve_comms_collective_count": comms_count,
        "serve_quant": args.quant,
        "serve_kv_quant": args.kv_quant,
        # requested vs resolved: "auto" resolves post-supported(), and a
        # long-context row claiming pallas must not hide an XLA fallback
        "serve_paged_kernel": args.paged_kernel,
        "serve_paged_kernel_resolved": engines[0].paged_kernel,
        "serve_static_launches_per_window": disp.get("launches_per_window"),
        "serve_static_inlined_layer_bodies": disp.get(
            "inlined_layer_bodies"
        ),
        "serve_static_host_transfers": disp.get("host_transfers"),
        "serve_peak_hbm_bytes": peak_hbm,
        "serve_bytes_per_token_static": static["bytes_per_token"],
        "serve_bytes_per_step_static": static["bytes_per_step"],
        "serve_weights_bytes_per_step_static": static[
            "weights_bytes_per_step"
        ],
        "serve_kv_bytes_per_step_static": static["kv_bytes_per_step"],
        "serve_hbm_floor_ms_static": static["floor_ms_per_step"],
        "serve_floor_ms_per_tok_static": static["floor_ms_per_token"],
        "serve_ms_per_tok": (
            round(ms_per_tok, 4) if ms_per_tok is not None else None
        ),
        # attainment = floor / measured: 1.0 means the decode step runs
        # at the HBM roofline; the residual is dispatch structure +
        # [B,1,D] matmul inefficiency (PERF.md's gap decomposition,
        # now measured in-band instead of hand-derived)
        "serve_attainment_frac": (
            # significant digits, not decimals: tiny-preset CPU rows sit
            # at ~1e-4 and must not round to a hard zero
            float(f"{static['floor_ms_per_token'] / ms_per_tok:.3g}")
            if ms_per_tok else None
        ),
        "serve_mfu": serve_mfu_v,
        "serve_static_live_tokens": round(live_mean, 1),
        "serve_requests": args.requests,
        "serve_rate_req_s": args.rate if args.preset != "tiny" else None,
        "serve_wall_s": round(wall, 3),
        "serve_tok_s": round(st["tokens_generated"] / wall, 1),
        "serve_ttft_p50_ms": pct(0.50),
        "serve_ttft_p99_ms": pct(0.99),
        # long-context serving (sequence-parallel prefill + host-RAM
        # cold-page spill): the resolved SP mode, the long-prompt TTFT
        # lane the sp off/on rung pair ladders, the static prefill
        # compute floor pair that brackets it (one-chip floor vs the
        # fully-row-sharded /tp ideal), and the spill counters that
        # price the host round-trips under pool pressure
        "serve_prefill_sp": engines[0].prefill_sp,
        "serve_prompt_len": args.prompt_len or None,
        "serve_ttft_long_p99": ttft_long_p99,
        "serve_prefill_floor_ms_static": prefill_floor_ms,
        "serve_prefill_sp_floor_ms_static": prefill_sp_floor_ms,
        "serve_spill": args.spill,
        "serve_num_pages": engines[0].alloc.num_pages,
        "serve_spilled_pages": st.get("spilled_pages", 0),
        "serve_spill_faultback_pages": st.get("spill_faultback_pages", 0),
        "serve_spill_prefetch_pages": st.get("spill_prefetch_pages", 0),
        "serve_spill_readmissions": st.get("spill_readmissions", 0),
        "serve_spill_discards": st.get("spill_discards", 0),
        "serve_spill_resident_pages": st.get("spill_resident_pages", 0),
        # disaggregated pools + affinity routing (serving.cluster)
        "serve_disagg": args.disagg,
        "serve_affinity": args.affinity,
        "serve_ttft_by_class": ttft_by_class,
        "serve_handoff_count": st.get("handoffs", 0),
        "serve_handoff_pages": st.get("handoff_pages_moved", 0),
        "serve_handoff_bytes": st.get("handoff_bytes", 0),
        "serve_handoff_failures": st.get("handoff_failures", 0),
        "serve_prefix_affinity_hits": st.get("prefix_affinity_hits", 0),
        "serve_routed_fallback": st.get("routed_fallback", 0),
        # telemetry-derived (serving.telemetry; null with --telemetry
        # off): time-between-tokens at the harvest cadence and
        # submit->first-admission queue delay
        "serve_telemetry": args.telemetry,
        "serve_tbt_p50_ms": pms(tbts, 0.50),
        "serve_tbt_p99_ms": pms(tbts, 0.99),
        "serve_queue_delay_p50_ms": pms(qdelays, 0.50),
        "serve_queue_delay_p99_ms": pms(qdelays, 0.99),
        "serve_timeline_files": timeline_files or None,
        "serve_flight_dumps": (
            list(eng.flight_dumps) if use_cluster else []
        ) or None,
        "serve_slot_occupancy": st["slot_occupancy"],
        "serve_decode_dispatches": st["decode_dispatches"],
        "serve_prefill_dispatches": st["prefill_dispatches"],
        "serve_tokens_generated": st["tokens_generated"],
        "serve_tokens_per_dispatch": st["tokens_per_dispatch"],
        "serve_evictions": st["evictions"],
        "serve_prefix_hit_rate": st["prefix_hit_rate"],
        "serve_prefill_tokens_saved": st["prefill_tokens_saved"],
        "serve_prefill_tokens_computed": st["prefill_tokens_computed"],
        "serve_cow_copies": st["copy_dispatches"],
        "serve_cold_reclaims": st["cold_reclaims"],
        "serve_verify_dispatches": st["verify_dispatches"],
        "serve_spec_drafted_tokens": st["spec_drafted_tokens"],
        "serve_spec_accepted_tokens": st["spec_accepted_tokens"],
        "serve_spec_acceptance_rate": st["spec_acceptance_rate"],
        # sampling shape: temperature 0 = greedy; > 0 composes with
        # --spec on via rejection-sampling verification, and the
        # acceptance rate above is the sampled accept fraction
        "serve_temperature": args.temperature,
        "serve_top_k": args.top_k,
        # trace replay / SLO accounting (serving.frontdoor)
        "serve_trace": args.trace,
        "serve_slo_ms": args.slo_ms or None,
        "serve_priority_levels": args.priority_levels,
        "serve_cancel_frac": args.cancel_frac,
        "serve_tenants": args.tenants or None,
        "serve_tenant_requests": tenant_requests,
        "serve_tenant_goodput": tenant_goodput,
        "serve_tenant_deadline_met": tenant_met,
        "serve_metrics_out": metrics_out_path,
        "serve_goodput_slo_tok_s": round(slo_tokens / wall, 1),
        "serve_deadline_met": len(met),
        "serve_deadline_missed": n_missed,
        "serve_deadline_shed": st.get("deadline_shed_requests", 0),
        "serve_cancelled": n_cancelled,
        "serve_expired_requests": n_expired,
        # fault tolerance / overload degradation (serving.faults)
        "serve_fault_plan": args.fault_plan,
        "serve_requests_finished": len(eng.finished),
        "serve_goodput_tok_s": round(good_tokens / wall, 1),
        "serve_faults_injected": st.get("faults_injected", 0),
        "serve_admission_rejected": st.get("admission_rejected", 0),
        "serve_reject_reasons": st.get("reject_reasons", {}),
        "serve_shed_requests": st.get("shed_requests", 0),
        "serve_deferred_submits": st.get("deferred_submits", 0),
        "serve_livelock_parks": st.get("livelock_parks", 0),
        "serve_overload_parks": st.get("overload_parks", 0),
        "serve_watchdog_trips": st.get("watchdog_trips", 0),
        "serve_retries": st.get("retries", 0),
        "serve_failovers": st.get("failovers", 0),
        "serve_requeued_requests": st.get("requeued_requests", 0),
        "serve_dead_replicas": st.get("dead_replicas", 0),
        "serve_replica_health": st.get(
            "replica_health", ["healthy"] * len(engines)
        ),
        "serve_recovery_s": (
            round(t_end - first_fault, 3) if first_fault is not None
            else None
        ),
        "serve_error": status_error,
    }
    run_done.set()  # record complete: main owns the output line now
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
