"""Benchmark: training throughput + MFU on real TPU hardware.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline: flagship-family (openwebtext_xl: D=2048, H=16, C=128, T=1024 —
the 1.5B per-layer compute shape, depth-scaled to fit one chip) training
MFU, compared against the reference's published 47.8% MFU for the SAME
model family (1.5B on v3-128, /root/reference/README.md:55 — its only
published efficiency number; see BASELINE.md "north star"). MFU is
per-FLOP, so the depth-scaled number tracks the full-depth one; the
1.5B's smaller embed/head FLOP share makes it conservative if anything.

Auxiliary rungs:
- gpt2s_*: GPT-2-small (124M, openwebtext config) MFU — a stricter shape
  for this hardware (768/64 projections half-fill the MXU; see PERF.md
  "measured ceilings"), tracked across rounds.
- llama_*: llama_7b-family per-layer shape (D=4096, H=32/Hkv=8 GQA,
  SwiGLU, C=128, T=2048), depth-scaled to one chip (r3).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import time

import jax
import numpy as np

BASELINE_MFU = 0.478  # reference 1.5B on TPU v3-128 (README.md:55)

# bench-run flight recorder (midgpt_tpu.train_telemetry): main() parks
# the telemetry object here so the deadline threads can dump the rung
# timeline best-effort — a watchdog/error row then carries its
# flight-dump path IN-BAND, like bench_serving's rows do.
_FLIGHT = {"tele": None, "dir": None}


def _flight_dump(reason: str):
    """Dump the rung-lifecycle flight record (None when telemetry never
    armed or the dump fails — a dump must never mask the JSON row).
    The filename carries the reason, so a mid-run watchdog dump and a
    later error dump never overwrite each other's in-band paths."""
    tele = _FLIGHT.get("tele")
    if tele is None:
        return None
    try:
        d = _FLIGHT.get("dir") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "artifacts"
        )
        name = "bench_flight_" + reason.replace(":", "_") + ".json"
        return tele.flight_dump(reason, path=os.path.join(d, name))["path"]
    except Exception:  # noqa: BLE001 — best-effort by design
        return None


def _train_attainment(cfg, n_dev: int, step_ms: float, prefix: str = ""):
    """Roofline keys for one measured training rung: the static
    compute/HBM floors (utils.metrics.train_floor — the SAME wiring
    MetricLogger's logged series uses, so bench rows and training logs
    can never disagree on the floor arithmetic) and attainment =
    floor / measured, emitted next to the rung's MFU so bench rows
    read against the hardware ceiling without hand arithmetic.
    Empty when the analytic floor doesn't cover the config
    (best-effort, like the comms summary)."""
    try:
        from midgpt_tpu.utils.metrics import train_floor

        fl = train_floor(cfg, n_dev)
        if fl is None:
            return {}
        return {
            prefix + "train_compute_floor_ms": fl["train_compute_floor_ms"],
            prefix + "train_hbm_floor_ms": fl["train_hbm_floor_ms"],
            prefix + "train_attainment_frac": (
                # significant digits, not decimals
                float(f"{fl['train_floor_ms_per_step'] / step_ms:.3g}")
                if step_ms > 0 else None
            ),
        }
    except Exception:  # noqa: BLE001 — attainment is best-effort
        return {}

# steps per timing sample: the scan-mode long chain fuses _SCAN_STEPS + 1
# optimizer steps into one dispatch (train.make_train_window)
_SCAN_STEPS = 10


def _fused_len(mode: str, n_steps: int = _SCAN_STEPS) -> int:
    """Optimizer steps fused per dispatch of the program _rung_measure
    timed: the scan path's long sample compiles make_scan(n_steps + 1)
    (the trainer's steps_per_dispatch knob); chained fallback is one
    step per dispatch. Single source of truth for the JSON record —
    must mirror _measure_scan's n-vs-(n+1) construction."""
    return n_steps + 1 if mode == "scan" else 1


def _run_config(
    remat: str, batch: int, base: str = "openwebtext", n_layer=None,
    loss_chunk: int = 256, block_size=None, unroll=None,
):
    """Build state + step for one candidate config; returns a timing
    closure. Raises on compile/alloc failure (caller falls back)."""
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.config import MeshConfig, get_config
    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.parallel.sharding import make_global_array
    from midgpt_tpu.train import init_state, make_optimizer, make_train_step

    cfg = get_config(base)
    if n_layer is not None:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, n_layer=n_layer)
        )
    if block_size is not None:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, block_size=block_size)
        )
    cfg = dataclasses.replace(
        cfg,
        batch_size=batch,
        g_accum_iters=1,
        # scan_unroll = n_layer: profiling showed the rolled lax.scan costs
        # ~40% of the step in dynamic-update-slice stacking + XLA's
        # memory-pressure remat/compression copies of the carried
        # activations; fully unrolling removed 58 ms/step of 'data
        # formatting' + most loop-fusion overhead (15.2% -> ~40% MFU)
        model=dataclasses.replace(
            cfg.model, attn_impl="auto", remat=remat,
            scan_unroll=cfg.model.n_layer if unroll is None else unroll,
        ),
        mesh=MeshConfig(replica=1, fsdp=-1, sequence=1, tensor=1),
        # head+xent computed T-chunk-wise: the [B,T,V] f32 logits (3.3 GB
        # at this config) never materialize, which is what makes the
        # remat='none' rung fit in HBM; unrolled chunk loop measured
        # slightly faster than the while-loop scan (PERF.md r2 sweep)
        loss_chunk=loss_chunk,
        loss_chunk_unroll=True,
    )

    mesh = create_mesh(cfg.mesh)
    tx, _ = make_optimizer(cfg)
    state = init_state(cfg, mesh, tx, jax.random.PRNGKey(0))
    train_step = make_train_step(cfg, tx, mesh)

    t = cfg.model.block_size
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.model.vocab_size, size=(1, batch, t), dtype=np.int32)
    y = rng.integers(0, cfg.model.vocab_size, size=(1, batch, t), dtype=np.int32)
    spec = P(None, ("replica", "fsdp"), "sequence")
    xg = make_global_array(x, mesh, spec)
    yg = make_global_array(y, mesh, spec)
    key = jax.random.PRNGKey(1)

    def chain(state, n):
        # n chained steps + ONE host sync; true step time = delta
        # between chain lengths.
        start = time.perf_counter()
        loss = None
        for _ in range(n):
            state, loss = train_step(state, xg, yg, key)
        _ = float(loss)
        return time.perf_counter() - start, state

    def make_scan(n: int):
        # n steps inside ONE dispatch (see _measure_scan) — the SAME fused
        # window program the trainer ships (train.make_train_window with
        # steps_per_dispatch=n), not a parallel hand-rolled scan: what
        # bench times is the program train() launches. The window consumes
        # an [n, G, B, T] device-resident batch window; bench replicates
        # one batch n times (timing, not training).
        from midgpt_tpu.train import make_train_window

        window = make_train_window(cfg, tx, mesh, n)
        wspec = P(None, *spec)
        xs = make_global_array(
            np.ascontiguousarray(np.broadcast_to(x, (n,) + x.shape)),
            mesh, wspec,
        )
        ys = make_global_array(
            np.ascontiguousarray(np.broadcast_to(y, (n,) + y.shape)),
            mesh, wspec,
        )

        def multi(state):
            state, out = window(state, xs, ys, key)
            return state, out["loss"][-1]

        return jax.jit(multi, donate_argnums=(0,))

    return cfg, state, chain, make_scan


def _measure(cfg, state, chain, n_steps: int = _SCAN_STEPS, repeats: int = 3):
    """(tokens/sec, step_ms) from chained-steps deltas; median of
    ``repeats`` measures.

    Caveat: the per-call deltas cancel a fixed host sync cost but NOT a
    fixed per-dispatch latency — every step inherits it. _measure_scan
    below is the latency-immune variant."""
    rates = []
    for _ in range(repeats):
        t_1, state = chain(state, 1)  # RTT + 1 step
        t_n, state = chain(state, n_steps + 1)
        rates.append((t_n - t_1) / n_steps)
    step_s = sorted(rates)[len(rates) // 2]
    tokens_per_sec = cfg.batch_size * cfg.model.block_size / step_s
    return tokens_per_sec, 1e3 * step_s, state


def _measure_scan(
    cfg, state, make_scan, n_steps: int = _SCAN_STEPS, repeats: int = 3
):
    """(tokens/sec, step_ms) like _measure, but each timing sample runs
    its steps inside ONE ``lax.scan`` dispatch, so per-dispatch
    latency appears once per sample and cancels in the 1-vs-(n+1) delta
    instead of accruing per step. Raises on compile failure — the caller
    falls back to the chained path."""
    # AOT-compile both before dispatching anything: a compile failure must
    # leave ``state`` untouched so the caller can fall back to the chained
    # path (the first scan dispatch donates the state buffers)
    m_1 = make_scan(1).lower(state).compile()
    m_n = make_scan(n_steps + 1).lower(state).compile()
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        state, loss = m_1(state)
        _ = float(loss)
        t_1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, loss = m_n(state)
        _ = float(loss)
        t_n = time.perf_counter() - t0
        rates.append((t_n - t_1) / n_steps)
    step_s = sorted(rates)[len(rates) // 2]
    tokens_per_sec = cfg.batch_size * cfg.model.block_size / step_s
    return tokens_per_sec, 1e3 * step_s, state


def _rung_measure(cfg, state, chain, make_scan):
    """Measure one rung: scan-based (dispatch-latency-immune) when the
    scan program compiles, chained-deltas otherwise. Returns
    (tokens_per_sec, step_ms, state, mode).

    The chained fallback only runs while ``state`` is still live: the
    scan path AOT-compiles before dispatching, so a compile failure
    leaves the buffers intact — but a RUNTIME failure after the first
    scan dispatch has already donated them, and the fallback would die
    on deleted arrays with a misleading error (code review r5)."""
    try:
        tps, step_ms, state = _measure_scan(cfg, state, make_scan)
        return tps, step_ms, state, "scan"
    except Exception:  # noqa: BLE001 — fallback gated on liveness below
        state_alive = not any(
            getattr(a, "is_deleted", lambda: False)()
            for a in jax.tree.leaves(state)
        )
        if not state_alive:
            raise
        _, state = chain(state, 1)  # compile + 1 step
        tps, step_ms, state = _measure(cfg, state, chain)
        return tps, step_ms, state, "chained"


def _emit_bench_error(msg: str, status: str = "error") -> None:
    """The driver parses bench output mechanically — every failure mode
    must still print the one-JSON-line contract. ``status`` makes the
    failure MODE machine-readable: "watchdog" rows hit a deadline,
    "error" rows are real failures; trajectory tooling
    (analysis/ledger.py) excludes both from its references. The row
    carries the rung-lifecycle flight-dump path in-band when telemetry
    was armed — a timeline, not a bare error string."""
    row = {
        "metric": "bench_error", "value": 0, "unit": "none",
        "vs_baseline": 0, "status": status, "error": msg[:400],
    }
    dump = _flight_dump(f"bench:{status}")
    if dump:
        row["flight_recorder"] = [dump]
    print(json.dumps(row), flush=True)


def _backend_watchdog(timeout_s: float = 600.0):
    """A plain deadline on backend start-up: past it, print the error
    row and exit 3 (a hung bench run is worse for the driver than a
    failed one). Cancelled once devices are visible."""
    import os
    import sys
    import threading

    done = threading.Event()

    def watch():
        if not done.wait(timeout_s):
            if done.is_set():  # init finished right at the boundary: the
                return  # main thread owns the output line
            _emit_bench_error(
                f"backend init exceeded {timeout_s:.0f}s",
                status="watchdog",
            )
            sys.stderr.write("bench deadline: backend init; exiting\n")
            os._exit(3)

    threading.Thread(target=watch, daemon=True).start()
    return done


def _progress_watchdog(record: dict, done, deadline_s: float = 900.0):
    """A plain deadline on the whole run. Past it: if a headline was
    measured, print the record marked ``partial`` (the numbers it holds
    are real) and exit 5 — a run that did not finish is not a passing
    run; else print the error row and exit 4."""
    import os
    import sys
    import threading

    def watch():
        if done.wait(deadline_s) or done.is_set():
            return  # normal completion owns the output line
        if "value" in record:
            record["partial"] = True
            record["status"] = "watchdog"
            dump = _flight_dump("bench:watchdog")
            if dump:
                record["flight_recorder"] = [dump]
            print(json.dumps(record), flush=True)
            sys.stderr.write(
                f"bench deadline: run exceeded {deadline_s:.0f}s; "
                "emitted partial record\n"
            )
            os._exit(5)
        _emit_bench_error(
            f"no rung completed within {deadline_s:.0f}s",
            status="watchdog",
        )
        os._exit(4)

    threading.Thread(target=watch, daemon=True).start()


def main() -> None:
    from midgpt_tpu.utils.metrics import flops_per_token, mfu

    t_start = time.perf_counter()

    # rung-lifecycle flight recorder (midgpt_tpu.train_telemetry): armed
    # BEFORE backend init, so even an init wedge dumps a timeline next
    # to its watchdog row — jax-free construction, nothing touches the
    # backend until the rungs run
    from midgpt_tpu.train_telemetry import TrainTelemetry

    tele = TrainTelemetry()
    _FLIGHT["tele"] = tele
    _rung = {"i": 0}

    def _rev(kind: str, **data) -> None:
        tele.emit(kind, step=_rung["i"], t=time.perf_counter(), **data)

    _init_done = _backend_watchdog()

    # persistent executable cache: repeat runs (and the fallback ladder)
    # skip recompiles
    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    try:
        n_dev = jax.device_count()
    except Exception as e:  # no backend: fail fast WITH the JSON contract
        _init_done.set()
        _emit_bench_error(f"backend init failed: {e}")
        raise SystemExit(3)
    _init_done.set()  # devices visible — cancel the init watchdog

    import threading as _threading

    _all_done = _threading.Event()

    # --- headline: flagship-family (openwebtext_xl per-layer shape) ------
    # ladder fastest-measured first (PERF.md r3 with the combined-backward
    # kernels: L6 B=20 68.8%, L8 B=12 68.5%, L6 B=16 66.8%; B=22/24 regress
    # — HBM compression returns); fall back if the compiler rejects a rung
    record = {}
    _progress_watchdog(record, _all_done)
    last_err = None
    # ladder note (r5): the old best rung L6 B=20 is OUT — its compile
    # crashed 3/3 times on 2026-07-31; L8 B=12 compiled reliably
    for xl_layers, xl_batch in (
        (8, 12 * n_dev), (6, 16 * n_dev), (8, 8 * n_dev),
    ):
        try:
            _rung["i"] += 1
            _rev("rung_start", rung=f"xl_L{xl_layers}_B{xl_batch}")
            xcfg, xstate, xchain, xmk = _run_config(
                "none", xl_batch, base="openwebtext_xl", n_layer=xl_layers,
                loss_chunk=512,
            )
            xtps, xstep_ms, xstate, xmode = _rung_measure(
                xcfg, xstate, xchain, xmk
            )
            _rev("rung_ok", rung=f"xl_L{xl_layers}_B{xl_batch}")
            xmfu = mfu(xtps, xcfg.model, n_dev)
            # mutate IN PLACE: _progress_watchdog holds this dict
            record.clear()
            record.update({
                "metric": f"openwebtext_xl_family_L{xl_layers}_train_mfu",
                "value": round(xmfu, 4),
                "unit": "fraction_of_peak",
                "vs_baseline": round(xmfu / BASELINE_MFU, 4),
                "tokens_per_sec_per_chip": round(xtps / n_dev, 1),
                "step_ms": round(xstep_ms, 1),
                "device": jax.devices()[0].device_kind,
                "n_devices": n_dev,
                "batch_per_chip": xcfg.batch_size // n_dev,
                "model_flops_per_token": flops_per_token(xcfg.model),
                "measure": xmode,
                # fused dispatch length of the measured program (the
                # trainer's steps_per_dispatch knob; 1 = chained fallback)
                "steps_per_dispatch": _fused_len(xmode),
            })
            # roofline attainment next to the MFU headline: the static
            # compute/HBM floors + floor/measured (analysis/traffic)
            record.update(_train_attainment(xcfg, n_dev, xstep_ms))
            del xstate, xchain
            gc.collect()
            break
        except Exception as exc:  # noqa: BLE001 — any compile/OOM falls through
            # keep the message but drop the traceback: its frames pin the
            # failed rung's device arrays (params + Adam moments) in HBM,
            # which would shrink the next rung's headroom
            exc.__traceback__ = None
            _rev("rung_error", rung=f"xl_L{xl_layers}_B{xl_batch}")
            last_err = exc
            xcfg = xstate = xchain = None
            gc.collect()
    else:
        # every XL rung failed (e.g. a smaller-HBM chip): fall through so
        # the 124M rung below becomes the headline — the contract is ONE
        # JSON line no matter what ran
        record["xl_error"] = repr(last_err)[:120]

    # --- auxiliary rung: 124M (GPT-2-small shape) ------------------------
    for remat, batch in (
        ("none", 24 * n_dev),
        ("none", 16 * n_dev),
        ("full", 16 * n_dev),
    ):
        try:
            _rung["i"] += 1
            _rev("rung_start", rung=f"gpt2s_{remat}_B{batch}")
            cfg, state, chain, mk = _run_config(remat, batch)
            tps, step_ms, state, _mode = _rung_measure(cfg, state, chain, mk)
            _rev("rung_ok", rung=f"gpt2s_{remat}_B{batch}")
            small_mfu = mfu(tps, cfg.model, n_dev)
            record.update(
                {
                    "gpt2s_metric": "openwebtext_124m_train_mfu",
                    "gpt2s_mfu": round(small_mfu, 4),
                    "gpt2s_vs_baseline": round(small_mfu / BASELINE_MFU, 4),
                    "gpt2s_tokens_per_sec_per_chip": round(tps / n_dev, 1),
                    "gpt2s_step_ms": round(step_ms, 1),
                    "gpt2s_remat": cfg.model.remat,
                    **_train_attainment(cfg, n_dev, step_ms, "gpt2s_"),
                }
            )
            if "value" not in record:  # XL never ran: promote to headline
                record.update(
                    {
                        "metric": "openwebtext_124m_train_mfu",
                        "value": round(small_mfu, 4),
                        "unit": "fraction_of_peak",
                        "vs_baseline": round(small_mfu / BASELINE_MFU, 4),
                        "tokens_per_sec_per_chip": round(tps / n_dev, 1),
                        "step_ms": round(step_ms, 1),
                        "device": jax.devices()[0].device_kind,
                        "n_devices": n_dev,
                        "model_flops_per_token": flops_per_token(cfg.model),
                    }
                )
            record.pop("gpt2s_error", None)  # a later rung succeeded
            del state, chain
            gc.collect()
            break
        except Exception as exc:  # noqa: BLE001 — aux rung is best-effort
            exc.__traceback__ = None
            _rev("rung_error", rung=f"gpt2s_{remat}_B{batch}")
            record["gpt2s_error"] = repr(exc)[:120]
            cfg = state = chain = None
            gc.collect()

    # --- auxiliary rung: llama family (GQA + SwiGLU, C=128, T=2048) ------
    # depth-scaled like the XL headline: the 7B per-layer compute shape
    # (D=4096, H=32/Hkv=8, SwiGLU) at the depth that fits one chip with
    # f32 params + Adam state (~770M params at L=2 incl. the 50304 embed)
    for ll_layers, ll_batch in ((2, 8 * n_dev), (2, 4 * n_dev)):
        try:
            lcfg, lstate, lchain, lmk = _run_config(
                "none", ll_batch, base="llama_7b", n_layer=ll_layers,
                loss_chunk=512,
            )
            ltps, lstep_ms, lstate, _lmode = _rung_measure(
                lcfg, lstate, lchain, lmk
            )
            lmfu = mfu(ltps, lcfg.model, n_dev)
            record.update(
                {
                    "llama_metric": f"llama_7b_family_L{ll_layers}_train_mfu",
                    "llama_mfu": round(lmfu, 4),
                    "llama_vs_baseline": round(lmfu / BASELINE_MFU, 4),
                    "llama_tokens_per_sec_per_chip": round(ltps / n_dev, 1),
                    "llama_step_ms": round(lstep_ms, 1),
                    "llama_batch_per_chip": lcfg.batch_size // n_dev,
                }
            )
            record.pop("llama_error", None)
            del lstate, lchain
            gc.collect()
            break
        except Exception as exc:  # noqa: BLE001 — aux rung is best-effort
            exc.__traceback__ = None
            record["llama_error"] = repr(exc)[:120]
            lcfg = lstate = lchain = None
            gc.collect()

    # --- auxiliary rung: long context (T=4096/8192, 124M family) ---------
    # flash + chunked loss at T >> the kernels' 1024 block cap: exercises
    # the multi-block backward path and the O(T) activation story that
    # ring attention + chunked xent exist for (VERDICT r4 Next #5). The
    # 8192 attempt is budget-gated.
    for lc_t, lc_batch, lc_remat in (
        (4096, 4 * n_dev, "none"),
        (4096, 2 * n_dev, "none"),
        (4096, 4 * n_dev, "full"),
    ):
        if time.perf_counter() - t_start > 420:
            record.setdefault("long_ctx_error", "skipped: bench budget")
            break
        try:
            ccfg, cstate, cchain, cmk = _run_config(
                lc_remat, lc_batch, base="openwebtext",
                block_size=lc_t, loss_chunk=512,
            )
            ctps, cstep_ms, cstate, _cmode = _rung_measure(
                ccfg, cstate, cchain, cmk
            )
            cmfu = mfu(ctps, ccfg.model, n_dev)
            record.update(
                {
                    "long_ctx_metric": f"openwebtext_124m_T{lc_t}_train_mfu",
                    "long_ctx_mfu": round(cmfu, 4),
                    "long_ctx_t": lc_t,
                    "long_ctx_tokens_per_sec_per_chip": round(ctps / n_dev, 1),
                    "long_ctx_step_ms": round(cstep_ms, 1),
                    "long_ctx_remat": lc_remat,
                    "long_ctx_batch_per_chip": ccfg.batch_size // n_dev,
                }
            )
            record.pop("long_ctx_error", None)
            del cstate, cchain
            gc.collect()
            break
        except Exception as exc:  # noqa: BLE001 — aux rung is best-effort
            exc.__traceback__ = None
            record["long_ctx_error"] = repr(exc)[:120]
            ccfg = cstate = cchain = None
            gc.collect()

    if time.perf_counter() - t_start < 480 and "long_ctx_mfu" in record:
        try:
            ccfg, cstate, cchain, cmk = _run_config(
                "none", 1 * n_dev, base="openwebtext",
                block_size=8192, loss_chunk=512,
            )
            ctps, cstep_ms, cstate, _cmode = _rung_measure(
                ccfg, cstate, cchain, cmk
            )
            record.update(
                {
                    "long_ctx8k_mfu": round(mfu(ctps, ccfg.model, n_dev), 4),
                    "long_ctx8k_tokens_per_sec_per_chip": round(
                        ctps / n_dev, 1
                    ),
                    "long_ctx8k_step_ms": round(cstep_ms, 1),
                }
            )
            del cstate, cchain
            gc.collect()
        except Exception as exc:  # noqa: BLE001
            exc.__traceback__ = None
            record["long_ctx8k_error"] = repr(exc)[:120]
            ccfg = cstate = cchain = None
            gc.collect()


    # --- comms audit: static per-step wire traffic of the headline -------
    # config (midgpt_tpu.analysis). Recompiling the measured program is an
    # executable-cache hit right after its rung ran; the scalar split
    # (ICI / DCN bytes per axis, collective count) rides the BENCH_*.json
    # record so the trajectory tracks comms alongside MFU. window_steps
    # makes the audit compile the SAME fused K-step window the headline
    # rung dispatched (scan mode fuses _SCAN_STEPS+1 steps), not a K=1
    # program the trainer never launched.
    audit_cfg = xcfg if xcfg is not None else cfg
    if audit_cfg is not None and time.perf_counter() - t_start < 540:
        try:
            from midgpt_tpu.analysis.harness import train_step_comms_summary

            record.update(train_step_comms_summary(
                audit_cfg,
                window_steps=record.get("steps_per_dispatch", 1),
            ))
        except Exception as exc:  # noqa: BLE001 — audit rung is best-effort
            exc.__traceback__ = None
            record["comms_error"] = repr(exc)[:120]
            gc.collect()

    _all_done.set()  # cancel the mid-run watchdog: main owns the output
    if "value" not in record:
        raise RuntimeError(f"no bench config ran: {record}")
    record.setdefault("status", "ok")
    print(json.dumps(record))


if __name__ == "__main__":
    main()
