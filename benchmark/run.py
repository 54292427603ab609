"""One run of one cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output. Fails (exit 3,
no result) where JAX finds no TPU or fewer chips than the cell asks for.
``--rehearsal`` is for this directory's own tests: it runs on whatever
device there is, and its line says which and carries no device metric."""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python gives it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import typing as tp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spec as specs  # noqa: E402
from benchmark import trace as tr  # noqa: E402

def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: run without a TPU; no device metric")
    ap.add_argument("--stand-in", default=None,
                    help="put a control or a planted fault in the program's "
                         "place for the comparison; correct should be false")
    ap.add_argument("--out", default=None,
                    help="debugging: directory for a summary of the trace "
                         "and a gzipped copy of it")
    return ap.parse_args(argv)


def devices_for(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    if rehearsal:
        return devs[:chips]
    if devs[0].platform != "tpu":
        say(f"no TPU: JAX found platform {devs[0].platform!r}")
        raise SystemExit(3)
    if len(devs) < chips:
        say(f"the cell asks for {chips} chips, JAX found {len(devs)}")
        raise SystemExit(3)
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` at the
    root of this checkout. Every program is written, however fast it
    compiled, so that a second run compiles nothing."""
    import jax

    from midgpt_tpu.utils.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks)) if peaks else 0


def per_layer(cell_spec, ctx, rehearsal=False):
    """Each per-layer metric by its own reader; one that finds nothing to
    read is left out. A rehearsal keeps counts and host spans only: nothing
    that needs a device or its peak."""
    out = {}
    for m in cell_spec["per_layer"]:
        if rehearsal and m["source"] not in ("program_span", "program_counter"):
            continue
        args = {k: _resolve(v, cell_spec, ctx)
                for k, v in m.get("args", {}).items()}
        val = specs.reader(m["reader"])(ctx, **args)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def _resolve(v, cell_spec, ctx):
    """``"counters.x"``, ``"sizes.x"`` and ``"traffic_params.x"`` in a metric
    file's arguments name a number of the run."""
    if isinstance(v, dict):
        return {k: _resolve(x, cell_spec, ctx) for k, x in v.items()}
    if isinstance(v, str) and "." in v:
        head, _, key = v.partition(".")
        src = {"counters": ctx["counters"], "sizes": cell_spec["sizes"],
               "traffic_params": cell_spec.get("traffic_params", {})}
        if head in src and key in src[head]:
            return src[head][key]
    return v


def main(argv=None) -> int:
    args = parse(argv)
    # what the program prints goes to standard error: standard output
    # carries the result and nothing else
    with contextlib.redirect_stdout(sys.stderr):
        line = measure(args)
    print(json.dumps(line), flush=True)
    return 0


def measure(args) -> tp.Dict[str, tp.Any]:
    cell_spec = specs.cell(args.workload)
    devs = devices_for(int(cell_spec["chips"]), args.rehearsal)
    cache = enable_cache()
    say(f"devices: {[str(d) for d in devs]} after "
        f"{time.perf_counter() - T0:.1f} s; compile cache: {cache}")
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        return _measure(args, cell_spec, devs, tmp)


def _measure(args, cell_spec, devs, tmp) -> tp.Dict[str, tp.Any]:
    about = cell_spec.get("trace", {})
    tracer = tr.Tracer(
        bool(args.trace), tmp,
        start_at=float(about.get("start_share", 0.3)) * args.seconds,
        duration=min(float(about.get("seconds", 3.0)), 0.5 * args.seconds),
    )
    kind = specs.kind(cell_spec["kind"])
    cell = kind.build(cell_spec, args.seed, devs, tracer.annotate,
                      args.stand_in)
    say(f"cell built after {time.perf_counter() - T0:.1f} s")
    cell.warm()
    say(f"warm after {time.perf_counter() - T0:.1f} s "
        f"{getattr(cell, 'setup_parts', '')}; measuring for {args.seconds} s")

    res = cell.run_window(args.seconds, tracer)
    setup_s = res["t_start"] - T0  # process start to the window's start
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    cell.free()

    extra: tp.Dict[str, tp.Any] = {}
    if args.trace:
        metrics = traced(args, cell_spec, cell, tracer, device, extra,
                         kind.ANNOTATIONS)
    else:
        units = {m["name"]: m["unit"] for m in cell_spec["end_to_end"]}
        vals = {"setup_s": setup_s}
        if not args.rehearsal:  # a CPU's rate is no device metric
            vals.update(res["end_to_end"])
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in vals.items() if k in units}

    t_chk = time.perf_counter()
    numbers = cell.check(args.stand_in)
    say(f"reference and comparison took {time.perf_counter() - t_chk:.1f} s")
    for n, v, lim in numbers:
        if lim is None:
            say(f"not compared {n}: {v:.6g} (no limit in the cell's file)")
    compared = [(n, v, float(lim)) for n, v, lim in numbers if lim is not None]
    for n, v, lim in compared:
        say(f"compared {n}: {v:.6g} (limit {lim:.6g})"
            f"{'' if v <= lim else '  <-- over'}")
    correct = bool(compared) and res["failed"] == 0 and all(
        v <= lim for _, v, lim in compared)  # a NaN is over any limit
    return {
        "correct": correct, "attempted": int(res["attempted"]),
        "failed": int(res["failed"]), "metrics": metrics, "device": device,
        **extra,
        "compared": {n: {"value": v, "limit": lim} for n, v, lim in compared},
    }


def traced(args, cell_spec, cell, tracer, device, extra, labels):
    """The per-layer metrics of a ``--trace 1`` run; fills ``busy_s`` and
    ``window_s`` into ``device`` and the breakdown into ``extra``, its idle
    gaps named by the kind's own annotations (``labels``)."""
    t_read = time.perf_counter()
    # a CPU's trace is no device trace
    trace = None if args.rehearsal else tracer.load()
    ctx = {"trace": trace, "spans": cell.spans, "counters": cell.counters,
           "device_kind": device["kind"], "sizes": cell_spec["sizes"]}
    metrics = per_layer(cell_spec, ctx, args.rehearsal)
    if trace is not None:
        got = tr.busy_and_window(trace)
        if got is not None:
            device["busy_s"], device["window_s"] = got
        extra["breakdown"] = tr.breakdown(trace, labels)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(
                    args.out, f"trace_{args.workload}.json"), "w") as f:
                json.dump(tr.summary(trace), f, indent=1)
            tracer.keep_copy(os.path.join(
                args.out, f"{args.workload}.xplane.pb.gz"))
    say(f"trace read and reduced in {time.perf_counter() - t_read:.1f} s; "
        f"starting and stopping the profiler blocked the host for "
        f"{' + '.join(f'{e - s:.2f}' for s, e in tracer.stalls)} s")
    return metrics


if __name__ == "__main__":
    sys.exit(main())
