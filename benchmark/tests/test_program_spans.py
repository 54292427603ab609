"""The two readers of the program's own spans (``midgpt_tpu.telemetry.span``
writes them into the profiler's trace): on a trace built by hand, where
every number can be said beforehand, and on one captured on the CPU round a
tiny engine."""

import glob
import os

import pytest

from benchmark import trace as tr
from benchmark.readers import (host_span_ms, trace_idle_by_span,
                               trace_idle_share)

DEV = ("/device:TPU:0", "XLA Ops")
MAIN = ("/host:CPU", "python3")
WITHIN = r"^midgpt\.engine\."


def _ctx(lines):
    return {"trace": tr.TraceData(lines) if lines is not None else None,
            "spans": [], "counters": {}, "device_kind": "TPU v5 lite",
            "sizes": {}}


def ev(name, start, end):
    return (name, float(start), float(end - start))


@pytest.fixture
def built():
    """Device operations over 0 .. 100 with gaps at 10-14, 30-36, 50-52,
    70-71 and 90-95 (18 of 100 idle). Two engine steps inside the traced
    interval, one that the interval's start cuts, and a loader span of a
    worker thread on the same line, since the trace cannot tell threads
    apart."""
    device = [ev("%fusion.1", 0, 10), ev("%fusion.2", 14, 30),
              ev("%fusion.3", 36, 50), ev("%fusion.4", 52, 70),
              ev("%fusion.5", 71, 90), ev("%fusion.6", 95, 100)]
    host = [
        ev("midgpt.engine.step", -5, 8),          # cut by the edge
        ev("midgpt.engine.harvest_apply", 6, 8),  # whole, its step is not
        ev("midgpt.engine.step", 9, 40),
        ev("midgpt.engine.schedule", 10, 12),
        ev("midgpt.engine.prefill_dispatch", 12, 13),
        ev("midgpt.engine.decode_dispatch", 29, 33),
        ev("midgpt.engine.harvest_wait", 33, 38),
        ev("midgpt.engine.harvest_apply", 38, 39),
        ev("midgpt.engine.step", 49, 72),
        ev("midgpt.engine.schedule", 49, 51),
        ev("midgpt.engine.decode_dispatch", 51, 60),
        ev("midgpt.engine.harvest_wait", 60, 71),
        ev("midgpt.loader.produce", 88, 93),      # a worker thread
        ev("midgpt.loader.gather", 88, 90),
        ev("engine.step", 0, 100),                # the harness's own
    ]
    return _ctx({DEV: device, MAIN: host})


def test_host_span_self_time_per_step(built):
    # the steps 9-40 and 49-72 count. Self time of the steps: 31 - (2 + 1
    # + 4 + 5 + 1) = 18 and 23 - (2 + 9 + 11) = 1; schedule 2 + 2;
    # harvest_apply 1 (the one at 6-8 belongs to the step that was cut)
    sched = host_span_ms.read(
        built, spans=r"^midgpt\.engine\.(step|schedule|grow|harvest_apply)$",
        per=r"^midgpt\.engine\.step$")
    assert sched == pytest.approx(1e3 * (18 + 1 + 4 + 1) / 2)
    disp = host_span_ms.read(
        built, spans=r"^midgpt\.engine\.(prefill|decode)_dispatch$",
        per=r"^midgpt\.engine\.step$")
    assert disp == pytest.approx(1e3 * (1 + 4 + 9) / 2)
    wait = host_span_ms.read(
        built, spans=r"^midgpt\.engine\.harvest_wait$",
        per=r"^midgpt\.engine\.step$")
    step = host_span_ms.read(
        built, spans=r"^midgpt\.engine\.step$",
        per=r"^midgpt\.engine\.step$", self_time=False)
    # the parts never exceed the whole: here they are all of it
    assert sched + disp + wait == pytest.approx(step)


def test_host_span_whole_or_self_per_event(built):
    whole = host_span_ms.read(built, spans=r"^midgpt\.loader\.produce$",
                              self_time=False)
    assert whole == pytest.approx(5e3)
    own = host_span_ms.read(built, spans=r"^midgpt\.loader\.produce$")
    assert own == pytest.approx(3e3)  # less the gather inside it
    assert host_span_ms.read(built, spans=r"^midgpt\.loader\.wait$") is None


def test_idle_pieces_go_to_the_innermost_span(built):
    def share(spans):
        return trace_idle_by_span.read(built, within=WITHIN, spans=spans)

    # gap 10-14: schedule 2, prefill_dispatch 1, the step's own glue 1;
    # gap 30-36: decode_dispatch 3, harvest_wait 3; gap 50-52: schedule 1,
    # decode_dispatch 1; gap 70-71: harvest_wait 1; gap 90-95: no engine
    # span is open (the loader's does not match ``within``)
    sched = share(r"^midgpt\.engine\.(step|schedule|grow|harvest_apply|submit)$")
    disp = share(r"^midgpt\.engine\.(prefill|decode)_dispatch$")
    wait = share(r"^midgpt\.engine\.harvest_wait$")
    caller = share(None)
    assert (sched, disp, wait, caller) == pytest.approx((4.0, 5.0, 4.0, 5.0))
    assert sched + disp + wait + caller == pytest.approx(
        trace_idle_share.read(built))
    # with the loader's spans let in, the worker's span takes its overlap
    # (90-93) of the gap the main thread had left uncovered
    assert trace_idle_by_span.read(
        built, within=r"^midgpt\.", spans=r"^midgpt\.loader\.produce$"
    ) == pytest.approx(3.0)


def test_a_span_cut_by_the_edge_still_covers_its_gaps():
    ctx = _ctx({DEV: [ev("%a", 0, 4), ev("%b", 6, 10)],
                MAIN: [ev("midgpt.engine.step", 5, 20)]})
    got = trace_idle_by_span.read(
        ctx, within=WITHIN, spans=r"^midgpt\.engine\.step$")
    assert got == pytest.approx(10.0)  # 5-6 of the gap 4-6, over 10
    assert trace_idle_by_span.read(
        ctx, within=WITHIN, spans=None) == pytest.approx(10.0)
    # for a duration it does not count
    assert host_span_ms.read(ctx, spans=r"^midgpt\.engine\.step$") is None


def test_nothing_to_read_is_none():
    for ctx in (_ctx(None), _ctx({MAIN: [ev("midgpt.engine.step", 0, 1)]})):
        assert host_span_ms.read(ctx, spans="step") is None
        assert trace_idle_by_span.read(ctx, within=WITHIN, spans=None) is None
    # a program that writes no such span, as the parent of the PR that
    # added these readers: its metric is left out, not read as zero
    bare = _ctx({DEV: [ev("%a", 0, 4), ev("%b", 6, 10)],
                 MAIN: [ev("engine.step", 0, 10)]})
    assert trace_idle_by_span.read(bare, within=WITHIN, spans=None) is None
    assert trace_idle_by_span.read(bare, within=WITHIN, spans="step") is None
    assert host_span_ms.read(bare, spans="step") is None


def test_spans_of_a_tiny_engine_captured_on_the_cpu(tmp_path):
    """``TraceData.from_file`` finds the engine's spans on the host plane of
    an xplane the CPU wrote; the CPU has no device plane, so a stand-in
    operation spans the capture."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import program, weights
    from benchmark.tests import tiny
    from midgpt_tpu.serving import ServingEngine

    mcfg = program.model_config(tiny.TINY_SIZES, tiny.TINY_SERVE["program"])
    model = program.fill_model(
        weights.make(weights.key_of(3), tiny.TINY_SIZES, jnp.float32), mcfg)
    eng = ServingEngine(model, **tiny.TINY_SERVE["engine"])
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(0, 512, size=20), 8)
    eng.step()  # compile outside the capture
    before = eng.stats()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    steps = 0
    while eng.has_work and steps < 40:
        eng.step()
        steps += 1
    jax.profiler.stop_trace()
    after = eng.stats()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    trace = tr.TraceData.from_file(path)
    host = [e for evs in trace.select(tr.HOST_PLANE, ".").values()
            for e in evs if e[0].startswith("midgpt.")]
    names = [n for n, _, _ in host]
    assert names.count("midgpt.engine.step") == steps
    assert names.count("midgpt.engine.decode_dispatch") == (
        after["decode_dispatches"] - before["decode_dispatches"])
    assert names.count("midgpt.engine.prefill_dispatch") == (
        after["prefill_dispatches"] - before["prefill_dispatches"])
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    trace.lines[DEV] = [("%stand-in", lo, hi - lo)]
    ctx = {"trace": trace, "spans": [], "counters": {}, "sizes": {},
           "device_kind": "cpu"}
    per = r"^midgpt\.engine\.step$"
    parts = [host_span_ms.read(ctx, spans=s, per=per) for s in (
        r"^midgpt\.engine\.(step|schedule|grow|harvest_apply)$",
        r"^midgpt\.engine\.(prefill|decode)_dispatch$",
        r"^midgpt\.engine\.harvest_wait$")]
    whole = host_span_ms.read(ctx, spans=per, per=per, self_time=False)
    assert all(p is not None and p > 0 for p in parts)
    assert sum(parts) == pytest.approx(whole, rel=1e-6)
