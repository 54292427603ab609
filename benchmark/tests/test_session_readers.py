"""The three readers of what the program keeps of a profiler session
(``midgpt_tpu.telemetry.session_logs()``, ``compile_log()``): on logs built
by hand, where every number can be said beforehand; where there is nothing
to read; and on a log captured on the CPU round a tiny engine under a
profiler session, with a trace built by hand in ``ctx``."""

import numpy as np
import pytest

from benchmark import trace as tr
from benchmark.readers import (compiles_in_session, session_request,
                               session_steps)
from midgpt_tpu import telemetry
from midgpt_tpu.serving import EngineTelemetry

DEV = ("/device:TPU:0", "XLA Ops")
PARTS = ("queue_delay_s", "prefill_s", "first_window_s")


def _ctx(traced=True):
    lines = {DEV: [("%fusion.1", 0.0, 1.0)]} if traced else None
    return {"trace": tr.TraceData(lines) if lines else None, "spans": [],
            "counters": {}, "device_kind": "TPU v5 lite", "sizes": {}}


@pytest.fixture
def kept():
    """What the program keeps, emptied before and after."""
    logs, ring = telemetry.session_logs(), telemetry.compile_log()
    logs.clear()
    ring.clear()
    yield logs, ring
    logs.clear()
    ring.clear()


def _built():
    """An engine's own log: steps 8 and 9 before the session, 10-13 in it,
    14 after. Request 1 was in a slot when the session opened (its beginning
    back-filled), 2 lived inside it, 3 got its first token after it closed,
    4 had its first token before it opened, 5 was still queued at the end."""
    log = EngineTelemetry()

    def census(step, *counts):
        log.emit("step", step=step, t=float(step), **dict(zip(
            ("decoding", "prefilling", "empty", "queued", "parked"),
            counts)))

    census(9, 4, 0, 0, 9, 9)
    log.open_session(10)
    old = dict(step=10, backfill=True)
    log.emit("submit", rid=1, t=0.0, **old)
    log.emit("queued", rid=1, t=0.0, **old)
    log.emit("admitted", rid=1, t=1.0, slot=0, **old)
    log.emit("submit", rid=4, t=-9.0, **old)
    log.emit("admitted", rid=4, t=-8.0, slot=1, **old)
    log.emit("prefill_chunk", rid=4, t=-7.0, slot=1, **old)
    log.emit("prefill_chunk", rid=1, step=10, t=3.0, slot=0, chunk=64)
    census(10, 2, 1, 1, 3, 0)
    log.emit("tokens", rid=4, step=10, t=3.5, n=4, total=12, slot=1)
    log.emit("submit", rid=2, step=10, t=10.0)
    log.emit("queued", rid=2, step=10, t=10.0)
    log.emit("submit", rid=5, step=10, t=10.0)
    log.emit("admitted", rid=2, step=11, t=10.5, slot=2)
    log.emit("prefill_chunk", rid=2, step=11, t=11.0, slot=2, chunk=64)
    census(11, 3, 1, 0, 2, 1)
    log.emit("tokens", rid=1, step=11, t=4.0, n=2, total=2, slot=0)
    log.emit("prefill_chunk", rid=2, step=12, t=12.5, slot=2, chunk=32)
    log.emit("submit", rid=3, step=12, t=12.0)
    log.emit("admitted", rid=3, step=12, t=12.0, slot=3)
    log.emit("prefill_chunk", rid=3, step=12, t=12.6, slot=3, chunk=8)
    census(12, 4, 0, 0, 0, 0)
    log.emit("tokens", rid=2, step=12, t=13.0, n=0, total=0, slot=2)
    census(13, 1, 1, 2, 1, 0)
    log.emit("tokens", rid=2, step=13, t=14.0, n=4, total=4, slot=2)
    log.close_session(14)
    census(14, 0, 0, 4, 9, 9)
    log.emit("tokens", rid=3, step=14, t=15.0, n=4, total=4, slot=3)
    return log


def test_parts_of_the_time_to_first_token_on_a_built_log(kept):
    _built()

    def part(name):
        return session_request.read(_ctx(), part=name, scale=1e3)

    # request 1: 1 + 2 + 1 = 4 s; request 2: 0.5 + 2 + 1.5 = 4 s; 3, 4 and
    # 5 have no first token in the session
    assert [part(p) for p in PARTS] == [750.0, 2000.0, 1250.0]
    assert part("ttft_s") == 4000.0 == sum(part(p) for p in PARTS)
    assert session_request.read(_ctx(), part="prefill_s") == 2.0
    rows, early = session_request.first_tokens(telemetry.session_logs())
    assert sorted(m["rid"] for m in rows) == [1, 2] and early == 1


def test_slot_census_on_a_built_log(kept):
    _built()

    def share(state):
        return session_steps.read(_ctx(), states=[state], of="slots")

    # steps 10-13 hold 2+3+4+1, 1+1+0+1 and 1+0+0+2 of their 16 slots
    shares = [share(s) for s in ("decoding", "prefilling", "empty")]
    assert shares == [62.5, 18.75, 18.75] and sum(shares) == 100.0
    assert session_steps.read(
        _ctx(), states=["queued", "parked"]) == (3 + 3 + 0 + 1) / 4
    assert session_steps.read(_ctx(), states=["decoding"]) == 2.5


def test_two_engines_of_one_session_are_read_together(kept):
    other = EngineTelemetry()
    other.open_session(3)  # the session is open when the second joins it
    _built()
    other.emit("step", step=3, t=0.0, decoding=0, prefilling=0, empty=4,
               queued=0, parked=0)
    other.emit("submit", rid=1, step=3, t=20.0)
    other.emit("admitted", rid=1, step=3, t=20.0, slot=0)
    other.emit("tokens", rid=1, step=4, t=21.0, n=1, total=1, slot=0)
    assert len(kept[0]) == 2
    assert session_steps.read(
        _ctx(), states=["decoding"], of="slots") == 50.0
    # (1 + 0.5 + 0) / 3, (2 + 2 + 0) / 3, (1 + 1.5 + 1) / 3
    assert [session_request.read(_ctx(), part=p) for p in PARTS] == (
        pytest.approx([0.5, 4 / 3, 3.5 / 3]))


def test_programs_built_in_the_session_on_a_built_ring(kept):
    _, ring = kept
    assert compiles_in_session.read(_ctx()) == 0.0
    ring.extend([("jit(warm)", 2.0, False), ("jit(chunk_fn)", 0.5, True),
                 ("jit(after)", 0.1, False)])
    assert compiles_in_session.read(_ctx()) == 1.0


def test_nothing_to_read_is_none(kept, monkeypatch):
    def all_three(ctx):
        return (session_request.read(ctx, part="prefill_s"),
                session_steps.read(ctx, states=["empty"], of="slots"),
                compiles_in_session.read(ctx))

    # no session ran: no log recorded one; the ring is read all the same
    assert all_three(_ctx()) == (None, None, 0.0)
    log = _built()
    # a rehearsal's session on the CPU is no traced window of the device
    assert all_three(_ctx(traced=False)) == (None, None, None)
    # an empty population: a session in which no first token fell and no
    # step ran
    log.request_log.clear()
    log.events.clear()
    assert all_three(_ctx()) == (None, None, 0.0)
    # a program that keeps no such thing: the parent of the PR that added
    # these readers, under these files
    _built()
    monkeypatch.delattr(telemetry, "session_logs")
    monkeypatch.delattr(telemetry, "compile_log")
    assert all_three(_ctx()) == (None, None, None)


def test_session_of_a_tiny_engine_captured_on_the_cpu(tmp_path, kept):
    import jax
    import jax.numpy as jnp

    from benchmark import program, weights
    from benchmark.tests import tiny
    from midgpt_tpu.serving import ServingEngine

    mcfg = program.model_config(tiny.TINY_SIZES, tiny.TINY_SERVE["program"])
    model = program.fill_model(
        weights.make(weights.key_of(3), tiny.TINY_SIZES, jnp.float32), mcfg)
    eng = ServingEngine(model, **tiny.TINY_SERVE["engine"])
    rng = np.random.default_rng(0)

    def submit(n):
        return [eng.submit(rng.integers(0, 512, size=40), 8)
                for _ in range(n)]

    submit(1)
    eng.run()  # chunk and window compiled
    early = submit(3)
    eng.step()  # two requests in a slot, one queued: before the capture
    fresh = jax.jit(lambda x: x * 2.0)
    assert eng.telemetry is None and not kept[0]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    steps = 0
    while eng.has_work and steps < 60:
        eng.step()
        steps += 1
        if steps == 2:
            late = submit(2)
            fresh(np.ones((steps,), np.float32))  # a shape first seen here
    jax.profiler.stop_trace()
    (log,) = kept[0]
    del eng  # as ``cell.free()`` drops it before the readers run
    ctx = _ctx()
    parts = [session_request.read(ctx, part=p, scale=1e3) for p in PARTS]
    whole = session_request.read(ctx, part="ttft_s", scale=1e3)
    assert all(p is not None and p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(whole, abs=1e-9)
    rows, n_early = session_request.first_tokens([log])
    assert sorted(m["rid"] for m in rows) == early + late and n_early == 3
    shares = [session_steps.read(ctx, states=[s], of="slots")
              for s in ("decoding", "prefilling", "empty")]
    assert sum(shares) == pytest.approx(100.0, abs=1e-9)
    assert shares[0] > 0 and shares[1] > 0
    census = [e for e in log.events if e.kind == "step"]
    assert len(census) == steps and all(log.in_session(e) for e in census)
    assert session_steps.read(ctx, states=["queued", "parked"]) > 0
    assert compiles_in_session.read(ctx) == 1.0
    assert [n for n, _, s in kept[1] if s] == ["jit(<lambda>)"]
    assert compiles_in_session.read(_ctx(traced=False)) is None
