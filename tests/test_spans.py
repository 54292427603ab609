"""The program's spans (``midgpt_tpu.telemetry.span``): one call puts a
phase into the profiler's trace, on the device events' clock, and — for an
owner that traces — onto its dispatch ring on the owner's clock.

The table of names below is ISSUE 25's; the benchmark's readers
(``benchmark/readers/host_span_ms.py``, ``trace_idle_by_span.py``) match
these names, and the harness's own annotations must never collide with
them (``trace.breakdown`` labels idle gaps by the harness's)."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ExperimentConfig, MeshConfig, ModelConfig
from midgpt_tpu.data import Loader, PrefetchLoader, Shard, write_tokens
from midgpt_tpu.models.gpt import GPT
from midgpt_tpu.serving import EngineTelemetry, ServingEngine
from midgpt_tpu import telemetry as shared
from midgpt_tpu.telemetry import TelemetryLog, span
from midgpt_tpu.train import train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINE_SPANS = (
    "midgpt.engine.step", "midgpt.engine.submit", "midgpt.engine.schedule",
    "midgpt.engine.prefill_dispatch", "midgpt.engine.grow",
    "midgpt.engine.decode_dispatch", "midgpt.engine.harvest_wait",
    "midgpt.engine.harvest_apply",
)
LOADER_SPANS = (
    "midgpt.loader.produce", "midgpt.loader.gather",
    "midgpt.loader.transfer", "midgpt.loader.wait",
)
TRAIN_SPANS = (
    "midgpt.train.launch", "midgpt.train.harvest", "midgpt.train.eval",
    "midgpt.train.ckpt_save", "midgpt.train.ckpt_wait",
)

CFG = ModelConfig(
    block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    dropout=0.0, attn_impl="naive", remat="none",
)


class Capture:
    """A profiler session on the CPU; afterwards ``host`` holds the
    ``midgpt.`` events of the host plane as (name, start ns, end ns)."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "xplane")
        self.host = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path = glob.glob(
            os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True
        )[0]
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("midgpt."):
                        self.host.append((
                            e.name, e.start_ns, e.start_ns + e.duration_ns
                        ))

    def named(self, name):
        return [e for e in self.host if e[0] == name]


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


# ---------------------------------------------------------------------------
# span() itself
# ---------------------------------------------------------------------------


def test_span_writes_the_record_on_the_owners_clock():
    ticks = iter(range(100, 200))
    log = TelemetryLog()
    with span(
        "midgpt.test.phase", log, "phase", clock=lambda: float(next(ticks)),
        rids=(7,), step=3, slot=1,
    ) as sp:
        sp.tokens = 5
        sp.data["late"] = 2
    (rec,) = log.dispatches
    assert (rec.kind, rec.step, rec.t, rec.dur) == ("phase", 3, 100.0, 1.0)
    assert (rec.rids, rec.tokens) == ((7,), 5)
    assert rec.data == {"slot": 1, "late": 2}
    assert (sp.t0, sp.dur) == (100.0, 1.0)
    # a record may start at an earlier span's start: a window's runs from
    # its launch to the end of its harvest
    with span("midgpt.test.later", log, "window", t0=sp.t0,
              clock=lambda: 110.0):
        pass
    assert (log.dispatches[-1].t, log.dispatches[-1].dur) == (100.0, 10.0)
    # no kind: the clock is read (the next span may start from it), no
    # record is written; nor for a phase that raises
    with span("midgpt.test.bare", log, clock=lambda: 1.0):
        pass
    with pytest.raises(KeyError):
        with span("midgpt.test.raises", log, "phase", clock=lambda: 1.0):
            raise KeyError("x")
    assert len(log.dispatches) == 2


def test_span_without_a_log_reads_no_clock():
    def clock():
        raise AssertionError("an owner that is not tracing reads no clock")

    with span("midgpt.test.quiet", None, "phase", clock=clock, step=1) as sp:
        pass
    assert sp.t0 is None and sp.dur == 0.0


def test_span_is_the_only_stamp_in_the_program():
    """Acceptance: one ``span()`` is the only place the program opens a
    ``TraceAnnotation`` or stamps a dispatch record."""
    found = []
    pkg = os.path.join(ROOT, "midgpt_tpu")
    for d, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(d, f)
            rel = os.path.relpath(path, pkg)
            with open(path) as fh:
                for i, line in enumerate(fh, 1):
                    if re.search(r"TraceAnnotation\(|\.record_dispatch\(",
                                 line) and "super()" not in line:
                        found.append((rel, i))
    assert {rel for rel, _ in found} == {"telemetry.py"}, found


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_span_names_avoid_the_harness_annotations(kind):
    """``trace.breakdown`` labels an idle gap by whichever host span
    matching the kind's ``ANNOTATIONS`` overlaps it most: a program span
    under those prefixes would change what the ledger's ``idle_gaps``
    says."""
    with open(os.path.join(ROOT, "benchmark", "kinds", kind + ".py")) as f:
        m = re.search(r'^ANNOTATIONS = r?"(.*)"$', f.read(), re.M)
    assert m, "the kind names its annotations"
    for name in ENGINE_SPANS + LOADER_SPANS + TRAIN_SPANS + (
        "midgpt.cluster.handoff",
    ):
        assert name.startswith("midgpt.")
        assert not re.search(m.group(1), name), (name, m.group(1))


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    return GPT.init(jax.random.PRNGKey(0), CFG)


def _prompt(i, n=12):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(50 + i), (n,), 0, CFG.vocab_size)
    )


@pytest.mark.parametrize("speculate", [0, 2], ids=["window", "verify"])
def test_engine_spans_on_the_host_plane(model, tmp_path, speculate):
    tele = EngineTelemetry()
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefill_chunk=8, speculate=speculate,
        telemetry=tele,
    )
    eng.submit(_prompt(0), 6)
    eng.run()  # every program compiled before the capture
    before = eng.stats()
    ring_before = len(tele.dispatches)
    steps = 0
    with Capture(tmp_path) as cap:
        for i in range(1, 4):
            eng.submit(_prompt(i), 6, seed=i)
        while eng.has_work and steps < 60:
            eng.step()
            steps += 1
    after = eng.stats()
    for name in ENGINE_SPANS:
        assert cap.named(name), f"{name} is not in /host:CPU"
    step_spans = cap.named("midgpt.engine.step")
    assert len(step_spans) == steps
    assert len(cap.named("midgpt.engine.submit")) == 3
    for name in ENGINE_SPANS[2:]:
        for e in cap.named(name):
            assert _inside(e, step_spans), (name, e)
    for e in cap.named("midgpt.engine.submit"):
        assert not _inside(e, step_spans)
    n_prefill = after["prefill_dispatches"] - before["prefill_dispatches"]
    n_decode = after["decode_dispatches"] - before["decode_dispatches"]
    assert n_prefill > 0 and n_decode > 0
    assert len(cap.named("midgpt.engine.prefill_dispatch")) == n_prefill
    assert len(cap.named("midgpt.engine.decode_dispatch")) == n_decode
    assert len(cap.named("midgpt.engine.harvest_wait")) == n_decode
    assert len(cap.named("midgpt.engine.harvest_apply")) == n_decode
    # the ring's records are written by the same spans: one a dispatch
    ring = [d.kind for d in list(tele.dispatches)[ring_before:]]
    decode_kind = "verify_dispatch" if speculate else "decode_window"
    assert ring.count("prefill_chunk") == n_prefill
    assert ring.count(decode_kind) == n_decode
    for d in tele.dispatches:
        assert d.dur >= 0.0
        if d.kind == "prefill_chunk":
            assert {"slot", "start", "chunk", "bucket"} <= set(d.data)
        if d.kind == decode_kind:
            assert len(d.rids) >= 1


def test_traced_engine_streams_and_signature_unchanged(model):
    """Spans are no argument of any program: an engine with telemetry on
    emits the tokens of one without, and two traced runs the same event
    sequence."""
    def run(telemetry):
        eng = ServingEngine(
            model, slots=2, page_size=8, window=4, temperature=0.0,
            cache_dtype=jnp.float32, prefill_chunk=8, telemetry=telemetry,
        )
        rids = [eng.submit(_prompt(i), 7, seed=i) for i in range(3)]
        fin = eng.run()
        return eng, [list(map(int, fin[r].tokens)) for r in rids]

    plain, off = run(None)
    first, on = run(True)
    second, again = run(True)
    assert off == on == again
    assert plain._window_fn is first._window_fn
    assert (first.telemetry.sequence_signature()
            == second.telemetry.sequence_signature())


# ---------------------------------------------------------------------------
# a profiler session is the request log's switch
# ---------------------------------------------------------------------------

ENGINE_KW = dict(slots=2, page_size=8, window=4, temperature=0.0,
                 cache_dtype=jnp.float32, prefill_chunk=8, prefill_budget=8)
PARTS = ("queue_delay_s", "prefill_s", "first_window_s")


@pytest.fixture
def no_session_logs():
    """A session of another test may have left a log open on the list."""
    shared.session_logs().clear()
    yield shared.session_logs()
    shared.session_logs().clear()


def _warm(model, **kw):
    eng = ServingEngine(model, **{**ENGINE_KW, **kw})
    eng.submit(_prompt(0, 20), 6)
    eng.run()  # every program compiled
    return eng


def _drain(eng, each_step=lambda: None, limit=80):
    steps = 0
    while eng.has_work and steps < limit:
        eng.step()
        each_step()
        steps += 1
    assert not eng.has_work
    return steps


def test_engine_without_a_session_never_holds_a_log(model, monkeypatch,
                                                    no_session_logs):
    """With no session open ``step()`` asks once and does nothing else new:
    no log, nothing on the list, every step."""
    from midgpt_tpu.serving import engine as engine_module

    asked = []

    class Asked:
        @staticmethod
        def is_enabled():
            asked.append(1)
            return False

    eng = _warm(model)
    monkeypatch.setattr(engine_module, "TraceAnnotation", Asked)
    for i in range(1, 4):
        eng.submit(_prompt(i, 20), 6, seed=i)

    def check():
        assert eng.telemetry is None and not no_session_logs

    steps = _drain(eng, check)
    assert len(asked) == steps > 3
    assert all(r.admit_time is not None and r.prefill_done_time is not None
               for r in eng.finished.values())


def test_request_log_attaches_for_a_session_and_detaches(
        model, tmp_path, no_session_logs):
    def serve(session):
        eng = _warm(model)
        rids = [eng.submit(_prompt(i, 20), 9, seed=i) for i in range(1, 4)]
        eng.step()
        eng.step()  # rid 1 and 2 admitted, 3 queued: before the session
        before = eng.fault_step
        assert eng.telemetry is None
        if session:
            with Capture(tmp_path) as cap:
                eng.step()  # the engine learns of the session at a step
                rids.append(eng.submit(_prompt(4, 20), 9, seed=4))
                steps = 1 + _drain(
                    eng, lambda: eng.telemetry is not None or pytest.fail(
                        "no log inside the session"))
            # the log leaves at the first step after the session
            assert eng.telemetry is not None
            eng.submit(_prompt(5), 2)
            eng.step()
            assert eng.telemetry is None
            (log,) = shared.session_logs()
            assert log.session_steps == (before + 1, before + 1 + steps)
            assert len(cap.named("midgpt.engine.step")) == steps
            return eng, rids, log
        eng.step()
        rids.append(eng.submit(_prompt(4, 20), 9, seed=4))
        _drain(eng)
        return eng, rids, None

    plain, rids, _ = serve(False)
    traced, rids_t, log = serve(True)
    assert rids == rids_t
    for r in rids:
        assert plain.finished[r].tokens == traced.finished[r].tokens
    assert plain._window_fn is traced._window_fn
    # the back-fill: each request of before the session has its beginning,
    # at the times the request itself carries
    for r in rids[:3]:
        req = traced.finished[r]
        evs = {e.kind: e for e in reversed(log.request_log[r])
               if e.data.get("backfill")}
        assert evs["submit"].t == evs["queued"].t == req.submit_time
        assert (evs["submit"].data["prompt_tokens"],
                evs["submit"].data["budget"]) == (20, 9)
        if r != rids[2]:  # in a slot when the log attached
            assert evs["admitted"].t == req.admit_time
        assert not any(log.in_session(e) for e in evs.values())
        m = log.request_metrics(r)
        assert m["ttft_s"] == pytest.approx(
            req.first_token_time - req.submit_time, abs=1e-12)
        assert sum(m[k] for k in PARTS) == pytest.approx(
            m["ttft_s"], abs=1e-12)
    # the one submitted inside it was logged as it happened
    assert not any(e.data.get("backfill")
                   for e in log.request_log[rids_t[3]])
    # the list keeps the log when the engine is gone
    del traced
    import gc

    gc.collect()
    assert shared.session_logs() == [log] and log.request_log


def test_engine_with_its_own_log_registers_it_the_same_way(
        model, tmp_path, no_session_logs):
    eng = _warm(model, telemetry=True)
    own = eng.telemetry
    assert own.session_steps is None and not no_session_logs
    eng.submit(_prompt(1, 20), 6)
    with Capture(tmp_path):
        steps = _drain(eng)
    assert eng.telemetry is own and shared.session_logs() == [own]
    first = own.session_steps[0]
    assert own.session_steps == (first, None)
    eng.submit(_prompt(2), 2)
    eng.step()
    assert eng.telemetry is own
    assert own.session_steps == (first, first + steps)
    # its earlier events are no part of the session, and nothing is
    # back-filled into a log that saw them happen
    assert not any(e.data.get("backfill") for e in own.events)
    inside = [e for e in own.events if own.in_session(e)]
    assert inside and len(inside) < len(own.events)
    assert {e.step for e in inside} <= set(range(first, first + steps))


def test_session_logs_are_bounded_and_a_new_session_starts_over(
        no_session_logs):
    logs = [TelemetryLog() for _ in range(shared.SESSION_LOGS_MAX + 3)]
    for i, log in enumerate(logs):
        log.open_session(i)  # eleven owners in one session
    assert shared.session_logs() == logs[3:]
    for log in logs:
        log.close_session(20)
    late = TelemetryLog()
    late.open_session(0)  # every log was closed: another session
    assert shared.session_logs() == [late]
    late.open_session(5)  # the same owner again, while still open
    assert shared.session_logs() == [late] and late.session_steps == (5, None)


def _served(model, case):
    """One finished request and the log that saw all of it."""
    if case in ("block", "whole_prefix_hit"):
        cfg = dataclasses.replace(CFG, block_len=4, block_steps=4,
                                  mask_token=CFG.vocab_size - 1)
        eng = ServingEngine(
            GPT.init(jax.random.PRNGKey(0), cfg), telemetry=True,
            paged_kernel="xla", **{**ENGINE_KW, "window": 5})
        prompt = np.arange(16, dtype=np.int32)  # two whole pages
        rid = eng.submit(prompt, 6)
        eng.run()
        if case == "whole_prefix_hit":
            rid = eng.submit(prompt, 6)
            eng.run()
            assert eng.finished[rid].cached_tokens == 16
        return eng, rid
    eng = ServingEngine(model, telemetry=True, **ENGINE_KW)
    rid = eng.submit(_prompt(1, 20), 6)
    if case == "evicted":
        eng.step()
        eng.step()  # two of three chunks in
        eng._evict(0)
    eng.run()
    return eng, rid


@pytest.mark.parametrize(
    "case", ["plain", "evicted", "whole_prefix_hit", "block"])
def test_time_to_first_token_splits_into_three_parts(model, case):
    eng, rid = _served(model, case)
    req, m = eng.finished[rid], eng.telemetry.request_metrics(rid)
    assert all(m[k] is not None and m[k] >= 0.0 for k in PARTS)
    assert sum(m[k] for k in PARTS) == pytest.approx(m["ttft_s"], abs=1e-12)
    # the log's events and the request's own stamps are one clock reading
    assert m["queue_delay_s"] == req.admit_time - req.submit_time
    assert m["prefill_s"] == req.prefill_done_time - req.admit_time
    assert m["first_window_s"] == (
        req.first_token_time - req.prefill_done_time)
    kinds = [e.kind for e in eng.telemetry.request_log[rid]]
    if case == "evicted":
        assert m["evictions"] == 1 and kinds.count("admitted") == 2
        assert kinds.index("evicted") < kinds.index("tokens")
    if case == "whole_prefix_hit":
        assert "prefill_chunk" not in kinds and m["prefill_s"] == 0.0
    else:
        assert m["prefill_s"] > 0.0


def test_request_metrics_without_the_first_token_has_no_ttft():
    """A log that attached after a request's first token holds its later
    harvests only: no time to a first token is made up from them."""
    log = EngineTelemetry()
    log.emit("submit", step=5, t=1.0, rid=1, backfill=True)
    log.emit("admitted", step=5, t=2.0, rid=1, backfill=True)
    log.emit("tokens", step=6, t=9.0, rid=1, n=4, total=12)
    m = log.request_metrics(1)
    assert m["queue_delay_s"] == 1.0
    assert m["ttft_s"] is m["prefill_s"] is m["first_window_s"] is None


def test_slot_census_adds_up_on_every_step(model):
    eng = ServingEngine(model, telemetry=True, **{**ENGINE_KW, "slots": 3})
    eng.step()  # nothing to do: no census
    assert not [e for e in eng.telemetry.events if e.kind == "step"]
    for i in range(5):
        eng.submit(_prompt(i, 20), 5 + i, seed=i)
    windows, steps = [], 0
    while eng.has_work:
        before = eng.windows
        eng.step()
        steps += 1
        windows.append(eng.windows - before)
    census = [e for e in eng.telemetry.events if e.kind == "step"]
    assert [e.step for e in census] == list(range(2, 2 + steps))
    for e, ran in zip(census, windows):
        d = e.data
        assert d["decoding"] + d["prefilling"] + d["empty"] == 3
        assert (d["decoding"] > 0) == bool(ran)
        assert e.rid is None and d["parked"] == 0
    first = census[0].data  # a step of prefill chunks alone
    assert (first["decoding"], first["prefilling"], first["queued"]) == (
        0, 3, 2)
    assert windows[0] == 0
    assert census[-1].data["queued"] == 0
    assert sum(e.data["decoding"] for e in census) == eng.occupancy_sum


def test_prefill_role_slots_waiting_for_handoff_count_as_prefilling(model):
    eng = ServingEngine(model, telemetry=True, role="prefill", **ENGINE_KW)
    eng.submit(_prompt(1, 8), 4)
    eng.step()
    assert eng.handoff_ready_slots() == [0]
    eng.step()
    for e in eng.telemetry.events:
        if e.kind == "step":
            assert (e.data["decoding"], e.data["prefilling"],
                    e.data["empty"]) == (0, 1, 1)


def test_compile_log_counts_a_new_shape_once_and_marks_a_session(tmp_path):
    log = shared.compile_log()

    @jax.jit
    def bump(x):
        return x * 2 + 1

    def built():
        return [(n, s) for n, _, s in log if n == "jit(bump)"]

    bump(jnp.ones(3)).block_until_ready()
    assert built() == [("jit(bump)", False)]
    bump(jnp.ones(3)).block_until_ready()  # a repeated call builds nothing
    assert built() == [("jit(bump)", False)]
    with Capture(tmp_path):
        bump(jnp.ones(3)).block_until_ready()
        assert built() == [("jit(bump)", False)]
        bump(jnp.ones(5)).block_until_ready()  # a new shape, in the session
    assert built() == [("jit(bump)", False), ("jit(bump)", True)]
    bump(jnp.ones(7)).block_until_ready()
    assert [s for _, s in built()] == [False, True, False]
    assert all(sec > 0 for n, sec, _ in log if n == "jit(bump)")
    assert log.maxlen == 256


def test_telemetry_stays_inert_under_a_session(tmp_path, no_session_logs):
    """``prove_telemetry_inert`` with a session open: the engine built
    without a log attaches one and still runs the same programs to the
    same tokens."""
    from midgpt_tpu.analysis.harness import prove_telemetry_inert

    with Capture(tmp_path):
        rep = prove_telemetry_inert()
    assert rep["ok"] and rep["streams_identical"]
    assert len(shared.session_logs()) == 2  # the engine's own, and its twin's


# ---------------------------------------------------------------------------
# the loader
# ---------------------------------------------------------------------------


def test_loader_spans_on_the_worker_thread(tmp_path):
    toks = np.tile(np.arange(64, dtype=np.uint16), 400)
    loader = Loader(
        shard=Shard(tokens=toks, global_len=toks.size, offset=0),
        block_size=16, batch_shape=(1, 4), seed=0,
    )
    log = TelemetryLog()
    pf = PrefetchLoader(loader, window=2, window_plan=[2, 2, 2])
    with Capture(tmp_path) as cap:
        pf.start()
        items = [pf.next(log, step=2 * i) for i in range(3)]
        pf.stop()
    assert items[0][0].shape == (2, 1, 4, 16)
    produce = cap.named("midgpt.loader.produce")
    assert len(produce) == 3
    for name in ("midgpt.loader.gather", "midgpt.loader.transfer"):
        got = cap.named(name)
        assert len(got) == 3
        assert all(_inside(e, produce) for e in got)
    waits = cap.named("midgpt.loader.wait")
    assert len(waits) == 3
    # the consumer's wait is the ring's prefetch_wait, by the same span
    assert [(d.kind, d.step) for d in log.dispatches] == [
        ("prefetch_wait", 0), ("prefetch_wait", 2), ("prefetch_wait", 4)
    ]


# ---------------------------------------------------------------------------
# train()
# ---------------------------------------------------------------------------


def _train_cfg(tmp_path, name, **kw) -> ExperimentConfig:
    data_dir = str(tmp_path / "data")
    if not os.path.exists(data_dir):
        toks = np.tile(np.arange(64), 2000)
        write_tokens(os.path.join(data_dir, "train.bin"), toks)
        write_tokens(os.path.join(data_dir, "val.bin"), toks[:20_000])
    defaults = dict(
        model=ModelConfig(
            block_size=16, vocab_size=64, n_layer=1, n_head=2, n_embd=32,
            dropout=0.0, attn_impl="naive", remat="none",
        ),
        rundir=str(tmp_path / name), data_dir=data_dir,
        learning_rate=1e-2, min_lr=1e-3, warmup_steps=2, lr_decay_steps=8,
        max_steps=8, batch_size=8, g_accum_iters=1, steps_per_dispatch=2,
        compute_dtype="float32", eval_interval=4, eval_batches=1,
        log_interval=1, mesh=MeshConfig(replica=8), ckpt_keep=8,
    )
    defaults.update(kw)
    return ExperimentConfig(**defaults)


def _checkpoint_steps(rundir):
    return sorted(
        int(d) for d in os.listdir(rundir)
        if d.isdigit() and os.path.isdir(os.path.join(rundir, d))
    ) if os.path.isdir(rundir) else []


def test_train_spans_ring_and_windows_handed_out(tmp_path):
    """``train()`` under a profiler session with ``train_telemetry``: its
    phases are in the trace under the ``midgpt.train.`` names, the ring's
    records come from the same spans, and ``on_window`` sees every window
    with its outputs still on the device."""
    import json

    seen = []

    def on_window(step, k, state, out):
        assert isinstance(out["loss"], jax.Array) and out["loss"].shape == (k,)
        assert isinstance(state.step, jax.Array)
        seen.append((step, k))

    cfg = _train_cfg(tmp_path, "full", train_telemetry=True)
    with Capture(tmp_path) as cap:
        final = train(cfg, on_window=on_window)
    assert seen == [(0, 2), (2, 2), (4, 2), (6, 2)]
    assert final["train_dispatches"] == 4 and "stopped_at" not in final
    for name in TRAIN_SPANS + LOADER_SPANS:
        assert cap.named(name), f"{name} is not in /host:CPU"
    assert len(cap.named("midgpt.train.launch")) == 4
    assert len(cap.named("midgpt.train.harvest")) == 4  # log_interval=1
    assert len(cap.named("midgpt.train.eval")) == 2
    assert len(cap.named("midgpt.loader.wait")) == 4
    with open(os.path.join(cfg.rundir, "train_telemetry.json")) as f:
        fl = json.load(f)
    kinds = [d["kind"] for d in fl["telemetry"]["dispatches"]]
    assert kinds.count("prefetch_wait") == 4
    assert kinds.count("train_window") == 4
    assert kinds.count("eval_pause") == 2
    assert kinds.count("ckpt_wait") == 1
    counters = fl["metrics"]["counters"]
    assert counters["prefetch_waits"] == 4 and counters["evals"] == 2
    assert counters["ckpt_saves"] == kinds.count("ckpt_save") >= 1
    for d in fl["telemetry"]["dispatches"]:
        if d["kind"] == "train_window":
            assert d["k"] == 2 and d["dur"] > 0
    # a caller that takes the windows keeps what it chooses: the save
    # after the first window is left out, interval and final saves stay
    assert _checkpoint_steps(cfg.rundir) == [3, 7]


def test_on_window_stops_train_without_a_checkpoint(tmp_path):
    """Three windows, then a true return: the loop ends there, with no
    save of any kind and no final evaluation."""
    calls = []

    def on_window(step, k, state, out):
        calls.append(step)
        return len(calls) == 3

    cfg = _train_cfg(
        tmp_path, "stopped", max_steps=40, lr_decay_steps=40,
        eval_interval=20, log_interval=100,
    )
    final = train(cfg, on_window=on_window)
    assert calls == [0, 2, 4]
    assert final["stopped_at"] == 5 and final["train_dispatches"] == 3
    assert "interrupted_at" not in final
    assert _checkpoint_steps(cfg.rundir) == []
    # without the callback the same run saves after its first window
    cfg2 = dataclasses.replace(cfg, rundir=str(tmp_path / "kept"), max_steps=4,
                               lr_decay_steps=4, eval_interval=4)
    train(cfg2)
    assert _checkpoint_steps(cfg2.rundir) == [1, 3]


# ---------------------------------------------------------------------------
# named scopes in the programs
# ---------------------------------------------------------------------------


def _scopes(lowered):
    """The name-scope components of every ``op_name`` of the program; a
    differentiated scope comes as ``jvp(name)`` / ``transpose(jvp(name))``."""
    text = lowered.compile().as_text()
    return {part for m in re.finditer(r'op_name="([^"]*)"', text)
            for part in re.split(r"[/()]", m.group(1))}


def test_train_window_scopes_in_the_hlo():
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.parallel.sharding import make_global_array
    from midgpt_tpu.train import get_train_window, init_state, make_optimizer

    cfg = ExperimentConfig(
        model=ModelConfig(
            block_size=16, vocab_size=64, n_layer=1, n_head=2, n_embd=32,
            dropout=0.0, attn_impl="naive", remat="none",
        ),
        batch_size=8, g_accum_iters=1, steps_per_dispatch=2, loss_chunk=8,
        compute_dtype="float32", mesh=MeshConfig(replica=8),
    )
    mesh = create_mesh(cfg.mesh)
    tx, _ = make_optimizer(cfg)
    state = init_state(cfg, mesh, tx, jax.random.PRNGKey(0))
    spec = P(None, None, ("replica", "fsdp"), "sequence")
    xs = make_global_array(np.zeros((2, 1, 8, 16), np.int32), mesh, spec)
    lowered = get_train_window(cfg, mesh, 2).lower(
        state, xs, xs, jax.random.PRNGKey(1)
    )
    have = _scopes(lowered)
    for scope in ("embed", "attention", "mlp", "head_loss", "optimizer"):
        assert scope in have, (scope, sorted(have)[:40])


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk"])
def test_serving_program_scopes_in_the_hlo(model, program):
    eng = ServingEngine(
        model, slots=2, page_size=8, window=4, temperature=0.0,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    if program == "decode_window":
        lowered = eng._window_fn.lower(
            eng.model, eng.pool, eng.logits, jnp.asarray(eng.bt),
            jnp.asarray(eng.pooled_len), jnp.asarray(eng.done),
            jnp.asarray(eng.emitted), jnp.asarray(eng.budget),
            jnp.asarray(eng.eos), jnp.asarray(eng.seeds), eng._key,
        )
    else:
        eng.submit(_prompt(0, 8), 4)
        eng.step()  # builds the bucket's chunk program
        (fn,) = eng._chunk_fns.values()
        lowered = fn.lower(
            eng.model, eng.pool, eng.logits, jnp.asarray(0, jnp.int32),
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(8, jnp.int32), jnp.asarray(eng.bt[0]),
        )
    have = _scopes(lowered)
    for scope in ("embed", "attention", "mlp", "head"):
        assert scope in have, (scope, sorted(have)[:40])
