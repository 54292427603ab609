"""Training-loop telemetry (midgpt_tpu.train_telemetry) + the train-side
inertness contract.

The hard gates, mirroring the serving telemetry suite:

- **Program identity**: the jitted K-step window resolves through
  ``train.get_train_window``'s module-level cache, whose key excludes
  every observability knob — so telemetry on/off (and rundir/logging
  cadence changes) select the ``is``-IDENTICAL cached callable, while a
  real program change (optimizer hyperparameters) does not.
- **Bitwise loss**: a K=4 drive with telemetry spans emitted around the
  cached program reproduces the plain drive's loss sequence bit for
  bit; end to end, two ``train()`` runs differing only in
  ``train_telemetry`` log identical loss sequences.
- **Anomaly monitors**: deterministic step-keyed trips (NaN sentinel,
  EWMA loss/grad-norm spikes) under injected spike series, the
  wall-informed throughput-drop detector, and the flight-record dump
  (recent history + telemetry rings) on trip.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import midgpt_tpu.train as train_mod
from midgpt_tpu.config import ExperimentConfig, MeshConfig, ModelConfig
from midgpt_tpu.data import write_tokens
from midgpt_tpu.telemetry import span
from midgpt_tpu.train import (
    get_train_window,
    init_state,
    make_optimizer,
    train,
)
from midgpt_tpu.train_telemetry import (
    AnomalyMonitors,
    TRAIN_COUNTERS,
    TRAIN_EVENT_KINDS,
    TRAIN_SPAN_KINDS,
    TrainTelemetry,
    chrome_trace_train,
)


def _base_cfg(**kw) -> ExperimentConfig:
    defaults = dict(
        model=ModelConfig(
            block_size=32, vocab_size=64, n_layer=2, n_head=2, n_embd=64,
            dropout=0.0, attn_impl="naive", remat="none",
        ),
        learning_rate=1e-2, min_lr=1e-3, warmup_steps=2,
        lr_decay_steps=8, max_steps=8,
        batch_size=8, g_accum_iters=2,
        compute_dtype="float32",  # bitwise gates: see test_train_window
        eval_interval=8, eval_batches=1, log_interval=1,
        mesh=MeshConfig(replica=1, fsdp=2, sequence=2, tensor=2),
    )
    defaults.update(kw)
    return ExperimentConfig(**defaults)


def _data_dir(tmp_path) -> str:
    data_dir = str(tmp_path / "data")
    os.makedirs(data_dir, exist_ok=True)
    toks = np.tile(np.arange(64), 4000)
    write_tokens(os.path.join(data_dir, "train.bin"), toks)
    write_tokens(os.path.join(data_dir, "val.bin"), toks[:40_000])
    return data_dir


# ---------------------------------------------------------------------------
# TrainTelemetry units (no compilation)
# ---------------------------------------------------------------------------


def test_taxonomy_spans_and_starvation_counter():
    tele = TrainTelemetry(starvation_s=0.01)
    tele.emit("run_start", step=0, t=0.0)
    tele.record_dispatch("eval_pause", step=0, t=0.1, dur=0.2, batches=1)
    # fast prefetch: counted, not starved
    tele.record_dispatch("prefetch_wait", step=0, t=0.3, dur=0.001)
    # slow prefetch: starved — counter + event
    tele.record_dispatch("prefetch_wait", step=4, t=0.4, dur=0.5)
    snap = tele.metrics_snapshot()
    assert snap["counters"]["prefetch_waits"] == 2
    assert snap["counters"]["prefetch_starved"] == 1
    assert [e.kind for e in tele.events] == [
        "run_start", "prefetch_starved"
    ]
    kinds = [d.kind for d in tele.dispatches]
    assert kinds == ["eval_pause", "prefetch_wait", "prefetch_wait"]
    assert snap["histograms"]["prefetch_wait_s"]["count"] == 2
    assert snap["histograms"]["eval_pause_s"]["count"] == 1
    # taxonomy is enforced both ways: serving kinds don't leak in
    with pytest.raises(AssertionError):
        tele.emit("decode_window", step=0, t=0.0)
    with pytest.raises(AssertionError):
        tele.record_dispatch("decode_window", step=0, t=0.0, dur=0.0)
    for name in TRAIN_COUNTERS:
        assert name in snap["counters"], name


def test_chrome_trace_train_structure():
    tele = TrainTelemetry()
    tele.emit("run_start", step=0, t=1.0)
    tele.record_dispatch("prefetch_wait", step=0, t=1.0, dur=0.01)
    tele.emit("window_launch", step=0, t=1.1, k=4)
    tele.record_dispatch("train_window", step=0, t=1.1, dur=0.4, k=4)
    tele.emit("anomaly", step=3, t=1.6, kind_detail="loss_spike")
    tr = chrome_trace_train(tele)
    names = [e.get("name") for e in tr["traceEvents"]]
    lanes = {
        e["args"]["name"]
        for e in tr["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert set(TRAIN_SPAN_KINDS) <= lanes and "events" in lanes
    spans = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
    assert {s["name"] for s in spans} == {"prefetch_wait", "train_window"}
    instants = [e for e in tr["traceEvents"] if e.get("ph") == "i"]
    assert {i["name"] for i in instants} == {"run_start", "anomaly"}
    assert "train_window" in names
    json.dumps(tr)  # Perfetto-loadable


def test_flight_dump_and_prometheus_export(tmp_path):
    from midgpt_tpu.telemetry import prometheus_text

    tele = TrainTelemetry()
    tele.emit("run_start", step=0, t=0.0)
    tele.metrics.counter("windows_dispatched").inc(3)
    path = str(tmp_path / "flight.json")
    rec = tele.flight_dump("test", path=path, extra={"round": 6})
    on_disk = json.load(open(path))
    assert on_disk["reason"] == "test" and on_disk["round"] == 6
    assert on_disk["telemetry"]["events"][0]["kind"] == "run_start"
    assert rec["metrics"]["counters"]["windows_dispatched"] == 3
    # the registry snapshot exports through the shared Prometheus path
    text = prometheus_text(tele.metrics_snapshot())
    for name in TRAIN_COUNTERS:
        assert f"midgpt_{name}_total" in text, name
    assert "midgpt_prefetch_wait_s_bucket" in text


# ---------------------------------------------------------------------------
# Anomaly monitors: deterministic step-keyed trips
# ---------------------------------------------------------------------------


def test_nan_sentinel_trips_immediately_and_skips_ewma():
    m = AnomalyMonitors()
    trips = m.observe_step(0, float("nan"), 1.0)
    assert [t["kind"] for t in trips] == ["nan"]
    trips = m.observe_step(1, 1.0, float("inf"))
    assert [t["kind"] for t in trips] == ["nan"]
    # the non-finite values must not have poisoned the spike EWMAs
    for s in range(2, 40):
        assert m.observe_step(s, 1.0, 1.0) == []


def test_loss_spike_trips_after_warmup_not_during():
    # a spike DURING warmup never trips (statistics still forming)
    m0 = AnomalyMonitors(warmup=10)
    assert m0.observe_step(0, 4.0, 1.0) == []
    assert m0.observe_step(1, 50.0, 1.0) == []
    # a smooth series, then a spike: trips at exactly the spike step
    m = AnomalyMonitors(warmup=10)
    for s in range(30):
        assert m.observe_step(s, 4.0 + 0.01 * (s % 3), 1.0) == []
    trips = m.observe_step(30, 40.0, 1.0)
    assert [t["kind"] for t in trips] == ["loss_spike"]
    assert trips[0]["step"] == 30
    assert trips[0]["detail"]["value"] == 40.0
    assert trips[0]["detail"]["threshold"] < 40.0


def test_grad_norm_spike_and_k1_none_skip():
    m = AnomalyMonitors(warmup=5)
    for s in range(20):
        m.observe_step(s, 4.0, 1.0)
    trips = m.observe_step(20, 4.0, 900.0)
    assert [t["kind"] for t in trips] == ["grad_norm_spike"]
    # the K=1 loop logs no grad norm: None skips the detector entirely
    m2 = AnomalyMonitors(warmup=5)
    for s in range(20):
        assert m2.observe_step(s, 4.0, None) == []


def test_monitors_are_deterministic_over_a_series():
    rng = np.random.default_rng(0)
    series = list(4.0 + 0.05 * rng.standard_normal(60))
    series[45] = 50.0

    def run():
        m = AnomalyMonitors(warmup=10)
        out = []
        for s, v in enumerate(series):
            out.extend(
                (t["kind"], t["step"]) for t in m.observe_step(s, v, 1.0)
            )
        return out

    first = run()
    assert ("loss_spike", 45) in first
    assert first == run()  # same series -> same trips, same steps


def test_throughput_drop_detector():
    m = AnomalyMonitors(tps_warmup=3)
    for s in range(5):
        assert m.observe_throughput(s, 1000.0) == []
    trips = m.observe_throughput(5, 300.0)
    assert [t["kind"] for t in trips] == ["throughput_drop"]


def test_trip_dumps_flight_record_with_history_and_cap(tmp_path):
    tele = TrainTelemetry()
    m = AnomalyMonitors(
        telemetry=tele, flight_dir=str(tmp_path), warmup=2, max_dumps=1
    )
    for s in range(5):
        m.observe_step(s, 4.0, 1.0)
    m.observe_step(5, float("nan"), 1.0)
    m.observe_step(6, float("nan"), 1.0)  # past max_dumps: no 2nd file
    assert len(m.trips) == 2 and len(m.dump_paths) == 1
    dump = json.load(open(m.dump_paths[0]))
    assert dump["reason"] == "anomaly:nan"
    assert dump["step"] == 5
    assert [h["step"] for h in dump["history"]][-1] == 5
    assert dump["telemetry"]["events"][-1]["kind"] == "anomaly"
    assert tele.metrics_snapshot()["counters"]["anomalies_tripped"] == 2
    assert len(list(tmp_path.glob("anomaly_*.json"))) == 1


# ---------------------------------------------------------------------------
# The inertness contract: program identity + bitwise loss
# ---------------------------------------------------------------------------


def test_window_cache_identity_excludes_observability_knobs(mesh8):
    """get_train_window resolves telemetry/rundir/logging variants to
    the IDENTICAL cached jitted callable (no compile happens here —
    jit wrappers build lazily), while a real program change (optimizer
    hyperparameter) gets its own program."""
    cfg = _base_cfg()
    w1 = get_train_window(cfg, mesh8, 4)
    observability_variant = dataclasses.replace(
        cfg, rundir="/tmp/elsewhere", train_telemetry=True,
        log_interval=7, max_steps=99, eval_interval=33, seed=5,
        data_seed=77,
    )
    assert get_train_window(observability_variant, mesh8, 4) is w1
    assert get_train_window(cfg, mesh8, 2) is not w1  # K is program shape
    program_variant = dataclasses.replace(cfg, learning_rate=5e-3)
    assert get_train_window(program_variant, mesh8, 4) is not w1


def test_window_drive_with_telemetry_attached_is_bitwise(mesh8):
    """Two K=4 drives of the SAME cached window program — one plain, one
    with TrainTelemetry emitting launch/harvest/span around every call —
    produce bitwise-identical per-step losses, and the telemetry
    actually recorded."""
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.sharding import make_global_array

    cfg = _base_cfg()
    tx, _ = make_optimizer(cfg)
    window = get_train_window(cfg, mesh8, 4)
    key = jax.random.PRNGKey(0)
    base = jax.random.PRNGKey(7)
    rng = np.random.default_rng(0)
    xs = rng.integers(0, 64, size=(8, 2, 4, 32), dtype=np.int32)
    ys = rng.integers(0, 64, size=(8, 2, 4, 32), dtype=np.int32)
    wspec = P(None, None, ("replica", "fsdp"), "sequence")

    def drive(tele):
        import time

        state = init_state(cfg, mesh8, tx, key)
        losses = []
        for w in range(0, 8, 4):
            xg = make_global_array(xs[w:w + 4], mesh8, wspec)
            yg = make_global_array(ys[w:w + 4], mesh8, wspec)
            if tele is not None:
                tele.emit(
                    "window_launch", step=w, t=time.perf_counter(), k=4
                )
            # the program's one way to time a phase: the ring's record
            # comes from span(), as in train()
            with span("midgpt.train.launch", tele, step=w, k=4) as launch:
                state, out = window(state, xg, yg, base)
            with span(
                "midgpt.train.harvest", tele, "train_window",
                t0=launch.t0, step=w, k=4,
            ):
                arr = np.asarray(out["loss"])
            if tele is not None:
                tele.emit(
                    "window_harvest", step=w + 3, t=time.perf_counter(), k=4
                )
            losses.append(arr)
        return np.concatenate(losses).astype(np.float32)

    plain = drive(None)
    tele = TrainTelemetry()
    traced = drive(tele)
    np.testing.assert_array_equal(
        plain.view(np.uint32), traced.view(np.uint32)
    )
    assert get_train_window(cfg, mesh8, 4) is window  # still the one
    assert [e.kind for e in tele.events] == [
        "window_launch", "window_harvest",
    ] * 2
    assert len(tele.dispatches) == 2


@pytest.mark.slow
def test_train_e2e_telemetry_on_off_bitwise_and_artifacts(tmp_path, monkeypatch):
    """train() end to end, K=4: telemetry on vs off logs the identical
    per-step loss sequence, resolves the SAME cached window program
    (module-level cache gains no new entries on the second run), and
    the traced run writes the timeline + flight artifacts with the
    attainment keys riding every throughput record."""
    # the CPU has no peak FLOP/s; the floor context needs one, so the
    # test names the chip it wants the floors against
    monkeypatch.setattr(
        "midgpt_tpu.utils.metrics.device_peak_flops", lambda: 197e12
    )
    data_dir = _data_dir(tmp_path)
    cfg_off = _base_cfg(
        rundir=str(tmp_path / "off"), data_dir=data_dir,
        steps_per_dispatch=4,
    )
    train(cfg_off)
    after_off = dict(train_mod._WINDOW_PROGRAMS)
    assert after_off, "the K=4 drive must resolve through the cache"

    cfg_on = dataclasses.replace(
        cfg_off, rundir=str(tmp_path / "on"), train_telemetry=True
    )
    train(cfg_on)
    after_on = dict(train_mod._WINDOW_PROGRAMS)
    # inertness: the traced run compiled NOTHING new — every window
    # program it used is the is-identical cached callable (earlier
    # tests in this file may have pre-populated the same keys: the
    # cache deliberately ignores rundir/telemetry/logging knobs)
    assert set(after_on) == set(after_off)
    for k in after_off:
        assert after_on[k] is after_off[k]

    def logged(rundir):
        out = {}
        recs = []
        with open(os.path.join(rundir, "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                recs.append(rec)
                if "loss/optimized" in rec:
                    out[rec["step"]] = rec["loss/optimized"]
        return out, recs

    l_off, _ = logged(cfg_off.rundir)
    l_on, recs_on = logged(cfg_on.rundir)
    assert sorted(l_off) == sorted(l_on) == list(range(1, 8))
    for s in l_off:
        assert l_off[s] == l_on[s], f"step {s} diverged under tracing"

    # attainment rides every throughput record (MetricLogger floor)
    tps_recs = [r for r in recs_on if "tokens_per_sec" in r]
    assert tps_recs
    for r in tps_recs:
        assert r["train_attainment_frac"] > 0
        assert r["train_hbm_floor_ms"] > 0
        assert r["train_compute_floor_ms"] > 0
        assert r["step_ms"] > 0

    # the traced run leaves a Perfetto timeline + flight record
    tl = json.load(open(os.path.join(cfg_on.rundir, "train_timeline.json")))
    span_names = {
        e["name"] for e in tl["traceEvents"] if e.get("ph") == "X"
    }
    assert {"prefetch_wait", "train_window", "eval_pause"} <= span_names
    fl = json.load(
        open(os.path.join(cfg_on.rundir, "train_telemetry.json"))
    )
    assert fl["reason"] == "run_end"
    kinds = {e["kind"] for e in fl["telemetry"]["events"]}
    assert {"run_start", "window_launch", "window_harvest",
            "run_end"} <= kinds
    assert fl["metrics"]["counters"]["windows_dispatched"] == 2
    assert fl["metrics"]["counters"]["steps_completed"] == 8
    # healthy tiny run: monitors observed every step, tripped nothing
    assert fl["metrics"]["counters"]["anomalies_tripped"] == 0
    # the untraced run writes no telemetry artifacts
    assert not os.path.exists(
        os.path.join(cfg_off.rundir, "train_timeline.json")
    )
