"""bench.py's one-JSON-line contract must survive a dead backend: the
driver records bench output mechanically, so a backend that fails or
hangs has to produce a parseable bench_error record and a non-zero exit
code, never a bare traceback, a hang, or a partial record that exits 0."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_emits_json_error_on_dead_backend():
    code = (
        f"import sys; sys.path.insert(0, {REPO!r}); import bench; bench.main()"
    )
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        # a platform name that exists on NO machine: init raises fast
        # everywhere (a real platform name could init on target hardware
        # and run the actual benchmark ladder from inside the test)
        "JAX_PLATFORMS": "no_such_backend",
        "XLA_FLAGS": "",
    }
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert r.returncode == 3, (r.returncode, r.stderr[-400:])
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout[-400:]
    rec = json.loads(lines[0])
    assert rec["metric"] == "bench_error"
    assert "error" in rec
    assert rec["status"] == "error", "a real failure is not a wedge"


def test_bench_watchdog_fires_on_hung_init():
    code = (
        f"import sys; sys.path.insert(0, {REPO!r}); import bench, time; "
        "bench._backend_watchdog(1.0); time.sleep(30); print('NOT_REACHED')"
    )
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",
    }
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r.returncode == 3
    assert "NOT_REACHED" not in r.stdout
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "bench_error"
    # structured deadline row: trajectories separate runs that hit a
    # deadline from regressions by this field
    assert rec["status"] == "watchdog"


def test_bench_partial_record_exits_nonzero():
    """A run cut by the whole-run deadline after its headline still
    prints what it measured, marked partial — and exits non-zero: a run
    that did not finish is not a passing run."""
    code = (
        f"import sys; sys.path.insert(0, {REPO!r}); "
        "import bench, threading, time; "
        "bench._progress_watchdog("
        "{'metric': 'm', 'value': 0.5}, threading.Event(), 0.5); "
        "time.sleep(30); print('NOT_REACHED')"
    )
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",
    }
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode == 5, (r.returncode, r.stderr[-400:])
    assert "NOT_REACHED" not in r.stdout
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["value"] == 0.5 and rec["partial"] is True
    assert rec["status"] == "watchdog"


def test_rung_measure_falls_back_when_scan_compile_fails():
    """_rung_measure must fall back to the chained path when the scan
    program fails to COMPILE (state untouched), and re-raise when the
    state buffers were already donated (a runtime failure mid-measure
    would otherwise hand deleted arrays to the fallback)."""
    sys.path.insert(0, REPO)
    import types

    import bench

    calls = {"chain": 0}

    class FakeLeaf:
        def __init__(self, deleted=False):
            self._deleted = deleted

        def is_deleted(self):
            return self._deleted

    state = [FakeLeaf()]

    def chain(st, n):
        calls["chain"] += 1
        return 0.01 * n, st

    cfg = types.SimpleNamespace(
        batch_size=8, model=types.SimpleNamespace(block_size=64)
    )

    def make_scan_compile_fails(n):
        class M:
            def lower(self, s):
                raise RuntimeError("compile boom")

        return M()

    tps, step_ms, st, mode = bench._rung_measure(
        cfg, state, chain, make_scan_compile_fails
    )
    assert mode == "chained" and calls["chain"] >= 2

    # donated state: the fallback must NOT run; original error re-raises
    dead = [FakeLeaf(deleted=True)]
    calls["chain"] = 0
    try:
        bench._rung_measure(cfg, dead, chain, make_scan_compile_fails)
        raise AssertionError("expected the compile error to re-raise")
    except RuntimeError as e:
        assert "compile boom" in str(e)
    assert calls["chain"] == 0


def test_bench_main_record_flow_with_stubbed_rungs(monkeypatch, capsys):
    """bench.main() end to end with _run_config stubbed to a trivial CPU
    closure: every rung family must land its keys in the ONE emitted
    JSON record (this is the mechanical guard for the record-wiring bug
    class — r5's code review caught the headline loop rebinding `record`
    and orphaning the watchdog's dict)."""
    import types

    sys.path.insert(0, REPO)
    import bench

    def fake_run_config(remat, batch, base="openwebtext", n_layer=None,
                        loss_chunk=256, block_size=None):
        cfg = types.SimpleNamespace(
            batch_size=batch,
            # a full dense-model shape: the attainment helper computes
            # the analytic train floor from these fields (traffic.py)
            model=types.SimpleNamespace(
                block_size=block_size or 64, remat=remat,
                mlp="gelu", mlp_hidden=None, mlp_ratio=4,
                n_embd=64, head_dim=16, n_head=4, kv_heads=4,
                n_layer=n_layer or 2, vocab_size=256, qk_norm=False,
            ),
        )

        def chain(state, n):
            return 0.002 * n, state

        def make_scan(n):
            raise RuntimeError("no scan on the stub")  # force chained

        return cfg, [], chain, make_scan

    monkeypatch.setattr(bench, "_run_config", fake_run_config)
    monkeypatch.setattr(
        "midgpt_tpu.utils.metrics.mfu", lambda tps, m, n: 0.5
    )
    monkeypatch.setattr(
        "midgpt_tpu.utils.metrics.flops_per_token", lambda m: 1e9
    )
    # the CPU has no peak FLOP/s; the floors need one, so the test names
    # the chip it wants them against
    monkeypatch.setattr(
        "midgpt_tpu.utils.metrics.device_peak_flops", lambda: 197e12
    )

    bench.main()
    out = capsys.readouterr().out
    lines = [l for l in out.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, out
    rec = json.loads(lines[0])
    # every rung family present in the single record
    assert rec["metric"].startswith("openwebtext_xl_family")
    assert "gpt2s_mfu" in rec
    assert "llama_mfu" in rec
    assert "long_ctx_mfu" in rec
    assert rec["measure"] == "chained"
    assert rec["status"] == "ok"
    # PR 15 contract: the headline + gpt2s rungs carry the static
    # roofline floors and attainment next to their MFU (the ledger's
    # static-key gating and the "self-interpreting r6 rows" promise
    # both read these by name)
    for prefix in ("", "gpt2s_"):
        assert rec[prefix + "train_compute_floor_ms"] > 0
        assert rec[prefix + "train_hbm_floor_ms"] > 0
        assert rec[prefix + "train_attainment_frac"] > 0


def test_emit_bench_error_carries_flight_dump_in_band(tmp_path, capsys):
    """Watchdog/error rows carry the rung-lifecycle flight-dump path
    in-band when telemetry is armed — the r4/r5 wedged-run lesson
    applied to the training bench (bench_serving's rows already do
    this)."""
    sys.path.insert(0, REPO)
    import bench
    from midgpt_tpu.train_telemetry import TrainTelemetry

    tele = TrainTelemetry()
    tele.emit("run_start", step=0, t=0.0)
    tele.emit("rung_start", step=1, t=1.0, rung="xl_L8_B12")
    old = dict(bench._FLIGHT)
    try:
        bench._FLIGHT.update(tele=tele, dir=str(tmp_path))
        bench._emit_bench_error("no rung completed", status="watchdog")
    finally:
        bench._FLIGHT.update(old)
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["metric"] == "bench_error"
    assert rec["status"] == "watchdog"
    assert rec["flight_recorder"], "dump path must ride in-band"
    dump = json.load(open(rec["flight_recorder"][0]))
    assert dump["reason"] == "bench:watchdog"
    assert [e["kind"] for e in dump["telemetry"]["events"]] == [
        "run_start", "rung_start",
    ]
    # without telemetry the row stays a bare (but valid) error record
    try:
        bench._FLIGHT.update(tele=None, dir=None)
        bench._emit_bench_error("boom")
    finally:
        bench._FLIGHT.update(old)
    rec2 = json.loads(capsys.readouterr().out.strip())
    assert "flight_recorder" not in rec2
