"""Perf-trajectory ledger (midgpt_tpu.analysis.ledger + the --ledger
CLI): trajectory ingestion, the static/wall-clock gating split,
watchdog-row exclusion, the key-inventory gate, the markdown trend
report, suite-timing ingestion — and the two acceptance gates: the CLI
exits NONZERO on a doctored regression record and GREEN on a
trajectory shaped like the five rounds this repo once shipped (three
measured rounds, then two deadline rows).

jax-free module: these tests run in milliseconds.
"""

import json
import os

import pytest

from midgpt_tpu.analysis.__main__ import main
from midgpt_tpu.analysis.ledger import (
    Row,
    diff_record,
    load_trajectory,
    markdown_report,
    parse_multichip_record,
    row_hardware,
    row_kind,
    row_ok,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Fixture trajectories
# ---------------------------------------------------------------------------

_HW_TRAIN = {
    "metric": "openwebtext_xl_family_L6_train_mfu",
    "value": 0.60,
    "unit": "fraction_of_peak",
    "vs_baseline": 1.25,
    "tokens_per_sec_per_chip": 48000.0,
    "step_ms": 340.0,
    "device": "TPU v5 lite",
    "n_devices": 1,
    "model_flops_per_token": 2.5e9,
    "gpt2s_metric": "openwebtext_124m_train_mfu",
    "gpt2s_mfu": 0.40,
    "status": "ok",
}

_SERVE = {
    "device": "TPU v5 lite",
    "status": "ok",
    "serve_shape": "124m S=8 K=8",
    "serve_tok_s": 1100.0,
    "serve_ms_per_tok": 0.9,
    "serve_bytes_per_token_static": 33000000,
    "serve_hbm_floor_ms_static": 0.33,
    "serve_floor_ms_per_tok_static": 0.041,
    "serve_attainment_frac": 0.046,
    "serve_mfu": 0.01,
    "serve_goodput_slo_tok_s": 1000.0,
}


def _write_trajectory(tmp_path, records):
    d = tmp_path / "traj"
    d.mkdir(exist_ok=True)
    for i, rec in enumerate(records, start=1):
        (d / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"n": i, "rc": 0, "parsed": rec})
        )
    return str(d)


def _write_record(tmp_path, rec, name="current.json"):
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return str(p)


# ---------------------------------------------------------------------------
# Row classification
# ---------------------------------------------------------------------------


def test_row_classification():
    assert row_kind(_HW_TRAIN) == "train"
    assert row_kind(_SERVE) == "serving"
    assert row_kind({"kind": "suite", "suite_total_call_s": 100}) == "suite"
    assert row_ok(_HW_TRAIN)
    assert not row_ok({"metric": "bench_error", "status": "error"})
    assert not row_ok({**_HW_TRAIN, "status": "watchdog"})
    assert not row_ok({**_HW_TRAIN, "partial": True})
    assert row_hardware(_HW_TRAIN)
    assert not row_hardware({**_HW_TRAIN, "device": "cpu"})


# ---------------------------------------------------------------------------
# Gating semantics (library level)
# ---------------------------------------------------------------------------


def _rows(*recs):
    return [Row(f"r{i}", i, rec) for i, rec in enumerate(recs, start=1)]


def test_hardware_wallclock_regression_is_hard():
    cur = {**_HW_TRAIN, "value": 0.40}  # -33% MFU
    findings = diff_record(cur, _rows(_HW_TRAIN))
    hard = [f for f in findings if f.severity == "hard"]
    assert any(f.key == "value" for f in hard)


def test_cpu_wallclock_regression_is_informational():
    cur = {**_HW_TRAIN, "device": "cpu", "value": 0.40}
    findings = diff_record(cur, _rows({**_HW_TRAIN, "device": "cpu"}))
    assert findings and all(f.severity == "info" for f in findings)


def test_small_drift_inside_band_is_clean():
    cur = {**_HW_TRAIN, "value": 0.58}  # -3.3%: inside the 10% band
    assert diff_record(cur, _rows(_HW_TRAIN)) == []


def test_static_key_drift_is_hard_even_on_cpu():
    ref = {**_SERVE, "device": "cpu"}
    cur = {**ref, "serve_bytes_per_token_static": 34000000}
    findings = diff_record(cur, _rows(ref))
    assert any(
        f.severity == "hard" and f.key == "serve_bytes_per_token_static"
        for f in findings
    )


def test_headline_keys_compare_only_within_same_metric():
    # the rung ladder changed shape: value halves but the metric name
    # differs, so there is no comparable reference — clean
    cur = {**_HW_TRAIN, "metric": "openwebtext_124m_train_mfu",
           "value": 0.30, "model_flops_per_token": 8e8}
    assert diff_record(cur, _rows(_HW_TRAIN)) == []


def test_serving_rows_compare_only_within_same_shape():
    cur = {**_SERVE, "serve_shape": "124m S=16 K=8",
           "serve_tok_s": 500.0, "serve_bytes_per_token_static": 1}
    assert diff_record(cur, _rows(_SERVE)) == []


def test_watchdog_current_row_is_never_a_regression():
    cur = {**_HW_TRAIN, "status": "watchdog", "value": 0.0}
    findings = diff_record(cur, _rows(_HW_TRAIN))
    assert all(f.severity == "info" for f in findings)


def test_watchdog_rows_excluded_from_reference():
    wedge = {**_HW_TRAIN, "status": "watchdog", "value": 0.01}
    cur = dict(_HW_TRAIN)
    # the wedge row (newest) must NOT become the reference: comparing
    # 0.60 against 0.01 would report a huge "improvement"; comparing a
    # later regression against 0.01 would hide it
    findings = diff_record(
        {**cur, "value": 0.40}, _rows(_HW_TRAIN, wedge)
    )
    assert any(
        f.key == "value" and f.reference == 0.60 for f in findings
    )


def test_serving_inventory_shrink_is_hard():
    cur = dict(_SERVE)
    del cur["serve_goodput_slo_tok_s"]
    findings = diff_record(cur, _rows(_SERVE))
    assert any(
        f.severity == "hard" and f.key == "serve_goodput_slo_tok_s"
        for f in findings
    )


def test_train_inventory_shrink_is_informational():
    ref = {**_HW_TRAIN, "llama_mfu": 0.6, "llama_metric": "llama_L2"}
    cur = {**_HW_TRAIN, "llama_error": "OOM"}
    findings = diff_record(cur, _rows(ref))
    assert findings and all(f.severity == "info" for f in findings)


def test_markdown_report_renders_tables_and_findings():
    rows = _rows(_HW_TRAIN, _SERVE)
    findings = diff_record({**_HW_TRAIN, "value": 0.40}, rows)
    text = markdown_report(rows, [("cur.json", _HW_TRAIN)], findings)
    assert "## train trajectory" in text
    assert "## serving trajectory" in text
    assert "openwebtext_xl_family_L6_train_mfu" in text
    assert "## Findings" in text and "[hard] value" in text
    assert "**cur.json** (current)" in text


# ---------------------------------------------------------------------------
# CLI acceptance gates
# ---------------------------------------------------------------------------


_DEADLINE_ROW = {
    "metric": "bench_error", "value": 0, "unit": "none", "vs_baseline": 0,
    "status": "watchdog", "error": "backend init exceeded 600s",
}


def _five_round_trajectory(tmp_path):
    """The shape of the rounds this repo once shipped: r1-r3 measured
    and improving (r3 the best), r4/r5 deadline rows with no number."""
    return _write_trajectory(tmp_path, [
        {**_HW_TRAIN, "value": 0.40, "gpt2s_mfu": 0.30},
        {**_HW_TRAIN, "value": 0.55, "gpt2s_mfu": 0.38},
        _HW_TRAIN,
        _DEADLINE_ROW,
        _DEADLINE_ROW,
    ])


def test_cli_green_on_shipped_trajectory(tmp_path, capsys):
    """Acceptance: `python -m midgpt_tpu.analysis --ledger` over such a
    trajectory is green — the r4/r5 deadline rows are not regressions,
    and r3 holds the trajectory's best numbers."""
    rc = main(["--ledger", "--trajectory", _five_round_trajectory(tmp_path)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["ok"] is True
    assert out["trajectory_rows"] >= 5
    # the self-check picked a real OK row, not a deadline row
    assert "BENCH_r03" in out["records"][0]


def test_cli_nonzero_on_injected_regression(tmp_path, capsys):
    """Acceptance: a doctored record (hardware row, gpt2s MFU down 30%)
    exits nonzero with the finding on stderr and in the report."""
    traj = _write_trajectory(tmp_path, [_HW_TRAIN])
    bad = _write_record(
        tmp_path, {**_HW_TRAIN, "gpt2s_mfu": 0.28}, "doctored.json"
    )
    report = str(tmp_path / "report.md")
    rc = main([
        "--ledger", "--trajectory", traj, "--record", bad,
        "--report", report,
    ])
    captured = capsys.readouterr()
    assert rc == 1
    assert json.loads(captured.out)["hard"] >= 1
    assert "gpt2s_mfu" in captured.err
    assert "[hard] gpt2s_mfu" in open(report).read()


def test_cli_green_on_faithful_record(tmp_path, capsys):
    traj = _write_trajectory(tmp_path, [_HW_TRAIN])
    good = _write_record(
        tmp_path, {**_HW_TRAIN, "value": 0.61}, "good.json"
    )
    rc = main(["--ledger", "--trajectory", traj, "--record", good])
    assert rc == 0
    capsys.readouterr()


def test_cli_static_regression_in_record_dir_reference(tmp_path, capsys):
    """Bench record dirs ingest as reference rows: a current serving
    record whose static bytes drifted against the archived row fails."""
    traj = _write_trajectory(tmp_path, [_HW_TRAIN])
    d = tmp_path / "records"
    d.mkdir()
    (d / "serving_a.json").write_text(
        json.dumps({**_SERVE, "device": "cpu"})
    )
    cur = _write_record(
        tmp_path,
        {**_SERVE, "device": "cpu", "serve_bytes_per_token_static": 1},
        "cur.json",
    )
    rc = main([
        "--ledger", "--trajectory", traj, "--records-dir", str(d),
        "--record", cur,
    ])
    assert rc == 1
    capsys.readouterr()


def test_cli_hardware_override_gates_cpu_rows(tmp_path, capsys):
    """--hardware on turns a CPU wall-clock drop into a hard gate."""
    traj = _write_trajectory(
        tmp_path, [{**_HW_TRAIN, "device": "cpu"}]
    )
    bad = _write_record(
        tmp_path, {**_HW_TRAIN, "device": "cpu", "value": 0.40}
    )
    assert main([
        "--ledger", "--trajectory", traj, "--record", bad,
    ]) == 0
    capsys.readouterr()
    assert main([
        "--ledger", "--trajectory", traj, "--record", bad,
        "--hardware", "on",
    ]) == 1
    capsys.readouterr()


def test_cli_suite_timing_ingested(tmp_path, capsys):
    traj = _write_trajectory(tmp_path, [_HW_TRAIN])
    st = tmp_path / "suite_timing.json"
    st.write_text(json.dumps({
        "kind": "suite", "suite_total_call_s": 431.5,
        "suite_n_calls": 415,
        "slowest": [{"nodeid": "tests/test_x.py::t", "s": 19.0}],
    }))
    report = str(tmp_path / "report.md")
    rc = main([
        "--ledger", "--trajectory", traj, "--suite-timing", str(st),
        "--record", _write_record(tmp_path, _HW_TRAIN),
        "--report", report,
    ])
    assert rc == 0
    assert "## suite trajectory" in open(report).read()
    assert "431.5" in open(report).read()
    capsys.readouterr()


def test_load_trajectory_orders_and_tolerates_junk(tmp_path):
    traj = _write_trajectory(tmp_path, [_HW_TRAIN, _SERVE])
    (tmp_path / "traj" / "BENCH_r10.json").write_text("not json {")
    rows = load_trajectory(str(tmp_path / "traj"))
    assert [r.index for r in rows] == [1, 2]
    d = tmp_path / "extra"
    d.mkdir()
    (d / "a.json").write_text(json.dumps(_SERVE))
    rows = load_trajectory(str(tmp_path / "traj"), [str(d)])
    assert len(rows) == 3 and rows[-1].index == 3


def test_suite_timing_artifact_from_conftest_schema(tmp_path):
    """The conftest SUITE_TIMING_OUT artifact parses as a ledger suite
    row (schema lockstep between the two sides)."""
    import subprocess
    import sys

    out = str(tmp_path / "suite.json")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", SUITE_TIMING_OUT=out,
    )
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/test_ledger.py::test_row_classification", "-q",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:]
    rec = json.load(open(out))
    assert rec["kind"] == "suite"
    assert row_kind(rec) == "suite"
    assert rec["suite_n_calls"] >= 1
    assert rec["suite_total_call_s"] >= 0
    assert rec["slowest"]


def test_train_rows_compare_only_within_same_device_population():
    """The static train floors embed peak FLOPs and chip count: a CPU
    smoke row must never hard-gate a TPU round's floors (code review
    PR 15) — different device/n_devices means no comparison at all."""
    ref = {**_HW_TRAIN, "train_hbm_floor_ms": 0.5,
           "train_compute_floor_ms": 1.0}
    cur = {**ref, "device": "cpu", "n_devices": 8,
           "train_hbm_floor_ms": 99.0, "value": 0.01}
    assert diff_record(cur, _rows(ref)) == []


def test_serving_rows_compare_only_at_same_offered_load():
    """serve_shape omits --rate/--requests; two rungs at different
    offered loads legitimately differ several-fold on wall-clock keys
    and must not gate each other (code review PR 15)."""
    ref = {**_SERVE, "serve_rate_req_s": 8.0, "serve_requests": 64}
    cur = {**ref, "serve_rate_req_s": 2.0, "serve_tok_s": 300.0,
           "serve_ms_per_tok": 4.0}
    assert diff_record(cur, _rows(ref)) == []
    # same load: the regression IS gated
    same = {**ref, "serve_tok_s": 300.0}
    assert any(
        f.key == "serve_tok_s" and f.severity == "hard"
        for f in diff_record(same, _rows(ref))
    )


# ---------------------------------------------------------------------------
# MULTICHIP ingestion
# ---------------------------------------------------------------------------

_MULTICHIP_RAW = {
    "n_devices": 8,
    "rc": 0,
    "ok": True,
    "skipped": False,
    "tail": (
        "dryrun_multichip(8): mesh {'replica': 1, 'fsdp': 2}, "
        "loss=6.0479 OK\n"
        "dryrun multi-slice (2 slices over DCN, mesh {'replica': 2}): "
        "loss=6.0844 OK\n"
        "dryrun GPT pipeline (4 stages): loss=5.9629 (matches non-PP "
        "5.9631, diff 2.0e-04) OK\n"
        "dryrun pipeline(4 stages): loss=330.5806 OK\n"
    ),
}


def test_multichip_record_parses_tail_losses():
    rec = parse_multichip_record(_MULTICHIP_RAW)
    assert row_kind(rec) == "multichip"
    assert row_ok(rec)
    assert rec["n_devices"] == 8
    assert rec["multichip_mesh_loss"] == pytest.approx(6.0479)
    assert rec["multichip_multi_slice_loss"] == pytest.approx(6.0844)
    # "GPT pipeline" and the seed-sum "pipeline" line are distinct keys
    assert rec["multichip_gpt_pipeline_loss"] == pytest.approx(5.9629)
    assert rec["multichip_pipeline_loss"] == pytest.approx(330.5806)


def test_multichip_wedge_row_excluded():
    """A non-ok/skipped wrapper is a wedge (status='error'), excluded
    from the reference exactly like the r4/r5 BENCH watchdog rows."""
    rec = parse_multichip_record({**_MULTICHIP_RAW, "ok": False, "rc": 1})
    assert not row_ok(rec)
    rec = parse_multichip_record({**_MULTICHIP_RAW, "skipped": True})
    assert not row_ok(rec)


def test_multichip_loss_drift_is_hard_static():
    ref = parse_multichip_record(_MULTICHIP_RAW)
    cur = {**ref, "multichip_multi_slice_loss": 6.5}  # ~7% drift
    findings = diff_record(cur, _rows(ref))
    assert any(
        f.severity == "hard" and f.key == "multichip_multi_slice_loss"
        for f in findings
    )
    # inside the 5% band: clean
    near = {**ref, "multichip_multi_slice_loss": 6.10}
    assert diff_record(near, _rows(ref)) == []


def test_multichip_rows_compare_only_within_same_device_count():
    ref = parse_multichip_record(_MULTICHIP_RAW)
    cur = {**ref, "n_devices": 4, "multichip_mesh_loss": 99.0}
    assert diff_record(cur, _rows(ref)) == []


def test_multichip_inventory_shrink_is_hard():
    ref = parse_multichip_record(_MULTICHIP_RAW)
    cur = dict(ref)
    del cur["multichip_gpt_pipeline_loss"]
    findings = diff_record(cur, _rows(ref))
    assert any(
        f.severity == "hard" and f.key == "multichip_gpt_pipeline_loss"
        for f in findings
    )


def test_load_trajectory_ingests_multichip_rounds(tmp_path):
    traj = _write_trajectory(tmp_path, [_HW_TRAIN])
    d = tmp_path / "traj"
    (d / "MULTICHIP_r01.json").write_text(json.dumps(_MULTICHIP_RAW))
    (d / "MULTICHIP_r02.json").write_text(
        json.dumps({**_MULTICHIP_RAW, "ok": False, "rc": 1})
    )
    rows = load_trajectory(str(d))
    kinds = [row_kind(r.record) for r in rows]
    assert kinds == ["train", "multichip", "multichip"]
    # indices continue past the BENCH rounds, in round order
    assert [r.index for r in rows] == [1, 2, 3]
    assert row_ok(rows[1].record) and not row_ok(rows[2].record)


def test_cli_self_check_covers_multichip_family(tmp_path, capsys):
    """Acceptance: MULTICHIP_r*.json rounds join the trajectory, the
    per-kind self-check diffs the newest OK multichip round against its
    predecessors, and the whole ledger stays green (train's newest OK
    row stays the FIRST record — BENCH_r03)."""
    traj = _five_round_trajectory(tmp_path)
    for i in range(1, 6):
        (tmp_path / "traj" / f"MULTICHIP_r{i:02d}.json").write_text(
            json.dumps(_MULTICHIP_RAW)
        )
    rc = main(["--ledger", "--trajectory", traj])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["ok"] is True
    assert out["trajectory_rows"] >= 10
    assert "BENCH_r03" in out["records"][0]
    assert any("MULTICHIP_r05" in r for r in out["records"])


def test_multichip_trend_section_in_report(tmp_path, capsys):
    traj = _write_trajectory(tmp_path, [_HW_TRAIN])
    d = tmp_path / "traj"
    (d / "MULTICHIP_r01.json").write_text(json.dumps(_MULTICHIP_RAW))
    report = str(tmp_path / "report.md")
    rc = main([
        "--ledger", "--trajectory", str(d),
        "--record", _write_record(tmp_path, _HW_TRAIN),
        "--report", report,
    ])
    assert rc == 0
    text = open(report).read()
    assert "## multichip trajectory" in text
    assert "6.084" in text  # multichip_multi_slice_loss column
    capsys.readouterr()
