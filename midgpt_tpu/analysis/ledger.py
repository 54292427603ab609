"""Perf-trajectory ledger: the regression gate over BENCH_*.json rows
and bench record files.

The repo accumulates one machine-written performance record per
hardware round (``BENCH_r*.json``) plus per-run bench records
(``bench.py`` / ``scripts/bench_serving.py`` JSON rows), but until this
module nothing *related* successive records: a silent MFU cliff, a
byte-stream regression that moved the static floor, or a tier-1 suite
quietly doubling its wall time would ride into the trajectory unread —
the exact blindness that let the r4/r5 wedged rounds sit undiagnosed.
The ledger ingests the trajectory, diffs a current record against it
with per-key tolerance bands, renders a markdown trend report, and
exits nonzero on regression (``python -m midgpt_tpu.analysis
--ledger``; the ``perf-ledger`` CI job drives it over a CPU bench run).

Gating policy (the heart of the module):

- **Static keys** — bytes/token, the HBM/compute floors, the dispatch
  launch structure, flops-per-token — are *compiled-in arithmetic*:
  they may not drift between records of the same geometry at all, on
  any backend. Violations are HARD (exit nonzero) everywhere.
- **Wall-clock keys** — MFU, tok/s, goodput, latency percentiles — are
  measurements: gated HARD on hardware rows (``device`` names a TPU),
  but only *informational* on CPU rows, where the numbers are
  noise-dominated by design (the CI job runs on shared runners).
- **Row status** is respected: ``watchdog`` / ``error`` / ``partial``
  rows (the r4/r5 wedges) are excluded from the reference trajectory
  and never gated as regressions — a hardware wedge is a wedge, not a
  perf cliff, which is the whole reason bench rows carry ``status``.
- **Key inventory**: a serving record silently *losing* keys its
  predecessor carried is itself a hard finding (the record-schema twin
  of the pinned ``ENGINE_STATS_KEYS`` contract); train records only
  warn, because a failed auxiliary rung legitimately drops its family
  (and says so via the ``*_error`` key).
- Comparisons only happen between *comparable* rows: serving records
  must share ``serve_shape``, train headline keys must share
  ``metric`` (the rung ladder changes shape between rounds), prefixed
  families (``gpt2s_``, ``llama_``, ...) match on their own ``*_metric``
  keys. The reference for each key is the most recent comparable OK row
  that carries it.

jax-free by construction (it runs in CI next to records, never on a
device).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import typing as tp

__all__ = [
    "BANDS",
    "Band",
    "Finding",
    "Row",
    "diff_record",
    "load_record",
    "load_suite_timing",
    "load_trajectory",
    "markdown_report",
    "parse_multichip_record",
    "row_hardware",
    "row_kind",
    "row_ok",
]


# ---------------------------------------------------------------------------
# Rows
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Row:
    """One trajectory entry: ``record`` is the parsed bench row,
    ``source`` where it came from, ``index`` its ordering key (the
    BENCH round number, then file order for ingested record dirs)."""

    source: str
    index: int
    record: tp.Mapping[str, tp.Any]


def row_kind(rec: tp.Mapping[str, tp.Any]) -> str:
    if rec.get("kind") == "multichip":
        return "multichip"
    if "serve_shape" in rec:
        return "serving"
    if rec.get("kind") == "suite" or "suite_total_call_s" in rec:
        return "suite"
    return "train"


def row_ok(rec: tp.Mapping[str, tp.Any]) -> bool:
    """Gateable rows only: watchdog/error/partial rows (hardware
    wedges, the r4/r5 class) are neither references nor regressions."""
    return (
        rec.get("status", "ok") == "ok"
        and rec.get("metric") != "bench_error"
        and not rec.get("partial")
    )


def row_hardware(rec: tp.Mapping[str, tp.Any]) -> bool:
    return "tpu" in str(rec.get("device", "")).lower()


def load_record(path: str) -> tp.Dict[str, tp.Any]:
    """One bench record: a raw bench/bench_serving JSON row, or a
    BENCH_r*.json driver wrapper (whose row sits under ``parsed``)."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and "parsed" in data and isinstance(
        data["parsed"], dict
    ):
        return data["parsed"]
    return data


_BENCH_RE = re.compile(r"BENCH_r(\d+)\.json$")
_MULTICHIP_RE = re.compile(r"MULTICHIP_r(\d+)\.json$")

#: tail-line prefixes of the multichip dryrun driver, mapped to the
#: ledger key tag each loss lands under. Order matters: more specific
#: prefixes first ("dryrun GPT pipeline" before "dryrun pipeline").
_MULTICHIP_LINE_TAGS: tp.Tuple[tp.Tuple[str, str], ...] = (
    ("dryrun_multichip", "mesh"),
    ("dryrun fused attention", "fused_attention"),
    ("dryrun MoE expert parallelism", "moe"),
    ("dryrun ring attention", "ring_attention"),
    ("dryrun ulysses", "ulysses"),
    ("dryrun multi-slice", "multi_slice"),
    ("dryrun GPT pipeline", "gpt_pipeline"),
    ("dryrun pipeline", "pipeline"),
)

_MULTICHIP_LOSS_RE = re.compile(r"loss=([0-9][0-9.eE+-]*)")


def parse_multichip_record(
    raw: tp.Mapping[str, tp.Any],
) -> tp.Dict[str, tp.Any]:
    """A ``MULTICHIP_r*.json`` driver wrapper as a ledger row: the
    per-parallelism dryrun losses from the ``tail`` text become
    ``multichip_<tag>_loss`` keys (STATIC-banded — a loss that drifts
    between rounds on a fixed seed/geometry means a parallelism path
    changed numerics), ``n_devices`` is the population key, and a
    non-ok/skipped wrapper becomes a wedge row (``status='error'``,
    excluded from the reference like the r4/r5 BENCH wedges)."""
    ok = (
        bool(raw.get("ok"))
        and raw.get("rc", 1) == 0
        and not raw.get("skipped")
    )
    rec: tp.Dict[str, tp.Any] = {
        "kind": "multichip",
        "status": "ok" if ok else "error",
        "n_devices": raw.get("n_devices"),
    }
    for line in str(raw.get("tail", "")).splitlines():
        line = line.strip()
        if not line.endswith("OK"):
            continue
        m = _MULTICHIP_LOSS_RE.search(line)
        if not m:
            continue
        for prefix, tag in _MULTICHIP_LINE_TAGS:
            if line.startswith(prefix):
                rec[f"multichip_{tag}_loss"] = float(m.group(1))
                break
    return rec


def load_trajectory(
    root: str, record_dirs: tp.Sequence[str] = (),
) -> tp.List[Row]:
    """The reference trajectory: every ``BENCH_r*.json`` under ``root``
    (ordered by round number), then every ``MULTICHIP_r*.json`` (round
    order, indices continuing past the BENCH rounds), then every
    ``*.json`` bench record in ``record_dirs`` (file order) — the r6
    queue's per-rung records and CI-archived rows ingest this way."""
    rows: tp.List[Row] = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = _BENCH_RE.search(path)
        if not m:
            continue
        try:
            rows.append(Row(path, int(m.group(1)), load_record(path)))
        except (json.JSONDecodeError, OSError):
            continue
    rows.sort(key=lambda r: r.index)
    nxt = (rows[-1].index + 1) if rows else 0
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json"))):
        if not _MULTICHIP_RE.search(path):
            continue
        try:
            with open(path) as f:
                raw = json.load(f)
        except (json.JSONDecodeError, OSError):
            continue
        if not isinstance(raw, dict):
            continue
        rows.append(Row(path, nxt, parse_multichip_record(raw)))
        nxt += 1
    for d in record_dirs:
        for path in sorted(glob.glob(os.path.join(d, "*.json"))):
            try:
                rec = load_record(path)
            except (json.JSONDecodeError, OSError):
                continue
            if not isinstance(rec, dict):
                continue
            rows.append(Row(path, nxt, rec))
            nxt += 1
    return rows


def load_suite_timing(path: str) -> tp.Dict[str, tp.Any]:
    """The conftest slowest-phase artifact (SUITE_TIMING_OUT), as a
    ledger row: tier-1 suite wall time tracked like any other metric."""
    with open(path) as f:
        rec = json.load(f)
    rec.setdefault("kind", "suite")
    return rec


# ---------------------------------------------------------------------------
# Bands
# ---------------------------------------------------------------------------

STATIC, HIGHER, LOWER = "static", "higher", "lower"


@dataclasses.dataclass(frozen=True)
class Band:
    """One key's gating policy. ``direction``: ``static`` (may not
    drift at all — hard everywhere), ``higher`` (higher is better;
    a drop beyond ``tol`` regresses), ``lower`` (vice versa).
    Wall-clock bands gate hard only on hardware rows."""

    direction: str
    tol: float


#: The per-key tolerance bands. Static keys are compiled-in arithmetic
#: (exact up to float rounding); throughput keys get 10%, latency
#: percentiles 25% (tail-noisy even on hardware).
BANDS: tp.Dict[str, Band] = {
    # --- static: serving byte/floor decomposition + launch structure --
    "serve_bytes_per_token_static": Band(STATIC, 1e-6),
    "serve_bytes_per_step_static": Band(STATIC, 1e-6),
    "serve_weights_bytes_per_step_static": Band(STATIC, 1e-6),
    "serve_kv_bytes_per_step_static": Band(STATIC, 1e-6),
    "serve_hbm_floor_ms_static": Band(STATIC, 1e-3),
    "serve_floor_ms_per_tok_static": Band(STATIC, 1e-3),
    "serve_static_launches_per_window": Band(STATIC, 0.0),
    "serve_static_inlined_layer_bodies": Band(STATIC, 0.0),
    "serve_static_host_transfers": Band(STATIC, 0.0),
    "serve_comms_bytes_per_dispatch": Band(STATIC, 1e-6),
    # --- static: training floors / FLOP accounting --------------------
    "model_flops_per_token": Band(STATIC, 1e-6),
    "train_hbm_floor_ms": Band(STATIC, 1e-3),
    "train_compute_floor_ms": Band(STATIC, 1e-3),
    # --- wall-clock: training throughput -------------------------------
    "value": Band(HIGHER, 0.10),
    "tokens_per_sec_per_chip": Band(HIGHER, 0.10),
    "train_attainment_frac": Band(HIGHER, 0.10),
    "gpt2s_mfu": Band(HIGHER, 0.10),
    "gpt2s_tokens_per_sec_per_chip": Band(HIGHER, 0.10),
    "llama_mfu": Band(HIGHER, 0.10),
    "llama_tokens_per_sec_per_chip": Band(HIGHER, 0.10),
    "long_ctx_mfu": Band(HIGHER, 0.10),
    "long_ctx8k_mfu": Band(HIGHER, 0.10),
    "decode_tok_s": Band(HIGHER, 0.15),
    "decode_prefill_tok_s": Band(HIGHER, 0.15),
    "decode_ms_per_tok": Band(LOWER, 0.15),
    "decode_attainment_frac": Band(HIGHER, 0.15),
    # --- wall-clock: serving throughput / latency ----------------------
    "serve_tok_s": Band(HIGHER, 0.10),
    "serve_goodput_tok_s": Band(HIGHER, 0.10),
    "serve_goodput_slo_tok_s": Band(HIGHER, 0.10),
    "serve_ms_per_tok": Band(LOWER, 0.10),
    "serve_attainment_frac": Band(HIGHER, 0.10),
    "serve_mfu": Band(HIGHER, 0.10),
    "serve_ttft_p99_ms": Band(LOWER, 0.25),
    "serve_tbt_p99_ms": Band(LOWER, 0.25),
    "serve_queue_delay_p99_ms": Band(LOWER, 0.25),
    # --- suite time (always informational: CI boxes vary) --------------
    "suite_total_call_s": Band(LOWER, 0.25),
    # --- static: multichip dryrun losses (fixed seed + geometry — a
    # drifting loss means a parallelism path changed numerics; the 5%
    # band absorbs cross-version RNG/layout noise, which measured at
    # most 0.64% across the shipped rounds) -----------------------------
    "multichip_mesh_loss": Band(STATIC, 0.05),
    "multichip_fused_attention_loss": Band(STATIC, 0.05),
    "multichip_moe_loss": Band(STATIC, 0.05),
    "multichip_ring_attention_loss": Band(STATIC, 0.05),
    "multichip_ulysses_loss": Band(STATIC, 0.05),
    "multichip_multi_slice_loss": Band(STATIC, 0.05),
    "multichip_gpt_pipeline_loss": Band(STATIC, 0.05),
    "multichip_pipeline_loss": Band(STATIC, 0.05),
}

#: Train headline keys that only compare between rows with the same
#: ``metric`` (the rung ladder legitimately changes shape per round).
_HEADLINE_KEYS = frozenset((
    "value", "tokens_per_sec_per_chip", "step_ms", "batch_per_chip",
    "model_flops_per_token", "train_hbm_floor_ms",
    "train_compute_floor_ms", "train_attainment_frac",
))

#: Prefixed train families match on their own ``<prefix>metric`` /
#: ``<prefix>shape`` key when both rows carry it.
_FAMILY_TAGS = (
    ("gpt2s_", "gpt2s_metric"),
    ("llama_", "llama_metric"),
    ("long_ctx_", "long_ctx_metric"),
    ("decode_", "decode_shape"),
)


#: Kinds whose key inventory is gated HARD, restricted to their own
#: prefix (losing a ``serve_``/``multichip_`` key is a schema break;
#: other keys on those rows are wrapper metadata).
_INVENTORY_PREFIXES = {"serving": "serve_", "multichip": "multichip_"}


def _same_population(
    kind: str,
    cur: tp.Mapping[str, tp.Any],
    ref: tp.Mapping[str, tp.Any],
) -> bool:
    """Row-level comparability: serving rows must share the geometry
    AND the offered load (serve_shape omits rate/request-count, and two
    rungs at different arrival rates legitimately differ several-fold
    on every wall-clock key); train rows must share the device + chip
    count (the static floors embed peak FLOPs and n_devices — a CPU
    smoke row must never hard-gate a TPU round's floors, or vice
    versa)."""
    if kind == "serving":
        return (
            cur.get("serve_shape") == ref.get("serve_shape")
            and cur.get("serve_rate_req_s") == ref.get("serve_rate_req_s")
            and cur.get("serve_requests") == ref.get("serve_requests")
        )
    if kind == "train":
        return (
            cur.get("device") == ref.get("device")
            and cur.get("n_devices") == ref.get("n_devices")
        )
    if kind == "multichip":
        # the dryrun losses depend on the virtual device pool (mesh
        # factorizations change with it) but not on the host device
        return cur.get("n_devices") == ref.get("n_devices")
    return True


def _comparable(
    kind: str,
    cur: tp.Mapping[str, tp.Any],
    ref: tp.Mapping[str, tp.Any],
    key: str,
) -> bool:
    if not _same_population(kind, cur, ref):
        return False
    if kind == "train":
        if key in _HEADLINE_KEYS:
            return cur.get("metric") == ref.get("metric")
        for prefix, tag in _FAMILY_TAGS:
            if key.startswith(prefix):
                a, b = cur.get(tag), ref.get(tag)
                return a is None or b is None or a == b
    return True


# ---------------------------------------------------------------------------
# Diffing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Finding:
    """One ledger observation. ``severity``: ``hard`` fails the gate;
    ``info`` rides the report only (CPU wall-clock drift, inventory
    warnings on train rows)."""

    severity: str
    key: str
    note: str
    current: tp.Optional[float] = None
    reference: tp.Optional[float] = None
    ref_source: tp.Optional[str] = None

    def __str__(self) -> str:
        vals = (
            f" (current {self.current!r} vs {self.reference!r}"
            f" from {self.ref_source})"
            if self.reference is not None else ""
        )
        return f"[{self.severity}] {self.key}: {self.note}{vals}"


def _find_ref(
    rows: tp.Sequence[Row],
    kind: str,
    cur: tp.Mapping[str, tp.Any],
    key: str,
) -> tp.Optional[Row]:
    for row in reversed(rows):
        rec = row.record
        if not row_ok(rec) or row_kind(rec) != kind:
            continue
        if rec.get(key) is None:
            continue
        if not _comparable(kind, cur, rec, key):
            continue
        return row
    return None


def diff_record(
    cur: tp.Mapping[str, tp.Any],
    rows: tp.Sequence[Row],
    *,
    hardware: tp.Optional[bool] = None,
) -> tp.List[Finding]:
    """Diff one record against the trajectory. ``hardware`` overrides
    the row's own device detection (the CI job pins CPU)."""
    if not row_ok(cur):
        return [Finding(
            "info", "status",
            f"non-ok row (status={cur.get('status', 'ok')!r}): a wedge "
            "is a wedge, not a regression — not gated",
        )]
    kind = row_kind(cur)
    hw = row_hardware(cur) if hardware is None else hardware
    findings: tp.List[Finding] = []

    for key, band in BANDS.items():
        cv = cur.get(key)
        if not isinstance(cv, (int, float)) or isinstance(cv, bool):
            continue
        ref = _find_ref(rows, kind, cur, key)
        if ref is None:
            continue
        rv = float(ref.record[key])
        cv = float(cv)
        scale = max(abs(rv), 1e-9)
        if band.direction == STATIC:
            if abs(cv - rv) > band.tol * scale + 1e-12:
                findings.append(Finding(
                    "hard", key,
                    "STATIC key drifted — compiled-in arithmetic "
                    "changed without a geometry change",
                    cv, rv, ref.source,
                ))
            continue
        frac = (rv - cv) / scale if band.direction == HIGHER else (
            (cv - rv) / scale
        )
        if frac > band.tol:
            sev = "hard" if hw else "info"
            findings.append(Finding(
                sev, key,
                f"regressed {frac:.1%} past the {band.tol:.0%} band"
                + ("" if hw else " (CPU row: informational)"),
                cv, rv, ref.source,
            ))

    # key-inventory gate: the record-schema twin of the pinned
    # ENGINE_STATS_KEYS contract
    prev = None
    for row in reversed(rows):
        if row_ok(row.record) and row_kind(row.record) == kind and (
            _same_population(kind, cur, row.record)
        ):
            prev = row
            break
    if prev is not None:
        # prefixed-inventory kinds gate hard on their own key family
        # (the record-schema contract); train/suite rows only warn — a
        # failed auxiliary rung legitimately drops its family
        prefix = _INVENTORY_PREFIXES.get(kind)
        lost = [
            k for k in prev.record
            if k not in cur and (prefix is None or k.startswith(prefix))
        ]
        for k in sorted(lost):
            findings.append(Finding(
                "hard" if prefix is not None else "info", k,
                f"key present in {prev.source} is missing from the "
                "current record (inventory shrank)",
            ))
    return findings


# ---------------------------------------------------------------------------
# Trend report
# ---------------------------------------------------------------------------

_TREND_COLUMNS = {
    "train": (
        "metric", "value", "gpt2s_mfu", "llama_mfu", "long_ctx_mfu",
        "decode_tok_s", "train_attainment_frac", "status",
    ),
    "serving": (
        "serve_tok_s", "serve_goodput_slo_tok_s", "serve_ms_per_tok",
        "serve_attainment_frac", "serve_mfu", "serve_hbm_floor_ms_static",
        "serve_bytes_per_token_static", "status",
    ),
    "suite": ("suite_total_call_s", "suite_n_calls", "status"),
    "multichip": (
        "n_devices", "multichip_mesh_loss", "multichip_multi_slice_loss",
        "multichip_gpt_pipeline_loss", "multichip_ring_attention_loss",
        "multichip_moe_loss", "status",
    ),
}


def _cell(v: tp.Any) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)[:40]


def markdown_report(
    rows: tp.Sequence[Row],
    current: tp.Sequence[tp.Tuple[str, tp.Mapping[str, tp.Any]]] = (),
    findings: tp.Sequence[Finding] = (),
) -> str:
    """The trend report the ``perf-ledger`` CI job uploads: one table
    per row kind over the trajectory (+ the current records, marked),
    then the findings, hard first."""
    out = ["# Perf-trajectory ledger", ""]
    by_kind: tp.Dict[str, tp.List[tp.Tuple[str, tp.Mapping]]] = {}
    for row in rows:
        by_kind.setdefault(row_kind(row.record), []).append(
            (os.path.basename(row.source), row.record)
        )
    for name, rec in current:
        by_kind.setdefault(row_kind(rec), []).append(
            (f"**{os.path.basename(name)}** (current)", rec)
        )
    for kind in ("train", "serving", "suite", "multichip"):
        entries = by_kind.get(kind)
        if not entries:
            continue
        cols = _TREND_COLUMNS[kind]
        out.append(f"## {kind} trajectory")
        out.append("")
        out.append("| source | " + " | ".join(cols) + " |")
        out.append("|---" * (len(cols) + 1) + "|")
        for src, rec in entries:
            status = (
                "ok" if row_ok(rec) else rec.get("status", "error")
            )
            vals = [
                _cell(status if c == "status" else rec.get(c))
                for c in cols
            ]
            out.append(f"| {src} | " + " | ".join(vals) + " |")
        out.append("")
    out.append("## Findings")
    out.append("")
    ordered = sorted(findings, key=lambda f: f.severity != "hard")
    if not ordered:
        out.append("No findings — trajectory clean.")
    for f in ordered:
        out.append(f"- {f}")
    out.append("")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# CLI driver (python -m midgpt_tpu.analysis --ledger)
# ---------------------------------------------------------------------------


def run_ledger(
    *,
    trajectory_root: str,
    records: tp.Sequence[str] = (),
    record_dirs: tp.Sequence[str] = (),
    suite_timing: tp.Optional[str] = None,
    report_path: tp.Optional[str] = None,
    hardware: tp.Optional[bool] = None,
) -> int:
    """The --ledger entry point. With ``records``: diff each against
    the trajectory (+ ingested record dirs). Without: self-check the
    trajectory — its most recent OK row is diffed against the rows
    before it (how CI keeps the shipped BENCH_r*.json green). Returns
    the exit code (1 on any hard finding)."""
    rows = load_trajectory(trajectory_root, record_dirs)
    if suite_timing:
        rows.append(Row(
            suite_timing,
            (rows[-1].index + 1) if rows else 0,
            load_suite_timing(suite_timing),
        ))

    current: tp.List[tp.Tuple[str, tp.Mapping[str, tp.Any]]] = []
    findings: tp.List[Finding] = []
    if records:
        for path in records:
            rec = load_record(path)
            current.append((path, rec))
            findings.extend(
                diff_record(rec, rows, hardware=hardware)
            )
    else:
        # self-check mode: the newest OK row OF EACH KIND vs everything
        # before it — the trajectory now ships several families (train
        # BENCH rounds, MULTICHIP rounds, ingested serving/suite rows),
        # and a single global "latest" would leave every other family's
        # shipped rows unchecked
        for kind in ("train", "serving", "suite", "multichip"):
            ok_rows = [
                r for r in rows
                if row_ok(r.record) and row_kind(r.record) == kind
            ]
            if not ok_rows:
                continue
            last = ok_rows[-1]
            before = [r for r in rows if r.index < last.index]
            current.append((f"{last.source} (self-check)", last.record))
            findings.extend(
                diff_record(last.record, before, hardware=hardware)
            )

    text = markdown_report(rows, current, findings)
    if report_path:
        with open(report_path, "w") as f:
            f.write(text + "\n")
    hard = [f for f in findings if f.severity == "hard"]
    summary = {
        "mode": "ledger",
        "trajectory_rows": len(rows),
        "records": [name for name, _ in current],
        "findings": len(findings),
        "hard": len(hard),
        "ok": not hard,
        "report": report_path,
    }
    print(json.dumps(summary, indent=2))
    import sys

    for f in findings:
        print(f"LEDGER {f}", file=sys.stderr)
    return 1 if hard else 0
