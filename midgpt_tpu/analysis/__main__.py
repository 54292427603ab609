"""CLI: compile a named config's real train step and audit it.

    python -m midgpt_tpu.analysis --config openwebtext_xl --mesh 8
    python -m midgpt_tpu.analysis --lint [paths...]

The audit mode compiles the config's donated train step on a CPU virtual
mesh (``--mesh N`` devices; no TPU needed), evaluates the config's
sharding-invariant ruleset, and prints one JSON report (rules + comms
cost). Exit status: 0 = all rules pass, 1 = violations (or unwaived lint
findings), 2 = usage error.

``--override-logical-rule name=axes`` rewrites one entry of the
activation logical-rule table before compiling — ``batch=`` (empty =
unsharded) reproduces the opaque-boundary batch-gather trap, which is
how the test suite proves the audit fails loudly.

``--steps-per-dispatch K`` audits the fused K-step window program
(train.make_train_window) instead of the per-step jit — the CI gate that
catches donation-across-the-window (or host-callback) regressions on a
CPU mesh instead of a TPU run.

Platform note: env setup must precede the first jax import, which is why
this module parses args and sets ``JAX_PLATFORMS``/``XLA_FLAGS`` before
touching the harness.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import typing as tp


def _parse_override(spec: str) -> tp.Tuple[str, tp.Any]:
    if "=" not in spec:
        raise argparse.ArgumentTypeError(
            f"expected name=axes (axes may be empty or '+'-joined): {spec!r}"
        )
    name, axes = spec.split("=", 1)
    if not axes:
        return name, None
    parts = axes.split("+")
    return name, parts[0] if len(parts) == 1 else tuple(parts)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m midgpt_tpu.analysis",
        description="static HLO/sharding audit of a config's train step",
    )
    p.add_argument("--config", help="named config (midgpt_tpu.get_config)")
    p.add_argument(
        "--mesh", type=int, default=8, metavar="N",
        help="CPU virtual device count to compile on (default 8)",
    )
    p.add_argument(
        "--platform", default="cpu", choices=("cpu", "tpu"),
        help="backend to compile on (default cpu: no hardware needed)",
    )
    p.add_argument(
        "--no-shrink", action="store_true",
        help="compile the config at full size instead of audit size",
    )
    p.add_argument(
        "--steps-per-dispatch", type=int, default=None, metavar="K",
        help="audit the fused K-step window program (train.make_train_window)"
        " instead of the per-step jit; default: the config's own value",
    )
    p.add_argument(
        "--override-logical-rule", action="append", default=[],
        type=_parse_override, metavar="NAME=AXES",
        help="rewrite an activation logical-rule entry before compiling "
        "(e.g. batch= to inject the batch-gather trap)",
    )
    p.add_argument(
        "--json", metavar="PATH",
        help="also write the JSON report to PATH",
    )
    p.add_argument(
        "--full", action="store_true",
        help="include the per-collective listing in the report",
    )
    p.add_argument(
        "--lint", nargs="*", metavar="PATH",
        help="run the AST TPU-footgun lint instead of the HLO audit "
        "(default path: the midgpt_tpu package)",
    )
    p.add_argument(
        "--serving", action="store_true",
        help="audit the serving engine's three hot-path programs "
        "(midgpt_tpu.serving) instead of the train step: the fused "
        "K-step DECODE window, the suffix-prefill CHUNK, and the "
        "speculative VERIFY program — donation must stay intact (KV "
        "pool + logits alias input->output) and no host sync may hide "
        "inside any of them; each program is then compiled AGAIN on "
        "the int8 quantized weight path (midgpt_tpu.quant) and must "
        "additionally pass no-dequant-materialization (int8 streams "
        "as s8 entry params, dequant fused into each matmul); "
        "--steps-per-dispatch sets K (default 4)",
    )
    p.add_argument(
        "--serving-slots", type=int, default=4, metavar="S",
        help="decode slots for the serving audit (default 4)",
    )
    p.add_argument(
        "--serving-page-size", type=int, default=16, metavar="P",
        help="KV page size for the serving audit (default 16)",
    )
    p.add_argument(
        "--serving-spec-len", type=int, default=4, metavar="N",
        help="draft length for the speculative verify-program audit "
        "(default 4)",
    )
    p.add_argument(
        "--serving-spec-sampled", action="store_true",
        help="with --serving: audit the speculative verify program a "
        "SECOND time at temperature 0.8 / top_k 20 — the rejection-"
        "sampling acceptance path (engine.py). The donation and "
        "no-host-sync rules apply unchanged, and with --traffic the "
        "sampled program gates against the SAME verify_program budget "
        "cells: the sampled wrapper appends only the per-slot seeds "
        "and the PRNG key (control scalars) to the entry interface, "
        "so any dense draft-probability stream joining the dispatch "
        "trips the unclassified-float rule.",
    )
    p.add_argument(
        "--choreo", action="store_true",
        help="with --serving: run the arithmetic-choreography prover "
        "(analysis.choreo) over the three serving programs, bf16 AND "
        "int8 — trace each program to a jaxpr, normalize the attention "
        "/lm-head subgraphs into op-and-dtype traces, and prove verify "
        "mirrors decode op-for-op, the prefill chunk mirrors "
        "naive_attention's softmax core, and the shared arithmetic "
        "(f32 softmax/accumulation, mask-before-scale, one lm-head "
        "choreography) holds everywhere. Each cell is then proven "
        "AGAIN at temperature 0.8 / top_k 20 ('<cell>/sampled'): the "
        "verify program's row-0 sampler must mirror the decode "
        "window's categorical op-for-op, and the rejection-sampling "
        "acceptance compares / residual renormalization / target "
        "softmax must all run in f32. The machine check for the "
        "PR 4/PR 5 bf16 argmax-flip bug class, extended to the "
        "sampled acceptance rule.",
    )
    p.add_argument(
        "--traffic", action="store_true",
        help="with --serving: compute each compiled program's static "
        "HBM streams (weight/KV/logits/control entry parameters + "
        "baked-in constants + collective wire bytes, analysis.traffic) "
        "and gate them against the checked-in byte budgets "
        "(analysis.budgets) when the audit geometry matches. The "
        "accounting generalization of no-dequant-materialization: any "
        "regression that re-materializes, re-gathers or constant-folds "
        "a large buffer moves bytes and trips the gate.",
    )
    p.add_argument(
        "--print-budgets", action="store_true",
        help="with --serving --traffic: print the measured streams as "
        "a ready-to-paste analysis/budgets.py BUDGETS fragment "
        "(regeneration path after an intentional geometry change)",
    )
    p.add_argument(
        "--precision", choices=("bf16", "int8", "both"), default="both",
        help="which weight paths the serving audits compile (default "
        "both; the CI matrix runs one per job so a quant failure "
        "cannot mask a bf16 one)",
    )
    p.add_argument(
        "--kv-quant", choices=("off", "on", "both"), default="off",
        help="which KV-pool precisions the serving audits compile: "
        "'on' stores the paged pool int8 with per-(page, KV-head) po2 "
        "scales (serving.paged) — the budget cells gain a '-kv8' "
        "precision suffix and the KV stream must land at ~half its "
        "bf16 bytes; 'both' compiles each selected weight precision "
        "with the float AND the int8 pool (default off)",
    )
    p.add_argument(
        "--layer-scan", choices=("off", "on", "both"), default="off",
        help="which layer-loop modes the serving audits compile: 'on' "
        "builds the programs with the per-layer loop folded into one "
        "lax.scan (ServingEngine layer_scan knob, ROADMAP item 1); "
        "'both' compiles and audits each selected precision/kv cell "
        "both ways (the fused program streams the same bytes, so the "
        "same budget cells gate it)",
    )
    p.add_argument(
        "--prefill-sp", choices=("off", "on", "both"), default="off",
        help="which prefill-chunk sharding modes the serving audits "
        "compile: 'on' additionally audits the SEQUENCE-PARALLEL chunk "
        "program (ServingEngine prefill_sp knob — the chunk's "
        "replicated row segments shard over the 'tensor' axis) as its "
        "own 'prefill_chunk_sp' budget cells; needs --mesh-shape with "
        "tensor > 1. With --choreo the SP leg is proven per precision "
        "cell: the SP trace must equal the plain chunk trace op for op "
        "(resharding only, zero arithmetic change — the bitwise-"
        "identity gate). With --fusion the SP program's launch "
        "structure gates against its own DISPATCH_BUDGETS cells. "
        "'both' = audit off and on (default off)",
    )
    p.add_argument(
        "--fusion", action="store_true",
        help="run the SCAN-EQUIVALENCE prover (analysis.fusion) + the "
        "static dispatch/launch budgets (analysis.dispatch, "
        "budgets.DISPATCH_BUDGETS): trace the three serving programs "
        "with the layer loop unrolled AND folded, prove the unrolled "
        "layers homogeneous (the fold's legality precondition) and the "
        "fused scan body op-for-op equal to the per-layer trace, then "
        "gate launches-per-window / scan trip structure / inlined "
        "layer bodies / host transfers for BOTH layer_scan values. "
        "Tracing only — no compilation; the sixth audit family. Runs "
        "standalone (like --choreo) or inside --serving.",
    )
    p.add_argument(
        "--telemetry", choices=("off", "on"), default="off",
        help="with --serving (or standalone): run the telemetry-"
        "inertness proof (analysis.harness.prove_telemetry_inert) — "
        "two engines differing only in telemetry= must resolve to the "
        "IDENTICAL cached jitted callables (telemetry is not a program-"
        "factory parameter, so donation/no-host-sync/traffic/dispatch "
        "results proven for the untraced programs apply verbatim with "
        "tracing on) and produce bitwise-equal greedy streams, with "
        "events actually recorded. Runs on a fixed tiny model in "
        "seconds, decode-window and verify paths both.",
    )
    p.add_argument(
        "--ledger", action="store_true",
        help="run the perf-trajectory ledger (analysis.ledger) instead "
        "of an HLO audit: ingest the BENCH_r*.json trajectory (+ any "
        "--records-dir bench rows and the --suite-timing artifact), "
        "diff the --record file(s) — or, with none, the newest OK "
        "trajectory row — against it with per-key tolerance bands "
        "(static byte/floor/dispatch keys gated hard everywhere; "
        "wall-clock keys hard on hardware rows, informational on CPU), "
        "render the --report markdown trend table, and exit 1 on any "
        "hard regression. jax-free.",
    )
    p.add_argument(
        "--record", action="append", default=[], metavar="PATH",
        help="with --ledger: current bench record(s) to gate against "
        "the trajectory (bench.py / bench_serving.py JSON rows, or a "
        "BENCH_r*.json driver wrapper)",
    )
    p.add_argument(
        "--records-dir", action="append", default=[], metavar="DIR",
        help="with --ledger: directory of *.json bench records to "
        "ingest into the reference trajectory (file order, after the "
        "BENCH rounds)",
    )
    p.add_argument(
        "--trajectory", default=None, metavar="DIR",
        help="with --ledger: directory holding BENCH_r*.json "
        "(default: the repo root)",
    )
    p.add_argument(
        "--suite-timing", default=None, metavar="PATH",
        help="with --ledger: the conftest suite-timing JSON artifact "
        "(SUITE_TIMING_OUT) — tier-1 wall time joins the trend table",
    )
    p.add_argument(
        "--report", default=None, metavar="PATH",
        help="with --ledger: write the markdown trend report here",
    )
    p.add_argument(
        "--hardware", choices=("auto", "on", "off"), default="auto",
        help="with --ledger: gate wall-clock keys hard (on), "
        "informationally (off), or by the record's own device field "
        "(auto, the default)",
    )
    p.add_argument(
        "--train-audit", action="store_true",
        help="run the TRAIN-side verification suite (the seventh audit "
        "family) instead of the per-step HLO audit: trace the fused "
        "K-step train window through the trainer's own "
        "get_train_window cache and PROVE the mixed-precision "
        "choreography (bf16 matmul operands, f32 master params + Adam "
        "moments, f32 loss/softmax, compute-dtype grad-accum carry, "
        "remat recompute structure — analysis.train_choreo); compile "
        "the window and gate its ICI/DCN collective wire bytes against "
        "the checked-in per-geometry cells (budgets.TRAIN_BUDGETS); "
        "and gate the launch structure (one launch per window, "
        "grad-accum scan of trip G, zero host transfers, 100%% donation "
        "aliasing — budgets.TRAIN_DISPATCH_BUDGETS). Runs K=1 AND K=4 "
        "by default (--train-window-steps); the CI train-audit job "
        "fans the three --train-geometry values out as a matrix.",
    )
    p.add_argument(
        "--train-geometry", default="fsdp", metavar="G",
        choices=("fsdp", "tp_fsdp", "dcn2"),
        help="with --train-audit: the mesh geometry cell to audit "
        "(budgets.TRAIN_AUDIT_GEOMETRIES; all need --mesh 8): 'fsdp' = "
        "8-way FSDP, 'tp_fsdp' = tensor=2 x fsdp=4, 'dcn2' = 2 slices "
        "over DCN with fsdp=4 inside each (default fsdp)",
    )
    p.add_argument(
        "--train-window-steps", default="1,4", metavar="K[,K...]",
        help="with --train-audit: comma-separated fused-window lengths "
        "to audit (default '1,4' — the budget cells pin the two equal, "
        "which is itself the window-scan invariant)",
    )
    p.add_argument(
        "--mesh-shape", default=None, metavar="SPEC",
        help="serving-audit mesh, e.g. 'tp=2' or 'tp=2,replica=2' "
        "(keys: tp/tensor, dp/replica, fsdp): compile/audit the three "
        "serving programs TP-SHARDED — KV-head-sharded pool, "
        "column/row-parallel weights, vocab-sharded logits — adding "
        "the no-batch-allgather-in-page-gather rule; needs --mesh >= "
        "the axis product. --serving only.",
    )
    return p


def _run_lint(paths: tp.List[str]) -> int:
    from midgpt_tpu.analysis.pylint_pass import lint_paths, unwaived

    if not paths:
        paths = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    findings = lint_paths(paths)
    for f in findings:
        print(f)
    bad = unwaived(findings)
    n_waived = len(findings) - len(bad)
    print(
        f"shardlint: {len(bad)} finding(s), {n_waived} waived",
        file=sys.stderr,
    )
    return 1 if bad else 0


def _ensure_devices(platform: str, n: int) -> None:
    """Pin the backend + device count; must run before jax backend init.

    When jax is already initialized in-process (tests), just verify the
    existing device pool is big enough for the requested mesh.
    """
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = platform
        flags = os.environ.get("XLA_FLAGS", "")
        if (
            platform == "cpu"
            and "xla_force_host_platform_device_count" not in flags
        ):
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    import jax

    jax.config.update("jax_threefry_partitionable", True)  # train.py parity
    have = jax.device_count()
    if have < n:
        raise SystemExit(
            f"requested --mesh {n} but only {have} device(s) are visible "
            "(is jax already initialized with a smaller pool?)"
        )


def _precisions(args) -> tp.Tuple[str, ...]:
    return {
        "bf16": ("bf16",), "int8": ("int8",), "both": ("bf16", "int8"),
    }[args.precision]


def _kv_modes(args) -> tp.Tuple[bool, ...]:
    return {
        "off": (False,), "on": (True,), "both": (False, True),
    }[args.kv_quant]


def _layer_scan_modes(args) -> tp.Tuple[str, ...]:
    return {
        "off": ("off",), "on": ("on",), "both": ("off", "on"),
    }[args.layer_scan]


def _sp_on(args) -> bool:
    return getattr(args, "prefill_sp", "off") in ("on", "both")


def _run_fusion(args, cfg):
    """The scan-equivalence prover + dispatch budgets (the sixth audit
    family): prove every selected precision x kv x backend cell, then
    gate the static launch structure for BOTH layer_scan values.
    Returns ``(section_dict, ok, violation_strings)``."""
    from midgpt_tpu.analysis.budgets import precision_key
    from midgpt_tpu.analysis.harness import (
        audit_serving_dispatch,
        prove_scan_equivalence,
    )

    out: tp.Dict[str, tp.Any] = {"equivalence": {}, "dispatch": {}}
    ok = True
    violations: tp.List[str] = []
    for precision in _precisions(args):
        for kvq in _kv_modes(args):
            for backend in ("xla", "pallas"):
                rep = prove_scan_equivalence(
                    cfg, quant=(precision == "int8"), kv_quant=kvq,
                    paged_kernel=backend,
                )
                tag = f"{precision_key(precision, kvq)}/{backend}"
                out["equivalence"][tag] = rep.to_dict()
                ok = ok and rep.ok
                violations.extend(
                    f"[fusion/{tag}] {c.name}: {c.detail}"
                    for c in rep.checks
                    if not c.ok
                )
    # launch budgets: structure is precision/backend-invariant (dtypes
    # change, scan nesting does not) — one trace per layer_scan value;
    # with --prefill-sp the sequence-parallel chunk rides along as its
    # own prefill_chunk_sp cells (resharding must not change launches)
    for ls in ("off", "on"):
        reports, bad = audit_serving_dispatch(
            cfg, layer_scan=ls,
            prefill_sp="on" if _sp_on(args) else "off",
        )
        out["dispatch"][ls] = {
            name: rep.to_dict() for name, rep in reports.items()
        }
        ok = ok and not bad
        violations.extend(f"[dispatch/ls={ls}] {v}" for v in bad)
    return out, ok, violations


def _run_fusion_only(args, cfg) -> int:
    section, ok, violations = _run_fusion(args, cfg)
    out: tp.Dict[str, tp.Any] = {
        "config": args.config, "mode": "scan-equivalence",
        **section, "ok": ok,
    }
    return _emit_report(out, ok, violations, args)


def _run_choreo(args, cfg):
    """Run the choreography prover for the selected precisions; returns
    ``(per_precision_dicts, ok, violation_strings)`` — shared by the
    standalone ``--choreo`` mode and the ``--serving --choreo`` path."""
    from midgpt_tpu.analysis.harness import prove_serving_choreography

    from midgpt_tpu.analysis.budgets import precision_key

    out: tp.Dict[str, tp.Any] = {}
    ok = True
    violations: tp.List[str] = []
    for precision in _precisions(args):
        for kvq in _kv_modes(args):
            # both paged-attention backends are proven per cell: the
            # prover only TRACES (no compilation), so the Pallas kernel
            # contract rides along at ~zero cost — the kernel body's
            # softmax signature must equal the decode window's
            for backend in ("xla", "pallas"):
                cell = f"{precision_key(precision, kvq)}/{backend}"
                # each cell is proven twice: greedy (the PR 4/PR 5
                # argmax choreography) and sampled (temperature > 0:
                # the verify row-0 sampler must mirror the decode
                # window's categorical, and the rejection-sampling
                # acceptance/residual/target-softmax arithmetic must
                # run in f32 — choreo.prove_sampled_choreography)
                for tag, kw in (
                    (cell, {}),
                    (f"{cell}/sampled",
                     dict(temperature=0.8, top_k=20)),
                ):
                    rep = prove_serving_choreography(
                        cfg, quant=(precision == "int8"), kv_quant=kvq,
                        paged_kernel=backend, **kw
                    )
                    out[tag] = rep.to_dict()
                    ok = ok and rep.ok
                    violations.extend(
                        f"[choreo/{tag}] {c.name}: {c.detail}"
                        for c in rep.checks
                        if not c.ok
                    )
            if _sp_on(args):
                # the sequence-parallel prefill leg: the SP chunk trace
                # must equal the plain chunk trace op for op (resharding
                # only — harness.prove_sp_prefill_choreography). Traced
                # on its own tensor=2 mesh; backend-independent (the SP
                # reshard wraps the whole block, not the kernel)
                from midgpt_tpu.analysis.harness import (
                    prove_sp_prefill_choreography,
                )

                tag = f"{precision_key(precision, kvq)}/sp"
                rep = prove_sp_prefill_choreography(
                    cfg, quant=(precision == "int8"), kv_quant=kvq,
                )
                out[tag] = rep.to_dict()
                ok = ok and rep.ok
                violations.extend(
                    f"[choreo/{tag}] {c.name}: {c.detail}"
                    for c in rep.checks
                    if not c.ok
                )
    return out, ok, violations


def _emit_report(
    out: tp.Dict[str, tp.Any], ok: bool, violations: tp.List[str], args
) -> int:
    """Shared report epilogue for the tracing-only prover modes: print
    the JSON report (+ --json file), the VIOLATION lines, and map ok to
    the exit code."""
    text = json.dumps(out, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    for v in violations:
        print(f"VIOLATION {v}", file=sys.stderr)
    return 0 if ok else 1


def _run_choreo_only(args, cfg) -> int:
    sections, ok, violations = _run_choreo(args, cfg)
    out: tp.Dict[str, tp.Any] = {
        "config": args.config, "mode": "serving-choreography",
        **sections, "ok": ok,
    }
    return _emit_report(out, ok, violations, args)


def _run_telemetry() -> tp.Tuple[tp.Dict[str, tp.Any], bool, tp.List[str]]:
    """The telemetry-inertness proof (--telemetry on): both dispatch
    shapes — the fused decode window (with chunked prefill, so the
    prefill-bucket programs are covered too) and the speculative verify
    program. Tiny fixed model, seconds, no compilation of the named
    config (the proof is an engine-logic property — see
    harness.prove_telemetry_inert)."""
    from midgpt_tpu.analysis.harness import prove_telemetry_inert

    sections: tp.Dict[str, tp.Any] = {}
    violations: tp.List[str] = []
    for name, kw in (
        ("decode_window_chunked", dict(prefill_chunk=4, speculate=0)),
        ("verify_spec4", dict(prefill_chunk=None, speculate=4)),
    ):
        try:
            sections[name] = prove_telemetry_inert(**kw)
        except AssertionError as e:
            sections[name] = {"ok": False, "error": str(e)}
            violations.append(f"telemetry-inert/{name}: {e}")
    return sections, not violations, violations


def _run_serving(args, cfg, mesh_shape) -> int:
    """The --serving audits: compile the engine's three hot-path
    programs (decode window / prefill chunk / speculative verify) on
    one shared geometry per selected precision, evaluate the serving
    ruleset on each, and optionally (a) gate the static HBM streams
    against the checked-in byte budgets (--traffic) and (b) run the
    arithmetic-choreography prover (--choreo)."""
    from midgpt_tpu.analysis.harness import (
        audit_decode_window,
        audit_prefill_chunk,
        audit_verify_program,
    )

    k = args.steps_per_dispatch or 4
    precisions = _precisions(args)
    # the chunked-prefill steady state interleaves a prefill chunk
    # between decode windows, and with speculation on every decode
    # dispatch IS a verify dispatch: all three programs are audited on
    # one shared geometry (_serving_audit_setup) per precision — the
    # int8 leg additionally gates no-dequant-materialization (s8 entry
    # params, dequant fused into each matmul)
    program_specs = (
        ("decode_window", audit_decode_window, dict(
            slots=args.serving_slots, window=k,
            page_size=args.serving_page_size,
        ), k),
        ("prefill_chunk", audit_prefill_chunk, dict(
            page_size=args.serving_page_size,
        ), 1),
        ("verify_program", audit_verify_program, dict(
            slots=args.serving_slots, spec_len=args.serving_spec_len,
            page_size=args.serving_page_size,
        ), 1),
    )
    if _sp_on(args):
        # the sequence-parallel prefill leg: its own budget cells (the
        # SP combine is real wire traffic — comms_max pins it) next to
        # the plain chunk's, same donation/no-host-sync/no-f64 rules
        if not (mesh_shape and mesh_shape.get("tensor", 1) > 1):
            print(
                "error: --prefill-sp needs --mesh-shape with tensor > 1 "
                "(single-chip SP is a no-op)",
                file=sys.stderr,
            )
            return 2
        program_specs = program_specs + (
            ("prefill_chunk_sp", audit_prefill_chunk, dict(
                page_size=args.serving_page_size, prefill_sp="on",
            ), 1),
        )
    if args.serving_spec_sampled:
        # the rejection-sampling verify leg: same program geometry at
        # temperature > 0. It gates against the SAME verify_program
        # budget cells — the sampled wrapper appends only the per-slot
        # seeds and the PRNG key (control scalars), so the weight/KV/
        # logits streams must land byte-identical to the greedy audit
        # and any dense draft-probability tensor joining the entry
        # interface trips the unclassified-float rule
        program_specs = program_specs + (
            ("verify_program_sampled", audit_verify_program, dict(
                slots=args.serving_slots, spec_len=args.serving_spec_len,
                page_size=args.serving_page_size,
                temperature=0.8, top_k=20,
            ), 1),
        )

    # --traffic budget gating applies only at the geometry the budgets
    # were measured at (analysis/budgets.AUDIT_GEOMETRY)
    budget_geom = None
    if args.traffic:
        from midgpt_tpu.analysis.budgets import (
            AUDIT_GEOMETRY,
            geometry_key,
        )

        matches = (
            args.config == AUDIT_GEOMETRY["config"]
            and not args.no_shrink
            and args.serving_slots == AUDIT_GEOMETRY["slots"]
            and k == AUDIT_GEOMETRY["window"]
            and args.serving_page_size == AUDIT_GEOMETRY["page_size"]
            and args.serving_spec_len == AUDIT_GEOMETRY["spec_len"]
        )
        budget_geom = geometry_key(mesh_shape) if matches else None

    ok = True
    violations: tp.List[str] = []
    sections: tp.Dict[str, tp.Any] = {}
    budget_fragment: tp.Dict[tp.Tuple[str, str], tp.Any] = {}
    from midgpt_tpu.analysis.budgets import precision_key

    cells = [
        (precision, kvq, ls)
        for precision in precisions
        for kvq in _kv_modes(args)
        for ls in _layer_scan_modes(args)
    ]
    for precision, kvq, ls in cells:
        pkey = precision_key(precision, kvq)
        for name, fn, kw, steps in program_specs:
            res = fn(
                cfg, shrink=not args.no_shrink,
                quant=(precision == "int8"), kv_quant=kvq,
                layer_scan=ls, mesh_shape=mesh_shape,
                traffic=args.traffic, **kw
            )
            analysis, report = res[0], res[1]
            ok = ok and report.ok
            violations.extend(str(v) for v in report.violations)
            section = {
                "donated_leaves": analysis.donated_leaves,
                "aliased_buffers": len(
                    {e.param_number for e in analysis.aliases}
                ),
                "rules": report.to_dict()["rules"],
            }
            if args.traffic:
                from midgpt_tpu.analysis.budgets import (
                    budget_for,
                    check_budget,
                )

                traf = res[2]
                section["traffic"] = traf.to_dict()
                # --print-budgets regeneration fragment: record the
                # FIRST layer_scan leg only (the unrolled one under
                # 'both' — the convention the checked-in cells were
                # measured with); letting the fused leg overwrite it
                # would regenerate cells from fused numbers exactly
                # when the two legs diverge. The sampled verify leg is
                # excluded: it gates against (and must match) the
                # greedy verify_program cells, it does not get its own
                if (
                    ls == _layer_scan_modes(args)[0]
                    and name != "verify_program_sampled"
                ):
                    budget_fragment[(name, pkey)] = traf
                budget_name = (
                    "verify_program"
                    if name == "verify_program_sampled"
                    else name
                )
                budget = (
                    budget_for(budget_name, pkey, budget_geom)
                    if budget_geom
                    else None
                )
                if budget is not None:
                    bad = check_budget(traf, budget)
                    section["budget"] = {
                        "geometry": budget_geom,
                        "ok": not bad,
                        "violations": bad,
                    }
                    ok = ok and not bad
                    violations.extend(bad)
                else:
                    section["budget"] = {
                        "geometry": budget_geom,
                        "ok": None,
                        "violations": [],
                    }
            # the fused program streams the same bytes through the same
            # entry interface, so both layer_scan legs gate against the
            # same budget cells; the section key records which leg
            sections[f"{name}/{pkey}" + ("/scan" if ls == "on" else "")] = (
                section
            )

    choreo_out = None
    if args.choreo:
        choreo_out, choreo_ok, choreo_violations = _run_choreo(args, cfg)
        ok = ok and choreo_ok
        violations.extend(choreo_violations)
    fusion_out = None
    if args.fusion:
        fusion_out, fusion_ok, fusion_violations = _run_fusion(args, cfg)
        ok = ok and fusion_ok
        violations.extend(fusion_violations)
    telemetry_out = None
    if args.telemetry == "on":
        telemetry_out, tele_ok, tele_violations = _run_telemetry()
        ok = ok and tele_ok
        violations.extend(tele_violations)

    out = {
        "config": args.config,
        "mode": "serving-audit",
        "precisions": list(precisions),
        "kv_quant": args.kv_quant,
        "layer_scan": args.layer_scan,
        "ok": ok,
        "geometry": {
            "slots": args.serving_slots,
            "steps_per_dispatch": k,
            "page_size": args.serving_page_size,
            "spec_len": args.serving_spec_len,
            "spec_sampled": bool(args.serving_spec_sampled),
            "mesh_shape": mesh_shape,
        },
        "programs": sections,
    }
    if choreo_out is not None:
        out["choreography"] = choreo_out
    if fusion_out is not None:
        out["fusion"] = fusion_out
    if telemetry_out is not None:
        out["telemetry"] = telemetry_out
    text = json.dumps(out, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    if args.print_budgets and args.traffic:
        # the fragment must carry EVERY gate key: a pasted budget
        # missing constants_max/comms_max would silently disable the
        # constant-folding and regather trips check_budget keys on
        geom = budget_geom
        if geom is None:
            from midgpt_tpu.analysis.budgets import geometry_key

            geom = geometry_key(mesh_shape)
            print(
                "# WARNING: non-default audit geometry — update "
                "AUDIT_GEOMETRY alongside the budgets",
                file=sys.stderr,
            )
        print("# analysis/budgets.py fragment (measured):", file=sys.stderr)
        for (name, precision), traf in budget_fragment.items():
            entry = {
                "weights": traf.streams["weights"],
                "kv": traf.streams["kv"],
                "logits": traf.streams["logits"],
                # headroom over the measured baseline: constants are
                # geometry-constant rope tables (any baked weight jumps
                # past 3x), comms scales with the audited payloads
                "constants_max": 3 * max(
                    traf.streams["constants"], 4096
                ),
            }
            if traf.comms_bytes:
                entry["comms_max"] = traf.comms_bytes * 3 // 2
            print(
                f"    ({name!r}, {precision!r}, {geom!r}): "
                + json.dumps(entry),
                file=sys.stderr,
            )
    if not ok:
        for v in violations:
            print(f"VIOLATION {v}", file=sys.stderr)
        return 1
    return 0


def _run_train_audit(args, cfg) -> int:
    """The --train-audit mode: prover + traffic cells + dispatch gate
    for one mesh geometry of the fused train window (see the flag help
    for the contract). Budget gating only applies when the audited
    config/window match what the cells were measured at
    (budgets.TRAIN_AUDIT_GEOMETRY) — like the serving budget_geom
    guard, a non-matching invocation still runs the prover but reports
    the missing cells as violations."""
    from midgpt_tpu.analysis.budgets import TRAIN_AUDIT_GEOMETRY
    from midgpt_tpu.analysis.harness import audit_train

    try:
        window_steps = tuple(
            int(s) for s in args.train_window_steps.split(",") if s.strip()
        )
    except ValueError:
        print(
            f"error: bad --train-window-steps {args.train_window_steps!r} "
            "(want comma-separated ints)",
            file=sys.stderr,
        )
        return 2
    if not window_steps or any(k < 1 for k in window_steps):
        print(
            "error: --train-window-steps needs at least one K >= 1",
            file=sys.stderr,
        )
        return 2
    if args.config != TRAIN_AUDIT_GEOMETRY["config"]:
        print(
            f"# note: train budget cells were measured on "
            f"{TRAIN_AUDIT_GEOMETRY['config']!r}; auditing "
            f"{args.config!r} will report missing cells",
            file=sys.stderr,
        )
    report = audit_train(cfg, args.train_geometry, window_steps)
    out = {
        "config": args.config,
        "mode": "train-audit",
        "geometry": args.train_geometry,
        "window_steps": list(window_steps),
        **{k: v for k, v in report.items() if k != "geometry"},
    }
    if args.print_budgets:
        print(
            "# analysis/budgets.py TRAIN_BUDGETS fragment (measured):",
            file=sys.stderr,
        )
        for cell in report["cells"]:
            traf = cell["traffic"]
            entry = {
                "ici_bytes": traf["ici_bytes"],
                "dcn_bytes": traf["dcn_bytes"],
                "by_axis": traf["by_axis"],
            }
            print(
                f"    ({args.train_geometry!r}, "
                f"{cell['window_steps']}): " + json.dumps(entry),
                file=sys.stderr,
            )
    return _emit_report(out, report["ok"], report["violations"], args)


def main(argv: tp.Optional[tp.Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.lint is not None:
        return _run_lint(list(args.lint))
    if args.ledger:
        # jax-free: the ledger reads JSON records only — no devices, no
        # config compile (it must run on any CI box in seconds)
        from midgpt_tpu.analysis.ledger import run_ledger

        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        return run_ledger(
            trajectory_root=args.trajectory or repo_root,
            records=args.record,
            record_dirs=args.records_dir,
            suite_timing=args.suite_timing,
            report_path=args.report,
            hardware={"auto": None, "on": True, "off": False}[
                args.hardware
            ],
        )
    if not args.config:
        build_parser().print_usage(sys.stderr)
        print("error: --config (or --lint) is required", file=sys.stderr)
        return 2

    _ensure_devices(args.platform, args.mesh)

    from midgpt_tpu.analysis.harness import audit_config
    from midgpt_tpu.config import get_config

    try:
        cfg = get_config(args.config)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.steps_per_dispatch is not None:
        if args.steps_per_dispatch < 1:
            print(
                f"error: --steps-per-dispatch must be >= 1, got "
                f"{args.steps_per_dispatch}",
                file=sys.stderr,
            )
            return 2
        import dataclasses

        cfg = dataclasses.replace(
            cfg, steps_per_dispatch=args.steps_per_dispatch
        )

    mesh_shape = None
    if args.mesh_shape:
        from midgpt_tpu.analysis.harness import parse_mesh_shape

        if not args.serving:
            print(
                "error: --mesh-shape applies to the --serving audits",
                file=sys.stderr,
            )
            return 2
        try:
            mesh_shape = parse_mesh_shape(args.mesh_shape)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    if args.train_audit:
        return _run_train_audit(args, cfg)
    if args.serving:
        return _run_serving(args, cfg, mesh_shape)
    if args.choreo and args.fusion:
        # both tracing-only provers in one invocation, one combined
        # report (running only one of them here would silently drop the
        # other's gate)
        c_sections, c_ok, c_viol = _run_choreo(args, cfg)
        f_sections, f_ok, f_viol = _run_fusion(args, cfg)
        ok = c_ok and f_ok
        out = {
            "config": args.config,
            "mode": "serving-choreography+scan-equivalence",
            "choreography": c_sections,
            "fusion": f_sections,
            "ok": ok,
        }
        return _emit_report(out, ok, c_viol + f_viol, args)
    if args.choreo:
        # standalone prover: no compilation, jaxpr tracing only — the
        # fast CI gate (--serving --choreo runs it next to the audits)
        return _run_choreo_only(args, cfg)
    if args.fusion:
        # standalone scan-equivalence prover + dispatch budgets: also
        # tracing only — the serving-choreo CI job's sixth-family gate
        return _run_fusion_only(args, cfg)
    if args.telemetry == "on":
        # standalone telemetry-inertness proof (tiny fixed model — the
        # named config only labels the report)
        sections, ok, viol = _run_telemetry()
        out = {
            "config": args.config, "mode": "telemetry-inertness",
            "telemetry": sections, "ok": ok,
        }
        return _emit_report(out, ok, viol, args)

    overrides = dict(args.override_logical_rule) or None
    if overrides:
        # validate before compiling so a typo'd axis name is a usage
        # error (exit 2), not a traceback misread as a rule violation
        from midgpt_tpu.parallel.sharding import DEFAULT_LOGICAL_RULES

        unknown = set(overrides) - set(DEFAULT_LOGICAL_RULES)
        if unknown:
            print(
                f"error: unknown logical axes {sorted(unknown)} "
                f"(known: {sorted(DEFAULT_LOGICAL_RULES)})",
                file=sys.stderr,
            )
            return 2
    analysis, report, cost = audit_config(
        cfg, shrink=not args.no_shrink, logical_overrides=overrides
    )
    if not args.full:
        cost = {k: v for k, v in cost.items() if k != "collectives"}
    out = {
        "config": args.config,
        "ok": report.ok,
        "mesh": {
            "axis_names": list(analysis.mesh.axis_names),
            "axis_sizes": list(analysis.mesh.axis_sizes),
            "num_slices": analysis.mesh.num_slices,
        },
        "geometry": {
            "global_batch": analysis.global_batch,
            "block": analysis.block,
            "steps_per_dispatch": cfg.steps_per_dispatch,
            "donated_leaves": analysis.donated_leaves,
            "aliased_buffers": len({e.param_number for e in analysis.aliases}),
        },
        "rules": report.to_dict()["rules"],
        "cost": cost,
    }
    text = json.dumps(out, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    if not report.ok:
        for v in report.violations:
            print(f"VIOLATION {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
