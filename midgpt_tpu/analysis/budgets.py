"""Checked-in HBM byte budgets for the serving programs.

The numbers a serving program is ALLOWED to stream, per dispatch, at
the CI audit geometry — the generalization of the shape-matching
``no-dequant-materialization`` / ``no-batch-allgather-in-page-gather``
rules into plain accounting: any regression that re-materializes,
re-gathers, or constant-folds a large buffer moves bytes, and a moved
byte count trips the gate regardless of what the HLO happens to look
like. Concretely:

- a model CLOSED OVER by a program (the PR 6 bug) removes the weight
  stream from the entry interface (below the weights band) and dumps it
  into ``constants`` (above the constants cap) — two trips, with the
  quantized variant additionally 4x over on the folded f32 copies;
- a KV-head-sharded pool regathered through the page gathers (the PR 7
  bug class) multiplies the sharded geometry's ``comms`` bytes past its
  cap;
- an accidental full-precision weight copy smuggled in as a second
  input lands in ``unclassified`` (its own violation).

Budgets are exact measured values with a relative tolerance band, keyed
by ``(program, precision, geometry)`` at the ONE audit geometry CI
compiles (:data:`AUDIT_GEOMETRY`): openwebtext shrunk to 2 layers /
block 256 / vocab 1024, slots=4, window=4, page_size=16, spec_len=4.
Regenerate after an intentional geometry or model change with::

    python -m midgpt_tpu.analysis --config openwebtext --serving \
        --traffic --print-budgets

and paste the emitted dict here — the diff IS the review artifact.

jax-free (pure numbers), like rules.py.
"""

from __future__ import annotations

import typing as tp

# the geometry every budget below was measured at; the CLI refuses to
# gate traffic on a non-matching geometry rather than mis-fail it
AUDIT_GEOMETRY: tp.Dict[str, tp.Any] = {
    "config": "openwebtext",
    "n_layer": 2,
    "block_size": 256,
    "vocab_size": 1024,
    "slots": 4,
    "window": 4,
    "page_size": 16,
    "spec_len": 4,
}

# streams are bytes at the compiled program's entry interface
# (traffic.traffic_report); comms is the per-dispatch collective wire
# estimate (cost.py's ring-algorithm arithmetic) on sharded geometries
BUDGETS: tp.Dict[tp.Tuple[str, str, str], tp.Dict[str, int]] = {
    # --- single chip, bf16 ---
    ("decode_window", "bf16", "single"): {
        "weights": 31457792, "kv": 6291456, "logits": 16384,
        "constants_max": 262144,
    },
    ("prefill_chunk", "bf16", "single"): {
        "weights": 31457792, "kv": 6291456, "logits": 16384,
        "constants_max": 262144,
    },
    ("verify_program", "bf16", "single"): {
        "weights": 31457792, "kv": 6291456, "logits": 16384,
        "constants_max": 262144,
    },
    # --- single chip, int8 (s8 matrices + f32 per-channel scales) ---
    ("decode_window", "int8", "single"): {
        "weights": 16574976, "kv": 6291456, "logits": 16384,
        "constants_max": 262144,
    },
    ("prefill_chunk", "int8", "single"): {
        "weights": 16574976, "kv": 6291456, "logits": 16384,
        "constants_max": 262144,
    },
    ("verify_program", "int8", "single"): {
        "weights": 16574976, "kv": 6291456, "logits": 16384,
        "constants_max": 262144,
    },
    # --- tp=2,replica=2 (per-shard local streams: weights and the
    # whole-KV-head pool halve; replica rides replicated) ---
    ("decode_window", "bf16", "replica2,tensor2"): {
        "weights": 15729152, "kv": 3145728, "logits": 8192,
        "constants_max": 262144, "comms_max": 165936,
    },
    ("prefill_chunk", "bf16", "replica2,tensor2"): {
        "weights": 15729152, "kv": 3145728, "logits": 8192,
        "constants_max": 262144, "comms_max": 2654208,
    },
    ("verify_program", "bf16", "replica2,tensor2"): {
        "weights": 15729152, "kv": 3145728, "logits": 8192,
        "constants_max": 262144, "comms_max": 829728,
    },
    ("decode_window", "int8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 3145728, "logits": 8192,
        "constants_max": 262144, "comms_max": 165936,
    },
    ("prefill_chunk", "int8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 3145728, "logits": 8192,
        "constants_max": 262144, "comms_max": 2654208,
    },
    ("verify_program", "int8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 3145728, "logits": 8192,
        "constants_max": 262144, "comms_max": 829728,
    },
    # --- int8-quantized KV pool (serving.paged kv_quant="int8"):
    # payload halves (s8 pages) + 12,288 B of f32 per-(page, KV-head)
    # scale planes join the KV stream — 3,158,016 = 6,291,456 / 2 +
    # 12,288, i.e. the pool bytes serving decode streams per step drop
    # to ~50.2% of the bf16 cells (asserted by tests/test_traffic.py).
    # Regenerated with --kv-quant on; weight streams are untouched. ---
    ("decode_window", "bf16-kv8", "single"): {
        "weights": 31457792, "kv": 3158016, "logits": 16384,
        "constants_max": 245760,
    },
    ("prefill_chunk", "bf16-kv8", "single"): {
        "weights": 31457792, "kv": 3158016, "logits": 16384,
        "constants_max": 245760,
    },
    ("verify_program", "bf16-kv8", "single"): {
        "weights": 31457792, "kv": 3158016, "logits": 16384,
        "constants_max": 245760,
    },
    ("decode_window", "int8-kv8", "single"): {
        "weights": 16574976, "kv": 3158016, "logits": 16384,
        "constants_max": 245760,
    },
    ("prefill_chunk", "int8-kv8", "single"): {
        "weights": 16574976, "kv": 3158016, "logits": 16384,
        "constants_max": 245760,
    },
    ("verify_program", "int8-kv8", "single"): {
        "weights": 16574976, "kv": 3158016, "logits": 16384,
        "constants_max": 245760,
    },
    # --- tp=2,replica=2 x int8 KV: per-shard pool payload halves again
    # (whole-KV-head sharding), scale planes shard with their heads ---
    ("decode_window", "bf16-kv8", "replica2,tensor2"): {
        "weights": 15729152, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 165936,
    },
    ("prefill_chunk", "bf16-kv8", "replica2,tensor2"): {
        "weights": 15729152, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 2654208,
    },
    ("verify_program", "bf16-kv8", "replica2,tensor2"): {
        "weights": 15729152, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 829728,
    },
    ("decode_window", "int8-kv8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 165936,
    },
    ("prefill_chunk", "int8-kv8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 2654208,
    },
    ("verify_program", "int8-kv8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 829728,
    },
    # --- sequence-parallel prefill chunk (ServingEngine prefill_sp,
    # --prefill-sp on): the SP program streams BYTE-IDENTICAL
    # weights/kv/logits to the plain chunk cells above — SP moves no
    # resident bytes; only the wire changes. Measured comms is the plain
    # chunk's TP collectives (1,769,472 B) + the SP row gathers of the
    # [1, 64, 768] chunk activations (983,040 B = the "SP combine");
    # comms_max caps at 1.5x measured, so a program that regathers
    # anything beyond the SP combine (e.g. a reduce-scatter+all-gather
    # pair replacing a psum, the bitwise hazard) trips the guard.
    # Regenerated with --prefill-sp on --mesh-shape tp=2,replica=2. ---
    ("prefill_chunk_sp", "bf16", "replica2,tensor2"): {
        "weights": 15729152, "kv": 3145728, "logits": 8192,
        "constants_max": 245760, "comms_max": 4128768,
    },
    ("prefill_chunk_sp", "int8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 3145728, "logits": 8192,
        "constants_max": 245760, "comms_max": 4128768,
    },
    ("prefill_chunk_sp", "bf16-kv8", "replica2,tensor2"): {
        "weights": 15729152, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 4128768,
    },
    ("prefill_chunk_sp", "int8-kv8", "replica2,tensor2"): {
        "weights": 8293888, "kv": 1579008, "logits": 8192,
        "constants_max": 245760, "comms_max": 4128768,
    },
}

# band half-width for the exact streams: wide enough for layout/padding
# noise across jax/XLA versions, narrow enough that the cheapest real
# regression (one duplicated weight matrix: the [256, 1024] head, +5%
# of the weight stream at this geometry) cannot hide inside it
TOLERANCE = 0.04

# ---------------------------------------------------------------------------
# dispatch/launch budget (analysis.dispatch) — the launch-side twin of the
# byte budgets above, one rule for every serving program of every model
# family, computed from the traced model's config: the whole window is ONE
# launch, nothing crosses to the host inside it, and the flat trace holds
# one inlined attention body per attention layer (a duplicated or lost
# layer body moves zero bytes at the entry interface, so the byte budgets
# cannot see it). Exact equality, no band: launch structure is integral.
# ---------------------------------------------------------------------------


def dispatch_budget(model_cfg) -> tp.Dict[str, int]:
    """The launch structure a serving program of a model of ``model_cfg``
    must have: one launch per window, no host transfer, and one inlined
    attention body per attention layer (``kv_layers``: the layers whose
    cache is pages of the pool)."""
    return {
        "launches_per_window": 1,
        "inlined_layer_bodies": model_cfg.kv_layers,
        "host_transfers": 0,
    }


def check_dispatch_budget(
    report,  # dispatch.DispatchReport
    budget: tp.Mapping[str, int],
) -> tp.List[str]:
    """Evaluate one program's measured launch structure against its
    dispatch budget; returns violation strings (empty = pass). A key the
    budget leaves out is not checked. Both directions are regressions:
    an extra inlined body is a layer traced twice, a missing one means
    the audit traced the wrong program."""
    out: tp.List[str] = []
    got = report.to_dict()
    for key in (
        "launches_per_window", "inlined_layer_bodies", "host_transfers",
    ):
        expect = budget.get(key)
        if expect is None:
            continue
        if got[key] != expect:
            hint = ""
            if key == "launches_per_window":
                hint = " — the window's steps no longer fit one launch"
            elif key == "host_transfers":
                hint = " — a host callback joined the hot path"
            out.append(
                f"{report.program}: {key} {got[key]} != budget "
                f"{expect}{hint}"
            )
    return out


def precision_key(precision: str, kv_quant: bool = False) -> str:
    """Budget-cell precision tag: the weight precision, suffixed
    ``-kv8`` when the paged KV pool is int8-quantized (serving.paged) —
    the pool payload halves and f32 per-(page, KV-head) scale planes
    join the KV stream, so kv-quant cells are distinct budget rows."""
    return f"{precision}-kv8" if kv_quant else precision


def geometry_key(
    mesh_shape: tp.Optional[tp.Mapping[str, int]]
) -> str:
    """``None`` -> 'single'; ``{"tensor": 2, "replica": 2}`` ->
    'replica2,tensor2' (sorted, size-1 axes dropped)."""
    if not mesh_shape:
        return "single"
    parts = [
        f"{name}{size}"
        for name, size in sorted(mesh_shape.items())
        if size > 1
    ]
    return ",".join(parts) if parts else "single"


def budget_for(
    program: str, precision: str, geometry: str
) -> tp.Optional[tp.Dict[str, int]]:
    return BUDGETS.get((program, precision, geometry))


def check_budget(
    report,  # traffic.TrafficReport
    budget: tp.Mapping[str, int],
    *,
    tolerance: float = TOLERANCE,
) -> tp.List[str]:
    """Evaluate one program's measured streams against its budget;
    returns violation strings (empty = pass). The exact streams are a
    BAND, not a cap — bytes leaving a stream are as much a regression
    as bytes joining one (a weight stream at 0 means the weights moved
    into the executable, not that serving got free)."""
    out: tp.List[str] = []
    for stream in ("weights", "kv", "logits"):
        expect = budget.get(stream)
        if expect is None:
            continue
        got = report.streams.get(stream, 0)
        lo = int(expect * (1 - tolerance))
        hi = int(expect * (1 + tolerance))
        if not (lo <= got <= hi):
            out.append(
                f"{report.program}: {stream} stream {got:,} B outside "
                f"budget [{lo:,}, {hi:,}] (expected ~{expect:,})"
            )
    cmax = budget.get("constants_max")
    if cmax is not None and report.streams.get("constants", 0) > cmax:
        out.append(
            f"{report.program}: {report.streams['constants']:,} B of "
            f"large constants baked into the executable (cap {cmax:,}) "
            "— model state is being constant-folded instead of streamed "
            "as entry parameters (the PR 6 closed-over-model bug class)"
        )
    comms_max = budget.get("comms_max")
    if comms_max is not None and report.comms_bytes > comms_max:
        out.append(
            f"{report.program}: {report.comms_bytes:,} B of collective "
            f"wire traffic per dispatch (cap {comms_max:,}) — a sharded "
            "buffer is being regathered (the page-gather all-gather "
            "bug class)"
        )
    if report.unclassified:
        shapes = ", ".join(
            f"{d}[{','.join(map(str, s))}]"
            for d, s in report.unclassified
        )
        out.append(
            f"{report.program}: unclassified large float entry "
            f"parameter(s): {shapes} — an unexplained stream joined "
            "the program interface"
        )
    return out


# ---------------------------------------------------------------------------
# TRAIN-side budgets: per-(mesh geometry, window K) wire-byte cells for
# the compiled fused train window, plus the window dispatch budget.
# Same philosophy as the serving cells above — exact measured values
# with a band — but the classified quantity is COLLECTIVE WIRE BYTES
# split by interconnect tier (cost.py's ring arithmetic): ICI bytes stay
# inside a slice; DCN bytes cross slices. The bug classes each cell
# catches:
#
# - a param spec widened across the slice axis (cross-slice FSDP
#   re-gather) moves the whole per-step gather/reduce-scatter volume
#   from ICI onto DCN — the dcn2 cell's ``dcn_bytes`` band trips AND the
#   single-slice cells' expected-zero DCN trips on any bytes at all;
# - an f32 operand reaching a collective that should carry bf16 doubles
#   that axis's bytes past the 4% band (this is how the psum-dtype
#   clause of the precision contract is gated — the jaxpr-level prover
#   cannot see collectives, see train_choreo's scope note);
# - a resharded activation or an extra all-gather shows up as an
#   unexpected ``by_axis`` key (its own violation, like ``unclassified``
#   in the serving cells).
#
# K=1 and K=4 cells are IDENTICAL by construction — cost.py counts a
# scan-body collective once per dispatch, and the fused window executes
# the same per-step collective set K times inside one scan. Checking
# both K values pins exactly that: a window whose bytes GREW with K has
# lost the scan (re-unrolled window) even before the dispatch gate runs.
# ---------------------------------------------------------------------------

# the geometry every train cell below was measured at (shrunk
# openwebtext; batch 16 so the microbatch divides every batch-sharding
# in TRAIN_AUDIT_GEOMETRIES)
TRAIN_AUDIT_GEOMETRY: tp.Dict[str, tp.Any] = {
    "config": "openwebtext",
    "n_layer": 2,
    "block_size": 256,
    "vocab_size": 1024,
    "batch_size": 16,
    "g_accum_iters": 2,
}

# the three mesh geometries the CI train-audit matrix compiles (8 host
# devices via --xla_force_host_platform_device_count): pure FSDP, a
# tensor*fsdp hybrid, and a 2-slice DCN mesh with FSDP inside each slice
TRAIN_AUDIT_GEOMETRIES: tp.Dict[str, tp.Dict[str, int]] = {
    "fsdp": dict(replica=1, fsdp=8, sequence=1, tensor=1),
    "tp_fsdp": dict(replica=1, fsdp=4, sequence=1, tensor=2),
    "dcn2": dict(replica=2, fsdp=4, sequence=1, tensor=1, num_slices=2),
}

# measured cells, keyed (geometry, window_steps). ``by_axis`` is the
# full per-mesh-axis split ("+"-joined for multi-axis collectives); any
# axis key not present here is an unexpected collective. Regenerate
# after an intentional change with::
#
#     python -m midgpt_tpu.analysis --config openwebtext --train-audit \
#         --train-geometry <g> --print-budgets
TRAIN_BUDGETS: tp.Dict[
    tp.Tuple[str, int], tp.Dict[str, tp.Any]
] = {
    ("fsdp", 1): {
        "ici_bytes": 108739547, "dcn_bytes": 0,
        "by_axis": {"fsdp": 108739547},
    },
    ("fsdp", 4): {
        "ici_bytes": 108739547, "dcn_bytes": 0,
        "by_axis": {"fsdp": 108739547},
    },
    ("tp_fsdp", 1): {
        "ici_bytes": 71978366, "dcn_bytes": 0,
        "by_axis": {"fsdp": 50725710, "tensor": 21252656},
    },
    ("tp_fsdp", 4): {
        "ici_bytes": 71978366, "dcn_bytes": 0,
        "by_axis": {"fsdp": 50725710, "tensor": 21252656},
    },
    # dcn2: the per-slice FSDP gathers stay on ICI; the cross-slice
    # grad reduction (replica axis + the replica+fsdp mixed reduce)
    # is the ONLY traffic allowed on DCN
    ("dcn2", 1): {
        "ici_bytes": 92605512, "dcn_bytes": 14156679,
        "by_axis": {
            "fsdp": 92605512, "replica+fsdp": 5505927,
            "replica": 8650752,
        },
    },
    ("dcn2", 4): {
        "ici_bytes": 92605512, "dcn_bytes": 14156679,
        "by_axis": {
            "fsdp": 92605512, "replica+fsdp": 5505927,
            "replica": 8650752,
        },
    },
}

# launch-side window budget (same on every geometry — the dispatch
# structure is mesh-independent): ONE launch per K-step window, the
# grad-accum loop folded as a scan of trip G, zero host transfers, and
# 100% of the donated train state aliased in the compiled executable
# (27 leaves at the audit geometry: 8 params + step + 8 mu + 8 nu +
# 2 optax counts)
TRAIN_DISPATCH_BUDGETS: tp.Dict[str, int] = {
    "launches_per_window": 1,
    "accum_scan_length": TRAIN_AUDIT_GEOMETRY["g_accum_iters"],
    "host_transfers": 0,
    "donated_leaves": 27,
}


def train_budget_for(
    geometry: str, window_steps: int
) -> tp.Optional[tp.Dict[str, tp.Any]]:
    return TRAIN_BUDGETS.get((geometry, window_steps))


def check_train_budget(
    report: tp.Mapping[str, tp.Any],  # harness.train_traffic_cell dict
    budget: tp.Mapping[str, tp.Any],
    *,
    geometry: str = "",
    tolerance: float = TOLERANCE,
) -> tp.List[str]:
    """Evaluate one compiled window's measured wire bytes against its
    cell; returns violation strings (empty = pass). Bands work both
    ways (a collective that vanished means the compiler stopped
    sharding something, not that training got free) — except
    expected-zero tiers, which trip on ANY bytes: a single DCN byte on
    a single-slice mesh means a spec leaked across the slice axis."""
    out: tp.List[str] = []
    tag = f"train_window[{geometry}]" if geometry else "train_window"
    for tier in ("ici_bytes", "dcn_bytes"):
        expect = budget.get(tier)
        if expect is None:
            continue
        got = int(report.get(tier, 0))
        if expect == 0:
            if got:
                out.append(
                    f"{tag}: {got:,} B of {tier.split('_')[0].upper()} "
                    f"traffic where the budget expects NONE — a sharding "
                    "spec crossed the slice boundary (the cross-slice "
                    "re-gather bug class)"
                )
            continue
        lo = int(expect * (1 - tolerance))
        hi = int(expect * (1 + tolerance))
        if not (lo <= got <= hi):
            hint = ""
            if got > hi:
                hint = (
                    " — extra collective volume joined the step (an f32 "
                    "operand on a bf16 collective, or a re-gathered "
                    "buffer)"
                )
            out.append(
                f"{tag}: {tier} {got:,} B outside budget "
                f"[{lo:,}, {hi:,}] (expected ~{expect:,}){hint}"
            )
    expect_axes = budget.get("by_axis")
    if expect_axes is not None:
        got_axes = dict(report.get("by_axis", {}))
        for axis, b in got_axes.items():
            if axis not in expect_axes and b:
                out.append(
                    f"{tag}: unexpected collective axis '{axis}' "
                    f"carrying {b:,} B — a collective the budget has "
                    "never seen joined the window"
                )
        for axis, expect in expect_axes.items():
            got = int(got_axes.get(axis, 0))
            lo = int(expect * (1 - tolerance))
            hi = int(expect * (1 + tolerance))
            if not (lo <= got <= hi):
                out.append(
                    f"{tag}: axis '{axis}' {got:,} B outside budget "
                    f"[{lo:,}, {hi:,}] (expected ~{expect:,})"
                )
    return out


def check_train_dispatch_budget(
    report,  # dispatch.TrainDispatchReport
    budget: tp.Mapping[str, int] = TRAIN_DISPATCH_BUDGETS,
    *,
    aliased_leaves: tp.Optional[int] = None,
) -> tp.List[str]:
    """Evaluate the traced window's launch structure (plus, when
    ``aliased_leaves`` is given, the compiled donation accounting)
    against the train dispatch budget. Exact equality, like the
    serving dispatch cells — launch structure is integral."""
    out: tp.List[str] = []
    got = report.to_dict()
    for key in ("launches_per_window", "accum_scan_length",
                "host_transfers"):
        expect = budget.get(key)
        if expect is None:
            continue
        if got[key] != expect:
            hint = ""
            if key == "launches_per_window":
                hint = (
                    " — the K-step window scan is gone; every step pays "
                    "dispatch latency again"
                )
            elif key == "accum_scan_length" and got[key] == 0:
                hint = (
                    " — the grad-accum loop re-unrolled (G inlined "
                    "copies of the step body, zero bytes moved)"
                )
            elif key == "host_transfers":
                hint = " — a host callback joined the fused window"
            out.append(
                f"{report.program}: {key} {got[key]} != budget "
                f"{expect}{hint}"
            )
    expect_donated = budget.get("donated_leaves")
    if aliased_leaves is not None and expect_donated is not None:
        if aliased_leaves != expect_donated:
            out.append(
                f"{report.program}: {aliased_leaves} donated state "
                f"leaves aliased in the executable != budget "
                f"{expect_donated} — un-aliased donation doubles the "
                "train state's HBM residency"
            )
    return out


def train_geometry_key(mesh_shape: tp.Mapping[str, int]) -> tp.Optional[str]:
    """Reverse lookup: the TRAIN_AUDIT_GEOMETRIES name whose axis sizes
    match ``mesh_shape`` (num_slices included), or None."""
    probe = {k: v for k, v in mesh_shape.items() if v != 1}
    for name, axes in TRAIN_AUDIT_GEOMETRIES.items():
        ref = {k: v for k, v in axes.items() if v != 1}
        if probe == ref:
            return name
    return None
