"""Mixed-precision choreography prover for the fused K-step TRAIN window.

train.py states its precision contract in prose: f32 master params and
f32 Adam moments, bf16 matmul operands (``cast_floating(state.params,
compute_dtype)``), f32 loss/softmax accumulation, a grad-accum scan
whose carried grads stay in compute dtype with one f32 loss scalar, and
a remat policy whose checkpointed segments recompute the forward
op-for-op. Every one of those clauses has a serving-side twin that
shipped as a real bug before the choreography prover existed (PR 8's
bf16 drift class), and the training side has none of that machine
checking — a ``mu_dtype=bfloat16`` slipped into the optimizer chain, an
accidental f32 upcast before the projections, or a remat policy that
recomputes something *else* would all train, converge slightly worse,
and burn a hardware round to notice.

This module proves the contract on the traced jaxpr of the REAL fused
window program (``train.get_train_window`` — the same cache the trainer
launches from), using :mod:`midgpt_tpu.analysis.choreo`'s flattened-
trace machinery. All checks are dtype/structure assertions on the
trace + the ``jax.eval_shape`` output tree; nothing executes.

Scope note — collective operand dtypes: the jaxpr of a pjit program
contains no collectives (GSPMD materializes them at compile time), so
psum/all-reduce wire dtypes are NOT provable here. They are gated
byte-wise by the train traffic budgets
(:data:`midgpt_tpu.analysis.budgets.TRAIN_BUDGETS` — an f32 gather of
a bf16 shard doubles its wire bytes and trips the band), which is the
stronger check anyway.

Deferral semantics — the grad-accum carry check: when the trace has no
grad-accum scan at all (``g_accum_iters == 1``, or the re-unrolled-loop
fault class), there is no carry whose dtype could be wrong, so the
check reports ok with an explicit "no grad-accum scan in trace" detail
— the *structure* (trip count == G) is the dispatch budget's gate
(:func:`midgpt_tpu.analysis.dispatch.train_dispatch_report`), and the
green-path tests assert the "found" detail so the check can never pass
vacuously on the shipped configs.
"""

from __future__ import annotations

import dataclasses
import typing as tp
from collections import Counter

from midgpt_tpu.analysis.choreo import (
    ChoreoCheck,
    FlatGraph,
    TraceRec,
    _FLOAT_DTYPES,
    attention_regions,
    flatten_jaxpr,
    normalized_trace,
)

__all__ = [
    "ScanRec",
    "TrainChoreoReport",
    "collapse_dot_kinds",
    "find_accum_scan",
    "find_window_scan",
    "prove_window_choreography",
    "window_scans",
]


# ---------------------------------------------------------------------------
# Scan discovery (jaxpr walk)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScanRec:
    """One ``lax.scan`` in the traced window, with its carry avals.

    ``depth`` counts enclosing scans only (call-like primitives — pjit,
    remat, custom_vjp — are transparent): the window scan sits at depth
    0, the grad-accum scan at depth 1, the layer scans at depth 2."""

    depth: int
    length: int
    carry_dtypes: tp.Tuple[str, ...]
    carry_shapes: tp.Tuple[tp.Tuple[int, ...], ...]

    @property
    def int32_scalar_carries(self) -> int:
        return sum(
            1
            for d, s in zip(self.carry_dtypes, self.carry_shapes)
            if d == "int32" and s == ()
        )

    @property
    def float_carries(self) -> int:
        return sum(1 for d in self.carry_dtypes if d in _FLOAT_DTYPES)


def _sub_jaxprs(params: tp.Mapping[str, tp.Any]):
    for p in params.values():
        cands = p if isinstance(p, (tuple, list)) else (p,)
        for c in cands:
            if hasattr(c, "eqns"):
                yield c
            elif hasattr(c, "jaxpr"):
                yield c.jaxpr


def window_scans(closed) -> tp.List[ScanRec]:
    """Every scan in the closed jaxpr, depth-annotated (scans nest,
    call-like wrappers are transparent), in traversal order."""
    out: tp.List[ScanRec] = []

    def walk(jpr, depth: int) -> None:
        for eqn in jpr.eqns:
            if eqn.primitive.name == "scan":
                nc = int(eqn.params.get("num_consts", 0))
                ncarry = int(eqn.params.get("num_carry", 0))
                carry = eqn.invars[nc : nc + ncarry]
                out.append(ScanRec(
                    depth=depth,
                    length=int(eqn.params.get("length", 0)),
                    carry_dtypes=tuple(
                        str(v.aval.dtype) for v in carry
                    ),
                    carry_shapes=tuple(
                        tuple(v.aval.shape) for v in carry
                    ),
                ))
                body = eqn.params.get("jaxpr")
                if body is not None:
                    walk(getattr(body, "jaxpr", body), depth + 1)
            else:
                for sub in _sub_jaxprs(eqn.params):
                    walk(sub, depth)

    walk(closed.jaxpr, 0)
    return out


def find_window_scan(
    scans: tp.Sequence[ScanRec], window_steps: int
) -> tp.Optional[ScanRec]:
    """The K-step window scan: a depth-0 scan of length K carrying the
    optimizer state — identified by the int32 scalar(s) in its carry
    (``state.step`` + the optax count leaves), which no data-plane scan
    carries."""
    for s in scans:
        if (
            s.depth == 0
            and s.length == window_steps
            and s.int32_scalar_carries >= 1
        ):
            return s
    return None


def find_accum_scan(
    scans: tp.Sequence[ScanRec], has_window_scan: bool
) -> tp.Optional[ScanRec]:
    """The grad-accum scan: nested directly inside the window scan body
    (depth 1 — or 0 when the window scan itself is absent), carrying the
    whole grad tree plus the f32 loss accumulator. The layer scans nest
    deeper and carry a single activation leaf, so ``float_carries >= 3``
    separates them even when ``n_layer == g_accum_iters``."""
    depth = 1 if has_window_scan else 0
    for s in scans:
        if s.depth == depth and s.float_carries >= 3:
            return s
    return None


# ---------------------------------------------------------------------------
# Trace helpers
# ---------------------------------------------------------------------------


def collapse_dot_kinds(rec: TraceRec) -> TraceRec:
    """Fold the dot sub-kinds (proj/rope/dot) into one. Inside a remat
    recompute the rope tables arrive as scan-body vars instead of
    consts, so the recomputed rotation dots classify as 'dot' where the
    forward's classified 'rope' — the op-for-op comparison must not
    care."""
    kind, ins, outs = rec
    if kind in ("proj", "rope", "dot"):
        return ("dot", ins, outs)
    return rec


def _float_leaves(tree) -> tp.List[str]:
    import jax

    return [
        str(leaf.dtype)
        for leaf in jax.tree.leaves(tree)
        if str(leaf.dtype) in _FLOAT_DTYPES
    ]


# ---------------------------------------------------------------------------
# The report
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainChoreoReport:
    """The train-window choreography proof: one ChoreoCheck per contract
    clause, plus the traced program names for the report."""

    checks: tp.Tuple[ChoreoCheck, ...]
    programs: tp.Tuple[str, ...]

    @property
    def ok(self) -> bool:
        return all(c.ok for c in self.checks)

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "ok": self.ok,
            "programs": list(self.programs),
            "checks": [
                {"name": c.name, "ok": c.ok, "detail": c.detail}
                for c in self.checks
            ],
        }


def prove_window_choreography(
    closed,
    out_tree,
    *,
    window_steps: int,
    g_accum_iters: int,
    compute_dtype: str = "bfloat16",
    param_dtype: str = "float32",
    n_param_leaves: tp.Optional[int] = None,
    remat_closed=None,
    program: str = "train_window",
) -> TrainChoreoReport:
    """Prove the train-window precision contract on a traced jaxpr.

    ``closed`` is ``jax.make_jaxpr(window)(state, xs, ys, key)`` for the
    remat="none" leg; ``out_tree`` the matching ``jax.eval_shape``
    result ``(new_state, aux)``. ``remat_closed``, when given, is the
    same trace with ``remat="full"`` and enables the remat-structure
    check (checkpointed segments recompute the forward op-for-op)."""
    checks: tp.List[ChoreoCheck] = []
    programs = [program]
    graph = flatten_jaxpr(closed)
    trace = normalized_trace(graph)

    # -- 1. matmul compute dtype: every weight-bearing dot runs on ----
    #       compute-dtype operands (the bf16 matmul clause)
    projs = [r for r in trace if r[0] == "proj"]
    bad = [
        r for r in projs
        if any(d in _FLOAT_DTYPES and d != compute_dtype for d in r[1])
    ]
    if not projs:
        checks.append(ChoreoCheck(
            "matmul-compute-dtype", False,
            "degenerate trace: no weight-bearing dots found",
        ))
    else:
        checks.append(ChoreoCheck(
            "matmul-compute-dtype", not bad,
            (
                f"{len(projs)} weight dots, all operands {compute_dtype}"
                if not bad
                else f"{len(bad)}/{len(projs)} weight dots carry non-"
                f"{compute_dtype} float operands, first: {bad[0]!r}"
            ),
        ))

    # -- 2. master params stay param_dtype, cast at the step boundary --
    new_state = out_tree[0]
    pdtypes = Counter(_float_leaves(new_state.params))
    params_ok = set(pdtypes) == {param_dtype}
    casts = [
        op for op in graph.ops
        if op.prim == "convert_element_type"
        and op.in_dtypes == (param_dtype,)
        and op.out_dtypes == (compute_dtype,)
        and "invar" in op.in_origins
    ]
    n_leaves = len(_float_leaves(new_state.params))
    want_casts = n_param_leaves if n_param_leaves is not None else n_leaves
    casts_ok = len(casts) >= want_casts
    checks.append(ChoreoCheck(
        "master-params-dtype", params_ok and casts_ok,
        (
            f"{n_leaves} param leaves {param_dtype}; "
            f"{len(casts)} {param_dtype}->{compute_dtype} input-origin "
            f"casts (cast_floating boundary, want >= {want_casts})"
            if params_ok and casts_ok
            else f"param leaf dtypes {dict(pdtypes)}, "
            f"{len(casts)} boundary casts (want >= {want_casts})"
        ),
    ))

    # -- 3. Adam moments stay param_dtype -----------------------------
    odtypes = Counter(_float_leaves(new_state.opt_state))
    moments_ok = set(odtypes) <= {param_dtype}
    checks.append(ChoreoCheck(
        "adam-moments-dtype", moments_ok,
        (
            f"{sum(odtypes.values())} optimizer float leaves, "
            f"all {param_dtype}"
            if moments_ok
            else f"optimizer float leaf dtypes {dict(odtypes)} — a "
            f"low-precision moment quietly degrades Adam's second-"
            f"moment tracking (the mu_dtype bug class)"
        ),
    ))

    # -- 4. softmax/loss accumulate in f32 -----------------------------
    exps = [r for r in trace if r[0] == "exp"]
    bad_exp = [
        r for r in exps
        if any(d != "float32" for d in r[1] + r[2])
    ]
    aux = out_tree[1]
    loss_dtype = str(aux["loss"].dtype) if "loss" in aux else "missing"
    softmax_ok = bool(exps) and not bad_exp and loss_dtype == "float32"
    checks.append(ChoreoCheck(
        "softmax-loss-f32", softmax_ok,
        (
            f"{len(exps)} exp ops all f32, loss output {loss_dtype}"
            if softmax_ok
            else f"exps={len(exps)} (bad: {bad_exp[:1]!r}), "
            f"loss output {loss_dtype}"
        ),
    ))

    # -- 5. grad-accum scan carry dtypes (deferral semantics) ----------
    scans = window_scans(closed)
    wscan = find_window_scan(scans, window_steps)
    ascan = find_accum_scan(scans, wscan is not None)
    if ascan is None:
        checks.append(ChoreoCheck(
            "grad-accum-carry", True,
            "no grad-accum scan in trace (structure gated by the "
            "dispatch budget)",
        ))
    else:
        bad_carry = [
            (d, s)
            for d, s in zip(ascan.carry_dtypes, ascan.carry_shapes)
            if d in _FLOAT_DTYPES and s != () and d != compute_dtype
        ]
        f32_scalars = sum(
            1
            for d, s in zip(ascan.carry_dtypes, ascan.carry_shapes)
            if d == "float32" and s == ()
        )
        ok = not bad_carry and f32_scalars >= 1
        checks.append(ChoreoCheck(
            "grad-accum-carry", ok,
            (
                f"found: length={ascan.length}, "
                f"{ascan.float_carries - f32_scalars} grad leaves "
                f"{compute_dtype}, {f32_scalars} f32 scalar accumulator"
                if ok
                else f"found: length={ascan.length}, non-{compute_dtype} "
                f"grad carries {bad_carry[:2]!r}, f32 scalars "
                f"{f32_scalars}"
            ),
        ))

    # -- 6. the window scan itself (carries the int32 step) ------------
    checks.append(ChoreoCheck(
        "window-scan-carry", wscan is not None,
        (
            f"window scan length={wscan.length}, "
            f"{wscan.int32_scalar_carries} int32 scalar carries "
            f"(state.step + optax counts)"
            if wscan is not None
            else f"no depth-0 scan of length {window_steps} with an "
            "int32 scalar carry — the fused window structure is gone "
            "(see the dispatch budget for the launch accounting)"
        ),
    ))

    # -- 7. remat: checkpointed segments recompute the forward ---------
    # The un-remat'd trace is a linearized forward: its first region
    # is the layer's forward arithmetic with the by-products the
    # backward will want (2x beside x**2, the softmax's x**-2)
    # interleaved. Under remat the primal pass saves nothing, so its
    # region is that same forward WITHOUT the by-products — an ordered
    # subsequence — and a later (checkpointed) segment must hold the
    # whole linearized forward again.
    if remat_closed is not None:
        programs.append(program + "+remat")
        base_regions = attention_regions(graph)
        remat_regions = attention_regions(flatten_jaxpr(remat_closed))
        fwd = [
            collapse_dot_kinds(r)
            for r in (base_regions[0] if base_regions else ())
        ]
        primal = [
            collapse_dot_kinds(r)
            for r in (remat_regions[0] if remat_regions else ())
        ]
        rest = iter(fwd)
        preserved = bool(primal) and all(r in rest for r in primal)
        recompute_ok = any(
            not (Counter(fwd) - Counter(collapse_dot_kinds(r) for r in e))
            for e in remat_regions[1:]
        )
        ok = bool(base_regions) and preserved and recompute_ok
        checks.append(ChoreoCheck(
            "remat-recompute", ok,
            (
                f"primal forward ({len(primal)} ops) is the linearized "
                f"forward ({len(fwd)} ops) in order; "
                f"{len(remat_regions) - 1} checkpointed segment(s), one "
                "contains the linearized forward op-for-op"
                if ok
                else f"base regions={len(base_regions)}, remat regions="
                f"{len(remat_regions)} (primal-is-forward={preserved}, "
                f"forward-recomputed={recompute_ok}) — the remat "
                "policy recomputes something other than the forward"
            ),
        ))

    return TrainChoreoReport(
        checks=tuple(checks), programs=tuple(programs)
    )
