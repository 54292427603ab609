"""Static dispatch/launch auditor for the serving programs.

The residual between r5's measured 0.905 ms/tok and the 0.278 ms HBM
floor is LAUNCH structure, not bytes (PERF.md): the whole-model decode
step unrolls its layer loop, so every window dispatch carries L inlined
copies of the per-layer kernel set — L times the launch overhead, L
times the executable size, and [B, 1, D] matmul shapes that cannot
amortize any of it. The byte budgets (analysis.traffic/budgets) cannot
see this class of regression: re-unrolling a folded loop moves ZERO
bytes at the entry interface. This module is the launch-side
counterpart — count the dispatch structure statically from the traced
program and gate it against checked-in budgets, exactly like the HBM
byte budgets:

- **launches per window** — XLA dispatches the engine must issue per
  scheduler window for this program. The decode window's K-step scan
  must cover all ``window_steps`` model steps, or the remainder would
  need extra launches (the PR 2/PR 3 fused-dispatch contract, now
  machine-checked).
- **scan trip structure** — every attention-carrying ``lax.scan`` in
  the traced program, with trip count and nesting depth; the fused
  program must show the layer loop as a scan of trip ``n_layer``
  (``layer_scan_length``) nested inside the window scan, and a
  re-unrolled program shows ``layer_scan_length == 0`` and fails the
  "on" budget.
- **inlined layer bodies** — how many copies of the per-layer attention
  arithmetic the flat trace carries (choreo.py's region extractor):
  1 when folded, ``n_layer`` when unrolled.
- **host transfers** — callback/infeed/outfeed primitives anywhere in
  the program (each is a device->host sync per dispatch; the budget
  pins 0, the jaxpr-level twin of the compiled no-host-sync rule).

Operates on jaxprs (no compilation); budgets live in
:data:`midgpt_tpu.analysis.budgets.DISPATCH_BUDGETS`, keyed by
``(program, layer_scan)`` at the audit geometry, and are gated by
:func:`midgpt_tpu.analysis.budgets.check_dispatch_budget`.
"""

from __future__ import annotations

import dataclasses
import typing as tp

from midgpt_tpu.analysis.choreo import attention_regions, flatten_jaxpr

# primitives that force a device->host transfer inside the program
_HOST_TRANSFER_PRIMS = frozenset({
    "io_callback", "pure_callback", "python_callback", "callback",
    "outside_call", "host_callback_call", "debug_callback", "infeed",
    "outfeed",
})


@dataclasses.dataclass(frozen=True)
class ScanInfo:
    """One ``lax.scan`` in the traced program."""

    length: int  # trip count
    depth: int  # scan-nesting depth (0 = top level)
    attention_regions: int  # inlined layer bodies in its FLAT body
    has_nested_attention_scan: bool  # an attention scan nests inside

    @property
    def is_layer_scan(self) -> bool:
        """The layer fold: an attention-carrying scan whose body holds
        exactly ONE inlined layer and no deeper attention scan — its
        trip count is the layer count. (The decode window's K-step scan
        has a NESTED layer scan when fused, or multiple inlined bodies
        when unrolled, so it never matches.)"""
        return (
            self.attention_regions == 1
            and not self.has_nested_attention_scan
        )

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "length": self.length,
            "depth": self.depth,
            "attention_regions": self.attention_regions,
            "is_layer_scan": self.is_layer_scan,
        }


@dataclasses.dataclass(frozen=True)
class DispatchReport:
    """Static launch structure of one traced serving program."""

    program: str
    window_steps: int  # model steps one scheduler window must cover
    scans: tp.Tuple[ScanInfo, ...]  # attention-carrying scans only
    inlined_layer_bodies: int  # attention regions in the flat trace
    host_transfers: int

    @property
    def layer_scan_length(self) -> int:
        """Trip count of the folded layer loop; 0 = unrolled."""
        for s in self.scans:
            if s.is_layer_scan:
                return s.length
        return 0

    @property
    def launches_per_window(self) -> int:
        """XLA dispatches per scheduler window: the outermost NON-layer
        attention scan must cover all ``window_steps`` model steps in
        one launch (ceil of the shortfall otherwise). Programs that run
        one model step per window (prefill chunk, verify) are one
        launch by construction."""
        steps_per_launch = max(
            (s.length for s in self.scans if not s.is_layer_scan),
            default=1,
        )
        return -(-self.window_steps // steps_per_launch)

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "program": self.program,
            "window_steps": self.window_steps,
            "scans": [s.to_dict() for s in self.scans],
            "layer_scan_length": self.layer_scan_length,
            "inlined_layer_bodies": self.inlined_layer_bodies,
            "launches_per_window": self.launches_per_window,
            "host_transfers": self.host_transfers,
        }


def _param_jaxprs(params: tp.Mapping[str, tp.Any]) -> tp.Iterator[tp.Any]:
    """Every jaxpr-like value in an eqn's params — including ones nested
    inside tuple/list params (``lax.cond``'s ``branches`` is a plain
    tuple of ClosedJaxprs; a bare hasattr test over params.values()
    would skip it and let a callback hidden in a cond branch pass the
    host-transfer gate vacuously)."""
    for p in params.values():
        candidates = p if isinstance(p, (tuple, list)) else (p,)
        for c in candidates:
            if hasattr(c, "eqns") or hasattr(c, "jaxpr"):
                yield c


def _walk(jpr, depth: int, scans: tp.List[ScanInfo],
          host: tp.List[int]) -> bool:
    """Recursive eqn walk; returns True when this jaxpr (transitively)
    contains attention arithmetic inside a scan at any depth."""
    found_attn_scan = False
    for eqn in jpr.eqns:
        name = eqn.primitive.name
        if name in _HOST_TRANSFER_PRIMS:
            host[0] += 1
        if name == "scan":
            body = eqn.params.get("jaxpr")
            inner = getattr(body, "jaxpr", body)
            nested_attn = _walk(inner, depth + 1, scans, host)
            regions = len(attention_regions(flatten_jaxpr(body)))
            if regions:
                scans.append(ScanInfo(
                    length=int(eqn.params.get("length", 0)),
                    depth=depth,
                    attention_regions=regions,
                    has_nested_attention_scan=nested_attn,
                ))
                found_attn_scan = True
            found_attn_scan = found_attn_scan or nested_attn
            continue
        for p in _param_jaxprs(eqn.params):
            sub = getattr(p, "jaxpr", p)
            found_attn_scan = (
                _walk(sub, depth, scans, host) or found_attn_scan
            )
    return found_attn_scan


@dataclasses.dataclass(frozen=True)
class TrainDispatchReport:
    """Static launch structure of the traced K-step TRAIN window.

    The training-side dispatch contract (train.make_train_window):

    - the whole window is ONE XLA dispatch — a depth-0 scan of trip
      count K carrying the optimizer state (``window_scan_length``);
      K separate launches would re-pay the dispatch latency the
      fused window exists to amortize;
    - the grad-accum loop inside each step is a ``lax.scan`` of trip
      count G (``accum_scan_length``) — re-unrolling it (the PR 11
      serving bug class, training-side) moves zero wire bytes but
      multiplies the compiled body by G;
    - no host transfers anywhere in the window (a mid-window callback
      serializes the whole fused dispatch).

    Donation accounting (100% of the donated state aliased) needs the
    compiled HLO, so it rides the traffic cell
    (:func:`midgpt_tpu.analysis.harness.train_traffic_cell`), not this
    trace-level report."""

    program: str
    window_steps: int  # expected K
    g_accum_iters: int  # expected G
    window_scan_length: int  # traced window-scan trip count (0 = absent)
    accum_scan_length: int  # traced accum-scan trip count (0 = absent)
    accum_carry_leaves: int  # float leaves carried by the accum scan
    host_transfers: int

    @property
    def launches_per_window(self) -> int:
        """1 when the K-step window scan is intact; K when the window
        structure is gone (each step body would need its own launch to
        preserve the step boundary the trainer observes)."""
        return (
            1
            if self.window_scan_length == self.window_steps
            else self.window_steps
        )

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "program": self.program,
            "window_steps": self.window_steps,
            "g_accum_iters": self.g_accum_iters,
            "window_scan_length": self.window_scan_length,
            "accum_scan_length": self.accum_scan_length,
            "accum_carry_leaves": self.accum_carry_leaves,
            "launches_per_window": self.launches_per_window,
            "host_transfers": self.host_transfers,
        }


def train_dispatch_report(
    closed_jaxpr, *, window_steps: int, g_accum_iters: int,
    program: str = "train_window",
) -> TrainDispatchReport:
    """Build the :class:`TrainDispatchReport` from a traced window
    jaxpr (``jax.make_jaxpr`` over ``train.get_train_window``'s
    program — no compilation). Scan identification is structural:
    the window scan is the depth-0 scan carrying an int32 scalar
    (``state.step`` + optax counts); the accum scan nests directly
    inside it and carries the whole grad tree plus the f32 loss
    accumulator (>= 3 float leaves — the layer scans carry one)."""
    from midgpt_tpu.analysis.train_choreo import (
        find_accum_scan,
        find_window_scan,
        window_scans,
    )

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    host = [0]
    _count_host_transfers(jaxpr, host)
    scans = window_scans(closed_jaxpr)
    wscan = find_window_scan(scans, window_steps)
    ascan = find_accum_scan(scans, wscan is not None)
    return TrainDispatchReport(
        program=program,
        window_steps=window_steps,
        g_accum_iters=g_accum_iters,
        window_scan_length=wscan.length if wscan is not None else 0,
        accum_scan_length=ascan.length if ascan is not None else 0,
        accum_carry_leaves=ascan.float_carries if ascan is not None else 0,
        host_transfers=host[0],
    )


def _count_host_transfers(jpr, host: tp.List[int]) -> None:
    for eqn in jpr.eqns:
        if eqn.primitive.name in _HOST_TRANSFER_PRIMS:
            host[0] += 1
        for p in _param_jaxprs(eqn.params):
            _count_host_transfers(getattr(p, "jaxpr", p), host)


def dispatch_report(
    closed_jaxpr, *, program: str, window_steps: int = 1
) -> DispatchReport:
    """Build the :class:`DispatchReport` for one traced program.
    ``window_steps`` is the number of model steps one scheduler window
    must cover with this program (the decode window's K; 1 for the
    prefill chunk and the verify program).

    Note the ``n_layer >= 2`` requirement of the audit geometry: at a
    single layer an unrolled window body is indistinguishable from a
    folded one (one inlined body either way)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    scans: tp.List[ScanInfo] = []
    host = [0]
    _walk(jaxpr, 0, scans, host)
    flat = flatten_jaxpr(closed_jaxpr)
    return DispatchReport(
        program=program,
        window_steps=window_steps,
        scans=tuple(sorted(scans, key=lambda s: (s.depth, -s.length))),
        inlined_layer_bodies=len(attention_regions(flat)),
        host_transfers=host[0],
    )
