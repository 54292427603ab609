"""Static dispatch/launch auditor for the serving programs.

The byte budgets (analysis.traffic/budgets) see what a program streams at
its entry interface; they cannot see how the program is launched. A
window split into several dispatches, a layer body traced twice, or a host
callback inside the window moves zero extra bytes there. This module is
the launch-side counterpart: it counts the dispatch structure statically
from the traced program, and
:func:`midgpt_tpu.analysis.budgets.check_dispatch_budget` gates it against
:func:`midgpt_tpu.analysis.budgets.dispatch_budget` of the traced model:

- **launches per window** — XLA dispatches the engine must issue per
  scheduler window for this program. The decode window's K-step scan
  must cover all ``window_steps`` model steps, or the remainder would
  need extra launches (the PR 2/PR 3 fused-dispatch contract, now
  machine-checked).
- **inlined layer bodies** — how many copies of the per-layer attention
  arithmetic the flat trace carries (choreo.py's region extractor): one
  per attention layer.
- **host transfers** — callback/infeed/outfeed primitives anywhere in
  the program (each is a device->host sync per dispatch; the budget
  pins 0, the jaxpr-level twin of the compiled no-host-sync rule).

Operates on jaxprs (no compilation).
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
    """One attention-carrying ``lax.scan`` in the traced program."""

    length: int  # trip count
    depth: int  # scan-nesting depth (0 = top level)
    attention_regions: int  # inlined layer bodies in its FLAT body

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class DispatchReport:
    """Static launch structure of one traced serving program."""

    program: str
    window_steps: int  # model steps one scheduler window must cover
    scans: tp.Tuple[ScanInfo, ...]  # attention-carrying scans only
    inlined_layer_bodies: int  # attention regions in the flat trace
    host_transfers: int

    @property
    def launches_per_window(self) -> int:
        """XLA dispatches per scheduler window: the longest attention
        scan must cover all ``window_steps`` model steps in one launch
        (ceil of the shortfall otherwise). Programs that run one model
        step per window (prefill chunk, verify) are one launch by
        construction."""
        steps_per_launch = max((s.length for s in self.scans), default=1)
        return -(-self.window_steps // steps_per_launch)

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return {
            "program": self.program,
            "window_steps": self.window_steps,
            "scans": [s.to_dict() for s in self.scans],
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
          host: tp.List[int]) -> None:
    """Recursive eqn walk: every attention-carrying scan at any depth
    into ``scans``, every host-transfer primitive counted in ``host``."""
    for eqn in jpr.eqns:
        name = eqn.primitive.name
        if name in _HOST_TRANSFER_PRIMS:
            host[0] += 1
        if name == "scan":
            body = eqn.params.get("jaxpr")
            _walk(getattr(body, "jaxpr", body), depth + 1, scans, host)
            regions = len(attention_regions(flatten_jaxpr(body)))
            if regions:
                scans.append(ScanInfo(
                    length=int(eqn.params.get("length", 0)),
                    depth=depth,
                    attention_regions=regions,
                ))
            continue
        for p in _param_jaxprs(eqn.params):
            _walk(getattr(p, "jaxpr", p), depth, scans, host)


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
    prefill chunk and the verify program)."""
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
