"""The profiler's trace, and its reduction to intervals: started and stopped
by the harness inside the timed window, read back from the ``.xplane.pb``
with ``jax.profiler.ProfileData``. Readers get a :class:`TraceData`."""

from __future__ import annotations

import contextlib
import glob
import os
import re
import time
import typing as tp

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"

Event = tp.Tuple[str, float, float]  # name, start seconds, duration seconds
CUSTOM_CALL = "[custom-call]"
NAME_CHARS = 160  # an operation's name is its HLO text: keep the head of it


class TraceData:
    """Events by plane and line, times in seconds on the trace's own clock."""

    def __init__(self, lines: tp.Dict[tp.Tuple[str, str], tp.List[Event]]):
        self.lines = lines

    @classmethod
    def from_file(cls, path: str) -> "TraceData":
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        lines: tp.Dict[tp.Tuple[str, str], tp.List[Event]] = {}
        for plane in data.planes:
            for line in plane.lines:
                evs = []
                for e in line.events:
                    name = e.name
                    short = name[:NAME_CHARS]
                    if "custom-call(" in name:
                        short += " " + CUSTOM_CALL  # a kernel, or a runtime call
                    evs.append((short, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9))
                if evs:
                    lines.setdefault((plane.name, line.name), []).extend(evs)
        return cls(lines)

    def select(self, plane: str, line: str) -> tp.Dict[str, tp.List[Event]]:
        """Events of every line matching, grouped by plane name."""
        out: tp.Dict[str, tp.List[Event]] = {}
        for (p, ln), evs in self.lines.items():
            if re.search(plane, p) and re.search(line, ln):
                out.setdefault(p, []).extend(evs)
        return out

    def device_ops(self, leaves: bool = False) -> tp.Dict[str, tp.List[Event]]:
        """The device's operations by device. ``leaves`` drops an operation
        that encloses others (a ``while`` over the steps of a window), so
        that no time is counted twice."""
        ops = self.select(DEVICE_PLANE, OPS_LINE)
        if leaves:
            ops = {p: leaf_events(evs) for p, evs in ops.items()}
        return ops

    def span(self) -> tp.Tuple[float, float]:
        """First start and last end over the device's operations."""
        evs = [e for v in self.device_ops().values() for e in v]
        if not evs:
            return (0.0, 0.0)
        return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))


def busy_and_window(trace: TraceData) -> tp.Optional[tp.Tuple[float, float]]:
    """Seconds in which an operation ran on the device, averaged over the
    devices traced, and the length of the traced span; None where no device
    operation was traced."""
    ops = trace.device_ops()
    lo, hi = trace.span()
    if not ops or hi <= lo:
        return None
    busy = sum(union_seconds(evs) for evs in ops.values()) / len(ops)
    return busy, hi - lo


def leaf_events(evs: tp.Iterable[Event]) -> tp.List[Event]:
    """The events without the ones that only enclose others: an event is
    dropped where the events starting inside it fill half of it or more (a
    ``while`` over a window's steps; not a fusion during which a 2 ns
    ``copy-start`` is stamped). Times are whole nanoseconds turned to
    seconds: an event that starts within 2 ns of another's end follows it."""
    eps = 2e-9
    out: tp.List[Event] = []
    open_: tp.List[tp.List[tp.Any]] = []  # [event, seconds of its children]

    def close() -> None:
        ev, inside = open_.pop()
        if ev[2] == 0 or inside < 0.5 * ev[2]:
            out.append(ev)
        if open_:
            open_[-1][1] += ev[2]

    for ev in sorted(evs, key=lambda e: (e[1], -e[2])):
        while open_ and ev[1] >= open_[-1][0][1] + open_[-1][0][2] - eps:
            close()
        open_.append([ev, 0.0])
    while open_:
        close()
    return out


def union_seconds(evs: tp.Iterable[Event]) -> float:
    busy, end = 0.0, -1.0
    for _, s, d in sorted(evs, key=lambda e: e[1]):
        if s + d <= end:
            continue
        busy += s + d - max(s, end)
        end = s + d
    return busy


def gaps(evs: tp.Iterable[Event]) -> tp.List[tp.Tuple[float, float]]:
    """Idle intervals (start, duration) between the operations."""
    out, end = [], None
    for _, s, d in sorted(evs, key=lambda e: e[1]):
        if end is not None and s > end:
            out.append((end, s - end))
        end = s + d if end is None else max(end, s + d)
    return out


class Tracer:
    """Starts the profiler ``start_at`` seconds into the window and stops it
    ``duration`` seconds later. Disabled, it does nothing, and its
    annotations cost one ``nullcontext``. Starting and stopping block the
    host for seconds (the device drains meanwhile): ``stalls`` keeps both
    calls' intervals, so that a host-clock metric of a traced run can leave
    them out."""

    def __init__(self, enabled: bool, out_dir: str, start_at: float,
                 duration: float):
        self.enabled, self.dir = enabled, out_dir
        self.start_at, self.duration = start_at, duration
        self.started = self.stopped = False
        self.t_start = self.t_stop = 0.0
        self.stalls: tp.List[tp.Tuple[float, float]] = []

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def poll(self, elapsed: float) -> None:
        if not self.enabled or self.stopped:
            return
        import jax

        if not self.started and elapsed >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the harness's annotations stay
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started, self.t_start = True, time.perf_counter()
            self.stalls.append((t, self.t_start))
        elif self.started and elapsed >= self.start_at + self.duration:
            self.finish()

    def finish(self) -> None:
        if self.started and not self.stopped:
            import jax

            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stopped, self.t_stop = True, time.perf_counter()
            self.stalls.append((t, self.t_stop))

    def stalled(self, lo: float, hi: float) -> float:
        """Seconds of ``lo`` .. ``hi`` spent inside the profiler's calls."""
        return sum(max(0.0, min(hi, e) - max(lo, s)) for s, e in self.stalls)

    def _file(self) -> tp.Optional[str]:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return max(found, key=os.path.getmtime) if found else None

    def load(self) -> tp.Optional[TraceData]:
        path = self._file() if self.stopped else None
        return TraceData.from_file(path) if path else None

    def keep_copy(self, dst: str) -> None:
        import gzip
        import shutil

        path = self._file()
        if path:
            with open(path, "rb") as src, gzip.open(dst, "wb") as out:
                shutil.copyfileobj(src, out)


def summary(trace: TraceData, top: int = 40) -> tp.Dict[str, tp.Any]:
    """Planes, lines and their heaviest names: what one reads by hand before
    writing an expression into a metric file."""
    out = {}
    for (p, ln), evs in sorted(trace.lines.items()):
        tot: tp.Dict[str, tp.List[float]] = {}
        for n, _, d in evs:
            acc = tot.setdefault(n, [0.0, 0])
            acc[0] += d
            acc[1] += 1
        heavy = sorted(tot.items(), key=lambda kv: -kv[1][0])[:top]
        out[f"{p} | {ln}"] = {
            "events": len(evs),
            "top": [[n, round(v[0], 6), v[1]] for n, v in heavy],
            "custom": [[n, round(v[0], 6), v[1]] for n, v in tot.items()
                       if CUSTOM_CALL in n and v[0] > 0],
        }
    return out


def breakdown(trace: TraceData, labels: str) -> tp.Dict[str, tp.Any]:
    """The ten heaviest device operations, and the ten longest idle gaps on
    the first device, each labelled by the harness's own annotation (names
    matching ``labels``) that covers most of it."""
    ops = trace.device_ops(leaves=True)
    if not ops:
        return {"device_ops": [], "idle_gaps": []}
    tot: tp.Dict[str, float] = {}
    for evs in ops.values():
        for n, _, d in evs:
            tot[n] = tot.get(n, 0.0) + d
    n_dev = len(ops)
    heavy = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
    host = [e for evs in trace.select(HOST_PLANE, ".").values() for e in evs
            if re.search(labels, e[0])]
    first = ops[sorted(ops)[0]]
    by_label: tp.Dict[str, float] = {}
    for s, d in gaps(first):
        best, cover = "unlabelled", 0.0
        for n, hs, hd in host:
            c = min(s + d, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = n, c
        by_label[best] = by_label.get(best, 0.0) + d
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[n, v / n_dev] for n, v in heavy],
        "idle_gaps": [[n, v] for n, v in idle],
    }
