"""Host time of the program's own spans, in milliseconds: the summed
duration of the host-plane events whose name matches ``spans``, divided by
the number of events matching ``per`` (or of the matched events themselves,
for the word ``event``). With ``self_time`` a span counts for its duration
minus the part that other ``midgpt.`` spans inside it cover, so that a
parent and its children never count a millisecond twice. The program
writes these spans itself (``midgpt_tpu.telemetry.span``, a
``jax.profiler.TraceAnnotation``), on the clock of the device's events.

Every Python thread's line has one name and ``TraceData`` merges them: a
span's children are the spans it contains in time. Only spans, and ``per``
events, that lie wholly between the device's first and last operation
count, and with a ``per`` pattern only spans inside one of its events: one
cut by the trace's edge has no duration to speak of, and the children of a
step that was cut would be counted against a step that was not."""

import re

from benchmark import trace as tr

PROGRAM = r"^midgpt\."


def host_spans(ctx, pattern, whole=True):
    """The host plane's events matching ``pattern`` as (name, start, end),
    by start, and the traced device interval; None without a trace or a
    device operation in it. ``whole`` keeps only events inside the
    interval."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    lo, hi = trace.span()
    if hi <= lo:
        return None
    evs = [(n, s, s + d)
           for line in trace.select(tr.HOST_PLANE, ".").values()
           for n, s, d in line if re.search(pattern, n)]
    if whole:
        evs = [e for e in evs if e[1] >= lo and e[2] <= hi]
    return sorted(evs, key=lambda e: (e[1], -e[2])), (lo, hi)


def self_seconds(span, others):
    """``span``'s duration less what the ``others`` inside it cover."""
    _, s, e = span
    inside = [("", o[1], o[2] - o[1]) for o in others
              if o is not span and o[1] >= s and o[2] <= e]
    return (e - s) - tr.union_seconds(inside)


def read(ctx, spans, per="event", self_time=True, **_):
    got = host_spans(ctx, PROGRAM)
    if got is None:
        return None
    program, _ = got
    hits = [e for e in program if re.search(spans, e[0])]
    if per != "event":
        units = [e for e in program if re.search(per, e[0])]
        hits = [h for h in hits
                if any(u[1] <= h[1] and h[2] <= u[2] for u in units)]
    n = len(hits) if per == "event" else len(units)
    if not hits or not n:
        return None
    if self_time:
        seconds = sum(self_seconds(e, program) for e in hits)
    else:
        seconds = sum(e[2] - e[1] for e in hits)
    return 1e3 * seconds / n
