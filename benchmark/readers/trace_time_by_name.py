"""Summed device time of the events whose name matches ``events`` on the
lines matching ``plane`` / ``line``, in milliseconds, averaged over the
planes and divided by ``per``: a counter's name, or the word ``event`` for
the number of matching events, times ``per_scale``."""

import re

from benchmark import trace as tr


def matched(ctx, events, plane=tr.DEVICE_PLANE, line=tr.OPS_LINE):
    trace = ctx.get("trace")
    if trace is None:
        return None
    by_plane = trace.select(plane, line)
    if line == tr.OPS_LINE:
        by_plane = {p: tr.leaf_events(evs) for p, evs in by_plane.items()}
    hits = {p: [e for e in evs if re.search(events, e[0])]
            for p, evs in by_plane.items()}
    hits = {p: evs for p, evs in hits.items() if evs}
    return hits or None


def read(ctx, events, per="event", per_scale=1.0, plane=tr.DEVICE_PLANE,
         line=tr.OPS_LINE, **_):
    hits = matched(ctx, events, plane, line)
    if hits is None:
        return None
    seconds = sum(d for evs in hits.values() for _, _, d in evs) / len(hits)
    if per == "event":
        n = sum(len(evs) for evs in hits.values()) / len(hits)
    else:
        n = ctx["counters"].get(per)
    if not n:
        return None
    return 1e3 * seconds / (n * per_scale)
