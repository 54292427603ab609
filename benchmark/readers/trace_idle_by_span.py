"""The device's idle time by what the host was doing in it, in percent of
the traced device interval (``trace_idle_share``'s denominator). Every idle
gap of the first device is cut at the borders of the host spans matching
``within``; each piece goes to the innermost such span that covers it (the
one that started last), or to none. Returned: the pieces whose span matches
``spans`` or, for ``spans`` null, the pieces no span covers. Metrics that
share ``within`` and whose ``spans`` split its names between them add up,
with the null one, to the device's idle share: every piece of every gap
lands in exactly one of them.

A span that the trace's edge cuts still covers its gaps. Threads are not
told apart (``host_span_ms`` says why): a worker's span that matches
``within`` takes the gaps it overlaps from a longer span of the main
thread."""

import re

from benchmark import trace as tr
from benchmark.readers.host_span_ms import host_spans


def innermost(spans):
    """Cut time at every border of ``spans`` (name, start, end): a list of
    (start, end, name of the innermost span open there), in order; time
    that no span covers is left out."""
    borders = sorted({t for _, s, e in spans for t in (s, e)})
    out, open_, i = [], [], 0
    for a, b in zip(borders, borders[1:]):
        while i < len(spans) and spans[i][1] <= a:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        if open_:
            out.append((a, b, max(open_, key=lambda sp: (sp[1], -sp[2]))[0]))
    return out


def read(ctx, within, spans=None, **_):
    got = host_spans(ctx, within, whole=False)
    if got is None:
        return None
    host, (lo, hi) = got
    if not host:
        return None  # a program without these spans: nothing to read
    ops = ctx["trace"].device_ops()
    idle = tr.gaps(ops[sorted(ops)[0]])
    pieces = innermost(host)
    covered = taken = 0.0  # seconds of gaps under any span, under ``spans``
    j = 0
    for s, d in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < s + d:
            a, b, name = pieces[k]
            part = min(b, s + d) - max(a, s)
            covered += part
            if spans is not None and re.search(spans, name):
                taken += part
            k += 1
    if spans is None:
        taken = sum(d for _, d in idle) - covered
    return 100.0 * taken / (hi - lo)
