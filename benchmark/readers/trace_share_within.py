"""One kernel's share of one program, in percent: the summed device time of
the events matching ``events`` (on the ops line) that start inside an event
of the "XLA Modules" line matching ``within``, over the summed time of those
program events; averaged over the planes. Nothing where the trace has no
such program or no such kernel inside it (a program without the kernel)."""

from benchmark.readers.trace_time_by_name import matched


def read(ctx, events, within, **_):
    hits = matched(ctx, events)
    spans = matched(ctx, within, line=r"^XLA Modules$")
    if hits is None or spans is None:
        return None
    shares = []
    for p, progs in spans.items():
        inside = sum(d for _, s0, d in hits.get(p, ())
                     if any(s <= s0 < s + w for _, s, w in progs))
        total = sum(w for _, _, w in progs)
        if inside > 0 and total > 0:
            shares.append(inside / total)
    return 100.0 * sum(shares) / len(shares) if shares else None
