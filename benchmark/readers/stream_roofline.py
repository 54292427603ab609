"""A program's or a kernel's share of its memory roofline, in percent: the
least time the chip could take to read what one unit of work has to read
(``ops_block.<bytes_fn>(sizes, **bytes_args)`` over the device kind's peak
bytes/s), over the device time of one unit: the summed time of the events
matching ``events`` on ``line``, divided by their number times ``per_scale``
(the forwards a program's event runs; 1 for a kernel's call). ``within``
keeps only the events that start inside an event of the "XLA Modules" line
matching it: a kernel's calls from one program, where another program calls
the same kernel under the same name."""

from benchmark import ops, ops_block
from benchmark.readers.trace_time_by_name import matched


def read(ctx, events, bytes_fn, bytes_args, per_scale=1.0, line=None,
         within=None, **_):
    hits = matched(ctx, events, **({"line": line} if line else {}))
    if hits is None or any(v is None or isinstance(v, str)
                           for v in bytes_args.values()):
        return None
    if within:
        spans = matched(ctx, within, line=r"^XLA Modules$") or {}
        hits = {p: [e for e in evs if any(
                    s <= e[1] < s + d for _, s, d in spans.get(p, ()))]
                for p, evs in hits.items()}
        hits = {p: evs for p, evs in hits.items() if evs}
        if not hits:
            return None
    seconds = sum(d for evs in hits.values() for _, _, d in evs) / len(hits)
    calls = sum(len(evs) for evs in hits.values()) / len(hits)
    units = calls * float(per_scale)
    need = getattr(ops_block, bytes_fn)(ctx["sizes"], **bytes_args)
    if need <= 0 or seconds <= 0 or units <= 0:
        return None
    least = need / ops.peak(ctx["device_kind"], "bytes_per_s")
    return 100.0 * least / (seconds / units)
