"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the traced events did, over their summed device
time. The work is ``ops.<work_fn>(sizes, **work_args)`` for each event that
matches ``unit_events`` (one kernel call of one layer); ``events`` names every
event whose time counts (a forward kernel and its backward, say); ``bound``
says which peak the kernel is held to, ``compute`` or ``memory``."""

from benchmark import ops
from benchmark.readers.trace_time_by_name import matched


def read(ctx, events, unit_events, work_fn, work_args, bound, **_):
    hits, units = matched(ctx, events), matched(ctx, unit_events)
    if hits is None or units is None:
        return None
    seconds = sum(d for evs in hits.values() for _, _, d in evs) / len(hits)
    calls = sum(len(evs) for evs in units.values()) / len(units)
    work = calls * getattr(ops, work_fn)(ctx["sizes"], **work_args)
    if work <= 0 or seconds <= 0:
        return None
    peak = ops.peak(ctx["device_kind"],
                    "flops" if bound == "compute" else "bytes_per_s")
    return 100.0 * (work / peak) / seconds
