"""``readers/stream_roofline.py``'s share of a memory roofline, with the byte
function's home given by name: ``benchmark.<module>.<bytes_fn>(sizes,
**bytes_args)`` over the device kind's peak bytes/s, over the device time of
one unit — the summed time of the events matching ``events`` on ``line``,
divided by their number times ``per_scale``; ``within`` keeps the events that
start inside an "XLA Modules" event matching it. Nothing where the trace has
no such event or a byte argument is not a number (a program without the
counter, a cell without the kernel)."""

import importlib

from benchmark import ops
from benchmark.readers.trace_time_by_name import matched


def read(ctx, module, events, bytes_fn, bytes_args, per_scale=1.0, line=None,
         within=None, **_):
    hits = matched(ctx, events, **({"line": line} if line else {}))
    if hits is None or any(v is None or isinstance(v, str)
                           for v in list(bytes_args.values()) + [per_scale]):
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
    fn = getattr(importlib.import_module(f"benchmark.{module}"), bytes_fn)
    need = fn(ctx["sizes"], **bytes_args)
    if need <= 0 or seconds <= 0 or units <= 0:
        return None
    least = need / ops.peak(ctx["device_kind"], "bytes_per_s")
    return 100.0 * least / (seconds / units)
