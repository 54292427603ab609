"""1 - (union of the device's operation intervals) / (first start to last
end), in percent, averaged over the chips traced."""

from benchmark.trace import busy_and_window


def read(ctx, **_):
    trace = ctx.get("trace")
    got = busy_and_window(trace) if trace is not None else None
    if got is None:
        return None
    busy, window = got
    return 100.0 * (1.0 - busy / window)
