"""The whole step's share of the chip's peak, in percent: the counter
``work`` (FLOPs the window's completed work required, recomputation not
counted) over window seconds x chips x the device kind's peak."""

from benchmark import ops


def read(ctx, work, **_):
    c = ctx["counters"]
    done, window = c.get(work), c.get("window_s")
    if not done or not window:
        return None
    peak = ops.peak(ctx["device_kind"], "flops") * c.get("chips", 1)
    return 100.0 * done / (window * peak)
