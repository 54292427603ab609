"""One number the cell itself counted or clocked during the window
(``counters[key]``), times ``scale``."""


def read(ctx, key, scale=1.0, **_):
    val = ctx["counters"].get(key)
    if val is None:
        return None
    return float(val) * scale
