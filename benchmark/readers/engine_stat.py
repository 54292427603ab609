"""One key of the program's own counters (``engine.stats()``), times
``scale``."""


def read(ctx, key, scale=1.0, **_):
    val = ctx["counters"].get("stats", {}).get(key)
    if val is None:
        return None
    return float(val) * scale
