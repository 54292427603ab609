"""A statistic of the harness's own spans named ``span``, in milliseconds,
each divided by its ``per`` field (a window of K steps gives a step)."""

import statistics


def read(ctx, span, stat="p50", per=None, **_):
    vals = [1e3 * s["dur"] / (s[per] if per else 1)
            for s in ctx["spans"] if s["name"] == span]
    if not vals:
        return None
    if stat == "p50":
        return statistics.median(vals)
    if stat == "mean":
        return statistics.fmean(vals)
    raise ValueError(f"unknown stat {stat!r}")
