"""One of the cell's numbers over another (``counters[num] /
counters[den]``), times ``scale``; nothing where either is missing or the
denominator is zero, as on a program that counts neither."""


def read(ctx, num, den, scale=1.0, **_):
    c = ctx["counters"]
    a, b = c.get(num), c.get(den)
    if a is None or not b:
        return None
    return float(a) / float(b) * scale
