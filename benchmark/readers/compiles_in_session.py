"""Programs built while the profiler session was open: the entries of
``midgpt_tpu.telemetry.compile_log()`` that a session saw. JAX reports one a
new shape of a jitted function, compiled or loaded from the persistent
cache, and either stalls the step that asked for it; set-up warms every
shape, so a measured window builds none and this reads 0. Nothing is read
without a trace in ``ctx`` or on a program that keeps no such log."""

import sys


def read(ctx, **_):
    if ctx.get("trace") is None:
        return None
    from midgpt_tpu import telemetry

    log = getattr(telemetry, "compile_log", None)
    if log is None:
        return None
    built = [(name, seconds) for name, seconds, in_session in log()
             if in_session]
    for name, seconds in built:
        print(f"compiles_in_session: {name} took {seconds:.3f} s inside "
              f"the session", file=sys.stderr)
    return float(len(built))
