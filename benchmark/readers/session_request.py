"""One part of the time to the first token, from the program's own request
log: the mean of ``part`` (a key of ``EngineTelemetry.request_metrics``:
``queue_delay_s``, ``prefill_s``, ``first_window_s``, or their sum
``ttft_s``) over the requests whose FIRST TOKEN was harvested in an engine
step of the traced session, times ``scale``. A mean, so that the three parts
add up to the mean time to the first token of the same requests, on the
engine's clock.

The program switches the log on for as long as a profiler session is open
and keeps it in ``midgpt_tpu.telemetry.session_logs()`` after the engine is
freed; a request that was under way when the session opened has its
beginning back-filled from the stamps it carries. Nothing is read without a
trace in ``ctx`` (a rehearsal's session on the CPU is no traced window of
the device), on a program that has no ``session_logs``, where no log
recorded a session, and where no first token fell into it."""

import sys

PARTS = ("queue_delay_s", "prefill_s", "first_window_s", "ttft_s")


def session_logs(ctx):
    """The logs of the newest profiler session, or None where there is
    nothing to read them for or from."""
    if ctx.get("trace") is None:
        return None
    from midgpt_tpu import telemetry

    logs = getattr(telemetry, "session_logs", lambda: None)()
    return logs or None


def first_tokens(logs):
    """``request_metrics`` of every request whose first token the session
    saw, and how many of them had been submitted before it opened."""
    rows, early = [], 0
    for log in logs:
        for rid, evs in log.request_log.items():
            first = next((e for e in evs
                          if e.kind == "tokens" and e.data.get("n")), None)
            if first is None or not log.in_session(first):
                continue
            m = log.request_metrics(rid)
            if any(m.get(p) is None for p in PARTS):
                continue  # the token it saw first was not the request's
            rows.append(m)
            early += any(e.data.get("backfill") for e in evs)
    return rows, early


def read(ctx, part, stat="mean", scale=1.0, **_):
    logs = session_logs(ctx)
    if logs is None or stat != "mean":
        return None
    rows, early = first_tokens(logs)
    if not rows:
        return None
    print(f"session_request {part}: {len(rows)} first tokens in the session, "
          f"{early} of requests submitted before it opened; the session's "
          f"log holds {sum(len(log.events) for log in logs)} events of "
          f"{sum(len(log.request_log) for log in logs)} requests",
          file=sys.stderr)
    return scale * sum(m[part] for m in rows) / len(rows)
