"""The slot census of the traced session's engine steps, from the ``step``
events of the program's own log (``session_request`` says where that log
comes from): ``states`` names the counts to add up — of ``decoding``,
``prefilling`` and ``empty``, which are the engine's slots on every step,
and ``queued`` and ``parked``, the requests waiting for one. With ``of:
slots`` the result is their share of all slots over the session's steps, in
percent; otherwise their mean a step. The census is taken inside the step,
where its window is dispatched: a slot that holds a request still
prefilling counts as that and not as empty, and no step after the window
closed dilutes it."""

import sys

from benchmark.readers.session_request import session_logs

SLOTS = ("decoding", "prefilling", "empty")


def read(ctx, states, of=None, **_):
    logs = session_logs(ctx)
    if logs is None:
        return None
    steps = [e.data for log in logs for e in log.events
             if e.kind == "step" and log.in_session(e)]
    if not steps:
        return None
    total = sum(d[k] for d in steps for k in states)
    print(f"session_steps {'+'.join(states)}: {len(steps)} engine steps in "
          f"the session", file=sys.stderr)
    if of == "slots":
        return 100.0 * total / sum(d[k] for d in steps for k in SLOTS)
    return total / len(steps)
